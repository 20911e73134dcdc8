"""The elastic cell traced at toy sizes on the CPU: the result line holds
the per-layer metrics that read the program's stage spans
(benchmark/lib/progspans.py), beside the ones it held before. A CPU run
shows that the spans are there and are read; none of these is a time of
the device."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "resnet50vd-dp4-elastic"
#: what a tiny run's spans must produce; a stage the CPU run skips may be
#: left out, and none is invented
ALWAYS = {"shrink_pause_ms", "grow_pause_ms", "shrink_device_put_ms",
          "grow_device_put_ms", "resize_other_ms", "save_snapshot_ms"}
BEFORE = {"save_stall_ms", "resize_reshard_ms", "resize_compile_ms",
          "window_compiles.elastic", "device_idle_pct.elastic"}


def test_traced_elastic_line_reads_the_program_s_spans(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("EDL_TPU_TRACE", None)   # nothing switches the spans on
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "5000000029", "--seconds", "1", "--trace", "1", "--cpu_tiny"],
        cwd=ROOT, env=env, timeout=900, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    [line] = p.stdout.strip().splitlines()
    res = json.loads(line)
    assert res["correct"] is True and res["failed"] == 0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        named = {m["name"]: m for m in json.load(f)["per_layer"]
                 if CELL in m.get("workloads", [CELL])}
    got = res["metrics"]
    assert set(got) <= set(named)
    assert ALWAYS | BEFORE <= set(got)
    spans = {k: v["value"] for k, v in got.items()
             if named[k]["source"] == "program_span"}
    assert all(v >= 0 for v in spans.values())
    # the stages lie inside the pause they are stages of (the readers of
    # `resize.device_put` from inside are parts of a stage, and one is MB)
    for d in ("shrink", "grow"):
        parts = sum(spans.get("%s_%s_ms" % (d, stage), 0.0) for stage in
                    ("device_put", "first_trace", "first_load", "first_run"))
        assert parts <= spans[d + "_pause_ms"] * 1.001
