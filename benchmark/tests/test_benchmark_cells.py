"""Each kind of cell end to end at toy sizes on the CPU, one process per
run as the driver makes them: the last line of stdout is the contract's
object, with exactly its keys."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOP = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE = {"platform", "kind", "count", "memory_peak_bytes"}


def _run(workload, trace, seconds, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               BENCH_RUN="ignored")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "5000000011", "--seconds", str(seconds), "--trace",
         str(trace), "--cpu_tiny"], cwd=ROOT, env=env, timeout=900,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1, "stdout holds the result line and nothing else"
    return json.loads(lines[0])


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _names(section, workload):
    return {m["name"] for m in _bench()[section]
            if "workloads" not in m or workload in m["workloads"]}


@pytest.mark.parametrize("workload,seconds", [
    ("gpt2s-train", 1), ("resnet50vd-dp4-elastic", 1)])
def test_untraced_line(workload, seconds, tmp_path):
    res = _run(workload, 0, seconds, tmp_path)
    assert set(res) == TOP
    assert set(res["device"]) == DEVICE
    assert res["device"]["platform"] == "cpu"   # never a device number
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == _names("end_to_end", workload)
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in res["metrics"].values())


@pytest.mark.parametrize("workload,seconds", [("gpt2s-train", 1)])
def test_traced_line(workload, seconds, tmp_path):
    res = _run(workload, 1, seconds, tmp_path)
    assert set(res) == TOP | {"breakdown"}
    assert set(res["device"]) == DEVICE | {"busy_s", "window_s"}
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in res["breakdown"].values())
    # a reader that finds nothing (no executable line on the CPU) is left
    # out; none is invented
    assert set(res["metrics"]) <= _names("per_layer", workload)
    assert "window_compiles.train" in res["metrics"]


def test_every_named_file_exists():
    b = _bench()
    for c in b["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "reference", c["name"] + ".py"))
    for w in b["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".py")), m["name"]
