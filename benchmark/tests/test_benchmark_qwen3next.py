"""The `qwen3next-gdn-train-16k` cell: end to end at its `tiny` sizes on the
CPU (one process, as the driver runs it) with every new reader returning a
number, and its full-size step compiled for a described (not attached) TPU
v5e, with `memory_analysis` printed and the kernels' calls a step counted —
nothing runs there, and a compile that passes is not a chip run. The
reference's step beside the trainer is in test_benchmark_check_memory.py.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_benchmark_qwen3next.py -s
"""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.lib import harness, optim  # noqa: E402

CELL = "qwen3next-gdn-train-16k"
NEW_METRICS = ("gdn_fwd_device_ms", "gdn_fwd_roofline_pct", "gdn_bwd_device_ms",
               "gdn_bwd_roofline_pct", "gdn_chunk_log_decay_min",
               "gdn_state_absmax")
GIB = float(1 << 30)
HBM = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(desc.devices[0])


def _cell():
    _, cell, cfg, job = harness.cell_spec(CELL)
    return cfg, job, harness.load_module("program", cfg["family"])


def _on(chip, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        tree)


def _report(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print("memory_analysis " + json.dumps({
        "program": name, "argument_gib": m.argument_size_in_bytes / GIB,
        "output_gib": m.output_size_in_bytes / GIB,
        "temp_gib": m.temp_size_in_bytes / GIB,
        "alias_gib": m.alias_size_in_bytes / GIB,
        "total_gib": total / GIB}))
    return total, m


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_at_tiny_sizes(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "5000000043", "--seconds", "1", "--trace", str(trace),
         "--cpu_tiny"], cwd=ROOT, env=env, timeout=900,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1
    res = json.loads(lines[0])
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"   # never a device number
    if trace:
        # the counters went device -> trainer.close() -> registry -> reader
        got = res["metrics"]
        assert got["moe_rows_dropped"]["value"] == 0.0
        assert got["gdn_chunk_log_decay_min"]["value"] < 0.0
        assert got["gdn_state_absmax"]["value"] > 0.0
        # the CPU runs the plain path: no kernel of that name in its trace
        assert "gdn_fwd_device_ms" not in got
    else:
        assert res["metrics"]["train_samples_s_chip"]["value"] > 0


def _batch(job):
    shape = (job["batch_per_chip"], job["seq_len"])
    return {"input_ids": jax.ShapeDtypeStruct(shape, jnp.int32)}


def test_readers_on_a_fixture_line(monkeypatch):
    """Every new reader on a recorded view: a kernel found by its name
    through autodiff's wrapping, its share against `kernel_costs`, the two
    counters; and None, not an error, where the program has none (the
    parent commit)."""
    from benchmark.lib import kernel_readers
    cfg, job, fam = _cell()
    view = {"trace": {"ops": [["jvp_gdn_fwd_", 0.06],
                              ["transpose_jvp_gdn_bwd__", 0.12],
                              ["checkpoint_flash_fwd_stream", 0.2],
                              ["flash_bwd_dq", 0.1], ["flash_bwd_dkv", 0.1]]},
            "counters": {"traced_steps": 10}, "config": cfg, "traffic": job,
            "cell": {"chips": 1},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}}
    monkeypatch.setattr(kernel_readers, "model_counters", lambda: {})
    read = lambda name: harness.load_module("metrics", name).read(view)
    assert read("gdn_fwd_device_ms") == pytest.approx(6.0)
    assert read("gdn_bwd_device_ms") == pytest.approx(12.0)
    assert read("flash_fwd_stream_device_ms") == pytest.approx(20.0)
    assert read("flash_bwd_device_ms") == pytest.approx(20.0)
    costs = fam.kernel_costs(cfg, job, 1)
    for name, ms, compute_bound in (("gdn_fwd", 6.0, False),
                                    ("gdn_bwd", 12.0, False),
                                    ("flash_fwd_stream", 20.0, True)):
        ops, nbytes = costs[name]
        assert (ops / 197e12 > nbytes / 819e9) == compute_bound
        assert read(name + "_roofline_pct") == pytest.approx(
            100.0 * max(ops / 197e12, nbytes / 819e9) / (ms / 1e3))
        assert read(name + "_roofline_pct") < 100.0
    for name, counters, want in (
            ("gdn_chunk_log_decay_min",
             {"gdn_chunk_log_decay_min": [-3.0, -90.5, -7.0, 0.0]}, -90.5),
            ("gdn_state_absmax",
             {"gdn_state_absmax": [0.5, 2.25, 1.0, 0.0]}, 2.25)):
        mod = harness.load_module("metrics", name)
        monkeypatch.setattr(mod, "model_counters", lambda c=counters: c)
        assert mod.read(view) == pytest.approx(want)
        monkeypatch.setattr(mod, "model_counters", lambda: {})
        assert mod.read(view) is None
    empty = dict(view, trace={"ops": [["fusion", 1.0]]})
    for name in NEW_METRICS[:4] + ("flash_fwd_stream_device_ms",
                                   "flash_fwd_stream_roofline_pct"):
        assert harness.load_module("metrics", name).read(empty) is None


def test_train_step_compiles_and_fits(one_chip, monkeypatch):
    """The cell's own step at published widths, 1 x 16384 tokens: the two
    delta-rule kernels, the STREAMED flash forward with the split backward
    and the grouped products are in it under their names, as often as the
    remat policy says (`kernel_costs` counts the same calls), and it
    fits."""
    from edl_tpu.runtime.trainer import make_train_state, make_train_step
    # the dispatches ask jax.default_backend(); this compile is for a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, job, fam = _cell()
    loss_fn, has_aux, shapes = fam.train_parts(cfg, job)
    tx = optim.make_tx(job["optimizer"])
    state = jax.eval_shape(lambda p, e: make_train_state(p, tx, e), *shapes)
    compiled = jax.jit(make_train_step(loss_fn, tx, has_aux),
                       donate_argnums=(0,)).lower(
        _on(one_chip, state), _on(one_chip, _batch(job)),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile()
    total, _ = _report("%s step" % CELL, compiled)
    text = compiled.as_text()
    kernels = sorted(set(re.findall(
        r"%?([\w.\-]+) = [^\n]*custom-call[^\n]*tpu_custom_call", text)))
    print("kernels " + json.dumps(kernels))
    # 3 linear layers: gdn_fwd ONCE a layer (its result and states are
    # saved under remat), gdn_bwd once; 1 full layer: the streamed forward
    # twice (the band kernels name no residual), dq and dkv once each; the
    # grouped products as in the other sparse cells
    calls = {name: sum(name in k for k in kernels)
             for name in ("gdn_fwd", "gdn_bwd", "flash_fwd_stream",
                          "flash_fwd_resident", "flash_bwd_dq",
                          "flash_bwd_dkv", "moe_gmm", "moe_tgmm", "dsa_",
                          "bdiff_")}
    assert calls == {"gdn_fwd": 3, "gdn_bwd": 3, "flash_fwd_stream": 2,
                     "flash_fwd_resident": 0, "flash_bwd_dq": 1,
                     "flash_bwd_dkv": 1, "moe_gmm": 16, "moe_tgmm": 8,
                     "dsa_": 0, "bdiff_": 0}
    assert total < HBM
