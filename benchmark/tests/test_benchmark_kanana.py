"""The `kanana-mla-train-8k` cell: end to end at its `tiny` sizes on the CPU
(one process, as the driver runs it) with every new reader returning a
number, and its full-size step compiled for a described (not attached) TPU
v5e, with `memory_analysis` printed and the kernels' calls a step counted —
nothing runs there, and a compile that passes is not a chip run. The
reference's step beside the trainer is in test_benchmark_check_memory.py.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_benchmark_kanana.py -s
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

CELL = "kanana-mla-train-8k"
NEW_METRICS = ("moe_bias_choice_flips_pct", "moe_route_weight_sum")
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
         "5300000053", "--seconds", "1", "--trace", str(trace),
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
        assert got["moe_route_weight_sum"]["value"] == pytest.approx(
            2.448, rel=1e-5)
        assert 0.0 < got["moe_bias_choice_flips_pct"]["value"] < 50.0
        assert got["moe_rows_dropped"]["value"] == 0.0
        # the CPU runs the dense path: no kernel of that name in its trace
        assert "flash_fwd_stream_device_ms" not in got
    else:
        assert res["metrics"]["train_samples_s_chip"]["value"] > 0


def _batch(job):
    shape = (job["batch_per_chip"], job["seq_len"])
    return {"input_ids": jax.ShapeDtypeStruct(shape, jnp.int32)}


def test_readers_on_a_fixture_line(monkeypatch):
    """The accepted kernel readers on a recorded view of this cell, against
    this family's `kernel_costs`; the two new readers on counters as the
    trainer mirrors them; and None, not an error, where the program has
    none (the parent commit)."""
    from benchmark.lib import kernel_readers
    cfg, job, fam = _cell()
    view = {"trace": {"ops": [["checkpoint_flash_fwd_stream", 0.3],
                              ["transpose_jvp_flash_bwd_dq", 0.25],
                              ["transpose_jvp_flash_bwd_dkv", 0.35]]},
            "counters": {"traced_steps": 10}, "config": cfg, "traffic": job,
            "cell": {"chips": 1},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}}
    monkeypatch.setattr(kernel_readers, "model_counters", lambda: {})
    read = lambda name: harness.load_module("metrics", name).read(view)
    assert read("flash_fwd_stream_device_ms") == pytest.approx(30.0)
    assert read("flash_bwd_device_ms") == pytest.approx(60.0)
    ops, nbytes = fam.kernel_costs(cfg, job, 1)["flash_fwd_stream"]
    assert ops / 197e12 > nbytes / 819e9            # compute-bound
    assert read("flash_fwd_stream_roofline_pct") == pytest.approx(
        100.0 * ops / 197e12 / 0.030)
    assert read("flash_fwd_stream_roofline_pct") < 100.0
    assert read("flash_fwd_resident_roofline_pct") is None
    tokens, layers, steps = 8192.0, 4, 10.0
    counters = {
        "route_weight_sum": [0.0] + [2.448 * tokens * steps] * layers,
        "route_bias_flips": [0.0] + [0.1 * tokens * 6 * steps] * layers,
        "steps": [steps]}
    for name, want in (("moe_route_weight_sum", 2.448),
                       ("moe_bias_choice_flips_pct", 10.0)):
        mod = harness.load_module("metrics", name)
        monkeypatch.setattr(mod, "model_counters", lambda: counters)
        assert mod.read(view) == pytest.approx(want)
        monkeypatch.setattr(mod, "model_counters", lambda: {})
        assert mod.read(view) is None


def test_train_step_compiles_and_fits(one_chip, monkeypatch):
    """The cell's own step at published widths, 1 x 8192 tokens: k + v of a
    head are 5 MiB, so every layer runs the STREAMED forward twice (the
    band kernels name no residual under remat) and the split backward
    once; the four expert layers run the grouped products — and it fits."""
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
    calls = {name: sum(name in k for k in kernels)
             for name in ("flash_fwd_resident", "flash_fwd_stream",
                          "flash_bwd_dq", "flash_bwd_dkv", "moe_gmm",
                          "moe_tgmm", "gdn_", "dsa_", "bdiff_")}
    layers = cfg["num_hidden_layers"]
    experts = layers - cfg["first_k_dense_replace"]
    # an expert layer: gate_up and down forward (saved under remat), their
    # two dx products and two dw products backward
    assert calls == {"flash_fwd_resident": 0, "flash_fwd_stream": 2 * layers,
                     "flash_bwd_dq": layers, "flash_bwd_dkv": layers,
                     "moe_gmm": 4 * experts, "moe_tgmm": 2 * experts,
                     "gdn_": 0, "dsa_": 0, "bdiff_": 0}
    costs = fam.kernel_costs(cfg, job, 1)
    assert sorted(costs) == ["flash_bwd", "flash_fwd_stream", "moe_gmm",
                             "moe_tgmm"]
    assert total < HBM
