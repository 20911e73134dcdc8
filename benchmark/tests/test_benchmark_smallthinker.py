"""The `smallthinker-moe-train-8k` cell: end to end at its `tiny` sizes on
the CPU (one process, as the driver runs it), and its full-size step
compiled for a described (not attached) TPU v5e, with `memory_analysis`
printed — nothing runs there, and a compile that passes is not a chip run.
The reference's step beside the trainer is in
test_benchmark_check_memory.py.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_benchmark_smallthinker.py -s
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

CELL = "smallthinker-moe-train-8k"
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
         "5000000011", "--seconds", "1", "--trace", str(trace),
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
        assert res["metrics"]["moe_rows_dropped"]["value"] == 0.0
        assert res["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1
    else:
        assert res["metrics"]["train_samples_s_chip"]["value"] > 0


def test_train_step_compiles_and_fits(one_chip, monkeypatch):
    """The cell's own step at published widths, 2 x 8192 tokens: the
    three Pallas kernels are in it under their names, and it fits."""
    from edl_tpu.runtime.trainer import make_train_state, make_train_step
    # the dispatches ask jax.default_backend(); this compile is for a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, job, fam = _cell()
    loss_fn, has_aux, shapes = fam.train_parts(cfg, job)
    tx = optim.make_tx(job["optimizer"])
    state = jax.eval_shape(lambda p, e: make_train_state(p, tx, e), *shapes)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (job["batch_per_chip"], job["seq_len"]), jnp.int32)}
    compiled = jax.jit(make_train_step(loss_fn, tx, has_aux),
                       donate_argnums=(0,)).lower(
        _on(one_chip, state), _on(one_chip, batch),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile()
    total, _ = _report("%s step" % CELL, compiled)
    text = compiled.as_text()
    kernels = sorted(set(re.findall(
        r"%?([\w.\-]+) = [^\n]*custom-call[^\n]*tpu_custom_call", text)))
    print("kernels " + json.dumps(kernels))
    # 4 layers: the flash forward twice (remat), the grouped products
    # forward once (their results are saved) and once for dx, dw once
    calls = {name: sum(name in k for k in kernels)
             for name in ("flash_fwd_resident", "moe_gmm", "moe_tgmm")}
    assert calls == {"flash_fwd_resident": 8, "moe_gmm": 16, "moe_tgmm": 8}
    assert total < HBM
