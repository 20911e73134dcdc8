"""The two LM cells' whole training steps compiled for a described (not
attached) TPU v5e WITH the attention the chip runs: the dispatch asks
`jax.default_backend()`, which is the CPU here, so
test_benchmark_chip_compile.py compiles the dense path; this file answers
"tpu" for it (in the test, not through an option of the program) and so
compiles the Pallas flash forward and backward kernels inside the step.
It records `memory_analysis`, and that no loop is left on the training
path. Nothing runs; a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_benchmark_flash_step_compile.py -s
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.lib import harness, optim  # noqa: E402

GIB = float(1 << 30)


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


# temp GiB of the step at the parent commit (the scans), same compile,
# same answer to the dispatch (PERF.md section 6, PR 28)
@pytest.mark.parametrize("config,family,traffic,parent_temp_gib", [
    ("gpt2-small", "gpt", "tokens-1024", 5.7314),
    ("smallthinker-21b-a3b", "sparse_decoder", "tokens-8192", 5.6941),
])
def test_lm_step_with_flash_kernels(one_chip, monkeypatch, config, family,
                                    traffic, parent_temp_gib):
    from edl_tpu.runtime.trainer import make_train_state, make_train_step
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = harness.load_json(os.path.join(harness.BENCH, "configs",
                                         config + ".json"))
    job = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                         traffic + ".json"))
    loss_fn, has_aux, shapes = harness.load_module(
        "program", family).train_parts(cfg, job)[:3]
    tx = optim.make_tx(job["optimizer"])
    on = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    state = jax.eval_shape(lambda *a: make_train_state(a[0], tx, *a[1:]),
                           *shapes)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (job["batch_per_chip"], job["seq_len"]), jnp.int32)}
    compiled = jax.jit(make_train_step(loss_fn, tx, has_aux),
                       donate_argnums=(0,)).lower(
        on(state), on(batch),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile()
    m, text = compiled.memory_analysis(), compiled.as_text()
    rec = {"program": config + " step, flash kernels",
           "temp_gib": m.temp_size_in_bytes / GIB,
           "parent_temp_gib": parent_temp_gib,
           "total_gib": (m.argument_size_in_bytes + m.output_size_in_bytes
                         + m.temp_size_in_bytes
                         - m.alias_size_in_bytes) / GIB}
    print("memory_analysis " + json.dumps(rec))
    assert "flash_fwd_resident" in text
    assert "flash_bwd" in text
    assert " while(" not in text, "a loop is back on the training path"
    # the forward's row statistic is the one new residual: 4 bytes a row
    # (0.6 MB a layer on GPT-2s: 7 MB of the 13 MB this reads above the
    # parent there). One array of the size of q kept per layer (the
    # kernels' transposed copies, before _attend_bwd's barrier) read
    # +0.66 GiB: that is the finding this line guards
    assert rec["temp_gib"] < parent_temp_gib + 0.05
