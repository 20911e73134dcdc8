"""Chip-compile rehearsal: compile the benchmark's largest programs at
published widths for a described (not attached) TPU v5e and record
`memory_analysis`, so that the slot count and the training batch are
checked before chip time is spent. Nothing runs; a compile that passes is
not a chip run.

The topology is described inside a fixture (on-chip-measurement guide,
section 2): only the worker that runs this file loads the TPU library.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_benchmark_chip_compile.py -s
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
HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    jax.config.update("jax_enable_compilation_cache", False)
    return desc


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _on(chip, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        tree)


def _report(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    rec = {"program": name, "argument_gib": m.argument_size_in_bytes / GIB,
           "temp_gib": m.temp_size_in_bytes / GIB,
           "alias_gib": m.alias_size_in_bytes / GIB,
           "total_gib": total / GIB}
    print("memory_analysis " + json.dumps(rec))
    return total


def _gpt():
    cfg = harness.load_json(os.path.join(harness.BENCH, "configs",
                                         "gpt2-small.json"))
    return cfg, harness.load_module("program", "gpt")


@pytest.mark.parametrize("rows,remat", [(8, True), (12, False),
                                        (16, False)])
def test_gpt2_small_train_step_fits(one_chip, rows, remat):
    from edl_tpu.runtime.trainer import make_train_state, make_train_step
    cfg, fam = _gpt()
    job = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                         "tokens-1024.json"))
    job = dict(job, remat=remat)
    loss_fn, has_aux, shapes = fam.train_parts(cfg, job)
    tx = optim.make_tx(job["optimizer"])
    state = jax.eval_shape(lambda p: make_train_state(p, tx), shapes[0])
    batch = {"input_ids": jax.ShapeDtypeStruct((rows, job["seq_len"]),
                                               jnp.int32)}
    step = jax.jit(make_train_step(loss_fn, tx, has_aux),
                   donate_argnums=(0,))
    compiled = step.lower(_on(one_chip, state), _on(one_chip, batch),
                          jax.ShapeDtypeStruct((2,), jnp.uint32,
                                               sharding=one_chip)).compile()
    total = _report("gpt2s_train rows=%d remat=%s" % (rows, remat), compiled)
    cell = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                          "tokens-1024.json"))
    if (rows, remat) == (cell["batch_per_chip"], cell["remat"]):
        assert total < HBM, "the cell's own batch must fit one chip"


def _engine_stub(cfg, fam, job):
    """The engine's jitted bodies without its KV arena (7 GB of zeros on
    the host): the methods only read `model` and count traces."""
    from edl_tpu.serve.decode_engine import DecodeEngine, _init_cache
    eng = DecodeEngine.__new__(DecodeEngine)
    eng.model = fam.build_model(cfg, job)
    eng._step_traces = eng._prefill_traces = eng._chunk_traces = 0
    params = jax.eval_shape(
        lambda: fam.train_parts(cfg, {"remat": False})[2][0])
    cache = jax.eval_shape(lambda: _init_cache(eng.model, None,
                                               job["slots"]))
    return eng, params, cache


def _serve_job():
    path = os.path.join(harness.BENCH, "traffic", "chat-steady.json")
    return harness.load_json(path)


@pytest.mark.parametrize("slots", [192, 160, 128])
def test_decode_step_fits(one_chip, slots):
    cfg, fam = _gpt()
    job = dict(_serve_job(), slots=slots)
    eng, params, cache = _engine_stub(cfg, fam, job)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(eng._step_impl, donate_argnums=1).lower(
        _on(one_chip, params), _on(one_chip, cache), vec, vec).compile()
    total = _report("decode_step slots=%d" % slots, compiled)
    if slots == _serve_job()["slots"]:
        assert total < HBM


@pytest.mark.parametrize("bucket", [1024])
def test_prefill_bucket_fits(one_chip, bucket):
    cfg, fam = _gpt()
    job = _serve_job()
    eng, params, cache = _engine_stub(cfg, fam, job)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip)
    compiled = jax.jit(eng._prefill_impl, donate_argnums=1).lower(
        _on(one_chip, params), _on(one_chip, cache), ids, scalar,
        scalar).compile()
    assert _report("prefill bucket=%d slots=%d" % (bucket, job["slots"]),
                   compiled) < HBM


@pytest.mark.parametrize("world", [4, 2])
def test_resnet50vd_dp_step_compiles_across_chips(topo, world):
    """The four-chip cell's step on a dp mesh of 4 and of its first 2
    chips (the live-resize sub-mesh): it compiles, the gradient
    all-reduce is there, and the per-chip bytes fit."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from edl_tpu.runtime.trainer import make_train_state, make_train_step
    cfg = harness.load_json(os.path.join(harness.BENCH, "configs",
                                         "resnet50-vd.json"))
    job = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                         "images-512-resize.json"))
    fam = harness.load_module("program", "resnet")
    loss_fn, has_aux, shapes = fam.train_parts(cfg, job)
    tx = optim.make_tx(job["optimizer"])
    mesh = Mesh(np.asarray(topo.devices[:world]), ("dp",))
    repl, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    state = jax.eval_shape(lambda p, e: make_train_state(p, tx, e), *shapes)
    n = job["batch_per_chip"] * 4
    size = cfg["image_size"]
    batch = {"image": jax.ShapeDtypeStruct((n, size, size, 3), jnp.bfloat16,
                                           sharding=rows),
             "label": jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rows)}
    compiled = jax.jit(make_train_step(loss_fn, tx, has_aux),
                       donate_argnums=(0,)).lower(
        _on(repl, state), batch,
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)).compile()
    assert "all-reduce" in compiled.as_text()
    assert _report("resnet50vd dp=%d global %d" % (world, n),
                   compiled) < HBM
