"""Static perf-accounting regression pins.

Every perf lever claims something about flops / bytes / live memory.
These tests pin the STATIC side of each claim so a lever cannot
silently regress between chip runs:

- dense-vs-blockwise attention: pinned on compiled memory growth —
  dense temp memory is quadratic in sequence length, blockwise (the
  flash kernel's semantic twin) and the flash backward are linear.
- the TPU compiler itself: `tools/perf_accounting.py` AOT-compiles the
  real steps against a deviceless v5e topology (libtpu's own compiler)
  and writes PERF_ACCOUNTING.json; the pins here assert that path stays
  alive: the flash kernels at the cells' shapes, the LM batch sweep.
"""

import jax
import jax.numpy as jnp
import pytest

from edl_tpu.tools import perf_accounting as pa


# -- attention memory complexity (compiled, CPU) --------------------------


def _attn_temp(seq, impl):
    out = pa.attention_account(jax.devices("cpu"), seq, impl)
    return out["temp_bytes"], out["flops"]


def test_dense_attention_temp_is_quadratic_blockwise_linear():
    d1, f1 = _attn_temp(512, "dense")
    d2, f2 = _attn_temp(1024, "dense")
    d4, f4 = _attn_temp(2048, "dense")
    # doubling seq must ~4x the dense temp (the s x s scores) and flops
    assert 3.0 < d2 / d1 < 5.5, (d1, d2)
    assert 3.0 < d4 / d2 < 5.5, (d2, d4)
    assert 3.4 < f2 / f1 < 4.6, (f1, f2)

    b1, _ = _attn_temp(512, "block")
    b2, _ = _attn_temp(1024, "block")
    b4, _ = _attn_temp(2048, "block")
    # blockwise live memory grows linearly: ~2x per doubling
    assert b2 / b1 < 2.7, (b1, b2)
    assert b4 / b2 < 2.7, (b2, b4)


def test_flash_backward_memory_is_linear():
    """The attention backward (the Pallas kernels of _flash_bwd, here in
    the interpreter) must stay O(seq) in live memory: score tiles never
    reach HBM, so nothing is quadratic. 4x the sequence must cost ~4x
    the temp (quadratic would be 16x)."""
    import jax.numpy as jnp

    from edl_tpu.ops import flash_attention as fa

    def temp_at(seq):
        s = jax.ShapeDtypeStruct((1, 12, seq, 64), jnp.bfloat16)
        lse = jax.ShapeDtypeStruct((12, 1, seq), jnp.float32)
        delta = jax.ShapeDtypeStruct((1, 12, seq), jnp.float32)

        def bwd(q, k, v, lse, delta, g):
            return fa._flash_bwd(q, k, v, lse, delta, g, True, 64 ** -0.5,
                                 True)
        comp = jax.jit(bwd).lower(s, s, s, lse, delta, s).compile()
        return comp.memory_analysis().temp_size_in_bytes

    t2k, t8k = temp_at(2048), temp_at(8192)
    assert t8k / t2k < 5.5, (t2k, t8k)


def test_dense_attention_memory_crossover_at_long_seq():
    """By 8k tokens the s x s scores dominate everything else: the
    dense forward needs several times the blockwise live memory (the
    reason flash/blockwise is the long-context default)."""
    dense = pa.attention_account(jax.devices("cpu"), 8192, "dense",
                                 grad=False)
    block = pa.attention_account(jax.devices("cpu"), 8192, "block",
                                 grad=False)
    assert dense["temp_bytes"] > 2.0 * block["temp_bytes"], \
        (dense["temp_bytes"], block["temp_bytes"])


# -- the TPU AOT accounting path itself -----------------------------------


def _tpu_topology_or_skip():
    try:
        return pa.v5e_devices()
    except Exception as e:  # noqa: BLE001
        pytest.skip("no local libtpu AOT compiler: %r" % e)


@pytest.mark.integration
@pytest.mark.parametrize("batch,kv_heads,seq,dim,group,window,streamed", [
    (12, 12, 1024, 64, 1, None, False),     # gpt2s-train
    (2, 1, 8192, 128, 7, None, False),      # the sparse decoder's full
    (2, 1, 8192, 128, 7, 4096, False),      # layer, and its window layers
    (1, 2, 32768, 64, 2, 8192, True),       # k and v beyond VMEM: streamed
])
def test_tpu_compiler_takes_the_flash_kernels_at_the_cells_shapes(
        batch, kv_heads, seq, dim, group, window, streamed):
    """Mosaic compiles the flash forward and backward kernels for a v5e
    at the widths the benchmark's LM cells run, and the streamed ones at a
    length where k and v no longer fit VMEM (what interpret mode cannot
    show: tiling, VMEM, transposed products), and the gradient holds no
    loop outside the kernels. Nothing runs: not a chip run."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from edl_tpu.ops import flash_attention as fa
    chip = SingleDeviceSharding(_tpu_topology_or_skip()[0])
    q = jax.ShapeDtypeStruct((batch, kv_heads, group * seq, dim),
                             jnp.bfloat16, sharding=chip)
    k = jax.ShapeDtypeStruct((batch, kv_heads, seq, dim), jnp.bfloat16,
                             sharding=chip)

    def grads(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window, group=group), q, k, v)
        return out, vjp(g)

    text = jax.jit(grads).lower(q, k, k, q).compile().as_text()
    names = ((fa.FWD_STREAM_NAME, fa.BWD_DQ_NAME, fa.BWD_DKV_NAME)
             if streamed else (fa.FWD_RESIDENT_NAME, fa.BWD_NAME))
    for name in names:
        assert name in text, name
    assert " while(" not in text


# -- bandwidth roofline (pure arithmetic, r5 measured profile) ------------


def test_roofline_account_is_internally_consistent():
    """Pin the roofline artifact (tools/roofline_resnet.py): the
    activation-pass accounting that closes the MFU question (r5:
    measured 52.4 ms step is within ~10% of the v5e bandwidth+MXU
    roofline) must stay arithmetically coherent — 55 BN input maps on
    resnet50_vd, a ~3 GB streaming pass at batch 128, a non-conv
    tail measured in single-digit pass counts, and a roofline the
    measured wall time can never legally undercut. Asserts the tool's
    OWN account() (one derivation, no formula drift between the
    artifact and this pin)."""
    from edl_tpu.tools import roofline_resnet as rl

    a = rl.account()
    assert a["n_bn"] == 55
    assert 2.5 < a["one_pass_gb"] < 3.5, a["one_pass_gb"]
    assert 5.0 < a["nonconv_passes"] < 12.0, a["nonconv_passes"]
    assert a["conv_floor_ms"] < a["conv_ms"]
    assert 50.0 < a["mxu_during_conv_pct"] < 100.0
    assert rl.MEASURED_WALL_MS >= a["roofline_ms"], (
        "wall time undercuts the roofline — re-derive the account")
    assert 0.0 <= a["headroom_pct"] < 25.0, a["headroom_pct"]


def test_bench_best_tpu_pointer_file_is_valid():
    """BENCH_BEST_TPU.json (the pointer perf_accounting's fold code
    maintains; ROADMAP D1) — keep it parseable, keyed by bench model
    names, and shaped like a bench record so it is directly comparable
    with the live metric line."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "BENCH_BEST_TPU.json")
    with open(path) as f:
        best = json.load(f)
    assert best, "pointer file is empty"
    assert set(best) <= {"resnet", "gpt", "bert"}, set(best)
    for model, rec in best.items():
        for key in ("metric", "value", "unit", "measured", "source"):
            assert key in rec, (model, key)
        assert rec["value"] > 0


@pytest.mark.integration
def test_lm_batch_arithmetic_intensity_rises_with_batch():
    """The static basis of the r5e LM batch sweep: growing the batch
    multiplies activation flops while the adamw state traffic stays
    constant, so flops-per-byte must rise — the compiler-accounted
    reason batch 8 (the measured 59k tok/s config) sits at low MFU.
    Pinned at a small GPT config to keep the AOT compile cheap; the
    full-size account lives in PERF_ACCOUNTING.json."""
    devices = _tpu_topology_or_skip()
    b2 = pa.lm_batch_account(devices, 2, num_layers=4, d_model=256,
                             seq=256, vocab=1024)
    b8 = pa.lm_batch_account(devices, 8, num_layers=4, d_model=256,
                             seq=256, vocab=1024)
    assert 3.0 < b8["flops"] / b2["flops"] < 5.0, (b2, b8)
    assert b8["bytes_accessed"] < b2["bytes_accessed"] * 3.5, (b2, b8)
    assert b8["flops_per_byte"] > b2["flops_per_byte"] * 1.15, (b2, b8)
