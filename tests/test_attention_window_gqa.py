"""`attention_context(window=, kv heads < q heads)`: the dense path, the
Pallas flash kernel (interpreter) and a naive repeat-the-heads softmax
agree, forward and gradients, for a window shorter than, equal to and
longer than the sequence, with and without rotary positions, on both
forward kernels (kv resident in VMEM, kv streamed) and on the backward's
sliced scans (window + block < sequence)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models.sparse_decoder import rope
from edl_tpu.ops import flash_attention as fa
from edl_tpu.ops.attention import attention_context, flash_dispatch_reason


def _naive(q, k, v, window):
    b, s, hq, d = q.shape
    g = hq // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None]
    keep = j <= i
    if window is not None:
        keep &= j > i - window
    sc = jnp.where(keep, sc, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)


def _inputs(s, hq, hkv, use_rope, d=32):
    key = jax.random.PRNGKey(s + hq)
    q, k, v, t = [jax.random.normal(jax.random.fold_in(key, i), (2, s, h, d))
                  for i, h in enumerate((hq, hkv, hkv, hq))]
    if use_rope:
        q, k = rope(q, 1.5e6), rope(k, 1.5e6)
    return q, k, v, t


def _value_and_grads(fn, q, k, v, t):
    out = fn(q, k, v)
    grads = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * t),
                     (0, 1, 2))(q, k, v)
    return (out,) + grads


CASES = [
    # seq, query heads, kv heads, window
    (256, 7, 1, 64),      # window < seq, the 7:1 group
    (256, 6, 2, 256),     # window = seq
    (256, 4, 4, 300),     # window > seq, equal head counts
    (256, 4, 2, None),    # grouped heads, whole causal prefix
    (1280, 2, 1, 300),    # the backward's scans cut to a slice of the
    (1024, 2, 1, 200),    # sequence (window + 512 < seq)
]


@pytest.mark.parametrize("use_rope", [0, 1])
@pytest.mark.parametrize("s,hq,hkv,window", CASES)
@pytest.mark.parametrize("path", ["dense", "flash"])
def test_window_gqa_matches_naive(path, s, hq, hkv, window, use_rope):
    q, k, v, t = _inputs(s, hq, hkv, use_rope)
    fn = lambda q, k, v: attention_context(  # noqa: E731
        q, k, v, causal=True, mask=None, dtype=jnp.float32,
        use_flash=path == "flash", window=window)
    got = _value_and_grads(fn, q, k, v, t)
    want = _value_and_grads(lambda q, k, v: _naive(q, k, v, window),
                            q, k, v, t)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("s,hq,hkv,window", [(256, 7, 1, 64),
                                             (384, 2, 2, 100)])
def test_streamed_kernel_takes_window_and_groups(monkeypatch, s, hq, hkv,
                                                 window):
    """kv too large to stay in VMEM streams through the grid: the same
    band, blocks outside it skipped."""
    monkeypatch.setattr(fa, "_RESIDENT_KV_BYTES", 0)
    q, k, v, _ = _inputs(s, hq, hkv, 1)
    got = fa.mha(q, k, v, causal=True, window=window, interpret=True)
    np.testing.assert_allclose(got, _naive(q, k, v, window), atol=2e-5,
                               rtol=2e-5)


def test_blockwise_reference_takes_a_window():
    q, k, v, _ = _inputs(256, 2, 2, 0)
    qt, kt, vt = [x.transpose(0, 2, 1, 3) for x in (q, k, v)]
    got = fa._blockwise_reference(qt, kt, vt, True, 32 ** -0.5, block_k=64,
                                  window=50)
    np.testing.assert_allclose(got.transpose(0, 2, 1, 3),
                               _naive(q, k, v, 50), atol=2e-5, rtol=2e-5)


def test_backward_without_window_is_the_unsliced_scan():
    """`window=None`, one query head per kv head: the backward reads the
    whole sequence for every kv block, as before windows existed — no
    dynamic slice in its jaxpr; with a short window there is."""
    q, k, v, t = [x.transpose(0, 2, 1, 3) for x in _inputs(1024, 2, 2, 0)]

    def bwd(window):
        return str(jax.make_jaxpr(lambda q, k, v, t: fa._flash_bwd(
            q, k, v, t, t, True, 0.2, window=window))(q, k, v, t))

    assert "dynamic_slice" not in bwd(None)
    assert "dynamic_slice" in bwd(128)


@pytest.mark.parametrize("seq,head_dim,fragment", [
    (8192, 128, None),            # the sparse decoder's layers: flash
    (8192, 100, "head_dim"), (8200, 128, "multiple of block")])
def test_dispatch_reason_at_long_sequences(seq, head_dim, fragment):
    """A window and grouped heads do not change where flash is legal:
    the reason is asked by shape alone."""
    why = flash_dispatch_reason(seq, head_dim, platform="tpu")
    assert (why is None) if fragment is None else (fragment in why)


def test_window_needs_causal_and_heads_must_divide():
    q, k, v, _ = _inputs(64, 4, 2, 0)
    with pytest.raises(ValueError, match="causal"):
        attention_context(q, k, v, causal=False, mask=None,
                          dtype=jnp.float32, window=8)
    with pytest.raises(ValueError, match="divide"):
        attention_context(q[:, :, :3], k, v, causal=True, mask=None,
                          dtype=jnp.float32)
