"""`attention_context(window=, kv heads < q heads)`: the dense path, the
Pallas flash kernel (interpreter) and a naive repeat-the-heads softmax
agree, forward and gradients, for a window shorter than, equal to and
longer than the sequence, with and without rotary positions, on both
forward kernels (kv resident in VMEM, kv streamed) and on the backward's
Pallas kernels (one while k and v stay in VMEM, one for dq and one for
dk/dv beyond; tiles inside the band, on its edges and outside it). The
forward computes a score tile the way the backward rebuilds it: operands
in the dtype they arrive in, float32 accumulation, tiles chosen from the
shape, a mask only on the band's edge tiles."""

import functools

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
    (1280, 2, 1, 300),    # a sequence of several blocks: tiles wholly
    (1024, 2, 1, 200),    # inside the band, on both edges, and skipped
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


def _kernel_variant(monkeypatch, variant, block=128):
    """128-wide backward tiles, so that these sequences have several; and
    the operand a kernel revisits held whole in VMEM or streamed through
    the grid (the same switch moves the forward between its kernels)."""
    monkeypatch.setattr(fa, "_BLOCK", block)
    if variant == "streamed":
        monkeypatch.setattr(fa, "_RESIDENT_KV_BYTES", 0)


@pytest.mark.parametrize("s,hq,hkv,window", CASES)
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 1e-2)])
@pytest.mark.parametrize("variant", ["resident", "streamed"])
def test_flash_gradient_matches_dense(monkeypatch, variant, dtype, tol, s,
                                      hq, hkv, window):
    """The Pallas backward against the dense path's gradient: float32
    inputs element by element at the forward's tolerance; bfloat16 inputs
    (products in bfloat16, float32 accumulation, p and ds rounded where
    they enter a product) against the float32 gradient at the same
    values, in relative L2 norm."""
    _kernel_variant(monkeypatch, variant)
    q, k, v, t = [x.astype(dtype) for x in _inputs(s, hq, hkv, 1)]

    def grads(use_flash, q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(attention_context(
            q, k, v, causal=True, mask=None, dtype=q.dtype,
            use_flash=use_flash, window=window).astype(jnp.float32)
            * t.astype(jnp.float32)), (0, 1, 2))(q, k, v)

    got = grads(True, q, k, v)
    want = grads(False, *[x.astype(jnp.float32) for x in (q, k, v)])
    for a, b in zip(got, want):
        assert a.dtype == q.dtype
        if dtype == "float32":
            np.testing.assert_allclose(a, b, atol=tol, rtol=tol)
        else:
            a = np.asarray(a, np.float32)
            assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("variant", ["resident", "streamed"])
def test_flash_gradient_pads_a_ragged_sequence(monkeypatch, variant, causal):
    """160 positions and backward tiles of 96, 48 or 24: none divides, so
    the wrapper pads q, dO, k and v to 192; without a causal mask the
    padded keys are masked in the kernel. (The forward runs in tiles of
    32, which divide: it takes no ragged sequence beyond one tile.)"""
    _kernel_variant(monkeypatch, variant, block=96)
    q, k, v, t = _inputs(160, 4, 4, 0)

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * t),
                        (0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: fa.mha(q, k, v, causal=causal, block_q=32,
                                       block_k=32, interpret=True))
    want = grads(lambda q, k, v: attention_context(
        q, k, v, causal=causal, mask=None, dtype=jnp.float32,
        use_flash=False))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def _kernel_forward(q, k, v, window):
    """Both outputs of the forward kernel the variant selects, in tiles of
    128, and its inputs in the kernels' layout."""
    g = q.shape[2] // k.shape[2]
    qt, kt, vt = fa._kernel_layout(q, g), fa._kernel_layout(k), \
        fa._kernel_layout(v)
    out, lse = fa._flash_fwd(qt, kt, vt, True, 32 ** -0.5, 128, 128, True,
                             window, g, fa._RESIDENT_KV_BYTES)
    return qt, kt, out, lse


def _band(s, g, window):
    """[g * s, s] bool: the keys each row of a kv head's query heads reads"""
    pos = jnp.tile(jnp.arange(s), g)[:, None], jnp.arange(s)[None]
    keep = pos[0] >= pos[1]
    if window is not None:
        keep &= pos[0] - pos[1] < window
    return keep


@pytest.mark.parametrize("s,hq,hkv,window", CASES)
@pytest.mark.parametrize("variant", ["resident", "streamed"])
def test_forward_takes_bfloat16_as_it_arrives(monkeypatch, variant, s, hq,
                                              hkv, window):
    """bfloat16 q, k, v go into the products as they are (float32
    accumulation; p rounded where it enters p.v): against the dense
    float32 path at the same values, the result in relative L2 norm and
    the row statistic element by element."""
    _kernel_variant(monkeypatch, variant)
    q, k, v, _ = [x.astype(jnp.bfloat16) for x in _inputs(s, hq, hkv, 1)]
    qt, kt, out, lse = _kernel_forward(q, k, v, window)
    assert out.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    q32, k32, v32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = _naive(q32, k32, v32, window)
    got = np.asarray(fa._model_layout(out, hq // hkv), np.float32)
    assert np.linalg.norm(got - want) <= 1e-2 * np.linalg.norm(want)
    sc = jnp.einsum("bhqd,bhkd->bhqk", qt.astype(jnp.float32),
                    kt.astype(jnp.float32)) * 32 ** -0.5
    want_lse = jax.nn.logsumexp(
        jnp.where(_band(s, hq // hkv, window), sc, -jnp.inf), axis=-1)
    np.testing.assert_allclose(lse.reshape(want_lse.shape), want_lse,
                               atol=2e-2, rtol=0)


@pytest.mark.parametrize("s,hq,hkv,window", CASES)
@pytest.mark.parametrize("variant", ["resident", "streamed"])
def test_forward_and_backward_agree_on_the_scores(monkeypatch, variant, s,
                                                  hq, hkv, window):
    """The backward rebuilds p = exp(_dot(q, k) * scale - lse) from the
    forward's lse: with bfloat16 inputs every row of it sums to 1 over the
    band, which holds only while both compute the scores the same way."""
    _kernel_variant(monkeypatch, variant)
    q, k, v, _ = [x.astype(jnp.bfloat16) for x in _inputs(s, hq, hkv, 1)]
    qt, kt, _, lse = _kernel_forward(q, k, v, window)
    scores = jax.vmap(jax.vmap(lambda q, k: fa._dot(q, k, fa._NT)))(
        qt, kt) * 32 ** -0.5
    p = jnp.exp(scores - lse.reshape(scores.shape[:3])[..., None])
    rows = jnp.sum(jnp.where(_band(s, hq // hkv, window), p, 0.0), axis=-1)
    np.testing.assert_allclose(rows, 1.0, atol=2e-3, rtol=0)


def _eqns(jaxpr, kernels=True):
    """Every equation of a jaxpr and of the jaxprs in it, with or without
    the kernels' bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        if kernels or eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _eqns(sub, kernels)


def _forward_call(q, k, **kwargs):
    """The forward's one `pallas_call` for arguments of these shapes
    (kernels' layout; nothing runs)."""
    jaxpr = jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, interpret=True, **kwargs))(q, k, k)
    call, = [e for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    return call


@pytest.mark.parametrize("window", [None, 128])
@pytest.mark.parametrize("variant", ["resident", "streamed"])
def test_forward_products_are_bfloat16_with_float32_sums(monkeypatch,
                                                         variant, window):
    """Inside both forward kernels every product has bfloat16 operands
    and a float32 result, and nothing bfloat16 is widened: no q, k or v
    block is cast to float32 (what the kernels did up to PR 36)."""
    _kernel_variant(monkeypatch, variant)
    q = jax.ShapeDtypeStruct((2, 2, 1024, 64), jnp.bfloat16)
    call = _forward_call(q, q, window=window)
    assert str(call.params["name"]) == {
        "resident": fa.FWD_RESIDENT_NAME,
        "streamed": fa.FWD_STREAM_NAME}[variant]
    body = list(_eqns(call.params["jaxpr"]))
    dots = [e for e in body if e.primitive.name == "dot_general"]
    assert dots
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
        assert e.outvars[0].aval.dtype == jnp.float32
    assert not [e for e in body if e.primitive.name == "convert_element_type"
                and e.invars[0].aval.dtype == jnp.bfloat16]


@pytest.mark.parametrize("seq,group,kwargs,tile", [
    (128, 1, {}, (128, 128)),           # one tile
    (640, 1, {}, (128, 128)),           # neither 512 nor 256 divides it
    (1024, 1, {}, (512, 512)),          # gpt2s-train
    (8192, 7, {}, (512, 512)),          # the sparse decoder: 7 heads a kv head
    (1024, 1, dict(block_q=32), (32, 512)),     # the caller's tile stands
    (1024, 2, dict(block_q=32, block_k=16), (32, 16)),
])
def test_forward_tile_follows_the_shape(seq, group, kwargs, tile):
    """The forward's tile edge is the backward's (`_tile_edge`: 512, its
    half or its quarter, the first that divides the sequence, one tile for
    a short one) unless the caller names one."""
    if not kwargs:
        assert fa._tile_edge(seq, fa._BLOCK) == tile[0]
    q = jax.ShapeDtypeStruct((1, 1, group * seq, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 1, seq, 128), jnp.bfloat16)
    call = _forward_call(q, k, group=group, **kwargs)
    assert call.params["grid_mapping"].grid[:2] == (1, group * seq // tile[0])
    assert {e.outvars[0].aval.shape for e in _eqns(call.params["jaxpr"])
            if e.primitive.name == "dot_general"} == {tile, (tile[0], 128)}


@pytest.mark.parametrize("window", [None, 300])
@pytest.mark.parametrize("variant", ["resident", "streamed"])
def test_inner_tiles_need_no_mask(monkeypatch, variant, window):
    """A tile wholly inside the band builds no positions, no compare and
    no select, and loses nothing by it: the result is the same when every
    tile of the band is taken as an edge tile."""
    _kernel_variant(monkeypatch, variant)
    s, block = 1280, 128
    first, ufirst, ulast, last = fa._kv_band(
        np, np.arange(s // block) * block, block, block, s // block,
        causal=True, window=window, ragged=False)
    assert (ulast > ufirst).any() and (ufirst > first).any() == (
        window is not None) and (last > ulast).all()

    x = jnp.zeros((block, 32))
    col = jnp.zeros((block, 1))
    tile = functools.partial(fa._softmax_tile, sm_scale=1.0, causal=True,
                             window=window, kv_len=None)
    ops = {m: {e.primitive.name for e in _eqns(jax.make_jaxpr(
        lambda x: tile(x, x, x, (x, col, col), 0, 0, masked=m))(x).jaxpr)}
        for m in (True, False)}
    assert {"iota", "ge", "select_n"} <= ops[True]
    assert not {"iota", "ge", "lt", "select_n"} & ops[False]

    q, k, v, _ = _inputs(s, 2, 1, 1)
    split = _kernel_forward(q, k, v, window)[2:]

    def as_edge(*args, masked, tile=fa._softmax_tile, **kwargs):
        return tile(*args, masked=True, **kwargs)

    monkeypatch.setattr(fa, "_softmax_tile", as_edge)
    fa._flash_fwd.clear_cache()
    try:
        masked = _kernel_forward(q, k, v, window)[2:]
    finally:
        fa._flash_fwd.clear_cache()
    for a, b in zip(split, masked):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(fa._model_layout(split[0], 2),
                               _naive(q, k, v, window), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block_q,block_k,s,sk,window,want", [
    (512, 512, 8192, 8192, None, 1),    # the cells' tiles
    (512, 512, 8192, 8192, 4096, 1),
    (512, 256, 1024, 1024, None, 2),
    (256, 512, 1024, 1024, 767, 1),     # the narrowest window the rule takes
    (256, 512, 1024, 1024, 766, None),  # the rest stay a loop: a window
    (128, 128, 256, 256, 64, None),     # that may cut the diagonal's tiles,
    (96, 64, 192, 192, None, None),     # neither edge dividing the other,
    (128, 128, 320, 320, None, None),   # a ragged last q block,
    (128, 128, 512, 256, None, None),   # q blocks past the keys
])
def test_diagonal_tiles_is_the_bands_own_count(block_q, block_k, s, sk,
                                               window, want):
    """Where the resident forward unrolls the diagonal's tiles in place of
    a loop, their number is what `_kv_band` gives for every q block."""
    assert fa._diagonal_tiles(block_q, block_k, s, sk, True, window) == want
    assert fa._diagonal_tiles(block_q, block_k, s, sk, False, None) is None
    _, _, ulast, last = fa._kv_band(
        np, np.arange(-(-s // block_q)) * block_q, block_q, block_k,
        -(-sk // block_k), causal=True, window=window, ragged=False)
    if want is not None:
        assert (last - ulast == want).all()


@pytest.mark.parametrize("s,hq,hkv,window", [(256, 7, 1, 64),
                                             (384, 2, 2, None)])
@pytest.mark.parametrize("variant", ["resident", "streamed"])
def test_forward_kernels_write_the_row_statistic(monkeypatch, variant, s, hq,
                                                 hkv, window):
    """lse = m + log(l) of every query row, from both forward kernels,
    against logsumexp of the dense scores; [batch * kv heads, 1, rows]:
    four bytes a row, the rows of a kv head's query heads in turn."""
    _kernel_variant(monkeypatch, variant)
    q, k, v, _ = _inputs(s, hq, hkv, 1)
    g = hq // hkv
    qt, kt, out, lse = _kernel_forward(q, k, v, window)
    sc = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * 32 ** -0.5
    want = jax.nn.logsumexp(jnp.where(_band(s, g, window), sc, -jnp.inf),
                            axis=-1)
    assert lse.shape == (2 * hkv, 1, g * s) and lse.dtype == jnp.float32
    np.testing.assert_allclose(lse.reshape(want.shape), want, atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(fa._model_layout(out, g),
                               _naive(q, k, v, window), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [None, 128])
@pytest.mark.parametrize("variant", ["resident", "streamed"])
def test_backward_is_pallas_kernels_and_no_loop(monkeypatch, variant, window):
    """The gradient's jaxpr holds no `while` and no `scan`, with and
    without a window: the backward is the Pallas kernels named
    `flash_bwd*` (the names the benchmark's reader finds in a trace): one
    while k and v stay in VMEM, one for dq and one for dk/dv beyond."""
    _kernel_variant(monkeypatch, variant)
    q, k, v, t = _inputs(1024, 2, 2, 0)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(fa.mha(
        q, k, v, causal=True, window=window, interpret=True) * t),
        (0, 1, 2)))(q, k, v)

    eqns = list(_eqns(jaxpr.jaxpr, kernels=False))
    assert not [e for e in eqns if e.primitive.name in ("while", "scan")]
    names = sorted(str(e.params["name"]) for e in eqns
                   if e.primitive.name == "pallas_call")
    want = {"resident": [fa.BWD_NAME, fa.FWD_RESIDENT_NAME],
            "streamed": [fa.BWD_DKV_NAME, fa.BWD_DQ_NAME,
                         fa.FWD_STREAM_NAME]}[variant]
    assert names == want


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
