"""Attention over a learned selection of keys (`ops/sparse_attention.py`)
and its place in the one dispatch (`ops/attention.py`):

- the thresholds: the counting kernel against `lax.top_k`, bit for bit, on
  scores that are exact in any order of summation, forced ties included;
  and what the kernel hands the forward beside them — the kept set, a bit
  a pair, against `selection_mask`, and the indexer's lse over it;
- the selection kernels (Pallas interpreter), forward and backward, against
  the dense masked path at 4 x and 8 x the top-k with tiles that cross the
  thresholds: the result, the indexer's loss, the keys kept, and every
  gradient — the indexer's ON ITS OWN;
- under remat: a policy that saves the forward rule's named residuals
  leaves the gradient ONE forward kernel, with the same bits;
- the dispatch: what a selection excludes, and that WITHOUT one the band
  kernels take the operands they took before (no dummy selection threaded
  through the plain path).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jaxpr_kernels import pallas_call_names

from edl_tpu.ops import attention, flash_attention
from edl_tpu.ops import sparse_attention as sa


def _inputs(dtype, s=64, heads=4, kv_heads=2, d=16, hi=8, di=8, b=2,
            seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda i, shape, dt=dtype: jax.random.normal(  # noqa: E731
        ks[i], shape, dt)
    return (normal(0, (b, s, heads, d)), normal(1, (b, s, kv_heads, d)),
            normal(2, (b, s, kv_heads, d)), normal(3, (b, s, hi, di)),
            normal(4, (b, s, di)), normal(5, (b, s, hi), jnp.float32))


def _on_a_grid(qi, ki, wi):
    """The same on a grid of halves: every product and partial sum of the
    index scores is then exact in float32, in any order."""
    r = lambda x: jnp.round(x.astype(jnp.float32) * 2) / 2  # noqa: E731
    return r(qi).astype(qi.dtype), r(ki).astype(ki.dtype), r(wi)


def _tau_between(qi, ki, wi, topk):
    """Thresholds half-way between the k-th and the (k+1)-th largest
    score: the last bit of a sum cannot move a key across them."""
    s = qi.shape[1]
    scores = jnp.where(sa._causal(s)[None], sa.index_scores(qi, ki, wi),
                       -jnp.inf)
    top = lax.top_k(scores, topk + 1)[0]
    return jnp.where(jnp.arange(s)[None] < topk, -jnp.inf,
                     0.5 * (top[..., topk - 1] + top[..., topk]))


# -- thresholds --------------------------------------------------------------

@pytest.mark.parametrize("s,topk,block", [(64, 16, 16), (64, 8, 32),
                                          (32, 8, 8), (16, 32, 8)])
def test_thresholds_kernel_equals_top_k_bit_for_bit(s, topk, block):
    _, _, _, qi, ki, wi = _inputs(jnp.bfloat16, s=s, hi=4, di=16)
    qi, ki, wi = _on_a_grid(qi, ki, wi)
    got = sa.index_thresholds(qi, ki, wi, topk, use_kernel=True,
                              interpret=True, block=block)
    want = sa.index_thresholds(qi, ki, wi, topk, use_kernel=False)
    assert got.shape == (2, s) and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.all(np.isneginf(np.asarray(got)[:, :topk]))
    if s > topk:
        # the grid makes ties: rows that keep more than top-k keys, all of
        # them AT the threshold, none below it
        scores = jnp.where(sa._causal(s)[None], sa.index_scores(qi, ki, wi),
                           -jnp.inf)
        kept = (scores >= got[..., None]).sum(-1)
        above = (scores > got[..., None]).sum(-1)
        want_kept = jnp.minimum(jnp.arange(s) + 1, topk)[None]
        assert bool(jnp.all(kept >= want_kept))
        assert bool(jnp.all(above[:, topk:] < topk))
        assert int((kept != want_kept).sum()) > 0, "no tie was forced"


def test_thresholds_of_a_forced_tie_keep_both_keys():
    """Two keys with one score at the threshold: both are kept."""
    b, s, hi, di, topk = 1, 16, 1, 8, 4
    qi = jnp.ones((b, s, hi, di), jnp.float32)
    ki = jnp.zeros((b, s, di), jnp.float32).at[0, :, 0].set(
        jnp.array([9, 8, 7, 5, 5, 1, 2, 3, 5, 0, 0, 0, 0, 0, 0, 0.]))
    wi = jnp.ones((b, s, hi), jnp.float32)
    for kernel in (True, False):
        tau = sa.index_thresholds(qi, ki, wi, topk, use_kernel=kernel,
                                  interpret=True, block=8)
        scale = sa.index_scale(hi, di)
        # queries 4.. see 9, 8, 7, 5, 5: the 4th largest is 5, twice
        np.testing.assert_allclose(np.asarray(tau[0, 4:]), 5 * scale)
        keep = sa.selection_mask(qi, ki, wi, tau)
        assert keep[0, 4].sum() == 5 and keep[0, 8].sum() == 6
        assert keep[0, 3].sum() == 4 and bool(jnp.isneginf(tau[0, 3]))


def _unpacked(mask, s, block):
    """[b, s, s] bool from the packed kept set, by the layout's rule: word
    [t, g * lanes + lane] holds at bit b the key (32 g + b) lanes + lane."""
    lanes = min(128, block)
    words = np.asarray(mask).astype(np.uint32)
    assert words.shape[1:] == (s, -(-s // (32 * lanes)) * lanes)
    keys = np.arange(s)
    word = (keys // lanes // 32) * lanes + keys % lanes
    return (words[:, :, word] >> (keys // lanes % 32).astype(np.uint32)) & 1 \
        == 1


def _tie(_s, _topk):
    """`test_thresholds_of_a_forced_tie_keep_both_keys`' inputs."""
    b, s, hi, di = 1, 16, 1, 8
    ki = jnp.zeros((b, s, di), jnp.float32).at[0, :, 0].set(
        jnp.array([9, 8, 7, 5, 5, 1, 2, 3, 5, 0, 0, 0, 0, 0, 0, 0.]))
    return (jnp.ones((b, s, hi, di), jnp.float32), ki,
            jnp.ones((b, s, hi), jnp.float32))


def _grid(s, _topk):
    _, _, _, qi, ki, wi = _inputs(jnp.bfloat16, s=s, hi=4, di=16)
    return _on_a_grid(qi, ki, wi)


@pytest.mark.parametrize("make,s,topk,block", [
    (_grid, 64, 16, 16), (_grid, 64, 8, 32), (_grid, 32, 8, 8),
    (_grid, 16, 32, 8),         # every row below top-k: all causal keys
    (_tie, 16, 4, 8),           # two keys AT the threshold: both bits set
])
def test_thresholds_kernel_hands_on_the_kept_set_and_its_lse(make, s, topk,
                                                             block):
    """Beside tau the kernel writes which keys it keeps, a bit a (query,
    key), and the log-sum-exp of I over them: the plain twin's, bit for
    bit, which is `selection_mask` packed."""
    qi, ki, wi = make(s, topk)
    tau, mask, lse_i = sa.index_selection(qi, ki, wi, topk, use_kernel=True,
                                          interpret=True, block=block)
    np.testing.assert_array_equal(np.asarray(tau), np.asarray(
        sa.index_thresholds(qi, ki, wi, topk, use_kernel=False)))
    keep = sa.selection_mask(qi, ki, wi, tau)
    twin_mask, twin_lse_i = sa.kept_set(qi, ki, wi, tau, block)
    assert mask.dtype == jnp.int32 and mask.shape == twin_mask.shape
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(twin_mask))
    np.testing.assert_array_equal(_unpacked(mask, s, block),
                                  np.asarray(keep))
    # rows below top-k keep their whole causal prefix
    below = min(topk, s)
    assert bool(jnp.all(keep[:, :below] == sa._causal(s)[None, :below]))
    # the indexer's lse over the kept keys, against the dense one
    want = jax.nn.logsumexp(jnp.where(keep, sa.index_scores(qi, ki, wi),
                                      -jnp.inf), axis=-1)
    np.testing.assert_allclose(lse_i, want, atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(twin_lse_i, want, atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("s,topk,block", [(64, 16, 16), (64, 8, 32)])
def test_forward_counts_the_keys_of_the_kept_set_it_was_handed(s, topk,
                                                              block):
    """`kept` is the forward kernel's witness of the mask it applied: the
    handed set's popcount a row — more than top-k where the grid ties."""
    q, k, v, qi, ki, wi = _inputs(jnp.bfloat16, s=s, hi=4, di=16)
    qi, ki, wi = _on_a_grid(qi, ki, wi)
    tau, mask, lse_i = sa.index_selection(qi, ki, wi, topk, use_kernel=True,
                                          interpret=True, block=block)
    _, _, kept = sa.select_attend(q, k, v, (qi, ki, wi, tau, mask, lse_i),
                                  interpret=True, block=block)
    bits = lax.population_count(mask).sum(-1)
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(bits))
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(
        sa.selection_mask(qi, ki, wi, tau).sum(-1)))
    assert int((kept > topk).sum()) > 0, "no tie was forced"
    # a set packed for another block is refused, not misread
    with pytest.raises(ValueError, match="another block"):
        sa.select_attend(q, k, v, (qi, ki, wi, tau, mask, lse_i),
                         interpret=True, block=block // 2)


# -- the selection kernels against the dense masked path ---------------------

def _run(fn, inputs, tau, **kw):
    """((a loss of the result and the index loss, (out, kl, kept)), its
    gradients by q, k, v, qi, ki, wi)."""
    def f(q, k, v, qi, ki, wi):
        out, kl, kept = fn(q, k, v, (qi, ki, wi, tau), **kw)
        return (jnp.sum(jnp.sin(out.astype(jnp.float32)))
                + 3.0 * kl.sum()), (out, kl, kept)
    return jax.value_and_grad(f, argnums=range(6), has_aux=True)(*inputs)


def _both(dtype, s, topk, block, heads=4, kv_heads=2):
    inputs = _inputs(dtype, s=s, heads=heads, kv_heads=kv_heads)
    tau = _tau_between(*inputs[3:], topk)
    return (_run(sa.select_attend, inputs, tau, interpret=True, block=block),
            _run(sa.dense_select_attend, inputs, tau), tau)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("s,topk,block,heads,kv_heads", [
    (64, 16, 16, 4, 2),     # 4 x top-k, 4 tiles a side, two kv heads
    (64, 8, 32, 8, 1),      # 8 x top-k, the cell's 8:1 group
])
def test_selection_kernels_match_the_dense_masked_path(
        dtype, tol, s, topk, block, heads, kv_heads):
    ((_, (out, kl, kept)), grads), ((_, (d_out, d_kl, d_kept)), d_grads), \
        tau = _both(dtype, s, topk, block, heads, kv_heads)
    # every row keeps exactly min(t + 1, top-k): tiles cross the threshold
    want = jnp.minimum(jnp.arange(s) + 1, topk)[None]
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(d_kept))
    np.testing.assert_array_equal(np.asarray(kept),
                                  np.broadcast_to(want, kept.shape))
    f32 = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    np.testing.assert_allclose(f32(out), f32(d_out), atol=tol, rtol=tol)
    np.testing.assert_allclose(f32(kl), f32(d_kl), atol=tol, rtol=tol)
    assert float(d_kl.mean()) > 1e-3, "an index loss of nothing"
    for name, got, ref in zip(("q", "k", "v", "qi", "ki", "wi"), grads,
                              d_grads):
        err = float(jnp.linalg.norm(f32(got) - f32(ref))
                    / jnp.linalg.norm(f32(ref)))
        assert err < tol, (name, err)


def test_selection_gradients_keep_to_their_sides():
    """The result moves q, k, v only (the choice is discrete); the index
    loss moves qi, ki, wi only (the mean probability is a stop-gradient);
    the thresholds move nothing."""
    q, k, v, qi, ki, wi = _inputs(jnp.float32, s=16, b=1)
    tau = _tau_between(qi, ki, wi, 4)
    for fn, kw in ((sa.select_attend, dict(interpret=True, block=8)),
                   (sa.dense_select_attend, {})):
        by_out = jax.grad(lambda *a: fn(a[0], a[1], a[2], (a[3], a[4], a[5],
                                                           a[6]), **kw)[
            0].sum(), argnums=range(7))(q, k, v, qi, ki, wi, tau)
        by_kl = jax.grad(lambda *a: fn(a[0], a[1], a[2], (a[3], a[4], a[5],
                                                          a[6]), **kw)[
            1].sum(), argnums=range(7))(q, k, v, qi, ki, wi, tau)
        norms = lambda g: [float(jnp.abs(x).max()) for x in g]  # noqa: E731
        assert all(n > 0 for n in norms(by_out)[:3])
        assert norms(by_out)[3:] == [0.0] * 4
        assert norms(by_kl)[:3] == [0.0] * 3 and norms(by_kl)[6] == 0.0
        assert all(n > 0 for n in norms(by_kl)[3:6])


def test_kernel_names_do_not_collide_with_the_readers_of_the_others():
    """benchmark/lib/kernel_readers.py matches a kernel by SUBSTRING."""
    ours = (sa.TAU_NAME, sa.FWD_NAME, sa.KL_NAME, sa.BWD_NAME)
    theirs = (flash_attention.FWD_RESIDENT_NAME, flash_attention.BWD_NAME,
              "moe_gmm", "moe_tgmm")
    for a in ours:
        assert not any(t in a for t in theirs)
        assert not any(a in b for b in ours if b != a)


@pytest.mark.parametrize("saved,forwards", [
    (sa.SAVED_UNDER_REMAT, 1), (sa.SAVED_UNDER_REMAT[:4], 2)])
def test_remat_that_saves_the_residuals_runs_the_forward_once(saved,
                                                              forwards):
    """The forward rule names what it leaves the backward (out, lse), and
    the thresholds' kernel what IT leaves it (tau, the indexer's lse):
    under a policy that saves the names the recomputation holds neither
    kernel — the kept set, which no name saves, is read by the one forward
    and by nothing after it —, under one without them it holds both a
    second time; and either way the gradients are bit for bit those of no
    remat at all."""
    q, k, v, qi, ki, wi = _inputs(jnp.float32, s=32)

    def layer(q, k, v, qi, ki, wi):
        tau, mask, lse_i = sa.index_selection(
            qi, ki, wi, 8, use_kernel=True, interpret=True, block=8)
        out, kl, _ = sa.select_attend(
            q * 1.5, k, v, (qi, ki, wi, tau, mask, lse_i), interpret=True,
            block=8)
        return jnp.sum(out * out) + kl.sum()

    assert sa.SAVED_UNDER_REMAT == (
        "attn.index_q", "attn.index_k", "attn.index_w", "attn.tau",
        "attn.select_out", "attn.select_lse", "attn.select_lse_i")
    plain = jax.grad(layer, range(6))
    assert sorted(pallas_call_names(
        jax.make_jaxpr(plain)(q, k, v, qi, ki, wi).jaxpr)) == sorted(
            [sa.TAU_NAME, sa.FWD_NAME, sa.KL_NAME, sa.BWD_NAME])
    rematted = jax.grad(jax.checkpoint(
        layer, policy=jax.checkpoint_policies.save_only_these_names(*saved)),
        range(6))
    names = pallas_call_names(
        jax.make_jaxpr(rematted)(q, k, v, qi, ki, wi).jaxpr)
    assert sorted(names) == sorted(
        [sa.TAU_NAME, sa.FWD_NAME] * forwards + [sa.KL_NAME, sa.BWD_NAME])
    for got, want in zip(jax.jit(rematted)(q, k, v, qi, ki, wi),
                         jax.jit(plain)(q, k, v, qi, ki, wi)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


#: the longest sequence the attention kernels take at the cell's widths
LONGEST = 25088


def test_kernels_refuse_what_does_not_fit_vmem():
    assert sa.kernel_reason(16384, 1, 128, 64) is None
    assert "VMEM" in sa.kernel_reason(131072, 1, 128, 64)
    # the backward's count holds a tile's 16 index products (16 MiB at a
    # block of 512) beside the sequence's operands and accumulators: the
    # longest length taken and the first refused, 512 on — what the chip's
    # compiler takes and refuses too (`test_kernels_compile_for_the_chip`)
    assert sa.kernel_reason(LONGEST, 1, 128, 64) is None
    assert "index products" in sa.kernel_reason(LONGEST + 512, 1, 128, 64)
    assert sa.kernel_reason(LONGEST, 1, 128, 64, index_heads=20)
    q, k, v, qi, ki, wi = _inputs(jnp.float32, s=12)
    with pytest.raises(ValueError, match="multiple of 8"):
        sa.select_attend(q, k, v, (qi, ki, wi, jnp.zeros((2, 12))),
                         interpret=True)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(desc.devices[0])


@pytest.mark.parametrize("s", [16384, LONGEST])
def test_kernels_compile_for_the_chip(one_chip, s):
    """The four kernels at the widths of the cell that runs them (8 heads
    of 128 on one kv head, 16 index heads of 64, bfloat16), compiled for a
    v5e that is described and not attached: what `kernel_reason` takes,
    the chip's compiler takes — VMEM for the sequence's operands, the
    backward's accumulators and its tile of index products."""
    b, h, kv, d, hi, di = 1, 8, 1, 128, 16, 64
    assert sa.kernel_reason(s, kv, d, di, 2, hi) is None
    arg = lambda dt, *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    bf, f32 = jnp.bfloat16, jnp.float32
    q, kk = arg(bf, b, h, s, d), arg(bf, b, kv, s, d)
    qi, ki, wi = arg(bf, b, hi, s, di), arg(bf, b, s, di), arg(f32, b, s, hi)
    row, rows = arg(f32, b, 1, s), arg(f32, b, h, s)
    block = sa._block_for(s, sa._BLOCK)
    mask = arg(jnp.int32, b, s, sa.mask_words(s, sa._mask_lanes(block)))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        tau = sa._thresholds.lower(qi, ki, wi, 2048, sa._TAU_BLOCK_Q, block,
                                   False).compile()
        fwd = sa._forward.lower(q, kk, kk, qi, ki, wi, row, mask, row,
                                d ** -0.5, block, False).compile()
        bwd = sa._backward.lower(q, kk, kk, q, rows, rows, qi, ki, wi, row,
                                 row, row, d ** -0.5, block, False).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    for compiled, names in ((tau, [sa.TAU_NAME]),
                            (fwd, [sa.FWD_NAME, sa.KL_NAME]),
                            (bwd, [sa.BWD_NAME])):
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') \
            == len(names)
        assert all(n in text for n in names)


def _dots(jaxpr):
    """`dot_general`s a kernel's body holds, its loops' bodies included."""
    return sum((eqn.primitive.name == "dot_general")
               + sum(_dots(sub) for sub in jax.core.jaxprs_in_params(
                   eqn.params)) for eqn in jaxpr.eqns)


def _pallas_eqns(jaxpr):
    """Every `pallas_call` equation of a jaxpr, its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_eqns(sub)


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (8, 1), (4, 4)])
def test_kernels_form_each_index_product_once_a_pass(heads, kv_heads):
    """A tile's products: the forward none of the indexer's (it reads the
    kept set) beside attention's — the scores ONE a kv head, for all the
    query heads that share it, and p . v one a head; the KL one an index
    head beside 1 a head; the backward ONE an index head — kept in VMEM for
    the indexer's backward, whose dqi and dki are 2 more — beside
    attention's 5 a head."""
    hi = 8
    q, k, v, qi, ki, wi = _inputs(jnp.float32, s=32, heads=heads,
                                  kv_heads=kv_heads, hi=hi)
    tau = jnp.zeros((2, 32))

    def f(q, k, v, qi, ki, wi):
        out, kl, _ = sa.select_attend(q, k, v, (qi, ki, wi, tau),
                                      interpret=True, block=8)
        return out.sum() + kl.sum()

    found = {eqn.params["name"]: _dots(eqn.params["jaxpr"])
             for eqn in _pallas_eqns(jax.make_jaxpr(jax.grad(f, range(6)))(
                 q, k, v, qi, ki, wi).jaxpr)}
    assert found == {sa.FWD_NAME: kv_heads + heads, sa.KL_NAME: hi + heads,
                     sa.BWD_NAME: 5 * heads + 3 * hi}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("block", [16, 32])
def test_a_group_in_one_product_equals_a_product_a_head(dtype, tol, block):
    """8 query heads on ONE kv head go through a key block as one block of
    rows; on the same k and v repeated to 8 kv heads (groups of 1) each
    head has a product of its own, the kernel as it was. A row's scores do
    not change with the rows beside it: the result, the index loss, the
    keys kept and every gradient a head owns are the same bits, and dk,
    dv — a repeated head's share rounded on its own — the same sum."""
    heads = 8
    q, k, v, *index = _inputs(dtype, s=64, heads=heads, kv_heads=1)
    tau = _tau_between(*index, 8)

    def run(k, v):
        return _run(sa.select_attend, (q, k, v, *index), tau, interpret=True,
                    block=block)

    ((_, results), grads) = run(k, v)
    ((_, a_head), a_head_grads) = run(jnp.repeat(k, heads, axis=2),
                                      jnp.repeat(v, heads, axis=2))
    for got, want in zip(results + (grads[0],) + grads[3:],
                         a_head + (a_head_grads[0],) + a_head_grads[3:]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    f32 = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    for name, got, shares in zip("kv", grads[1:3], a_head_grads[1:3]):
        want = f32(shares).sum(axis=2, keepdims=True)
        err = float(np.linalg.norm(f32(got) - want) / np.linalg.norm(want))
        assert 0 < float(np.abs(want).max()) and err < tol, (name, err)


@pytest.mark.parametrize("block", [8, 32])
def test_indexer_gradients_match_the_dense_path_at_two_blocks(block):
    """dqi, dki and dwi of the index loss ALONE, read from the products
    the backward kept: the dense path's, at a tile that is one block of
    the sequence and at tiles that cross every threshold."""
    q, k, v, qi, ki, wi = _inputs(jnp.float32, s=32, heads=8, kv_heads=1)
    tau = _tau_between(qi, ki, wi, 8)

    def by_kl(fn, **kw):
        return jax.grad(lambda qi, ki, wi: fn(
            q, k, v, (qi, ki, wi, tau), **kw)[1].sum(), range(3))(qi, ki, wi)

    for name, got, want in zip(
            ("qi", "ki", "wi"),
            by_kl(sa.select_attend, interpret=True, block=block),
            by_kl(sa.dense_select_attend)):
        err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert 0 < float(jnp.abs(want).max()) and err < 2e-5, (name, err)


# -- the dispatch ------------------------------------------------------------

@pytest.mark.parametrize("use_flash", [False, True])
def test_attention_context_with_a_selection(use_flash):
    q, k, v, qi, ki, wi = _inputs(jnp.float32, s=32)
    select = (qi, ki, wi, _tau_between(qi, ki, wi, 8))
    got = attention.attention_context(q, k, v, causal=True, mask=None,
                                      dtype=jnp.float32, select=select,
                                      use_flash=use_flash)
    want = sa.dense_select_attend(q, k, v, select)[0]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # and through the flash module's own entry points
    via_mha = flash_attention.mha(q, k, v, causal=True, select=select,
                                  interpret=True)
    np.testing.assert_allclose(via_mha, want, atol=2e-5, rtol=2e-5)
    # the last query reads 8 keys, not its 32: not the plain causal result
    plain = attention.attention_context(q, k, v, causal=True, mask=None,
                                        dtype=jnp.float32, use_flash=False)
    assert float(jnp.abs(plain - want).max()) > 1e-2


@pytest.mark.parametrize("kwargs,match", [
    (dict(causal=False), "causal"),
    (dict(causal=True, window=8), "window"),
    (dict(causal=True, use_ring=True), "ring"),
    (dict(causal=True, mask=jnp.ones((2, 32), bool)), "padding mask"),
])
def test_a_selection_excludes(kwargs, match):
    q, k, v, qi, ki, wi = _inputs(jnp.float32, s=32, kv_heads=4)
    select = (qi, ki, wi, jnp.zeros((2, 32)))
    kwargs.setdefault("mask", None)
    with pytest.raises(ValueError, match=match):
        attention.attention_context(q, k, v, dtype=jnp.float32,
                                    select=select, **kwargs)


def _pallas_calls(jaxpr):
    return [(eqn.params["name"], len(eqn.invars), len(eqn.outvars))
            for eqn in _pallas_eqns(jaxpr)]


@pytest.mark.parametrize("s,window,calls", [
    # resident forward and the one backward kernel: q, k, v -> out, lse;
    # q, k, v, dO, lse, delta -> dq, dk, dv
    (64, None, {("flash_fwd_resident", 3, 2), ("flash_bwd", 6, 3)}),
    (64, 16, {("flash_fwd_resident", 3, 2), ("flash_bwd", 6, 3)}),
])
def test_without_a_selection_the_band_kernels_take_what_they_took(
        s, window, calls):
    """A later edit that threads a dummy selection through the plain path
    changes these counts."""
    q, k, v, _, _, _ = _inputs(jnp.float32, s=s)

    def f(q, k, v):
        return attention.attention_context(
            q, k, v, causal=True, mask=None, dtype=jnp.float32,
            use_flash=True, window=window).sum()

    found = _pallas_calls(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(
        q, k, v).jaxpr)
    assert set(found) == calls


def test_with_a_selection_the_kernels_carry_names_of_their_own():
    q, k, v, qi, ki, wi = _inputs(jnp.float32, s=32)
    tau = jnp.zeros((2, 32))

    def f(q, k, v, qi, ki, wi):
        out, kl, _ = attention.selected_attention(
            q, k, v, (qi, ki, wi, tau), dtype=jnp.float32, use_flash=True)
        return out.sum() + kl.sum()

    found = _pallas_calls(jax.make_jaxpr(jax.grad(f, range(6)))(
        q, k, v, qi, ki, wi).jaxpr)
    # the forward takes q, k, v and the packed kept set — no indexer
    # operand, no threshold; the KL and the backward rebuild the scores
    assert set(found) == {(sa.FWD_NAME, 4, 3), (sa.KL_NAME, 8, 1),
                          (sa.BWD_NAME, 12, 6)}
