"""Attention over a learned selection of keys (`ops/sparse_attention.py`)
and its place in the one dispatch (`ops/attention.py`):

- the thresholds: the counting kernel against `lax.top_k`, bit for bit, on
  scores that are exact in any order of summation, forced ties included;
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


# -- the selection kernels against the dense masked path ---------------------

def _both(dtype, s, topk, block, heads=4, kv_heads=2):
    q, k, v, qi, ki, wi = _inputs(dtype, s=s, heads=heads,
                                  kv_heads=kv_heads)
    tau = _tau_between(qi, ki, wi, topk)

    def run(fn, **kw):
        def f(q, k, v, qi, ki, wi):
            out, kl, kept = fn(q, k, v, (qi, ki, wi, tau), **kw)
            return (jnp.sum(jnp.sin(out.astype(jnp.float32)))
                    + 3.0 * kl.sum()), (out, kl, kept)
        return jax.value_and_grad(f, argnums=range(6), has_aux=True)(
            q, k, v, qi, ki, wi)

    return (run(sa.select_attend, interpret=True, block=block),
            run(sa.dense_select_attend), tau)


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
    """The forward rule names what it leaves the backward (out, lse, the
    indexer's lse): under a policy that saves the names the recomputation
    holds no forward kernel, under one without them it holds a second; and
    either way the gradients are bit for bit those of no remat at all."""
    q, k, v, qi, ki, wi = _inputs(jnp.float32, s=32)
    tau = _tau_between(qi, ki, wi, 8)

    def layer(q, k, v, qi, ki, wi):
        out, kl, _ = sa.select_attend(q * 1.5, k, v, (qi, ki, wi, tau),
                                      interpret=True, block=8)
        return jnp.sum(out * out) + kl.sum()

    rematted = jax.grad(jax.checkpoint(
        layer, policy=jax.checkpoint_policies.save_only_these_names(*saved)),
        range(6))
    names = pallas_call_names(
        jax.make_jaxpr(rematted)(q, k, v, qi, ki, wi).jaxpr)
    assert sorted(names) == sorted(
        [sa.FWD_NAME] * forwards + [sa.KL_NAME, sa.BWD_NAME])
    for got, want in zip(jax.jit(rematted)(q, k, v, qi, ki, wi),
                         jax.jit(jax.grad(layer, range(6)))(
                             q, k, v, qi, ki, wi)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernels_refuse_what_does_not_fit_vmem():
    assert sa.kernel_reason(16384, 1, 128, 64) is None
    assert "VMEM" in sa.kernel_reason(131072, 1, 128, 64)
    q, k, v, qi, ki, wi = _inputs(jnp.float32, s=12)
    with pytest.raises(ValueError, match="multiple of 8"):
        sa.select_attend(q, k, v, (qi, ki, wi, jnp.zeros((2, 12))),
                         interpret=True)


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


def _pallas_calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"],
                          len(eqn.invars), len(eqn.outvars)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, found)
    return found


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
        q, k, v).jaxpr, [])
    assert set(found) == calls


def test_with_a_selection_the_kernels_carry_names_of_their_own():
    q, k, v, qi, ki, wi = _inputs(jnp.float32, s=32)
    tau = jnp.zeros((2, 32))

    def f(q, k, v, qi, ki, wi):
        out, kl, _ = attention.selected_attention(
            q, k, v, (qi, ki, wi, tau), dtype=jnp.float32, use_flash=True)
        return out.sum() + kl.sum()

    found = _pallas_calls(jax.make_jaxpr(jax.grad(f, range(6)))(
        q, k, v, qi, ki, wi).jaxpr, [])
    assert {n for n, _, _ in found} == {sa.FWD_NAME, sa.KL_NAME, sa.BWD_NAME}
