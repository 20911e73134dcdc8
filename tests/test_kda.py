"""The delta rule at a VECTOR decay (`ops/gated_delta.py`: Kimi Delta
Attention's, one rate a key channel) against the recurrence a token at a
time:

- the chunked rule, forward and every gradient (q, k, v, the log decay,
  beta), `lax.scan` path and the kernels in the interpreter (which form a
  chunk's operands themselves: one head, a program's group of heads, 8 and
  16 heads, exactly two chunks, a ragged tail), at decays strong enough
  that `exp(-gamma)` WOULD overflow float32 (gamma below -100 inside a
  chunk, and below -200), every result finite; the kernels against the
  plain path in bfloat16;
- the gradient's program on the kernel path holds ``kda_fwd`` and
  ``kda_bwd`` once each and nothing of the chunk-local algebra outside
  them;
- a log decay constant over the channels gives what the scalar entry gives,
  and the scalar entry still runs `gdn_*` where the vector one runs `kda_*`;
- no exponential of the rule's jaxpr is taken of a positive number;
- the pieces: a diagonal block's pairs and its hand-written cotangents, the
  (Akk, Aqk) pair against the sum as it stands.

Sizes are the smallest that cross a chunk (80 tokens in chunks of 32) and a
16-row block.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxpr_kernels import chunk_local_algebra, pallas_call_names

from edl_tpu.ops import gated_delta as gd

CHUNK = 32


@pytest.fixture(scope="module", autouse=True)
def exact_products():
    """float32 products as written, for this file's tests alone."""
    with jax.default_matmul_precision("highest"):
        yield


def token_rule(q, k, v, a, beta):
    """S_t = (I - beta k k^T) Diag(exp(a_t)) S_{t-1} + beta k v^T; o = S^T q,
    a token at a time; also the largest |S| any token left."""
    b, _, h, dk = k.shape

    def token(state, x):
        q_t, k_t, v_t, a_t, b_t = x
        state = state * jnp.exp(a_t)[..., None]
        held = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + k_t[..., None] * (
            (v_t - held) * b_t[..., None])[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, a, beta))
    _, o = jax.lax.scan(token, jnp.zeros((b, h, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def _inputs(s, strength, b=1, h=2, dk=16, dv=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    a = -strength * jax.nn.softplus(
        2.0 * jax.random.normal(ks[3], (b, s, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return (q, k, v, a, beta), jax.random.normal(ks[5], (b, s, h, dv))


#: (tokens, decay's strength, heads): the first two are the file's own; the
#: kernel path serves `KDA_HEADS` heads a program and the plain path
#: `HEAD_GROUP` at a time, so 1 head, one group of 8 and two; 64 tokens are
#: exactly two chunks, 80 leave a ragged tail; at strength 8 gamma falls
#: below -200 inside a chunk
CASES = {"mild": (80, 0.1, 2), "forgetting": (80, 4.0, 2),
         "one-head": (80, 4.0, 1), "group-of-8": (80, 0.1, 8),
         "two-groups": (80, 4.0, 16), "two-chunks": (64, 0.1, 2),
         "two-chunks-forgetting": (64, 4.0, 2),
         "gamma-under-200": (80, 8.0, 2)}


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    s, strength, h = CASES[request.param]
    args, w = _inputs(s, strength, h=h)
    want = token_rule(*args)
    grads = jax.grad(lambda *a: jnp.sum(token_rule(*a) * w),
                     argnums=range(5))(*args)
    return strength, args, w, want, grads


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["scan", "kernels"])
def test_chunked_rule_matches_the_recurrence(case, use_kernel):
    strength, args, w, want, want_grads = case
    s, h = args[0].shape[1:3]
    got, stats = gd.gated_delta_rule(*args, chunk=CHUNK,
                                     use_kernel=use_kernel)
    assert got.dtype == args[2].dtype and bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the statistic is the chunks' own: the most negative cumulative sum
    a = np.pad(np.asarray(args[3]), ((0, 0), (0, -s % CHUNK), (0, 0), (0, 0)))
    low = a.reshape(1, -1, CHUNK, h, 16).sum(axis=2).min()
    assert float(stats["chunk_log_decay_min"]) == pytest.approx(low,
                                                                rel=1e-6)
    if strength > 1:            # exp(-gamma) would be inf in float32
        assert low < (-200.0 if strength > 4 else -100.0)
    assert 0.0 < float(stats["state_absmax"]) < 10.0
    grads = jax.grad(lambda *a: jnp.sum(gd.gated_delta_rule(
        *a, chunk=CHUNK, use_kernel=use_kernel)[0] * w),
        argnums=range(5))(*args)
    for name, g, want_g in zip(("q", "k", "v", "log decay", "beta"), grads,
                               want_grads):
        assert bool(jnp.all(jnp.isfinite(g))), name
        np.testing.assert_allclose(
            g, want_g, atol=1e-5 * float(jnp.abs(want_g).max()),
            rtol=1e-4, err_msg=name)


def _distance(got, want):
    got, want = (x.astype(jnp.float32) for x in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("dtype,near", [(jnp.float32, 2e-6),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("strength", [0.1, 4.0], ids=["mild", "forgetting"])
def test_kernels_give_what_the_plain_path_gives(dtype, near, strength):
    """The kernels form what `chunk_operands_vector` forms, at its rounding
    points, and their hand-written cotangents are JAX's of the plain path:
    forward and every gradient, relative L2; in bfloat16 the two round the
    cotangents at different places, and both stay as near the float32
    recurrence."""
    (q, k, v, a, beta), w = _inputs(80, strength, h=4)
    args = tuple(x.astype(dtype) for x in (q, k, v)) + (a, beta)

    def run(use_kernel):
        def loss(*a):
            o = gd.gated_delta_rule(*a, chunk=CHUNK, use_kernel=use_kernel)[0]
            return jnp.sum(o.astype(jnp.float32) * w), o
        return jax.value_and_grad(loss, argnums=range(5), has_aux=True)(*args)

    ((_, o_k), g_k), ((_, o_p), g_p) = run(True), run(False)
    assert o_k.dtype == dtype
    assert _distance(o_k, o_p) < near
    want = jax.grad(lambda *a: jnp.sum(token_rule(*a) * w),
                    argnums=range(5))(q, k, v, a, beta)
    for name, got, plain, exact in zip(("q", "k", "v", "log decay", "beta"),
                                       g_k, g_p, want):
        assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))), name
        assert _distance(got, plain) < 10 * near, name
        assert _distance(got, exact) < max(1.25 * _distance(plain, exact),
                                           10 * near), name


def test_the_chunk_local_algebra_is_inside_the_kernels():
    """Value and gradient of the rule on the kernel path: one ``kda_fwd``,
    one ``kda_bwd``, and outside their bodies no loop, no `exp` over a
    block's [16, 16, dk] or a chunk's [chunk, chunk] pairs and no array laid
    a (head, chunk) — the operands U, Wk, Qg, Kg, A and their cotangents
    exist in VMEM alone. The plain path holds every one of them."""
    args, w = _inputs(80, 0.5, h=8)

    def traced(use_kernel):
        return jax.make_jaxpr(jax.value_and_grad(
            lambda *a: jnp.sum(gd.gated_delta_rule(
                *a, chunk=CHUNK, use_kernel=use_kernel)[0] * w),
            argnums=range(5)))(*args).jaxpr

    kernels = traced(True)
    assert sorted(pallas_call_names(kernels)) == [gd.KDA_BWD_NAME,
                                                  gd.KDA_FWD_NAME]
    assert chunk_local_algebra(kernels, CHUNK, gd.SUB, 16, 8) == []
    plain = chunk_local_algebra(traced(False), CHUNK, gd.SUB, 16, 8)
    assert pallas_call_names(traced(False)) == []
    for what in ("scan", "exp (3, 2, 16, 16, 16)", "(8, 3, 32, 32)",
                 "(8, 3, 32, 16)", "(8, 3, 32, 8)"):
        assert any(what in line for line in plain), (what, sorted(set(plain)))


def test_state_is_carried_across_chunks(case):
    """What a scan that loses its state computes is something else."""
    _, args, _, want, _ = case
    cut = lambda x: x[:, :64].reshape((2, 32) + x.shape[2:])
    reset = token_rule(*(cut(x) for x in args)).reshape(
        (1, 64) + want.shape[2:])
    assert float(jnp.abs(reset - want[:, :64]).max()) > 1e-3


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["scan", "kernels"])
def test_a_decay_constant_over_the_channels_is_the_scalar_rule(use_kernel):
    (q, k, v, a, beta), w = _inputs(80, 0.5, seed=1)
    one = a[..., 0]
    wide = jnp.broadcast_to(one[..., None], a.shape)

    def run(g):
        return gd.gated_delta_rule(q, k, v, g, beta, chunk=CHUNK,
                                   use_kernel=use_kernel)

    (o1, s1), (o2, s2) = run(one), run(wide)
    np.testing.assert_allclose(o2, o1, atol=2e-6)
    for name in s1:
        assert float(s2[name]) == pytest.approx(float(s1[name]), rel=1e-5)
    g1 = jax.grad(lambda g: jnp.sum(run(g)[0] * w))(one)
    g2 = jax.grad(lambda g: jnp.sum(run(g)[0] * w))(wide).sum(-1)
    np.testing.assert_allclose(g2, g1, atol=1e-5 * float(jnp.abs(g1).max()),
                               rtol=1e-4)


def test_each_form_runs_its_own_kernels():
    (q, k, v, a, beta), w = _inputs(80, 0.5)

    def names(g):
        return pallas_call_names(jax.make_jaxpr(jax.grad(
            lambda q: jnp.sum(gd.gated_delta_rule(
                q, k, v, g, beta, chunk=CHUNK, use_kernel=True)[0] * w)))(
                    q).jaxpr)

    assert sorted(names(a)) == [gd.KDA_BWD_NAME, gd.KDA_FWD_NAME]
    assert sorted(names(a[..., 0])) == [gd.BWD_NAME, gd.FWD_NAME]


@pytest.mark.parametrize("saved,forwards", [(gd.KDA_SAVED_UNDER_REMAT, 1),
                                            ((), 2)])
def test_remat_that_saves_the_residuals_runs_kda_fwd_once(saved, forwards):
    args, w = _inputs(80, 0.5)

    def loss(*a):
        run = jax.checkpoint(
            lambda *a: gd.gated_delta_rule(*a, chunk=CHUNK,
                                           use_kernel=True)[0],
            policy=jax.checkpoint_policies.save_only_these_names(*saved))
        return jnp.sum(run(*a) * w)

    names = pallas_call_names(jax.make_jaxpr(
        jax.grad(loss, argnums=range(5)))(*args).jaxpr)
    assert names.count(gd.KDA_FWD_NAME) == forwards
    assert names.count(gd.KDA_BWD_NAME) == 1


def test_no_exponent_formed_is_positive(monkeypatch):
    """Every `exp` the rule takes at a vector decay, forward and backward —
    the diagonal blocks, both factors about the boundary, the decayed q and
    k, the chunk's decay — reads numbers <= 0, at decays where gamma falls
    below -100 inside a chunk; and the scalar form's likewise, as always."""
    (q, k, v, a, beta), w = _inputs(80, 4.0)
    assert float(gd.gated_delta_rule(q, k, v, a, beta, chunk=CHUNK)[1][
        "chunk_log_decay_min"]) < -100.0
    seen = []
    exp = jnp.exp

    def watched(x):
        jax.debug.callback(lambda top: seen.append(float(top)), jnp.max(
            jnp.where(jnp.isfinite(x), x, -jnp.inf)))
        return exp(x)

    monkeypatch.setattr(gd.jnp, "exp", watched)
    # the vector form's kernels (the interpreter runs their `exp`s as it
    # runs any other; their jitted callers are traced anew, and once more
    # after, without the watch), then both forms' plain paths
    kernels = (gd._kda_forward, gd._kda_backward)
    try:
        for g, use_kernel in ((a, True), (a, False), (a[..., 0], False)):
            del seen[:]
            for f in kernels:
                f.clear_cache()
            grads = jax.grad(lambda *args: jnp.sum(gd.gated_delta_rule(
                *args, chunk=CHUNK, use_kernel=use_kernel)[0] * w),
                argnums=range(5))(q, k, v, g, beta)
            jax.block_until_ready(grads)
            jax.effects_barrier()
            assert len(seen) >= (30 if use_kernel else 5)
            assert max(seen) <= 0.0
    finally:
        for f in kernels:
            f.clear_cache()


def _pairs_as_they_stand(x, y, gam):
    diff = gam[..., :, None, :] - gam[..., None, :, :]
    c = gam.shape[-2]
    lower = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    # float64-free but safe at these mild decays: the difference is small
    return jnp.where(lower, jnp.sum(
        x[..., :, None, :] * y[..., None, :, :] * jnp.exp(
            jnp.where(lower[..., None], diff, 0.0)), axis=-1), 0.0)


def test_decayed_pairs_are_the_sum_as_it_stands():
    (q, k, _, a, _), _ = _inputs(64, 0.3, seed=2)
    lay = lambda x: jnp.moveaxis(x, 2, 1)           # [b, h, 64, dk]
    q, k, gam = lay(q), lay(k), jnp.cumsum(lay(a), axis=-2)
    akk, aqk = gd._decayed_pairs(q, k, gam, gd.SUB)
    np.testing.assert_allclose(akk, _pairs_as_they_stand(k, k, gam),
                               atol=2e-6)
    np.testing.assert_allclose(aqk, _pairs_as_they_stand(q, k, gam),
                               atol=2e-6)


def test_block_pairs_cotangents_are_jax_s_own():
    """`_block_pairs` writes its backward by hand (it forms the
    exponentials again): against JAX's differentiation of the same sum."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (3, 2, 16, 8))
    y = jax.random.normal(ks[1], (3, 16, 8))
    gam = -jnp.cumsum(jax.nn.softplus(jax.random.normal(ks[2], (3, 16, 8))),
                      axis=-2)
    w = jax.random.normal(ks[3], (3, 2, 16, 16))
    plain = lambda x, y, gam: jnp.sum(x[..., :, None, :] * gd._block_decays(
        gam, y), axis=-1)
    np.testing.assert_allclose(gd._block_pairs(x, y, gam), plain(x, y, gam),
                               atol=1e-6)
    got = jax.grad(lambda *a: jnp.sum(gd._block_pairs(*a) * w),
                   argnums=(0, 1, 2))(x, y, gam)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * w),
                    argnums=(0, 1, 2))(x, y, gam)
    for g, want_g in zip(got, want):
        np.testing.assert_allclose(g, want_g, atol=2e-5, rtol=1e-4)


def test_entry_refuses_shapes_it_cannot_take():
    (q, k, v, a, beta), _ = _inputs(32, 0.5)
    with pytest.raises(ValueError, match="gated delta rule"):
        gd.gated_delta_rule(q, k, v, a[..., :8], beta)
    with pytest.raises(ValueError, match="gated delta rule"):
        gd.gated_delta_rule(q, k, v, a, beta[..., None])
    with pytest.raises(ValueError, match="gated delta rule"):
        gd.gated_delta_rule(q, k, v, a, beta, chunk=24)
