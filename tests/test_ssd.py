"""Mamba-2's state-space duality scan (`ops/ssd.py`): the chunked form, plain
and in the two Pallas kernels (interpreter), against the recurrence run token
by token — forward and every cotangent, at a chunk boundary, where a chunk
forgets, and with the heads of one group sharing B and C (a group of one
head and one group of all heads among the cases); what a layer under remat
saves; that nothing of a chunk's local algebra exists outside the kernels;
`causal_conv`'s bias; the ungated experts of `parallel/moe.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxpr_kernels import equations_outside_kernels, pallas_call_names

from edl_tpu.ops import gated_delta, ssd
from edl_tpu.ops.grouped_matmul import tile_of
from edl_tpu.parallel import moe

HI = jax.lax.Precision.HIGHEST

#: heads, their width, groups, state size; tokens a chunk in these tests
H, P, G, N = 4, 8, 2, 16
CHUNK = 16
#: (tokens, groups): less than a chunk (the trainer's dummy), one chunk,
#: several, no whole number; a group of ONE head, one group of ALL heads
SHAPES = [(8, G), (16, G), (48, G), (50, G), (48, H), (48, 1)]
#: what the cotangents are taken of: all six operands; and, over exactly two
#: chunks, the steps and A alone (the hand-written cotangent of gamma)
EVERY = tuple(range(6))
COTANGENTS = [shape + (EVERY,) for shape in SHAPES] + [(32, G, (1, 2))]


# -- (a) the scan against its recurrence --------------------------------------

def _recurrence(x, dt, a_log, b, c, d):
    """Token by token, float32 at the highest precision: S_t = exp(dt_t A)
    S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t."""
    r = x.shape[2] // b.shape[2]
    b, c = (jnp.repeat(y, r, axis=2) for y in (b, c))
    a = -jnp.exp(a_log)

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = state * jnp.exp(dt_t * a)[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", x_t * dt_t[..., None], b_t, precision=HI)
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HI)

    s0 = jnp.zeros((x.shape[0], x.shape[2], x.shape[3], b.shape[-1]))
    _, y = jax.lax.scan(token, s0, tuple(jnp.moveaxis(z, 1, 0)
                                         for z in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


def _inputs(s, groups=G, state=N):
    """Steps from 0.001 to 0.4 and A from -1 to -4: a head forgets a chunk
    of 16 as far as exp(-25) and another remembers a thousand tokens."""
    ks = jax.random.split(jax.random.PRNGKey(s), 7)
    x = jax.random.normal(ks[0], (2, s, H, P))
    dt = jnp.exp(jax.random.uniform(ks[1], (2, s, H), minval=-7.0,
                                    maxval=-0.9))
    a_log = jnp.log(1.0 + jnp.arange(H, dtype=jnp.float32))
    b = jax.random.normal(ks[2], (2, s, groups, state))
    c = jax.random.normal(ks[3], (2, s, groups, state))
    d = 1.0 + 0.1 * jax.random.normal(ks[4], (H,))
    return (x, dt, a_log, b, c, d), jax.random.normal(ks[5], (2, s, H, P))


def _scan(use_kernel):
    return lambda *a: ssd.ssd_scan(*a, chunk=CHUNK, use_kernel=use_kernel)


@pytest.mark.parametrize("s,groups", SHAPES)
@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_forward_matches_the_recurrence(s, groups, path):
    args, _ = _inputs(s, groups)
    want = _recurrence(*args)
    got, stats = _scan(path == "kernels")(*args)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
    logs = np.asarray(args[1] * -jnp.exp(args[2]))
    pad = -s % CHUNK
    sums = np.pad(logs, ((0, 0), (0, pad), (0, 0))).reshape(
        2, -1, CHUNK, H).sum(axis=2)
    np.testing.assert_allclose(stats["chunk_log_decay_min"], sums.min(),
                               rtol=1e-5)
    assert 0.0 < float(stats["state_absmax"]) < 10.0


@pytest.mark.parametrize("s,groups,of", COTANGENTS)
@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_every_cotangent_matches_the_recurrence(s, groups, of, path):
    args, w = _inputs(s, groups)
    want = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * w),
                    argnums=of)(*args)
    got = jax.grad(lambda *a: jnp.sum(_scan(path == "kernels")(*a)[0] * w),
                   argnums=of)(*args)
    assert [g.shape for g in got] == [args[i].shape for i in of]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()),
                                   rtol=2e-3)


def test_the_state_crosses_a_chunk_boundary():
    """One write in the first chunk, read in the third: with x zero but at
    token 3, y at token 40 is exp(sum of dt A between) dt_3 (B_3 . C_40)
    x_3, two boundaries later."""
    (x, dt, a_log, b, c, d), _ = _inputs(48)
    x = jnp.zeros_like(x).at[:, 3].set(x[:, 3])
    d = jnp.zeros_like(d)
    a = -jnp.exp(a_log)
    decay = jnp.exp(jnp.sum(dt[:, 4:41] * a, axis=1))          # [2, H]
    bc = jnp.repeat(jnp.sum(b[:, 3] * c[:, 40], axis=-1), H // G, axis=1)
    want = (decay * dt[:, 3] * bc)[..., None] * x[:, 3]
    for use_kernel in (False, True):
        got, _ = _scan(use_kernel)(x, dt, a_log, b, c, d)
        np.testing.assert_allclose(got[:, 40], want, atol=1e-6, rtol=2e-4)
        # with the state reset at every chunk nothing arrives
        assert float(jnp.abs(got[:, 40]).max()) > 1e-4


def test_heads_of_a_group_share_b_and_c():
    """Heads 0, 1 read group 0's B and C, heads 2, 3 group 1's: group 1's
    B moves heads 2 and 3 alone, and its cotangent is the sum of theirs."""
    (x, dt, a_log, b, c, d), w = _inputs(48)
    other = b.at[:, :, 1].add(1.0)
    for use_kernel in (False, True):
        y0, _ = _scan(use_kernel)(x, dt, a_log, b, c, d)
        y1, _ = _scan(use_kernel)(x, dt, a_log, other, c, d)
        np.testing.assert_array_equal(y0[:, :, :2], y1[:, :, :2])
        assert float(jnp.abs(y0[:, :, 2:] - y1[:, :, 2:]).max()) > 1e-2
        by_head = lambda hs: jax.grad(lambda b: jnp.sum(
            (_scan(use_kernel)(x, dt, a_log, b, c, d)[0] * w)[:, :, hs]))(b)
        whole = by_head(slice(0, 4))
        np.testing.assert_allclose(
            whole[:, :, 1], (by_head(slice(2, 3)) + by_head(slice(3, 4)))[
                :, :, 1], atol=1e-5, rtol=1e-4)
        assert float(jnp.abs(by_head(slice(2, 4))[:, :, 0]).max()) == 0.0


def test_a_chunk_that_forgets_does_not_overflow():
    """Steps of 3 under A = -4: a chunk's cumulative log decay reaches -192,
    where exp(-gamma) is infinite in float32; every decay the scan takes is
    exp of a difference that is never positive."""
    (x, dt, a_log, b, c, d), w = _inputs(32)
    dt = jnp.full_like(dt, 3.0)
    want = _recurrence(x, dt, a_log, b, c, d)
    for use_kernel in (False, True):
        got, stats = _scan(use_kernel)(x, dt, a_log, b, c, d)
        assert float(stats["chunk_log_decay_min"]) == pytest.approx(-192.0)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
        grads = jax.grad(lambda *a: jnp.sum(_scan(use_kernel)(*a)[0] * w),
                         argnums=range(6))(x, dt, a_log, b, c, d)
        assert all(bool(jnp.isfinite(g).all()) for g in grads)


def test_kernels_take_bfloat16_and_keep_a_float32_state():
    (x, dt, a_log, b, c, d), _ = _inputs(48)
    want = _recurrence(x, dt, a_log, b, c, d)
    low = lambda y: y.astype(jnp.bfloat16)
    for use_kernel in (False, True):
        got, stats = _scan(use_kernel)(low(x), dt, a_log, low(b), low(c), d)
        assert got.dtype == jnp.bfloat16
        assert stats["state_absmax"].dtype == jnp.float32
        err = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                    / jnp.linalg.norm(want))
        assert err < 0.02, err


def test_the_default_chunk_is_the_published_one_and_shapes_are_checked():
    assert ssd.CHUNK == 128
    (x, dt, a_log, b, c, d), _ = _inputs(8)
    got, _ = ssd.ssd_scan(x, dt, a_log, b, c, d)    # one padded chunk of 128
    np.testing.assert_allclose(got, _recurrence(x, dt, a_log, b, c, d),
                               atol=2e-5, rtol=2e-4)
    for bad in ((x, dt, a_log, b[:, :, :1].repeat(3, 2), c, d),
                (x, dt[..., :2], a_log, b, c, d),
                (x, dt, a_log, b, c[..., :4], d)):
        with pytest.raises(ValueError, match="ssd scan"):
            ssd.ssd_scan(*bad)


@pytest.mark.parametrize("saved,forwards", [(ssd.SAVED_UNDER_REMAT, 1),
                                            ((), 2)])
def test_remat_that_saves_the_residuals_runs_the_forward_once(saved,
                                                              forwards):
    args, w = _inputs(48)

    def loss(*a):
        run = jax.checkpoint(
            lambda *a: _scan(True)(*a)[0],
            policy=jax.checkpoint_policies.save_only_these_names(*saved))
        return jnp.sum(run(*a) * w)

    names = pallas_call_names(jax.make_jaxpr(
        jax.grad(loss, argnums=range(6)))(*args).jaxpr)
    assert names.count(ssd.FWD_NAME) == forwards
    assert names.count(ssd.BWD_NAME) == 1


def test_the_kernels_read_b_by_group():
    """Nothing of a chunk's local algebra exists outside the kernels, forward
    or backward: both read x's own array (leading size: the batch's) beside
    B, C and the steps by GROUP (batch x groups), and no array of the
    gradient's program — an operand or a result of either kernel or anything
    round them — is a [chunk, chunk] plane a (head, chunk) or a copy of B or
    C a head (the largest arrays are x's and the chunk-end states')."""
    state = 24                          # no other size of these tests
    args, w = _inputs(48, state=state)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda *a: jnp.sum(_scan(True)(*a)[0] * w), argnums=range(6)))(
            *args).jaxpr

    def arrays(jaxpr):
        """(equation, shape) of every array, a kernel's own body left out."""
        return [(eqn, tuple(getattr(v.aval, "shape", ())))
                for eqn in equations_outside_kernels(jaxpr)
                for v in eqn.invars + eqn.outvars]

    seen = arrays(jaxpr)
    calls = {id(eqn): eqn for eqn, _ in seen
             if eqn.primitive.name == "pallas_call"}.values()
    assert sorted(eqn.params["name"] for eqn in calls) == [ssd.BWD_NAME,
                                                           ssd.FWD_NAME]
    for eqn in calls:
        leading = {v.aval.shape[0] for v in eqn.invars + eqn.outvars}
        assert leading == {2, 2 * G}, (eqn.params["name"], leading)
    a_head = 2 * H * 48 * min(CHUNK, state)
    for eqn, shape in seen:
        assert shape[-2:] != (CHUNK, CHUNK), (eqn.primitive.name, shape)
        assert int(np.prod(shape)) < a_head, (eqn.primitive.name, shape)
    # the plain path is what this looks for: M and exp(gamma) * C a head
    plain = [shape for _, shape in arrays(jax.make_jaxpr(
        lambda *a: _scan(False)(*a)[0])(*args).jaxpr)]
    assert (2, H, 3, CHUNK, CHUNK) in plain and (2, H, 3, CHUNK,
                                                 state) in plain


# -- (b) the convolution's bias -----------------------------------------------

def test_causal_conv_adds_its_bias_to_every_token():
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))
    plain = gated_delta.causal_conv(u, w)
    np.testing.assert_array_equal(gated_delta.causal_conv(u, w, None), plain)
    np.testing.assert_allclose(gated_delta.causal_conv(u, w, bias),
                               plain + bias, atol=1e-6)
    # the first token sees zeros before it and the bias all the same
    np.testing.assert_allclose(
        gated_delta.causal_conv(u, w, bias)[:, 0], u[:, 0] * w[:, 3] + bias,
        atol=1e-6)


# -- (c) ungated experts ------------------------------------------------------

def _experts(seed=0, t=96, d=16, f=24, experts=8, k=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(ks[0], (t, d))
    router = jax.random.normal(ks[1], (d, experts)) * 0.5
    w_up = jax.random.normal(ks[2], (experts, d, f)) * 0.3
    w_down = jax.random.normal(ks[3], (experts, f, d)) * 0.3
    idx, p = moe.route_top_k(u, router, k)
    return u, idx, p, w_up, w_down


def _dense_ungated(u, idx, p, w_up, w_down, first, held):
    out = jnp.zeros_like(u)
    for e in range(held):
        weight = jnp.sum(jnp.where(idx == first + e, p, 0.0), axis=-1)
        hid = jnp.square(jax.nn.relu(jnp.dot(u, w_up[e], precision=HI)))
        out = out + weight[:, None] * jnp.dot(hid, w_down[e], precision=HI)
    return out


def test_relu2_is_the_squared_relu():
    x = jnp.linspace(-2.0, 2.0, 9)
    np.testing.assert_allclose(moe.EXPERT_ACTIVATIONS["relu2"](x),
                               jnp.where(x > 0, x * x, 0.0))
    assert set(moe.EXPERT_ACTIVATIONS) == {"relu", "silu", "relu2"}


@pytest.mark.parametrize("first,held", [(0, 8), (2, 3)])
def test_ungated_held_experts_match_the_dense_sum(first, held):
    """Two matrices an expert: result and the gradients of u, p and both
    matrices against a dense masked sum."""
    u, idx, p, w_up, w_down = _experts()
    es = slice(first, first + held)

    def ours(u, p, w_up, w_down):
        return moe.held_experts_ffn(u, idx, p, w_up[es], w_down[es], first,
                                    tm=32, activation="relu2",
                                    gated=False)[0]

    def dense(u, p, w_up, w_down):
        return _dense_ungated(u, idx, p, w_up[es], w_down[es], first, held)

    args = (u, p, w_up, w_down)
    np.testing.assert_allclose(ours(*args), dense(*args), atol=2e-5,
                               rtol=2e-4)
    w = jax.random.normal(jax.random.PRNGKey(9), u.shape)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), argnums=range(4))(*args)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), argnums=range(4))(
        *args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()),
                                   rtol=2e-3)


def test_an_ungated_expert_makes_no_gate_s_product():
    """One `moe_gmm` up and one down forward, their two dx and two dw
    products backward — a gated expert's counts at two thirds the columns:
    nothing stands in for the gate the model does not have."""
    u, idx, p, w_up, w_down = _experts()

    def grad_calls(gated, w_first):
        loss = lambda u, a, b: jnp.sum(moe.held_experts_ffn(
            u, idx, p, a, b, 0, tm=32, gated=gated,
            activation="relu2" if not gated else "relu")[0])
        return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
            u, w_first, w_down).jaxpr

    def shapes(jaxpr):
        out = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append((eqn.params["name"],
                            eqn.outvars[0].aval.shape[-1]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                out += shapes(sub)
        return out

    ungated = shapes(grad_calls(False, w_up))
    gated = shapes(grad_calls(True, jnp.concatenate([w_up, w_up], axis=-1)))
    assert sorted(n for n, _ in ungated) == sorted(n for n, _ in gated) == [
        "moe_gmm"] * 4 + ["moe_tgmm"] * 2
    f = w_up.shape[-1]
    assert sorted(w for _, w in ungated) == sorted([f, 16, f, 16, f, 16])
    assert 2 * f in [w for _, w in gated]


def test_ungated_dense_and_shared_forms_are_one_arithmetic():
    u, _, _, w_up, w_down = _experts()
    want = jnp.dot(jnp.square(jax.nn.relu(jnp.dot(u, w_up[0], precision=HI))),
                   w_down[0], precision=HI)
    for got in (moe.dense_ffn(u, w_up[0], w_down[0], activation="relu2",
                              gated=False),
                moe.shared_expert_ffn(u, w_up[0], w_down[0], None,
                                      activation="relu2", gated=False)):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
    gate = jax.random.normal(jax.random.PRNGKey(5), (u.shape[1],))
    gated = moe.shared_expert_ffn(u, w_up[0], w_down[0], gate,
                                  activation="relu2", gated=False)
    np.testing.assert_allclose(
        gated, want * jax.nn.sigmoid(jnp.dot(u, gate))[:, None], atol=2e-5,
        rtol=2e-4)


def test_ungated_experts_save_their_up_product_under_a_name_of_its_own():
    assert moe.SAVED_UNDER_REMAT == ("moe.choice", "moe.gate_up", "moe.down",
                                     "moe.up")
    u, idx, p, w_up, w_down = _experts()

    def forward_products(saved):
        run = jax.checkpoint(
            lambda u, a, b: moe.held_experts_ffn(
                u, idx, p, a, b, 0, tm=32, activation="relu2",
                gated=False)[0],
            policy=jax.checkpoint_policies.save_only_these_names(*saved))
        names = pallas_call_names(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(run(*a)), argnums=(0, 1, 2)))(
                u, w_up, w_down).jaxpr)
        return names.count("moe_gmm") - 2       # less the two dx products

    assert forward_products(moe.SAVED_UNDER_REMAT) == 2
    assert forward_products(("moe.choice", "moe.gate_up")) == 4


@pytest.mark.parametrize("k,n,want", [
    (2688, 1856, (384, 1856)), (1856, 2688, (928, 896)),
    (2048, 1536, (1024, 768)), (768, 2048, (768, 1024)),
    (2560, 1536, (640, 768)), (512, 2048, (512, 1024))])
def test_tgmm_tiles_a_width_that_no_multiple_of_128_divides(k, n, want):
    """`moe_tgmm`'s result block [tk, tn] float32: 1856 = 64 x 29 spans its
    axis as lanes and is cut by 16 as rows, and the rows are cut finer
    where the lanes stayed whole; the accepted cells' widths tile as they
    did (`tile_of` alone)."""
    from edl_tpu.ops import grouped_matmul
    x = jax.ShapeDtypeStruct((1024, k), jnp.bfloat16)
    dy = jax.ShapeDtypeStruct((1024, n), jnp.bfloat16)
    ints = lambda s: jax.ShapeDtypeStruct(s, jnp.int32)
    jaxpr = jax.make_jaxpr(lambda x, dy, tg, nu: grouped_matmul._tgmm(
        x, dy, tg, nu, 2, 512, True))(x, dy, ints((2,)), ints((1,))).jaxpr
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    block = call.params["grid_mapping"].block_mappings[-1].block_shape
    assert tuple(int(getattr(b, "block_size", b)) for b in block)[1:] == want
    assert want[0] * want[1] * 4 <= grouped_matmul._TGMM_OUT_BYTES
    if k % 128 == 0 and n % 128 == 0:
        assert want == (tile_of(k), tile_of(n))
