"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): a layer whose
memory is one [dk, dv] float32 matrix a value head, written and read a token
at a time,

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,        alpha_t = exp(g_t) in (0, 1], S_0 = 0

— a recurrence, not a (query, key) mask, so none of the attention dispatch's
paths can take it. :func:`gated_delta_rule` is its one entry, as
``attention_context`` is attention's. It runs the recurrence in CHUNKS of
``CHUNK`` tokens with the state carried between them. With gamma the
cumulative sum of g inside a chunk, D[i, j] = exp(gamma_i - gamma_j) for
i >= j (every decay is taken in this form, never ``exp(-gamma)``, which
overflows where a chunk forgets: the ``chunk_log_decay_min`` the entry
returns says how far below zero a chunk's gamma went) and S the state that
enters the chunk:

    T  = (I + strict_lower(diag(beta) (D * K K^T)))^-1      [C, C]
    U  = T (beta * V)            Wk = T (beta * exp(gamma) * K)
    W  = U - Wk S                the chunk's writes, each less what the
                                 state and the chunk's earlier writes held
    O  = (exp(gamma) * Q) S + lower(D * Q K^T) W
    S' = exp(gamma_C) S + (exp(gamma_C - gamma) * K)^T W

What is local to a chunk (T, U, Wk, the two decayed copies of q and k, the
[C, C] matrix in front of W) is plain ``jax.numpy`` in every path and is
differentiated by JAX. What is sequential — W, O and S' from S, chunk after
chunk, and in reverse for the gradient — is, on a TPU, two Pallas kernels:

- ``gdn_fwd``: one program a (value head, chunk), the state in VMEM in
  float32 across the chunks of a head; writes the result and every
  chunk-end state;
- ``gdn_bwd``: the same chunks from the last to the first, the state's
  cotangent in VMEM; W is rebuilt from the saved state that entered the
  chunk.

bf16 operands into the MXU (the operands as they arrive), float32
accumulation, float32 state and decays. Elsewhere (the CPU) the sequential
part is a ``lax.scan`` over the chunks in ``jax.numpy``, differentiated by
JAX; ``use_kernel=True`` forces the kernels (on the CPU in the Pallas
interpreter, for tests).

THE VECTOR FORM (Kimi Delta Attention, arXiv:2510.26692): the log decay
comes [b, s, h, dk], one rate a KEY CHANNEL,

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T

— Diag(alpha_t) scales the rows of S; the same order as above, decay then
delta. gamma is then a [C, dk] matrix a chunk, non-increasing down every
column, and the decay no longer comes out of the products: with

    Akk[i, j] = sum_d k_id k_jd exp(gamma_id - gamma_jd)
    Aqk[i, j] = sum_d q_id k_jd exp(gamma_id - gamma_jd)

in place of D * K K^T and D * Q K^T, exp(gamma) * K and exp(gamma_C -
gamma) * K taken a channel at a time and S' = Diag(exp(gamma_C)) S + ...,
the algebra above stands. EVERY EXPONENT FORMED IS <= 0 (the obvious
factoring (q * exp(gamma)) (k * exp(-gamma))^T overflows where a chunk
forgets): a chunk is cut into blocks of ``SUB`` rows; a diagonal block is
the sum over d as it stands, masked to i >= j (:func:`_block_pairs`, a head
at a time); the blocks left of the diagonal in block row I are ONE product
of ``x_i * exp(gamma_i - gamma_r)`` with ``k_j * exp(gamma_r - gamma_j)``
about the boundary r = the FIRST ROW OF BLOCK I (j < r <= i, so both
exponents are <= 0; a factor that underflows belongs to a pair whose true
weight is below float32 too). These operands are made ``HEAD_GROUP`` heads at
a time under a checkpoint (:func:`_in_groups`). The sequential part is the
kernels ``kda_fwd`` and ``kda_bwd``, the scalar pair's grid and walk with
the state held TRANSPOSED, [dv, dk]: a chunk's decay exp(gamma_C) is then
one row of dk lanes and scales the state lane by lane, where a [dk, 1]
column a chunk would pad to 128 lanes in HBM. The state is carried in
float32 in VMEM; the chunk-end copies the backward reads leave ``kda_fwd``
in the operands' dtype (the backward takes them as operands of the MXU, and
for the decay's cotangent): in bfloat16 they are 134 MB a layer at 8192
tokens of 32 heads, not 268. So the two pairs share their grid, their specs
and their custom_vjp, not their bodies: the scalar kernels lower as they
did.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops import flash_attention as fa

#: the kernels' names in a device trace
FWD_NAME = "gdn_fwd"
BWD_NAME = "gdn_bwd"
#: and the vector form's
KDA_FWD_NAME = "kda_fwd"
KDA_BWD_NAME = "kda_bwd"

#: `checkpoint_name`s of what ``gdn_fwd`` leaves: its result and the
#: chunk-end states. A layer rematerialised under a policy that saves them
#: runs the forward kernel once a step; at 16384 tokens of 16 value heads
#: of 128 x 128 in chunks of 64 they are 67 MB (bfloat16) and 268 MB
#: (float32) a layer
SAVED_UNDER_REMAT = ("gdn.out", "gdn.states")
#: the vector form's, under the same policy: at 8192 tokens of 32 heads of
#: 128 x 128 they are 67 MB and (in the operands' bfloat16) 134 MB a layer
KDA_SAVED_UNDER_REMAT = ("kda.out", "kda.states")

#: tokens a chunk (the published code's)
CHUNK = 64
#: rows a block of the vector form's [chunk, chunk] matrices
SUB = 16
#: heads whose chunk-local operands the vector form makes at a time
HEAD_GROUP = 8

#: [..., i, j] @ [..., j, k] in float32 at the highest precision: the small
#: [chunk, chunk] products of the inverse
_mm = functools.partial(jnp.einsum, "...ij,...jk->...ik",
                        precision=lax.Precision.HIGHEST)


def causal_conv(u, w, bias=None):
    """Causal depthwise convolution over the sequence: u [b, s, c],
    w [c, width] -> out[t] = sum_j w[:, j] * u[t - (width - 1) + j] (+
    ``bias`` [c], where the layer has one), u zero before the sequence
    starts. Float32 sums, float32 out (what follows, an activation, rounds
    once)."""
    width = w.shape[1]
    s = u.shape[1]
    padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    out = sum(padded[:, j:j + s] * w[:, j] for j in range(width))
    return out if bias is None else out + bias.astype(jnp.float32)


@jax.custom_vjp
def unit_lower_inverse(l):
    """(I + l)^-1 for STRICTLY lower triangular l [..., c, c] float32: l is
    nilpotent, so the inverse is the finite product (I + x)(I + x^2)(I +
    x^4) ... with x = -l — matrix products alone, no substitution loop."""
    c = l.shape[-1]
    eye = jnp.eye(c, dtype=l.dtype)
    power = -l
    inv = eye + power
    reach = 2
    while reach < c:
        power = _mm(power, power)
        inv = _mm(inv, eye + power)
        reach *= 2
    return inv


def _inverse_fwd(l):
    inv = unit_lower_inverse(l)
    return inv, inv


def _inverse_bwd(inv, g):
    t = jnp.swapaxes(inv, -1, -2)
    return (-_mm(_mm(t, g), t),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def chunk_operands(q, k, v, g, beta):
    """What is local to a chunk, from q, k [B, n, C, dk], v [B, n, C, dv]
    (one dtype), g, beta [B, n, C] float32: (U, Wk, Qg, Kg, A, decay) of the
    module docstring — U [B, n, C, dv], Wk, Qg, Kg [B, n, C, dk], A [B, n,
    C, C] in the inputs' dtype, decay = exp(gamma_C) [B, n] float32."""
    dt = v.dtype
    c = g.shape[-1]
    f32 = jnp.float32
    gam = jnp.cumsum(g, axis=-1)
    last = gam[..., -1:]
    idx = jnp.arange(c)
    lower = idx[:, None] >= idx[None, :]
    # exp of a difference that is never positive: zero above the diagonal
    decays = jnp.exp(jnp.where(lower, gam[..., :, None] - gam[..., None, :],
                               -jnp.inf))
    pair = functools.partial(jnp.einsum, "bnik,bnjk->bnij",
                             preferred_element_type=f32)
    inside = jnp.where(idx[:, None] > idx[None, :],
                       beta[..., None] * decays * pair(k, k), 0.0)
    t = unit_lower_inverse(inside).astype(dt)
    mix = functools.partial(jnp.einsum, "bnij,bnjd->bnid",
                            preferred_element_type=f32)
    k32 = k.astype(f32)
    u = mix(t, (v.astype(f32) * beta[..., None]).astype(dt))
    wk = mix(t, (k32 * (beta * jnp.exp(gam))[..., None]).astype(dt))
    a = decays * pair(q, k)
    qg = q.astype(f32) * jnp.exp(gam)[..., None]
    kg = k32 * jnp.exp(last - gam)[..., None]
    return tuple(x.astype(dt) for x in (u, wk, qg, kg, a)) + (
        jnp.exp(last[..., 0]),)


@jax.custom_vjp
def _block_pairs(x, y, gam):
    """A diagonal block's pairs at a vector decay, the sum over d as it
    stands: x [..., r, m, dk], y, gam [..., m, dk] float32 -> [..., r, m, m],
    out[i, j] = sum_d x_id y_jd exp(gam_id - gam_jd) for i >= j, 0 above
    (at least one leading axis). A slice of the leading axis at a time,
    forward and backward: where XLA keeps the [m, m, dk] exponentials of a
    block, all of a layer's at once are 2 GiB at 8192 tokens of 32 heads.
    Its backward forms them again instead of keeping them."""
    return lax.map(lambda args: jnp.sum(
        args[0][..., :, None, :] * _block_decays(args[2], args[1]), axis=-1),
        (x, y, gam))


def _block_decays(gam, y):
    """y_jd exp(gam_id - gam_jd) for i >= j, 0 above: [..., 1, m, m, dk]."""
    idx = jnp.arange(gam.shape[-2])
    lower = (idx[:, None] >= idx[None, :])[..., None]
    return (y[..., None, :, :] * jnp.exp(jnp.where(
        lower, gam[..., :, None, :] - gam[..., None, :, :],
        -jnp.inf)))[..., None, :, :, :]


def _block_pairs_fwd(x, y, gam):
    return _block_pairs(x, y, gam), (x, y, gam)


def _block_pairs_bwd(res, g):
    def one(args):
        x, y, gam, g = args
        # d out[i, j] / d gam_id = x_id y_jd E_ijd = -d out[i, j] / d gam_jd
        weighted = g[..., None] * _block_decays(gam, jnp.ones_like(y))
        dx = jnp.sum(weighted * y[..., None, None, :, :], axis=-2)
        dy = jnp.sum(weighted * x[..., :, None, :], axis=(-4, -3))
        return dx, dy, jnp.sum(x * dx, axis=-3) - y * dy

    return lax.map(one, res + (g,))


_block_pairs.defvjp(_block_pairs_fwd, _block_pairs_bwd)


def _decayed_pairs(q, k, gam, sub):
    """(Akk, Aqk) of the module docstring's vector form, [B, n, C, C]
    float32, for i >= j and 0 above: q, k [B, n, C, dk] (one dtype), gam
    [B, n, C, dk] float32, in blocks of ``sub`` rows."""
    dt = q.dtype
    f32 = jnp.float32
    c, dk = gam.shape[-2:]
    m = c // sub
    lead = gam.shape[:-2]
    blocks = lambda x: x.astype(f32).reshape(lead + (m, sub, dk))
    kb, gb = blocks(k), blocks(gam)
    both = jnp.stack([kb, blocks(q)], axis=-3)     # k's rows, then q's
    own = _block_pairs(both, kb, gb)
    # left of the diagonal, about r = block I's first row: both <= 0
    edge = gb[..., :1, :]                                   # [.., m, 1, dk]
    rows = both * jnp.exp(gb - edge)[..., None, :, :]
    before = (jnp.arange(c)[None, :] < sub * jnp.arange(m)[:, None])
    cols = k.astype(f32)[..., None, :, :] * jnp.exp(jnp.where(
        before[..., None], edge - gam[..., None, :, :], -jnp.inf))
    left = jnp.einsum("...mrid,...mjd->...rmij", rows.astype(dt),
                      cols.astype(dt), preferred_element_type=f32)
    # a diagonal block laid at its place among the row's m blocks
    own = (jnp.moveaxis(own, -3, -4)[..., None, :]
           * jnp.eye(m, dtype=f32)[:, None, :, None])
    full = left.reshape(lead + (2, c, c)) + own.reshape(lead + (2, c, c))
    return full[..., 0, :, :], full[..., 1, :, :]


def chunk_operands_vector(q, k, v, g, beta):
    """`chunk_operands` at a vector decay: g [B, n, C, dk] float32, and the
    decay it returns is exp(gamma_C) [B, n, dk]."""
    dt = v.dtype
    f32 = jnp.float32
    c = g.shape[-2]
    gam = jnp.cumsum(g, axis=-2)
    last = gam[..., -1:, :]
    akk, aqk = _decayed_pairs(q, k, gam, min(SUB, c))
    idx = jnp.arange(c)
    inside = jnp.where(idx[:, None] > idx[None, :], beta[..., None] * akk,
                       0.0)
    t = unit_lower_inverse(inside).astype(dt)
    mix = functools.partial(jnp.einsum, "bnij,bnjd->bnid",
                            preferred_element_type=f32)
    k32 = k.astype(f32)
    grown = jnp.exp(gam)
    u = mix(t, (v.astype(f32) * beta[..., None]).astype(dt))
    wk = mix(t, (k32 * beta[..., None] * grown).astype(dt))
    qg = q.astype(f32) * grown
    kg = k32 * jnp.exp(last - gam)
    return tuple(x.astype(dt) for x in (u, wk, qg, kg, aqk)) + (
        jnp.exp(last[..., 0, :]),)


def _in_groups(f, args, group):
    """f(*args) -> a tuple, over slices of ``group`` of the leading axis one
    after another, each under `jax.checkpoint`: a slice's temporaries are
    gone before the next one's are made, forward and backward, and what is
    kept for the backward is the arguments. (The vector form's operands
    hold [dk]-wide decays a token where the scalar form's hold one number:
    all 32 heads' at 8192 tokens were 5.8 GiB of a layer's backward.)"""
    lead = args[0].shape[0]
    if lead <= group or lead % group:
        return f(*args)
    cut = lambda x: x.reshape((lead // group, group) + x.shape[1:])
    out = lax.map(jax.checkpoint(lambda xs: f(*xs)),
                  tuple(cut(x) for x in args))
    return tuple(x.reshape((lead,) + x.shape[2:]) for x in out)


def _scan_plain(u, wk, qg, kg, a, decay):
    """The sequential part in jax.numpy: (O [B, n, C, dv] in u's dtype,
    chunk-end states [B, n, dk, dv] float32). ``decay`` [B, n], or [B, n,
    dk] at a vector decay: the state's rows then decay each at its rate."""
    dt = u.dtype
    f32 = jnp.float32
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)

    def chunk(s, xs):
        u, wk, qg, kg, a, decay = xs
        sd = s.astype(dt)
        w = (u.astype(f32) - dot("bck,bkv->bcv", wk, sd)).astype(dt)
        o = dot("bck,bkv->bcv", qg, sd) + dot("bij,bjv->biv", a, w)
        s = (s * (decay[:, None, None] if decay.ndim == 1
                  else decay[:, :, None]) + dot("bck,bcv->bkv", kg, w))
        return s, (o.astype(dt), s)

    front = lambda x: jnp.moveaxis(x, 1, 0)
    s0 = jnp.zeros((u.shape[0], wk.shape[-1], u.shape[-1]), f32)
    _, (o, states) = lax.scan(chunk, s0, tuple(front(x) for x in (
        u, wk, qg, kg, a, decay)))
    return jnp.moveaxis(o, 0, 1), jnp.moveaxis(states, 0, 1)


# -- the kernels -------------------------------------------------------------

def _fwd_kernel(u_ref, wk_ref, qg_ref, kg_ref, a_ref, dec_ref, o_ref, st_ref,
                s_acc):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_acc[:] = jnp.zeros_like(s_acc)

    s = s_acc[:]
    dt = u_ref.dtype
    sd = s.astype(dt)
    w = (u_ref[0].astype(jnp.float32)
         - fa._dot(wk_ref[0], sd, fa._NN)).astype(dt)
    o_ref[0] = (fa._dot(qg_ref[0], sd, fa._NN)
                + fa._dot(a_ref[0], w, fa._NN)).astype(o_ref.dtype)
    s = s * dec_ref[0] + fa._dot(kg_ref[0], w, fa._TN)
    s_acc[:] = s
    st_ref[0] = s


def _bwd_kernel(u_ref, wk_ref, qg_ref, kg_ref, a_ref, dec_ref, st_ref,
                do_ref, du_ref, dwk_ref, dqg_ref, dkg_ref, da_ref, ddec_ref,
                ds_acc, *, n_chunks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ds_acc[:] = jnp.zeros_like(ds_acc)

    dt = u_ref.dtype
    # the state that entered this chunk: the chunk before's end, or zero
    s = jnp.where(j < n_chunks - 1, st_ref[0], 0.0)
    sd = s.astype(dt)
    ds = ds_acc[:]
    dsd = ds.astype(dt)
    do = do_ref[0]
    w = (u_ref[0].astype(jnp.float32)
         - fa._dot(wk_ref[0], sd, fa._NN)).astype(dt)
    dw = fa._dot(a_ref[0], do, fa._TN) + fa._dot(kg_ref[0], dsd, fa._NN)
    dwd = dw.astype(dt)
    du_ref[0] = dwd
    dwk_ref[0] = (-fa._dot(dwd, sd, fa._NT)).astype(dt)
    dqg_ref[0] = fa._dot(do, sd, fa._NT).astype(dt)
    dkg_ref[0] = fa._dot(w, dsd, fa._NT).astype(dt)
    da_ref[0] = fa._dot(do, w, fa._NT).astype(dt)
    ddec_ref[0] = jnp.sum(ds * s, axis=0, keepdims=True)
    ds_acc[:] = (fa._dot(qg_ref[0], do, fa._TN) + ds * dec_ref[0]
                 - fa._dot(wk_ref[0], dwd, fa._TN))


def _kda_fwd_kernel(u_ref, wk_ref, qg_ref, kg_ref, a_ref, dec_ref, o_ref,
                    st_ref, s_acc):
    """`_fwd_kernel` with the state TRANSPOSED, [dv, dk]: the chunk's decay
    is a row of dk lanes."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_acc[:] = jnp.zeros_like(s_acc)

    s = s_acc[:]
    dt = u_ref.dtype
    sd = s.astype(dt)
    w = (u_ref[0].astype(jnp.float32)
         - fa._dot(wk_ref[0], sd, fa._NT)).astype(dt)
    o_ref[0] = (fa._dot(qg_ref[0], sd, fa._NT)
                + fa._dot(a_ref[0], w, fa._NN)).astype(o_ref.dtype)
    s = s * dec_ref[0] + fa._dot(w, kg_ref[0], fa._TN)
    s_acc[:] = s
    st_ref[0] = s.astype(st_ref.dtype)


def _kda_bwd_kernel(u_ref, wk_ref, qg_ref, kg_ref, a_ref, dec_ref, st_ref,
                    do_ref, du_ref, dwk_ref, dqg_ref, dkg_ref, da_ref,
                    ddec_ref, ds_acc, *, n_chunks):
    """`_bwd_kernel` with the state and its cotangent transposed."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ds_acc[:] = jnp.zeros_like(ds_acc)

    dt = u_ref.dtype
    # the saved chunk-end states come in the operands' dtype
    sd = jnp.where(j < n_chunks - 1, st_ref[0], jnp.zeros_like(st_ref[0]))
    s = sd.astype(jnp.float32)
    ds = ds_acc[:]
    dsd = ds.astype(dt)
    do = do_ref[0]
    w = (u_ref[0].astype(jnp.float32)
         - fa._dot(wk_ref[0], sd, fa._NT)).astype(dt)
    dw = fa._dot(a_ref[0], do, fa._TN) + fa._dot(kg_ref[0], dsd, fa._NT)
    dwd = dw.astype(dt)
    du_ref[0] = dwd
    dwk_ref[0] = (-fa._dot(dwd, sd, fa._NN)).astype(dt)
    dqg_ref[0] = fa._dot(do, sd, fa._NN).astype(dt)
    dkg_ref[0] = fa._dot(w, dsd, fa._NN).astype(dt)
    da_ref[0] = fa._dot(do, w, fa._NT).astype(dt)
    ddec_ref[0] = jnp.sum(ds * s, axis=0, keepdims=True)
    ds_acc[:] = (fa._dot(do, qg_ref[0], fa._TN) + ds * dec_ref[0]
                 - fa._dot(dwd, wk_ref[0], fa._TN))


def _rows(x):
    """[B, n, r, d] -> [B, n * r, d]: a chunk is a block of rows."""
    return x.reshape(x.shape[0], x.shape[1] * x.shape[2], x.shape[3])


def _lanes(decay, dv):
    """decay [B, n] -> [B * n, 1, dv]: one row of lanes a chunk; a vector
    decay [B, n, dk] -> [B * n, 1, dk], the row it is."""
    if decay.ndim == 3:
        return decay.reshape(-1, 1, decay.shape[-1])
    return jnp.broadcast_to(decay.reshape(-1, 1, 1), (decay.size, 1, dv))


@functools.partial(jax.jit, static_argnums=(6,))
def _forward(u, wk, qg, kg, a, decay, interpret):
    bh, n, c, dv = u.shape
    dk = wk.shape[-1]
    vector = decay.ndim == 3
    # the state as a kernel holds it, and the width of a chunk's decay row
    sr, sc = (dv, dk) if vector else (dk, dv)
    at = lambda rows, d: pl.BlockSpec((1, rows, d), lambda i, j: (i, j, 0))
    o, states = pl.pallas_call(
        _kda_fwd_kernel if vector else _fwd_kernel,
        grid=(bh, n),
        in_specs=[at(c, dv), at(c, dk), at(c, dk), at(c, dk), at(c, c),
                  pl.BlockSpec((1, 1, sc), lambda i, j: (i * n + j, 0, 0))],
        out_specs=(at(c, dv), at(sr, sc)),
        out_shape=(jax.ShapeDtypeStruct((bh, n * c, dv), u.dtype),
                   jax.ShapeDtypeStruct((bh, n * sr, sc),
                                        u.dtype if vector else jnp.float32)),
        scratch_shapes=[pltpu.VMEM((sr, sc), jnp.float32)],
        compiler_params=fa._compiler_params("parallel", "arbitrary"),
        interpret=interpret, name=KDA_FWD_NAME if vector else FWD_NAME,
    )(_rows(u), _rows(wk), _rows(qg), _rows(kg), _rows(a), _lanes(decay, dv))
    return o.reshape(bh, n, c, dv), states.reshape(bh, n, sr, sc)


@functools.partial(jax.jit, static_argnums=(8,))
def _backward(u, wk, qg, kg, a, decay, states, do, interpret):
    bh, n, c, dv = u.shape
    dk = wk.shape[-1]
    vector = decay.ndim == 3
    sr, sc = (dv, dk) if vector else (dk, dv)
    # program j of a head works on chunk n - 1 - j
    at = lambda rows, d: pl.BlockSpec((1, rows, d),
                                      lambda i, j: (i, n - 1 - j, 0))
    lanes = pl.BlockSpec((1, 1, sc), lambda i, j: (i * n + n - 1 - j, 0, 0))
    like = lambda x: jax.ShapeDtypeStruct(_rows(x).shape, x.dtype)
    du, dwk, dqg, dkg, da, ddec = pl.pallas_call(
        functools.partial(_kda_bwd_kernel if vector else _bwd_kernel,
                          n_chunks=n),
        grid=(bh, n),
        in_specs=[at(c, dv), at(c, dk), at(c, dk), at(c, dk), at(c, c), lanes,
                  pl.BlockSpec((1, sr, sc), lambda i, j: (
                      i, jnp.maximum(n - 2 - j, 0), 0)),
                  at(c, dv)],
        out_specs=(at(c, dv), at(c, dk), at(c, dk), at(c, dk), at(c, c),
                   lanes),
        out_shape=(like(u), like(wk), like(qg), like(kg), like(a),
                   jax.ShapeDtypeStruct((bh * n, 1, sc), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((sr, sc), jnp.float32)],
        compiler_params=fa._compiler_params("parallel", "arbitrary"),
        interpret=interpret, name=KDA_BWD_NAME if vector else BWD_NAME,
    )(_rows(u), _rows(wk), _rows(qg), _rows(kg), _rows(a), _lanes(decay, dv),
      _rows(states), _rows(do.astype(u.dtype)))
    # a scalar decay's cotangent comes a lane at a time: add the lanes up
    ddec = ddec if vector else ddec.sum(axis=(1, 2))
    return (du.reshape(u.shape), dwk.reshape(wk.shape), dqg.reshape(qg.shape),
            dkg.reshape(kg.shape), da.reshape(a.shape),
            ddec.reshape(decay.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_kernels(u, wk, qg, kg, a, decay, interpret):
    return _scan_fwd(u, wk, qg, kg, a, decay, interpret)[0]


def _scan_fwd(u, wk, qg, kg, a, decay, interpret):
    """The two the kernel made carry ``SAVED_UNDER_REMAT``'s names, here
    inside the rule (as ops/block_diffusion_attention.py's): a layer under
    remat that saves them rebuilds the chunk-local operands in its
    backward and holds no second ``gdn_fwd``."""
    o, states = _forward(u, wk, qg, kg, a, decay, interpret)
    o, states = (checkpoint_name(x, name) for x, name in zip(
        (o, states),
        KDA_SAVED_UNDER_REMAT if decay.ndim == 3 else SAVED_UNDER_REMAT))
    return (o, states), (u, wk, qg, kg, a, decay, states)


def _scan_bwd(interpret, res, g):
    # the states leave the rule for a counter alone: no cotangent is read
    return _backward(*res, g[0], interpret)


_scan_kernels.defvjp(_scan_fwd, _scan_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk=CHUNK, use_kernel=None):
    """q, k [b, s, key heads, dk] as they enter the rule (normalised,
    scaled), v [b, s, value heads, dv] (value head h reads key head h //
    (value heads // key heads)), g float32 log decay (<= 0): [b, s, value
    heads], one a head and token, or [b, s, value heads, dk], one a key
    channel (the VECTOR form of the module docstring), beta [b, s, value
    heads] -> (o [b, s, value heads, dv] in v's dtype,
    {"chunk_log_decay_min": the most negative cumulative log decay a
    chunk (and, of a vector decay, a channel) reached, "state_absmax": the
    largest |S| at a chunk's end}, float32 scalars that carry no gradient).
    A sequence that is no whole number of chunks is padded with tokens that
    neither write nor decay. ``use_kernel``: None = the kernels on a TPU,
    the plain path elsewhere."""
    b, s, hv, dv = v.shape
    hk, dk = k.shape[2:]
    vector = g.ndim == 4
    if hv % hk or q.shape != k.shape or beta.shape != (b, s, hv) \
            or g.shape != (b, s, hv) + ((dk,) if vector else ()) \
            or (vector and chunk % min(SUB, chunk)):
        raise ValueError("gated delta rule: q %s k %s v %s g %s beta %s"
                         % (q.shape, k.shape, v.shape, g.shape, beta.shape))
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    pad = -s % chunk
    n = (s + pad) // chunk

    def chunks(x, repeat=1):
        """[b, s, h, ...] -> [b * value heads, n, chunk, ...]."""
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        if repeat > 1:
            x = jnp.repeat(x, repeat, axis=2)
        x = jnp.moveaxis(x, 2, 1)
        return x.reshape((b * hv, n, chunk) + x.shape[3:])

    gc = chunks(g.astype(jnp.float32))
    local = (chunks(q.astype(v.dtype), hv // hk),
             chunks(k.astype(v.dtype), hv // hk), chunks(v), gc,
             chunks(beta.astype(jnp.float32)))
    operands = (_in_groups(chunk_operands_vector, local, HEAD_GROUP)
                if vector else chunk_operands(*local))
    if use_kernel:
        o, states = _scan_kernels(*operands,
                                  jax.default_backend() == "cpu")
    else:
        o, states = _scan_plain(*operands)
    o = jnp.moveaxis(o.reshape(b, hv, n * chunk, dv), 1, 2)[:, :s]
    if vector:          # the kernels hand them over in the operands' dtype
        states = states.astype(jnp.float32)
    stats = {"chunk_log_decay_min": jnp.min(jnp.sum(gc, axis=2)),
             "state_absmax": jnp.max(jnp.abs(states))}
    return o, jax.tree_util.tree_map(lax.stop_gradient, stats)
