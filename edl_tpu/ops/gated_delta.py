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

THE SCALAR FORM (``g`` [b, s, h]): what is local to a chunk (T, U, Wk, the
two decayed copies of q and k, the [C, C] matrix in front of W) is plain
``jax.numpy`` in every path and is differentiated by JAX
(:func:`chunk_operands`). What is sequential — W, O and S' from S, chunk
after chunk, and in reverse for the gradient — is, on a TPU, two Pallas
kernels:

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
the sum over d as it stands, masked to i >= j; the blocks left of the
diagonal in block row I are ONE product of ``x_i * exp(gamma_i - gamma_r)``
with ``k_j * exp(gamma_r - gamma_j)`` about the boundary r = the FIRST ROW
OF BLOCK I (j < r <= i, so both exponents are <= 0; a factor that underflows
belongs to a pair whose true weight is below float32 too).

The plain path (the CPU's, and the oracle of the kernels' hand-written
backward) makes these operands in ``jax.numpy`` (:func:`chunk_operands_vector`,
``HEAD_GROUP`` heads at a time under a checkpoint, :func:`_in_groups`; a
diagonal block a head at a time, :func:`_block_pairs`), walks the chunks in
a ``lax.scan`` and is differentiated by JAX. On a TPU the rule is a
``custom_vjp`` over ITS OWN ARGUMENTS (q, k, v, the log decay, beta) and two
Pallas kernels, one program a (``KDA_HEADS`` heads, chunk), that read the
rule's arguments a head at a time ([B, H, S, d]: whole tiles of the model's
arrays, permuted) and form everything local to a chunk in VMEM — nothing of it
(Akk, Aqk, T, U, Wk, the decayed copies, their cotangents) exists in HBM:

- ``kda_fwd``: gamma (the log decay's cumulative sum in the chunk, one
  float32 product with a triangle of ones), the diagonal blocks (a loop over
  a block's ``SUB`` columns, unrolled where Mosaic compiles it, every
  block of the program's heads at once: [R, dk] exponentials a column, R =
  heads x C rows), the left blocks' products, T by the doubling of
  :func:`unit_lower_inverse` in float32 products at full precision, U, Wk,
  Qg, Kg, exp(gamma_C); then the sequential part with a head's float32
  state held TRANSPOSED, [dv, dk], in VMEM scratch across the chunks (a
  chunk's decay exp(gamma_C) is then one row of dk lanes and scales the
  state lane by lane). The program's heads lie one below the other, so
  what is [C, C] a head is one [R, R] matrix with the heads' blocks on its
  diagonal: two heads of 64 tokens fill the MXU's 128 x 128, and the
  inverse's ten dependent products serve both. Writes the result and the
  chunk-end states, in the operands' dtype (the backward takes them as
  operands of the MXU: in bfloat16 they are 67 MB a layer at 8192 tokens
  of 16 heads);
- ``kda_bwd``: the same chunks last to first; forms the forward's operands
  AGAIN from the same inputs and the saved state, runs the sequential
  cotangents (dU, dWk, dQg, dKg, dAqk, d decay; dS in VMEM scratch) and
  carries them through the chunk-local algebra by hand: dT = dU (beta V)^T
  + dWk (beta e^gamma K)^T, dL = -T^T dT T^T (strictly lower, float32), d
  beta and dAkk from it, the left blocks' two products a block row, the
  diagonal blocks' cotangents by `_block_pairs_bwd`'s identities (d gamma_i
  += x_i dx_i, d gamma_j -= k_j dk_j: a second loop over a block's
  columns), the decayed copies' and exp(gamma_C)'s, and gamma's cotangent
  back through the cumulative sum. Writes dq, dk, dv (operands' dtype), the
  log decay's cotangent (float32) and beta's a (head, token).

The residuals are (q, k, v, g, beta, states): a layer under remat that saves
``KDA_SAVED_UNDER_REMAT`` rebuilds nothing of the rule in its backward.
bfloat16 into the MXU at the plain path's rounding points (T, beta V, beta
e^gamma K, the factors about a block's boundary, U, Wk, Qg, Kg, Aqk, the
state as it is read; the cotangents dU and dWk, dAkk and dAqk of the left
blocks as they enter a product), float32 accumulation, float32 gamma,
decays, ``exp``, state and inverse. The two forms share the entry, the
chunking and the statistics, not their kernels: the scalar kernels lower as
they did.
"""

import functools
import types

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


def _rows(x):
    """[B, n, r, d] -> [B, n * r, d]: a chunk is a block of rows."""
    return x.reshape(x.shape[0], x.shape[1] * x.shape[2], x.shape[3])


def _lanes(decay, dv):
    """decay [B, n] -> [B * n, 1, dv]: one row of lanes a chunk."""
    return jnp.broadcast_to(decay.reshape(-1, 1, 1), (decay.size, 1, dv))


@functools.partial(jax.jit, static_argnums=(6,))
def _forward(u, wk, qg, kg, a, decay, interpret):
    bh, n, c, dv = u.shape
    dk = wk.shape[-1]
    at = lambda rows, d: pl.BlockSpec((1, rows, d), lambda i, j: (i, j, 0))
    o, states = pl.pallas_call(
        _fwd_kernel,
        grid=(bh, n),
        in_specs=[at(c, dv), at(c, dk), at(c, dk), at(c, dk), at(c, c),
                  pl.BlockSpec((1, 1, dv), lambda i, j: (i * n + j, 0, 0))],
        out_specs=(at(c, dv), at(dk, dv)),
        out_shape=(jax.ShapeDtypeStruct((bh, n * c, dv), u.dtype),
                   jax.ShapeDtypeStruct((bh, n * dk, dv), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=fa._compiler_params("parallel", "arbitrary"),
        interpret=interpret, name=FWD_NAME,
    )(_rows(u), _rows(wk), _rows(qg), _rows(kg), _rows(a), _lanes(decay, dv))
    return o.reshape(bh, n, c, dv), states.reshape(bh, n, dk, dv)


@functools.partial(jax.jit, static_argnums=(8,))
def _backward(u, wk, qg, kg, a, decay, states, do, interpret):
    bh, n, c, dv = u.shape
    dk = wk.shape[-1]
    # program j of a head works on chunk n - 1 - j
    at = lambda rows, d: pl.BlockSpec((1, rows, d),
                                      lambda i, j: (i, n - 1 - j, 0))
    lanes = pl.BlockSpec((1, 1, dv), lambda i, j: (i * n + n - 1 - j, 0, 0))
    like = lambda x: jax.ShapeDtypeStruct(_rows(x).shape, x.dtype)
    du, dwk, dqg, dkg, da, ddec = pl.pallas_call(
        functools.partial(_bwd_kernel, n_chunks=n),
        grid=(bh, n),
        in_specs=[at(c, dv), at(c, dk), at(c, dk), at(c, dk), at(c, c), lanes,
                  pl.BlockSpec((1, dk, dv), lambda i, j: (
                      i, jnp.maximum(n - 2 - j, 0), 0)),
                  at(c, dv)],
        out_specs=(at(c, dv), at(c, dk), at(c, dk), at(c, dk), at(c, c),
                   lanes),
        out_shape=(like(u), like(wk), like(qg), like(kg), like(a),
                   jax.ShapeDtypeStruct((bh * n, 1, dv), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=fa._compiler_params("parallel", "arbitrary"),
        interpret=interpret, name=BWD_NAME,
    )(_rows(u), _rows(wk), _rows(qg), _rows(kg), _rows(a), _lanes(decay, dv),
      _rows(states), _rows(do.astype(u.dtype)))
    # the decay's cotangent comes a lane at a time: add the lanes up
    ddec = ddec.sum(axis=(1, 2))
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
        (o, states), SAVED_UNDER_REMAT))
    return (o, states), (u, wk, qg, kg, a, decay, states)


def _scan_bwd(interpret, res, g):
    # the states leave the rule for a counter alone: no cotangent is read
    return _backward(*res, g[0], interpret)


_scan_kernels.defvjp(_scan_fwd, _scan_bwd)


# -- the vector form's kernels ------------------------------------------------
# One program a (batch row x ``hg`` heads, chunk), its blocks a head at a
# time: q, k, v, the log decay and their cotangents [B, H, S, d], a chunk's
# [hg, C, d] (against the model's [B, S, H, d] that is a permutation of
# whole (8 or 16 tokens x 128 channels) tiles on this chip, which the
# compiler folds into the producers; the heads side by side out of [B, S,
# H x d] read 5 ms a step slower); beta and its cotangent [B * H / hg, S,
# hg], a token a ROW (what scales a token's row of K, V and Akk); the
# states [B * H, n, dv, dk], TRANSPOSED as they are carried. Inside, the
# program's heads lie one BELOW the other, R = hg * C rows, and what is [C,
# C] of one head (Akk, Aqk, T and their cotangents) is one [R, R] matrix
# with the heads' blocks on its diagonal: two heads of 64 tokens fill the
# MXU's 128 x 128, and a product such as T (beta V) serves both at once.

_full = functools.partial(lax.dot_general, precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


def _stacked(ref):
    """A block [1, hg, C, d] -> [hg * C, d] float32: the program's heads
    one below the other."""
    return jnp.concatenate([ref[0, h].astype(jnp.float32)
                            for h in range(ref.shape[1])], axis=0)


def _over(x, n, of):
    """[R, d] -> [R, d]: row ``of(g)`` of x laid over rows g * n ... g * n +
    n - 1, for every group g of n rows."""
    return jnp.concatenate(
        [jnp.broadcast_to(x[of(g):of(g) + 1], (n, x.shape[1]))
         for g in range(x.shape[0] // n)], axis=0)


def _quotient(x, n):
    """x // n and x % n of an iota, by shift and mask where n is a power of
    two."""
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1), x & (n - 1)
    return x // n, x % n


def _row_of_each_block(ref, j, sub):
    """Row j of every block of ``sub`` rows of ref [R, d], laid over its
    block's rows: [R, d]."""
    r, d = ref.shape
    return jnp.concatenate(
        [jnp.broadcast_to(ref[pl.ds(b * sub + j, 1), :], (sub, d))
         for b in range(r // sub)], axis=0)


def _own_pairs(x, q32, k32, gam, k_scr, g_scr, sub, unroll):
    """The diagonal blocks' pairs, the sum over d as it stands, of every
    head of the program at once (rows: head after head, [R, dk]): (k's, q's)
    [R, R] float32, out[i, j] = sum_d x_id k_jd exp(gam_id - gam_jd) at
    column j of a row's OWN block; what it holds elsewhere (above the
    diagonal, in other blocks' columns) the caller masks. A loop over a
    block's ``sub`` columns: the exponentials of one column, [R, dk], at a
    time."""
    def column(j, pairs):
        # i >= j is gam_i <= gam_j; rows above the diagonal are masked later
        z = _row_of_each_block(k_scr, j, sub) * jnp.exp(jnp.minimum(
            gam - _row_of_each_block(g_scr, j, sub), 0.0))
        return tuple(jnp.where(x.column == j,
                               jnp.sum(y * z, axis=1, keepdims=True), p)
                     for y, p in zip((k32, q32), pairs))

    zero = jnp.zeros(x.row.shape, jnp.float32)
    return lax.fori_loop(0, sub, column, (zero, zero), unroll=unroll)


def _masks(r, c, sub, dk):
    """The iotas and masks of a program's [R, R] matrices and [R, dk]
    rows."""
    x = types.SimpleNamespace()
    x.hg = r // c
    x.row = lax.broadcasted_iota(jnp.int32, (r, r), 0)
    x.col = lax.broadcasted_iota(jnp.int32, (r, r), 1)
    x.tok = lax.broadcasted_iota(jnp.int32, (r, dk), 0)
    x.head = _quotient(x.row, c)[0] == _quotient(x.col, c)[0]
    block, x.column = _quotient(x.col, sub)   # a column's place in its block
    x.own = _quotient(x.row, sub)[0] == block
    x.within = _quotient(x.tok, c)[1]           # a row's token in its chunk
    x.heads = [slice(h * c, (h + 1) * c) for h in range(x.hg)]
    x.strict = x.head & (x.row > x.col)
    x.lower = x.head & (x.row >= x.col)
    # gamma = before @ g, a chunk's cumulative sum a head: i >= j
    x.before = x.lower.astype(jnp.float32)
    return x


def _chunk_local(x, q32, k32, v32, gam, beta, own_k, own_q, c, sub, dt):
    """The chunk-local algebra of `chunk_operands_vector` for a program's
    heads, at its rounding points: q32, k32 [R, dk], v32 [R, dv] float32
    (of values in ``dt``), gam [R, dk] float32, beta [R, 1] float32, the
    diagonal blocks' pairs [R, R] (:func:`_own_pairs`). Every exponent is
    <= 0."""
    f32 = jnp.float32
    r, dk = gam.shape
    m = c // sub
    # left of the diagonal, about r = a block row's first row: both <= 0
    x.er = jnp.exp(gam - _over(gam, sub, lambda g: g * sub))
    x.rk, x.rq = k32 * x.er, q32 * x.er
    left_k = [[jnp.zeros((sub, r), f32)] for _ in x.heads]
    left_q = [[jnp.zeros((sub, r), f32)] for _ in x.heads]
    x.left = []
    for b in range(1, m):
        ec = jnp.where(x.within < b * sub, jnp.exp(jnp.minimum(
            _over(gam, c, lambda h: h * c + b * sub) - gam, 0.0)), 0.0)
        cols = k32 * ec
        both = jnp.concatenate(
            [y[h * c + b * sub:h * c + (b + 1) * sub]
             for h in range(x.hg) for y in (x.rk, x.rq)], axis=0).astype(dt)
        left = fa._dot(both, cols.astype(dt), fa._NT)
        for h in range(x.hg):
            left_k[h].append(left[2 * h * sub:(2 * h + 1) * sub])
            left_q[h].append(left[(2 * h + 1) * sub:(2 * h + 2) * sub])
        x.left.append((ec, cols, both))
    whole = lambda parts: jnp.where(x.head, jnp.concatenate(
        [y for head in parts for y in head], axis=0), 0.0)
    own = x.own & x.lower
    x.akk = whole(left_k) + jnp.where(own, own_k, 0.0)
    aqk = whole(left_q) + jnp.where(own, own_q, 0.0)
    # T = (I + strict_lower(beta Akk))^-1 by doubling, float32 products
    eye = (x.row == x.col).astype(f32)
    power = jnp.where(x.strict, -beta * x.akk, 0.0)
    x.inv = eye + power
    reach = 2
    while reach < c:
        power = _full(power, power, fa._NN)
        x.inv = _full(x.inv, eye + power, fa._NN)
        reach *= 2
    x.t = x.inv.astype(dt)
    x.grown = jnp.exp(gam)
    x.fade = jnp.exp(_over(gam, c, lambda h: (h + 1) * c - 1) - gam)
    x.decay = [jnp.exp(gam[rows][c - 1:c]) for rows in x.heads]
    x.vb = (v32 * beta).astype(dt)
    x.kb32 = k32 * beta * x.grown
    x.kb = x.kb32.astype(dt)
    x.u = fa._dot(x.t, x.vb, fa._NN).astype(dt)
    x.wk = fa._dot(x.t, x.kb, fa._NN).astype(dt)
    x.qg32, x.kg32 = q32 * x.grown, k32 * x.fade
    x.qg, x.kg = x.qg32.astype(dt), x.kg32.astype(dt)
    x.a = aqk.astype(dt)
    return x


def _program(q_ref, k_ref, v_ref, g_ref, b_ref, k_scr, g_scr, sub, unroll):
    """What both kernels form first: (q32, k32, v32, beta [R, 1], the
    chunk-local algebra), gamma — the log decay's cumulative sum inside the
    chunk, one float32 product — in ``g_scr``."""
    c, hg = b_ref.shape[1:]
    x = _masks(hg * c, c, sub, g_scr.shape[1])
    q32, k32, v32 = _stacked(q_ref), _stacked(k_ref), _stacked(v_ref)
    gam = _full(x.before, _stacked(g_ref), fa._NN)
    k_scr[:] = k32
    g_scr[:] = gam
    own_k, own_q = _own_pairs(x, q32, k32, gam, k_scr, g_scr, sub, unroll)
    beta = jnp.concatenate([b_ref[0, :, h:h + 1] for h in range(hg)], axis=0)
    return q32, k32, v32, beta, _chunk_local(
        x, q32, k32, v32, gam, beta, own_k, own_q, c, sub, v_ref.dtype)


def _writes(x, sd):
    """W = U - Wk S of the program's heads, ``sd`` the states that entered
    the chunk (the operands' dtype)."""
    return jnp.concatenate(
        [(x.u[rows].astype(jnp.float32)
          - fa._dot(x.wk[rows], sd[h], fa._NT)).astype(sd[h].dtype)
         for h, rows in enumerate(x.heads)], axis=0)


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref, k_scr,
                    g_scr, s_acc, *, sub, unroll):
    """A chunk of ``hg`` heads: its operands formed in VMEM, then the
    sequential part, a head's float32 state TRANSPOSED [dv, dk] — the
    chunk's decay is a row of dk lanes."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_acc[:] = jnp.zeros_like(s_acc)

    dt = v_ref.dtype
    x = _program(q_ref, k_ref, v_ref, g_ref, b_ref, k_scr, g_scr, sub,
                 unroll)[-1]
    sd = [s_acc[h].astype(dt) for h in range(x.hg)]
    w = _writes(x, sd)
    o = fa._dot(x.a, w, fa._NN)
    for h, rows in enumerate(x.heads):
        o_ref[0, h] = (fa._dot(x.qg[rows], sd[h], fa._NT)
                       + o[rows]).astype(o_ref.dtype)
        s = s_acc[h] * x.decay[h] + fa._dot(w[rows], x.kg[rows], fa._TN)
        s_acc[h] = s
        st_ref[h, 0] = s.astype(st_ref.dtype)


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, db_ref, k_scr, g_scr,
                    dy_scr, ds_acc, *, sub, unroll, n_chunks):
    """The same chunks last to first: the forward's operands formed AGAIN,
    the sequential cotangents (dU, dWk, dQg, dKg, dAqk, d decay) from the
    saved state and the carried dS, and those carried through the
    chunk-local algebra by hand, in VMEM."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ds_acc[:] = jnp.zeros_like(ds_acc)

    f32 = jnp.float32
    dk = ds_acc.shape[2]
    c = b_ref.shape[1]
    m = c // sub
    dt = v_ref.dtype
    q32, k32, v32, beta, x = _program(q_ref, k_ref, v_ref, g_ref, b_ref,
                                      k_scr, g_scr, sub, unroll)
    gam = g_scr[:]
    down = lambda y: jnp.sum(y, axis=0, keepdims=True)
    across = lambda y: jnp.sum(y, axis=1, keepdims=True)
    at_row = lambda i, y: jnp.where(x.tok == i, y, 0.0)
    by_head = lambda parts: jnp.concatenate(parts, axis=0)
    # the sequential part's cotangents, a head's state at a time
    do = by_head([do_ref[0, h] for h in range(x.hg)])
    # the state that entered this chunk: the chunk before's end, or zero
    sd = [jnp.where(j < n_chunks - 1, st_ref[h, 0],
                    jnp.zeros_like(st_ref[h, 0])) for h in range(x.hg)]
    ds = [ds_acc[h] for h in range(x.hg)]   # of this chunk's END states
    dsd = [y.astype(dt) for y in ds]
    w = _writes(x, sd)
    dwd = (fa._dot(x.a, do, fa._TN) + by_head(
        [fa._dot(x.kg[rows], dsd[h], fa._NT)
         for h, rows in enumerate(x.heads)])).astype(dt)           # dU
    dwk = by_head([(-fa._dot(dwd[rows], sd[h], fa._NN)).astype(dt)
                   for h, rows in enumerate(x.heads)])
    dqg = by_head([fa._dot(do[rows], sd[h], fa._NN)
                   for h, rows in enumerate(x.heads)])
    dkg = by_head([fa._dot(w[rows], dsd[h], fa._NN)
                   for h, rows in enumerate(x.heads)])
    daqk = jnp.where(x.lower, fa._dot(do, w, fa._NT), 0.0)
    for h, rows in enumerate(x.heads):
        ds_acc[h] = (fa._dot(do[rows], x.qg[rows], fa._TN)
                     + ds[h] * x.decay[h]
                     - fa._dot(dwd[rows], x.wk[rows], fa._TN))
    # U = T (beta V), Wk = T (beta e^gamma K), T = (I + L)^-1
    dvb = fa._dot(x.t, dwd, fa._TN)
    dkb = fa._dot(x.t, dwk, fa._TN)
    dinv = jnp.where(x.head, fa._dot(dwd, x.vb, fa._NT)
                     + fa._dot(dwk, x.kb, fa._NT), 0.0)
    dl = jnp.where(x.strict,
                   -_full(_full(x.inv, dinv, fa._TN), x.inv, fa._NT), 0.0)
    dakk = dl * beta
    dbeta = (across(dvb * v32) + across(dkb * k32 * x.grown)
             + across(dl * x.akk))
    # the decayed copies and exp(gamma_C)
    faded = dkg * x.kg32
    dk32 = dkb * beta * x.grown + dkg * x.fade
    dq32 = dqg * x.grown
    dgam = dkb * x.kb32 + dqg * x.qg32 - faded
    for h, rows in enumerate(x.heads):
        dgam += at_row((h + 1) * c - 1, down(faded[rows]) + down(
            ds[h] * sd[h].astype(f32)) * x.decay[h])
    # the blocks left of the diagonal: two products a block row
    drk = [[jnp.zeros((sub, dk), f32)] for _ in x.heads]
    drq = [[jnp.zeros((sub, dk), f32)] for _ in x.heads]
    for b in range(1, m):
        ec, cols, both = x.left[b - 1]
        dboth = jnp.concatenate(
            [y[h * c + b * sub:h * c + (b + 1) * sub]
             for h in range(x.hg) for y in (dakk, daqk)], axis=0).astype(dt)
        drows = fa._dot(dboth, cols.astype(dt), fa._NN)
        dcols = jnp.where(x.within < b * sub,
                          fa._dot(dboth, both, fa._TN), 0.0)
        for h in range(x.hg):
            drk[h].append(drows[2 * h * sub:(2 * h + 1) * sub])
            drq[h].append(drows[(2 * h + 1) * sub:(2 * h + 2) * sub])
        dk32 += dcols * ec
        about = dcols * cols
        dgam -= about
        for h, rows in enumerate(x.heads):
            dgam += at_row(h * c + b * sub, down(about[rows]))
    drk = by_head([y for head in drk for y in head])
    drq = by_head([y for head in drq for y in head])
    dk32 += drk * x.er
    dq32 += drq * x.er
    about = drk * x.rk + drq * x.rq
    dgam += about
    for g in range(x.hg * m):
        if g % m:
            dgam -= at_row(g * sub, down(about[g * sub:(g + 1) * sub]))
    # the diagonal blocks: d gamma_i += x_i dx_i, d gamma_j -= k_j dk_j
    dkk, dqk = jnp.where(x.own, dakk, 0.0), jnp.where(x.own, daqk, 0.0)

    def column(jj, carry):
        dk_rows, dq_rows = carry
        e = jnp.exp(jnp.minimum(
            gam - _row_of_each_block(g_scr, jj, sub), 0.0))
        gk = across(jnp.where(x.column == jj, dkk, 0.0))
        gq = across(jnp.where(x.column == jj, dqk, 0.0))
        # what row j of each block takes as the pairs' k_j
        taken = (gk * k32 + gq * q32) * e
        for b in range(x.hg * m):
            dy_scr[pl.ds(b * sub + jj, 1), :] = down(
                taken[b * sub:(b + 1) * sub])
        z = _row_of_each_block(k_scr, jj, sub) * e
        return dk_rows + gk * z, dq_rows + gq * z

    zero = jnp.zeros(gam.shape, f32)
    dk_rows, dq_rows = lax.fori_loop(0, sub, column, (zero, zero),
                                     unroll=unroll)
    dk_cols = dy_scr[:]
    dq32 += dq_rows
    dk32 += dk_rows + dk_cols
    dgam += k32 * (dk_rows - dk_cols) + q32 * dq_rows
    dvs = dvb * beta
    # gamma_i = sum of g_j over j <= i: g_j takes gamma's cotangents from j on
    dg = _full(x.before, dgam, fa._TN)
    for h, rows in enumerate(x.heads):
        for ref, y in ((dq_ref, dq32), (dk_ref, dk32), (dv_ref, dvs),
                       (dg_ref, dg)):
            ref[0, h] = y[rows].astype(ref.dtype)
        db_ref[0, :, h:h + 1] = dbeta[rows]


#: heads a program of the vector form's kernels
KDA_HEADS = 2


def _kda_specs(q, v, chunk, at):
    """(hg, grid's leading size, BlockSpecs of (q or k or g, v, beta, the
    states)), ``at`` the chunk of program (i, j): heads i % G * hg ... of
    batch row i // G."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    hg = KDA_HEADS if h % KDA_HEADS == 0 else 1
    g = h // hg
    wide = lambda d: pl.BlockSpec((1, hg, chunk, d),
                                  lambda i, j: (i // g, i % g, at(j), 0))
    return (hg, b * g, wide(dk), wide(dv),
            pl.BlockSpec((1, chunk, hg), lambda i, j: (i, at(j), 0)),
            lambda of: pl.BlockSpec((hg, 1, dv, dk),
                                    lambda i, j: (i, of(j), 0, 0)))


def _by_head(x):
    """[B, S, H, d] <-> [B, H, S, d]: how the kernels read and write."""
    return jnp.swapaxes(x, 1, 2)


def _kda_operands(q, k, v, g, beta, hg):
    """The kernels' operands of the rule's arguments: a head at a time, [B,
    H, S, d]; beta a token a row, ``hg`` heads a program."""
    b, s, h = beta.shape
    return tuple(_by_head(x) for x in (q, k, v, g)) + (jnp.swapaxes(
        beta.reshape(b, s, h // hg, hg), 1, 2).reshape(-1, s, hg),)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _kda_forward(q, k, v, g, beta, chunk, interpret):
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    n = s // chunk
    hg, lead, keys, values, scalars, state = _kda_specs(
        q, v, chunk, lambda j: j)
    flat = _kda_operands(q, k, v, g, beta, hg)
    o, states = pl.pallas_call(
        functools.partial(_kda_fwd_kernel, sub=min(SUB, chunk),
                          unroll=not interpret),
        grid=(lead, n),
        in_specs=[keys, keys, values, keys, scalars],
        out_specs=(values, state(lambda j: j)),
        out_shape=(jax.ShapeDtypeStruct(flat[2].shape, v.dtype),
                   jax.ShapeDtypeStruct((b * h, n, dv, dk), v.dtype)),
        scratch_shapes=[pltpu.VMEM((hg * chunk, dk), jnp.float32),
                        pltpu.VMEM((hg * chunk, dk), jnp.float32),
                        pltpu.VMEM((hg, dv, dk), jnp.float32)],
        compiler_params=fa._compiler_params("parallel", "arbitrary"),
        interpret=interpret, name=KDA_FWD_NAME,
    )(*flat)
    return _by_head(o), states


@functools.partial(jax.jit, static_argnums=(7, 8))
def _kda_backward(q, k, v, g, beta, states, do, chunk, interpret):
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    n = s // chunk
    # program j of a group of heads works on chunk n - 1 - j
    hg, lead, keys, values, scalars, state = _kda_specs(
        q, v, chunk, lambda j: n - 1 - j)
    flat = _kda_operands(q, k, v, g, beta, hg)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_kda_bwd_kernel, sub=min(SUB, chunk),
                          unroll=not interpret, n_chunks=n),
        grid=(lead, n),
        in_specs=[keys, keys, values, keys, scalars,
                  state(lambda j: jnp.maximum(n - 2 - j, 0)), values],
        out_specs=(keys, keys, values, keys, scalars),
        out_shape=tuple(like(x) for x in flat),
        scratch_shapes=[pltpu.VMEM((hg * chunk, dk), jnp.float32),
                        pltpu.VMEM((hg * chunk, dk), jnp.float32),
                        pltpu.VMEM((hg * chunk, dk), jnp.float32),
                        pltpu.VMEM((hg, dv, dk), jnp.float32)],
        compiler_params=fa._compiler_params("parallel", "arbitrary"),
        interpret=interpret, name=KDA_BWD_NAME,
    )(*flat, states, _by_head(do.astype(v.dtype)))
    return tuple(_by_head(x) for x in (dq, dk_, dv_, dg)) + (jnp.swapaxes(
        dbeta.reshape(b, h // hg, s, hg), 1, 2).reshape(b, s, h),)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda_kernels(q, k, v, g, beta, chunk, interpret):
    """q, k [B, S, H, dk], v [B, S, H, dv] (one dtype), g [B, S, H, dk]
    float32 (the log decay), beta [B, S, H] float32, S a whole number of
    chunks -> (O [B, S, H, dv] in v's dtype, chunk-end states [B * H, n,
    dv, dk] in v's dtype)."""
    return _kda_fwd(q, k, v, g, beta, chunk, interpret)[0]


def _kda_fwd(q, k, v, g, beta, chunk, interpret):
    """The two the kernel made carry ``KDA_SAVED_UNDER_REMAT``'s names, here
    inside the rule; the rest of the residuals are the rule's own arguments
    (as ops/ssd.py's): a layer under remat that saves the two rebuilds
    nothing of the rule in its backward and holds no second ``kda_fwd``."""
    o, states = _kda_forward(q, k, v, g, beta, chunk, interpret)
    o, states = (checkpoint_name(x, name) for x, name in zip(
        (o, states), KDA_SAVED_UNDER_REMAT))
    return (o, states), (q, k, v, g, beta, states)


def _kda_bwd(chunk, interpret, res, g):
    # the states leave the rule for a counter alone: no cotangent is read
    return _kda_backward(*res, g[0], chunk, interpret)


_kda_kernels.defvjp(_kda_fwd, _kda_bwd)


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
    f32 = jnp.float32

    def padded(x):
        return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))

    if vector and use_kernel:
        heads = lambda x: x if hv == hk else jnp.repeat(x, hv // hk, axis=2)
        g = padded(g.astype(f32))
        o, states = _kda_kernels(
            padded(heads(q.astype(v.dtype))), padded(heads(k.astype(v.dtype))),
            padded(v), g, padded(beta.astype(f32)), chunk,
            jax.default_backend() == "cpu")
        stats = {"chunk_log_decay_min": jnp.min(jnp.sum(
                     g.reshape(b, n, chunk, hv, dk), axis=2)),
                 "state_absmax": jnp.max(jnp.abs(states.astype(f32)))}
        return o[:, :s], jax.tree_util.tree_map(lax.stop_gradient, stats)

    def chunks(x, repeat=1):
        """[b, s, h, ...] -> [b * value heads, n, chunk, ...]."""
        x = padded(x)
        if repeat > 1:
            x = jnp.repeat(x, repeat, axis=2)
        x = jnp.moveaxis(x, 2, 1)
        return x.reshape((b * hv, n, chunk) + x.shape[3:])

    gc = chunks(g.astype(f32))
    local = (chunks(q.astype(v.dtype), hv // hk),
             chunks(k.astype(v.dtype), hv // hk), chunks(v), gc,
             chunks(beta.astype(f32)))
    if vector:
        o, states = _scan_plain(*_in_groups(chunk_operands_vector, local,
                                            HEAD_GROUP))
    elif use_kernel:
        o, states = _scan_kernels(*chunk_operands(*local),
                                  jax.default_backend() == "cpu")
    else:
        o, states = _scan_plain(*chunk_operands(*local))
    o = jnp.moveaxis(o.reshape(b, hv, n * chunk, dv), 1, 2)[:, :s]
    stats = {"chunk_log_decay_min": jnp.min(jnp.sum(gc, axis=2)),
             "state_absmax": jnp.max(jnp.abs(states))}
    return o, jax.tree_util.tree_map(lax.stop_gradient, stats)
