"""Mamba-2's state-space duality (SSD) scan (arXiv:2405.21060): a layer whose
memory is one [P, N] float32 matrix a head (P the head's width, N the state
size), decayed by a scalar and written by an outer product a token,

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S_0 = 0
    y_t = S_t C_t + D x_t,          A = -exp(a_log) < 0, one scalar a head

with B_t and C_t [N] SHARED by the heads of a group (head h reads group
h // (heads // groups): a grouped-query recurrence). The gated delta rule's
code (``ops/gated_delta.py``) cannot take it: there the write and its
correction share ``beta``, the state is square and every head has its own
key. :func:`ssd_scan` is its one entry. It runs the recurrence in CHUNKS of
``chunk`` tokens with the state carried between them. With gamma the
cumulative sum of dt A inside a chunk, L[i, j] = exp(gamma_i - gamma_j) for
i >= j else 0 (every decay is taken in this form, never ``exp(-gamma)``,
which overflows where a chunk forgets: the ``chunk_log_decay_min`` the entry
returns says how far below zero a chunk's gamma went) and S the state that
enters the chunk:

    Y  = (L * (C B^T)) (dt * X) + (exp(gamma) * C) S^T + D X
    S' = exp(gamma_last) S + (exp(gamma_last - gamma) * dt * X)^T B

The plain path (the CPU's) forms what is local to a chunk — gamma, C B^T
ONCE A GROUP, its product with a head's L, the three scaled copies of x and
C (:func:`chunk_operands`) — in ``jax.numpy``, walks the chunks in a
``lax.scan`` and is differentiated by JAX: it is the oracle of the kernels'
hand-written backward. On a TPU the scan is two Pallas kernels, ONE PROGRAM
A (batch x group, chunk), that read the model's own arrays — x with a
group's heads side by side, B and C a group — and the steps, and form the
rest in VMEM: nothing of a chunk's local algebra (M, the scaled copies, the
decay matrix, their cotangents) exists in HBM. Their blocks are
CHANNEL-major, x [B, H * P, S] and B, C [B, G * N, S] with a token a lane:
how the TPU compiler lays out what the causal convolution in front of the
scan leaves, so the ``swapaxes`` that says so here moves no byte there.

- ``ssd_fwd``: C B^T once for the group's heads, then a head at a time L,
  M, Y and S', the heads' states in VMEM in float32 across the group's
  chunks; writes the result and every chunk-end state;
- ``ssd_bwd``: the same chunks from the last to the first, the states'
  cotangents in VMEM; L, M and the scaled copies are formed AGAIN from the
  same inputs; writes dx, dB and dC summed over the group's heads, and a
  (head, token) the direct part of dt's cotangent and gamma's.

What stays in ``jax.numpy`` on the kernel path: gamma's ``cumsum`` inside a
chunk (with it, by JAX, its reverse, the product with A and the sums to
``a_log`` and the step's bias: [B, S, H] float32 arrays), the skip D x and
the two statistics. The rule's residuals are its own arguments (x, dt,
gamma, B, C) and the chunk-end states: a layer under remat that saves
``SAVED_UNDER_REMAT`` rebuilds nothing of the scan in its backward. bf16
operands into the MXU (M, dt * x, exp(gamma) * C, the decayed dt * x, the
state as it is read: the plain path's rounding points), float32
accumulation, float32 state, gamma, decays and ``exp``. ``use_kernel=True``
forces the kernels (on the CPU in the Pallas interpreter, for tests).
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
FWD_NAME = "ssd_fwd"
BWD_NAME = "ssd_bwd"

#: `checkpoint_name`s of what ``ssd_fwd`` leaves: its result and the
#: chunk-end states. A layer rematerialised under a policy that saves them
#: runs the forward kernel once a step; at 8192 tokens of 32 heads of 64 x
#: 128 in chunks of 128 they are 34 MB (bfloat16) and 67 MB (float32) a layer
SAVED_UNDER_REMAT = ("ssd.out", "ssd.states")

#: tokens a chunk (the published configuration's ``chunk_size``)
CHUNK = 128


def chunk_operands(x, dt, a, b, c):
    """What is local to a chunk, from x [B, H, n, C, P], dt [B, H, n, C]
    float32 (> 0), a [H] float32 (< 0), b, c [B, G, n, C, N] (x's dtype):
    (M, Xdt, Cg, Xd, decay, log decay) of the module docstring — M = L *
    (C B^T) [B, H, n, C, C], Xdt = dt * X and Xd = exp(gamma_last - gamma) *
    dt * X [B, H, n, C, P], Cg = exp(gamma) * C [B, H, n, C, N], all in x's
    dtype; decay = exp(gamma_last) and gamma_last [B, H, n] float32."""
    dtype = x.dtype
    f32 = jnp.float32
    bsz, h, n, cs, _ = x.shape
    g = b.shape[1]
    gam = jnp.cumsum(dt * a[None, :, None, None], axis=-1)
    last = gam[..., -1:]
    idx = jnp.arange(cs)
    lower = idx[:, None] >= idx[None, :]
    # exp of a difference that is never positive: zero above the diagonal
    decays = jnp.exp(jnp.where(lower, gam[..., :, None] - gam[..., None, :],
                               -jnp.inf))
    cb = jnp.einsum("bgnik,bgnjk->bgnij", c, b, preferred_element_type=f32)
    by_group = lambda y: y.reshape((bsz, g, h // g) + y.shape[2:])
    m = (by_group(decays) * cb[:, :, None]).reshape(decays.shape)
    xdt = x.astype(f32) * dt[..., None]
    cg = (by_group(jnp.exp(gam))[..., None]
          * c.astype(f32)[:, :, None]).reshape(gam.shape + c.shape[-1:])
    xd = xdt * jnp.exp(last - gam)[..., None]
    return tuple(y.astype(dtype) for y in (m, xdt, cg, xd)) + (
        jnp.exp(last[..., 0]), last[..., 0])


def _scan_plain(m, xdt, cg, xd, b, decay):
    """The sequential part in jax.numpy: (Y [B, H, n, C, P] in x's dtype,
    chunk-end states [B, H, n, P, N] float32)."""
    dtype = xdt.dtype
    f32 = jnp.float32
    bsz, h, n = decay.shape
    g = b.shape[1]
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)
    # a head's chunks with its group's beside them
    b = jnp.repeat(b, h // g, axis=1)

    def chunk(s, xs):
        m, xdt, cg, xd, b, decay = xs
        y = dot("bhij,bhjp->bhip", m, xdt) + dot("bhin,bhpn->bhip", cg,
                                                 s.astype(dtype))
        s = s * decay[..., None, None] + dot("bhip,bhin->bhpn", xd, b)
        return s, (y.astype(dtype), s)

    front = lambda y: jnp.moveaxis(y, 2, 0)
    s0 = jnp.zeros((bsz, h, xdt.shape[-1], b.shape[-1]), f32)
    _, (y, states) = lax.scan(chunk, s0, tuple(front(y) for y in (
        m, xdt, cg, xd, b, decay)))
    return jnp.moveaxis(y, 0, 2), jnp.moveaxis(states, 0, 2)


# -- the kernels -------------------------------------------------------------
# One program a (batch x group, chunk), its blocks CHANNEL-major, a token a
# lane (how the compiler lays out what the causal convolution leaves, so no
# copy stands between the two): x, y, dy, dx [r * P, C] (the group's r heads
# one below the other), B, C and their cotangents [N, C], the states
# [r, P, N]; the steps a (head, token) twice — ``rows`` [2 r, C] (dt in rows
# 0..r-1, gamma in r..2r-1: what scales a token's column, and the j of
# L[i, j]) and ``cols`` [C, r] (gamma, token a row: the i of L[i, j]).

def _head(k, x_ref, rows_ref, cols_ref, cb, c32, lower):
    """Head ``k`` of a group's chunk, formed in VMEM: (M in float32, L, dt *
    x [P, C] in float32, exp(gamma) * C [N, C] in float32, the rows dt,
    exp(gamma), exp(gamma_last - gamma) [1, C], exp(gamma_last) a column of
    the state [P, 1])."""
    r = cols_ref.shape[2]
    p = x_ref.shape[1] // r
    dt, gam = rows_ref[0, k:k + 1], rows_ref[0, r + k:r + k + 1]
    by_row = cols_ref[0, :, k:k + 1]
    last = by_row[-1:]
    # exp of a difference that is never positive: zero above the diagonal
    decays = jnp.exp(jnp.where(lower, by_row - gam, -jnp.inf))
    xdt = x_ref[0, k * p:(k + 1) * p].astype(jnp.float32) * dt
    grown = jnp.exp(gam)
    return (decays * cb, decays, xdt, grown * c32, dt, grown,
            jnp.exp(last - gam), jnp.exp(jnp.broadcast_to(last, (p, 1))))


def _group(b_ref, c_ref):
    """What a group's heads share in a chunk: (C B^T [C, C] float32, C
    [N, C] in float32, the mask i >= j)."""
    cb = fa._dot(c_ref[0], b_ref[0], fa._TN)
    idx = lax.broadcasted_iota(jnp.int32, cb.shape, 0)
    return cb, c_ref[0].astype(jnp.float32), idx >= lax.broadcasted_iota(
        jnp.int32, cb.shape, 1)


def _fwd_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, y_ref, st_ref,
                s_acc):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_acc[:] = jnp.zeros_like(s_acc)

    dtype = x_ref.dtype
    r, p = s_acc.shape[:2]
    cb, c32, lower = _group(b_ref, c_ref)
    for k in range(r):
        m, _, xdt, cg, _, _, fade, decay = _head(k, x_ref, rows_ref,
                                                 cols_ref, cb, c32, lower)
        s = s_acc[k]
        y_ref[0, k * p:(k + 1) * p] = (
            fa._dot(xdt.astype(dtype), m.astype(dtype), fa._NT)
            + fa._dot(s.astype(dtype), cg.astype(dtype), fa._NN)).astype(
                y_ref.dtype)
        s = s * decay + fa._dot((xdt * fade).astype(dtype), b_ref[0], fa._NT)
        s_acc[k] = s
        st_ref[0, 0, k] = s


def _bwd_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, st_ref, dy_ref,
                dx_ref, db_ref, dc_ref, drows_ref, dcols_ref, ds_acc, *,
                n_chunks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ds_acc[:] = jnp.zeros_like(ds_acc)

    dtype = x_ref.dtype
    r, p = ds_acc.shape[:2]
    cb, c32, lower = _group(b_ref, c_ref)
    is_last = lax.broadcasted_iota(jnp.int32, (1, cb.shape[0]), 1) \
        == cb.shape[0] - 1
    total = lambda v: jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=0,
                              keepdims=True)
    dcb = jnp.zeros_like(cb)
    db = jnp.zeros_like(c32)
    dc = jnp.zeros_like(c32)
    for k in range(r):
        m, decays, xdt, cg, dt, grown, fade, decay = _head(
            k, x_ref, rows_ref, cols_ref, cb, c32, lower)
        # the state that entered this chunk: the chunk before's end, or zero
        s = jnp.where(j < n_chunks - 1, st_ref[0, 0, k], 0.0)
        ds = ds_acc[k]              # the cotangent of this chunk's END state
        dsd = ds.astype(dtype)
        dy = dy_ref[0, k * p:(k + 1) * p]
        dm = fa._dot(dy, xdt.astype(dtype), fa._TN)
        dcg = fa._dot(s.astype(dtype), dy, fa._TN)
        dxd = fa._dot(dsd, b_ref[0], fa._NN)
        dxdt = fa._dot(dy, m.astype(dtype), fa._NN) + dxd * fade
        dcb += dm * decays
        db += fa._dot(dsd, (xdt * fade).astype(dtype), fa._TN)
        dc += dcg * grown
        dx_ref[0, k * p:(k + 1) * p] = (dxdt * dt).astype(dx_ref.dtype)
        x = x_ref[0, k * p:(k + 1) * p].astype(jnp.float32)
        drows_ref[0, k:k + 1] = jnp.sum(dxdt * x, axis=0, keepdims=True)
        # gamma's cotangent: as the j of L, of exp(gamma) and of the fade,
        # at the chunk's last token what the fade and the decay add (rows),
        # and as the i of L (a column)
        pairs = dm * m
        dfade = jnp.sum(dxd * xdt, axis=0, keepdims=True) * fade
        dlast = total(dfade) + total(ds * s * decay)
        drows_ref[0, r + k:r + k + 1] = (
            jnp.sum(dcg * cg, axis=0, keepdims=True) - dfade
            - jnp.sum(pairs, axis=0, keepdims=True)
            + jnp.where(is_last, dlast, 0.0))
        dcols_ref[0, :, k:k + 1] = jnp.sum(pairs, axis=1, keepdims=True)
        ds_acc[k] = ds * decay + fa._dot(dy, cg.astype(dtype), fa._NT)
    dcb = dcb.astype(dtype)
    db_ref[0] = (db + fa._dot(c_ref[0], dcb, fa._NN)).astype(db_ref.dtype)
    dc_ref[0] = (dc + fa._dot(b_ref[0], dcb, fa._NT)).astype(dc_ref.dtype)


def _by_group(v, g):
    """[B, S, H] -> (token a lane [B * G, r, S], token a row [B * G, S, r])."""
    bsz, s, h = v.shape
    v = v.reshape(bsz, s, g, h // g)
    return (jnp.transpose(v, (0, 2, 3, 1)).reshape(bsz * g, h // g, s),
            jnp.transpose(v, (0, 2, 1, 3)).reshape(bsz * g, s, h // g))


def _operands(x, dt, gam, b, c):
    """The kernels' operands (x, B, C, rows, cols) of the model's arrays:
    channel-major, a token a lane."""
    bsz, s = x.shape[:2]
    g = b.shape[2]
    lanes = lambda y: jnp.swapaxes(y.reshape(bsz, s, -1), 1, 2)
    gam_rows, gam_cols = _by_group(gam, g)
    return (lanes(x), lanes(b), lanes(c),
            jnp.concatenate([_by_group(dt, g)[0], gam_rows], axis=1),
            gam_cols)


def _specs(x, b, chunk, at):
    """The BlockSpecs of (x, B or C, rows, cols, states), ``at`` the chunk
    of program (i, j): group i % G of batch row i // G."""
    h, p = x.shape[2:]
    g, sn = b.shape[2:]
    r = h // g
    return (pl.BlockSpec((1, r * p, chunk),
                         lambda i, j: (i // g, i % g, at(j))),
            pl.BlockSpec((1, sn, chunk), lambda i, j: (i // g, i % g, at(j))),
            pl.BlockSpec((1, 2 * r, chunk), lambda i, j: (i, 0, at(j))),
            pl.BlockSpec((1, chunk, r), lambda i, j: (i, at(j), 0)),
            lambda of: pl.BlockSpec((1, 1, r, p, sn),
                                    lambda i, j: (i, of(j), 0, 0, 0)))


def _tokens_first(y, shape):
    """[B, channels, S] -> ``shape`` [B, S, ...]."""
    return jnp.swapaxes(y, 1, 2).reshape(shape)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward(x, dt, gam, b, c, chunk, interpret):
    bsz, s, h, p = x.shape
    g, sn = b.shape[2:]
    r, n = h // g, s // chunk
    wide, group, rows, cols, state = _specs(x, b, chunk, lambda j: j)
    y, states = pl.pallas_call(
        _fwd_kernel,
        grid=(bsz * g, n),
        in_specs=[wide, group, group, rows, cols],
        out_specs=(wide, state(lambda j: j)),
        out_shape=(jax.ShapeDtypeStruct((bsz, h * p, s), x.dtype),
                   jax.ShapeDtypeStruct((bsz * g, n, r, p, sn), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((r, p, sn), jnp.float32)],
        compiler_params=fa._compiler_params("parallel", "arbitrary"),
        interpret=interpret, name=FWD_NAME,
    )(*_operands(x, dt, gam, b, c))
    return _tokens_first(y, x.shape), states


@functools.partial(jax.jit, static_argnums=(7, 8))
def _backward(x, dt, gam, b, c, states, dy, chunk, interpret):
    bsz, s, h, p = x.shape
    g, sn = b.shape[2:]
    r, n = h // g, s // chunk
    # program j of a group works on chunk n - 1 - j
    wide, group, rows, cols, state = _specs(x, b, chunk,
                                            lambda j: n - 1 - j)
    flat = _operands(x, dt, gam, b, c)
    like = lambda y: jax.ShapeDtypeStruct(y.shape, y.dtype)
    dx, db, dc, drows, dcols = pl.pallas_call(
        functools.partial(_bwd_kernel, n_chunks=n),
        grid=(bsz * g, n),
        in_specs=[wide, group, group, rows, cols,
                  state(lambda j: jnp.maximum(n - 2 - j, 0)), wide],
        out_specs=(wide, group, group, rows, cols),
        out_shape=tuple(like(y) for y in flat),
        scratch_shapes=[pltpu.VMEM((r, p, sn), jnp.float32)],
        compiler_params=fa._compiler_params("parallel", "arbitrary"),
        interpret=interpret, name=BWD_NAME,
    )(*flat, states, jnp.swapaxes(
        dy.astype(x.dtype).reshape(bsz, s, h * p), 1, 2))
    # back to [B, S, H]
    heads = lambda y: jnp.transpose(y.reshape(bsz, g, r, s),
                                    (0, 3, 1, 2)).reshape(bsz, s, h)
    dgam = heads(drows[:, r:]) + jnp.transpose(
        dcols.reshape(bsz, g, s, r), (0, 2, 1, 3)).reshape(bsz, s, h)
    return (_tokens_first(dx, x.shape), heads(drows[:, :r]), dgam,
            _tokens_first(db, b.shape), _tokens_first(dc, c.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan_kernels(x, dt, gam, b, c, chunk, interpret):
    """x [B, S, H, P], the steps dt and their cumulative log decay inside a
    chunk gam [B, S, H] float32, b, c [B, S, G, N] (x's dtype), S a whole
    number of chunks -> (Y [B, S, H, P] in x's dtype, chunk-end states
    [B * G, n, H // G, P, N] float32)."""
    return _scan_fwd(x, dt, gam, b, c, chunk, interpret)[0]


def _scan_fwd(x, dt, gam, b, c, chunk, interpret):
    """The two the kernel made carry ``SAVED_UNDER_REMAT``'s names, here
    inside the rule (as ops/gated_delta.py's); the rest of the residuals are
    the rule's own arguments: a layer under remat that saves the two
    rebuilds nothing of the scan in its backward and holds no second
    ``ssd_fwd``."""
    y, states = _forward(x, dt, gam, b, c, chunk, interpret)
    y, states = (checkpoint_name(v, name)
                 for v, name in zip((y, states), SAVED_UNDER_REMAT))
    return (y, states), (x, dt, gam, b, c, states)


def _scan_bwd(chunk, interpret, res, g):
    # the states leave the rule for a counter alone: no cotangent is read
    return _backward(*res, g[0], chunk, interpret)


_scan_kernels.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, a_log, b, c, d, *, chunk=CHUNK, use_kernel=None):
    """x [B, S, H, P], dt [B, S, H] float32 (the step after its softplus,
    > 0), a_log [H] (A = -exp(a_log)), b, c [B, S, G, N] (head h reads group
    h // (H // G)), d [H] (the skip) -> (y [B, S, H, P] in x's dtype,
    {"chunk_log_decay_min": the most negative cumulative log decay a chunk
    reached, "state_absmax": the largest |S| at a chunk's end}, float32
    scalars that carry no gradient). A sequence that is no whole number of
    chunks is padded with tokens of step 0, which neither write nor decay.
    ``use_kernel``: None = the kernels on a TPU, the plain path elsewhere."""
    bsz, s, h, p = x.shape
    g, sn = b.shape[2:]
    if h % g or b.shape != c.shape or b.shape[:2] != (bsz, s) \
            or dt.shape != (bsz, s, h) or a_log.shape != (h,) \
            or d.shape != (h,):
        raise ValueError("ssd scan: x %s dt %s a_log %s b %s c %s d %s"
                         % (x.shape, dt.shape, a_log.shape, b.shape, c.shape,
                            d.shape))
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    pad = -s % chunk
    n = (s + pad) // chunk
    f32 = jnp.float32
    a = -jnp.exp(a_log.astype(f32))

    def padded(y):
        return jnp.pad(y, ((0, 0), (0, pad)) + ((0, 0),) * (y.ndim - 2))

    if use_kernel:
        steps = padded(dt.astype(f32))
        gam = jnp.cumsum((steps * a).reshape(bsz, n, chunk, h), axis=2)
        y, states = _scan_kernels(
            padded(x), steps, gam.reshape(steps.shape),
            padded(b.astype(x.dtype)), padded(c.astype(x.dtype)), chunk,
            jax.default_backend() == "cpu")
        y, log_decay = y[:, :s], gam[:, :, -1]
    else:
        def chunks(y):
            """[B, S, h, ...] -> [B, h, n, chunk, ...]."""
            y = jnp.moveaxis(padded(y), 2, 1)
            return y.reshape(y.shape[:2] + (n, chunk) + y.shape[3:])

        bc = chunks(b.astype(x.dtype))
        m, xdt, cg, xd, decay, log_decay = chunk_operands(
            chunks(x), chunks(dt.astype(f32)), a, bc,
            chunks(c.astype(x.dtype)))
        y, states = _scan_plain(m, xdt, cg, xd, bc, decay)
        y = jnp.moveaxis(y.reshape(bsz, h, n * chunk, p), 1, 2)[:, :s]
    y = (y.astype(f32) + d.astype(f32)[:, None] * x.astype(f32)).astype(
        x.dtype)
    stats = {"chunk_log_decay_min": jnp.min(log_decay),
             "state_absmax": jnp.max(jnp.abs(states))}
    return y, jax.tree_util.tree_map(lax.stop_gradient, stats)
