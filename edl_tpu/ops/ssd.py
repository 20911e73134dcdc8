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

What is local to a chunk (gamma, C B^T — formed ONCE A GROUP —, its product
with a head's L, the three scaled copies of x and C, D x) is plain
``jax.numpy`` in every path and is differentiated by JAX. What is sequential
— Y and S' from S, chunk after chunk, and in reverse for the gradient — is,
on a TPU, two Pallas kernels:

- ``ssd_fwd``: one program a (head, chunk), the state in VMEM in float32
  across the chunks of a head; writes the result and every chunk-end state;
- ``ssd_bwd``: the same chunks from the last to the first, the state's
  cotangent in VMEM.

B is never copied a head: both kernels read the group's block through their
index maps, and ``ssd_bwd`` writes a head's part of B's cotangent, which is
summed over the group's heads outside. C enters as exp(gamma) * C, which is a
head's own. bf16 operands into the MXU (the operands as they arrive),
float32 accumulation, float32 state and decays. Elsewhere (the CPU) the
sequential part is a ``lax.scan`` over the chunks in ``jax.numpy``,
differentiated by JAX; ``use_kernel=True`` forces the kernels (on the CPU in
the Pallas interpreter, for tests).
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

def _fwd_kernel(m_ref, xdt_ref, cg_ref, xd_ref, b_ref, dec_ref, y_ref, st_ref,
                s_acc):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_acc[:] = jnp.zeros_like(s_acc)

    s = s_acc[:]
    sd = s.astype(xdt_ref.dtype)
    y_ref[0] = (fa._dot(m_ref[0], xdt_ref[0], fa._NN)
                + fa._dot(cg_ref[0], sd, fa._NT)).astype(y_ref.dtype)
    s = s * dec_ref[0] + fa._dot(xd_ref[0], b_ref[0], fa._TN)
    s_acc[:] = s
    st_ref[0] = s


def _bwd_kernel(m_ref, xdt_ref, cg_ref, xd_ref, b_ref, dec_ref, st_ref,
                dy_ref, dm_ref, dxdt_ref, dcg_ref, dxd_ref, db_ref, ddec_ref,
                ds_acc, *, n_chunks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ds_acc[:] = jnp.zeros_like(ds_acc)

    dtype = xdt_ref.dtype
    # the state that entered this chunk: the chunk before's end, or zero
    s = jnp.where(j < n_chunks - 1, st_ref[0], 0.0)
    sd = s.astype(dtype)
    ds = ds_acc[:]                  # the cotangent of this chunk's END state
    dsd = ds.astype(dtype)
    dy = dy_ref[0]
    dm_ref[0] = fa._dot(dy, xdt_ref[0], fa._NT).astype(dtype)
    dxdt_ref[0] = fa._dot(m_ref[0], dy, fa._TN).astype(dtype)
    dcg_ref[0] = fa._dot(dy, sd, fa._NN).astype(dtype)
    dxd_ref[0] = fa._dot(b_ref[0], dsd, fa._NT).astype(dtype)
    db_ref[0] = fa._dot(xd_ref[0], dsd, fa._NN).astype(dtype)
    ddec_ref[0] = jnp.sum(ds * s, axis=0, keepdims=True)
    ds_acc[:] = ds * dec_ref[0] + fa._dot(dy, cg_ref[0], fa._TN)


def _rows(x):
    """[B, h, n, r, d] -> [B * h, n * r, d]: a chunk is a block of rows."""
    return x.reshape(x.shape[0] * x.shape[1], x.shape[2] * x.shape[3],
                     x.shape[4])


def _lanes(decay, width):
    """decay [B, H, n] -> [B * H * n, 1, width]: one row of lanes a chunk."""
    return jnp.broadcast_to(decay.reshape(-1, 1, 1), (decay.size, 1, width))


@functools.partial(jax.jit, static_argnums=(6,))
def _forward(m, xdt, cg, xd, b, decay, interpret):
    bsz, h, n, c, p = xdt.shape
    g, sn = b.shape[1], b.shape[-1]
    r = h // g
    at = lambda rows, d: pl.BlockSpec((1, rows, d), lambda i, j: (i, j, 0))
    y, states = pl.pallas_call(
        _fwd_kernel,
        grid=(bsz * h, n),
        in_specs=[at(c, c), at(c, p), at(c, sn), at(c, p),
                  # the group's B: head i of the flattened (batch, head)
                  # reads group i // r of the flattened (batch, group)
                  pl.BlockSpec((1, c, sn), lambda i, j: (i // r, j, 0)),
                  pl.BlockSpec((1, 1, sn), lambda i, j: (i * n + j, 0, 0))],
        out_specs=(at(c, p), at(p, sn)),
        out_shape=(jax.ShapeDtypeStruct((bsz * h, n * c, p), xdt.dtype),
                   jax.ShapeDtypeStruct((bsz * h, n * p, sn), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((p, sn), jnp.float32)],
        compiler_params=fa._compiler_params("parallel", "arbitrary"),
        interpret=interpret, name=FWD_NAME,
    )(_rows(m), _rows(xdt), _rows(cg), _rows(xd), _rows(b),
      _lanes(decay, sn))
    return y.reshape(xdt.shape), states.reshape(bsz, h, n, p, sn)


@functools.partial(jax.jit, static_argnums=(8,))
def _backward(m, xdt, cg, xd, b, decay, states, dy, interpret):
    bsz, h, n, c, p = xdt.shape
    g, sn = b.shape[1], b.shape[-1]
    r = h // g
    # program j of a head works on chunk n - 1 - j
    at = lambda rows, d: pl.BlockSpec((1, rows, d),
                                      lambda i, j: (i, n - 1 - j, 0))
    lanes = pl.BlockSpec((1, 1, sn), lambda i, j: (i * n + n - 1 - j, 0, 0))
    like = lambda x: jax.ShapeDtypeStruct(_rows(x).shape, x.dtype)
    dm, dxdt, dcg, dxd, db, ddec = pl.pallas_call(
        functools.partial(_bwd_kernel, n_chunks=n),
        grid=(bsz * h, n),
        in_specs=[at(c, c), at(c, p), at(c, sn), at(c, p),
                  pl.BlockSpec((1, c, sn), lambda i, j: (i // r, n - 1 - j,
                                                         0)),
                  lanes,
                  pl.BlockSpec((1, p, sn), lambda i, j: (
                      i, jnp.maximum(n - 2 - j, 0), 0)),
                  at(c, p)],
        out_specs=(at(c, c), at(c, p), at(c, sn), at(c, p), at(c, sn),
                   lanes),
        out_shape=(like(m), like(xdt), like(cg), like(xd), like(cg),
                   jax.ShapeDtypeStruct((bsz * h * n, 1, sn), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((p, sn), jnp.float32)],
        compiler_params=fa._compiler_params("parallel", "arbitrary"),
        interpret=interpret, name=BWD_NAME,
    )(_rows(m), _rows(xdt), _rows(cg), _rows(xd), _rows(b),
      _lanes(decay, sn), _rows(states), _rows(dy.astype(xdt.dtype)))
    # a head's part of its group's cotangent, added up in float32
    db = db.reshape(bsz, g, r, n, c, sn).astype(jnp.float32).sum(axis=2)
    return (dm.reshape(m.shape), dxdt.reshape(xdt.shape),
            dcg.reshape(cg.shape), dxd.reshape(xd.shape), db.astype(b.dtype),
            ddec.sum(axis=(1, 2)).reshape(decay.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_kernels(m, xdt, cg, xd, b, decay, interpret):
    return _scan_fwd(m, xdt, cg, xd, b, decay, interpret)[0]


def _scan_fwd(m, xdt, cg, xd, b, decay, interpret):
    """The two the kernel made carry ``SAVED_UNDER_REMAT``'s names, here
    inside the rule (as ops/gated_delta.py's): a layer under remat that
    saves them rebuilds the chunk-local operands in its backward and holds
    no second ``ssd_fwd``."""
    y, states = _forward(m, xdt, cg, xd, b, decay, interpret)
    y, states = (checkpoint_name(x, name)
                 for x, name in zip((y, states), SAVED_UNDER_REMAT))
    return (y, states), (m, xdt, cg, xd, b, decay, states)


def _scan_bwd(interpret, res, g):
    # the states leave the rule for a counter alone: no cotangent is read
    return _backward(*res, g[0], interpret)


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

    def chunks(y):
        """[B, S, h, ...] -> [B, h, n, chunk, ...]."""
        y = jnp.pad(y, ((0, 0), (0, pad)) + ((0, 0),) * (y.ndim - 2))
        y = jnp.moveaxis(y, 2, 1)
        return y.reshape(y.shape[:2] + (n, chunk) + y.shape[3:])

    bc = chunks(b.astype(x.dtype))
    m, xdt, cg, xd, decay, log_decay = chunk_operands(
        chunks(x), chunks(dt.astype(f32)), -jnp.exp(a_log.astype(f32)), bc,
        chunks(c.astype(x.dtype)))
    if use_kernel:
        y, states = _scan_kernels(m, xdt, cg, xd, bc, decay,
                                  jax.default_backend() == "cpu")
    else:
        y, states = _scan_plain(m, xdt, cg, xd, bc, decay)
    y = jnp.moveaxis(y.reshape(bsz, h, n * chunk, p), 1, 2)[:, :s]
    y = (y.astype(f32) + d.astype(f32)[:, None] * x.astype(f32)).astype(
        x.dtype)
    stats = {"chunk_log_decay_min": jnp.min(log_decay),
             "state_absmax": jnp.max(jnp.abs(states))}
    return y, jax.tree_util.tree_map(lax.stop_gradient, stats)
