"""Pallas grouped matrix product for TPU: rows sorted by group, each row
tile multiplied by its own group's matrix — the expert layer's products
(``parallel/moe.py:held_experts_ffn``) without multiplying any row by an
expert it was not routed to.

The caller lays the rows out so that every tile of ``tm`` rows belongs to
ONE group (each group padded to whole tiles, at least one), and says which
(``tile_group``) and how many tiles are in use (``n_used``). Tiles past
``n_used`` cost neither a product nor a fetch: their block indices repeat
the last used tile's, and they write zeros. Consecutive tiles of one group
read the same block of its matrix, which therefore stays in VMEM. Shapes
are static whatever the routing, so nothing recompiles.

- ``moe_gmm``:  out[tile] = x[tile] @ w[tile_group[tile]]      (forward, dx)
- ``moe_tgmm``: dw[g] = sum over g's tiles of x[tile]^T @ dy[tile]   (dw)
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the kernels' names in a device trace (the op class lib/xplane.py shows)
GMM_NAME = "moe_gmm"
TGMM_NAME = "moe_tgmm"


def tile_of(n, cap=1024, unit=128):
    """Largest multiple of ``unit`` (128: a block's lanes) that divides
    ``n`` and is at most ``cap``; ``n`` itself where there is none (a block
    may span a whole axis)."""
    for t in range(cap - cap % unit, 0, -unit):
        if n % t == 0:
            return t
    return n


#: bytes of one float32 block of ``moe_tgmm``'s result (it is held twice)
_TGMM_OUT_BYTES = 4 << 20


def _gmm_kernel(tile_group_ref, n_used_ref, x_ref, w_ref, o_ref):
    del tile_group_ref
    used = pl.program_id(1) < n_used_ref[0]

    @pl.when(used)
    def _product():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(used))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)


#: the grouped product keeps a whole [k, tn] block of one group's matrix in
#: VMEM beside a [tm, k] tile of rows, both double-buffered
_GMM_VMEM_BYTES = 64 << 20


def _gmm(x, w, tile_group, n_used, tm, interpret):
    """Grid (column blocks, row tiles), row tiles innermost and k whole:
    consecutive tiles of one group read the same block of its matrix, so
    a group's matrix crosses from HBM once per column block, not once per
    row tile."""
    m, k = x.shape
    n = w.shape[2]
    tn = tile_of(n)
    last = lambda i, nu: jnp.minimum(i, nu[0] - 1)  # noqa: E731
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, m // tm),
            # a tile past the used ones repeats the last used tile's blocks
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, tg, nu: (last(i, nu), 0)),
                pl.BlockSpec((1, k, tn),
                             lambda j, i, tg, nu: (tg[last(i, nu)], 0, j))],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, tg, nu: (i, j))),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_GMM_VMEM_BYTES),
        interpret=interpret,
        name=GMM_NAME,
    )(tile_group, n_used, x, w)


def _tgmm_kernel(tile_group_ref, n_used_ref, xt_ref, dy_ref, o_ref):
    i = pl.program_id(2)
    used = i < n_used_ref[0]
    first = jnp.logical_or(
        i == 0, tile_group_ref[i] != tile_group_ref[jnp.maximum(i - 1, 0)])

    @pl.when(jnp.logical_and(used, first))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(used)
    def _product():
        o_ref[0] += jnp.dot(xt_ref[...], dy_ref[...],
                            preferred_element_type=jnp.float32)


def _tgmm(x, dy, tile_group, n_used, groups, tm, interpret):
    """dw [groups, k, n] float32; every group owns at least one tile, so
    every block of the result is written."""
    m, k = x.shape
    n = dy.shape[1]
    tn = tile_of(n)
    # a width that no multiple of 128 divides (1856) spans its axis as
    # lanes; as rows it is cut by the rows' own unit, and the rows are cut
    # finer where the lanes had to stay whole, so the result's block fits
    tk = tile_of(k, min(1024, _TGMM_OUT_BYTES // (4 * tn)),
                 128 if k % 128 == 0 else 16)
    last = lambda i, nu: jnp.minimum(i, nu[0] - 1)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, m // tm),
            in_specs=[
                pl.BlockSpec((tk, tm),
                             lambda a, b, i, tg, nu: (a, last(i, nu))),
                pl.BlockSpec((tm, tn),
                             lambda a, b, i, tg, nu: (last(i, nu), b))],
            out_specs=pl.BlockSpec(
                (1, tk, tn),
                lambda a, b, i, tg, nu: (tg[last(i, nu)], a, b))),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        interpret=interpret,
        name=TGMM_NAME,
        # rows, transposed once outside the kernel (as the TPU's
        # grouped-product kernels do): the product contracts over rows
    )(tile_group, n_used, x.T, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_matmul(x, w, tile_group, n_used, tm=512, interpret=False):
    """x [m, k] @ w[group of the row's tile] [groups, k, n] -> [m, n] in
    x's dtype (w is multiplied in it too), float32 accumulation; dw comes
    back in w's own dtype, from float32 sums. ``tile_group`` [m // tm]
    int32 (non-decreasing; every group at least once), ``n_used`` [1]
    int32: tiles in use, at least one; the others come out zero."""
    return _gmm(x, w.astype(x.dtype), tile_group, n_used, tm, interpret)


def _gm_fwd(x, w, tile_group, n_used, tm, interpret):
    return (_gmm(x, w.astype(x.dtype), tile_group, n_used, tm, interpret),
            (x, w, tile_group, n_used))


def _gm_bwd(tm, interpret, res, dy):
    x, w, tile_group, n_used = res
    dx = _gmm(dy, jnp.swapaxes(w, 1, 2).astype(dy.dtype), tile_group,
              n_used, tm, interpret)
    dw = _tgmm(x, dy, tile_group, n_used, w.shape[0], tm, interpret)
    return dx, dw.astype(w.dtype), None, None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)


def grouped_matmul_reference(x, w, tile_group, n_used, tm=512):
    """The same product in plain jax.numpy (tests): every tile against
    its group's matrix, unused tiles zero."""
    m = x.shape[0]
    xt = x.reshape(m // tm, tm, -1).astype(jnp.float32)
    out = jnp.einsum("tmk,tkn->tmn", xt, w[tile_group].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    live = jnp.arange(m // tm) < n_used[0]
    return jnp.where(live[:, None, None], out, 0.0).reshape(m, -1).astype(
        x.dtype)
