"""Attention under the two-stream block mask of training by diffusion over
blocks (BD3-LM, arXiv:2503.09573): each sequence of T tokens runs through
the layers twice in one stream of 2T rows, a noised copy x_t beside the
clean copy x_0, ``[x_t ; x_0]``, in blocks of ``block_length`` tokens. With
blk(i) = (i mod T) // block_length, a query reads

- noised query, clean key:   blk(key) <  blk(query)  (earlier blocks)
- noised query, noised key:  blk(key) == blk(query)  (its own block, both ways)
- clean query,  clean key:   blk(key) <= blk(query)  (block-causal)
- clean query,  noised key:  never

which is T^2 + T * block_length pairs a sequence of the (2T)^2 a dense mask
scores. That is no band of one diagonal: the tiles a query tile reaches are
a band over the CLEAN keys (the staircase of the block length on its
diagonal tile, strict for a noised query and inclusive for a clean one) and,
for a noised query tile, ONE tile of noised keys (block-diagonal). So the
band kernels of ops/flash_attention.py stay causal, and these two kernels
have names of their own:

- ``bdiff_fwd``: one program a query tile; the clean half of k and v whole
  in VMEM (what ``_RESIDENT_KV_BYTES`` bounds), the query tile's own tile
  of noised keys beside it; the clean tiles before the diagonal with no
  mask, the diagonal tile and the own tile with theirs, all in one online
  softmax. Writes the result, lse, and the pairs each row attended (what
  the mask it applied kept: the model's ``pairs_attended`` counter);
- ``bdiff_bwd``: the same visits, FlashAttention-2's backward; dk and dv of
  both halves accumulate in float32 in VMEM over the query tiles of every
  query head of the kv head (a clean key tile gathers from both query
  halves, a noised one from its own diagonal tile alone).

The tile arithmetic, the layouts and the conventions (operands as they
arrive, float32 accumulation, scale on the float32 scores) are
ops/flash_attention.py's. ``streams`` = (block_length, clean_from) is the
mask as data of the call; ``clean_from`` is where the clean half begins
and has to be half the stream.
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
FWD_NAME = "bdiff_fwd"
BWD_NAME = "bdiff_bwd"

#: `checkpoint_name`s of what ``bdiff_fwd`` leaves its backward, the result
#: (model layout) and lse: a layer rematerialised under a policy that saves
#: them runs the forward kernel once a step (why and at what cost:
#: ``_attend_fwd``)
SAVED_UNDER_REMAT = ("attn.block_diffusion_out", "attn.block_diffusion_lse")


def check_streams(streams, seq_len):
    """(block_length, clean_from) of a stream of ``seq_len`` rows, or
    ValueError: the clean half is the second half, in whole blocks."""
    block_length, clean_from = (int(x) for x in streams)
    if block_length < 1 or 2 * clean_from != seq_len \
            or clean_from % block_length:
        raise ValueError(
            "two-stream block mask: a stream of %d rows needs its clean half "
            "to begin at %d (got %d), in whole blocks of %d"
            % (seq_len, seq_len // 2, clean_from, block_length))
    return block_length, clean_from


def stream_mask(q_idx, k_idx, block_length, clean_from):
    """[q, k] bool from the four rules, for stream indexes ``q_idx`` [q] and
    ``k_idx`` [k] (plain jnp: the dense path and the tests)."""
    qn, kn = q_idx[:, None] < clean_from, k_idx[None, :] < clean_from
    qb = (q_idx[:, None] % clean_from) // block_length
    kb = (k_idx[None, :] % clean_from) // block_length
    return jnp.where(kn, jnp.logical_and(qn, kb == qb),
                     jnp.where(qn, kb < qb, kb <= qb))


def pairs_of(seq_len, block_length):
    """Pairs one sequence of ``seq_len`` clean tokens attends."""
    return seq_len * seq_len + seq_len * block_length


def _tile(clean_from):
    return fa._tile_edge(clean_from, fa._BLOCK)


def kernel_reason(seq_len, head_dim, streams, itemsize=2):
    """Why the kernels refuse this stream (the dense path then runs), or
    None: whole tiles of whole blocks, and the clean half of k and v
    resident in VMEM."""
    block_length, clean_from = streams
    tile = _tile(clean_from)
    if clean_from % tile:
        return ("two-stream block mask: %d clean tokens are no whole number "
                "of %d-wide tiles" % (clean_from, tile))
    if tile % block_length:
        return ("two-stream block mask: block length %d does not divide "
                "the %d-wide tile" % (block_length, tile))
    if 2 * clean_from * head_dim * itemsize > fa._RESIDENT_KV_BYTES:
        return ("two-stream block mask: the clean half's k + v (%d bytes) "
                "pass the kernels' resident limit"
                % (2 * clean_from * head_dim * itemsize))
    return None


def tiles_visited(n_tiles):
    """[(query tile, key tile, kind)] of one query head's run of the stream,
    in tiles of the stream (noised 0 .. n_tiles - 1, clean after): what the
    kernels' loops visit. kind: "inner" builds no mask, "stair" the
    diagonal's staircase, "own" the block-diagonal."""
    out = []
    for j in range(2 * n_tiles):
        it = j % n_tiles
        out += [(j, n_tiles + ki, "inner") for ki in range(it)]
        out.append((j, n_tiles + it, "stair"))
        if j < n_tiles:
            out.append((j, it, "own"))
    return out


def _visits(block, n_t, block_length):
    """What a program knows of its query tile: (its tile of the half, 1 for
    a noised tile else 0, the block of each row [T, 1] and of each key
    [1, T] inside a tile). A tile holds whole blocks, so the blocks of a
    tile's rows and of a diagonal or own tile's keys compare as they are."""
    js = lax.rem(pl.program_id(1), 2 * n_t)
    qb = lax.broadcasted_iota(jnp.int32, (block, 1), 0) // block_length
    kb = lax.broadcasted_iota(jnp.int32, (1, block), 1) // block_length
    return lax.rem(js, n_t), (js < n_t).astype(jnp.int32), qb, kb


def _count(keep):
    return jnp.sum(keep.astype(jnp.float32), axis=-1, keepdims=True)


def _fwd_kernel(q_ref, kc_ref, vc_ref, kn_ref, vn_ref, o_ref, lse_ref,
                cnt_ref, *, block, n_t, block_length, sm_scale):
    it, noised, qb, kb = _visits(block, n_t, block_length)
    q = q_ref[0]

    def clean(ki, carry, keep=None):
        k_lo = pl.multiple_of(ki * block, block)
        return fa._softmax_update(q, kc_ref[0, pl.ds(k_lo, block), :],
                                  vc_ref[0, pl.ds(k_lo, block), :], carry,
                                  keep, sm_scale)

    carry = (jnp.zeros(q.shape, jnp.float32),
             jnp.full((block, 1), fa._NEG_INF, jnp.float32),
             jnp.zeros((block, 1), jnp.float32))
    carry = lax.fori_loop(0, it, clean, carry)
    stair = kb <= qb - noised
    carry = clean(it, carry, stair)
    cnt = (it * block).astype(jnp.float32) + _count(stair)
    own = kb == qb

    def own_tile(_, args):          # a loop of one trip or none
        return (fa._softmax_update(q, kn_ref[0], vn_ref[0], args[0], own,
                                   sm_scale), args[1] + _count(own))

    (acc, m, l), cnt = lax.fori_loop(0, noised, own_tile, (carry, cnt))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = fa._flip(m + jnp.log(l))
    cnt_ref[0] = fa._flip(cnt)


def _bwd_kernel(q_ref, kc_ref, vc_ref, kn_ref, vn_ref, do_ref, lse_ref,
                delta_ref, dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, block,
                n_t, block_length, sm_scale):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    it, noised, qb, kb = _visits(block, n_t, block_length)
    q, do = q_ref[0], do_ref[0]
    lse, delta = fa._flip(lse_ref[0]), fa._flip(delta_ref[0])
    clean_from = n_t * block

    def tile(k, v, row_lo, dq, keep):
        """One visited tile: its dq, and its dk and dv added to the
        accumulators' rows from ``row_lo`` of the stream."""
        p, ds = fa._p_and_ds_kept(q, k, do, v, lse, delta, keep, sm_scale)
        ds = ds.astype(k.dtype)
        dk_acc[pl.ds(row_lo, block), :] += fa._dot(ds, q, fa._TN)
        dv_acc[pl.ds(row_lo, block), :] += fa._dot(p.astype(do.dtype), do,
                                                   fa._TN)
        return dq + fa._dot(ds, k, fa._NN)

    def clean(ki, dq, keep=None):
        k_lo = pl.multiple_of(ki * block, block)
        return tile(kc_ref[0, pl.ds(k_lo, block), :],
                    vc_ref[0, pl.ds(k_lo, block), :],
                    pl.multiple_of(clean_from + k_lo, block), dq, keep)

    dq = lax.fori_loop(0, it, clean, jnp.zeros(q.shape, jnp.float32))
    dq = clean(it, dq, kb <= qb - noised)
    dq = lax.fori_loop(
        0, noised,
        lambda _, dq: tile(kn_ref[0], vn_ref[0],
                           pl.multiple_of(it * block, block), dq, kb == qb),
        dq)
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _specs(block, n_t, d):
    """(a query tile, its row statistics, the clean half whole, the query
    tile's own tile of the noised half) of [bh, rows, d] arrays."""
    clean_from = n_t * block
    return (pl.BlockSpec((1, block, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, clean_from, d), lambda i, j: (i, 1, 0)),
            pl.BlockSpec((1, block, d), lambda i, j: (i, lax.rem(j, n_t), 0)))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _forward(q, k, v, sm_scale, block_length, interpret):
    """q [b, h, group * 2T, d] (the query heads of kv head h, one run of the
    stream after another), k, v [b, h, 2T, d] -> (out as q, lse and the
    pairs each row attended [b * h, 1, group * 2T] float32)."""
    b, h, rows, d = q.shape
    s = k.shape[2]
    block = _tile(s // 2)
    n_t = s // 2 // block
    bh = b * h
    q_tile, q_stats, half, own = _specs(block, n_t, d)
    kf, vf = k.reshape(bh, s, d), v.reshape(bh, s, d)
    stats = jax.ShapeDtypeStruct((bh, 1, rows), jnp.float32)
    out, lse, cnt = pl.pallas_call(
        functools.partial(_fwd_kernel, block=block, n_t=n_t,
                          block_length=block_length, sm_scale=sm_scale),
        grid=(bh, rows // block),
        in_specs=[q_tile, half, half, own, own],
        out_specs=(q_tile, q_stats, q_stats),
        out_shape=(jax.ShapeDtypeStruct((bh, rows, d), q.dtype), stats,
                   stats),
        compiler_params=fa._compiler_params("parallel", "parallel"),
        interpret=interpret, name=FWD_NAME,
    )(q.reshape(bh, rows, d), kf, vf, kf, vf)
    return out.reshape(b, h, rows, d), lse, cnt


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _backward(q, k, v, lse, delta, g, sm_scale, block_length, interpret):
    b, h, rows, d = q.shape
    s = k.shape[2]
    block = _tile(s // 2)
    n_t = s // 2 // block
    bh = b * h
    q_tile, q_stats, half, own = _specs(block, n_t, d)
    whole = pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0))
    kf, vf = k.reshape(bh, s, d), v.reshape(bh, s, d)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, block=block, n_t=n_t,
                          block_length=block_length, sm_scale=sm_scale),
        grid=(bh, rows // block),
        in_specs=[q_tile, half, half, own, own, q_tile, q_stats, q_stats],
        out_specs=(q_tile, whole, whole),
        out_shape=(jax.ShapeDtypeStruct((bh, rows, d), q.dtype),
                   jax.ShapeDtypeStruct(kf.shape, k.dtype),
                   jax.ShapeDtypeStruct(vf.shape, v.dtype)),
        scratch_shapes=[pltpu.VMEM((s, d), jnp.float32),
                        pltpu.VMEM((s, d), jnp.float32)],
        compiler_params=fa._compiler_params("parallel", "arbitrary"),
        interpret=interpret, name=BWD_NAME,
    )(q.reshape(bh, rows, d), kf, vf, kf, vf,
      g.astype(q.dtype).reshape(bh, rows, d), lse,
      delta.reshape(bh, 1, rows))
    return (dq.reshape(b, h, rows, d), dk.reshape(b, h, s, d),
            dv.reshape(b, h, s, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attend(q, k, v, sm_scale, block_length, interpret):
    return _attend_fwd(q, k, v, sm_scale, block_length, interpret)[0]


def _attend_fwd(q, k, v, sm_scale, block_length, interpret):
    """Model layout in and out, and so the residuals (as
    flash_attention._attend_fwd with ``seq_major``). The two the kernel
    made, out and lse, carry ``SAVED_UNDER_REMAT``'s names, here inside the
    `custom_vjp`'s rule (a name on the layer's context outside it would
    keep the context and still rerun the kernel for lse): a layer under
    remat that saves them keeps 34 MB at a 16384-row stream of 8 heads of
    128 and its recomputation holds no ``bdiff_fwd`` (2.7 ms a call there),
    with gradients bit for bit those of a second, deterministic, call. The
    count is a result of the one forward call alone."""
    group = q.shape[2] // k.shape[2]
    out, lse, cnt = _forward(
        fa._kernel_layout(q, group), fa._kernel_layout(k),
        fa._kernel_layout(v), sm_scale, block_length, interpret)
    out, lse = (checkpoint_name(x, n) for x, n in zip(
        (fa._model_layout(out, group), lse), SAVED_UNDER_REMAT))
    b, s, heads = q.shape[:3]
    # every query head counts the same pairs: their mean, one count a row
    pairs = cnt.reshape(b, heads, s).mean(axis=1)
    return (out, pairs), (q, k, v, out, lse)


def _attend_bwd(sm_scale, block_length, interpret, res, g):
    q, k, v, out, lse = res
    g = g[0]                        # the count carries no gradient
    group = q.shape[2] // k.shape[2]
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    q, k, v = lax.optimization_barrier((q, k, v))
    dq, dk, dv = _backward(
        fa._kernel_layout(q, group), fa._kernel_layout(k),
        fa._kernel_layout(v), lse,
        fa._kernel_layout(delta[..., None], group)[..., 0],
        fa._kernel_layout(g, group), sm_scale, block_length, interpret)
    return (fa._model_layout(dq, group), fa._model_layout(dk),
            fa._model_layout(dv))


_attend.defvjp(_attend_fwd, _attend_bwd)


def attend(q, k, v, streams, sm_scale=None, interpret=False):
    """The kernels: q [b, 2T, heads, d], k, v [b, 2T, kv_heads, d] (query
    head i reads kv head i // group; K and V are never repeated) ->
    (context as q, pairs [b, 2T] float32: the keys each row read, as the
    forward kernel counted them)."""
    block_length, clean_from = check_streams(streams, q.shape[1])
    reason = kernel_reason(q.shape[1], q.shape[-1],
                           (block_length, clean_from), k.dtype.itemsize)
    if reason or k.shape[1] != q.shape[1] or q.shape[2] % k.shape[2]:
        raise ValueError(reason or "two-stream block mask: q %s against k %s"
                         % (q.shape, k.shape))
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _attend(q, k, v, sm_scale, block_length, interpret)


def dense_attend(q, k, v, streams, sm_scale=None):
    """The same from the whole [2T, 2T] mask, float32 scores: the CPU's
    path and that of shapes the kernels refuse."""
    block_length, clean_from = check_streams(streams, q.shape[1])
    b, s, heads, d = q.shape
    kv_heads = k.shape[2]
    group = heads // kv_heads
    if sm_scale is None:
        sm_scale = d ** -0.5
    idx = jnp.arange(s)
    keep = stream_mask(idx, idx, block_length, clean_from)
    qg = (q * sm_scale).astype(jnp.float32).reshape(b, s, kv_heads, group, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    pairs = jnp.broadcast_to(keep.sum(-1).astype(jnp.float32)[None], (b, s))
    return out.reshape(b, s, heads, d), pairs
