"""Pallas flash attention for TPU: blockwise online-softmax forward
kernels and FlashAttention-2's backward kernels.

The hot op of the decoder models (edl_tpu/models/gpt.py,
edl_tpu/models/sparse_decoder.py; models/bert.py runs it without a causal
mask) on the training path. Never materializes the [seq, seq] score
matrix, forward or backward:

- forward: a Pallas kernel gridded over (batch*heads, q_blocks) that loops
  over the band's kv blocks with k and v whole in VMEM, or, beyond
  ``_RESIDENT_KV_BYTES``, streams them through a third grid dimension.
  Online softmax; the result and, beside it, the row statistic
  lse = m + log(l), float32, four bytes a query row;
- backward: a custom_vjp whose residuals are (q, k, v, out, lse). Two
  Pallas kernels rebuild p = exp(scores - lse) tile by tile in VMEM: one
  gridded over q blocks accumulates dq over the band's kv blocks, one
  gridded over kv blocks accumulates dk and dv over the q blocks (of every
  query head of the kv head) whose band reaches it.

Forward and backward compute a score tile the same way. Products take their
operands in the inputs' dtype (bfloat16 in training) with float32
accumulation (``_dot``); the scale goes to the float32 scores, so the
forward's lse is built from the very expression the backward rebuilds p
from; scores, m, l, the accumulators, p and ds are float32, and p and ds
are rounded only where they enter a product. Tiles are ``_BLOCK`` (512)
wide, or its half or quarter by what divides the sequence (``_tile_edge``),
one tile for a short sequence. Of the band's tiles only those that straddle
the diagonal, the window's far edge or a padded tail build a mask
(``_kv_band``, ``_q_band``); those wholly inside it build none. The op
composes with jit/grad/remat and with the ring-attention sp layer
(edl_tpu/parallel/ring_attention.py), which shards the sequence BEFORE
attention is applied per shard.

Layout: q, k, v are [batch, heads, seq, head_dim]; v, and with it the
result, dO and dv, may have a width of its own beside q's and k's (latent
attention: a 192-wide score against 128-wide values): every kernel sizes
its q/k tiles from q and its v tiles from v, and the default scale is the
q/k width's. With grouped-query
attention k and v have fewer heads and q is [batch, kv_heads, group * seq,
head_dim] (the query heads of a kv head one after another: K/V are never
repeated); a causal ``window`` keeps a query's own position and the
``window - 1`` before it, and blocks outside the band are neither loaded
nor computed, in the forward and in the backward kernels (the streamed
forward alone loads them and skips their compute; ``mha`` takes
the model's [batch, seq, heads, dim] layout, does the regrouping, and
keeps its residuals in that layout). A learned SELECTION of single keys
(``select=``) is no band: ``mha`` and ``flash_attention`` hand it to the
masked kernels of ops/sparse_attention.py, which share this file's
conventions and helpers; without one, nothing here changes. The TWO-STREAM
BLOCK MASK of training by diffusion over blocks (``streams=``: a noised and
a clean copy of each sequence in one stream; a band over the clean keys
plus one tile of noised ones) is no band of one diagonal either: ``mha``
and ``flash_attention`` hand it to ops/block_diffusion_attention.py, whose
two kernels use this file's tile arithmetic (``_softmax_update``,
``_p_and_ds_kept``); it takes no window and no selection, and needs
``causal`` unset, the mask being the whole of what a query may read.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _q_block_index(n_q_seq):
    """Which block of the SEQUENCE this program's q rows are. With
    grouped-query heads the rows of one kv head hold its query heads one
    after another (``group`` runs of the sequence), so the row block wraps
    every ``n_q_seq`` blocks; with equal head counts it is the row block."""
    qi = pl.program_id(1)
    return qi if n_q_seq is None else lax.rem(qi, n_q_seq)


def _flip(x):
    """A row statistic moved between the sublanes and the lanes: [n, 1] ->
    [1, n] or back. Whole 128s go through a [128, n] transpose; any other
    n through an identity mask and a sum. Both are exact."""
    col = x.shape[1] == 1
    n = x.shape[0] if col else x.shape[1]
    if n % 128 == 0:
        if col:
            return jnp.broadcast_to(x, (n, 128)).T[:1]
        return jnp.broadcast_to(x, (128, n)).T[:, :1]
    eye = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, x, 0.0), axis=0 if col else 1,
                   keepdims=True)


# the tiles, forward and backward: [block, block] float32 scores and p (the
# backward's dp and ds too) live in VMEM only. While k and v fit VMEM whole
# (_RESIDENT_KV_BYTES) one kernel a pass loops over the band's kv blocks;
# beyond that the other side streams through the grid
_BLOCK = 512
_VMEM_LIMIT = 64 << 20
_RESIDENT_KV_BYTES = 4 << 20

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims):
    """Operands as they arrive (bfloat16 in training, float32 in the tight
    tests), float32 accumulation."""
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _tile_edge(s, widest):
    """The tile edge for a sequence of ``s``: ``widest`` (512), its half
    or its quarter, the first that divides it (wide tiles amortise the
    loop and the grid step; the band's edge wastes at most a tile's
    width), else one tile for a short sequence, else ``widest`` and a
    ragged tail (the backward zero-pads it)."""
    for block in (widest, widest // 2, widest // 4):
        if s % block == 0:
            return block
    return min(widest, s)


def _kv_band(xp, q_lo, block_q, block_k, n_k, causal, window, ragged):
    """For the q block that starts at position ``q_lo``: the kv blocks its
    band reaches are [first, last); those wholly inside the band, which
    need no mask, are [ufirst, ulast). ``xp`` is jnp inside a kernel or an
    index map and numpy where the wrapper sizes the grid."""
    q_hi = q_lo + block_q - 1
    first = ufirst = 0 * q_lo
    last = ulast = n_k + 0 * q_lo
    if ragged:                      # the tail of k and v past the sequence
        ulast = ulast - 1
    if causal:
        last = xp.minimum(q_hi // block_k + 1, n_k)
        ulast = (q_lo + 1) // block_k
    if window is not None:
        first = xp.maximum(q_lo - window + 1, 0) // block_k
        ufirst = (xp.maximum(q_hi - window + 1, 0) + block_k - 1) // block_k
    ufirst = xp.clip(ufirst, first, last)
    return first, ufirst, xp.clip(ulast, ufirst, last), last


def _edge_and_inner(idx, first, ufirst, ulast, last):
    """(tile is visited and needs a mask, tile is visited and needs none)"""
    visited = jnp.logical_and(idx >= first, idx < last)
    inner = jnp.logical_and(idx >= ufirst, idx < ulast)
    return (jnp.logical_and(visited, jnp.logical_not(inner)),
            jnp.logical_and(visited, inner))


def _band_mask(q_pos, k_pos, causal, window, kv_len):
    """Which pairs of an edge tile count, or None where all do: under
    ``causal`` no key ahead of the query and, with a window, none further
    back than ``window - 1``; ``kv_len`` set, no key of the tail past it."""
    keep = None
    if causal:
        keep = q_pos >= k_pos
        if window is not None:
            keep = jnp.logical_and(keep, q_pos - k_pos < window)
    if kv_len is not None:
        tail = k_pos < kv_len
        keep = tail if keep is None else jnp.logical_and(keep, tail)
    return keep


def _softmax_update(q, k, v, carry, keep, sm_scale):
    """One kv tile of the online softmax: (acc, m, l), float32, moved on
    by p = exp(scale * q.k^T - m). The scores are the expression
    ``_p_and_ds`` rebuilds p from; l sums the float32 p, which is rounded
    only where it enters p.v. ``keep`` [TQ, TK] is the tile's mask, or None
    for a tile that needs none (no compare and no select are built)."""
    acc, m, l = carry
    scores = _dot(q, k, _NT) * sm_scale                      # [TQ, TK]
    if keep is not None:
        scores = jnp.where(keep, scores, _NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)
    if keep is not None:            # a row the tile holds no key of
        p = jnp.where(keep, p, 0.0)
    correction = jnp.exp(m - m_new)
    return (acc * correction + _dot(p.astype(v.dtype), v, _NN), m_new,
            l * correction + p.sum(axis=-1, keepdims=True))


def _softmax_tile(q, k, v, carry, q_lo, k_lo, *, sm_scale, masked, causal,
                  window, kv_len):
    """``_softmax_update`` for a tile of the causal band. ``masked`` is
    static: a tile wholly inside the band builds no positions."""
    keep = None
    if masked:
        tq, tk = q.shape[0], k.shape[0]
        keep = _band_mask(
            q_lo + lax.broadcasted_iota(jnp.int32, (tq, 1), 0),
            k_lo + lax.broadcasted_iota(jnp.int32, (1, tk), 1),
            causal, window, kv_len)
    return _softmax_update(q, k, v, carry, keep, sm_scale)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, block_q, block_k, n_q_seq, n_k, band, tile):
    """One (bh, q_block, k_block) grid step. kv blocks stream through VMEM
    via the third grid dimension (fastest-varying, revisiting the same out
    block), so VMEM holds only tiles regardless of sequence length; a
    block outside the band computes nothing."""
    ki = pl.program_id(2)
    q_lo = _q_block_index(n_q_seq) * block_q

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def compute(masked):
        acc_ref[:], m_ref[:], l_ref[:] = _softmax_tile(
            q_ref[0], k_ref[0], v_ref[0], (acc_ref[:], m_ref[:], l_ref[:]),
            q_lo, ki * block_k, masked=masked, **tile)

    edge, inner = _edge_and_inner(
        ki, *_kv_band(jnp, q_lo, block_q, block_k, n_k, **band))
    pl.when(edge)(functools.partial(compute, True))
    pl.when(inner)(functools.partial(compute, False))

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0] = _flip(m_ref[:] + jnp.log(l))


def _diagonal_tiles(block_q, block_k, q_len, kv_len, causal, window):
    """How many edge tiles end the band of EVERY q block, or None where
    that differs from one q block to the next: with a causal mask, whole
    tiles of which one edge divides the other, no q block past the keys
    and a window (if any) too wide to cut into the diagonal's tiles, they
    are the max(1, block_q // block_k) kv blocks that hold the diagonal."""
    if (causal and 0 in (block_q % block_k, block_k % block_q)
            and q_len % block_q == 0 and q_len <= kv_len
            and (window is None or window >= block_q + block_k - 1)):
        return max(1, block_q // block_k)
    return None


def _fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q,
                         block_k, n_q_seq, n_k, band, tile, diagonal):
    """Fast path for kv that fits VMEM: one program a q block loops over
    the band's kv blocks only, so a causal mask skips the loads AND the
    compute right of the diagonal, and a window those left of the band.
    ``diagonal`` is ``_diagonal_tiles``' count."""
    q_lo = _q_block_index(n_q_seq) * block_q
    q = q_ref[0]

    def tiles(masked):
        def body(ki, carry):
            k_lo = pl.multiple_of(ki * block_k, block_k)
            return _softmax_tile(q, k_ref[0, pl.ds(k_lo, block_k), :],
                                 v_ref[0, pl.ds(k_lo, block_k), :], carry,
                                 q_lo, k_lo, masked=masked, **tile)
        return body

    first, ufirst, ulast, last = _kv_band(jnp, q_lo, block_q, block_k, n_k,
                                          **band)
    carry = (jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32),
             jnp.full((block_q, 1), _NEG_INF, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32))
    # a program is a few tiles long, so its loops' set-up shows (GPT-2s's
    # 1.5 tiles: a third of the kernel): an edge the band does not have gets
    # no loop, and a diagonal of a known number of tiles is not a loop
    if band["window"] is not None:
        carry = lax.fori_loop(first, ufirst, tiles(True), carry)
    carry = lax.fori_loop(ufirst, ulast, tiles(False), carry)
    if diagonal is not None:
        for t in range(diagonal):
            carry = tiles(True)(ulast + t, carry)
    elif band["causal"]:
        carry = lax.fori_loop(ulast, last, tiles(True), carry)
    acc, m, l = carry
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = _flip(m + jnp.log(l))


#: the kernels' names in a device trace (the op class lib/xplane.py shows)
FWD_RESIDENT_NAME = "flash_fwd_resident"
FWD_STREAM_NAME = "flash_fwd_stream"


def _compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _statics(block_q, block_k, n_q_seq, n_k, sm_scale, causal, window,
             kv_len):
    """What each kernel here is specialised on: its tiles, the band
    (``_kv_band``, ``_q_band``) and a tile's arithmetic. ``kv_len`` is the
    sequence's length where the last kv block has a tail past it that only
    a mask removes, else None."""
    return dict(block_q=block_q, block_k=block_k, n_q_seq=n_q_seq, n_k=n_k,
                band=dict(causal=causal, window=window,
                          ragged=kv_len is not None),
                tile=dict(sm_scale=sm_scale, causal=causal, window=window,
                          kv_len=kv_len))


@functools.partial(jax.jit, static_argnums=tuple(range(3, 11)))
def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
               window=None, group=1, resident_bytes=_RESIDENT_KV_BYTES):
    """q is [b, h, group * s, d]: the ``group`` query heads that share kv
    head h, one run of the sequence after another; k [b, h, s, d] and v
    [b, h, s, dv] are never repeated in memory; the result is dv wide.
    Under jit with the tiles and ``resident_bytes`` static (the caller
    passes the module's)."""
    b, h, rows, d = q.shape
    dv = v.shape[-1]
    s = rows // group
    sk = k.shape[2]
    bh = b * h
    qf = q.reshape(bh, rows, d)
    kf = k.reshape(bh, sk, d)
    vf = v.reshape(bh, sk, dv)
    block_q = min(block_q, s)
    block_k = min(block_k, sk)
    n_q = pl.cdiv(rows, block_q)
    n_k = pl.cdiv(sk, block_k)
    n_q_seq = None
    if group > 1:
        if s % block_q:
            raise ValueError("grouped-query flash needs seq %d to be a "
                             "multiple of block_q %d" % (s, block_q))
        n_q_seq = s // block_q
    if window is not None and not causal:
        raise ValueError("a window is the last `window` keys up to the "
                         "query's own: it needs causal=True")
    ragged = sk % block_k != 0      # the streamed kernel masks the tail
    shape = _statics(block_q, block_k, n_q_seq, n_k, sm_scale, causal,
                     window, sk if ragged else None)

    # the row statistic lse = m + log(l), float32, rows along the lanes:
    # 4 bytes a row, the residual the backward kernels rebuild p from
    out_shape = (jax.ShapeDtypeStruct((bh, rows, dv), q.dtype),
                 jax.ShapeDtypeStruct((bh, 1, rows), jnp.float32))
    q_block, o_block = (pl.BlockSpec((1, block_q, w),
                                     lambda i, j, *_: (i, j, 0))
                        for w in (d, dv))
    q_stats = pl.BlockSpec((1, 1, block_q), lambda i, j, *_: (i, 0, j))
    # k's and v's own bytes: a 192-wide k beside a 128-wide v is 5/4 of two
    # 128-wide tensors
    kv_bytes = sk * (d + dv) * k.dtype.itemsize
    if kv_bytes <= resident_bytes and not ragged:
        k_whole, v_whole = (pl.BlockSpec((1, sk, w), lambda i, j: (i, 0, 0))
                            for w in (d, dv))
        out, lse = pl.pallas_call(
            functools.partial(
                _fwd_kernel_resident, **shape, diagonal=_diagonal_tiles(
                    block_q, block_k, s, sk, causal, window)),
            grid=(bh, n_q),
            in_specs=[q_block, k_whole, v_whole],
            out_specs=(o_block, q_stats),
            out_shape=out_shape,
            compiler_params=_compiler_params("parallel", "parallel"),
            interpret=interpret,
            name=FWD_RESIDENT_NAME,
        )(qf, kf, vf)
        return out.reshape(b, h, rows, dv), lse

    k_block, v_block = (pl.BlockSpec((1, block_k, w),
                                     lambda i, j, kb: (i, kb, 0))
                        for w in (d, dv))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, **shape),
        grid=(bh, n_q, n_k),
        in_specs=[q_block, k_block, v_block],
        out_specs=(o_block, q_stats),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=interpret,
        name=FWD_STREAM_NAME,
    )(qf, kf, vf)
    return out.reshape(b, h, rows, dv), lse


def _block_layout(k, v, block_k):
    """Pad kv to a whole number of blocks and reshape for scanning:
    (kb, vb) are [n_blocks, b, h, block_k, d] f32 (the blockwise
    reference's layout)."""
    b, h, sk, d = k.shape
    n_blocks = (sk + block_k - 1) // block_k
    pad = n_blocks * block_k - sk
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = kp.reshape(b, h, n_blocks, block_k, d).astype(
        jnp.float32).transpose(2, 0, 1, 3, 4)
    vb = vp.reshape(b, h, n_blocks, block_k, d).astype(
        jnp.float32).transpose(2, 0, 1, 3, 4)
    return kb, vb, n_blocks


def _block_mask(ki, block_k, s, sk, causal, window=None):
    """[s, block_k] validity mask for kv block ``ki``: ragged tail rows
    beyond sk are invalid; under causal q may not attend ahead, and with
    a window not further back than its own position and the ``window - 1``
    before it. The mask convention of the kernels, in plain jnp."""
    q_pos = jnp.arange(s)[:, None]
    k_pos = ki * block_k + jnp.arange(block_k)[None, :]
    mask = k_pos < sk
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return mask


def _blockwise_reference(q, k, v, causal, sm_scale, block_k=512,
                         window=None):
    """O(seq)-memory attention via lax.scan over kv blocks — the
    semantic twin of the pallas forward (equal head counts)."""
    b, h, s, d = q.shape
    sk = k.shape[2]
    q32 = q.astype(jnp.float32) * sm_scale
    kb, vb, n_blocks = _block_layout(k, v, block_k)

    def body(carry, blk):
        acc, m, l = carry
        k_blk, v_blk, ki = blk
        scores = jnp.einsum("bhqd,bhkd->bhqk", q32, k_blk)
        mask = _block_mask(ki, block_k, s, sk, causal, window)
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
        m_new = jnp.maximum(m, scores.max(-1))
        p = jnp.exp(scores - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, h, s, v.shape[-1]), jnp.float32)
    m0 = jnp.full((b, h, s), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    (acc, m, l), _ = lax.scan(body, (acc0, m0, l0),
                              (kb, vb, jnp.arange(n_blocks)))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


#: the backward kernels' names in a device trace: they hold `flash_bwd`
#: and not the forward's names, so each reader sums its own kernels
BWD_NAME = "flash_bwd"
BWD_DQ_NAME = "flash_bwd_dq"
BWD_DKV_NAME = "flash_bwd_dkv"


def _q_band(xp, k_lo, block_q, block_k, n_q, n_k, causal, window, ragged):
    """The same for the kv block that starts at ``k_lo``: the q blocks (of
    one query head's run of the sequence) whose band reaches it."""
    k_hi = k_lo + block_k - 1
    first = ufirst = 0 * k_lo
    last = ulast = n_q + 0 * k_lo
    if causal:
        first = k_lo // block_q
        ufirst = (k_hi + block_q - 1) // block_q
    if window is not None:
        last = xp.minimum((k_hi + window - 1) // block_q + 1, n_q)
        ulast = (k_lo + window) // block_q
    if ragged:                      # every tile of the padded tail is masked
        ulast = xp.where(k_lo == (n_k - 1) * block_k, 0, ulast)
    ufirst = xp.clip(ufirst, first, last)
    return first, ufirst, xp.clip(ulast, ufirst, last), last


def _p_and_ds_kept(a, b, da, db, lse, delta, keep, sm_scale):
    """One tile of the recomputed softmax and of its gradient, float32:
    p = exp(scale * a.b^T - lse), ds = p * (da.db^T - delta). (a, b) is
    (q, k) with lse and delta columns, or (k, q) with them rows: the tile
    is then the transpose, with no transposing done. The scale goes to the
    float32 scores, so q is not rounded again. ``keep`` is the tile's mask
    or None."""
    p = jnp.exp(_dot(a, b, _NT) * sm_scale - lse)
    if keep is not None:            # else a loop of no trips, traced
        p = jnp.where(keep, p, 0.0)
    return p, p * (_dot(da, db, _NT) - delta)


def _p_and_ds(a, b, da, db, lse, delta, q_pos, k_pos, *, sm_scale, masked,
              causal, window, kv_len):
    """``_p_and_ds_kept`` for a tile of the causal band; q_pos is a column
    with (a, b) = (q, k) and a row with (k, q). ``masked`` is static: only
    a tile that straddles the diagonal, the band's far edge or the padded
    tail builds a mask."""
    keep = _band_mask(q_pos, k_pos, causal, window, kv_len) if masked \
        else None
    return _p_and_ds_kept(a, b, da, db, lse, delta, keep, sm_scale)


def _bwd_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, block_q,
                         block_k, n_q_seq, n_k, band, tile):
    """k and v whole in VMEM: one program a q block loops over the band's
    kv blocks only, computes each tile once (five products), sums its dq in
    registers and adds its share of dk and dv to float32 accumulators that
    stay in VMEM over all q blocks, of every query head, of the kv head."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_lo = _q_block_index(n_q_seq) * block_q
    q, do = q_ref[0], do_ref[0]
    lse, delta = _flip(lse_ref[0]), _flip(delta_ref[0])       # [TQ, 1]
    q_pos = q_lo + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def tiles(masked):
        def body(ki, dq):
            k_lo = pl.multiple_of(ki * block_k, block_k)
            k = k_ref[0, pl.ds(k_lo, block_k), :]
            v = v_ref[0, pl.ds(k_lo, block_k), :]
            k_pos = k_lo + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            p, ds = _p_and_ds(q, k, do, v, lse, delta, q_pos, k_pos,
                              masked=masked, **tile)
            ds = ds.astype(k.dtype)
            dk_acc[pl.ds(k_lo, block_k), :] += _dot(ds, q, _TN)
            dv_acc[pl.ds(k_lo, block_k), :] += _dot(p.astype(do.dtype), do,
                                                    _TN)
            return dq + _dot(ds, k, _NN)
        return body

    first, ufirst, ulast, last = _kv_band(jnp, q_lo, block_q, block_k, n_k,
                                          **band)
    dq = jnp.zeros(q.shape, jnp.float32)
    dq = lax.fori_loop(first, ufirst, tiles(True), dq)
    dq = lax.fori_loop(ufirst, ulast, tiles(False), dq)
    dq = lax.fori_loop(ulast, last, tiles(True), dq)
    dq_ref[0] = (dq * tile["sm_scale"]).astype(dq_ref.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * tile["sm_scale"]).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, block_q, block_k, n_q_seq, n_k, band, tile):
    """dq of one q block with kv streamed: grid step t brings the t-th kv
    block of its band (the index map clamps, so steps past the band load
    nothing new and compute nothing)."""
    t = pl.program_id(2)
    q_lo = _q_block_index(n_q_seq) * block_q
    first, ufirst, ulast, last = _kv_band(jnp, q_lo, block_q, block_k, n_k,
                                          **band)
    ki = first + t

    @pl.when(t == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def compute(masked):
        q_pos = q_lo + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32,
                                                    (1, block_k), 1)
        k = k_ref[0]
        _, ds = _p_and_ds(q_ref[0], k, do_ref[0], v_ref[0],
                          _flip(lse_ref[0]), _flip(delta_ref[0]), q_pos,
                          k_pos, masked=masked, **tile)
        acc_ref[:] += _dot(ds.astype(k.dtype), k, _NN)

    edge, inner = _edge_and_inner(ki, first, ufirst, ulast, last)
    pl.when(edge)(functools.partial(compute, True))
    pl.when(inner)(functools.partial(compute, False))

    @pl.when(t == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[:] * tile["sm_scale"]).astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref,
                    dv_ref, dk_acc, dv_acc, *, block_q, block_k, n_q_seq,
                    n_k, n_steps, band, tile):
    """dk and dv of one kv block with q, dO, lse and delta streamed: grid
    step t brings, for query head t // n_steps, the (t % n_steps)-th q
    block whose band reaches it. Tiles are [TK, TQ]: lse and delta are
    read as they are stored, rows along the lanes."""
    t = pl.program_id(2)
    k_lo = pl.program_id(1) * block_k
    first, ufirst, ulast, last = _q_band(jnp, k_lo, block_q, block_k,
                                         n_q_seq, n_k, **band)
    qi = first + lax.rem(t, n_steps)

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute(masked):
        k_pos = k_lo + lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
        q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                    (1, block_q), 1)
        q, do = q_ref[0], do_ref[0]
        p, ds = _p_and_ds(k_ref[0], q, v_ref[0], do, lse_ref[0],
                          delta_ref[0], q_pos, k_pos, masked=masked, **tile)
        dk_acc[:] += _dot(ds.astype(q.dtype), q, _NN)
        dv_acc[:] += _dot(p.astype(do.dtype), do, _NN)

    edge, inner = _edge_and_inner(qi, first, ufirst, ulast, last)
    pl.when(edge)(functools.partial(compute, True))
    pl.when(inner)(functools.partial(compute, False))

    @pl.when(t == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * tile["sm_scale"]).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _pad_runs(x, axis, group, s, to):
    """Zero-pad each of the ``group`` runs of length ``s`` along ``axis``
    to length ``to`` (a whole number of blocks)."""
    if to == s:
        return x
    shape = x.shape
    x = x.reshape(shape[:axis] + (group, s) + shape[axis + 1:])
    pad = [(0, 0)] * x.ndim
    pad[axis + 1] = (0, to - s)
    return jnp.pad(x, pad).reshape(shape[:axis] + (group * to,)
                                   + shape[axis + 1:])


@functools.partial(jax.jit, static_argnums=tuple(range(6, 13)))
def _flash_bwd(q, k, v, lse, delta, g, causal, sm_scale, interpret=False,
               window=None, group=1, block=_BLOCK,
               resident_bytes=_RESIDENT_KV_BYTES):
    """FlashAttention-2's backward on the chip, visiting only the tiles of
    the causal band. p is rebuilt from the forward's ``lse`` [bh, 1, rows],
    ds from it and ``delta`` [b, h, rows] = rowsum(dO * out); score tiles
    never leave VMEM; every product takes its operands in the inputs' dtype
    and accumulates in float32. Rows are the ``group`` query heads of a kv
    head, each a run of the sequence: dk and dv sum over all of them, K and
    V are never repeated. While k and v fit VMEM whole, one kernel; beyond
    that, one gridded over q blocks for dq and one over kv blocks for dk
    and dv, the other side streamed. A ragged sequence is zero-padded to
    whole tiles here (padded rows carry dO = 0 and add nothing). Under jit
    with ``block`` and ``resident_bytes`` static (the caller passes the
    module's), so the layers of a model share one trace and one lowering."""
    b, h, rows, d = q.shape
    dv = v.shape[-1]                # v's, dO's and dv's width, beside q/k's
    s, sk, bh = rows // group, k.shape[2], b * h
    block_q, block_k = _tile_edge(s, block), _tile_edge(sk, block)
    s_pad = pl.cdiv(s, block_q) * block_q
    sk_pad = pl.cdiv(sk, block_k) * block_k
    n_q_seq, n_k = s_pad // block_q, sk_pad // block_k
    n_q = group * n_q_seq
    # without a causal mask the padded keys need one of their own
    ragged = not causal and sk_pad != sk

    qf = _pad_runs(q.reshape(bh, rows, d), 1, group, s, s_pad)
    dof = _pad_runs(g.astype(q.dtype).reshape(bh, rows, dv), 1, group, s,
                    s_pad)
    lse = _pad_runs(lse, 2, group, s, s_pad)
    delta = _pad_runs(delta.reshape(bh, 1, rows), 2, group, s, s_pad)
    kf = _pad_runs(k.reshape(bh, sk, d), 1, 1, sk, sk_pad)
    vf = _pad_runs(v.reshape(bh, sk, dv), 1, 1, sk, sk_pad)

    shape = _statics(block_q, block_k, n_q_seq, n_k, sm_scale, causal,
                     window, sk if ragged else None)
    band = shape["band"]
    call = functools.partial(pl.pallas_call, interpret=interpret)
    q_block, do_block = (pl.BlockSpec((1, block_q, w),
                                      lambda i, j, *_: (i, j, 0))
                         for w in (d, dv))
    q_stats = pl.BlockSpec((1, 1, block_q), lambda i, j, *_: (i, 0, j))
    k_block, v_block = (pl.BlockSpec((1, block_k, w),
                                     lambda i, j, *_: (i, j, 0))
                        for w in (d, dv))
    dq_shape = jax.ShapeDtypeStruct(qf.shape, q.dtype)
    dkv_shape = (jax.ShapeDtypeStruct(kf.shape, k.dtype),
                 jax.ShapeDtypeStruct(vf.shape, v.dtype))

    if sk_pad * (d + dv) * k.dtype.itemsize <= resident_bytes:
        k_whole, v_whole = (pl.BlockSpec((1, sk_pad, w),
                                         lambda i, j: (i, 0, 0))
                            for w in (d, dv))
        dq, dk, dv_ = call(
            functools.partial(_bwd_kernel_resident, **shape),
            grid=(bh, n_q),
            in_specs=[q_block, k_whole, v_whole, do_block, q_stats,
                      q_stats],
            out_specs=(q_block, k_whole, v_whole),
            out_shape=(dq_shape,) + dkv_shape,
            scratch_shapes=[pltpu.VMEM((sk_pad, d), jnp.float32),
                            pltpu.VMEM((sk_pad, dv), jnp.float32)],
            compiler_params=_compiler_params("parallel", "arbitrary"),
            name=BWD_NAME)(qf, kf, vf, dof, lse, delta)
    else:
        # grid steps a q block needs: the widest band, in kv blocks
        first, _, _, last = _kv_band(np, np.arange(n_q_seq) * block_q,
                                     block_q, block_k, n_k, **band)

        def kv_of(i, j, t):
            first, _, _, last = _kv_band(
                jnp, lax.rem(j, n_q_seq) * block_q, block_q, block_k, n_k,
                **band)
            return i, jnp.minimum(first + t, last - 1), 0

        k_step, v_step = (pl.BlockSpec((1, block_k, w), kv_of)
                          for w in (d, dv))
        dq = call(
            functools.partial(_bwd_dq_kernel, **shape),
            grid=(bh, n_q, int(np.max(last - first))),
            in_specs=[q_block, k_step, v_step, do_block, q_stats, q_stats],
            out_specs=q_block, out_shape=dq_shape,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
            name=BWD_DQ_NAME)(qf, kf, vf, dof, lse, delta)

        # ... and a kv block, in q blocks, for each query head in turn
        first, _, _, last = _q_band(np, np.arange(n_k) * block_k, block_q,
                                    block_k, n_q_seq, n_k, **band)
        n_steps = int(np.max(last - first))

        def q_of(j, t):
            first, _, _, last = _q_band(jnp, j * block_k, block_q, block_k,
                                        n_q_seq, n_k, **band)
            return (lax.div(t, n_steps) * n_q_seq
                    + jnp.minimum(first + lax.rem(t, n_steps), last - 1))

        q_step, do_step = (pl.BlockSpec((1, block_q, w),
                                        lambda i, j, t: (i, q_of(j, t), 0))
                           for w in (d, dv))
        stats_step = pl.BlockSpec((1, 1, block_q),
                                  lambda i, j, t: (i, 0, q_of(j, t)))
        dk, dv_ = call(
            functools.partial(_bwd_dkv_kernel, n_steps=n_steps, **shape),
            grid=(bh, n_k, group * n_steps),
            in_specs=[k_block, v_block, q_step, do_step, stats_step,
                      stats_step],
            out_specs=(k_block, v_block), out_shape=dkv_shape,
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, dv), jnp.float32)],
            compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
            name=BWD_DKV_NAME)(kf, vf, qf, dof, lse, delta)

    if s_pad != s:
        dq = dq.reshape(bh, group, s_pad, d)[:, :, :s]
    return (dq.reshape(b, h, rows, d), dk[:, :sk].reshape(b, h, sk, d),
            dv_[:, :sk].reshape(b, h, sk, dv))


def _kernel_layout(x, group=1):
    """The models' [batch, seq, heads, dim] -> the kernels' [batch,
    heads // group, group * seq, dim]: the ``group`` query heads of a kv
    head one run of the sequence after another."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, h // group, group * s, d)


def _model_layout(x, group=1):
    b, h, rows, d = x.shape
    return x.reshape(b, h * group, rows // group, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 11)))
def _attend(q, k, v, causal, sm_scale, block_q, block_k, interpret, window,
            group, seq_major):
    return _attend_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret, window, group, seq_major)[0]


def _attend_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                window, group, seq_major):
    """With ``seq_major`` the arguments, the result and so the residuals
    are in the models' layout, which the projections around the attention
    hold anyway: the kernels' [batch, heads, seq, dim] copies live for the
    length of a kernel and are remade in the backward, not kept from the
    forward (three arrays the size of q a layer, otherwise). A tile edge
    the caller left open is chosen from the shape, as the backward's."""
    qt, kt, vt = q, k, v
    if seq_major:
        qt, kt, vt = _kernel_layout(q, group), _kernel_layout(k), \
            _kernel_layout(v)
    out, lse = _flash_fwd(
        qt, kt, vt, causal, sm_scale,
        block_q or _tile_edge(qt.shape[2] // group, _BLOCK),
        block_k or _tile_edge(kt.shape[2], _BLOCK), interpret, window, group,
        _RESIDENT_KV_BYTES)
    if seq_major:
        out = _model_layout(out, group)
    return out, (q, k, v, out, lse)


def _attend_bwd(causal, sm_scale, block_q, block_k, interpret, window, group,
                seq_major, res, g):
    q, k, v, out, lse = res
    # delta_i = sum_d dO_i * out_i, the softmax jacobian's row term
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if seq_major:
        # the barrier keeps XLA from merging these transposes with the
        # forward's, which would keep the forward's copies alive instead
        q, k, v = lax.optimization_barrier((q, k, v))
        q, g = _kernel_layout(q, group), _kernel_layout(g, group)
        k, v = _kernel_layout(k), _kernel_layout(v)
        delta = _kernel_layout(delta[..., None], group)[..., 0]
    dq, dk, dv = _flash_bwd(q, k, v, lse, delta, g, causal, sm_scale,
                            interpret, window, group, _BLOCK,
                            _RESIDENT_KV_BYTES)
    if seq_major:
        dq, dk, dv = _model_layout(dq, group), _model_layout(dk), \
            _model_layout(dv)
    return dq, dk, dv


_attend.defvjp(_attend_fwd, _attend_bwd)


def _selected(q, k, v, causal, sm_scale, window, interpret, select):
    """The kernels of a learned selection live in ops/sparse_attention.py
    under names of their own (`dsa_*`): one rebuilt tile of the index
    scores serves every query head, which the band kernels' one-head
    programs cannot share. Model layout in and out."""
    from edl_tpu.ops import sparse_attention
    if not causal or window is not None:
        raise ValueError("a selection needs causal=True and no window")
    return sparse_attention.select_attend(q, k, v, select, sm_scale=sm_scale,
                                          interpret=interpret)[0]


def _two_streams(q, k, v, causal, sm_scale, window, interpret, select,
                 streams):
    """The two-stream block mask's kernels live in
    ops/block_diffusion_attention.py (`bdiff_*`): a band over the clean
    half plus one tile of the noised half, which `_kv_band` cannot say.
    Model layout in and out."""
    from edl_tpu.ops import block_diffusion_attention
    if causal or window is not None or select is not None:
        raise ValueError("the two-stream block mask is the whole mask: no "
                         "causal flag, window or selection beside it")
    return block_diffusion_attention.attend(q, k, v, streams,
                                            sm_scale=sm_scale,
                                            interpret=interpret)[0]


def flash_attention(q, k, v, causal=False, sm_scale=None, block_q=None,
                    block_k=None, interpret=False, window=None, group=1,
                    select=None, streams=None):
    """Blockwise exact attention; k/v are [batch, kv_heads, seq, dim] and
    q/out [batch, kv_heads, group * seq, dim]: the ``group`` query heads
    of a kv head one run of the sequence after another (``group=1``: the
    usual [batch, heads, seq, dim]); v and the result may be of another
    width than q and k, whose width the default ``sm_scale`` is taken from. ``window`` (needs ``causal``) keeps,
    for each query, its own position and the ``window - 1`` before it;
    ``block_q`` / ``block_k`` fix the forward's tile, which is otherwise
    chosen from the sequence (``_tile_edge``), as the backward's always is;
    ``select`` = (qi, ki, wi, tau) in the models' layout keeps the keys a
    learned indexer chose (ops/sparse_attention.py); ``streams`` =
    (block_length, clean_from) is the two-stream block mask
    (ops/block_diffusion_attention.py)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if streams is not None:
        return _kernel_layout(_two_streams(
            _model_layout(q, group), _model_layout(k), _model_layout(v),
            causal, sm_scale, window, interpret, select, streams), group)
    if select is not None:
        return _kernel_layout(_selected(
            _model_layout(q, group), _model_layout(k), _model_layout(v),
            causal, sm_scale, window, interpret, select), group)
    return _attend(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   window, group, False)


def mha(q, k, v, causal=False, sm_scale=None, window=None, block_q=None,
        block_k=None, interpret=False, select=None, streams=None):
    """The same for [batch, seq, heads, dim] arrays (the model code's
    layout). k and v may have fewer heads than q (grouped-query attention:
    query head i reads kv head i // group); they are not repeated in
    memory."""
    if streams is not None:
        return _two_streams(q, k, v, causal, sm_scale, window, interpret,
                            select, streams)
    if select is not None:
        return _selected(q, k, v, causal, sm_scale, window, interpret,
                         select)
    hq, hkv = q.shape[2], k.shape[2]
    group = hq // hkv
    if group * hkv != hq:
        raise ValueError("%d query heads do not divide over %d kv heads"
                         % (hq, hkv))
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _attend(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   window, group, True)
