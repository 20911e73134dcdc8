"""Pallas flash attention for TPU: blockwise online-softmax forward kernel
with a memory-efficient blockwise-recompute backward.

The hot op of the transformer models (edl_tpu/models/bert.py) and of the
teacher inference servers. Never materializes the [seq, seq] score matrix:

- forward: a Pallas kernel gridded over (batch*heads, q_blocks); each
  program streams kv blocks from VMEM with fp32 online-softmax
  accumulation on the MXU (q/k/v blocks sized to the 128-lane tiling);
- backward: custom_vjp that recomputes per-block attention under
  `lax.scan` (flash-style recompute — O(seq) memory, XLA-fused), so the
  kernel composes with jit/grad and with the ring-attention sp layer
  (edl_tpu/parallel/ring_attention.py) which shards the sequence BEFORE
  attention is applied per shard.

Layout: q, k, v are [batch, heads, seq, head_dim]. With grouped-query
attention k and v have fewer heads and q is [batch, kv_heads, group * seq,
head_dim] (the query heads of a kv head one after another: K/V are never
repeated); a causal ``window`` keeps a query's own position and the
``window - 1`` before it, and blocks outside the band are skipped, in the
forward kernels and in the backward's scans (``mha`` takes the model's
[batch, seq, heads, dim] layout and does the regrouping).
"""

import functools

import jax
import jax.numpy as jnp
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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                block_k, seq_len, causal, sm_scale, q_block, window=None,
                n_q_seq=None):
    """One (bh, q_block, k_block) grid step. kv blocks stream through VMEM
    via the third grid dimension (fastest-varying, revisiting the same out
    block), so VMEM holds only tiles regardless of sequence length."""
    qi = _q_block_index(n_q_seq)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: blocks strictly right of the diagonal contribute nothing
    diag_ok = (ki * block_k <= qi * q_block + q_block - 1) if causal \
        else True
    if window is not None:
        # ... and so do blocks wholly left of the band's oldest key
        diag_ok = jnp.logical_and(
            diag_ok, ki * block_k + block_k - 1 >= qi * q_block - window + 1)

    @pl.when(diag_ok)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * sm_scale      # [TQ, d]
        tq = q.shape[0]
        k_blk = k_ref[0].astype(jnp.float32)             # [TK, d]
        v_blk = v_ref[0].astype(jnp.float32)
        scores = jax.lax.dot_general(                    # [TQ, TK]
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        q_pos = qi * q_block + lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32,
                                                    (1, block_k), 1)
        mask = k_pos < seq_len                           # ragged last block
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window is not None:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        scores = jnp.where(mask, scores, _NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        p = jnp.where(mask, p, 0.0)
        correction = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * correction + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:]
                    / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def _fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, *, block_k, seq_len,
                         causal, sm_scale, q_block, window=None,
                         n_q_seq=None):
    """Fast path for kv that fits VMEM: fori_loop over kv blocks so causal
    masking skips the loads AND compute right of the diagonal, and a
    window those left of the band."""
    qi = _q_block_index(n_q_seq)
    q = q_ref[0].astype(jnp.float32) * sm_scale        # [TQ, d]
    tq, d = q.shape
    q_pos = qi * q_block + lax.broadcasted_iota(jnp.int32, (tq, 1), 0)

    def body(ki, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(
            jnp.float32)
        v_blk = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(
            jnp.float32)
        scores = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            mask = q_pos >= k_pos
            if window is not None:
                mask = jnp.logical_and(mask, q_pos - k_pos < window)
            scores = jnp.where(mask, scores, _NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)
        correction = jnp.exp(m - m_new)
        l_new = l * correction + p.sum(axis=-1, keepdims=True)
        acc_new = acc * correction + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc = jnp.zeros((tq, d), jnp.float32)
    m = jnp.full((tq, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((tq, 1), jnp.float32)
    if causal:
        last = lax.div(qi * q_block + (tq - 1), block_k) + 1
    else:
        last = seq_len // block_k
    first = 0
    if window is not None:
        first = lax.div(jnp.maximum(qi * q_block - window + 1, 0), block_k)
    acc, m, l = lax.fori_loop(first, last, body, (acc, m, l))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


# kv (k + v) resident in VMEM up to this many bytes; beyond it, stream
_RESIDENT_KV_BYTES = 4 << 20

#: the kernels' names in a device trace (the op class lib/xplane.py shows)
FWD_RESIDENT_NAME = "flash_fwd_resident"
FWD_STREAM_NAME = "flash_fwd_stream"


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
               window=None, group=1):
    """q is [b, h, group * s, d]: the ``group`` query heads that share kv
    head h, one run of the sequence after another; k, v are [b, h, s, d]
    and are never repeated in memory."""
    b, h, rows, d = q.shape
    s = rows // group
    sk = k.shape[2]
    bh = b * h
    qf = q.reshape(bh, rows, d)
    kf = k.reshape(bh, sk, d)
    vf = v.reshape(bh, sk, d)
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
    band = dict(window=window, n_q_seq=n_q_seq)

    kv_bytes = 2 * sk * d * k.dtype.itemsize
    if kv_bytes <= _RESIDENT_KV_BYTES and sk % block_k == 0:
        out = pl.pallas_call(
            functools.partial(_fwd_kernel_resident, block_k=block_k,
                              seq_len=sk, causal=causal, sm_scale=sm_scale,
                              q_block=block_q, **band),
            grid=(bh, n_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda i, j: (i, j, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, rows, d), q.dtype),
            interpret=interpret,
            name=FWD_RESIDENT_NAME,
        )(qf, kf, vf)
        return out.reshape(b, h, rows, d)

    out = pl.pallas_call(
        functools.partial(_fwd_kernel, block_k=block_k, seq_len=sk,
                          causal=causal, sm_scale=sm_scale,
                          q_block=block_q, **band),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb: (i, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb: (i, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, rows, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name=FWD_STREAM_NAME,
    )(qf, kf, vf)
    return out.reshape(b, h, rows, d)


def _block_layout(k, v, block_k):
    """Pad kv to a whole number of blocks and reshape for scanning:
    (kb, vb) are [n_blocks, b, h, block_k, d] f32. ONE copy of the
    layout shared by the blockwise forward and the recompute backward
    so the two can never disagree on padding."""
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


def _block_mask(ki, block_k, s, sk, causal, window=None, q_pos=None):
    """[rows, block_k] validity mask for kv block ``ki``: ragged tail rows
    beyond sk are invalid; under causal q may not attend ahead, and with
    a window not further back than its own position and the ``window - 1``
    before it. ``q_pos`` gives the rows' positions where they are not
    0..s-1 (a slice of the sequence, query heads stacked). The one copy
    of the mask convention for forward AND backward."""
    q_pos = (jnp.arange(s) if q_pos is None else q_pos)[:, None]
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

    acc0 = jnp.zeros((b, h, s, d), jnp.float32)
    m0 = jnp.full((b, h, s), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    (acc, m, l), _ = lax.scan(body, (acc0, m0, l0),
                              (kb, vb, jnp.arange(n_blocks)))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, causal=False, sm_scale=None, block_q=128,
                    block_k=128, interpret=False, window=None, group=1):
    """Blockwise exact attention; k/v are [batch, kv_heads, seq, dim] and
    q/out [batch, kv_heads, group * seq, dim]: the ``group`` query heads
    of a kv head one run of the sequence after another (``group=1``: the
    usual [batch, heads, seq, dim]). ``window`` (needs ``causal``) keeps,
    for each query, its own position and the ``window - 1`` before it."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                      interpret, window, group)


def _flash_bwd(q, k, v, out, g, causal, sm_scale, block_k=512,
               window=None, group=1):
    """The FA2-style memory-efficient backward: recompute per-block
    attention from saved (out) plus a cheap O(seq)-carry statistics
    pass, then accumulate dq and emit per-block dk/dv under lax.scan.
    Live memory is O(seq*(dim + block_k)) — LINEAR in sequence length.
    (The previous implementation took jax.vjp of the blockwise forward,
    whose scan residuals stash every block's scores: O(seq^2) — the
    static account showed its temp memory EXCEEDING dense attention at
    8k, PERF_ACCOUNTING.json r5.)

    With a ``window`` both scans visit, for each kv block, only the
    ``span`` query positions whose band can reach it (a slice of the
    sequence that starts at the block), not the whole sequence; without
    one the slice is the sequence and nothing is cut. Rows are the
    ``group`` query heads of a kv head, each a run of the sequence."""
    b, h, rows, d = q.shape
    s = rows // group
    sk = k.shape[2]
    span = s
    if window is not None and block_k + window < s:
        span = block_k + window
    q32 = q.astype(jnp.float32) * sm_scale
    g32 = g.astype(jnp.float32)
    kb, vb, n_blocks = _block_layout(k, v, block_k)

    def start_of(ki):
        # first query position a kv block's scans read; the last blocks'
        # slices are pushed back so that they end with the sequence
        return jnp.minimum(ki * block_k, s - span)

    def cut(x, ki):
        """x [b, h, group * s, ...] -> its rows at positions
        [start, start + span) of every query head."""
        if span == s:
            return x
        x = x.reshape((b, h, group, s) + x.shape[3:])
        x = lax.dynamic_slice_in_dim(x, start_of(ki), span, axis=3)
        return x.reshape((b, h, group * span) + x.shape[4:])

    def put(x, part, ki, combine):
        """Write ``combine(old rows, part)`` back where ``cut`` read."""
        if span == s:
            return combine(x, part)
        shape = x.shape
        x = x.reshape((b, h, group, s) + shape[3:])
        part = part.reshape((b, h, group, span) + shape[3:])
        old = lax.dynamic_slice_in_dim(x, start_of(ki), span, axis=3)
        x = lax.dynamic_update_slice_in_dim(x, combine(old, part),
                                            start_of(ki), axis=3)
        return x.reshape(shape)

    def mask_of(ki):
        q_pos = None
        if span != s or group > 1:
            q_pos = jnp.tile((0 if span == s else start_of(ki))
                             + jnp.arange(span), group)
        return _block_mask(ki, block_k, s, sk, causal, window, q_pos)

    # pass 1: row statistics (m, l) only — O(seq) carry, no O(s^2) stash
    def stats_body(carry, blk):
        m_all, l_all = carry
        k_blk, ki = blk
        m, l = cut(m_all, ki), cut(l_all, ki)
        scores = jnp.einsum("bhqd,bhkd->bhqk", cut(q32, ki), k_blk)
        mask = mask_of(ki)
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
        m_new = jnp.maximum(m, scores.max(-1))
        l = l * jnp.exp(m - m_new) + jnp.where(
            mask[None, None],
            jnp.exp(scores - m_new[..., None]), 0.0).sum(-1)
        keep = lambda old, new: new
        return (put(m_all, m_new, ki, keep), put(l_all, l, ki, keep)), None

    m0 = jnp.full((b, h, rows), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, rows), jnp.float32)
    (m, l), _ = lax.scan(stats_body, (m0, l0),
                         (kb, jnp.arange(n_blocks)))
    l = jnp.maximum(l, 1e-30)
    # delta_i = sum_d g_i * out_i  (the softmax-jacobian row term)
    delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1)  # [b,h,rows]

    # pass 2: dq accumulates in the carry; dk/dv emit per block (the
    # stacked outputs reassemble to full dk/dv — O(seq*dim) total)
    def grad_body(dq, blk):
        k_blk, v_blk, ki = blk
        mask = mask_of(ki)
        q_c, g_c = cut(q32, ki), cut(g32, ki)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_c, k_blk)
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
        p = jnp.exp(scores - cut(m, ki)[..., None]) / cut(l, ki)[..., None]
        p = jnp.where(mask[None, None], p, 0.0)
        dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, g_c)
        dp = jnp.einsum("bhqd,bhkd->bhqk", g_c, v_blk)
        ds = p * (dp - cut(delta, ki)[..., None])
        dq = put(dq, sm_scale * jnp.einsum("bhqk,bhkd->bhqd", ds, k_blk),
                 ki, jnp.add)
        # q32 already carries one sm_scale factor, which is exactly
        # dk_j = sm_scale * sum_i ds_ij q_i
        dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds, q_c)
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, h, rows, d), jnp.float32)
    dq, (dk_blocks, dv_blocks) = lax.scan(
        grad_body, dq0, (kb, vb, jnp.arange(n_blocks)))
    dk = dk_blocks.transpose(1, 2, 0, 3, 4).reshape(b, h,
                                                    n_blocks * block_k, d)
    dv = dv_blocks.transpose(1, 2, 0, 3, 4).reshape(b, h,
                                                    n_blocks * block_k, d)
    return (dq.astype(q.dtype), dk[:, :, :sk].astype(k.dtype),
            dv[:, :, :sk].astype(v.dtype))


def _vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
             window, group):
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    out = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                     window, group)
    return out, (q, k, v, out)


def _vjp_bwd(causal, sm_scale, block_q, block_k, interpret, window, group,
             res, g):
    q, k, v, out = res
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _flash_bwd(q, k, v, out, g, causal, sm_scale, window=window,
                      group=group)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


def mha(q, k, v, causal=False, sm_scale=None, window=None, **kw):
    """Convenience wrapper for [batch, seq, heads, dim] layouts (the model
    code's layout): transposes in/out around flash_attention. k and v may
    have fewer heads than q (grouped-query attention: query head i reads
    kv head i // group); they are not repeated in memory."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    if group * hkv != hq:
        raise ValueError("%d query heads do not divide over %d kv heads"
                         % (hq, hkv))
    qt = q.transpose(0, 2, 1, 3)
    if group > 1:
        qt = qt.reshape(b, hkv, group * s, d)
    out = flash_attention(qt, k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal, sm_scale,
                          window=window, group=group, **kw)
    if group > 1:
        out = out.reshape(b, hq, s, d)
    return out.transpose(0, 2, 1, 3)
