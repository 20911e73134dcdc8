"""Attention over a LEARNED selection of keys (DeepSeek-Sparse-Attention,
arXiv:2512.02556): an indexer scores every causal (query, key) pair,

    I[t, s] = sum_j wi[t, j] * relu(qi[t, j] . ki[s]) * (heads_i * dim_i) ** -0.5

each query keeps the ``k`` keys with the largest scores (all of them while
it has at most ``k``), attention runs over the kept keys only, and the
indexer learns from KL(mean over heads of the attention's probabilities ||
softmax of I over the kept keys). The kept set is not a band, so no block
of the causal triangle can be skipped: the kernels below are MASKED
kernels — they visit every causal tile and go on as the flash kernels do.
Within a pass an index product that has been formed is not formed again:
the scores are built ONCE for the thresholds, which hand the forward the
set they kept, a bit a pair; the two passes that need the scores' VALUES
at the kept pairs (the KL, the backward) rebuild their tile of I from (qi,
ki, wi) in VMEM and compare it with the row's threshold ``tau``. No
[seq, seq] array of scores or probabilities crosses HBM; the packed set
does, 1/32 of one (32 MiB a layer at 16384 positions), from the
thresholds' kernel to the forward and no further.

Four Pallas kernels, each gridded over (batch, query blocks) with k, v and
ki of the sequence whole in VMEM and every held query head of the block
served by one tile of the kept set — and, in the forward, the query heads
that share a kv head (``group = heads // kv_heads`` of them) by ONE score
product against each key block, as one block of ``group * block_q`` rows:
the key block is laid into the MXU once a kv head and not once a head
(7.33 -> 6.45, 5.65, 4.44 ms a layer at groups of 2, 4, 8, 16384 positions
and 8 heads of 128 on a v5e, the same bits; PERF.md, PR 58). The whole
group always: every size measured is faster than a product a head, and a
group of 1 is a product a head. Softmax and p . v stay a head at a time
(one p . v a group is slower), and so do the KL's and the backward's
products (stacked, the one is no faster and the other slower):

- ``dsa_index_tau`` reads qi, ki, wi: the thresholds, EXACT — the row's
  scores go to VMEM as order-preserving integers and the k-th largest is
  found bit by bit, 32 counting passes, no sort and no approximation; then,
  from the scores it still holds, the KEPT SET bit-packed (`_mask_slab`'s
  layout: a key block is whole lane slabs of words, unpacked by one AND)
  and the indexer's log-sum-exp over it;
- ``dsa_fwd`` reads q, k, v and the packed set — no indexer operand, no
  threshold: online-softmax attention over the kept keys, a kv head's
  query heads scored together; beside the result the row statistic lse
  (per head) and the number of keys of the set it applied (what the step
  really ran);
- ``dsa_index_kl`` reads q, k, lse, qi, ki, wi, tau and the indexer's lse:
  the KL term per row (needs the final lse, so a second pass over the
  tiles, and I's values, so it rebuilds them);
- ``dsa_bwd`` reads what the KL reads with v, dO and delta: one pass for
  dq, dk, dv AND the indexer's dqi, dki, dwi (pi - mean p through relu and
  wi): each tile's p is computed once, and each product qi_j . ki once —
  the 16 of a tile stay in VMEM from the rebuild of I to the indexer's
  backward.

``select_attend`` ties the last three into one ``custom_vjp``;
``index_selection`` is the first with its plain ``jax.numpy`` twins
(``lax.top_k`` for tau, ``kept_set`` for the other two), and
``dense_select_attend`` the dense dispatch's and the tests' attention.
What the backward reads of a layer's forward carries a name
(``SAVED_RESIDUALS``: the result and lse inside the forward rule, the
indexer's lse where the thresholds make it), so that a layer
rematerialised under a policy that saves the names keeps them and its
recomputation holds neither ``dsa_index_tau`` nor ``dsa_fwd``: each kernel
runs once a layer and step. The kept set has no name: its one reader is
the forward. Products take their operands in the dtype they arrive in
with float32 accumulation; scores, thresholds, probabilities and
statistics are float32.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops.flash_attention import _NEG_INF, _NN, _NT, _TN, _dot, _flip

#: the kernels' names in a device trace
TAU_NAME = "dsa_index_tau"
FWD_NAME = "dsa_fwd"
KL_NAME = "dsa_index_kl"
BWD_NAME = "dsa_bwd"

#: `checkpoint_name`s of what a layer's forward leaves its backward — the
#: result (model layout) and lse, put on them INSIDE the `custom_vjp`'s
#: forward rule (a name on the layer's context outside it would keep the
#: context and still rerun the kernel for lse), and the indexer's lse, put
#: on it by `index_selection`, whose kernel makes it. At 16384 positions and
#: 8 heads of 128 a layer under remat keeps 34 MB against the 7 + 8 ms of a
#: second `dsa_fwd` and `dsa_index_tau` whose every value the first had
#: made; the kernels are deterministic, so the gradients are bit for bit a
#: recomputation's. kl and kept are results of the one forward call: no
#: backward reads them.
SAVED_RESIDUALS = ("attn.select_out", "attn.select_lse", "attn.select_lse_i")
#: all a layer under remat keeps of its selection: first the
#: `checkpoint_name`s of the indexer's queries, keys and head weights and of
#: the thresholds — the choice WITH what it was made from (35 MiB a layer at
#: 16384 positions). A recomputed score that differs in its last bit from
#: the one its threshold was found among moves a key across it, as a
#: recomputed top-k of the router breaks a near tie the other way.
SAVED_UNDER_REMAT = ("attn.index_q", "attn.index_k", "attn.index_w",
                     "attn.tau") + SAVED_RESIDUALS

_BLOCK = 512
_TAU_BLOCK_Q = 128
_VMEM_LIMIT = 100 << 20
#: k, v, ki and the float32 accumulators of dk, dv, dki stay in VMEM for
#: the whole sequence: what that may cost (bytes, the backward's count)
_RESIDENT_LIMIT = 72 << 20
_INT_MIN = np.int32(-2 ** 31)


def index_scale(heads_i, dim_i):
    return float(heads_i * dim_i) ** -0.5


def index_scores(qi, ki, wi):
    """I [b, t, s] float32 from qi [b, s, hi, di], ki [b, s, di], wi
    [b, s, hi]: products in the operands' dtype, float32 accumulation, the
    heads summed in order (the order the kernels sum them in)."""
    hi, di = qi.shape[2], qi.shape[3]
    w = wi.astype(jnp.float32) * index_scale(hi, di)
    acc = jnp.zeros((qi.shape[0], qi.shape[1], ki.shape[1]), jnp.float32)
    for j in range(hi):
        x = jnp.einsum("btd,bsd->bts", qi[:, :, j], ki,
                       preferred_element_type=jnp.float32)
        acc = acc + jnp.maximum(x, 0.0) * w[:, :, j, None]
    return acc


def _causal(s):
    pos = jnp.arange(s)
    return pos[:, None] >= pos[None, :]


def _kept(scores, tau):
    """[b, t, s] bool: key s of the causal prefix reaches query t's
    threshold."""
    return jnp.logical_and(_causal(scores.shape[1])[None],
                           lax.stop_gradient(scores)
                           >= lax.stop_gradient(tau)[..., None])


def selection_mask(qi, ki, wi, tau):
    """[b, t, s] bool: key s is read by query t."""
    return _kept(index_scores(qi, ki, wi), tau)


def _block_for(s, block):
    for b in (block, block // 2):
        if b and s % b == 0:
            return b
    return s


def thresholds_kernel_reason(seq_len, index_dim, itemsize=2):
    """None where the thresholds' kernel takes the shape (ki, a query
    block's keys of the whole sequence and its part of the packed kept set
    in VMEM), else why not."""
    if seq_len % 8:
        return "seq_len %d not a multiple of 8" % seq_len
    need = seq_len * (max(index_dim, 128) * 2 * itemsize
                      + _TAU_BLOCK_Q * 4 + _TAU_BLOCK_Q // 4)
    if need > _RESIDENT_LIMIT:
        return "ki and a block's scores of %d positions do not fit VMEM" \
            % seq_len
    return None


def kernel_reason(seq_len, kv_heads, head_dim, index_dim, itemsize=2,
                  index_heads=16):
    """None where the attention kernels take the shape, else why not: the
    backward's count — k, v and ki once each (one buffer: `_specs`), the
    float32 dk, dv and dki they accumulate into, a VMEM lane tile wide at
    least, and a tile's products qi_j . ki of every index head."""
    if seq_len % 8:
        return "seq_len %d not a multiple of 8" % seq_len
    block = _block_for(seq_len, _BLOCK)
    need = (seq_len * (kv_heads * head_dim * (2 * itemsize + 8)
                       + max(index_dim, 128) * (itemsize + 4))
            + index_heads * block * block * 4)
    if need > _RESIDENT_LIMIT:
        return ("k, v and ki of %d positions do not fit VMEM whole (%d "
                "bytes with the backward's accumulators and its tile of "
                "index products)" % (seq_len, need))
    return None


# -- inside the kernels ----------------------------------------------------

def _columns(ref, n):
    """The n columns [rows, 1] of a [1, rows, n] block."""
    x = ref[0]
    return [x[:, j:j + 1] for j in range(n)]


def _row_columns(ref, n):
    """The n rows of a [1, n, rows] block, each as a column [rows, 1]."""
    return [_flip(ref[0, j:j + 1, :]) for j in range(n)]


def _index_tile(qi_ref, ki_blk, wi_cols, x_ref=None):
    """One [TQ, TK] tile of I: the same sums in the same order in every
    kernel, so that `I >= tau` decides alike in all of them. ``x_ref``
    [hi, TQ, TK] keeps each head's product for a kernel that reads it
    again."""
    acc = jnp.zeros((qi_ref.shape[2], ki_blk.shape[0]), jnp.float32)
    for j, w in enumerate(wi_cols):
        x = _dot(qi_ref[0, j], ki_blk, _NT)
        if x_ref is not None:
            x_ref[j] = x
        acc = acc + jnp.maximum(x, 0.0) * w
    return acc


def _ordered(x):
    """float32 <-> int32 whose signed order is the floats' order (its own
    inverse)."""
    return x ^ ((x >> 31) & np.int32(0x7FFFFFFF))


def _positions(q_lo, block_q, k_lo, block_k):
    return (q_lo + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0),
            k_lo + lax.broadcasted_iota(jnp.int32, (1, block_k), 1))


def _n_blocks(q_lo, block_q, block_k):
    """kv blocks up to and with the one that holds the block's last row."""
    return lax.div(q_lo + block_q - 1, block_k) + 1


def _mask_lanes(block_k):
    """Keys to a SLAB of the packed kept set: a VMEM lane tile, or the
    whole key block of a small shape."""
    return 128 if block_k % 128 == 0 else block_k


def mask_words(seq_len, lanes):
    """int32 words a row of the packed kept set holds."""
    return -(-seq_len // (32 * lanes)) * lanes


def _mask_slab(n, lanes):
    """Where slab n of a row (keys n * lanes .. + lanes) lies in the packed
    kept set: (the lane its words start at, its bit in them) — word
    [t, g * lanes + lane] holds at bit b the key (32 g + b) lanes + lane,
    so a key block is whole lane slabs of words and unpacks with an AND."""
    return (pl.multiple_of((n >> 5) * lanes, lanes),
            lax.shift_left(jnp.int32(1), jnp.asarray(n & 31, jnp.int32)))


def _tau_kernel(qi_ref, ki_ref, wi_ref, tau_ref, mask_ref, lsei_ref, keys_ref,
                *, block_q, block_k, topk, hi):
    q_lo = pl.program_id(1) * block_q
    wi_cols = _columns(wi_ref, hi)
    last = _n_blocks(q_lo, block_q, block_k)
    lanes = _mask_lanes(block_k)
    slabs = [slice(u * lanes, (u + 1) * lanes)
             for u in range(block_k // lanes)]

    def fill(kb, top):
        k_lo = pl.multiple_of(kb * block_k, block_k)
        q_pos, k_pos = _positions(q_lo, block_q, k_lo, block_k)
        tile = _index_tile(qi_ref, ki_ref[0, pl.ds(k_lo, block_k), :],
                           wi_cols)
        key = _ordered(lax.bitcast_convert_type(tile, jnp.int32))
        keys_ref[:, pl.ds(k_lo, block_k)] = jnp.where(q_pos >= k_pos, key,
                                                      _INT_MIN)
        seen = jnp.where(q_pos >= k_pos, tile, _NEG_INF)
        for u in slabs:
            top = jnp.maximum(top, seen[:, u])
        return top

    # the row's largest score is kept whatever the threshold: the maximum
    # of the indexer's softmax over the kept keys
    top = lax.fori_loop(0, last, fill,
                        jnp.full((block_q, lanes), _NEG_INF, jnp.float32)
                        ).max(axis=1, keepdims=True)

    def count(cand):
        """[TQ, 1] float32: keys of the row at or above its candidate."""
        def body(kb, c):
            k_lo = pl.multiple_of(kb * block_k, block_k)
            hit = jnp.where(keys_ref[:, pl.ds(k_lo, block_k)] >= cand, 1.0,
                            0.0)
            for u in slabs:
                c = c + hit[:, u]
            return c
        c = lax.fori_loop(0, last, body,
                          jnp.zeros((block_q, lanes), jnp.float32))
        return c.sum(axis=1, keepdims=True)

    # the k-th largest key, from the sign bit down: the largest value that
    # at least k keys of the row reach
    want = float(topk)
    zero = jnp.zeros((block_q, 1), jnp.int32)
    prefix = jnp.where(count(zero) >= want, zero, _INT_MIN)

    def bit(n, prefix):
        cand = prefix | lax.shift_left(jnp.int32(1), 30 - n)
        return jnp.where(count(cand) >= want, cand, prefix)

    prefix = lax.fori_loop(0, 31, bit, prefix)
    q_pos = q_lo + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    tau = jnp.where(q_pos < topk, -jnp.inf, lax.bitcast_convert_type(
        _ordered(prefix), jnp.float32))
    tau_ref[0] = _flip(tau)

    # what was decided, handed on: the kept set a bit a (query, key) — the
    # keys at or above tau; a key past the row's own position holds
    # _INT_MIN, below every threshold — and the indexer's log-sum-exp over
    # it, from the scores this block still holds
    floor = _ordered(lax.bitcast_convert_type(tau, jnp.int32))
    mask_ref[0] = jnp.zeros(mask_ref.shape[1:], jnp.int32)

    def hand_on(kb, total):
        k_lo = pl.multiple_of(kb * block_k, block_k)
        keys = keys_ref[:, pl.ds(k_lo, block_k)]
        hit = keys >= floor
        e = jnp.where(hit, jnp.exp(lax.bitcast_convert_type(
            _ordered(keys), jnp.float32) - top), 0.0)
        for n, u in enumerate(slabs):
            at, one = _mask_slab(kb * len(slabs) + n, lanes)
            words = pl.ds(at, lanes)
            mask_ref[0, :, words] = mask_ref[0, :, words] | jnp.where(
                hit[:, u], one, 0)
            total = total + e[:, u]
        return total

    total = lax.fori_loop(0, last, hand_on,
                          jnp.zeros((block_q, lanes), jnp.float32))
    lsei_ref[0] = _flip(top + jnp.log(jnp.maximum(
        total.sum(axis=1, keepdims=True), 1e-30)))


def _keep_tile(qi_ref, ki_ref, wi_cols, tau, q_lo, k_lo, block_q, block_k,
               x_ref=None):
    q_pos, k_pos = _positions(q_lo, block_q, k_lo, block_k)
    tile = _index_tile(qi_ref, ki_ref[0, pl.ds(k_lo, block_k), :], wi_cols,
                       x_ref)
    return tile, jnp.logical_and(q_pos >= k_pos, tile >= tau)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, kept_ref,
                acc_ref, m_ref, l_ref, *, block_q, block_k, sm_scale, heads,
                group):
    q_lo = pl.program_id(1) * block_q
    lanes = _mask_lanes(block_k)
    per_block = block_k // lanes
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

    def body(kb, cnt):
        k_lo = pl.multiple_of(kb * block_k, block_k)
        # the kept set as the thresholds' kernel packed it: causal and at
        # or above tau, nothing to decide here
        bits = []
        for n in range(per_block):
            at, one = _mask_slab(kb * per_block + n, lanes)
            bits.append(mask_ref[0, :, pl.ds(at, lanes)] & one)
        keep = jnp.concatenate(bits, axis=1) != 0
        cnt = cnt + jnp.where(keep, 1.0, 0.0).sum(axis=1, keepdims=True)
        for kv in range(heads // group):
            k_blk = k_ref[0, kv, pl.ds(k_lo, block_k), :]
            v_blk = v_ref[0, kv, pl.ds(k_lo, block_k), :]
            # the kv head's query heads as ONE block of group * block_q
            # rows: the key block goes through the MXU once for all of
            # them (a row's scores do not change with the rows beside it)
            first = kv * group
            scores = _dot(q_ref[0, first:first + group].reshape(
                group * block_q, -1), k_blk, _NT).reshape(
                    group, block_q, block_k)
            for g in range(group):
                h = first + g
                s = jnp.where(keep, scores[g] * sm_scale, _NEG_INF)
                m_prev = m_ref[h]
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
                corr = jnp.exp(m_prev - m_new)
                m_ref[h] = m_new
                l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
                acc_ref[h] = acc_ref[h] * corr + _dot(p.astype(v_blk.dtype),
                                                      v_blk, _NN)
        return cnt

    cnt = lax.fori_loop(0, _n_blocks(q_lo, block_q, block_k), body,
                        jnp.zeros((block_q, 1), jnp.float32))
    for h in range(heads):
        l = jnp.maximum(l_ref[h], 1e-30)
        o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)
        lse_ref[0, h:h + 1, :] = _flip(m_ref[h] + jnp.log(l))
    kept_ref[0] = _flip(cnt)


def _kl_kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, wi_ref, tau_ref,
               lsei_ref, kl_ref, *, block_q, block_k, sm_scale, heads, group,
               hi):
    q_lo = pl.program_id(1) * block_q
    wi_cols = _columns(wi_ref, hi)
    tau = _flip(tau_ref[0])
    lse_cols = _row_columns(lse_ref, heads)

    def body(kb, carry):
        a, b, total = carry
        k_lo = pl.multiple_of(kb * block_k, block_k)
        tile, keep = _keep_tile(qi_ref, ki_ref, wi_cols, tau, q_lo, k_lo,
                                block_q, block_k)
        pbar = jnp.zeros((block_q, block_k), jnp.float32)
        for h in range(heads):
            k_blk = k_ref[0, h // group, pl.ds(k_lo, block_k), :]
            pbar = pbar + jnp.where(keep, jnp.exp(
                _dot(q_ref[0, h], k_blk, _NT) * sm_scale - lse_cols[h]), 0.0)
        pbar = pbar * (1.0 / heads)
        a = a + (pbar * jnp.log(jnp.maximum(pbar, 1e-37))).sum(
            axis=1, keepdims=True)
        b = b + (pbar * tile).sum(axis=1, keepdims=True)
        return a, b, total + pbar.sum(axis=1, keepdims=True)

    zero = jnp.zeros((block_q, 1), jnp.float32)
    a, b, total = lax.fori_loop(0, _n_blocks(q_lo, block_q, block_k), body,
                                (zero, zero, zero))
    # sum pbar (log pbar - (I - lse_I)) over the kept keys
    kl_ref[0] = _flip(a - b + _flip(lsei_ref[0]) * total)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi_ref,
                ki_ref, wi_ref, tau_ref, lsei_ref, gkl_ref, dq_ref, dk_ref,
                dv_ref, dqi_ref, dki_ref, dwi_ref, dq_acc, dqi_acc, x_ref, *,
                block_q, block_k, sm_scale, heads, group, hi):
    """dk, dv and dki are float32 and of the whole sequence: their one
    block stays in VMEM over a batch row's query blocks and is the
    accumulator (the caller scales and rounds them)."""
    i = pl.program_id(1)
    q_lo = i * block_q

    @pl.when(i == 0)
    def _init():
        dk_ref[:] = jnp.zeros_like(dk_ref)
        dv_ref[:] = jnp.zeros_like(dv_ref)
        dki_ref[:] = jnp.zeros_like(dki_ref)

    dq_acc[:] = jnp.zeros_like(dq_acc)
    dqi_acc[:] = jnp.zeros_like(dqi_acc)
    wi_cols = _columns(wi_ref, hi)
    tau = _flip(tau_ref[0])
    lsei, gkl = _flip(lsei_ref[0]), _flip(gkl_ref[0])
    lse_cols = _row_columns(lse_ref, heads)
    delta_cols = _row_columns(delta_ref, heads)
    head_lane = lax.broadcasted_iota(jnp.int32, (1, hi), 1)

    def body(kb, dwi):
        k_lo = pl.multiple_of(kb * block_k, block_k)
        # the tile's products qi_j . ki stay in x_ref for the indexer's
        # backward below
        tile, keep = _keep_tile(qi_ref, ki_ref, wi_cols, tau, q_lo, k_lo,
                                block_q, block_k, x_ref)
        rows = pl.ds(k_lo, block_k)
        pbar = jnp.zeros((block_q, block_k), jnp.float32)
        for h in range(heads):
            kv = h // group
            k_blk, v_blk = k_ref[0, kv, rows, :], v_ref[0, kv, rows, :]
            q, do = q_ref[0, h], do_ref[0, h]
            p = jnp.where(keep, jnp.exp(_dot(q, k_blk, _NT) * sm_scale
                                        - lse_cols[h]), 0.0)
            ds = (p * (_dot(do, v_blk, _NT) - delta_cols[h])).astype(
                k_blk.dtype)
            dq_acc[h] += _dot(ds, k_blk, _NN)
            dk_ref[0, kv, rows, :] += _dot(ds, q, _TN)
            dv_ref[0, kv, rows, :] += _dot(p.astype(do.dtype), do, _TN)
            pbar = pbar + p
        # d KL / d I = softmax of I over the kept keys - mean p, there
        di = gkl * (jnp.where(keep, jnp.exp(tile - lsei), 0.0)
                    - pbar * (1.0 / heads))
        ki_blk = ki_ref[0, rows, :]
        for j in range(hi):
            qi, x = qi_ref[0, j], x_ref[j]
            dwi = dwi + jnp.where(head_lane == j, (
                di * jnp.maximum(x, 0.0)).sum(axis=1, keepdims=True), 0.0)
            dx = jnp.where(x > 0.0, di * wi_cols[j], 0.0).astype(qi.dtype)
            dqi_acc[j] += _dot(dx, ki_blk, _NN)
            dki_ref[0, rows, :] += _dot(dx, qi, _TN)
        return dwi

    dwi = lax.fori_loop(0, _n_blocks(q_lo, block_q, block_k), body,
                        jnp.zeros((block_q, hi), jnp.float32))
    dq_ref[0] = (dq_acc[:] * sm_scale).astype(dq_ref.dtype)
    dqi_ref[0] = dqi_acc[:].astype(dqi_ref.dtype)
    dwi_ref[0] = dwi


# -- the calls -------------------------------------------------------------

def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _heads_first(x):
    """[b, s, h, d] -> [b, h, s, d]"""
    return x.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _thresholds(qi, ki, wi, topk, block_q, block_k, interpret):
    """qi [b, hi, s, di], ki [b, s, di], wi [b, s, hi] (scaled, float32)
    -> tau [b, 1, s], the packed kept set [b, s, words], lse_i [b, 1, s]."""
    b, hi, s, di = qi.shape
    words = mask_words(s, _mask_lanes(block_k))
    row = pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j))
    return pl.pallas_call(
        functools.partial(_tau_kernel, block_q=block_q, block_k=block_k,
                          topk=topk, hi=hi),
        grid=(b, s // block_q),
        in_specs=[pl.BlockSpec((1, hi, block_q, di),
                               lambda i, j: (i, 0, j, 0)),
                  pl.BlockSpec((1, s, di), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((1, block_q, hi), lambda i, j: (i, j, 0))],
        out_specs=(row, pl.BlockSpec((1, block_q, words),
                                     lambda i, j: (i, j, 0)), row),
        out_shape=(jax.ShapeDtypeStruct((b, 1, s), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, words), jnp.int32),
                   jax.ShapeDtypeStruct((b, 1, s), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((block_q, s), jnp.int32)],
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret, name=TAU_NAME)(qi, ki, wi)


def _scaled(wi, qi):
    return wi.astype(jnp.float32) * index_scale(qi.shape[2], qi.shape[3])


def kept_set(qi, ki, wi, tau, block=None):
    """What the thresholds' kernel hands ``dsa_fwd`` beside tau, in plain
    ``jax.numpy`` from [seq, seq] arrays (off the chip, for a caller that
    brings its own tau, and what the kernel's are tested against): (the
    kept set ``selection_mask`` bit-packed as `_mask_slab` lays it out for
    the kernels' key block [b, s, words] int32, the log-sum-exp of I over
    it [b, s])."""
    b, s = tau.shape
    scores = index_scores(qi, ki, wi)
    keep = _kept(scores, tau)
    top = jnp.where(keep, scores, _NEG_INF).max(axis=-1)
    total = jnp.where(keep, jnp.exp(scores - top[..., None]), 0.0).sum(-1)
    lanes = _mask_lanes(_block_for(s, block or _BLOCK))
    words = mask_words(s, lanes)
    bits = jnp.pad(keep, ((0, 0), (0, 0), (0, 32 * words - s))).reshape(
        b, s, words // lanes, 32, lanes).astype(jnp.uint32)
    packed = (bits << jnp.arange(32, dtype=jnp.uint32)[:, None]).sum(
        axis=3, dtype=jnp.uint32)
    return (lax.bitcast_convert_type(packed, jnp.int32).reshape(b, s, words),
            top + jnp.log(jnp.maximum(total, 1e-30)))


def index_selection(qi, ki, wi, topk, use_kernel=None, interpret=False,
                    block=None):
    """(tau [b, s] float32, the packed kept set [b, s, words] int32, lse_i
    [b, s] float32): for each query the ``topk``-th largest of its causal
    scores I[t, s <= t], -inf while it has at most ``topk`` keys — so that
    {s <= t : I[t, s] >= tau[t]} are the keys it keeps, exact ties at tau
    all kept — with that set a bit a pair, as ``dsa_fwd`` reads it, and
    the log-sum-exp of I over it. Exact on either path (``use_kernel``:
    the counting kernel, which makes all three from the scores it holds;
    default on a TPU for shapes it takes — else ``lax.top_k`` over the
    materialised scores and ``kept_set``). ``block`` is ``select_attend``'s.
    tau and lse_i carry the names a layer under remat saves them by (the
    kept set has none: it lives until the layer's one forward kernel has
    read it). No gradient."""
    qi, ki, wi = lax.stop_gradient((qi, ki, wi))
    b, s, hi, di = qi.shape
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      and thresholds_kernel_reason(
                          s, di, ki.dtype.itemsize) is None)
    if use_kernel:
        block_q = _block_for(s, block or _TAU_BLOCK_Q)
        block_k = _block_for(s, block or _BLOCK)
        tau, mask, lse_i = _thresholds(_heads_first(qi), ki, _scaled(wi, qi),
                                       int(topk), block_q, block_k,
                                       interpret)
        tau, lse_i = tau[:, 0], lse_i[:, 0]
    else:
        if s <= topk:
            tau = jnp.full((b, s), -jnp.inf, jnp.float32)
        else:
            scores = jnp.where(_causal(s)[None], index_scores(qi, ki, wi),
                               -jnp.inf)
            kth = lax.top_k(scores, topk)[0][..., -1]
            tau = jnp.where(jnp.arange(s)[None] < topk, -jnp.inf, kth)
        mask, lse_i = kept_set(qi, ki, wi, tau, block)
    return (checkpoint_name(tau, SAVED_UNDER_REMAT[3]), mask,
            checkpoint_name(lse_i, SAVED_RESIDUALS[2]))


def index_thresholds(qi, ki, wi, topk, use_kernel=None, interpret=False,
                     block=None):
    """``index_selection``'s tau alone."""
    return index_selection(qi, ki, wi, topk, use_kernel, interpret, block)[0]


def _specs(b, s, heads, kv_heads, d, hi, di, block_q):
    """The block specs the three attention kernels share. The operands
    and results of the WHOLE sequence change block with the batch row
    alone, so one buffer: within a row nothing is fetched behind the
    first."""
    at_q = lambda i, j: (i, 0, j, 0)       # noqa: E731
    whole = dict(pipeline_mode=pl.Buffered(1))
    return {
        "q": pl.BlockSpec((1, heads, block_q, d), at_q),
        "kv": pl.BlockSpec((1, kv_heads, s, d), lambda i, j: (i, 0, 0, 0),
                           **whole),
        "qi": pl.BlockSpec((1, hi, block_q, di), at_q),
        "ki": pl.BlockSpec((1, s, di), lambda i, j: (i, 0, 0), **whole),
        "wi": pl.BlockSpec((1, block_q, hi), lambda i, j: (i, j, 0)),
        "row": pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        "rows": pl.BlockSpec((1, heads, block_q), lambda i, j: (i, 0, j)),
    }


@functools.partial(jax.jit, static_argnums=(9, 10, 11))
def _forward(q, k, v, qi, ki, wi, tau, mask, lse_i, sm_scale, block,
             interpret):
    """Kernel layouts: q [b, h, s, d], k, v [b, hkv, s, d], qi [b, hi, s,
    di], ki [b, s, di], wi [b, s, hi], tau and lse_i [b, 1, s], the packed
    kept set [b, s, words]. Returns (out, lse [b, h, s], kept, kl: each
    [b, 1, s])."""
    b, heads, s, d = q.shape
    kv_heads, hi, di = k.shape[1], qi.shape[1], qi.shape[3]
    sp = _specs(b, s, heads, kv_heads, d, hi, di, block)
    shape = dict(block_q=block, block_k=block, sm_scale=sm_scale,
                 heads=heads, group=heads // kv_heads)
    row = jax.ShapeDtypeStruct((b, 1, s), jnp.float32)
    rows = jax.ShapeDtypeStruct((b, heads, s), jnp.float32)
    call = functools.partial(pl.pallas_call, grid=(b, s // block),
                             compiler_params=_params("parallel", "parallel"),
                             interpret=interpret)
    out, lse, kept = call(
        functools.partial(_fwd_kernel, **shape),
        in_specs=[sp["q"], sp["kv"], sp["kv"],
                  pl.BlockSpec((1, block, mask.shape[2]),
                               lambda i, j: (i, j, 0))],
        out_specs=(sp["q"], sp["rows"], sp["row"]),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype), rows, row),
        scratch_shapes=[pltpu.VMEM((heads, block, d), jnp.float32),
                        pltpu.VMEM((heads, block, 1), jnp.float32),
                        pltpu.VMEM((heads, block, 1), jnp.float32)],
        name=FWD_NAME)(q, k, v, mask)
    kl = call(
        functools.partial(_kl_kernel, hi=hi, **shape),
        in_specs=[sp["q"], sp["kv"], sp["rows"], sp["qi"], sp["ki"],
                  sp["wi"], sp["row"], sp["row"]],
        out_specs=sp["row"], out_shape=row,
        name=KL_NAME)(q, k, lse, qi, ki, wi, tau, lse_i)
    return out, lse, kept, kl


@functools.partial(jax.jit, static_argnums=(12, 13, 14))
def _backward(q, k, v, do, lse, delta, qi, ki, wi, tau, lse_i, gkl,
              sm_scale, block, interpret):
    b, heads, s, d = q.shape
    kv_heads, hi, di = k.shape[1], qi.shape[1], qi.shape[3]
    sp = _specs(b, s, heads, kv_heads, d, hi, di, block)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    f32 = lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32)  # noqa: E731
    dq, dk, dv, dqi, dki, dwi = pl.pallas_call(
        functools.partial(_bwd_kernel, block_q=block, block_k=block,
                          sm_scale=sm_scale, heads=heads,
                          group=heads // kv_heads, hi=hi),
        grid=(b, s // block),
        in_specs=[sp["q"], sp["kv"], sp["kv"], sp["q"], sp["rows"],
                  sp["rows"], sp["qi"], sp["ki"], sp["wi"], sp["row"],
                  sp["row"], sp["row"]],
        out_specs=(sp["q"], sp["kv"], sp["kv"], sp["qi"], sp["ki"],
                   sp["wi"]),
        out_shape=(like(q), f32(k), f32(v), like(qi), f32(ki), like(wi)),
        scratch_shapes=[pltpu.VMEM((heads, block, d), jnp.float32),
                        pltpu.VMEM((hi, block, di), jnp.float32),
                        pltpu.VMEM((hi, block, block), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret, name=BWD_NAME)(
            q, k, v, do, lse, delta, qi, ki, wi, tau, lse_i, gkl)
    return (dq, (dk * sm_scale).astype(k.dtype), dv.astype(v.dtype), dqi,
            dki.astype(ki.dtype), dwi)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _select_attend(q, k, v, qi, ki, wi, tau, mask, lse_i, sm_scale, block,
                   interpret):
    return _select_attend_fwd(q, k, v, qi, ki, wi, tau, mask, lse_i,
                              sm_scale, block, interpret)[0]


def _select_attend_fwd(q, k, v, qi, ki, wi, tau, mask, lse_i, sm_scale,
                       block, interpret):
    """Arguments, results and residuals in the models' layout (the kernels'
    copies live for the length of a kernel, as in ops/flash_attention). The
    residuals the kernel made, the result and lse, carry
    ``SAVED_RESIDUALS``' names; lse_i came with its own. The kept set is
    read here and is no residual."""
    out, lse, kept, kl = _forward(
        _heads_first(q), _heads_first(k), _heads_first(v), _heads_first(qi),
        ki, _scaled(wi, qi), tau[:, None], mask, lse_i[:, None], sm_scale,
        block, interpret)
    out, lse = (checkpoint_name(x, n) for x, n in zip(
        (_heads_first(out), lse), SAVED_RESIDUALS))
    return ((out, kl[:, 0], kept[:, 0]),
            (q, k, v, qi, ki, wi, tau, out, lse, lse_i))


def _select_attend_bwd(sm_scale, block, interpret, res, g):
    q, k, v, qi, ki, wi, tau, out, lse, lse_i = res
    g_out, g_kl, _ = g
    delta = jnp.sum(g_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)
    q, k, v, qi = lax.optimization_barrier((q, k, v, qi))
    dq, dk, dv, dqi, dki, dwi = _backward(
        _heads_first(q), _heads_first(k), _heads_first(v),
        _heads_first(g_out.astype(q.dtype)), lse, delta, _heads_first(qi),
        ki, _scaled(wi, qi), tau[:, None], lse_i[:, None],
        g_kl.astype(jnp.float32)[:, None], sm_scale, block, interpret)
    scale = index_scale(qi.shape[2], qi.shape[3])
    return (_heads_first(dq), _heads_first(dk), _heads_first(dv),
            _heads_first(dqi), dki, (dwi * scale).astype(wi.dtype),
            jnp.zeros_like(tau), None, jnp.zeros_like(lse_i))


_select_attend.defvjp(_select_attend_fwd, _select_attend_bwd)


def select_attend(q, k, v, select, sm_scale=None, interpret=False,
                  block=None):
    """q [b, s, h, d]; k, v [b, s, hkv, d] (query head i reads kv head
    ``i // (h // hkv)``); ``select`` = (qi [b, s, hi, di], ki [b, s, di],
    wi [b, s, hi], tau [b, s]) and, from ``index_selection`` under the
    same ``block``, the packed kept set and lse_i — made here by
    ``kept_set`` for a caller that brings tau alone. Returns (out [b, s,
    h, d], kl [b, s]: KL(stop_gradient(mean over heads of p) || softmax of
    I over the kept keys), kept [b, s]: keys each query read). Gradients:
    out -> q, k, v only (the choice is discrete); kl -> qi, ki, wi only."""
    qi, ki, wi, tau, *made = select
    b, s, heads, d = q.shape
    why = kernel_reason(s, k.shape[2], d, qi.shape[3], k.dtype.itemsize,
                        qi.shape[2])
    if why:
        raise ValueError("no selection kernel for this shape: " + why)
    if heads % k.shape[2]:
        raise ValueError("%d query heads do not divide over %d kv heads"
                         % (heads, k.shape[2]))
    if sm_scale is None:
        sm_scale = d ** -0.5
    block = _block_for(s, block or _BLOCK)
    tau = lax.stop_gradient(tau).astype(jnp.float32)
    mask, lse_i = lax.stop_gradient(
        tuple(made) or kept_set(qi, ki, wi, tau, block))
    if mask.shape != (b, s, mask_words(s, _mask_lanes(block))):
        raise ValueError("a kept set %s packed for another block than %d"
                         % (mask.shape, block))
    return _select_attend(q, k, v, qi, ki, wi, tau, mask, lse_i, sm_scale,
                          block, interpret)


def dense_select_attend(q, k, v, select, sm_scale=None):
    """The same in plain ``jax.numpy`` with [seq, seq] arrays (the dense
    dispatch; what the kernels are tested against)."""
    qi, ki, wi, tau = select[:4]
    b, s, heads, d = q.shape
    kv_heads = k.shape[2]
    group = heads // kv_heads
    if sm_scale is None:
        sm_scale = d ** -0.5
    scores_i = index_scores(qi, ki, wi)
    keep = _kept(scores_i, tau)
    qg = q.reshape(b, s, kv_heads, group, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    probs = jax.nn.softmax(jnp.where(keep[:, None, None], scores, _NEG_INF),
                           axis=-1)
    probs = jnp.where(keep[:, None, None], probs, 0.0)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    pbar = lax.stop_gradient(probs.mean(axis=(1, 2)))
    log_pi = jax.nn.log_softmax(jnp.where(keep, scores_i, _NEG_INF), axis=-1)
    kl = jnp.sum(jnp.where(keep, pbar * (jnp.log(jnp.maximum(pbar, 1e-37))
                                         - log_pi), 0.0), axis=-1)
    return (out.reshape(b, s, heads, d).astype(q.dtype), kl,
            keep.sum(axis=-1).astype(jnp.float32))
