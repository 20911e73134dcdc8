"""Shared attention-impl dispatch for the model families (BERT, GPT, the
sparse decoder): in-shard ring / ring over the sp mesh axis / Pallas flash
kernel / dense — one copy of the -1e30 mask convention, sm_scale, and the
interpret mode CPU tests use. The flash and dense paths take a causal
``window``, a learned selection of keys (``select``) or the two-stream
block mask of training by diffusion over blocks (``streams``), and fewer
key-value heads than query heads (grouped-query attention); K and V are
never repeated in memory. Lives in ops/ (neutral
layer) so model modules don't import each other for infrastructure.

``use_flash=None`` (the default) auto-dispatches: on TPU, shapes the
Pallas kernel handles exactly take the flash path; everything else stays
dense. Explicit ``True``/``False`` still force a path, so callers that
pinned a choice before the auto default keep their behavior.
"""

import os

import jax
import jax.numpy as jnp

# What the Pallas kernels (ops/flash_attention.py) take without a ragged
# tile: their tiles are 512 wide, or 256 or 128 by what divides the
# sequence, so whole multiples of 128 (or one tile for a shorter
# sequence). Lane tiling wants head_dim % 8 == 0.
_FLASH_BLOCK = 128
_FLASH_HEAD_MULT = 8


def flash_dispatch_reason(seq_len, head_dim, *, mask=None, platform=None,
                          seq_kv=None, offset=None, streams=None,
                          itemsize=2, v_head_dim=None):
    """Why auto-dispatch would (not) pick flash for this shape.

    ``head_dim`` is the width of q and k; ``v_head_dim`` that of v and of
    the result where it is another (latent attention: 192 beside 128),
    which the band kernels take as they take equal widths.

    The kernel takes any number of query heads per key-value head and,
    under ``causal``, a ``window`` (a query sees its own position and the
    ``window - 1`` before it); neither changes the answer below, so
    neither is asked for. (A window without ``causal`` is refused by
    :func:`attention_context` itself, whatever the path.)

    Returns ``None`` when the flash path is legal and profitable, else a
    human-readable reason string (the dense path is taken). Pure shape
    math — safe to call from tests and benches without tracing.

    ``seq_kv`` (default: ``seq_len``) is the K/V sequence length.
    Decode-shaped queries — seq_q=1 (or any seq_q != seq_kv) against a
    cached K/V — are NEVER flash-legal here: the Pallas kernel derives
    its causal block mask from the query position, so with q shorter
    than kv it would mask against the wrong diagonal and read an
    under-tiled q block. The decode path in models/gpt.py owns its own
    masked dense attention against the cache; auto-dispatch must not
    steal it mid-decode.

    ``offset`` (chunked/suffix prefill: the chunk's KV write offset)
    marks a CHUNK-SHAPED query: row i's causal frontier sits at
    ``offset + i``, not ``i``, and the legal key range spans the whole
    cached row. The flash kernel anchors its diagonal at position 0, so
    any non-None offset is dense-only for the same reason decode is —
    the offset-prefill path in models/gpt.py owns its masked dense
    attention against the cache.

    ``streams`` = (block_length, clean_from): the TWO-STREAM BLOCK MASK
    (a noised copy of each sequence before its clean copy;
    ops/block_diffusion_attention.py). Its kernels are refused, and the
    dense path over the whole [2T, 2T] mask taken, where the clean half is
    no whole number of tiles, the block length does not divide a tile, or
    the clean half's k + v (``itemsize`` bytes an element) pass the
    kernels' resident limit.
    """
    if mask is not None:
        return "attention_mask set (flash kernel has no mask support)"
    if offset is not None:
        return ("chunk-shaped query (prefill_offset set): flash causal "
                "masking anchors the diagonal at position 0, not at the "
                "chunk offset")
    if seq_kv is not None and seq_kv != seq_len:
        return ("decode-shaped query (seq_q %d != seq_kv %d): flash "
                "causal masking assumes square q/kv" % (seq_len, seq_kv))
    platform = platform or jax.default_backend()
    if os.environ.get("EDL_TPU_FLASH_AUTO", "") == "0":
        return "disabled via EDL_TPU_FLASH_AUTO=0"
    if platform != "tpu":
        return "platform %r (interpret-mode flash is slower than dense)" \
            % platform
    if head_dim % _FLASH_HEAD_MULT != 0:
        return "head_dim %d not a multiple of %d" % (head_dim,
                                                     _FLASH_HEAD_MULT)
    if v_head_dim is not None and v_head_dim != head_dim:
        if v_head_dim % _FLASH_HEAD_MULT != 0:
            return "v_head_dim %d not a multiple of %d" % (
                v_head_dim, _FLASH_HEAD_MULT)
        if streams is not None:
            return ("the two-stream block mask's kernels take one width "
                    "for q, k and v (%d beside %d)" % (head_dim, v_head_dim))
    if streams is not None:
        from edl_tpu.ops import block_diffusion_attention
        return block_diffusion_attention.kernel_reason(
            seq_len, head_dim, streams, itemsize)
    if seq_len > _FLASH_BLOCK and seq_len % _FLASH_BLOCK != 0:
        # ragged q blocks are not masked by the kernel; ragged kv is.
        # Stay conservative: only whole-block (or single-block) seqs.
        return "seq_len %d not a multiple of block %d" % (seq_len,
                                                          _FLASH_BLOCK)
    return None


def _causal_band(q_pos, k_pos, window):
    """[q, k] bool: which keys a query may read: none ahead of it and,
    with a window, its own position and the ``window - 1`` before."""
    keep = q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        keep = jnp.logical_and(keep, q_pos[:, None] - k_pos[None, :] < window)
    return keep


def selected_attention(q, k, v, select, *, dtype, use_flash=None):
    """Causal attention over a LEARNED selection of keys
    (ops/sparse_attention.py): ``select`` = (qi [b, s, hi, di], ki [b, s,
    di], wi [b, s, hi], tau [b, s]) — the indexer's queries, keys and head
    weights and each query's threshold — and, where ``index_selection``
    made them with tau, the packed kept set and the indexer's lse that the
    forward kernel reads in place of the scores; query t reads the keys
    s <= t whose score I[t, s] reaches tau[t]. Returns
    (context [b, s, heads, dim], kl [b, s], kept [b, s]): the context, the
    indexer's loss per row (its only source of gradient) and the number of
    keys each query read. The same dispatch as :func:`attention_context`:
    the Pallas kernels on a TPU for shapes they take (``use_flash=True``
    forces them, in the interpreter on the CPU), else dense."""
    from edl_tpu.ops import sparse_attention
    if use_flash is None:
        use_flash = (
            flash_dispatch_reason(q.shape[1], q.shape[-1],
                                  seq_kv=k.shape[1]) is None
            and sparse_attention.kernel_reason(
                q.shape[1], k.shape[2], q.shape[-1], select[0].shape[-1],
                k.dtype.itemsize, select[0].shape[2]) is None)
    if use_flash:
        out, kl, kept = sparse_attention.select_attend(
            q, k, v, select, interpret=jax.default_backend() == "cpu")
    else:
        out, kl, kept = sparse_attention.dense_select_attend(q, k, v, select)
    return out.astype(dtype), kl, kept


def block_diffusion_attention(q, k, v, streams, *, dtype, use_flash=None):
    """Attention under the TWO-STREAM BLOCK MASK
    (ops/block_diffusion_attention.py): ``streams`` = (block_length,
    clean_from) for a stream [x_t ; x_0] of 2 * clean_from rows. Returns
    (context [b, s, heads, dim], pairs [b, s] float32: the keys each row
    read under the mask the path applied). The same dispatch as
    :func:`attention_context`: the Pallas kernels on a TPU for shapes they
    take (``use_flash=True`` forces them, in the interpreter on the CPU),
    else dense, with :func:`flash_dispatch_reason` saying why."""
    from edl_tpu.ops import block_diffusion_attention as bda
    streams = bda.check_streams(streams, q.shape[1])
    if use_flash is None:
        use_flash = flash_dispatch_reason(
            q.shape[1], q.shape[-1], seq_kv=k.shape[1], streams=streams,
            itemsize=k.dtype.itemsize) is None
    if use_flash:
        out, pairs = bda.attend(q, k, v, streams,
                                interpret=jax.default_backend() == "cpu")
    else:
        out, pairs = bda.dense_attend(q, k, v, streams)
    return out.astype(dtype), pairs


def attention_context(q, k, v, *, causal, mask, dtype, ring_axis=None,
                      use_ring=False, use_flash=None, mesh=None,
                      window=None, select=None, streams=None):
    """The shared attention-impl dispatch for BERT, GPT and the sparse
    decoder: in-shard ring (already inside a shard_map over
    ``ring_axis``) / ring over the sp mesh axis / Pallas flash kernel /
    dense — one copy of the -1e30 mask convention, sm_scale, and the
    interpret mode CPU tests use.

    q is [batch, seq, heads, dim]; k and v are [batch, seq_kv, kv_heads,
    dim] with ``heads`` a multiple of ``kv_heads`` (query head i reads kv
    head ``i // (heads // kv_heads)``). On the flash and dense paths v may
    be of another width than q and k (latent attention): the result is v's
    width, the scale q's and k's. ``window`` (flash and dense paths,
    needs ``causal``): a query reads its own position and the
    ``window - 1`` before it; ``None`` reads the whole causal prefix.
    ``select`` (flash and dense paths, needs ``causal``, excludes
    ``window``, a padding mask and the ring): (qi, ki, wi, tau) of a learned
    indexer — a query reads the keys of its causal prefix whose index score
    reaches its threshold (:func:`selected_attention`, which also returns
    the indexer's loss and the keys kept). ``streams`` (flash and dense
    paths; excludes ``causal``, ``window``, ``select``, a padding mask and
    the ring — it is the whole of what a query may read): (block_length,
    clean_from), the two-stream block mask of training by diffusion over
    blocks (:func:`block_diffusion_attention`, which also returns the pairs
    each row read).

    ``use_flash``: ``True`` forces the Pallas flash kernel, ``False``
    forces dense, ``None`` (default) auto-dispatches by
    :func:`flash_dispatch_reason` (flash on TPU for kernel-legal shapes,
    dense otherwise). The old default was ``False``; auto is numerics-
    gated against dense in tier-1 (tests/test_attention_dispatch.py).
    """
    head_dim, v_dim = q.shape[-1], v.shape[-1]
    scale = head_dim ** -0.5
    heads, kv_heads = q.shape[2], k.shape[2]
    group = heads // kv_heads
    if group * kv_heads != heads:
        raise ValueError("%d query heads do not divide over %d kv heads"
                         % (heads, kv_heads))
    if window is not None and not causal:
        raise ValueError("a window needs causal=True")
    if (select is not None or streams is not None or ring_axis
            or use_ring) and v_dim != head_dim:
        raise ValueError("only the band kernels and the dense path take v "
                         "at another width (%d) than q and k (%d)"
                         % (v_dim, head_dim))
    if streams is not None:
        if causal or window is not None or select is not None:
            raise ValueError("the two-stream block mask is the whole mask: "
                             "no causal flag, window or selection beside it")
        if ring_axis or use_ring:
            raise ValueError("ring attention takes no two-stream block mask")
        if mask is not None:
            raise ValueError("the two-stream block mask takes no padding "
                             "mask")
        if q.shape[1] != k.shape[1]:
            raise ValueError("the two-stream block mask needs square q/kv")
        return block_diffusion_attention(q, k, v, streams, dtype=dtype,
                                         use_flash=use_flash)[0]
    if select is not None:
        if not causal:
            raise ValueError("a selection needs causal=True")
        if window is not None:
            raise ValueError("a selection excludes a window")
        if ring_axis or use_ring:
            raise ValueError("ring attention takes no selection")
        if mask is not None:
            raise ValueError("a selection takes no padding mask")
        if q.shape[1] != k.shape[1]:
            raise ValueError("a selection needs square q/kv")
        return selected_attention(q, k, v, select, dtype=dtype,
                                  use_flash=use_flash)[0]
    if ring_axis or use_ring:
        if window is not None or group > 1:
            raise ValueError("ring attention takes no window and equal "
                             "head counts")
    if ring_axis:
        from edl_tpu.parallel.ring_attention import _ring_attention_shard
        return _ring_attention_shard(q, k, v, axis_name=ring_axis,
                                     causal=causal, sm_scale=scale)
    if use_ring:
        from edl_tpu.parallel.ring_attention import ring_attention
        return ring_attention(q, k, v, mesh, causal=causal)
    if use_flash is None:
        use_flash = flash_dispatch_reason(q.shape[1], head_dim,
                                          mask=mask, seq_kv=k.shape[1],
                                          v_head_dim=v_dim) is None
    if use_flash:
        if mask is not None:
            raise ValueError(
                "use_flash does not support attention_mask yet; drop "
                "the mask (fixed-length batches) or use the dense path")
        if q.shape[1] != k.shape[1]:
            raise ValueError(
                "use_flash=True with decode-shaped q (seq_q %d != "
                "seq_kv %d): the flash kernel's causal mask assumes "
                "square q/kv; use the cached dense decode path"
                % (q.shape[1], k.shape[1]))
        from edl_tpu.ops.flash_attention import mha
        # the Pallas interpreter is for CPU tests that FORCE
        # use_flash=True (auto-dispatch never picks flash off-TPU).
        # Every chip-side process runs with JAX_PLATFORMS=tpu, under
        # which JAX raises at start-up rather than handing out a CPU
        # backend — so a process that lost its chip cannot reach this
        # line and quietly run the kernel in the interpreter.
        return mha(q, k, v, causal=causal, window=window,
                   interpret=jax.default_backend() == "cpu")
    b, s = q.shape[:2]
    if group > 1:
        # the query heads of one kv head, one run of the sequence after
        # another: [b, group * s, kv_heads, d] against k as it is
        q = q.reshape(b, s, kv_heads, group, head_dim).transpose(
            0, 3, 1, 2, 4).reshape(b, group * s, kv_heads, head_dim)
    scores = jnp.einsum("bqhd,bkhd->bhqk",
                        (q * scale).astype(jnp.float32),
                        k.astype(jnp.float32))
    if causal:
        keep = _causal_band(jnp.tile(jnp.arange(s), group),
                            jnp.arange(k.shape[1]), window)
        scores = jnp.where(keep[None, None], scores, -1e30)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                     v.astype(jnp.float32)).astype(dtype)
    if group > 1:
        out = out.reshape(b, group, s, kv_heads, v_dim).transpose(
            0, 2, 3, 1, 4).reshape(b, s, heads, v_dim)
    return out
