"""Ring attention: exact attention over sequence-sharded q/k/v with
blockwise online softmax and ICI neighbor exchange.

Long-context sequence/context parallelism for this framework (net-new vs
the reference, which had none — SURVEY.md §5.7, a stated first-class goal
of the TPU rebuild). The algorithm is the public ring-attention recipe
(blockwise flash-style accumulation + `lax.ppermute` of the kv block around
the `sp` mesh axis); communication is overlapped with the next block's
compute by XLA and rides ICI, never materializing the full [seq, seq]
score matrix or the full kv on any chip.

Shapes: q, k, v are [batch, seq, heads, head_dim], sharded on ``seq`` over
the ``sp`` axis. Accumulation is float32 regardless of input dtype.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from edl_tpu.runtime.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS

_NEG_INF = -1e30


def _ring_attention_shard(q, k, v, *, axis_name, causal, sm_scale):
    axis_size = lax.psum(1, axis_name)
    axis_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk = k.shape[1]

    # [b, h, sq, d] layouts for the accumulators
    q32 = (q.astype(jnp.float32) * sm_scale).transpose(0, 2, 1, 3)
    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    q_pos = axis_idx * sq + jnp.arange(sq)

    def body(step, carry):
        k_blk, v_blk, acc, m, l = carry
        src_block = (axis_idx - step) % axis_size
        k32 = k_blk.astype(jnp.float32).transpose(0, 2, 1, 3)
        v32 = v_blk.astype(jnp.float32).transpose(0, 2, 1, 3)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q32, k32)
        if causal:
            k_pos = src_block * sk + jnp.arange(sk)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, _NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        if causal:  # fully-masked rows contribute nothing
            p = jnp.where(scores <= _NEG_INF / 2, 0.0, p)
        correction = jnp.exp(m - m_new)
        l_new = l * correction + p.sum(axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v32)
        # rotate the kv block to the next device on the ring
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return k_next, v_next, acc_new, m_new, l_new

    _, _, acc, _, l = lax.fori_loop(0, axis_size, body,
                                    (k, v, acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention(q, k, v, mesh, causal=False, sm_scale=None,
                   batch_axis=DATA_AXIS, seq_axis=SEQ_AXIS,
                   head_axis="auto"):
    """Exact attention with q/k/v sequence-sharded over ``seq_axis``.

    Returns [batch, seq, heads, head_dim] with the same sharding as q.
    Differentiable (ppermute has a transpose rule; the backward pass runs
    the ring in reverse).

    head_axis: additionally shard the head dim (tensor parallelism
    composed with sequence parallelism — heads are independent, so the
    ring runs per tp shard with no extra communication). "auto" uses the
    mesh's tp axis when it is >1 and divides num_heads; None disables.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if head_axis == "auto":
        tp = mesh.shape.get(MODEL_AXIS, 1)
        head_axis = (MODEL_AXIS
                     if tp > 1 and q.shape[2] % tp == 0 else None)
    spec = P(batch_axis, seq_axis, head_axis, None)
    fn = shard_map(
        functools.partial(_ring_attention_shard, axis_name=seq_axis,
                          causal=causal, sm_scale=sm_scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def dense_attention(q, k, v, causal=False, sm_scale=None):
    """Reference single-device attention (for tests and small models)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk",
                        q.astype(jnp.float32) * sm_scale,
                        k.astype(jnp.float32))
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
