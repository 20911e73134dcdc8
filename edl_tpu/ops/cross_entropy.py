"""The cross-entropy of a row of logits against one integer target, for the
language models' losses: float32 ``logits`` [..., T, V] AS THE HEAD LEFT THEM
and int32 ``targets`` [..., T] -> ``lse - logits[target]`` [..., T] float32.

The target's logit is a masked sum over V, not a gather, so the forward is
reductions over V of the logits where they lie, and the backward one
elementwise pass, ``g * (softmax - one_hot)``, written here and not derived:
nothing stands between the head's product and the loss for the compiler to
copy, flatten or slice. A caller that predicts the NEXT token shifts the
targets ([B, T] int32) and drops the last row of the RESULT, never a row of
the logits.
"""

import jax
import jax.numpy as jnp
from jax import lax


def _hit(logits, targets):
    """[..., T, V] bool: the target's place in its row."""
    return lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1) == targets[..., None]


def _forward(logits, targets):
    top = logits.max(axis=-1)
    lse = top + jnp.log(jnp.exp(logits - top[..., None]).sum(axis=-1))
    picked = jnp.where(_hit(logits, targets), logits, 0.0).sum(axis=-1)
    return lse - picked, (logits, lse, targets)


def _backward(saved, g):
    logits, lse, targets = saved
    return g[..., None] * (
        jnp.exp(logits - lse[..., None])
        - _hit(logits, targets).astype(logits.dtype)), None


@jax.custom_vjp
def token_cross_entropy(logits, targets):
    """float32 logits [..., T, V], int32 targets [..., T] -> [..., T]."""
    return _forward(logits, targets)[0]


token_cross_entropy.defvjp(_forward, _backward)


def next_ids(ids):
    """[B, T] ids -> each position's NEXT id, the targets of a next-token
    loss over all T rows; the last position takes the first id, a valid
    target for the one row whose loss the caller drops."""
    return jnp.roll(ids, -1, axis=1)
