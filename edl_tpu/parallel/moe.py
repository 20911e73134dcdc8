"""Expert parallelism: a top-k-routed mixture-of-experts FFN with experts
sharded over the ``ep`` mesh axis and token exchange via all_to_all.

Net-new vs the reference (no EP anywhere in its tree, SURVEY.md §2.7).
Switch/GShard-style routing: each token goes to its top-k experts with
renormalized gate weights, bounded by a per-expert capacity; slots that
overflow are dropped (a token whose every slot dropped passes through
unchanged). Inside shard_map, tokens are exchanged with `lax.all_to_all`
over ep (ICI), each slice runs only its local experts' FFNs, and results
return the same way. The Switch auxiliary load-balancing loss
(E * Σ_e fraction_e * mean_prob_e) is available from both the sharded and
dense paths so training can penalize routing collapse.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from edl_tpu.runtime.mesh import EXPERT_AXIS


def init_moe_params(rng, num_experts, d_model, d_ff):
    k1, k2, k3 = jax.random.split(rng, 3)
    scale = d_model ** -0.5
    return {
        "router": jax.random.normal(k1, (d_model, num_experts)) * scale,
        "w_in": jax.random.normal(k2, (num_experts, d_model, d_ff)) * scale,
        "w_out": jax.random.normal(k3, (num_experts, d_ff, d_model))
                 * (d_ff ** -0.5),
    }


def _route(x, router, k):
    """(logits [n,E] f32, probs [n,E], gates [n,k] renorm., choices [n,k])."""
    logits = (x @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, choices = lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return logits, probs, gates.astype(x.dtype), choices


def _aux_loss(probs, choices, num_experts):
    """Switch load-balance loss: E * Σ_e f_e * p̄_e — minimized (=1) when
    routing is uniform. f_e counts top-1 assignments (the load that
    actually binds capacity); p̄_e is the mean router probability."""
    f = jnp.mean(jax.nn.one_hot(choices[:, 0], num_experts,
                                dtype=jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p)


def _z_loss(logits):
    """ST-MoE router z-loss: mean_t (logsumexp_e logits)² — penalizes
    large router logits, which drift into fp32-softmax saturation and
    training instability in long MoE runs."""
    return jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)


def moe_ffn_dense(params, x, k=1, combine_by_gate=True, return_aux=False,
                  return_metrics=False):
    """Reference implementation: every expert computed densely, combined
    by the renormalized top-k gates (capacity ignored). k=1 keeps the
    classic Switch behavior (gate ≡ 1 after renormalization).

    return_metrics returns (out, {"aux_loss", "z_loss", "drop_fraction"})
    — drop_fraction is 0 by construction (no capacity bound here)."""
    num_experts = params["w_in"].shape[0]
    logits, probs, gates, choices = _route(x, params["router"], k)
    h = jnp.einsum("nd,edf->enf", x, params["w_in"])
    h = jax.nn.relu(h)
    y = jnp.einsum("enf,efd->end", h, params["w_out"])      # [E, n, d]
    combine = jnp.zeros((x.shape[0], num_experts), x.dtype)
    for slot in range(k):
        combine = combine + jax.nn.one_hot(
            choices[:, slot], num_experts, dtype=x.dtype) * (
                gates[:, slot:slot + 1] if combine_by_gate else 1.0)
    out = jnp.einsum("end,ne->nd", y, combine)
    if return_metrics:
        return out, {"aux_loss": _aux_loss(probs, choices, num_experts),
                     "z_loss": _z_loss(logits),
                     "drop_fraction": jnp.zeros((), jnp.float32)}
    if return_aux:
        return out, _aux_loss(probs, choices, num_experts)
    return out


def _moe_shard(params, x, *, axis_name, num_experts, capacity, k,
               stat_axes):
    """One ep slice: local tokens [n, d], local experts [E/ep, d, ...].
    Returns (y [n, d], metrics dict) — the metrics are GLOBAL: f/p/z/drop
    stats are pmean-reduced over all token shards so every slice returns
    the same values the dense reference computes."""
    ep = lax.psum(1, axis_name)
    experts_local = num_experts // ep
    n, d = x.shape

    logits, probs, gates, choices = _route(x, params["router"], k)
    f = lax.pmean(jnp.mean(jax.nn.one_hot(
        choices[:, 0], num_experts, dtype=jnp.float32), axis=0), stat_axes)
    p = lax.pmean(jnp.mean(probs, axis=0), stat_axes)
    aux = num_experts * jnp.sum(f * p)
    z = lax.pmean(_z_loss(logits), stat_axes)

    # flatten the k routing slots: slot i of token t is row t*k+i
    flat_choice = choices.reshape(n * k)
    flat_gate = gates.reshape(n * k)
    xk = jnp.repeat(x, k, axis=0)                     # [n*k, d]

    # per-destination-slice capacity buffers: [ep, capacity, d]
    dest_slice = flat_choice // experts_local
    one_hot_dest = jax.nn.one_hot(dest_slice, ep, dtype=jnp.int32)
    pos = jnp.cumsum(one_hot_dest, axis=0) - 1        # [n*k, ep]
    my_pos = jnp.take_along_axis(pos, dest_slice[:, None], axis=1)[:, 0]
    keep = my_pos < capacity

    send = jnp.zeros((ep, capacity, d), x.dtype)
    send_expert = jnp.zeros((ep, capacity), jnp.int32)
    # overflow slots scatter OUT OF BOUNDS and are dropped — clipping
    # them into slot capacity-1 would clobber the slot that owns it
    drop_row = jnp.where(keep, dest_slice, ep)
    send = send.at[(drop_row, my_pos)].set(xk, mode="drop")
    send_expert = send_expert.at[(drop_row, my_pos)].set(
        flat_choice % experts_local, mode="drop")
    idx = (dest_slice, jnp.clip(my_pos, 0, capacity - 1))  # gather-safe

    # exchange: recv[i] = what slice i sent to us
    recv = lax.all_to_all(send, axis_name, 0, 0, tiled=False)
    recv_expert = lax.all_to_all(send_expert, axis_name, 0, 0,
                                 tiled=False)
    recv_flat = recv.reshape(ep * capacity, d)
    recv_expert_flat = recv_expert.reshape(ep * capacity)

    # run every LOCAL expert on the received tokens, select by assignment
    h = jnp.einsum("nd,edf->enf", recv_flat, params["w_in"])
    h = jax.nn.relu(h)
    y_all = jnp.einsum("enf,efd->end", h, params["w_out"])
    sel = jax.nn.one_hot(recv_expert_flat, experts_local).T[..., None]
    y = (y_all * sel).sum(axis=0).reshape(ep, capacity, d)

    # send results home and combine kept slots by gate weight
    back = lax.all_to_all(y, axis_name, 0, 0, tiled=False)
    slot_y = back[idx]                                # [n*k, d]
    slot_w = jnp.where(keep, flat_gate, 0)[:, None]
    contrib = (slot_y * slot_w).reshape(n, k, d).sum(axis=1)
    kept_w = slot_w.reshape(n, k).sum(axis=1)
    # fraction of routing slots that overflowed capacity — THE signal
    # for tuning capacity_factor (0 = nothing dropped)
    drop = lax.pmean(jnp.mean(1.0 - keep.astype(jnp.float32)), stat_axes)
    metrics = {"aux_loss": aux, "z_loss": z, "drop_fraction": drop}
    # token with every slot dropped → identity passthrough
    return jnp.where(kept_w[:, None] > 0, contrib, x), metrics


def moe_ffn(params, x, mesh, capacity_factor=2.0, k=1,
            ep_axis=EXPERT_AXIS, return_aux=False, return_metrics=False):
    """Expert-parallel MoE FFN; x: [tokens, d_model] sharded over (dp, ep)
    — the standard EP layout: every slice routes only its own tokens, so
    there is no redundant routing compute or duplicated all_to_all rows.

    params['w_in']/['w_out'] have a leading expert axis sharded over ep;
    the router is replicated. Per-destination capacity =
    ceil(k * tokens_per_slice * capacity_factor / ep). ``k`` routes each
    token to its top-k experts with renormalized gate combine (k=1 ≡
    Switch). return_aux adds the load-balancing loss; return_metrics adds
    the full dict {"aux_loss", "z_loss", "drop_fraction"} (all reduced
    over token shards, identical on every slice).
    """
    ep = mesh.shape[ep_axis]
    dp = mesh.shape["dp"]
    num_experts = params["w_in"].shape[0]
    if num_experts % ep != 0:
        raise ValueError("num_experts %d not divisible by ep %d"
                         % (num_experts, ep))
    if x.shape[0] % (dp * ep) != 0:
        raise ValueError("tokens %d not divisible by dp*ep=%d"
                         % (x.shape[0], dp * ep))
    n_local = x.shape[0] // (dp * ep)
    capacity = int(max(1, -(-n_local * k * capacity_factor // ep)))

    param_specs = {
        "router": P(),
        "w_in": P(ep_axis),
        "w_out": P(ep_axis),
    }
    fn = shard_map(
        functools.partial(_moe_shard, axis_name=ep_axis,
                          num_experts=num_experts, capacity=capacity, k=k,
                          stat_axes=("dp", ep_axis)),
        mesh=mesh,
        in_specs=(param_specs, P(("dp", ep_axis))),
        out_specs=(P(("dp", ep_axis)),
                   {"aux_loss": P(), "z_loss": P(), "drop_fraction": P()}),
        check_vma=False)
    y, metrics = fn(params, x)
    if return_metrics:
        return y, metrics
    if return_aux:
        return y, metrics["aux_loss"]
    return y
