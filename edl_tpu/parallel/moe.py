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


# -- dropless held experts -------------------------------------------------
#
# One chip's share of an expert-parallel layer: the router scores ALL the
# layer's experts, this program holds ``experts_held`` of them starting at
# ``first_expert`` and computes their part of the result for every row
# routed to them — no capacity, no dropped row, and no row multiplied by an
# expert it did not choose (ops/grouped_matmul.py). Rows routed to experts
# held elsewhere are left out; on one chip the layer runs without its
# exchange, and nothing stands in for the absent chips.

#: rows of a grouped-product tile; each held expert's rows are padded to
#: whole tiles (at least one), so a tile belongs to one expert
ROW_TILE = 512

#: `jax.ad_checkpoint.checkpoint_name`s of the chosen experts and of the two
#: grouped products' results: a layer under remat that saves these
#: (`checkpoint_policies.save_only_these_names`) does not run the experts'
#: forward products a second time — the part of a step whose cost follows
#: the routing — and recomputes the plan, the rows laid out for them and the
#: gate around them (the combine is one rule that keeps what it is handed
#: and is not run again). The choice is saved WITH the products: a
#: recomputed top-k may break a near tie the other way, and rows laid out by
#: one choice must not meet products saved under another.
#: An UNGATED expert (two matrices) keeps its one up product under a name of
#: its own, "moe.up", beside the choice and the down product.
SAVED_UNDER_REMAT = ("moe.choice", "moe.gate_up", "moe.down", "moe.up")


def route_top_k(h, router, k):
    """Router scores in float32 over ALL experts, the k largest per row,
    and their softmax over the chosen k (equal to a softmax over all,
    renormalised over the chosen). Returns (idx [T, k] int32, p [T, k])."""
    from jax.ad_checkpoint import checkpoint_name
    scores = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    idx = checkpoint_name(lax.top_k(lax.stop_gradient(scores), k)[1].astype(
        jnp.int32), SAVED_UNDER_REMAT[0])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, jax.nn.softmax(top, axis=-1)


def route_sigmoid_top_k(h, router, bias, k, scaling=1.0, norm_eps=1e-20):
    """The router of a layer that scores with a SIGMOID and chooses by
    score + bias (DeepSeek-V3's, one group): z = h router in float32 over
    ALL experts, sc = sigmoid(z); the k chosen are the largest of
    sc + ``bias`` [E] (the bias steers the load and is in nothing else: no
    gradient reaches it, being read under the stop-gradient the choice is
    made under); their weights come from the UNBIASED scores, normalised
    over the chosen k — held here or not — and times ``scaling``:
    w_e = scaling * sc_e / (sum over the chosen of sc + ``norm_eps``) —
    DeepSeek-V3's 1e-20 unless the model publishes another; it is the
    published model's constant and nothing more: k sigmoid scores sum to
    about k / 2, so no test tells 1e-6 from 1e-20 (under 1e-5 of the
    gradient in float32, tests/test_lfm2_decoder.py). The choice
    is saved under remat as :func:`route_top_k`'s. Returns (idx [T, k]
    int32, w [T, k], counters: float32 scalars ``route_bias_flips`` — the
    (token, slot) choices that the k largest of sc alone would not have
    made — and ``route_weight_sum`` — the sum over tokens of their k
    weights, ``scaling`` a token)."""
    from jax.ad_checkpoint import checkpoint_name
    sc = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    plain = lax.stop_gradient(sc)
    idx = checkpoint_name(lax.top_k(
        plain + bias.astype(jnp.float32), k)[1].astype(jnp.int32),
        SAVED_UNDER_REMAT[0])
    top = jnp.take_along_axis(sc, idx, axis=-1)
    w = scaling * top / (top.sum(axis=-1, keepdims=True) + norm_eps)
    unbiased = lax.top_k(plain, k)[1]
    kept = (idx[:, :, None] == unbiased[:, None, :]).any(axis=-1)
    return idx, w, {
        "route_bias_flips": jnp.sum(jnp.logical_not(kept)).astype(
            jnp.float32),
        "route_weight_sum": lax.stop_gradient(w).sum()}


def plan_held_rows(idx, first_expert, experts_held, tm=ROW_TILE):
    """Where every (token, choice) that picked a held expert goes among
    the rows sorted by expert. Static shapes: ``rows = T * k +
    experts_held * tm`` slots, the worst case, of which the used tiles
    come first: ``n_used`` says how many, and whatever moves rows into or
    out of this order (the passes below, the grouped products) visits
    those and no more. Choices are numbered choice-major (``j * T + t``)
    and their arrays are [k, T].

    Returns a dict: ``slot`` [k, T] (the row of each choice; ``rows`` =
    out of range where the expert is held elsewhere), ``slot_token``
    [rows] (the token in each row; T = empty), ``slot_choice`` [rows]
    (j * T + t of the choice in each row; T * k = empty), ``tile_group``
    [rows // tm], ``n_used`` [1], ``counts`` [experts_held]."""
    t, k = idx.shape
    rows = t * k + experts_held * tm
    local = idx.T.reshape(-1) - first_expert
    held = jnp.logical_and(local >= 0, local < experts_held)
    key = jnp.where(held, local, experts_held)
    # a counting sort: the rank of a choice among those of its expert
    onehot = (key[:, None] == jnp.arange(experts_held)[None, :]).astype(
        jnp.int32)
    before = jnp.cumsum(onehot, axis=0) - onehot
    counts = onehot.sum(axis=0)
    rank = jnp.sum(before * onehot, axis=1)
    padded = jnp.maximum((counts + tm - 1) // tm, 1) * tm
    ends = jnp.cumsum(padded)
    starts = ends - padded
    slot = jnp.where(held, starts[jnp.minimum(key, experts_held - 1)] + rank,
                     rows)
    choice = jnp.arange(t * k, dtype=jnp.int32)
    slot_choice = jnp.full((rows,), t * k, jnp.int32).at[slot].set(
        choice, mode="drop")
    slot_token = jnp.where(slot_choice < t * k, slot_choice % t, t)
    # a tile's expert: how many held experts end at or before its first
    # row (compared against all of them: no loop on the device)
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(rows // tm) * tm, side="right",
                         method="compare_all"),
        experts_held - 1).astype(jnp.int32)
    return {"slot": slot.reshape(k, t).astype(jnp.int32),
            "slot_token": slot_token.astype(jnp.int32),
            "slot_choice": slot_choice,
            "tile_group": tile_group,
            "n_used": (ends[-1:] // tm).astype(jnp.int32),
            "counts": counts}


def _tile_of_rows(plan, i, tm, *arrays):
    """Tile ``i``'s ``tm`` entries of ``slot_token``, of ``slot_choice`` and
    of each of ``arrays`` (all laid out by row)."""
    return [lax.dynamic_slice_in_dim(a, i * tm, tm) for a in
            (plan["slot_token"], plan["slot_choice"]) + arrays]


def _over_used_tiles(plan, tile, init):
    """``tile(i, carry)`` for every tile in use, in order: a loop whose trip
    count the device reads from the plan, so a pass costs what the routing
    laid out and not the static worst case."""
    return lax.fori_loop(0, plan["n_used"][0], tile, init)


#: what XLA's scatter-add costs a row on the chip, in rows of its gather
#: (TPU v5e, PR 47: 0.26–0.38 us a row added against 0.047 a row gathered)
SCATTER_ROWS_PER_GATHERED = 6.0


def _sum_at_tokens(rows, plan, tm, weights=None):
    """out [T, D] float32: for every token the sum over its held choices
    of rows[the choice's row] (times ``weights`` [k * T], by choice) — the
    token side of both rules, by whichever of two exact forms the routing
    makes the cheaper. Where it filled few tiles, the used tiles are added
    to their tokens one after the other (a tile's real rows name distinct
    tokens wherever a token chose distinct experts, but its padding
    repeats the empty token: nothing is promised about the indices, and
    rows whose token is out of range are left out). A scatter-add costs
    several gathers a row, so where the routing filled more than that
    share of the [k, T] choices — a layer that holds most of its experts,
    a batch learnt onto the held ones — every choice gathers its row
    instead (out of range = none), which costs the same whatever was
    served."""
    k, t = plan["slot"].shape

    def by_used_tile():
        def tile(i, acc):
            tok, choice, part = _tile_of_rows(plan, i, tm, rows)
            part = part.astype(jnp.float32)
            if weights is not None:
                part = part * _row_weights(weights, choice)[:, None]
            return acc.at[tok].add(part, mode="drop")
        return _over_used_tiles(plan, tile, jnp.zeros((t, rows.shape[1]),
                                                      jnp.float32))

    def by_choice():
        picked = jnp.take(rows, plan["slot"], axis=0, mode="fill",
                          fill_value=0).astype(jnp.float32)
        if weights is not None:
            picked = picked * weights.reshape(k, t)[..., None]
        return picked.sum(axis=0)

    rows_moved = (plan["n_used"][0] * tm).astype(jnp.float32)
    return lax.cond(rows_moved * SCATTER_ROWS_PER_GATHERED <= k * t,
                    by_used_tile, by_choice)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lay_rows(u, plan, tm):
    """u's rows in the plan's order: row r of the result is
    ``u[slot_token[r]]`` in every tile in use; padding rows and the tiles
    past ``n_used`` are zeros, and the latter are never visited."""
    def tile(i, rows):
        tok, _ = _tile_of_rows(plan, i, tm)
        return lax.dynamic_update_slice_in_dim(
            rows, jnp.take(u, tok, axis=0, mode="fill", fill_value=0),
            i * tm, 0)
    return _over_used_tiles(plan, tile, jnp.zeros(
        (plan["slot_token"].shape[0], u.shape[1]), u.dtype))


def _lay_rows_fwd(u, plan, tm):
    return _lay_rows(u, plan, tm), plan


def _lay_rows_bwd(tm, plan, g):
    """The cotangent's rows added to their tokens, in float32: the terms
    of a token are its held choices' rows."""
    with jax.named_scope("moe.dispatch"):
        d_u = _sum_at_tokens(g, plan, tm)
    return d_u.astype(g.dtype), None


_lay_rows.defvjp(_lay_rows_fwd, _lay_rows_bwd)


def _row_weights(p_by_choice, choice):
    """The weights [k * T] (p.T flattened: choices are numbered j * T + t) of
    the choices in a tile's rows; 0 for a padding row."""
    return jnp.take(p_by_choice, choice, mode="fill", fill_value=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine_rows(y, p, plan, tm):
    """m [T, D] float32: for every token the sum over its held choices of
    p * y[the choice's row]. The weighting is part of the rule: its
    backward is one walk of the used tiles and keeps no [k, T, D] array."""
    return _sum_at_tokens(y, plan, tm, p.T.reshape(-1))


def _combine_rows_fwd(y, p, plan, tm):
    return _combine_rows(y, p, plan, tm), (y, p, plan)


def _combine_rows_bwd(tm, res, g):
    """One walk over the used tiles: the cotangent's rows gathered by
    token give d_y[row] = p_row * g[token] and d_p of the row's choice =
    <g[token], y[row]>."""
    y, p, plan = res
    t, k = p.shape
    p_by_choice = p.T.reshape(-1)

    def tile(i, carry):
        d_y, d_p = carry
        tok, choice, part = _tile_of_rows(plan, i, tm, y)
        g_rows = jnp.take(g, tok, axis=0, mode="fill", fill_value=0)
        d_y = lax.dynamic_update_slice_in_dim(
            d_y, (g_rows * _row_weights(p_by_choice, choice)[:, None]
                  ).astype(y.dtype), i * tm, 0)
        return d_y, d_p.at[choice].set(
            jnp.sum(g_rows * part.astype(jnp.float32), axis=-1), mode="drop")
    with jax.named_scope("moe.combine"):
        d_y, d_p = _over_used_tiles(
            plan, tile, (jnp.zeros_like(y), jnp.zeros((k * t,), jnp.float32)))
    return d_y, d_p.reshape(k, t).T.astype(p.dtype), None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


#: an expert's activation, by name: the gate's in a gated linear unit, the
#: hidden layer's own in an ungated expert (``relu2``: relu(x) squared)
EXPERT_ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu,
                      "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def held_experts_ffn(u, idx, p, w_gate_up, w_down, first_expert,
                     tm=ROW_TILE, interpret=None, activation="relu",
                     gated=True):
    """The held experts' part of a top-k expert layer of gated linear
    units: ReGLU (``activation="relu"``, the default) or SwiGLU
    (``"silu"``) — or, with ``gated=False``, of UNGATED experts of two
    matrices each, down(act(up u)): one grouped product up, the activation,
    one down, and no gate's product anywhere.

    u [T, D] (the layer's normed input), idx/p [T, k] from
    :func:`route_top_k`, w_gate_up [held, D, 2 * F] (gate then up; an
    ungated expert's up alone, [held, D, F]), w_down [held, F, D]. Returns
    (m [T, D] in u's dtype:
    sum over the chosen held experts of p * down(act(gate u) * (up u)),
    counters: float32 scalars ``rows_held``, ``load_max``, ``load_mean``,
    ``tokens_unserved``, ``rows_dropped``, ``rows_moved``). The four passes
    that move rows into the experts' order and back (`_lay_rows`,
    `_combine_rows` and their backward rules) walk the ``n_used`` tiles
    the routing filled, as the grouped products between them do: their
    cost follows the rows served, their shapes stay those of the worst
    case, and nothing compiles again with the routing. ``interpret``
    (default: on the CPU backend, as the attention dispatch does) runs the
    Pallas kernels in the interpreter, for tests."""
    from jax.ad_checkpoint import checkpoint_name
    from edl_tpu.ops.grouped_matmul import grouped_matmul
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    held, d, f2 = w_gate_up.shape
    t, k = idx.shape
    with jax.named_scope("moe.dispatch"):
        plan = plan_held_rows(idx, first_expert, held, tm)
        rows = _lay_rows(u, plan, tm)
    with jax.named_scope("moe.experts"):
        gm = functools.partial(grouped_matmul, tile_group=plan["tile_group"],
                               n_used=plan["n_used"], tm=tm,
                               interpret=interpret)
        act = EXPERT_ACTIVATIONS[activation]
        if gated:
            gu = checkpoint_name(gm(rows, w_gate_up), SAVED_UNDER_REMAT[1])
            hid = act(gu[:, :f2 // 2]) * gu[:, f2 // 2:]
        else:
            hid = act(checkpoint_name(gm(rows, w_gate_up),
                                      SAVED_UNDER_REMAT[3]))
        y = checkpoint_name(gm(hid, w_down), SAVED_UNDER_REMAT[2])
    with jax.named_scope("moe.combine"):
        m = _combine_rows(y, p, plan, tm)
    with jax.named_scope("moe.dispatch"):   # what the plan counted
        served = plan["slot"] < rows.shape[0]
        counts = plan["counts"].astype(jnp.float32)
        counters = {
            "rows_held": counts.sum(),
            "load_max": counts.max(),
            "load_mean": counts.mean(),
            "tokens_unserved": jnp.sum(
                jnp.logical_not(served.any(axis=0))).astype(jnp.float32),
            # every choice of a held expert has a row: the rows placed
            # equal the choices counted, whatever the routing
            "rows_dropped": counts.sum() - jnp.sum(
                plan["slot_choice"] < t * k).astype(jnp.float32),
            # what one pass into or out of the rows' order visits: the
            # served rows and each held expert's padding to whole tiles
            "rows_moved": (plan["n_used"][0] * tm).astype(jnp.float32),
        }
        return m.astype(u.dtype), counters


def _gated_linear_unit(u, w_gate_up, w_down, activation, gated=True):
    """down(act(gate u) * (up u)) of a dense gated linear unit, float32 out
    of the last product: u [T, D], w_gate_up [D, 2 * F] (gate then up),
    w_down [F, D] -> [T, D]. ``gated=False``: down(act(up u)), w_gate_up the
    up matrix alone, [D, F]."""
    dt = u.dtype
    f = w_down.shape[0]
    gu = jnp.dot(u, w_gate_up.astype(dt))
    act = EXPERT_ACTIVATIONS[activation]
    hid = act(gu[:, :f]) * gu[:, f:] if gated else act(gu)
    return jnp.dot(hid, w_down.astype(dt), preferred_element_type=jnp.float32)


def shared_expert_ffn(u, w_gate_up, w_down, w_gate, activation="silu",
                      gated=True):
    """The SHARED expert of a layer that has one beside its routed experts:
    every row passes through it, scaled by a learned sigmoid gate of its
    own — sigmoid(u . w_gate) * down(act(gate u) * (up u)) — or, with
    ``w_gate`` None, by nothing (:func:`dense_ffn`'s arithmetic). A plain
    dense gated linear unit: u [T, D], w_gate_up [D, 2 * F] (gate then up),
    w_down [F, D], w_gate [D] -> [T, D] in u's dtype; ``gated=False``: an
    ungated one of two matrices, w_gate_up [D, F]. Every chip of an
    expert-parallel group computes it alike, so where the shares of a layer
    are added up it is counted once."""
    with jax.named_scope("moe.shared"):
        y = _gated_linear_unit(u, w_gate_up, w_down, activation, gated)
        if w_gate is None:
            return y.astype(u.dtype)
        gate = jax.nn.sigmoid(jnp.dot(u.astype(jnp.float32),
                                      w_gate.astype(jnp.float32)))
        return (y * gate[:, None]).astype(u.dtype)


def dense_ffn(u, w_gate_up, w_down, activation="silu", gated=True):
    """The feed-forward part of a layer WITHOUT experts: the same gated
    linear unit (``gated=False``: ungated, of two matrices) for every row,
    no router, no gate of its own and nothing to count. u [T, D], w_gate_up
    [D, 2 * F] ([D, F] ungated), w_down [F, D] -> [T, D] in u's dtype."""
    with jax.named_scope("ffn.dense"):
        return _gated_linear_unit(u, w_gate_up, w_down, activation,
                                  gated).astype(u.dtype)
