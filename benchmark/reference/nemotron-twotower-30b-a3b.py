"""Plain reference for the `nemotron-twotower-30b-a3b` configuration: one
chip's share of the `nemotron_h` decoder that
Nemotron-Labs-TwoTower-30B-A3B-Base-BF16's config.json describes, in
straightforward `jax.numpy`, float32, every matrix product at
`Precision.HIGHEST`, no kernel, no chunk, no cache. It takes its weights
from the seed and nothing from the program.

The stack: h = embed(ids); for each layer h = h + f_l(RMSNorm(h; g_l)) — ONE
sublayer a layer, its kind the l-th character of `hybrid_override_pattern`;
then RMSNorm(h; g_f), an untied head over the held vocabulary rows and mean
next-token cross-entropy. RMSNorm has a plain gain and eps 1e-5. For this
chip's Mamba heads `first_mamba_head .. + H - 1` (whole groups of 8),
attention heads and experts `first_expert .. + held - 1`, u the normed input
[T, D]:

    M  [z | xBC | dt] = u W_in, widths H P | H P + 2 G N | H, no bias
       xBC = SiLU(causal depthwise conv of width 4 over xBC, WITH bias)
       x [H, P], B [G, N], C [G, N] = split(xBC)
       dt = softplus(dt + dt_bias);  A = -exp(A_log), a scalar a head
       per head h of group g(h) = h // 8 and token t, S_0 = 0:
         S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_{g,t}^T      (S in R^{P x N})
         y_t = S_t C_{g,t} + D_h x_t
       y = GroupRMSNorm(y * SiLU(z); g_n): the H P values normed in G groups
       of 8 P (the heads that share B and C), plain gain;  f = y W_out
    *  q, k, v = u W_q, u W_k, u W_v (heads of 128, no bias, NO positions)
       causal softmax(q k^T / sqrt(128)) v, query head i reads key-value
       head i // (Hq / Hkv);  f = concat W_o
    E  z = u W_r (all 128);  sc = sigmoid(z);  E(t) = the 6 largest of
       sc + b (n_group = topk_group = 1: no group step);
       w_e = 2.5 sc_e / (sum over E(t) of sc + 1e-20)
       f = sum over e in E(t), e held, of w_e relu(u W_up^e)^2 W_down^e
           + relu(u W_su)^2 W_sd        (the shared expert, ungated, 3712)

The gradient reaches W_r through w_e (all six chosen scores are in the
normaliser, held or not); b gets none. Rows routed to experts held
elsewhere, the other heads' part of W_out's and W_o's sums, are left out;
that partial result goes on to the next layer.

Departures from the published model, each also under `assumed` in the
configuration file: the denoising tower is ABSENT (config.json describes one
decoder; this is that decoder under next-token prediction); the bias b is a
constant of the run; `time_step_limit` (0, inf) clamps nothing and is not
written. To fit beside the trainer, the recurrence's tokens are scanned in
checkpointed blocks (the state is kept at block boundaries and every token
is still one step), attention by blocks of QUERY rows (each row's softmax is
whole inside its block), the experts one after another as a dense masked
sum, layers under `jax.checkpoint`, and the head's loss by blocks of tokens:
memory only, never a number.

`q="int8"` is the CONTROL, not a feature: both operands of every matrix
product (the recurrence's outer product and read among them) are rounded to
8-bit integers with one scale per tensor (absmax / 127) before they are
multiplied. `correct` has to refuse it.
"""

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST

#: rows per block of the blockwise parts (memory only, never a number)
QUERY_BLOCK = 256
TOKEN_BLOCK = 1024
SCAN_BLOCK = 64


def _fake_int8(x):
    s = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    return jnp.round(x / s) * s


def _ein(spec, a, b, q):
    if q == "int8":
        a, b = _fake_int8(a), _fake_int8(b)
    elif q is not None:
        raise ValueError("unknown control precision %r" % (q,))
    return jnp.einsum(spec, a, b, precision=HI)


def _dims(cfg):
    return {"v": cfg["vocab_size"], "d": cfg["hidden_size"],
            "hm": cfg["mamba_num_heads"], "p": cfg["mamba_head_dim"],
            "g": cfg["n_groups"], "n": cfg["ssm_state_size"],
            "k": cfg["conv_kernel"],
            "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "e_all": cfg["num_router_outputs"],
            "held": cfg["n_routed_experts"],
            "f": cfg["moe_intermediate_size"],
            "fs": cfg["n_shared_experts"]
            * cfg["moe_shared_expert_intermediate_size"]}


def layer_kinds(cfg):
    """One character a layer: M (Mamba-2), * (attention) or E (experts)."""
    kinds = cfg["hybrid_override_pattern"]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set("M*E"):
        raise ValueError("hybrid_override_pattern %r for %d layers"
                         % (kinds, cfg["num_hidden_layers"]))
    return kinds


def _layer_shapes(cfg, kind):
    z = _dims(cfg)
    d = z["d"]
    inner, bc = z["hm"] * z["p"], 2 * z["g"] * z["n"]
    if kind == "M":
        return {"g": ((d,), "g"),
                # columns: z | x | B | C | dt
                "w_in": ((d, 2 * inner + bc + z["hm"]), "w"),
                "w_conv": ((inner + bc, z["k"]), "conv"),
                "b_conv": ((inner + bc,), "conv"),
                "a_log": ((z["hm"],), "a_log"),
                "dt_bias": ((z["hm"],), "dt_bias"),
                "d_skip": ((z["hm"],), "one"),
                "g_n": ((inner,), "g"),
                "w_o": ((inner, d), "w")}
    if kind == "*":
        return {"g": ((d,), "g"),
                "w_q": ((d, z["hq"] * z["hd"]), "w"),
                "w_k": ((d, z["hkv"] * z["hd"]), "w"),
                "w_v": ((d, z["hkv"] * z["hd"]), "w"),
                "w_o": ((z["hq"] * z["hd"], d), "w")}
    return {"g": ((d,), "g"),
            "w_r": ((d, z["e_all"]), "w"), "b_r": ((z["e_all"],), "w"),
            "w_up": ((z["held"], d, z["f"]), "w"),
            "w_down": ((z["held"], z["f"], d), "w"),
            "w_su": ((d, z["fs"]), "w"), "w_sd": ((z["fs"], d), "w")}


def weight_shapes(cfg):
    """name -> (shape, kind); layer l's tensors are named "l/<name>"."""
    z = _dims(cfg)
    out = {"embed": ((z["v"], z["d"]), "w"), "head": ((z["d"], z["v"]), "w"),
           "g_f": ((z["d"],), "g")}
    for i, kind in enumerate(layer_kinds(cfg)):
        for name, spec in _layer_shapes(cfg, kind).items():
            out["%d/%s" % (i, name)] = spec
    return out


def layer_weights(w, i):
    """Layer i's tensors under their plain names."""
    head = "%d/" % i
    return {k[len(head):]: v for k, v in w.items() if k.startswith(head)}


def init_weights(cfg, key):
    """Seeded weights, traced inside the caller's ONE jitted call: matrices
    N(0, initializer_range); the embedding, the routers and the routers'
    selection bias at ranges of their own (the configuration's
    `assumed.weights`); RMSNorm gains 1 + N(0, range), so that a path that
    drops a gain shows in `correct`. A Mamba layer's as published: A_log =
    log(1 + the head's index in the WHOLE model), D = 1, dt_bias =
    softplus^-1(dt) with dt log-uniform in [time_step_min, time_step_max]
    floored at time_step_floor, the convolution's taps and bias
    U(-1/2, 1/2) (a depthwise convolution of width 4)."""
    std = cfg["initializer_range"]
    own = {"embed": cfg["embedding_initializer_range"],
           "w_r": cfg["router_initializer_range"],
           "b_r": cfg["router_bias_initializer_range"]}
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(
            weight_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if kind == "a_log":
            x = jnp.log(1.0 + cfg["first_mamba_head"]
                        + jnp.arange(shape[0], dtype=jnp.float32))
        elif kind == "one":
            x = jnp.ones(shape, jnp.float32)
        elif kind == "dt_bias":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(cfg["time_step_min"]),
                math.log(cfg["time_step_max"]))), cfg["time_step_floor"])
            x = jnp.log(jnp.expm1(dt))
        elif kind == "conv":
            bound = cfg["conv_kernel"] ** -0.5
            x = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        else:
            x = jax.random.normal(k, shape, jnp.float32)
            x = x * own.get(name.rsplit("/", 1)[-1], std)
            x = 1.0 + x if kind == "g" else x
        out[name] = x
    return out


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def causal_conv(u, w, bias):
    """u [B, S, C], w [C, K], bias [C]: c_t = bias + sum_j w[:, j]
    u_{t-(K-1)+j}, u zero before the sequence starts."""
    kernel = w.shape[1]
    s = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (kernel - 1, 0), (0, 0)))
    return bias + sum(padded[:, j:j + s] * w[:, j] for j in range(kernel))


def state_step(state, decay, write, x_t, b_t, qc=None):
    """One token of the recurrence: S_t = decay S_{t-1} + write x_t B_t^T,
    with decay = exp(dt_t A) and write = dt_t [B, H]; x_t [B, H, P], b_t
    [B, H, N]."""
    return state * decay[..., None, None] + _ein(
        "bhp,bhn->bhpn", x_t * write[..., None], b_t, qc)


def ssm_scan(x, dt, a, b, c, qc=None):
    """The recurrence, token by token: x [B, S, H, P], dt [B, S, H], a [H]
    (< 0), b, c [B, S, H, N] (each group's already repeated for its heads)
    -> S_t C_t [B, S, H, P] (the skip D x is the caller's). Blocks of tokens
    are checkpointed (memory only)."""
    bsz, s, h, p = x.shape

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = state_step(state, jnp.exp(dt_t * a), dt_t, x_t, b_t, qc)
        return state, _ein("bhpn,bhn->bhp", state, c_t, qc)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    blk = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s
    xs = tuple(jnp.moveaxis(y, 1, 0).reshape((s // blk, blk) + y.shape[:1]
                                             + y.shape[2:])
               for y in (x, dt, b, c))
    _, y = jax.lax.scan(block, jnp.zeros((bsz, h, p, b.shape[-1])), xs)
    return jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1)


def gated_norm(y, z, g_n, groups, eps):
    """GroupRMSNorm(y * SiLU(z); g_n) over [B, S, H P]: the gate BEFORE the
    norm, the statistics over each of `groups` equal parts."""
    y = y * jax.nn.silu(z)
    parts = y.reshape(y.shape[:-1] + (groups, -1))
    parts = parts * jax.lax.rsqrt(
        jnp.mean(jnp.square(parts), -1, keepdims=True) + eps)
    return parts.reshape(y.shape) * g_n


def mamba_part(u, lw, cfg, qc=None):
    """An M layer before the residual: u [B, S, D] -> [B, S, D]."""
    z = _dims(cfg)
    b, s, _ = u.shape
    h, p, g, n = z["hm"], z["p"], z["g"], z["n"]
    inner = h * p
    proj = _ein("bsd,dk->bsk", u, lw["w_in"], qc)
    gate, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * g * n],
                     proj[..., 2 * inner + 2 * g * n:])
    xbc = jax.nn.silu(causal_conv(xbc, lw["w_conv"], lw["b_conv"]))
    x = xbc[..., :inner].reshape(b, s, h, p)
    by_head = lambda y: jnp.repeat(y.reshape(b, s, g, n), h // g, axis=2)
    bm = by_head(xbc[..., inner:inner + g * n])
    cm = by_head(xbc[..., inner + g * n:])
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    y = ssm_scan(x, dt, -jnp.exp(lw["a_log"]), bm, cm, qc)
    y = y + lw["d_skip"][:, None] * x
    y = gated_norm(y.reshape(b, s, inner), gate, lw["g_n"], g,
                   cfg["layer_norm_epsilon"])
    return _ein("bsk,kd->bsd", y, lw["w_o"], qc)


def positions(x, cfg):
    """What the attention applies to q and k: nothing (`assumed.positions`)."""
    return x


def attention_part(u, lw, cfg, qc=None):
    """A * layer before the residual: u [B, S, D] -> [B, S, D]."""
    z = _dims(cfg)
    b, s, _ = u.shape
    hq, hkv, hd = z["hq"], z["hkv"], z["hd"]
    grp = hq // hkv
    qh = positions(_ein("bsd,dk->bsk", u, lw["w_q"], qc).reshape(
        b, s, hq, hd), cfg)
    kh = positions(_ein("bsd,dk->bsk", u, lw["w_k"], qc).reshape(
        b, s, hkv, hd), cfg)
    vh = _ein("bsd,dk->bsk", u, lw["w_v"], qc).reshape(b, s, hkv, hd)
    qh = qh.reshape(b, s, hkv, grp, hd)     # query head i reads kv i // grp
    blk = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def block(q_blk, q_pos):
        sc = _ein("bqkgd,btkd->bkgqt", q_blk, kh, qc) * hd ** -0.5
        keep = q_pos[:, None] >= jnp.arange(s)[None, :]
        pr = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return _ein("bkgqt,btkd->bqkgd", pr, vh, qc)

    a = jax.lax.map(lambda args: block(*args), (
        qh.reshape(b, s // blk, blk, hkv, grp, hd).swapaxes(0, 1),
        jnp.arange(s).reshape(s // blk, blk)))
    a = a.swapaxes(0, 1).reshape(b, s, hq * hd)
    return _ein("bsk,kd->bsd", a, lw["w_o"], qc)


def route(u, w_r, b_r, cfg, qc=None):
    """u [N, D] -> (E [N, k] expert ids chosen by score + bias, w [N, k]
    from the unbiased scores, the scores [N, all])."""
    sc = jax.nn.sigmoid(_ein("nd,de->ne", u, w_r, qc))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(sc) + b_r,
                           cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(sc, idx, axis=-1)
    return idx, (cfg["routed_scaling_factor"] * top
                 / (top.sum(axis=-1, keepdims=True) + 1e-20)), sc


def expert(u, w_up, w_down, qc=None):
    """An ungated expert of two matrices: relu(u W_up)^2 W_down."""
    hid = jnp.square(jax.nn.relu(_ein("nd,df->nf", u, w_up, qc)))
    return _ein("nf,fd->nd", hid, w_down, qc)


def routed_part(u, idx, p, lw, cfg, qc=None):
    """The held experts' part: a dense masked sum, one expert at a time."""
    first = cfg["first_expert"]
    out = jnp.zeros_like(u)

    @jax.checkpoint
    def one(u, weight, w_up, w_down):
        return weight[:, None] * expert(u, w_up, w_down, qc)

    for e in range(cfg["n_routed_experts"]):
        weight = jnp.sum(jnp.where(idx == first + e, p, 0.0), axis=-1)
        out = out + one(u, weight, lw["w_up"][e], lw["w_down"][e])
    return out


def shared_part(u, lw, qc=None):
    """The shared expert: one ungated expert of width 3712, no gate of its
    own, for every token."""
    return expert(u, lw["w_su"], lw["w_sd"], qc)


def experts_part(u, lw, cfg, qc=None):
    """An E layer before the residual: u [B, S, D] -> [B, S, D]."""
    b, s, d = u.shape
    u = u.reshape(b * s, d)
    idx, p, _ = route(u, lw["w_r"], lw["b_r"], cfg, qc)
    return (routed_part(u, idx, p, lw, cfg, qc)
            + shared_part(u, lw, qc)).reshape(b, s, d)


def layer(x, lw, cfg, kind, qc=None):
    f = {"M": mamba_part, "*": attention_part, "E": experts_part}[kind]
    return x + f(_rms(x, lw["g"], cfg["layer_norm_epsilon"]), lw, cfg, qc)


def hidden(w, ids, cfg, qc=None):
    """ids [B, T] -> final-RMSNorm hidden states [B, T, D]."""
    x = w["embed"][ids]
    for i, kind in enumerate(layer_kinds(cfg)):
        x = jax.checkpoint(
            lambda x, lw, kind=kind: layer(x, lw, cfg, kind, qc))(
            x, layer_weights(w, i))
    return _rms(x, w["g_f"], cfg["layer_norm_epsilon"])


def layer_counts(w, ids, cfg):
    """What the program's counters hold after one step, per layer (0 where
    a layer has no such part): rows the held experts serve, (token, slot)
    choices the bias changed, the sum of the chosen weights; and for a
    Mamba layer the most negative sum of dt A over a chunk of `chunk_size`
    tokens and the largest |S| at a chunk's end."""
    b, t = ids.shape
    z = _dims(cfg)
    k, eps = cfg["num_experts_per_tok"], cfg["layer_norm_epsilon"]
    first, held = cfg["first_expert"], cfg["n_routed_experts"]
    chunk = cfg["chunk_size"]
    names = ("rows_held", "route_bias_flips", "route_weight_sum",
             "ssd_chunk_log_decay_min", "ssd_state_absmax")
    out = {n: [] for n in names}
    x = w["embed"][ids]
    for i, kind in enumerate(layer_kinds(cfg)):
        lw = layer_weights(w, i)
        u = _rms(x, lw["g"], eps)
        got = {n: jnp.zeros(()) for n in names}
        if kind == "E":
            idx, p, sc = route(u.reshape(b * t, -1), lw["w_r"], lw["b_r"],
                               cfg)
            plain = jax.lax.top_k(sc, k)[1]
            kept = (idx[:, :, None] == plain[:, None, :]).any(-1)
            got.update(
                rows_held=jnp.sum(jnp.logical_and(
                    idx >= first, idx < first + held)).astype(jnp.float32),
                route_bias_flips=jnp.sum(jnp.logical_not(kept)).astype(
                    jnp.float32),
                route_weight_sum=p.sum())
        elif kind == "M":
            h, pw, g, n = z["hm"], z["p"], z["g"], z["n"]
            inner = h * pw
            proj = jnp.einsum("bsd,dk->bsk", u, lw["w_in"], precision=HI)
            xbc = jax.nn.silu(causal_conv(
                proj[..., inner:2 * inner + 2 * g * n], lw["w_conv"],
                lw["b_conv"]))
            dt = jax.nn.softplus(proj[..., 2 * inner + 2 * g * n:]
                                 + lw["dt_bias"])
            a = -jnp.exp(lw["a_log"])
            pad = -t % chunk
            logs = jnp.pad(dt * a, ((0, 0), (0, pad), (0, 0))).reshape(
                b, -1, chunk, h).sum(axis=2)
            xh = xbc[..., :inner].reshape(b, t, h, pw)
            bm = jnp.repeat(xbc[..., inner:inner + g * n].reshape(
                b, t, g, n), h // g, axis=2)

            def token(state, xs):
                x_t, dt_t, b_t = xs
                state = state_step(state, jnp.exp(dt_t * a), dt_t, x_t, b_t)
                return state, jnp.max(jnp.abs(state))

            _, tops = jax.lax.scan(
                token, jnp.zeros((b, h, pw, n)),
                tuple(jnp.moveaxis(y, 1, 0) for y in (xh, dt, bm)))
            ends = jnp.concatenate([tops[chunk - 1::chunk], tops[-1:]])
            got.update(ssd_chunk_log_decay_min=logs.min(),
                       ssd_state_absmax=ends.max())
        for n in names:
            out[n].append(got[n])
        x = layer(x, lw, cfg, kind)
    return {n: jnp.stack(v) for n, v in out.items()}


def loss(w, batch, cfg, qc=None):
    """The mean next-token cross-entropy for batch["input_ids"] [B, T],
    by blocks of tokens."""
    ids = batch["input_ids"]
    b, t = ids.shape
    h = hidden(w, ids, cfg, qc).reshape(b * t, -1)
    # the last position of a sequence has no target: weight 0
    target = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1).reshape(-1)
    weight = jnp.tile(jnp.arange(t) < t - 1, b).astype(jnp.float32)
    blk = TOKEN_BLOCK if (b * t) % TOKEN_BLOCK == 0 else b * t

    @jax.checkpoint
    def block(total, args):
        h_blk, tgt, wt = args
        lg = _ein("nd,dv->nv", h_blk, w["head"], qc)
        logp = jax.nn.log_softmax(lg, axis=-1)
        picked = jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
        return total - jnp.sum(picked * wt), None

    total, _ = jax.lax.scan(
        block, jnp.zeros((), jnp.float32),
        (h.reshape(-1, blk, h.shape[-1]), target.reshape(-1, blk),
         weight.reshape(-1, blk)))
    return total / (b * (t - 1))


def loss_and_grad(w, batch, cfg, q=None):
    """(loss, d loss / d w) of the whole batch in one pass."""
    return jax.value_and_grad(lambda w_: loss(w_, batch, cfg, q))(w)
