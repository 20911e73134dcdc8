"""Plain reference for the `kanana-2-30b-a3b` configuration: one chip's
share of Kanana-2-30B-A3B (kakaocorp; `model_type` deepseek_v3) in
straightforward `jax.numpy`, float32, every matrix product at
`Precision.HIGHEST`, no kernel, no cache. It takes its weights from the
seed and nothing from the program.

One layer, all projections without bias, x [T, D], for this chip's heads
`first_head .. first_head + H - 1` and experts `first_expert .. + held - 1`:

    1. h = RMSNorm(x; g1);  q = h W_q -> [T, H, 192];
       q_nope = q[..., :128], q_pe = q[..., 128:]
    2. c', kp' = split(h W_kva, [512, 64])  (W_kva [D, 576] is WHOLE on
       every chip: the latent is not sharded);  c = RMSNorm(c'; g_c)
    3. k_nope, v = split((c W_kvb) as [T, H, 256], [128, 128])
    4. q_pe, kp = rope(q_pe), rope(kp')  (theta 1e6 over the 64; kp is ONE
       head, read by all H)
    5. s[i, j] = (q_nope[i] . k_nope[j] + q_pe[i] . kp[j]) / sqrt(192),
       j <= i;  p = softmax;  x' = x + concat_H(p v) W_o   (v 128 wide)
    6. u = RMSNorm(x'; g2)
       layer < first_k_dense_replace:  out = x' + SwiGLU_6144(u)
       else:  z = u W_r (all 128);  sc = sigmoid(z);
              E(t) = the 6 largest of sc + b  (n_group = topk_group = 1: no
              group step);  w_e = 2.448 sc_e / (sum over E(t) of sc + 1e-20)
              out = x' + sum over e in E(t), e held, of w_e SwiGLU_768^e(u)
                       + SwiGLU_1536^shared(u)
    7. final RMSNorm, untied head over the held vocabulary rows, mean
       next-token cross-entropy.

The gradient reaches W_r through w_e (all six chosen scores are in the
normaliser, held or not); b gets none. Rows routed to experts held
elsewhere, and the other heads' part of W_o's sum, are left out; that
partial result goes on to the next layer.

Departures from the published model, each also under `assumed` in the
configuration file: half-split rotary pairs inside the 64 (the published
`rope_interleave` is the same scores under a fixed permutation of W_q's
and W_kva's rotary columns); no multi-token-prediction module; the bias b
is a constant of the run; the two shared experts are one SwiGLU of 1536.
To fit beside the trainer, step 5 is computed by blocks of QUERY rows
(each row's softmax is whole inside its block, so no number changes), the
experts one after another as a dense masked sum, layers under
`jax.checkpoint`, and the head's loss by blocks of tokens, so that the
float32 logits and their cotangent never stand whole.

`q="int8"` is the CONTROL, not a feature: both operands of every matrix
product are rounded to 8-bit integers with one scale per tensor
(absmax / 127) before they are multiplied. `correct` has to refuse it.
"""

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST

#: rows per block of the blockwise parts (memory only, never a number)
QUERY_BLOCK = 256
TOKEN_BLOCK = 1024


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
            "n": cfg["num_hidden_layers"], "h": cfg["num_attention_heads"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "dc": cfg["kv_lora_rank"],
            "e_all": cfg["num_router_outputs"],
            "held": cfg["n_routed_experts"],
            "f": cfg["moe_intermediate_size"],
            "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "fd": cfg["intermediate_size"]}


def is_dense(cfg, i):
    """Layer i has a dense feed-forward part and no experts."""
    return i < cfg["first_k_dense_replace"]


def _layer_shapes(cfg, i):
    z = _dims(cfg)
    d, h = z["d"], z["h"]
    out = {
        "g1": ((d,), "g"), "g2": ((d,), "g"), "g_c": ((z["dc"],), "g"),
        "w_q": ((d, h * (z["dn"] + z["dr"])), "w"),
        "w_kva": ((d, z["dc"] + z["dr"]), "w"),
        "w_kvb": ((z["dc"], h * (z["dn"] + z["dv"])), "w"),
        "w_o": ((h * z["dv"], d), "w")}
    if is_dense(cfg, i):
        # W_gate = w_ffn_gate_up[:, :fd], W_up = w_ffn_gate_up[:, fd:]
        return dict(out, w_ffn_gate_up=((d, 2 * z["fd"]), "w"),
                    w_ffn_down=((z["fd"], d), "w"))
    return dict(
        out, w_r=((d, z["e_all"]), "w"), b_r=((z["e_all"],), "w"),
        w_gate_up=((z["held"], d, 2 * z["f"]), "w"),
        w_down=((z["held"], z["f"], d), "w"),
        w_sgu=((d, 2 * z["fs"]), "w"), w_sd=((z["fs"], d), "w"))


def weight_shapes(cfg):
    """name -> (shape, kind); layer l's tensors are named "l/<name>"."""
    z = _dims(cfg)
    out = {"embed": ((z["v"], z["d"]), "w"), "head": ((z["d"], z["v"]), "w"),
           "g_f": ((z["d"],), "g")}
    for i in range(z["n"]):
        for name, spec in _layer_shapes(cfg, i).items():
            out["%d/%s" % (i, name)] = spec
    return out


def layer_weights(w, i):
    """Layer i's tensors under their plain names."""
    head = "%d/" % i
    return {k[len(head):]: v for k, v in w.items() if k.startswith(head)}


def init_weights(cfg, key):
    """Seeded weights, traced inside the caller's ONE jitted call:
    matrices N(0, initializer_range); the embedding, the routers and the
    routers' selection bias at ranges of their own (the configuration's
    `assumed.weights`); RMSNorm gains 1 + N(0, range), so that a path that
    drops a gain shows in `correct`."""
    std = cfg["initializer_range"]
    own = {"embed": cfg["embedding_initializer_range"],
           "w_r": cfg["router_initializer_range"],
           "b_r": cfg["router_bias_initializer_range"]}
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(
            weight_shapes(cfg).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        x = x * own.get(name.rsplit("/", 1)[-1], std)
        out[name] = 1.0 + x if kind == "g" else x
    return out


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """x [B, T, H, w], half-split pairs (i, i + w/2) over the whole w."""
    w = x.shape[-1]
    half = w // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / w)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def latent_norm(c, g, eps):
    """Step 2's RMSNorm over the latent (the rotary key part has none)."""
    return _rms(c, g, eps)


def softmax_scale(cfg):
    return float(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5


def rotary_scores(qp_blk, kp, q=None):
    """The rotary part of step 5's scores: every head's q_pe against the
    ONE rotary key. [B, Q, H, 64] x [B, T, 64] -> [B, H, Q, T]."""
    return _ein("bqhd,bkd->bhqk", qp_blk, kp, q)


def latent_projections(h, lw, cfg, q=None):
    """Steps 1-4: (q_nope [B, T, H, 128], q_pe [B, T, H, 64], k_nope
    [B, T, H, 128], kp [B, T, 64] — one head —, v [B, T, H, 128])."""
    z = _dims(cfg)
    b, t, _ = h.shape
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    qh = _ein("btd,dk->btk", h, lw["w_q"], q).reshape(
        b, t, z["h"], z["dn"] + z["dr"])
    down = _ein("btd,dk->btk", h, lw["w_kva"], q)
    c = latent_norm(down[..., :z["dc"]], lw["g_c"], eps)
    kv = _ein("btc,ck->btk", c, lw["w_kvb"], q).reshape(
        b, t, z["h"], z["dn"] + z["dv"])
    kp = _rope(down[:, :, None, z["dc"]:], theta)[:, :, 0]
    return (qh[..., :z["dn"]], _rope(qh[..., z["dn"]:], theta),
            kv[..., :z["dn"]], kp, kv[..., z["dn"]:])


def attention_part(h, lw, cfg, q=None):
    """Steps 1-5 before the residual: h [B, T, D] -> [B, T, D]."""
    z = _dims(cfg)
    b, t, _ = h.shape
    q_nope, q_pe, k_nope, kp, v = latent_projections(h, lw, cfg, q)
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    scale = softmax_scale(cfg)

    @jax.checkpoint
    def block(qn_blk, qp_blk, q_pos):
        s = (_ein("bqhd,bkhd->bhqk", qn_blk, k_nope, q)
             + rotary_scores(qp_blk, kp, q)) * scale
        keep = q_pos[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), axis=-1)
        return _ein("bhqk,bkhd->bqhd", p, v, q)

    cut = lambda x: x.reshape((b, t // blk, blk) + x.shape[2:]).swapaxes(
        0, 1)                                                  # noqa: E731
    a = jax.lax.map(lambda args: block(*args), (
        cut(q_nope), cut(q_pe), jnp.arange(t).reshape(t // blk, blk)))
    a = a.swapaxes(0, 1).reshape(b, t, z["h"] * z["dv"])
    return _ein("btk,kd->btd", a, lw["w_o"], q)


def route(u, w_r, b_r, cfg, q=None):
    """u [N, D] -> (E [N, k] expert ids chosen by score + bias, w [N, k]
    from the unbiased scores, the scores [N, all])."""
    sc = jax.nn.sigmoid(_ein("nd,de->ne", u, w_r, q))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(sc) + b_r,
                           cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(sc, idx, axis=-1)
    return idx, (cfg["routed_scaling_factor"] * top
                 / (top.sum(axis=-1, keepdims=True) + 1e-20)), sc


def _glu(u, w_gate_up, w_down, q):
    f = w_down.shape[0]
    hid = (jax.nn.silu(_ein("nd,df->nf", u, w_gate_up[:, :f], q))
           * _ein("nd,df->nf", u, w_gate_up[:, f:], q))
    return _ein("nf,fd->nd", hid, w_down, q)


def routed_part(u, idx, p, lw, cfg, q=None):
    """The held experts' part of step 6: a dense masked sum, one expert at
    a time."""
    first = cfg["first_expert"]
    out = jnp.zeros_like(u)

    @jax.checkpoint
    def one(u, weight, w_gate_up, w_down):
        return weight[:, None] * _glu(u, w_gate_up, w_down, q)

    for e in range(cfg["n_routed_experts"]):
        weight = jnp.sum(jnp.where(idx == first + e, p, 0.0), axis=-1)
        out = out + one(u, weight, lw["w_gate_up"][e], lw["w_down"][e])
    return out


def shared_part(u, lw, q=None):
    """The shared experts: one SwiGLU of their joint width, no gate."""
    return _glu(u, lw["w_sgu"], lw["w_sd"], q)


def feed_forward_part(u, lw, cfg, dense, q=None):
    """Step 6 before the residual: u [N, D] -> [N, D]."""
    if dense:
        return _glu(u, lw["w_ffn_gate_up"], lw["w_ffn_down"], q)
    idx, p, _ = route(u, lw["w_r"], lw["b_r"], cfg, q)
    return routed_part(u, idx, p, lw, cfg, q) + shared_part(u, lw, q)


def layer(x, lw, cfg, dense, q=None):
    b, t, d = x.shape
    eps = cfg["rms_norm_eps"]
    x = x + attention_part(_rms(x, lw["g1"], eps), lw, cfg, q)
    u = _rms(x, lw["g2"], eps).reshape(b * t, d)
    return x + feed_forward_part(u, lw, cfg, dense, q).reshape(b, t, d)


def hidden(w, ids, cfg, q=None):
    """ids [B, T] -> final-RMSNorm hidden states [B, T, D]."""
    x = w["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda x, lw, dense=is_dense(cfg, i): layer(x, lw, cfg, dense,
                                                        q))(
            x, layer_weights(w, i))
    return _rms(x, w["g_f"], cfg["rms_norm_eps"])


def routing_counts(w, ids, cfg):
    """What the program's routing counters hold after one step, per layer
    (0 for a dense one): rows the held experts serve, (token, slot) choices
    the bias changed, the sum of the chosen weights."""
    b, t = ids.shape
    k, eps = cfg["num_experts_per_tok"], cfg["rms_norm_eps"]
    first, held = cfg["first_expert"], cfg["n_routed_experts"]
    x = w["embed"][ids]
    out = {"rows_held": [], "route_bias_flips": [], "route_weight_sum": []}
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_weights(w, i)
        if is_dense(cfg, i):
            for v in out.values():
                v.append(jnp.zeros(()))
        else:
            a = x + attention_part(_rms(x, lw["g1"], eps), lw, cfg)
            u = _rms(a, lw["g2"], eps).reshape(b * t, -1)
            idx, p, sc = route(u, lw["w_r"], lw["b_r"], cfg)
            plain = jax.lax.top_k(sc, k)[1]
            kept = (idx[:, :, None] == plain[:, None, :]).any(-1)
            out["rows_held"].append(jnp.sum(jnp.logical_and(
                idx >= first, idx < first + held)).astype(jnp.float32))
            out["route_bias_flips"].append(
                jnp.sum(jnp.logical_not(kept)).astype(jnp.float32))
            out["route_weight_sum"].append(p.sum())
        x = layer(x, lw, cfg, is_dense(cfg, i))
    return {n: jnp.stack(v) for n, v in out.items()}


def loss(w, batch, cfg, q=None):
    """Step 7 for batch["input_ids"] [B, T]; the cross-entropy by blocks
    of tokens."""
    ids = batch["input_ids"]
    b, t = ids.shape
    h = hidden(w, ids, cfg, q).reshape(b * t, -1)
    # the last position of a sequence has no target: weight 0
    target = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1).reshape(-1)
    weight = jnp.tile(jnp.arange(t) < t - 1, b).astype(jnp.float32)
    blk = TOKEN_BLOCK if (b * t) % TOKEN_BLOCK == 0 else b * t

    @jax.checkpoint
    def block(total, args):
        h_blk, tgt, wt = args
        lg = _ein("nd,dv->nv", h_blk, w["head"], q)
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
