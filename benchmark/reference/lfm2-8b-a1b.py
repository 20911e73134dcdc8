"""Plain reference for the `lfm2-8b-a1b` configuration: one chip's share of
LFM2-8B-A1B (LiquidAI; `model_type` lfm2_moe) in straightforward
`jax.numpy`, float32, every matrix product at `Precision.HIGHEST`, no
kernel, no cache. It takes its weights from the seed and nothing from the
program.

h [T, D] is the residual stream, E [V, D] the ONE matrix that is embedding
and head, for this chip's vocabulary rows `first_vocab_row .. + V - 1` and
experts `first_expert .. + held - 1`; no projection has a bias.

    0. h = E[ids]                       (no scale, no learned positions)
    every layer p:
    1. u = RMSNorm(h; g1)               (the published `operator_norm`)
    2. `layer_types[p] == "conv"`, the gated short convolution:
         [B | C | x] = u W_in           (W_in [D, 3 D], thirds in THAT order)
         z_t = sum_{j=0..K-1} w[:, j] * (B * x)_{t-(K-1)+j}
                                        (depthwise, causal: zero before the
                                        sequence; K = conv_L_cache = 3; no
                                        bias, NO activation)
         h = h + (C * z) W_out
       `layer_types[p] == "full_attention"`:
         q = u W_q -> [T, Hq, hd], k = u W_k, v = u W_v -> [T, Hkv, hd]
         q = rope(RMSNorm_hd(q; g_q)), k = rope(RMSNorm_hd(k; g_k))
                                        (the norm BEFORE the turn; the turn
                                        over the whole head, half-split
                                        pairs, theta 1e6)
         p = softmax over j <= i of q_i . k_j / sqrt(hd), query head a reads
         key-value head a // (Hq / Hkv);  h = h + concat_H(p v) W_o
    3. f = RMSNorm(h; g2)               (`ffn_norm`)
       p < num_dense_layers:  h = h + (silu(f W_1) * (f W_3)) W_2
       else:  s = sigmoid(f W_r) over ALL experts;  E(t) = the 4 largest of
              s + b;  w_e = routed_scaling_factor * s_e
                            / (sum over E(t) of s + 1e-6)
              h = h + sum over e in E(t), e held, of
                      w_e (silu(f W1_e) * (f W3_e)) W2_e
    4. RMSNorm(h; g_f) (the published `embedding_norm`), logits = h E^T over
       the held rows, mean next-token cross-entropy.

The gradient reaches W_r through w_e (all four chosen scores are in the
normaliser, held or not); b gets none. E's gradient is the SUM of its two
uses: the rows gathered in step 0 and the product of step 4. Rows routed to
experts held elsewhere are left out; that partial result goes on to the
next layer.

Departures from the published model, each also under `assumed` in the
configuration file: half-split rotary pairs (the published code's own
`rotate_half`); the bias b is a constant of the run; no auxiliary loss. To
fit beside the trainer, attention is computed by blocks of QUERY rows (each
row's softmax is whole inside its block, so no number changes), the experts
one after another as a dense masked sum, layers under `jax.checkpoint`, and
the head's loss by blocks of tokens, so that the float32 logits and their
cotangent never stand whole.

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
    hq = cfg["num_attention_heads"]
    return {"v": cfg["vocab_size"], "d": cfg["hidden_size"],
            "n": cfg["num_hidden_layers"], "hq": hq,
            "hkv": cfg["num_key_value_heads"],
            "hd": cfg["head_dim"],
            "kw": cfg["conv_L_cache"], "e_all": cfg["num_router_outputs"],
            "held": cfg["num_experts"], "f": cfg["moe_intermediate_size"],
            "fd": cfg["intermediate_size"]}


def is_dense(cfg, i):
    """Layer i has a dense feed-forward part and no experts."""
    return i < cfg["num_dense_layers"]


def is_conv(cfg, i):
    """Layer i's mixer is the gated short convolution."""
    kind = cfg["layer_types"][i]
    if kind not in ("conv", "full_attention"):
        raise ValueError("layer_types[%d] = %r" % (i, kind))
    return kind == "conv"


def _layer_shapes(cfg, i):
    z = _dims(cfg)
    d, hd = z["d"], z["hd"]
    out = {"g1": ((d,), "g"), "g2": ((d,), "g")}
    if is_conv(cfg, i):
        # B = (u w_in)[:, :d], C = [:, d:2d], x = [:, 2d:]
        out.update(w_in=((d, 3 * d), "w"), w_conv=((d, z["kw"]), "taps"),
                   w_out=((d, d), "w"))
    else:
        out.update(w_q=((d, z["hq"] * hd), "w"),
                   w_k=((d, z["hkv"] * hd), "w"),
                   w_v=((d, z["hkv"] * hd), "w"),
                   w_o=((z["hq"] * hd, d), "w"),
                   g_q=((hd,), "g"), g_k=((hd,), "g"))
    if is_dense(cfg, i):
        # W_1 = w_ffn_gate_up[:, :fd], W_3 = w_ffn_gate_up[:, fd:]
        return dict(out, w_ffn_gate_up=((d, 2 * z["fd"]), "w"),
                    w_ffn_down=((z["fd"], d), "w"))
    return dict(out, w_r=((d, z["e_all"]), "w"), b_r=((z["e_all"],), "w"),
                w_gate_up=((z["held"], d, 2 * z["f"]), "w"),
                w_down=((z["held"], z["f"], d), "w"))


def weight_shapes(cfg):
    """name -> (shape, kind); layer l's tensors are named "l/<name>". There
    is no head: it is `embed`."""
    z = _dims(cfg)
    out = {"embed": ((z["v"], z["d"]), "w"), "g_f": ((z["d"],), "g")}
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
    matrices N(0, initializer_range); the tied embedding, the routers and
    the routers' selection bias at ranges of their own (the configuration's
    `assumed.weights`); RMSNorm gains 1 + N(0, range), so that a path that
    drops a gain shows in `correct`; the convolution's taps U(-K^-1/2,
    K^-1/2), what a depthwise convolution of K taps starts from."""
    std = cfg["initializer_range"]
    own = {"embed": cfg["embedding_initializer_range"],
           "w_r": cfg["router_initializer_range"],
           "b_r": cfg["router_bias_initializer_range"]}
    bound = float(cfg["conv_L_cache"]) ** -0.5
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(
            weight_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if kind == "taps":
            out[name] = jax.random.uniform(k, shape, jnp.float32, -bound,
                                           bound)
            continue
        x = jax.random.normal(k, shape, jnp.float32)
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


def short_conv(y, w_conv):
    """y [B, T, D], w_conv [D, K]: z_t = sum_j w[:, j] y_{t-(K-1)+j}, y zero
    before the sequence — K shifted products, one a tap."""
    t, kw = y.shape[1], w_conv.shape[1]
    out = jnp.zeros_like(y)
    for j in range(kw):
        back = kw - 1 - j               # tap j reads `back` tokens behind
        shifted = jnp.concatenate(
            [jnp.zeros_like(y[:, :back]), y[:, :t - back]], axis=1)
        out = out + shifted * w_conv[:, j]
    return out


def gated_conv(b_gate, c_gate, x, w_conv):
    """Everything between the two projections: C * conv(B * x)."""
    return c_gate * short_conv(b_gate * x, w_conv)


def conv_part(u, lw, cfg, q=None):
    """Step 2 of a conv layer before the residual: u [B, T, D] -> (its part
    [B, T, D], the largest |C * z| it formed)."""
    d = u.shape[-1]
    bcx = _ein("btd,dk->btk", u, lw["w_in"], q)
    y = gated_conv(bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:],
                   lw["w_conv"])
    return _ein("btk,kd->btd", y, lw["w_out"], q), jnp.max(jnp.abs(y))


def qk_positions(qh, kh, lw, cfg):
    """RMSNorm over each head's width, THEN the rotary turn."""
    eps, theta = cfg["norm_eps"], float(cfg["rope_theta"])
    return (_rope(_rms(qh, lw["g_q"], eps), theta),
            _rope(_rms(kh, lw["g_k"], eps), theta))


def attention_part(u, lw, cfg, q=None):
    """Step 2 of an attention layer before the residual."""
    z = _dims(cfg)
    b, t, _ = u.shape
    hq, hkv, hd = z["hq"], z["hkv"], z["hd"]
    grp = hq // hkv
    qh = _ein("btd,dk->btk", u, lw["w_q"], q).reshape(b, t, hq, hd)
    kh = _ein("btd,dk->btk", u, lw["w_k"], q).reshape(b, t, hkv, hd)
    vh = _ein("btd,dk->btk", u, lw["w_v"], q).reshape(b, t, hkv, hd)
    qh, kh = qk_positions(qh, kh, lw, cfg)
    qh = qh.reshape(b, t, hkv, grp, hd)     # query head a reads kv a // grp
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    @jax.checkpoint
    def block(q_blk, q_pos):
        sc = _ein("bqhgd,bkhd->bhgqk", q_blk, kh, q) * float(hd) ** -0.5
        keep = q_pos[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return _ein("bhgqk,bkhd->bqhgd", p, vh, q)

    a = jax.lax.map(lambda args: block(*args), (
        qh.reshape((b, t // blk, blk) + qh.shape[2:]).swapaxes(0, 1),
        jnp.arange(t).reshape(t // blk, blk)))
    a = a.swapaxes(0, 1).reshape(b, t, hq * hd)
    return _ein("btk,kd->btd", a, lw["w_o"], q)


def mixer_part(u, lw, cfg, conv, q=None):
    """(the mixer's part of the residual, a conv layer's |C * z| maximum; 0
    for an attention layer)."""
    if conv:
        return conv_part(u, lw, cfg, q)
    return attention_part(u, lw, cfg, q), jnp.zeros(())


def route(f, w_r, b_r, cfg, q=None):
    """f [N, D] -> (E [N, k] expert ids chosen by score + bias, w [N, k]
    from the unbiased scores, the scores [N, all])."""
    sc = jax.nn.sigmoid(_ein("nd,de->ne", f, w_r, q))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(sc) + b_r,
                           cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(sc, idx, axis=-1)
    return idx, (cfg["routed_scaling_factor"] * top
                 / (top.sum(axis=-1, keepdims=True)
                    + cfg["router_norm_eps"])), sc


def _glu(f, w_gate_up, w_down, q):
    width = w_down.shape[0]
    hid = (jax.nn.silu(_ein("nd,df->nf", f, w_gate_up[:, :width], q))
           * _ein("nd,df->nf", f, w_gate_up[:, width:], q))
    return _ein("nf,fd->nd", hid, w_down, q)


def routed_part(f, idx, p, lw, cfg, q=None):
    """The held experts' part of step 3: a dense masked sum, one expert at
    a time."""
    first = cfg["first_expert"]
    out = jnp.zeros_like(f)

    @jax.checkpoint
    def one(f, weight, w_gate_up, w_down):
        return weight[:, None] * _glu(f, w_gate_up, w_down, q)

    for e in range(cfg["num_experts"]):
        weight = jnp.sum(jnp.where(idx == first + e, p, 0.0), axis=-1)
        out = out + one(f, weight, lw["w_gate_up"][e], lw["w_down"][e])
    return out


def feed_forward_part(f, lw, cfg, dense, q=None):
    """Step 3 before the residual: f [N, D] -> [N, D]."""
    if dense:
        return _glu(f, lw["w_ffn_gate_up"], lw["w_ffn_down"], q)
    idx, p, _ = route(f, lw["w_r"], lw["b_r"], cfg, q)
    return routed_part(f, idx, p, lw, cfg, q)


def layer(h, lw, cfg, conv, dense, q=None):
    b, t, d = h.shape
    eps = cfg["norm_eps"]
    h = h + mixer_part(_rms(h, lw["g1"], eps), lw, cfg, conv, q)[0]
    f = _rms(h, lw["g2"], eps).reshape(b * t, d)
    return h + feed_forward_part(f, lw, cfg, dense, q).reshape(b, t, d)


def hidden(w, ids, cfg, q=None):
    """ids [B, T] -> final-RMSNorm hidden states [B, T, D]."""
    h = w["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(
            lambda h, lw, conv=is_conv(cfg, i), dense=is_dense(cfg, i):
            layer(h, lw, cfg, conv, dense, q))(h, layer_weights(w, i))
    return _rms(h, w["g_f"], cfg["norm_eps"])


def layer_counts(w, ids, cfg):
    """What the program's counters hold after one step, per layer: rows the
    held experts serve, (token, slot) choices the bias changed and the sum
    of the chosen weights (0 for a dense layer), and the largest |C * z| of
    a conv layer (0 for an attention layer)."""
    b, t = ids.shape
    k, eps = cfg["num_experts_per_tok"], cfg["norm_eps"]
    first, held = cfg["first_expert"], cfg["num_experts"]
    h = w["embed"][ids]
    out = {"rows_held": [], "route_bias_flips": [], "route_weight_sum": [],
           "conv_gate_absmax": []}
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_weights(w, i)
        mixed, top = mixer_part(_rms(h, lw["g1"], eps), lw, cfg,
                                is_conv(cfg, i))
        out["conv_gate_absmax"].append(top)
        if is_dense(cfg, i):
            for name in ("rows_held", "route_bias_flips",
                         "route_weight_sum"):
                out[name].append(jnp.zeros(()))
        else:
            f = _rms(h + mixed, lw["g2"], eps).reshape(b * t, -1)
            idx, p, sc = route(f, lw["w_r"], lw["b_r"], cfg)
            plain = jax.lax.top_k(sc, k)[1]
            kept = (idx[:, :, None] == plain[:, None, :]).any(-1)
            out["rows_held"].append(jnp.sum(jnp.logical_and(
                idx >= first, idx < first + held)).astype(jnp.float32))
            out["route_bias_flips"].append(
                jnp.sum(jnp.logical_not(kept)).astype(jnp.float32))
            out["route_weight_sum"].append(p.sum())
        h = layer(h, lw, cfg, is_conv(cfg, i), is_dense(cfg, i))
    return {n: jnp.stack(v) for n, v in out.items()}


def head_matrix(w):
    """The head IS the embedding: [D, V]."""
    return w["embed"].T


def loss(w, batch, cfg, q=None):
    """Step 4 for batch["input_ids"] [B, T]; the cross-entropy by blocks
    of tokens."""
    ids = batch["input_ids"]
    b, t = ids.shape
    h = hidden(w, ids, cfg, q).reshape(b * t, -1)
    head = head_matrix(w)
    # the last position of a sequence has no target: weight 0
    target = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1).reshape(-1)
    weight = jnp.tile(jnp.arange(t) < t - 1, b).astype(jnp.float32)
    blk = TOKEN_BLOCK if (b * t) % TOKEN_BLOCK == 0 else b * t

    @jax.checkpoint
    def block(total, args):
        h_blk, tgt, wt = args
        lg = _ein("nd,dv->nv", h_blk, head, q)
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
