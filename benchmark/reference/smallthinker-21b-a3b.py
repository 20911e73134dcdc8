"""Plain reference for the `smallthinker-21b-a3b` configuration: one chip's
share of SmallThinker-21BA3B-Instruct (PowerInfer; arXiv:2507.20984) in
straightforward `jax.numpy`, float32, every matrix product at
`Precision.HIGHEST`, no kernel, no sort, no cache. It takes its weights
from the seed and nothing from the program.

One layer, all projections without bias, x [T, D]:

    h = RMSNorm(x; g1)                      s = h W_r    (W_r: D x 64)
    I(t) = the 6 largest of s[t, :]         p[t, e] = softmax over I(t)
    q = h W_q -> [T, Hq, 128]    k = h W_k, v = h W_v -> [T, Hkv, 128]
        (RoPE on q, k iff rope_layout[l] = 1)
    a[t, i] = sum_j softmax_j(q[t, i] . k[j, i // g] / sqrt(128)) v[j, i // g]
        over j <= t and, iff sliding_window_layout[l] = 1, j > t - 4096
    x' = x + concat_i(a) W_o                u = RMSNorm(x'; g2)
    m[t] = sum_{e in I(t), e held} p[t, e] W_down[e](relu(W_gate[e] u[t])
                                                     * (W_up[e] u[t]))
    out = x' + m
    logits = RMSNorm(x_L; g_f) W_head;  loss = mean next-token cross-entropy

The share (the file's `deployment`): `num_attention_heads` query heads on
`num_key_value_heads` key-value heads, experts `first_expert ..
first_expert + moe_num_primary_experts - 1` of the router's
`moe_router_outputs`, `vocab_size` rows of the embedding and of the head.
Rows routed to experts held elsewhere are left out of m, and that partial
result goes on to the next layer.

Departures from the published model, each also under `assumed` in the
configuration file: the router reads h (the family's description; the
config has no key); ReLU gates (described as "sparse ReGLU"; no
`hidden_act` key); no biases, no query/key norm; RoPE in the half-split
convention; the window holds the query's own position and the 4095 before
it. To fit beside the trainer, attention is computed by query blocks, the
experts one after another as a dense masked sum, and the loss by token
blocks; none changes a number. For the same reason the weights are kept
layer by layer in the shapes the program's tree has (W_gate and W_up are
the two halves of one `w_gate_up`), so that relabelling a gradient into
the program's layout copies nothing.

`q="int8"` is the CONTROL, not a feature: both operands of every matrix
product are rounded to 8-bit integers with one scale per tensor
(absmax / 127) before they are multiplied. It is the precision step below
the configuration's bf16 compute, and `correct` has to refuse it.
"""

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST

#: rows per block of the blockwise parts (memory only, never a number)
QUERY_BLOCK = 512
TOKEN_BLOCK = 2048


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
            "n": cfg["num_hidden_layers"], "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "e_all": cfg["moe_router_outputs"],
            "held": cfg["moe_num_primary_experts"],
            "f": cfg["moe_ffn_hidden_size"]}


def _layer_shapes(cfg):
    z = _dims(cfg)
    d, f = z["d"], z["f"]
    return {
        "g1": ((d,), "g"), "g2": ((d,), "g"),
        "w_r": ((d, z["e_all"]), "w"),
        "w_q": ((d, z["hq"] * z["hd"]), "w"),
        "w_k": ((d, z["hkv"] * z["hd"]), "w"),
        "w_v": ((d, z["hkv"] * z["hd"]), "w"),
        "w_o": ((z["hq"] * z["hd"], d), "w"),
        # W_gate[e] = w_gate_up[e][:, :f], W_up[e] = w_gate_up[e][:, f:]
        "w_gate_up": ((z["held"], d, 2 * f), "w"),
        "w_down": ((z["held"], f, d), "w"),
    }


def weight_shapes(cfg):
    """name -> (shape, kind); layer l's tensors are named "l/<name>"."""
    z = _dims(cfg)
    out = {"embed": ((z["v"], z["d"]), "w"), "head": ((z["d"], z["v"]), "w"),
           "g_f": ((z["d"],), "g")}
    for i in range(z["n"]):
        for name, spec in _layer_shapes(cfg).items():
            out["%d/%s" % (i, name)] = spec
    return out


def layer_weights(w, i):
    """Layer i's tensors under their plain names."""
    head = "%d/" % i
    return {k[len(head):]: v for k, v in w.items() if k.startswith(head)}


def init_weights(cfg, key):
    """Seeded weights, traced inside the caller's ONE jitted call:
    matrices N(0, initializer_range), the embedding and the routers at
    ranges of their own (the configuration's `assumed.weights`), RMSNorm
    gains 1 + N(0, range), so that a path that drops a gain shows in
    `correct`."""
    std = cfg["initializer_range"]
    own = {"embed": cfg["embedding_initializer_range"],
           "w_r": cfg["router_initializer_range"]}
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
    """x [B, T, H, hd], half-split pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def route(h, w_r, cfg, q=None):
    """h [N, D] -> (I [N, k] expert ids, p [N, k])."""
    s = _ein("nd,de->ne", h, w_r, q)
    top, idx = jax.lax.top_k(s, cfg["moe_num_active_primary_experts"])
    return idx, jax.nn.softmax(top, axis=-1)


def attention_part(h, lw, use_rope, use_window, cfg, q=None):
    """The held heads' part of the attention result, before the residual:
    h [B, T, D] -> [B, T, D]. `use_rope`, `use_window`: the layer's
    entries of `rope_layout` and `sliding_window_layout`."""
    z = _dims(cfg)
    b, t, _ = h.shape
    hq, hkv, hd = z["hq"], z["hkv"], z["hd"]
    g = hq // hkv
    qh = _ein("btd,dk->btk", h, lw["w_q"], q).reshape(b, t, hq, hd)
    kh = _ein("btd,dk->btk", h, lw["w_k"], q).reshape(b, t, hkv, hd)
    vh = _ein("btd,dk->btk", h, lw["w_v"], q).reshape(b, t, hkv, hd)
    if use_rope:
        qh = _rope(qh, float(cfg["rope_theta"]))
        kh = _rope(kh, float(cfg["rope_theta"]))
    qh = qh.reshape(b, t, hkv, g, hd)       # query head i reads kv i // g
    window = cfg["sliding_window_size"]
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    k_pos = jnp.arange(t)

    @jax.checkpoint
    def block(q_blk, q_pos):
        s = _ein("bqhgd,bkhd->bhgqk", q_blk, kh, q) / jnp.sqrt(float(hd))
        back = q_pos[:, None] - k_pos[None, :]
        keep = back >= 0
        if use_window:
            keep = jnp.logical_and(keep, back < window)
        s = jnp.where(keep, s, -jnp.inf)
        return _ein("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), vh, q)

    q_blocks = qh.reshape(b, t // blk, blk, hkv, g, hd).swapaxes(0, 1)
    a = jax.lax.map(lambda args: block(*args),
                    (q_blocks, k_pos.reshape(t // blk, blk)))
    a = a.swapaxes(0, 1).reshape(b, t, hq * hd)
    return _ein("btk,kd->btd", a, lw["w_o"], q)


def experts_part(u, idx, p, lw, cfg, q=None):
    """The held experts' part of the expert layer: u [N, D], routing
    (idx, p) [N, k] -> [N, D]; a dense masked sum, one expert at a time."""
    first, f = cfg["first_expert"], cfg["moe_ffn_hidden_size"]
    out = jnp.zeros_like(u)

    @jax.checkpoint
    def one(u, weight, w_gate_up, w_down):
        hid = (jax.nn.relu(_ein("nd,df->nf", u, w_gate_up[:, :f], q))
               * _ein("nd,df->nf", u, w_gate_up[:, f:], q))
        return weight[:, None] * _ein("nf,fd->nd", hid, w_down, q)

    for e in range(cfg["moe_num_primary_experts"]):
        weight = jnp.sum(jnp.where(idx == first + e, p, 0.0), axis=-1)
        out = out + one(u, weight, lw["w_gate_up"][e], lw["w_down"][e])
    return out


def layer(x, lw, use_rope, use_window, cfg, q=None):
    b, t, d = x.shape
    eps = cfg["rms_norm_eps"]
    h = _rms(x, lw["g1"], eps)
    idx, p = route(h.reshape(b * t, d), lw["w_r"], cfg, q)
    x = x + attention_part(h, lw, use_rope, use_window, cfg, q)
    u = _rms(x, lw["g2"], eps)
    return x + experts_part(u.reshape(b * t, d), idx, p, lw, cfg,
                            q).reshape(b, t, d)


def hidden(w, ids, cfg, q=None):
    """ids [B, T] -> final-RMSNorm hidden states [B, T, D]."""
    x = w["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda x, lw, i=i: layer(x, lw, cfg["rope_layout"][i],
                                     cfg["sliding_window_layout"][i], cfg,
                                     q))(x, layer_weights(w, i))
    return _rms(x, w["g_f"], cfg["rms_norm_eps"])


def loss(w, batch, cfg, q=None):
    """Mean next-token cross-entropy of batch["input_ids"] [B, T] over the
    held rows of the vocabulary, by blocks of tokens."""
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
    """(loss, d loss / d w) of the whole batch in one pass: sequences
    share nothing but the weights, and a second gradient tree for adding
    up blocks of them would not fit beside the trainer."""
    return jax.value_and_grad(lambda w_: loss(w_, batch, cfg, q))(w)
