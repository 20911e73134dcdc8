"""Plain reference for the `keye-vl2-30b-a3b` configuration: one chip's
share of the LANGUAGE MODEL of Keye-VL-2.0-30B-A3B (Kwai-Keye; the vision
tower is left out) in straightforward `jax.numpy`, float32, every matrix
product at `Precision.HIGHEST`, no kernel, no cache. It takes its weights
from the seed and nothing from the program. The attention is
DeepSeek-Sparse-Attention (arXiv:2512.02556) in its sparse-training stage.

One layer, all projections without bias, x [T, D]; sg = stop-gradient:

    1. h = RMSNorm(x; g1);  q = h W_q -> [T, Hq, 128];  k = h W_k,
       v = h W_v -> [T, Hkv, 128];  q = RMSNorm(q; g_q), k = RMSNorm(k; g_k)
       over the head width;  RoPE on q, k
    2. qi = sg(h) W_qi -> [T, 16, 64];  ki = sg(h) W_ki -> [T, 64];
       wi = sg(h) W_wi -> [T, 16];  RoPE on qi, ki
       I[t, s] = sum_j wi[t, j] relu(qi[t, j] . ki[s]) 64^-1/2 16^-1/2, s <= t
    3. tau[t] = the 2048th largest of {I[t, s] : s <= t} (lax.top_k on the
       materialised scores; -inf while t + 1 <= 2048);
       S_t = {s <= t : I[t, s] >= tau[t]}
    4. p_h[t, s] = softmax over S_t of q_h[t] . k[s] / sqrt(128);
       a_h[t] = sum_s p_h v[s];  x' = x + concat_h(a_h) W_o
    5. u = RMSNorm(x'; g2);  r = u W_r (W_r: D x 128);  E(t) = the 8
       largest of r[t];  p[t, e] = softmax over E(t);
       x'' = x' + sum_{e in E(t), e held} p[t, e] W_down[e](silu(W_gate[e] u)
                                                           * (W_up[e] u))
    6. loss = mean next-token cross-entropy over the held vocabulary rows
       + index_loss_weight * mean over layers and tokens of
         KL(sg(mean_h p_h[t, .]) || softmax over S_t of I[t, .])

The indexer's tensors (W_qi, W_ki, W_wi) get their gradient from the KL
term alone (it reaches I and nothing else: the mean of the p_h and the
indexer's input are stop-gradients, the choice is discrete); everything
else from the cross-entropy alone.

The share (the file's `deployment`): `num_attention_heads` query heads on
`num_key_value_heads` key-value heads, experts `first_expert ..
first_expert + num_experts - 1` of the router's `num_local_experts`,
`vocab_size` rows of the embedding and of the head; the indexer WHOLE.
Rows routed to experts held elsewhere are left out, and that partial
result goes on to the next layer.

Departures from the published model, each also under `assumed` in the
configuration file: RMSNorm on q and k over the head width; RoPE
(half-split pairs, theta 1e7; the three M-RoPE sections carry one text
index) on q, k, qi and ki; the indexer trained by the KL term with weight
1; ties at tau all kept. To fit beside the trainer, steps 2-4 and the KL
are computed by blocks of QUERY rows (each row's scores, threshold and
softmaxes are whole inside its block, so no number changes), the experts
one after another as a dense masked sum, and the loss by token blocks.

`q="int8"` is the CONTROL, not a feature: both operands of every matrix
product are rounded to 8-bit integers with one scale per tensor
(absmax / 127) before they are multiplied. `correct` has to refuse it.
"""

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST

#: rows per block of the blockwise parts (memory only, never a number)
QUERY_BLOCK = 256
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
    sa = cfg["sa_config"]
    return {"v": cfg["vocab_size"], "d": cfg["hidden_size"],
            "n": cfg["num_hidden_layers"], "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "e_all": cfg["num_local_experts"], "held": cfg["num_experts"],
            "f": cfg["moe_intermediate_size"],
            "hi": sa["indexer_num_heads"], "di": sa["indexer_head_dim"],
            "topk": sa["topk"]}


def _layer_shapes(cfg):
    z = _dims(cfg)
    d, f, hd = z["d"], z["f"], z["hd"]
    return {
        "g1": ((d,), "g"), "g2": ((d,), "g"),
        "g_q": ((hd,), "g"), "g_k": ((hd,), "g"),
        "w_r": ((d, z["e_all"]), "w"),
        "w_q": ((d, z["hq"] * hd), "w"),
        "w_k": ((d, z["hkv"] * hd), "w"),
        "w_v": ((d, z["hkv"] * hd), "w"),
        "w_o": ((z["hq"] * hd, d), "w"),
        "w_qi": ((d, z["hi"] * z["di"]), "w"),
        "w_ki": ((d, z["di"]), "w"),
        "w_wi": ((d, z["hi"]), "w"),
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


def route(u, w_r, cfg, q=None):
    """u [N, D] -> (E [N, k] expert ids, p [N, k])."""
    s = _ein("nd,de->ne", u, w_r, q)
    top, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    return idx, jax.nn.softmax(top, axis=-1)


def projections(h, lw, cfg, q=None):
    """Steps 1 and 2 up to the rotary positions: (q [B, T, Hq, hd], k, v
    [B, T, Hkv, hd], qi [B, T, hi, di], ki [B, T, di], wi [B, T, hi])."""
    z = _dims(cfg)
    b, t, _ = h.shape
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    qh = _ein("btd,dk->btk", h, lw["w_q"], q).reshape(b, t, z["hq"], z["hd"])
    kh = _ein("btd,dk->btk", h, lw["w_k"], q).reshape(b, t, z["hkv"],
                                                      z["hd"])
    vh = _ein("btd,dk->btk", h, lw["w_v"], q).reshape(b, t, z["hkv"],
                                                      z["hd"])
    qh = _rope(_rms(qh, lw["g_q"], eps), theta)
    kh = _rope(_rms(kh, lw["g_k"], eps), theta)
    hs = jax.lax.stop_gradient(h)
    qi = _rope(_ein("btd,dk->btk", hs, lw["w_qi"], q).reshape(
        b, t, z["hi"], z["di"]), theta)
    ki = _rope(_ein("btd,dk->btk", hs, lw["w_ki"], q)[:, :, None],
               theta)[:, :, 0]
    wi = _ein("btd,dj->btj", hs, lw["w_wi"], q)
    return qh, kh, vh, qi, ki, wi


def index_scores(qi_blk, ki, wi_blk, cfg, q=None):
    """I [B, Q, T] for a block of queries against all keys (step 2)."""
    z = _dims(cfg)
    x = _ein("bqjd,bsd->bqjs", qi_blk, ki, q)
    return jnp.sum(wi_blk[..., None] * jax.nn.relu(x), axis=2) * (
        float(z["di"]) ** -0.5 * float(z["hi"]) ** -0.5)


def selection(scores, q_pos, topk):
    """Step 3 for a block of queries: scores [B, Q, T], q_pos [Q] ->
    keep [B, Q, T] bool."""
    t = scores.shape[-1]
    causal = q_pos[:, None] >= jnp.arange(t)[None, :]
    if t <= topk:
        return jnp.broadcast_to(causal[None], scores.shape)
    masked = jnp.where(causal[None], scores, -jnp.inf)
    tau = jax.lax.top_k(masked, topk)[0][..., -1]
    tau = jnp.where(q_pos[None] < topk, -jnp.inf, tau)
    return jnp.logical_and(causal[None], masked >= tau[..., None])


def attention_part(h, lw, cfg, q=None):
    """Steps 1-4 before the residual, and the KL of step 6 per row:
    h [B, T, D] -> ([B, T, D], [B, T])."""
    z = _dims(cfg)
    b, t, _ = h.shape
    hq, hkv, hd = z["hq"], z["hkv"], z["hd"]
    g = hq // hkv
    qh, kh, vh, qi, ki, wi = projections(h, lw, cfg, q)
    qh = qh.reshape(b, t, hkv, g, hd)       # query head i reads kv i // g
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    @jax.checkpoint
    def block(q_blk, qi_blk, wi_blk, q_pos):
        scores = index_scores(qi_blk, ki, wi_blk, cfg, q)
        keep = selection(jax.lax.stop_gradient(scores), q_pos, z["topk"])
        s = _ein("bqhgd,bkhd->bhgqk", q_blk, kh, q) / jnp.sqrt(float(hd))
        p = jax.nn.softmax(jnp.where(keep[:, None, None], s, -jnp.inf),
                           axis=-1)
        a = _ein("bhgqk,bkhd->bqhgd", p, vh, q)
        pbar = jax.lax.stop_gradient(jnp.mean(p, axis=(1, 2)))
        log_pi = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf),
                                    axis=-1)
        kl = jnp.sum(jnp.where(
            pbar > 0, pbar * (jnp.log(jnp.where(pbar > 0, pbar, 1.0))
                              - jnp.where(keep, log_pi, 0.0)), 0.0), axis=-1)
        return a, kl

    cut = lambda x: x.reshape((b, t // blk, blk) + x.shape[2:]).swapaxes(
        0, 1)                                                  # noqa: E731
    a, kl = jax.lax.map(lambda args: block(*args), (
        cut(qh), cut(qi), cut(wi), jnp.arange(t).reshape(t // blk, blk)))
    a = a.swapaxes(0, 1).reshape(b, t, hq * hd)
    return (_ein("btk,kd->btd", a, lw["w_o"], q),
            kl.swapaxes(0, 1).reshape(b, t))


def experts_part(u, idx, p, lw, cfg, q=None):
    """The held experts' part of step 5: u [N, D], routing (idx, p)
    [N, k] -> [N, D]; a dense masked sum, one expert at a time."""
    first, f = cfg["first_expert"], cfg["moe_intermediate_size"]
    out = jnp.zeros_like(u)

    @jax.checkpoint
    def one(u, weight, w_gate_up, w_down):
        hid = (jax.nn.silu(_ein("nd,df->nf", u, w_gate_up[:, :f], q))
               * _ein("nd,df->nf", u, w_gate_up[:, f:], q))
        return weight[:, None] * _ein("nf,fd->nd", hid, w_down, q)

    for e in range(cfg["num_experts"]):
        weight = jnp.sum(jnp.where(idx == first + e, p, 0.0), axis=-1)
        out = out + one(u, weight, lw["w_gate_up"][e], lw["w_down"][e])
    return out


def layer(x, lw, cfg, q=None):
    """x [B, T, D] -> (x'' [B, T, D], the layer's KL per row [B, T])."""
    b, t, d = x.shape
    eps = cfg["rms_norm_eps"]
    a, kl = attention_part(_rms(x, lw["g1"], eps), lw, cfg, q)
    x = x + a
    u = _rms(x, lw["g2"], eps).reshape(b * t, d)
    idx, p = route(u, lw["w_r"], cfg, q)
    return x + experts_part(u, idx, p, lw, cfg, q).reshape(b, t, d), kl


def hidden(w, ids, cfg, q=None):
    """ids [B, T] -> (final-RMSNorm hidden states [B, T, D], the index
    loss: mean over layers and tokens of the KL)."""
    x = w["embed"][ids]
    total = 0.0
    for i in range(cfg["num_hidden_layers"]):
        x, kl = jax.checkpoint(lambda x, lw: layer(x, lw, cfg, q))(
            x, layer_weights(w, i))
        total = total + jnp.mean(kl)
    return (_rms(x, w["g_f"], cfg["rms_norm_eps"]),
            total / cfg["num_hidden_layers"])


def loss(w, batch, cfg, q=None):
    """Step 6 for batch["input_ids"] [B, T]; the cross-entropy by blocks
    of tokens."""
    ids = batch["input_ids"]
    b, t = ids.shape
    h, index_loss = hidden(w, ids, cfg, q)
    h = h.reshape(b * t, -1)
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
    return total / (b * (t - 1)) + cfg["index_loss_weight"] * index_loss


def loss_and_grad(w, batch, cfg, q=None):
    """(loss, d loss / d w) of the whole batch in one pass."""
    return jax.value_and_grad(lambda w_: loss(w_, batch, cfg, q))(w)
