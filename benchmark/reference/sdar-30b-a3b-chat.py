"""Plain reference for the `sdar-30b-a3b-chat` configuration: one chip's
share of SDAR-30B-A3B-Chat (JetLM; arXiv:2510.06303) TRAINED BY DIFFUSION
OVER BLOCKS (the objective of BD3-LM, arXiv:2503.09573, which SDAR adopts
without a logit shift), in straightforward `jax.numpy`, float32, every
matrix product at `Precision.HIGHEST`, no kernel, no cache. It takes its
weights and its noise from the seed and nothing from the program.

One sequence of T clean tokens x_0 in blocks of B (`block_length`),
blk(i) = i // B:

    noise   t_b ~ U[t_min, 1] a (sequence, block); each token of block b
            becomes `mask_token_id` independently with probability t_b;
            m_i = 1 where position i was masked (`noise_batch`)
    stream  [x_t ; x_0], 2T tokens; the token at index i of either half has
            position i (RoPE)

One layer, for every token of the stream, all projections without bias:

    1. h = RMSNorm(x; g1);  q = RMSNorm(h W_q; g_q), k = RMSNorm(h W_k; g_k)
       over the head width, v = h W_v;  RoPE on q, k at the token's position
    2. a = softmax(q k^T / sqrt(128) + M) v;  x' = x + a W_o, with M from
       four rules (0 where allowed, -inf elsewhere):
         noised query i, clean key j:   blk(j) <  blk(i)
         noised query i, noised key j:  blk(j) == blk(i)
         clean query i,  clean key j:   blk(j) <= blk(i)
         clean query,    noised key:    never
    3. u = RMSNorm(x'; g2);  r = u W_r (W_r: D x 128);  E = the 8 largest of
       r;  p = softmax over E;
       x'' = x' + sum_{e in E, e held} p_e W_down[e](silu(W_gate[e] u)
                                                    * (W_up[e] u))

    loss = 1 / (rows T) sum_i m_i / t_blk(i) CE(logits_i, x_0,i), logits =
           RMSNorm(x_L; g_f) W_head on the NOISED half only: the target of a
           masked position is its OWN clean token (no shift)

The share (the file's `deployment`): `num_attention_heads` query heads on
`num_key_value_heads` key-value heads, experts `first_expert ..
first_expert + num_experts - 1` of the router's `num_router_outputs`,
`vocab_size` rows of the embedding and of the head. Rows routed to experts
held elsewhere are left out, and that partial result goes on to the next
layer.

Departures from the published model, each also under `assumed` in the
configuration file: the block length, the noise schedule and the loss's
weight and normalisation (the config gives none); the mask token is the
last row of the vocabulary slice held here; the seeded weights draw the
embedding at range 1.0 and the routers at 0.16 and number each layer's
experts by the mask token's router score (`init_weights`), so that every
seed gives the held experts the same rows to serve. To fit beside the trainer,
attention is computed by blocks of QUERY rows (each row's scores and
softmax are whole inside its block, so no number changes), the experts one
after another as a dense masked sum, and the loss by token blocks.

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
    return {"v": cfg["vocab_size"], "d": cfg["hidden_size"],
            "n": cfg["num_hidden_layers"], "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "e_all": cfg["num_router_outputs"], "held": cfg["num_experts"],
            "f": cfg["moe_intermediate_size"]}


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
    """Seeded weights, traced inside the caller's ONE jitted call: matrices
    N(0, initializer_range), RMSNorm gains 1 + N(0, range), so that a path
    that drops a gain shows in `correct`; the embedding and the routers at
    ranges of their own and the experts numbered by `_number_experts`, so
    that every seed gives the held experts the same rows to serve (the
    configuration's `assumed.weights` says why)."""
    std = cfg["initializer_range"]
    own = {"embed": cfg["embedding_initializer_range"],
           "w_r": cfg["router_initializer_range"]}
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(
            weight_shapes(cfg).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        x = x * own.get(name.rsplit("/", 1)[-1], std)
        out[name] = 1.0 + x if kind == "g" else x
    return _number_experts(out, cfg)


def _number_experts(w, cfg):
    """Number each layer's experts by the MASK TOKEN's router score: its
    first choice, then the others from its last choice upwards (a
    permutation of the router's columns: which experts a chip holds is the
    deployment's choice, not the seed's). Every masked position, a quarter
    of the stream, enters a layer as nearly the same vector and goes to
    the same 8 experts as one lump; numbered so, the chip modelled here
    (`first_expert` 0) holds ONE of the 8 in every layer, for every seed,
    and its other experts are the mask token's last choices."""
    e = w["embed"][cfg["mask_token_id"]]
    out = dict(w)
    for i in range(cfg["num_hidden_layers"]):
        u = _rms(e, w["%d/g2" % i], cfg["rms_norm_eps"])
        order = jnp.argsort(jnp.dot(u, w["%d/w_r" % i], precision=HI))
        order = jnp.concatenate([order[-1:], order[:-1]])
        out["%d/w_r" % i] = w["%d/w_r" % i][:, order]
    return out


def noise_batch(ids, key, cfg):
    """ids [rows, T] (never the mask token) -> the batch of one step:
    input_ids (clean), noisy_ids, loss_weight = m / t float32. One key for
    the blocks' t, one for the tokens' draws."""
    b_len, t_min = cfg["block_length"], cfg["noise"]["t_min"]
    rows, t_len = ids.shape
    key_t, key_m = jax.random.split(key)
    t = jax.random.uniform(key_t, (rows, t_len // b_len), jnp.float32,
                           t_min, 1.0)
    draws = jax.random.uniform(key_m, (rows, t_len), jnp.float32)
    m = draws.reshape(rows, t_len // b_len, b_len) < t[:, :, None]
    weight = jnp.where(m, 1.0 / t[:, :, None], 0.0).reshape(rows, t_len)
    m = m.reshape(rows, t_len)
    return {"input_ids": ids,
            "noisy_ids": jnp.where(m, cfg["mask_token_id"], ids).astype(
                jnp.int32),
            "loss_weight": weight}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, theta, pos):
    """x [B, S, H, hd] at positions pos [S], half-split pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = pos.astype(jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def allowed(q_idx, k_idx, t_len, b_len):
    """M's support, [Q, K] bool, for stream indexes q_idx [Q], k_idx [K] of
    the stream [x_t ; x_0] of 2 t_len tokens: the four rules, one by one."""
    q_noised, k_noised = q_idx[:, None] < t_len, k_idx[None, :] < t_len
    q_blk = jnp.where(q_idx < t_len, q_idx, q_idx - t_len)[:, None] // b_len
    k_blk = jnp.where(k_idx < t_len, k_idx, k_idx - t_len)[None, :] // b_len
    rule1 = q_noised & ~k_noised & (k_blk < q_blk)
    rule2 = q_noised & k_noised & (k_blk == q_blk)
    rule3 = ~q_noised & ~k_noised & (k_blk <= q_blk)
    return rule1 | rule2 | rule3        # rule 4: a clean query, a noised key


def route(u, w_r, cfg, q=None):
    """u [N, D] -> (E [N, k] expert ids, p [N, k])."""
    s = _ein("nd,de->ne", u, w_r, q)
    top, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    return idx, jax.nn.softmax(top, axis=-1)


def attention_part(h, lw, cfg, q=None):
    """Steps 1-2 before the residual: h [B, 2T, D] -> [B, 2T, D]."""
    z = _dims(cfg)
    b, s, _ = h.shape
    t_len = s // 2
    hq, hkv, hd = z["hq"], z["hkv"], z["hd"]
    g = hq // hkv
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    pos = jnp.concatenate([jnp.arange(t_len), jnp.arange(t_len)])
    qh = _ein("bsd,dk->bsk", h, lw["w_q"], q).reshape(b, s, hq, hd)
    kh = _ein("bsd,dk->bsk", h, lw["w_k"], q).reshape(b, s, hkv, hd)
    vh = _ein("bsd,dk->bsk", h, lw["w_v"], q).reshape(b, s, hkv, hd)
    qh = _rope(_rms(qh, lw["g_q"], eps), theta, pos)
    kh = _rope(_rms(kh, lw["g_k"], eps), theta, pos)
    qh = qh.reshape(b, s, hkv, g, hd)       # query head i reads kv i // g
    blk = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def block(q_blk, q_idx):
        sc = _ein("bqhgd,bkhd->bhgqk", q_blk, kh, q) / jnp.sqrt(float(hd))
        keep = allowed(q_idx, jnp.arange(s), t_len, cfg["block_length"])
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return _ein("bhgqk,bkhd->bqhgd", p, vh, q)

    a = jax.lax.map(lambda args: block(*args), (
        qh.reshape((b, s // blk, blk) + qh.shape[2:]).swapaxes(0, 1),
        jnp.arange(s).reshape(s // blk, blk)))
    a = a.swapaxes(0, 1).reshape(b, s, hq * hd)
    return _ein("bsk,kd->bsd", a, lw["w_o"], q)


def experts_part(u, idx, p, lw, cfg, q=None):
    """The held experts' part of step 3: u [N, D], routing (idx, p)
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
    """x [B, 2T, D] -> x'' [B, 2T, D]."""
    b, s, d = x.shape
    eps = cfg["rms_norm_eps"]
    x = x + attention_part(_rms(x, lw["g1"], eps), lw, cfg, q)
    u = _rms(x, lw["g2"], eps).reshape(b * s, d)
    idx, p = route(u, lw["w_r"], cfg, q)
    return x + experts_part(u, idx, p, lw, cfg, q).reshape(b, s, d)


def hidden(w, stream, cfg, q=None):
    """stream ids [B, 2T] -> final-RMSNorm hidden states of the NOISED half
    [B, T, D]."""
    x = w["embed"][stream]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda x, lw: layer(x, lw, cfg, q))(
            x, layer_weights(w, i))
    return _rms(x[:, :stream.shape[1] // 2], w["g_f"], cfg["rms_norm_eps"])


def loss(w, batch, cfg, q=None):
    """The weighted cross-entropy of the masked positions, by blocks of
    tokens."""
    ids = batch["input_ids"]
    b, t = ids.shape
    h = hidden(w, jnp.concatenate([batch["noisy_ids"], ids], axis=1), cfg,
               q).reshape(b * t, -1)
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
        (h.reshape(-1, blk, h.shape[-1]), ids.reshape(-1, blk),
         batch["loss_weight"].astype(jnp.float32).reshape(-1, blk)))
    return total / (b * t)


def loss_and_grad(w, batch, cfg, q=None):
    """(loss, d loss / d w) of the whole batch in one pass."""
    return jax.value_and_grad(lambda w_: loss(w_, batch, cfg, q))(w)
