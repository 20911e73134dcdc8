"""Plain reference for the `gpt2-small` configuration: GPT-2 as published
(Radford et al. 2019; openai/gpt-2 `src/model.py`), in straightforward
`jax.numpy`, float32, every matrix product at `Precision.HIGHEST`, no
kernels, no cache, no batching tricks. It takes its weights from the seed
and nothing from the program.

Departures from the published model, each also in the config file's
`assumed`: none in the mathematics; `layer_norm_epsilon` is read from the
config file (see its `reduced`).

`q="int8"` is the CONTROL, not a feature: both operands of every matrix
product are rounded to 8-bit integers with one scale per tensor
(absmax / 127) before they are multiplied. It is the precision step below
the configuration's bf16 compute, and `correct` has to refuse it.
"""

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _fake_int8(x):
    s = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    return jnp.round(x / s) * s


def _mm(a, b, q):
    if q == "int8":
        a, b = _fake_int8(a), _fake_int8(b)
    elif q is not None:
        raise ValueError("unknown control precision %r" % (q,))
    return jnp.matmul(a, b, precision=HI)


def _dims(cfg):
    d = cfg["n_embd"]
    return (cfg["vocab_size"], d, cfg["n_layer"], cfg["n_positions"],
            cfg.get("n_inner") or 4 * d, cfg["n_head"])


def weight_shapes(cfg):
    """name -> (shape, kind); per-layer tensors are stacked on axis 0."""
    v, d, n, t, f, _ = _dims(cfg)
    return {
        "wte": ((v, d), "w"), "wpe": ((t, d), "w"),
        "ln_1_g": ((n, d), "g"), "ln_1_b": ((n, d), "b"),
        "attn_w": ((n, d, 3 * d), "w"), "attn_b": ((n, 3 * d), "b"),
        "proj_w": ((n, d, d), "w"), "proj_b": ((n, d), "b"),
        "ln_2_g": ((n, d), "g"), "ln_2_b": ((n, d), "b"),
        "fc_w": ((n, d, f), "w"), "fc_b": ((n, f), "b"),
        "out_w": ((n, f, d), "w"), "out_b": ((n, d), "b"),
        "ln_f_g": ((d,), "g"), "ln_f_b": ((d,), "b"),
    }


def init_weights(cfg, key):
    """Seeded weights, traced inside the caller's ONE jitted call.
    Matrices and biases are N(0, initializer_range) as GPT-2 initialises
    its matrices; gains are 1 + N(0, range). Biases and gains are random
    too, so that a path that drops one shows in `correct`."""
    std = cfg["initializer_range"]
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(
            weight_shapes(cfg).items())):
        x = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        out[name] = 1.0 + x if kind == "g" else x
    return out


def _ln(x, g, b, eps):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * g + b


def _gelu(x):  # GPT-2's tanh form ("gelu_new")
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def hidden(w, ids, cfg, q=None):
    """ids [B, T] -> final-LayerNorm hidden states [B, T, D]."""
    _, d, n, _, _, heads = _dims(cfg)
    eps = cfg["layer_norm_epsilon"]
    b, t = ids.shape
    hd = d // heads
    x = w["wte"][ids] + w["wpe"][jnp.arange(t)][None]
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def layer(x, lw):
        h = _ln(x, lw["ln_1_g"], lw["ln_1_b"], eps)
        qkv = _mm(h, lw["attn_w"], q) + lw["attn_b"]
        qh, kh, vh = [a.reshape(b, t, heads, hd).transpose(0, 2, 1, 3)
                      for a in jnp.split(qkv, 3, axis=-1)]
        s = _mm(qh, kh.transpose(0, 1, 3, 2), q) / jnp.sqrt(float(hd))
        s = jnp.where(causal, s, -jnp.inf)
        a = _mm(jax.nn.softmax(s, axis=-1), vh, q)
        a = a.transpose(0, 2, 1, 3).reshape(b, t, d)
        x = x + _mm(a, lw["proj_w"], q) + lw["proj_b"]
        h = _ln(x, lw["ln_2_g"], lw["ln_2_b"], eps)
        h = _gelu(_mm(h, lw["fc_w"], q) + lw["fc_b"])
        return x + _mm(h, lw["out_w"], q) + lw["out_b"], None

    per_layer = {k: v for k, v in w.items()
                 if k not in ("wte", "wpe", "ln_f_g", "ln_f_b")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    return _ln(x, w["ln_f_g"], w["ln_f_b"], eps)


def logits(w, h, q=None):
    """Tied output head: hidden [..., D] -> logits [..., V]."""
    return _mm(h, w["wte"].T, q)


def loss(w, batch, cfg, q=None):
    """Mean next-token cross-entropy of batch["input_ids"] [B, T]."""
    ids = batch["input_ids"]
    lg = logits(w, hidden(w, ids, cfg, q), q)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    tgt = ids[:, 1:]
    return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))


#: sequences per reference gradient call: per-sequence losses are
#: independent, so the batch is computed in blocks of this many rows
MICROBATCH_ROWS = 2


def loss_and_grad(w, batch, cfg, q=None):
    """(loss, d loss / d w) of the whole batch, computed in blocks."""
    ids = batch["input_ids"]
    rows = ids.shape[0]
    mb = MICROBATCH_ROWS if rows % MICROBATCH_ROWS == 0 else 1
    blocks = ids.reshape(rows // mb, mb, ids.shape[1])
    fn = jax.value_and_grad(
        lambda w_, ids_: loss(w_, {"input_ids": ids_}, cfg, q))

    def body(acc, ids_):
        l, g = fn(w, ids_)
        return jax.tree_util.tree_map(jnp.add, acc, (l, g)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, w))
    (l, g), _ = jax.lax.scan(body, zero, blocks)
    k = float(rows // mb)
    return l / k, jax.tree_util.tree_map(lambda x: x / k, g)


def greedy(w, prompts, lens, n_new, cfg, q=None):
    """Greedy continuation by whole-sequence recomputation (no cache):
    prompts [B, T] padded, lens [B]; returns tokens [B, n_new]. Causal
    masking makes the padding beyond a row's length harmless."""
    rows = jnp.arange(prompts.shape[0])

    def step(carry, i):
        ids = carry
        h = hidden(w, ids, cfg, q)
        nxt = jnp.argmax(logits(w, h[rows, lens + i - 1], q), -1)
        ids = ids.at[rows, lens + i].set(nxt.astype(ids.dtype))
        return ids, nxt

    _, toks = jax.lax.scan(step, prompts, jnp.arange(n_new))
    return toks.T


def forced_logits(w, ids, positions, cfg, q=None):
    """Teacher-forced logits: ids [B, T] (prompt then the tokens that
    were actually emitted), positions [B, K] -> [B, K, V]: the logits
    that choose the token AFTER each listed position."""
    h = hidden(w, ids, cfg, q)
    picked = jnp.take_along_axis(h, positions[..., None], axis=1)
    return logits(w, picked, q)
