"""Plain reference for the `ouro-2.6b` configuration: one chip's share of
Ouro-2.6B (ByteDance, 2025-10; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741) trained by its first-stage objective,
in straightforward `jax.numpy`, float32, every matrix product at
`Precision.HIGHEST`, no kernel, no cache, no scan over passes: a Python
loop over `total_ut_steps` passes x `num_hidden_layers` layers that meets
the SAME weight arrays in every pass. It takes its weights from the seed
and nothing from the program.

T tokens, d = `hidden_size`, U = `total_ut_steps`, L layers;
RMS(z; g) = g * z / sqrt(mean(z^2) + eps), a PLAIN gain.

    x(0) = E[ids]
    for u = 1 .. U:   z = x(u-1)
        for l = 1 .. L, the same weights in every pass:
            z = z + RMS(Attn_l(RMS(z; g1_l)); g2_l)
            n = RMS(z; g3_l)
            z = z + RMS(W_down_l (silu(W_gate_l n) * (W_up_l n)); g4_l)
        x(u) = RMS(z; g_f)        # the final norm closes EVERY pass, and
                                  # its result enters the next pass
        logits(u) = x(u) W_head;  a(u) = x(u) . w_e + b_e
    Attn: q, k, v = h W_q, h W_k, h W_v in heads of `head_dim`, no bias, no
        q/k norm; rotary positions over the whole head (half-split pairs,
        theta 1e6); causal softmax(q k^T / sqrt(head_dim)) v; W_o

    lambda_i(u) = sigmoid(a_i(u));  p_i(1) = lambda_i(1),
    p_i(u) = lambda_i(u) prod_{j<u} (1 - lambda_i(j))  for 1 < u < U,
    p_i(U) = prod_{j<U} (1 - lambda_i(j))          (sum_u p_i(u) = 1)
    l_i(u) = CE(logits_i(u), id_{i+1})
    loss = mean_i [ sum_u p_i(u) l_i(u) - beta H(p_i) ],
    H(p) = - sum_u p(u) log p(u)

Everything is differentiated: the gate through p, every weight through all
U passes; the gradient of a shared weight is whatever autodiff gives the
one array.

The share (the file's `deployment`): `num_hidden_layers` of the 48 layers
(one stage of twelve, walked U times round) and `vocab_size` rows of the
embedding and of the head; every head and the whole feed-forward part.

Departures from the published description, each also under `assumed` in
the configuration file: the four norms' places, the final norm inside the
loop, the gate's form and input, the loss and beta, half-split rotary
pairs, no second-stage gate training and no early exit while training. To
fit beside the trainer, attention is computed by blocks of QUERY rows (each
row's scores and softmax are whole inside its block, so no number
changes), the per-token losses by blocks of tokens, and every layer
application is checkpointed (memory only, never a number).

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

#: a layer's tensors: name -> (shape from the sizes, kind); kinds: "w" a
#: matrix N(0, range); "g" a plain gain 1 + N(0, range)
_LAYER = {
    "g1": (("d",), "g"), "g2": (("d",), "g"), "g3": (("d",), "g"),
    "g4": (("d",), "g"),
    "w_q": (("d", "hq*hd"), "w"), "w_k": (("d", "hkv*hd"), "w"),
    "w_v": (("d", "hkv*hd"), "w"), "w_o": (("hq*hd", "d"), "w"),
    # W_gate = w_gate_up[:, :f], W_up = w_gate_up[:, f:]
    "w_gate_up": (("d", "2*f"), "w"), "w_down": (("f", "d"), "w")}


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
    z = {"v": cfg["vocab_size"], "d": cfg["hidden_size"],
         "n": cfg["num_hidden_layers"], "hq": cfg["num_attention_heads"],
         "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
         "f": cfg["intermediate_size"]}
    return dict(z, **{"hq*hd": z["hq"] * z["hd"],
                      "hkv*hd": z["hkv"] * z["hd"], "2*f": 2 * z["f"]})


def weight_shapes(cfg):
    """name -> (shape, kind); layer l's tensors are named "l/<name>"."""
    z = _dims(cfg)
    out = {"embed": ((z["v"], z["d"]), "w"), "head": ((z["d"], z["v"]), "w"),
           "g_f": ((z["d"],), "g"), "w_e": ((z["d"],), "w"),
           "b_e": ((1,), "w")}
    for i in range(z["n"]):
        for name, (shape, kind) in _LAYER.items():
            out["%d/%s" % (i, name)] = (tuple(z[k] for k in shape), kind)
    return out


def layer_weights(w, i):
    """Layer i's tensors under their plain names."""
    head = "%d/" % i
    return {k[len(head):]: v for k, v in w.items() if k.startswith(head)}


def init_weights(cfg, key):
    """Seeded weights, traced inside the caller's ONE jitted call: matrices,
    the gate's vector and its bias N(0, initializer_range), the embedding at
    a range of its own (the configuration's `assumed.weights`), gains
    1 + N(0, range), so that a path that drops a gain shows in `correct`."""
    std = cfg["initializer_range"]
    own = {"embed": cfg["embedding_initializer_range"]}
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(
            weight_shapes(cfg).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        x = x * own.get(name, std)
        out[name] = 1.0 + x if kind == "g" else x
    return out


def _norm(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps)) * g


def rotary(x, theta):
    """x [B, S, H, hd] at positions 0 .. S - 1, half-split pairs
    (i, i + hd/2) over the whole head."""
    hd = x.shape[-1]
    half = hd // 2
    freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention_part(h, lw, cfg, qc=None):
    """Attn before its norm and the residual: h [B, S, D] -> [B, S, D]."""
    z = _dims(cfg)
    b, s, _ = h.shape
    hq, hkv, hd = z["hq"], z["hkv"], z["hd"]
    grp = hq // hkv
    heads = lambda name, n: _ein("bsd,dk->bsk", h, lw[name], qc).reshape(
        b, s, n, hd)
    qh = rotary(heads("w_q", hq), cfg["rope_theta"])
    kh = rotary(heads("w_k", hkv), cfg["rope_theta"])
    vh = heads("w_v", hkv)
    qh = qh.reshape(b, s, hkv, grp, hd)     # query head i reads kv i // grp
    blk = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def block(q_blk, q_idx):
        sc = _ein("bqhgd,bkhd->bhgqk", q_blk, kh, qc) / jnp.sqrt(float(hd))
        keep = q_idx[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return _ein("bhgqk,bkhd->bqhgd", p, vh, qc)

    a = jax.lax.map(lambda args: block(*args), (
        qh.reshape((b, s // blk, blk) + qh.shape[2:]).swapaxes(0, 1),
        jnp.arange(s).reshape(s // blk, blk)))
    a = a.swapaxes(0, 1).reshape(b, s, hq * hd)
    return _ein("bsk,kd->bsd", a, lw["w_o"], qc)


def feed_forward_part(n, lw, qc=None):
    """W_down (silu(W_gate n) * (W_up n)): n [B, S, D] -> [B, S, D]."""
    f = lw["w_down"].shape[0]
    gate = _ein("bsd,df->bsf", n, lw["w_gate_up"][:, :f], qc)
    up = _ein("bsd,df->bsf", n, lw["w_gate_up"][:, f:], qc)
    return _ein("bsf,fd->bsd", jax.nn.silu(gate) * up, lw["w_down"], qc)


def layer(z, lw, cfg, qc=None):
    """One application of one layer: four norms, two inside the residual
    branches."""
    eps = cfg["rms_norm_eps"]
    z = z + _norm(attention_part(_norm(z, lw["g1"], eps), lw, cfg, qc),
                  lw["g2"], eps)
    return z + _norm(feed_forward_part(_norm(z, lw["g3"], eps), lw, qc),
                     lw["g4"], eps)


def one_pass(x, w, cfg, qc=None):
    """x(u-1) [B, S, D] -> x(u): the layers in order, then the final norm.
    Checkpointed whole, and every layer application inside it again: a
    pass keeps its input alone for the backward (memory only)."""
    @jax.checkpoint
    def run(x, w):
        for i in range(cfg["num_hidden_layers"]):
            x = jax.checkpoint(lambda x, lw: layer(x, lw, cfg, qc))(
                x, layer_weights(w, i))
        return _norm(x, w["g_f"], cfg["rms_norm_eps"])
    return run(x, w)


@jax.custom_vjp
def _hand_on(w):
    """The weights as they are, for the next pass; backwards, the sum so far
    of the later passes' gradient contributions as it is, behind a barrier:
    without it the compiler adds a shared weight's U contributions in ONE
    late fusion and holds all U of them, 0.8 GB a pass, until then. The
    same terms in the same order as plain autodiff (memory only)."""
    return w


_hand_on.defvjp(lambda w: (w, None),
                lambda _, g: (jax.lax.optimization_barrier(g),))


def passes(w, ids, cfg, qc=None):
    """ids [B, S] -> [x(1), ..., x(U)], each [B, S, D]: every pass's result
    after the final norm; the SAME arrays w in every pass."""
    x = w["embed"][ids]
    out = []
    for _ in range(cfg["total_ut_steps"]):
        x = one_pass(x, w, cfg, qc)
        out.append(x)
        w = _hand_on(w)
    return out


def logits(w, ids, cfg, qc=None):
    """[U, B, S, V]: every pass's logits, whole (for tests at small sizes)."""
    return jnp.stack([_ein("bsd,dv->bsv", x, w["head"], qc)
                      for x in passes(w, ids, cfg, qc)])


def token_losses(h, tgt, head, qc=None):
    """h [N, D], targets [N] -> the N cross-entropies, by blocks of tokens."""
    n = h.shape[0]
    blk = TOKEN_BLOCK if n > TOKEN_BLOCK else n
    pad = -n % blk
    h = jnp.pad(h, ((0, pad), (0, 0)))
    tgt = jnp.pad(tgt, (0, pad))

    @jax.checkpoint
    def block(args):
        h_blk, t_blk = args
        logp = jax.nn.log_softmax(_ein("nd,dv->nv", h_blk, head, qc), axis=-1)
        return -jnp.take_along_axis(logp, t_blk[:, None], axis=-1)[:, 0]

    out = jax.lax.map(block, (h.reshape(-1, blk, h.shape[-1]),
                              tgt.reshape(-1, blk)))
    return out.reshape(-1)[:n]


def read_pass(x, tgt, w, qc=None):
    """x(u) [B, S, D] -> (l(u) [N], a(u) [N]) over the N = B (S - 1)
    positions that predict a token; checkpointed (memory only)."""
    @jax.checkpoint
    def run(x, head, w_e, b_e):
        h = x[:, :-1].reshape(tgt.shape[0], -1)
        return (token_losses(h, tgt, head, qc),
                _ein("nd,d->n", h, w_e, qc) + b_e[0])
    return run(x, w["head"], w["w_e"], w["b_e"])


def exit_log_probabilities(a):
    """a [U - 1, N] gate scores -> log p [U, N]: log lambda(u) + the sum
    over j < u of log(1 - lambda(j)); the last pass takes what is left."""
    log_exit = jax.nn.log_sigmoid(a)
    log_stay = jax.nn.log_sigmoid(-a)
    rows, left = [], jnp.zeros_like(a[0])
    for u in range(a.shape[0]):
        rows.append(log_exit[u] + left)
        left = left + log_stay[u]
    return jnp.stack(rows + [left])


def loss_parts(w, batch, cfg, qc=None):
    """(loss, p [U, N], l [U, N]) over the N = B (S - 1) predicted tokens."""
    ids = batch["input_ids"]
    b, s = ids.shape
    tgt = ids[:, 1:].reshape(-1)
    ces, scores = zip(*[read_pass(x, tgt, w, qc)
                        for x in passes(w, ids, cfg, qc)])
    ce = jnp.stack(ces)
    log_p = exit_log_probabilities(jnp.stack(scores[:-1]))
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=0)
    per_token = jnp.sum(p * ce, axis=0) - cfg["exit_entropy_weight"] * entropy
    return jnp.mean(per_token), p, ce


def loss(w, batch, cfg, qc=None):
    return loss_parts(w, batch, cfg, qc)[0]


def loss_and_grad(w, batch, cfg, q=None):
    """(loss, d loss / d w) of the whole batch in one pass."""
    return jax.value_and_grad(lambda w_: loss(w_, batch, cfg, q))(w)
