"""Plain reference for the `qwen3-next-80b-a3b` configuration: one chip's
share of Qwen3-Next-80B-A3B-Instruct (Qwen, 2025-09) trained by next-token
prediction, in straightforward `jax.numpy`, float32, every matrix product
at `Precision.HIGHEST`, no kernel, no cache, the linear-attention layers'
recurrence TOKEN BY TOKEN. It takes its weights from the seed and nothing
from the program.

Layers come in periods of `full_attention_interval` (4): layers 4i .. 4i+2
are LINEAR-attention layers (the gated delta rule, arXiv:2412.06464), layer
4i+3 a gated FULL-attention layer; every layer ends in the same expert part.
norm(x; g) = x / sqrt(mean(x^2) + eps) * (1 + g): the gain is zero-centred.

Linear-attention layer, Hk key heads, Hv = 2 Hk value heads, dk = dv = 128,
all projections without bias, h = norm(x; g1):

    1. q, k = h W_q, h W_k  [Hk x dk];  v, z = h W_v, h W_z  [Hv x dv];
       b, a = h W_b, h W_a  [Hv]
    2. (q | k | v) <- SiLU(conv(q | k | v)): causal depthwise convolution
       over the sequence, kernel 4, no bias, a channel at a time:
       c_t = sum_{j=0..3} w[:, j] u_{t-3+j}, u zero before the sequence
    3. beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias),
       alpha_t = exp(g_t) in (0, 1]   (one a value head)
    4. q_t <- q_t / sqrt(sum q_t^2 + 1e-6) dk^-1/2,
       k_t <- k_t / sqrt(sum k_t^2 + 1e-6), a head at a time; key head j
       serves value heads 2j and 2j + 1
    5. a value head at a time, S_0 = 0 in R^{dk x dv}:
       S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,
       o_t = S_t^T q_t
    6. y_t = o_t / sqrt(mean o_t^2 + eps) * w_n * SiLU(z_t) over dv, a head
       at a time (PLAIN gain w_n);  x' = x + concat(y) W_o

Full-attention layer, h = norm(x; g1):

    [q | gate] = h W_q, a head at a time (256 of query, 256 of gate);
    k, v = h W_k, h W_v;  q = norm(q; g_q), k = norm(k; g_k) over the 256;
    rotary positions on the FIRST 64 of the 256 (half-split inside those
    64, theta 1e7), the other 192 untouched; causal softmax attention,
    scale 256^-1/2; a <- a * sigmoid(gate);  x' = x + a W_o

Expert part of every layer, u = norm(x'; g2):

    p = softmax(u W_r) over all 512, the 10 largest, renormalised;
    routed = sum over the chosen experts HELD here of
             p_e W_down,e (SiLU(u W_gate,e) * u W_up,e)
    shared = sigmoid(u . w_sg) W_down,s (SiLU(u W_gate,s) * u W_up,s)
    x'' = x' + routed + shared

    logits = norm(x_L; g_f) W_head;  loss = mean CE(logits_t, id_{t+1})

The share (the file's `deployment`): `linear_num_key_heads` key heads with
their value heads, `num_attention_heads` query heads on
`num_key_value_heads` key-value heads, experts `first_expert ..
first_expert + num_experts - 1` of the router's `num_router_outputs`, the
shared expert whole, `vocab_size` rows of the embedding and of the head.
Rows routed to experts held elsewhere are left out, and that partial result
goes on to the next layer.

Departures from the published description, each also under `assumed` in
the configuration file: the multi-token-prediction module is absent; the
linear layer's parts are separate tensors (the published `in_proj_qkvz`
groups the same columns by key head); seeded A_log and dt_bias. To fit
beside the trainer, attention is computed by blocks of QUERY rows (each
row's scores and softmax are whole inside its block, so no number
changes), the recurrence in checkpointed blocks of tokens (still one token
at a time), the experts one after another as a dense masked sum, and the
loss by token blocks.

`q="int8"` is the CONTROL, not a feature: both operands of every matrix
product are rounded to 8-bit integers with one scale per tensor
(absmax / 127) before they are multiplied. `correct` has to refuse it.
"""

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST

#: rows per block of the blockwise parts (memory only, never a number)
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
SCAN_BLOCK = 128


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
            "hk": cfg["linear_num_key_heads"],
            "hv": cfg["linear_num_value_heads"],
            "dk": cfg["linear_key_head_dim"],
            "dv": cfg["linear_value_head_dim"],
            "conv": cfg["linear_conv_kernel_dim"],
            "e_all": cfg["num_router_outputs"], "held": cfg["num_experts"],
            "f": cfg["moe_intermediate_size"],
            "fs": cfg["shared_expert_intermediate_size"]}


def is_linear(cfg, i):
    """Layers 4i .. 4i+2 are linear-attention layers, 4i+3 a full one."""
    return (i + 1) % cfg["full_attention_interval"] != 0


def _layer_shapes(cfg, i):
    """name -> (shape, kind). Kinds: "w" a matrix N(0, range); "g0" a
    zero-centred gain N(0, range), applied as 1 + g; "g1" a plain gain
    1 + N(0, range); "a_log" and "dt_bias" the decay's two."""
    z = _dims(cfg)
    d, f, fs = z["d"], z["f"], z["fs"]
    experts = {
        "g2": ((d,), "g0"), "w_r": ((d, z["e_all"]), "w"),
        # W_gate[e] = w_gate_up[e][:, :f], W_up[e] = w_gate_up[e][:, f:]
        "w_gate_up": ((z["held"], d, 2 * f), "w"),
        "w_down": ((z["held"], f, d), "w"),
        "w_sgu": ((d, 2 * fs), "w"), "w_sd": ((fs, d), "w"),
        "w_sg": ((d,), "w")}
    if is_linear(cfg, i):
        hk, hv, dk, dv = z["hk"], z["hv"], z["dk"], z["dv"]
        mixer = {
            "w_q": ((d, hk * dk), "w"), "w_k": ((d, hk * dk), "w"),
            "w_v": ((d, hv * dv), "w"), "w_z": ((d, hv * dv), "w"),
            "w_b": ((d, hv), "w"), "w_a": ((d, hv), "w"),
            # channels: q's, then k's, then v's
            "w_conv": ((2 * hk * dk + hv * dv, z["conv"]), "w"),
            "a_log": ((hv,), "a_log"), "dt_bias": ((hv,), "dt_bias"),
            "g_n": ((dv,), "g1"), "w_o": ((hv * dv, d), "w")}
    else:
        hq, hkv, hd = z["hq"], z["hkv"], z["hd"]
        mixer = {
            # a head at a time: hd of query, then hd of gate
            "w_q": ((d, hq * 2 * hd), "w"),
            "w_k": ((d, hkv * hd), "w"), "w_v": ((d, hkv * hd), "w"),
            "g_q": ((hd,), "g0"), "g_k": ((hd,), "g0"),
            "w_o": ((hq * hd, d), "w")}
    return dict(mixer, g1=((d,), "g0"), **experts)


def weight_shapes(cfg):
    """name -> (shape, kind); layer l's tensors are named "l/<name>"."""
    z = _dims(cfg)
    out = {"embed": ((z["v"], z["d"]), "w"), "head": ((z["d"], z["v"]), "w"),
           "g_f": ((z["d"],), "g0")}
    for i in range(z["n"]):
        for name, spec in _layer_shapes(cfg, i).items():
            out["%d/%s" % (i, name)] = spec
    return out


def layer_weights(w, i):
    """Layer i's tensors under their plain names."""
    head = "%d/" % i
    return {k[len(head):]: v for k, v in w.items() if k.startswith(head)}


def init_weights(cfg, key):
    """Seeded weights, traced inside the caller's ONE jitted call: matrices
    N(0, initializer_range), the embedding and the routers at ranges of
    their own (the configuration's `assumed.weights`); zero-centred gains
    N(0, range) and the plain one 1 + N(0, range), so that a path that
    drops a gain shows in `correct`; A_log = log U(1, 16), dt_bias =
    softplus^-1(dt) with dt log-uniform in [1e-3, 1e-1]."""
    std = cfg["initializer_range"]
    own = {"embed": cfg["embedding_initializer_range"],
           "w_r": cfg["router_initializer_range"]}
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(
            weight_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if kind == "a_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            x = jnp.log(jnp.expm1(dt))
        else:
            x = jax.random.normal(k, shape, jnp.float32)
            x = x * own.get(name.rsplit("/", 1)[-1], std)
            x = 1.0 + x if kind == "g1" else x
        out[name] = x
    return out


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def _norm(x, g, eps):
    """RMSNorm with a zero-centred gain."""
    return _rms(x, eps) * (1.0 + g)


def rotary(x, cfg):
    """x [B, S, H, hd] at positions 0 .. S - 1: the FIRST `partial_rotary_
    factor` x hd of the head turned, half-split pairs (i, i + rd/2) inside
    them, the rest untouched."""
    rd = int(cfg["partial_rotary_factor"] * x.shape[-1])
    half = rd // 2
    freq = float(cfg["rope_theta"]) ** (
        -jnp.arange(half, dtype=jnp.float32) * 2.0 / rd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b, rest = x[..., :half], x[..., half:rd], x[..., rd:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def causal_conv(u, w):
    """u [B, S, C], w [C, K]: c_t = sum_j w[:, j] u_{t-(K-1)+j}."""
    kernel = w.shape[1]
    s = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (kernel - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * w[:, j] for j in range(kernel))


def delta_rule(q, k, v, g, beta, qc=None):
    """Step 5, token by token: q, k [B, S, Hv, dk] (each key head already
    repeated for its value heads), v [B, S, Hv, dv], g, beta [B, S, Hv] ->
    o [B, S, Hv, dv]. Blocks of tokens are checkpointed (memory only)."""
    b, s, hv, dk = k.shape

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        held = _ein("bhkv,bhk->bhv", state, k_t, qc)
        state = state + k_t[..., None] * (
            (v_t - held) * beta_t[..., None])[..., None, :]
        return state, _ein("bhkv,bhk->bhv", state, q_t, qc)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    blk = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((s // blk, blk) + x.shape[:1]
                                             + x.shape[2:])
               for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((b, hv, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)


def linear_attention_part(h, lw, cfg, qc=None):
    """Steps 1-6 before the residual: h [B, S, D] -> [B, S, D]."""
    z = _dims(cfg)
    b, s, _ = h.shape
    hk, hv, dk, dv = z["hk"], z["hv"], z["dk"], z["dv"]
    proj = lambda name: _ein("bsd,dk->bsk", h, lw[name], qc)
    mixed = jax.nn.silu(causal_conv(
        jnp.concatenate([proj("w_q"), proj("w_k"), proj("w_v")], axis=-1),
        lw["w_conv"]))
    q = mixed[..., :hk * dk].reshape(b, s, hk, dk)
    k = mixed[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
    v = mixed[..., 2 * hk * dk:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(proj("w_b"))
    g = -jnp.exp(lw["a_log"]) * jax.nn.softplus(proj("w_a") + lw["dt_bias"])
    unit = lambda x: x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)
    q, k = (jnp.repeat(x, hv // hk, axis=2)
            for x in (unit(q) * dk ** -0.5, unit(k)))
    o = delta_rule(q, k, v, g, beta, qc)
    y = (_rms(o, cfg["rms_norm_eps"]) * lw["g_n"]
         * jax.nn.silu(proj("w_z").reshape(b, s, hv, dv)))
    return _ein("bsk,kd->bsd", y.reshape(b, s, hv * dv), lw["w_o"], qc)


def output_gate(a, gate):
    return a * jax.nn.sigmoid(gate)


def full_attention_part(h, lw, cfg, qc=None):
    """The gated full-attention layer before the residual."""
    z = _dims(cfg)
    b, s, _ = h.shape
    hq, hkv, hd = z["hq"], z["hkv"], z["hd"]
    grp = hq // hkv
    eps = cfg["rms_norm_eps"]
    qg = _ein("bsd,dk->bsk", h, lw["w_q"], qc).reshape(b, s, hq, 2 * hd)
    qh, gate = qg[..., :hd], qg[..., hd:]
    kh = _ein("bsd,dk->bsk", h, lw["w_k"], qc).reshape(b, s, hkv, hd)
    vh = _ein("bsd,dk->bsk", h, lw["w_v"], qc).reshape(b, s, hkv, hd)
    qh = rotary(_norm(qh, lw["g_q"], eps), cfg)
    kh = rotary(_norm(kh, lw["g_k"], eps), cfg)
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
    a = output_gate(a.swapaxes(0, 1).reshape(b, s, hq, hd), gate)
    return _ein("bsk,kd->bsd", a.reshape(b, s, hq * hd), lw["w_o"], qc)


def route(u, w_r, cfg, qc=None):
    """u [N, D] -> (E [N, k] expert ids, p [N, k])."""
    s = _ein("nd,de->ne", u, w_r, qc)
    top, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    return idx, jax.nn.softmax(top, axis=-1)


def _glu(u, w_gate_up, w_down, qc):
    f = w_down.shape[0]
    hid = (jax.nn.silu(_ein("nd,df->nf", u, w_gate_up[:, :f], qc))
           * _ein("nd,df->nf", u, w_gate_up[:, f:], qc))
    return _ein("nf,fd->nd", hid, w_down, qc)


def routed_part(u, idx, p, lw, cfg, qc=None):
    """The held experts' part: u [N, D], routing (idx, p) [N, k] -> [N, D];
    a dense masked sum, one expert at a time."""
    first = cfg["first_expert"]
    out = jnp.zeros_like(u)

    @jax.checkpoint
    def one(u, weight, w_gate_up, w_down):
        return weight[:, None] * _glu(u, w_gate_up, w_down, qc)

    for e in range(cfg["num_experts"]):
        weight = jnp.sum(jnp.where(idx == first + e, p, 0.0), axis=-1)
        out = out + one(u, weight, lw["w_gate_up"][e], lw["w_down"][e])
    return out


def shared_part(u, lw, qc=None):
    """The shared expert, every row, under its sigmoid gate."""
    gate = jax.nn.sigmoid(_ein("nd,d->n", u, lw["w_sg"], qc))
    return gate[:, None] * _glu(u, lw["w_sgu"], lw["w_sd"], qc)


def layer(x, lw, cfg, linear, qc=None):
    """x [B, S, D] -> x'' [B, S, D]."""
    b, s, d = x.shape
    eps = cfg["rms_norm_eps"]
    mixer = linear_attention_part if linear else full_attention_part
    x = x + mixer(_norm(x, lw["g1"], eps), lw, cfg, qc)
    u = _norm(x, lw["g2"], eps).reshape(b * s, d)
    idx, p = route(u, lw["w_r"], cfg, qc)
    out = routed_part(u, idx, p, lw, cfg, qc) + shared_part(u, lw, qc)
    return x + out.reshape(b, s, d)


def hidden(w, ids, cfg, qc=None):
    """ids [B, S] -> final-norm hidden states [B, S, D]."""
    x = w["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        linear = is_linear(cfg, i)
        x = jax.checkpoint(
            lambda x, lw, linear=linear: layer(x, lw, cfg, linear, qc))(
                x, layer_weights(w, i))
    return _norm(x, w["g_f"], cfg["rms_norm_eps"])


def loss(w, batch, cfg, qc=None):
    """Next-token cross-entropy, mean over the B (S - 1) predicted tokens,
    by blocks of tokens."""
    ids = batch["input_ids"]
    b, s = ids.shape
    h = hidden(w, ids, cfg, qc)[:, :-1].reshape(b * (s - 1), -1)
    tgt = ids[:, 1:].reshape(-1)
    n = h.shape[0]
    blk = TOKEN_BLOCK if n > TOKEN_BLOCK else n
    pad = -n % blk
    h = jnp.pad(h, ((0, pad), (0, 0)))
    tgt = jnp.pad(tgt, (0, pad))
    live = (jnp.arange(n + pad) < n).astype(jnp.float32)

    @jax.checkpoint
    def block(total, args):
        h_blk, t_blk, on = args
        lg = _ein("nd,dv->nv", h_blk, w["head"], qc)
        logp = jax.nn.log_softmax(lg, axis=-1)
        picked = jnp.take_along_axis(logp, t_blk[:, None], axis=-1)[:, 0]
        return total - jnp.sum(picked * on), None

    total, _ = jax.lax.scan(
        block, jnp.zeros((), jnp.float32),
        (h.reshape(-1, blk, h.shape[-1]), tgt.reshape(-1, blk),
         live.reshape(-1, blk)))
    return total / n


def loss_and_grad(w, batch, cfg, q=None):
    """(loss, d loss / d w) of the whole batch in one pass."""
    return jax.value_and_grad(lambda w_: loss(w_, batch, cfg, q))(w)
