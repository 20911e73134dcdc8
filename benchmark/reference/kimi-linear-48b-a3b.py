"""Plain reference for the `kimi-linear-48b-a3b` configuration: one chip's
share of Kimi-Linear-48B-A3B-Instruct (moonshotai, 2025-10; `model_type`
kimi_linear, arXiv:2510.26692) trained by next-token prediction, in
straightforward `jax.numpy`, float32, every matrix product at
`Precision.HIGHEST`, no kernel, no chunk algebra, no cache: the KDA layers'
recurrence ONE TOKEN AT A TIME as the equation below states it. It takes its
weights from the seed and nothing from the program.

`linear_attn_config` counts layers from 1: a layer is Kimi Delta Attention
(`kda_layers`) or multi-head latent attention (`full_attn_layers`); the
first `first_k_dense_replace` layers end in a dense feed-forward part, the
others in experts. norm(x; g) = x / sqrt(mean(x^2) + 1e-5) * g, a plain gain.
All projections without bias; u = norm(x; g1):

KDA layer, H heads, dk = dv = 128 (`linear_attn_config.head_dim`):

    1. q, k, v = SiLU(conv(u W_q | u W_k | u W_v)), each [H, 128]: causal
       depthwise convolution over the sequence, kernel 4, no bias, a channel
       at a time: c_t = sum_{j=0..3} w[:, j] x_{t-3+j}, x zero before the
       sequence
    2. q_t <- q_t / sqrt(sum q_t^2 + 1e-6) 128^-1/2,
       k_t <- k_t / sqrt(sum k_t^2 + 1e-6), a head at a time
    3. a_t = -exp(A_log_h) softplus((u W_fa) W_fb + dt_bias)  [H, 128], the
       LOG decay, one a key channel, <= 0;  alpha_t = exp(a_t)
       beta_t = sigmoid(u W_b)  [H]
    4. a head at a time, S_0 = 0 in R^{128 x 128}:
       S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
       o_t = S_t^T q_t        (Diag(alpha_t) scales the ROWS of S)
    5. y_t = sigmoid((u W_ga) W_gb) * norm(o_t; g_n) over each head's 128
       (ONE gain [128] for all heads; the gate AFTER the norm)
       x' = x + concat(y) W_o

MLA layer (`q_lora_rank` null), H heads:

    q = u W_q -> [H, 192];  [c' | k_r] = u W_kva  (c' [512], k_r [64], W_kva
    WHOLE on every chip);  c = norm(c'; g_c);  [k_n | v] = c W_kvb ->
    [H, 128 + 128];  key of a head = [k_n | k_r], k_r ONE part all heads
    read; NO rotary turn on q's last 64 or on k_r (`mla_use_nope`);
    s[i, j] = q_i . key_j / sqrt(192), j <= i;  p = softmax;
    x' = x + concat_H(p v) W_o

Feed-forward part, w = norm(x'; g2):

    layer <= first_k_dense_replace:  out = x' + SwiGLU_9216(w)
    else:  z = w W_r (all 256), float32;  sc = sigmoid(z);
           E(t) = the 8 largest of sc + b  (one group: no group step);
           w_e = 2.446 sc_e / (sum over E(t) of sc + 1e-20)
           out = x' + sum over e in E(t), e held, of w_e SwiGLU_1024^e(w)
                    + SwiGLU_1024^shared(w)

    logits = norm(x_L; g_f) W_head over the held rows; loss = mean
    CE(logits_t, id_{t+1})

The share (the file's `deployment`): `linear_attn_config.num_heads` KDA
heads (every KDA quantity is a head's own; W_fa and W_ga are whole on every
chip), `num_attention_heads` MLA heads (W_kva and its norm whole), experts
`first_expert .. first_expert + num_experts - 1` of the router's
`num_router_outputs`, the shared expert whole, `vocab_size` rows of the
embedding and of the head. Rows routed to experts held elsewhere are left
out, and that partial result goes on to the next layer.

Departures from the published description, each also under `assumed` in the
configuration file: the low-rank gates' rank is `linear_attn_config.head_dim`
(no config key); the bias b is a constant of the run; seeded A_log, dt_bias
and convolution taps as published initialisations. To fit beside the
trainer, the recurrence runs in checkpointed blocks of tokens (still one
token at a time) and of heads, a KDA layer's stages (each of q, k, v through
its convolution, the decay, the rule, the way out) and a layer's two
sublayers are checkpointed apart, a gated unit runs a block of rows at a
time, MLA's scores a block of QUERY rows at a time (each row's
softmax is whole inside its block), the experts one after another as a dense
masked sum, layers under `jax.checkpoint`, and the head's loss by blocks of
rows: memory only, never a number.

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
TOKEN_BLOCK = 1024
SCAN_BLOCK = 128
HEAD_BLOCK = 8


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
    lin = cfg["linear_attn_config"]
    return {"v": cfg["vocab_size"], "d": cfg["hidden_size"],
            "n": cfg["num_hidden_layers"], "h": cfg["num_attention_heads"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "dc": cfg["kv_lora_rank"],
            "hk": lin["num_heads"], "dk": lin["head_dim"],
            "conv": lin["short_conv_kernel_size"],
            "rank": cfg["kda_gate_rank"],
            "e_all": cfg["num_router_outputs"], "held": cfg["num_experts"],
            "f": cfg["moe_intermediate_size"],
            "fs": cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
            "fd": cfg["intermediate_size"]}


def is_kda(cfg, i):
    """Layer i (from 0) is a KDA layer; else latent attention."""
    lin = cfg["linear_attn_config"]
    kda = i + 1 in lin["kda_layers"]
    if kda == (i + 1 in lin["full_attn_layers"]):
        raise ValueError("layer %d is in both or neither of kda_layers and "
                         "full_attn_layers" % (i + 1))
    return kda


def is_dense(cfg, i):
    """Layer i has a dense feed-forward part and no experts."""
    return i < cfg["first_k_dense_replace"]


def _layer_shapes(cfg, i):
    """name -> (shape, kind). Kinds: "w" a matrix N(0, range); "g" a plain
    gain 1 + N(0, range); "conv" the convolution's taps; "a_log" and
    "dt_bias" the decay's two."""
    z = _dims(cfg)
    d = z["d"]
    if is_kda(cfg, i):
        wide = z["hk"] * z["dk"]
        mixer = {
            "w_q": ((d, wide), "w"), "w_k": ((d, wide), "w"),
            "w_v": ((d, wide), "w"),
            # channels: q's, then k's, then v's
            "w_conv": ((3 * wide, z["conv"]), "conv"),
            "w_fa": ((d, z["rank"]), "w"), "w_fb": ((z["rank"], wide), "w"),
            "a_log": ((z["hk"],), "a_log"), "dt_bias": ((wide,), "dt_bias"),
            "w_b": ((d, z["hk"]), "w"),
            "w_ga": ((d, z["rank"]), "w"), "w_gb": ((z["rank"], wide), "w"),
            "g_n": ((z["dk"],), "g"), "w_o": ((wide, d), "w")}
    else:
        h = z["h"]
        mixer = {
            "g_c": ((z["dc"],), "g"),
            "w_q": ((d, h * (z["dn"] + z["dr"])), "w"),
            "w_kva": ((d, z["dc"] + z["dr"]), "w"),
            "w_kvb": ((z["dc"], h * (z["dn"] + z["dv"])), "w"),
            "w_o": ((h * z["dv"], d), "w")}
    out = dict(mixer, g1=((d,), "g"), g2=((d,), "g"))
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
    """Seeded weights, traced inside the caller's ONE jitted call: matrices
    N(0, initializer_range); the embedding, the routers and the routers'
    selection bias at ranges of their own (the configuration's
    `assumed.weights`); RMSNorm gains 1 + N(0, range), so that a path that
    drops a gain shows in `correct`; the convolution's taps U(-1/2, 1/2);
    A_log = log U(1, 16) a head, dt_bias = softplus^-1(dt) with dt
    log-uniform in [1e-3, 1e-1] a channel."""
    std = cfg["initializer_range"]
    own = {"embed": cfg["embedding_initializer_range"],
           "w_r": cfg["router_initializer_range"],
           "b_r": cfg["router_bias_initializer_range"]}
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
        elif kind == "conv":
            x = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
        else:
            x = jax.random.normal(k, shape, jnp.float32)
            x = x * own.get(name.rsplit("/", 1)[-1], std)
            x = 1.0 + x if kind == "g" else x
        out[name] = x
    return out


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def causal_conv(u, w):
    """u [B, S, C], w [C, K]: c_t = sum_j w[:, j] u_{t-(K-1)+j}."""
    kernel = w.shape[1]
    s = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (kernel - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * w[:, j] for j in range(kernel))


def log_decay(low, lw, cfg, qc=None):
    """Step 3's a_t: low = u W_fa [B, S, rank] -> [B, S, H, dk], <= 0."""
    z = _dims(cfg)
    b, s, _ = low.shape
    raw = _ein("bsr,rk->bsk", low, lw["w_fb"], qc) + lw["dt_bias"]
    return (-jnp.exp(lw["a_log"])[:, None]
            * jax.nn.softplus(raw.reshape(b, s, z["hk"], z["dk"])))


def token_update(state, q_t, k_t, v_t, a_t, beta_t, qc=None):
    """Step 4 for one token: state [B, H, dk, dv], q_t, k_t, a_t [B, H, dk],
    v_t [B, H, dv], beta_t [B, H] -> (S_t, o_t). The rows decay FIRST, then
    the delta reads what they hold."""
    state = state * jnp.exp(a_t)[..., None]
    held = _ein("bhkv,bhk->bhv", state, k_t, qc)
    state = state + k_t[..., None] * (
        (v_t - held) * beta_t[..., None])[..., None, :]
    return state, _ein("bhkv,bhk->bhv", state, q_t, qc)


def delta_rule(q, k, v, a, beta, qc=None):
    """Step 4, token by token: q, k, a [B, S, H, dk], v [B, S, H, dv], beta
    [B, S, H] -> o [B, S, H, dv]. Blocks of tokens are checkpointed (memory
    only)."""
    b, s, h, dk = k.shape

    def token(state, x):
        return token_update(state, *x, qc)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    blk = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((s // blk, blk) + x.shape[:1]
                                             + x.shape[2:])
               for x in (q, k, v, a, beta))
    _, o = jax.lax.scan(block, jnp.zeros((b, h, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)


def gated_norm(o, gate, g_n, eps):
    """Step 5 before W_o: the head-wise norm, THEN the sigmoid gate."""
    return jax.nn.sigmoid(gate) * _rms(o, g_n, eps)


def _conv_branch(u, w, taps, qc):
    """Step 1 for one of q, k, v: SiLU(conv(u W)) [B, S, H dk]."""
    return jax.nn.silu(causal_conv(_ein("bsd,dk->bsk", u, w, qc), taps))


def _rule(q, k, v, a, beta, qc):
    """Steps 2 and 4 from the convolution's q, k, v [B, S, H, dk], a block
    of heads at a time (memory only: a head's recurrence is its own)."""
    dk = q.shape[-1]
    unit = lambda x: x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)

    @jax.checkpoint
    def heads(xs):
        q, k, v, a, beta = xs
        return delta_rule(unit(q) * dk ** -0.5, unit(k), v, a, beta, qc)

    h = q.shape[2]
    if h <= HEAD_BLOCK or h % HEAD_BLOCK:
        return heads((q, k, v, a, beta))
    cut = lambda x: jnp.moveaxis(x.reshape(
        x.shape[:2] + (h // HEAD_BLOCK, HEAD_BLOCK) + x.shape[3:]), 2, 0)
    o = jnp.moveaxis(jax.lax.map(heads, tuple(
        cut(x) for x in (q, k, v, a, beta))), 0, 2)
    return o.reshape(v.shape)


def kda_part(u, lw, cfg, qc=None):
    """Steps 1-5 before the residual: u [B, S, D] -> [B, S, D]. Each stage
    is checkpointed (memory only): a stage's temporaries are gone before the
    next one's are made, forward and backward."""
    z = _dims(cfg)
    b, s, _ = u.shape
    hk, dk = z["hk"], z["dk"]
    wide = hk * dk
    # fresh functions a call: `jax.checkpoint` remembers a function's trace
    stage = lambda f: jax.checkpoint(lambda *args: f(*args, qc))
    q, k, v = (stage(_conv_branch)(
        u, lw[name], lw["w_conv"][i * wide:(i + 1) * wide]).reshape(
            b, s, hk, dk) for i, name in enumerate(("w_q", "w_k", "w_v")))
    a = stage(lambda u, lw, qc: log_decay(
        _ein("bsd,dk->bsk", u, lw["w_fa"], qc), lw, cfg, qc))(u, lw)
    beta = jax.nn.sigmoid(_ein("bsd,dk->bsk", u, lw["w_b"], qc))
    o = stage(_rule)(q, k, v, a, beta)

    def out(u, o, lw, qc):
        gate = _ein("bsr,rk->bsk", _ein("bsd,dk->bsk", u, lw["w_ga"], qc),
                    lw["w_gb"], qc)
        y = gated_norm(o, gate.reshape(b, s, hk, dk), lw["g_n"],
                       cfg["rms_norm_eps"])
        return _ein("bsk,kd->bsd", y.reshape(b, s, wide), lw["w_o"], qc)

    return stage(out)(u, o, lw)


def positions(x, cfg):
    """What the MLA layer does to q's last 64 and to k_r [B, S, heads, 64]:
    nothing (`mla_use_nope`)."""
    return x


def softmax_scale(cfg):
    return float(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5


def latent_part(u, lw, cfg, qc=None):
    """The MLA layer before the residual: u [B, S, D] -> [B, S, D]."""
    z = _dims(cfg)
    b, s, _ = u.shape
    h, dn, dc, eps = z["h"], z["dn"], z["dc"], cfg["rms_norm_eps"]
    qh = _ein("bsd,dk->bsk", u, lw["w_q"], qc).reshape(
        b, s, h, dn + z["dr"])
    down = _ein("bsd,dk->bsk", u, lw["w_kva"], qc)
    c = _rms(down[..., :dc], lw["g_c"], eps)
    kv = _ein("bsc,ck->bsk", c, lw["w_kvb"], qc).reshape(
        b, s, h, dn + z["dv"])
    q_n, q_r = qh[..., :dn], positions(qh[..., dn:], cfg)
    k_n, vh = kv[..., :dn], kv[..., dn:]
    k_r = positions(down[:, :, None, dc:], cfg)[:, :, 0]
    blk = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    scale = softmax_scale(cfg)

    @jax.checkpoint
    def block(qn_blk, qr_blk, q_pos):
        sc = (_ein("bqhd,bkhd->bhqk", qn_blk, k_n, qc)
              + _ein("bqhd,bkd->bhqk", qr_blk, k_r, qc)) * scale
        keep = q_pos[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(keep[None, None], sc, -jnp.inf), axis=-1)
        return _ein("bhqk,bkhd->bqhd", p, vh, qc)

    cut = lambda x: x.reshape((b, s // blk, blk) + x.shape[2:]).swapaxes(
        0, 1)                                                  # noqa: E731
    a = jax.lax.map(lambda args: block(*args), (
        cut(q_n), cut(q_r), jnp.arange(s).reshape(s // blk, blk)))
    a = a.swapaxes(0, 1).reshape(b, s, h * z["dv"])
    return _ein("bsk,kd->bsd", a, lw["w_o"], qc)


def route(u, w_r, b_r, cfg, qc=None):
    """u [N, D] -> (E [N, k] expert ids chosen by score + bias, w [N, k]
    from the unbiased scores, the scores [N, all])."""
    sc = jax.nn.sigmoid(_ein("nd,de->ne", u, w_r, qc))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(sc) + b_r,
                           cfg["num_experts_per_token"])
    top = jnp.take_along_axis(sc, idx, axis=-1)
    return idx, (cfg["routed_scaling_factor"] * top
                 / (top.sum(axis=-1, keepdims=True) + 1e-20)), sc


def _glu(u, w_gate_up, w_down, qc):
    """W_down (SiLU(u W_gate) * (u W_up)), a block of rows at a time."""
    f = w_down.shape[0]

    @jax.checkpoint
    def rows(u):
        hid = (jax.nn.silu(_ein("nd,df->nf", u, w_gate_up[:, :f], qc))
               * _ein("nd,df->nf", u, w_gate_up[:, f:], qc))
        return _ein("nf,fd->nd", hid, w_down, qc)

    n = u.shape[0]
    if n <= TOKEN_BLOCK or n % TOKEN_BLOCK:
        return rows(u)
    return jax.lax.map(rows, u.reshape(-1, TOKEN_BLOCK, u.shape[1])).reshape(
        n, -1)


def routed_part(u, idx, p, lw, cfg, qc=None):
    """The held experts' part: a dense masked sum, one expert at a time."""
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
    """The shared expert: one SwiGLU, no gate of its own, every row."""
    return _glu(u, lw["w_sgu"], lw["w_sd"], qc)


def feed_forward_part(u, lw, cfg, dense, qc=None):
    """The feed-forward part before the residual: u [N, D] -> [N, D]."""
    if dense:
        return _glu(u, lw["w_ffn_gate_up"], lw["w_ffn_down"], qc)
    idx, p, _ = route(u, lw["w_r"], lw["b_r"], cfg, qc)
    return routed_part(u, idx, p, lw, cfg, qc) + shared_part(u, lw, qc)


def layer(x, lw, cfg, kda, dense, qc=None):
    """The two sublayers are checkpointed apart (memory only)."""
    b, s, d = x.shape
    eps = cfg["rms_norm_eps"]
    mixer = kda_part if kda else latent_part
    x = x + jax.checkpoint(lambda x, lw: mixer(
        _rms(x, lw["g1"], eps), lw, cfg, qc))(x, lw)
    return x + jax.checkpoint(lambda x, lw: feed_forward_part(
        _rms(x, lw["g2"], eps).reshape(b * s, d), lw, cfg, dense, qc))(
            x, lw).reshape(b, s, d)


def hidden(w, ids, cfg, qc=None):
    """ids [B, S] -> final-RMSNorm hidden states [B, S, D]."""
    x = w["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda x, lw, kda=is_kda(cfg, i), dense=is_dense(cfg, i): layer(
                x, lw, cfg, kda, dense, qc))(x, layer_weights(w, i))
    return _rms(x, w["g_f"], cfg["rms_norm_eps"])


def layer_counts(w, ids, cfg, chunk):
    """What the program's counters hold after one step, per layer: rows the
    held experts serve, (token, slot) choices the bias changed, the sum of
    the chosen weights (0 for the dense layer); and the most negative sum
    of a_t over a chunk of `chunk` tokens, a channel and head (0 for an MLA
    layer)."""
    b, s = ids.shape
    k, eps = cfg["num_experts_per_token"], cfg["rms_norm_eps"]
    first, held = cfg["first_expert"], cfg["num_experts"]
    x = w["embed"][ids]
    out = {"rows_held": [], "route_bias_flips": [], "route_weight_sum": [],
           "kda_chunk_log_decay_min": []}
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_weights(w, i)
        kda, dense = is_kda(cfg, i), is_dense(cfg, i)
        u = _rms(x, lw["g1"], eps)
        low = jnp.zeros(())
        if kda:
            a = log_decay(_ein("bsd,dk->bsk", u, lw["w_fa"], None), lw, cfg)
            a = jnp.pad(a, ((0, 0), (0, -s % chunk), (0, 0), (0, 0)))
            low = jnp.min(jnp.sum(a.reshape((b, -1, chunk) + a.shape[2:]),
                                  axis=2))
        out["kda_chunk_log_decay_min"].append(low)
        if dense:
            for name in ("rows_held", "route_bias_flips",
                         "route_weight_sum"):
                out[name].append(jnp.zeros(()))
        else:
            mixed = x + (kda_part if kda else latent_part)(u, lw, cfg)
            idx, p, sc = route(_rms(mixed, lw["g2"], eps).reshape(b * s, -1),
                               lw["w_r"], lw["b_r"], cfg)
            plain = jax.lax.top_k(sc, k)[1]
            kept = (idx[:, :, None] == plain[:, None, :]).any(-1)
            out["rows_held"].append(jnp.sum(jnp.logical_and(
                idx >= first, idx < first + held)).astype(jnp.float32))
            out["route_bias_flips"].append(
                jnp.sum(jnp.logical_not(kept)).astype(jnp.float32))
            out["route_weight_sum"].append(p.sum())
        x = layer(x, lw, cfg, kda, dense)
    return {n: jnp.stack(v) for n, v in out.items()}


def loss(w, batch, cfg, qc=None):
    """Next-token cross-entropy, mean over the B (S - 1) predicted tokens,
    by blocks of rows."""
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
