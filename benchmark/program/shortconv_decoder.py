"""How a `family: shortconv_decoder` configuration — a decoder whose layers
are a GATED SHORT CONVOLUTION (one in-projection to three streams, C *
conv(B * x) with a causal depthwise convolution of a few taps, an
out-projection) or grouped-query softmax attention behind q/k RMSNorm and a
rotary turn, in the order the configuration's `layer_types` gives, the
leading `num_dense_layers` over a dense SwiGLU and the others over
sigmoid-routed experts with no shared one, the head TIED to the embedding —
is handed to the program under test: `edl_tpu/models/sparse_decoder.py` for
the model, its loss and its routing and convolution counters (the trainer's
extra state), the reference's seeded weights relabelled into the program's
parameter tree. Nothing here computes a number that `correct` compares; the
counts below are what the utilization and roofline metrics divide by."""

import jax
import jax.numpy as jnp

from benchmark.lib.harness import BenchError, load_module

_sparse = load_module("program", "sparse_decoder")
band_pairs, make_batch = _sparse.band_pairs, _sparse.make_batch


def _program():
    from edl_tpu.models import sparse_decoder
    if not hasattr(sparse_decoder, "SHORTCONV_COUNTERS"):
        raise BenchError("this program's decoder has no gated short "
                         "convolution and no tied head")
    return sparse_decoder


def conv_layers(cfg):
    """Per layer: 1 = gated short convolution, 0 = attention."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if len(kinds) != cfg["num_hidden_layers"] or any(
            k not in ("conv", "full_attention") for k in kinds):
        raise BenchError("layer_types %r for %d layers"
                         % (cfg["layer_types"], cfg["num_hidden_layers"]))
    return tuple(int(k == "conv") for k in kinds)


def dense_layers(cfg):
    """Per layer: 1 = a dense feed-forward part (the leading
    `num_dense_layers`), 0 = routed experts."""
    return tuple(int(i < cfg["num_dense_layers"])
                 for i in range(cfg["num_hidden_layers"]))


def build_model(cfg, job):
    sparse_decoder = _program()
    n = cfg["num_hidden_layers"]
    return sparse_decoder.SparseDecoder(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_layers=n, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["num_router_outputs"],
        experts_held=cfg["num_experts"], first_expert=cfg["first_expert"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        rope_layout=(1,) * n, window_layout=(0,) * n, window=0,
        rope_theta=float(cfg["rope_theta"]), eps=cfg["norm_eps"],
        dtype=jnp.bfloat16, remat=bool(job.get("remat", False)),
        use_flash=None, router_input="moe_norm", expert_activation="silu",
        qk_norm=True, dense_width=cfg["intermediate_size"],
        dense_layout=dense_layers(cfg), router_scoring="sigmoid",
        routed_scaling=float(cfg["routed_scaling_factor"]),
        router_norm_eps=cfg["router_norm_eps"],
        mixer_layout=tuple(4 * flag for flag in conv_layers(cfg)),
        conv_width=cfg["conv_L_cache"],
        tie_embeddings=cfg["tie_word_embeddings"])


def to_program(w, cfg):
    """Reference weights -> (params, extra) of `SparseDecoder`; a
    relabelling: projections are cut into streams or heads by a reshape,
    everything else is the tensor itself. There is no `lm_head`."""
    sparse_decoder = _program()
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    params = {"embed": w["embed"], "norm_final": {"scale": w["g_f"]}}
    dense = dense_layers(cfg)
    for i, conv in enumerate(conv_layers(cfg)):
        lw = {k.split("/", 1)[1]: v for k, v in w.items()
              if k.startswith("%d/" % i)}
        mixer = ({"in_proj_bcx": lw["w_in"].reshape(d, 3, d),
                  "conv": lw["w_conv"], "out": lw["w_out"]} if conv else
                 {"query": lw["w_q"].reshape(d, hq, hd),
                  "key": lw["w_k"].reshape(d, hkv, hd),
                  "value": lw["w_v"].reshape(d, hkv, hd),
                  "out": lw["w_o"].reshape(hq, hd, d),
                  "norm_query": {"scale": lw["g_q"]},
                  "norm_key": {"scale": lw["g_k"]}})
        ffn = ({"ffn_gate_up": lw["w_ffn_gate_up"],
                "ffn_down": lw["w_ffn_down"]} if dense[i] else
               {"router": lw["w_r"], "router_bias": lw["b_r"],
                "experts_gate_up": lw["w_gate_up"],
                "experts_down": lw["w_down"]})
        params["layer_%d" % i] = dict(
            mixer, norm_attn={"scale": lw["g1"]},
            norm_moe={"scale": lw["g2"]}, **ffn)
    return params, sparse_decoder.init_counters(len(dense), scored=True,
                                                shortconv=True)


def train_parts(cfg, job):
    """(loss_fn, has_aux, expected (params, extra) shapes): the program's
    own `create_model_and_loss`, traced abstractly so that its eager
    initialisation costs no device time."""
    sparse_decoder = _program()
    box = {}

    def build():
        _, params, extra, loss_fn = sparse_decoder.create_model_and_loss(
            build_model(cfg, job), dummy_seq=16)
        box["loss_fn"] = loss_fn
        return params, extra

    shapes = jax.eval_shape(build)
    return box["loss_fn"], True, shapes


def _as_sparse(cfg, layers):
    """The keys benchmark/program/sparse_decoder.py reads, for `layers`
    layers that each hold grouped-query attention AND routed experts."""
    return {"hidden_size": cfg["hidden_size"], "head_dim": cfg["head_dim"],
            "num_attention_heads": cfg["num_attention_heads"],
            "num_key_value_heads": cfg["num_key_value_heads"],
            "num_hidden_layers": layers,
            "sliding_window_layout": [0] * layers, "sliding_window_size": 0,
            "moe_ffn_hidden_size": cfg["moe_intermediate_size"],
            "moe_num_primary_experts": cfg["num_experts"],
            "moe_num_active_primary_experts": cfg["num_experts_per_tok"],
            "moe_router_outputs": cfg["num_router_outputs"]}


def expected_expert_rows(cfg, tokens):
    """Rows the held experts of ONE expert layer serve a step under even
    routing."""
    return _sparse.expected_expert_rows(_as_sparse(cfg, 1), tokens)


def matrix_weights_per_token(cfg):
    """{part: matrix weights that EVERY token meets in one layer that has
    the part}: a conv layer's in- and out-projection and its taps; an
    attention layer's four projections; the dense feed-forward part; an
    expert layer's router; and the head (the embedding read a second time:
    the gather at the bottom multiplies nothing). The routed experts are
    counted by their rows."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"conv": 4 * d * d + d * cfg["conv_L_cache"],
            "attention": d * (hq + 2 * hkv) * hd + hq * hd * d,
            "dense": 3 * d * cfg["intermediate_size"],
            "router": d * cfg["num_router_outputs"],
            "head": d * cfg["vocab_size"]}


def train_flops(cfg, job, rows):
    """Operations the forward and backward passes of one step REQUIRE (no
    recomputation, no padding, nothing for a pair outside the causal
    mask): 6 per matrix weight per row that meets it — a mixer's
    projections, the dense part, an expert layer's router and the head for
    every token, a routed expert's three matrices for the EXPECTED 4 x
    held/32 rows a token — and the attention layers' two products over the
    causal pairs, forward and twice backward. The two gates of a conv layer
    (two multiplications a channel and token) are not counted."""
    t = job["seq_len"]
    tokens = rows * float(t)
    conv, dense = conv_layers(cfg), dense_layers(cfg)
    n_conv, n_attn = sum(conv), len(conv) - sum(conv)
    n_dense, n_expert = sum(dense), len(dense) - sum(dense)
    w = matrix_weights_per_token(cfg)
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    per_token = (n_conv * w["conv"] + n_attn * w["attention"]
                 + n_dense * w["dense"] + n_expert * w["router"] + w["head"])
    return (6.0 * tokens * per_token
            + n_expert * 6.0 * expected_expert_rows(cfg, tokens) * expert
            + n_attn * 3.0 * rows * band_pairs(t)
            * cfg["num_attention_heads"] * 2 * 2 * cfg["head_dim"])


# -- what each Pallas kernel of this family's step must do, per step -----
#
# {name: (operations, bytes)}: the least the algorithm needs for THE CALLS
# ONE STEP MAKES, the same whatever implements it. Under remat by layer the
# attention layer runs the flash forward TWICE (the band kernels name no
# residual) and the backward once — the RESIDENT kernels at 8192 tokens: k +
# v of a key-value head of 64 are 2 MiB —; the grouped products once, in the
# expert layers alone. `expert_rows`: the rows the held experts really
# served a step, AVERAGED OVER ALL THE ENTRIES the counters hold
# (benchmark/lib/kernel_readers.py:expert_rows_per_step) — the dense layer's
# zero among them.
#
# `shortconv_gate` is no kernel but a SCOPE, `mixer.conv.gate`: everything
# between a conv layer's two products, whatever XLA makes of it. Forward it
# must read B, C and x and write C * z once (4 arrays of tokens x channels,
# bfloat16); backward it must read the cotangent, B, C and x and write
# three cotangents (7 arrays); remat adds one forward. Its operations — two
# multiplications and the taps' sums a channel and token, forward — are the
# vector unit's and stand beside the bytes as nothing.

def kernel_costs(cfg, job, rows, expert_rows=None):
    t, d = job["seq_len"], cfg["hidden_size"]
    conv, dense = conv_layers(cfg), dense_layers(cfg)
    entries = len(conv)
    n_conv, n_attn = sum(conv), entries - sum(conv)
    n_expert = entries - sum(dense)
    tokens = rows * float(t)
    if expert_rows is None:
        a_layer = expected_expert_rows(cfg, tokens)
    else:
        a_layer = expert_rows * entries / float(n_expert)
    costs = {"flash_fwd_resident": _sparse.kernel_costs(
        _as_sparse(cfg, n_attn), job, rows, a_layer)["flash_fwd_resident"]}
    experts = _sparse.kernel_costs(_as_sparse(cfg, n_expert), job, rows,
                                   a_layer)
    costs["moe_gmm"], costs["moe_tgmm"] = (experts["moe_gmm"],
                                           experts["moe_tgmm"])
    forwards = 2 if job.get("remat") else 1
    arrays = 4 * forwards + 7
    taps = cfg["conv_L_cache"]
    costs["shortconv_gate"] = (
        n_conv * tokens * d * 3.0 * (2 + 2 * taps),
        n_conv * tokens * d * 2.0 * arrays)
    return costs
