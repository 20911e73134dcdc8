"""How a `family: kda_latent_decoder` configuration — a decoder whose layers
are Kimi Delta Attention (a delta rule whose decay is a vector, one rate a
key channel) or multi-head latent attention WITHOUT positions, in the order
the configuration's `linear_attn_config` gives, the leading layer(s) over a
dense feed-forward part and the others over sigmoid-routed experts beside an
ungated shared expert — is handed to the program under test:
`edl_tpu/models/sparse_decoder.py` for the model, its loss and its routing
and delta-rule counters (the trainer's extra state), the reference's seeded
weights relabelled into the program's parameter tree. Nothing here computes
a number that `correct` compares; the counts below are what the utilization
and roofline metrics divide by."""

import jax
import jax.numpy as jnp

from benchmark.lib.harness import BenchError, load_module

_sparse = load_module("program", "sparse_decoder")
_latent = load_module("program", "latent_decoder")
band_pairs, make_batch = _sparse.band_pairs, _sparse.make_batch
dense_layers = _latent.dense_layers


def _program():
    from edl_tpu.models import sparse_decoder
    if not hasattr(sparse_decoder, "KDA_COUNTERS"):
        raise BenchError("this program's decoder has no Kimi-Delta-Attention "
                         "layer: no delta rule at a vector decay")
    return sparse_decoder


def kda_layers(cfg):
    """Per layer: 1 = Kimi Delta Attention, 0 = latent attention
    (`linear_attn_config` counts layers from 1)."""
    lin = cfg["linear_attn_config"]
    n = cfg["num_hidden_layers"]
    kda = tuple(int(i + 1 in lin["kda_layers"]) for i in range(n))
    full = tuple(int(i + 1 in lin["full_attn_layers"]) for i in range(n))
    if any(a == b for a, b in zip(kda, full)):
        raise BenchError("linear_attn_config: kda_layers %r and "
                         "full_attn_layers %r for %d layers"
                         % (lin["kda_layers"], lin["full_attn_layers"], n))
    return kda


def shared_width(cfg):
    """The `num_shared_experts` shared experts are ONE gated linear unit."""
    return cfg["num_shared_experts"] * cfg["moe_intermediate_size"]


def build_model(cfg, job):
    sparse_decoder = _program()
    n = cfg["num_hidden_layers"]
    lin = cfg["linear_attn_config"]
    return sparse_decoder.SparseDecoder(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_layers=n, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        num_experts=cfg["num_router_outputs"],
        experts_held=cfg["num_experts"], first_expert=cfg["first_expert"],
        experts_per_token=cfg["num_experts_per_token"],
        expert_width=cfg["moe_intermediate_size"],
        rope_layout=(0,) * n, window_layout=(0,) * n, window=0,
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        dtype=jnp.bfloat16, remat=bool(job.get("remat", False)),
        use_flash=None, router_input="moe_norm",
        expert_activation=cfg["hidden_act"],
        shared_expert_width=shared_width(cfg), shared_expert_gate=False,
        dense_width=cfg["intermediate_size"],
        dense_layout=dense_layers(cfg), latent_dim=cfg["kv_lora_rank"],
        rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        router_scoring=cfg["moe_router_activation_func"],
        routed_scaling=cfg["routed_scaling_factor"],
        mixer_layout=tuple(3 * flag for flag in kda_layers(cfg)),
        conv_width=lin["short_conv_kernel_size"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_gate_rank=cfg["kda_gate_rank"])


def _kda_to_program(lw, cfg):
    """A KDA layer's tensors: the convolution's channels as q's, k's and
    v's, the two gates' down-projections side by side, everything else cut
    into heads by a reshape."""
    lin = cfg["linear_attn_config"]
    d, h, dh = cfg["hidden_size"], lin["num_heads"], lin["head_dim"]
    rank = cfg["kda_gate_rank"]
    by_head = lambda x: x.reshape(x.shape[0], h, dh)
    return {"query": by_head(lw["w_q"]), "key": by_head(lw["w_k"]),
            "value": by_head(lw["w_v"]),
            "conv": lw["w_conv"].reshape(3, h * dh, -1),
            "gates_down": jnp.stack([lw["w_fa"], lw["w_ga"]], axis=1),
            "decay_up": lw["w_fb"].reshape(rank, h, dh),
            "gate_up": lw["w_gb"].reshape(rank, h, dh),
            "A_log": lw["a_log"], "dt_bias": lw["dt_bias"].reshape(h, dh),
            "in_proj_b": lw["w_b"], "norm_kda": {"scale": lw["g_n"]},
            "out": lw["w_o"].reshape(h, dh, d)}


def to_program(w, cfg):
    """Reference weights -> (params, extra) of `SparseDecoder`; a
    relabelling: projections are cut into heads by a reshape, everything
    else is the tensor itself."""
    sparse_decoder = _program()
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dv, dc = (cfg["qk_nope_head_dim"], cfg["v_head_dim"],
                  cfg["kv_lora_rank"])
    params = {"embed": w["embed"], "lm_head": w["head"],
              "norm_final": {"scale": w["g_f"]}}
    dense = dense_layers(cfg)
    for i, kda in enumerate(kda_layers(cfg)):
        lw = {k.split("/", 1)[1]: v for k, v in w.items()
              if k.startswith("%d/" % i)}
        mixer = _kda_to_program(lw, cfg) if kda else {
            "norm_latent": {"scale": lw["g_c"]},
            "query": lw["w_q"].reshape(d, h, dn + cfg["qk_rope_head_dim"]),
            "kv_down": lw["w_kva"],
            "kv_up": lw["w_kvb"].reshape(dc, h, dn + dv),
            "out": lw["w_o"].reshape(h, dv, d)}
        ffn = ({"ffn_gate_up": lw["w_ffn_gate_up"],
                "ffn_down": lw["w_ffn_down"]} if dense[i] else
               {"router": lw["w_r"], "router_bias": lw["b_r"],
                "experts_gate_up": lw["w_gate_up"],
                "experts_down": lw["w_down"],
                "shared_gate_up": lw["w_sgu"], "shared_down": lw["w_sd"]})
        params["layer_%d" % i] = dict(
            mixer, norm_attn={"scale": lw["g1"]},
            norm_moe={"scale": lw["g2"]}, **ffn)
    return params, sparse_decoder.init_counters(len(dense), scored=True,
                                                kda=True)


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


def _as_latent(cfg, layers):
    """The keys benchmark/program/latent_decoder.py reads, for `layers`
    latent-attention layers of this family (none of them dense: the
    leading dense layer's feed-forward part is counted here)."""
    return {"hidden_size": cfg["hidden_size"],
            "num_attention_heads": cfg["num_attention_heads"],
            "num_key_value_heads": cfg["num_key_value_heads"],
            "qk_head_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "qk_nope_head_dim": cfg["qk_nope_head_dim"],
            "qk_rope_head_dim": cfg["qk_rope_head_dim"],
            "v_head_dim": cfg["v_head_dim"],
            "kv_lora_rank": cfg["kv_lora_rank"],
            "num_hidden_layers": layers, "first_k_dense_replace": 0,
            "intermediate_size": cfg["intermediate_size"],
            "moe_intermediate_size": cfg["moe_intermediate_size"],
            "n_shared_experts": cfg["num_shared_experts"],
            "n_routed_experts": cfg["num_experts"],
            "num_experts_per_tok": cfg["num_experts_per_token"],
            "num_router_outputs": cfg["num_router_outputs"],
            "vocab_size": cfg["vocab_size"]}


def expected_expert_rows(cfg, tokens):
    """Rows the held experts of ONE expert layer serve a step under even
    routing."""
    return _latent.expected_expert_rows(_as_latent(cfg, 1), tokens)


def matrix_weights_per_token(cfg):
    """{part: matrix weights that EVERY token meets in one layer that has
    the part}: a KDA layer's projections (q, k, v, the two low-rank gates,
    beta, the result's) and its convolution's taps; the latent attention's
    four projections; the dense feed-forward part; an expert layer's router
    and shared expert; and the head. The routed experts are counted by
    their rows, the rule by `train_flops`."""
    lin = cfg["linear_attn_config"]
    d, rank = cfg["hidden_size"], cfg["kda_gate_rank"]
    wide = lin["num_heads"] * lin["head_dim"]
    latent = _latent.matrix_weights_per_token(_as_latent(cfg, 1))
    return dict(latent, kda=(
        d * 3 * wide + 3 * wide * lin["short_conv_kernel_size"]
        + 2 * (d * rank + rank * wide) + d * lin["num_heads"] + wide * d))


def train_flops(cfg, job, rows):
    """Operations the forward and backward passes of one step REQUIRE (no
    recomputation, no padding, nothing for a pair outside the causal
    mask): 6 per matrix weight per row that meets it — a mixer's
    projections, the dense part, an expert layer's router and shared expert
    for every token, a routed expert's three matrices for the EXPECTED 8 x
    held/256 rows a token, the head for every token; the latent layers' two
    attention products over the causal pairs (192 and 128 wide), forward
    and twice backward; and the delta rule as the RECURRENCE states it, a
    token and head at a time: S^T k, the write k w^T and S^T q, each 2 dk
    dv operations forward and twice that backward (18 dk dv a token and
    head; the rows' decay, dk dv multiplications, is not counted, as a
    scalar decay's is not, and the chunked form the program runs makes
    more products, which are its own and not required)."""
    t = job["seq_len"]
    tokens = rows * float(t)
    kda = kda_layers(cfg)
    dense = dense_layers(cfg)
    n_kda, n_mla = sum(kda), len(kda) - sum(kda)
    n_dense, n_expert = sum(dense), len(dense) - sum(dense)
    w = matrix_weights_per_token(cfg)
    lin = cfg["linear_attn_config"]
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    per_token = (n_kda * w["kda"] + n_mla * w["attention"]
                 + n_dense * w["dense"]
                 + n_expert * (w["router"] + w["shared"]) + w["head"])
    return (6.0 * tokens * per_token
            + n_expert * 6.0 * expected_expert_rows(cfg, tokens) * expert
            + n_mla * 3.0 * rows * band_pairs(t)
            * cfg["num_attention_heads"] * 2 * (
                cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                + cfg["v_head_dim"])
            + n_kda * 18.0 * tokens * lin["num_heads"]
            * lin["head_dim"] ** 2)


# -- what each Pallas kernel of this family's step must do, per step -----
#
# {kernel name as the device trace shows it: (operations, bytes)}: the
# least the kernel's algorithm needs for THE CALLS ONE STEP MAKES, the same
# whatever implements it. Under remat by layer: `kda_fwd` once a KDA layer
# (the layer saves its result and states,
# ops/gated_delta.py:KDA_SAVED_UNDER_REMAT) and `kda_bwd` once; the latent
# layer's flash forward TWICE (the band kernels name no residual) and its
# backward once, streamed and split at 8192 tokens (k + v of a head are 5
# MiB); the grouped products once, in the expert layers alone.
# `expert_rows`: the rows the held experts really served a step, AVERAGED
# OVER ALL THE ENTRIES the counters hold (benchmark/lib/kernel_readers.py:
# expert_rows_per_step) — the dense layer's zero among them.

def kda_chunk():
    """Tokens a chunk of the program's chunked delta rule."""
    from edl_tpu.ops import gated_delta
    return gated_delta.CHUNK


def kernel_costs(cfg, job, rows, expert_rows=None):
    t = job["seq_len"]
    kda = kda_layers(cfg)
    dense = dense_layers(cfg)
    entries = len(kda)
    n_kda, n_mla = float(sum(kda)), entries - sum(kda)
    n_expert = entries - sum(dense)
    tokens = rows * float(t)
    # the latent family's counts for `n_mla` layers, all with experts, then
    # the grouped products again for THIS stack's expert layers
    as_mla = _as_latent(cfg, n_mla)
    costs = dict(_latent.kernel_costs(as_mla, job, rows, None))
    if expert_rows is None:
        a_layer = expected_expert_rows(cfg, tokens)
    else:
        a_layer = expert_rows * entries / float(n_expert)
    experts = _latent.kernel_costs(_as_latent(cfg, n_expert), job, rows,
                                   a_layer)
    costs["moe_gmm"], costs["moe_tgmm"] = (experts["moe_gmm"],
                                           experts["moe_tgmm"])
    # the sequential part of the chunked delta rule at a VECTOR decay, a
    # (head, chunk) of C tokens at a time (ops/gated_delta.py's docstring).
    # Forward: Wk S, Qg S and Kg^T W at 2 C dk dv each, A W at 2 C^2 dv; in
    # U, Wk, Qg, Kg, A (bfloat16) and the chunk's decay, one float32 a key
    # channel, out the result and the chunk-end state the backward will read
    # (both bfloat16: the state is CARRIED in float32, in VMEM).
    # Backward: A^T dO and dO W^T at 2 C^2 dv, Kg dS, dO S^T, W dS^T, dW
    # S^T, Qg^T dO and Wk^T dW at 2 C dk dv (W's own rebuilding is not
    # counted); in what the forward read, the state and dO, out the five
    # operands' cotangents and the decay's. A kernel that forms the chunk's
    # operands in VMEM is judged by this same count.
    c = float(kda_chunk())
    lin = cfg["linear_attn_config"]
    dk = dv = lin["head_dim"]
    chunks = n_kda * lin["num_heads"] * tokens / c
    operands = 2.0 * c * (dv + 3 * dk + c) + 4.0 * dk
    costs["kda_fwd"] = (
        chunks * (3 * 2 * c * dk * dv + 2 * c * c * dv),
        chunks * (operands + 2.0 * c * dv + 2.0 * dk * dv))
    costs["kda_bwd"] = (
        chunks * (6 * 2 * c * dk * dv + 2 * 2 * c * c * dv),
        chunks * (2 * operands + 2.0 * c * dv + 2.0 * dk * dv))
    return costs
