"""How a `family: gated_delta_decoder` configuration — a sparse decoder
whose layers come in periods of gated-delta-rule (linear-attention) layers
and one gated full-attention layer, each with a shared expert beside the
routed ones — is handed to the program under test:
`edl_tpu/models/sparse_decoder.py` for the model, its loss and its routing
and delta-rule counters (the trainer's extra state), the reference's seeded
weights relabelled into the program's parameter tree. Nothing here computes
a number that `correct` compares; the counts below are what the utilization
and roofline metrics divide by."""

import jax
import jax.numpy as jnp

from benchmark.lib.harness import BenchError, load_module

_sparse = load_module("program", "sparse_decoder")
band_pairs, make_batch = _sparse.band_pairs, _sparse.make_batch


def _program():
    from edl_tpu.models import sparse_decoder
    if not hasattr(sparse_decoder, "GATED_DELTA_COUNTERS"):
        raise BenchError("this program's sparse decoder has no "
                         "gated-delta-rule layer")
    return sparse_decoder


def linear_layers(cfg):
    """Per layer: 1 = gated delta rule, 0 = full attention (the last of
    every `full_attention_interval`)."""
    return tuple(int((i + 1) % cfg["full_attention_interval"] != 0)
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
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        dtype=jnp.bfloat16, remat=bool(job.get("remat", False)),
        use_flash=None, router_input="moe_norm",
        expert_activation=cfg["hidden_act"], qk_norm=True,
        mixer_layout=linear_layers(cfg),
        gdn_key_heads=cfg["linear_num_key_heads"],
        gdn_value_heads=cfg["linear_num_value_heads"],
        gdn_head_dim=cfg["linear_key_head_dim"],
        conv_width=cfg["linear_conv_kernel_dim"], attn_gate=True,
        rotary_dim=int(cfg["partial_rotary_factor"] * cfg["head_dim"]),
        zero_centered_norm=True,
        shared_expert_width=cfg["shared_expert_intermediate_size"])


def to_program(w, cfg):
    """Reference weights -> (params, extra) of `SparseDecoder`: projections
    are cut into heads by a reshape; a linear layer's separate q, k, v, z
    (and b, a) are laid side by side a KEY head at a time, the published
    `in_proj_qkvz` (`in_proj_ba`) grouping; everything else is the tensor
    itself."""
    sparse_decoder = _program()
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dh = cfg["linear_key_head_dim"]
    n = cfg["num_hidden_layers"]
    by_key_head = lambda *parts: jnp.concatenate(
        [x.reshape(d, hk, -1) for x in parts], axis=-1)
    params = {"embed": w["embed"], "lm_head": w["head"],
              "norm_final": {"scale": w["g_f"]}}
    for i, linear in enumerate(linear_layers(cfg)):
        lw = {k.split("/", 1)[1]: v for k, v in w.items()
              if k.startswith("%d/" % i)}
        if linear:
            mixer = {
                "in_proj_qkvz": by_key_head(lw["w_q"], lw["w_k"], lw["w_v"],
                                            lw["w_z"]),
                "in_proj_ba": by_key_head(lw["w_b"], lw["w_a"]),
                "conv": lw["w_conv"], "A_log": lw["a_log"],
                "dt_bias": lw["dt_bias"],
                "norm_gdn": {"scale": lw["g_n"]},
                "out": lw["w_o"].reshape(hv, dh, d)}
        else:
            mixer = {
                "query": lw["w_q"].reshape(d, hq, 2 * hd),
                "key": lw["w_k"].reshape(d, hkv, hd),
                "value": lw["w_v"].reshape(d, hkv, hd),
                "out": lw["w_o"].reshape(hq, hd, d),
                "norm_query": {"scale": lw["g_q"]},
                "norm_key": {"scale": lw["g_k"]}}
        params["layer_%d" % i] = dict(
            mixer, norm_attn={"scale": lw["g1"]},
            norm_moe={"scale": lw["g2"]}, router=lw["w_r"],
            experts_gate_up=lw["w_gate_up"], experts_down=lw["w_down"],
            shared_gate_up=lw["w_sgu"], shared_down=lw["w_sd"],
            shared_gate=lw["w_sg"])
    return params, sparse_decoder.init_counters(n, gated_delta=True)


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


def _as_sparse(cfg):
    """The keys benchmark/program/sparse_decoder.py reads for the routed
    experts' counts, from this family's configuration."""
    n = cfg["num_hidden_layers"]
    return {"hidden_size": cfg["hidden_size"], "head_dim": cfg["head_dim"],
            "num_attention_heads": cfg["num_attention_heads"],
            "num_key_value_heads": cfg["num_key_value_heads"],
            "num_hidden_layers": n, "sliding_window_layout": [0] * n,
            "sliding_window_size": 0,
            "moe_ffn_hidden_size": cfg["moe_intermediate_size"],
            "moe_num_primary_experts": cfg["num_experts"],
            "moe_num_active_primary_experts": cfg["num_experts_per_tok"],
            "moe_router_outputs": cfg["num_router_outputs"]}


def expected_expert_rows(cfg, tokens):
    return _sparse.expected_expert_rows(_as_sparse(cfg), tokens)


def matrix_weights_per_token(cfg):
    """(a linear layer's, the full layer's, the head's) matrix weights that
    EVERY token meets: the mixer's projections (and the convolution's
    taps), the router, the shared expert with its gate; the routed experts
    are counted by their rows."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    experts = (d * cfg["num_router_outputs"]
               + 3 * d * cfg["shared_expert_intermediate_size"] + d)
    channels = 2 * hk * dk + hv * dv
    linear = (d * (channels + hv * dv + 2 * hv)
              + channels * cfg["linear_conv_kernel_dim"] + hv * dv * d)
    full = d * (2 * hq + 2 * hkv) * hd + hq * hd * d
    return linear + experts, full + experts, d * cfg["vocab_size"]


def train_flops(cfg, job, rows):
    """Operations the forward and backward passes of one step REQUIRE (no
    recomputation, no padding, nothing for a pair outside the causal
    mask): 6 per matrix weight per row that meets it — a mixer's
    projections, the router and the SHARED expert for every token, a routed
    expert's three matrices for the EXPECTED 10 x held/512 rows a token,
    the head for every token; the full layer's two attention products over
    the causal pairs, forward and twice backward; and the delta rule as
    the RECURRENCE states it, a token and value head at a time: S^T k, the
    write k w^T and S^T q, each 2 dk dv operations forward and twice that
    backward (18 dk dv a token and head; the chunked form the program runs
    makes more products, which are its own and not required)."""
    t = job["seq_len"]
    tokens = rows * t
    linear, full, head = matrix_weights_per_token(cfg)
    layers = linear_layers(cfg)
    n_linear = sum(layers)
    n_full = len(layers) - n_linear
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    dkdv = cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]
    return (6.0 * tokens * (n_linear * linear + n_full * full + head)
            + len(layers) * 6.0 * expected_expert_rows(cfg, tokens) * expert
            + n_full * 3.0 * rows * band_pairs(t) * (
                cfg["num_attention_heads"] * 2 * 2 * cfg["head_dim"])
            + n_linear * 18.0 * tokens * cfg["linear_num_value_heads"]
            * dkdv)


# -- what each Pallas kernel of this family's step must do, per step -----
#
# {kernel name as the device trace shows it: (operations, bytes)}: the
# least the kernel's algorithm needs for THE CALLS ONE STEP MAKES. Under
# remat by layer: `gdn_fwd` once a linear layer (the layer saves its result
# and states, ops/gated_delta.py:SAVED_UNDER_REMAT) and `gdn_bwd` once; the
# full layer's flash forward TWICE (the band kernels name no residual) and
# its backward once; the grouped products once.

def gdn_chunk():
    """Tokens a chunk of the program's chunked delta rule."""
    from edl_tpu.ops import gated_delta
    return gated_delta.CHUNK


def kernel_costs(cfg, job, rows, expert_rows=None):
    hd, t = cfg["head_dim"], job["seq_len"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers = linear_layers(cfg)
    n_linear = float(sum(layers))
    n_full = len(layers) - n_linear
    forwards = 2 if job.get("remat") else 1
    costs = dict(_sparse.kernel_costs(_as_sparse(cfg), job, rows,
                                      expert_rows))
    del costs["flash_fwd_resident"]
    pairs = rows * band_pairs(t)
    tokens = rows * float(t)
    # q and the result once, k and v once a kv head (bfloat16); lse out
    # (float32 a row and query head)
    costs["flash_fwd_stream"] = (
        n_full * forwards * pairs * hq * 2 * 2 * hd,
        n_full * forwards * tokens * (hd * (2 * hq + 2 * hkv) * 2.0
                                      + 4.0 * hq))
    # five products a pair (scores again, dp, dq, dk, dv); in: q, dO, k, v,
    # lse and delta; out: dq, dk, dv
    costs["flash_bwd"] = (
        n_full * pairs * hq * 5 * 2 * hd,
        n_full * tokens * (hd * (3 * hq + 4 * hkv) * 2.0 + 8.0 * hq))
    # the sequential part of the chunked delta rule, a (value head, chunk)
    # of C tokens at a time (ops/gated_delta.py's docstring). Forward:
    # Wk S, Qg S and Kg^T W at 2 C dk dv each, A W at 2 C^2 dv; in U, Wk,
    # Qg, Kg, A (bfloat16) and the chunk's decay, out the result
    # (bfloat16) and the chunk-end state (float32). Backward: A^T dO and
    # dO W^T at 2 C^2 dv, Kg dS, dO S^T, W dS^T, dW S^T, Qg^T dO and
    # Wk^T dW at 2 C dk dv (W's own rebuilding is not counted); in what
    # the forward read, the state and dO, out the five operands'
    # cotangents and the decay's.
    c = float(gdn_chunk())
    hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    chunks = n_linear * hv * tokens / c
    operands = 2.0 * c * (dv + 3 * dk + c) + 4.0 * dv
    costs["gdn_fwd"] = (
        chunks * (3 * 2 * c * dk * dv + 2 * c * c * dv),
        chunks * (operands + 2.0 * c * dv + 4.0 * dk * dv))
    costs["gdn_bwd"] = (
        chunks * (6 * 2 * c * dk * dv + 2 * 2 * c * c * dv),
        chunks * (2 * operands + 2.0 * c * dv + 4.0 * dk * dv))
    return costs
