"""How a `family: latent_decoder` configuration — a sparse decoder with
multi-head latent attention (keys and values rebuilt from one latent a
token, one rotary key part for all heads, q/k wider than v), a sigmoid
router that chooses by score + bias, an ungated shared expert beside the
routed ones and leading dense layer(s) in the same stack — is handed to the
program under test: `edl_tpu/models/sparse_decoder.py` for the model, its
loss and its routing counters (the trainer's extra state), the reference's
seeded weights relabelled into the program's parameter tree. Nothing here
computes a number that `correct` compares; the counts below are what the
utilization and roofline metrics divide by."""

import jax
import jax.numpy as jnp

from benchmark.lib.harness import BenchError, load_module

_sparse = load_module("program", "sparse_decoder")
band_pairs, make_batch = _sparse.band_pairs, _sparse.make_batch


def _program():
    from edl_tpu.models import sparse_decoder
    if not hasattr(sparse_decoder, "ROUTE_COUNTERS"):
        raise BenchError("this program's decoder has no latent path: no "
                         "latent attention, no sigmoid router")
    return sparse_decoder


def dense_layers(cfg):
    """Per layer: 1 = a dense feed-forward part (the leading
    `first_k_dense_replace`), 0 = routed experts beside the shared one."""
    return tuple(int(i < cfg["first_k_dense_replace"])
                 for i in range(cfg["num_hidden_layers"]))


def shared_width(cfg):
    """The `n_shared_experts` shared experts are ONE gated linear unit."""
    return cfg["n_shared_experts"] * cfg["moe_intermediate_size"]


def build_model(cfg, job):
    sparse_decoder = _program()
    n = cfg["num_hidden_layers"]
    return sparse_decoder.SparseDecoder(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_layers=n, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["qk_head_dim"],
        num_experts=cfg["num_router_outputs"],
        experts_held=cfg["n_routed_experts"],
        first_expert=cfg["first_expert"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        rope_layout=(1,) * n, window_layout=(0,) * n, window=0,
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        dtype=jnp.bfloat16, remat=bool(job.get("remat", False)),
        use_flash=None, router_input="moe_norm",
        expert_activation=cfg["hidden_act"],
        shared_expert_width=shared_width(cfg), shared_expert_gate=False,
        dense_width=cfg["intermediate_size"],
        dense_layout=dense_layers(cfg), latent_dim=cfg["kv_lora_rank"],
        rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], router_scoring=cfg["scoring_func"],
        routed_scaling=cfg["routed_scaling_factor"])


def to_program(w, cfg):
    """Reference weights -> (params, extra) of `SparseDecoder`; a
    relabelling that copies nothing: projections are cut into heads by a
    reshape, everything else is the tensor itself."""
    sparse_decoder = _program()
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dv, dc = (cfg["qk_nope_head_dim"], cfg["v_head_dim"],
                  cfg["kv_lora_rank"])
    params = {"embed": w["embed"], "lm_head": w["head"],
              "norm_final": {"scale": w["g_f"]}}
    for i, dense in enumerate(dense_layers(cfg)):
        lw = {k.split("/", 1)[1]: v for k, v in w.items()
              if k.startswith("%d/" % i)}
        ffn = ({"ffn_gate_up": lw["w_ffn_gate_up"],
                "ffn_down": lw["w_ffn_down"]} if dense else
               {"router": lw["w_r"], "router_bias": lw["b_r"],
                "experts_gate_up": lw["w_gate_up"],
                "experts_down": lw["w_down"],
                "shared_gate_up": lw["w_sgu"], "shared_down": lw["w_sd"]})
        params["layer_%d" % i] = dict(
            ffn, norm_attn={"scale": lw["g1"]},
            norm_moe={"scale": lw["g2"]},
            norm_latent={"scale": lw["g_c"]},
            query=lw["w_q"].reshape(d, h, cfg["qk_head_dim"]),
            kv_down=lw["w_kva"],
            kv_up=lw["w_kvb"].reshape(dc, h, dn + dv),
            out=lw["w_o"].reshape(h, dv, d))
    return params, sparse_decoder.init_counters(len(dense_layers(cfg)),
                                                scored=True)


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
    """The keys benchmark/program/sparse_decoder.py reads for the routed
    experts' counts, for `layers` expert layers of this family."""
    return {"hidden_size": cfg["hidden_size"],
            "head_dim": cfg["qk_head_dim"],
            "num_attention_heads": cfg["num_attention_heads"],
            "num_key_value_heads": cfg["num_key_value_heads"],
            "num_hidden_layers": layers,
            "sliding_window_layout": [0] * layers, "sliding_window_size": 0,
            "moe_ffn_hidden_size": cfg["moe_intermediate_size"],
            "moe_num_primary_experts": cfg["n_routed_experts"],
            "moe_num_active_primary_experts": cfg["num_experts_per_tok"],
            "moe_router_outputs": cfg["num_router_outputs"]}


def expected_expert_rows(cfg, tokens):
    """Rows the held experts of ONE expert layer serve a step under even
    routing."""
    return _sparse.expected_expert_rows(_as_sparse(cfg, 1), tokens)


def matrix_weights_per_token(cfg):
    """{part: matrix weights that EVERY token meets in one layer that has
    the part}: the latent attention's four projections (the latent's
    down-projection whole, the rest by this chip's heads), the dense
    layer's feed-forward part, an expert layer's router and shared expert;
    and the head. The routed experts are counted by their rows."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv, dc = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"], cfg["kv_lora_rank"])
    return {"attention": (d * h * (dn + dr) + d * (dc + dr)
                          + dc * h * (dn + dv) + h * dv * d),
            "dense": 3 * d * cfg["intermediate_size"],
            "router": d * cfg["num_router_outputs"],
            "shared": 3 * d * shared_width(cfg),
            "head": d * cfg["vocab_size"]}


def train_flops(cfg, job, rows):
    """Operations the forward and backward passes of one step REQUIRE (no
    recomputation, no padding, nothing for a pair outside the causal
    mask): 6 per matrix weight per row that meets it — the latent
    attention's projections in every layer, the dense layer's feed-forward
    part, an expert layer's router and shared expert for every token, a
    routed expert's three matrices for the EXPECTED 6 x held/128 rows a
    token, the head for every token — plus the attention core over the
    causal pairs, forward and twice backward: a 192-wide score and a
    128-wide value product a pair and head."""
    t = job["seq_len"]
    tokens = rows * t
    layers = dense_layers(cfg)
    n_dense = sum(layers)
    n_expert = len(layers) - n_dense
    w = matrix_weights_per_token(cfg)
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    per_token = (len(layers) * w["attention"] + n_dense * w["dense"]
                 + n_expert * (w["router"] + w["shared"]) + w["head"])
    return (6.0 * tokens * per_token
            + n_expert * 6.0 * expected_expert_rows(cfg, tokens) * expert
            + len(layers) * 3.0 * rows * band_pairs(t)
            * cfg["num_attention_heads"] * 2 * (cfg["qk_head_dim"]
                                                + cfg["v_head_dim"]))


# -- what each Pallas kernel of this family's step must do, per step -----
#
# {kernel name as the device trace shows it: (operations, bytes)}: the
# least the kernel's algorithm needs for THE CALLS ONE STEP MAKES. Under
# remat by layer every layer runs the flash forward TWICE (the band kernels
# name no residual) and the backward once; the grouped products once (the
# layer saves their results), in the expert layers alone. At 8192 tokens k
# + v of a head are 5 MiB, past the resident limit: the streamed forward
# and the split backward (`flash_bwd_dq` + `flash_bwd_dkv`, both read under
# `flash_bwd`) run; a shorter sequence runs the resident pair.
# `expert_rows`: the rows the held experts really served a step, AVERAGED
# OVER ALL THE ENTRIES the counters hold (benchmark/lib/kernel_readers.py:
# expert_rows_per_step) — the dense layers' zeros among them —, so the
# step's rows are that mean times the entries, not times the expert layers.

def _resident(cfg, job):
    from edl_tpu.ops import flash_attention
    return (job["seq_len"] * (cfg["qk_head_dim"] + cfg["v_head_dim"]) * 2
            <= flash_attention._RESIDENT_KV_BYTES)


def kernel_costs(cfg, job, rows, expert_rows=None):
    t, h = job["seq_len"], cfg["num_attention_heads"]
    dqk, dv, dr = (cfg["qk_head_dim"], cfg["v_head_dim"],
                   cfg["qk_rope_head_dim"])
    layers = dense_layers(cfg)
    entries, n_expert = len(layers), len(layers) - sum(layers)
    forwards = 2 if job.get("remat") else 1
    tokens = rows * float(t)
    if expert_rows is None:
        a_layer = expected_expert_rows(cfg, tokens)
    else:
        a_layer = expert_rows * entries / float(n_expert)
    costs = dict(_sparse.kernel_costs(_as_sparse(cfg, n_expert), job, rows,
                                      a_layer))
    del costs["flash_fwd_resident"]
    pairs = rows * band_pairs(t)
    # the key's rotary part is ONE head: read once a token, not once a head
    k_and_v = h * (dqk - dr) + dr + h * dv
    resident = _resident(cfg, job)
    # q in, the result out (bfloat16), k and v once; lse out (float32 a row
    # and head)
    costs["flash_fwd_resident" if resident else "flash_fwd_stream"] = (
        entries * forwards * pairs * h * 2 * (dqk + dv),
        entries * forwards * tokens * (
            2.0 * (h * dqk + h * dv + k_and_v) + 4.0 * h))
    # five products a pair (scores again, dp, dq, dk: 192 wide but dp; dv);
    # in: q, dO, k, v, lse and delta; out: dq, dk, dv
    costs["flash_bwd"] = (
        entries * pairs * h * 2 * (3 * dqk + 2 * dv),
        entries * tokens * (2.0 * (2 * h * dqk + h * dv + 2 * k_and_v)
                            + 8.0 * h))
    return costs
