"""How a `family: dsa_decoder` configuration — a sparse decoder whose
every layer reads the keys a learned indexer chose (DeepSeek-Sparse-
Attention) — is handed to the program under test:
`edl_tpu/models/sparse_decoder.py` for the model, its loss (cross-entropy
plus the indexer's own term) and its routing and selection counters (the
trainer's extra state), the reference's seeded weights relabelled into the
program's parameter tree. Nothing here computes a number that `correct`
compares; the counts below are what the utilization and roofline metrics
divide by."""

import jax
import jax.numpy as jnp

from benchmark.lib.harness import BenchError, load_module

_sparse = load_module("program", "sparse_decoder")
band_pairs, make_batch = _sparse.band_pairs, _sparse.make_batch


def _program():
    from edl_tpu.models import sparse_decoder
    if not hasattr(sparse_decoder, "SELECT_COUNTERS"):
        raise BenchError("this program's sparse decoder has no learned "
                         "selection of keys")
    return sparse_decoder


def build_model(cfg, job):
    sparse_decoder = _program()
    n, sa = cfg["num_hidden_layers"], cfg["sa_config"]
    return sparse_decoder.SparseDecoder(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_layers=n, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["num_local_experts"],
        experts_held=cfg["num_experts"], first_expert=cfg["first_expert"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        rope_layout=(1,) * n, window_layout=(0,) * n, window=0,
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        dtype=jnp.bfloat16, remat=bool(job.get("remat", False)),
        use_flash=None, select_layout=(1,) * n, select_topk=sa["topk"],
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"], router_input="moe_norm",
        expert_activation=cfg["hidden_act"], qk_norm=True,
        index_loss_weight=float(cfg["index_loss_weight"]))


def to_program(w, cfg):
    """Reference weights -> (params, extra) of `SparseDecoder`; a
    relabelling that copies nothing: projections are cut into heads by a
    reshape, everything else is the tensor itself."""
    sparse_decoder = _program()
    d, hd, sa = cfg["hidden_size"], cfg["head_dim"], cfg["sa_config"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = cfg["num_hidden_layers"]
    params = {"embed": w["embed"], "lm_head": w["head"],
              "norm_final": {"scale": w["g_f"]}}
    for i in range(n):
        lw = {k.split("/", 1)[1]: v for k, v in w.items()
              if k.startswith("%d/" % i)}
        params["layer_%d" % i] = {
            "norm_attn": {"scale": lw["g1"]},
            "query": lw["w_q"].reshape(d, hq, hd),
            "key": lw["w_k"].reshape(d, hkv, hd),
            "value": lw["w_v"].reshape(d, hkv, hd),
            "out": lw["w_o"].reshape(hq, hd, d),
            "norm_query": {"scale": lw["g_q"]},
            "norm_key": {"scale": lw["g_k"]},
            "index_query": lw["w_qi"].reshape(
                d, sa["indexer_num_heads"], sa["indexer_head_dim"]),
            "index_key": lw["w_ki"],
            "index_weight": lw["w_wi"],
            "norm_moe": {"scale": lw["g2"]},
            "router": lw["w_r"],
            "experts_gate_up": lw["w_gate_up"],
            "experts_down": lw["w_down"]}
    return params, sparse_decoder.init_counters(n, True)


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


def kept_pairs(t, topk):
    """(query, key) pairs a sequence keeps: every causal pair of the first
    `topk` queries, `topk` of each later one."""
    if topk >= t:
        return t * (t + 1) / 2.0
    return topk * (topk + 1) / 2.0 + (t - topk) * float(topk)


def _as_sparse(cfg):
    """The keys benchmark/program/sparse_decoder.py reads for the expert
    layer's counts, from this family's configuration."""
    n = cfg["num_hidden_layers"]
    return {"hidden_size": cfg["hidden_size"], "head_dim": cfg["head_dim"],
            "num_attention_heads": cfg["num_attention_heads"],
            "num_key_value_heads": cfg["num_key_value_heads"],
            "num_hidden_layers": n, "sliding_window_layout": [0] * n,
            "sliding_window_size": 0,
            "moe_ffn_hidden_size": cfg["moe_intermediate_size"],
            "moe_num_primary_experts": cfg["num_experts"],
            "moe_num_active_primary_experts": cfg["num_experts_per_tok"],
            "moe_router_outputs": cfg["num_local_experts"]}


def expected_expert_rows(cfg, tokens):
    return _sparse.expected_expert_rows(_as_sparse(cfg), tokens)


def train_flops(cfg, job, rows):
    """Operations the forward and backward passes of one step REQUIRE (no
    recomputation, no padding, nothing for a pair the selection drops): 6
    per matrix weight per row that meets it — attention's projections and
    the router for every token, an expert's three matrices for the
    EXPECTED 8 x held/128 rows a token, the head for every token —, 4 for
    the indexer's projections (their input is a stop-gradient: no dx); the
    indexer's scores ONCE over every causal pair (the choice needs them
    all), their backward (dqi, dki) and attention's two products, forward
    and backward, over the KEPT pairs only."""
    d, hd, t = cfg["hidden_size"], cfg["head_dim"], job["seq_len"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    tokens = rows * t
    kept = rows * kept_pairs(t, sa["topk"])
    per_token = (d * (hq + 2 * hkv) * hd + hq * hd * d
                 + d * cfg["num_local_experts"])
    layer = (6.0 * tokens * per_token
             + 4.0 * tokens * d * (hi * di + di + hi)
             + 6.0 * expected_expert_rows(cfg, tokens)
             * 3 * d * cfg["moe_intermediate_size"]
             + rows * band_pairs(t) * hi * 2 * di
             + kept * hi * 2 * 2 * di
             + 3.0 * kept * hq * 2 * 2 * hd)
    return (cfg["num_hidden_layers"] * layer
            + 6.0 * tokens * d * cfg["vocab_size"])


# -- what each Pallas kernel of this family's step must do, per step -----
#
# {kernel name as the device trace shows it: (operations, bytes)}, on the
# rule of train_flops: the selection kernels are MASKED kernels that visit
# every causal tile, and are held to what the algorithm requires — the
# index scores once over the causal pairs (the thresholds' kernel),
# everything else over the kept pairs only — so one that computes all
# pairs reads at most kept / causal of its roofline. Under remat the
# forward runs twice a layer; the thresholds (saved) and the KL (its value
# is no residual) once.

def kernel_costs(cfg, job, rows, expert_rows=None):
    hd, t = cfg["head_dim"], job["seq_len"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    n = float(cfg["num_hidden_layers"])
    forwards = 2 if job.get("remat") else 1
    kept = rows * kept_pairs(t, sa["topk"])
    tokens = rows * t
    # qi, ki (bfloat16) and wi (float32) in; a row statistic is float32
    index_bytes = tokens * ((hi * di + di) * 2.0 + hi * 4.0)
    qkv_bytes = tokens * hd * 2.0          # one head of q, k, v, dO or out
    costs = dict(_sparse.kernel_costs(_as_sparse(cfg), job, rows,
                                      expert_rows))
    del costs["flash_fwd_resident"]
    costs.update({
        "dsa_index_tau": (n * rows * band_pairs(t) * hi * 2 * di,
                          n * (index_bytes + tokens * 4.0)),
        "dsa_fwd": (forwards * n * kept * hq * 2 * 2 * hd,
                    forwards * n * (qkv_bytes * (2 * hq + 2 * hkv)
                                    + index_bytes
                                    + tokens * 4.0 * (hq + 3))),
        "dsa_index_kl": (n * kept * hq * 2 * hd,
                         n * (qkv_bytes * (hq + hkv) + index_bytes
                              + tokens * 4.0 * (hq + 3))),
        # five products a query head (scores again, dp, dq, dk, dv) and
        # three an indexer head (scores again, dqi, dki); in: q, dO, k, v,
        # the indexer's operands, lse and delta; out: dq, dk, dv, dqi,
        # dki, dwi
        "dsa_bwd": (n * kept * (hq * 5 * 2 * hd + hi * 3 * 2 * di),
                    n * (qkv_bytes * (3 * hq + 4 * hkv) + 2 * index_bytes
                         + tokens * 4.0 * (2 * hq + 3))),
    })
    return costs
