"""How a `family: block_diffusion_decoder` configuration — a sparse decoder
trained by diffusion over blocks: every sequence runs through the layers
as a noised and a clean copy in one stream, under a two-stream block mask —
is handed to the program under test: `edl_tpu/models/sparse_decoder.py` for
the model, its block-diffusion loss and its counters (the trainer's extra
state), the reference's seeded weights relabelled into the program's
parameter tree, the batch noised by the REFERENCE's own `noise_batch`.
Nothing here computes a number that `correct` compares; the counts below
are what the utilization and roofline metrics divide by."""

import jax
import jax.numpy as jnp

from benchmark.lib.harness import BenchError, load_module

_sparse = load_module("program", "sparse_decoder")


def _program():
    from edl_tpu.models import sparse_decoder
    if not hasattr(sparse_decoder, "BLOCK_DIFFUSION_COUNTERS"):
        raise BenchError("this program's sparse decoder cannot be trained "
                         "by diffusion over blocks")
    return sparse_decoder


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
        block_length=cfg["block_length"])


def to_program(w, cfg):
    """Reference weights -> (params, extra) of `SparseDecoder`; a
    relabelling that copies nothing: projections are cut into heads by a
    reshape, everything else is the tensor itself."""
    sparse_decoder = _program()
    d, hd = cfg["hidden_size"], cfg["head_dim"]
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
            "norm_moe": {"scale": lw["g2"]},
            "router": lw["w_r"],
            "experts_gate_up": lw["w_gate_up"],
            "experts_down": lw["w_down"]}
    return params, sparse_decoder.init_counters(n, block_diffusion=True)


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


def make_batch(cfg, job, key, rows):
    """Clean ids drawn uniformly from the rows of the vocabulary held here
    less the mask token's (the last), noised by the reference's own
    `noise_batch`: input_ids, noisy_ids (int32) and loss_weight (float32),
    each [rows, T]."""
    ref = load_module("reference", cfg["name"])
    ids = jax.random.randint(jax.random.fold_in(key, 0),
                             (rows, job["seq_len"]), 0,
                             cfg["mask_token_id"], jnp.int32)
    return ref.noise_batch(ids, jax.random.fold_in(key, 1), cfg)


def attended_pairs(t, block_length):
    """(query, key) pairs a sequence of `t` clean tokens attends under the
    two-stream block mask: T(T - B)/2 noised-to-clean + TB noised-to-own
    + T(T + B)/2 clean-to-clean."""
    return float(t) * t + float(t) * block_length


def required_pairs(cfg, t):
    """Pairs the mathematics requires over all layers, a sequence: every
    layer's T^2 + TB, less the last layer's clean queries — T(T + B)/2 —
    whose result only the experts of that layer read, and nothing reads
    theirs (the head reads the noised half)."""
    b = cfg["block_length"]
    return (cfg["num_hidden_layers"] * attended_pairs(t, b)
            - t * (t + b) / 2.0)


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
            "moe_router_outputs": cfg["num_router_outputs"]}


def expected_expert_rows(cfg, tokens):
    return _sparse.expected_expert_rows(_as_sparse(cfg), tokens)


def train_flops(cfg, job, rows):
    """Operations the forward and backward passes of one step REQUIRE (no
    recomputation, no padding, nothing for a pair outside the mask): 6 per
    matrix weight per row that meets it over the 2T-token stream —
    attention's projections and the router for every token, an expert's
    three matrices for the EXPECTED 8 x held/128 rows a token that is not
    the mask token and for ONE row a masked position ((1 + t_min) / 2 of
    the noised half; they go as one lump to 8 experts of which one is held
    here: the configuration's `assumed.weights`) —, except that in the LAST layer the clean half
    meets only W_k and W_v (what it feeds the noised half; nothing reads
    its own result); attention's two products, forward and backward, over
    `required_pairs`; the head over the EXPECTED masked positions
    T (1 + t_min) / 2 a sequence."""
    d, hd, t = cfg["hidden_size"], cfg["head_dim"], job["seq_len"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = cfg["num_hidden_layers"]
    tokens = rows * t                    # of one half of the stream
    per_token = (d * (hq + 2 * hkv) * hd + hq * hd * d
                 + d * cfg["num_router_outputs"])
    expert = 3 * d * cfg["moe_intermediate_size"]
    masked = tokens * (1.0 + cfg["noise"]["t_min"]) / 2.0
    unmasked = (n - 1) * tokens + n * (tokens - masked)
    expert_rows = expected_expert_rows(cfg, unmasked) + n * masked
    return ((2 * n - 1) * 6.0 * tokens * per_token
            + 6.0 * expert_rows * expert
            + 6.0 * tokens * d * 2 * hkv * hd
            + 3.0 * rows * required_pairs(cfg, t) * hq * 2 * 2 * hd
            + 6.0 * masked * d * cfg["vocab_size"])


# -- what each Pallas kernel of this family's step must do, per step -----
#
# {kernel name as the device trace shows it: (operations, bytes)}, on the
# rule of train_flops: the attention kernels are held to the pairs the
# mathematics requires (`required_pairs`: the last layer's clean queries
# are computed by the call and required by nothing, so they read as lost
# roofline, not as work). Under remat the forward runs twice a layer, the
# backward once; the grouped products once, over the 2T-token stream.

def kernel_costs(cfg, job, rows, expert_rows=None):
    hd, t = cfg["head_dim"], job["seq_len"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = float(cfg["num_hidden_layers"])
    forwards = 2 if job.get("remat") else 1
    pairs = rows * required_pairs(cfg, t)
    stream = rows * 2.0 * t
    qkv_bytes = stream * hd * 2.0          # one head of q, k, v, dO or out
    costs = dict(_sparse.kernel_costs(
        _as_sparse(cfg), dict(job, seq_len=2 * t), rows, expert_rows))
    del costs["flash_fwd_resident"]
    costs.update({
        # q in and the result out once, k and v once a kv head (bfloat16);
        # lse and the pairs counted out (float32 a row and query head)
        "bdiff_fwd": (forwards * pairs * hq * 2 * 2 * hd,
                      forwards * n * (qkv_bytes * (2 * hq + 2 * hkv)
                                      + stream * 4.0 * 2 * hq)),
        # five products a pair (scores again, dp, dq, dk, dv); in: q, dO,
        # k, v, lse and delta; out: dq, dk, dv
        "bdiff_bwd": (pairs * hq * 5 * 2 * hd,
                      n * (qkv_bytes * (3 * hq + 4 * hkv)
                           + stream * 4.0 * 2 * hq)),
    })
    return costs
