"""How a `family: sparse_decoder` configuration is handed to the program
under test: `edl_tpu/models/sparse_decoder.py` for the model, its loss and
its routing counters (the trainer's extra state), the reference's seeded
weights relabelled into the program's parameter tree. Nothing here
computes a number that `correct` compares; the counts below are what the
utilization and roofline metrics divide by."""

import jax
import jax.numpy as jnp


def build_model(cfg, job):
    from edl_tpu.models import sparse_decoder
    n = cfg["num_hidden_layers"]
    return sparse_decoder.SparseDecoder(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_layers=n, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["moe_router_outputs"],
        experts_held=cfg["moe_num_primary_experts"],
        first_expert=cfg["first_expert"],
        experts_per_token=cfg["moe_num_active_primary_experts"],
        expert_width=cfg["moe_ffn_hidden_size"],
        rope_layout=tuple(cfg["rope_layout"][:n]),
        window_layout=tuple(cfg["sliding_window_layout"][:n]),
        window=cfg["sliding_window_size"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        dtype=jnp.bfloat16, remat=bool(job.get("remat", False)),
        use_flash=None)


def to_program(w, cfg):
    """Reference weights -> (params, extra) of `SparseDecoder`; a
    relabelling that copies nothing: projections are cut into heads by a
    reshape, everything else is the tensor itself."""
    from edl_tpu.models import sparse_decoder
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
            "router": lw["w_r"],
            "query": lw["w_q"].reshape(d, hq, hd),
            "key": lw["w_k"].reshape(d, hkv, hd),
            "value": lw["w_v"].reshape(d, hkv, hd),
            "out": lw["w_o"].reshape(hq, hd, d),
            "norm_moe": {"scale": lw["g2"]},
            "experts_gate_up": lw["w_gate_up"],
            "experts_down": lw["w_down"]}
    return params, sparse_decoder.init_counters(n)


def train_parts(cfg, job):
    """(loss_fn, has_aux, expected (params, extra) shapes): the program's
    own `create_model_and_loss`, traced abstractly so that its eager
    initialisation costs no device time."""
    from edl_tpu.models import sparse_decoder
    box = {}

    def build():
        _, params, extra, loss_fn = sparse_decoder.create_model_and_loss(
            build_model(cfg, job), dummy_seq=16)
        box["loss_fn"] = loss_fn
        return params, extra

    shapes = jax.eval_shape(build)
    return box["loss_fn"], True, shapes


def make_batch(cfg, job, key, rows):
    """ids drawn uniformly from the rows of the vocabulary held here."""
    return {"input_ids": jax.random.randint(
        key, (rows, job["seq_len"]), 0, cfg["vocab_size"], jnp.int32)}


def band_pairs(t, window=None):
    """(query, key) pairs inside the causal band of one sequence."""
    if window is None or window >= t:
        return t * (t + 1) / 2.0
    return window * (window + 1) / 2.0 + (t - window) * float(window)


def _layers(cfg):
    n = cfg["num_hidden_layers"]
    return [cfg["sliding_window_size"] if flag else None
            for flag in cfg["sliding_window_layout"][:n]]


def expected_expert_rows(cfg, tokens):
    """Rows the held experts serve a step under uniform routing."""
    return (tokens * cfg["moe_num_active_primary_experts"]
            * cfg["moe_num_primary_experts"] / float(
                cfg["moe_router_outputs"]))


def train_flops(cfg, job, rows):
    """Operations the forward and backward passes of one step REQUIRE (no
    recomputation, no padding): 6 per matrix weight per row that meets it
    — attention's projections and the router for every token, an
    expert's three matrices for the EXPECTED 6 x held/64 rows a token,
    the head for every token — plus attention's two products over the
    pairs INSIDE the band only."""
    d, hd, t = cfg["hidden_size"], cfg["head_dim"], job["seq_len"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    tokens = rows * t
    per_token = (d * (hq + 2 * hkv) * hd + hq * hd * d
                 + d * cfg["moe_router_outputs"])
    expert = 3 * d * cfg["moe_ffn_hidden_size"]
    total = 0.0
    for window in _layers(cfg):
        total += 6.0 * tokens * per_token
        total += 6.0 * expected_expert_rows(cfg, tokens) * expert
        total += 3.0 * rows * band_pairs(t, window) * hq * 2 * 2 * hd
    return total + 6.0 * tokens * d * cfg["vocab_size"]


# -- what each Pallas kernel of this family's step must do, per step -----
#
# {kernel name as the device trace shows it: (operations, bytes)}: the
# least the algorithm needs for the calls one step makes — under remat the
# flash forward runs twice a layer, the grouped products once (the layer
# saves their results) —, for the `<kernel>_roofline_pct` readers.
# `expert_rows`: the rows the held experts really served a step per layer
# (the program's counter), the expected count where none.

def kernel_costs(cfg, job, rows, expert_rows=None):
    d, hd, t = cfg["hidden_size"], cfg["head_dim"], job["seq_len"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, held = cfg["moe_ffn_hidden_size"], cfg["moe_num_primary_experts"]
    forwards = 2 if job.get("remat") else 1
    if expert_rows is None:
        expert_rows = expected_expert_rows(cfg, rows * t)
    flash_ops = flash_bytes = 0.0
    for window in _layers(cfg):
        flash_ops += rows * band_pairs(t, window) * hq * 2 * 2 * hd
        # q and the result once, k and v once per kv head, bfloat16
        flash_bytes += rows * t * hd * (2 * hq + 2 * hkv) * 2.0
    n = float(cfg["num_hidden_layers"])
    weights = 3 * d * f              # one expert's three matrices
    # forward: rows x (gate, up, down); backward dx: the same again
    gmm_ops = n * 2 * 2.0 * expert_rows * weights
    # rows in and out (bfloat16) and each held expert's matrices once
    # (bfloat16) per product
    gmm_bytes = n * 2 * (
        2.0 * expert_rows * (d + 2 * f + f + d) + 2.0 * held * weights)
    tgmm_ops = n * 2.0 * expert_rows * weights
    tgmm_bytes = n * (2.0 * expert_rows * (d + 2 * f + f + d)
                      + 4.0 * held * weights)
    return {"flash_fwd_resident": (forwards * flash_ops,
                                   forwards * flash_bytes),
            "moe_gmm": (gmm_ops, gmm_bytes),
            "moe_tgmm": (tgmm_ops, tgmm_bytes)}
