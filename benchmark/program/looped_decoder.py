"""How a `family: looped_decoder` configuration — a DENSE decoder whose
stack of layers is run `total_ut_steps` times over with the same weights,
an exit gate after every pass and a loss that is an expectation over exits
— is handed to the program under test: `edl_tpu/models/sparse_decoder.py`
for the model, its loss and its per-pass counters (the trainer's extra
state), the reference's seeded weights relabelled into the program's
parameter tree. Nothing here computes a number that `correct` compares; the
counts below are what the utilization and roofline metrics divide by."""

import jax
import jax.numpy as jnp

from benchmark.lib.harness import BenchError, load_module

_sparse = load_module("program", "sparse_decoder")
band_pairs, make_batch = _sparse.band_pairs, _sparse.make_batch


def _program():
    from edl_tpu.models import sparse_decoder
    if not hasattr(sparse_decoder, "LOOP_COUNTERS"):
        raise BenchError("this program's decoder has no loop_steps: it "
                         "cannot run a stack of layers more than once")
    return sparse_decoder


def build_model(cfg, job):
    sparse_decoder = _program()
    n = cfg["num_hidden_layers"]
    return sparse_decoder.SparseDecoder(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_layers=n, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=0, experts_held=0, first_expert=0, experts_per_token=0,
        expert_width=0, rope_layout=(1,) * n,
        window_layout=tuple(int(kind != "full_attention")
                            for kind in cfg["layer_types"][:n]),
        window=cfg["sliding_window"] or 0,
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        dtype=jnp.bfloat16, remat=bool(job.get("remat", False)),
        use_flash=None, expert_activation=cfg["hidden_act"],
        dense_width=cfg["intermediate_size"], sandwich_norm=True,
        loop_steps=cfg["total_ut_steps"],
        exit_entropy_weight=cfg["exit_entropy_weight"])


def to_program(w, cfg):
    """Reference weights -> (params, extra) of the looped `SparseDecoder`;
    a relabelling that copies nothing: projections are cut into heads by a
    reshape, everything else is the tensor itself. ONE tensor a shared
    weight, on both sides."""
    sparse_decoder = _program()
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = cfg["num_hidden_layers"]
    params = {"embed": w["embed"], "lm_head": w["head"],
              "norm_final": {"scale": w["g_f"]},
              "exit_gate": w["w_e"], "exit_gate_bias": w["b_e"]}
    for i in range(n):
        lw = {k.split("/", 1)[1]: v for k, v in w.items()
              if k.startswith("%d/" % i)}
        params["layer_%d" % i] = {
            "norm_attn": {"scale": lw["g1"]},
            "norm_attn_out": {"scale": lw["g2"]},
            "norm_moe": {"scale": lw["g3"]},
            "norm_ffn_out": {"scale": lw["g4"]},
            "query": lw["w_q"].reshape(d, hq, hd),
            "key": lw["w_k"].reshape(d, hkv, hd),
            "value": lw["w_v"].reshape(d, hkv, hd),
            "out": lw["w_o"].reshape(hq, hd, d),
            "ffn_gate_up": lw["w_gate_up"], "ffn_down": lw["w_down"]}
    return params, sparse_decoder.init_counters(
        n, routed=False, loop_steps=cfg["total_ut_steps"])


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


def matrix_weights_per_token(cfg):
    """(a layer's, the head's) matrix weights that a token meets in ONE
    pass: attention's four projections and the feed-forward part's three
    matrices; the head with the exit gate's vector."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = (d * (hq + 2 * hkv) * hd + hq * hd * d
             + 3 * d * cfg["intermediate_size"])
    return layer, d * cfg["vocab_size"] + d


def train_flops(cfg, job, rows):
    """Operations the forward and backward passes of one step REQUIRE (no
    recomputation, no padding, nothing for a pair outside the causal
    mask): every one of the `total_ut_steps` passes is required — the
    loop IS the model —, so 6 per matrix weight per token PER PASS, the
    head and the gate with it, and attention's two products over the
    causal pairs, forward and twice backward, per layer and pass (the
    accepted families' count: the scores' rebuilding in the backward is
    not required)."""
    t = job["seq_len"]
    passes, n = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    layer, head = matrix_weights_per_token(cfg)
    return passes * (
        6.0 * rows * t * (n * layer + head)
        + n * 3.0 * rows * band_pairs(t) * (
            cfg["num_attention_heads"] * 2 * 2 * cfg["head_dim"]))


# -- what each Pallas kernel of this family's step must do, per step -----
#
# {kernel name as the device trace shows it: (operations, bytes)}: the
# least the kernel's algorithm needs for THE CALLS ONE STEP MAKES: a layer
# is applied `total_ut_steps` times, and under remat by layer every
# application runs the flash forward TWICE (the band kernels name no
# residual) and the backward once.

def kernel_costs(cfg, job, rows, expert_rows=None):
    hd, t = cfg["head_dim"], job["seq_len"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    applications = float(cfg["total_ut_steps"] * cfg["num_hidden_layers"])
    forwards = 2 if job.get("remat") else 1
    pairs = rows * band_pairs(t)
    tokens = rows * float(t)
    return {
        # q and the result once, k and v once a kv head (bfloat16); lse out
        # (float32 a row and query head)
        "flash_fwd_resident": (
            applications * forwards * pairs * hq * 2 * 2 * hd,
            applications * forwards * tokens * (
                hd * (2 * hq + 2 * hkv) * 2.0 + 4.0 * hq)),
        # five products a pair (scores again, dp, dq, dk, dv); in: q, dO,
        # k, v, lse and delta; out: dq, dk, dv
        "flash_bwd": (
            applications * pairs * hq * 5 * 2 * hd,
            applications * tokens * (hd * (3 * hq + 4 * hkv) * 2.0
                                     + 8.0 * hq))}
