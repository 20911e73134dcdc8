"""How a `family: hybrid_ssm_decoder` configuration — a decoder whose every
layer is ONE sublayer behind one norm: a Mamba-2 state-space mixer, a
grouped-query attention without positions, or a sigmoid-routed mixture of
UNGATED squared-ReLU experts beside an ungated shared expert, in the order
the configuration's `hybrid_override_pattern` gives — is handed to the
program under test: `edl_tpu/models/sparse_decoder.py` for the model, its
loss and its routing and scan counters (the trainer's extra state), the
reference's seeded weights relabelled into the program's parameter tree.
Nothing here computes a number that `correct` compares; the counts below are
what the utilization and roofline metrics divide by."""

import jax
import jax.numpy as jnp

from benchmark.lib.harness import BenchError, load_module

_sparse = load_module("program", "sparse_decoder")
band_pairs, make_batch = _sparse.band_pairs, _sparse.make_batch


def _program():
    from edl_tpu.models import sparse_decoder
    if not hasattr(sparse_decoder, "SSD_COUNTERS"):
        raise BenchError("this program's decoder has no SSD path: no "
                         "Mamba-2 mixer, no layer of one sublayer")
    return sparse_decoder


def layer_kinds(cfg):
    """One character a layer: M (Mamba-2), * (attention), E (experts)."""
    kinds = cfg["hybrid_override_pattern"]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set("M*E"):
        raise BenchError("hybrid_override_pattern %r for %d layers"
                         % (kinds, cfg["num_hidden_layers"]))
    return kinds


def shared_width(cfg):
    return (cfg["n_shared_experts"]
            * cfg["moe_shared_expert_intermediate_size"])


def build_model(cfg, job):
    sparse_decoder = _program()
    kinds = layer_kinds(cfg)
    n = len(kinds)
    return sparse_decoder.SparseDecoder(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_layers=n, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["num_router_outputs"],
        experts_held=cfg["n_routed_experts"],
        first_expert=cfg["first_expert"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        rope_layout=(0,) * n, window_layout=(0,) * n, window=0,
        rope_theta=float(cfg["rope_theta"]), eps=cfg["layer_norm_epsilon"],
        dtype=jnp.bfloat16, remat=bool(job.get("remat", False)),
        use_flash=None, router_input="moe_norm",
        expert_activation=cfg["mlp_hidden_act"], expert_gated=False,
        shared_expert_width=shared_width(cfg), shared_expert_gate=False,
        router_scoring="sigmoid",
        routed_scaling=cfg["routed_scaling_factor"],
        mixer_layout=tuple(2 if k == "M" else 0 for k in kinds),
        part_layout=tuple(2 if k == "E" else 1 for k in kinds),
        conv_width=cfg["conv_kernel"], ssm_heads=cfg["mamba_num_heads"],
        ssm_head_dim=cfg["mamba_head_dim"], ssm_groups=cfg["n_groups"],
        ssm_state=cfg["ssm_state_size"], ssm_chunk=cfg["chunk_size"],
        ssm_first_head=cfg["first_mamba_head"])


def _mamba_to_program(lw, cfg):
    """A Mamba layer's tensors, the published column order z | x | B | C |
    dt relaid a GROUP at a time (the group's heads' z, their x, its B, its
    C; the convolution's channels and the steps likewise)."""
    d = cfg["hidden_size"]
    h, p, g, n = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                  cfg["n_groups"], cfg["ssm_state_size"])
    inner, wide = h * p, h // g * p
    cut = (0, inner, 2 * inner, 2 * inner + g * n, 2 * inner + 2 * g * n)

    def by_group(m, cols, lead):
        """columns [lo, hi) of m [..., all] as [..., G, (hi - lo) / G]."""
        part = jnp.moveaxis(m, lead, -1)[..., cols[0]:cols[1]]
        return part.reshape(part.shape[:-1] + (g, -1))

    w_in = lw["w_in"]
    zxbc = jnp.concatenate(
        [by_group(w_in, cut[i:i + 2], 1) for i in range(4)], axis=-1)
    conv_cut = (0, inner, inner + g * n, inner + 2 * g * n)
    lay = lambda m: jnp.concatenate(
        [by_group(m, conv_cut[i:i + 2], 0) for i in range(3)], axis=-1)
    conv = jnp.moveaxis(lay(lw["w_conv"]), 0, -1).reshape(
        g * (wide + 2 * n), -1)
    return {"norm_attn": {"scale": lw["g"]},
            "in_proj_zxbc": zxbc,
            "in_proj_dt": w_in[:, cut[4]:].reshape(d, g, h // g),
            "conv": conv, "conv_bias": lay(lw["b_conv"]).reshape(-1),
            "A_log": lw["a_log"], "dt_bias": lw["dt_bias"],
            "D": lw["d_skip"], "norm_ssm": {"scale": lw["g_n"]},
            "out": lw["w_o"].reshape(h, p, d)}


def to_program(w, cfg):
    """Reference weights -> (params, extra) of `SparseDecoder`: projections
    are cut into heads by a reshape, a Mamba layer's columns are relaid by
    group, everything else is the tensor itself."""
    sparse_decoder = _program()
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    kinds = layer_kinds(cfg)
    params = {"embed": w["embed"], "lm_head": w["head"],
              "norm_final": {"scale": w["g_f"]}}
    for i, kind in enumerate(kinds):
        lw = {k.split("/", 1)[1]: v for k, v in w.items()
              if k.startswith("%d/" % i)}
        if kind == "M":
            layer = _mamba_to_program(lw, cfg)
        elif kind == "*":
            layer = {"norm_attn": {"scale": lw["g"]},
                     "query": lw["w_q"].reshape(d, hq, hd),
                     "key": lw["w_k"].reshape(d, hkv, hd),
                     "value": lw["w_v"].reshape(d, hkv, hd),
                     "out": lw["w_o"].reshape(hq, hd, d)}
        else:
            layer = {"norm_moe": {"scale": lw["g"]}, "router": lw["w_r"],
                     "router_bias": lw["b_r"], "experts_up": lw["w_up"],
                     "experts_down": lw["w_down"], "shared_up": lw["w_su"],
                     "shared_down": lw["w_sd"]}
        params["layer_%d" % i] = layer
    return params, sparse_decoder.init_counters(len(kinds), scored=True,
                                                ssd=True)


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


def expected_expert_rows(cfg, tokens):
    """Rows the held experts of ONE expert layer serve a step under even
    routing."""
    return (tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / float(cfg["num_router_outputs"]))


def matrix_weights_per_token(cfg):
    """{part: matrix weights that EVERY token meets in one layer of the
    kind that has the part}: a Mamba layer's two projections and its
    convolution's taps; the attention's four projections; an expert
    layer's router and shared expert (two matrices); and the head. The
    routed experts are counted by their rows, the scan by `ssd_ops`."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    bc = 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    inner = h * p
    return {"mamba": (d * (2 * inner + bc + h)
                      + (inner + bc) * cfg["conv_kernel"] + inner * d),
            "attention": d * (hq + 2 * hkv) * hd + hq * hd * d,
            "router": d * cfg["num_router_outputs"],
            "shared": 2 * d * shared_width(cfg),
            "head": d * cfg["vocab_size"]}


def ssd_ops(cfg, tokens):
    """Operations the SSD scan of ONE Mamba layer requires in its chunked
    form at the configuration's chunk C, forward: {part: operations}. Inside
    a chunk only the pairs (i, j <= i) count, C (C + 1) / 2 of them: C B^T
    once a GROUP (2 N a pair) and its product with dt * X once a head (2 P
    a pair); across chunks, a head and token at a time, the read (exp(gamma)
    C) S^T and the write (exp(..) dt X)^T B at 2 P N each."""
    c = float(cfg["chunk_size"])
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    pairs = tokens * (c + 1) / 2.0
    return {"cb": g * pairs * 2 * n, "intra": h * pairs * 2 * p,
            "read": tokens * h * 2 * p * n, "write": tokens * h * 2 * p * n}


def train_flops(cfg, job, rows):
    """Operations the forward and backward passes of one step REQUIRE (no
    recomputation, no padding, nothing for a pair outside a causal mask):
    6 per matrix weight per row that meets it — a Mamba layer's
    projections and convolution, the attention's projections, an expert
    layer's router and shared expert for every token, a routed expert's
    TWO matrices for the EXPECTED 6 x held/128 rows a token, the head for
    every token; the attention core over the causal pairs and the SSD scan
    in its chunked form (`ssd_ops`), forward and twice that backward."""
    t = job["seq_len"]
    tokens = rows * float(t)
    kinds = layer_kinds(cfg)
    n_m, n_a, n_e = (kinds.count(k) for k in "M*E")
    w = matrix_weights_per_token(cfg)
    expert = 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    per_token = (n_m * w["mamba"] + n_a * w["attention"]
                 + n_e * (w["router"] + w["shared"]) + w["head"])
    return (6.0 * tokens * per_token
            + n_e * 6.0 * expected_expert_rows(cfg, tokens) * expert
            + n_a * 3.0 * rows * band_pairs(t)
            * cfg["num_attention_heads"] * 2 * 2 * cfg["head_dim"]
            + n_m * 3.0 * sum(ssd_ops(cfg, tokens).values()))


# -- what each Pallas kernel of this family's step must do, per step -----
#
# {kernel name as the device trace shows it: (operations, bytes)}: the
# least the kernel's algorithm needs for THE CALLS ONE STEP MAKES, the same
# whatever implements it. Under remat by layer: `ssd_fwd` once a Mamba layer
# (the layer saves its result and states, ops/ssd.py:SAVED_UNDER_REMAT) and
# `ssd_bwd` once; the attention layer's flash forward TWICE (the band
# kernels name no residual) and its backward once; the grouped products
# once, in the expert layers alone, at an ungated expert's TWO matrices.
# `expert_rows`: the rows the held experts really served a step, AVERAGED
# OVER ALL THE ENTRIES the counters hold (benchmark/lib/kernel_readers.py:
# expert_rows_per_step) — the Mamba and attention layers' zeros among them.

def _resident(cfg, job):
    from edl_tpu.ops import flash_attention
    return (job["seq_len"] * 2 * cfg["head_dim"] * 2
            <= flash_attention._RESIDENT_KV_BYTES)


def kernel_costs(cfg, job, rows, expert_rows=None):
    d, hd, t = cfg["hidden_size"], cfg["head_dim"], job["seq_len"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    kinds = layer_kinds(cfg)
    n_m, n_a, n_e = (float(kinds.count(k)) for k in "M*E")
    forwards = 2 if job.get("remat") else 1
    tokens = rows * float(t)
    pairs = rows * band_pairs(t)
    costs = {
        # q and the result once, k and v once a kv head (bfloat16); lse out
        # (float32 a row and query head)
        "flash_fwd_resident" if _resident(cfg, job) else "flash_fwd_stream": (
            n_a * forwards * pairs * hq * 2 * 2 * hd,
            n_a * forwards * tokens * (hd * (2 * hq + 2 * hkv) * 2.0
                                       + 4.0 * hq)),
        # five products a pair (scores again, dp, dq, dk, dv); in: q, dO,
        # k, v, lse and delta; out: dq, dk, dv
        "flash_bwd": (
            n_a * pairs * hq * 5 * 2 * hd,
            n_a * tokens * (hd * (3 * hq + 4 * hkv) * 2.0 + 8.0 * hq))}
    # the grouped products of an ungated expert: up and down forward, their
    # two dx products backward (moe_gmm), the two dw products (moe_tgmm)
    if expert_rows is None:
        served = n_e * expected_expert_rows(cfg, tokens)
    else:
        served = expert_rows * len(kinds)
    weights = 2 * d * f
    # rows in and out of both products (bfloat16) and each held expert's
    # matrices once (bfloat16 in, float32 out of the dw products)
    row_bytes = 2.0 * served * (d + f + f + d)
    costs["moe_gmm"] = (2 * 2.0 * served * weights,
                        2 * (row_bytes + n_e * 2.0 * held * weights))
    costs["moe_tgmm"] = (2.0 * served * weights,
                         row_bytes + n_e * 4.0 * held * weights)
    # the sequential part of the SSD scan, a (head, chunk) of C tokens at a
    # time. Forward: the chunk's own pairs against dt * X, the read and the
    # write (`ssd_ops` less C B^T, which is formed outside the kernel); in
    # x (bfloat16), dt (float32) and the group's B and C once a GROUP, out
    # the result (bfloat16) and the chunk-end state (float32). Backward:
    # each of the three products' two cotangents; in what the forward read,
    # dY and the state that entered, out dx, ddt and, once a group, dB, dC.
    ops = ssd_ops(cfg, tokens)
    c = float(cfg["chunk_size"])
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    operands = tokens * (h * (2.0 * p + 4.0) + g * 2 * 2.0 * n)
    states = tokens / c * h * 4.0 * p * n
    scan = ops["intra"] + ops["read"] + ops["write"]
    costs["ssd_fwd"] = (n_m * scan,
                        n_m * (operands + tokens * h * 2.0 * p + states))
    costs["ssd_bwd"] = (n_m * 2 * scan,
                        n_m * (2 * operands + tokens * h * 2.0 * p + states))
    return costs
