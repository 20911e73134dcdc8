"""How a `family: gpt` configuration is handed to the program under test:
`edl_tpu/models/gpt.py` for the model and its loss, the reference's seeded
weights relabelled into the program's parameter tree. Nothing here
computes a number that is compared."""

import jax
import jax.numpy as jnp


def build_model(cfg, job):
    from edl_tpu.models import gpt
    d = cfg["n_embd"]
    return gpt.Gpt(vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
                   d_model=d, num_heads=cfg["n_head"],
                   mlp_dim=cfg.get("n_inner") or 4 * d,
                   max_len=cfg["n_positions"], dtype=jnp.bfloat16,
                   remat=bool(job.get("remat", False)), use_flash=None)


def to_program(w, cfg):
    """Reference weights -> the flax tree of `models.gpt.Gpt` (params);
    a relabelling: GPT-2's fused c_attn is cut into query/key/value."""
    d, h = cfg["n_embd"], cfg["n_head"]
    hd = d // h
    ln = lambda g, b: {"scale": g, "bias": b}
    params = {"word_embed": {"embedding": w["wte"]},
              "pos_embed": {"embedding": w["wpe"]},
              "ln_final": ln(w["ln_f_g"], w["ln_f_b"])}
    for i in range(cfg["n_layer"]):
        qkv_w = jnp.split(w["attn_w"][i], 3, axis=-1)
        qkv_b = jnp.split(w["attn_b"][i], 3, axis=-1)
        att = {n: {"kernel": kw.reshape(d, h, hd), "bias": kb.reshape(h, hd)}
               for n, kw, kb in zip(("query", "key", "value"), qkv_w, qkv_b)}
        att["out"] = {"kernel": w["proj_w"][i].reshape(h, hd, d),
                      "bias": w["proj_b"][i]}
        params["block_%d" % i] = {
            "ln_attn": ln(w["ln_1_g"][i], w["ln_1_b"][i]),
            "attention": att,
            "ln_mlp": ln(w["ln_2_g"][i], w["ln_2_b"][i]),
            "mlp_up": {"kernel": w["fc_w"][i], "bias": w["fc_b"][i]},
            "mlp_down": {"kernel": w["out_w"][i], "bias": w["out_b"][i]}}
    return params, None


def train_parts(cfg, job):
    """(loss_fn, has_aux, expected (params, extra) shapes): the program's
    own `create_model_and_loss`, traced abstractly so that its eager
    initialisation costs no device time."""
    from edl_tpu.models import gpt
    box = {}

    def build():
        _, params, loss_fn = gpt.create_model_and_loss(
            model=build_model(cfg, job), dummy_seq=16)
        box["loss_fn"] = loss_fn
        return params, None

    shapes = jax.eval_shape(build)
    return box["loss_fn"], False, shapes


def make_batch(cfg, job, key, rows):
    return {"input_ids": jax.random.randint(
        key, (rows, job["seq_len"]), 0, cfg["vocab_size"], jnp.int32)}


def train_flops(cfg, job, rows):
    """Operations the forward and backward passes of one step REQUIRE
    (no recomputation): 6 per matrix weight per token, the tied head
    included, plus causal attention's 2 products over the half of the
    score matrix that is not masked."""
    d, n, t = cfg["n_embd"], cfg["n_layer"], job["seq_len"]
    f = cfg.get("n_inner") or 4 * d
    weights = n * (4 * d * d + 2 * d * f) + cfg["vocab_size"] * d
    attn = n * 2 * 2 * (t / 2.0) * d  # per token, forward
    return rows * t * (6.0 * weights + 3.0 * attn)


def build_engine(cfg, job, params, quantized=False):
    """The serving system under test: `DecodeEngine` with the default
    `DecodeAdmission`, prefix cache on, monolithic prefill. `quantized`
    is the CONTROL: the program's own int8 weight path."""
    from edl_tpu.serve.decode_engine import DecodeEngine
    if quantized:
        from edl_tpu.ops.quant import quantize_tree
        params = quantize_tree(params)
    return DecodeEngine(build_model(cfg, job), params, slots=job["slots"],
                        prefix_cache=True, prefill_chunk=0)


def decode_bytes(cfg, job, live_positions):
    """Bytes one decode step MUST read: every weight once in the type it
    is stored in (float32), and the cached keys and values (bfloat16) of
    the positions that are live."""
    d, n = cfg["n_embd"], cfg["n_layer"]
    f = cfg.get("n_inner") or 4 * d
    weights = (n * (4 * d * d + 2 * d * f + 9 * d + f) + 2 * d
               + (cfg["vocab_size"] + cfg["n_positions"]) * d)
    return 4.0 * weights + live_positions * n * 2 * d * 2.0
