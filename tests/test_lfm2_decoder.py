"""The decoder whose layers are a GATED SHORT CONVOLUTION or grouped-query
attention behind q/k norm, the leading one over a dense feed-forward part
and the others over sigmoid-routed experts, its head TIED to its embedding
(`models/sparse_decoder.py`: `_short_conv`, `mixer_layout` 4,
`tie_embeddings`, `router_norm_eps`; the `lfm2-8b-a1b` configuration).

- the decoder's loss, counters and every gradient leaf against
  `benchmark/reference/lfm2-8b-a1b.py` at the `tiny` size in float32 (plain
  paths and kernels), bfloat16 inside the tiny limits, the int8 control far
  outside them, and what each compared number guards, by omission, on three
  seeds;
- causality, the tied matrix's gradient, what a conv layer refuses;
- the shares add up: four expert shares to the uncut expert layer, four
  vocabulary slices to the uncut logits' columns; and the three accepted
  sigmoid-routed models are the programs they were;
- the three scopes in the step's jaxpr and in the registry, the
  configuration against the published one, the counters through
  `ElasticTrainer` to the new readers.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxpr_kernels import (gradient_jaxpr, gradient_kernel_calls,
                           traced_gradient)

from benchmark.lib import harness, kernel_readers
from edl_tpu.models import sparse_decoder
from edl_tpu.obs import devtime
from edl_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "lfm2-8b-a1b"
TRAFFIC = "tokens-8192-conv"
CELL = "lfm2-conv-train-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("shortconv_gate_device_ms", "shortconv_gate_roofline_pct",
               "shortconv_gate_absmax")
SEEDS = (11, 3, 23)
#: the catalog row's `config` for LFM2-8B-A1B, as published
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention",
                    "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


def _cfg():
    return harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                          CONFIG + ".json"))


def _tiny_cfg():
    cfg = _cfg()
    return dict(cfg, **cfg["tiny"])


def _job():
    return harness.load_json(os.path.join(REPO, "benchmark", "traffic",
                                          TRAFFIC + ".json"))


def _tiny_limits():
    return _job()["tiny"]["limits"]


@functools.lru_cache(maxsize=None)
def _modules():
    cfg = _tiny_cfg()
    return (harness.load_module("reference", CONFIG),
            harness.load_module("program", cfg["family"]))


def _seeded(seed):
    """(cfg, ref, fam, weights, batch) at the tiny size: 2 x 48 tokens."""
    cfg = _tiny_cfg()
    ref, fam = _modules()
    w = ref.init_weights(cfg, jax.random.PRNGKey(seed))
    batch = fam.make_batch(cfg, _job()["tiny"], jax.random.PRNGKey(seed + 1),
                           2)
    assert batch["input_ids"].shape == (2, 48)
    return cfg, ref, fam, w, batch


# -- the decoder against the plain reference ----------------------------------

@pytest.fixture(scope="module")
def lfm2():
    return _seeded(SEEDS[0])


@functools.lru_cache(maxsize=None)
def _program_step(dtype, remat, use_flash):
    """One jitted (params, batch) -> ((loss, extra), grads) a variant: the
    seeds share its trace."""
    cfg = _tiny_cfg()
    _, fam = _modules()
    model = fam.build_model(cfg, {"remat": remat}).clone(
        dtype=dtype, use_flash=use_flash)
    _, _, extra, loss_fn = sparse_decoder.create_model_and_loss(model)
    return jax.jit(lambda p, batch: jax.value_and_grad(
        lambda p: loss_fn(p, extra, batch, None), has_aux=True)(p))


def _loss_and_grad(fam, cfg, w, batch, dtype, remat=True, use_flash=None):
    params, _ = fam.to_program(w, cfg)
    (loss, extra), grads = _program_step(dtype, remat, use_flash)(params,
                                                                  batch)
    return loss, grads, extra


def _leaves(tree):
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_leaves_with_path(tree)}


def _leaf_names():
    cfg = _tiny_cfg()
    _, fam = _modules()
    return sorted(_leaves(fam.train_parts(cfg, {"remat": True})[2][0]))


def _distance(got, want):
    num = sum(float(jnp.sum(jnp.square(got[k] - want[k]))) for k in want)
    den = sum(float(jnp.sum(jnp.square(want[k]))) for k in want)
    return (num / den) ** 0.5


@functools.lru_cache(maxsize=None)
def _sound_reference():
    cfg = _tiny_cfg()
    ref, _ = _modules()
    return jax.jit(lambda w, batch: ref.loss_and_grad(w, batch, cfg))


def _reference(seeded, w=None, fn=None, cfg=None):
    """(loss, the gradient's leaves in the program's layout)."""
    own_cfg, _, fam, own, batch = seeded
    loss, g = (fn or _sound_reference())(own if w is None else w, batch)
    return loss, _leaves(fam.to_program(g, cfg or own_cfg)[0])


@pytest.fixture(scope="module")
def reference(lfm2):
    return _reference(lfm2)


@pytest.fixture(scope="module", params=["plain", "kernels"])
def lfm2_float32(request, lfm2):
    cfg, _, fam, w, batch = lfm2
    loss, grads, extra = _loss_and_grad(
        fam, cfg, w, batch, jnp.float32,
        use_flash=request.param == "kernels")
    return loss, _leaves(grads), extra


def test_loss_and_counters_match_the_reference_float32(lfm2, reference,
                                                       lfm2_float32):
    cfg, ref, fam, w, batch = lfm2
    loss, _, extra = lfm2_float32
    np.testing.assert_allclose(loss, reference[0], rtol=2e-5)
    c = extra["counters"]
    assert sorted(c) == sorted(
        sparse_decoder.COUNTERS + sparse_decoder.ROUTE_COUNTERS
        + sparse_decoder.SHORTCONV_COUNTERS + ("steps",))
    assert float(c["steps"]) == 1.0
    assert float(c["rows_dropped"].sum()) == 0.0
    conv = np.asarray(fam.conv_layers(cfg), bool)
    experts = ~np.asarray(fam.dense_layers(cfg), bool)
    assert conv.tolist() == [True, False, True, True, True]
    assert experts.tolist() == [False, True, True, True, True]
    want = jax.jit(lambda w: ref.layer_counts(w, batch["input_ids"], cfg))(w)
    for name in ("rows_held", "route_bias_flips"):
        np.testing.assert_array_equal(c[name], want[name])
    for name in ("route_weight_sum", "conv_gate_absmax"):
        np.testing.assert_allclose(c[name], want[name], rtol=1e-5)
    # routed_scaling_factor 1 a token: the weights are normalised
    np.testing.assert_allclose(
        np.asarray(c["route_weight_sum"])[experts],
        cfg["routed_scaling_factor"] * batch["input_ids"].size, rtol=1e-5)
    # a layer without the part counts zeros
    for name in sparse_decoder.COUNTERS + sparse_decoder.ROUTE_COUNTERS:
        assert (np.asarray(c[name])[~experts] == 0).all()
    assert (np.asarray(c["rows_held"])[experts] > 0).all()
    assert (np.asarray(c["route_bias_flips"])[experts] > 0).all()
    top = np.asarray(c["conv_gate_absmax"])
    assert (top[conv] > 0).all() and (top[~conv] == 0).all()


@pytest.mark.parametrize("leaf", _leaf_names())
def test_gradient_leaf_matches_reference_float32(lfm2_float32, reference,
                                                 leaf):
    _, grads, _ = lfm2_float32
    want = reference[1][leaf]
    assert "lm_head" not in leaf        # the head is `embed`
    if "router_bias" in leaf:       # in the choice alone: nothing reaches it
        assert float(jnp.abs(grads[leaf]).max()) == 0.0
        assert float(jnp.abs(want).max()) == 0.0
        return
    scale = float(jnp.abs(want).max())
    assert scale > 0          # every other tensor of the model learns
    np.testing.assert_allclose(grads[leaf], want, atol=2e-4 * scale,
                               rtol=2e-3)


def test_remat_changes_no_number(lfm2, lfm2_float32):
    cfg, _, fam, w, batch = lfm2
    loss, grads, _ = _loss_and_grad(fam, cfg, w, batch, jnp.float32,
                                    remat=False)
    np.testing.assert_allclose(loss, lfm2_float32[0], rtol=1e-6)
    assert _distance(_leaves(grads), lfm2_float32[1]) < 1e-5


@pytest.mark.parametrize("remat", [True, False])
def test_the_kernels_a_step_calls(lfm2, remat):
    """A conv layer calls no kernel; the one attention layer the resident
    flash forward (twice under remat: the band kernels name no residual) and
    its backward; the four expert layers the grouped products."""
    cfg, _, fam, w, batch = lfm2
    calls = gradient_kernel_calls(fam, cfg, w, batch, remat)
    assert calls["flash_fwd_resident"] == (2 if remat else 1)
    assert calls["flash_bwd"] == 1
    assert calls["moe_gmm"] == 16 and calls["moe_tgmm"] == 8
    assert set(calls) == {"flash_fwd_resident", "flash_bwd", "moe_gmm",
                          "moe_tgmm"}


@pytest.mark.parametrize("remat", [True, False])
def test_the_three_scopes_are_in_the_step_and_in_the_registry(lfm2, remat):
    cfg, _, fam, w, batch = lfm2
    scopes = ("mixer.conv.in_proj", "mixer.conv.gate", "mixer.conv.out")
    for scope in scopes:
        assert devtime.SCOPES[scope] == ("mixer", 63)
        assert devtime.scope_of(
            "jit(step)/transpose(jvp(layer_3/%s))/mul" % scope) == (
            scope, "bwd")
    text = str(gradient_jaxpr(fam, cfg, w, batch, remat).pretty_print(
        name_stack=True))
    for scope in scopes + ("attn.full", "ffn.dense", "moe.route", "lm_head",
                           "embed"):
        assert scope in text, scope
    for other in ("mixer.gdn", "mixer.kda", "ssm.", "attn.latent"):
        assert other not in text
    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        doc = f.read()
    for scope in scopes:
        assert "| `%s` | mixer | 63 |" % scope in doc


@functools.lru_cache(maxsize=None)
def _bfloat16(seed):
    cfg, _, fam, w, batch = seeded = _seeded(seed)
    loss, grads, _ = _loss_and_grad(fam, cfg, w, batch, jnp.bfloat16)
    return seeded, (loss, _leaves(grads))


def _errors(got, want):
    (loss, grads), (want_loss, want_grads) = got, want
    return (abs(float(loss) - float(want_loss)) / float(want_loss),
            _distance(grads, want_grads))


@pytest.mark.parametrize("seed", SEEDS)
def test_matches_reference_bfloat16(seed):
    """bf16 activations and products as the cell runs them: inside the
    tiny limits, by the loss and by the whole gradient in relative L2."""
    limits = _tiny_limits()
    seeded, got = _bfloat16(seed)
    loss_err, grad_err = _errors(got, _reference(seeded))
    assert loss_err < limits["loss_rel_err"]
    assert grad_err < limits["grad_rel_err"]


@pytest.mark.parametrize("seed", SEEDS)
def test_int8_control_is_far_from_the_reference(seed):
    """The control `correct` has to refuse: outside the tiny limits."""
    cfg, ref, fam, w, batch = seeded = _seeded(seed)
    _, g8 = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg, "int8"))(w)
    assert _distance(_leaves(fam.to_program(g8, cfg)[0]),
                     _reference(seeded)[1]) \
        > 5 * _tiny_limits()["grad_rel_err"]


# what the tiny limits guard: each omission is made in the REFERENCE, and the
# bfloat16 program, which does not make it, must then read outside the
# gradient's limit — {name: ("patch", the reference's function to replace,
# its replacement given the sound one and the module), ("weights", the
# tensor of every layer that has it, its replacement) or ("config", keys)}

def _one_token_ahead(sound, ref):
    """The taps laid one token late: tap j reads y_{t-1+j}, so the last one
    reads the NEXT token."""
    def short_conv(y, w_conv):
        ahead = jnp.concatenate([y[:, 1:], jnp.zeros_like(y[:, :1])], axis=1)
        return sound(ahead, w_conv)
    return short_conv


def _norm_after_the_turn(sound, ref):
    def qk_positions(qh, kh, lw, cfg):
        eps, theta = cfg["norm_eps"], float(cfg["rope_theta"])
        return (ref._rms(ref._rope(qh, theta), lw["g_q"], eps),
                ref._rms(ref._rope(kh, theta), lw["g_k"], eps))
    return qk_positions


def _bias_in_the_weights(sound, ref):
    def route(f, w_r, b_r, cfg, q=None):
        idx, _, sc = sound(f, w_r, b_r, cfg, q)
        top = jnp.take_along_axis(sc + b_r, idx, axis=-1)
        return idx, (cfg["routed_scaling_factor"] * top / (
            top.sum(axis=-1, keepdims=True) + cfg["router_norm_eps"])), sc
    return route


OMISSIONS = {
    "the taps in reverse order": ("weights", "w_conv", lambda x: x[:, ::-1]),
    "the convolution reading one token ahead": ("patch", "short_conv",
                                                _one_token_ahead),
    "the gate B dropped": (
        "patch", "gated_conv", lambda sound, ref: lambda b, c, x, w:
        c * ref.short_conv(x, w)),
    "C applied before the convolution": (
        "patch", "gated_conv", lambda sound, ref: lambda b, c, x, w:
        ref.short_conv(c * b * x, w)),
    "a SiLU after the convolution": (
        "patch", "gated_conv", lambda sound, ref: lambda b, c, x, w:
        c * jax.nn.silu(ref.short_conv(b * x, w))),
    "q/k norm after the rotary turn": ("patch", "qk_positions",
                                       _norm_after_the_turn),
    "the bias in the weights as well as in the choice": (
        "patch", "route", _bias_in_the_weights),
    "an untied head": (
        "patch", "head_matrix", lambda sound, ref: lambda w:
        jax.lax.stop_gradient(w["embed"]).T),
    "the first dense layer with experts": ("config",
                                           {"num_dense_layers": 0}),
}
_OMITTED = {}       # name -> the reference's jitted step with it left out


def omitted(seeded, monkeypatch, name):
    """The reference's (loss, gradient leaves) with `name` left out; one
    trace a name, made under the patch and shared by the seeds."""
    cfg, ref, fam, w, batch = seeded
    how, what, make = (OMISSIONS[name] + (None,))[:3]
    if how == "weights":
        hit = [k for k in w if k.endswith("/" + what)]
        assert hit
        return _reference(seeded, dict(w, **{k: make(w[k]) for k in hit}))
    if how == "config":
        # the same weights, the dense layer's feed-forward part replaced by
        # a router and held experts seeded beside them; compared on the
        # leaves both programs have
        other = dict(cfg, **what)
        extra = ref.init_weights(other, jax.random.PRNGKey(97))
        w2 = {k: w.get(k, extra[k]) for k in extra}
        if name not in _OMITTED:
            _OMITTED[name] = jax.jit(
                lambda w, batch: ref.loss_and_grad(w, batch, other))
        loss, grads = _reference(seeded, w2, _OMITTED[name], other)
        return loss, {k: v for k, v in grads.items() if "ffn_" not in k
                      and not k.startswith("['layer_0']['router")
                      and not k.startswith("['layer_0']['experts")}
    if name not in _OMITTED:
        monkeypatch.setattr(ref, what, make(getattr(ref, what), ref))
        fn = jax.jit(lambda w, batch: ref.loss_and_grad(w, batch, cfg))
        fn(w, batch)                    # traced here, under the patch
        _OMITTED[name] = fn
    return _reference(seeded, fn=_OMITTED[name])


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(OMISSIONS))
def test_what_the_limits_guard_by_omission(monkeypatch, name, seed):
    """The bfloat16 program against a reference that leaves one thing out:
    refused by the tiny gradient's limit, on every seed."""
    seeded, got = _bfloat16(seed)
    want_loss, want = omitted(seeded, monkeypatch, name)
    grads = {k: got[1][k] for k in want}
    _, grad_err = _errors((got[0], grads), (want_loss, want))
    assert grad_err > _tiny_limits()["grad_rel_err"], grad_err


@pytest.mark.parametrize("seed", SEEDS)
def test_the_normaliser_s_epsilon_cannot_be_told_apart(seed):
    """1e-20 where the model publishes 1e-6: four sigmoid scores sum to
    about 2, so the two normalisers differ by parts in 1e7 — float32's own
    rounding, far inside what bfloat16 moves. No compared number guards the
    value; the program takes the published one all the same."""
    cfg, ref, fam, w, batch = seeded = _seeded(seed)
    other = dict(cfg, router_norm_eps=1e-20)
    loss, g = jax.jit(lambda w: ref.loss_and_grad(w, batch, other))(w)
    sound = _reference(seeded)
    loss_err, grad_err = _errors(
        (loss, _leaves(fam.to_program(g, cfg)[0])), sound)
    assert loss_err < 1e-6 and grad_err < 1e-5
    assert fam.build_model(cfg, {}).router_norm_eps == 1e-6
    assert _cfg()["router_norm_eps"] == 1e-6


# -- causality, the tied matrix, what a conv layer refuses --------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_later_token_changes_nothing_before_it(lfm2, dtype):
    """A change at token t + 1 leaves every logit up to t bit for bit: the
    convolution reads nothing ahead, nor does anything else."""
    cfg, _, fam, w, batch = lfm2
    model = fam.build_model(cfg, {}).clone(dtype=dtype, use_flash=False)
    params, _ = fam.to_program(w, cfg)
    ids = batch["input_ids"]
    apply = jax.jit(lambda ids: model.apply({"params": params}, ids)[0])
    base = apply(ids)
    for t in (0, 1, 20, 46):
        later = ids.at[:, t + 1].set((ids[:, t + 1] + 1) % cfg["vocab_size"])
        got = apply(later)
        np.testing.assert_array_equal(got[:, :t + 1], base[:, :t + 1])
        assert float(jnp.abs(got[:, t + 1] - base[:, t + 1]).max()) > 0


@pytest.mark.parametrize("seq_len", [1, 2])
def test_a_sequence_shorter_than_the_kernel_runs(lfm2, seq_len):
    """Fewer tokens than the convolution has taps: zeros stand before the
    sequence, in program and reference alike."""
    cfg, ref, fam, w, batch = lfm2
    assert seq_len < cfg["conv_L_cache"]
    ids = batch["input_ids"][:, :seq_len]
    logits = _logits_fn(cfg["vocab_size"])(fam.to_program(w, cfg)[0], ids)
    want = jax.jit(lambda w: jnp.einsum(
        "btd,vd->btv", ref.hidden(w, ids, cfg), w["embed"],
        precision=jax.lax.Precision.HIGHEST))(w)
    np.testing.assert_allclose(logits, want, atol=2e-5, rtol=2e-4)


def test_the_tied_matrix_s_gradient_is_the_sum_of_its_two_uses(lfm2,
                                                               reference):
    """d loss / d E = what reaches the rows gathered at the bottom + what
    reaches the product at the top: each use alone is found by holding the
    other's copy fixed, and the program's one gradient is their sum."""
    cfg, ref, fam, w, batch = lfm2
    model = fam.build_model(cfg, {"remat": True}).clone(dtype=jnp.float32)
    params, _ = fam.to_program(w, cfg)
    assert "lm_head" not in params
    ids = batch["input_ids"]

    def loss_of(bottom, top):
        """The model with `bottom` gathered and `top` multiplied."""
        untied = model.clone(tie_embeddings=False)
        logits, _ = untied.apply(
            {"params": dict(params, embed=bottom, lm_head=top.T)}, ids)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1).mean()

    e = params["embed"]
    at_bottom, at_top = jax.jit(jax.grad(loss_of, argnums=(0, 1)))(e, e)
    assert float(jnp.abs(at_bottom).max()) > 0
    assert float(jnp.abs(at_top).max()) > 0
    want = reference[1]["['embed']"]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(at_bottom + at_top, want, atol=2e-4 * scale,
                               rtol=2e-3)
    # neither use alone is the gradient
    assert float(jnp.abs(at_top - want).max()) > 0.05 * scale
    assert float(jnp.abs(at_bottom - want).max()) > 0.05 * scale


def test_a_conv_layer_refuses_what_it_cannot_be(lfm2):
    cfg, _, fam, w, batch = lfm2
    model = fam.build_model(cfg, {})
    ids = batch["input_ids"]
    for attrs in (dict(window=8, window_layout=(1,) * 5),
                  dict(select_layout=(1,) * 5, select_topk=4, index_heads=1,
                       index_dim=8)):
        with pytest.raises(ValueError,
                           match="short-convolution layer takes no mask"):
            model.clone(**attrs).init(jax.random.PRNGKey(0), ids)
    # q/k norm, a gate and a latent are the ATTENTION layers': the stack
    # hands a conv layer none of them, and a conv layer handed one refuses
    layer = sparse_decoder.SparseDecoderLayer(
        heads=4, kv_heads=2, head_dim=8, num_experts=0, experts_held=0,
        first_expert=0, experts_per_token=0, expert_width=0, use_rope=True,
        rope_theta=1e6, window=None, dtype=jnp.float32, parts="mixer",
        mixer="shortconv", conv_width=3)
    x = jnp.zeros((1, 8, 32))
    layer.init(jax.random.PRNGKey(0), x)
    for attrs in (dict(qk_norm=True), dict(attn_gate=True),
                  dict(latent_dim=16, rope_head_dim=4)):
        with pytest.raises(ValueError, match="no latent, q/k norm or gate"):
            layer.clone(**attrs).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="no tied head"):
        model.clone(loop_steps=2).init(jax.random.PRNGKey(0), ids)


# -- the shares add up --------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer(lfm2):
    """Four shares of 2 of the 8 experts, each routing over all 8 by score +
    bias through the PROGRAM's router (the published 1e-6 in its
    normaliser) and held experts: their parts give the uncut reference's
    expert layer — the mixer in front of it and a dense layer's result are
    whole on every chip and counted once —, and the rows they serve are all
    the choices."""
    cfg, ref, _, _, _ = lfm2
    whole = dict(cfg, num_hidden_layers=1, layer_types=["conv"],
                 num_dense_layers=0, num_experts=8, first_expert=0)
    lw = ref.layer_weights(ref.init_weights(whole, jax.random.PRNGKey(7)), 0)
    f = jax.random.normal(jax.random.PRNGKey(8), (96, cfg["hidden_size"]))
    want = ref.feed_forward_part(f, lw, whole, False)
    total, rows, seen = jnp.zeros_like(want), 0.0, []

    @jax.jit
    def one_share(w_gate_up, w_down, first):
        idx, p, routed = moe.route_sigmoid_top_k(
            f, lw["w_r"], lw["b_r"], cfg["num_experts_per_tok"],
            float(cfg["routed_scaling_factor"]), cfg["router_norm_eps"])
        return moe.held_experts_ffn(f, idx, p, w_gate_up, w_down, first,
                                    activation="silu"), routed

    for share in range(4):
        es = slice(2 * share, 2 * share + 2)
        (m, counters), routed = one_share(lw["w_gate_up"][es],
                                          lw["w_down"][es], 2 * share)
        total = total + m
        rows += float(counters["rows_held"])
        seen.append({n: float(v) for n, v in routed.items()})
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)
    assert rows == f.shape[0] * cfg["num_experts_per_tok"]
    assert all(s == seen[0] for s in seen)
    assert seen[0]["route_weight_sum"] == pytest.approx(f.shape[0], rel=1e-5)


@functools.lru_cache(maxsize=None)
def _logits_fn(vocab):
    """ONE jitted (params, ids) -> logits a vocabulary size."""
    cfg = _tiny_cfg()
    _, fam = _modules()
    model = fam.build_model(cfg, {}).clone(
        dtype=jnp.float32, use_flash=False, vocab_size=vocab)
    return jax.jit(lambda params, ids: model.apply({"params": params},
                                                   ids)[0])


@pytest.mark.parametrize("share", range(4))
def test_a_vocabulary_slice_s_logits_are_the_uncut_logits_columns(lfm2,
                                                                  share):
    """The ONE tied matrix cut by rows: a share that holds rows 96 k ..
    96 k + 95 of an uncut [384, 32] matrix gathers from them and multiplies
    by them, and on ids of its slice its logits are the uncut model's
    columns 96 k .. 96 k + 95, through the PROGRAM's model."""
    cfg, ref, fam, w, batch = lfm2
    v = cfg["vocab_size"]
    full = jax.random.normal(jax.random.PRNGKey(31), (4 * v,
                                                      cfg["hidden_size"]))
    full = full * cfg["embedding_initializer_range"]
    params, _ = fam.to_program(w, cfg)
    ids = batch["input_ids"]
    rows = slice(share * v, (share + 1) * v)
    held = _logits_fn(v)(dict(params, embed=full[rows]), ids)
    uncut = _logits_fn(4 * v)(dict(params, embed=full), ids + share * v)
    assert uncut.shape[-1] == 4 * v
    np.testing.assert_allclose(held, uncut[..., rows], atol=1e-5, rtol=1e-5)


#: the whole traced program — loss, counters and gradient at the tiny sizes —
#: of the three accepted models whose routers score with a sigmoid. PR 63,
#: which made the normaliser's epsilon an argument, left them the text that
#: 0a911dc gave; these are PR 64's, whose loss alone is new
#: (tests/test_gated_delta.py, beside its pins, says what changed) (without
#: remat too: nemotron 62e7993ad1e79cf0, kimi df6582bc27047a2c — left out
#: for their tracing time; a router is the same program under either)
SIGMOID_ROUTED = {
    ("kanana-2-30b-a3b", True): "c36e23020ac4035d",
    ("kanana-2-30b-a3b", False): "f9384e174bd5218b",
    ("nemotron-twotower-30b-a3b", True): "39821334c40fcfa8",
    ("kimi-linear-48b-a3b", True): "7a08b2f7b27cb014",
}


@pytest.mark.parametrize("config,remat", sorted(SIGMOID_ROUTED))
def test_the_accepted_sigmoid_routers_are_the_programs_they_were(config,
                                                                 remat):
    """Kanana, Nemotron and Kimi: equal text is an equal program, so equal
    bits on any machine."""
    _, traced = traced_gradient(config, remat)
    assert traced == SIGMOID_ROUTED[(config, remat)]


@pytest.mark.parametrize("scaling,k,outputs", [(2.448, 6, 128), (2.5, 6, 128),
                                               (2.446, 8, 256)])
def test_the_default_normaliser_is_bit_for_bit_the_old_one(scaling, k,
                                                           outputs):
    """`route_sigmoid_top_k` with no `norm_eps` against the formula it held
    before, at Kanana's, Nemotron's and Kimi's factor, choices and router
    width: the same choice, the same weights and the same gradient, bit for
    bit."""
    h = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (32, outputs))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (outputs,))

    def before(router):
        sc = jax.nn.sigmoid(jnp.dot(h, router,
                                    precision=jax.lax.Precision.HIGHEST))
        idx = jax.lax.top_k(jax.lax.stop_gradient(sc) + bias, k)[1]
        top = jnp.take_along_axis(sc, idx, axis=-1)
        return idx, scaling * top / (top.sum(axis=-1, keepdims=True) + 1e-20)

    def now(router):
        return moe.route_sigmoid_top_k(h, router, bias, k, scaling)[:2]

    for got, want in zip(jax.jit(now)(router), jax.jit(before)(router)):
        np.testing.assert_array_equal(got, want)
    probe = jax.random.normal(jax.random.PRNGKey(4), (64, k))
    grad = lambda fn: jax.jit(jax.grad(
        lambda r: jnp.sum(fn(r)[1] * probe)))(router)
    np.testing.assert_array_equal(grad(now), grad(before))


# -- the configuration and the family -----------------------------------------

def _catalog_row():
    if not os.path.exists(CATALOG):
        return None
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows if r["name"] == "LFM2-8B-A1B"][0]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_the_published_value(key):
    """Every key of the published config that is not in `reduced` equals the
    published value; one that is, is a cut of scale and keeps its published
    value under `published`."""
    cfg = _cfg()
    row = _catalog_row()
    if row is not None:         # the literal above IS the catalog's row
        assert row["config"] == PUBLISHED
        assert row["source_url"] == cfg["source"]
    if key in cfg["reduced"]:
        assert cfg["published"][key] == PUBLISHED[key]
        assert cfg[key] != PUBLISHED[key]
        assert key in cfg["reduced_why"]
    else:
        assert cfg[key] == PUBLISHED[key]
        assert type(cfg[key]) is type(PUBLISHED[key])


def test_configuration_is_the_cut_the_issue_names():
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_dense_layers", "num_experts",
                              "vocab_size"]
    assert sorted(cfg["published"]) == sorted(cfg["reduced"])
    # the layers at the published positions 1 to 5: the second of the two
    # leading dense layers, then one whole period
    assert cfg["layer_types"] == PUBLISHED["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["num_router_outputs"],
            cfg["vocab_size"], cfg["head_dim"]) == (
        5, 1, 8, 32, 65536 // 4, 2048 // 32)
    assert (cfg["first_expert"], cfg["first_head"],
            cfg["first_vocab_row"]) == (0, 0, 0)
    assert cfg["tie_word_embeddings"] is True
    assert cfg["router_norm_eps"] == 1e-6
    for name in ("tie_word_embeddings", "head_dim", "final_norm",
                 "router_norm_eps", "in_projection_order", "convolution",
                 "qk_norm", "positions", "expert_bias", "parameter_dtype",
                 "compute_dtype", "weights", "dropout_and_aux_losses"):
        assert name in cfg["assumed"]
    assert "4 : 1" in cfg["reduced_why"]["layer_types"]
    assert "4 chips share each layer's experts" in cfg["deployment"]
    assert "507,820,288" in cfg["deployment"]
    tiny = _tiny_cfg()
    assert tiny["layer_types"] == cfg["layer_types"]
    assert (tiny["hidden_size"], tiny["num_attention_heads"],
            tiny["num_key_value_heads"], tiny["head_dim"],
            tiny["num_router_outputs"], tiny["num_experts"],
            tiny["num_experts_per_tok"]) == (32, 4, 2, 8, 8, 2, 3)
    _, fam = _modules()
    shapes = fam.train_parts(cfg, {"remat": True})[2][0]
    assert "lm_head" not in shapes
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    d = 2048
    conv = 3 * d * d + d * d + 3 * d
    attention = 2 * d * 32 * 64 + 2 * d * 8 * 64 + 2 * 64
    experts = d * 32 + 32 + 8 * 3 * d * 1792
    assert n == (16384 * d + d + 5 * 2 * d + 4 * conv + attention
                 + 3 * d * 7168 + 4 * experts) == 507820288


def test_family_refuses_a_program_without_the_convolution(monkeypatch):
    """What the parent commit meets when it is handed this cell: a
    BenchError at once, from every entry of the family's file, before any
    weight is made."""
    cfg = _tiny_cfg()
    _, fam = _modules()
    monkeypatch.delattr(sparse_decoder, "SHORTCONV_COUNTERS")
    for call in (lambda: fam.build_model(cfg, {}),
                 lambda: fam.train_parts(cfg, {}),
                 lambda: fam.to_program({}, cfg)):
        with pytest.raises(harness.BenchError,
                           match="no gated short convolution"):
            call()


# -- the training state through the trainer to the readers --------------------

def test_counters_reach_the_readers_through_the_trainer(lfm2, monkeypatch):
    import optax
    from edl_tpu.runtime import trainer as trainer_mod
    from edl_tpu.runtime.mesh import make_mesh
    from edl_tpu.runtime.trainer import ElasticTrainer
    # the process's gauge of model counters, empty for this test and as it
    # was after it: other files' tests count its series
    monkeypatch.setattr(trainer_mod._MODEL_COUNTER, "_children", {})
    cfg, _, fam, w, _ = lfm2
    loss_fn, has_aux, _ = fam.train_parts(cfg, {"remat": False})
    params, extra = jax.tree_util.tree_map(jnp.array, fam.to_program(w, cfg))
    tr = ElasticTrainer(loss_fn, params, optax.adamw(1e-3),
                        total_batch_size=2, extra_state=extra,
                        has_aux=has_aux,
                        mesh=make_mesh(devices=jax.devices()[:2]))
    batch = fam.make_batch(cfg, {"seq_len": 48}, jax.random.PRNGKey(5), 2)
    try:
        for _ in range(2):
            tr.train_step(tr.local_batch_slice(batch))
    finally:
        tr.close()
    counters = kernel_readers.model_counters()
    assert counters["steps"] == [2.0]
    top = counters["conv_gate_absmax"]
    assert len(top) == 5
    # a running maximum, not a sum; the attention layer's stays 0
    assert top[1] == 0.0 and min(top[:1] + top[2:]) > 0.0
    assert counters["route_weight_sum"][1] == pytest.approx(2 * 96, rel=1e-5)
    view = {"traffic": _job(), "cell": {"chips": 1, "name": CELL},
            "config": _cfg(), "counters": {"traced_steps": 2},
            "trace": {"ops": []}}
    assert harness.load_module("metrics", "shortconv_gate_absmax").read(
        view) == max(top)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_none_where_there_is_nothing_to_read(monkeypatch,
                                                                name):
    """The parent commit's program has no such counter and no such scope in
    its trace: the reader says nothing and does not raise."""
    view = {"traffic": _job(), "cell": {"chips": 1, "name": "no-such-cell"},
            "config": _cfg(), "counters": {"traced_steps": 10},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
            "trace": {"ops": [["fusion.3", 0.2], ["moe_gmm", 0.1]]}}
    mod = harness.load_module("metrics", name)
    if hasattr(mod, "model_counters"):
        monkeypatch.setattr(mod, "model_counters",
                            lambda: {"steps": [2.0], "rows_held": [4.0]})
    assert mod.read(view) is None
    # a trace that has the program's scopes and not this one
    gate = getattr(mod, "_gate", mod)
    if hasattr(gate, "table"):
        monkeypatch.setattr(gate, "table",
                            lambda view: {("attn.full", "fwd"): 1.0})
        assert mod.read(view) is None


@pytest.mark.parametrize("times_least, want", [(4.0, 25.0), (0.5, 200.0)])
def test_the_roofline_reader_reads_the_share_as_computed(monkeypatch,
                                                         times_least, want):
    """No cut-off: a share over 100 — a count of required bytes set too
    high, or time the compiler books elsewhere — is reported as it is, for
    the driver's check to judge."""
    cfg, job = _cfg(), _job()
    _, fam = _modules()
    view = {"traffic": job, "cell": {"chips": 1, "name": CELL},
            "config": cfg, "counters": {"traced_steps": 10},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
            "trace": {"ops": []}}
    mod = harness.load_module("metrics", "shortconv_gate_roofline_pct")
    monkeypatch.setattr(kernel_readers, "model_counters", lambda: {})
    ms = times_least * fam.kernel_costs(
        cfg, job, 1)["shortconv_gate"][1] / 819e9 * 1e3
    monkeypatch.setattr(mod._gate, "table", lambda view: {
        ("mixer.conv.gate", "fwd"): ms / 4,
        ("mixer.conv.gate", "bwd"): 3 * ms / 4})
    assert mod.read(view) == pytest.approx(want)


def test_benchmark_lists_the_cell_where_it_reports():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    # found by name: later PRs append after them
    added = {m["name"]: m for m in bench["per_layer"]
             if m["name"] in NEW_METRICS}
    assert sorted(added) == sorted(NEW_METRICS)
    for m in added.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_samples_s_chip"
    assert [added[n]["layer"] for n in NEW_METRICS] == [
        "model parts", "kernels", "attention"]
    cell = [c for c in bench["workloads"] if c["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == _cfg()["reduced"]
    assert len(bench["workloads"]) >= 12 and len(bench["configs"]) >= 11
    for listed in bench["workloads"]:
        assert len(listed["why"]) <= 200
    reports = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", ())}
    assert {"mixer_device_ms", "attn_device_ms", "scope_coverage_pct",
            "step_mfu_pct", "moe_gmm_roofline_pct", "moe_tgmm_roofline_pct",
            "moe_bias_choice_flips_pct", "moe_route_weight_sum",
            "flash_fwd_resident_roofline_pct", "flash_bwd_device_ms",
            "moe_rows_dropped"} <= reports
    assert not any(n.startswith(("kda_", "gdn_", "ssd_", "dsa_", "bdiff_",
                                 "flash_fwd_stream")) for n in reports)
    job = _job()
    assert (job["batch_per_chip"], job["seq_len"], job["remat"],
            job["optimizer"]["lr"], job["check_steps"]) == (
        1, 8192, True, 1e-6, 1)
