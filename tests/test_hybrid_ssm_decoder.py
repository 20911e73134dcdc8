"""The decoder whose layers are ONE sublayer each — a Mamba-2 state-space
mixer, an attention without positions, or a mixture of ungated squared-ReLU
experts (`models/sparse_decoder.py`: `part_layout`, `_mamba2`,
`expert_gated`; the `nemotron-twotower-30b-a3b` configuration).

- the decoder's loss, counters and every gradient leaf against
  `benchmark/reference/nemotron-twotower-30b-a3b.py` at the `tiny` size in
  float32 (plain scan and kernels), bfloat16 inside the tiny limits, the int8
  control far outside them, and what each compared number guards, by omission;
- the shares add up: head shares (whole groups) of the Mamba mixer with the
  gated norm local to a share, head shares of the attention, expert shares of
  the routed part with the shared expert counted once, vocabulary shares of
  the logits;
- the configuration, the family's counts, the counters through
  `ElasticTrainer` (save, restore, live resize) to the new readers.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxpr_kernels import (equations_outside_kernels, gradient_jaxpr,
                           gradient_kernel_calls)

from benchmark.lib import harness, kernel_readers
from edl_tpu.models import sparse_decoder
from edl_tpu.ops import ssd
from edl_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "nemotron-twotower-30b-a3b"
TRAFFIC = "tokens-8192-ssm"
HI = jax.lax.Precision.HIGHEST


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


# -- (a) the decoder against the plain reference ------------------------------

@pytest.fixture(scope="module")
def nemotron():
    cfg = _tiny_cfg()
    ref = harness.load_module("reference", CONFIG)
    fam = harness.load_module("program", cfg["family"])
    w = ref.init_weights(cfg, jax.random.PRNGKey(3))
    batch = fam.make_batch(cfg, {"seq_len": 32}, jax.random.PRNGKey(4), 2)
    return cfg, ref, fam, w, batch


def _loss_and_grad(cfg, fam, w, batch, dtype, remat=True, use_flash=None,
                   **attrs):
    model = fam.build_model(cfg, {"remat": remat}).clone(
        dtype=dtype, use_flash=use_flash, **attrs)
    _, _, extra, loss_fn = sparse_decoder.create_model_and_loss(model)
    params, _ = fam.to_program(w, cfg)
    (loss, extra), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, extra, batch, None), has_aux=True))(params)
    return loss, grads, extra


def _leaves(tree):
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_leaves_with_path(tree)}


def _leaf_names():
    cfg = _tiny_cfg()
    fam = harness.load_module("program", cfg["family"])
    return sorted(_leaves(fam.train_parts(cfg, {"remat": True})[2][0]))


def _distance(got, want):
    num = sum(float(jnp.sum(jnp.square(got[k] - want[k]))) for k in want)
    den = sum(float(jnp.sum(jnp.square(want[k]))) for k in want)
    return (num / den) ** 0.5


def _reference(nemotron, w=None):
    """(loss, the gradient's leaves in the program's layout)."""
    cfg, ref, fam, own, batch = nemotron
    loss, g = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg))(
        own if w is None else w)
    return loss, _leaves(fam.to_program(g, cfg)[0])


@pytest.fixture(scope="module")
def reference(nemotron):
    return _reference(nemotron)


@pytest.fixture(scope="module", params=["plain", "kernels"])
def nemotron_float32(request, nemotron, reference):
    cfg, ref, fam, w, batch = nemotron
    loss, grads, extra = _loss_and_grad(
        cfg, fam, w, batch, jnp.float32,
        use_flash=request.param == "kernels")
    return loss, _leaves(grads), extra


def test_loss_and_counters_match_the_reference_float32(nemotron, reference,
                                                       nemotron_float32):
    cfg, ref, fam, w, batch = nemotron
    loss, _, extra = nemotron_float32
    np.testing.assert_allclose(loss, reference[0], rtol=2e-5)
    c = extra["counters"]
    assert sorted(c) == sorted(
        sparse_decoder.COUNTERS + sparse_decoder.ROUTE_COUNTERS
        + sparse_decoder.SSD_COUNTERS + ("steps",))
    assert float(c["steps"]) == 1.0
    assert float(c["rows_dropped"].sum()) == 0.0
    assert fam.layer_kinds(cfg) == "MEM*E"
    want = jax.jit(lambda w: ref.layer_counts(w, batch["input_ids"], cfg))(w)
    for name in ("rows_held", "route_bias_flips"):
        np.testing.assert_array_equal(c[name], want[name])
    for name in ("route_weight_sum",) + sparse_decoder.SSD_COUNTERS:
        np.testing.assert_allclose(c[name], want[name], rtol=1e-5)
    experts = np.asarray([k == "E" for k in fam.layer_kinds(cfg)])
    mamba = np.asarray([k == "M" for k in fam.layer_kinds(cfg)])
    tokens = batch["input_ids"].size
    np.testing.assert_allclose(
        np.asarray(c["route_weight_sum"])[experts],
        cfg["routed_scaling_factor"] * tokens, rtol=1e-5)
    # a layer without the part counts zeros
    for name in sparse_decoder.COUNTERS + sparse_decoder.ROUTE_COUNTERS:
        assert (np.asarray(c[name])[~experts] == 0).all()
    assert (np.asarray(c["rows_held"])[experts] > 0).all()
    low, top = (np.asarray(c[n]) for n in sparse_decoder.SSD_COUNTERS)
    assert (low[mamba] < 0).all() and (top[mamba] > 0).all()
    assert (low[~mamba] == 0).all() and (top[~mamba] == 0).all()


@pytest.mark.parametrize("leaf", _leaf_names())
def test_gradient_leaf_matches_reference_float32(nemotron_float32, reference,
                                                 leaf):
    _, grads, _ = nemotron_float32
    want = reference[1][leaf]
    if "router_bias" in leaf:       # in the choice alone: nothing reaches it
        assert float(jnp.abs(grads[leaf]).max()) == 0.0
        assert float(jnp.abs(want).max()) == 0.0
        return
    scale = float(jnp.abs(want).max())
    assert scale > 0          # every other tensor of the model learns
    np.testing.assert_allclose(grads[leaf], want, atol=2e-4 * scale,
                               rtol=2e-3)


def test_remat_changes_no_number(nemotron, nemotron_float32):
    cfg, _, fam, w, batch = nemotron
    loss, grads, _ = _loss_and_grad(cfg, fam, w, batch, jnp.float32,
                                    remat=False)
    np.testing.assert_allclose(loss, nemotron_float32[0], rtol=1e-6)
    assert _distance(_leaves(grads), nemotron_float32[1]) < 1e-5


def test_remat_runs_ssd_fwd_once_a_layer(nemotron):
    """Under remat the layer saves the scan's result and states
    (`ssd.SAVED_UNDER_REMAT`, in the family's one policy) and an ungated
    expert layer its up and down products: the gradient holds `ssd_fwd`
    once a Mamba layer, as without remat, four `moe_gmm` an expert layer,
    and the attention layer's band kernel twice."""
    cfg, _, fam, w, batch = nemotron
    assert set(ssd.SAVED_UNDER_REMAT) <= set(sparse_decoder.SAVED_UNDER_REMAT)
    assert "moe.up" in sparse_decoder.SAVED_UNDER_REMAT
    for remat in (True, False):
        calls = gradient_kernel_calls(fam, cfg, w, batch, remat)
        assert calls[ssd.FWD_NAME] == calls[ssd.BWD_NAME] == 2
        assert calls["moe_gmm"] == 8 and calls["moe_tgmm"] == 4
        assert calls["flash_fwd_resident"] == (2 if remat else 1)
    # and nothing of the scan is rebuilt round the kernels: no decay matrix
    # exp(gamma_i - gamma_j) a (head, chunk), forward or backward
    plane = (cfg["chunk_size"],) * 2
    exps = [eqn.outvars[0].aval.shape for eqn in equations_outside_kernels(
        gradient_jaxpr(fam, cfg, w, batch, True))
        if eqn.primitive.name == "exp"]
    assert exps and not [s for s in exps if s[-2:] == plane]


@pytest.fixture(scope="module")
def nemotron_bfloat16(nemotron):
    cfg, _, fam, w, batch = nemotron
    loss, grads, _ = _loss_and_grad(cfg, fam, w, batch, jnp.bfloat16)
    return loss, _leaves(grads)


def _errors(got, want):
    """(loss_rel_err, grad_rel_err) as `correct` compares them."""
    return (abs(float(got[0]) - float(want[0])) / abs(float(want[0])),
            _distance(got[1], want[1]))


def test_matches_reference_bfloat16(nemotron_bfloat16, reference):
    """bf16 activations and products as the cell runs them: inside the
    tiny limits, by the loss and by the whole gradient in relative L2."""
    limits = _tiny_limits()
    loss_err, grad_err = _errors(nemotron_bfloat16, reference)
    assert loss_err < limits["loss_rel_err"]
    assert grad_err < limits["grad_rel_err"]


def test_int8_control_is_far_from_the_reference(nemotron, reference):
    """The control `correct` has to refuse: outside the tiny limits."""
    cfg, ref, fam, w, batch = nemotron
    _, g8 = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg, "int8"))(w)
    got = _leaves(fam.to_program(g8, cfg)[0])
    assert _distance(got, reference[1]) > 5 * _tiny_limits()["grad_rel_err"]


# what each compared number guards: a reference with ONE thing left out or
# done otherwise, against which the program (as it is) must read outside a
# limit — {name: ("patch", the reference's function to replace, its
# replacement given the sound one and the module) or ("weights", the tensor
# of every layer that has it, its replacement)}

def _reset_every_chunk(sound, ref):
    """The recurrence with the state zeroed every 8 tokens (the tiny
    `chunk_size`): what a chunked scan that loses its carried state
    computes."""
    def scan(x, dt, a, b, c, qc=None):
        bsz, s = x.shape[:2]
        cut = lambda y: y.reshape((bsz * s // 8, 8) + y.shape[2:])
        return sound(cut(x), cut(dt), a, cut(b), cut(c), qc).reshape(x.shape)
    return scan


def _norm_after_the_gate(sound, ref):
    def gated_norm(y, z, g_n, groups, eps):
        return sound(y, jnp.full_like(z, 1.2785), g_n, groups, eps) \
            * jax.nn.silu(z)       # silu(1.2785) = 1: the norm alone
    return gated_norm


def _rotary(sound, ref):
    def positions(x, cfg):
        half = x.shape[-1] // 2
        freq = float(cfg["rope_theta"]) ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    return positions


def _gated_expert(sound, ref):
    """A gated linear unit of three matrices, the third W_up's columns in
    reverse order: relu(u W_up)^2 * (u W_gate) W_down."""
    def expert(u, w_up, w_down, qc=None):
        up = jnp.dot(u, w_up, precision=HI)
        hid = jnp.square(jax.nn.relu(up)) * up[:, ::-1]
        return jnp.dot(hid, w_down, precision=HI)
    return expert


OMISSIONS = {
    "the state reset at every chunk": ("patch", "ssm_scan",
                                       _reset_every_chunk),
    "no decay": ("weights", "a_log", lambda x: jnp.full_like(x, -1e9)),
    "no skip D x": ("weights", "d_skip", jnp.zeros_like),
    "no dt on the write": (
        "patch", "state_step", lambda sound, ref: lambda state, decay, write,
        x_t, b_t, qc=None: sound(state, decay, jnp.ones_like(write), x_t,
                                 b_t, qc)),
    "no bias in the convolution": ("weights", "b_conv", jnp.zeros_like),
    "the SiLU gate after the norm": ("patch", "gated_norm",
                                     _norm_after_the_gate),
    "one norm group instead of G": (
        "patch", "gated_norm", lambda sound, ref: lambda y, z, g_n, groups,
        eps: sound(y, z, g_n, 1, eps)),
    "a gated expert of three matrices": ("patch", "expert", _gated_expert),
    "no shared expert": ("weights", "w_sd", jnp.zeros_like),
    "rotary positions in the attention": ("patch", "positions", _rotary),
}


def omitted(nemotron, monkeypatch, name):
    """The reference's (loss, gradient leaves) with `name` left out."""
    _, ref, _, w, _ = nemotron
    how, what, make = OMISSIONS[name]
    if how == "patch":
        monkeypatch.setattr(ref, what, make(getattr(ref, what), ref))
        return _reference(nemotron)
    hit = [k for k in w if k.endswith("/" + what)]
    assert hit
    return _reference(nemotron, dict(w, **{k: make(w[k]) for k in hit}))


@pytest.mark.parametrize("name", sorted(OMISSIONS))
def test_what_the_limits_guard_by_omission(monkeypatch, nemotron,
                                           nemotron_bfloat16, name):
    """The bfloat16 program against a reference that leaves one thing out:
    refused by at least one of the two limits."""
    limits = _tiny_limits()
    loss_err, grad_err = _errors(nemotron_bfloat16,
                                 omitted(nemotron, monkeypatch, name))
    assert (loss_err > limits["loss_rel_err"]
            or grad_err > limits["grad_rel_err"]), (loss_err, grad_err)


def test_the_norm_after_the_gate_is_the_sound_norm_at_a_unit_gate(nemotron):
    """`_norm_after_the_gate` reuses the reference's own norm: at a gate of
    1 the two orders agree, so the omission differs by the order alone."""
    _, ref, _, _, _ = nemotron
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    z = jnp.full_like(y, 1.2785)
    g_n = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    np.testing.assert_allclose(
        _norm_after_the_gate(ref.gated_norm, ref)(y, z, g_n, 2, 1e-5),
        ref.gated_norm(y, z, g_n, 2, 1e-5), rtol=2e-4, atol=1e-5)


def test_layers_hold_one_sublayer_each():
    cfg = _tiny_cfg()
    fam = harness.load_module("program", cfg["family"])
    params, extra = fam.train_parts(cfg, {"remat": True})[2]
    mamba = ["A_log", "D", "conv", "conv_bias", "dt_bias", "in_proj_dt",
             "in_proj_zxbc", "norm_attn", "norm_ssm", "out"]
    experts = ["experts_down", "experts_up", "norm_moe", "router",
               "router_bias", "shared_down", "shared_up"]
    assert sorted(params["layer_0"]) == sorted(params["layer_2"]) == mamba
    assert sorted(params["layer_1"]) == sorted(params["layer_4"]) == experts
    assert sorted(params["layer_3"]) == ["key", "norm_attn", "out", "query",
                                         "value"]
    # two matrices an expert: no gate's columns anywhere
    assert params["layer_1"]["experts_up"].shape == (2, 32, 16)
    assert params["layer_1"]["shared_up"].shape == (32, 32)
    assert sorted(extra["counters"]) == sorted(
        sparse_decoder.COUNTERS + sparse_decoder.ROUTE_COUNTERS
        + sparse_decoder.SSD_COUNTERS + ("steps",))


def test_a_layer_of_one_sublayer_refuses_what_it_cannot_be(nemotron):
    cfg, _, fam, _, _ = nemotron
    model = fam.build_model(cfg, {})
    ids = jnp.zeros((1, 16), jnp.int32)
    init = lambda **attrs: model.clone(**attrs).init(jax.random.PRNGKey(0),
                                                     ids)
    with pytest.raises(ValueError, match="one norm"):
        init(router_input="attn_norm")
    with pytest.raises(ValueError, match="one norm"):
        init(sandwich_norm=True)
    with pytest.raises(ValueError, match="Mamba-2 layer takes no mask"):
        init(window_layout=(1,) * 5, window=8)
    with pytest.raises(IndexError):
        init(part_layout=(3,) * 5)
    # the defaults are every accepted model's: a mixer and a feed-forward
    # part in every layer, gated experts
    assert sparse_decoder.SparseDecoderLayer.parts == "both"
    assert sparse_decoder.SparseDecoderLayer.expert_gated is True
    assert sparse_decoder.SparseDecoder.part_layout == ()


def test_own_initialisation_is_the_published_one():
    """`create_model_and_loss`'s own parameters: A_log = log(1 + the head's
    index in the WHOLE model), D = 1, dt_bias = softplus^-1 of a step in
    [0.001, 0.1]."""
    cfg = _tiny_cfg()
    fam = harness.load_module("program", cfg["family"])
    model = fam.build_model(dict(cfg, first_mamba_head=4), {})
    params = sparse_decoder.create_model_and_loss(model)[1]
    lw = params["layer_0"]
    np.testing.assert_allclose(lw["A_log"], np.log(np.arange(5.0, 9.0)),
                               rtol=1e-6)
    np.testing.assert_array_equal(lw["D"], np.ones(4))
    step = np.asarray(jax.nn.softplus(lw["dt_bias"]))
    assert (step >= 1e-3 * 0.999).all() and (step <= 0.1 * 1.001).all()


# -- (b) the shares add up ----------------------------------------------------

#: the small uncut layer: 2 shares of 2 Mamba groups (4 heads), 2 shares of
#: 4 query heads on 1 key-value head, 16 shares of 2 experts
UNCUT = dict(mamba_num_heads=8, n_groups=4, num_attention_heads=8,
             num_key_value_heads=2, n_routed_experts=32,
             num_router_outputs=32, first_expert=0, num_experts_per_tok=6)


def _uncut(nemotron, kind):
    cfg, ref, _, _, _ = nemotron
    whole = dict(cfg, num_hidden_layers=1, hybrid_override_pattern=kind,
                 **UNCUT)
    lw = ref.layer_weights(ref.init_weights(whole, jax.random.PRNGKey(7)), 0)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 48, cfg["hidden_size"]))
    return whole, lw, x


def _mixer_layer(cfg, **attrs):
    return sparse_decoder.SparseDecoderLayer(
        heads=4, kv_heads=1, head_dim=cfg["head_dim"], num_experts=0,
        experts_held=0, first_expert=0, experts_per_token=0, expert_width=0,
        use_rope=False, rope_theta=1e4, window=None,
        eps=cfg["layer_norm_epsilon"], dtype=jnp.float32, use_flash=False,
        parts="mixer", conv_width=cfg["conv_kernel"], ssm_heads=4,
        ssm_head_dim=cfg["mamba_head_dim"], ssm_groups=2,
        ssm_state=cfg["ssm_state_size"], ssm_chunk=cfg["chunk_size"],
        **attrs)


def test_head_shares_of_the_mamba_mixer_add_up_group_by_group(nemotron,
                                                              monkeypatch):
    """Two shares of 2 whole groups (4 heads), each with its own columns of
    W_in, channels of the convolution, A, D, dt_bias, gains and rows of
    W_out, through the PROGRAM's layer: their parts of the residual add up
    to the uncut reference's mixer — because the gated norm's statistics
    are a group's own: with ONE norm over all the heads they would not."""
    cfg, ref, fam, _, _ = nemotron
    whole, lw, x = _uncut(nemotron, "M")
    p, n = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    eps = cfg["layer_norm_epsilon"]
    want = ref.mamba_part(ref._rms(x, lw["g"], eps), lw, whole)
    inner, bc = 8 * p, 4 * n
    cols = lambda lo, width, i, of: np.arange(
        lo + i * width // of, lo + (i + 1) * width // of)
    layer = _mixer_layer(cfg, mixer="mamba2")
    share_cfg = dict(whole, mamba_num_heads=4, n_groups=2)
    total = jnp.zeros_like(want)
    for share in range(2):
        conv_rows = np.concatenate([cols(0, inner, share, 2),
                                    cols(inner, bc, share, 2),
                                    cols(inner + bc, bc, share, 2)])
        heads = cols(0, 8, share, 2)
        part = {"g": lw["g"],
                "w_in": lw["w_in"][:, np.concatenate([
                    cols(0, inner, share, 2), inner + conv_rows,
                    2 * inner + 2 * bc + heads])],
                "w_conv": lw["w_conv"][conv_rows],
                "b_conv": lw["b_conv"][conv_rows],
                "a_log": lw["a_log"][heads], "dt_bias": lw["dt_bias"][heads],
                "d_skip": lw["d_skip"][heads],
                "g_n": lw["g_n"][cols(0, inner, share, 2)],
                "w_o": lw["w_o"][cols(0, inner, share, 2)]}
        out, counters = layer.apply(
            {"params": fam._mamba_to_program(part, share_cfg)}, x)
        assert sorted(counters) == ["ssd_chunk_log_decay_min",
                                    "ssd_state_absmax"]
        # a share IS the reference at the share's sizes: nothing of the
        # other share's heads enters its norm
        np.testing.assert_allclose(
            out - x, ref.mamba_part(ref._rms(x, part["g"], eps), part,
                                    share_cfg), atol=2e-5, rtol=2e-4)
        total = total + (out - x)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)
    sound = ref.gated_norm
    monkeypatch.setattr(ref, "gated_norm", lambda y, z, g_n, groups, eps:
                        sound(y, z, g_n, 1, eps))
    one_norm = ref.mamba_part(ref._rms(x, lw["g"], eps), lw, whole)
    assert float(jnp.abs(total - one_norm).max()) > 1e-2


def test_head_shares_of_the_attention_add_up(nemotron):
    """Two shares of 4 query heads on their one key-value head, no
    positions, through the PROGRAM's layer."""
    cfg, ref, _, _, _ = nemotron
    whole, lw, x = _uncut(nemotron, "*")
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    want = ref.attention_part(
        ref._rms(x, lw["g"], cfg["layer_norm_epsilon"]), lw, whole)
    layer = _mixer_layer(cfg)
    total = jnp.zeros_like(want)
    for share in range(2):
        q, kv = slice(4 * share, 4 * share + 4), slice(share, share + 1)
        params = {"norm_attn": {"scale": lw["g"]},
                  "query": lw["w_q"].reshape(d, 8, hd)[:, q],
                  "key": lw["w_k"].reshape(d, 2, hd)[:, kv],
                  "value": lw["w_v"].reshape(d, 2, hd)[:, kv],
                  "out": lw["w_o"].reshape(8, hd, d)[q]}
        out, counters = layer.apply({"params": params}, x)
        assert counters == {}
        total = total + (out - x)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)


def test_expert_shares_add_up_with_the_shared_expert_counted_once(nemotron):
    """Sixteen shares of 2 ungated experts, each routing over all 32 by
    score + bias through the PROGRAM's router and held experts: their
    parts, and the shared expert ONCE, give the uncut reference's expert
    part; the rows they serve are all the choices."""
    cfg, ref, _, _, _ = nemotron
    whole, lw, x = _uncut(nemotron, "E")
    u = x.reshape(-1, cfg["hidden_size"])
    want = ref.experts_part(x, lw, whole).reshape(u.shape)
    total = moe.shared_expert_ffn(u, lw["w_su"], lw["w_sd"], None,
                                  activation="relu2", gated=False)
    rows, seen = 0.0, []

    @jax.jit
    def one_share(w_up, w_down, first):
        idx, p, routed = moe.route_sigmoid_top_k(
            u, lw["w_r"], lw["b_r"], 6, cfg["routed_scaling_factor"])
        return moe.held_experts_ffn(u, idx, p, w_up, w_down, first,
                                    activation="relu2", gated=False), routed

    for share in range(16):
        es = slice(2 * share, 2 * share + 2)
        (m, counters), routed = one_share(lw["w_up"][es], lw["w_down"][es],
                                          2 * share)
        total = total + m
        rows += float(counters["rows_held"])
        seen.append({n: float(v) for n, v in routed.items()})
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)
    assert rows == u.shape[0] * 6
    assert all(s == seen[0] for s in seen)
    assert seen[0]["route_weight_sum"] == pytest.approx(
        cfg["routed_scaling_factor"] * u.shape[0], rel=1e-5)


def test_vocabulary_shares_side_by_side_give_the_uncut_logits(nemotron):
    """Eight shares of the untied head (and of the embedding, whose rows
    the ids of every share find alike here) through the PROGRAM: their
    logits side by side are the uncut reference's."""
    cfg, ref, fam, _, batch = nemotron
    v = cfg["vocab_size"]
    whole = dict(cfg, vocab_size=8 * v)
    w = ref.init_weights(whole, jax.random.PRNGKey(9))
    w["embed"] = jnp.tile(w["embed"][:v], (8, 1))
    ids = batch["input_ids"]
    h = ref.hidden(w, ids, whole)
    want = jnp.einsum("btd,dv->btv", h, w["head"], precision=HI)
    model = fam.build_model(cfg, {}).clone(dtype=jnp.float32)
    logits = jax.jit(lambda part: model.apply(
        {"params": fam.to_program(part, cfg)[0]}, ids)[0])
    got = []
    for share in range(8):
        rows = slice(share * v, (share + 1) * v)
        got.append(logits(dict(w, embed=w["embed"][rows],
                               head=w["head"][:, rows])))
    np.testing.assert_allclose(jnp.concatenate(got, axis=-1), want,
                               atol=2e-4, rtol=2e-4)


# -- (c) the configuration and the family's counts ----------------------------

PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def test_configuration_holds_the_published_widths():
    cfg = _cfg()
    assert cfg["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "mamba_num_heads", "n_groups", "num_attention_heads",
        "num_key_value_heads", "vocab_size"]
    assert cfg["published"] == {
        "num_hidden_layers": 52,
        "hybrid_override_pattern": PUBLISHED_PATTERN,
        "n_routed_experts": 128, "mamba_num_heads": 64, "n_groups": 8,
        "num_attention_heads": 32, "num_key_value_heads": 2,
        "vocab_size": 131072}
    assert [PUBLISHED_PATTERN.count(k) for k in "ME*"] == [23, 23, 6]
    assert (cfg["hidden_size"], cfg["mamba_head_dim"], cfg["ssm_state_size"],
            cfg["conv_kernel"], cfg["chunk_size"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["n_shared_experts"], cfg["num_router_outputs"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["mlp_hidden_act"], cfg["n_group"], cfg["topk_group"],
            cfg["layer_norm_epsilon"], cfg["use_conv_bias"], cfg["expand"],
            cfg["time_step_min"], cfg["time_step_max"],
            cfg["time_step_floor"]) == (
        2688, 64, 128, 4, 128, 128, 1856, 1856, 3712, 1, 128, 6, 2.5,
        "relu2", 1, 1, 1e-5, True, 2, 0.001, 0.1, 0.0001)
    # cut A of ISSUE 57: the stretch at the published positions 35-43, whole
    # groups of 8 Mamba heads, the floors' 8 experts and eighth of the rows
    assert cfg["hybrid_override_pattern"] == PUBLISHED_PATTERN[35:44] \
        == "MEMEMEM*E"
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["mamba_num_heads"], cfg["n_groups"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["vocab_size"]) == (9, 8, 32, 4, 16, 1, 131072 // 8)
    assert cfg["mamba_num_heads"] // cfg["n_groups"] == 64 // 8
    for name in ("denoising_tower", "positions", "expand",
                 "mamba_initialisation", "router_bias", "groups",
                 "shared_expert", "parameter_dtype", "compute_dtype",
                 "weights", "dropout_and_aux_losses"):
        assert name in cfg["assumed"]
    assert "ABSENT" in cfg["assumed"]["denoising_tower"]
    for name in cfg["reduced"]:
        assert name in cfg["reduced_why"]
    assert "16 chips share each layer's experts" in cfg["deployment"]
    assert cfg["tiny"]["hybrid_override_pattern"] == "MEM*E"
    fam = harness.load_module("program", cfg["family"])
    shapes = fam.train_parts(cfg, {"remat": True})[2][0]
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    d = 2688
    mamba = (d * (2 * 2048 + 2 * 512 + 32) + 3072 * 4 + 3072 + 3 * 32 + 2048
             + 2048 * d + d)
    attention = d * 18 * 128 + 16 * 128 * d + d
    experts = d * 128 + 128 + 8 * 2 * d * 1856 + 2 * d * 3712 + d
    assert n == (4 * mamba + attention + 4 * experts + 2 * 16384 * d + d) \
        == 577780864


def test_train_flops_and_kernel_costs_count_what_they_say():
    cfg = _cfg()
    fam = harness.load_module("program", cfg["family"])
    t = 8192
    job = {"seq_len": t, "remat": True}
    d = 2688
    w = fam.matrix_weights_per_token(cfg)
    assert w == {"mamba": d * 5152 + 3072 * 4 + 2048 * d,
                 "attention": d * 18 * 128 + 2048 * d, "router": d * 128,
                 "shared": 2 * d * 3712, "head": d * 16384}
    scan = fam.ssd_ops(cfg, t)
    pairs = t * 129 / 2.0               # the chunk's own (i, j <= i)
    assert scan == {"cb": 4 * pairs * 2 * 128, "intra": 32 * pairs * 2 * 64,
                    "read": t * 32 * 2 * 64 * 128,
                    "write": t * 32 * 2 * 64 * 128}
    rows = t * 6 * 8 / 128.0                        # a layer, even routing
    assert fam.expected_expert_rows(cfg, t) == rows == 3072.0
    core = 3.0 * (t * (t + 1) / 2.0) * 16 * 2 * 2 * 128
    routed = 4 * 6.0 * rows * 2 * d * 1856
    flops = fam.train_flops(cfg, job, 1)
    assert flops == pytest.approx(
        6.0 * t * (4 * w["mamba"] + w["attention"] + 4 * (
            w["router"] + w["shared"]) + w["head"]) + routed + core
        + 4 * 3.0 * sum(scan.values()))
    assert 12.1e12 < flops < 12.5e12
    mamba = 4 * (6.0 * t * w["mamba"] + 3.0 * sum(scan.values()))
    assert 0.31 < mamba / flops < 0.34              # 33% of required ops
    experts = 4 * 6.0 * t * (w["router"] + w["shared"]) + routed
    assert 0.37 < experts / flops < 0.40
    assert 0.10 < (6.0 * t * w["attention"] + core) / flops < 0.12
    # with all their heads (64 Mamba heads, 32 / 2 of attention) the same
    # nine layers would give the Mamba layers 45%
    uncut = dict(cfg, **{k: cfg["published"][k] for k in (
        "mamba_num_heads", "n_groups", "num_attention_heads",
        "num_key_value_heads")})
    whole = fam.train_flops(uncut, job, 1)
    w_all = fam.matrix_weights_per_token(uncut)
    mamba_all = 4 * (6.0 * t * w_all["mamba"]
                     + 3.0 * sum(fam.ssd_ops(uncut, t).values()))
    assert 0.44 < mamba_all / whole < 0.47
    costs = fam.kernel_costs(cfg, job, 1)
    assert sorted(costs) == ["flash_bwd", "flash_fwd_resident", "moe_gmm",
                             "moe_tgmm", "ssd_bwd", "ssd_fwd"]
    assert costs["flash_fwd_resident"][0] == pytest.approx(2 * core / 3)
    in_kernel = scan["intra"] + scan["read"] + scan["write"]
    assert costs["ssd_fwd"][0] == pytest.approx(4 * in_kernel)
    assert costs["ssd_bwd"][0] == pytest.approx(2 * 4 * in_kernel)
    # x and dt a head, B and C a GROUP, the result, the chunk-end states
    assert costs["ssd_fwd"][1] == pytest.approx(4 * (
        t * (32 * (2 * 64 + 4) + 4 * 2 * 2 * 128) + t * 32 * 2 * 64
        + t / 128 * 32 * 4 * 64 * 128))
    for name in ("ssd_fwd", "ssd_bwd"):             # memory-bound on a v5e
        ops, nbytes = costs[name]
        assert ops / 197e12 < nbytes / 819e9
    # an ungated expert is two matrices
    weights = 2 * d * 1856
    assert costs["moe_gmm"][0] == pytest.approx(2 * 2.0 * 4 * rows * weights)
    assert costs["moe_tgmm"][0] == pytest.approx(2.0 * 4 * rows * weights)
    # THE 9-ENTRY AVERAGE: the readers hand over the mean of `rows_held`
    # over ALL the counters' entries, the zeros of the layers without
    # experts among them
    counters = {"rows_held": [0.0, 30000.0, 0.0, 31000.0, 0.0, 30500.0, 0.0,
                              0.0, 31380.0], "steps": [10.0]}
    mean = kernel_readers.expert_rows_per_step(counters)
    served = 3000 + 3100 + 3050 + 3138
    assert mean == pytest.approx(served / 9.0)
    got = fam.kernel_costs(cfg, job, 1, mean)
    assert got["moe_gmm"][0] == pytest.approx(2 * 2.0 * served * weights)
    assert got["moe_tgmm"][1] == pytest.approx(
        2.0 * served * (2 * d + 2 * 1856) + 4 * 4.0 * 8 * weights)


def test_family_refuses_a_program_without_the_ssd_path(monkeypatch):
    """What the parent commit meets when it is handed this cell: a
    BenchError at once, from every entry of the family's file."""
    cfg = _tiny_cfg()
    fam = harness.load_module("program", cfg["family"])
    monkeypatch.delattr(sparse_decoder, "SSD_COUNTERS")
    for call in (lambda: fam.build_model(cfg, {}),
                 lambda: fam.train_parts(cfg, {}),
                 lambda: fam.to_program({}, cfg)):
        with pytest.raises(harness.BenchError, match="no SSD path"):
            call()


# -- (d) the training state through the trainer -------------------------------

def _trainer(nemotron, n_devices, ckpt=None):
    import optax
    from edl_tpu.runtime.mesh import make_mesh
    from edl_tpu.runtime.trainer import ElasticTrainer
    cfg, _, fam, w, _ = nemotron
    loss_fn, has_aux, _ = fam.train_parts(cfg, {"remat": False})
    # copies: the trainer donates what it is handed
    params, extra = jax.tree_util.tree_map(jnp.array, fam.to_program(w, cfg))
    return ElasticTrainer(loss_fn, params, optax.adamw(1e-3),
                          total_batch_size=4, extra_state=extra,
                          has_aux=has_aux, checkpoint_dir=ckpt,
                          mesh=make_mesh(devices=jax.devices()[:n_devices]))


def _state_bytes(trainer):
    return {jax.tree_util.keystr(k): np.asarray(v).tobytes()
            for k, v in jax.tree_util.tree_leaves_with_path(
                trainer.train_state)}


def test_state_survives_save_restore_and_a_live_resize(nemotron, tmp_path,
                                                       monkeypatch):
    """The Mamba leaves ([d, G, k] projections, [H] vectors, the
    convolution's bias) through `_reshard_tree` and the checkpoint: a live
    4 -> 2 -> 4 is byte for byte the stop-resume chain over the same
    worlds."""
    from edl_tpu.runtime import trainer as trainer_mod
    # the process's gauge of model counters, empty for this test and as it
    # was after it: other files' tests count its series
    monkeypatch.setattr(trainer_mod._MODEL_COUNTER, "_children", {})
    cfg, _, fam, _, _ = nemotron
    batch = fam.make_batch(cfg, {"seq_len": 32}, jax.random.PRNGKey(5), 4)
    step = lambda tr: tr.train_step(tr.local_batch_slice(batch))
    live = _trainer(nemotron, 4)
    chain = []
    try:
        step(live)
        assert live.live_resize(2)["mode"] == "live"
        step(live)
        live.live_resize(4)
        step(live)
        ckpt = str(tmp_path / "ckpt")
        for i, world in enumerate((4, 2, 4)):
            tr = _trainer(nemotron, world, ckpt)
            chain.append(tr)
            assert tr.resume() == (i > 0)
            step(tr)
            tr.save()
        got, want = _state_bytes(live), _state_bytes(chain[-1])
        assert any("in_proj_zxbc" in k for k in want)
        assert any("conv_bias" in k for k in want)
        assert sorted(got) == sorted(want)
        assert [k for k in want if got[k] != want[k]] == []
    finally:
        for tr in [live] + chain:
            tr.close()
    counters = kernel_readers.model_counters()
    assert counters["steps"] == [3.0]
    assert len(counters["ssd_state_absmax"]) == 5


@pytest.mark.parametrize("name", ["ssd_chunk_log_decay_min",
                                  "ssd_state_absmax", "ssd_fwd_device_ms",
                                  "ssd_fwd_roofline_pct", "ssd_bwd_device_ms",
                                  "ssd_bwd_roofline_pct"])
def test_new_readers_return_none_where_there_is_nothing_to_read(monkeypatch,
                                                                name):
    """The parent commit's program has no such counter and no such kernel
    in its trace: the reader says nothing and does not raise."""
    cfg, job = _cfg(), _job()
    view = {"traffic": job, "cell": {"chips": 1}, "config": cfg,
            "counters": {"traced_steps": 10},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
            "trace": {"ops": [["fusion.3", 0.2], ["moe_gmm", 0.1]]}}
    mod = harness.load_module("metrics", name)
    if hasattr(mod, "model_counters"):
        monkeypatch.setattr(mod, "model_counters",
                            lambda: {"steps": [2.0], "rows_held": [4.0]})
    assert mod.read(view) is None
    if not hasattr(mod, "model_counters"):
        kernel = name[:7]                       # ssd_fwd, ssd_bwd
        seen = dict(view, trace={"ops": [["transpose_jvp_%s" % kernel,
                                          0.05]]})
        monkeypatch.setattr(kernel_readers, "model_counters", lambda: {})
        assert mod.read(seen) > 0
