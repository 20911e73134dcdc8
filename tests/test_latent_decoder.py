"""Multi-head latent attention, the sigmoid router that chooses by score +
bias, the ungated shared expert and the leading dense layer
(`models/sparse_decoder.py`, `parallel/moe.py:route_sigmoid_top_k`,
`ops/flash_attention.py` at unequal q/k and v widths; the
`kanana-2-30b-a3b` configuration).

- the band kernels (Pallas interpreter) at q/k 192 beside v 128, forward and
  backward, resident and streamed, against the dense path; the dispatch;
- the decoder's loss, counters and every gradient leaf against
  `benchmark/reference/kanana-2-30b-a3b.py` at the `tiny` size in float32,
  bfloat16 inside the tiny limits, the int8 control far outside them, and
  what each compared number guards, by omission;
- the shares add up: head shares of the attention, expert shares of the
  routed part with the shared expert and the latent counted once, vocabulary
  shares of the logits;
- the configuration, the family's counts, the counters through
  `ElasticTrainer` to the two new readers.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness, kernel_readers
from edl_tpu.models import sparse_decoder
from edl_tpu.ops import attention, flash_attention
from edl_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "kanana-2-30b-a3b"
TRAFFIC = "tokens-8192-mla"


# -- (a) the band kernels at unequal widths -----------------------------------

def _qkv(seq, heads=2, dk=192, dv=128, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    shape = lambda w: (1, seq, heads, w)
    return (jax.random.normal(ks[0], shape(dk), dtype),
            jax.random.normal(ks[1], shape(dk), dtype),
            jax.random.normal(ks[2], shape(dv), dtype),
            jax.random.normal(ks[3], shape(dv), jnp.float32))


def _context_and_grads(q, k, v, w, use_flash, window=None):
    def loss(q, k, v):
        out = attention.attention_context(
            q, k, v, causal=True, mask=None, dtype=jnp.float32,
            use_flash=use_flash, window=window)
        return jnp.sum(out * w), out
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out,) + grads


@pytest.mark.parametrize("seq,window", [(256, None), (384, None),
                                        (256, 100)])
@pytest.mark.parametrize("resident", [True, False],
                         ids=["resident", "streamed"])
def test_band_kernels_take_v_at_a_width_of_its_own(monkeypatch, resident,
                                                   seq, window):
    """q and k 192 wide, v, the result, dO and dv 128 wide: the kernels in
    the interpreter against the dense path, result and all three
    gradients; with no resident room the streamed forward and the split
    backward run, else the resident pair."""
    if not resident:
        monkeypatch.setattr(flash_attention, "_RESIDENT_KV_BYTES", 0)
    q, k, v, w = _qkv(seq)
    want = _context_and_grads(q, k, v, w, False, window)
    got = _context_and_grads(q, k, v, w, True, window)
    assert got[0].shape == (1, seq, 2, 128)
    assert [g.shape[-1] for g in got[1:]] == [192, 192, 128]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_unequal_widths_run_the_kernels_their_bytes_call_for(monkeypatch):
    """The resident limit reads k's and v's OWN bytes: 1024 rows of 192 +
    128 in float32 are 1.25 MiB, over a limit of 1 MiB that two 128-wide
    tensors would have met exactly."""
    from jaxpr_kernels import pallas_call_names
    q, k, v, w = _qkv(1024, heads=1)
    # a new function a call: nothing traced under the other limit is reused
    names = lambda: pallas_call_names(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention.mha(
            q, k, v, causal=True, interpret=True) * w),
        argnums=(0, 1, 2)))(q, k, v).jaxpr)
    assert sorted(set(names())) == [flash_attention.BWD_NAME,
                                    flash_attention.FWD_RESIDENT_NAME]
    monkeypatch.setattr(flash_attention, "_RESIDENT_KV_BYTES", 1 << 20)
    assert sorted(set(names())) == [flash_attention.BWD_DKV_NAME,
                                    flash_attention.BWD_DQ_NAME,
                                    flash_attention.FWD_STREAM_NAME]


def test_scale_is_the_query_width_s():
    q, k, v, _ = _qkv(128)
    got = flash_attention.mha(q, k, v, causal=True, interpret=True)
    want = flash_attention.mha(q, k, v, causal=True, interpret=True,
                               sm_scale=192 ** -0.5)
    np.testing.assert_array_equal(got, want)
    other = flash_attention.mha(q, k, v, causal=True, interpret=True,
                                sm_scale=128 ** -0.5)
    assert float(jnp.abs(other - want).max()) > 1e-2


def test_flash_dispatch_reason_at_192_beside_128():
    reason = attention.flash_dispatch_reason
    assert reason(8192, 192, platform="tpu", v_head_dim=128) is None
    assert reason(8192, 192, platform="tpu", v_head_dim=192) is None
    assert "v_head_dim 100" in reason(8192, 192, platform="tpu",
                                      v_head_dim=100)
    assert "one width" in reason(8192, 192, platform="tpu", v_head_dim=128,
                                 streams=(4, 4096))
    assert "platform" in reason(8192, 192, platform="cpu", v_head_dim=128)
    q, k, v, _ = _qkv(128)
    for masked in (dict(select=(q, k, v, v)), dict(streams=(4, 64)),
                   dict(use_ring=True)):
        with pytest.raises(ValueError, match="another width"):
            attention.attention_context(q, k, v, causal="select" in masked,
                                        mask=None, dtype=jnp.float32,
                                        **masked)


# -- (b) the decoder against the plain reference ------------------------------

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


@pytest.fixture(scope="module")
def kanana():
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


def _reference(kanana, ref=None):
    """(loss, the gradient's leaves in the program's layout)."""
    cfg, own, fam, w, batch = kanana
    loss, g = jax.jit(lambda w: (ref or own).loss_and_grad(w, batch, cfg))(w)
    return loss, _leaves(fam.to_program(g, cfg)[0])


@pytest.fixture(scope="module")
def reference(kanana):
    return _reference(kanana)


@pytest.fixture(scope="module", params=["plain", "kernels"])
def kanana_float32(request, kanana, reference):
    cfg, ref, fam, w, batch = kanana
    loss, grads, extra = _loss_and_grad(
        cfg, fam, w, batch, jnp.float32,
        use_flash=request.param == "kernels")
    return loss, _leaves(grads), extra


def test_loss_and_counters_match_the_reference_float32(kanana, reference,
                                                       kanana_float32):
    cfg, ref, fam, w, batch = kanana
    loss, _, extra = kanana_float32
    np.testing.assert_allclose(loss, reference[0], rtol=2e-5)
    c = extra["counters"]
    assert sorted(c) == sorted(sparse_decoder.COUNTERS
                               + sparse_decoder.ROUTE_COUNTERS + ("steps",))
    assert float(c["steps"]) == 1.0
    assert float(c["rows_dropped"].sum()) == 0.0
    want = jax.jit(lambda w: ref.routing_counts(w, batch["input_ids"], cfg))(
        w)
    assert fam.dense_layers(cfg) == (1, 0, 0)
    for name in ("rows_held", "route_bias_flips"):
        np.testing.assert_array_equal(c[name], want[name])
        assert float(c[name][0]) == 0.0 < float(c[name][1:].min())
    tokens = batch["input_ids"].size
    np.testing.assert_allclose(
        c["route_weight_sum"],
        [0.0] + [cfg["routed_scaling_factor"] * tokens] * 2, rtol=1e-5)
    np.testing.assert_allclose(c["route_weight_sum"],
                               want["route_weight_sum"], rtol=1e-5)
    for name in sparse_decoder.COUNTERS:        # a dense layer counts zeros
        assert float(c[name][0]) == 0.0


@pytest.mark.parametrize("leaf", _leaf_names())
def test_gradient_leaf_matches_reference_float32(kanana_float32, reference,
                                                 leaf):
    _, grads, _ = kanana_float32
    want = reference[1][leaf]
    if "router_bias" in leaf:       # in the choice alone: nothing reaches it
        assert float(jnp.abs(grads[leaf]).max()) == 0.0
        assert float(jnp.abs(want).max()) == 0.0
        return
    scale = float(jnp.abs(want).max())
    assert scale > 0          # every other tensor of the model learns
    np.testing.assert_allclose(grads[leaf], want, atol=2e-4 * scale,
                               rtol=2e-3)


def test_remat_changes_no_number(kanana, kanana_float32):
    cfg, _, fam, w, batch = kanana
    loss, grads, _ = _loss_and_grad(cfg, fam, w, batch, jnp.float32,
                                    remat=False)
    np.testing.assert_allclose(loss, kanana_float32[0], rtol=1e-6)
    assert _distance(_leaves(grads), kanana_float32[1]) < 1e-5


@pytest.fixture(scope="module")
def kanana_bfloat16(kanana):
    cfg, _, fam, w, batch = kanana
    loss, grads, _ = _loss_and_grad(cfg, fam, w, batch, jnp.bfloat16)
    return loss, _leaves(grads)


def _errors(got, want):
    """(loss_rel_err, grad_rel_err) as `correct` compares them."""
    return (abs(float(got[0]) - float(want[0])) / abs(float(want[0])),
            _distance(got[1], want[1]))


def test_matches_reference_bfloat16(kanana_bfloat16, reference):
    """bf16 activations and products as the cell runs them: inside the
    tiny limits, by the loss and by the whole gradient in relative L2."""
    limits = _tiny_limits()
    loss_err, grad_err = _errors(kanana_bfloat16, reference)
    assert loss_err < limits["loss_rel_err"]
    assert grad_err < limits["grad_rel_err"]


def test_int8_control_is_far_from_the_reference(kanana, reference):
    """The control `correct` has to refuse: outside the tiny limits."""
    cfg, ref, fam, w, batch = kanana
    _, g8 = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg, "int8"))(w)
    got = _leaves(fam.to_program(g8, cfg)[0])
    assert _distance(got, reference[1]) > 5 * _tiny_limits()["grad_rel_err"]


# what each compared number guards: a reference with ONE thing left out or
# done otherwise, against which the program (as it is) must read outside a
# limit — {name: (the reference's function to replace, its replacement given
# the sound one)}

def _first_head_only(sound):
    def scores(qp_blk, kp, q=None):
        s = sound(qp_blk, kp, q)
        return s * (jnp.arange(s.shape[1]) == 0)[None, :, None, None]
    return scores


def _route_with(weights):
    """`route` with the weights worked out by `weights(sc, idx, b_r, cfg)`
    and the choice by `choice(sc, b_r)`."""
    def route(u, w_r, b_r, cfg, q=None, choice=lambda sc, b: sc + b):
        sc = jax.nn.sigmoid(jnp.einsum("nd,de->ne", u, w_r,
                                       precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(choice(jax.lax.stop_gradient(sc), b_r),
                               cfg["num_experts_per_tok"])
        return idx, weights(sc, idx, b_r, cfg), sc
    return route


def _sound_weights(sc, idx, b_r, cfg, bias=0.0, factor=None, norm=True):
    top = jnp.take_along_axis(sc + bias * b_r, idx, axis=-1)
    if norm:
        top = top / (top.sum(axis=-1, keepdims=True) + 1e-20)
    return top * (cfg["routed_scaling_factor"] if factor is None else factor)


OMISSIONS = {
    "the latent's norm dropped": (
        "latent_norm", lambda sound: lambda c, g, eps: c),
    "the latent's norm without its gain": (
        "latent_norm", lambda sound: lambda c, g, eps: sound(c, 1.0, eps)),
    "the rotary key read by the first head alone": (
        "rotary_scores", _first_head_only),
    "the scale at the value's width": (
        "softmax_scale",
        lambda sound: lambda cfg: float(cfg["v_head_dim"]) ** -0.5),
    "the bias left out of the choice": (
        "route", lambda sound: lambda *a, **k: _route_with(_sound_weights)(
            *a, choice=lambda sc, b: sc, **k)),
    "the bias let into the weights": (
        "route", lambda sound: _route_with(
            lambda *a: _sound_weights(*a, bias=1.0))),
    "no routed scaling factor": (
        "route", lambda sound: _route_with(
            lambda *a: _sound_weights(*a, factor=1.0))),
    "no normaliser over the chosen": (
        "route", lambda sound: _route_with(
            lambda *a: _sound_weights(*a, norm=False))),
    "the shared expert under a gate": (
        "shared_part", lambda sound: lambda u, lw, q=None: sound(u, lw, q)
        * jax.nn.sigmoid(jnp.mean(u, axis=-1, keepdims=True))),
    "no shared expert": (
        "shared_part", lambda sound: lambda u, lw, q=None: 0.0 * u),
}


def test_the_sound_route_is_the_reference_s(kanana):
    """The omissions' `route` with nothing left out is the reference's."""
    cfg, ref, _, w, _ = kanana
    lw = ref.layer_weights(w, 1)
    u = jax.random.normal(jax.random.PRNGKey(5), (64, cfg["hidden_size"]))
    got = _route_with(_sound_weights)(u, lw["w_r"], lw["b_r"], cfg)
    want = ref.route(u, lw["w_r"], lw["b_r"], cfg)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6)


@pytest.mark.parametrize("name", sorted(OMISSIONS))
def test_what_the_limits_guard_by_omission(monkeypatch, kanana,
                                           kanana_bfloat16, name):
    """The bfloat16 program against a reference that leaves one thing out:
    refused by at least one of the two limits."""
    _, ref, _, _, _ = kanana
    attr, make = OMISSIONS[name]
    monkeypatch.setattr(ref, attr, make(getattr(ref, attr)))
    limits = _tiny_limits()
    loss_err, grad_err = _errors(kanana_bfloat16, _reference(kanana))
    assert (loss_err > limits["loss_rel_err"]
            or grad_err > limits["grad_rel_err"]), (loss_err, grad_err)


def test_a_dense_layer_given_experts_is_another_parameter_tree(kanana):
    """What `kinds/train.py:make_trainer` refuses: the stack with experts
    in its leading layer too."""
    cfg, _, fam, _, _ = kanana
    want = jax.tree_util.tree_structure(
        fam.train_parts(cfg, {"remat": True})[2])
    model = fam.build_model(cfg, {"remat": True})
    own = lambda m: jax.tree_util.tree_structure(jax.eval_shape(
        lambda: sparse_decoder.create_model_and_loss(m)[1:3]))
    assert own(model) == want
    assert own(model.clone(dense_layout=())) != want
    names = _leaf_names()
    assert any("layer_0" in n and "ffn_gate_up" in n for n in names)
    assert not any("layer_0" in n and ("experts_" in n or "router" in n
                                       or "shared_" in n) for n in names)
    assert not any("shared_gate'" in n or "'key'" in n or "'value'" in n
                   for n in names)


def test_a_latent_layer_refuses_what_it_cannot_be(kanana):
    cfg, _, fam, _, _ = kanana
    model = fam.build_model(cfg, {})
    ids = jnp.zeros((1, 16), jnp.int32)
    for attrs in (dict(qk_norm=True), dict(attn_gate=True),
                  dict(kv_heads=1)):
        with pytest.raises(ValueError, match="latent-attention layer"):
            model.clone(**attrs).init(jax.random.PRNGKey(0), ids)
    # since PR 61 a latent layer may go without positions: its rotary key
    # part is then read unturned (tests/test_kimi_linear_decoder.py)
    n = cfg["num_hidden_layers"]
    bare = model.clone(rope_layout=(0,) * n).init(jax.random.PRNGKey(0), ids)
    assert jax.tree_util.tree_structure(bare) == jax.tree_util.tree_structure(
        model.init(jax.random.PRNGKey(0), ids))
    with pytest.raises(ValueError, match="router_scoring"):
        model.clone(router_scoring="tanh").init(jax.random.PRNGKey(0), ids)


# -- (c) the shares add up ----------------------------------------------------

#: the small uncut layer: 4 head shares of 2, 16 expert shares of 2
UNCUT = dict(num_attention_heads=8, num_key_value_heads=8,
             n_routed_experts=32, num_router_outputs=32, first_expert=0,
             num_hidden_layers=2, num_experts_per_tok=6)


def _uncut(kanana):
    cfg, ref, _, _, _ = kanana
    whole = dict(cfg, **UNCUT)
    lw = ref.layer_weights(ref.init_weights(whole, jax.random.PRNGKey(7)), 1)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 48, cfg["hidden_size"]))
    return whole, lw, x


def test_head_shares_of_the_attention_add_up_with_the_latent_whole(kanana):
    """Four shares of 2 heads, each with the latent's down-projection and
    norm WHOLE and its own columns of W_q and W_kvb and rows of W_o,
    through the PROGRAM's layer (its feed-forward part silenced): their
    parts of the residual add up to the uncut reference's attention."""
    cfg, ref, fam, _, _ = kanana
    whole, lw, x = _uncut(kanana)
    d, dc = cfg["hidden_size"], cfg["kv_lora_rank"]
    dqk, dn, dv = (cfg["qk_head_dim"], cfg["qk_nope_head_dim"],
                   cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    want = ref.attention_part(ref._rms(x, lw["g1"], eps), lw, whole)
    layer = sparse_decoder.SparseDecoderLayer(
        heads=2, kv_heads=2, head_dim=dqk, num_experts=0, experts_held=0,
        first_expert=0, experts_per_token=0, expert_width=0, use_rope=True,
        rope_theta=float(cfg["rope_theta"]), window=None, eps=eps,
        dtype=jnp.float32, use_flash=False, dense_width=8,
        expert_activation="silu", latent_dim=dc,
        rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=dv)
    total = jnp.zeros_like(want)
    by_head = lambda m, w: m.reshape(m.shape[0], 8, w)
    for share in range(4):
        hs = slice(2 * share, 2 * share + 2)
        params = {
            "norm_attn": {"scale": lw["g1"]}, "norm_moe": {"scale": lw["g2"]},
            "norm_latent": {"scale": lw["g_c"]}, "kv_down": lw["w_kva"],
            "query": by_head(lw["w_q"], dqk)[:, hs],
            "kv_up": by_head(lw["w_kvb"], dn + dv)[:, hs],
            "out": lw["w_o"].reshape(8, dv, d)[hs],
            "ffn_gate_up": jnp.zeros((d, 16)), "ffn_down": jnp.zeros((8, d))}
        out, counters = layer.apply({"params": params}, x)
        assert counters == {}
        total = total + (out - x)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)


def test_expert_shares_add_up_with_the_shared_expert_counted_once(kanana):
    """Sixteen shares of 2 experts, each routing over all 32 by score +
    bias through the PROGRAM's router and held experts: their parts, and
    the shared expert ONCE, give the uncut reference's feed-forward part;
    every share counts the same flips and weights, and the rows they serve
    are all the choices."""
    cfg, ref, _, _, _ = kanana
    whole, lw, x = _uncut(kanana)
    u = x.reshape(-1, cfg["hidden_size"])
    want = ref.feed_forward_part(u, lw, whole, False)
    total = moe.shared_expert_ffn(u, lw["w_sgu"], lw["w_sd"], None)
    rows, seen = 0.0, []
    for share in range(16):
        idx, p, routed = moe.route_sigmoid_top_k(
            u, lw["w_r"], lw["b_r"], 6, cfg["routed_scaling_factor"])
        es = slice(2 * share, 2 * share + 2)
        m, counters = moe.held_experts_ffn(
            u, idx, p, lw["w_gate_up"][es], lw["w_down"][es], 2 * share,
            activation="silu")
        total = total + m
        rows += float(counters["rows_held"])
        seen.append({n: float(v) for n, v in routed.items()})
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)
    assert rows == u.shape[0] * 6
    assert all(s == seen[0] for s in seen)
    assert seen[0]["route_bias_flips"] > 0
    assert seen[0]["route_weight_sum"] == pytest.approx(
        cfg["routed_scaling_factor"] * u.shape[0], rel=1e-5)


def test_vocabulary_shares_side_by_side_give_the_uncut_logits(kanana):
    """Eight shares of the untied head (and of the embedding, whose rows
    the ids of every share find alike here) through the PROGRAM: their
    logits side by side are the uncut reference's."""
    cfg, ref, fam, _, batch = kanana
    v = cfg["vocab_size"]
    whole = dict(cfg, vocab_size=8 * v)
    w = ref.init_weights(whole, jax.random.PRNGKey(9))
    w["embed"] = jnp.tile(w["embed"][:v], (8, 1))
    ids = batch["input_ids"]
    h = ref.hidden(w, ids, whole)
    want = jnp.einsum("btd,dv->btv", h, w["head"],
                      precision=jax.lax.Precision.HIGHEST)
    model = fam.build_model(cfg, {}).clone(dtype=jnp.float32)
    got = []
    for share in range(8):
        rows = slice(share * v, (share + 1) * v)
        part = dict(w, embed=w["embed"][rows], head=w["head"][:, rows])
        got.append(model.apply({"params": fam.to_program(part, cfg)[0]},
                               ids)[0])
    np.testing.assert_allclose(jnp.concatenate(got, axis=-1), want,
                               atol=2e-4, rtol=2e-4)


# -- (d) the configuration and the family's counts ----------------------------

def test_configuration_holds_the_published_widths():
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "num_attention_heads", "num_key_value_heads",
                              "vocab_size"]
    assert cfg["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 128,
        "num_attention_heads": 32, "num_key_value_heads": 32,
        "vocab_size": 128256}
    assert (cfg["hidden_size"], cfg["qk_head_dim"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["q_lora_rank"], cfg["moe_intermediate_size"],
            cfg["n_shared_experts"], cfg["intermediate_size"],
            cfg["num_router_outputs"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"], cfg["scoring_func"],
            cfg["first_k_dense_replace"], cfg["n_group"], cfg["topk_group"],
            cfg["rope_theta"], cfg["rms_norm_eps"]) == (
        2048, 192, 128, 64, 128, 512, None, 768, 2, 6144, 128, 6, 2.448,
        "sigmoid", 1, 1, 1, 1000000, 1e-6)
    # the floors: 1 + 4 layers, 8 experts, an eighth of the vocabulary
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["vocab_size"]) == (5, 8, 8, 8, 128256 // 8)
    for name in ("rope", "mtp", "router_bias", "groups", "shared_experts",
                 "latent_norm", "weights"):
        assert name in cfg["assumed"]
    for name in cfg["reduced"]:
        assert name in cfg["reduced_why"]
    assert "16 chips share each layer's experts" in cfg["deployment"]
    fam = harness.load_module("program", cfg["family"])
    shapes = fam.train_parts(cfg, {"remat": True})[2][0]
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    attention_ = (2048 * 8 * 192 + 2048 * 576 + 512 + 512 * 8 * 256
                  + 8 * 128 * 2048) + 2 * 2048
    expert_layer = (attention_ + 2048 * 128 + 128 + 8 * 3 * 2048 * 768
                    + 3 * 2048 * 1536)
    assert n == (attention_ + 3 * 2048 * 6144 + 4 * expert_layer
                 + 2 * 16032 * 2048 + 2048) == 330589184


def test_interleaved_rotary_pairs_are_half_split_ones_permuted():
    """`assumed.rope`: the published `rope_interleave` (pairs 2i, 2i + 1)
    gives the scores of the half-split convention (i, i + 32) under ONE
    permutation of the rotary columns, applied to query and key alike."""
    ref = harness.load_module("reference", CONFIG)
    theta, w = 1e6, 64
    q, k = (jax.random.normal(jax.random.PRNGKey(i), (1, 48, 2, w))
            for i in (1, 2))

    def interleaved(x):
        freq = theta ** (-jnp.arange(w // 2, dtype=jnp.float32) * 2.0 / w)
        ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)

    # column j of the half-split layout sits at perm[j] of the interleaved
    perm = jnp.concatenate([jnp.arange(0, w, 2), jnp.arange(1, w, 2)])
    lay = lambda x: jnp.zeros_like(x).at[..., perm].set(x)
    scores = lambda a, b: jnp.einsum("bqhd,bkhd->bhqk", a, b)
    np.testing.assert_allclose(
        scores(interleaved(lay(q)), interleaved(lay(k))),
        scores(ref._rope(q, theta), ref._rope(k, theta)), atol=2e-4)


def test_train_flops_and_kernel_costs_count_what_they_say():
    cfg = _cfg()
    fam = harness.load_module("program", cfg["family"])
    t = 8192
    job = {"seq_len": t, "remat": True}
    w = fam.matrix_weights_per_token(cfg)
    assert w == {"attention": 2048 * 8 * 192 + 2048 * 576 + 512 * 8 * 256
                 + 8 * 128 * 2048, "dense": 3 * 2048 * 6144,
                 "router": 2048 * 128, "shared": 3 * 2048 * 1536,
                 "head": 2048 * 16032}
    pairs = t * (t + 1) / 2.0
    core = 5 * 3.0 * pairs * 8 * 2 * (192 + 128)
    rows = t * 6 * 8 / 128.0                        # a layer, even routing
    assert fam.expected_expert_rows(cfg, t) == rows == 3072.0
    routed = 4 * 6.0 * rows * 3 * 2048 * 768
    flops = fam.train_flops(cfg, job, 1)
    assert flops == pytest.approx(
        6.0 * t * (5 * w["attention"] + w["dense"] + 4 * (
            w["router"] + w["shared"]) + w["head"]) + routed + core)
    assert 10.0e12 < flops < 10.3e12
    latent = core + 6.0 * t * 5 * w["attention"]
    assert 0.42 < latent / flops < 0.44             # 43% of required ops
    assert 0.03 < routed / flops < 0.04
    # uncut, at the same length: 61%, the routed experts 25%
    uncut = dict(cfg, **cfg["published"])
    whole = fam.train_flops(uncut, job, 1)
    w_all = fam.matrix_weights_per_token(uncut)
    latent_all = (48 * 3.0 * pairs * 32 * 2 * 320
                  + 6.0 * t * 48 * w_all["attention"])
    assert 0.60 < latent_all / whole < 0.62
    # the calls the step makes under remat: at 8192 tokens k + v of a head
    # are 5 MiB: the streamed forward twice a layer, the split backward once
    costs = fam.kernel_costs(cfg, job, 1)
    assert sorted(costs) == ["flash_bwd", "flash_fwd_stream", "moe_gmm",
                             "moe_tgmm"]
    assert costs["flash_fwd_stream"][0] == pytest.approx(2 * core / 3)
    assert costs["flash_bwd"][0] == pytest.approx(
        5 * pairs * 8 * 2 * (3 * 192 + 2 * 128))
    # bytes: the rotary key once a token, not once a head
    assert costs["flash_fwd_stream"][1] == pytest.approx(5 * 2 * t * (
        2.0 * (8 * 192 + 8 * 128 + 8 * 128 + 64 + 8 * 128) + 4.0 * 8))
    for name in ("flash_fwd_stream", "flash_bwd"):  # compute-bound on a v5e
        ops, nbytes = costs[name]
        assert ops / 197e12 > nbytes / 819e9
    short = fam.kernel_costs(cfg, dict(job, seq_len=4096), 1)
    assert "flash_fwd_resident" in short and "flash_fwd_stream" not in short
    # THE 5-ENTRY AVERAGE: the readers hand over the mean of `rows_held`
    # over ALL the counters' entries, the dense layer's zero among them
    counters = {"rows_held": [0.0, 30000.0, 31000.0, 30500.0, 31380.0],
                "steps": [10.0]}
    mean = kernel_readers.expert_rows_per_step(counters)
    assert mean == pytest.approx((3000 + 3100 + 3050 + 3138) / 5.0)
    served = 3000 + 3100 + 3050 + 3138
    got = fam.kernel_costs(cfg, job, 1, mean)
    weights = 3 * 2048 * 768
    assert got["moe_gmm"][0] == pytest.approx(2 * 2.0 * served * weights)
    assert got["moe_tgmm"][0] == pytest.approx(2.0 * served * weights)
    # ... and the experts' matrices of the FOUR expert layers
    assert got["moe_tgmm"][1] == pytest.approx(
        2.0 * served * (2048 + 3 * 768 + 2048) + 4 * 4.0 * 8 * weights)
    assert costs["moe_gmm"][0] == pytest.approx(
        2 * 2.0 * 4 * rows * weights)


def test_family_refuses_a_program_without_the_latent_path(monkeypatch):
    """What the parent commit meets when it is handed this cell: a
    BenchError at once, from every entry of the family's file."""
    cfg = _tiny_cfg()
    fam = harness.load_module("program", cfg["family"])
    monkeypatch.delattr(sparse_decoder, "ROUTE_COUNTERS")
    for call in (lambda: fam.build_model(cfg, {}),
                 lambda: fam.train_parts(cfg, {}),
                 lambda: fam.to_program({}, cfg)):
        with pytest.raises(harness.BenchError, match="no latent path"):
            call()


# -- (e) the counters, through the trainer, to the readers -------------------

def test_trainer_mirrors_the_router_s_counters(kanana):
    import optax
    from edl_tpu.runtime.mesh import make_mesh
    from edl_tpu.runtime.trainer import ElasticTrainer
    cfg, _, fam, w, batch = kanana
    loss_fn, has_aux, _ = fam.train_parts(cfg, {"remat": False})
    # copies: the trainer donates what it is handed
    params, extra = jax.tree_util.tree_map(jnp.array, fam.to_program(w, cfg))
    bias = {n: np.asarray(v) for n, v in _leaves(params).items()
            if "router_bias" in n}
    trainer = ElasticTrainer(loss_fn, params, optax.sgd(1e-3),
                             total_batch_size=2, extra_state=extra,
                             has_aux=has_aux,
                             mesh=make_mesh(devices=jax.devices()[:1]))
    try:
        staged = trainer.place_batch(batch)
        for _ in range(2):
            trainer.train_step(staged)
        after = _leaves(trainer.train_state["params"])
    finally:
        trainer.close()
    for name, value in bias.items():        # nothing moves the bias
        np.testing.assert_array_equal(after[name], value)
    got = kernel_readers.model_counters()
    assert got["steps"] == [2.0]
    flips, total = got["route_bias_flips"], got["route_weight_sum"]
    assert len(flips) == len(total) == 3 and flips[0] == total[0] == 0.0
    view = {"traffic": {"seq_len": 32, "batch_per_chip": 2},
            "cell": {"chips": 1}, "config": cfg}
    read = lambda name: harness.load_module("metrics", name).read(view)
    assert read("moe_route_weight_sum") == pytest.approx(
        cfg["routed_scaling_factor"], rel=1e-5)
    assert read("moe_bias_choice_flips_pct") == pytest.approx(
        100.0 * sum(flips) / (64 * 3 * 2 * 2.0))
    assert 0.0 < read("moe_bias_choice_flips_pct") < 50.0
    assert read("moe_rows_dropped") == 0.0


@pytest.mark.parametrize("name", ["moe_bias_choice_flips_pct",
                                  "moe_route_weight_sum"])
def test_new_readers_return_none_where_nothing_was_counted(monkeypatch,
                                                           name):
    """The parent commit's program has no such counter: the reader says
    nothing and does not raise."""
    view = {"traffic": {"seq_len": 32, "batch_per_chip": 2},
            "cell": {"chips": 1}, "config": _tiny_cfg()}
    mod = harness.load_module("metrics", name)
    for counters in ({}, {"steps": [2.0]},
                     {"steps": [0.0], "route_bias_flips": [1.0],
                      "route_weight_sum": [1.0]},
                     {"steps": [2.0], "route_bias_flips": [0.0, 0.0],
                      "route_weight_sum": [0.0, 0.0]}):
        monkeypatch.setattr(mod, "model_counters", lambda: counters)
        assert mod.read(view) is None
