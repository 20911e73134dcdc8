"""The decoder whose layers are Kimi Delta Attention or latent attention
WITHOUT positions, the leading one over a dense feed-forward part and the
others over experts (`models/sparse_decoder.py`: `_kda`, `mixer_layout` 3,
`_latent_attention` unturned; the `kimi-linear-48b-a3b` configuration).

- the decoder's loss, counters and every gradient leaf against
  `benchmark/reference/kimi-linear-48b-a3b.py` at the `tiny` size in float32
  (plain scan and kernels), bfloat16 inside the tiny limits, the int8 control
  far outside them, and what each compared number guards, by omission;
- the shares add up: two head shares of a KDA layer and of the MLA layer
  (what both chips compute alike — the low-rank down-projections, the latent
  — counted once), expert shares with the shared expert counted once;
- the five scopes in the step's jaxpr, the kernels a step calls, the
  configuration, the family's counts, the counters through `ElasticTrainer`
  to the new readers.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxpr_kernels import (chunk_local_algebra, gradient_jaxpr,
                           gradient_kernel_calls)

from benchmark.lib import harness, kernel_readers
from edl_tpu.models import sparse_decoder
from edl_tpu.obs import devtime
from edl_tpu.ops import gated_delta
from edl_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "kimi-linear-48b-a3b"
TRAFFIC = "tokens-8192-kda"
CELL = "kimi-kda-train-8k"
HI = jax.lax.Precision.HIGHEST
NEW_METRICS = ("kda_fwd_device_ms", "kda_fwd_roofline_pct",
               "kda_bwd_device_ms", "kda_bwd_roofline_pct",
               "kda_chunk_log_decay_min", "kda_state_absmax")


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


# -- (c) the decoder against the plain reference ------------------------------

@pytest.fixture(scope="module")
def kimi():
    """80 tokens a sequence: a chunk of 64 and 16 of the next, so the state
    crosses a chunk boundary and a chunk holds four 16-row blocks."""
    cfg = _tiny_cfg()
    ref = harness.load_module("reference", CONFIG)
    fam = harness.load_module("program", cfg["family"])
    w = ref.init_weights(cfg, jax.random.PRNGKey(11))
    batch = fam.make_batch(cfg, _job()["tiny"], jax.random.PRNGKey(12), 2)
    assert batch["input_ids"].shape == (2, 80)
    return cfg, ref, fam, w, batch


def _loss_and_grad(cfg, fam, w, batch, dtype, remat=True, use_flash=None):
    model = fam.build_model(cfg, {"remat": remat}).clone(
        dtype=dtype, use_flash=use_flash)
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


def _reference(kimi, w=None):
    """(loss, the gradient's leaves in the program's layout)."""
    cfg, ref, fam, own, batch = kimi
    loss, g = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg))(
        own if w is None else w)
    return loss, _leaves(fam.to_program(g, cfg)[0])


@pytest.fixture(scope="module")
def reference(kimi):
    return _reference(kimi)


@pytest.fixture(scope="module", params=["plain", "kernels"])
def kimi_float32(request, kimi, reference):
    cfg, ref, fam, w, batch = kimi
    loss, grads, extra = _loss_and_grad(
        cfg, fam, w, batch, jnp.float32,
        use_flash=request.param == "kernels")
    return loss, _leaves(grads), extra


def test_loss_and_counters_match_the_reference_float32(kimi, reference,
                                                       kimi_float32):
    cfg, ref, fam, w, batch = kimi
    loss, _, extra = kimi_float32
    np.testing.assert_allclose(loss, reference[0], rtol=2e-5)
    c = extra["counters"]
    assert sorted(c) == sorted(
        sparse_decoder.COUNTERS + sparse_decoder.ROUTE_COUNTERS
        + sparse_decoder.KDA_COUNTERS + ("steps",))
    assert float(c["steps"]) == 1.0
    assert float(c["rows_dropped"].sum()) == 0.0
    kda = np.asarray(fam.kda_layers(cfg), bool)
    experts = ~np.asarray(fam.dense_layers(cfg), bool)
    assert kda.tolist() == [True, True, True, False, True]
    assert experts.tolist() == [False, True, True, True, True]
    want = jax.jit(lambda w: ref.layer_counts(
        w, batch["input_ids"], cfg, gated_delta.CHUNK))(w)
    for name in ("rows_held", "route_bias_flips"):
        np.testing.assert_array_equal(c[name], want[name])
    for name in ("route_weight_sum", "kda_chunk_log_decay_min"):
        np.testing.assert_allclose(c[name], want[name], rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(c["route_weight_sum"])[experts],
        cfg["routed_scaling_factor"] * batch["input_ids"].size, rtol=1e-5)
    # a layer without the part counts zeros
    for name in sparse_decoder.COUNTERS + sparse_decoder.ROUTE_COUNTERS:
        assert (np.asarray(c[name])[~experts] == 0).all()
    assert (np.asarray(c["rows_held"])[experts] > 0).all()
    low, top = (np.asarray(c[n]) for n in sparse_decoder.KDA_COUNTERS)
    assert (low[kda] < 0).all() and (top[kda] > 0).all()
    assert (low[~kda] == 0).all() and (top[~kda] == 0).all()


@pytest.mark.parametrize("leaf", _leaf_names())
def test_gradient_leaf_matches_reference_float32(kimi_float32, reference,
                                                 leaf):
    _, grads, _ = kimi_float32
    want = reference[1][leaf]
    if "router_bias" in leaf:       # in the choice alone: nothing reaches it
        assert float(jnp.abs(grads[leaf]).max()) == 0.0
        assert float(jnp.abs(want).max()) == 0.0
        return
    scale = float(jnp.abs(want).max())
    assert scale > 0          # every other tensor of the model learns
    np.testing.assert_allclose(grads[leaf], want, atol=2e-4 * scale,
                               rtol=2e-3)


def test_remat_changes_no_number(kimi, kimi_float32):
    cfg, _, fam, w, batch = kimi
    loss, grads, _ = _loss_and_grad(cfg, fam, w, batch, jnp.float32,
                                    remat=False)
    np.testing.assert_allclose(loss, kimi_float32[0], rtol=1e-6)
    assert _distance(_leaves(grads), kimi_float32[1]) < 1e-5


def test_remat_runs_kda_fwd_once_a_layer(kimi):
    """Under remat the layer saves the rule's result and states
    (`gated_delta.KDA_SAVED_UNDER_REMAT`, in the family's one policy): the
    gradient holds `kda_fwd` once a KDA layer, as without remat, none of the
    scalar rule's kernels, and the latent layer's band kernel twice. And
    nothing of the rule's chunk-local algebra outside a kernel: its
    residuals are its own arguments, so the layer's backward rebuilds none
    of it (PR 62)."""
    cfg, _, fam, w, batch = kimi
    assert set(gated_delta.KDA_SAVED_UNDER_REMAT) <= set(
        sparse_decoder.SAVED_UNDER_REMAT)
    lin = cfg["linear_attn_config"]
    for remat in (True, False):
        assert chunk_local_algebra(
            gradient_jaxpr(fam, cfg, w, batch, remat), gated_delta.CHUNK,
            gated_delta.SUB, lin["head_dim"], lin["head_dim"],
            scope="mixer.kda.scan") == []
        calls = gradient_kernel_calls(fam, cfg, w, batch, remat)
        assert calls[gated_delta.KDA_FWD_NAME] == 4
        assert calls[gated_delta.KDA_BWD_NAME] == 4
        assert calls[gated_delta.FWD_NAME] == calls[gated_delta.BWD_NAME] == 0
        assert calls["moe_gmm"] == 16 and calls["moe_tgmm"] == 8
        assert calls["flash_fwd_resident"] == (2 if remat else 1)


@pytest.mark.parametrize("remat", [True, False])
def test_the_five_scopes_are_in_the_step_and_in_the_registry(kimi, remat):
    """(e): every `mixer.kda.*` scope names operations of the gradient's
    jaxpr, and `obs/devtime.py` maps each to the part `mixer`."""
    cfg, _, fam, w, batch = kimi
    scopes = ("mixer.kda.proj", "mixer.kda.conv", "mixer.kda.gate",
              "mixer.kda.scan", "mixer.kda.out")
    for scope in scopes:
        assert devtime.SCOPES[scope] == ("mixer", 61)
    text = str(gradient_jaxpr(fam, cfg, w, batch, remat).pretty_print(
        name_stack=True))
    for scope in scopes + ("attn.latent", "attn.latent.down", "ffn.dense",
                           "moe.route"):
        assert scope in text, scope
    assert "mixer.gdn" not in text and "ssm." not in text


@pytest.fixture(scope="module")
def kimi_bfloat16(kimi):
    cfg, _, fam, w, batch = kimi
    loss, grads, _ = _loss_and_grad(cfg, fam, w, batch, jnp.bfloat16)
    return loss, _leaves(grads)


def _errors(got, want):
    (loss, grads), (want_loss, want_grads) = got, want
    return (abs(float(loss) - float(want_loss)) / float(want_loss),
            _distance(grads, want_grads))


def test_matches_reference_bfloat16(kimi_bfloat16, reference):
    """bf16 activations and products as the cell runs them: inside the
    tiny limits, by the loss and by the whole gradient in relative L2."""
    limits = _tiny_limits()
    loss_err, grad_err = _errors(kimi_bfloat16, reference)
    assert loss_err < limits["loss_rel_err"]
    assert grad_err < limits["grad_rel_err"]


def test_int8_control_is_far_from_the_reference(kimi, reference):
    """The control `correct` has to refuse: outside the tiny limits."""
    cfg, ref, fam, w, batch = kimi
    _, g8 = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg, "int8"))(w)
    assert _distance(_leaves(fam.to_program(g8, cfg)[0]), reference[1]) \
        > 5 * _tiny_limits()["grad_rel_err"]


# what the tiny limits guard: each omission is made in the REFERENCE, and the
# bfloat16 program, which does not make it, must then read outside a limit —
# {name: ("patch", the reference's function to replace, its replacement
# given the sound one and the module) or ("weights", the tensor of every
# layer that has it, its replacement)}

def _one_rate_a_head(sound, ref):
    """The decay taken as ONE scalar a head and token: the channels' mean."""
    def log_decay(low, lw, cfg, qc=None):
        a = sound(low, lw, cfg, qc)
        return jnp.broadcast_to(a.mean(-1, keepdims=True), a.shape)
    return log_decay


def _reset_every_chunk(sound, ref):
    """The recurrence with the state zeroed every 16 tokens: what a chunked
    scan that loses its carried state computes."""
    def rule(q, k, v, a, beta, qc=None):
        b, s = q.shape[:2]
        cut = lambda x: x.reshape((b * s // 16, 16) + x.shape[2:])
        return sound(*(cut(x) for x in (q, k, v, a, beta)), qc).reshape(
            v.shape)
    return rule


def _decay_after_the_delta(sound, ref):
    """S_t = Diag(alpha_t) ((I - beta k k^T) S_{t-1} + beta k v^T)."""
    def token_update(state, q_t, k_t, v_t, a_t, beta_t, qc=None):
        held = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HI)
        state = (state + k_t[..., None] * (
            (v_t - held) * beta_t[..., None])[..., None, :]) \
            * jnp.exp(a_t)[..., None]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=HI)
    return token_update


def _gate_before_the_norm(sound, ref):
    def gated_norm(o, gate, g_n, eps):
        return ref._rms(jax.nn.sigmoid(gate) * o, g_n, eps)
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


OMISSIONS = {
    "one decay a head (the channels' mean)": ("patch", "log_decay",
                                              _one_rate_a_head),
    "the state reset at every chunk": ("patch", "delta_rule",
                                       _reset_every_chunk),
    "the decay applied after the delta": ("patch", "token_update",
                                          _decay_after_the_delta),
    "no output gate": (
        "patch", "gated_norm", lambda sound, ref: lambda o, gate, g_n, eps:
        ref._rms(o, g_n, eps)),
    "the output gate before the norm": ("patch", "gated_norm",
                                        _gate_before_the_norm),
    "a rotary turn in the MLA layer": ("patch", "positions", _rotary),
    "no shared expert": ("weights", "w_sd", jnp.zeros_like),
}


def omitted(kimi, monkeypatch, name):
    """The reference's (loss, gradient leaves) with `name` left out."""
    _, ref, _, w, _ = kimi
    how, what, make = OMISSIONS[name]
    if how == "patch":
        monkeypatch.setattr(ref, what, make(getattr(ref, what), ref))
        return _reference(kimi)
    hit = [k for k in w if k.endswith("/" + what)]
    assert hit
    return _reference(kimi, dict(w, **{k: make(w[k]) for k in hit}))


@pytest.mark.parametrize("name", sorted(OMISSIONS))
def test_what_the_limits_guard_by_omission(monkeypatch, kimi, kimi_bfloat16,
                                           name):
    """The bfloat16 program against a reference that leaves one thing out:
    refused by at least one of the two limits."""
    limits = _tiny_limits()
    loss_err, grad_err = _errors(kimi_bfloat16,
                                 omitted(kimi, monkeypatch, name))
    assert (loss_err > limits["loss_rel_err"]
            or grad_err > limits["grad_rel_err"]), (loss_err, grad_err)


def test_a_kda_layer_refuses_what_it_cannot_be(kimi):
    cfg, _, fam, w, batch = kimi
    model = fam.build_model(cfg, {})
    for attrs in (dict(window=8, window_layout=(1,) * 5),
                  dict(select_layout=(1,) * 5, select_topk=4, index_heads=1,
                       index_dim=8)):
        with pytest.raises(ValueError,
                           match="Kimi-Delta-Attention layer takes no mask"):
            model.clone(**attrs).init(jax.random.PRNGKey(0),
                                      batch["input_ids"])


# -- (d) the shares add up ----------------------------------------------------

UNCUT = dict(num_attention_heads=4, num_key_value_heads=4, num_experts=16,
             num_router_outputs=16, first_expert=0, num_experts_per_token=3)


def _uncut(kimi, layer):
    """One layer of 4 KDA heads / 4 MLA heads / 16 experts: `layer` 1 is a
    KDA layer over the dense part, 4 the MLA layer over experts."""
    cfg, ref, _, _, _ = kimi
    lin = dict(cfg["linear_attn_config"], num_heads=4,
               kda_layers=[1] if layer == 1 else [],
               full_attn_layers=[] if layer == 1 else [1])
    whole = dict(cfg, num_hidden_layers=1, linear_attn_config=lin,
                 first_k_dense_replace=int(layer == 1), **UNCUT)
    lw = ref.layer_weights(ref.init_weights(whole, jax.random.PRNGKey(7)), 0)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 80, cfg["hidden_size"]))
    return whole, lw, x


def _mixer_layer(cfg, **attrs):
    lin = cfg["linear_attn_config"]
    return sparse_decoder.SparseDecoderLayer(
        heads=2, kv_heads=2,
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        num_experts=0, experts_held=0, first_expert=0, experts_per_token=0,
        expert_width=0, use_rope=False, rope_theta=1e4, window=None,
        eps=cfg["rms_norm_eps"], dtype=jnp.float32, use_flash=False,
        parts="mixer", conv_width=lin["short_conv_kernel_size"],
        kda_heads=2, kda_head_dim=lin["head_dim"],
        kda_gate_rank=cfg["kda_gate_rank"], latent_dim=cfg["kv_lora_rank"],
        rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        **attrs)


def test_head_shares_of_a_kda_layer_add_up(kimi):
    """Cut B's layout, the one the cell runs: two shares of 2 of the 4
    heads, each with its columns
    of W_q, W_k, W_v, W_f_up, W_g_up, W_b, its channels of the convolution,
    its A_log and dt_bias and its rows of W_o — and W_f_down, W_g_down and
    the norm's gain WHOLE on both —, through the PROGRAM's layer: their
    parts of the residual add up to the uncut reference's mixer, because
    every KDA quantity, the norm's statistics included, is a head's own."""
    cfg, ref, fam, _, _ = kimi
    whole, lw, x = _uncut(kimi, 1)
    dh = cfg["linear_attn_config"]["head_dim"]
    eps = cfg["rms_norm_eps"]
    want = ref.kda_part(ref._rms(x, lw["g1"], eps), lw, whole)
    layer = _mixer_layer(cfg, mixer="kda")
    share_cfg = dict(whole, linear_attn_config=dict(
        whole["linear_attn_config"], num_heads=2))
    wide = 4 * dh
    total = jnp.zeros_like(want)
    for share in range(2):
        cols = np.arange(share * 2 * dh, (share + 1) * 2 * dh)
        heads = np.arange(2 * share, 2 * share + 2)
        part = dict(lw, **{n: lw[n][:, cols] for n in (
            "w_q", "w_k", "w_v", "w_fb", "w_gb")})
        part.update(
            w_conv=lw["w_conv"][np.concatenate(
                [cols, wide + cols, 2 * wide + cols])],
            a_log=lw["a_log"][heads], dt_bias=lw["dt_bias"][cols],
            w_b=lw["w_b"][:, heads], w_o=lw["w_o"][cols])
        params = dict(fam._kda_to_program(part, share_cfg),
                      norm_attn={"scale": lw["g1"]})
        out, counters = layer.apply({"params": params}, x)
        assert sorted(counters) == ["kda_chunk_log_decay_min",
                                    "kda_state_absmax"]
        # a share IS the reference at the share's sizes
        np.testing.assert_allclose(
            out - x, ref.kda_part(ref._rms(x, lw["g1"], eps), part,
                                  share_cfg), atol=2e-5, rtol=2e-4)
        total = total + (out - x)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)


def test_head_shares_of_the_latent_layer_add_up_without_positions(kimi):
    """Two shares of 2 of the 4 heads, W_kv_down and the latent's norm whole
    on both, through the PROGRAM's layer with `use_rope` False; and with the
    rotary turn the layer computes something else."""
    cfg, ref, _, _, _ = kimi
    whole, lw, x = _uncut(kimi, 4)
    d = cfg["hidden_size"]
    dn, dr, dv, dc = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"], cfg["kv_lora_rank"])
    want = ref.latent_part(ref._rms(x, lw["g1"], cfg["rms_norm_eps"]), lw,
                           whole)
    total = jnp.zeros_like(want)
    turned = jnp.zeros_like(want)
    for share in range(2):
        hs = slice(2 * share, 2 * share + 2)
        params = {"norm_attn": {"scale": lw["g1"]},
                  "norm_latent": {"scale": lw["g_c"]},
                  "query": lw["w_q"].reshape(d, 4, dn + dr)[:, hs],
                  "kv_down": lw["w_kva"],
                  "kv_up": lw["w_kvb"].reshape(dc, 4, dn + dv)[:, hs],
                  "out": lw["w_o"].reshape(4, dv, d)[hs]}
        out, counters = _mixer_layer(cfg).apply({"params": params}, x)
        assert counters == {}
        total = total + (out - x)
        turned = turned + (_mixer_layer(cfg).clone(use_rope=True).apply(
            {"params": params}, x)[0] - x)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)
    assert float(jnp.abs(turned - want).max()) > 1e-3


def test_expert_shares_add_up_with_the_shared_expert_counted_once(kimi):
    """Eight shares of 2 experts, each routing over all 16 by score + bias
    through the PROGRAM's router and held experts: their parts, and the
    shared expert ONCE, give the uncut reference's expert part; the rows
    they serve are all the choices."""
    cfg, ref, _, _, _ = kimi
    whole, lw, x = _uncut(kimi, 4)
    u = x.reshape(-1, cfg["hidden_size"])
    want = ref.feed_forward_part(u, lw, whole, False)
    total = moe.shared_expert_ffn(u, lw["w_sgu"], lw["w_sd"], None,
                                  activation="silu")
    rows, seen = 0.0, []

    @jax.jit
    def one_share(w_gate_up, w_down, first):
        idx, p, routed = moe.route_sigmoid_top_k(
            u, lw["w_r"], lw["b_r"], 3, cfg["routed_scaling_factor"])
        return moe.held_experts_ffn(u, idx, p, w_gate_up, w_down, first,
                                    activation="silu"), routed

    for share in range(8):
        es = slice(2 * share, 2 * share + 2)
        (m, counters), routed = one_share(lw["w_gate_up"][es],
                                          lw["w_down"][es], 2 * share)
        total = total + m
        rows += float(counters["rows_held"])
        seen.append({n: float(v) for n, v in routed.items()})
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)
    assert rows == u.shape[0] * 3
    assert all(s == seen[0] for s in seen)
    assert seen[0]["route_weight_sum"] == pytest.approx(
        cfg["routed_scaling_factor"] * u.shape[0], rel=1e-5)


# -- the configuration and the family's counts --------------------------------

def test_configuration_holds_the_published_widths():
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "linear_attn_config",
                              "num_experts", "vocab_size",
                              "num_attention_heads", "num_key_value_heads"]
    published = cfg["published"]
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"], published["num_attention_heads"],
            published["num_key_value_heads"],
            published["linear_attn_config"]["num_heads"]) == (
        27, 256, 163840, 32, 32, 32)
    full = published["linear_attn_config"]["full_attn_layers"]
    kda = published["linear_attn_config"]["kda_layers"]
    assert full == [4, 8, 12, 16, 20, 24, 27]
    assert sorted(full + kda) == list(range(1, 28)) and len(kda) == 20
    lin = cfg["linear_attn_config"]
    # every WIDTH as published
    assert (cfg["hidden_size"], lin["head_dim"],
            lin["short_conv_kernel_size"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["q_lora_rank"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_shared_experts"],
            cfg["num_router_outputs"], cfg["num_experts_per_token"],
            cfg["routed_scaling_factor"], cfg["moe_router_activation_func"],
            cfg["num_expert_group"], cfg["topk_group"], cfg["rms_norm_eps"],
            cfg["first_k_dense_replace"], cfg["mla_use_nope"],
            cfg["head_dim"], cfg["kda_gate_rank"]) == (
        2304, 128, 4, 128, 64, 128, 512, None, 9216, 1024, 1, 256, 8, 2.446,
        "sigmoid", 1, 1, 1e-5, 1, True, 72, 128)
    # cut B of ISSUE 61: the published positions 1 to 5, HALF the heads of
    # either kind (2 chips share them), the floors' 8 experts and eighth of
    # the rows
    assert (lin["kda_layers"], lin["full_attn_layers"]) == (
        [k for k in kda if k <= 5], [k for k in full if k <= 5])
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            lin["num_heads"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"]) == (5, 8, 163840 // 8, 16, 16, 16)
    for name in ("kda_gate_rank", "kda_initialisation", "l2_norm", "decay",
                 "output", "positions", "head_dim", "router_bias", "groups",
                 "shared_expert", "parameter_dtype", "compute_dtype",
                 "weights", "dropout_and_aux_losses"):
        assert name in cfg["assumed"]
    for name in cfg["reduced"]:
        assert name in cfg["reduced_why"]
    # the accepted reader's spelling of the source's key
    assert cfg["num_experts_per_tok"] == cfg["num_experts_per_token"]
    assert cfg["tiny"]["num_experts_per_tok"] \
        == cfg["tiny"]["num_experts_per_token"]
    assert "num_experts_per_tok" in cfg["assumed"]
    assert "4 : 1" in cfg["reduced_why"]["linear_attn_config"]
    assert "32 chips share each layer's experts" in cfg["deployment"]
    assert "2 its heads" in cfg["deployment"]
    fam = harness.load_module("program", cfg["family"])
    shapes = fam.train_parts(cfg, {"remat": True})[2][0]
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    d, wide = 2304, 16 * 128
    kda_mixer = (4 * d * wide + 3 * wide * 4 + 2 * (d * 128 + 128 * wide)
                 + d * 16 + 16 + wide + 128)
    mla = d * 16 * 192 + d * 576 + 512 + 512 * 16 * 256 + 16 * 128 * d
    experts = d * 256 + 256 + 8 * 3 * d * 1024 + 3 * d * 1024
    dense = 3 * d * 9216
    assert n == (4 * kda_mixer + mla + 4 * experts + dense + 5 * 2 * d
                 + 2 * 20480 * d + d) == 510692160


def test_train_flops_and_kernel_costs_count_what_they_say():
    cfg = _cfg()
    fam = harness.load_module("program", cfg["family"])
    t = 8192
    job = {"seq_len": t, "remat": True}
    d, wide = 2304, 16 * 128
    w = fam.matrix_weights_per_token(cfg)
    assert w == {
        "kda": 4 * d * wide + 3 * wide * 4 + 2 * (d * 128 + 128 * wide)
        + d * 16,
        "attention": d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d,
        "dense": 3 * d * 9216, "router": d * 256, "shared": 3 * d * 1024,
        "head": d * 20480}
    rows = t * 8 * 8 / 256.0                        # a layer, even routing
    assert fam.expected_expert_rows(cfg, t) == rows == 2048.0
    core = 3.0 * (t * (t + 1) / 2.0) * 16 * 2 * (192 + 128)
    routed = 4 * 6.0 * rows * 3 * d * 1024
    rule = 4 * 18.0 * t * 16 * 128 * 128
    flops = fam.train_flops(cfg, job, 1)
    assert flops == pytest.approx(
        6.0 * t * (4 * w["kda"] + w["attention"] + w["dense"] + 4 * (
            w["router"] + w["shared"]) + w["head"]) + routed + core + rule)
    assert 13.0e12 < flops < 13.4e12
    kda = 4 * 6.0 * t * w["kda"] + rule
    assert 0.30 < kda / flops < 0.33                # 31% of required ops
    assert 0.12 < (6.0 * t * w["attention"] + core) / flops < 0.15
    # with every head held (cut A) the same five layers would give the KDA
    # layers 43% and the latent layer 18%
    uncut = dict(cfg, num_attention_heads=32, num_key_value_heads=32,
                 linear_attn_config=dict(cfg["linear_attn_config"],
                                         num_heads=32))
    whole = fam.train_flops(uncut, job, 1)
    w_all = fam.matrix_weights_per_token(uncut)
    assert 18.8e12 < whole < 19.0e12
    assert 0.42 < (4 * 6.0 * t * w_all["kda"] + 2 * rule) / whole < 0.44
    costs = fam.kernel_costs(cfg, job, 1)
    assert sorted(costs) == ["flash_bwd", "flash_fwd_stream", "kda_bwd",
                             "kda_fwd", "moe_gmm", "moe_tgmm"]
    assert costs["flash_fwd_stream"][0] == pytest.approx(2 * core / 3)
    chunks = 4 * 16 * t / 64.0
    assert costs["kda_fwd"][0] == pytest.approx(
        chunks * (3 * 2 * 64 * 128 * 128 + 2 * 64 * 64 * 128))
    assert costs["kda_bwd"][0] == pytest.approx(
        chunks * (6 * 2 * 64 * 128 * 128 + 2 * 2 * 64 * 64 * 128))
    # five bfloat16 operands and the decay's ROW of 128 float32 in, the
    # result and the chunk-end state (bfloat16 both) out
    assert costs["kda_fwd"][1] == pytest.approx(chunks * (
        2.0 * 64 * (4 * 128 + 64) + 4.0 * 128 + 2.0 * 64 * 128
        + 2.0 * 128 * 128))
    for name in ("kda_fwd", "kda_bwd"):             # memory-bound on a v5e
        ops, nbytes = costs[name]
        assert ops / 197e12 < nbytes / 819e9
    weights = 3 * d * 1024
    assert costs["moe_gmm"][0] == pytest.approx(2 * 2.0 * 4 * rows * weights)
    assert costs["moe_tgmm"][0] == pytest.approx(2.0 * 4 * rows * weights)
    # THE 5-ENTRY AVERAGE: the readers hand over the mean of `rows_held`
    # over ALL the counters' entries, the dense layer's zero among them
    counters = {"rows_held": [0.0, 20000.0, 21000.0, 20500.0, 21380.0],
                "steps": [10.0]}
    mean = kernel_readers.expert_rows_per_step(counters)
    served = 2000 + 2100 + 2050 + 2138
    assert mean == pytest.approx(served / 5.0)
    got = fam.kernel_costs(cfg, job, 1, mean)
    assert got["moe_gmm"][0] == pytest.approx(2 * 2.0 * served * weights)
    assert got["kda_fwd"] == costs["kda_fwd"]


def test_family_refuses_a_program_without_the_vector_rule(monkeypatch):
    """What the parent commit meets when it is handed this cell: a
    BenchError at once, from every entry of the family's file."""
    cfg = _tiny_cfg()
    fam = harness.load_module("program", cfg["family"])
    monkeypatch.delattr(sparse_decoder, "KDA_COUNTERS")
    for call in (lambda: fam.build_model(cfg, {}),
                 lambda: fam.train_parts(cfg, {}),
                 lambda: fam.to_program({}, cfg)):
        with pytest.raises(harness.BenchError,
                           match="no Kimi-Delta-Attention"):
            call()


# -- the training state through the trainer to the readers --------------------

def test_counters_reach_the_readers_through_the_trainer(kimi, monkeypatch):
    import optax
    from edl_tpu.runtime import trainer as trainer_mod
    from edl_tpu.runtime.mesh import make_mesh
    from edl_tpu.runtime.trainer import ElasticTrainer
    # the process's gauge of model counters, empty for this test and as it
    # was after it: other files' tests count its series
    monkeypatch.setattr(trainer_mod._MODEL_COUNTER, "_children", {})
    cfg, _, fam, w, _ = kimi
    loss_fn, has_aux, _ = fam.train_parts(cfg, {"remat": False})
    params, extra = jax.tree_util.tree_map(jnp.array, fam.to_program(w, cfg))
    tr = ElasticTrainer(loss_fn, params, optax.adamw(1e-3),
                        total_batch_size=2, extra_state=extra,
                        has_aux=has_aux,
                        mesh=make_mesh(devices=jax.devices()[:2]))
    batch = fam.make_batch(cfg, {"seq_len": 80}, jax.random.PRNGKey(5), 2)
    try:
        for _ in range(2):
            tr.train_step(tr.local_batch_slice(batch))
    finally:
        tr.close()
    counters = kernel_readers.model_counters()
    assert counters["steps"] == [2.0]
    low, top = (counters[n] for n in sparse_decoder.KDA_COUNTERS)
    assert len(low) == len(top) == 5
    # running minimum and maximum, not sums; the MLA layer's stay 0
    assert low[3] == top[3] == 0.0 and min(low) < 0.0 < max(top)
    view = {"traffic": _job(), "cell": {"chips": 1}, "config": _cfg(),
            "counters": {"traced_steps": 2}, "trace": {"ops": []}}
    assert harness.load_module("metrics", "kda_chunk_log_decay_min").read(
        view) == min(low)
    assert harness.load_module("metrics", "kda_state_absmax").read(
        view) == max(top)


# -- (f) the six new readers on a fixture -------------------------------------

def test_readers_on_a_fixture_line(monkeypatch):
    cfg, job = _cfg(), _job()
    fam = harness.load_module("program", cfg["family"])
    view = {"trace": {"ops": [["checkpoint_kda_fwd", 0.05],
                              ["transpose_jvp_kda_bwd", 0.1],
                              ["checkpoint_gdn_fwd", 9.0]]},
            "counters": {"traced_steps": 10}, "config": cfg, "traffic": job,
            "cell": {"chips": 1},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}}
    monkeypatch.setattr(kernel_readers, "model_counters", lambda: {})
    read = lambda name: harness.load_module("metrics", name).read(view)
    assert read("kda_fwd_device_ms") == pytest.approx(5.0)
    assert read("kda_bwd_device_ms") == pytest.approx(10.0)
    costs = fam.kernel_costs(cfg, job, 1)
    for name, ms in (("kda_fwd", 5.0), ("kda_bwd", 10.0)):
        nbytes = costs[name][1]
        assert read(name + "_roofline_pct") == pytest.approx(
            100.0 * nbytes / 819e9 / (ms / 1e3))
        assert 0.0 < read(name + "_roofline_pct") < 100.0
    counters = {"kda_chunk_log_decay_min": [-30.0, -90.0, -101.5, 0.0, -7.0],
                "kda_state_absmax": [0.5, 0.25, 0.75, 0.0, 0.1],
                "steps": [10.0]}
    for name, want in (("kda_chunk_log_decay_min", -101.5),
                       ("kda_state_absmax", 0.75)):
        mod = harness.load_module("metrics", name)
        monkeypatch.setattr(mod, "model_counters", lambda: counters)
        assert mod.read(view) == want


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_none_where_there_is_nothing_to_read(monkeypatch,
                                                                name):
    """The parent commit's program has no such counter and no such kernel
    in its trace: the reader says nothing and does not raise."""
    cfg, job = _cfg(), _job()
    view = {"traffic": job, "cell": {"chips": 1}, "config": cfg,
            "counters": {"traced_steps": 10},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
            "trace": {"ops": [["fusion.3", 0.2], ["moe_gmm", 0.1],
                              ["gdn_fwd", 0.1]]}}
    mod = harness.load_module("metrics", name)
    if hasattr(mod, "model_counters"):
        monkeypatch.setattr(mod, "model_counters",
                            lambda: {"steps": [2.0], "rows_held": [4.0]})
    assert mod.read(view) is None


def test_benchmark_lists_the_cell_where_it_reports():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])     # later PRs append after them
    assert names[first:first + 6] == list(NEW_METRICS)
    for m in bench["per_layer"][first:first + 6]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_samples_s_chip"
    assert bench["workloads"][10]["name"] == CELL
    assert bench["configs"][9]["name"] == CONFIG
    for cell in bench["workloads"]:
        assert len(cell["why"]) <= 200
