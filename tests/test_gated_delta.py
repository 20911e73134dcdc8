"""The gated delta rule and the model that mixes it with gated full
attention (`ops/gated_delta.py`, `models/sparse_decoder.py`,
`parallel/moe.py:shared_expert_ffn`; the `qwen3-next-80b-a3b`
configuration).

- the kernels (Pallas interpreter) and the plain chunked form against the
  token-by-token recurrence, result and gradients, at sequences of less than
  a chunk, one chunk, several chunks and no whole number of chunks;
- under remat: a policy that saves the forward rule's named residuals leaves
  the gradient ONE `gdn_fwd` a layer;
- the decoder's loss and gradient against `benchmark/reference/
  qwen3-next-80b-a3b.py` at the `tiny` size, the int8 control outside it,
  and what each compared number guards;
- the shares of a layer add up to the uncut layer, the shared expert counted
  once;
- the counters through `ElasticTrainer` to the readers;
- the three accepted sparse models trace to the program they were.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness, kernel_readers
from jaxpr_kernels import (gradient_kernel_calls, pallas_call_names,
                           traced_gradient)
from edl_tpu.models import sparse_decoder
from edl_tpu.ops import gated_delta as gd
from edl_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "qwen3-next-80b-a3b"
HI = jax.lax.Precision.HIGHEST

#: less than a chunk (the trainer's dummy), one chunk, several, no whole number
LENGTHS = [16, 64, 192, 200]
HK, HV, DK, DV = 2, 4, 16, 16


# -- (a) the rule against its recurrence --------------------------------------

def _recurrence(q, k, v, g, beta):
    """Token by token, float32 at the highest precision."""
    r = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(x, r, axis=2) for x in (q, k))

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        held = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HI)
        state = state + k_t[..., None] * ((v_t - held)
                                          * beta_t[..., None])[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=HI)

    s0 = jnp.zeros((v.shape[0], v.shape[2], k.shape[-1], v.shape[-1]))
    _, o = jax.lax.scan(token, s0, tuple(jnp.moveaxis(x, 1, 0)
                                         for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _inputs(s):
    """Normalised q and k, log decays from -1.6 to -0.001 a token: a chunk
    forgets as far as exp(-100) and a head remembers a thousand tokens."""
    ks = jax.random.split(jax.random.PRNGKey(s), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (2, s, HK, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (2, s, HK, DK)))
    v = jax.random.normal(ks[2], (2, s, HV, DV))
    g = -jnp.exp(jax.random.uniform(ks[3], (2, s, HV), minval=-7.0,
                                    maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, s, HV)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (2, s, HV, DV))


@pytest.mark.parametrize("s", LENGTHS)
@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_forward_matches_the_recurrence(s, path):
    args, _ = _inputs(s)
    want = _recurrence(*args)
    got, stats = gd.gated_delta_rule(*args, use_kernel=path == "kernels")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
    g = np.asarray(args[3])
    pad = -s % gd.CHUNK
    sums = np.pad(g, ((0, 0), (0, pad), (0, 0))).reshape(
        2, -1, gd.CHUNK, HV).sum(axis=2)
    np.testing.assert_allclose(stats["chunk_log_decay_min"], sums.min(),
                               rtol=1e-5)
    assert 0.0 < float(stats["state_absmax"]) < 10.0


@pytest.mark.parametrize("s", LENGTHS)
@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_gradient_matches_the_recurrence(s, path):
    args, w = _inputs(s)
    want = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * w),
                    argnums=range(5))(*args)
    got = jax.grad(lambda *a: jnp.sum(gd.gated_delta_rule(
        *a, use_kernel=path == "kernels")[0] * w), argnums=range(5))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()),
                                   rtol=2e-3)


def test_kernels_take_bfloat16_and_keep_a_float32_state():
    (q, k, v, g, beta), w = _inputs(192)
    want = _recurrence(q, k, v, g, beta)
    low = lambda x: x.astype(jnp.bfloat16)
    for use_kernel in (False, True):
        got, stats = gd.gated_delta_rule(low(q), low(k), low(v), g, beta,
                                         use_kernel=use_kernel)
        assert got.dtype == jnp.bfloat16
        assert stats["state_absmax"].dtype == jnp.float32
        err = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                    / jnp.linalg.norm(want))
        assert err < 0.02, err


def test_a_chunk_that_forgets_does_not_overflow():
    """Log decays of -3 a token: a chunk's cumulative log decay reaches
    -192, where exp(-gamma) is infinite in float32; every decay the rule
    takes is exp of a difference that is never positive."""
    (q, k, v, g, beta), w = _inputs(128)
    g = jnp.full_like(g, -3.0)
    want = _recurrence(q, k, v, g, beta)
    for use_kernel in (False, True):
        fn = lambda *a: gd.gated_delta_rule(*a, use_kernel=use_kernel)
        got, stats = fn(q, k, v, g, beta)
        assert float(stats["chunk_log_decay_min"]) == pytest.approx(-192.0)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
        grads = jax.grad(lambda *a: jnp.sum(fn(*a)[0] * w),
                         argnums=range(5))(q, k, v, g, beta)
        assert all(bool(jnp.isfinite(x).all()) for x in grads)


def test_unit_lower_inverse_is_the_inverse():
    c = 64
    l = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, c, c)) * 0.2,
                 -1)
    inv = gd.unit_lower_inverse(l)
    np.testing.assert_allclose(
        jnp.einsum("bij,bjk->bik", jnp.eye(c) + l, inv, precision=HI),
        jnp.broadcast_to(jnp.eye(c), l.shape), atol=1e-4)
    w = jax.random.normal(jax.random.PRNGKey(1), l.shape)
    got = jax.grad(lambda l: jnp.sum(gd.unit_lower_inverse(l) * w))(l)
    want = jax.grad(lambda l: jnp.sum(jnp.linalg.inv(jnp.eye(c) + l) * w))(l)
    np.testing.assert_allclose(got, want, atol=1e-3 * float(
        jnp.abs(want).max()), rtol=1e-3)


def test_causal_conv_reads_the_three_tokens_before():
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    got = np.asarray(gd.causal_conv(u, w))
    u, w = np.asarray(u), np.asarray(w)
    for t in range(10):
        want = sum(w[:, j] * u[:, t - 3 + j] for j in range(4)
                   if t - 3 + j >= 0)
        np.testing.assert_allclose(got[:, t], want, atol=1e-5)


@pytest.mark.parametrize("saved,forwards", [(gd.SAVED_UNDER_REMAT, 1),
                                            ((), 2)])
def test_remat_that_saves_the_residuals_runs_the_forward_once(saved,
                                                              forwards):
    args, w = _inputs(192)

    def loss(*a):
        run = jax.checkpoint(
            lambda *a: gd.gated_delta_rule(*a, use_kernel=True)[0],
            policy=jax.checkpoint_policies.save_only_these_names(*saved))
        return jnp.sum(run(*a) * w)

    names = pallas_call_names(jax.make_jaxpr(
        jax.grad(loss, argnums=range(5)))(*args).jaxpr)
    assert names.count(gd.FWD_NAME) == forwards
    assert names.count(gd.BWD_NAME) == 1


# -- (b) the decoder against the plain reference ------------------------------

def _tiny_cfg():
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         CONFIG + ".json"))
    return dict(cfg, **cfg["tiny"])


def _tiny_limits():
    return harness.load_json(os.path.join(
        REPO, "benchmark", "traffic", "tokens-16384-gdn.json"))["tiny"][
            "limits"]


@pytest.fixture(scope="module")
def qwen():
    cfg = _tiny_cfg()
    ref = harness.load_module("reference", CONFIG)
    fam = harness.load_module("program", cfg["family"])
    w = ref.init_weights(cfg, jax.random.PRNGKey(3))
    batch = fam.make_batch(cfg, {"seq_len": 32}, jax.random.PRNGKey(4), 2)
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


@pytest.fixture(scope="module")
def reference(qwen):
    """(loss, gradient, its leaves in the program's layout)."""
    cfg, ref, fam, w, batch = qwen
    loss, g = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg))(w)
    return loss, g, _leaves(fam.to_program(g, cfg)[0])


@pytest.fixture(scope="module", params=["plain", "kernels"])
def qwen_float32(request, qwen, reference):
    cfg, ref, fam, w, batch = qwen
    want_loss, _, want = reference
    loss, grads, extra = _loss_and_grad(
        cfg, fam, w, batch, jnp.float32,
        use_flash=request.param == "kernels")
    return loss, _leaves(grads), extra, want_loss, want


def test_loss_and_counters_match_the_reference_float32(qwen, qwen_float32):
    cfg, _, fam, _, _ = qwen
    loss, _, extra, want_loss, _ = qwen_float32
    np.testing.assert_allclose(loss, want_loss, rtol=2e-5)
    c = extra["counters"]
    assert float(c["steps"]) == 1.0
    assert float(c["rows_dropped"].sum()) == 0.0
    linear = np.asarray(fam.linear_layers(cfg), bool)
    assert linear.tolist() == [True, True, True, False]
    low, top = (np.asarray(c[n]) for n in sparse_decoder.GATED_DELTA_COUNTERS)
    assert (low[linear] < 0).all() and (top[linear] > 0).all()
    assert (low[~linear] == 0).all() and (top[~linear] == 0).all()


@pytest.mark.parametrize("leaf", _leaf_names())
def test_gradient_leaf_matches_reference_float32(qwen_float32, leaf):
    _, grads, _, _, want = qwen_float32
    scale = float(jnp.abs(want[leaf]).max())
    assert scale > 0          # every tensor of the model learns
    np.testing.assert_allclose(grads[leaf], want[leaf], atol=2e-4 * scale,
                               rtol=2e-3)


def test_matches_reference_bfloat16(qwen, reference):
    """bf16 activations and products as the cell runs them: inside the
    tiny limits, by the loss and by the whole gradient in relative L2."""
    cfg, ref, fam, w, batch = qwen
    want_loss, _, want = reference
    limits = _tiny_limits()
    loss, grads, _ = _loss_and_grad(cfg, fam, w, batch, jnp.bfloat16)
    assert abs(float(loss) - float(want_loss)) / float(want_loss) \
        < limits["loss_rel_err"]
    assert _distance(_leaves(grads), want) < limits["grad_rel_err"]


def test_int8_control_is_far_from_the_reference(qwen, reference):
    """The control `correct` has to refuse: outside the tiny limits."""
    cfg, ref, fam, w, batch = qwen
    _, g, _ = reference
    _, g8 = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg, "int8"))(w)
    assert _distance(g8, g) > 5 * _tiny_limits()["grad_rel_err"]


def _reset_every(ref, every):
    """The reference's recurrence with the state zeroed every `every`
    tokens: what a chunked scan that loses its carried state computes."""
    whole = ref.delta_rule

    def rule(q, k, v, g, beta, qc=None):
        b, s = q.shape[:2]
        cut = lambda x: x.reshape((b * s // every, every) + x.shape[2:])
        return whole(*(cut(x) for x in (q, k, v, g, beta)), qc).reshape(
            v.shape)
    return rule


@pytest.mark.parametrize("omission", ["state_reset_every_chunk",
                                      "dropped_decay",
                                      "dropped_shared_expert",
                                      "dropped_output_gate",
                                      "rotary_over_the_whole_head"])
def test_what_each_compared_number_guards(qwen, reference, monkeypatch,
                                          omission):
    """Each omission, made in the reference, reads outside at least one of
    the tiny limits the bf16 program reads inside."""
    cfg, ref, _, w, batch = qwen
    want, g, _ = reference
    layers = range(cfg["num_hidden_layers"])
    if omission == "state_reset_every_chunk":
        monkeypatch.setattr(ref, "delta_rule", _reset_every(ref, 8))
    elif omission == "dropped_decay":        # exp(A_log) = 0: g = 0
        w = dict(w, **{"%d/a_log" % i: jnp.full_like(w["%d/a_log" % i], -1e9)
                       for i in layers if ref.is_linear(cfg, i)})
    elif omission == "dropped_shared_expert":
        w = dict(w, **{"%d/w_sd" % i: jnp.zeros_like(w["%d/w_sd" % i])
                       for i in layers})
    elif omission == "dropped_output_gate":
        monkeypatch.setattr(ref, "output_gate", lambda a, gate: a)
    else:
        cfg = dict(cfg, partial_rotary_factor=1.0)
    loss, g2 = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg))(w)
    limits = _tiny_limits()
    assert (abs(float(loss) - float(want)) / float(want)
            > limits["loss_rel_err"]
            or _distance(g2, g) > limits["grad_rel_err"])


def test_remat_runs_gdn_fwd_once_a_layer(qwen):
    """Under remat the layer saves the rule's result and states
    (`gd.SAVED_UNDER_REMAT`, in the family's one policy): the gradient
    holds `gdn_fwd` once a linear layer, as without remat, and the full
    layer's band kernel twice."""
    cfg, _, fam, w, batch = qwen
    assert set(gd.SAVED_UNDER_REMAT) <= set(sparse_decoder.SAVED_UNDER_REMAT)
    n_linear = sum(fam.linear_layers(cfg))
    for remat in (True, False):
        calls = gradient_kernel_calls(fam, cfg, w, batch, remat)
        assert calls[gd.FWD_NAME] == calls[gd.BWD_NAME] == n_linear == 3
        assert calls["flash_fwd_resident"] == (2 if remat else 1)


def test_leaves_hold_both_kinds_of_layer():
    cfg = _tiny_cfg()
    fam = harness.load_module("program", cfg["family"])
    params, extra = fam.train_parts(cfg, {"remat": True})[2]
    experts = ["experts_down", "experts_gate_up", "norm_attn", "norm_moe",
               "router", "shared_down", "shared_gate", "shared_gate_up"]
    assert sorted(params["layer_0"]) == sorted(experts + [
        "A_log", "conv", "dt_bias", "in_proj_ba", "in_proj_qkvz", "norm_gdn",
        "out"])
    assert sorted(params["layer_3"]) == sorted(experts + [
        "key", "norm_key", "norm_query", "out", "query", "value"])
    assert sorted(extra["counters"]) == sorted(
        sparse_decoder.COUNTERS + sparse_decoder.GATED_DELTA_COUNTERS
        + ("steps",))


def test_configuration_holds_the_published_widths():
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         CONFIG + ".json"))
    assert cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "num_attention_heads",
        "num_key_value_heads", "linear_num_key_heads",
        "linear_num_value_heads", "vocab_size"]
    assert cfg["published"] == {
        "num_hidden_layers": 48, "num_experts": 512,
        "num_attention_heads": 16, "num_key_value_heads": 2,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "vocab_size": 151936}
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["num_router_outputs"],
            cfg["partial_rotary_factor"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["full_attention_interval"]) == (
                2048, 256, 128, 128, 4, 512, 512, 10, 512, 0.25, 10000000,
                1e-6, 4)
    for name in ("norm_gain", "gated_norm", "qk_l2norm", "in_proj_grouping",
                 "decay", "rope", "multi_token_prediction", "weights"):
        assert name in cfg["assumed"]
    assert cfg["tiny"]["num_hidden_layers"] == 4      # one whole period
    fam = harness.load_module("program", cfg["family"])
    shapes = fam.train_parts(cfg, {"remat": True})[2][0]
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n - 259.5e6) < 0.05e6          # the file's deployment


def test_train_flops_and_kernel_costs_count_what_they_say():
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         CONFIG + ".json"))
    fam = harness.load_module("program", cfg["family"])
    job = {"seq_len": 16384, "remat": True}
    t = 16384
    linear, full, head = fam.matrix_weights_per_token(cfg)
    assert linear == 2048 * (6144 + 32) + 4096 * 4 + 2048 * 2048 \
        + 2048 * 512 + 3 * 2048 * 512 + 2048
    assert full == 2048 * 4096 + 2 * 2048 * 256 + 2048 * 2048 \
        + 2048 * 512 + 3 * 2048 * 512 + 2048
    rows = fam.expected_expert_rows(cfg, t)
    assert rows == t * 10 * 8 / 512.0             # 320 rows an expert
    pairs = t * (t + 1) / 2.0
    attention = 3.0 * pairs * 8 * 2 * 2 * 256
    scan = 3 * 18.0 * t * 16 * 128 * 128
    flops = fam.train_flops(cfg, job, 1)
    assert flops == pytest.approx(
        6.0 * t * (3 * linear + full + head)
        + 4 * 6.0 * rows * 3 * 2048 * 512 + attention + scan)
    assert 15.0e12 < flops < 16.0e12
    assert 0.20 < attention / flops < 0.22 and scan / flops < 0.02
    # the calls the step makes under remat: the rule's forward ONCE a
    # linear layer, the streamed flash forward twice, backwards once
    costs = fam.kernel_costs(cfg, job, 1)
    assert sorted(costs) == ["flash_bwd", "flash_fwd_stream", "gdn_bwd",
                             "gdn_fwd", "moe_gmm", "moe_tgmm"]
    assert costs["flash_fwd_stream"][0] == pytest.approx(2 * attention / 3)
    assert costs["flash_bwd"][0] == pytest.approx(2.5 * attention / 3)
    once = fam.kernel_costs(cfg, dict(job, remat=False), 1)
    assert once["flash_fwd_stream"][0] == pytest.approx(attention / 3)
    assert once["gdn_fwd"] == costs["gdn_fwd"]
    c = gd.CHUNK
    chunks = 3 * 16 * t / c
    assert costs["gdn_fwd"][0] == pytest.approx(
        chunks * (6 * c * 128 * 128 + 2 * c * c * 128))
    assert costs["gdn_bwd"][0] == pytest.approx(
        chunks * (12 * c * 128 * 128 + 4 * c * c * 128))
    # the chunk-end states are most of what the forward writes
    assert costs["gdn_fwd"][1] > chunks * 4 * 128 * 128
    for name in ("gdn_fwd", "gdn_bwd"):     # memory-bound on a v5e
        ops, nbytes = costs[name]
        assert nbytes / 819e9 > ops / 197e12


# -- (c) the counters, through the trainer, to the readers -------------------

def test_trainer_mirrors_the_rule_s_counters(qwen):
    import optax
    from edl_tpu.runtime.mesh import make_mesh
    from edl_tpu.runtime.trainer import ElasticTrainer
    cfg, _, fam, w, batch = qwen
    loss_fn, has_aux, _ = fam.train_parts(cfg, {"remat": False})
    params, extra = fam.to_program(w, cfg)
    trainer = ElasticTrainer(loss_fn, params, optax.sgd(1e-3),
                             total_batch_size=2, extra_state=extra,
                             has_aux=has_aux,
                             mesh=make_mesh(devices=jax.devices()[:1]))
    try:
        staged = trainer.place_batch(batch)
        for _ in range(2):
            trainer.train_step(staged)
    finally:
        trainer.close()
    got = kernel_readers.model_counters()
    assert got["steps"] == [2.0]
    low, top = got["gdn_chunk_log_decay_min"], got["gdn_state_absmax"]
    assert len(low) == len(top) == 4 and low[3] == top[3] == 0.0
    assert max(low[:3]) < 0.0 < min(top[:3])
    view = {"traffic": {"seq_len": 32, "batch_per_chip": 2},
            "cell": {"chips": 1}, "config": cfg}
    read = lambda name: harness.load_module("metrics", name).read(view)
    assert read("gdn_chunk_log_decay_min") == min(low)
    assert read("gdn_state_absmax") == max(top)
    assert read("moe_rows_dropped") == 0.0


# -- (d) the share tests ------------------------------------------------------

def test_head_shares_of_a_linear_layer_add_up_to_the_uncut_layer(qwen):
    """Two shares of 2 key heads and their 4 value heads, each over the
    whole sequence: their parts of the mixer's result add up to the uncut
    layer's (4 key, 8 value heads at the tiny widths)."""
    cfg, ref, _, _, _ = qwen
    whole = dict(cfg, linear_num_key_heads=4, linear_num_value_heads=8,
                 num_hidden_layers=1)
    lw = ref.layer_weights(ref.init_weights(whole, jax.random.PRNGKey(7)), 0)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 48, cfg["hidden_size"]))
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    want = ref.linear_attention_part(x, lw, whole)
    total = jnp.zeros_like(want)
    for share in range(2):
        part = dict(whole, linear_num_key_heads=2, linear_num_value_heads=4)
        ks = slice(share * 2 * dk, (share + 1) * 2 * dk)
        vs = slice(share * 4 * dv, (share + 1) * 4 * dv)
        hs = slice(share * 4, (share + 1) * 4)
        conv = lw["w_conv"]
        cut = dict(lw, w_q=lw["w_q"][:, ks], w_k=lw["w_k"][:, ks],
                   w_v=lw["w_v"][:, vs], w_z=lw["w_z"][:, vs],
                   w_b=lw["w_b"][:, hs], w_a=lw["w_a"][:, hs],
                   a_log=lw["a_log"][hs], dt_bias=lw["dt_bias"][hs],
                   w_conv=jnp.concatenate([
                       conv[:4 * dk][ks], conv[4 * dk:8 * dk][ks],
                       conv[8 * dk:][vs]]),
                   w_o=lw["w_o"][vs])
        total += ref.linear_attention_part(x, cut, part)
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=1e-4)


def test_head_shares_of_the_full_layer_add_up_to_the_uncut_layer(qwen):
    """Two shares of 2 query heads (with their gates) and their kv head."""
    cfg, ref, _, _, _ = qwen
    whole = dict(cfg, num_attention_heads=4, num_key_value_heads=2,
                 num_hidden_layers=4)
    lw = ref.layer_weights(ref.init_weights(whole, jax.random.PRNGKey(7)), 3)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 48, cfg["hidden_size"]))
    hd = whole["head_dim"]
    want = ref.full_attention_part(x, lw, whole)
    total = jnp.zeros_like(want)
    for share in range(2):
        part = dict(whole, num_attention_heads=2, num_key_value_heads=1)
        qs = slice(share * 4 * hd, (share + 1) * 4 * hd)    # query + gate
        os_ = slice(share * 2 * hd, (share + 1) * 2 * hd)
        ks = slice(share * hd, (share + 1) * hd)
        cut = dict(lw, w_q=lw["w_q"][:, qs], w_k=lw["w_k"][:, ks],
                   w_v=lw["w_v"][:, ks], w_o=lw["w_o"][os_])
        total += ref.full_attention_part(x, cut, part)
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=1e-4)


def test_expert_shares_add_up_with_the_shared_expert_counted_once(qwen):
    """All 64 shares of 2 routed experts of 128, what the program's own
    held-experts layer gives for each, plus the shared expert ONCE, add up
    to the uncut reference's expert part."""
    cfg, ref, _, _, _ = qwen
    whole = dict(cfg, num_router_outputs=128, num_experts=128,
                 first_expert=0, num_hidden_layers=1)
    lw = ref.layer_weights(ref.init_weights(whole, jax.random.PRNGKey(7)), 0)
    u = jax.random.normal(jax.random.PRNGKey(8), (64, cfg["hidden_size"]))
    idx, p = ref.route(u, lw["w_r"], whole)
    want = ref.routed_part(u, idx, p, lw, whole) + ref.shared_part(u, lw)
    got_idx, got_p = moe.route_top_k(u, lw["w_r"], cfg["num_experts_per_tok"])
    np.testing.assert_array_equal(np.sort(got_idx, -1), np.sort(idx, -1))
    total = moe.shared_expert_ffn(u, lw["w_sgu"], lw["w_sd"], lw["w_sg"])
    held_part = jax.jit(lambda gate_up, down, first: moe.held_experts_ffn(
        u, got_idx, got_p, gate_up, down, first, tm=8, activation="silu")[0])
    for share in range(64):
        held = slice(2 * share, 2 * share + 2)
        total += held_part(lw["w_gate_up"][held], lw["w_down"][held],
                           2 * share)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-3)
    # counted 64 times, it would be far off
    off = total + 63 * ref.shared_part(u, lw)
    assert float(jnp.abs(off - want).max()) > 100 * 2e-5


# -- (e) the accepted models are what they were --------------------------------

#: sha256 (first 16 hex digits) of the gradient's jaxpr — the whole traced
#: program, loss and counters, of the model at its `tiny` sizes under remat
#: and without — recorded ANEW AT PR 47, whose expert layer walks the used
#: tiles where it ran whole-size gathers: these programs changed on purpose
#: there (from PR 42's hashes, which PRs 43–46 had kept), and that the new
#: passes compute what the old ones did is shown by arithmetic, not by text
#: (tests/test_sparse_decoder.py::
#: test_used_tile_passes_match_the_whole_size_gathers) — and ANEW AT PR 64,
#: for every language model pinned in the tests: the LOSS alone changed
#: there (`ops/cross_entropy.py:token_cross_entropy` over all rows of the
#: logits, no slice and no gather; no other line of the models did), and
#: that it computes what optax's did on the sliced logits is
#: tests/test_cross_entropy.py's to show. Equal text is an
#: equal program, so equal bits on any machine: a later PR that adds a model
#: must leave these as they are. (Keye's program without remat is PR 55's:
#: its thresholds hand the attention the kept set and the indexer's lse
#: beside tau, `ops/sparse_attention.py:index_selection` — off the chip from
#: the plain twin, which the dense path leaves unread; what the selection
#: computes is held to the dense path and to the parent's bits by
#: tests/test_sparse_attention.py. Under remat the text did not change.)
EXPERTS_WALK_USED_TILES_SINCE_PR_47 = {
    ("smallthinker-21b-a3b", True): "3f32de6669464c9e",
    ("smallthinker-21b-a3b", False): "9d02f591f4c0cca8",
    ("keye-vl2-30b-a3b", True): "177819187c02b43d",
    ("keye-vl2-30b-a3b", False): "60ef72db093e95c9",
    ("sdar-30b-a3b-chat", True): "ba3ad333b5e67745",
    ("sdar-30b-a3b-chat", False): "d1f70aae0138f189",
}


#: the same digest of Qwen3-Next's gradient WITH THE KERNELS FORCED — the
#: program a TPU traces: the SCALAR rule's `gdn_fwd` / `gdn_bwd` round their
#: chunk-local operands in `jax.numpy`. PR 62, which gave the vector form
#: kernels that form a chunk's operands themselves, left the scalar form
#: to the letter (the digests of 48d3016 then); these are PR 64's, whose
#: loss is new and whose rule is not. (The plain paths' digests of this
#: model are tests/test_looped_decoder.py's.)
SCALAR_RULE_ON_ITS_KERNELS = {
    ("qwen3-next-80b-a3b", True): "0dc038c0c6607b60",
    ("qwen3-next-80b-a3b", False): "757df546501bedd4",
}


@pytest.mark.parametrize("config,remat",
                         sorted(SCALAR_RULE_ON_ITS_KERNELS))
def test_the_scalar_rule_on_its_kernels_is_the_program_it_was(config, remat):
    _, traced = traced_gradient(config, remat, kernels=True)
    assert traced == SCALAR_RULE_ON_ITS_KERNELS[(config, remat)]


@pytest.mark.parametrize("config,remat",
                         sorted(EXPERTS_WALK_USED_TILES_SINCE_PR_47))
def test_accepted_models_give_bit_for_bit_what_they_gave(config, remat):
    """SmallThinker, Keye and SDAR with the layer as it is now: the same
    parameter tree (no second kind of mixer, no shared expert) and,
    operation for operation, the same program for loss, counters and
    gradient as PR 47 left."""
    params, traced = traced_gradient(config, remat)
    assert not any("gdn" in name or "shared" in name
                   for name in _leaves(params))
    assert traced == EXPERTS_WALK_USED_TILES_SINCE_PR_47[(config, remat)]
