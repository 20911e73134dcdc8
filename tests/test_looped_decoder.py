"""A LOOPED decoder: a stack of dense layers run several times over with the
same weights, an exit gate after every pass and a loss that is an
expectation over exits (``models/sparse_decoder.py``: ``loop_steps``,
``dense_width``, ``sandwich_norm``; Ouro-2.6B, arXiv:2510.25741).

(a) the program against the plain reference
    (benchmark/reference/ouro-2.6b.py) at the configuration's `tiny` sizes:
    float32 tight, bfloat16 inside the traffic file's tiny limits, the int8
    control and five omissions outside them;
(b) what the loop is made of: one parameter tree whatever the number of
    passes, one scan whose body holds the stack once, a shared weight's
    gradient the float32 sum of its uses, a dense layer with no router;
(c) the counters, through the trainer, to the readers;
(d) the cut: the vocabulary's shares laid side by side are the uncut head;
(e) the accepted decoders are the program they were.
CPU, tiny sizes."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness, kernel_readers
from jaxpr_kernels import gradient_kernel_calls, traced_gradient
from edl_tpu.models import sparse_decoder
from edl_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "ouro-2.6b"
TRAFFIC = "tokens-8192-loop"


def _cfg():
    return harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                          CONFIG + ".json"))


def _tiny_cfg():
    cfg = _cfg()
    return dict(cfg, **cfg["tiny"])


def _tiny_limits():
    return harness.load_json(os.path.join(
        REPO, "benchmark", "traffic", TRAFFIC + ".json"))["tiny"]["limits"]


@pytest.fixture(scope="module")
def ouro():
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


# -- (a) the program against the plain reference ------------------------------

@pytest.fixture(scope="module")
def reference(ouro):
    """(loss, gradient, its leaves in the program's layout, p, l)."""
    cfg, ref, fam, w, batch = ouro
    loss, g = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg))(w)
    _, p, ce = jax.jit(lambda w: ref.loss_parts(w, batch, cfg))(w)
    return loss, g, _leaves(fam.to_program(g, cfg)[0]), p, ce


@pytest.fixture(scope="module", params=["plain", "kernels"])
def ouro_float32(request, ouro, reference):
    cfg, ref, fam, w, batch = ouro
    loss, grads, extra = _loss_and_grad(
        cfg, fam, w, batch, jnp.float32,
        use_flash=request.param == "kernels")
    return loss, _leaves(grads), extra


def test_loss_and_counters_match_the_reference_float32(ouro, ouro_float32,
                                                       reference):
    """The loss, and the counters read what the loss applied: the exit
    distribution's mean a pass (summing to 1) and each pass's own loss, as
    the reference works them out token by token."""
    cfg, _, _, _, _ = ouro
    loss, _, extra = ouro_float32
    want_loss, _, _, p, ce = reference
    np.testing.assert_allclose(loss, want_loss, rtol=2e-6)
    c = extra["counters"]
    assert sorted(c) == sorted(sparse_decoder.LOOP_COUNTERS + ("steps",))
    assert float(c["steps"]) == 1.0
    passes = cfg["total_ut_steps"]
    assert all(c[n].shape == (passes,)
               for n in sparse_decoder.LOOP_COUNTERS)
    np.testing.assert_allclose(np.asarray(p).sum(0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(float(c["loop_exit_mass"].sum()), 1.0,
                               rtol=1e-6)
    np.testing.assert_allclose(c["loop_exit_mass"], p.mean(1), rtol=1e-5)
    np.testing.assert_allclose(c["loop_pass_loss"], ce.mean(1), rtol=1e-5)
    rms = np.asarray(c["loop_stream_rms_max"])
    # a unit-RMS stream and four unit-RMS branches a pass (two layers)
    assert (rms > 1.5).all() and (rms < 4.0).all()


@pytest.mark.parametrize("leaf", _leaf_names())
def test_gradient_leaf_matches_reference_float32(ouro_float32, reference,
                                                 leaf):
    _, grads, _ = ouro_float32
    want = reference[2]
    scale = float(jnp.abs(want[leaf]).max())
    assert scale > 0          # every tensor of the model learns
    np.testing.assert_allclose(grads[leaf], want[leaf], atol=2e-4 * scale,
                               rtol=2e-3)


def test_matches_reference_bfloat16(ouro, reference):
    """bf16 activations and products as the cell runs them: inside the
    tiny limits, by the loss and by the whole gradient in relative L2."""
    cfg, ref, fam, w, batch = ouro
    want_loss, _, want = reference[:3]
    limits = _tiny_limits()
    loss, grads, _ = _loss_and_grad(cfg, fam, w, batch, jnp.bfloat16)
    assert abs(float(loss) - float(want_loss)) / float(want_loss) \
        < limits["loss_rel_err"]
    assert _distance(_leaves(grads), want) < limits["grad_rel_err"]


def test_int8_control_is_far_from_the_reference(ouro, reference):
    """The control `correct` has to refuse: outside the tiny limits."""
    cfg, ref, fam, w, batch = ouro
    g = reference[1]
    _, g8 = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg, "int8"))(w)
    assert _distance(g8, g) > 5 * _tiny_limits()["grad_rel_err"]


def _norm_not_carried(ref):
    """The final norm read by head and gate but NOT carried: the next pass
    starts from the un-normed stream."""
    def passes(w, ids, cfg, qc=None):
        z, out = w["embed"][ids], []
        for _ in range(cfg["total_ut_steps"]):
            for i in range(cfg["num_hidden_layers"]):
                z = ref.layer(z, ref.layer_weights(w, i), cfg, qc)
            out.append(ref._norm(z, w["g_f"], cfg["rms_norm_eps"]))
        return out
    return passes


def _last_pass_gated(ref):
    """The last pass under a gate like the others' (the one before's score)
    instead of taking what mass is left: the masses no longer sum to 1."""
    whole = ref.exit_log_probabilities

    def log_p(a):
        out = whole(a)
        return out.at[-1].add(jax.nn.log_sigmoid(a[-1]))
    return log_p


def _no_norm_after_attention(ref):
    def layer(z, lw, cfg, qc=None):
        eps = cfg["rms_norm_eps"]
        z = z + ref.attention_part(ref._norm(z, lw["g1"], eps), lw, cfg, qc)
        return z + ref._norm(ref.feed_forward_part(
            ref._norm(z, lw["g3"], eps), lw, qc), lw["g4"], eps)
    return layer


@pytest.mark.parametrize("omission", [
    "three_passes_for_four", "final_norm_not_carried",
    "last_pass_without_the_remaining_mass", "entropy_term_dropped",
    "norm_after_a_sublayer_dropped"])
def test_what_each_compared_number_guards(ouro, reference, monkeypatch,
                                          omission):
    """Each omission, made in the reference, reads outside at least one of
    the tiny limits the bf16 program reads inside."""
    cfg, ref, _, w, batch = ouro
    want, g = reference[:2]
    if omission == "three_passes_for_four":
        cfg = dict(cfg, total_ut_steps=cfg["total_ut_steps"] - 1)
    elif omission == "final_norm_not_carried":
        monkeypatch.setattr(ref, "passes", _norm_not_carried(ref))
    elif omission == "last_pass_without_the_remaining_mass":
        monkeypatch.setattr(ref, "exit_log_probabilities",
                            _last_pass_gated(ref))
    elif omission == "entropy_term_dropped":
        cfg = dict(cfg, exit_entropy_weight=0.0)
    else:
        monkeypatch.setattr(ref, "layer", _no_norm_after_attention(ref))
    loss, g2 = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg))(w)
    limits = _tiny_limits()
    assert (abs(float(loss) - float(want)) / float(want)
            > limits["loss_rel_err"]
            or _distance(g2, g) > limits["grad_rel_err"])


# -- (b) what the loop is made of ----------------------------------------------

@pytest.mark.parametrize("loop_steps", [1, 4])
def test_parameter_tree_holds_the_stack_once(loop_steps):
    """`num_layers` layers whatever the number of passes, a dense layer
    with its four norms and no router or expert; the gate only where there
    is a loop; the counters per pass only there."""
    cfg = _tiny_cfg()
    fam = harness.load_module("program", cfg["family"])
    model = fam.build_model(cfg, {"remat": True}).clone(loop_steps=loop_steps)
    params, extra = jax.eval_shape(
        lambda: sparse_decoder.create_model_and_loss(model)[1:3])
    layers = ["layer_%d" % i for i in range(cfg["num_hidden_layers"])]
    gate = ["exit_gate", "exit_gate_bias"] if loop_steps > 1 else []
    assert sorted(params) == sorted(
        ["embed", "lm_head", "norm_final"] + layers + gate)
    assert sorted(params["layer_0"]) == [
        "ffn_down", "ffn_gate_up", "key", "norm_attn", "norm_attn_out",
        "norm_ffn_out", "norm_moe", "out", "query", "value"]
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    assert params["layer_0"]["ffn_gate_up"].shape == (d, 2 * f)
    assert params["layer_0"]["ffn_down"].shape == (f, d)
    want = (sparse_decoder.LOOP_COUNTERS if loop_steps > 1 else ()) + (
        "steps",)
    assert sorted(extra["counters"]) == sorted(want)


def _scans(jaxpr, found=None):
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scans(sub, found)
    return found


def test_step_holds_the_stack_once_and_adds_in_float32(ouro):
    """The gradient is ONE scan forward and one backward over the passes;
    the backward's carry holds every layer weight's float32 accumulator,
    and the Pallas kernels in the traced program are a layer's, not a
    pass's: their number does not grow with the passes."""
    cfg, _, fam, w, batch = ouro
    model = fam.build_model(cfg, {"remat": True})
    _, _, extra, loss_fn = sparse_decoder.create_model_and_loss(model)
    params, _ = fam.to_program(w, cfg)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, extra, batch, None)[0]))(params).jaxpr
    over_passes = [e for e in _scans(jaxpr)
                   if e.params["length"] == cfg["total_ut_steps"]]
    assert [bool(e.params["reverse"]) for e in over_passes] == [False, True]
    backward = over_passes[1]
    carried = [v.aval for v in backward.outvars[:backward.params[
        "num_carry"]]]
    shape = params["layer_0"]["ffn_down"].shape     # no other leaf's
    assert sum(a.shape == shape and a.dtype == jnp.float32
               for a in carried) == cfg["num_hidden_layers"]
    assert not any(a.dtype == jnp.bfloat16 and a.shape == shape
                   for a in carried)
    layers = cfg["num_hidden_layers"]
    for passes in (2, 4):
        calls = gradient_kernel_calls(
            fam, dict(cfg, total_ut_steps=passes), w, batch, True)
        assert dict(calls) == {"flash_fwd_resident": 2 * layers,
                               "flash_bwd": layers}


def test_shared_weight_gradient_is_the_sum_over_untied_copies(ouro,
                                                              monkeypatch):
    """The program's gradient of a layer's weights against the reference
    with FOUR untied copies of the stack, one a pass: the sum of the four
    copies' gradients."""
    cfg, ref, fam, w, batch = ouro
    passes = cfg["total_ut_steps"]
    _, grads, _ = _loss_and_grad(cfg, fam, w, batch, jnp.float32)

    def untied_loss(copies):
        later = iter(copies[1:] + [copies[-1]])
        # the pass hands the weights on: here, to the next copy
        monkeypatch.setattr(ref, "_hand_on", lambda _: next(later))
        return ref.loss(copies[0], batch, cfg)

    per_copy = jax.grad(untied_loss)([dict(w) for _ in range(passes)])
    layer_keys = [k for k in w if "/" in k]
    summed = {k: sum(g[k] for g in per_copy) for k in layer_keys}
    # a copy alone is NOT the shared weight's gradient
    assert _distance({k: per_copy[0][k] for k in layer_keys}, summed) > 0.3
    got = _leaves(grads)
    want = _leaves(fam.to_program(dict(w, **summed), cfg)[0])
    for name in got:
        if "layer_" in name:
            scale = float(jnp.abs(want[name]).max())
            np.testing.assert_allclose(got[name], want[name],
                                       atol=2e-4 * scale, rtol=2e-3)


def test_dense_ffn_shares_the_shared_expert_s_arithmetic():
    """One gated linear unit: the shared expert under a wide-open gate is
    the dense feed-forward part."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    u = jax.random.normal(ks[0], (8, 16), jnp.float32)
    gate_up = jax.random.normal(ks[1], (16, 48)) * 0.2
    down = jax.random.normal(ks[2], (24, 16)) * 0.2
    dense = moe.dense_ffn(u, gate_up, down)
    shared = moe.shared_expert_ffn(u, gate_up, down,
                                   jnp.zeros((16,)), "silu")
    np.testing.assert_allclose(dense, 2.0 * shared, rtol=1e-6)
    want = (jax.nn.silu(u @ gate_up[:, :24]) * (u @ gate_up[:, 24:])) @ down
    np.testing.assert_allclose(dense, want, rtol=1e-5, atol=1e-6)


def test_a_loop_takes_no_block_mask_and_no_selection():
    cfg = _tiny_cfg()
    fam = harness.load_module("program", cfg["family"])
    model = fam.build_model(cfg, {}).clone(select_layout=(1, 1),
                                           select_topk=4, index_heads=1,
                                           index_dim=8)
    with pytest.raises(ValueError, match="looped"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))


def test_routed_layers_in_a_loop_count_once_a_step():
    """A layer's routing counters come out of the scan a pass at a time and
    are folded over the passes by their own rule: [L] a name, as without a
    loop."""
    model = sparse_decoder.SparseDecoder(
        vocab_size=32, d_model=16, num_layers=2, heads=2, kv_heads=1,
        head_dim=8, num_experts=4, experts_held=2, first_expert=0,
        experts_per_token=2, expert_width=16, rope_layout=(1, 1),
        window_layout=(0, 0), window=0, rope_theta=1e4, dtype=jnp.float32,
        loop_steps=3)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 32)
    _, params, extra, loss_fn = sparse_decoder.create_model_and_loss(model)
    loss, extra = jax.jit(lambda p: loss_fn(p, extra, {"input_ids": ids},
                                            None))(params)
    c = extra["counters"]
    assert np.isfinite(float(loss))
    assert c["rows_held"].shape == (2,) and c["loop_exit_mass"].shape == (3,)
    once = model.clone(loop_steps=1)
    layers = {k: v for k, v in params.items() if "exit_gate" not in k}
    _, first = once.apply({"params": layers}, ids)
    # the first pass is the unlooped model's: the fold adds two more
    assert (np.asarray(c["rows_held"]) >= np.asarray(first["rows_held"])
            ).all()
    assert float(c["rows_dropped"].sum()) == 0.0


# -- (c) the counters, through the trainer, to the readers -------------------

def test_trainer_mirrors_the_loop_s_counters(ouro, monkeypatch):
    import optax
    from edl_tpu.runtime import trainer as trainer_mod
    from edl_tpu.runtime.mesh import make_mesh
    from edl_tpu.runtime.trainer import ElasticTrainer
    # the gauge is the process's: what a routed model's test mirrored before
    # this one (another file, the same worker) is not this model's to report
    monkeypatch.setattr(trainer_mod._MODEL_COUNTER, "_children", {})
    cfg, _, fam, w, batch = ouro
    loss_fn, has_aux, _ = fam.train_parts(cfg, {"remat": False})
    # the trainer donates its state; the fixture's arrays stay the tests'
    params, extra = jax.tree_util.tree_map(jnp.copy, fam.to_program(w, cfg))
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
    mass, rms = got["loop_exit_mass"], got["loop_stream_rms_max"]
    assert len(mass) == len(rms) == len(got["loop_pass_loss"]) == 4
    assert sum(mass) == pytest.approx(2.0, rel=1e-5)     # 1 a step
    view = {"traffic": {"seq_len": 32, "batch_per_chip": 2},
            "cell": {"chips": 1}, "config": cfg}
    read = lambda name: harness.load_module("metrics", name).read(view)
    assert read("loop_exit_mass_sum_pct") == pytest.approx(100.0, rel=1e-5)
    assert read("loop_expected_exit_pass") == pytest.approx(
        sum((u + 1) * m for u, m in enumerate(mass)) / 2.0)
    assert 1.0 < read("loop_expected_exit_pass") < 4.0
    assert read("loop_stream_rms_max") == max(rms)
    # a model without experts has no routing to report
    assert read("moe_rows_dropped") is None


def test_readers_return_none_where_the_program_counts_nothing(monkeypatch):
    for name in ("loop_exit_mass_sum_pct", "loop_expected_exit_pass",
                 "loop_stream_rms_max"):
        mod = harness.load_module("metrics", name)
        monkeypatch.setattr(mod, "model_counters", lambda: {})
        assert mod.read({}) is None


# -- (d) the cut ----------------------------------------------------------------

def test_vocabulary_shares_side_by_side_are_the_uncut_logits(ouro):
    """Eight chips share the vocabulary: each holds an eighth of the head's
    columns (and is handed the embedding rows of the ids, which its group
    exchanges). The program's float32 logits of the eight shares, laid
    side by side, are the uncut reference's, in every pass."""
    cfg, ref, fam, w, batch = ouro
    shares = 8
    rows = cfg["vocab_size"] // shares
    ids = batch["input_ids"] % rows          # ids of the first share's rows
    want = ref.logits(w, ids, cfg)
    model = fam.build_model(dict(cfg, vocab_size=rows), {"remat": False}
                            ).clone(dtype=jnp.float32)
    got = []
    for k in range(shares):
        share = dict(w, embed=w["embed"][:rows],
                     head=w["head"][:, k * rows:(k + 1) * rows])
        params, _ = fam.to_program(share, cfg)
        got.append(model.apply({"params": params}, ids)[0])
    got = jnp.concatenate(got, axis=-1)
    assert got.shape == (cfg["total_ut_steps"],) + ids.shape + (
        cfg["vocab_size"],)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_configuration_holds_the_published_widths():
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "vocab_size": 49152}
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["total_ut_steps"], cfg["rope_theta"], cfg["rms_norm_eps"],
            cfg["tie_word_embeddings"], cfg["num_hidden_layers"],
            cfg["vocab_size"]) == (2048, 128, 16, 16, 5632, 4, 1000000,
                                   1e-6, False, 4, 49152 // 8)
    assert len(cfg["layer_types"]) == 48
    assert set(cfg["layer_types"]) == {"full_attention"}
    for name in ("norms", "final_norm_in_loop", "exit_gate", "loss",
                 "no_second_stage", "rope", "weights"):
        assert name in cfg["assumed"]
    assert "12 pipeline stages" in cfg["deployment"]
    fam = harness.load_module("program", cfg["family"])
    shapes = fam.train_parts(cfg, {"remat": True})[2][0]
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == 4 * 51388416 + 2 * 12582912 + 2048 + 2049 == 230723585


def test_train_flops_and_kernel_costs_count_what_they_say():
    cfg = _cfg()
    fam = harness.load_module("program", cfg["family"])
    t = 8192
    job = {"seq_len": t, "remat": True}
    layer, head = fam.matrix_weights_per_token(cfg)
    assert layer == 2048 * 3 * 2048 + 2048 * 2048 + 3 * 2048 * 5632
    assert head == 2048 * 6144 + 2048
    pairs = t * (t + 1) / 2.0
    attention = 16 * 3.0 * pairs * 16 * 2 * 2 * 128     # 16 applications
    flops = fam.train_flops(cfg, job, 1)
    assert flops == pytest.approx(
        4 * 6.0 * t * (4 * layer + head) + attention)
    assert 55.5e12 < flops < 56.5e12
    assert 0.23 < attention / flops < 0.24
    assert 0.043 < 4 * 6.0 * t * head / flops < 0.045
    # uncut: 48 layers a pass, the whole vocabulary
    uncut = dict(cfg, **cfg["published"])
    whole = fam.train_flops(uncut, job, 1)
    assert 0.029 < 4 * 6.0 * t * (2048 * 49152 + 2048) / whole < 0.031
    # the calls the step makes under remat: the resident forward twice a
    # layer APPLICATION, the backward once
    costs = fam.kernel_costs(cfg, job, 1)
    assert sorted(costs) == ["flash_bwd", "flash_fwd_resident"]
    assert costs["flash_fwd_resident"][0] == pytest.approx(2 * attention / 3)
    assert costs["flash_bwd"][0] == pytest.approx(2.5 * attention / 3)
    once = fam.kernel_costs(cfg, dict(job, remat=False), 1)
    assert once["flash_fwd_resident"][0] == pytest.approx(attention / 3)
    for ops, nbytes in costs.values():      # compute-bound on a v5e
        assert ops / 197e12 > nbytes / 819e9


def test_family_refuses_a_program_without_the_loop(monkeypatch):
    """What the parent commit meets when it is handed this cell: a
    BenchError at once, from every entry of the family's file."""
    cfg = _tiny_cfg()
    fam = harness.load_module("program", cfg["family"])
    monkeypatch.delattr(sparse_decoder, "LOOP_COUNTERS")
    for call in (lambda: fam.build_model(cfg, {}),
                 lambda: fam.train_parts(cfg, {}),
                 lambda: fam.to_program({}, cfg)):
        with pytest.raises(harness.BenchError, match="loop_steps"):
            call()


# -- (e) the accepted decoders are what they were -------------------------------

#: sha256 (first 16 hex digits) of the gradient's jaxpr — the whole traced
#: program, loss and counters, of the model at its `tiny` sizes under remat
#: and without. Qwen3-Next's were recorded ANEW AT PR 47, whose expert layer
#: walks the used tiles where it ran whole-size gathers: the four sparse
#: programs changed on purpose there, and that the new passes compute what
#: the old ones did is shown by arithmetic, not by text
#: (tests/test_sparse_decoder.py::
#: test_used_tile_passes_match_the_whole_size_gathers). ALL of these were
#: recorded anew at PR 64, which changed the LOSS alone (every row of the
#: logits against shifted targets, `ops/cross_entropy.py`; what it computes
#: is tests/test_cross_entropy.py's to show). A later PR that adds
#: a model must leave these the same program. SmallThinker, Keye and SDAR
#: are pinned beside them by tests/test_gated_delta.py. Ouro-2.6B, dense
#: through the same block, holds no expert: PR 47 left its program as the
#: commit before it (cdb8dcc) gave it, and only PR 64's loss has changed
#: it since
EXPERTS_WALK_USED_TILES_SINCE_PR_47 = {
    ("qwen3-next-80b-a3b", True): "b9f93eb6607a08aa",
    ("qwen3-next-80b-a3b", False): "4a86af7fc5eefb37",
}
DENSE_TRACED = {
    ("ouro-2.6b", True): "886383d22eb53540",
    ("ouro-2.6b", False): "01aba6af6c10bad1",
}


@pytest.mark.parametrize("config,remat",
                         sorted(EXPERTS_WALK_USED_TILES_SINCE_PR_47))
def test_accepted_models_give_bit_for_bit_what_they_gave(config, remat):
    """Qwen3-Next (both kinds of mixer, the shared expert whose arithmetic
    the dense feed-forward part shares) with the decoder as it is now: the
    same parameter tree and, operation for operation, the same program for
    loss, counters and gradient as PR 47 left."""
    params, traced = traced_gradient(config, remat)
    names = _leaves(params)
    assert not any(part in name for name in names
                   for part in ("ffn_", "exit_gate", "norm_attn_out",
                                "norm_ffn_out"))
    assert traced == EXPERTS_WALK_USED_TILES_SINCE_PR_47[(config, remat)]


@pytest.mark.parametrize("config,remat", sorted(DENSE_TRACED))
def test_a_model_without_experts_traces_as_before_the_experts_changed(
        config, remat):
    """Ouro-2.6B runs `moe.dense_ffn` and no plan, no row pass: a change
    to the held experts' layer leaves it the same program."""
    params, traced = traced_gradient(config, remat)
    names = _leaves(params)
    assert any("ffn_" in name for name in names)
    assert not any("experts_" in name for name in names)
    assert traced == DENSE_TRACED[(config, remat)]
