"""Training by diffusion over blocks: the two-stream block mask in the one
attention dispatch (`ops/block_diffusion_attention.py` under
`ops/attention.py`), the decoder's loss, positions and counters
(`models/sparse_decoder.py`), the noise on the input side, and the share
test of the `sdar-30b-a3b-chat` configuration.

- the kernels (Pallas interpreter) and the dense path against a plain masked
  softmax built from the four rules: result, lse, pairs, dq, dk, dv;
- under remat: a policy that saves the forward rule's named residuals
  leaves the gradient ONE forward kernel, with the same bits;
- the tiles the kernels visit are the tiles the mask reaches, and the pairs
  are T^2 + T x block length;
- the decoder's loss and gradient against `benchmark/reference/
  sdar-30b-a3b-chat.py` at the `tiny` size, and the int8 control outside it;
- the noising function; the counters through `ElasticTrainer`;
- the causal, windowed and selected paths give what they gave.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness, kernel_readers
from jaxpr_kernels import gradient_kernel_calls, pallas_call_names
from edl_tpu.models import sparse_decoder
from edl_tpu.ops import attention
from edl_tpu.ops import block_diffusion_attention as bda
from edl_tpu.ops import flash_attention as fa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "sdar-30b-a3b-chat"

#: (clean tokens T, block length): one 128-wide tile and three
SHAPES = [(128, 4), (128, 32), (384, 4), (384, 32)]
HQ, HKV, HD = 4, 2, 16


def _allowed(t_len, b_len):
    """[2T, 2T] bool, rule by rule, in numpy."""
    i = np.arange(2 * t_len)
    noised = i < t_len
    blk = (i % t_len) // b_len
    qn, kn = noised[:, None], noised[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return ((qn & ~kn & (kb < qb)) | (qn & kn & (kb == qb))
            | (~qn & ~kn & (kb <= qb)))


def _inputs(t_len, dtype="float32"):
    key = jax.random.PRNGKey(t_len)
    shape = lambda h: (1, 2 * t_len, h, HD)
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), shape(h))
                  for i, h in enumerate((HQ, HKV, HKV, HQ)))
    return [x.astype(dtype) for x in (q, k, v)] + [w]


def _plain(q, k, v, t_len, b_len):
    """(context, lse [b, h, s]) of the plain masked softmax, float32."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k, v = (jnp.repeat(x, HQ // HKV, axis=2) for x in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * HD ** -0.5
    sc = jnp.where(_allowed(t_len, b_len), sc, -jnp.inf)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v),
            jax.nn.logsumexp(sc, -1))


def _close(got, want, dtype):
    """float32 element by element; bfloat16 in relative L2 norm against the
    float32 result at the same values (PR 37's tolerances)."""
    got = np.asarray(got, np.float32)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    else:
        assert np.linalg.norm(got - want) <= 1e-2 * np.linalg.norm(want)


# -- (a) kernels and dense path against the plain masked softmax -------------

@pytest.mark.parametrize("t_len,b_len", SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_forward_matches_the_plain_mask(t_len, b_len, dtype):
    q, k, v, _ = _inputs(t_len, dtype)
    want, want_lse = _plain(q, k, v, t_len, b_len)
    group = HQ // HKV
    out, lse, cnt = bda._forward(
        fa._kernel_layout(q, group), fa._kernel_layout(k),
        fa._kernel_layout(v), HD ** -0.5, b_len, True)
    assert out.dtype == q.dtype and lse.dtype == jnp.float32
    _close(fa._model_layout(out, group), want, dtype)
    np.testing.assert_allclose(
        lse.reshape(want_lse.shape), want_lse,
        atol=2e-5 if dtype == "float32" else 2e-2, rtol=0)
    # every query head counted the keys its rows read under the mask
    np.testing.assert_array_equal(
        np.asarray(cnt).reshape(HQ, 2 * t_len),
        np.tile(_allowed(t_len, b_len).sum(-1), (HQ, 1)))


@pytest.mark.parametrize("t_len,b_len", SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_backward_matches_the_plain_mask(t_len, b_len, dtype):
    q, k, v, w = _inputs(t_len, dtype)
    got = jax.grad(lambda q, k, v: jnp.sum(
        attention.attention_context(
            q, k, v, causal=False, mask=None, dtype=q.dtype, use_flash=True,
            streams=(b_len, t_len)).astype(jnp.float32) * w), (0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(
        _plain(q, k, v, t_len, b_len)[0] * w), (0, 1, 2))(
        *[x.astype(jnp.float32) for x in (q, k, v)])
    for a, b in zip(got, want):
        assert a.dtype == q.dtype
        _close(a, b, dtype)


@pytest.mark.parametrize("saved,forwards", [(bda.SAVED_UNDER_REMAT, 1),
                                            ((), 2)])
def test_remat_that_saves_the_residuals_runs_the_forward_once(saved,
                                                              forwards):
    """The forward rule names what it leaves the backward (out, lse): under
    a policy that saves the names the recomputation holds no forward
    kernel, under one without them it holds a second; and either way the
    gradients are bit for bit those of no remat at all."""
    t_len, b_len = 128, 4
    q, k, v, w = _inputs(t_len)

    def layer(q, k, v):
        out, _ = bda.attend(q * 1.5, k, v, (b_len, t_len), interpret=True)
        return jnp.sum(out * out * w)

    rematted = jax.grad(jax.checkpoint(
        layer, policy=jax.checkpoint_policies.save_only_these_names(*saved)),
        (0, 1, 2))
    assert sorted(pallas_call_names(jax.make_jaxpr(rematted)(q, k, v).jaxpr)) \
        == sorted([bda.FWD_NAME] * forwards + [bda.BWD_NAME])
    for got, want in zip(jax.jit(rematted)(q, k, v),
                         jax.jit(jax.grad(layer, (0, 1, 2)))(q, k, v)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("t_len,b_len", SHAPES)
def test_dense_path_matches_the_plain_mask(t_len, b_len):
    """The CPU's path (auto-dispatch off the TPU), result and gradient."""
    q, k, v, w = _inputs(t_len)
    assert "platform" in attention.flash_dispatch_reason(
        2 * t_len, HD, streams=(b_len, t_len))
    run = lambda q, k, v: attention.block_diffusion_attention(
        q, k, v, (b_len, t_len), dtype=jnp.float32)
    out, pairs = run(q, k, v)
    np.testing.assert_allclose(out, _plain(q, k, v, t_len, b_len)[0],
                               atol=2e-5, rtol=2e-5)
    assert float(pairs.sum()) == bda.pairs_of(t_len, b_len)
    got = jax.grad(lambda q, k, v: jnp.sum(run(q, k, v)[0] * w),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(
        _plain(q, k, v, t_len, b_len)[0] * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_flash_attention_entry_takes_the_kernel_layout():
    q, k, v, _ = _inputs(128)
    group = HQ // HKV
    out = fa.flash_attention(fa._kernel_layout(q, group),
                             fa._kernel_layout(k), fa._kernel_layout(v),
                             group=group, interpret=True, streams=(4, 128))
    np.testing.assert_allclose(fa._model_layout(out, group),
                               _plain(q, k, v, 128, 4)[0], atol=2e-5,
                               rtol=2e-5)


# -- (b) tiles visited, pairs attended ----------------------------------------

@pytest.mark.parametrize("t_len,b_len", SHAPES + [(1024, 4)])
def test_tiles_visited_are_the_tiles_the_mask_reaches(t_len, b_len):
    tile = fa._tile_edge(t_len, fa._BLOCK)
    n_t = t_len // tile
    keep = _allowed(t_len, b_len)
    tiles = keep.reshape(2 * n_t, tile, 2 * n_t, tile).transpose(0, 2, 1, 3)
    reached = {(i, j) for i in range(2 * n_t) for j in range(2 * n_t)
               if tiles[i, j].any()}
    visited = bda.tiles_visited(n_t)
    assert {(i, j) for i, j, _ in visited} == reached
    assert len(visited) == len(reached)          # none twice
    for i, j, kind in visited:
        assert tiles[i, j].all() == (kind == "inner")
    assert keep.sum() == bda.pairs_of(t_len, b_len) \
        == t_len * t_len + t_len * b_len


@pytest.mark.parametrize("streams,seq,why", [
    ((4, 8192), 16384, None),
    ((32, 1024), 2048, None),
    ((4, 16384), 32768, "resident limit"),
    ((4, 600), 1200, "no whole number"),
    ((48, 384), 768, "does not divide"),
])
def test_dispatch_reason_names_the_mask_and_why_it_is_refused(streams, seq,
                                                              why):
    reason = attention.flash_dispatch_reason(seq, 128, platform="tpu",
                                             streams=streams)
    if why is None:
        assert reason is None
    else:
        assert "two-stream block mask" in reason and why in reason


@pytest.mark.parametrize("kwargs", [
    dict(causal=True), dict(causal=True, window=8), dict(use_ring=True),
    dict(mask=jnp.ones((1, 64), bool)),
    dict(select=(None, None, None, None))])
def test_combinations_without_meaning_are_refused(kwargs):
    q, k, v, _ = _inputs(32)
    args = dict(dict(causal=False, mask=None, dtype=jnp.float32,
                     streams=(4, 32)), **kwargs)
    with pytest.raises(ValueError):
        attention.attention_context(q, k, v, **args)


def test_a_stream_that_is_no_two_halves_is_refused():
    q, k, v, _ = _inputs(32)
    for streams in ((4, 30), (5, 32), (0, 32)):
        with pytest.raises(ValueError, match="two-stream"):
            attention.attention_context(q, k, v, causal=False, mask=None,
                                        dtype=jnp.float32, streams=streams)


# -- (c) the decoder against the plain reference ------------------------------

def _tiny_cfg():
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         CONFIG + ".json"))
    return dict(cfg, **cfg["tiny"])


@pytest.fixture(scope="module")
def sdar():
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


@pytest.fixture(scope="module")
def reference(sdar):
    """(loss, gradient leaves in the program's layout) of the reference."""
    cfg, ref, fam, w, batch = sdar
    loss, g = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg))(w)
    return loss, g, _leaves(fam.to_program(g, cfg)[0])


@pytest.fixture(scope="module", params=["dense", "kernels"])
def sdar_float32(request, sdar, reference):
    cfg, ref, fam, w, batch = sdar
    want_loss, _, want = reference
    loss, grads, extra = _loss_and_grad(
        cfg, fam, w, batch, jnp.float32,
        use_flash=request.param == "kernels")
    return loss, _leaves(grads), extra, want_loss, want


def test_loss_and_counters_match_the_reference_float32(sdar, sdar_float32):
    cfg, _, _, _, batch = sdar
    loss, _, extra, want_loss, _ = sdar_float32
    np.testing.assert_allclose(loss, want_loss, rtol=2e-5)
    c = extra["counters"]
    n = cfg["num_hidden_layers"]
    assert float(c["steps"]) == 1.0
    np.testing.assert_array_equal(
        c["pairs_attended"], [2.0 * bda.pairs_of(32, cfg["block_length"])] * n)
    assert float(c["loss_tokens"]) == float(
        (batch["loss_weight"] > 0).sum()) > 0
    assert float(c["rows_dropped"].sum()) == 0.0


@pytest.mark.parametrize("leaf", _leaf_names())
def test_gradient_leaf_matches_reference_float32(sdar_float32, leaf):
    _, grads, _, _, want = sdar_float32
    scale = float(jnp.abs(want[leaf]).max())
    assert scale > 0          # every tensor of the model learns
    np.testing.assert_allclose(grads[leaf], want[leaf], atol=2e-4 * scale,
                               rtol=2e-3)


def test_matches_reference_bfloat16(sdar, reference):
    """bf16 activations and products as the cell runs them: the loss and
    the whole gradient in relative L2 norm."""
    cfg, ref, fam, w, batch = sdar
    want_loss, _, want = reference
    loss, grads, _ = _loss_and_grad(cfg, fam, w, batch, jnp.bfloat16)
    assert abs(float(loss) - float(want_loss)) < 2e-3 * float(want_loss)
    got = _leaves(grads)
    num = sum(float(jnp.sum(jnp.square(got[k] - want[k]))) for k in want)
    den = sum(float(jnp.sum(jnp.square(want[k]))) for k in want)
    assert (num / den) ** 0.5 < 0.05


def test_remat_keeps_what_the_attention_left_its_backward(sdar):
    """Under remat the layer saves the two-stream kernel's own residuals
    (`bda.SAVED_UNDER_REMAT`, in the family's one policy) beside the chosen
    experts: in bf16 every gradient leaf matches the layer that recomputes
    nothing, and the gradient holds `bdiff_fwd` once a layer, as without
    remat."""
    cfg, _, fam, w, batch = sdar
    plain = _leaves(_loss_and_grad(cfg, fam, w, batch, jnp.bfloat16,
                                   remat=False)[1])
    rematted = _leaves(_loss_and_grad(cfg, fam, w, batch, jnp.bfloat16,
                                      remat=True)[1])
    for leaf, want in plain.items():
        err = float(jnp.linalg.norm(rematted[leaf] - want)
                    / jnp.linalg.norm(want))
        assert err < 0.02, (leaf, err)
    assert set(bda.SAVED_UNDER_REMAT) <= set(sparse_decoder.SAVED_UNDER_REMAT)
    for remat in (True, False):
        calls = gradient_kernel_calls(fam, cfg, w, batch, remat)
        for kernel in (bda.FWD_NAME, bda.BWD_NAME):
            assert calls[kernel] == cfg["num_hidden_layers"]


def test_int8_control_is_far_from_the_reference(sdar, reference):
    """The control `correct` has to refuse: further from the float32
    reference than the bf16 program is, by the gradient."""
    cfg, ref, fam, w, batch = sdar
    _, g, _ = reference
    _, g8 = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg, "int8"))(w)
    num = sum(float(jnp.sum(jnp.square(g8[k] - g[k]))) for k in g)
    den = sum(float(jnp.sum(jnp.square(g[k]))) for k in g)
    assert (num / den) ** 0.5 > 0.1


def test_what_each_compared_number_guards(sdar, reference):
    """At the seed's weights the logits are nearly flat, so the loss is
    about (mean weight) x log(vocabulary) whatever the targets: a dropped
    1/t moves `loss_rel_err` far outside its limit, a SHIFTED target or the
    clean copy at the head hardly moves it — those `grad_rel_err` sees."""
    cfg, ref, _, w, batch = sdar
    run = jax.jit(lambda batch: ref.loss_and_grad(w, batch, cfg))
    want, g, _ = reference

    def moved(wrong):
        loss, g2 = run(wrong)
        num = sum(float(jnp.sum(jnp.square(g2[k] - g[k]))) for k in g)
        den = sum(float(jnp.sum(jnp.square(g[k]))) for k in g)
        return abs(float(loss) - float(want)) / float(want), (num / den) ** .5

    unweighted = dict(batch, loss_weight=(batch["loss_weight"] > 0).astype(
        jnp.float32))
    assert moved(unweighted)[0] > 0.1
    shifted = dict(batch, input_ids=jnp.roll(batch["input_ids"], -1, 1))
    clean_head = dict(batch, noisy_ids=batch["input_ids"])
    for wrong in (shifted, clean_head):
        loss_moved, grad_moved = moved(wrong)
        assert loss_moved < 0.01 and grad_moved > 0.5


def test_leaves_hold_the_norms_and_no_indexer():
    cfg = _tiny_cfg()
    fam = harness.load_module("program", cfg["family"])
    params, extra = fam.train_parts(cfg, {"remat": True})[2]
    assert sorted(params["layer_0"]) == [
        "experts_down", "experts_gate_up", "key", "norm_attn", "norm_key",
        "norm_moe", "norm_query", "out", "query", "router", "value"]
    assert sorted(extra["counters"]) == sorted(
        sparse_decoder.COUNTERS + ("pairs_attended", "loss_tokens", "steps"))


def test_configuration_holds_the_published_widths():
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         CONFIG + ".json"))
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "num_attention_heads", "num_key_value_heads",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "num_attention_heads": 32,
                                "num_key_value_heads": 4,
                                "vocab_size": 151936}
    assert (cfg["hidden_size"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_router_outputs"]) == (2048, 128, 768, 8, 128)
    assert cfg["mask_token_id"] == cfg["vocab_size"] - 1
    for name in ("block_length", "noise", "loss_weight", "stream_order",
                 "mask_token_id"):
        assert name in cfg["assumed"]
    fam = harness.load_module("program", cfg["family"])
    shapes = fam.train_parts(cfg, {"remat": True})[2][0]
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n - 248.7e6) < 0.05e6          # the file's deployment


def test_train_flops_count_the_mask_and_the_masked_positions():
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         CONFIG + ".json"))
    fam = harness.load_module("program", cfg["family"])
    job = {"seq_len": 8192, "remat": True}
    t, b, n = 8192, 4, 4
    assert fam.attended_pairs(t, b) == t * t + t * b
    assert fam.required_pairs(cfg, t) == n * (t * t + t * b) \
        - t * (t + b) / 2
    flops = fam.train_flops(cfg, job, 1)
    attention_flops = 3.0 * fam.required_pairs(cfg, t) * 8 * 2 * 2 * 128
    assert 6.0e12 < flops < 7.0e12
    assert 0.40 < attention_flops / flops < 0.50
    costs = fam.kernel_costs(cfg, job, 1)
    assert sorted(costs) == ["bdiff_bwd", "bdiff_fwd", "moe_gmm", "moe_tgmm"]
    assert costs["bdiff_fwd"][0] == pytest.approx(2 * attention_flops / 3)
    assert costs["bdiff_bwd"][0] == pytest.approx(
        2.5 * attention_flops / 3)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_every_seed_gives_the_held_experts_the_same_rows(seed):
    """The seeded weights number each layer's experts by the mask token's
    router score — its first choice, then the others from its last choice
    upwards: whatever the seed, the masked positions (one vector as they
    enter a layer, so ONE lump) go to expert 0, which is held here, and to
    no other of the first `num_experts`."""
    cfg = _tiny_cfg()
    ref = harness.load_module("reference", CONFIG)
    w = ref.init_weights(cfg, jax.random.PRNGKey(seed))
    e = w["embed"][cfg["mask_token_id"]]
    for i in range(cfg["num_hidden_layers"]):
        lw = ref.layer_weights(w, i)
        u = ref._rms(e, lw["g2"], cfg["rms_norm_eps"])
        score = np.asarray(u @ lw["w_r"])
        assert score.argmax() == 0
        assert (np.diff(score[1:]) >= 0).all()
        chosen = np.asarray(ref.route(u[None], lw["w_r"], cfg)[0][0])
        assert sorted(chosen[chosen < cfg["num_experts"]]) == [0]


# -- (d) the noise on the input side ------------------------------------------

def test_noise_is_one_t_a_block_and_the_mask_id_is_never_a_target():
    cfg = _tiny_cfg()
    ref = harness.load_module("reference", CONFIG)
    b_len, mask_id = 8, cfg["mask_token_id"]
    ids = jax.random.randint(jax.random.PRNGKey(0), (64, 512), 0, mask_id)
    key = jax.random.PRNGKey(1)
    noisy, weight = jax.jit(
        lambda ids, key: sparse_decoder.block_diffusion_noise(
            ids, key, b_len, mask_id, 1e-3))(ids, key)
    masked = np.asarray(noisy == mask_id)
    assert noisy.dtype == jnp.int32 and weight.dtype == jnp.float32
    np.testing.assert_array_equal(np.where(masked, ids, noisy), ids)
    np.testing.assert_array_equal(np.asarray(weight) > 0, masked)
    assert not np.any(np.asarray(ids) == mask_id)
    # one t a block: the masked positions of a block share their weight 1/t
    per_block = np.asarray(weight).reshape(64, -1, b_len)
    top = per_block.max(-1, keepdims=True)
    assert np.all((per_block == 0) | (per_block == top))
    t = 1.0 / top[top > 0]
    assert 1e-3 <= t.min() and t.max() <= 1.0
    # masked share ~ t: E[m / t] = 1 over the tokens, E[m] = (1 + t_min) / 2
    assert abs(np.asarray(weight).mean() - 1.0) < 0.05
    assert abs(masked.mean() - 0.5) < 0.02
    want = ref.noise_batch(ids, key, dict(cfg, block_length=b_len))
    np.testing.assert_array_equal(noisy, want["noisy_ids"])
    np.testing.assert_array_equal(weight, want["loss_weight"])


def test_rope_takes_positions_and_counts_them_by_default():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 2, 8))
    np.testing.assert_array_equal(
        sparse_decoder.rope(x, 1e4),
        sparse_decoder.rope(x, 1e4, jnp.arange(16)))
    twice = sparse_decoder.rope(jnp.concatenate([x, x], 1), 1e4,
                                jnp.tile(jnp.arange(16), 2))
    np.testing.assert_array_equal(twice[:, :16], twice[:, 16:])


# -- the counters, through the trainer, to the readers ------------------------

def test_trainer_takes_three_leaves_and_mirrors_the_counters(sdar):
    import optax
    from edl_tpu.runtime.mesh import make_mesh
    from edl_tpu.runtime.trainer import ElasticTrainer
    cfg, _, fam, w, batch = sdar
    loss_fn, has_aux, _ = fam.train_parts(cfg, {"remat": False})
    params, extra = fam.to_program(w, cfg)
    trainer = ElasticTrainer(loss_fn, params, optax.sgd(1e-3),
                             total_batch_size=2, extra_state=extra,
                             has_aux=has_aux,
                             mesh=make_mesh(devices=jax.devices()[:1]))
    try:
        staged = trainer.place_batch(batch)
        assert {k: v.dtype for k, v in staged.items()} == {
            "input_ids": jnp.int32, "noisy_ids": jnp.int32,
            "loss_weight": jnp.float32}
        for _ in range(2):
            trainer.train_step(staged)
    finally:
        trainer.close()
    got = kernel_readers.model_counters()
    n = cfg["num_hidden_layers"]
    assert got["steps"] == [2.0]
    assert got["pairs_attended"] == [2 * 2.0 * bda.pairs_of(32, 4)] * n
    assert got["loss_tokens"] == [2.0 * float((batch["loss_weight"]
                                               > 0).sum())]
    view = {"traffic": {"seq_len": 32, "batch_per_chip": 2},
            "cell": {"chips": 1}, "config": cfg}
    assert harness.load_module("metrics", "bd_pairs_attended_pct").read(
        view) == 100.0
    assert harness.load_module("metrics", "bd_loss_token_pct").read(
        view) == pytest.approx(100.0 * got["loss_tokens"][0] / (2 * 2 * 32))


# -- (e) the share test -------------------------------------------------------

def test_head_shares_add_up_to_the_uncut_layer(sdar):
    """Four shares of 2 query heads and their kv head, each over the whole
    stream under the whole mask: their parts of the attention result add up
    to the uncut layer's (8 query heads on 4 kv heads at the tiny widths).
    The sixteen expert shares are a case of
    `test_sparse_decoder.py::test_silu_expert_shares_add_up_to_the_uncut_layer`."""
    cfg, ref, _, _, _ = sdar
    whole = dict(cfg, num_attention_heads=8, num_key_value_heads=4,
                 num_hidden_layers=1)
    lw = ref.layer_weights(ref.init_weights(whole, jax.random.PRNGKey(7)), 0)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 64, cfg["hidden_size"]))
    hd = whole["head_dim"]
    want = ref.attention_part(x, lw, whole)
    total = jnp.zeros_like(want)
    for share in range(4):
        part = dict(whole, num_attention_heads=2, num_key_value_heads=1)
        qs = slice(share * 2 * hd, (share + 1) * 2 * hd)
        ks = slice(share * hd, (share + 1) * hd)
        cut = dict(lw, w_q=lw["w_q"][:, qs], w_k=lw["w_k"][:, ks],
                   w_v=lw["w_v"][:, ks], w_o=lw["w_o"][qs])
        total += ref.attention_part(x, cut, part)
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=1e-4)


# -- (f) the causal, windowed and selected paths are what they were ----------

def _old_softmax_tile(q, k, v, carry, q_lo, k_lo, *, sm_scale, masked,
                      causal, window, kv_len):
    """`_softmax_tile` as PR 37 left it, before its arithmetic moved into
    `_softmax_update`."""
    from jax import lax
    acc, m, l = carry
    scores = fa._dot(q, k, fa._NT) * sm_scale
    keep = None
    if masked:
        tq, tk = scores.shape
        keep = fa._band_mask(
            q_lo + lax.broadcasted_iota(jnp.int32, (tq, 1), 0),
            k_lo + lax.broadcasted_iota(jnp.int32, (1, tk), 1),
            causal, window, kv_len)
    if keep is not None:
        scores = jnp.where(keep, scores, fa._NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)
    if keep is not None:
        p = jnp.where(keep, p, 0.0)
    correction = jnp.exp(m - m_new)
    return (acc * correction + fa._dot(p.astype(v.dtype), v, fa._NN), m_new,
            l * correction + p.sum(axis=-1, keepdims=True))


def _old_p_and_ds(a, b, da, db, lse, delta, q_pos, k_pos, *, sm_scale,
                  masked, causal, window, kv_len):
    p = jnp.exp(fa._dot(a, b, fa._NT) * sm_scale - lse)
    keep = fa._band_mask(q_pos, k_pos, causal, window, kv_len) if masked \
        else None
    if keep is not None:
        p = jnp.where(keep, p, 0.0)
    return p, p * (fa._dot(da, db, fa._NT) - delta)


@pytest.mark.parametrize("variant,window,dtype", [
    ("resident", None, "float32"), ("resident", 96, "bfloat16"),
    ("streamed", None, "bfloat16"), ("streamed", 96, "float32")])
def test_band_kernels_give_bit_for_bit_what_they_gave(monkeypatch, variant,
                                                      window, dtype):
    """The band kernels with the tile arithmetic as it was and as it is:
    result and gradients equal bit for bit."""
    monkeypatch.setattr(fa, "_BLOCK", 128)
    if variant == "streamed":
        monkeypatch.setattr(fa, "_RESIDENT_KV_BYTES", 0)
    key = jax.random.PRNGKey(11)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 256, h, 16)
                                 ).astype(dtype)
               for i, h in enumerate((4, 2, 2)))

    def run():
        out, vjp = jax.vjp(lambda q, k, v: fa.mha(
            q, k, v, causal=True, window=window, interpret=True), q, k, v)
        return (out,) + vjp(jnp.ones_like(out))

    new = run()
    monkeypatch.setattr(fa, "_softmax_tile", _old_softmax_tile)
    monkeypatch.setattr(fa, "_p_and_ds", _old_p_and_ds)
    jax.clear_caches()
    for a, b in zip(new, run()):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    jax.clear_caches()


def test_selected_path_and_causal_counters_are_what_they_were():
    """A causal model keeps its counter tree, its call without positions,
    and a selection still excludes what it excluded."""
    assert sparse_decoder.counter_names() == sparse_decoder.COUNTERS
    assert sparse_decoder.counter_names(True) == (
        sparse_decoder.COUNTERS + sparse_decoder.SELECT_COUNTERS)
    assert sorted(sparse_decoder.init_counters(2)["counters"]) == sorted(
        sparse_decoder.COUNTERS + ("steps",))
    q, k, v, _ = _inputs(32)
    with pytest.raises(ValueError, match="excludes a window"):
        attention.attention_context(q, k, v, causal=True, mask=None,
                                    dtype=jnp.float32, window=8,
                                    select=(None,) * 4)
