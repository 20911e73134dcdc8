"""The sparse decoder (`models/sparse_decoder.py`), its dropless
held-experts layer (`parallel/moe.py:held_experts_ffn`,
`ops/grouped_matmul.py`) and the benchmark's readers of what they count:

- the model against the plain reference (`benchmark/reference/
  smallthinker-21b-a3b.py`) on seeded weights at the `tiny` size: loss and
  every gradient leaf, in float32 and in bfloat16;
- the share test: the 8 expert shares and the 4 head shares of one layer
  add up to what the uncut reference gives for the whole layer;
- dropless under adversarial routing: every token picks held experts only;
  none picks any;
- the routing counters from the device, through `ElasticTrainer`, into the
  metrics registry, and the benchmark's readers on a recorded view.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness, kernel_readers
from jaxpr_kernels import gradient_kernel_calls
from edl_tpu.models import sparse_decoder
from edl_tpu.ops import grouped_matmul as gm
from edl_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "smallthinker-21b-a3b"
HI = jax.lax.Precision.HIGHEST


@pytest.fixture(scope="module")
def tiny():
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         CONFIG + ".json"))
    cfg = dict(cfg, **cfg["tiny"])
    ref = harness.load_module("reference", CONFIG)
    fam = harness.load_module("program", cfg["family"])
    w = ref.init_weights(cfg, jax.random.PRNGKey(3))
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0,
                             cfg["vocab_size"], jnp.int32)
    return cfg, ref, fam, w, {"input_ids": ids}


def _program_loss_and_grad(cfg, fam, w, batch, dtype):
    model = fam.build_model(cfg, {"remat": True}).clone(dtype=dtype)
    _, _, extra, loss_fn = sparse_decoder.create_model_and_loss(model)
    params, _ = fam.to_program(w, cfg)
    (loss, extra), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, extra, batch, None), has_aux=True))(params)
    return loss, grads, extra


def _leaves(tree):
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_leaves_with_path(tree)}


def _grad_leaf_names():
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         CONFIG + ".json"))
    cfg = dict(cfg, **cfg["tiny"])
    fam = harness.load_module("program", cfg["family"])
    return sorted(_leaves(fam.train_parts(cfg, {"remat": True})[2][0]))


@pytest.fixture(scope="module")
def both_float32(tiny):
    cfg, ref, fam, w, batch = tiny
    want_loss, g = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg))(w)
    loss, grads, extra = _program_loss_and_grad(cfg, fam, w, batch,
                                                jnp.float32)
    return (loss, _leaves(grads), extra, want_loss,
            _leaves(fam.to_program(g, cfg)[0]))


def test_model_loss_matches_reference_float32(both_float32):
    loss, _, extra, want_loss, _ = both_float32
    assert abs(float(loss) - float(want_loss)) <= 1e-4 * float(want_loss)
    assert float(extra["counters"]["rows_dropped"].sum()) == 0.0


@pytest.mark.parametrize("leaf", _grad_leaf_names())
def test_model_gradient_leaf_matches_reference_float32(both_float32, leaf):
    _, got, _, _, want = both_float32
    scale = float(jnp.abs(want[leaf]).max())
    assert scale > 0, "a leaf the loss does not reach"
    np.testing.assert_allclose(got[leaf], want[leaf], atol=1e-4 * scale,
                               rtol=1e-4)


def test_model_matches_reference_bfloat16(tiny):
    """bf16 activations and products against the float32 reference: the
    comparison `correct` makes on the chip (a relative L2 error over all
    parameters), at the tiny size."""
    cfg, ref, fam, w, batch = tiny
    want_loss, g = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg))(w)
    loss, grads, _ = _program_loss_and_grad(cfg, fam, w, batch,
                                            jnp.bfloat16)
    assert abs(float(loss) - float(want_loss)) <= 2e-3 * float(want_loss)
    got, want = _leaves(grads), _leaves(fam.to_program(g, cfg)[0])
    num = sum(float(jnp.sum(jnp.square(got[k] - want[k]))) for k in want)
    den = sum(float(jnp.sum(jnp.square(want[k]))) for k in want)
    assert (num / den) ** 0.5 < 0.05


def test_remat_keeps_the_choice_with_the_saved_products(tiny):
    """Under remat the layer saves the chosen experts WITH the two grouped
    products (`moe.SAVED_UNDER_REMAT`): in bf16, where a recomputed top-k
    can break a near tie the other way, every gradient leaf — the routers'
    above all — matches the layer that recomputes nothing. The policy is
    the family's one (`sparse_decoder.SAVED_UNDER_REMAT`), which also holds
    the masked attention kernels' residual names: a model of band layers
    alone emits none of them, builds and differentiates as it did."""
    cfg, _, fam, w, batch = tiny

    def grads(remat):
        model = fam.build_model(cfg, {"remat": remat})
        _, _, extra, loss_fn = sparse_decoder.create_model_and_loss(model)
        params, _ = fam.to_program(w, cfg)
        return _leaves(jax.jit(jax.grad(
            lambda p: loss_fn(p, extra, batch, None)[0]))(params))

    plain, rematted = grads(False), grads(True)
    for leaf, want in plain.items():
        err = float(jnp.linalg.norm(rematted[leaf] - want)
                    / jnp.linalg.norm(want))
        assert err < 0.02, (leaf, err)


def test_band_layers_emit_none_of_the_names_and_still_run_forward_twice(
        tiny):
    """The band kernels name no residual (their cost count in
    benchmark/program/sparse_decoder.py holds two forwards under remat: the
    band path follows once that count reads the trace's calls): under the
    widened policy a rematerialised band layer holds the forward kernel
    twice, as it did, and once without remat."""
    from edl_tpu.ops import flash_attention as fa
    cfg, _, fam, w, batch = tiny
    layers = cfg["num_hidden_layers"]
    assert set(moe.SAVED_UNDER_REMAT) <= set(sparse_decoder.SAVED_UNDER_REMAT)
    for remat, forwards in ((True, 2), (False, 1)):
        calls = gradient_kernel_calls(fam, cfg, w, batch, remat)
        assert calls[fa.FWD_RESIDENT_NAME] == forwards * layers
        assert calls[fa.BWD_NAME] == layers


def test_int8_control_is_far_from_the_reference(tiny):
    cfg, ref, _, w, batch = tiny
    loss, g = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg))(w)
    loss8, g8 = jax.jit(
        lambda w: ref.loss_and_grad(w, batch, cfg, "int8"))(w)
    num = sum(float(jnp.sum(jnp.square(g8[k] - g[k]))) for k in g)
    den = sum(float(jnp.sum(jnp.square(g[k]))) for k in g)
    assert (num / den) ** 0.5 > 0.2
    with pytest.raises(ValueError):
        ref.loss_and_grad(w, batch, cfg, "int4")


# -- the share test ----------------------------------------------------------

def _uncut_layer(tiny):
    """One layer at the tiny widths, UNCUT: all 8 experts, 8 query heads on
    4 kv heads; and its input."""
    cfg, ref, _, _, _ = tiny
    whole = dict(cfg, moe_num_primary_experts=cfg["moe_router_outputs"],
                 num_attention_heads=8, num_key_value_heads=4,
                 num_hidden_layers=1, first_expert=0)
    w = ref.init_weights(whole, jax.random.PRNGKey(7))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 32, cfg["hidden_size"]))
    return whole, ref.layer_weights(w, 0), x


@pytest.mark.parametrize("use_window", [0, 1])
def test_head_shares_add_up_to_the_uncut_layer(tiny, use_window):
    _, ref, _, _, _ = tiny
    whole, lw, h = _uncut_layer(tiny)
    hd = whole["head_dim"]
    want = ref.attention_part(h, lw, use_window, use_window, whole)
    total = jnp.zeros_like(want)
    for share in range(4):          # 2 query heads and their kv head each
        part = dict(whole, num_attention_heads=2, num_key_value_heads=1)
        qs = slice(share * 2 * hd, (share + 1) * 2 * hd)
        ks = slice(share * hd, (share + 1) * hd)
        cut = dict(lw, w_q=lw["w_q"][:, qs], w_k=lw["w_k"][:, ks],
                   w_v=lw["w_v"][:, ks], w_o=lw["w_o"][qs])
        total += ref.attention_part(h, cut, use_window, use_window, part)
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("who", ["reference", "program"])
def test_expert_shares_add_up_to_the_uncut_layer(tiny, who):
    """8 shares of 1 expert each (`first_expert` 0..7), the router whole in
    every one: reference shares against the uncut reference, and the
    program's grouped-product layer, share by share, against it too."""
    _, ref, _, _, _ = tiny
    whole, lw, x = _uncut_layer(tiny)
    u = x.reshape(-1, x.shape[-1])
    idx, p = ref.route(u, lw["w_r"], whole)
    want = ref.experts_part(u, idx, p, lw, whole)
    total = jnp.zeros_like(want)
    for first in range(8):
        cut = dict(lw, w_gate_up=lw["w_gate_up"][first:first + 1],
                   w_down=lw["w_down"][first:first + 1])
        if who == "reference":
            part = dict(whole, moe_num_primary_experts=1, first_expert=first)
            total += ref.experts_part(u, idx, p, cut, part)
        else:
            m, counters = moe.held_experts_ffn(
                u, idx, p, cut["w_gate_up"], cut["w_down"], first, tm=16)
            assert float(counters["rows_dropped"]) == 0.0
            total += m
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)


# -- dropless, whatever the routing -----------------------------------------

def _dense_held(u, idx, p, w_gate_up, w_down, first):
    f = w_down.shape[1]
    out = jnp.zeros_like(u)
    for e in range(w_down.shape[0]):
        weight = jnp.where(idx == first + e, p, 0.0).sum(1)
        gu = jnp.dot(u, w_gate_up[e], precision=HI)
        out += weight[:, None] * jnp.dot(
            jax.nn.relu(gu[:, :f]) * gu[:, f:], w_down[e], precision=HI)
    return out


def _layer_inputs(t=96, d=128, f=128, held=4, k=3):
    key = jax.random.PRNGKey(1)
    u = jax.random.normal(key, (t, d))
    w_gate_up = 0.1 * jax.random.normal(jax.random.fold_in(key, 2),
                                        (held, d, 2 * f))
    w_down = 0.1 * jax.random.normal(jax.random.fold_in(key, 3),
                                     (held, f, d))
    p = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 4),
                                         (t, k)), -1)
    return u, w_gate_up, w_down, p


@pytest.mark.parametrize("routing,rows,unserved", [
    ("all_to_one_held_expert", 96 * 3, 0),   # every choice is held: 3T rows
    ("none_held", 0, 96),
    ("mixed", None, None)])
def test_dropless_under_adversarial_routing(routing, rows, unserved):
    u, w_gate_up, w_down, p = _layer_inputs()
    t, first = u.shape[0], 4
    if routing == "all_to_one_held_expert":
        # the worst case for a capacity layer: one expert takes every
        # token's first choice, the other choices are held too
        idx = jnp.tile(jnp.array([[5, 6, 7]], jnp.int32), (t, 1))
    elif routing == "none_held":
        idx = jnp.tile(jnp.array([[0, 1, 2]], jnp.int32), (t, 1))
    else:
        idx = jax.random.randint(jax.random.PRNGKey(9), (t, 3), 0, 16,
                                 jnp.int32)
    m, c = moe.held_experts_ffn(u, idx, p, w_gate_up, w_down, first, tm=16)
    np.testing.assert_allclose(
        m, _dense_held(u, idx, p, w_gate_up, w_down, first), atol=1e-5,
        rtol=1e-5)
    assert float(c["rows_dropped"]) == 0.0
    held = np.logical_and(np.asarray(idx) >= first, np.asarray(idx) < 8)
    assert float(c["rows_held"]) == held.sum()
    assert float(c["tokens_unserved"]) == (~held.any(1)).sum()
    if rows is not None:
        assert (float(c["rows_held"]), float(c["tokens_unserved"])) == (
            rows, unserved)


def test_held_experts_gradients_match_dense():
    u, w_gate_up, w_down, p = _layer_inputs()
    idx = jax.random.randint(jax.random.PRNGKey(5), (u.shape[0], 3), 0, 16,
                             jnp.int32)
    fast = lambda *a: jnp.sum(jnp.sin(moe.held_experts_ffn(  # noqa: E731
        a[0], idx, a[3], a[1], a[2], 4, tm=16)[0]))
    slow = lambda *a: jnp.sum(jnp.sin(_dense_held(  # noqa: E731
        a[0], idx, a[3], a[1], a[2], 4)))
    got = jax.grad(fast, (0, 1, 2, 3))(u, w_gate_up, w_down, p)
    want = jax.grad(slow, (0, 1, 2, 3))(u, w_gate_up, w_down, p)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()),
                                   rtol=1e-4)


# -- the passes over the used tiles against the whole-size gathers ----------

@jax.custom_vjp
def _take_rows(src, idx, inverse):
    """The plain reference of the layer's four row passes, as the layer
    ran them up to PR 46: src[idx] over the WHOLE static index space, zeros
    where idx is out of range; ``inverse`` [r, len(src)] lists for each row
    of src the r places of the result that may read it, so the backward
    pass is a whole-size gather too."""
    del inverse
    return jnp.take(src, idx, axis=0, mode="fill", fill_value=0)


def _take_rows_fwd(src, idx, inverse):
    return _take_rows(src, idx, inverse), inverse


def _take_rows_bwd(inverse, g):
    g = g.reshape((-1, g.shape[-1]))
    back = jnp.take(g, inverse, axis=0, mode="fill", fill_value=0)
    return back.sum(axis=0), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _whole_size_held_experts_ffn(u, idx, p, w_gate_up, w_down, first, tm):
    """`moe.held_experts_ffn` with every row pass at the static worst case
    of T x k choices and T x k + held x tm rows: the same plan, the same
    grouped products, the same names under remat."""
    from jax.ad_checkpoint import checkpoint_name
    f = w_down.shape[1]
    plan = moe.plan_held_rows(idx, first, w_down.shape[0], tm)
    rows = _take_rows(u, plan["slot_token"], plan["slot"])
    product = functools.partial(
        gm.grouped_matmul, tile_group=plan["tile_group"],
        n_used=plan["n_used"], tm=tm, interpret=True)
    gu = checkpoint_name(product(rows, w_gate_up), moe.SAVED_UNDER_REMAT[1])
    y = checkpoint_name(product(jax.nn.relu(gu[:, :f]) * gu[:, f:], w_down),
                        moe.SAVED_UNDER_REMAT[2])
    picked = _take_rows(y, plan["slot"], plan["slot_choice"][None, :])
    return jnp.sum(picked.astype(jnp.float32) * p.T[..., None],
                   axis=0).astype(u.dtype)


def _top_choices(seed, t, experts, k=3):
    """k DISTINCT experts a token, as `lax.top_k` gives them."""
    scores = jax.random.normal(jax.random.PRNGKey(seed), (t, experts))
    return jax.lax.top_k(scores, k)[1].astype(jnp.int32)


def _rows_to_one_expert(t, n):
    """The first ``n`` tokens' first choice is held expert 4; every other
    choice of every token is held elsewhere."""
    first = jnp.where(jnp.arange(t) < n, 4, 0)
    return jnp.stack([first, jnp.full((t,), 1), jnp.full((t,), 2)],
                     axis=1).astype(jnp.int32)


#: name -> (idx of [96, 3] choices, first held expert); 4 experts held, tiles
#: of 16 rows
ROUTINGS = {
    "all_to_one_held_expert": lambda: (
        jnp.tile(jnp.array([[5, 6, 7]], jnp.int32), (96, 1)), 4),
    "none_held": lambda: (
        jnp.tile(jnp.array([[0, 1, 2]], jnp.int32), (96, 1)), 4),
    "every_expert_held": lambda: (_top_choices(11, 96, 4), 0),
    "exactly_a_tile_of_rows": lambda: (_rows_to_one_expert(96, 16), 4),
    "a_tile_and_one_row": lambda: (_rows_to_one_expert(96, 17), 4),
    "mixed": lambda: (_top_choices(12, 96, 16), 4),
}


def _used_tiles(idx, first, held, tm):
    counts = [int(np.sum(np.asarray(idx) == first + e)) for e in range(held)]
    return sum(max(-(-c // tm), 1) for c in counts)


#: the token side's two forms, each forced whatever the routing (the cost
#: of a scatter-added row in gathered rows: nothing, or beyond any layout),
#: and the form the layer itself picks at these sizes
TOKEN_SIDE = {"tile_by_tile": 0.0, "choice_by_choice": float("inf"),
              "as_the_layer_picks": None}


@pytest.mark.parametrize("token_side", sorted(TOKEN_SIDE))
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_used_tile_passes_match_the_whole_size_gathers(routing, remat,
                                                       token_side,
                                                       monkeypatch):
    """Forward, and the gradients to u, p and both expert matrices, of the
    layer whose row passes walk the used tiles against the same layer with
    whole-size gathers, in float32 under a loss LINEAR in the result (so
    both sides are handed the same cotangent): bit-equal where no sum
    changed its order (the rows laid out and the row-side cotangent, hence
    both products and both matrices' gradients), to 1e-6 of the largest
    entry where one did (added tile by tile, a token's choices come in the
    order of their experts; a row's <g, y> is one tile's reduction)."""
    if TOKEN_SIDE[token_side] is not None:
        monkeypatch.setattr(moe, "SCATTER_ROWS_PER_GATHERED",
                            TOKEN_SIDE[token_side])
    u, w_gate_up, w_down, p = _layer_inputs()
    idx, first = ROUTINGS[routing]()
    c = jax.random.normal(jax.random.PRNGKey(13), u.shape)
    policy = jax.checkpoint_policies.save_only_these_names(
        *moe.SAVED_UNDER_REMAT)

    def result_and_grads(layer):
        run = lambda *a: layer(a[0], idx, a[1], a[2], a[3], first, tm=16)
        if remat:
            run = jax.checkpoint(run, policy=policy)
        return jax.jit(jax.value_and_grad(
            lambda *a: (jnp.vdot(run(*a), c), run(*a)), (0, 1, 2, 3),
            has_aux=True))(u, p, w_gate_up, w_down)
    (_, m_got), got = result_and_grads(
        lambda *a, **kw: moe.held_experts_ffn(*a, **kw)[0])
    (_, m_want), want = result_and_grads(_whole_size_held_experts_ffn)

    def close(a, b):
        np.testing.assert_allclose(
            a, b, rtol=0, atol=1e-6 * max(float(jnp.abs(b).max()), 1e-30))
    close(m_got, m_want)
    close(got[0], want[0])                       # d_u
    close(got[1], want[1])                       # d_p
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))
    assert float(jnp.abs(want[0]).max()) > 0 or routing == "none_held"


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_rows_moved_counts_the_used_tiles(routing):
    """A pass visits ``n_used`` tiles of ``tm`` rows — the served rows and
    each held expert's padding — whatever the static size of the layout."""
    u, w_gate_up, w_down, p = _layer_inputs()
    idx, first = ROUTINGS[routing]()
    _, c = moe.held_experts_ffn(u, idx, p, w_gate_up, w_down, first, tm=16)
    plan = moe.plan_held_rows(idx, first, 4, 16)
    assert float(c["rows_moved"]) == 16 * int(plan["n_used"][0]) == (
        16 * _used_tiles(idx, first, 4, 16))
    assert float(c["rows_held"]) <= float(c["rows_moved"]) < (
        float(c["rows_held"]) + 4 * 16 + 1)
    assert float(c["rows_dropped"]) == 0.0


@pytest.mark.parametrize("n_used", [1, 3, 6])
def test_grouped_matmul_kernels_match_plain_products(n_used):
    tm, k, n = 16, 256, 128
    key = jax.random.PRNGKey(n_used)
    x = jax.random.normal(key, (6 * tm, k))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, k, n))
    tile_group = jnp.array([0, 0, 1, 2, 2, 2], jnp.int32)
    used = jnp.array([n_used], jnp.int32)
    args = (tile_group, used, tm)
    got = gm.grouped_matmul(x, w, tile_group, used, tm, True)
    want = gm.grouped_matmul_reference(x, w, *args)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    fast = jax.grad(lambda x, w: jnp.sum(jnp.cos(gm.grouped_matmul(
        x, w, tile_group, used, tm, True))), (0, 1))(x, w)
    slow = jax.grad(lambda x, w: jnp.sum(jnp.cos(
        gm.grouped_matmul_reference(x, w, *args))), (0, 1))(x, w)
    if n_used == 6:   # every group owns a used tile: dw is whole
        for a, b in zip(fast, slow):
            np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)
    else:
        np.testing.assert_allclose(fast[0], slow[0], atol=1e-3, rtol=1e-3)


def test_row_plan_is_sorted_by_expert_in_whole_tiles():
    idx = jnp.array([[4, 9], [5, 4], [0, 5], [4, 6]], jnp.int32)
    plan = moe.plan_held_rows(idx, 4, 3, tm=2)
    assert plan["counts"].tolist() == [3, 2, 1]
    # expert 4: 2 tiles (3 rows), expert 5: 1, expert 6: 1
    assert plan["tile_group"].tolist()[:4] == [0, 0, 1, 2]
    assert int(plan["n_used"][0]) == 4
    # choices are numbered choice-major: all first choices, then all
    # second ones; `slot` is [k, T]
    assert plan["slot"].tolist() == [[0, 4, 14, 1], [14, 2, 5, 6]]
    assert plan["slot_token"].tolist()[:7] == [0, 3, 1, 4, 1, 2, 3]


# -- counters: device -> trainer -> registry -> readers ---------------------

def test_trainer_mirrors_routing_counters_into_the_registry(tiny):
    import optax
    from edl_tpu.obs import metrics
    from edl_tpu.runtime.trainer import ElasticTrainer
    cfg, _, fam, w, batch = tiny
    loss_fn, has_aux, _ = fam.train_parts(cfg, {"remat": False})
    params, extra = fam.to_program(w, cfg)
    from edl_tpu.runtime.mesh import make_mesh
    # one device, as the cell: the interpreted Pallas kernels are not
    # partitioned over the test harness's eight virtual ones
    trainer = ElasticTrainer(loss_fn, params, optax.sgd(1e-3),
                             total_batch_size=2, extra_state=extra,
                             has_aux=has_aux,
                             mesh=make_mesh(devices=jax.devices()[:1]))
    ids = batch["input_ids"]
    try:
        for _ in range(2):
            trainer.train_step(trainer.place_batch({"input_ids": ids}))
        assert "steps" not in kernel_readers.model_counters() or (
            kernel_readers.model_counters()["steps"] != [2.0])
    finally:
        trainer.close()
    got = kernel_readers.model_counters()
    n = cfg["num_hidden_layers"]
    assert got["steps"] == [2.0]
    assert len(got["rows_held"]) == n and sum(got["rows_held"]) > 0
    assert got["rows_dropped"] == [0.0] * n
    # whole tiles, at least the rows served
    assert all(moved % moe.ROW_TILE == 0 and moved >= held
               for moved, held in zip(got["rows_moved"], got["rows_held"]))
    assert kernel_readers.load_max_over_mean(got) >= 1.0
    assert kernel_readers.expert_rows_per_step(got) == pytest.approx(
        sum(got["rows_held"]) / n / 2.0)
    snap = metrics.REGISTRY.snapshot()["metrics"]["edl_train_model_counter"]
    assert snap["labelnames"] == ["name", "index"]


def _view(ops, steps=30):
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         CONFIG + ".json"))
    job = harness.load_json(os.path.join(REPO, "benchmark", "traffic",
                                         "tokens-8192.json"))
    return {"trace": {"ops": ops}, "counters": {"traced_steps": steps},
            "config": cfg, "traffic": job,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}}


@pytest.mark.parametrize("kernel", ["flash_fwd_resident", "moe_gmm",
                                    "moe_tgmm"])
def test_kernel_readers_on_a_recorded_view(kernel):
    """A view as lib/xplane.py reduces a trace: op classes and seconds
    over 30 traced steps. The kernel is found under its name however
    autodiff wrapped it; the share is its least time over the measured."""
    ops = [["fusion", 3.0], [kernel, 0.6], ["jvp_%s_" % kernel, 0.3],
           ["while", 0.5]]
    device_ms = harness.load_module("metrics", kernel + "_device_ms")
    roofline = harness.load_module("metrics", kernel + "_roofline_pct")
    view = _view(ops)
    assert device_ms.read(view) == pytest.approx(30.0)
    share = roofline.read(view)
    assert 0.0 < share < 100.0
    fam = harness.load_module("program", "sparse_decoder")
    flops, nbytes = fam.kernel_costs(view["config"], view["traffic"], 2,
                                     kernel_readers.expert_rows_per_step(
                                         kernel_readers.model_counters()))[
                                             kernel]
    assert share == pytest.approx(
        100.0 * max(flops / 197e12, nbytes / 819e9) / 0.030)
    # a program without the kernel: nothing to read, nothing raised
    empty = _view([["fusion", 3.0]])
    assert device_ms.read(empty) is None and roofline.read(empty) is None


@pytest.mark.parametrize("ops,want", [
    # every backward kernel, however autodiff and remat wrapped its name,
    # and not the forward's: 0.9 s over 30 steps
    ([["flash_bwd", 0.3], ["flash_bwd_dq", 0.2], ["flash_bwd_dkv", 0.2],
      ["transpose_jvp_flash_bwd_", 0.2], ["flash_fwd_resident", 0.7],
      ["while", 0.5]], 30.0),
    # the parent's program: scans and no kernel: nothing read, none raised
    ([["fusion", 3.0], ["while", 0.5], ["flash_fwd_resident", 0.7]], None),
])
def test_flash_bwd_reader_sums_the_backward_kernels_only(ops, want):
    from edl_tpu.ops import flash_attention as fa
    for bwd in (fa.BWD_NAME, fa.BWD_DQ_NAME, fa.BWD_DKV_NAME):
        assert "flash_bwd" in bwd
        for fwd in (fa.FWD_RESIDENT_NAME, fa.FWD_STREAM_NAME):
            assert fwd not in bwd and "flash_bwd" not in fwd
    got = harness.load_module("metrics", "flash_bwd_device_ms").read(
        _view(ops))
    assert got == (None if want is None else pytest.approx(want))
    fwd_ms = harness.load_module("metrics", "flash_fwd_resident_device_ms")
    assert fwd_ms.read(_view(ops)) == pytest.approx(0.7 / 30 * 1e3)


@pytest.mark.parametrize("name", ["moe_rows_dropped",
                                  "moe_expert_load_max_over_mean",
                                  "moe_rows_moved_over_served"])
def test_counter_readers_return_none_without_counters(monkeypatch, name):
    monkeypatch.setattr(kernel_readers, "model_counters", lambda: {})
    mod = harness.load_module("metrics", name)
    monkeypatch.setattr(mod, "model_counters", lambda: {})
    assert mod.read({}) is None


@pytest.mark.parametrize("counters,want", [
    # two layers over three steps: 3072 + 4096 rows moved for 2560 + 2000
    ({"rows_moved": [3072.0, 4096.0], "rows_held": [2560.0, 2000.0],
      "steps": [3.0]}, 7168.0 / 4560.0),
    # a program from before the counter (the parent's): nothing to read
    ({"rows_held": [2560.0, 2000.0], "steps": [3.0]}, None),
    # no row served in the whole run: no ratio
    ({"rows_moved": [1024.0], "rows_held": [0.0], "steps": [1.0]}, None),
])
def test_rows_moved_over_served_reader(monkeypatch, counters, want):
    mod = harness.load_module("metrics", "moe_rows_moved_over_served")
    monkeypatch.setattr(mod, "model_counters", lambda: counters)
    got = mod.read({})
    assert got == (None if want is None else pytest.approx(want))


def test_train_flops_counts_the_band_and_the_expected_rows():
    fam = harness.load_module("program", "sparse_decoder")
    view = _view([])
    total = fam.train_flops(view["config"], view["traffic"], 2)
    assert total == pytest.approx(10.99e12, rel=0.01)   # ISSUE 27: 10.9
    assert fam.band_pairs(8192) == 8192 * 8193 / 2
    assert fam.band_pairs(8192, 4096) == 4096 * 4097 / 2 + 4096 * 4096
    assert fam.expected_expert_rows(view["config"], 16384) == 12288


# == keye-vl2-30b-a3b: learned selection, post-attention router, SiLU =======

KEYE = "keye-vl2-30b-a3b"


def _keye_cfg():
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         KEYE + ".json"))
    return dict(cfg, **cfg["tiny"])


@pytest.fixture(scope="module")
def keye():
    cfg = _keye_cfg()
    ref = harness.load_module("reference", KEYE)
    fam = harness.load_module("program", cfg["family"])
    w = ref.init_weights(cfg, jax.random.PRNGKey(3))
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0,
                             cfg["vocab_size"], jnp.int32)
    return cfg, ref, fam, w, {"input_ids": ids}


def _keye_loss_and_grad(cfg, fam, w, batch, dtype, remat=True,
                        use_flash=None):
    model = fam.build_model(cfg, {"remat": remat}).clone(
        dtype=dtype, use_flash=use_flash)
    _, _, extra, loss_fn = sparse_decoder.create_model_and_loss(model)
    params, _ = fam.to_program(w, cfg)
    (loss, extra), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, extra, batch, None), has_aux=True))(params)
    return loss, grads, extra


def _keye_leaf_names():
    cfg = _keye_cfg()
    fam = harness.load_module("program", cfg["family"])
    return sorted(_leaves(fam.train_parts(cfg, {"remat": True})[2][0]))


INDEXER_LEAVES = ("index_query", "index_key", "index_weight")


@pytest.fixture(scope="module", params=["dense", "kernels"])
def keye_float32(request, keye):
    cfg, ref, fam, w, batch = keye
    want_loss, g = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg))(w)
    loss, grads, extra = _keye_loss_and_grad(
        cfg, fam, w, batch, jnp.float32,
        use_flash=request.param == "kernels")
    return (loss, _leaves(grads), extra, want_loss,
            _leaves(fam.to_program(g, cfg)[0]))


def test_keye_loss_and_counters_match_the_reference_float32(keye,
                                                            keye_float32):
    cfg = keye[0]
    loss, _, extra, want_loss, _ = keye_float32
    assert abs(float(loss) - float(want_loss)) <= 1e-4 * float(want_loss)
    c = extra["counters"]
    n, topk = cfg["num_hidden_layers"], cfg["sa_config"]["topk"]
    want = 2 * sum(min(t + 1, topk) for t in range(32))
    # at least top-k keys a row; more only at an exact tie at a threshold
    # (the tiny indexer's scores are often exactly 0): the rows that are
    # off are counted, and are few
    for kept, off in zip(c["pairs_kept"].tolist(),
                         c["rows_off_count"].tolist()):
        assert 0 <= kept - want <= 8 * off and off <= 4
    assert c["rows_dropped"].tolist() == [0.0] * n
    assert all(x > 1e-4 for x in c["index_loss"].tolist())


@pytest.mark.parametrize("leaf", _keye_leaf_names())
def test_keye_gradient_leaf_matches_reference_float32(keye_float32, leaf):
    """Every leaf ON ITS OWN: the indexer's three tensors a layer are 5%
    of the parameters and would hide in one norm over all of them."""
    _, got, _, _, want = keye_float32
    scale = float(jnp.abs(want[leaf]).max())
    assert scale > 0, "a leaf the loss does not reach"
    if "layer_0" in leaf or not any(i in leaf for i in INDEXER_LEAVES):
        np.testing.assert_allclose(got[leaf], want[leaf], atol=2e-4 * scale,
                                   rtol=2e-4)
    else:
        # a deeper indexer's input carries float32's last bits of layer 0,
        # and one key that crosses a threshold moves its gradient by a
        # percent: the leaf's own norm, not its elements
        err = float(jnp.linalg.norm(got[leaf] - want[leaf])
                    / jnp.linalg.norm(want[leaf]))
        assert err < 0.05, err


def test_keye_leaves_hold_the_indexer_the_norms_and_no_more():
    names = _keye_leaf_names()
    layer0 = {n.split("']['")[1].rstrip("']") for n in names
              if "layer_0" in n}
    assert layer0 == {"norm_attn", "norm_query", "norm_key", "norm_moe",
                      "query", "key", "value", "out", "router",
                      "experts_gate_up", "experts_down", *INDEXER_LEAVES}
    assert sum(any(i in n for i in INDEXER_LEAVES) for n in names) == 6


def test_keye_matches_reference_bfloat16(keye):
    """What `correct` compares on the chip, at the tiny size — and the
    indexer's group on its own, which one norm over all leaves would
    hide."""
    cfg, ref, fam, w, batch = keye
    want_loss, g = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg))(w)
    loss, grads, _ = _keye_loss_and_grad(cfg, fam, w, batch, jnp.bfloat16)
    assert abs(float(loss) - float(want_loss)) <= 5e-3 * float(want_loss)
    got, want = _leaves(grads), _leaves(fam.to_program(g, cfg)[0])

    def err(keys):
        num = sum(float(jnp.sum(jnp.square(got[k] - want[k]))) for k in keys)
        den = sum(float(jnp.sum(jnp.square(want[k]))) for k in keys)
        return (num / den) ** 0.5

    assert err(list(want)) < 0.3
    assert err([k for k in want if any(i in k for i in INDEXER_LEAVES)]
               ) < 0.3


def test_keye_remat_keeps_the_thresholds_with_what_depends_on_them(keye):
    """Under remat the layer saves the thresholds WITH the indexer's
    operands they were found from, beside the chosen experts: in bf16
    every gradient leaf matches the layer that recomputes nothing. It saves
    the selection kernels' own residuals too — the forward's result and
    lse, the thresholds' kernel's lse of the indexer — so the gradient
    holds `dsa_index_tau` and `dsa_fwd` once a layer, as without remat:
    the kept set the first hands the second has no name and no later
    reader."""
    cfg, _, fam, w, batch = keye
    plain = _leaves(_keye_loss_and_grad(cfg, fam, w, batch, jnp.bfloat16,
                                        remat=False)[1])
    rematted = _leaves(_keye_loss_and_grad(cfg, fam, w, batch,
                                           jnp.bfloat16, remat=True)[1])
    for leaf, want in plain.items():
        err = float(jnp.linalg.norm(rematted[leaf] - want)
                    / jnp.linalg.norm(want))
        assert err < 0.02, (leaf, err)
    from edl_tpu.ops import sparse_attention
    assert sparse_attention.SAVED_UNDER_REMAT[3] == "attn.tau"
    assert set(sparse_attention.SAVED_RESIDUALS) \
        <= set(sparse_attention.SAVED_UNDER_REMAT) \
        <= set(sparse_decoder.SAVED_UNDER_REMAT)
    # the kept set lives from the thresholds to the one forward: no name
    assert len(sparse_attention.SAVED_UNDER_REMAT) == 7 and not any(
        "mask" in n or "kept" in n for n in sparse_decoder.SAVED_UNDER_REMAT)
    layers = cfg["num_hidden_layers"]
    for remat in (True, False):
        calls = gradient_kernel_calls(fam, cfg, w, batch, remat)
        assert {n: c for n, c in calls.items() if n.startswith("dsa")} == {
            sparse_attention.TAU_NAME: layers,
            sparse_attention.FWD_NAME: layers,
            sparse_attention.KL_NAME: layers,
            sparse_attention.BWD_NAME: layers}


def test_keye_int8_control_is_far_from_the_reference(keye):
    cfg, ref, _, w, batch = keye
    _, g = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg))(w)
    _, g8 = jax.jit(lambda w: ref.loss_and_grad(w, batch, cfg, "int8"))(w)
    num = sum(float(jnp.sum(jnp.square(g8[k] - g[k]))) for k in g)
    den = sum(float(jnp.sum(jnp.square(g[k]))) for k in g)
    assert (num / den) ** 0.5 > 0.2


def _keye_uncut_layer(keye):
    """One layer at the tiny widths, UNCUT: all 16 experts of a 16-wide
    router, 8 query heads on 4 kv heads, the indexer as it is."""
    cfg, ref, _, _, _ = keye
    whole = dict(cfg, num_experts=16, num_local_experts=16,
                 num_attention_heads=8, num_key_value_heads=4,
                 num_hidden_layers=1, first_expert=0)
    w = ref.init_weights(whole, jax.random.PRNGKey(7))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 32, cfg["hidden_size"]))
    return whole, ref.layer_weights(w, 0), x


def test_keye_head_shares_add_up_to_the_uncut_layer(keye):
    """Four shares of 2 query heads and their kv head, the indexer WHOLE in
    every one (each chip computes every row's choice alike): their parts
    of the attention result add up to the uncut layer's; the choice, which
    every share computes, is counted once — it is the same in all four."""
    _, ref, _, _, _ = keye
    whole, lw, h = _keye_uncut_layer(keye)
    hd = whole["head_dim"]
    want, _ = ref.attention_part(h, lw, whole)
    total = jnp.zeros_like(want)
    for share in range(4):
        part = dict(whole, num_attention_heads=2, num_key_value_heads=1)
        qs = slice(share * 2 * hd, (share + 1) * 2 * hd)
        ks = slice(share * hd, (share + 1) * hd)
        cut = dict(lw, w_q=lw["w_q"][:, qs], w_k=lw["w_k"][:, ks],
                   w_v=lw["w_v"][:, ks], w_o=lw["w_o"][qs])
        total += ref.attention_part(h, cut, part)[0]
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=1e-4)


#: configurations whose layer holds SiLU experts behind a post-attention
#: router, and the key that names the router's width in each
SILU_EXPERT_CONFIGS = {KEYE: "num_local_experts",
                       "sdar-30b-a3b-chat": "num_router_outputs"}


@pytest.mark.parametrize("config", sorted(SILU_EXPERT_CONFIGS))
@pytest.mark.parametrize("who", ["reference", "program"])
def test_silu_expert_shares_add_up_to_the_uncut_layer(config, who):
    """Sixteen shares of 1 SiLU expert each, the router whole in every
    one, add up to the uncut reference's expert layer (16 experts of a
    16-wide router at the tiny widths)."""
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         config + ".json"))
    ref = harness.load_module("reference", config)
    whole = dict(cfg, **cfg["tiny"])
    whole.update({"num_experts": 16, SILU_EXPERT_CONFIGS[config]: 16,
                  "num_hidden_layers": 1, "first_expert": 0})
    lw = ref.layer_weights(ref.init_weights(whole, jax.random.PRNGKey(7)), 0)
    u = jax.random.normal(jax.random.PRNGKey(8), (64, whole["hidden_size"]))
    idx, p = ref.route(u, lw["w_r"], whole)
    want = ref.experts_part(u, idx, p, lw, whole)
    total = jnp.zeros_like(want)
    for first in range(16):
        cut = dict(lw, w_gate_up=lw["w_gate_up"][first:first + 1],
                   w_down=lw["w_down"][first:first + 1])
        if who == "reference":
            part = dict(whole, num_experts=1, first_expert=first)
            total += ref.experts_part(u, idx, p, cut, part)
        else:
            m, counters = moe.held_experts_ffn(
                u, idx, p, cut["w_gate_up"], cut["w_down"], first, tm=16,
                activation="silu")
            assert float(counters["rows_dropped"]) == 0.0
            total += m
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)


def test_expert_activation_is_named_and_relu_by_default():
    u, w_gate_up, w_down, p = _layer_inputs()
    idx = jax.random.randint(jax.random.PRNGKey(5), (u.shape[0], 3), 0, 16,
                             jnp.int32)
    relu = moe.held_experts_ffn(u, idx, p, w_gate_up, w_down, 4, tm=16)[0]
    named = moe.held_experts_ffn(u, idx, p, w_gate_up, w_down, 4, tm=16,
                                 activation="relu")[0]
    silu = moe.held_experts_ffn(u, idx, p, w_gate_up, w_down, 4, tm=16,
                                activation="silu")[0]
    np.testing.assert_array_equal(np.asarray(relu), np.asarray(named))
    assert float(jnp.abs(silu - relu).max()) > 1e-3
    with pytest.raises(KeyError):
        moe.held_experts_ffn(u, idx, p, w_gate_up, w_down, 4, tm=16,
                             activation="gelu")


def test_smallthinker_tree_and_counters_are_what_they_were(tiny):
    """The new attributes default to what SmallThinker runs: its parameter
    tree has no indexer or q/k norm, its extra state no selection
    counter."""
    cfg, _, fam, _, _ = tiny
    params, extra = fam.train_parts(cfg, {"remat": True})[2]
    assert sorted(params["layer_0"]) == [
        "experts_down", "experts_gate_up", "key", "norm_attn", "norm_moe",
        "out", "query", "router", "value"]
    assert sorted(extra["counters"]) == sorted(
        sparse_decoder.COUNTERS + ("steps",))
    model = fam.build_model(cfg, {"remat": True})
    assert (model.router_input, model.expert_activation, model.qk_norm,
            model.selects()) == ("attn_norm", "relu", False, False)


def test_trainer_mirrors_selection_counters_and_the_readers_read_them(keye):
    import optax
    from edl_tpu.runtime.mesh import make_mesh
    from edl_tpu.runtime.trainer import ElasticTrainer
    cfg, _, fam, w, batch = keye
    loss_fn, has_aux, _ = fam.train_parts(cfg, {"remat": False})
    params, extra = fam.to_program(w, cfg)
    trainer = ElasticTrainer(loss_fn, params, optax.sgd(1e-3),
                             total_batch_size=2, extra_state=extra,
                             has_aux=has_aux,
                             mesh=make_mesh(devices=jax.devices()[:1]))
    try:
        for _ in range(2):
            trainer.train_step(trainer.place_batch(batch))
    finally:
        trainer.close()
    got = kernel_readers.model_counters()
    n, topk = cfg["num_hidden_layers"], cfg["sa_config"]["topk"]
    kept = 2 * sum(min(t + 1, topk) for t in range(32))
    assert got["steps"] == [2.0]
    assert all(0 <= x - 2.0 * kept <= 64 for x in got["pairs_kept"])
    assert len(got["rows_off_count"]) == n and len(got["index_loss"]) == n
    view = {"traffic": {"seq_len": 32, "batch_per_chip": 2},
            "cell": {"chips": 1}}
    pct = harness.load_module("metrics", "dsa_pairs_kept_pct").read(view)
    assert pct == pytest.approx(100.0 * kept / (2 * 32 * 33 / 2), rel=0.05)
    assert pct == 100.0 * sum(got["pairs_kept"]) / (2 * n * 2 * 32 * 33 / 2)
    off = harness.load_module("metrics", "dsa_rows_off_count").read(view)
    assert off == pytest.approx(sum(got["rows_off_count"]) / 2.0)


def test_selection_counter_readers_return_none_without_counters(monkeypatch):
    """On the parent commit there is no such counter: nothing read,
    nothing raised."""
    for name in ("dsa_pairs_kept_pct", "dsa_rows_off_count"):
        mod = harness.load_module("metrics", name)
        monkeypatch.setattr(mod, "model_counters", lambda: {})
        assert mod.read({"traffic": {"seq_len": 32, "batch_per_chip": 2},
                         "cell": {"chips": 1}}) is None


def _keye_view(ops, steps=10):
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         KEYE + ".json"))
    job = harness.load_json(os.path.join(REPO, "benchmark", "traffic",
                                         "tokens-16384.json"))
    return {"trace": {"ops": ops}, "counters": {"traced_steps": steps},
            "config": cfg, "traffic": job,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}}


@pytest.mark.parametrize("kernel", ["dsa_index_tau", "dsa_fwd",
                                    "dsa_index_kl", "dsa_bwd", "moe_gmm",
                                    "moe_tgmm"])
def test_keye_kernel_readers_on_a_recorded_view(kernel):
    ops = [["fusion", 3.0], [kernel, 0.2], ["checkpoint_%s_" % kernel, 0.1],
           ["while", 0.5]]
    device_ms = harness.load_module("metrics", kernel + "_device_ms")
    roofline = harness.load_module("metrics", kernel + "_roofline_pct")
    view = _keye_view(ops)
    assert device_ms.read(view) == pytest.approx(30.0)
    assert 0.0 < roofline.read(view) < 100.0
    empty = _keye_view([["fusion", 3.0]])
    assert device_ms.read(empty) is None and roofline.read(empty) is None


def test_keye_flops_count_the_kept_pairs_only():
    fam = harness.load_module("program", "dsa_decoder")
    view = _keye_view([])
    cfg, job = view["config"], view["traffic"]
    assert fam.kept_pairs(16384, 2048) == 2048 * 2049 / 2 + 14336 * 2048
    assert fam.kept_pairs(16384, 2048) / fam.band_pairs(16384) == \
        pytest.approx(0.2344, abs=1e-4)
    assert fam.expected_expert_rows(cfg, 16384) == 8192
    total = fam.train_flops(cfg, job, 1)
    assert total == pytest.approx(10.45e12, rel=0.02)
    # a masked kernel that computes every causal pair cannot read more
    # than kept / causal of its roofline on operations
    costs = fam.kernel_costs(cfg, job, 1)
    assert set(costs) == {"dsa_index_tau", "dsa_fwd", "dsa_index_kl",
                          "dsa_bwd", "moe_gmm", "moe_tgmm"}
    executed_fwd = 2 * 4 * fam.band_pairs(16384) * (8 * 4 * 128 + 16 * 128)
    assert costs["dsa_fwd"][0] / executed_fwd < 0.2344


def test_keye_configuration_holds_the_published_widths():
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         KEYE + ".json"))
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"],
            cfg["num_local_experts"], cfg["num_experts_per_tok"],
            cfg["sa_config"]["topk"], cfg["rope_theta"]) == (
                2048, 128, 768, 128, 8, 2048, 10000000)
    assert sorted(cfg["reduced"]) == sorted(cfg["published"]) == sorted(
        cfg["reduced_why"])
    ref = harness.load_module("reference", KEYE)
    n = sum(int(np.prod(shape)) for shape, _ in
            ref.weight_shapes(cfg).values())
    assert n == pytest.approx(257.8e6, rel=2e-3)
    src = open(os.path.join(REPO, "benchmark", "reference",
                            KEYE + ".py")).read()
    assert "edl_tpu" not in src.replace("`edl_tpu/`", "")
