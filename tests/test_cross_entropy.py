"""``ops/cross_entropy.py:token_cross_entropy`` and the losses that call it.

(a) value and gradient against optax's integer-label cross-entropy in
    float32: one and two rows of sequences, a stack of passes, T odd and
    even, V off the 128 lanes, logits of +-80, a target at either end of V;
(b) the models' losses — every row of the logits against targets shifted by
    one, the last row dropped from the RESULT — against the form they had,
    ``logits[:, :-1]`` against ``ids[:, 1:]``: next-token, exit-expectation
    on a looped model, block-diffusion with its weights (no shift), and
    ``models/gpt.py``'s; the last row's logit cotangent is exactly 0;
(c) what the traced gradient holds: no gather, slice or dynamic_slice of an
    array of the logits' shape.
CPU, tiny sizes."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from jaxpr_kernels import equations_outside_kernels
from edl_tpu.models import gpt, sparse_decoder
from edl_tpu.ops.cross_entropy import next_ids, token_cross_entropy

VOCAB = 97      # off the lanes, and no other array of these models is as wide


# -- (a) the function against optax -------------------------------------------

def _case(shape, seed=0, scale=4.0):
    k_l, k_t, k_w = jax.random.split(jax.random.PRNGKey(seed), 3)
    logits = scale * jax.random.normal(k_l, shape, jnp.float32)
    targets = jax.random.randint(k_t, shape[:-1], 0, shape[-1])
    weight = jax.random.uniform(k_w, shape[:-1], jnp.float32)
    return logits, targets, weight


def _both(logits, targets, weight):
    """((ce, d logits) of the function, the same of optax) under one
    cotangent, ``weight``."""
    def value_and_cotangent(fn):
        ce, vjp = jax.vjp(lambda lg: fn(lg, targets), logits)
        return ce, vjp(weight)[0]

    return [jax.jit(value_and_cotangent, static_argnums=0)(fn) for fn in (
        token_cross_entropy, optax.softmax_cross_entropy_with_integer_labels)]


@pytest.mark.parametrize("shape", [
    (1, 7, 131), (1, 8, 131), (2, 7, 200), (2, 8, 257),
    (4, 1, 7, 131), (4, 1, 8, 131)], ids=str)
def test_value_and_gradient_are_optax_s(shape):
    (ce, grad), (want_ce, want_grad) = _both(*_case(shape, seed=len(shape)))
    assert ce.shape == shape[:-1] and ce.dtype == jnp.float32
    assert grad.shape == shape and grad.dtype == jnp.float32
    np.testing.assert_allclose(ce, want_ce, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("sign", [80.0, -80.0])
def test_logits_of_80_do_not_overflow(sign):
    logits, targets, weight = _case((2, 7, 131), seed=5, scale=1.0)
    # one entry a row stands 80 above (below) the rest; exp(80) has no
    # float32, the row's maximum is taken out first
    logits = logits.at[..., 3].add(sign)
    (ce, grad), (want_ce, want_grad) = _both(logits, targets, weight)
    assert np.isfinite(ce).all() and np.isfinite(grad).all()
    np.testing.assert_allclose(ce, want_ce, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("target", [0, 130])
def test_a_target_at_either_end_of_the_vocabulary(target):
    logits, _, weight = _case((1, 8, 131), seed=6)
    targets = jnp.full((1, 8), target, jnp.int32)
    (ce, grad), (want_ce, want_grad) = _both(logits, targets, weight)
    np.testing.assert_allclose(ce, want_ce, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-5, atol=1e-7)
    # softmax less one-hot: the target's column alone is negative
    assert (np.asarray(grad)[..., target] < 0).all()
    assert (np.delete(np.asarray(grad), target, axis=-1) > 0).all()


def test_next_ids_shifts_by_one_and_keeps_the_ids_in_range():
    ids = jnp.arange(10, dtype=jnp.int32).reshape(2, 5) + 3
    got = np.asarray(next_ids(ids))
    np.testing.assert_array_equal(got[:, :-1], np.asarray(ids)[:, 1:])
    np.testing.assert_array_equal(got[:, -1], np.asarray(ids)[:, 0])


# -- (b) the models' losses against the form they had -------------------------

def _decoder(**kw):
    """One dense layer: the losses are what is under test."""
    return sparse_decoder.SparseDecoder(
        vocab_size=VOCAB, d_model=16, num_layers=1, heads=2, kv_heads=1,
        head_dim=8, num_experts=0, experts_held=0, first_expert=0,
        experts_per_token=0, expert_width=0, dense_width=16,
        rope_layout=(1,), window_layout=(0,), window=0, rope_theta=1e4,
        dtype=jnp.float32, use_flash=False, **kw)


def _sliced(logits, ids):
    """The cross-entropy the losses called: a slice of the logits, optax's
    gather."""
    return optax.softmax_cross_entropy_with_integer_labels(
        logits[..., :-1, :], jnp.broadcast_to(
            ids[:, 1:], logits.shape[:-2] + (ids.shape[1] - 1,)))


def _next_token_was(model, params, batch):
    logits, _ = model.apply({"params": params}, batch["input_ids"])
    return _sliced(logits, batch["input_ids"]).mean()


def _exit_expectation_was(model, params, batch):
    ids = batch["input_ids"]
    logits, scores, _ = model.apply({"params": params}, ids)
    ce = _sliced(logits, ids)
    scores = scores[:, :, :-1]
    stay = jnp.cumsum(jax.nn.log_sigmoid(-scores), axis=0)
    log_p = jnp.concatenate([
        jax.nn.log_sigmoid(scores[:1]),
        jax.nn.log_sigmoid(scores[1:]) + stay[:-1], stay[-1:]], axis=0)
    return jnp.mean(jnp.sum(
        jnp.exp(log_p) * (ce + model.exit_entropy_weight * log_p), axis=0))


def _block_diffusion_was(model, params, batch):
    ids, t_len = batch["input_ids"], batch["input_ids"].shape[1]
    logits, _ = model.apply(
        {"params": params}, jnp.concatenate([batch["noisy_ids"], ids], 1),
        jnp.tile(jnp.arange(t_len), 2), (model.block_length, t_len))
    return jnp.mean(batch["loss_weight"]
                    * optax.softmax_cross_entropy_with_integer_labels(
                        logits, ids))


def _gpt_was(model, params, batch):
    logits = model.apply({"params": params}, batch["input_ids"])
    return _sliced(logits, batch["input_ids"]).mean()


#: a model's loss: the module that calls the function, the parameters, the
#: batch, the logits' shape, the loss as it is and as it was
Loss = collections.namedtuple("Loss", "module params batch shape now was")


def _ids(t_len, rows=2):
    return jax.random.randint(jax.random.PRNGKey(1), (rows, t_len), 0, VOCAB)


def _shaken(params):
    """The parameters off their initial values, where a model's rows would
    all have the same logits."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        p + 0.3 * jax.random.normal(k, p.shape, p.dtype)
        for p, k in zip(leaves, keys)])


def _sparse(was, t_len=12, **kw):
    model = _decoder(**kw)
    _, params, extra, loss_fn = sparse_decoder.create_model_and_loss(model)
    ids = _ids(t_len)
    batch = {"input_ids": ids}
    if model.block_length:
        noisy, weight = sparse_decoder.block_diffusion_noise(
            ids, jax.random.PRNGKey(3), model.block_length, VOCAB - 1)
        batch = dict(batch, noisy_ids=noisy, loss_weight=weight)
    passes = (model.loop_steps,) if model.loop_steps > 1 else ()
    return Loss(sparse_decoder, _shaken(params), batch,
                passes + ids.shape + (VOCAB,),
                lambda p: loss_fn(p, extra, batch, None)[0],
                lambda p: was(model, p, batch))


def _gpt(t_len=12):
    model, params, loss_fn = gpt.create_model_and_loss(
        vocab_size=VOCAB, num_layers=1, d_model=16, num_heads=2, mlp_dim=32,
        max_len=16, dtype=jnp.float32)
    batch = {"input_ids": _ids(t_len)}
    return Loss(gpt, _shaken(params), batch,
                batch["input_ids"].shape + (VOCAB,),
                lambda p: loss_fn(p, batch, None),
                lambda p: _gpt_was(model, p, batch))


LOSSES = {
    "next_token": lambda: _sparse(_next_token_was),
    "next_token_odd": lambda: _sparse(_next_token_was, t_len=11),
    "exit_expectation": lambda: _sparse(_exit_expectation_was, loop_steps=3),
    "block_diffusion": lambda: _sparse(_block_diffusion_was, block_length=4),
    "gpt": _gpt,
}


@pytest.fixture(scope="module", params=sorted(LOSSES))
def loss(request):
    return LOSSES[request.param]()


@pytest.fixture(scope="module")
def both(loss):
    """((loss, gradient) as it is, the same as it was)."""
    return tuple(jax.jit(jax.value_and_grad(fn))(loss.params)
                 for fn in (loss.now, loss.was))


def test_loss_is_the_sliced_form_s(both):
    (got, _), (want, _) = both
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_every_parameter_s_gradient_is_the_sliced_form_s(both):
    (_, got), (_, want) = both
    largest = max(float(np.abs(w).max())
                  for w in jax.tree_util.tree_leaves(want))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        # a leaf against its own size; one whose gradient is rounding alone
        # (a key's bias under a softmax) against the largest leaf's
        scale = max(float(np.abs(w).max()), 1e-2 * largest)
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_row_with_no_target_gets_no_cotangent(loss, monkeypatch):
    """The logits' own cotangent, read through a zero added to them on their
    way into the function: the last row's is exactly 0 where the loss
    predicts the next token, and no other row's is."""
    box = {}
    monkeypatch.setattr(
        loss.module, "token_cross_entropy",
        lambda logits, targets: token_cross_entropy(
            logits + box["probe"], targets))

    def probed(probe):
        box["probe"] = probe
        return loss.now(loss.params)

    cot = np.asarray(jax.jit(jax.grad(probed))(
        jnp.zeros(loss.shape, jnp.float32)))
    if "loss_weight" in loss.batch:     # no shift: a row counts by its weight
        np.testing.assert_array_equal(
            np.abs(cot).max(axis=-1) > 0,
            np.asarray(loss.batch["loss_weight"]) > 0)
        return
    assert (cot[..., -1, :] == 0.0).all()
    assert (np.abs(cot[..., :-1, :]).max(axis=-1) > 0).all()


# -- (c) what the traced gradient holds ---------------------------------------

def test_nothing_slices_or_gathers_the_logits(loss):
    moved = ("gather", "slice", "dynamic_slice")

    def found(fn):
        return sorted(
            eqn.primitive.name for eqn in equations_outside_kernels(
                jax.make_jaxpr(jax.grad(fn))(loss.params).jaxpr)
            if eqn.primitive.name in moved
            and any(getattr(v.aval, "shape", None) == loss.shape
                    for v in eqn.invars))

    assert found(loss.now) == []
    assert found(loss.was)              # the reading finds what it looks for
