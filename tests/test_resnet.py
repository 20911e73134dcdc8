"""ResNet family tests: shapes, vd structure, training step with BN aux
state through ElasticTrainer on the dp mesh."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from edl_tpu.models import resnet
from edl_tpu.runtime.trainer import ElasticTrainer


def test_resnet50_vd_forward_shape():
    model = resnet.ResNet(depth=50, num_classes=10, vd=True,
                          dtype=jnp.float32)
    x = jnp.zeros((2, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32
    # vd deep stem present
    assert "stem1" in variables["params"]
    assert "stem3" in variables["params"]
    # vd downsample shortcut in first stride-2 block
    assert "downsample" in variables["params"]["stage1_block0"]


def test_space_to_depth_stem_exact():
    """The s2d stem is a pure compute-layout change: identical param tree
    and bit-nearly-identical outputs for the SAME parameters."""
    m0 = resnet.ResNet(depth=50, num_classes=10, vd=True, dtype=jnp.float32)
    m1 = resnet.ResNet(depth=50, num_classes=10, vd=True, dtype=jnp.float32,
                       space_to_depth=True)
    x = jnp.asarray(np.random.RandomState(0)
                    .randn(2, 64, 64, 3).astype(np.float32))
    v = m0.init(jax.random.PRNGKey(0), x, train=False)
    v1 = m1.init(jax.random.PRNGKey(0), x, train=False)
    assert (jax.tree_util.tree_structure(v)
            == jax.tree_util.tree_structure(v1))
    y0 = m0.apply(v, x, train=False)
    y1 = m1.apply(v, x, train=False)  # the s2d model with m0's params
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=1e-5, atol=1e-5)
    # gradients agree too (the scatter is differentiated through)
    def loss(variables, model):
        return (model.apply(variables, x, train=False) ** 2).mean()
    g0 = jax.grad(loss)(v, m0)["params"]["stem1"]["kernel"]
    g1 = jax.grad(loss)(v, m1)["params"]["stem1"]["kernel"]
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("depth", [18, 50])
def test_resnet_depths(depth):
    model = resnet.ResNet(depth=depth, num_classes=7, vd=False,
                          dtype=jnp.float32)
    x = jnp.zeros((1, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    assert model.apply(variables, x, train=False).shape == (1, 7)


@pytest.mark.parametrize("depth", [11, 16])
def test_vgg_forward_and_train(depth, tmp_path):
    from edl_tpu.models import vgg

    model, params, loss_fn = vgg.create_model_and_loss(
        depth=depth, num_classes=4, image_size=32, fc_dim=64,
        dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    logits = model.apply({"params": params}, x, train=False)
    assert logits.shape == (2, 4) and logits.dtype == jnp.float32
    # block structure per the reference spec table
    n_convs = sum(vgg.VGG_SPECS[depth])
    conv_names = [k for k in jax.tree_util.tree_flatten_with_path(
        params)[0] for k in [jax.tree_util.keystr(k[0])]
        if "conv" in k and "kernel" in k]
    assert len(conv_names) == n_convs

    trainer = ElasticTrainer(
        loss_fn, params, optax.sgd(0.01, momentum=0.9),
        total_batch_size=16, checkpoint_dir=str(tmp_path / "ckpt"))
    batch = resnet.synthetic_image_batch(16, image_size=32, num_classes=4,
                                         seed=0)
    losses = [float(trainer.train_step(batch)) for _ in range(6)]
    assert losses[-1] < losses[0]


def test_resnet_trains_with_bn_aux(tmp_path):
    model, params, extra, loss_fn = resnet.create_model_and_loss(
        depth=18, num_classes=4, vd=True, image_size=32,
        dtype=jnp.float32)
    trainer = ElasticTrainer(
        loss_fn, params, optax.sgd(0.05, momentum=0.9),
        total_batch_size=16, checkpoint_dir=str(tmp_path / "ckpt"),
        extra_state=extra, has_aux=True)

    def batch(seed):
        b = resnet.synthetic_image_batch(16, image_size=32, num_classes=4,
                                         seed=seed % 3)  # few distinct
        return b

    stats_before = jax.device_get(
        trainer.extra_state["batch_stats"])
    losses = [float(trainer.train_step(batch(i))) for i in range(8)]
    assert losses[-1] < losses[0]
    stats_after = jax.device_get(trainer.extra_state["batch_stats"])
    # BN running stats actually updated through the aux path
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).sum()),
        stats_before, stats_after)
    assert sum(jax.tree_util.tree_leaves(diffs)) > 0

    # checkpoint roundtrip includes BN stats
    trainer.begin_epoch(0)
    trainer.end_epoch(save=True)
    model2, params2, extra2, loss_fn2 = resnet.create_model_and_loss(
        depth=18, num_classes=4, vd=True, image_size=32, dtype=jnp.float32)
    trainer2 = ElasticTrainer(
        loss_fn2, params2, optax.sgd(0.05, momentum=0.9),
        total_batch_size=16, checkpoint_dir=str(tmp_path / "ckpt"),
        extra_state=extra2, has_aux=True)
    assert trainer2.resume()
    restored = jax.device_get(trainer2.extra_state["batch_stats"])
    chex_like = jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                np.asarray(b), rtol=1e-5),
        stats_after, restored)
    del chex_like


def test_resnext_grouped_bottleneck():
    """ResNeXt: grouped 3x3 with base_width-scaled inner channels; the
    32x16d config widens conv1/conv2 to 512 channels per stage-0 block
    while the grouped conv keeps params at width^2*9/groups."""
    import jax

    from edl_tpu.models import resnet

    model = resnet.ResNeXt(depth=50, groups=4, base_width=16,
                           num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    # inner width: 64 * 16/64 * 4 = 64 for stage-0 (filters=64)
    k = variables["params"]["stage0_block0"]["conv2"]["kernel"]
    # grouped conv kernel: [3, 3, width/groups, width]
    assert k.shape == (3, 3, 64 // 4, 64)
    # vanilla (non-vd) stem by default
    assert "stem" in variables["params"]
    # trains one step
    _, params, extra, loss_fn = resnet.create_model_and_loss(
        depth=50, num_classes=10, vd=False, image_size=32,
        dtype=jnp.float32, groups=4, base_width=16)
    import optax

    from edl_tpu.runtime.trainer import make_train_state, make_train_step
    tx = optax.sgd(0.01)
    state = make_train_state(params, tx, extra)
    step = jax.jit(make_train_step(loss_fn, tx, has_aux=True))
    batch = {"image": np.zeros((4, 32, 32, 3), np.float32),
             "label": np.zeros((4,), np.int32)}
    state, loss = step(state, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(loss))


def test_resnext_rejects_basicblock_groups():
    from edl_tpu.models import resnet

    model = resnet.ResNet(depth=18, groups=2, num_classes=10,
                          dtype=jnp.float32)
    with pytest.raises(ValueError, match="bottleneck"):
        model.init(jax.random.PRNGKey(0),
                   jnp.zeros((1, 32, 32, 3)), train=False)


@pytest.mark.parametrize("dp", [2, 4])
def test_batch_statistics_are_the_global_batch_s_under_dp(dp):
    """One training-mode pass over a batch of 16 sharded over `dp` devices
    (as the elastic cell's is) gives the logits, the loss and the new
    running statistics of the same pass on one device: BatchNorm's mean
    and variance are the whole batch's, not a shard's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    model, params, extra, loss_fn = resnet.create_model_and_loss(
        depth=18, num_classes=10, image_size=32, dtype=jnp.float32)
    # He-scaled kernels at init leave the last BN of a block at gain 0:
    # lift every gain so that each layer's statistics reach the logits
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf + 0.5 if path[-1].key == "scale" else leaf,
        params)
    batch = {k: jnp.asarray(v) for k, v in
             resnet.synthetic_image_batch(16, 32, 10, seed=3).items()}

    def one_pass(params, extra, batch):
        logits, _ = model.apply(
            {"params": params, **extra}, batch["image"], train=True,
            mutable=["batch_stats"])
        loss, new_extra = loss_fn(params, extra, batch, None)
        return logits, loss, new_extra["batch_stats"]

    want = jax.jit(one_pass)(params, extra, batch)
    mesh = Mesh(np.array(jax.devices()[:dp]), ("dp",))
    whole = NamedSharding(mesh, P())
    got = jax.jit(one_pass, in_shardings=(
        whole, whole, NamedSharding(mesh, P("dp"))))(params, extra, batch)
    assert len(got[0].sharding.device_set) == dp
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    # a shard's own statistics would be another answer
    shard = jax.jit(one_pass)(params, extra,
                              {k: v[:16 // dp] for k, v in batch.items()})
    assert float(jnp.abs(shard[2]["stem_bn1"]["var"]
                         - want[2]["stem_bn1"]["var"]).max()) > 1e-3


#: sha256 over the sorted (path, shape, dtype) of `params` and
#: `batch_stats`, recorded on the commit before PR 52 (ac8177a), which
#: removed the second BatchNorm: a renamed or reshaped variable would break
#: every saved ResNet, and no benchmark cell (fresh weights each run) would
#: show it
TREE_AT_PR_51 = {
    "18": (
        dict(depth=18, vd=False),
        "65ab0234581a51e39d7bf2e4c2e3484bee10737fe856148170600d2d56e0180c"),
    "50": (
        dict(depth=50, vd=False),
        "f3f3f4e3c3b41fab83fb1e6698bab0e1484bccbec6be8cb8c4af7610b8145eb3"),
    "50vd": (
        dict(depth=50, vd=True, space_to_depth=True),
        "e65cf5139161ed9fab7bc2a05ff306ebdc200d64b1ad28c37b430cbd2d24ff1d"),
    # the other depths of DEPTH_CONFIGS, and the grouped bottleneck
    "34": (
        dict(depth=34, vd=False),
        "01a06d4a1da7fac42d053d5818b5d7328354b601785adc63caef9afd30dee290"),
    "101vd": (
        dict(depth=101, vd=True),
        "c884a7a37eab18ef061491cec2e689678cd3cce66228af009fdf6b18703eea9f"),
    "152vd": (
        dict(depth=152, vd=True),
        "c2298db5ad0b7018fcfccc4323fccfd7cf49a5c15f6bb940d601c0ce087af94c"),
    "101-32x16d": (
        dict(depth=101, vd=False, groups=32, base_width=16),
        "15fa9eebec1c98d7338c5d0039ccdc5e5af37180ad35b87b77c77f5c278160d5"),
}


@pytest.mark.parametrize("name", sorted(TREE_AT_PR_51))
def test_parameter_tree_is_what_the_parent_s_checkpoints_hold(name):
    kw, want = TREE_AT_PR_51[name]
    params, extra = jax.eval_shape(
        lambda: resnet.create_model_and_loss(image_size=64, **kw)[1:3])
    leaves = sorted(
        (jax.tree_util.keystr(path), tuple(leaf.shape), str(leaf.dtype))
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            {"params": params, **extra})[0])
    assert any("batch_stats" in path for path, _, _ in leaves)
    assert hashlib.sha256(repr(leaves).encode()).hexdigest() == want
