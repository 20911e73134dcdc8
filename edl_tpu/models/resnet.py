"""ResNet / ResNet-vd family in flax.linen, bf16-first for the MXU.

Reference parity: the models zoo used by the collective example
(example/collective/resnet50/models/resnet.py + resnet_vd variants; the
headline benchmark model is ResNet50_vd — README.md:83). Built TPU-first:
NHWC layout, bfloat16 compute with float32 params/BN statistics, and
cross-replica BatchNorm for free via sharded-batch jit (XLA inserts the
mean/var all-reduce from the sharding annotations).

The vd tweaks vs vanilla ResNet:
- deep stem: three 3x3 convs (32, 32, 64) instead of one 7x7;
- stride-2 moved off the 1x1 bottleneck conv onto the 3x3;
- downsampling shortcuts use avg_pool then stride-1 1x1 conv.
"""

from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax


def _make_norm(train, dtype):
    """The BN constructor shared by stems and blocks: statistics over the
    whole (global) batch in training, float32 parameters."""
    return partial(nn.BatchNorm, use_running_average=not train,
                   momentum=0.9, epsilon=1e-5, dtype=dtype,
                   param_dtype=jnp.float32)

DEPTH_CONFIGS = {
    18: ((2, 2, 2, 2), False),
    34: ((3, 4, 6, 3), False),
    50: ((3, 4, 6, 3), True),
    101: ((3, 4, 23, 3), True),
    152: ((3, 8, 36, 3), True),
}


def space_to_depth(x, block=2):
    """[B, H, W, C] -> [B, H/b, W/b, b*b*C] (channel = (di*b+dj)*C + c)."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h // block, w // block, block * block * c)


class _S2DStemConv(nn.Module):
    """The vd stem's 3x3/stride-2 conv on 3 channels, computed on the
    space-to-depth input instead (MLPerf-style TPU optimization).

    A 3-channel 224x224 conv runs the MXU at K=27 contraction depth —
    mostly padding. On the 2x2 space-to-depth image it becomes a DENSE
    stride-1 2x2 conv with K=48: the trained parameter stays the original
    [3,3,3,F] kernel (checkpoint-compatible either way); it is scattered
    into the equivalent [2,2,4*3,F] kernel inside the step, which is exact
    — every (tap, packed-channel) pair maps to one original (u,v,c) weight
    or to zero where the 4x4 region exceeds the 3x3 window.
    """
    features: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, y):
        # y: [B, H/2, W/2, 12] space-to-depth image
        in_c = 3
        w = self.param("kernel", nn.initializers.lecun_normal(),
                       (3, 3, in_c, self.features), jnp.float32)
        w2 = jnp.zeros((2, 2, 4 * in_c, self.features), w.dtype)
        for dp in range(2):
            for dq in range(2):
                for di in range(2):
                    for dj in range(2):
                        u, v = 2 * dp + di, 2 * dq + dj
                        if u < 3 and v < 3:
                            ch = (di * 2 + dj) * in_c
                            w2 = w2.at[dp, dq, ch:ch + in_c].set(w[u, v])
        return jax.lax.conv_general_dilated(
            y.astype(self.dtype), w2.astype(self.dtype),
            window_strides=(1, 1), padding=((0, 1), (0, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


class BottleneckBlock(nn.Module):
    filters: int
    stride: int
    vd: bool
    dtype: Any = jnp.bfloat16
    # ResNeXt: cardinality (grouped 3x3) and per-group base width; the
    # inner width is filters * base_width/64 * groups (groups=1,
    # base_width=64 = plain ResNet)
    groups: int = 1
    base_width: int = 64

    @nn.compact
    def __call__(self, x, train):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = _make_norm(train, self.dtype)
        width = int(self.filters * self.base_width / 64.0) * self.groups
        residual = x
        y = conv(width, (1, 1), name="conv1")(x)
        y = nn.relu(norm(name="bn1")(y))
        y = conv(width, (3, 3), strides=(self.stride, self.stride),
                 feature_group_count=self.groups, name="conv2")(y)
        y = nn.relu(norm(name="bn2")(y))
        y = conv(self.filters * 4, (1, 1), name="conv3")(y)
        y = norm(name="bn3", scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            if self.vd and self.stride > 1:
                residual = nn.avg_pool(residual, (2, 2), strides=(2, 2))
                residual = conv(self.filters * 4, (1, 1),
                                name="downsample")(residual)
            else:
                residual = conv(self.filters * 4, (1, 1),
                                strides=(self.stride, self.stride),
                                name="downsample")(residual)
            residual = norm(name="downsample_bn")(residual)
        return nn.relu(y + residual)


class BasicBlock(nn.Module):
    filters: int
    stride: int
    vd: bool
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = _make_norm(train, self.dtype)
        residual = x
        y = conv(self.filters, (3, 3), strides=(self.stride, self.stride),
                 name="conv1")(x)
        y = nn.relu(norm(name="bn1")(y))
        y = conv(self.filters, (3, 3), name="conv2")(y)
        y = norm(name="bn2", scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            if self.vd and self.stride > 1:
                residual = nn.avg_pool(residual, (2, 2), strides=(2, 2))
                residual = conv(self.filters, (1, 1),
                                name="downsample")(residual)
            else:
                residual = conv(self.filters, (1, 1),
                                strides=(self.stride, self.stride),
                                name="downsample")(residual)
            residual = norm(name="downsample_bn")(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    depth: int = 50
    num_classes: int = 1000
    vd: bool = True
    dtype: Any = jnp.bfloat16
    stage_filters: Sequence[int] = (64, 128, 256, 512)
    # activation recompute per residual block: save only block boundaries,
    # recompute conv/BN internals in backward (reference knob:
    # train_with_fleet.py:322-325 fleet recompute checkpointing)
    remat: bool = False
    # MLPerf-style space-to-depth stem: exact, checkpoint-compatible
    # re-layout of the thin first conv (vd stems only)
    space_to_depth: bool = False
    # ResNeXt cardinality/width (bottleneck depths only); the reference's
    # distill teacher config names ResNeXt101_32x16d_wsl (BASELINE.md)
    groups: int = 1
    base_width: int = 64

    @nn.compact
    def __call__(self, x, train=False):
        blocks_per_stage, bottleneck = DEPTH_CONFIGS[self.depth]
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = _make_norm(train, self.dtype)
        # device-side scopes (obs/devtime.py): the stem, each stage with
        # its batch norms (XLA fuses them into the convolutions), the head
        with jax.named_scope("conv.stem"):
            x = x.astype(self.dtype)
            if self.vd:
                if self.space_to_depth:
                    x = _S2DStemConv(32, self.dtype, name="stem1")(
                        space_to_depth(x, 2))
                else:
                    x = conv(32, (3, 3), strides=(2, 2), name="stem1")(x)
                x = nn.relu(norm(name="stem_bn1")(x))
                x = conv(32, (3, 3), name="stem2")(x)
                x = nn.relu(norm(name="stem_bn2")(x))
                x = conv(64, (3, 3), name="stem3")(x)
                x = nn.relu(norm(name="stem_bn3")(x))
            else:
                x = conv(64, (7, 7), strides=(2, 2), name="stem")(x)
                x = nn.relu(norm(name="stem_bn")(x))
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")

        block_cls = BottleneckBlock if bottleneck else BasicBlock
        if self.remat:
            # train is a static python bool → static_argnums (0 = self)
            block_cls = nn.remat(block_cls, static_argnums=(2,))
        block_kw = ({"groups": self.groups, "base_width": self.base_width}
                    if bottleneck else {})
        if not bottleneck and (self.groups != 1 or self.base_width != 64):
            raise ValueError("grouped (ResNeXt) blocks need a bottleneck "
                             "depth (>= 50), got depth=%d" % self.depth)
        for stage, (filters, n_blocks) in enumerate(
                zip(self.stage_filters, blocks_per_stage)):
            for i in range(n_blocks):
                stride = 2 if stage > 0 and i == 0 else 1
                with jax.named_scope(("conv.stage1", "conv.stage2",
                                      "conv.stage3", "conv.stage4")[stage]):
                    x = block_cls(filters, stride, self.vd, self.dtype,
                                  name="stage%d_block%d" % (stage, i),
                                  **block_kw)(x, train)

        with jax.named_scope("head"):
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(self.num_classes, dtype=jnp.float32,
                         param_dtype=jnp.float32, name="head")(x)
        return x


def ResNet50_vd(**kw):
    return ResNet(depth=50, vd=True, **kw)


def ResNeXt(depth=101, groups=32, base_width=16, **kw):
    """ResNeXt-{depth} {groups}x{base_width}d (e.g. the reference's
    distill teacher ResNeXt101_32x16d_wsl — BASELINE.md; 'wsl' names the
    weakly-supervised pretraining of the public weights, not an
    architecture difference). Vanilla (non-vd) stem by default, matching
    the canonical ResNeXt."""
    kw.setdefault("vd", False)
    return ResNet(depth=depth, groups=groups, base_width=base_width, **kw)


def ResNeXt101_32x16d(**kw):
    return ResNeXt(depth=101, groups=32, base_width=16, **kw)


def create_model_and_loss(depth=50, num_classes=1000, vd=True,
                          image_size=224, label_smoothing=0.1,
                          dtype=jnp.bfloat16, remat=False,
                          space_to_depth=False, groups=1, base_width=64):
    """Build (model, params, batch_stats, loss_fn) wired for ElasticTrainer
    with has_aux=True — aux carries the BatchNorm running stats."""
    model = ResNet(depth=depth, num_classes=num_classes, vd=vd, dtype=dtype,
                   remat=remat, space_to_depth=space_to_depth, groups=groups,
                   base_width=base_width)
    dummy = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), dummy, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})

    def loss_fn(params, extra, batch, rng):
        logits, updated = model.apply(
            {"params": params, "batch_stats": extra["batch_stats"]},
            batch["image"], train=True, mutable=["batch_stats"])
        with jax.named_scope("head"):
            labels = batch["label"]
            one_hot = optax.smooth_labels(
                jax.nn.one_hot(labels, num_classes), label_smoothing)
            loss = optax.softmax_cross_entropy(logits, one_hot).mean()
        return loss, {"batch_stats": updated["batch_stats"]}

    return model, params, {"batch_stats": batch_stats}, loss_fn


def synthetic_image_batch(batch_size, image_size=224, num_classes=1000,
                          seed=0):
    rng = np.random.RandomState(seed)
    return {
        "image": rng.randn(batch_size, image_size, image_size, 3)
                    .astype(np.float32),
        "label": rng.randint(0, num_classes, size=(batch_size,))
                    .astype(np.int32),
    }
