"""Plain reference for the `resnet50-vd` configuration: ResNet50_vd
(He et al. 2018, "Bag of Tricks", model D; PaddleClas `ResNet50_vd`, the
reference EDL's headline model), in straightforward `jax.numpy`, float32,
every convolution and product at `Precision.HIGHEST`. Training-mode
BatchNorm with full-batch statistics, label-smoothed cross-entropy. It
takes its weights from the seed and nothing from the program.

Departures from the published model (also in the config's `assumed`):
stride-2 3x3 convolutions and the 3x3 max-pool pad (0, 1) as XLA's SAME
does where PaddleClas pads (1, 1): the same work, shifted by one pixel.

`q="int8"` is the CONTROL (see reference/gpt2-small.py).
"""

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST

STAGE_BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def _fake_int8(x):
    s = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    return jnp.round(x / s) * s


def _conv(x, w, stride, q):
    if q == "int8":
        x, w = _fake_int8(x), _fake_int8(w)
    elif q is not None:
        raise ValueError("unknown control precision %r" % (q,))
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def layer_shapes(cfg):
    """Ordered [(name, kind, shape)], kind in conv | bn | bn_last | dense."""
    out = []
    cin = 3
    for i, c in enumerate(cfg["stem_widths"]):
        out += [("stem%d" % (i + 1), "conv", (3, 3, cin, c)),
                ("stem_bn%d" % (i + 1), "bn", (c,))]
        cin = c
    for s, (width, n) in enumerate(zip(cfg["stage_widths"],
                                       STAGE_BLOCKS[cfg["depth"]])):
        for b in range(n):
            p = "stage%d_block%d/" % (s, b)
            cout = width * cfg["bottleneck_expansion"]
            out += [(p + "conv1", "conv", (1, 1, cin, width)),
                    (p + "bn1", "bn", (width,)),
                    (p + "conv2", "conv", (3, 3, width, width)),
                    (p + "bn2", "bn", (width,)),
                    (p + "conv3", "conv", (1, 1, width, cout)),
                    (p + "bn3", "bn_last", (cout,))]
            if cin != cout or (s > 0 and b == 0):
                out += [(p + "downsample", "conv", (1, 1, cin, cout)),
                        (p + "downsample_bn", "bn", (cout,))]
            cin = cout
    out.append(("head", "dense", (cin, cfg["num_classes"])))
    return out


def init_weights(cfg, key):
    """Seeded weights, traced inside the caller's ONE jitted call: He
    normal kernels; BatchNorm gain 1 + 0.1 N (0.2 of that on a block's
    last BatchNorm, so the residual sum stays tame), bias 0.1 N; head
    N(0, 0.01) with a random bias."""
    w = {}
    for i, (name, kind, shape) in enumerate(layer_shapes(cfg)):
        k = jax.random.fold_in(key, i)
        n = lambda j, s: jax.random.normal(jax.random.fold_in(k, j), s,
                                           jnp.float32)
        if kind == "conv":
            fan_in = shape[0] * shape[1] * shape[2]
            w[name] = {"kernel": n(0, shape) * (2.0 / fan_in) ** 0.5}
        elif kind in ("bn", "bn_last"):
            gain = 1.0 + 0.1 * n(0, shape)
            w[name] = {"scale": gain * (0.2 if kind == "bn_last" else 1.0),
                       "bias": 0.1 * n(1, shape)}
        else:
            w[name] = {"kernel": 0.01 * n(0, shape),
                       "bias": 0.01 * n(1, shape[1:])}
    return w


def _bn(x, p, eps):
    m = jnp.mean(x, (0, 1, 2))
    v = jnp.mean(jnp.square(x - m), (0, 1, 2))
    return (x - m) * jax.lax.rsqrt(v + eps) * p["scale"] + p["bias"]


def _pool(x, window, stride, init, op, padding):
    return jax.lax.reduce_window(
        x, init, op, (1, window, window, 1), (1, stride, stride, 1),
        padding)


def forward(w, images, cfg, q=None):
    """images [B, H, W, 3] -> logits [B, classes]; training-mode BN."""
    eps = cfg["batch_norm_epsilon"]
    x = images.astype(jnp.float32)
    for i in range(len(cfg["stem_widths"])):
        x = _conv(x, w["stem%d" % (i + 1)]["kernel"], 2 if i == 0 else 1, q)
        x = jax.nn.relu(_bn(x, w["stem_bn%d" % (i + 1)], eps))
    x = _pool(x, 3, 2, -jnp.inf, jax.lax.max, "SAME")

    def block(x, bw, stride):
        y = jax.nn.relu(_bn(_conv(x, bw["conv1"]["kernel"], 1, q),
                            bw["bn1"], eps))
        y = jax.nn.relu(_bn(_conv(y, bw["conv2"]["kernel"], stride, q),
                            bw["bn2"], eps))
        y = _bn(_conv(y, bw["conv3"]["kernel"], 1, q), bw["bn3"], eps)
        r = x
        if "downsample" in bw:
            if stride > 1:  # the vd shortcut: average-pool, then 1x1
                r = _pool(r, 2, 2, 0.0, jax.lax.add, "VALID") / 4.0
            r = _bn(_conv(r, bw["downsample"]["kernel"], 1, q),
                    bw["downsample_bn"], eps)
        return jax.nn.relu(y + r)

    for s, n in enumerate(STAGE_BLOCKS[cfg["depth"]]):
        for b in range(n):
            p = "stage%d_block%d/" % (s, b)
            bw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
            x = jax.checkpoint(block, static_argnums=(2,))(
                x, bw, 2 if s > 0 and b == 0 else 1)
    x = jnp.mean(x, (1, 2))
    h = w["head"]
    if q == "int8":
        return jnp.matmul(_fake_int8(x), _fake_int8(h["kernel"]),
                          precision=HI) + h["bias"]
    return jnp.matmul(x, h["kernel"], precision=HI) + h["bias"]


def loss(w, batch, cfg, q=None):
    """Label-smoothed softmax cross-entropy, mean over the batch."""
    lg = forward(w, batch["image"], cfg, q)
    k = cfg["num_classes"]
    a = cfg["label_smoothing"]
    target = jax.nn.one_hot(batch["label"], k) * (1.0 - a) + a / k
    return -jnp.mean(jnp.sum(target * jax.nn.log_softmax(lg, -1), -1))


def loss_and_grad(w, batch, cfg, q=None):
    return jax.value_and_grad(lambda w_: loss(w_, batch, cfg, q))(w)
