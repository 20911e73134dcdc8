"""How a `family: resnet` configuration is handed to the program under
test: `edl_tpu/models/resnet.py` (space-to-depth stem, as `bench.py` runs
it) for the model and its loss, the reference's seeded weights relabelled
into the program's parameter tree."""

import jax
import jax.numpy as jnp


def to_program(w, cfg):
    """Reference weights -> (params, extra) of `models.resnet.ResNet`:
    `stageS_blockB/x` becomes a nested entry, BatchNorm running
    statistics start at mean 0, variance 1."""
    params, stats = {}, {}
    for name, leaf in w.items():
        *outer, last = name.split("/")
        at = params
        for k in outer:
            at = at.setdefault(k, {})
        at[last] = leaf
        if "scale" in leaf:  # a BatchNorm
            at = stats
            for k in outer:
                at = at.setdefault(k, {})
            at[last] = {"mean": jnp.zeros_like(leaf["scale"]),
                        "var": jnp.ones_like(leaf["scale"])}
    return params, {"batch_stats": stats}


def train_parts(cfg, job):
    from edl_tpu.models import resnet
    box = {}

    def build():
        _, params, extra, loss_fn = resnet.create_model_and_loss(
            depth=cfg["depth"], num_classes=cfg["num_classes"], vd=True,
            image_size=cfg["image_size"],
            label_smoothing=cfg["label_smoothing"], dtype=jnp.bfloat16,
            space_to_depth=True)
        box["loss_fn"] = loss_fn
        return params, extra

    shapes = jax.eval_shape(build)
    return box["loss_fn"], True, shapes


def make_batch(cfg, job, key, rows):
    s = cfg["image_size"]
    return {"image": jax.random.normal(key, (rows, s, s, 3), jnp.bfloat16),
            "label": jax.random.randint(jax.random.fold_in(key, 1), (rows,),
                                        0, cfg["num_classes"], jnp.int32)}


def train_flops(cfg, job, rows):
    """Operations the forward and backward passes of one step REQUIRE:
    2 per multiply-add of every convolution and of the head, times 3
    (forward, and the two products of the backward pass). Sizes follow
    the layer list of the published model, not the program."""
    blocks = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
    size = cfg["image_size"] // 2          # stem1 has stride 2
    macs, cin = 0, 3
    for c in cfg["stem_widths"]:
        macs += size * size * 9 * cin * c
        cin = c
    size //= 2                             # the 3x3 max-pool
    expansion = cfg["bottleneck_expansion"]
    for s, (width, n) in enumerate(zip(cfg["stage_widths"],
                                       blocks[cfg["depth"]])):
        for b in range(n):
            out = size // 2 if (s > 0 and b == 0) else size
            cout = width * expansion
            macs += size * size * cin * width          # 1x1
            macs += out * out * 9 * width * width      # 3x3, strided
            macs += out * out * width * cout           # 1x1
            if cin != cout or (s > 0 and b == 0):
                macs += out * out * cin * cout         # vd shortcut
            size, cin = out, cout
    macs += cin * cfg["num_classes"]
    return rows * 3.0 * 2.0 * macs
