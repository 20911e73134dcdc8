"""Profile the benchmark train step and print the device op-time
breakdown — the perf methodology for this framework (SURVEY.md §6 /
VERDICT r1 next-step #2: "profile with jax.profiler, iterate").

Captures a ``jax.profiler.trace`` of the ResNet50_vd train step, then
parses the xplane protobuf directly (the tensorboard profiler plugin in
this image is ABI-mismatched with its TF) and aggregates device time by
op class. This is the tool that located the round-2 BN bottleneck:
of a 50 ms step, conv fusions took ~19 ms (~87% MFU over conv time)
while BatchNorm statistic reductions (``convert_reduce_fusion``) took
~15.8 ms.

Usage:
    python -m edl_tpu.tools.profile_bench [--no-s2d] [--batch N]
           [--logdir DIR]

Prints: XLA cost-model FLOPs/step, traced ms/step, and the per-op-class
device-time table.
"""

import argparse
import collections
import glob
import os
import re
import sys
import time

# must be decided before the first google.protobuf import (jax/tf pull it
# in): the pre-protobuf-4 generated xplane_pb2 needs the python impl
os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")


def build_step(batch, s2d):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from edl_tpu.models import resnet
    from edl_tpu.runtime.mesh import DATA_AXIS, make_mesh
    from edl_tpu.runtime.trainer import make_train_state, make_train_step

    model, params, extra, loss_fn = resnet.create_model_and_loss(
        depth=50, num_classes=1000, vd=True, image_size=224,
        dtype=jnp.bfloat16, space_to_depth=s2d)
    mesh = make_mesh()
    repl = NamedSharding(mesh, P())
    data_sh = NamedSharding(mesh, P(DATA_AXIS))
    tx = optax.sgd(0.1, momentum=0.9)
    state = jax.device_put(make_train_state(params, tx, extra), repl)
    step = make_train_step(loss_fn, tx, has_aux=True)
    jit_step = jax.jit(step, in_shardings=(repl, data_sh, repl),
                       out_shardings=(repl, repl), donate_argnums=(0,))
    key = jax.random.PRNGKey(0)
    staged = {
        "image": jax.device_put(
            jax.random.normal(key, (batch, 224, 224, 3), jnp.bfloat16),
            data_sh),
        "label": jax.device_put(
            jax.random.randint(key, (batch,), 0, 1000, jnp.int32),
            data_sh),
    }
    rng = jax.device_put(jax.random.PRNGKey(0), repl)
    # also a non-donating jit for lowering/cost analysis
    jit_nodonate = jax.jit(step, in_shardings=(repl, data_sh, repl),
                           out_shardings=(repl, repl))
    return jit_step, jit_nodonate, state, staged, rng


def xplane_op_breakdown(logdir, steps):
    """Aggregate the device 'XLA Ops' line by op class (unique-id suffix
    stripped). Returns [(op_class, ms_per_step, events, us_per_event)]."""
    # the generated xplane_pb2 in this image predates protobuf 4's
    # C-extension descriptor check; the pure-python impl accepts it
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION",
                          "python")
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except (ImportError, TypeError) as e:
        print("xplane proto unavailable (%s)" % e)
        return None

    paths = glob.glob(os.path.join(logdir, "**/*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    space = xplane_pb2.XSpace()
    with open(sorted(paths)[-1], "rb") as f:
        space.ParseFromString(f.read())
    # merge across device planes (one per chip running the same SPMD
    # program) and report the PER-CHIP average, so multi-chip hosts don't
    # inflate ms/step by n_chips
    agg = collections.Counter()
    cnt = collections.Counter()
    n_planes = 0
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            n_planes += 1
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                base = re.sub(r"\.\d+", "", name.split(" = ")[0])
                agg[base] += ev.duration_ps
                cnt[base] += 1
    if n_planes == 0:
        return None
    rows = [(base, ps / 1e9 / steps / n_planes, cnt[base],
             ps / 1e6 / cnt[base]) for base, ps in agg.most_common()]
    return rows or None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--s2d", dest="s2d", action="store_true")
    ap.add_argument("--no-s2d", dest="s2d", action="store_false")
    ap.set_defaults(s2d=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--logdir", default="/tmp/edl_tpu_profile")
    args = ap.parse_args(argv)

    import jax

    jit_step, jit_nodonate, state, staged, rng = build_step(
        args.batch, args.s2d)
    for _ in range(3):
        state, loss = jit_step(state, staged, rng)
    jax.block_until_ready(loss)

    ca = jit_nodonate.lower(state, staged, rng).compile().cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    print("cost-model flops/step: %.1f GFLOP (%.2f GFLOP/img)"
          % (flops / 1e9, flops / 1e9 / args.batch), flush=True)

    t0 = time.perf_counter()
    with jax.profiler.trace(args.logdir):
        for _ in range(args.steps):
            state, loss = jit_step(state, staged, rng)
        jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    ms = 1000 * dt / args.steps
    print("traced %d steps: %.1f ms/step (host wall; tracing adds "
          "overhead — use the device table below)"
          % (args.steps, ms), flush=True)

    rows = xplane_op_breakdown(args.logdir, args.steps)
    if rows is None:
        print("no xplane produced (platform without profiler support)")
        return 1
    total = sum(r[1] for r in rows)
    print("device XLA-op time: %.2f ms/step; implied %.1f TFLOP/s"
          % (total, flops / 1e9 / total))
    print("%9s %8s %7s  %s" % ("ms/step", "us/event", "events", "op class"))
    for base, ms_step, n, us in rows[:25]:
        print("%9.3f %8.1f %7d  %s" % (ms_step, us, n, base[:70]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
