"""Profile the benchmark train step and print the device op-time
breakdown — the perf methodology for this framework (SURVEY.md §6 /
VERDICT r1 next-step #2: "profile with jax.profiler, iterate").

Captures a ``jax.profiler.trace`` of the ResNet50_vd train step, then
reduces it with ``edl_tpu.obs.devtime`` to device SELF time by the
program's scopes (stem, stages, head, optimizer; forward / backward) and
by op class. This is the tool that located the round-2 BN bottleneck:
of a 50 ms step, conv fusions took ~19 ms (~87% MFU over conv time)
while BatchNorm statistic reductions (``convert_reduce_fusion``) took
~15.8 ms.

    python -m edl_tpu.tools.profile_bench --parse_only --logdir DIR \\
           --steps N [--hlo STEP.txt]

prints the same two tables for a profile that is already saved (an
operator's capture of a running job). A trace names an operation, not the
scope it was traced under: ``--hlo`` is the traced program's text
(``compiled.as_text()``), which carries each operation's name stack;
without it the by-class table alone says anything.

Usage:
    python -m edl_tpu.tools.profile_bench [--no-s2d] [--batch N]
           [--logdir DIR]

Prints: XLA cost-model FLOPs/step, traced ms/step, and the two
device-time tables.
"""

import argparse
import sys
import time


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


def xplane_op_breakdown(logdir, steps, names=None):
    """The newest trace under ``logdir`` as device SELF time per step
    (`edl_tpu.obs.devtime`: a `while` or a `conditional` is charged only
    what its body does not cover, so the rows add up to the device's busy
    time): ``{"by_scope": [((scope, phase), ms_per_step)], "by_class":
    [(op_class, ms_per_step)]}``, largest first, or None where the
    directory holds no trace or the trace no device operation. ``names``:
    the traced program's ``devtime.op_names`` (default: those of the
    trainers alive in this process); without them every row of
    ``by_scope`` reads `unscoped`."""
    from edl_tpu.obs import devtime
    path = devtime.newest_trace(logdir)
    if path is None:
        return None
    events = devtime.load(path, names=names)
    if not events:
        return None
    rows = lambda table: sorted(((k, sec * 1e3 / steps)
                                 for k, sec in table.items()),
                                key=lambda kv: -kv[1])
    return {"by_scope": rows(devtime.by_scope(events)),
            "by_class": rows(devtime.by_class(events))}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--s2d", dest="s2d", action="store_true")
    ap.add_argument("--no-s2d", dest="s2d", action="store_false")
    ap.set_defaults(s2d=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--logdir", default="/tmp/edl_tpu_profile")
    ap.add_argument("--parse_only", action="store_true",
                    help="reduce the profile already under --logdir "
                         "(--steps: the steps it holds); run nothing")
    ap.add_argument("--hlo", default=None,
                    help="with --parse_only: the traced program's text "
                         "(compiled.as_text(), or XLA's dump after "
                         "optimizations), for the table by scope")
    args = ap.parse_args(argv)
    if args.parse_only:
        names = None
        if args.hlo:
            from edl_tpu.obs import devtime
            with open(args.hlo) as f:
                names = devtime.op_names(f.read())
        return _print_tables(args.logdir, args.steps, names=names)

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

    from edl_tpu.obs import devtime
    names = devtime.op_names(
        jit_step.lower(state, staged, rng).compile().as_text())
    return _print_tables(args.logdir, args.steps, flops, names)


def _print_tables(logdir, steps, flops=None, names=None):
    tables = xplane_op_breakdown(logdir, steps, names)
    if tables is None:
        print("no xplane produced (platform without profiler support)")
        return 1
    total = sum(ms for _, ms in tables["by_class"])
    print("device busy time: %.2f ms/step" % total
          + ("" if flops is None else "; implied %.1f TFLOP/s"
             % (flops / 1e9 / total)))
    print("%9s  %-6s %s" % ("ms/step", "phase", "scope (self time)"))
    for (scope, phase), ms in tables["by_scope"][:25]:
        print("%9.3f  %-6s %s" % (ms, phase, scope))
    print("%9s  %s" % ("ms/step", "op class (self time)"))
    for base, ms in tables["by_class"][:25]:
        print("%9.3f  %s" % (ms, base[:70]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
