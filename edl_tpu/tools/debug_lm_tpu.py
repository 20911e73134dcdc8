"""Localize the LM slow-step pathology (ROADMAP S3).

The first chip GPT-2s bench run (pre-PR-1 chip sweep, 2026-07-31, git
history) compiled and warmed up in 60.3 s, then ran the steady-state
loop at >12 s/step — ~100x the compute bound for 8x1024 tokens on a
v5e chip — and blew the attempt budget. ResNet50 (205 MB donated train
state) ran at full speed in the same session; GPT-2s carries ~1.5 GB
(f32 adamw m/v + params), so the leading suspect was donated-state
aliasing. Never root-caused; S3 re-runs this on the chip and deletes
the tool if it does not reproduce.

This tool times INDIVIDUALLY BLOCKED steps across variants that move
exactly one lever each, so one run pins the culprit:

  adamw+donate     the bench configuration (1.5 GB state)
  sgd+donate       ~2/3 smaller optimizer state, same model
  adamw+nodonate   same state size, aliasing off on purpose
  adamw+b1         batch 1: collapses activation/compute terms
  noremat          remat off: isolates the jax.checkpoint interaction
  tiny             gpt_tiny control (fits any theory that scales)

Each variant prints compile time and 4 per-step wall times. Variants
are independent the only way that survives the pathology under study:
each runs in its OWN subprocess with a hard kill-timeout (a wedging
dispatch blocks inside C++ where Python signals, deadline checks, and
except clauses never run — the bench learned this the hard way), so a
hung variant is killed and reported while the rest still run. A global
deadline bounds the whole tool.
"""

import argparse
import subprocess
import sys
import time
import traceback


def _build(variant):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    # the shared persistent compile cache: a cold GPT-2s compile is a
    # large part of the variant's kill budget; later variants (and
    # bench attempts) then start in seconds
    from edl_tpu.utils import compile_cache
    compile_cache.enable()

    from edl_tpu.models import gpt as family
    from edl_tpu.runtime.mesh import DATA_AXIS, make_mesh
    from edl_tpu.runtime.trainer import make_train_state, make_train_step

    tiny = variant == "tiny"
    remat = variant != "noremat" and not tiny
    if tiny:
        model = family.gpt_tiny(dtype=jnp.bfloat16)
    else:
        model = family.Gpt(dtype=jnp.bfloat16, remat=remat)
    batch = 1 if variant == "adamw+b1" else 8
    seq = 64 if tiny else 1024
    model, params, loss_fn = family.create_model_and_loss(
        model=model, dummy_seq=16)
    mesh = make_mesh()
    repl = NamedSharding(mesh, P())
    data_sh = NamedSharding(mesh, P(DATA_AXIS))
    tx = optax.sgd(1e-2) if variant == "sgd+donate" else optax.adamw(1e-4)
    state = jax.device_put(make_train_state(params, tx), repl)
    donate = () if variant == "adamw+nodonate" else (0,)
    jit_step = jax.jit(make_train_step(loss_fn, tx),
                       in_shardings=(repl, data_sh, repl),
                       out_shardings=(repl, repl),
                       donate_argnums=donate)
    key = jax.random.PRNGKey(0)
    batch_dev = {"input_ids": jax.device_put(
        jax.random.randint(key, (batch, seq), 0, model.vocab_size,
                           jnp.int32), data_sh)}
    rng = jax.device_put(key, repl)
    state_mb = sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(state)) / 1e6
    return jit_step, state, batch_dev, rng, state_mb


def _probe_ok(timeout_s=90):
    """Cheap matmul probe in a subprocess. A wedged device hangs at
    init; probing BEFORE each variant stops the tool instead of letting
    per-variant kill-timeouts fire into a dead device, where every
    remaining variant would report a false "pathology" verdict."""
    code = ("import jax, jax.numpy as jnp;"
            "assert jax.devices()[0].platform == 'tpu', "
            "jax.devices()[0].platform;"
            "x = jnp.ones((512, 512), jnp.bfloat16);"
            "(x @ x).block_until_ready();print('OK')")
    # SIGTERM first so the JAX client can release the chip in an
    # orderly way; only escalate if the child ignores it for 10 s.
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
        return proc.returncode == 0 and b"OK" in out
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
        return False


def run_variant(variant, steps, deadline):
    import jax

    t0 = time.perf_counter()
    jit_step, state, batch_dev, rng, state_mb = _build(variant)
    # first call = compile + run
    state, loss = jit_step(state, batch_dev, rng)
    jax.block_until_ready(loss)
    print("[%s] state %.0f MB, compile+first-step %.1fs"
          % (variant, state_mb, time.perf_counter() - t0), flush=True)
    for i in range(steps):
        if time.perf_counter() > deadline:
            print("[%s] deadline hit, stopping" % variant, flush=True)
            return
        t0 = time.perf_counter()
        state, loss = jit_step(state, batch_dev, rng)
        jax.block_until_ready(loss)
        print("[%s] step %d: %.3fs" % (variant, i,
                                       time.perf_counter() - t0),
              flush=True)


def main():
    ap = argparse.ArgumentParser()
    # cheap/robust first: a wedge mid-tool then costs the least signal
    # (bench --model gpt measures the adamw+donate config end to end)
    ap.add_argument("--variants", default=(
        "tiny,adamw+b1,noremat,adamw+nodonate,sgd+donate,adamw+donate"))
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--budget_s", type=float, default=900.0,
                    help="global wall budget across all variants")
    ap.add_argument("--variant_timeout_s", type=float, default=240.0,
                    help="kill-timeout per variant subprocess")
    ap.add_argument("--_one", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args._one:
        # child mode: one variant, in-process (the parent holds the kill)
        try:
            run_variant(args._one, args.steps,
                        time.perf_counter() + args.budget_s)
        except Exception:
            print("[%s] FAILED:" % args._one, flush=True)
            traceback.print_exc()
        return
    deadline = time.monotonic() + args.budget_s
    for variant in args.variants.split(","):
        remaining = deadline - time.monotonic()
        if remaining <= 30:
            print("[%s] skipped: global budget exhausted" % variant,
                  flush=True)
            continue
        if not _probe_ok():
            print("[%s] DEVICE WEDGED (pre-variant probe hung) — "
                  "stopping; remaining variants would only produce "
                  "false kill verdicts" % variant, flush=True)
            return
        # re-clock after the probe so the child's budget cannot
        # overrun --budget_s by the probe's wall time
        remaining = deadline - time.monotonic()
        if remaining <= 30:
            print("[%s] skipped: global budget exhausted" % variant,
                  flush=True)
            continue
        timeout_s = min(args.variant_timeout_s, remaining)
        try:
            subprocess.run(
                [sys.executable, "-m", "edl_tpu.tools.debug_lm_tpu",
                 "--_one", variant, "--steps", str(args.steps),
                 "--budget_s", str(timeout_s * 0.9)],
                timeout=timeout_s)
        except subprocess.TimeoutExpired:
            print("[%s] KILLED after %.0fs (hung dispatch or starved "
                  "compile; the next probe decides whether the device "
                  "survived)"
                  % (variant, timeout_s), flush=True)


if __name__ == "__main__":
    main()
