"""Benchmark: ResNet50_vd training throughput (img/s) on local devices.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count"} — the device identity as JAX
reports it rides every result as fields. The configuration asked for
either runs on a TPU whose ``device_kind`` is in the peaks table
(parallel/costmodel.py) or the bench exits non-zero and prints no
result: there is no CPU rung, no substituted configuration and no
placeholder line.

Baseline: the reference's headline number — ResNet50_vd pure collective
training at 1828 img/s on 8x V100 (README.md:83, BASELINE.md), i.e.
228.5 img/s per accelerator. This bench runs on whatever chips are
visible, so vs_baseline is normalized PER CHIP:
vs_baseline = (img/s per local chip) / 228.5.

Modes:
  --feed device  (default) data staged on device once: pure compute rate.
  --feed host    numpy batches from the synthetic input pipeline are
                 sharded onto device every step: the end-to-end rate a
                 real training loop sees (the DALI role).
  --feed native  the C++ JPEG loader on REAL images (--data_dir) feeds
                 the step: decode+augment+normalize end to end.

Process model: the top-level process never touches jax (a parent that
has touched JAX holds the chip). The measurement runs in ONE child with
a hard kill-timeout — a hung backend blocks inside C++ where Python
signals are never delivered, so only a parent can bound it. The child
runs with JAX_PLATFORMS=tpu, so JAX raises instead of dropping to the
CPU when the chip is missing or held.

Variants: --no-s2d disables the space-to-depth stem; --batch_per_chip
to sweep; --steps_per_call K scans K train steps per jit dispatch
(amortizes per-step host dispatch).
"""

import argparse
import json
import os
import subprocess
import sys
import time

BASELINE_IMGS_PER_SEC_PER_CHIP = 1828.0 / 8.0

# wall-clock anchor for the slow-step guard: the attempt subprocess
# must finish inside the parent's kill-timeout, so the loop budget is
# charged against time-since-process-start, not a fresh stopwatch
_PROC_START = time.perf_counter()


def _guarded_timed_loop(dispatch, block, iters):
    """The timed measurement loop, with a slow-step pathology guard
    (ROADMAP S3): the first chip GPT-2s run's steady-state step rate
    was ~100x its compute bound; 30 queued dispatches blew the attempt
    budget and the kill landed mid-queue. A slow
    step must become a MEASUREMENT, not a hang: time one blocked
    dispatch, size the queued timed loop to what fits the remaining
    attempt budget, then run it (queued-dispatch methodology preserved
    inside the loop — both benches share this function so the
    methodology cannot drift between them).

    ``dispatch`` issues one step and returns the value to block on
    (mutating the caller's train state via closure); ``block`` is
    jax.block_until_ready. Returns (iters, dt, slowstep): the iters
    actually measured, the loop wall time, and whether the sample is a
    pathology report. The tag is decided from the MEASURED rate, not
    the probe — a blocked probe pays a full host round trip that the
    queued loop amortizes away, so a truncated-but-healthy loop is
    just fewer samples, while a probe-only measurement or a loop whose
    measured rate would still blow the budget at the requested length
    is genuinely slow.
    """
    t0 = time.perf_counter()
    block(dispatch())
    probe_s = time.perf_counter() - t0
    # budget what's actually left of the attempt timeout (compile +
    # warmup already spent some), not a fixed constant that could
    # itself overshoot the parent's kill
    remaining_s = ATTEMPT_TIMEOUT_S * 0.80 - (time.perf_counter()
                                              - _PROC_START)
    loop_budget_s = min(
        float(os.environ.get("BENCH_LOOP_BUDGET", "150")),
        max(remaining_s, 0.0))
    requested_iters = iters
    truncated = probe_s * iters > loop_budget_s
    if truncated:
        slow_iters = int(loop_budget_s / probe_s)
        log("probe dispatch took %.2fs — %d iters would blow the %.0fs "
            "loop budget; %s"
            % (probe_s, iters, loop_budget_s,
               "reporting the probe step as the measurement"
               if slow_iters < 2
               else "measuring %d iters instead" % slow_iters))
        if slow_iters < 2:
            # the blocked probe IS the measurement; never queue
            # dispatches the parent's kill could land in the middle of
            return 1, probe_s, True
        iters = slow_iters
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = dispatch()
    block(out)
    dt = time.perf_counter() - t0
    slowstep = truncated and \
        (dt / iters) * requested_iters > loop_budget_s
    return iters, dt, slowstep

# Kill timeout (seconds) of the measurement child: a hung backend
# blocks inside C++ (no exception, no signal delivery), so the ONLY
# robust bound is a parent process that kills the child.
ATTEMPT_TIMEOUT_S = int(os.environ.get("BENCH_ATTEMPT_TIMEOUT", "420"))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def run(batch_per_chip=128, image_size=224, warmup=3, iters=20,
        s2d=True, feed="device", steps_per_call=1, bn_stats_every=1,
        data_dir=None, *, peak_tflops):
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from edl_tpu.models import resnet
    from edl_tpu.runtime.mesh import DATA_AXIS, make_mesh
    from edl_tpu.runtime.trainer import make_train_state, make_train_step

    n_chips = jax.local_device_count()
    batch = batch_per_chip * n_chips
    log("bench: %d chip(s) (%s), global batch %d, s2d=%s, feed=%s, "
        "steps_per_call=%d, bn_stats_every=%d"
        % (n_chips, jax.devices()[0].platform, batch, s2d, feed,
           steps_per_call, bn_stats_every))

    model, params, extra, loss_fn = resnet.create_model_and_loss(
        depth=50, num_classes=1000, vd=True, image_size=image_size,
        dtype=jnp.bfloat16, space_to_depth=s2d,
        bn_stats_every=bn_stats_every)
    mesh = make_mesh()
    repl = NamedSharding(mesh, P())
    data_sh = NamedSharding(mesh, P(DATA_AXIS))

    tx = optax.sgd(0.1, momentum=0.9)
    # the SAME step the product trainer runs (trainer.make_train_step)
    state = jax.device_put(make_train_state(params, tx, extra), repl)
    step = make_train_step(loss_fn, tx, has_aux=True)
    if steps_per_call > 1:
        # scan K steps per dispatch: training loops are dispatch-bound
        # whenever the host is slow relative to the step. Same train
        # step, scanned.
        base_step = step

        def step(state, batch_, rng_):
            def body(s, _):
                s2, loss_ = base_step(s, batch_, rng_)
                return s2, loss_
            state2, losses = lax.scan(body, state, None,
                                      length=steps_per_call)
            return state2, losses[-1]
    jit_step = jax.jit(step,
                       in_shardings=(repl, data_sh, repl),
                       out_shardings=(repl, repl), donate_argnums=(0,))
    rng = jax.device_put(jax.random.PRNGKey(0), repl)

    prefetcher = None
    if feed in ("host", "native"):
        from edl_tpu.data.prefetch import DevicePrefetcher

        def to_bf16(b):
            return {"image": b["image"].astype(jnp.bfloat16),
                    "label": b["label"]}

        if feed == "native":
            # the C++ loader on REAL JPEGs: the end-to-end DALI-role
            # rate (decode+augment+normalize feeding the train step)
            from edl_tpu.data.native_loader import (
                native_image_folder_pipeline)

            def stream():
                epoch = 0
                while True:
                    # train=True drops the ragged tail: every batch
                    # is full-size by construction
                    for b in native_image_folder_pipeline(
                            data_dir, batch, image_size=image_size,
                            train=True, epoch_seed=epoch):
                        yield b
                    epoch += 1
            source = stream()
        else:
            from edl_tpu.data.input_pipeline import synthetic_pipeline
            source = synthetic_pipeline(batch, image_size=image_size)
        prefetcher = DevicePrefetcher(source, data_sh, size=2,
                                      transform=to_bf16)
        next_batch = lambda: next(prefetcher)
    else:
        key = jax.random.PRNGKey(0)
        staged = {
            "image": jax.device_put(
                jax.random.normal(key, (batch, image_size, image_size, 3),
                                  jnp.bfloat16), data_sh),
            "label": jax.device_put(
                jax.random.randint(key, (batch,), 0, 1000, jnp.int32),
                data_sh),
        }
        next_batch = lambda: staged

    try:
        log("compiling + warmup (%d steps)..." % warmup)
        t0 = time.perf_counter()
        for _ in range(warmup):
            state, loss = jit_step(state, next_batch(), rng)
        jax.block_until_ready(loss)
        log("warmup done in %.1fs (loss=%.3f)" % (time.perf_counter() - t0,
                                                  float(loss)))

        def dispatch():
            nonlocal state
            state, loss_ = jit_step(state, next_batch(), rng)
            return loss_

        iters, dt, guard_fired = _guarded_timed_loop(
            dispatch, jax.block_until_ready, iters)
        ms_per_step = 1000 * dt / (iters * steps_per_call)
    finally:
        # a failed run must not leave the prefetch thread running
        if prefetcher is not None:
            prefetcher.close()

    imgs_per_sec = batch * iters * steps_per_call / dt
    per_chip = imgs_per_sec / n_chips
    log("throughput: %.1f img/s total, %.1f img/s per chip (%.1f ms/step)"
        % (imgs_per_sec, per_chip, ms_per_step))
    # physics gate: ResNet50_vd fwd+bwd is ~25 GFLOP/img at 224px (XLA
    # cost model) — a step "faster" than the RUNNING chip's published
    # peak + 25% margin timed the dispatch, not the device. Mark it so
    # an artifact can never silently carry a fake number.
    gflop_per_img = 25.0 * (image_size / 224.0) ** 2
    implied_tflops = per_chip * gflop_per_img / 1000.0
    log("implied %.1f TFLOP/s per chip" % implied_tflops)
    suspect = implied_tflops > peak_tflops * 1.25
    if suspect:
        log("WARNING: implied TFLOP/s exceeds the chip's physical peak "
            "— not a measurement; marking metric _suspect")
    metric = "resnet50_vd_train_imgs_per_sec_per_chip"
    if suspect:
        metric += "_suspect"
    if feed == "host":
        metric += "_hostfed"
    elif feed == "native":
        metric += "_nativefed"
    if steps_per_call > 1:
        metric += "_scan%d" % steps_per_call
    if bn_stats_every > 1:
        metric += "_bn%d" % bn_stats_every
    if batch_per_chip != MODEL_DEFAULT_BATCH["resnet"]:
        # sweep hygiene: a non-default batch must be visible in the name
        metric += "_b%d" % batch_per_chip
    if guard_fired:
        # a guard-truncated run is a pathology report, not a healthy
        # throughput sample
        metric += "_slowstep"
    return {
        "metric": metric,
        "value": round(per_chip, 1),
        "unit": "img/s/chip",
        "vs_baseline": round(per_chip / BASELINE_IMGS_PER_SEC_PER_CHIP, 3),
    }


# per-model CLI defaults
MODEL_DEFAULT_BATCH = {"gpt": 8, "bert": 32, "resnet": 128}
MODEL_DEFAULT_SEQ = {"gpt": 1024, "bert": 512}


def _run_lm(kind, batch_per_chip, seq_len, iters, flash, remat,
            peak_tflops, warmup=3):
    """LM/encoder train-throughput loop (tokens/s/chip): --model gpt is
    the GPT-2-small shape (12L/768d/12h, vocab 32k), --model bert is
    BERT-base with a classification head at seq 512 (the flash-attention
    A/B vehicle). Same mesh/sharding/timing/physics gate as run().
    vs_baseline 0.0: the reference published no LM/encoder number."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from edl_tpu.runtime.mesh import DATA_AXIS, make_mesh
    from edl_tpu.runtime.trainer import make_train_state, make_train_step

    n_chips = jax.local_device_count()
    batch = batch_per_chip * n_chips
    key = jax.random.PRNGKey(0)
    if kind == "gpt":
        from edl_tpu.models import gpt as family
        model = family.Gpt(dtype=jnp.bfloat16, remat=remat,
                           use_flash=flash)
        prefix = "gpt2s"
    else:
        from edl_tpu.models import bert as family
        model = family.bert_base(dtype=jnp.bfloat16, use_flash=flash,
                                 remat=remat)
        prefix = "bert_base"
    requested_seq = seq_len
    seq_len = min(seq_len, model.max_len)
    if requested_seq != seq_len:
        log("bench[%s]: seq_len %d clamped to the model max %d"
            % (kind, requested_seq, seq_len))
    log("bench[%s]: %d chip(s) (%s), global batch %d, seq %d, flash=%s"
        % (kind, n_chips, jax.devices()[0].platform, batch, seq_len,
           flash))
    model, params, loss_fn = family.create_model_and_loss(
        model=model, dummy_seq=min(16, seq_len))
    mesh = make_mesh()
    repl = NamedSharding(mesh, P())
    data_sh = NamedSharding(mesh, P(DATA_AXIS))
    tx = optax.adamw(1e-4)
    state = jax.device_put(make_train_state(params, tx), repl)
    jit_step = jax.jit(make_train_step(loss_fn, tx),
                       in_shardings=(repl, data_sh, repl),
                       out_shardings=(repl, repl), donate_argnums=(0,))
    batch_dev = {"input_ids": jax.device_put(
        jax.random.randint(key, (batch, seq_len), 0, model.vocab_size,
                           jnp.int32), data_sh)}
    if kind == "bert":
        batch_dev["label"] = jax.device_put(
            jax.random.randint(key, (batch,), 0, model.num_classes,
                               jnp.int32), data_sh)
    rng = jax.device_put(key, repl)

    log("compiling + warmup (%d steps)..." % warmup)
    t0 = time.perf_counter()
    for _ in range(warmup):
        state, loss = jit_step(state, batch_dev, rng)
    jax.block_until_ready(loss)
    log("warmup done in %.1fs (loss=%.3f)" % (time.perf_counter() - t0,
                                              float(loss)))
    def dispatch():
        nonlocal state
        state, loss_ = jit_step(state, batch_dev, rng)
        return loss_

    iters, dt, guard_fired = _guarded_timed_loop(
        dispatch, jax.block_until_ready, iters)
    per_chip = batch * seq_len * iters / dt / n_chips
    log("throughput: %.0f tok/s per chip (%.1f ms/step)"
        % (per_chip, 1000 * dt / iters))
    # physics gate (see run()): ~6*N per token + the attention term
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        state["params"]))
    flops_per_token = 6.0 * n_params + 12.0 * model.num_layers \
        * model.d_model * seq_len
    implied_tflops = per_chip * flops_per_token / 1e12
    log("implied %.1f TFLOP/s per chip" % implied_tflops)
    metric = prefix + "_train_tokens_per_sec_per_chip"
    if seq_len != min(MODEL_DEFAULT_SEQ[kind], model.max_len):
        # a clamped or swept length must be visible in the metric name,
        # or a seq-sweep log records duplicates as distinct results
        metric += "_seq%d" % seq_len
    if batch_per_chip != MODEL_DEFAULT_BATCH[kind]:
        # same sweep hygiene for batch scaling
        metric += "_b%d" % batch_per_chip
    if not remat:
        metric += "_noremat"
    if flash:
        metric += "_flash"
    if guard_fired:
        # a guard-truncated run is a pathology report, not a healthy
        # throughput sample
        metric += "_slowstep"
    if implied_tflops > peak_tflops * 1.25:
        log("WARNING: implied TFLOP/s exceeds the chip's physical peak "
            "— marking metric _suspect")
        metric += "_suspect"
    return {"metric": metric, "value": round(per_chip, 1),
            "unit": "tok/s/chip", "vs_baseline": 0.0}


def _oneshot(args):
    """The measurement child: run exactly the configuration asked for on
    the TPU and print its JSON line, or exit non-zero with no result."""
    from edl_tpu.parallel import costmodel
    from edl_tpu.utils import compile_cache

    compile_cache.enable()
    device = costmodel.device_identity()
    if device["platform"] != "tpu":
        log("bench: needs a TPU, found platform %r — no result"
            % device["platform"])
        sys.exit(1)
    # raises on a chip with no published peaks: the physics gate must
    # not judge a measurement against some other chip's roofline
    peak = costmodel.chip_peaks(device["device_kind"])["bf16_tflops"]
    if args.model in ("gpt", "bert"):
        result = _run_lm(args.model, args.batch_per_chip, args.seq_len,
                         args.iters, args.flash, args.remat, peak)
    else:
        kwargs = dict(batch_per_chip=args.batch_per_chip,
                      iters=args.iters, s2d=args.s2d, feed=args.feed,
                      steps_per_call=args.steps_per_call,
                      bn_stats_every=args.bn_stats_every,
                      data_dir=args.data_dir, peak_tflops=peak)
        if args.image_size != 224:
            kwargs.update(image_size=args.image_size, warmup=2)
        result = run(**kwargs)
        if args.image_size != 224:
            # a different configuration: say so, and the 224px
            # baseline ratio does not apply to it
            result["metric"] += "_smallcfg"
            result["vs_baseline"] = 0.0
    result.update(device)
    print(json.dumps(result), flush=True)


def _attempt(argv, timeout_s):
    """Run the measurement child with a hard kill-timeout; returns its
    parsed JSON result or None. A subprocess (not a thread/SIGALRM)
    because a hung backend blocks inside C++ where Python signals are
    never delivered."""
    cmd = [sys.executable, os.path.abspath(__file__), "--_oneshot"] + argv
    log("bench: %s (timeout %ds)" % (" ".join(argv) or "<default>",
                                     timeout_s))
    # JAX_PLATFORMS=tpu: a missing or held chip raises in the child
    # instead of silently running the step on the CPU backend. The
    # child's slow-step guard budgets against the same kill deadline.
    env = dict(os.environ, JAX_PLATFORMS="tpu",
               BENCH_ATTEMPT_TIMEOUT=str(int(timeout_s)))
    try:
        proc = subprocess.run(cmd, env=env, timeout=timeout_s,
                              stdout=subprocess.PIPE, stderr=sys.stderr)
    except subprocess.TimeoutExpired:
        log("bench: child timed out after %ds — killed" % timeout_s)
        return None
    if proc.returncode != 0:
        log("bench: child exited rc=%d" % proc.returncode)
        return None
    for line in reversed(proc.stdout.decode(errors="replace").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                pass
    log("bench: child produced no JSON line")
    return None


def _build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("resnet", "gpt", "bert"),
                    default="resnet",
                    help="resnet = the headline (img/s); gpt = the LM "
                         "surface (tok/s, GPT-2-small shape); bert = the "
                         "encoder surface (tok/s, bert-base @ seq 512)")
    ap.add_argument("--batch_per_chip", type=int, default=None,
                    help="default: 128 (resnet) / 8 (gpt) / 32 (bert)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--image_size", type=int, default=224)
    ap.add_argument("--seq_len", type=int, default=None,
                    help="sequence length (default: 1024 gpt / "
                         "512 bert)")
    ap.add_argument("--flash", action="store_true",
                    help="gpt/bert: Pallas flash attention")
    ap.add_argument("--remat", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="gpt/bert: per-layer activation recompute. The "
                    "static account says --no-remat cuts both flops and "
                    "HBM traffic when the batch fits (PERF_ACCOUNTING "
                    "lm_batch) — A/B it")
    ap.add_argument("--s2d", dest="s2d", action="store_true")
    ap.add_argument("--no-s2d", dest="s2d", action="store_false")
    ap.set_defaults(s2d=True)
    ap.add_argument("--feed", choices=("device", "host", "native"),
                    default="device",
                    help="device = staged-once compute rate; host = "
                         "synthetic pipeline fed per step; native = the "
                         "C++ JPEG loader on --data_dir fed per step")
    ap.add_argument("--data_dir", default=None,
                    help="image-folder root for --feed native")
    ap.add_argument("--steps_per_call", type=int, default=1,
                    help="scan K train steps per jit dispatch (amortizes "
                         "host->device dispatch latency)")
    ap.add_argument("--bn_stats_every", type=int, default=1,
                    help="BN train statistics from every k-th batch row "
                         "(4 at batch 128 = the reference's per-GPU "
                         "stats batch of 32)")
    ap.add_argument("--_oneshot", action="store_true",
                    help=argparse.SUPPRESS)
    return ap


def main():
    ap = _build_parser()
    args = ap.parse_args()
    if args.batch_per_chip is None:
        args.batch_per_chip = MODEL_DEFAULT_BATCH[args.model]
    if args.seq_len is None:
        args.seq_len = MODEL_DEFAULT_SEQ.get(args.model, 1024)
    # argument conflicts fail fast, before any child is spawned
    if args.steps_per_call < 1:
        ap.error("--steps_per_call must be >= 1")
    if args.bn_stats_every < 1:
        ap.error("--bn_stats_every must be >= 1")
    if args.model == "resnet" and args.bn_stats_every > 1 \
            and args.batch_per_chip // args.bn_stats_every < 16:
        # measured in the r4 gate experiment: 8-sample BN statistics
        # (batch 32 / every 4) cost real accuracy (0.8 vs 0.85+); the
        # convergence gate covers stats batches >= 32, so refuse
        # configs below half that rather than bench an untested regime
        ap.error("--bn_stats_every %d at batch %d leaves a BN stats "
                 "batch of %d (< 16); subset statistics this small "
                 "measurably hurt convergence"
                 % (args.bn_stats_every, args.batch_per_chip,
                    args.batch_per_chip // args.bn_stats_every))
    if args.feed != "device" and args.steps_per_call > 1:
        ap.error("--steps_per_call measures pure device rate and skips "
                 "the per-step feed; use it with --feed device")
    if args.feed == "native" and not args.data_dir:
        ap.error("--feed native needs --data_dir")
    if getattr(args, "_oneshot"):
        _oneshot(args)
        return

    result = _attempt(sys.argv[1:], ATTEMPT_TIMEOUT_S)
    if result is None or result.get("platform") != "tpu":
        log("bench: the configuration asked for did not run on a TPU — "
            "no result")
        sys.exit(1)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
