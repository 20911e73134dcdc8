"""ResNet-vd elastic collective training.

Reference parity: example/collective/resnet50/train_with_fleet.py — the
headline config (SURVEY.md §3.2): bf16 ResNet50_vd, warmup + cosine/
piecewise LR with the batch-scaling rule, per-epoch rank-0 checkpoints,
throughput logging every ``fetch_steps`` and a final benchmark-log JSON
(reference :532-548,642-658). Runs standalone or under the launcher;
synthetic data by default (the input-pipeline module supplies real data).
"""

import argparse
import json
import sys
import time


def main(argv=None):
    from edl_tpu.runtime.trainer import maybe_init_distributed
    maybe_init_distributed()

    import jax.numpy as jnp
    import optax

    from edl_tpu.controller import train_status as ts
    from edl_tpu.models import resnet
    from edl_tpu.runtime import lr_schedules
    from edl_tpu.runtime.trainer import ElasticTrainer

    p = argparse.ArgumentParser()
    p.add_argument("--depth", type=int, default=50)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--steps_per_epoch", type=int, default=10)
    p.add_argument("--total_batch_size", type=int, default=32)
    p.add_argument("--image_size", type=int, default=64)
    p.add_argument("--num_classes", type=int, default=100)
    p.add_argument("--base_lr", type=float, default=0.1)
    p.add_argument("--warmup_epochs", type=int, default=1)
    p.add_argument("--lr_schedule", choices=["cosine", "piecewise"],
                   default="cosine")
    p.add_argument("--dtype", choices=["bf16", "f32"], default="f32")
    p.add_argument("--grad_accum", type=int, default=1,
                   help="microbatches per optimizer update; raise after "
                        "a scale-down to keep global batch AND per-chip "
                        "memory constant")
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1 weight-update sharding: optimizer "
                        "moments sharded over dp")
    p.add_argument("--max_per_device_batch", type=int, default=None,
                   help="per-device batch budget; grad accumulation is "
                        "chosen per world size to fit it")
    p.add_argument("--fetch_steps", type=int, default=10)
    p.add_argument("--eval_steps", type=int, default=0,
                   help="eval batches per epoch on rank 0 (0 = off)")
    p.add_argument("--data_dir", default=None,
                   help="image-folder dataset root (class subdirs of "
                        "jpegs); default = synthetic stream")
    p.add_argument("--eval_dir", default=None,
                   help="image-folder eval split (with --data_dir)")
    p.add_argument("--loader", choices=["tf", "native"], default="tf",
                   help="host decode pipeline: tf.data (portable) or "
                        "the C++ native loader (production TPU-VM feed)")
    p.add_argument("--seed", type=int, default=None,
                   help="graph-level tf.data augmentation seed "
                        "(reproducible crops/flips for gating runs)")
    p.add_argument("--prewarm_worlds", default="",
                   help="comma list of chip counts to AOT-compile the "
                        "step for (background, after epoch 0) so a "
                        "resize restart loads its step instead of "
                        "compiling (kept beside the compile cache, "
                        "edl_tpu/utils/compile_cache.py)")
    args = p.parse_args(argv)

    if args.seed is not None:
        if args.loader == "tf":
            import tensorflow as tf
            tf.random.set_seed(args.seed)
        else:
            print("WARNING: --seed only seeds the tf.data augmentation; "
                  "--loader native uses its own per-item deterministic "
                  "RNG and ignores it", flush=True)

    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    total_steps = args.epochs * args.steps_per_epoch
    lr = lr_schedules.scale_lr_for_batch(args.base_lr,
                                         args.total_batch_size)
    if args.lr_schedule == "cosine":
        base = lr_schedules.cosine_decay(lr, total_steps)
    else:
        bounds = [total_steps // 3, 2 * total_steps // 3]
        base = lr_schedules.piecewise_decay(lr, bounds)
    schedule = lr_schedules.linear_warmup(
        base, args.warmup_epochs * args.steps_per_epoch)

    if args.data_dir:
        from edl_tpu.data.input_pipeline import list_image_files
        files, class_names = list_image_files(args.data_dir)
        args.num_classes = max(args.num_classes, len(class_names))

    model, params, extra, loss_fn = resnet.create_model_and_loss(
        depth=args.depth, num_classes=args.num_classes,
        image_size=args.image_size, dtype=dtype)
    trainer = ElasticTrainer(
        loss_fn, params, optax.sgd(schedule, momentum=0.9),
        total_batch_size=args.total_batch_size, extra_state=extra,
        has_aux=True, grad_accum=args.grad_accum, zero1=args.zero1,
        max_per_device_batch=args.max_per_device_batch)
    env = trainer.env
    trainer.install_preemption_handler()
    resumed = trainer.resume()
    start_epoch = trainer.state.next_epoch() if resumed else 0
    print("resnet%d_vd: rank=%d world=%d start_epoch=%d resumed=%s"
          % (args.depth, env.global_rank, trainer.world_size, start_epoch,
             resumed), flush=True)

    evaluator = None
    if (args.eval_steps or args.eval_dir) and env.global_rank == 0:
        from edl_tpu.runtime.evaluation import Evaluator

        def eval_apply(params, extra, batch):
            return model.apply(
                {"params": params, "batch_stats": extra["batch_stats"]},
                batch["image"], train=False)
        evaluator = Evaluator(eval_apply)

    def host_batches(epoch):
        """Per-host batch stream for one epoch (real data when --data_dir,
        else the deterministic synthetic stream), capped at
        steps_per_epoch."""
        if args.data_dir:
            if args.loader == "native":
                from edl_tpu.data.native_loader import (
                    native_image_folder_pipeline as folder_pipeline)
            else:
                from edl_tpu.data.input_pipeline import (
                    image_folder_pipeline as folder_pipeline)
            n = 0
            while n < args.steps_per_epoch:  # cycle the folder if short
                for b in folder_pipeline(
                        args.data_dir, trainer.per_host_batch,
                        image_size=args.image_size, train=True,
                        epoch_seed=epoch * 131 + n,
                        shard_index=env.global_rank,
                        shard_count=trainer.world_size):
                    if len(b["label"]) != trainer.per_host_batch:
                        continue  # ragged tail
                    yield b
                    n += 1
                    if n >= args.steps_per_epoch:
                        return
        else:
            for step in range(args.steps_per_epoch):
                full = resnet.synthetic_image_batch(
                    args.total_batch_size, image_size=args.image_size,
                    num_classes=args.num_classes,
                    seed=epoch * 100000 + step)
                yield trainer.local_batch_slice(full)

    def eval_batches():
        if args.eval_dir:
            if args.loader == "native":
                from edl_tpu.data.native_loader import (
                    native_image_folder_pipeline as folder_pipeline)
            else:
                from edl_tpu.data.input_pipeline import (
                    image_folder_pipeline as folder_pipeline)
            return folder_pipeline(
                args.eval_dir, args.total_batch_size,
                image_size=args.image_size, train=False)
        return (resnet.synthetic_image_batch(
            args.total_batch_size, image_size=args.image_size,
            num_classes=args.num_classes, seed=2**31 - 1 - i)
            for i in range(args.eval_steps))

    from edl_tpu.utils.errors import PreemptedError

    loss = loss_arr = host_batch = None
    accs = None
    imgs_seen = 0
    t_start = time.perf_counter()
    try:
        for epoch in range(start_epoch, args.epochs):
            trainer.begin_epoch(epoch)
            if epoch == args.epochs - 1:
                # after begin_epoch: it reports RUNNING, which would
                # clobber the scale-out-stopping NEARTHEEND verdict
                trainer.report_status(ts.TrainStatus.NEARTHEEND)
            t_epoch = time.perf_counter()
            for step, host_batch in enumerate(host_batches(epoch)):
                loss_arr = trainer.train_step(host_batch)
                loss = float(loss_arr)
                imgs_seen += args.total_batch_size
                if (step + 1) % args.fetch_steps == 0:
                    dt = time.perf_counter() - t_epoch
                    print("epoch %d step %d loss %.4f  %.1f img/s"
                          % (epoch, step + 1, loss,
                             args.total_batch_size * (step + 1) / dt),
                          flush=True)
            trainer.end_epoch(save=True)
            if epoch == start_epoch and args.prewarm_worlds:
                trainer.prewarm_resize_compiles(
                    [int(w) for w in args.prewarm_worlds.split(",")
                     if w], block=False)
            if evaluator is not None:
                # rank-0 eval, reference parity: train_with_fleet.py:573-610.
                # device_get first: the train state is sharded over the GLOBAL
                # mesh and a single-rank jit over it would touch devices this
                # process cannot address in multi-host runs
                import jax as _jax
                host_params = _jax.device_get(trainer.train_state["params"])
                host_extra = _jax.device_get(trainer.extra_state)
                accs = evaluator.evaluate(host_params, host_extra,
                                          eval_batches())
                print("epoch %d eval: %s" % (epoch, accs), flush=True)
    except PreemptedError as e:
        # emergency checkpoint already written; exit with the restart
        # convention code (liveft's exit-101) so supervisors restart us
        print("preempted: %s" % e, flush=True)
        return 101

    trainer.report_status(ts.TrainStatus.SUCCEED)
    wall = time.perf_counter() - t_start
    # benchmark-log emission (reference train_with_fleet.py:642-658)
    result = {
        "model": "ResNet%d_vd" % args.depth,
        "final_loss": loss,
        "steps": trainer.global_step,
        "world": trainer.world_size,
        "imgs_per_sec": round(imgs_seen / wall, 1),
        "resumed": resumed,
    }
    # where the run really happened: the device identity as JAX reports
    # it, this incarnation's compile/first-step stamps, and — from the
    # same placement train_step uses — which devices held the batch and
    # the loss, so "everything on the first chip" cannot read as dp=N
    import jax

    from edl_tpu.parallel.costmodel import device_identity
    result.update(device_identity())
    timing = trainer.resize_timing
    result.update({k: round(timing[k], 3)
                   for k in ("compile_s", "first_step_s", "restore_s")
                   if k in timing})
    if loss_arr is not None:
        shards = trainer.place_batch(
            {"label": host_batch["label"]})["label"].addressable_shards
        result.update({
            "batch_devices": len({s.device.id for s in shards}),
            "per_device_batch": sorted({s.data.size for s in shards}),
            "loss_devices": len(loss_arr.sharding.device_set),
            "device_bytes_in_use": [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.local_devices()],
        })
    if accs:
        result.update({"eval_" + k: v for k, v in accs.items()})
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
