"""ResNet student training against a fleet of TPU teacher servers.

Reference parity: example/distill/resnet/train_with_fleet.py — the student
wraps its reader in a DistillReader and adds a soft-label term to the loss
(reference :103-104,445-449); teachers are ResNeXt-class models served by
edl_tpu.distill.teacher_server instead of Paddle Serving.

Bring-up (see tests/test_examples_standalone.py for a scripted version):
  1. store server, 2. teacher(s) + registry, 3. discovery server,
  4. this student (fixed or dynamic teacher list).
"""

import argparse
import json
import sys


def main(argv=None):
    from edl_tpu.runtime.trainer import maybe_init_distributed
    maybe_init_distributed()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from edl_tpu.distill.distill_reader import DistillReader
    from edl_tpu.models import resnet
    from edl_tpu.runtime.trainer import ElasticTrainer

    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--steps_per_epoch", type=int, default=8)
    p.add_argument("--total_batch_size", type=int, default=16)
    p.add_argument("--image_size", type=int, default=32)
    p.add_argument("--num_classes", type=int, default=10)
    p.add_argument("--distill_weight", type=float, default=0.5)
    p.add_argument("--teachers", default="",
                   help="comma list of fixed teacher endpoints")
    p.add_argument("--discovery", default="",
                   help="discovery server endpoint (dynamic teachers)")
    p.add_argument("--service_name", default="resnet_teacher")
    p.add_argument("--require_num", type=int, default=2)
    args = p.parse_args(argv)

    model, params, extra, base_loss = resnet.create_model_and_loss(
        depth=18, num_classes=args.num_classes, image_size=args.image_size,
        dtype=jnp.float32)

    w = args.distill_weight

    def loss_fn(params, extra_state, batch, rng):
        logits, updated = model.apply(
            {"params": params, "batch_stats": extra_state["batch_stats"]},
            batch["image"], train=True, mutable=["batch_stats"])
        one_hot = jax.nn.one_hot(batch["label"], args.num_classes)
        hard = optax.softmax_cross_entropy(logits, one_hot).mean()
        teacher_probs = jax.nn.softmax(
            batch["soft_label"].astype(jnp.float32), axis=-1)
        soft = optax.softmax_cross_entropy(logits, teacher_probs).mean()
        return (1 - w) * hard + w * soft, \
            {"batch_stats": updated["batch_stats"]}

    trainer = ElasticTrainer(
        loss_fn, params, optax.sgd(0.05, momentum=0.9),
        total_batch_size=args.total_batch_size, extra_state=extra,
        has_aux=True)
    trainer.install_preemption_handler()

    def gen():
        for step in range(args.steps_per_epoch):
            b = resnet.synthetic_image_batch(
                args.total_batch_size, image_size=args.image_size,
                num_classes=args.num_classes, seed=step)
            yield b["image"], b["label"]

    dr = DistillReader(ins=["image"], predicts=["logits"])
    dr.set_batch_generator(gen)
    if args.discovery:
        dr.set_dynamic_teacher(args.discovery, args.service_name,
                               args.require_num)
    else:
        dr.set_fixed_teacher([e for e in args.teachers.split(",") if e])

    from edl_tpu.utils.errors import PreemptedError

    loss = None
    try:
        for epoch in range(args.epochs):
            trainer.begin_epoch(epoch)
            for image, label, soft_label in dr():
                loss = float(trainer.train_step(trainer.local_batch_slice({
                    "image": np.asarray(image),
                    "label": np.asarray(label),
                    "soft_label": np.asarray(soft_label),
                })))
            trainer.end_epoch(save=False)
            print("epoch %d loss %.4f" % (epoch, loss), flush=True)
    except PreemptedError as e:
        # emergency checkpoint written (when a checkpoint dir is
        # configured); exit-101 is the restart convention
        print("preempted: %s" % e, flush=True)
        dr.stop()
        return 101
    dr.stop()
    print(json.dumps({"final_loss": loss, "steps": trainer.global_step}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
