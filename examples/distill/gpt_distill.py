"""Sequence-level knowledge distillation for a causal LM.

The LM counterpart of examples/distill/resnet_distill.py (reference
soft-label pattern: example/distill/resnet/train_with_fleet.py:103-104,
445-449, applied per position): a student GPT trains against the
per-position next-token distributions of a GPT teacher served by
`edl_tpu.distill.teacher_server --model gpt`, wired through the
DistillReader (fixed or discovered teacher fleet).

Loss = (1-w) * hard next-token CE + w * per-position soft CE against
the teacher's probs (positions 0..L-2 predict token t+1, matching the
teacher's alignment).

Bring-up (scripted in tests/test_examples_standalone.py):
  1. store server, 2. gpt teacher(s) + registry, 3. discovery server,
  4. this student.
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
    from edl_tpu.models import gpt
    from edl_tpu.runtime.trainer import ElasticTrainer

    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--steps_per_epoch", type=int, default=8)
    p.add_argument("--total_batch_size", type=int, default=8)
    p.add_argument("--seq_len", type=int, default=16)
    p.add_argument("--vocab_size", type=int, default=64)
    p.add_argument("--distill_weight", type=float, default=0.5)
    p.add_argument("--teachers", default="",
                   help="comma list of fixed teacher endpoints")
    p.add_argument("--discovery", default="",
                   help="discovery server endpoint (dynamic teachers)")
    p.add_argument("--service_name", default="gpt_teacher")
    p.add_argument("--require_num", type=int, default=1)
    args = p.parse_args(argv)

    model = gpt.Gpt(num_layers=2, d_model=64, num_heads=4, mlp_dim=128,
                    vocab_size=args.vocab_size,
                    max_len=max(args.seq_len, 16), dtype=jnp.float32)
    model, params, _ = gpt.create_model_and_loss(
        model=model, dummy_seq=args.seq_len)

    w = args.distill_weight

    def loss_fn(params, batch, rng):
        ids = batch["input_ids"]
        logits = model.apply({"params": params}, ids)
        hard = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], ids[:, 1:]).mean()
        # teacher probs share the student's alignment: position t
        # predicts token t+1; the last position has no target
        tprobs = batch["soft_label"].astype(jnp.float32)[:, :-1]
        soft = optax.softmax_cross_entropy(logits[:, :-1], tprobs).mean()
        return (1 - w) * hard + w * soft

    trainer = ElasticTrainer(
        loss_fn, params, optax.adamw(1e-3),
        total_batch_size=args.total_batch_size)
    trainer.install_preemption_handler()

    def gen():
        for step in range(args.steps_per_epoch):
            b = gpt.synthetic_lm_batch(
                args.total_batch_size, seq_len=args.seq_len,
                vocab_size=args.vocab_size, seed=step)
            # label slot unused (the hard loss shifts input_ids itself)
            yield b["input_ids"], np.zeros(
                (args.total_batch_size,), np.int32)

    dr = DistillReader(ins=["input_ids"], predicts=["probs"])
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
            for input_ids, _label, probs in dr():
                loss = float(trainer.train_step(trainer.local_batch_slice({
                    "input_ids": np.asarray(input_ids),
                    "soft_label": np.asarray(probs),
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
