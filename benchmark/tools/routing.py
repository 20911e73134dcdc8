"""What the held experts of a routed cell serve, layer by layer and step by
step, at the cell's own size: the rows of the first and of the last step,
their share of the expectation under even routing, the worst
`load_max` over the mean load, rows moved over rows served, and how many
(layer, step) pairs sent the token side of the expert layer
(`edl_tpu/parallel/moe.py:_sum_at_tokens`) down its whole-size branch.
A cell's rate follows these since PR 47 (PERF.md section 6), so this is
where to look when its runs spread by the seed. Where the layers select
their keys (`rows_off_count`, `pairs_kept`), also every step's rows, by
layer, that kept another number of keys than min(position + 1, top-k),
and the first and last step's share of causal pairs kept: when exact ties
at a threshold set in as the one staged batch is learnt.

    python3 benchmark/tools/routing.py --workload <name> --seeds 1,2,... \\
        [--steps 70] [--set embedding_initializer_range=0.02,...]

`--set` overrides numbers of the configuration for this reading only (the
weights' ranges of `assumed.weights`, to read what another seeding does).
One process, the trainer's own step; counters are read back after every
step, so no time here is a metric.
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def read_steps(run, kind, steps):
    """One seed's summary over `steps` steps from the seed: each step's
    rows by layer are the differences of the program's running sums."""
    import jax
    import numpy as np
    from edl_tpu.parallel import moe
    j = kind.make_job(run)
    model = j["fam"].build_model(j["cfg"], j["job"])
    # a model trained by diffusion over blocks routes two copies of a row
    copies = 2 if "block_length" in j["cfg"] else 1
    choices = (j["batch"]["input_ids"].size * copies
               * model.experts_per_token)
    even = choices * model.experts_held / float(model.num_experts)
    trainer = kind.make_trainer(run, j)
    names = ("rows_held", "rows_moved", "rows_dropped")
    tokens = j["batch"]["input_ids"].shape
    try:
        batch = trainer.place_batch(j["batch"])
        seen, last = [], None
        for _ in range(steps):
            jax.block_until_ready(trainer.train_step(batch))
            now = {k: np.asarray(v, dtype=np.float64) for k, v in
                   jax.device_get(
                       trainer.train_state["extra"]["counters"]).items()}
            names = names + tuple(n for n in ("rows_off_count", "pairs_kept")
                                  if n in now and n not in names)
            seen.append({n: now[n] - (last[n] if last else 0.0)
                         for n in names})
            last = now
    finally:
        trainer.close()
    held = np.stack([s["rows_held"] for s in seen])      # [steps, layers]
    moved = np.stack([s["rows_moved"] for s in seen])
    whole = moved * moe.SCATTER_ROWS_PER_GATHERED > choices
    mean_load = last["load_mean"] / steps
    selection = {}
    if "rows_off_count" in seen[0]:
        causal = tokens[0] * tokens[1] * (tokens[1] + 1) / 2.0
        selection = {
            "rows_off_by_step": [s["rows_off_count"].tolist() for s in seen],
            "pairs_kept_share_first": (seen[0]["pairs_kept"]
                                       / causal).tolist(),
            "pairs_kept_share_last": (seen[-1]["pairs_kept"]
                                      / causal).tolist()}
    return dict(selection, **{
        "seed": run.seed, "steps": steps, "choices_a_step": choices,
        "rows_even_routing": even,
        "rows_first_step": held[0].tolist(),
        "rows_last_step": held[-1].tolist(),
        "share_of_even_first": (held[0] / even).tolist(),
        "share_of_even_last": (held[-1] / even).tolist(),
        "rows_a_step_mean": float(held.sum(1).mean()),
        "load_max_over_mean": float(np.max(
            last["load_max"] / np.where(mean_load > 0, mean_load, np.inf))),
        "rows_moved_over_served": float(moved.sum() / held.sum()),
        "rows_dropped": float(sum(s["rows_dropped"].sum() for s in seen)),
        "whole_size_layer_steps": int(whole.sum()),
        "layer_steps": int(whole.size),
        "whole_size_by_layer": whole.sum(0).tolist(),
    })


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=70)
    ap.add_argument("--set", default="", dest="overrides")
    ap.add_argument("--cpu_tiny", action="store_true")
    ap.add_argument("--tag", default=None)
    args = ap.parse_args(argv)
    from benchmark.lib import harness
    harness.enable_compile_cache(args.cpu_tiny)
    overrides = {k: float(v) for k, v in (
        kv.split("=") for kv in args.overrides.split(",") if kv)}
    rows = []
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        run = harness.Run(argparse.Namespace(
            workload=args.workload, seed=seed, seconds=0, trace=0,
            cpu_tiny=args.cpu_tiny), time.monotonic())
        run.config = dict(run.config, **overrides)
        run.claim_devices()
        kind = harness.load_module("kinds", run.traffic["kind"])
        rows.append(dict(read_steps(run, kind, args.steps),
                         overrides=overrides))
        print(json.dumps(rows[-1]), flush=True)
        del run
        gc.collect()
    out = os.path.join(harness.ROOT, "chiprun_out", "routing-%s.json"
                       % (args.tag or args.workload))
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
