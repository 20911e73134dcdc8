"""What share of tokens is sent to another set of experts when the router
reads the program's bf16 activations instead of the reference's float32
ones: the discrete part of the distance `correct` measures in a cell whose
layers route (its traffic file's `limits_from` quotes it). First layer
only, where both sides read the same embedding; deeper layers inherit
their inputs' differences as well.

    python3 benchmark/tools/route_flips.py --workload <name> --seeds 1,2,...
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from benchmark.lib import harness
    _, cell, cfg, job = harness.cell_spec(args.workload)
    ref = harness.load_module("reference", cell["config"])
    fam = harness.load_module("program", cfg["family"])
    from edl_tpu.parallel import moe

    @jax.jit
    def shares(key):
        w = ref.init_weights(cfg, jax.random.fold_in(key, 0))
        ids = fam.make_batch(cfg, job, jax.random.fold_in(key, 1),
                             job["batch_per_chip"])["input_ids"].reshape(-1)
        x = w["embed"][ids]
        lw = ref.layer_weights(w, 0)
        h = ref._rms(x, lw["g1"], cfg["rms_norm_eps"])
        want, _ = ref.route(h, lw["w_r"], cfg)
        # the program: bf16 embedding rows, float32 norm statistics, a
        # bf16 result, float32 scores (models/sparse_decoder.py)
        hb = ref._rms(x.astype(jnp.bfloat16).astype(jnp.float32), lw["g1"],
                      cfg["rms_norm_eps"]).astype(jnp.bfloat16)
        got, _ = moe.route_top_k(hb, lw["w_r"],
                                 cfg["moe_num_active_primary_experts"])
        same = jnp.all(jnp.sort(got, -1) == jnp.sort(want, -1), axis=-1)
        first, held = cfg["first_expert"], cfg["moe_num_primary_experts"]
        here = lambda i: jnp.sort(jnp.where(  # noqa: E731
            jnp.logical_and(i >= first, i < first + held), i, -1), -1)
        same_here = jnp.all(here(got) == here(want), axis=-1)
        return 1.0 - jnp.mean(same), 1.0 - jnp.mean(same_here)

    for seed in [int(s) for s in args.seeds.split(",") if s]:
        any_, held = shares(harness.key_from_seed(seed))
        print(json.dumps({"seed": seed, "device": jax.devices()[0].platform,
                          "tokens_with_another_expert_set": float(any_),
                          "tokens_with_another_held_set": float(held)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
