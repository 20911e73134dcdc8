"""How far the program's bf16 CHOICE of keys lies from the float32
reference's, and what that does to the gradient, in a cell whose layers
select their keys with a learned indexer (its traffic file's `limits_from`
quotes both):

- layer 0, where both sides read the same embedding: the share of query
  rows whose set of kept keys differs, and the share of kept (query, key)
  pairs that differ;
- `grad_rel_err`, the number `correct` compares, by GROUP of leaves — the
  indexer's tensors are 5% of the parameters and would hide in one norm
  over all of them.

    python3 benchmark/tools/selection_flips.py --workload <name> --seeds 1,2,...
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

GROUPS = (("indexer", ("index_query", "index_key", "index_weight")),
          ("attention", ("query", "key", "value", "out", "norm_query",
                         "norm_key", "norm_attn")),
          ("router", ("router",)),
          ("experts", ("experts_gate_up", "experts_down", "norm_moe")),
          ("embedding_and_head", ("embed", "lm_head", "norm_final")))
BLOCK = 256


def flips(run, j):
    """Layer 0's choice, program against reference, by blocks of queries."""
    import jax
    import jax.numpy as jnp
    from edl_tpu.ops import sparse_attention
    cfg, ref, fam = j["cfg"], j["ref"], j["fam"]
    one = dict(cfg, num_hidden_layers=1)
    topk = cfg["sa_config"]["topk"]

    @jax.jit
    def shares(w, params, batch):
        ids = batch["input_ids"]
        t = ids.shape[1]
        model = fam.build_model(one, {"remat": False})
        _, state = model.apply(
            {"params": {k: v for k, v in params.items()
                        if not k.startswith("layer_") or k == "layer_0"}},
            ids, mutable=["intermediates"])
        qi, ki, wi, tau = state["intermediates"]["layer_0"]["select"][0]
        lw = ref.layer_weights(w, 0)
        h = ref._rms(w["embed"][ids], lw["g1"], cfg["rms_norm_eps"])
        _, _, _, rqi, rki, rwi = ref.projections(h, lw, cfg)

        def block(args):
            qi_b, wi_b, tau_b, rqi_b, rwi_b, pos = args
            got = jnp.logical_and(
                pos[:, None] >= jnp.arange(t)[None, :],
                sparse_attention.index_scores(qi_b, ki, wi_b)
                >= tau_b[..., None])
            want = ref.selection(ref.index_scores(rqi_b, rki, rwi_b, cfg),
                                 pos, topk)
            differ = jnp.logical_xor(got, want)
            return (differ.any(-1).sum(), jnp.logical_and(
                differ, want).sum(), want.sum())

        blk = BLOCK if t % BLOCK == 0 else t
        cut = lambda x: x.reshape((x.shape[0], t // blk, blk)  # noqa: E731
                                  + x.shape[2:]).swapaxes(0, 1)
        rows, pairs, kept = jax.lax.map(block, (
            cut(qi), cut(wi), cut(tau), cut(rqi), cut(rwi),
            jnp.arange(t).reshape(t // blk, blk)))
        return (rows.sum() / (ids.shape[0] * t),
                pairs.sum() / kept.sum())

    rows, pairs = shares(j["w"], j["params"], j["batch"])
    return {"rows_with_another_set": float(rows),
            "kept_pairs_not_kept_by_the_program": float(pairs)}


def grad_errors(run, j, kind):
    """`grad_rel_err` of step 1 by group of leaves, as `correct` reads it:
    the program's gradient from the optimizer's first moment."""
    import jax
    import jax.numpy as jnp
    from benchmark.lib import optim
    job = j["job"]
    trainer = kind.make_trainer(run, j)
    try:
        loss = trainer.train_step(trainer.place_batch(j["batch"]))
        jax.block_until_ready(loss)
        scale = optim.moment_scale(job["optimizer"])
        moment = jax.tree_util.tree_map(
            lambda x: x / scale, optim.first_moment(
                trainer.train_state["opt_state"],
                trainer.train_state["params"]))
        _, g0 = kind.reference_steps(j, 1)
        sq = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: jnp.stack([jnp.sum(jnp.square(
                x.astype(jnp.float32) - y)), jnp.sum(jnp.square(y))]),
            a, b))(moment, g0)
    finally:
        trainer.close()
    flat = {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_leaves_with_path(sq)}
    out = {}
    for group, names in GROUPS + (("all", None),):
        picked = [v for k, v in flat.items() if names is None or any(
            "'%s'" % n in k for n in names)]
        num, den = (sum(float(v[i]) for v in picked) for i in (0, 1))
        out["grad_rel_err_" + group] = (num / den) ** 0.5
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--cpu_tiny", action="store_true")
    args = ap.parse_args(argv)
    from benchmark.lib import harness
    harness.enable_compile_cache(args.cpu_tiny)
    import jax
    rows = []
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        run = harness.Run(argparse.Namespace(
            workload=args.workload, seed=seed, seconds=0, trace=0,
            cpu_tiny=args.cpu_tiny), time.monotonic())
        run.claim_devices()
        kind = harness.load_module("kinds", run.traffic["kind"])
        j = kind.make_job(run)
        row = dict(flips(run, j), seed=seed,
                   device=jax.devices()[0].platform)
        row.update(grad_errors(run, j, kind))
        rows.append(row)
        print(json.dumps(row), flush=True)
        del run, j
    out = os.path.join(harness.ROOT, "chiprun_out",
                       "selection_flips-%s.json" % args.workload)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
