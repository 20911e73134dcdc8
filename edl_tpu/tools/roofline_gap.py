"""Measured-vs-predicted roofline gap bench: run the REAL trainer step
per (model, mesh) config, attribute the measured wall time to the cost
model's terms, and fit calibration constants the planner can load.

The cost model (:mod:`edl_tpu.parallel.costmodel`) predicts a per-config
step time as a breakdown {compute_s, hbm_s, bubble, dp_s, tp_s, pp_s,
ep_s}; nothing previously compared the trainer against it. This bench
closes the loop:

- **measured total**: the canonical train step (make_train_step /
  make_accum_step — the exact callables ElasticTrainer jits), donated
  buffers, jit with the trainer's shardings, timed over ``--iters``;
- **collective terms** (dp, tp): timed STANDALONE on the same mesh — a
  shard_map pmean of a gradient-sized tree for dp, an activation-sized
  all-reduce for tp — so their seconds can be subtracted out;
- **compute/hbm floor**: measured total minus the measured collective
  seconds. The model's floor is max(compute_s, hbm_s) * bubble, so the
  compute and hbm ratios BOTH report measured_floor/predicted_floor
  (the floor is attributed jointly; the ``exercised`` flag records
  which side the model predicts as binding);
- **unexercised terms** (an axis of size 1) report ratio 1.0 with
  ``exercised: false`` — present for every term, honest about which
  ones the config actually measured.

Calibration: achieved constants are fitted from the binding terms
(sustained tflops from a compute-bound floor, HBM GB/s from an
hbm-bound floor, ICI GB/s from the dp all-reduce wire time) and emitted
as a ``roofline_calib/v1`` record; ``--calib_out`` writes it to a file
that ``EDL_TPU_ROOFLINE_CALIB`` points the planner at
(costmodel.calibrated_chip — fail-open per field, so a CPU-measured
constant outside sanity bounds keeps the datasheet builtin).

Overlap sweep: configs with ``grad_accum > 1`` on a dp > 1 mesh are
timed with the delayed-reduction overlap schedule
(make_accum_step(overlap_axis=...)) on AND off; the ratio attribution
uses the off run (one XLA-inserted all-reduce per update — the cost
model's shape) and the ``overlap`` record reports the speedup.
``--remat`` sweeps the whole-loss recompute policy.

Usage:
    JAX_PLATFORMS=cpu python -m edl_tpu.tools.roofline_gap --micro
    python -m edl_tpu.tools.roofline_gap            # TPU, full shapes

Emits ONE JSON line (schema "roofline_gap/v1"):
    mode            micro | full
    platform, device_kind, device_count
                    the devices the step ran on, as JAX reports them
    chip_builtin    the datasheet constants predictions used (the
                    running chip's; --micro uses the named v5e target)
    configs         per-(model, mesh) records: mesh factors, world,
                    measured {total_s, floor_s, dp_s, tp_s},
                    predicted (the step_time_s breakdown),
                    ratios {compute, hbm, bubble, dp, tp, pp, ep},
                    exercised (same keys, bool),
                    tokens_per_sec_per_chip, overlap (or null)
    calibration     roofline_calib/v1 record (fitted chip constants)
    gpt_arc         the gpt tok/s/chip arc perf_accounting.py folds
                    into BENCH_BEST_TPU.json (TPU platforms only)
"""

import argparse
import json
import os
import sys
import time

# the bench runs jax in-process; micro mode pins the virtual-CPU world
# BEFORE the first import (full mode must keep the real TPU backend; a
# test harness that already initialized jax keeps its own device world)
if "jax" not in sys.modules and (
        "--micro" in sys.argv
        or os.environ.get("JAX_PLATFORMS", "") == "cpu"):
    from edl_tpu.utils.cpu_mesh import force_cpu_env
    force_cpu_env(os.environ, 8)

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from edl_tpu.parallel import costmodel
from edl_tpu.runtime.mesh import make_mesh
from edl_tpu.runtime.trainer import (make_accum_step, make_train_state,
                                     make_train_step)

RATIO_TERMS = ("compute", "hbm", "bubble", "dp", "tp", "pp", "ep")

# a measured term below this is timer noise, not a signal to fit against
_MIN_MEASURED_S = 1e-7

MICRO_CONFIGS = (
    # pure-dp gpt with accumulation: exercises the dp term AND the
    # overlap schedule (grad_accum 2 over dp 2)
    {"name": "gpt_dp2_accum2", "model": "gpt", "mesh": {"dp": 2},
     "total_batch": 8, "seq": 64, "grad_accum": 2,
     "model_kw": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                  "mlp_dim": 128, "vocab_size": 256, "max_len": 64}},
    # wider dp bert, single-shot step
    {"name": "bert_dp4", "model": "bert", "mesh": {"dp": 4},
     "total_batch": 8, "seq": 64, "grad_accum": 1,
     "model_kw": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                  "mlp_dim": 128, "vocab_size": 256, "max_len": 64}},
)

FULL_CONFIGS = (
    # the BENCH_BEST shape: gpt2-small-ish at the measured 59k config
    {"name": "gpt2s_dp_all", "model": "gpt", "mesh": {"dp": 0},
     "total_batch": 8, "seq": 1024, "grad_accum": 1,
     "model_kw": {"num_layers": 12, "d_model": 768, "num_heads": 12,
                  "mlp_dim": 3072, "vocab_size": 32000,
                  "max_len": 1024}},
    {"name": "gpt2s_dp_all_accum4", "model": "gpt", "mesh": {"dp": 0},
     "total_batch": 32, "seq": 1024, "grad_accum": 4,
     "model_kw": {"num_layers": 12, "d_model": 768, "num_heads": 12,
                  "mlp_dim": 3072, "vocab_size": 32000,
                  "max_len": 1024}},
    {"name": "bert_base_dp_all", "model": "bert", "mesh": {"dp": 0},
     "total_batch": 32, "seq": 512, "grad_accum": 1,
     "model_kw": {"num_layers": 12, "d_model": 768, "num_heads": 12,
                  "mlp_dim": 3072, "vocab_size": 30522,
                  "max_len": 512}},
)


def _build(cfg, dtype):
    """(params, loss_fn, batch, profile) for one config."""
    kw = dict(cfg["model_kw"], dtype=dtype)
    if cfg["model"] == "gpt":
        from edl_tpu.models import gpt as mod
        model = mod.gpt_tiny(**kw)
        _, params, loss_fn = mod.create_model_and_loss(
            model=model, dummy_seq=cfg["seq"])
        batch = mod.synthetic_lm_batch(cfg["total_batch"], cfg["seq"],
                                       kw["vocab_size"])
    else:
        from edl_tpu.models import bert as mod
        model = mod.bert_tiny(**kw)
        _, params, loss_fn = mod.create_model_and_loss(
            model=model, dummy_seq=cfg["seq"])
        batch = mod.synthetic_text_batch(cfg["total_batch"], cfg["seq"],
                                         kw["vocab_size"])
    profile = costmodel.transformer_profile(
        n_layers=kw["num_layers"], d_model=kw["d_model"],
        n_heads=kw["num_heads"], seq_len=cfg["seq"],
        vocab_size=kw["vocab_size"],
        dtype_bytes=2 if dtype == jnp.bfloat16 else 4,
        name=cfg["model"])
    return params, loss_fn, batch, profile


def _microbatch_major(batch, k):
    if k <= 1:
        return batch
    return jax.tree_util.tree_map(
        lambda x: np.reshape(x, (k, x.shape[0] // k) + x.shape[1:]),
        batch)


def _time_step(step, state, batch, rng, state_sh, batch_sh, repl,
               iters, warmup):
    jit_step = jax.jit(step, in_shardings=(state_sh, batch_sh, repl),
                       out_shardings=(state_sh, repl),
                       donate_argnums=(0,))
    for _ in range(warmup):
        state, loss = jit_step(state, batch, rng)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = jit_step(state, batch, rng)
    jax.block_until_ready((state, loss))
    return (time.perf_counter() - t0) / iters, float(loss)


def _time_allreduce(mesh, axes, tree, iters):
    """Wall seconds of ONE all-reduce of ``tree`` over ``axes`` on
    ``mesh`` — the standalone measurement of a collective term."""
    def f(t):
        return jax.tree_util.tree_map(
            lambda g: lax.pmean(g, axes), t)

    jf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                               check_vma=False))
    out = jf(tree)  # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jf(out)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run_config(cfg, iters, warmup, remat_policy, dtype, chip):
    factors = {"dp": 1, "tp": 1, "pp": 1, "ep": 1}
    factors.update(cfg["mesh"])
    if factors["dp"] == 0:  # 0 = all devices on the dp axis
        other = factors["tp"] * factors["pp"] * factors["ep"]
        factors["dp"] = max(1, jax.device_count() // other)
    world = factors["dp"] * factors["tp"] * factors["pp"] * factors["ep"]
    if world > jax.device_count():
        raise ValueError("config %s wants %d devices, have %d"
                         % (cfg["name"], world, jax.device_count()))
    mesh = make_mesh(devices=jax.devices()[:world],
                     **{k: v for k, v in factors.items() if v > 1})

    params, loss_fn, batch, profile = _build(cfg, dtype)
    tx = optax.adamw(1e-3)
    k = cfg["grad_accum"]
    # host copy: the timed step donates its state, so each run places a
    # fresh device tree from host memory
    host_state = jax.device_get(make_train_state(params, tx))
    batch = _microbatch_major(batch, k)
    rng = jax.random.PRNGKey(0)

    repl = NamedSharding(mesh, P())
    state_sh = jax.tree_util.tree_map(lambda _: repl, host_state)
    row_spec = "dp" if factors["dp"] > 1 else None
    batch_sh = NamedSharding(
        mesh, P(None, row_spec) if k > 1 else P(row_spec))
    place = lambda: (jax.device_put(host_state, state_sh),
                     jax.device_put(batch, batch_sh))

    if k > 1:
        step_off = make_accum_step(loss_fn, tx, k,
                                   remat_policy=remat_policy)
    else:
        step_off = make_train_step(loss_fn, tx,
                                   remat_policy=remat_policy)
    st, bt = place()
    total_s, loss = _time_step(step_off, st, bt, rng, state_sh,
                               batch_sh, repl, iters, warmup)

    overlap = None
    if k > 1 and factors["dp"] > 1:
        step_on = make_accum_step(loss_fn, tx, k,
                                  remat_policy=remat_policy,
                                  overlap_axis="dp", mesh=mesh)
        st, bt = place()
        on_s, _ = _time_step(step_on, st, bt, rng, state_sh,
                             batch_sh, repl, iters, warmup)
        overlap = {"off_s": round(total_s, 6), "on_s": round(on_s, 6),
                   "speedup": round(total_s / on_s, 4) if on_s else 0.0}

    # standalone collective timings on the same mesh
    measured_dp_s = 0.0
    if factors["dp"] > 1:
        grads_like = jax.device_put(
            jax.tree_util.tree_map(jnp.zeros_like, params), repl)
        measured_dp_s = _time_allreduce(mesh, ("dp",), grads_like,
                                        iters)
    measured_tp_s = 0.0
    if factors["tp"] > 1:
        tokens_local = cfg["total_batch"] * cfg["seq"] // factors["dp"]
        act = jnp.zeros((tokens_local, profile["d_model"]), dtype)
        # 4 all-reduces per layer (2 fwd + 2 bwd)
        one = _time_allreduce(mesh, ("tp",), act, iters)
        measured_tp_s = 4.0 * profile["n_layers"] * one

    pred = costmodel.step_time_s(factors, profile, cfg["total_batch"],
                                 chip=chip)
    pred_floor = max(pred["compute_s"], pred["hbm_s"]) * pred["bubble"]
    measured_floor = max(total_s - measured_dp_s - measured_tp_s,
                         _MIN_MEASURED_S)

    def ratio(measured, predicted):
        return round(measured / predicted, 4) if predicted \
            > _MIN_MEASURED_S else 1.0

    floor_ratio = ratio(measured_floor, pred_floor)
    compute_bound = pred["compute_s"] >= pred["hbm_s"]
    ratios = {
        "compute": floor_ratio,
        "hbm": floor_ratio,
        "bubble": 1.0,  # needs pp > 1 to separate from the floor
        "dp": ratio(measured_dp_s, pred["dp_s"])
        if factors["dp"] > 1 else 1.0,
        "tp": ratio(measured_tp_s, pred["tp_s"])
        if factors["tp"] > 1 else 1.0,
        "pp": 1.0,
        "ep": 1.0,
    }
    exercised = {
        "compute": compute_bound,
        "hbm": not compute_bound,
        "bubble": factors["pp"] > 1,
        "dp": factors["dp"] > 1,
        "tp": factors["tp"] > 1,
        "pp": factors["pp"] > 1,
        "ep": factors["ep"] > 1,
    }

    tokens = cfg["total_batch"] * cfg["seq"]
    tok_s_chip = tokens / total_s / world if total_s else 0.0

    # achieved constants for the calibration fit (only the terms this
    # config actually measured; the caller merges across configs)
    fit = {}
    flops = 3.0 * profile["flops_per_token"] * tokens
    if compute_bound and measured_floor > _MIN_MEASURED_S:
        fit["bf16_tflops"] = flops / world / measured_floor / 1e12
    if not compute_bound and measured_floor > _MIN_MEASURED_S:
        shard = factors["tp"] * factors["pp"] * factors["ep"]
        fit["hbm_gbps"] = 3.0 * profile["param_bytes"] / shard \
            / measured_floor / 1e9
    if factors["dp"] > 1 and measured_dp_s > _MIN_MEASURED_S:
        grad_bytes = profile["param_bytes"]
        wire = 2.0 * grad_bytes * (factors["dp"] - 1) / factors["dp"]
        fit["ici_gbps"] = wire / measured_dp_s / 1e9

    return {
        "name": cfg["name"],
        "model": cfg["model"],
        "mesh": {a: s for a, s in factors.items() if s > 1} or {"dp": 1},
        "world": world,
        "total_batch": cfg["total_batch"],
        "seq_len": cfg["seq"],
        "grad_accum": k,
        "remat_policy": remat_policy,
        "iters": iters,
        "loss": round(loss, 4),
        "measured": {"total_s": round(total_s, 9),
                     "floor_s": round(measured_floor, 9),
                     "dp_s": round(measured_dp_s, 9),
                     "tp_s": round(measured_tp_s, 9)},
        "predicted": {kk: (round(vv, 12) if kk != "bubble" else vv)
                      for kk, vv in pred.items()},
        "ratios": ratios,
        "exercised": exercised,
        "tokens_per_sec_per_chip": round(tok_s_chip, 1),
        "overlap": overlap,
    }, fit


def _merge_fits(fits):
    """Best sustained constant per field across configs (max: the chip
    demonstrated at least this)."""
    chip = {}
    for fit in fits:
        for field, val in fit.items():
            if np.isfinite(val) and val > 0:
                chip[field] = max(chip.get(field, 0.0), val)
    return {field: round(val, 3) for field, val in chip.items()}


def main(argv=None):
    p = argparse.ArgumentParser(
        "measured-vs-predicted roofline gap per (model, mesh) config")
    p.add_argument("--micro", action="store_true",
                   help="CPU smoke shapes (tier-1 schema guard)")
    p.add_argument("--iters", type=int, default=0,
                   help="timed iterations per config (0 = mode default)")
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--configs", default="",
                   help="comma list of config names (default: all for "
                        "the mode)")
    p.add_argument("--remat", default=None,
                   choices=[None, "full", "dots", "dots_no_batch"],
                   help="whole-loss remat policy swept into the step")
    p.add_argument("--calib_out", default="",
                   help="write the roofline_calib/v1 record here "
                        "(point EDL_TPU_ROOFLINE_CALIB at it)")
    args = p.parse_args(argv)

    device = costmodel.device_identity()
    platform = device["platform"]
    if args.micro:
        # the schema guard runs on any backend: its ratios are against
        # the planner's named target chip, and the record says which
        # platform produced the "measured" side
        chip = costmodel.CHIP_V5E
    else:
        # a real measured-vs-predicted gap is against the RUNNING
        # chip's published peaks; an unknown device_kind raises
        chip = costmodel.chip_peaks(device["device_kind"])
    configs = MICRO_CONFIGS if args.micro else FULL_CONFIGS
    if args.configs:
        want = {n.strip() for n in args.configs.split(",") if n.strip()}
        configs = [c for c in configs if c["name"] in want]
    iters = args.iters or (2 if args.micro else 20)
    dtype = jnp.float32 if platform == "cpu" else jnp.bfloat16

    rc = 0
    records, fits = [], []
    for cfg in configs:
        try:
            rec, fit = run_config(cfg, iters, args.warmup, args.remat,
                                  dtype, chip)
            records.append(rec)
            fits.append(fit)
        except Exception as e:  # noqa: BLE001
            records.append({"name": cfg["name"], "error": repr(e)})
            rc = 1

    calibration = {
        "schema": costmodel.CALIB_SCHEMA,
        "platform": platform,
        "mode": "micro" if args.micro else "full",
        "fitted_from": [r["name"] for r in records if "error" not in r],
        "measured": time.strftime("%Y-%m-%d"),
        "chip": dict({"name": "%s+fit" % platform}, **_merge_fits(fits)),
    }

    gpt_arc = None
    for rec in records:
        if rec.get("model") == "gpt" and "error" not in rec:
            gpt_arc = {
                "metric": "gpt_train_tokens_per_sec_per_chip",
                "value": rec["tokens_per_sec_per_chip"],
                "unit": "tok/s/chip",
                "platform": platform,
                "config": rec["name"],
                "measured": time.strftime("%Y-%m-%d"),
            }
            break

    doc = {
        "schema": "roofline_gap/v1",
        "mode": "micro" if args.micro else "full",
        "platform": platform,
        "device_kind": device["device_kind"],
        "device_count": device["device_count"],
        "chip_builtin": dict(chip),
        "configs": records,
        "calibration": calibration,
        "gpt_arc": gpt_arc,
    }
    if args.calib_out:
        with open(args.calib_out, "w") as f:
            json.dump(calibration, f)
    print(json.dumps(doc), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
