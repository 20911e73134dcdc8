"""Hardware-faithful static performance accounting — the TPU compiler's
own cost model, WITHOUT a chip.

Why this exists: every perf lever in this repo (flash attention, remat,
tp/sp/pp sharding) ultimately makes a claim about flops, HBM bytes, or
live memory on a v5e. Measuring them needs a chip; but libtpu ships
the full production TPU compiler, and
``jax.experimental.topologies.get_topology_desc("v5e:2x2", "tpu")``
yields a deviceless topology that ``jit(step).lower(...).compile()``
compiles against CLIENT-SIDE — the real XLA-TPU/Mosaic pipeline, whose
``cost_analysis()`` (flops, bytes accessed) and ``memory_analysis()``
(temp/argument/output bytes) ARE the hardware cost model. A lever is
then "statically accounted on the production compiler" before it gets
chip time, and `tests/test_perf_accounting.py` pins the deltas so a
lever cannot silently regress. A static account is never a measurement.

Role parity: the reference publishes a measured perf table
(/root/reference/README.md:81-85) as its performance contract;
benchmark/run.py is this repo's live-measurement side, this tool is the
static side.

Run:  python -m edl_tpu.tools.perf_accounting --platform tpu \
          --out PERF_ACCOUNTING.json
(the CLI pins its own host process to the CPU backend: the compile is
deviceless and must not claim a chip; ``--platform cpu`` is the smoke).
"""

import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
import optax

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from edl_tpu.parallel.costmodel import CHIP_V5E


def scrub_env_for_cli():
    """CLI-only: every account compiles against a DEVICELESS topology,
    so the host process is pinned to the CPU backend — a static account
    must never claim (or wait for) a real chip. Deliberately NOT run at
    import: importing this module to reuse a helper must never
    reconfigure the host process."""
    from edl_tpu.utils.cpu_mesh import force_cpu_env
    force_cpu_env(os.environ, 1)
    jax.config.update("jax_platforms", "cpu")

# the named target chip of the static accounts (costmodel.CHIP_PEAKS),
# for mapping byte deltas to expected ms
V5E_HBM_GBPS = CHIP_V5E["hbm_gbps"]
V5E_BF16_TFLOPS = CHIP_V5E["bf16_tflops"]

# reference baselines for the BENCH_BEST_TPU.json vs_baseline column
# (value / baseline, the resnet record's convention): gpt's is the r5b
# measured 59,157.8 tok/s/chip — the "flat 59k" every later measurement
# is judged against
BASELINES = {"gpt": 59157.8}


def _default_best_path():
    return os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                        "BENCH_BEST_TPU.json")


def fold_roofline_gap(gap_doc, best_path, force=False):
    """Fold a ``roofline_gap/v1`` gpt tok/s arc into the BENCH_BEST
    pointer file: take the max of the existing and measured value, stamp
    the source, and ALWAYS recompute vs_baseline from the known gpt
    baseline — the headline record can no longer sit at a silent 0.0.

    Refuses non-TPU arcs unless ``force`` (a CPU micro run must never
    masquerade as a TPU best). Returns (changed, message)."""
    if not isinstance(gap_doc, dict) \
            or gap_doc.get("schema") != "roofline_gap/v1":
        return False, "not a roofline_gap/v1 doc"
    arc = gap_doc.get("gpt_arc")
    if not arc:
        return False, "no gpt arc in the gap doc"
    platform = arc.get("platform")
    if platform != "tpu" and not force:
        return False, ("gpt arc measured on %r — refusing to fold a "
                       "non-TPU number into %s (force overrides)"
                       % (platform, os.path.basename(best_path)))
    with open(best_path) as f:
        best = json.load(f)
    rec = best.setdefault("gpt", {
        "metric": "gpt2s_train_tokens_per_sec_per_chip",
        "value": 0.0, "unit": "tok/s/chip",
        "measured": "", "source": ""})
    changed = []
    value = float(arc.get("value") or 0.0)
    if value > float(rec.get("value") or 0.0):
        rec["value"] = value
        rec["measured"] = arc.get("measured", rec.get("measured", ""))
        rec["source"] = "roofline_gap/v1 %s (%s)" % (
            arc.get("config", "?"), platform)
        changed.append("value -> %.1f" % value)
    baseline = float(rec.get("baseline") or BASELINES["gpt"])
    want_vs = round(float(rec["value"]) / baseline, 3) if baseline else 0.0
    if rec.get("vs_baseline") != want_vs or rec.get("baseline") != baseline:
        rec["vs_baseline"] = want_vs
        rec["baseline"] = baseline
        changed.append("vs_baseline -> %.3f" % want_vs)
    if changed:
        with open(best_path, "w") as f:
            json.dump(best, f, indent=1)
            f.write("\n")
        return True, "gpt record updated: %s" % "; ".join(changed)
    return False, "gpt record already current (value %.1f)" % rec["value"]


def recompute_vs_baseline(best_path):
    """Backfill vs_baseline for records stuck at 0.0/absent whose model
    has a known baseline. Returns the list of models fixed."""
    with open(best_path) as f:
        best = json.load(f)
    fixed = []
    for model, rec in best.items():
        if model not in BASELINES:
            continue
        baseline = float(rec.get("baseline") or BASELINES[model])
        want = round(float(rec.get("value") or 0.0) / baseline, 3)
        if rec.get("vs_baseline") in (0.0, None) \
                or rec.get("baseline") != baseline:
            rec["vs_baseline"] = want
            rec["baseline"] = baseline
            fixed.append(model)
    if fixed:
        with open(best_path, "w") as f:
            json.dump(best, f, indent=1)
            f.write("\n")
    return fixed


def spec_like(tree, sharding=None):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype,
                                       sharding=sharding), tree)


def v5e_devices():
    """Deviceless v5e devices from libtpu's own topology description —
    no chips needed. v5e:2x2 is the smallest layout the default
    host bounds accept; accounts slice what they need from the 4."""
    from jax.experimental import topologies
    td = topologies.get_topology_desc(topology_name="v5e:2x2",
                                      platform="tpu")
    return list(td.devices)


def _analyze(compiled):
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
    }


def compile_stats(fn, arg_specs, devices, in_shardings=None,
                  out_shardings=None, donate_argnums=(), mesh=None):
    """AOT-compile ``fn`` for ``devices`` and return the compiler's own
    account of it. The devices may be topology (deviceless) devices.
    ``mesh`` overrides the default 1-D ("dp",) mesh for model-parallel
    accounts; ``in_shardings``/``out_shardings`` are callables of the
    mesh (or ready pytrees when ``mesh`` is given explicitly)."""
    if mesh is None:
        mesh = Mesh(np.array(devices).reshape(len(devices)), ("dp",))
    repl = NamedSharding(mesh, P())

    def resolve(sh):
        return sh(mesh) if callable(sh) else sh

    kw = {"in_shardings": (resolve(in_shardings) if in_shardings
                           is not None else
                           jax.tree_util.tree_map(lambda _: repl,
                                                  tuple(arg_specs)))}
    if out_shardings is not None:
        kw["out_shardings"] = resolve(out_shardings)
    jitted = jax.jit(fn, donate_argnums=donate_argnums, **kw)
    t0 = time.time()
    compiled = jitted.lower(*arg_specs).compile()
    out = _analyze(compiled)
    out["compile_s"] = round(time.time() - t0, 1)
    return out


# -- account 2: attention — dense vs flash/blockwise ----------------------


def attention_account(devices, seq, impl, batch=1, heads=12, dim=64,
                      grad=True, interpret=False):
    """Forward(+backward) attention at GPT-2s head shape. ``impl``:
    dense (materializes the s x s scores), flash (the Pallas kernel —
    Mosaic compiles it AOT like any other op; ``interpret=True`` for
    CPU, where the custom-vjp backward runs the same two Pallas
    kernels of _flash_bwd in the interpreter), block (the lax.scan
    blockwise reference, the kernel's semantic twin)."""
    from edl_tpu.ops.attention import attention_context
    from edl_tpu.ops.flash_attention import _blockwise_reference, mha

    def fwd(q, k, v):
        if impl == "dense":
            return attention_context(q, k, v, causal=True, mask=None,
                                     dtype=jnp.bfloat16)
        if impl == "flash":
            return mha(q, k, v, causal=True, interpret=interpret)
        return _blockwise_reference(q, k, v, True, dim ** -0.5,
                                    block_k=512)

    if grad:
        def fn(q, k, v):
            return jax.grad(lambda t: jnp.sum(
                fwd(t, k, v).astype(jnp.float32)))(q)
    else:
        fn = fwd
    s = jax.ShapeDtypeStruct((batch, seq, heads, dim), jnp.bfloat16)
    out = compile_stats(fn, (s, s, s), devices[:1])
    out.update({"account": "attention_%s" % impl, "seq": seq,
                "batch": batch, "heads": heads, "dim": dim,
                "grad": grad})
    return out


# -- account 3: remat (jax.checkpoint trades flops for live memory) -------


def remat_account(devices, policy, num_layers=8, d_model=512, seq=1024,
                  batch=8, per_layer=False):
    """``policy`` exercises the trainer's whole-loss remat_policy knob;
    ``per_layer=True`` instead exercises the models' per-layer
    ``remat`` flag (layer-boundary jax.checkpoint — the bench LM
    default), which is the memory lever that actually matters."""
    from edl_tpu.models import gpt as gpt_mod
    from edl_tpu.runtime.trainer import make_train_state, make_train_step
    _, params, loss_fn = gpt_mod.create_model_and_loss(
        num_layers=num_layers, d_model=d_model, num_heads=8,
        mlp_dim=4 * d_model, vocab_size=512, max_len=seq,
        remat=per_layer)
    tx = optax.sgd(0.1)
    state = make_train_state(params, tx)
    step = make_train_step(loss_fn, tx, remat_policy=policy)
    bspec = {"input_ids": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    out = compile_stats(step, (spec_like(state), bspec, rng),
                        devices[:1], donate_argnums=(0,))
    out.update({"account": "gpt_remat"
                + ("_per_layer" if per_layer else ""),
                "remat_policy": policy or "none",
                "per_layer": per_layer,
                "num_layers": num_layers, "d_model": d_model,
                "seq": seq, "batch": batch})
    return out


def lm_batch_account(devices, batch, num_layers=12, d_model=768,
                     seq=1024, vocab=32000, remat=True,
                     use_flash=False, kind="gpt"):
    """Static basis for the LM batch-scaling sweep.
    Compiles the bench's exact train-step shape (GPT-2s, adamw,
    donated state; ``remat`` parameterized — True is the bench
    default) at a given batch on the real TPU compiler and records
    flops, bytes and their ratio.

    MEASURED CONCLUSION (r5, PERF_ACCOUNTING.json): the pre-run
    hypothesis — "optimizer state is constant in batch, so batch
    scaling multiplies arithmetic intensity" — is WRONG at seq 1024.
    Activation/remat traffic dominates (adamw m/v is 1.3 GB of the
    94.7 GB/step at batch 8) and scales with batch: 4x batch = 4.0x
    flops but 3.62x bytes, so flops/byte rises only ~10% (80.5 ->
    88.8). Both batches sit near the HBM bandwidth floor; the r5e
    sweep's expected win is the floor ratio (~+27-32%), not 4x."""
    from edl_tpu.runtime.trainer import make_train_state, make_train_step
    bspec = {"input_ids": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    if kind == "gpt":
        from edl_tpu.models import gpt as family
        _, params, loss_fn = family.create_model_and_loss(
            num_layers=num_layers, d_model=d_model,
            num_heads=max(1, d_model // 64), mlp_dim=4 * d_model,
            vocab_size=vocab, max_len=seq, remat=remat,
            use_flash=use_flash)
    elif kind == "bert":
        # mirror the bench's bert config: bert_base defaults + the
        # bench's dtype/remat/flash knobs, classification batch. The
        # size params are gpt-branch-only — recording caller-passed
        # sizes against bert_base's hardwired shape would stamp
        # metadata that doesn't match the compiled model.
        from edl_tpu.models import bert as family
        model = family.bert_base(dtype=jnp.bfloat16, remat=remat,
                                 use_flash=use_flash)
        passed = (num_layers, d_model, vocab)
        actual = (model.num_layers, model.d_model, model.vocab_size)
        if passed not in ((12, 768, 32000), actual):
            # (12, 768, 32000) = the untouched gpt-branch defaults
            raise ValueError(
                "kind='bert' uses bert_base's own shape %r; "
                "num_layers/d_model/vocab are not configurable here"
                % (actual,))
        num_layers, d_model, vocab = actual
        if seq > model.max_len:
            # position indices past max_len would gather out of bounds
            # (XLA clamps silently — the row would describe an
            # impossible model)
            raise ValueError("seq %d > bert_base max_len %d"
                             % (seq, model.max_len))
        _, params, loss_fn = family.create_model_and_loss(
            model=model, dummy_seq=16)
        bspec["label"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    else:
        raise ValueError("kind must be 'gpt' or 'bert', got %r" % kind)
    tx = optax.adamw(1e-4)
    state = make_train_state(params, tx)
    step = make_train_step(loss_fn, tx)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    out = compile_stats(step, (spec_like(state), bspec, rng),
                        devices[:1], donate_argnums=(0,))
    if out.get("flops") and out.get("bytes_accessed"):
        out["flops_per_byte"] = round(out["flops"]
                                      / out["bytes_accessed"], 2)
    out.update({"account": "lm_batch", "kind": kind, "batch": batch,
                "num_layers": num_layers, "d_model": d_model,
                "seq": seq, "remat": remat, "use_flash": use_flash})
    return out


def bert_tp_account(devices, dp=2, tp=2, num_layers=4, d_model=512,
                    seq=512, batch=32, zero1=False):
    """Megatron-rule tensor parallelism on the REAL TPU compiler: a
    bert train step with params tp-sharded (bert_partition_rules) over
    a dp x tp mesh of topology chips, optimizer state structurally
    mirroring the param layout. Static proof the model-parallel path
    is TPU-valid — the collectives XLA inserts for the tp layout show
    up in bytes_accessed."""
    from edl_tpu.models import bert
    from edl_tpu.parallel.sharding import (match_partition_rules,
                                           opt_state_shardings)
    from edl_tpu.runtime.trainer import make_train_state, make_train_step

    _, params, loss_fn = bert.create_model_and_loss(
        model=bert.bert_tiny(num_layers=num_layers, d_model=d_model,
                             num_heads=8, mlp_dim=4 * d_model,
                             max_len=seq, dtype=jnp.bfloat16))
    mesh = Mesh(np.array(devices[:dp * tp]).reshape(dp, tp),
                ("dp", "tp"))
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("dp"))
    pspecs = match_partition_rules(bert.bert_partition_rules(), params)
    psh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    tx = optax.sgd(0.1, momentum=0.9)
    state = make_train_state(params, tx)
    osh = opt_state_shardings(tx, params, psh, repl,
                              zero1_mesh=mesh if zero1 else None)
    state_sh = {"params": psh, "opt_state": osh, "step": repl,
                "extra": None}
    step = make_train_step(loss_fn, tx)
    bspec = {"input_ids": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
             "label": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    out = compile_stats(
        step, (spec_like(state), bspec, rng), devices, mesh=mesh,
        in_shardings=(state_sh, {"input_ids": data, "label": data},
                      repl),
        out_shardings=(state_sh, repl), donate_argnums=(0,))
    out.update({"account": "bert_tp_train_step"
                + ("_zero1" if zero1 else ""),
                "dp": dp, "tp": tp, "zero1": zero1,
                "num_layers": num_layers, "d_model": d_model,
                "seq": seq, "batch": batch})
    return out


def ring_sp_account(devices, sp=4, seq=8192, heads=12, dim=64, batch=1):
    """Ring attention (sequence parallelism: shard_map + ppermute) on
    the real TPU compiler, fwd+bwd — static proof the sp collectives
    are TPU-valid at long context."""
    from edl_tpu.parallel.ring_attention import ring_attention
    from edl_tpu.runtime.mesh import make_mesh
    mesh = make_mesh(dp=1, sp=sp, devices=devices[:sp])
    seq_sh = NamedSharding(mesh, P("dp", "sp", None, None))
    s = jax.ShapeDtypeStruct((batch, seq, heads, dim), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True)
                       .astype(jnp.float32))

    def fn(q, k, v):
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    out = compile_stats(fn, (s, s, s), devices[:sp], mesh=mesh,
                        in_shardings=(seq_sh,) * 3,
                        out_shardings=(seq_sh,) * 3)
    out.update({"account": "ring_attention_sp%d" % sp, "sp": sp,
                "seq": seq, "heads": heads, "dim": dim, "batch": batch,
                "grad": True})
    return out


def pipeline_pp_account(devices, pp=4, num_layers=8, d_model=256,
                        seq=512, batch=8, num_micro=4):
    """The 1F1B pipeline schedule (shard_map stage handoffs) on the
    real TPU compiler — static proof the pp schedule is TPU-valid."""
    from edl_tpu.models import gpt as gpt_mod
    from edl_tpu.parallel.pipeline import pipeline_value_and_grad
    from edl_tpu.runtime.mesh import make_mesh
    mesh = make_mesh(dp=1, pp=pp, devices=devices[:pp])
    params, enc, stg, dec, _ = gpt_mod.create_gpt_pipeline(
        pp=pp, num_layers=num_layers, d_model=d_model, num_heads=8,
        mlp_dim=4 * d_model, vocab_size=512, max_len=seq, seq_len=seq)
    x = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    y = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def fn(p, xb, yb):
        return pipeline_value_and_grad(p, xb, yb, encode_fn=enc,
                                       stage_fn=stg, decode_fn=dec,
                                       mesh=mesh, num_micro=num_micro)

    # the REAL pp layout: stage params sharded over the pp axis
    # (leading stacked-stage dim); ends + token batch replicated.
    # Replicated-everything would make jit reshard before the schedule
    # and the account would charge the pp layout for a full per-chip
    # param copy it never holds.
    repl = NamedSharding(mesh, P())
    stages_sh = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P("pp")), params["stages"])
    params_sh = {"encode": jax.tree_util.tree_map(lambda _: repl,
                                                  params["encode"]),
                 "stages": stages_sh,
                 "decode": jax.tree_util.tree_map(lambda _: repl,
                                                  params["decode"])}
    out = compile_stats(fn, (spec_like(params), x, y), devices[:pp],
                        mesh=mesh,
                        in_shardings=(params_sh, repl, repl))
    out.update({"account": "gpt_1f1b_pp%d" % pp, "pp": pp,
                "num_layers": num_layers, "d_model": d_model,
                "seq": seq, "batch": batch, "num_micro": num_micro})
    return out


ACCOUNTS = ("attention", "remat", "sharded_tp", "sharded_sp",
            "sharded_pp", "lm_batch")


def run_accounts(names, platform):
    devices = v5e_devices() if platform == "tpu" else jax.devices("cpu")
    results = []

    def go(label, fn, *a, **kw):
        try:
            r = fn(*a, **kw)
            print(json.dumps(r), flush=True)
            results.append(r)
        except Exception:
            # keep the config kwargs on the error entry so a failed
            # account row still says WHICH config failed
            err = {"account": label, "error":
                   traceback.format_exc(limit=3).splitlines()[-1]}
            err.update({k: v for k, v in kw.items()
                        if isinstance(v, (int, float, str, bool))})
            print(json.dumps(err), flush=True)
            traceback.print_exc()
            results.append(err)

    if "attention" in names:
        for seq in (2048, 8192):
            for impl in ("dense", "flash"):
                go("attention_%s" % impl, attention_account, devices,
                   seq, impl, interpret=(platform != "tpu"))
    if "remat" in names:
        for pol in (None, "full", "dots"):
            go("remat", remat_account, devices, pol)
        go("remat_per_layer", remat_account, devices, None,
           per_layer=True)
    if "sharded_tp" in names and platform == "tpu":
        go("sharded_tp", bert_tp_account, devices)
        go("sharded_tp_zero1", bert_tp_account, devices, zero1=True)
    if "sharded_sp" in names and platform == "tpu":
        go("sharded_sp", ring_sp_account, devices)
    if "sharded_pp" in names and platform == "tpu":
        go("sharded_pp", pipeline_pp_account, devices)
    if "lm_batch" in names and platform == "tpu":
        for b in (8, 32):
            for remat in (True, False):
                if b == 32 and not remat:
                    # known verdict, not a regression: the compiler
                    # proved this config needs 24.8 GB of 15.75 GB hbm
                    # (r5) — record it without burning the ~95 s
                    # compile and without the error row flipping the
                    # regeneration run's exit code to 1. The pinned
                    # text goes stale if the loop's model shape or
                    # topology ever changes — re-verify then.
                    skip = {"account": "lm_batch", "batch": b,
                            "remat": remat, "skipped":
                            "RESOURCE_EXHAUSTED at compile: needs "
                            "24.81G of 15.75G hbm (remat is "
                            "load-bearing at batch 32)"}
                    print(json.dumps(skip), flush=True)
                    results.append(skip)
                    continue
                go("lm_batch", lm_batch_account, devices, batch=b,
                   remat=remat)
        # flash variants of the bench configs (scores never hit HBM —
        # the account predicts the gpt --flash stages' outcome)
        for b in (8, 32):
            go("lm_batch", lm_batch_account, devices, batch=b,
               use_flash=True)
        # bert-base at the bench config (seq 512, batch 32), dense vs
        # flash — predictions for the queued bert stages
        for fl in (False, True):
            go("lm_batch", lm_batch_account, devices, batch=32,
               seq=512, kind="bert", use_flash=fl)
    return results


def main(argv=None):
    p = argparse.ArgumentParser("static perf accounting")
    p.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    p.add_argument("--accounts", default=",".join(ACCOUNTS))
    p.add_argument("--out", default=None, help="write JSON list here")
    p.add_argument("--fold_roofline_gap", default=None, metavar="PATH",
                   help="fold the gpt arc of a roofline_gap/v1 output "
                        "file into the BENCH_BEST pointer and exit")
    p.add_argument("--best", default=None,
                   help="BENCH_BEST_TPU.json path (default: repo root)")
    p.add_argument("--force_fold", action="store_true",
                   help="fold even a non-TPU arc (testing only)")
    p.add_argument("--recompute_vs_baseline", action="store_true",
                   help="backfill vs_baseline for 0.0 records and exit")
    args = p.parse_args(argv)
    if args.fold_roofline_gap or args.recompute_vs_baseline:
        # pure-JSON maintenance of the pointer file: no jax, no scrub
        best_path = args.best or _default_best_path()
        if args.fold_roofline_gap:
            with open(args.fold_roofline_gap) as f:
                lines = [ln for ln in f.read().splitlines() if ln.strip()]
            gap_doc = json.loads(lines[-1]) if lines else {}
            changed, msg = fold_roofline_gap(gap_doc, best_path,
                                             force=args.force_fold)
            print(msg)
        if args.recompute_vs_baseline:
            fixed = recompute_vs_baseline(best_path)
            print("vs_baseline backfilled: %s" % (fixed or "nothing"))
        return 0
    scrub_env_for_cli()
    names = [n for n in args.accounts.split(",") if n]
    unknown = sorted(set(names) - set(ACCOUNTS))
    if unknown:
        p.error("unknown accounts %s (valid: %s)"
                % (",".join(unknown), ",".join(ACCOUNTS)))
    results = run_accounts(names, args.platform)
    doc = {"platform": args.platform,
           "compiler": "libtpu AOT (deviceless v5e:2x2 topology)"
           if args.platform == "tpu" else "XLA CPU",
           "v5e_hbm_gbps": V5E_HBM_GBPS,
           "v5e_bf16_tflops": V5E_BF16_TFLOPS,
           "results": results}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
    errs = sum(1 for r in results if "error" in r)
    print("accounts: %d ok, %d failed" % (len(results) - errs, errs))
    return 1 if errs else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
