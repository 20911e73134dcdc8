"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user would call, at
the full width of the models the repo supports (depth is what it is; the
weights are random, from a seed):

  probe   a short child reports the device as JAX sees it and exits;
  train   one coordination store (``python -m edl_tpu.coordination.server``),
          one launcher (``python -m edl_tpu.controller.launch``), one
          trainer (``examples/resnet/train.py``, ResNet50_vd, 224 px,
          bf16, 128 images per chip) driving ALL visible chips through
          ElasticTrainer; then the newest checkpoint is dropped (a lost
          last save) and a second launcher runs the SAME command line,
          as a restart does: it must resume an epoch back, take the
          steps that are left, and find its step in the compile cache;
  kernel  the Pallas flash-attention kernel, forward and gradient, at the
          GPT-2s and BERT-base attention shapes, compiled natively and
          compared with the dense path at HIGHEST precision;
  serve   an ``lm_teacher`` at GPT-2s width answering a handful of
          ``lm_generate`` requests from a CPU-pinned client.

One process per chip: this parent never imports jax (a parent that has
touched JAX holds the chip), and it runs its children ONE AT A TIME —
each child that needs the chip has exited before the next starts. Every
chip-side child runs with JAX_PLATFORMS=tpu, so JAX raises instead of
dropping to the CPU when the chip is missing or held.

Exit 0 and a last stdout line ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}`` — exactly those keys — only if every phase
passed; anything else is a non-zero exit that names the phase, and no
result line. What the phases observed (seconds, compile seconds, losses)
goes to stderr and ``chiprun_out/chip_smoke/result.json``: observations
of a smoke run, not metrics.

``--cpu_tiny`` runs the same phases at toy sizes on the CPU backend (the
kernel in the Pallas interpreter) so the script itself can be debugged
without a chip; its result line says ``"platform": "cpu"``.
"""

import argparse
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")
SEED = 0
BUDGET_S = 1150.0  # the contract allows 1200 s, compilation included

# Normalised max error |x - ref|_max / |ref|_max allowed between the
# flash kernel (bf16 in, bf16 out) and the dense path at HIGHEST
# precision on f32 copies of the same inputs. bf16 keeps 8 significant
# bits, so rounding the output alone costs up to 2^-8 = 3.9e-3 of the
# largest value, and the MXU's single-pass bf16 products inside the
# kernel and its XLA backward add about as much again. The dense path at
# DEFAULT precision — what the models run when flash is not dispatched —
# is measured beside it at the same normalisation and printed: on the
# v5e it came to 2.6e-3..4.6e-3 across out/dq/dk/dv at both shapes, and
# the kernel to 2.4e-3..5.1e-3, the same band (PERF.md, Bring-up). 2e-2
# is four times that band: wide enough for rounding, far too narrow for
# a wrong mask, scale or block index (those give errors of order 1).
KERNEL_TOL = 2e-2

SIZES = {
    "full": {
        "platform": "tpu",
        "train": ["--depth", "50", "--image_size", "224",
                  "--num_classes", "1000", "--dtype", "bf16"],
        "per_chip_batch": 128, "steps_per_epoch": 3,
        "kernel_shapes": [("gpt2s", (8, 12, 1024, 64), True),
                          ("bert_base", (32, 12, 512, 64), False)],
        "lm": dict(num_layers=12, d_model=768, num_heads=12, mlp_dim=3072,
                   vocab_size=32000, max_len=1024, slots=8),
        "prompt_len": 40, "prefix_len": 200, "suffix_len": 8,
        "max_new": 16,
    },
    "cpu_tiny": {
        "platform": "cpu",
        "train": ["--depth", "18", "--image_size", "32",
                  "--num_classes", "10", "--dtype", "f32"],
        "per_chip_batch": 2, "steps_per_epoch": 2,
        "kernel_shapes": [("tiny_causal", (2, 4, 16, 16), True),
                          ("tiny_full", (2, 4, 16, 16), False)],
        "lm": dict(num_layers=2, d_model=64, num_heads=4, mlp_dim=128,
                   vocab_size=256, max_len=128, slots=8),
        "prompt_len": 12, "prefix_len": 40, "suffix_len": 4,
        "max_new": 6,
    },
}


class PhaseFailed(Exception):
    def __init__(self, phase, why):
        super().__init__("phase %s FAILED: %s" % (phase, why))


def say(msg):
    print("[chip_smoke] %s" % msg, file=sys.stderr, flush=True)


# -- children: started one at a time, each in its own process group --------

_LIVE = []


def _env(platform, **extra):
    env = dict(os.environ, JAX_PLATFORMS=platform, PYTHONPATH=REPO,
               EDL_TPU_POD_IP="127.0.0.1")
    if platform == "cpu":
        # the CPU debug size still spreads a batch: 4 virtual devices
        env.setdefault("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    env.update(extra)
    return env


def _start(cmd, env, log_path):
    log = open(log_path, "wb")
    try:
        proc = subprocess.Popen(cmd, env=env, cwd=REPO, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    finally:
        log.close()
    _LIVE.append(proc)
    return proc


def _stop(proc, grace=10.0):
    """SIGTERM the child's whole process group, SIGKILL what is left."""
    for sig in (signal.SIGTERM, signal.SIGKILL):
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            break
        try:
            proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            continue
    if proc in _LIVE:
        _LIVE.remove(proc)


def _read(path):
    try:
        with open(path, "rb") as f:
            return f.read().decode("utf-8", "replace")
    except OSError:
        return ""


def _tail(path, n=25):
    return "\n".join(_read(path).splitlines()[-n:])


def _run(phase, cmd, env, log_name, timeout, deadline):
    """Run one child to its end; a non-zero exit or a timeout fails the
    phase. Returns the child's log text."""
    timeout = min(timeout, deadline - time.monotonic())
    if timeout <= 0:
        raise PhaseFailed(phase, "no time left in the %.0fs budget"
                          % BUDGET_S)
    log_path = os.path.join(OUT, log_name)
    proc = _start(cmd, env, log_path)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        _stop(proc)
        raise PhaseFailed(phase, "%s still running after %.0fs — killed\n%s"
                          % (log_name, timeout, _tail(log_path)))
    _stop(proc)  # reap whatever the child left in its group
    if rc != 0:
        raise PhaseFailed(phase, "%s exited %d\n%s"
                          % (log_name, rc, _tail(log_path)))
    return _read(log_path)


def _last_json(text, phase, what):
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    raise PhaseFailed(phase, "%s printed no JSON line" % what)


def _child(mode, size):
    return [sys.executable, os.path.join(REPO, "chip_smoke.py"),
            "--child", mode, "--size", size]


def _check(phase, cond, why):
    if not cond:
        raise PhaseFailed(phase, why)


def _check_device(phase, got, device):
    _check(phase, all(got.get(k) == device[k] for k in device),
           "ran on %s, the probe saw %s"
           % ({k: got.get(k) for k in device}, device))


# -- phase 0: what device is this ------------------------------------------


def phase_probe(size, cfg, deadline):
    from edl_tpu.parallel import costmodel  # jax-free import

    out = _run("probe", _child("probe", size), _env(cfg["platform"]),
               "probe.log", 120, deadline)
    device = _last_json(out, "probe", "the probe child")
    _check("probe", device["platform"] == cfg["platform"],
           "JAX reports platform %r, want %r — no accelerator"
           % (device["platform"], cfg["platform"]))
    if cfg["platform"] == "tpu":
        # an unknown chip is an error, not a default (KeyError names it)
        try:
            costmodel.chip_peaks(device["device_kind"])
        except KeyError as e:
            raise PhaseFailed("probe", str(e))
    return device


# -- phase 1: store -> launcher -> ElasticTrainer, then resume -------------


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_port(phase, proc, port, log_name, timeout=30.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if proc.poll() is not None:
            break
        try:
            socket.create_connection(("127.0.0.1", port), 1.0).close()
            return
        except OSError:
            time.sleep(0.2)
    raise PhaseFailed(phase, "%s not serving on port %d\n%s"
                      % (log_name, port,
                         _tail(os.path.join(OUT, log_name))))


def _parse_trainer_log(phase, log_path):
    """(header line, per-step losses, final JSON) of one incarnation."""
    text = _read(log_path)
    header = [ln for ln in text.splitlines() if "start_epoch=" in ln]
    _check(phase, header, "no trainer header in %s\n%s"
           % (log_path, _tail(log_path)))
    losses = []
    for ln in text.splitlines():
        parts = ln.split()
        if ln.startswith("epoch ") and "loss" in parts:
            losses.append(float(parts[parts.index("loss") + 1]))
    return header[-1], losses, _last_json(text, phase, log_path)


def phase_train(size, cfg, device, deadline):
    from edl_tpu.utils import compile_cache  # jax-free import

    n = device["device_count"]
    spe, epochs = cfg["steps_per_epoch"], 3
    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    port = _free_port()
    store = _start([sys.executable, "-m", "edl_tpu.coordination.server",
                    "--host", "127.0.0.1", "--port", str(port)],
                   _env("cpu"), os.path.join(OUT, "store.log"))
    results = {}
    try:
        _wait_port("train", store, port, "store.log")
        for tag in ("fresh", "resume"):
            log_dir = os.path.join(OUT, "train_" + tag)
            shutil.rmtree(log_dir, ignore_errors=True)
            cmd = [sys.executable, "-m", "edl_tpu.controller.launch",
                   "--job_id", "chip_smoke_" + tag,
                   "--store_endpoints", "127.0.0.1:%d" % port,
                   "--nodes_range", "1:1", "--checkpoint_path", ckpt,
                   "--log_dir", log_dir,
                   os.path.join(REPO, "examples", "resnet", "train.py"),
                   ] + cfg["train"] + [
                   "--total_batch_size", str(cfg["per_chip_batch"] * n),
                   "--epochs", str(epochs), "--steps_per_epoch", str(spe),
                   "--fetch_steps", "1"]
            t0 = time.monotonic()
            _run("train", cmd, _env(cfg["platform"]),
                 "launcher_%s.log" % tag, 600, deadline)
            header, losses, final = _parse_trainer_log(
                "train", os.path.join(log_dir, "workerlog.0"))
            say("train/%s: %s" % (tag, header.strip()))
            say("train/%s: losses %s, final %s" % (tag, losses, final))
            resumed = tag == "resume"
            want_steps = spe if resumed else epochs * spe
            _check("train", ("resumed=%s" % resumed) in header
                   and final["resumed"] is resumed,
                   "%s incarnation: %s" % (tag, header.strip()))
            _check("train", len(losses) == want_steps
                   and all(math.isfinite(x) for x in losses)
                   and math.isfinite(final["final_loss"]),
                   "%s: want %d finite per-step losses, got %s"
                   % (tag, want_steps, losses))
            _check("train", final["steps"] == epochs * spe,
                   "%s: global step %s, want %d"
                   % (tag, final["steps"], epochs * spe))
            _check_device("train", final, device)
            # the work is spread: "everything on the first chip" fails
            _check("train", final["batch_devices"] == n
                   and final["loss_devices"] == n
                   and final["per_device_batch"] == [cfg["per_chip_batch"]],
                   "%s: batch on %s devices (%s rows each), loss on %s; "
                   "want %d devices x %d rows"
                   % (tag, final["batch_devices"],
                      final["per_device_batch"], final["loss_devices"], n,
                      cfg["per_chip_batch"]))
            if cfg["platform"] == "tpu":
                _check("train", len(final["device_bytes_in_use"]) == n
                       and all(b and b > 0
                               for b in final["device_bytes_in_use"]),
                       "%s: a chip holds no memory after a step: %s"
                       % (tag, final["device_bytes_in_use"]))
            if not resumed:
                versions = sorted(e for e in os.listdir(ckpt)
                                  if e.startswith("v_"))
                _check("train", len(versions) >= 2,
                       "want a checkpoint per epoch under %s, found %s"
                       % (ckpt, versions))
                # lose the last save: the restart below resumes an
                # epoch back and has steps left to take
                shutil.rmtree(os.path.join(ckpt, versions[-1]))
                cache = compile_cache.cache_dir()
                _check("train", os.path.isdir(cache) and os.listdir(cache),
                       "the trainer wrote no compile cache under %s"
                       % cache)
            if resumed:
                # same program, same restored state, same synthetic
                # data: the restart must retrace the epoch it lost
                lost = results["fresh"]["losses"][-spe:]
                _check("train", all(abs(a - b) <= 1e-3
                                    for a, b in zip(losses, lost)),
                       "the resumed epoch's losses %s do not repeat the "
                       "ones the fresh run printed for it %s"
                       % (losses, lost))
            results[tag] = {
                "seconds": round(time.monotonic() - t0, 1),
                "steps": len(losses), "losses": losses,
                "compile_s": final.get("compile_s"),
                "first_step_s": final.get("first_step_s"),
                "restore_s": final.get("restore_s"),
                "per_device_batch": final["per_device_batch"],
                "device_bytes_in_use": final["device_bytes_in_use"],
            }
        say("train: compile_s fresh %s -> resume %s (the cache at work)"
            % (results["fresh"]["compile_s"],
               results["resume"]["compile_s"]))
        return results
    finally:
        _stop(store)
        shutil.rmtree(ckpt, ignore_errors=True)


# -- phase 2: the Pallas kernel, native, against the dense path ------------


def phase_kernel(size, cfg, device, deadline):
    out = _run("kernel", _child("kernel", size), _env(cfg["platform"]),
               "kernel.log", 420, deadline)
    rep = _last_json(out, "kernel", "the kernel child")
    _check_device("kernel", rep["device"], device)
    for shape in rep["shapes"]:
        say("kernel/%s: flash vs HIGHEST %s | dense default vs HIGHEST %s"
            % (shape["name"], shape["flash_err"], shape["dense_err"]))
        if cfg["platform"] == "tpu":
            _check("kernel", shape["mosaic_fwd"] and shape["mosaic_grad"],
                   "%s: no Mosaic custom call in the lowered text — the "
                   "kernel did not compile natively" % shape["name"])
        bad = {k: v for k, v in shape["flash_err"].items()
               if not v <= KERNEL_TOL}
        _check("kernel", not bad, "%s: flash disagrees with the dense "
               "reference beyond %g: %s" % (shape["name"], KERNEL_TOL, bad))
    return {"seconds": rep["seconds"], "tolerance": KERNEL_TOL,
            "shapes": rep["shapes"]}


def child_kernel(cfg):
    from edl_tpu.utils import compile_cache
    compile_cache.enable()
    import jax
    import jax.numpy as jnp

    from edl_tpu.ops.attention import attention_context
    from edl_tpu.ops.flash_attention import flash_attention
    from edl_tpu.parallel.costmodel import device_identity

    t0 = time.monotonic()
    interpret = cfg["platform"] != "tpu"
    shapes = []
    for name, (b, h, s, d), causal in cfg["kernel_shapes"]:
        keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
        q, k, v, g = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
                      for kk in keys)

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=causal,
                                   interpret=interpret)

        def dense(q, k, v):
            # the repo's own dense path ([b, s, h, d] layout)
            t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
            return t(attention_context(t(q), t(k), t(v), causal=causal,
                                       mask=None, dtype=q.dtype,
                                       use_flash=False))

        def with_grads(fn):
            def loss(q, k, v):
                return jnp.sum(fn(q, k, v).astype(jnp.float32)
                               * g.astype(jnp.float32))
            return jax.jit(lambda q, k, v: (
                fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)))

        flash_fwd = jax.jit(flash)
        flash_all = with_grads(flash)
        mosaic = ["tpu_custom_call" in f.lower(q, k, v).as_text()
                  for f in (flash_fwd, flash_all)]
        out_f, grads_f = flash_all(q, k, v)
        out_d, grads_d = with_grads(dense)(q, k, v)
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        with jax.default_matmul_precision("highest"):
            out_r, grads_r = with_grads(dense)(*f32)

        def err(x, ref):
            x = x.astype(jnp.float32)
            return float(jnp.max(jnp.abs(x - ref)) / jnp.max(jnp.abs(ref)))

        def errs(out, grads):
            e = {"out": err(out, out_r)}
            e.update({n_: err(x, r) for n_, x, r
                      in zip(("dq", "dk", "dv"), grads, grads_r)})
            return {k_: round(v_, 6) for k_, v_ in e.items()}

        finite = all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
                     for x in (out_f,) + tuple(grads_f))
        if not finite:
            raise SystemExit("%s: non-finite flash output" % name)
        shapes.append({"name": name, "shape": [b, h, s, d],
                       "causal": causal, "mosaic_fwd": mosaic[0],
                       "mosaic_grad": mosaic[1],
                       "flash_err": errs(out_f, grads_f),
                       "dense_err": errs(out_d, grads_d)})
    print(json.dumps({"device": device_identity(), "shapes": shapes,
                      "seconds": round(time.monotonic() - t0, 1)}),
          flush=True)


# -- phase 3: lm_teacher at GPT-2s width, a CPU-pinned client --------------


def smoke_prompts(cfg):
    """The requests, from the seed: (prompt, send-alone?) pairs. Two are
    identical, two share a long prefix, three more arrive together. First
    tokens are pinned apart so that ONLY the intended pairs share
    anything — the prefix accounting below is then exact."""
    import random

    rnd = random.Random(SEED)
    vocab = cfg["lm"]["vocab_size"]

    def toks(first, n):
        return [first] + [rnd.randrange(16, vocab) for _ in range(n - 1)]

    a = toks(1, cfg["prompt_len"])
    prefix = toks(2, cfg["prefix_len"])
    b1 = prefix + toks(3, cfg["suffix_len"])
    b2 = prefix + toks(4, cfg["suffix_len"])
    serial = [a, list(a), b1, b2, toks(5, cfg["prompt_len"])]
    burst = [toks(6 + i, cfg["prompt_len"]) for i in range(3)]
    # identical prompt: everything but its last token is reused
    want = {"hits": 2, "misses": 6,
            "reuse_tokens": (len(a) - 1) + len(prefix)}
    return serial, burst, want


def phase_serve(size, cfg, device, deadline):
    t0 = time.monotonic()
    log_path = os.path.join(OUT, "serve_server.log")
    server = _start(_child("serve", size), _env(cfg["platform"]), log_path)
    try:
        endpoint = None
        while endpoint is None:
            if server.poll() is not None:
                raise PhaseFailed("serve", "the server exited %s before "
                                  "serving\n%s" % (server.returncode,
                                                   _tail(log_path)))
            if time.monotonic() > min(deadline, t0 + 300):
                raise PhaseFailed("serve", "the server did not come up\n"
                                  + _tail(log_path))
            for ln in _read(log_path).splitlines():
                if ln.startswith("SMOKE_ENDPOINT="):
                    endpoint = ln.split("=", 1)[1].strip()
            time.sleep(0.5)
        # the client is pinned to the CPU: it must never want the chip
        out = _run("serve", _child("client", size) + ["--endpoint",
                                                      endpoint],
                   _env("cpu"), "serve_client.log", 500, deadline)
        client = _last_json(out, "serve", "the client child")
        # the server computes its reference and exits on its own once it
        # has seen every sequence through
        try:
            rc = server.wait(timeout=max(1.0, min(
                300, deadline - time.monotonic())))
        except subprocess.TimeoutExpired:
            raise PhaseFailed("serve", "the server did not finish its "
                              "report\n" + _tail(log_path))
        _check("serve", rc == 0, "the server exited %d\n%s"
               % (rc, _tail(log_path)))
    finally:
        _stop(server)
    rep = _last_json(_read(log_path), "serve", "the server child")
    stats, ref = rep["stats"], rep["reference"]
    serial, burst, want = smoke_prompts(cfg)
    prompts = serial + burst
    got = client["generated"]
    _check_device("serve", stats, device)
    _check("serve", len(got) == len(prompts)
           and all(len(g) == cfg["max_new"] for g in got)
           and client["echoed_prompts"],
           "not every request returned exactly %d new tokens after its "
           "prompt: %s" % (cfg["max_new"], [len(g) for g in got]))
    _check("serve", got[0] == got[1],
           "identical prompts gave different streams")
    pfx = stats["decode_prefix"]
    _check("serve", stats["decode_step_traces"] == 1,
           "the fused decode step traced %s times"
           % stats["decode_step_traces"])
    _check("serve", all(pfx.get(k) == v for k, v in want.items()),
           "prefix accounting %s, want %s"
           % ({k: pfx.get(k) for k in want}, want))
    _check("serve", stats["decode_admission"]["shed_total"] == 0
           and stats["decode_evicted_total"] == 0
           and stats["decode_sequences_total"] == len(prompts),
           "shed/evicted/done: %s/%s/%s"
           % (stats["decode_admission"]["shed"],
              stats["decode_evicted_total"],
              stats["decode_sequences_total"]))
    # parity with the unbatched models.gpt.generate on the same device is
    # REPORTED, not gated: a batched and an unbatched f32 matmul need not
    # round alike on the MXU, and with random weights the top-2 logit gap
    # is small. Tokens are counted up to each stream's first divergence.
    matched = sum(next((i for i, (x, y) in enumerate(zip(g, r)) if x != y),
                       len(g)) for g, r in zip(got, ref))
    parity = {"tokens_matched": matched,
              "tokens_total": cfg["max_new"] * len(prompts),
              "sequences_matched": sum(g == r for g, r in zip(got, ref)),
              "sequences_total": len(prompts)}
    say("serve: parity with models.gpt.generate %s" % parity)
    return {"seconds": round(time.monotonic() - t0, 1), "parity": parity,
            "prefix": {k: pfx.get(k) for k in want},
            "decode_step_traces": stats["decode_step_traces"],
            "ttft_p50_ms": stats["decode_ttft_p50_ms"],
            "itl_p50_ms": stats["decode_itl_p50_ms"]}


def _lm_model_and_params(cfg):
    import jax
    import jax.numpy as jnp

    from edl_tpu.models import gpt

    lm = cfg["lm"]
    # lm_teacher's own model: f32, same widths (distill/teacher_server.py)
    model = gpt.Gpt(num_layers=lm["num_layers"], d_model=lm["d_model"],
                    num_heads=lm["num_heads"], mlp_dim=lm["mlp_dim"],
                    vocab_size=lm["vocab_size"], max_len=lm["max_len"],
                    dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(SEED),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def child_serve(cfg):
    import jax
    import jax.numpy as jnp

    from edl_tpu.distill.teacher_server import lm_teacher
    from edl_tpu.models import gpt

    model, params = _lm_model_and_params(cfg)
    srv = lm_teacher(host="127.0.0.1", params=params, **cfg["lm"]).start()
    print("SMOKE_ENDPOINT=%s" % srv.endpoint, flush=True)
    serial, burst, _ = smoke_prompts(cfg)
    prompts = serial + burst
    t0 = time.monotonic()
    while srv.stats()["decode_sequences_total"] < len(prompts):
        if time.monotonic() - t0 > 900:
            raise SystemExit("the client never finished")
        time.sleep(0.2)
    stats = srv.stats()
    srv.stop()
    # the reference: unbatched greedy generate, same params, same device
    gen = jax.jit(lambda prm, ids: gpt.generate(model, prm, ids,
                                                cfg["max_new"]))
    ref = {}
    for p in prompts:
        if tuple(p) not in ref:
            out = gen(params, jnp.asarray([p], jnp.int32))
            ref[tuple(p)] = [int(t) for t in out[0, len(p):]]
    print(json.dumps({"stats": stats,
                      "reference": [ref[tuple(p)] for p in prompts]}),
          flush=True)


def child_client(cfg, endpoint):
    from edl_tpu.rpc.client import RpcClient

    serial, burst, _ = smoke_prompts(cfg)
    client = RpcClient(endpoint, timeout=600.0)
    try:
        reports = [client.call_async("lm_generate", p, cfg["max_new"])
                   .result(600.0) for p in serial]
        futures = [client.call_async("lm_generate", p, cfg["max_new"])
                   for p in burst]
        reports += [f.result(600.0) for f in futures]
    finally:
        client.close()
    prompts = serial + burst
    print(json.dumps({
        "generated": [[int(t) for t in r["generated"]] for r in reports],
        "echoed_prompts": all(
            [int(t) for t in r["tokens"]] == p + [int(t) for t
                                                  in r["generated"]]
            for r, p in zip(reports, prompts)),
    }), flush=True)


def child_probe(cfg):
    from edl_tpu.parallel.costmodel import device_identity
    print(json.dumps(device_identity()), flush=True)


# -- the parent ------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu_tiny", action="store_true",
                    help="debug the script on the CPU backend at toy "
                         "sizes (no chip, no device numbers)")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--size", default="full", help=argparse.SUPPRESS)
    ap.add_argument("--endpoint", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        cfg = SIZES[args.size]
        if args.child == "client":
            return child_client(cfg, args.endpoint)
        return {"probe": child_probe, "kernel": child_kernel,
                "serve": child_serve}[args.child](cfg)

    size = "cpu_tiny" if args.cpu_tiny else "full"
    cfg = SIZES[size]
    deadline = time.monotonic() + BUDGET_S
    os.makedirs(OUT, exist_ok=True)
    phases = {}
    try:
        device = phase_probe(size, cfg, deadline)
        say("device: %s" % device)
        for name, fn in (("train", phase_train), ("kernel", phase_kernel),
                         ("serve", phase_serve)):
            say("phase %s ..." % name)
            phases[name] = fn(size, cfg, device, deadline)
            say("phase %s passed: %s" % (name, json.dumps(phases[name])))
    except PhaseFailed as e:
        say(str(e))
        return 1
    finally:
        for proc in list(_LIVE):
            _stop(proc)
    # The last stdout line is the contract's object and nothing more: the
    # driver refuses any other key. What the phases observed was said on
    # stderr as each passed and is kept in result.json.
    result = {"ok": True,
              "device": {"platform": str(device["platform"]),
                         "kind": str(device["device_kind"]),
                         "count": int(device["device_count"])}}
    with open(os.path.join(OUT, "result.json"), "w") as f:
        json.dump(dict(result, phases=phases), f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
