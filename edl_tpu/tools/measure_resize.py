"""Resize-recovery measurement: seconds from SIGKILL to the first
post-restore step.

SURVEY.md §7 names restart latency as THE metric to engineer for
elastic TPU training, and the reference's fault-tolerance story is
judged in minutes (doc/edl_live_fault_tolerance.md:37, <5 min). This
tool produces the repo's measured numbers: one launcher pod training
the resnet example, hard-killed mid-run, then respawned; recovery is
the wall time until the store-visible global step advances past the
pre-kill step (i.e. the trainer re-initialized, re-compiled — or
cache-hit / AOT-loaded — restored, and committed new progress).

Arcs:
- cold / warm: SAME-world restart against an empty / the first
  incarnation's XLA persistent compile cache. (warm = cache hit; the
  classic restart.) Every arc hands its children
  JAX_COMPILATION_CACHE_DIR inside the arc's own work directory, so a
  run neither reads nor fills the program's default cache.
- resize_prewarm_on / resize_prewarm_off: WORLD-CHANGING restart
  (n devices -> n//2), the arc the AOT resize prewarm exists for: the
  persistent cache can never carry a compile across world sizes (its
  key includes the platform topology), so without prewarm the shrunken
  world pays a full compile, and with --prewarm_worlds the first
  incarnation serialized the smaller world's step executable ahead of
  time and the restart just loads it. Runs on a virtual CPU world
  with --platform cpu (2 -> 1 devices); --platform tpu runs the same
  arcs on a multi-chip host.

- live / stop_resume: the zero-downtime comparison. The ``live`` arc
  drives the in-place reshard through the live-resize two-phase commit
  (the worker process NEVER exits — kill_s and barrier_s are
  structurally zero, the new ``reshard_s`` stage appears, and downtime
  is just the training pause); ``stop_resume`` SIGKILLs the same worker
  and respawns it on the shrunken world, the classic ladder.

    python -m edl_tpu.tools.measure_resize --arcs cold,warm
    python -m edl_tpu.tools.measure_resize --platform cpu \
        --arcs resize_prewarm_on,resize_prewarm_off
    python -m edl_tpu.tools.measure_resize --platform cpu \
        --from_devices 8 --arcs live,stop_resume

Each arc prints one JSON line.
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _spawn_store():
    from edl_tpu.coordination.server import StoreServer
    return StoreServer(host="127.0.0.1", port=0).start()


def _tpu_visibility_env(n_chips):
    """libtpu variables that confine ONE process to the first
    ``n_chips`` chips of this host. Established on a v5litepod-4 (2x2)
    host with libtpu 0.0.34 (PR 21): TPU_VISIBLE_DEVICES alone works
    for one chip but is refused for two ("expected 4, actual 2") — the
    process must also be told its own chip grid, x-major like the chip
    ids (chips 0,1 are the x row, so two chips are "2,1,1", not
    "1,2,1")."""
    x = int(os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS",
                           "2,2,1").split(",")[0])
    if n_chips <= x:
        grid = (n_chips, 1, 1)
    elif n_chips % x == 0:
        grid = (x, n_chips // x, 1)
    else:
        raise ValueError("%d chips do not tile a host grid %d wide"
                         % (n_chips, x))
    return {"TPU_VISIBLE_DEVICES": ",".join(str(i)
                                            for i in range(n_chips)),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "%d,%d,%d" % grid,
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def _spawn_pod(store_endpoint, job_id, log_dir, ckpt_dir, cache_dir,
               args, n_devices=None, prewarm_worlds="", extra_env=None):
    env = dict(os.environ)  # TPU env inherited
    if n_devices is not None and args.platform == "cpu":
        from edl_tpu.utils.cpu_mesh import force_cpu_env
        force_cpu_env(env, n_devices)
    elif n_devices is not None:
        # TPU host: confine the incarnation to its chips, so the
        # shrunken one actually sees fewer (without this the "resize"
        # arcs restart into the same full world and the prewarm
        # comparison is meaningless)
        env.update(_tpu_visibility_env(n_devices))
    env.update({
        "PYTHONPATH": REPO,
        "EDL_TPU_POD_IP": "127.0.0.1",
        "EDL_TPU_TTL": "3",
        "EDL_TPU_CHECKPOINT_PATH": ckpt_dir,
        "JAX_COMPILATION_CACHE_DIR": cache_dir,
    })
    if extra_env:
        env.update(extra_env)
    os.makedirs(log_dir, exist_ok=True)
    log = open(os.path.join(log_dir, "pod.log"), "ab")
    cmd = [sys.executable, "-u", "-m", "edl_tpu.controller.launch",
           "--job_id", job_id,
           "--store_endpoints", store_endpoint,
           "--nodes_range", "1:1",
           "--log_dir", os.path.join(log_dir, "trainers"),
           os.path.join(REPO, "examples", "resnet", "train.py"),
           "--epochs", "1000",
           "--steps_per_epoch", str(args.steps_per_epoch),
           "--total_batch_size", str(args.batch),
           "--image_size", str(args.image_size),
           "--num_classes", "100", "--dtype", args.dtype,
           "--fetch_steps", "1"]
    if prewarm_worlds:
        cmd += ["--prewarm_worlds", prewarm_worlds]
    proc = subprocess.Popen(cmd, env=env, stdout=log,
                            stderr=subprocess.STDOUT,
                            preexec_fn=os.setsid)
    log.close()
    return proc


def _kill_group(proc):
    try:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
    except ProcessLookupError:
        pass


def _store_step(coord):
    try:
        from edl_tpu.runtime import state as state_mod
        st = state_mod.load_from_store(coord)
        return None if st is None else int(st.global_step)
    except Exception:
        return None


def _wait_step(coord, pred, timeout, proc=None):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        s = _store_step(coord)
        if s is not None and pred(s):
            return s, time.monotonic() - t0
        if proc is not None and proc.poll() is not None:
            raise RuntimeError("pod exited rc=%r before reaching the "
                               "target step" % proc.returncode)
        time.sleep(0.2)
    raise TimeoutError("step predicate not reached in %.0fs" % timeout)


def run_arc(tag, args):
    from edl_tpu.coordination.client import CoordClient

    tmp = tempfile.mkdtemp(prefix="measure_resize_%s_" % tag)
    cache_dir = os.path.join(tmp, "cache")
    store = _spawn_store()
    job_id = "rz_%s_%d" % (tag, os.getpid())
    coord = CoordClient([store.endpoint], root=job_id)
    pod = None
    try:
        pod = _spawn_pod(store.endpoint, job_id,
                         os.path.join(tmp, "logs"),
                         os.path.join(tmp, "ckpt"), cache_dir, args)
        # initial launch: first epoch committed == compile + ckpt work
        s0, t_first = _wait_step(coord, lambda s: s >= args.steps_per_epoch,
                                 args.timeout, pod)
        t0 = time.monotonic()
        _kill_group(pod)
        # baseline on the CURRENT store step (the key is permanent and
        # survives the kill; steps kept committing after s0 was read)
        base = _store_step(coord)
        base = s0 if base is None else max(base, s0)
        # warm = the restart finds the first incarnation's cache; cold
        # = it gets an empty one
        pod = _spawn_pod(store.endpoint, job_id,
                         os.path.join(tmp, "logs2"),
                         os.path.join(tmp, "ckpt"),
                         cache_dir if tag == "warm" else cache_dir + "_cold",
                         args)
        s1, _ = _wait_step(coord, lambda s: s > base, args.timeout, pod)
        recovery = time.monotonic() - t0
        return {
            "metric": "resize_recovery_s_%s_cache" % tag,
            "value": round(recovery, 1),
            "unit": "s",
            "initial_launch_to_first_epoch_s": round(t_first, 1),
            "pre_kill_step": s0, "first_post_restore_step": s1,
            "steps_per_epoch": args.steps_per_epoch,
            "batch": args.batch, "image_size": args.image_size,
        }
    finally:
        if pod is not None:
            _kill_group(pod)
        store.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def _wait_aot_file(cache_dir, world, timeout):
    import glob as glob_mod
    pat = os.path.join(cache_dir, "aot_steps", "step_w%d_*.pkl" % world)
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if glob_mod.glob(pat):
            return time.monotonic() - t0
        time.sleep(0.5)
    raise TimeoutError("prewarm artifact %s not produced in %.0fs"
                       % (pat, timeout))


def run_resize_arc(prewarm, args):
    """World-CHANGING restart: a pod on ``--from_devices`` devices is
    SIGKILLed and respawned on half as many; with ``prewarm`` the first
    incarnation AOT-compiled the smaller world's step ahead of time."""
    from edl_tpu.coordination.client import CoordClient

    tag = "resize_prewarm_%s" % ("on" if prewarm else "off")
    n_hi = args.from_devices
    n_lo = n_hi // 2
    tmp = tempfile.mkdtemp(prefix="measure_%s_" % tag)
    cache = os.path.join(tmp, "cache")
    os.makedirs(cache)
    store = _spawn_store()
    job_id = "rz_%s_%d" % (tag, os.getpid())
    coord = CoordClient([store.endpoint], root=job_id)
    pod = None
    try:
        pod = _spawn_pod(store.endpoint, job_id,
                         os.path.join(tmp, "logs"),
                         os.path.join(tmp, "ckpt"), cache, args,
                         n_devices=n_hi,
                         prewarm_worlds=str(n_lo) if prewarm else "")
        s0, t_first = _wait_step(coord,
                                 lambda s: s >= args.steps_per_epoch,
                                 args.timeout, pod)
        prewarm_wait = None
        if prewarm:
            # the example kicks the prewarm thread after its first
            # epoch; the measurement starts only once the artifact is
            # durable (a real deployment prewarns during steady state)
            prewarm_wait = round(_wait_aot_file(cache, n_lo,
                                                args.timeout), 1)
        t0 = time.monotonic()
        _kill_group(pod)
        # the store's global-step key is PERMANENT and survives the
        # kill; training also kept committing during the prewarm wait
        # above. Baseline on the step visible right now, not the stale
        # s0, or the recovery "completes" the instant the store answers
        base = _store_step(coord)
        base = s0 if base is None else max(base, s0)
        pod = _spawn_pod(store.endpoint, job_id,
                         os.path.join(tmp, "logs2"),
                         os.path.join(tmp, "ckpt"), cache, args,
                         n_devices=n_lo)
        s1, _ = _wait_step(coord, lambda s: s > base, args.timeout, pod)
        recovery = time.monotonic() - t0
        return {
            "metric": "resize_recovery_s_%s" % tag[7:],  # prewarm_{on,off}
            "value": round(recovery, 1),
            "unit": "s",
            "from_devices": n_hi, "to_devices": n_lo,
            "platform": args.platform,
            "initial_launch_to_first_epoch_s": round(t_first, 1),
            "prewarm_artifact_wait_s": prewarm_wait,
            "pre_kill_step": s0, "first_post_restore_step": s1,
            "steps_per_epoch": args.steps_per_epoch,
            "batch": args.batch, "image_size": args.image_size,
        }
    finally:
        if pod is not None:
            _kill_group(pod)
        store.stop()
        shutil.rmtree(tmp, ignore_errors=True)


# -- peer-served restore arcs (resize_bench/v1) ---------------------------
#
# peer_restore_on / peer_restore_off: SAME-world restart with the
# checkpoint behind a (fake-)GCS endpoint, so the FS restore path pays a
# real storage protocol instead of the page cache. The _on arc keeps a
# holdout peer (tools/peer_holdout.py) serving the committed snapshot
# from host RAM — the surviving-peer role — and the respawned trainer
# restores over the pipelined RPC plane; the _off arc disables the peer
# plane (EDL_TPU_PEER_RESTORE=0) and restores from storage. Both emit
# one ``resize_bench/v1`` JSON line with the per-stage downtime
# breakdown (detect / kill / barrier / restore / compile / first_step),
# the restore stages read back from the trainer's published
# ``resize_timing_r<rank>`` record (SERVICE_METRICS; absolute unix
# stamps align with this driver's clock).

# reshard_s: in-place live-resize stage (drain + mesh rebuild + state
# reshard); 0.0 for every stop-resume arc, which instead pays
# kill/barrier/restore. Old resize_bench/v1 records simply lack the key
# and _peer_result defaults it — the schema is append-only.
BREAKDOWN_STAGES = ("detect_s", "kill_s", "barrier_s", "restore_s",
                    "reshard_s", "compile_s", "first_step_s")


def _peer_result(tag, args, mode, total_s, breakdown, restore,
                 **extras):
    out = {
        "schema": "resize_bench/v1",
        "metric": "resize_downtime_s_%s" % tag,
        "value": round(total_s, 3),
        "unit": "s",
        "arc": tag,
        "mode": mode,
        "platform": args.platform,
        "breakdown": {k: round(float(breakdown.get(k, 0.0)), 3)
                      for k in BREAKDOWN_STAGES},
        "restore": restore,
    }
    out.update(extras)
    return out


def _spawn_holdout(store_endpoint, job_id, ckpt_dir, ready_file,
                   log_dir, extra_env):
    env = dict(os.environ)
    env.update(extra_env or {})
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"  # serves numpy buffers; never needs TPU
    os.makedirs(log_dir, exist_ok=True)
    log = open(os.path.join(log_dir, "holdout.log"), "ab")
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "edl_tpu.tools.peer_holdout",
         "--store_endpoints", store_endpoint, "--job_id", job_id,
         "--ckpt", ckpt_dir, "--ready_file", ready_file],
        env=env, stdout=log, stderr=subprocess.STDOUT,
        preexec_fn=os.setsid)
    log.close()
    return proc


def _wait_file(path, timeout, proc=None, what="holdout ready"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if os.path.exists(path) and open(path).read().strip():
            return open(path).read().strip()
        if proc is not None and proc.poll() is not None:
            raise RuntimeError("%s: process exited rc=%r"
                               % (what, proc.returncode))
        time.sleep(0.1)
    raise TimeoutError("%s not reached in %.0fs" % (what, timeout))


def _read_resize_timing(coord, after_ts, timeout):
    """The respawned trainer's resize_timing record (published at its
    first post-restore step). ``after_ts`` filters out the previous
    incarnation's record under the same permanent key."""
    from edl_tpu.controller import constants as C
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        try:
            for name, value in coord.get_service(C.SERVICE_METRICS):
                if not name.startswith("resize_timing_r"):
                    continue
                rec = json.loads(value)
                if (rec.get("t_construct", 0) >= after_ts
                        and "t_first_step" in rec):
                    return rec
        except Exception:  # noqa: BLE001 — store may flap mid-restart
            pass
        time.sleep(0.2)
    raise TimeoutError("resize_timing record not published in %.0fs"
                       % timeout)


def run_peer_arc(peer, args):
    """Pod-based peer_restore arc: train -> (holdout) -> SIGKILL ->
    respawn -> first step, per-stage breakdown from the trainer's
    published timing."""
    from edl_tpu.coordination.client import CoordClient
    from edl_tpu.tools.fake_gcs import FakeGCSServer

    tag = "peer_restore_%s" % ("on" if peer else "off")
    tmp = tempfile.mkdtemp(prefix="measure_%s_" % tag)
    gcs = FakeGCSServer().start()
    ckpt_dir = "gs://resize-bench/ckpt"
    extra_env = {
        "STORAGE_EMULATOR_HOST": gcs.endpoint,
        # stream layout: the format both the peer publish path and the
        # per-span FS fallback serve
        "EDL_TPU_ASYNC_SAVE": "1",
        "EDL_TPU_PEER_RESTORE": "1" if peer else "0",
    }
    store = _spawn_store()
    job_id = "rz_%s_%d" % (tag, os.getpid())
    coord = CoordClient([store.endpoint], root=job_id)
    pod = holdout = None
    try:
        # both restarts of this arc compile cold (each incarnation gets
        # its own empty cache): the on/off pair differs in restore only
        pod = _spawn_pod(store.endpoint, job_id,
                         os.path.join(tmp, "logs"), ckpt_dir,
                         os.path.join(tmp, "cache"),
                         args, extra_env=extra_env)
        s0, t_first = _wait_step(coord,
                                 lambda s: s >= args.steps_per_epoch,
                                 args.timeout, pod)
        if peer:
            ready = os.path.join(tmp, "holdout.ready")
            holdout = _spawn_holdout(store.endpoint, job_id, ckpt_dir,
                                     ready, os.path.join(tmp, "logs"),
                                     {"STORAGE_EMULATOR_HOST":
                                      gcs.endpoint})
            _wait_file(ready, args.timeout, holdout)
        t_kill = time.time()
        _kill_group(pod)
        t_killed = time.time()
        base = _store_step(coord)
        base = s0 if base is None else max(base, s0)
        t_spawn = time.time()
        pod = _spawn_pod(store.endpoint, job_id,
                         os.path.join(tmp, "logs2"), ckpt_dir,
                         os.path.join(tmp, "cache_cold"),
                         args, extra_env=extra_env)
        s1, _ = _wait_step(coord, lambda s: s > base, args.timeout, pod)
        rec = _read_resize_timing(coord, after_ts=t_kill, timeout=30.0)
        breakdown = {
            "detect_s": t_spawn - t_killed,
            "kill_s": t_killed - t_kill,
            "barrier_s": max(0.0, rec["t_resume_start"] - t_spawn),
            "restore_s": rec.get("restore_s", 0.0),
            "compile_s": rec.get("compile_s", 0.0),
            "first_step_s": rec.get("first_step_s", 0.0),
        }
        restore = {"source": rec.get("restore_source"),
                   "bytes": rec.get("restore_bytes"),
                   "peers": rec.get("restore_peers"),
                   "version": rec.get("version")}
        out = _peer_result(
            tag, args, "pod", rec["t_first_step"] - t_kill, breakdown,
            restore,
            initial_launch_to_first_epoch_s=round(t_first, 1),
            pre_kill_step=s0, first_post_restore_step=s1,
            steps_per_epoch=args.steps_per_epoch, batch=args.batch,
            image_size=args.image_size)
        if peer and rec.get("restore_source") == "fs":
            out["warning"] = ("peer arc fell back to FS — no live peer "
                              "covered the resumed version")
        return out
    finally:
        for proc in (pod, holdout):
            if proc is not None:
                _kill_group(proc)
        store.stop()
        gcs.stop()
        if os.environ.get("MEASURE_RESIZE_KEEP"):
            print("kept workdir: %s" % tmp, file=sys.stderr)
        else:
            shutil.rmtree(tmp, ignore_errors=True)


def run_peer_arc_micro(peer, args):
    """In-process micro arc: save one stream checkpoint behind fake
    GCS, then time a placed restore with (``peer``) a holdout peer
    serving it from RAM vs without (storage path). Hermetic and fast —
    this is the tier-1 smoke arc; detect/kill/barrier are not exercised
    and report 0."""
    import numpy as np

    from edl_tpu.coordination.client import CoordClient
    from edl_tpu.runtime.checkpoint import CheckpointManager
    from edl_tpu.runtime.fs import GCSFS
    from edl_tpu.tools.fake_gcs import FakeGCSServer

    import jax

    tag = "peer_restore_%s" % ("on" if peer else "off")
    tmp = tempfile.mkdtemp(prefix="measure_%s_micro_" % tag)
    gcs = FakeGCSServer().start()
    ckpt_dir = "gs://resize-bench/ckpt"
    cm = CheckpointManager(ckpt_dir, fs=GCSFS(endpoint=gcs.endpoint))
    store = _spawn_store()
    job_id = "rzm_%s_%d" % (tag, os.getpid())
    coord = CoordClient([store.endpoint], root=job_id)
    holdout = None
    try:
        rng = np.random.RandomState(0)
        n = max(1, int(args.micro_mb))
        tree = {"layer%d" % i: rng.standard_normal(
            (256, 1024)).astype(np.float32) for i in range(n)}
        cm.save_async(1, tree, meta={"bench": tag}).result(60.0)
        dev = jax.devices()[0]
        sharding = jax.sharding.SingleDeviceSharding(dev)
        shardings = {k: sharding for k in tree}
        if peer:
            from edl_tpu.runtime.state_server import PeerRestorer
            ready = os.path.join(tmp, "holdout.ready")
            holdout = _spawn_holdout(store.endpoint, job_id, ckpt_dir,
                                     ready, tmp,
                                     {"STORAGE_EMULATOR_HOST":
                                      gcs.endpoint})
            _wait_file(ready, args.timeout, holdout)
            t0 = time.perf_counter()
            _, restored, _, stats = PeerRestorer(
                coord, cm).restore_placed(1, tree, shardings)
            restore_s = time.perf_counter() - t0
            restore = {"source": stats["source"],
                       "bytes": stats["peer_bytes"],
                       "peers": stats["peers"], "version": 1}
        else:
            t0 = time.perf_counter()
            _, restored, _ = cm.restore_placed(1, tree, shardings)
            restore_s = time.perf_counter() - t0
            nbytes = sum(int(a.nbytes)
                         for a in jax.tree_util.tree_leaves(restored))
            restore = {"source": "fs", "bytes": nbytes, "peers": 0,
                       "version": 1}
        # compile + first step on the restored state: a tiny jitted
        # reduction stands in for the example's step (the micro arc
        # times the RESTORE paths; steps are the pod arcs' job)
        step = jax.jit(lambda t: sum(x.sum()
                                     for x in jax.tree_util
                                     .tree_leaves(t)))
        c0 = time.perf_counter()
        jax.block_until_ready(step(restored))
        compile_s = time.perf_counter() - c0
        c1 = time.perf_counter()
        jax.block_until_ready(step(restored))
        first_step_s = time.perf_counter() - c1
        breakdown = {"detect_s": 0.0, "kill_s": 0.0, "barrier_s": 0.0,
                     "restore_s": restore_s, "compile_s": compile_s,
                     "first_step_s": first_step_s}
        return _peer_result(
            tag, args, "micro",
            restore_s + compile_s + first_step_s, breakdown, restore,
            micro_mb=n, state_bytes=n * 256 * 1024 * 4)
    finally:
        if holdout is not None:
            _kill_group(holdout)
        cm.close()
        store.stop()
        gcs.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def _spawn_redundancy_holdout(store_endpoint, job_id, rank, ready_file,
                              log_dir, kill=0):
    """A surviving-partner stand-in (tools/peer_holdout.py
    --redundancy): accepts erasure-coded shards and serves them back.
    ``kill=N`` SIGKILLs it when the Nth state.shard read arrives — the
    decode-with-missing-partner drill."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    os.makedirs(log_dir, exist_ok=True)
    log = open(os.path.join(log_dir, "holdout_r%d.log" % rank), "ab")
    cmd = [sys.executable, "-u", "-m", "edl_tpu.tools.peer_holdout",
           "--store_endpoints", store_endpoint, "--job_id", job_id,
           "--redundancy", "--rank", str(rank),
           "--ready_file", ready_file]
    if kill:
        cmd += ["--kill", str(kill)]
    proc = subprocess.Popen(cmd, env=env, stdout=log,
                            stderr=subprocess.STDOUT,
                            preexec_fn=os.setsid)
    log.close()
    return proc


class _CountingFS(object):
    """FS wrapper that counts read operations — the kill arc's proof
    that the parity rebuild issued ZERO FS reads."""

    def __init__(self, fs):
        self._fs = fs
        self.reads = 0

    def open(self, path, mode):
        if "r" in mode:
            self.reads += 1
        return self._fs.open(path, mode)

    def read_range(self, path, offset, length):
        self.reads += 1
        return self._fs.read_range(path, offset, length)

    def listdir(self, path):
        self.reads += 1
        return self._fs.listdir(path)

    def exists(self, path):
        self.reads += 1
        return self._fs.exists(path)

    def __getattr__(self, name):
        return getattr(self._fs, name)


def run_kill_pod_arc_micro(args):
    """Kill-one-pod micro arc (diskless fault tolerance,
    runtime/redundancy.py). An in-process "victim pod" saves a stream
    checkpoint behind fake GCS and pushes k=2,m=1 erasure-coded shards
    of its committed snapshot to three surviving-partner stand-ins,
    one of which is armed to SIGKILL itself on the first rebuild touch
    (the decode-with-missing-partner path). The victim then "dies" and
    recovery walks the real ladder — peer rung (no peers: everything
    is dead), then parity — and the arc proves:

    - the parity restore is byte-identical to the FS restore,
    - with ``fs_reads == 0`` (a counting FS wrapper sees the window),
    - surviving the mid-rebuild partner kill,
    - and a chaos-faulted rebuild (``redundancy.rebuild:error``)
      degrades to the FS rung byte-identically (``fallback_drill``).

    Hermetic and in-process; this is the tier-1 smoke arc for the
    redundancy tier. Always micro — there is no pod-fleet variant."""
    import numpy as np

    from edl_tpu.coordination.client import CoordClient
    from edl_tpu.robustness import faults
    from edl_tpu.runtime import redundancy
    from edl_tpu.runtime.checkpoint import CheckpointManager
    from edl_tpu.runtime.fs import GCSFS
    from edl_tpu.runtime.state_server import (PeerRestorer,
                                              snapshot_entries)
    from edl_tpu.tools.fake_gcs import FakeGCSServer
    from edl_tpu.utils import errors

    import jax

    tag = "kill_pod"
    tmp = tempfile.mkdtemp(prefix="measure_%s_micro_" % tag)
    gcs = FakeGCSServer().start()
    ckpt_dir = "gs://resize-bench/ckpt"
    fs = _CountingFS(GCSFS(endpoint=gcs.endpoint))
    cm = CheckpointManager(ckpt_dir, fs=fs)
    store = _spawn_store()
    job_id = "rzm_%s_%d" % (tag, os.getpid())
    coord = CoordClient([store.endpoint], root=job_id)
    holdouts = []
    plane = None
    try:
        rng = np.random.RandomState(0)
        n = max(1, int(args.micro_mb))
        tree = {"layer%d" % i: rng.standard_normal(
            (256, 1024)).astype(np.float32) for i in range(n)}
        cm.save_async(1, tree, meta={"bench": tag}).result(60.0)
        dev = jax.devices()[0]
        sharding = jax.sharding.SingleDeviceSharding(dev)
        shardings = {k: sharding for k in tree}

        # three surviving partners; rank 9102 dies on its first
        # state.shard read, so the decode must finish from the other
        # two (9102 holds data shard 1 — the rebuild is forced through
        # the parity shard and a real GF(256) matrix inversion)
        kill_rank = 9102
        for rank in (9101, 9102, 9103):
            ready = os.path.join(tmp, "holdout_%d.ready" % rank)
            proc = _spawn_redundancy_holdout(
                store.endpoint, job_id, rank, ready, tmp,
                kill=1 if rank == kill_rank else 0)
            holdouts.append((rank, proc))
            _wait_file(ready, args.timeout, proc,
                       what="redundancy holdout r%d" % rank)

        # the victim's commit-path hand-off (trainer save() does this
        # on the persist driver thread)
        entries, dtags = snapshot_entries(tree)
        push = redundancy.push_shards(coord, "victim", 1, entries,
                                      dtags, meta={"bench": tag},
                                      k=2, m=1)
        if push["pushed"] != 3:
            raise RuntimeError("expected 3 shards pushed, got %r"
                               % (push,))

        # FS baseline: the cold-layer restore the parity rung
        # replaces. Best-of-3, same as the parity window below — the
        # bench guard gates parity < FS, so both sides get the same
        # noise shield.
        fs_times = []
        for _ in range(3):
            fs.reads = 0
            t0 = time.perf_counter()
            _, fs_tree, _ = cm.restore_placed(1, tree, shardings)
            fs_times.append(time.perf_counter() - t0)
        fs_baseline = {"restore_s": round(min(fs_times), 3),
                       "fs_reads": int(fs.reads)}

        # the kill: the victim is gone (this process just drops its
        # state); recovery walks the ladder — peers first (none live),
        # then the parity rung. fs.reads counts BOTH passes: the
        # first one eats the mid-rebuild partner SIGKILL (its time is
        # kept as cold_restore_s), the rest are clean repeats.
        fs.reads = 0
        parity_times = []
        for _ in range(3):
            t0 = time.perf_counter()
            try:
                PeerRestorer(coord, cm).restore_placed(
                    1, tree, shardings)
                raise RuntimeError("peer rung unexpectedly served a "
                                   "world with no survivors")
            except errors.PeerRestoreError:
                pass  # expected: every state-holding pod is dead
            _, parity_tree, _, stats = redundancy.restore_placed(
                coord, 1, tree, shardings)
            parity_times.append(time.perf_counter() - t0)
        restore_s = min(parity_times)
        parity_fs_reads = int(fs.reads)

        killed = next(p for r, p in holdouts if r == kill_rank)
        try:  # SIGKILLed itself mid-rebuild, by design
            killed.wait(timeout=30)
            killed_partner = True
        except subprocess.TimeoutExpired:
            killed_partner = False

        def _identical(a, b):
            fa = jax.tree_util.tree_leaves(a)
            fb = jax.tree_util.tree_leaves(b)
            return len(fa) == len(fb) and all(
                np.asarray(x).tobytes() == np.asarray(y).tobytes()
                for x, y in zip(fa, fb))

        byte_identical = _identical(parity_tree, fs_tree)

        # chaos drill: a faulted rebuild must degrade to the FS rung
        # losslessly (and be visible: fault fired, fallback recorded)
        plane = faults.FaultPlane(seed=0).install()
        fault = plane.inject("redundancy.rebuild", "error")
        fs.reads = 0
        drill_source = "parity"
        try:
            redundancy.restore_placed(coord, 1, tree, shardings)
        except errors.RedundancyError:
            drill_source = "fs"
        _, drill_tree, _ = cm.restore_placed(1, tree, shardings)
        fallback_drill = {
            "fault_fired": bool(fault.fired),
            "source": drill_source,
            "fs_reads": int(fs.reads),
            "byte_identical": _identical(drill_tree, fs_tree)}

        # compile + first step on the parity-restored state (same
        # stand-in step as the peer micro arcs)
        step = jax.jit(lambda t: sum(x.sum()
                                     for x in jax.tree_util
                                     .tree_leaves(t)))
        c0 = time.perf_counter()
        jax.block_until_ready(step(parity_tree))
        compile_s = time.perf_counter() - c0
        c1 = time.perf_counter()
        jax.block_until_ready(step(parity_tree))
        first_step_s = time.perf_counter() - c1

        breakdown = {"detect_s": 0.0, "kill_s": 0.0, "barrier_s": 0.0,
                     "restore_s": restore_s, "compile_s": compile_s,
                     "first_step_s": first_step_s}
        restore = {"source": stats["source"],
                   "bytes": stats["parity_bytes"],
                   "peers": stats["holders"], "version": 1,
                   "fs_reads": parity_fs_reads,
                   "owners": stats["owners"],
                   "killed_partner": bool(killed_partner),
                   "cold_restore_s": round(parity_times[0], 3),
                   "byte_identical": bool(byte_identical)}
        return _peer_result(
            tag, args, "micro",
            restore_s + compile_s + first_step_s, breakdown, restore,
            micro_mb=n, state_bytes=n * 256 * 1024 * 4,
            shards={"k": 2, "m": 1, "pushed": push["pushed"]},
            fs_baseline=fs_baseline, fallback_drill=fallback_drill)
    finally:
        if plane is not None:
            plane.uninstall()
        for _rank, proc in holdouts:
            _kill_group(proc)
        cm.close()
        store.stop()
        gcs.stop()
        shutil.rmtree(tmp, ignore_errors=True)


# -- live vs stop-resume arcs (zero-downtime in-place resize) --------------
#
# live: one resize_worker process on --from_devices devices; the driver
# plays the coordinator — claims the leader key, publishes a prepare
# intent through the live-resize 2PC, waits for the worker's ack, and
# commits. The worker drains, reshards IN PLACE, and keeps stepping;
# "downtime" is the training pause (t_first_step - t_resume_start) —
# kill_s and barrier_s are structurally 0 because no process dies.
# A second intent grows the world back, proving the arc is reversible
# within one process lifetime.
#
# stop_resume: the SAME worker, but the driver SIGKILLs it and respawns
# on the shrunken world; the classic ladder (kill + detect + respawn +
# restore + compile) measured with the same record plumbing. The pair
# is the paper's headline comparison.


def _spawn_worker(store_endpoint, job_id, log_dir, args, n_devices,
                  cache_dir, prewarm_worlds="", ckpt="",
                  who="bench_worker"):
    env = dict(os.environ)
    if args.platform == "cpu":
        from edl_tpu.utils.cpu_mesh import force_cpu_env
        # the process always SEES from_devices virtual devices; the
        # worker meshes the first n of them — so a live shrink and a
        # stop-resume respawn run in identical device environments
        force_cpu_env(env, max(n_devices, args.from_devices))
    env.update({"PYTHONPATH": REPO, "EDL_TPU_POD_IP": "127.0.0.1",
                "EDL_TPU_TTL": "3",
                "JAX_COMPILATION_CACHE_DIR": cache_dir})
    os.makedirs(log_dir, exist_ok=True)
    log = open(os.path.join(log_dir, "worker.log"), "ab")
    cmd = [sys.executable, "-u", "-m", "edl_tpu.tools.resize_worker",
           "--store_endpoints", store_endpoint, "--job_id", job_id,
           "--who", who, "--n_devices", str(n_devices),
           "--total_batch", str(args.batch)]
    if prewarm_worlds:
        cmd += ["--prewarm_worlds", prewarm_worlds]
    if ckpt:
        cmd += ["--ckpt", ckpt]
    if getattr(args, "mesh", ""):
        cmd += ["--mesh", args.mesh]
    proc = subprocess.Popen(cmd, env=env, stdout=log,
                            stderr=subprocess.STDOUT,
                            preexec_fn=os.setsid)
    log.close()
    return proc


def _read_worker_step(coord):
    from edl_tpu.controller import constants as C
    try:
        raw = coord.get_value(C.SERVICE_METRICS, "worker_step")
        return None if not raw else json.loads(raw)
    except Exception:  # noqa: BLE001 — store may flap mid-restart
        return None


def _wait_worker_step(coord, pred, timeout, proc=None):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        rec = _read_worker_step(coord)
        if rec is not None and pred(rec):
            return rec
        if proc is not None and proc.poll() is not None:
            raise RuntimeError("worker exited rc=%r before the step "
                               "predicate" % proc.returncode)
        time.sleep(0.2)
    raise TimeoutError("worker step predicate not reached in %.0fs"
                       % timeout)


def _drive_live_resize(coord, who, n_devices, timeout, mesh=None):
    """Publish a prepare intent for ``who`` → wait for the ack → commit;
    returns (t_intent, timing_rec). The caller must hold the leader key
    as 'bench_driver'. ``mesh`` ({axis: size}) rides the intent so the
    worker rebuilds that factorization instead of pure dp."""
    import uuid

    from edl_tpu.runtime import live_resize as live_mod

    t_intent = time.time()
    intent = live_mod.make_intent(uuid.uuid4().hex, [who],
                                  devices=int(n_devices),
                                  leader="bench_driver", mesh=mesh,
                                  deadline_s=timeout)
    if not live_mod.publish_prepare(coord, "bench_driver", intent):
        raise RuntimeError("bench driver does not hold the leader key")
    ok, acks = live_mod.wait_for_acks(coord, intent, timeout)
    if not ok:
        live_mod.abort(coord, "bench_driver", intent,
                       reason="bench ack wait failed")
        raise RuntimeError("live resize to %d not acked ok: %r"
                           % (n_devices, acks))
    live_mod.commit(coord, "bench_driver", intent)
    rec = _read_resize_timing(coord, after_ts=t_intent, timeout=timeout)
    if rec.get("mode") != "live":
        raise RuntimeError("expected a live timing record, got %r"
                           % rec.get("mode"))
    return t_intent, rec


def run_live_arc(args):
    from edl_tpu.controller import constants as C
    from edl_tpu.coordination.client import CoordClient

    tag = "live"
    n_hi = args.from_devices
    n_lo = max(1, n_hi // 2)
    tmp = tempfile.mkdtemp(prefix="measure_live_")
    cache = os.path.join(tmp, "cache")
    os.makedirs(cache)
    store = _spawn_store()
    job_id = "rz_live_%d" % os.getpid()
    coord = CoordClient([store.endpoint], root=job_id)
    worker = None
    wait_s = min(args.timeout, 120.0)
    try:
        worker = _spawn_worker(store.endpoint, job_id,
                               os.path.join(tmp, "logs"), args, n_hi,
                               cache_dir=cache, prewarm_worlds=str(n_lo),
                               ckpt=os.path.join(tmp, "ckpt"))
        _wait_worker_step(coord, lambda r: r["step"] >= 3, args.timeout,
                          worker)
        coord.set_server_permanent(C.SERVICE_LEADER, C.LEADER_SERVER,
                                   "bench_driver")
        # a sharded arc (--mesh dp,tp) pins the model axes on the
        # intent; dp is left to the trainer to fill from the world size
        intent_mesh = None
        if getattr(args, "mesh", ""):
            from edl_tpu.runtime.mesh import parse_mesh_arg
            intent_mesh = {a: s for a, s in
                           parse_mesh_arg(args.mesh).items()
                           if a != "dp" and s} or None
        t_intent, rec = _drive_live_resize(coord, "bench_worker", n_lo,
                                           wait_s, mesh=intent_mesh)
        pause = rec["t_first_step"] - rec["t_resume_start"]
        breakdown = {
            "detect_s": max(0.0, rec["t_resume_start"] - t_intent),
            "kill_s": 0.0, "barrier_s": 0.0, "restore_s": 0.0,
            "reshard_s": (rec.get("drain_s", 0.0)
                          + rec.get("reshard_s", 0.0)),
            "compile_s": rec.get("compile_s", 0.0),
            "first_step_s": rec.get("first_step_s", 0.0),
        }
        restore = {"source": rec.get("restore_source"),
                   "bytes": rec.get("restore_bytes"),
                   "peers": rec.get("restore_peers"),
                   "version": rec.get("version")}
        # grow back to the full world: same process, second intent
        _, rec_up = _drive_live_resize(coord, "bench_worker", n_hi,
                                       wait_s, mesh=intent_mesh)
        alive = worker.poll() is None
        out = _peer_result(
            tag, args, "live", pause, breakdown, restore,
            from_devices=n_hi, to_devices=n_lo,
            prewarm=rec.get("prewarm"),
            drain_s=round(rec.get("drain_s", 0.0), 3),
            ledger=rec.get("ledger"),
            mesh=rec.get("mesh"), from_mesh=rec.get("from_mesh"),
            process_survived=alive,
            grow={"to_devices": n_hi,
                  "pause_s": round(rec_up["t_first_step"]
                                   - rec_up["t_resume_start"], 3),
                  "mesh": rec_up.get("mesh"),
                  "prewarm": rec_up.get("prewarm")})
        if not alive:
            out["warning"] = ("worker process exited during the live "
                              "arc — the in-place path did not hold")
        return out
    finally:
        if worker is not None:
            _kill_group(worker)
        store.stop()
        if os.environ.get("MEASURE_RESIZE_KEEP"):
            print("kept workdir: %s" % tmp, file=sys.stderr)
        else:
            shutil.rmtree(tmp, ignore_errors=True)


def run_stop_resume_arc(args):
    import glob as glob_mod

    from edl_tpu.coordination.client import CoordClient

    tag = "stop_resume"
    n_hi = args.from_devices
    n_lo = max(1, n_hi // 2)
    tmp = tempfile.mkdtemp(prefix="measure_stop_resume_")
    cache = os.path.join(tmp, "cache")
    os.makedirs(cache)
    ckpt = os.path.join(tmp, "ckpt")
    store = _spawn_store()
    job_id = "rz_sr_%d" % os.getpid()
    coord = CoordClient([store.endpoint], root=job_id)
    worker = None
    try:
        worker = _spawn_worker(store.endpoint, job_id,
                               os.path.join(tmp, "logs"), args, n_hi,
                               cache_dir=cache, ckpt=ckpt)
        # at least one committed checkpoint before the kill, or the
        # respawn has nothing to resume (worker saves every 5 steps)
        _wait_worker_step(coord, lambda r: r["step"] >= 7, args.timeout,
                          worker)
        t0 = time.monotonic()
        while not glob_mod.glob(os.path.join(ckpt, "v_*")):
            if time.monotonic() - t0 > args.timeout:
                raise TimeoutError("no checkpoint committed before kill")
            time.sleep(0.2)
        t_kill = time.time()
        _kill_group(worker)
        t_killed = time.time()
        t_spawn = time.time()
        worker = _spawn_worker(store.endpoint, job_id,
                               os.path.join(tmp, "logs2"), args, n_lo,
                               cache_dir=cache, ckpt=ckpt)
        rec = _read_resize_timing(coord, after_ts=t_kill,
                                  timeout=args.timeout)
        breakdown = {
            "detect_s": t_spawn - t_killed,
            "kill_s": t_killed - t_kill,
            "barrier_s": max(0.0, rec["t_resume_start"] - t_spawn),
            "restore_s": rec.get("restore_s", 0.0),
            "reshard_s": 0.0,
            "compile_s": rec.get("compile_s", 0.0),
            "first_step_s": rec.get("first_step_s", 0.0),
        }
        restore = {"source": rec.get("restore_source"),
                   "bytes": rec.get("restore_bytes"),
                   "peers": rec.get("restore_peers"),
                   "version": rec.get("version")}
        # pause_in_process_s: the respawned trainer's own restore +
        # first-step window — the portion of the downtime its time
        # ledger can see (kill/respawn time belongs to no process)
        return _peer_result(
            tag, args, "stop_resume", rec["t_first_step"] - t_kill,
            breakdown, restore, from_devices=n_hi, to_devices=n_lo,
            pause_in_process_s=round(
                rec["t_first_step"] - rec["t_resume_start"], 3),
            mesh=rec.get("mesh"), ledger=rec.get("ledger"))
    finally:
        if worker is not None:
            _kill_group(worker)
        store.stop()
        if os.environ.get("MEASURE_RESIZE_KEEP"):
            print("kept workdir: %s" % tmp, file=sys.stderr)
        else:
            shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None):
    p = argparse.ArgumentParser("measure kill->first-step recovery")
    p.add_argument("--arcs", default="cold,warm")
    p.add_argument("--steps_per_epoch", type=int, default=20)
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--image_size", type=int, default=224)
    p.add_argument("--timeout", type=float, default=600.0)
    p.add_argument("--dtype", default="bf16",
                   help="bf16 on TPU; use f32 for CPU arcs (XLA CPU "
                        "emulates bf16 an order of magnitude slower)")
    p.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                   help="cpu = virtual-device worlds for the resize "
                        "arcs (hermetic); tpu inherits the host's TPU "
                        "env (the world-changing arcs then need a "
                        "multi-chip host)")
    p.add_argument("--from_devices", type=int, default=2,
                   help="resize arcs shrink from this world to half "
                        "of it (8 for the queued TPU run)")
    p.add_argument("--mesh", default="",
                   help='worker mesh factorization for the live/'
                        'stop_resume arcs, e.g. "dp,tp" — the model '
                        "axes ride the resize intent so the shrunken "
                        "world keeps them (sharded-state arcs)")
    p.add_argument("--micro", action="store_true",
                   help="peer_restore arcs only: hermetic in-process "
                        "restore-path timing instead of the full pod "
                        "kill/respawn (the tier-1 smoke mode)")
    p.add_argument("--micro_mb", type=int, default=64,
                   help="approximate micro-arc state size in MB")
    args = p.parse_args(argv)
    if args.platform == "cpu":
        # the micro arcs run jax IN this process; the pod arcs only
        # inherit — either way a CPU run must never grab the TPU
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    rc = 0
    for tag in args.arcs.split(","):
        tag = tag.strip()
        try:
            if tag in ("peer_restore_on", "peer_restore_off"):
                out = (run_peer_arc_micro if args.micro
                       else run_peer_arc)(tag.endswith("_on"), args)
            elif tag == "kill_pod":
                out = run_kill_pod_arc_micro(args)
            elif tag == "live":
                out = run_live_arc(args)
            elif tag == "stop_resume":
                out = run_stop_resume_arc(args)
            elif tag in ("resize_prewarm_on", "resize_prewarm_off"):
                out = run_resize_arc(tag.endswith("_on"), args)
            else:
                out = run_arc(tag, args)
            print(json.dumps(out), flush=True)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"metric": "resize_recovery_%s" % tag,
                              "error": repr(e)}), flush=True)
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
