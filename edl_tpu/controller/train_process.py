"""Trainer subprocess management: spawn with the env contract, watch, kill.

Reference parity: edl/utils/train_process.py — the PADDLE_* env contract
(:46-56) becomes the EDL_TPU_* contract below; process-tree SIGTERM→SIGKILL
via psutil (:89-112); child polling and rank-0 log tailing (:115-188).

The env contract (read back by edl_tpu.controller.env.TrainerEnv):
  EDL_TPU_JOB_ID / EDL_TPU_STORE_ENDPOINTS   job identity + coordination
  EDL_TPU_POD_ID / EDL_TPU_POD_RANK          this host
  EDL_TPU_TRAINER_ID / EDL_TPU_RANK_IN_POD   this process
  EDL_TPU_GLOBAL_RANK / EDL_TPU_WORLD_SIZE   process id / count for
                                             jax.distributed.initialize
  EDL_TPU_COORDINATOR                        rank-0 trainer endpoint
  EDL_TPU_TRAINER_ENDPOINTS                  all trainer endpoints (csv)
  EDL_TPU_LOCAL_DEVICES                      local chip indices (csv;
                                             bookkeeping — nothing
                                             confines a process to them,
                                             see check_chip_ownership)
  EDL_TPU_CLUSTER_STAGE                      stage uuid of this incarnation
  EDL_TPU_MESH                               planned (dp, tp, pp, ep)
                                             factorization (json), when
                                             the generator ran a planner
"""

import json
import os
import subprocess
import sys
import time

import psutil

from edl_tpu.utils.errors import TrainProcessError
from edl_tpu.utils.logger import logger


def check_chip_ownership(pod, environ=None):
    """Refuse a pod whose trainer processes would claim the same chips.

    A chip belongs to one process at a time, and a JAX process claims
    EVERY chip of its host unless libtpu is told otherwise. The launcher
    does not tell it: confining a process needs the host's chip grid and
    a process grid across hosts (TPU_CHIPS_PER_PROCESS_BOUNDS,
    TPU_PROCESS_BOUNDS, TPU_PROCESS_ADDRESSES, ... — established on a
    2x2 v5e host, PR 21), which the pod model does not carry. So more
    than one trainer per pod is accepted only where the trainers cannot
    reach a chip at all — the CPU harness, JAX_PLATFORMS=cpu in the
    environment they inherit. Raises TrainProcessError otherwise."""
    environ = os.environ if environ is None else environ
    if len(pod.trainers) > 1 and environ.get("JAX_PLATFORMS") != "cpu":
        raise TrainProcessError(
            "%d trainer processes on one host would each claim every "
            "local chip (JAX_PLATFORMS=%r): run one trainer per host "
            "(--nproc_per_node 1, the JAX process model) — several per "
            "host is the CPU harness and needs JAX_PLATFORMS=cpu"
            % (len(pod.trainers), environ.get("JAX_PLATFORMS")))


class TrainerProc(object):
    def __init__(self, proc, trainer, log_path):
        self.proc = proc
        self.trainer = trainer
        self.log_path = log_path
        self.log_offset = 0


def start_trainers(job_env, pod, cluster, training_script, script_args,
                   log_dir):
    os.makedirs(log_dir, exist_ok=True)
    endpoints = cluster.trainer_endpoints()
    coordinator = endpoints[0]
    world = cluster.world_size()
    procs = []
    for t in pod.trainers:
        env = dict(os.environ)
        env.update({
            "EDL_TPU_JOB_ID": job_env.job_id,
            "EDL_TPU_STORE_ENDPOINTS": ",".join(job_env.store_endpoints),
            "EDL_TPU_POD_ID": pod.id,
            "EDL_TPU_POD_RANK": str(pod.rank),
            "EDL_TPU_TRAINER_ID": t.id,
            "EDL_TPU_RANK_IN_POD": str(t.rank_in_pod),
            "EDL_TPU_GLOBAL_RANK": str(t.global_rank),
            "EDL_TPU_WORLD_SIZE": str(world),
            "EDL_TPU_COORDINATOR": coordinator,
            "EDL_TPU_TRAINER_ENDPOINTS": ",".join(endpoints),
            "EDL_TPU_TRAINER_ENDPOINT": t.endpoint,
            "EDL_TPU_LOCAL_DEVICES": ",".join(str(d) for d in t.devices),
            "EDL_TPU_CLUSTER_STAGE": cluster.stage,
        })
        if job_env.checkpoint_path:
            env["EDL_TPU_CHECKPOINT_PATH"] = job_env.checkpoint_path
        if getattr(cluster, "mesh", None):
            # the generator's planned (dp, tp, pp, ep) factorization —
            # a stop-resume restart builds the SAME mesh the roofline
            # scored, not a flat dp default
            env["EDL_TPU_MESH"] = json.dumps(cluster.mesh)
        log_path = os.path.join(log_dir,
                                "workerlog.%d" % t.rank_in_pod)
        log_file = open(log_path, "ab", buffering=0)
        cmd = [sys.executable, "-u", training_script] + list(script_args)
        proc = subprocess.Popen(cmd, env=env, stdout=log_file,
                                stderr=subprocess.STDOUT)
        log_file.close()
        logger.info("spawned trainer rank=%s pid=%d log=%s", t.global_rank,
                    proc.pid, log_path)
        procs.append(TrainerProc(proc, t, log_path))
    return procs


def watch_trainers(procs, tail_rank0=True):
    """Poll children. Returns (all_done, any_failed). Tails the rank-0 log
    to our stdout (reference parity: train_process.py:115-127)."""
    alive, failed = False, False
    for tp in procs:
        ret = tp.proc.poll()
        if ret is None:
            alive = True
        elif ret != 0:
            failed = True
            logger.error("trainer pid=%d exited with code %d (log: %s)",
                         tp.proc.pid, ret, tp.log_path)
    if tail_rank0 and procs:
        tp = procs[0]
        try:
            with open(tp.log_path, "rb") as f:
                f.seek(tp.log_offset)
                chunk = f.read()
                tp.log_offset += len(chunk)
            if chunk:
                sys.stdout.write(chunk.decode("utf-8", "replace"))
                sys.stdout.flush()
        except OSError:
            pass
    return (not alive), failed


def terminate_trainers(procs, grace=10.0):
    """SIGTERM the whole process tree of each trainer, SIGKILL stragglers."""
    victims = []
    for tp in procs:
        if tp.proc.poll() is not None:
            continue
        try:
            parent = psutil.Process(tp.proc.pid)
            victims.extend(parent.children(recursive=True))
            victims.append(parent)
        except psutil.NoSuchProcess:
            pass
    for p in victims:
        try:
            p.terminate()
        except psutil.NoSuchProcess:
            pass
    _, survivors = psutil.wait_procs(victims, timeout=grace)
    for p in survivors:
        try:
            p.kill()
        except psutil.NoSuchProcess:
            pass
    for tp in procs:
        try:
            tp.proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            logger.error("trainer pid=%d refused to die", tp.proc.pid)
    time.sleep(0)  # let reaped children settle
