"""The minimum end-to-end slice (SURVEY.md §7.3): fit_a_line under the
launcher — barrier → train → per-epoch checkpoint → forced resize →
resume-from-checkpoint → completion. Real launcher + trainer processes, CPU
devices, real multi-process jax.distributed when world > 1."""

import json
import os
import subprocess
import sys
import time

import pytest

from edl_tpu.controller import cluster as cluster_mod
from edl_tpu.controller import status
from edl_tpu.controller.status import Status

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "examples", "fit_a_line", "train.py")


def _spawn(store_endpoint, job_id, nodes_range, tmp_path, name,
           script_args=()):
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO,
        "EDL_TPU_POD_IP": "127.0.0.1",
        "EDL_TPU_TTL": "3",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
    })
    log = open(str(tmp_path / ("%s.log" % name)), "wb")
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "edl_tpu.controller.launch",
         "--job_id", job_id, "--store_endpoints", store_endpoint,
         "--nodes_range", nodes_range,
         "--checkpoint_path", str(tmp_path / "ckpt"),
         "--log_dir", str(tmp_path / ("%s_logs" % name)),
         SCRIPT] + list(script_args),
        env=env, stdout=log, stderr=subprocess.STDOUT,
        preexec_fn=os.setsid)
    log.close()
    return proc


def _logs(tmp_path):
    out = []
    for root, _, files in os.walk(str(tmp_path)):
        for f in files:
            if f.endswith(".log") or f.startswith("workerlog"):
                p = os.path.join(root, f)
                with open(p, "rb") as fh:
                    out.append("== %s ==\n%s" % (
                        p, fh.read().decode("utf-8", "replace")))
    return "\n".join(out)


@pytest.mark.integration
def test_fit_a_line_single_pod(store, tmp_path):
    coord = store.client(root="fal1")
    p = _spawn(store.endpoint, "fal1", "1:1", tmp_path, "pod1",
               ("--epochs", "3", "--steps_per_epoch", "10"))
    try:
        assert p.wait(timeout=180) == 0, _logs(tmp_path)
        assert status.load_job_status(coord) == Status.SUCCEED
        log = (tmp_path / "pod1_logs" / "workerlog.0").read_text()
        result = json.loads([l for l in log.splitlines()
                             if l.startswith("{")][-1])
        assert result["steps"] == 30
        assert result["final_loss"] < 0.05, log
        # per-epoch checkpoints committed
        ckpts = [d for d in os.listdir(str(tmp_path / "ckpt"))
                 if d.startswith("v_")]
        assert len(ckpts) == 3, ckpts
    finally:
        p.kill()


@pytest.mark.integration
def test_fit_a_line_elastic_resize_resume(store, tmp_path):
    """1 pod trains slowly; pod2 joins (resize to world=2, multi-process
    jax.distributed); trainers restart and RESUME from the checkpoint
    instead of starting over."""
    coord = store.client(root="fal2")
    slow = ("--epochs", "4", "--steps_per_epoch", "10", "--step_sleep",
            "0.25")
    p1 = _spawn(store.endpoint, "fal2", "1:2", tmp_path, "pod1", slow)
    p2 = None
    try:
        # wait for pod1's first checkpoint (epoch 0 done)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            d = tmp_path / "ckpt"
            if d.exists() and any(n.startswith("v_") for n in
                                  os.listdir(str(d))):
                break
            time.sleep(0.5)
        else:
            raise AssertionError("no checkpoint appeared\n" + _logs(tmp_path))

        p2 = _spawn(store.endpoint, "fal2", "1:2", tmp_path, "pod2", slow)
        assert p1.wait(timeout=300) == 0, _logs(tmp_path)
        assert p2.wait(timeout=300) == 0, _logs(tmp_path)
        assert status.load_job_status(coord) == Status.SUCCEED

        log1 = (tmp_path / "pod1_logs" / "workerlog.0").read_text()
        # the restarted trainer resumed from a non-zero epoch
        resumes = [l for l in log1.splitlines() if "resumed=True" in l]
        assert resumes, log1
        assert any("world=2" in l for l in resumes), log1
        result = json.loads([l for l in log1.splitlines()
                             if l.startswith("{")][-1])
        assert result["world"] == 2
        assert result["final_loss"] < 0.05
    finally:
        p1.kill()
        if p2 is not None:
            p2.kill()
