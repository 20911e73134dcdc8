"""One job's preemption and exactly-once arcs: SIGTERM mid-run, the
emergency checkpoint, the resume, and the data plane's accounting."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import REPO, _make_real_dataset
from edl_tpu.controller import status
from edl_tpu.controller.status import Status


@pytest.mark.integration
def test_fit_a_line_preemption_emergency_checkpoint(tmp_path):
    """SIGTERM mid-epoch: the trainer writes an emergency checkpoint at
    the current step, exits 101 (the restart convention), and a restart
    resumes from that step — not from the last epoch boundary."""
    import signal
    import time

    from conftest import cpu_subprocess_env
    env = cpu_subprocess_env(
        2, EDL_TPU_CHECKPOINT_PATH=str(tmp_path / "ckpt"))
    cmd = [sys.executable, "-u",
           os.path.join(REPO, "examples/fit_a_line/train.py"),
           "--epochs", "2", "--steps_per_epoch", "500",
           "--step_sleep", "0.02"]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    # wait for training to actually start (first step done), then preempt
    deadline = time.time() + 60
    lines = []
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line == "" and proc.poll() is not None:
            break  # child died before starting
        lines.append(line)
        if line.startswith("fit_a_line:"):
            break
    time.sleep(2.0)  # a few 20ms steps into epoch 0
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=60)
    lines.append(out)
    assert proc.returncode == 101, "".join(lines)
    assert "preempted" in out, out

    # the emergency checkpoint landed mid-epoch-0 (no epoch-end save
    # exists before step 500)
    from edl_tpu.runtime.checkpoint import CheckpointManager

    versions = CheckpointManager(str(tmp_path / "ckpt")).versions()
    assert versions, out
    emergency_step = versions[-1]
    assert 0 < emergency_step < 500, (versions, out)

    # a restart resumes from it and completes (no sleep: fast finish)
    cmd2 = [sys.executable, "-u",
            os.path.join(REPO, "examples/fit_a_line/train.py"),
            "--epochs", "2", "--steps_per_epoch", "500"]
    proc2 = subprocess.run(cmd2, env=env, capture_output=True, text=True,
                           timeout=150)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert "resumed=True" in proc2.stdout, proc2.stdout
    final = json.loads([l for l in proc2.stdout.splitlines()
                        if l.startswith("{")][-1])
    assert final["steps"] > emergency_step


@pytest.mark.integration
def test_bert_pipeline_preemption_resume(tmp_path):
    """SIGTERM the PIPELINED trainer mid-run: emergency checkpoint with
    pp-sharded stages, exit 101, and a rerun resumes past the preempted
    step — elasticity composed with pipeline parallelism at the process
    level."""
    import signal
    import time

    from conftest import cpu_subprocess_env

    env = cpu_subprocess_env(
        8, EDL_TPU_CHECKPOINT_PATH=str(tmp_path / "ckpt"))
    cmd = [sys.executable, "-u",
           os.path.join(REPO, "examples/bert_pipeline/train.py"),
           "--pp", "4", "--steps", "400", "--d_model", "32",
           "--num_heads", "2", "--mlp_dim", "64", "--seq_len", "16",
           "--vocab_size", "50"]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    deadline = time.time() + 90
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line == "" and proc.poll() is not None:
            raise AssertionError("died before starting")
        if line.startswith("step 5 "):  # compiled and actually stepping
            break
    time.sleep(1.0)
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=60)
    assert proc.returncode == 101, out
    assert "preempted" in out, out

    from edl_tpu.runtime.checkpoint import CheckpointManager

    versions = CheckpointManager(str(tmp_path / "ckpt")).versions()
    assert versions and 0 < versions[-1] < 400, (versions, out)

    proc2 = subprocess.run(
        cmd[:6] + ["40"] + cmd[7:], env=env, capture_output=True,
        text=True, timeout=150)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert "resumed=True step=%d" % versions[-1] in proc2.stdout, \
        proc2.stdout


def _make_linear_dataset(root, files, per_file, seed):
    """Whitespace 'v1 ... v13 y' record files with a learnable linear
    target; returns (root, total_records)."""
    rng = np.random.RandomState(seed)
    w_true = np.linspace(-1.0, 1.0, 13).astype(np.float32)
    root.mkdir()
    total = 0
    for f in range(files):
        lines = []
        for _ in range(per_file):
            x = rng.randn(13).astype(np.float32)
            y = float(x @ w_true + 0.5)
            lines.append(" ".join("%.6f" % v for v in x) + " %.6f" % y)
            total += 1
        (root / ("part%d.txt" % f)).write_text("\n".join(lines))
    return root, total


@pytest.mark.integration
def test_elastic_data_example_end_to_end(store, tmp_path):
    """The data-server path e2e: launcher → trainer → ElasticReader
    (leader balancer + batch serving) → mark_consumed/State checkpoints;
    records_seen must equal the dataset exactly (no loss, no dupes)."""
    import subprocess as sp

    data_dir, total = _make_linear_dataset(tmp_path / "data", files=8,
                                           per_file=64, seed=0)

    from conftest import cpu_subprocess_env
    env = cpu_subprocess_env(8, EDL_TPU_POD_IP="127.0.0.1",
                             EDL_TPU_TTL="3")
    log = open(str(tmp_path / "pod1.log"), "wb")
    p = sp.Popen(
        [sys.executable, "-u", "-m", "edl_tpu.controller.launch",
         "--job_id", "edata", "--store_endpoints", store.endpoint,
         "--nodes_range", "1:1",
         "--checkpoint_path", str(tmp_path / "ckpt"),
         "--log_dir", str(tmp_path / "pod1_logs"),
         os.path.join(REPO, "examples", "elastic_data", "train.py"),
         "--data_dir", str(data_dir), "--batch_size", "16"],
        env=env, stdout=log, stderr=sp.STDOUT, preexec_fn=os.setsid)
    log.close()
    try:
        assert p.wait(timeout=240) == 0, \
            (tmp_path / "pod1.log").read_text()
        worker_log = (tmp_path / "pod1_logs" / "workerlog.0").read_text()
        out = json.loads([l for l in worker_log.splitlines()
                          if l.startswith("{")][-1])
        assert out["records_seen"] == total, out
        assert out["steps"] == total // 16
        assert out["final_loss"] < 0.5, out
        coord = store.client(root="edata")
        assert status.load_job_status(coord) == Status.SUCCEED
    finally:
        try:
            os.killpg(os.getpgid(p.pid), 9)
        except ProcessLookupError:
            pass


@pytest.mark.integration
def test_elastic_data_exactly_once_across_preemption(store, tmp_path):
    """The coherence proof for the data plane + preemption story: a
    SIGTERM mid-consumption writes an emergency checkpoint whose
    consumed-record ranges cover EXACTLY the trained batches (ranges are
    marked before each step), and the restarted run consumes exactly
    the remainder — no record lost, none replayed."""
    import signal as sig
    import subprocess as sp
    import time

    from edl_tpu.runtime.checkpoint import CheckpointManager

    # per_file batch-divisible: a ragged tail is not divisible by the
    # inherited 8-device dp mesh
    data_dir, total = _make_linear_dataset(tmp_path / "data", files=4,
                                           per_file=64, seed=1)

    from conftest import cpu_subprocess_env
    # the launcher env contract, minus the launcher: the coord-backed
    # reader registry needs a trainer identity
    env = cpu_subprocess_env(
        8, EDL_TPU_STORE_ENDPOINTS=store.endpoint,
        EDL_TPU_JOB_ID="eonce", EDL_TPU_POD_ID="pod_eonce",
        EDL_TPU_TRAINER_ID="t0", EDL_TPU_GLOBAL_RANK="0",
        EDL_TPU_WORLD_SIZE="1",
        EDL_TPU_CHECKPOINT_PATH=str(tmp_path / "ckpt"))
    cmd = [sys.executable, "-u",
           os.path.join(REPO, "examples", "elastic_data", "train.py"),
           "--data_dir", str(data_dir), "--batch_size", "8",
           "--step_sleep", "0.15"]
    # unbuffered binary pipe + os.read: select on a TextIOWrapper lies
    # once readline() pulls multiple lines into the user-space buffer,
    # and a bare readline() would block past the deadline on a hang
    import select

    p1 = sp.Popen(cmd, env=env, stdout=sp.PIPE, stderr=sp.STDOUT,
                  bufsize=0)
    fd = p1.stdout.fileno()
    deadline = time.time() + 60
    seen = b""
    while time.time() < deadline and b"elastic_data:" not in seen:
        ready, _, _ = select.select([fd], [], [], 1.0)
        if ready:
            chunk = os.read(fd, 65536)
            if chunk == b"":
                raise AssertionError("run 1 died before starting:\n"
                                     + seen.decode(errors="replace"))
            seen += chunk
        elif p1.poll() is not None:
            raise AssertionError("run 1 died before starting:\n"
                                 + seen.decode(errors="replace"))
    assert b"elastic_data:" in seen, \
        "run 1 never printed its banner within the deadline"
    time.sleep(2.5)  # ~15 batches in
    p1.send_signal(sig.SIGTERM)
    raw1, _ = p1.communicate(timeout=60)
    out1 = (seen + raw1).decode(errors="replace")
    assert p1.returncode == 101, out1
    assert "preempted" in out1, out1

    # the emergency checkpoint's consumed ranges = what run 1 trained
    cm = CheckpointManager(str(tmp_path / "ckpt"))
    _, _, meta = cm.restore(cm.versions()[-1])
    spans = meta["state"]["data_checkpoint"]["processed"]
    consumed_run1 = sum(e - b + 1 for f_spans in spans.values()
                       for b, e in f_spans)
    assert 0 < consumed_run1 < total, (consumed_run1, total)

    p2 = sp.run(cmd[:-2], env=env, stdout=sp.PIPE, stderr=sp.STDOUT,
                text=True, timeout=150)
    assert p2.returncode == 0, p2.stdout
    out = json.loads([l for l in p2.stdout.splitlines()
                      if l.startswith("{")][-1])
    assert out["resumed"] is True, out
    # exactly the remainder: nothing lost, nothing replayed
    assert out["records_seen"] == total - consumed_run1, \
        (out, consumed_run1, total)


@pytest.mark.integration
def test_resnet_real_data_accuracy_through_launcher(store, tmp_path):
    """Accuracy-parity-path evidence (VERDICT r1 #7): train ResNet18 on a
    REAL on-disk image-folder dataset through the full stack (launcher →
    trainer → tf.data decode/augment/shard → eval split) and assert the
    benchmark-log JSON reports converged eval accuracy: 10 classes
    (chance 0.1), a 160-image eval split (accuracy quantum 0.00625, one
    confused class costs 0.1), graph-seeded augmentation, the bar 0.9."""
    import json as json_mod
    import subprocess as sp

    from conftest import cpu_subprocess_env

    train_dir = _make_real_dataset(str(tmp_path / "train"), classes=10,
                                   per_class=40)
    eval_dir = _make_real_dataset(str(tmp_path / "eval"), classes=10,
                                  per_class=16, seed=99)
    env = cpu_subprocess_env(2, EDL_TPU_POD_IP="127.0.0.1",
                             EDL_TPU_TTL="3")
    log = open(str(tmp_path / "pod1.log"), "wb")
    p = sp.Popen(
        [sys.executable, "-u", "-m", "edl_tpu.controller.launch",
         "--job_id", "acc_job", "--store_endpoints", store.endpoint,
         "--nodes_range", "1:1",
         "--log_dir", str(tmp_path / "pod1_logs"),
         os.path.join(REPO, "examples", "resnet", "train.py"),
         "--depth", "18", "--epochs", "3", "--steps_per_epoch", "8",
         "--total_batch_size", "128", "--image_size", "32",
         "--num_classes", "10", "--seed", "7",
         "--data_dir", train_dir, "--eval_dir", eval_dir,
         "--base_lr", "0.08", "--warmup_epochs", "1"],
        env=env, stdout=log, stderr=sp.STDOUT, preexec_fn=os.setsid)
    log.close()
    try:
        assert p.wait(timeout=300) == 0, \
            (tmp_path / "pod1.log").read_text()
        worker_log = (tmp_path / "pod1_logs" / "workerlog.0").read_text()
        result = json_mod.loads([l for l in worker_log.splitlines()
                                 if l.startswith("{")][-1])
        assert result["steps"] == 24
        assert result["eval_acc1"] > 0.9, worker_log
        coord = store.client(root="acc_job")
        assert status.load_job_status(coord) == Status.SUCCEED
    finally:
        try:
            os.killpg(os.getpgid(p.pid), 9)
        except ProcessLookupError:
            pass
