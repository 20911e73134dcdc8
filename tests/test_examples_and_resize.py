"""Example-suite smoke tests + the resize mutation driver end-to-end."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from edl_tpu.controller import status
from edl_tpu.controller.status import Status
from edl_tpu.distill.teacher_server import TeacherServer
from edl_tpu.tools.resize_driver import ResizeDriver

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(path, args, timeout=240, device_count=2):
    from conftest import cpu_subprocess_env
    env = cpu_subprocess_env(device_count)
    proc = subprocess.run(
        [sys.executable, "-u", os.path.join(REPO, path)] + args,
        env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    result = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(result)


@pytest.mark.integration
def test_resnet_example_standalone():
    out = _run_example("examples/resnet/train.py", [
        "--depth", "18", "--epochs", "1", "--steps_per_epoch", "4",
        "--total_batch_size", "8", "--image_size", "32",
        "--num_classes", "4"])
    assert out["model"] == "ResNet18_vd"
    assert out["steps"] == 4
    assert out["imgs_per_sec"] > 0


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
    deadline = time.time() + 120
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
    out, _ = proc.communicate(timeout=120)
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
                           timeout=240)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert "resumed=True" in proc2.stdout, proc2.stdout
    final = json.loads([l for l in proc2.stdout.splitlines()
                        if l.startswith("{")][-1])
    assert final["steps"] > emergency_step


@pytest.mark.integration
def test_bert_pipeline_example_learns():
    out = _run_example("examples/bert_pipeline/train.py", [
        "--pp", "4", "--steps", "60", "--d_model", "32",
        "--num_heads", "2", "--mlp_dim", "64", "--seq_len", "16",
        "--vocab_size", "50", "--lr", "5e-3"],
        timeout=300, device_count=8)
    assert out["model"] == "bert_pipeline_pp4_dp2"
    # the parity task is learnable: loss must drop toward 0 from ~ln(2)
    assert out["final_loss"] < out["first_loss"] - 0.2, out


@pytest.mark.integration
def test_bert_pipeline_example_interleaved_learns():
    """--chunks 2: the interleaved (circular) engine behind the same
    example CLI, on a config where the Megatron-exact schedule wins."""
    out = _run_example("examples/bert_pipeline/train.py", [
        "--pp", "2", "--chunks", "2", "--num_layers", "4",
        "--num_micro", "8", "--steps", "60", "--d_model", "32",
        "--num_heads", "2", "--mlp_dim", "64", "--seq_len", "16",
        "--vocab_size", "50", "--lr", "5e-3"],
        timeout=600, device_count=8)
    assert out["model"] == "bert_pipeline_pp2_dp4_v2"
    assert out["final_loss"] < out["first_loss"] - 0.2, out


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
    deadline = time.time() + 180
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line == "" and proc.poll() is not None:
            raise AssertionError("died before starting")
        if line.startswith("step 5 "):  # compiled and actually stepping
            break
    time.sleep(1.0)
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=180)
    assert proc.returncode == 101, out
    assert "preempted" in out, out

    from edl_tpu.runtime.checkpoint import CheckpointManager

    versions = CheckpointManager(str(tmp_path / "ckpt")).versions()
    assert versions and 0 < versions[-1] < 400, (versions, out)

    proc2 = subprocess.run(
        cmd[:6] + ["40"] + cmd[7:], env=env, capture_output=True,
        text=True, timeout=400)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert "resumed=True step=%d" % versions[-1] in proc2.stdout, \
        proc2.stdout


@pytest.mark.integration
def test_long_context_example_runs_with_remat():
    out = _run_example("examples/long_context/train.py", [
        "--sp", "4", "--seq_len", "256", "--steps", "6", "--d_model",
        "32", "--num_heads", "2", "--mlp_dim", "64", "--remat"],
        timeout=300, device_count=8)
    assert out["model"] == "bert_ring_sp4_dp2"
    assert out["seq_len"] == 256 and out["remat"]
    assert np.isfinite(out["final_loss"])
    assert out["tokens_per_sec"] > 0


@pytest.mark.integration
def test_gpt_example_learns_and_generates():
    out = _run_example("examples/gpt/train.py",
                       ["--steps", "150"], timeout=400)
    assert out["final_loss"] < 0.3 * out["first_loss"]
    assert out["gen_accuracy"] >= 0.75


@pytest.mark.integration
def test_ctr_example_learns():
    out = _run_example("examples/ctr/train.py", [
        "--epochs", "2", "--steps_per_epoch", "30",
        "--total_batch_size", "128", "--num_fields", "6",
        "--vocab_per_field", "50"])
    assert out["final_loss"] < 0.67  # below chance-level BCE (~0.69)


@pytest.mark.integration
def test_resnet_distill_example_with_teacher():
    def teacher_fn(feed):
        # a deterministic "teacher": logits derived from channel means
        img = feed["image"]
        base = img.mean(axis=(1, 2, 3), keepdims=False)
        return {"logits": np.stack([base * (i + 1) for i in range(10)],
                                   axis=1).astype(np.float32)}

    teacher = TeacherServer(
        teacher_fn, {"image": ([32, 32, 3], "<f4")},
        {"logits": ([10], "<f4")}, max_batch=16, host="127.0.0.1").start()
    try:
        out = _run_example("examples/distill/resnet_distill.py", [
            "--epochs", "1", "--steps_per_epoch", "4",
            "--total_batch_size", "8", "--teachers", teacher.endpoint])
        assert out["steps"] == 4
    finally:
        teacher.stop()


@pytest.mark.integration
def test_nlp_distill_example_with_bert_teacher():
    import jax
    import jax.numpy as jnp

    from edl_tpu.models import bert

    model = bert.bert_tiny(dtype=jnp.float32)
    dummy = jnp.zeros((1, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), dummy)

    @jax.jit
    def infer(ids):
        return model.apply(variables, ids)

    def teacher_fn(feed):
        return {"logits": np.asarray(infer(jnp.asarray(
            feed["input_ids"].astype(np.int32))))}

    teacher = TeacherServer(
        teacher_fn, {"input_ids": ([32], "<i4")}, {"logits": ([2], "<f4")},
        max_batch=16, host="127.0.0.1").start()
    try:
        out = _run_example("examples/distill/nlp_distill.py", [
            "--epochs", "1", "--steps_per_epoch", "4", "--batch_size", "8",
            "--teachers", teacher.endpoint])
        assert "final_loss" in out
    finally:
        teacher.stop()


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
    deadline = time.time() + 120
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
    raw1, _ = p1.communicate(timeout=120)
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
                text=True, timeout=240)
    assert p2.returncode == 0, p2.stdout
    out = json.loads([l for l in p2.stdout.splitlines()
                      if l.startswith("{")][-1])
    assert out["resumed"] is True, out
    # exactly the remainder: nothing lost, nothing replayed
    assert out["records_seen"] == total - consumed_run1, \
        (out, consumed_run1, total)


def _make_real_dataset(root, classes=4, per_class=48, size=48, seed=0):
    """Real JPEGs on disk with visually-learnable classes (distinct base
    colors + noise) in class-per-subdirectory layout."""
    from PIL import Image
    rng = np.random.RandomState(seed)
    palette = [(220, 40, 40), (40, 220, 40), (40, 40, 220), (220, 220, 40),
               (220, 40, 220), (40, 220, 220), (230, 140, 30),
               (130, 70, 200), (110, 190, 90), (160, 160, 160)]
    assert classes <= len(palette)
    for c in range(classes):
        d = os.path.join(root, "class_%d" % c)
        os.makedirs(d, exist_ok=True)
        for i in range(per_class):
            img = np.ones((size, size, 3), np.float32) * palette[c]
            img += rng.randn(size, size, 3) * 25.0
            Image.fromarray(np.clip(img, 0, 255).astype(np.uint8)).save(
                os.path.join(d, "img%03d.jpg" % i))
    return root


@pytest.mark.integration
@pytest.mark.parametrize("bn_every,min_acc", [(1, 0.9), (4, 0.9)])
def test_resnet_real_data_accuracy_through_launcher(store, tmp_path,
                                                    bn_every, min_acc):
    """Accuracy-parity-path evidence (VERDICT r1 #7): train ResNet18 on a
    REAL on-disk image-folder dataset through the full stack (launcher →
    trainer → tf.data decode/augment/shard → eval split) and assert the
    benchmark-log JSON reports converged eval accuracy.

    bn_every=4 is the CONVERGENCE GATE for the subset-statistics BN
    throughput lever (NOTES r2 gap #1): the bench may only default to
    --bn_stats_every 4 because this real-data run converges with it.
    Sharpened per VERDICT r3 weak #3: 10 classes (chance 0.1), a
    160-image eval split (accuracy quantum 0.00625, one confused class
    costs 0.1), graph-seeded augmentation, and BOTH parametrizations
    face the same 0.9 bar — if subset statistics hurt convergence,
    bn_every=4 fails while bn_every=1 passes.

    The gate runs at total_batch 128 so bn_every=4 computes statistics
    from 32 samples — the bench default's effective stats batch AND the
    reference's per-GPU stats batch. That floor is load-bearing: the
    r4 sharpening experiment measured bn4 at total_batch 32 (8-sample
    stats) converging to 0.8 while bn1 passed 0.85+ — subset statistics
    below ~16 samples demonstrably cost accuracy, so bench.py refuses
    stats batches under 16 (see bench.py --bn_stats_every)."""
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
         "--base_lr", "0.08", "--warmup_epochs", "1",
         "--bn_stats_every", str(bn_every)],
        env=env, stdout=log, stderr=sp.STDOUT, preexec_fn=os.setsid)
    log.close()
    try:
        assert p.wait(timeout=540) == 0, \
            (tmp_path / "pod1.log").read_text()
        worker_log = (tmp_path / "pod1_logs" / "workerlog.0").read_text()
        result = json_mod.loads([l for l in worker_log.splitlines()
                                 if l.startswith("{")][-1])
        assert result["steps"] == 24
        assert result["eval_acc1"] > min_acc, worker_log
        coord = store.client(root="acc_job")
        assert status.load_job_status(coord) == Status.SUCCEED
    finally:
        try:
            os.killpg(os.getpgid(p.pid), 9)
        except ProcessLookupError:
            pass


@pytest.mark.integration
def test_resize_driver_north_star_8_4_8(tmp_path):
    """The BASELINE north star at full pod count: 8 launcher pods against
    the C++ store, forced resize 8→4→8 (simulated preemption of half the
    fleet, then recovery), per-stage recovery times measured and resize
    metrics recorded on the store (reference: README.md:126-131 job-server
    demo; recovery-time story edl_live_fault_tolerance.md:37)."""
    import json as json_mod

    from edl_tpu.controller import constants
    from edl_tpu.coordination.client import CoordClient
    from edl_tpu.coordination.native import NativeStoreServer, ensure_binary
    try:
        ensure_binary()
    except Exception as e:
        pytest.skip("native store unavailable: %r" % e)

    with NativeStoreServer(data_dir=str(tmp_path / "wal")) as s:
        driver = ResizeDriver(
            s.endpoint, "ns_job", "4:8",
            [os.path.join(REPO, "tests", "fixtures", "dummy_trainer.py"),
             "600", "0"],
            log_dir=str(tmp_path),
            env_extra={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                       "EDL_TPU_POD_IP": "127.0.0.1", "EDL_TPU_TTL": "5"})
        try:
            events = driver.run_schedule([8, 4, 8], interval=3)
            assert [e["target"] for e in events] == [8, 4, 8]
            # three distinct cluster incarnations, all with measured
            # recovery times
            assert len({e["stage"] for e in events}) == 3
            assert all(e["recovery_s"] >= 0 for e in events)
            coord = CoordClient([s.endpoint], root="ns_job")
            assert status.load_job_status(coord) != Status.FAILED
            # per-pod resize-recovery metrics landed on the store
            metrics = dict(coord.get_service(constants.SERVICE_METRICS))
            assert metrics, "no resize metrics recorded"
            history = [h for v in metrics.values()
                       for h in json_mod.loads(v)]
            assert any(h["recovery_s"] >= 0 for h in history)
        finally:
            driver.shutdown(kill=True)


@pytest.mark.integration
def test_resize_driver_schedule(store, tmp_path):
    """The 8→4→8 story in miniature: 2→1→2 with recovery times measured."""
    driver = ResizeDriver(
        store.endpoint, "resize_job", "1:2",
        [os.path.join(REPO, "examples", "fit_a_line", "train.py"),
         "--epochs", "100", "--steps_per_epoch", "5", "--step_sleep",
         "0.3"],
        log_dir=str(tmp_path),
        env_extra={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                   "EDL_TPU_POD_IP": "127.0.0.1", "EDL_TPU_TTL": "3",
                   "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                   "EDL_TPU_CHECKPOINT_PATH": str(tmp_path / "ckpt")})
    try:
        events = driver.run_schedule([2, 1, 2], interval=3)
        assert [e["target"] for e in events] == [2, 1, 2]
        assert len({e["stage"] for e in events}) == 3
        assert all(e["recovery_s"] < 120 for e in events)
        coord = store.client(root="resize_job")
        assert status.load_job_status(coord) != Status.FAILED
    finally:
        driver.shutdown(kill=True)


@pytest.mark.integration
def test_resize_driver_graceful_preemption(store, tmp_path):
    """--signal term: the graceful-preemption drill. SIGTERM reaches the
    victim pod's whole group; the trainers' coordinated stop writes a
    MID-EPOCH emergency checkpoint across ranks; the surviving launcher
    treats exit-101 as preemption (not failure) and the resized cluster
    resumes — steps survive that a SIGKILL drill would replay."""
    import glob

    from edl_tpu.runtime.checkpoint import CheckpointManager

    driver = ResizeDriver(
        store.endpoint, "graceful_job", "1:2",
        [os.path.join(REPO, "examples", "fit_a_line", "train.py"),
         # 50-step epochs: the stop lead now tracks watcher latency
         # only (~11 steps at this cadence — heartbeat staleness is
         # handled by per-rank projection, r5), so a preemption a dozen
         # steps into an epoch lands mid-epoch, which the discriminator
         # below requires. The r4 lead ballooned to ~30 steps and
         # forced 200-step epochs here.
         "--epochs", "100", "--steps_per_epoch", "50",
         "--step_sleep", "0.1"],
        # grace 30s (k8s-realistic): under full-suite CPU contention the
        # two-rank coordinated stop + aligned save can overrun 15s and
        # the drill then SIGKILLs mid-save (observed as a rare full-
        # suite-only flake; the test passes in isolation in ~15s)
        log_dir=str(tmp_path), stop_signal="term", grace=30.0,
        env_extra={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                   "EDL_TPU_POD_IP": "127.0.0.1", "EDL_TPU_TTL": "3",
                   "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                   "EDL_TPU_CHECKPOINT_PATH": str(tmp_path / "ckpt")})
    try:
        import time

        from edl_tpu.controller import train_status as ts_mod

        coord = store.client(root="graceful_job")
        driver.set_target(2)
        c2, _ = driver.wait_cluster(2)
        # preempt only once training is actually RUNNING (the trainers
        # report it at begin_epoch, after the handler is installed) —
        # a SIGTERM during distributed init has nothing to checkpoint
        deadline = time.time() + 90
        while time.time() < deadline:
            sts = [ts_mod.load_train_status(coord, pid)
                   for pid in c2.pod_ids()]
            if any(s is not None for s in sts):
                break
            time.sleep(0.3)
        else:
            raise AssertionError("training never started")
        time.sleep(2.0)  # a dozen 0.1s steps into epoch 0
        driver.set_target(1)
        _, waited = driver.wait_cluster(1, prev_stage=c2.stage)
        events = [{"target": 1, "recovery_s": waited,
                   "resumed_step": driver._store_global_step()}]
        assert status.load_job_status(coord) != Status.FAILED
        versions = CheckpointManager(str(tmp_path / "ckpt")).versions()
        logs = ""
        for p in glob.glob(str(tmp_path / "pod*_trainers") +
                           "/workerlog.*"):
            with open(p, errors="replace") as f:
                logs += f.read()
        # epoch-end saves land at multiples of 50; a mid-epoch version
        # proves the SIGTERM emergency checkpoint fired
        assert versions, \
            "no checkpoint written during the drill\n" + logs[-3000:]
        assert any(v % 50 != 0 for v in versions), (versions,
                                                    logs[-3000:])
        assert events[-1]["resumed_step"], events
        assert "preempted" in logs, logs[-2000:]
    finally:
        driver.shutdown(kill=True)


@pytest.mark.integration
def test_chaos_soak_mixed_preemptions(store, tmp_path):
    """Bounded chaos soak: a deterministic-seed sequence of resize
    mutations with MIXED preemption modes (hard SIGKILL and graceful
    SIGTERM) against one job, then run-to-completion — the job must
    never FAIL, recover after every mutation, and finish SUCCEED."""
    import random
    import time

    rng = random.Random(7)  # jitters the sleeps only — the mutation
    # sequence itself is explicit so BOTH modes provably run
    driver = ResizeDriver(
        store.endpoint, "chaos_job", "1:2",
        [os.path.join(REPO, "examples", "fit_a_line", "train.py"),
         "--epochs", "6", "--steps_per_epoch", "30",
         "--step_sleep", "0.1"],
        log_dir=str(tmp_path), stop_signal="kill", grace=15.0,
        env_extra={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                   "EDL_TPU_POD_IP": "127.0.0.1", "EDL_TPU_TTL": "3",
                   "XLA_FLAGS":
                       "--xla_force_host_platform_device_count=2",
                   "EDL_TPU_CHECKPOINT_PATH": str(tmp_path / "ckpt")})
    coord = store.client(root="chaos_job")
    try:
        driver.set_target(2)
        prev_stage = driver.wait_cluster(2)[0].stage
        for step_i, (mode, target) in enumerate(
                [("term", 1), ("kill", 2), ("term", 1)]):
            time.sleep(rng.uniform(2.0, 4.0))
            driver._stop_signal = mode
            driver.set_target(target)
            cluster, waited = driver.wait_cluster(target,
                                                  prev_stage=prev_stage)
            prev_stage = cluster.stage
            assert waited < 120, (step_i, mode, target, waited)
        # let the survivor finish the job
        deadline = time.time() + 300
        while time.time() < deadline:
            if status.load_job_status(coord) == Status.SUCCEED:
                break
            assert status.load_job_status(coord) != Status.FAILED
            time.sleep(1.0)
        assert status.load_job_status(coord) == Status.SUCCEED
    finally:
        driver.shutdown(kill=True)


@pytest.mark.integration
def test_gpt_distill_example_with_lm_teacher():
    """Sequence-level KD end-to-end: gpt teacher backend -> DistillReader
    -> student GPT trained on per-position soft targets."""
    from edl_tpu.distill.teacher_server import gpt_teacher

    teacher = gpt_teacher(vocab_size=64, seq_len=16, max_batch=8,
                          host="127.0.0.1").start()
    try:
        out = _run_example("examples/distill/gpt_distill.py", [
            "--epochs", "1", "--steps_per_epoch", "4",
            "--total_batch_size", "8", "--seq_len", "16",
            "--vocab_size", "64", "--teachers", teacher.endpoint])
        assert out["steps"] == 4
        assert np.isfinite(out["final_loss"])
    finally:
        teacher.stop()


@pytest.mark.integration
def test_chaos_soak_resize_plus_store_failover(tmp_path):
    """The combined reliability drill: elastic resize mutations AND a
    coordination-store primary loss in one arc. Pods run against
    [primary, standby]; a graceful scale-down lands, then the PRIMARY
    is killed mid-job (standby promotes, leases/elections re-form),
    then another resize mutation runs against the promoted store — and
    the job still finishes SUCCEED. Every failure domain the framework
    claims to survive, exercised together."""
    import time as time_mod

    from edl_tpu.coordination.server import StoreServer
    from edl_tpu.coordination.standby import StandbyServer

    primary = StoreServer(host="127.0.0.1").start()
    sb = StandbyServer([primary.endpoint], host="127.0.0.1",
                       auto_promote=True, promote_after=1.5,
                       sync_poll=0.5).start()
    endpoints = "%s,%s" % (primary.endpoint, sb.endpoint)
    driver = ResizeDriver(
        endpoints, "chaos_ha_job", "1:2",
        [os.path.join(REPO, "examples", "fit_a_line", "train.py"),
         "--epochs", "6", "--steps_per_epoch", "30",
         "--step_sleep", "0.1"],
        log_dir=str(tmp_path), stop_signal="term", grace=15.0,
        env_extra={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                   "EDL_TPU_POD_IP": "127.0.0.1", "EDL_TPU_TTL": "3",
                   "XLA_FLAGS":
                       "--xla_force_host_platform_device_count=2",
                   "EDL_TPU_CHECKPOINT_PATH": str(tmp_path / "ckpt")})
    from edl_tpu.coordination.client import CoordClient
    coord = CoordClient(endpoints.split(","), root="chaos_ha_job",
                        failover_grace=25.0)
    try:
        driver.set_target(2)
        prev_stage = driver.wait_cluster(2)[0].stage
        time_mod.sleep(2.0)
        # mutation 1: graceful scale-down on the healthy primary
        driver.set_target(1)
        cluster, waited = driver.wait_cluster(1, prev_stage=prev_stage)
        prev_stage = cluster.stage
        assert waited < 120

        # the store outage, mid-job
        primary.stop()
        deadline = time_mod.time() + 30
        while time_mod.time() < deadline and not sb.promoted:
            time_mod.sleep(0.2)
        assert sb.promoted

        # mutation 2: scale back out against the PROMOTED store (the
        # driver's own client rides the failover via endpoint rotation;
        # wait_cluster's own timeout enforces the bound)
        time_mod.sleep(2.0)
        driver.set_target(2)
        driver.wait_cluster(2, prev_stage=prev_stage, timeout=180)

        deadline = time_mod.time() + 300
        while time_mod.time() < deadline:
            if status.load_job_status(coord) == Status.SUCCEED:
                break
            assert status.load_job_status(coord) != Status.FAILED
            time_mod.sleep(1.0)
        assert status.load_job_status(coord) == Status.SUCCEED
    finally:
        driver.shutdown(kill=True)
        sb.stop()
        primary.stop()  # idempotent; without it a pre-outage failure
        # leaks the primary's server threads into the pytest process


@pytest.mark.integration
def test_four_host_dp_tp_resize_with_store_failover(tmp_path):
    """VERDICT r4 item 8 — the closest CPU-reachable analogue of a real
    multi-host TPU resize, one rung past the 2-pod drills: FOUR
    launcher pods x 2 virtual devices each, bert with tp=2 INSIDE the
    dp mesh (params sharded across the process boundary), resized
    4 -> 2 -> 4 gracefully while the coordination store's PRIMARY is
    killed mid-arc (standby promotes). Ties together in one arc:
    launcher elasticity at >2 hosts, tp-sharded save + placed restore
    across RESHAPED meshes (4x2 -> 2x2 -> 4x2 devices), coordinated
    preemption, store HA, the prewarm scope guard, and exactly-once
    step-keyed data consumption (FEED accounting below).

    Reference north star: BASELINE.md's 8 -> 4 -> 8 on v5e-16."""
    import glob
    import re
    import time as time_mod

    from edl_tpu.coordination.server import StoreServer
    from edl_tpu.coordination.standby import StandbyServer

    primary = StoreServer(host="127.0.0.1").start()
    sb = StandbyServer([primary.endpoint], host="127.0.0.1",
                       auto_promote=True, promote_after=1.5,
                       sync_poll=0.5).start()
    endpoints = "%s,%s" % (primary.endpoint, sb.endpoint)
    driver = ResizeDriver(
        endpoints, "dptp_job", "2:4",
        [os.path.join(REPO, "tests", "fixtures", "dp_tp_trainer.py"),
         "--epochs", "4", "--steps_per_epoch", "20",
         "--total_batch_size", "24", "--tp", "2",
         "--step_sleep", "0.05"],
        log_dir=str(tmp_path), stop_signal="term", grace=60.0,
        # TTL 10 (not the 2-pod drills' 3): FOUR bert compiles + gloo
        # init can starve every launcher's heartbeat thread at once on
        # a loaded CI box; the below-min grace (2xTTL) then rides it out
        env_extra={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                   "EDL_TPU_POD_IP": "127.0.0.1", "EDL_TPU_TTL": "10",
                   "XLA_FLAGS":
                       "--xla_force_host_platform_device_count=2",
                   "EDL_TPU_CHECKPOINT_PATH": str(tmp_path / "ckpt")})
    from edl_tpu.coordination.client import CoordClient
    coord = CoordClient(endpoints.split(","), root="dptp_job",
                        failover_grace=25.0)
    try:
        def _logs():
            out = ""
            for p in glob.glob(str(tmp_path) + "/pod*_trainers/"
                               "workerlog.*"):
                with open(p, errors="replace") as f:
                    out += f.read()
            return out

        def _wait_world_trains(world, why, min_steps=5):
            # each stage must actually COMMIT steps (4-process
            # distributed init + bert compile + shard restore takes
            # tens of seconds on CPU) before the next mutation lands:
            # a SIGTERM that catches trainers mid-compile leaves no
            # boundary for the coordinated stop to save at, and the
            # grace-expiry SIGKILL then tears down the whole jax world
            # unsaved. FEED step=N+1 is printed only after step N's
            # train_step returned, which in a lockstep collective world
            # means EVERY rank finished compiling and committed N.
            deadline = time_mod.time() + 300
            pat = r"FEED step=(\d+) rank=0 world=%d" % world
            while time_mod.time() < deadline:
                steps = [int(m.group(1))
                         for m in re.finditer(pat, _logs())]
                if steps and max(steps) > min_steps:
                    return
                assert status.load_job_status(coord) != Status.FAILED
                time_mod.sleep(1.0)
            raise AssertionError("world-%d stage never trained (%s)\n%s"
                                 % (world, why, _logs()[-3000:]))

        driver.set_target(4)
        prev_stage = driver.wait_cluster(4, timeout=300)[0].stage
        _wait_world_trains(4, "initial 4-host stage")

        # graceful scale-down to 2 hosts: coordinated stop + tp-sharded
        # emergency save, then a 2x2-device restore of 4-rank shards
        driver.set_target(2)
        cluster, waited = driver.wait_cluster(2, prev_stage=prev_stage,
                                              timeout=300)
        prev_stage = cluster.stage
        _wait_world_trains(2, "post-scale-down stage")

        # the store outage mid-job
        primary.stop()
        deadline = time_mod.time() + 30
        while time_mod.time() < deadline and not sb.promoted:
            time_mod.sleep(0.2)
        assert sb.promoted

        # scale back OUT against the promoted store
        time_mod.sleep(1.0)
        driver.set_target(4)
        driver.wait_cluster(4, prev_stage=prev_stage, timeout=300)

        deadline = time_mod.time() + 420
        while time_mod.time() < deadline:
            if status.load_job_status(coord) == Status.SUCCEED:
                break
            assert status.load_job_status(coord) != Status.FAILED
            time_mod.sleep(1.0)
        assert status.load_job_status(coord) == Status.SUCCEED

        logs = _logs()

        # exactly-once, step-keyed: rank 0's FEED lines across every
        # incarnation must cover 1..final contiguously; duplicates only
        # at preemption boundaries (a fetched-but-stopped batch), of
        # which this arc has 2 resizes + 1 failover window
        feeds = [int(m.group(1)) for m in
                 re.finditer(r"FEED step=(\d+) rank=0", logs)]
        assert feeds, logs[-2000:]
        final = max(feeds)
        missing = set(range(1, final + 1)) - set(feeds)
        assert not missing, ("steps never fed (data lost): %s"
                             % sorted(missing))
        dups = len(feeds) - len(set(feeds))
        assert dups <= 6, ("replayed windows beyond preemption "
                           "boundaries: %d" % dups)

        # the job really ran at BOTH world sizes with tp inside
        assert re.search(r"FEED step=\d+ rank=0 world=4", logs), \
            logs[-2000:]
        assert re.search(r"FEED step=\d+ rank=0 world=2", logs), \
            logs[-2000:]
        # prewarm was engaged and its multi-process guard refused
        assert "why='multi-process world'" in logs, logs[-2000:]

        # the 4-host stage wrote SHARDED checkpoints (tp state crosses
        # hosts there; whether the 2-host mesh lays tp locally — and
        # saves dense — depends on device order, so it isn't pinned)
        import json as json_mod
        ranks_seen = set()
        for mp in glob.glob(str(tmp_path / "ckpt") + "/v_*/MANIFEST"):
            with open(mp) as f:
                m = json_mod.load(f)
            if m.get("sharded"):
                ranks_seen.add(m.get("ranks"))
        assert 4 in ranks_seen, ranks_seen
        # ...and the reshaped 2-host mesh RESUMED from them (the
        # placed-restore-across-meshes arc this test exists for)
        assert re.search(
            r"dp_tp: rank=0 world=2 start_epoch=\d+ resumed=True",
            logs), logs[-2000:]
    finally:
        driver.shutdown(kill=True)
        sb.stop()
        primary.stop()
