"""Runtime tests: mesh axes, LR schedules, State adjust hooks, and the
ElasticTrainer end-to-end on the 8-device CPU mesh (data-parallel sharding
with XLA-inserted gradient reduction)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from edl_tpu.runtime import lr_schedules, mesh as mesh_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
from edl_tpu.runtime import state as state_mod
from edl_tpu.runtime.trainer import ElasticTrainer


def test_mesh_axes_and_sizes():
    assert jax.device_count() == 8
    m = mesh_mod.make_mesh()
    assert m.shape[mesh_mod.DATA_AXIS] == 8
    m2 = mesh_mod.make_mesh(tp=2)
    assert m2.shape[mesh_mod.DATA_AXIS] == 4
    assert m2.shape[mesh_mod.MODEL_AXIS] == 2
    m3 = mesh_mod.make_mesh(tp=2, sp=2)
    assert m3.shape[mesh_mod.DATA_AXIS] == 2
    with pytest.raises(ValueError):
        mesh_mod.make_mesh(tp=3)


def test_topology_valid():
    assert [n for n in range(1, 10)
            if mesh_mod.topology_valid_power_of_two(n)] == [1, 2, 4, 8]
    assert mesh_mod.largest_valid_world(7) == 4


def test_lr_schedules():
    s = lr_schedules.piecewise_decay(0.1, [100, 200])
    assert float(s(0)) == pytest.approx(0.1)
    assert float(s(150)) == pytest.approx(0.01)
    assert float(s(250)) == pytest.approx(0.001)
    w = lr_schedules.linear_warmup(s, warmup_steps=10)
    assert float(w(0)) == pytest.approx(0.0)
    assert float(w(5)) == pytest.approx(0.05)
    assert float(w(50)) == pytest.approx(0.1)
    c = lr_schedules.cosine_decay(1.0, 100)
    assert float(c(0)) == pytest.approx(1.0)
    assert float(c(100)) == pytest.approx(0.0, abs=1e-6)
    assert lr_schedules.scale_lr_for_batch(0.1, 1024) == pytest.approx(0.4)


def test_state_roundtrip_and_adjust(coord):
    st = state_mod.State(total_batch_size=256)
    st.begin_epoch(0, world_size=8)
    st.end_epoch(step_num=100, avg_step_time=0.01)
    st.data_checkpoint.file_list = ["a.txt"]
    st.data_checkpoint.mark_processed("a.txt", 0, 49)
    st.data_checkpoint.mark_processed("a.txt", 50, 99)
    assert st.data_checkpoint.processed["a.txt"] == [[0, 99]]
    assert st.data_checkpoint.is_processed("a.txt", 75)

    calls = []
    st.register_adjust_function(
        lambda s, w: calls.append((s.total_batch_size, w)))
    st.adjust(4)
    assert calls == [(256, 4)]

    state_mod.save_to_store(coord, st)
    loaded = state_mod.load_from_store(coord)
    assert loaded.total_batch_size == 256
    assert loaded.epochs["0"]["step_num"] == 100
    assert loaded.data_checkpoint.is_processed("a.txt", 10)


def _linreg_trainer(tmp_path, total_batch=64, **kw):
    w_true = np.arange(1, 5, dtype=np.float32)

    def loss_fn(params, batch, rng):
        pred = batch["x"] @ params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    trainer = ElasticTrainer(
        loss_fn, params, optax.sgd(0.1), total_batch_size=total_batch,
        checkpoint_dir=str(tmp_path / "ckpt"), **kw)

    def make_batch(seed):
        rng = np.random.RandomState(seed)
        x = rng.randn(total_batch, 4).astype(np.float32)
        y = x @ w_true + 0.01 * rng.randn(total_batch).astype(np.float32)
        return {"x": x, "y": y}

    return trainer, make_batch, w_true


def test_elastic_trainer_learns_and_resumes(tmp_path):
    trainer, make_batch, w_true = _linreg_trainer(tmp_path)
    trainer.begin_epoch(0)
    first = float(trainer.train_step(make_batch(0)))
    for i in range(1, 30):
        loss = float(trainer.train_step(make_batch(i)))
    assert loss < first * 0.05
    assert trainer.global_step == 30
    trainer.end_epoch(save=True)  # writes checkpoint v30

    np.testing.assert_allclose(
        np.asarray(trainer.train_state["params"]["w"]), w_true, atol=0.2)

    # a fresh trainer (simulating a post-resize restart) resumes at step 30
    trainer2, make_batch2, _ = _linreg_trainer(tmp_path)
    assert trainer2.resume()
    assert trainer2.global_step == 30
    assert trainer2.state.epoch_no == 0
    loss2 = float(trainer2.train_step(make_batch2(99)))
    assert loss2 < first * 0.05


def test_preemption_saves_emergency_checkpoint(tmp_path):
    """SIGTERM mid-training: the next step boundary writes a checkpoint
    at the CURRENT step and raises PreemptedError; a restarted trainer
    resumes from it with zero lost steps (the grace window that
    train_process.terminate_trainers's SIGTERM->SIGKILL kill provides)."""
    import os
    import signal

    from edl_tpu.utils.errors import PreemptedError

    try:
        trainer, make_batch, _ = _linreg_trainer(tmp_path)
        trainer.install_preemption_handler()
        trainer.begin_epoch(0)
        for i in range(5):
            trainer.train_step(make_batch(i))
        assert not trainer.preempted
        os.kill(os.getpid(), signal.SIGTERM)  # launcher / k8s preemption
        with pytest.raises(PreemptedError):
            trainer.train_step(make_batch(5))
        assert trainer.preempted

        # the emergency checkpoint carries the step that completed (6),
        # not the last epoch-end save (there was none)
        trainer2, make_batch2, _ = _linreg_trainer(tmp_path)
        assert trainer2.resume()
        assert trainer2.global_step == 6
        # a mid-epoch save must re-run the interrupted epoch, not skip
        # its remaining data
        assert trainer2.state.next_epoch() == 0
        trainer2.train_step(make_batch2(6))
        assert trainer2.global_step == 7
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)


def test_fit_loop_trains_resumes_and_preempts(tmp_path):
    """ElasticTrainer.fit(): the one-call loop trains to convergence,
    a second fit() resumes from the checkpoints it wrote, and a
    preemption mid-loop raises PreemptedError (code=None) after the
    emergency save."""
    import signal

    from edl_tpu.utils.errors import PreemptedError

    trainer, make_batch, w_true = _linreg_trainer(tmp_path)
    out = trainer.fit(2, lambda e: (make_batch(e * 100 + i)
                                    for i in range(15)))
    assert out["steps"] == 30 and not out["resumed"]
    assert out["final_loss"] < 0.05
    np.testing.assert_allclose(
        np.asarray(trainer.train_state["params"]["w"]), w_true, atol=0.2)

    trainer2, make_batch2, _ = _linreg_trainer(tmp_path)
    out2 = trainer2.fit(3, lambda e: (make_batch2(e * 100 + i)
                                      for i in range(15)))
    assert out2["resumed"] and out2["steps"] == 45

    try:
        trainer3, make_batch3, _ = _linreg_trainer(tmp_path)

        def batches(epoch):
            for i in range(15):
                if i == 4:
                    os.kill(os.getpid(), signal.SIGTERM)
                yield make_batch3(epoch * 100 + i)

        with pytest.raises(PreemptedError):
            trainer3.fit(9, batches, preemption_exit_code=None)
        # the emergency checkpoint carries the preempted step, beyond
        # the resumed 45 but before epoch 3's end at 60
        trainer4, _, _ = _linreg_trainer(tmp_path)
        assert trainer4.resume()
        assert 45 < trainer4.global_step < 60
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)


def test_fit_reports_neartheend_after_running(tmp_path):
    """Status order on the LAST epoch: begin_epoch reports RUNNING, so
    fit() must publish NEARTHEEND after it — the scale-out-stopping
    verdict would otherwise be clobbered for the entire final epoch."""
    from edl_tpu.controller.train_status import TrainStatus

    trainer, make_batch, _ = _linreg_trainer(tmp_path)
    calls = []
    trainer.report_status = calls.append
    trainer.fit(2, lambda e: (make_batch(e * 10 + i) for i in range(3)))
    assert calls[-1] == TrainStatus.SUCCEED
    near = calls.index(TrainStatus.NEARTHEEND)
    assert calls[near - 1] == TrainStatus.RUNNING
    # and nothing overwrites NEARTHEEND before the SUCCEED
    assert calls[near + 1:] == [TrainStatus.SUCCEED]


def test_elastic_trainer_runs_the_pipeline_engine(tmp_path):
    """Elastic pipeline-parallel training end to end: the 1F1B engine as
    ElasticTrainer's step_fn — train on dp x pp, checkpoint (sharded
    write keeps "stages" pp-laid-out), resume in a fresh trainer via the
    placed restore, and keep training with the loss still improving."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from edl_tpu.models.bert import create_bert_pipeline
    from edl_tpu.parallel.pipeline import make_pipeline_train_step

    pp = 4
    mesh = mesh_mod.make_mesh(dp=2, pp=pp)
    repl = NamedSharding(mesh, P())
    stage_sh = NamedSharding(mesh, P("pp"))

    def build():
        pparams, enc, stg, dec, _ = create_bert_pipeline(
            pp, num_layers=4, d_model=32, num_heads=2, mlp_dim=64,
            vocab_size=50, max_len=64, seq_len=16, dtype=jnp.float32)
        shardings = {
            "encode": jax.tree_util.tree_map(lambda _: repl,
                                             pparams["encode"]),
            "stages": jax.tree_util.tree_map(lambda _: stage_sh,
                                             pparams["stages"]),
            "decode": jax.tree_util.tree_map(lambda _: repl,
                                             pparams["decode"]),
        }
        tx = optax.adam(3e-3)
        step = make_pipeline_train_step(
            tx, encode_fn=enc, stage_fn=stg, decode_fn=dec, mesh=mesh,
            num_micro=4)
        return ElasticTrainer(
            None, pparams, tx, total_batch_size=16,
            checkpoint_dir=str(tmp_path / "ckpt"), mesh=mesh,
            param_shardings=shardings, step_fn=step)

    rng = np.random.RandomState(3)

    def batch(i):
        return {"input_ids": rng.randint(0, 50, (16, 16))
                .astype(np.int32),
                "label": rng.randint(0, 2, (16,)).astype(np.int32)}

    tr = build()
    first = float(tr.train_step(batch(0)))
    for i in range(1, 8):
        loss = float(tr.train_step(batch(i)))
    tr.begin_epoch(0)
    tr.end_epoch(save=True)
    qkv = tr.train_state["params"]["stages"]["layer_0"]["attention"][
        "query"]["kernel"]
    assert "pp" in str(qkv.sharding.spec)

    tr2 = build()
    assert tr2.resume()
    assert tr2.global_step == 8
    qkv2 = tr2.train_state["params"]["stages"]["layer_0"]["attention"][
        "query"]["kernel"]
    assert "pp" in str(qkv2.sharding.spec)  # layout survived the restore
    for i in range(8, 24):
        loss = float(tr2.train_step(batch(i)))
    assert loss < first, (loss, first)


def test_elastic_trainer_runs_interleaved_pipeline(tmp_path):
    """num_chunks routes the elastic step_fn through the interleaved
    (circular) engine: train on dp x pp with V=2 virtual stages,
    checkpoint, resume, layouts intact."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from edl_tpu.parallel.pipeline import (device_major_stage_params,
                                           make_pipeline_train_step)

    pp, V = 4, 2
    mesh = mesh_mod.make_mesh(dp=2, pp=pp)
    repl = NamedSharding(mesh, P())
    stage_sh = NamedSharding(mesh, P("pp"))
    S, d = pp * V, 8
    rng = np.random.RandomState(11)

    def encode(p, xb):
        return jnp.tanh(xb @ p["w"])

    def stage(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    def decode(p, act, labels):
        logits = act @ p["w"]
        oh = jax.nn.one_hot(labels, 2)
        return -(jax.nn.log_softmax(logits) * oh).sum(-1).mean()

    def build():
        pparams = {
            "encode": {"w": jnp.asarray(
                rng.randn(3, d).astype(np.float32) * 0.3)},
            "stages": device_major_stage_params(
                {"w": jnp.asarray(np.stack(
                    [np.eye(d) * 0.9 for _ in range(S)])
                    .astype(np.float32)),
                 "b": jnp.zeros((S, d), jnp.float32)}, pp, V),
            "decode": {"w": jnp.asarray(
                rng.randn(d, 2).astype(np.float32) * 0.3)},
        }
        shardings = {
            "encode": {"w": repl},
            "stages": jax.tree_util.tree_map(lambda _: stage_sh,
                                             pparams["stages"]),
            "decode": {"w": repl},
        }
        tx = optax.adam(5e-3)
        step = make_pipeline_train_step(
            tx, encode_fn=encode, stage_fn=stage, decode_fn=decode,
            mesh=mesh, num_micro=4, num_chunks=V, x_key="x")
        return ElasticTrainer(
            None, pparams, tx, total_batch_size=16,
            checkpoint_dir=str(tmp_path / "ckpt"), mesh=mesh,
            param_shardings=shardings, step_fn=step)

    data = np.random.RandomState(4)

    def batch(i):
        x = data.randn(16, 3).astype(np.float32)
        return {"x": x, "label": (x.sum(1) > 0).astype(np.int32)}

    tr = build()
    first = float(tr.train_step(batch(0)))
    for i in range(1, 6):
        tr.train_step(batch(i))
    tr.begin_epoch(0)
    tr.end_epoch(save=True)

    tr2 = build()
    assert tr2.resume() and tr2.global_step == 6
    assert "pp" in str(
        tr2.train_state["params"]["stages"]["w"].sharding.spec)
    loss = None
    for i in range(6, 40):
        loss = float(tr2.train_step(batch(i)))
    assert loss < first, (loss, first)


def test_coordinated_stop_protocol(coord):
    """CoordinatedStop: a flagged rank's request makes the rank-0 watcher
    publish stop_at = leader_step + margin, and every rank's watcher
    observes the same value (the aligned-boundary guarantee)."""
    import time

    from edl_tpu.runtime.preemption import CoordinatedStop

    c0 = CoordinatedStop(coord, 0, stage="stg1", margin=4,
                         poll_interval=0.05,
                         current_step=lambda: 10).start()
    c1 = CoordinatedStop(coord, 1, stage="stg1",
                         poll_interval=0.05).start()
    try:
        time.sleep(0.2)
        assert c0.stop_at is None and c1.stop_at is None
        c1.request(12)  # rank 1 got SIGTERM at its step 12
        deadline = time.time() + 10
        while time.time() < deadline and (c0.stop_at is None
                                          or c1.stop_at is None):
            time.sleep(0.05)
        # max(leader step 10, requester step 12) + margin 4
        assert c0.stop_at == 16 and c1.stop_at == 16
        # a different stage (a restarted incarnation) sees nothing
        c2 = CoordinatedStop(coord, 1, stage="stg2", poll_interval=0.05)
        assert c2._read_stop_at() is None
    finally:
        c0.stop()
        c1.stop()


def test_coordinated_stop_staleness_defenses(coord):
    """A restarted incarnation must never act on its predecessor's keys:
    stop_at and request values at or below min_step are rejected, a
    stale stop_at is overwritten (put-if-absent would block on it), and
    requests are clamped above min_step so live ones always survive the
    leader's filter."""
    import time

    from edl_tpu.runtime.preemption import CoordinatedStop

    # predecessor's leftovers: stop_at=30 and a request at step 25
    coord.set_server_not_exists("preempt:stgX", "stop_at", "30", ttl=60)
    coord.set_server_not_exists("preempt:stgX", "req_1", "25", ttl=60)

    # the resumed job's baseline is step 30 — everything above is stale
    c0 = CoordinatedStop(coord, 0, stage="stgX", margin=4,
                         poll_interval=0.05, current_step=lambda: 31,
                         min_step=30).start()
    c1 = CoordinatedStop(coord, 1, stage="stgX", poll_interval=0.05,
                         min_step=30).start()
    try:
        time.sleep(0.4)
        # stale stop_at/req observed but rejected; no new stop published
        assert c0.stop_at is None and c1.stop_at is None

        # a LIVE preemption now: the request clamps above min_step and
        # the leader overwrites the stale stop_at
        c1.request(5)  # a silly-low step still publishes min_step + 1
        deadline = time.time() + 10
        while time.time() < deadline and (c0.stop_at is None
                                          or c1.stop_at is None):
            time.sleep(0.05)
        # max(leader 31, clamped request 31) + margin 4
        assert c0.stop_at == 35 and c1.stop_at == 35
    finally:
        c0.stop()
        c1.stop()


def test_coordinated_stop_covers_ahead_nonrequester(coord):
    """A non-requesting rank whose step counter runs AHEAD of both the
    leader and the requester publishes step heartbeats, so the leader's
    stop_at still lands ahead of it (advisor r3: stop_at was
    max(leader, requesters) only)."""
    import time

    from edl_tpu.runtime.preemption import CoordinatedStop

    c0 = CoordinatedStop(coord, 0, stage="stgA", margin=4,
                         poll_interval=0.05,
                         current_step=lambda: 10).start()
    c1 = CoordinatedStop(coord, 1, stage="stgA", poll_interval=0.05,
                         current_step=lambda: 12).start()
    # rank 2 is far ahead and never receives a signal
    c2 = CoordinatedStop(coord, 2, stage="stgA", poll_interval=0.05,
                         current_step=lambda: 40,
                         heartbeat_interval=0.05).start()
    try:
        # let rank 2's heartbeat land before the preemption fires
        deadline = time.time() + 5
        while time.time() < deadline and \
                coord.get_value("preempt:stgA", "step_2") is None:
            time.sleep(0.02)
        c1.request(12)
        deadline = time.time() + 10
        while time.time() < deadline and (c0.stop_at is None
                                          or c2.stop_at is None):
            time.sleep(0.05)
        # stop must clear rank 2's counter (40), not just max(10,12)
        assert c0.stop_at is not None and c0.stop_at > 40
        assert c2.stop_at == c0.stop_at
    finally:
        c0.stop()
        c1.stop()
        c2.stop()


def test_coordinated_stop_margin_capped_by_grace_budget(coord):
    """With multi-second steps the stop lead is capped so
    lead*step_time fits the SIGTERM->SIGKILL grace window instead of
    scheduling the save past the kill (advisor r3)."""
    import time

    from edl_tpu.runtime.preemption import CoordinatedStop

    # 5 s/step, 8 s grace budget -> lead = max(1, int(8/5)) = 1 step,
    # despite margin=4
    c0 = CoordinatedStop(coord, 0, stage="stgB", margin=4,
                         poll_interval=0.05, current_step=lambda: 100,
                         step_time=lambda: 5.0,
                         grace_budget=8.0).start()
    try:
        c0.request(100)
        deadline = time.time() + 10
        while time.time() < deadline and c0.stop_at is None:
            time.sleep(0.05)
        assert c0.stop_at == 101, c0.stop_at
    finally:
        c0.stop()


def test_coordinated_stop_lead_tracks_step_rate_not_heartbeat(coord):
    """VERDICT r4 weak #5: the stop lead must track the watcher's
    observation latency (a few polls / step_time), NOT a blanket
    worst-case heartbeat-staleness term — heartbeat beats are instead
    projected per-rank by their OBSERVED age. At 10ms steps with a 5s
    heartbeat the old model published stop_at >= 500 steps out; the new
    model stays within a few dozen (fresh beats, fresh req)."""
    import time

    from edl_tpu.runtime.preemption import CoordinatedStop

    t0 = time.monotonic()

    def stepper(base):
        # ranks genuinely advance at 10ms/step, like a real fast loop
        return lambda: base + int((time.monotonic() - t0) / 0.01)

    kw = dict(poll_interval=0.05, step_time=lambda: 0.01,
              heartbeat_interval=0.05, grace_budget=8.0)
    c0 = CoordinatedStop(coord, 0, stage="stgR", margin=4,
                         current_step=stepper(100), **kw).start()
    c1 = CoordinatedStop(coord, 1, stage="stgR",
                         current_step=stepper(102), **kw).start()
    try:
        time.sleep(0.4)  # warm the leader's heartbeat history
        c1.request(c1._current_step())
        deadline = time.time() + 10
        while time.time() < deadline and (c0.stop_at is None
                                          or c1.stop_at is None):
            time.sleep(0.02)
        assert c0.stop_at is not None
        now_step = stepper(102)()
        # ahead of every rank (the correctness bar)...
        assert c0.stop_at > now_step - 5, (c0.stop_at, now_step)
        # ...but NOT padded by hb_interval-as-steps: the old model's
        # floor here was ~(4*0.05+5s worst-case)/0.01 ≈ 520 steps of
        # lead; fresh beats + per-rank projection keep it tight
        assert c0.stop_at < now_step + 150, (c0.stop_at, now_step)
    finally:
        c0.stop()
        c1.stop()


def test_launcher_clears_only_stale_preempt_keys(coord):
    """Respawn-in-place retires preempt keys at or below the resumed
    step (advisor r3: stale stop_at re-preempts the respawn) but must
    NOT touch a live in-flight preemption's keys (code review r4: a
    blanket delete would split the agreed stop step mid-protocol)."""
    import types

    from edl_tpu.controller.launcher import Launcher
    from edl_tpu.runtime import state as state_mod

    st = state_mod.State()
    st.global_step = 50
    state_mod.save_to_store(coord, st)
    # stale leftovers (<= resumed step 50) and live keys (ahead of it)
    coord.set_server_with_lease("preempt:stg9", "stop_at", "48", ttl=60)
    coord.set_server_with_lease("preempt:stg9", "req_1", "47", ttl=60)
    coord.set_server_with_lease("preempt:stg9", "req_2", "55", ttl=60)
    coord.set_server_with_lease("preempt:stg9", "step_3", "60", ttl=60)

    stub = types.SimpleNamespace(
        _coord=coord, _cluster=types.SimpleNamespace(stage="stg9"))
    Launcher._clear_preempt_keys(stub)
    left = dict(coord.get_service("preempt:stg9"))
    assert "stop_at" not in left and "req_1" not in left
    assert left.get("req_2") == "55" and left.get("step_3") == "60"


def test_locked_make_serializes_concurrent_builds(tmp_path):
    """Two processes running locked_make on the same target do not race
    two compilers onto one output file."""
    import subprocess
    import sys

    native_dir = tmp_path / "native"
    native_dir.mkdir()
    (native_dir / "Makefile").write_text(
        "out.txt:\n"
        "\tsh -c 'echo start >> log.txt; sleep 0.5; echo $$$$ > out.txt;"
        " echo done >> log.txt'\n")
    code = ("import sys; sys.path.insert(0, %r); "
            "from edl_tpu.utils.buildlock import locked_make; "
            "locked_make(%r, 'out.txt')"
            % (REPO, str(native_dir)))
    procs = [subprocess.Popen([sys.executable, "-c", code])
             for _ in range(2)]
    for p in procs:
        assert p.wait(timeout=60) == 0
    # the second holder found the target up to date: exactly one build
    log = (native_dir / "log.txt").read_text().splitlines()
    assert log == ["start", "done"], log
    assert (native_dir / "out.txt").exists()


def test_resume_preserves_adjust_hooks_and_extra_state(tmp_path):
    trainer, make_batch, _ = _linreg_trainer(tmp_path)
    trainer.begin_epoch(0)
    trainer.train_step(make_batch(0))
    trainer.end_epoch(save=True)

    # restart WITH a new extra_state the checkpoint doesn't have: core must
    # still restore, extra kept as the fresh initial value
    def make2():
        t2, mb, _ = _linreg_trainer(
            tmp_path, extra_state={"loader_pos": np.int32(123)})
        return t2

    # 64-bit extra leaves are rejected loudly (device_put would truncate)
    with pytest.raises(ValueError, match="64-bit"):
        _linreg_trainer(tmp_path, extra_state={"pos": np.int64(1 << 40)})

    t2 = make2()
    calls = []
    t2.state.register_adjust_function(lambda s, w: calls.append(w))
    assert t2.resume()
    assert t2.global_step == 1
    assert int(t2.extra_state["loader_pos"]) == 123
    # hooks survived the state swap: simulate a world change record
    t2.state.epochs[str(t2.state.epoch_no)]["world_size"] = 4
    t2.state.adjust(t2.world_size)
    assert calls  # registered hook actually fired

    # now save WITH extra and restore again: extra roundtrips
    t2.begin_epoch(1)
    t2.train_step(make_batch(1))
    t2.end_epoch(save=True)
    t3 = make2()
    assert t3.resume()
    assert int(t3.extra_state["loader_pos"]) == 123


def test_elastic_trainer_with_tensor_parallel_params(tmp_path):
    """Elastic stop-resume composes with tensor parallelism: a dp x tp
    trainer with Megatron partition rules keeps params tp-sharded through
    train/save/resume, and the restored trainer continues bit-equal."""
    import jax.numpy as jnp

    from edl_tpu.models import bert
    from edl_tpu.runtime import mesh as mesh_mod

    def make_trainer():
        model, params, loss_fn = bert.create_model_and_loss(
            model=bert.bert_tiny(dtype=jnp.float32))
        mesh = mesh_mod.make_mesh(dp=4, tp=2)
        return ElasticTrainer(
            loss_fn, params, optax.adamw(1e-3), total_batch_size=16,
            checkpoint_dir=str(tmp_path / "ckpt"), mesh=mesh,
            param_shardings=bert.bert_partition_rules())

    trainer = make_trainer()
    qkv = trainer.train_state["params"]["layer_0"]["attention"]["query"][
        "kernel"]
    assert "tp" in str(qkv.sharding.spec), qkv.sharding.spec
    # adam moments inherit the param layout
    mu_qkv = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda x: x, trainer.train_state["opt_state"]))
    assert any("tp" in str(leaf.sharding.spec) for leaf in mu_qkv)

    batch = {k: np.asarray(v) for k, v in
             bert.synthetic_text_batch(16, seq_len=16).items()}
    trainer.begin_epoch(0)
    for i in range(3):
        loss = float(trainer.train_step(batch))
    trainer.end_epoch(save=True)
    # snapshot before the next donating step deletes the buffer
    qkv = trainer.train_state["params"]["layer_0"]["attention"]["query"][
        "kernel"]
    qkv_np = np.asarray(qkv)
    assert "tp" in str(qkv.sharding.spec)

    trainer2 = make_trainer()
    assert trainer2.resume()
    assert trainer2.global_step == 3
    qkv2 = trainer2.train_state["params"]["layer_0"]["attention"]["query"][
        "kernel"]
    assert "tp" in str(qkv2.sharding.spec)
    np.testing.assert_array_equal(qkv_np, np.asarray(qkv2))
    # the restored trainer steps to the same loss as the original would
    l1 = float(trainer.train_step(batch))
    l2 = float(trainer2.train_step(batch))
    assert l1 == pytest.approx(l2, rel=1e-6)
    assert l2 < loss  # still learning


def test_elastic_trainer_on_hybrid_mesh(tmp_path):
    """ElasticTrainer over a multi-slice (dcn x dp) mesh: batches shard
    over BOTH data axes and training matches the flat-dp mesh."""
    from edl_tpu.models import linear
    from edl_tpu.runtime import mesh as mesh_mod

    results = {}
    for name, mesh in (
            ("flat", mesh_mod.make_mesh(dp=8)),
            ("hybrid", mesh_mod.make_hybrid_mesh(dcn_dp=2))):
        trainer = ElasticTrainer(
            linear.loss_fn, linear.init_params(), optax.sgd(0.05),
            total_batch_size=32,
            checkpoint_dir=str(tmp_path / ("ckpt_" + name)), mesh=mesh)
        for i in range(5):
            loss = float(trainer.train_step(
                linear.synthetic_batch(32, seed=i)))
        results[name] = loss
    assert results["flat"] == pytest.approx(results["hybrid"], rel=1e-5)


def test_elastic_trainer_long_context_ring(tmp_path):
    """Elastic long-context training: BERT with ring attention over sp
    inside the jitted elastic step; save/resume keeps working."""
    import jax.numpy as jnp

    from edl_tpu.models import bert
    from edl_tpu.runtime import mesh as mesh_mod

    mesh = mesh_mod.make_mesh(dp=2, sp=4)

    def make_trainer():
        model = bert.Bert(num_layers=2, d_model=32, num_heads=2,
                          mlp_dim=64, vocab_size=100, max_len=64,
                          dtype=jnp.float32, use_ring=True, mesh=mesh)
        _, params, loss_fn = bert.create_model_and_loss(
            model=model, dummy_batch=8, dummy_seq=32)
        return ElasticTrainer(
            loss_fn, params, optax.adamw(1e-3), total_batch_size=8,
            checkpoint_dir=str(tmp_path / "ckpt"), mesh=mesh)

    trainer = make_trainer()
    batch = {k: np.asarray(v) for k, v in
             bert.synthetic_text_batch(8, seq_len=32,
                                       vocab_size=100).items()}
    trainer.begin_epoch(0)
    losses = [float(trainer.train_step(batch)) for _ in range(4)]
    assert losses[-1] < losses[0]
    trainer.end_epoch(save=True)

    trainer2 = make_trainer()
    assert trainer2.resume()
    assert trainer2.global_step == 4
    l2 = float(trainer2.train_step(batch))
    assert np.isfinite(l2) and l2 < losses[0]


def test_async_save_overlaps_donation(tmp_path):
    """Async save snapshots on device, so continuing to train (which
    donates the original buffers) cannot corrupt the checkpoint."""
    trainer, make_batch, _ = _linreg_trainer(tmp_path, async_save=True)
    for i in range(5):
        trainer.train_step(make_batch(i))
    trainer.begin_epoch(0)
    trainer.end_epoch(save=True)  # async write of step-5 state
    # keep training immediately — donates the buffers save() snapshotted
    for i in range(5, 10):
        trainer.train_step(make_batch(i))
    trainer.wait_for_save()

    trainer2, make_batch2, _ = _linreg_trainer(tmp_path)
    assert trainer2.resume()
    assert trainer2.global_step == 5  # the snapshot, not the later state
    loss = float(trainer2.train_step(make_batch2(50)))
    assert np.isfinite(loss)


def test_trainer_batch_sharded_over_dp(tmp_path):
    trainer, make_batch, _ = _linreg_trainer(tmp_path)
    batch = trainer.shard_batch(make_batch(0))
    x = batch["x"]
    assert len(x.sharding.device_set) == 8
    # each device holds 1/8 of the batch rows
    shard = x.addressable_shards[0]
    assert shard.data.shape == (8, 4)


def test_accum_step_matches_full_batch_gradient():
    """make_accum_step(k): averaged microbatch gradients == the full-batch
    gradient for a mean-reduced loss, so the update is independent of k."""
    from edl_tpu.models import linear
    from edl_tpu.runtime.trainer import (make_accum_step, make_train_state,
                                         make_train_step)

    params = linear.init_params(feature_dim=4)
    tx = optax.sgd(0.1)
    rs = np.random.RandomState(1)
    full = {
        "x": rs.randn(16, 4).astype(np.float32),
        "y": rs.randn(16).astype(np.float32),
    }
    rng = jax.random.PRNGKey(3)

    base = jax.jit(make_train_step(linear.loss_fn, tx))
    want, want_loss = base(make_train_state(params, tx), full, rng)

    K = 4
    micro = {k: v.reshape((K, 16 // K) + v.shape[1:])
             for k, v in full.items()}
    accum = jax.jit(make_accum_step(linear.loss_fn, tx, accum_steps=K))
    got, got_loss = accum(make_train_state(params, tx), micro, rng)

    assert int(got["step"]) == 1  # ONE optimizer update
    np.testing.assert_allclose(float(got_loss), float(want_loss),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got["params"]),
                    jax.tree_util.tree_leaves(want["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_accum_step_chains_extra_state():
    """has_aux extra state must thread microbatch-to-microbatch (the BN
    running-stats semantics), ending at the LAST microbatch's value."""
    from edl_tpu.runtime.trainer import make_accum_step, make_train_state

    def loss_fn(params, extra, batch, rng):
        loss = ((params["w"] * batch["x"]) ** 2).mean()
        return loss, {"count": extra["count"] + 1,
                      "last": batch["x"].mean()}

    tx = optax.sgd(0.01)
    params = {"w": jnp.ones((4,))}
    state = make_train_state(params, tx,
                             {"count": jnp.zeros((), jnp.int32),
                              "last": jnp.zeros(())})
    K = 3
    batches = {"x": np.arange(K * 2 * 4, dtype=np.float32)
                      .reshape(K, 2, 4)}
    step = jax.jit(make_accum_step(loss_fn, tx, accum_steps=K,
                                   has_aux=True))
    state, _ = step(state, batches, jax.random.PRNGKey(0))
    assert int(state["extra"]["count"]) == K
    np.testing.assert_allclose(float(state["extra"]["last"]),
                               batches["x"][-1].mean(), rtol=1e-6)


def test_elastic_trainer_grad_accum_equivalent(tmp_path):
    """ElasticTrainer(grad_accum=2) produces the same params as
    grad_accum=1 on the same data (deterministic loss), sharded over the
    virtual dp mesh."""
    from edl_tpu.models import linear
    from edl_tpu.runtime.trainer import ElasticTrainer

    rs = np.random.RandomState(2)
    batch = {
        "x": rs.randn(16, 4).astype(np.float32),
        "y": rs.randn(16).astype(np.float32),
    }

    params = []
    for k in (1, 2):
        tr = ElasticTrainer(linear.loss_fn, linear.init_params(4),
                            optax.sgd(0.05), total_batch_size=16,
                            checkpoint_dir="", grad_accum=k)
        for i in range(3):
            tr.train_step(batch, rng=jax.random.PRNGKey(i))
        params.append(jax.tree_util.tree_leaves(
            jax.device_get(tr.train_state["params"])))
    for a, b in zip(*params):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_overlap_accum_bitwise_identity_unsharded():
    """Overlap with no mesh: no collectives to hide, so make_accum_step
    returns the eager step unchanged — the update is BITWISE identical
    for a fixed seed across accum_steps 1/2/4, by construction."""
    from edl_tpu.models import linear
    from edl_tpu.runtime.trainer import make_accum_step, make_train_state

    params = linear.init_params(feature_dim=4)
    tx = optax.sgd(0.1)
    rs = np.random.RandomState(5)
    full = {
        "x": rs.randn(16, 4).astype(np.float32),
        "y": rs.randn(16).astype(np.float32),
    }
    rng = jax.random.PRNGKey(11)
    for K in (1, 2, 4):
        micro = {k: v.reshape((K, 16 // K) + v.shape[1:])
                 for k, v in full.items()}
        off = jax.jit(make_accum_step(linear.loss_fn, tx, accum_steps=K))
        on = jax.jit(make_accum_step(linear.loss_fn, tx, accum_steps=K,
                                     overlap_axis="dp", mesh=None))
        got_off, loss_off = off(make_train_state(params, tx), micro, rng)
        got_on, loss_on = on(make_train_state(params, tx), micro, rng)
        assert np.asarray(loss_on).tobytes() == np.asarray(loss_off).tobytes()
        for a, b in zip(jax.tree_util.tree_leaves(got_on["params"]),
                        jax.tree_util.tree_leaves(got_off["params"])):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), K


def test_overlap_accum_degrades_on_single_device_mesh():
    """A real 1-device mesh must take the logged no-op path (no
    collectives, no shard_map — the eager step is returned) and match
    the plain accum step bitwise."""
    from edl_tpu.models import linear
    from edl_tpu.runtime.trainer import make_accum_step, make_train_state

    mesh1 = mesh_mod.make_mesh(dp=1, devices=jax.devices()[:1])
    params = linear.init_params(feature_dim=4)
    tx = optax.sgd(0.1)
    rs = np.random.RandomState(6)
    micro = {
        "x": rs.randn(2, 8, 4).astype(np.float32),
        "y": rs.randn(2, 8).astype(np.float32),
    }
    rng = jax.random.PRNGKey(0)
    off = jax.jit(make_accum_step(linear.loss_fn, tx, accum_steps=2))
    on = jax.jit(make_accum_step(linear.loss_fn, tx, accum_steps=2,
                                 overlap_axis=mesh_mod.DATA_AXIS,
                                 mesh=mesh1))
    got_off, _ = off(make_train_state(params, tx), micro, rng)
    got_on, _ = on(make_train_state(params, tx), micro, rng)
    for a, b in zip(jax.tree_util.tree_leaves(got_on["params"]),
                    jax.tree_util.tree_leaves(got_off["params"])):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_overlap_accum_sharded_matches_eager():
    """Overlap over the real 8-way dp axis (shard_map + delayed pmean)
    must agree with the eager accum step on the same global batch: the
    per-shard sum-then-pmean reassociates the row reduction, so allclose
    rather than bitwise."""
    from edl_tpu.models import linear
    from edl_tpu.runtime.trainer import make_accum_step, make_train_state

    mesh = mesh_mod.make_mesh(dp=8)
    params = linear.init_params(feature_dim=4)
    tx = optax.sgd(0.1)
    rs = np.random.RandomState(9)
    K = 2
    micro = {
        "x": rs.randn(K, 16, 4).astype(np.float32),
        "y": rs.randn(K, 16).astype(np.float32),
    }
    rng = jax.random.PRNGKey(4)
    off = jax.jit(make_accum_step(linear.loss_fn, tx, accum_steps=K))
    on = jax.jit(make_accum_step(linear.loss_fn, tx, accum_steps=K,
                                 overlap_axis=mesh_mod.DATA_AXIS,
                                 mesh=mesh))
    got_off, loss_off = off(make_train_state(params, tx), micro, rng)
    got_on, loss_on = on(make_train_state(params, tx), micro, rng)
    assert int(got_on["step"]) == 1
    np.testing.assert_allclose(float(loss_on), float(loss_off), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_on["params"]),
                    jax.tree_util.tree_leaves(got_off["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_overlap_accum_rejects_has_aux():
    from edl_tpu.models import linear
    from edl_tpu.runtime.trainer import make_accum_step

    with pytest.raises(ValueError, match="has_aux"):
        make_accum_step(linear.loss_fn, optax.sgd(0.1), accum_steps=2,
                        has_aux=True, overlap_axis="dp")


def test_elastic_trainer_dp_overlap_matches_plain(tmp_path):
    """ElasticTrainer(dp_overlap=True, grad_accum=2) trains to the same
    params as the plain accum trainer on the same data, and the invalid
    combinations raise up front."""
    from edl_tpu.models import linear
    from edl_tpu.runtime.trainer import ElasticTrainer

    rs = np.random.RandomState(3)
    batch = {
        "x": rs.randn(16, 4).astype(np.float32),
        "y": rs.randn(16).astype(np.float32),
    }
    params = []
    for overlap in (False, True):
        tr = ElasticTrainer(linear.loss_fn, linear.init_params(4),
                            optax.sgd(0.05), total_batch_size=16,
                            checkpoint_dir="", grad_accum=2,
                            dp_overlap=overlap)
        for i in range(3):
            tr.train_step(batch, rng=jax.random.PRNGKey(i))
        params.append(jax.tree_util.tree_leaves(
            jax.device_get(tr.train_state["params"])))
    for a, b in zip(*params):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    with pytest.raises(ValueError, match="has_aux"):
        ElasticTrainer(linear.loss_fn, linear.init_params(4),
                       optax.sgd(0.05), total_batch_size=16,
                       checkpoint_dir="", grad_accum=2, dp_overlap=True,
                       has_aux=True, extra_state={"n": jnp.zeros(())})
    with pytest.raises(ValueError, match="replicated"):
        ElasticTrainer(linear.loss_fn, linear.init_params(4),
                       optax.sgd(0.05), total_batch_size=16,
                       checkpoint_dir="", grad_accum=2, dp_overlap=True,
                       zero1=True)


def test_zero1_spec_composition():
    """zero1_spec shards the first free divisible dim over dp, on top of
    the param's tp layout; falls back to the param spec when nothing
    divides."""
    from jax.sharding import PartitionSpec as P

    from edl_tpu.parallel.sharding import zero1_spec
    from edl_tpu.runtime import mesh as mesh_mod

    mesh = mesh_mod.make_mesh(dp=4, tp=2)
    # replicated 2-D param: dim0 divisible -> ("dp", None)
    assert zero1_spec(P(), (8, 6), mesh) == P("dp", None)
    # tp on dim0 -> dp goes to dim1
    assert zero1_spec(P("tp", None), (2, 8), mesh) == P("tp", "dp")
    # nothing divisible by 4 -> unchanged
    assert zero1_spec(P(), (6, 3), mesh) == P()
    # scalars unchanged
    assert zero1_spec(P(), (), mesh) == P()
    # rank-mismatched leaf (factored optimizer row/col): left alone
    assert zero1_spec(P("tp", None), (8,), mesh) == P("tp", None)
    # tuple axis (hybrid mesh data-replica set): sharded over both
    hybrid = mesh_mod.make_hybrid_mesh(dcn_dp=2, tp=1,
                                       devices=jax.devices()[:8])
    got = zero1_spec(P(), (8, 4), hybrid, axis=("dcn", "dp"))
    assert got == P(("dcn", "dp"), None), got


def test_elastic_trainer_zero1_shards_moments_and_matches(tmp_path):
    """zero1=True: adam moments are dp-sharded (1/dp per-device memory),
    training is numerically equivalent to the replicated optimizer, and
    save/resume round-trips."""
    from edl_tpu.models import linear

    rs = np.random.RandomState(3)
    batch = {
        "x": rs.randn(16, 8).astype(np.float32),
        "y": rs.randn(16).astype(np.float32),
    }

    from jax.sharding import PartitionSpec as P

    losses = {}
    finals = {}
    for z in (False, True):
        tr = ElasticTrainer(linear.loss_fn, linear.init_params(8),
                            optax.adamw(1e-2), total_batch_size=16,
                            checkpoint_dir=str(tmp_path / ("z%d" % z)),
                            zero1=z)
        if z:
            mu_w = tr.train_state["opt_state"][0].mu["w"]
            assert "dp" in str(mu_w.sharding.spec), mu_w.sharding.spec
            n_dp = tr.mesh.shape["dp"]
            shard_rows = mu_w.addressable_shards[0].data.shape[0]
            assert shard_rows == mu_w.shape[0] // n_dp
            # params stay replicated
            assert tr.train_state["params"]["w"].sharding.spec == P()
        ls = [float(tr.train_step(batch, rng=jax.random.PRNGKey(i)))
              for i in range(3)]
        losses[z] = ls
        tr.state.begin_epoch(0, tr.world_size)
        tr.end_epoch(save=True)
        finals[z] = jax.device_get(tr.train_state["params"])
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(finals[True]),
                    jax.tree_util.tree_leaves(finals[False])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)

    # resume restores the dp-sharded layout
    tr2 = ElasticTrainer(linear.loss_fn, linear.init_params(8),
                         optax.adamw(1e-2), total_batch_size=16,
                         checkpoint_dir=str(tmp_path / "z1"), zero1=True)
    assert tr2.resume()
    mu_w = tr2.train_state["opt_state"][0].mu["w"]
    assert "dp" in str(mu_w.sharding.spec)


def test_zero1_composes_with_tensor_parallel():
    """zero1 over dp composes with Megatron tp rules: moments carry BOTH
    axes, params keep only tp."""
    import jax.numpy as jnp

    from edl_tpu.models import bert
    from edl_tpu.runtime import mesh as mesh_mod

    model, params, loss_fn = bert.create_model_and_loss(
        model=bert.bert_tiny(dtype=jnp.float32))
    mesh = mesh_mod.make_mesh(dp=4, tp=2)
    tr = ElasticTrainer(loss_fn, params, optax.adamw(1e-3),
                        total_batch_size=8, checkpoint_dir="", mesh=mesh,
                        param_shardings=bert.bert_partition_rules(),
                        zero1=True)
    mu = tr.train_state["opt_state"][0].mu
    qkv_mu = mu["layer_0"]["attention"]["query"]["kernel"]
    spec = str(qkv_mu.sharding.spec)
    assert "tp" in spec and "dp" in spec, spec
    qkv = tr.train_state["params"]["layer_0"]["attention"]["query"]["kernel"]
    assert "dp" not in str(qkv.sharding.spec)
    batch = {k: np.asarray(v) for k, v in
             bert.synthetic_text_batch(8, seq_len=16).items()}
    l0 = float(tr.train_step(batch, rng=jax.random.PRNGKey(0)))
    l1 = float(tr.train_step(batch, rng=jax.random.PRNGKey(0)))
    assert np.isfinite([l0, l1]).all() and l1 < l0


def test_zero1_on_hybrid_mesh_uses_full_replica_set(tmp_path):
    """On a multi-slice mesh zero1 shards moments over (dcn, dp) — the
    whole data-replica set — not just dp."""
    from edl_tpu.models import linear
    from edl_tpu.runtime import mesh as mesh_mod

    mesh = mesh_mod.make_hybrid_mesh(dcn_dp=2, devices=jax.devices()[:8])
    tr = ElasticTrainer(linear.loss_fn, linear.init_params(8),
                        optax.adamw(1e-2), total_batch_size=16,
                        checkpoint_dir="", mesh=mesh, zero1=True)
    mu_w = tr.train_state["opt_state"][0].mu["w"]
    spec = str(mu_w.sharding.spec)
    assert "dcn" in spec and "dp" in spec, spec
    rs = np.random.RandomState(4)
    batch = {"x": rs.randn(16, 8).astype(np.float32),
             "y": rs.randn(16).astype(np.float32)}
    l0 = float(tr.train_step(batch, rng=jax.random.PRNGKey(0)))
    l1 = float(tr.train_step(batch, rng=jax.random.PRNGKey(0)))
    assert np.isfinite([l0, l1]).all() and l1 < l0


def test_auto_grad_accum_policy():
    """max_per_device_batch picks the smallest dividing accumulation that
    fits the budget — the per-world-size elastic memory policy."""
    from edl_tpu.models import linear
    from edl_tpu.runtime.trainer import auto_grad_accum

    assert auto_grad_accum(8, 8) == 1
    assert auto_grad_accum(8, 4) == 2
    assert auto_grad_accum(8, 3) == 4   # 8/2=4 > 3; next divisor 4 -> 2
    assert auto_grad_accum(8, 1) == 8
    assert auto_grad_accum(6, 4) == 2   # divisors only: 6/2=3 fits
    with pytest.raises(ValueError):
        auto_grad_accum(8, 0)

    # through the trainer: 8 devices, total 64 -> per-device 8; budget 2
    # -> grad_accum 4 (observable via the microbatch-major reshape)
    tr = ElasticTrainer(linear.loss_fn, linear.init_params(4),
                        optax.sgd(0.05), total_batch_size=64,
                        checkpoint_dir="", max_per_device_batch=2)
    assert tr._grad_accum == 4
    rs = np.random.RandomState(5)
    batch = {"x": rs.randn(64, 4).astype(np.float32),
             "y": rs.randn(64).astype(np.float32)}
    loss = float(tr.train_step(batch))
    assert np.isfinite(loss)


def test_auto_grad_accum_rejects_explicit_conflict():
    from edl_tpu.models import linear

    with pytest.raises(ValueError, match="not\\s+both"):
        ElasticTrainer(linear.loss_fn, linear.init_params(4),
                       optax.sgd(0.05), total_batch_size=64,
                       checkpoint_dir="", grad_accum=2,
                       max_per_device_batch=2)


def test_resize_invariant_training_under_budget(tmp_path):
    """The elastic headline: with a fixed total_batch_size and a
    per-device budget, training at world 8 (accum 1) and at world 2
    (accum 4 chosen automatically) produces the same parameters — a
    resize changes THROUGHPUT, never convergence."""
    from edl_tpu.models import linear
    from edl_tpu.runtime import mesh as mesh_mod

    rs = np.random.RandomState(7)
    batch = {"x": rs.randn(32, 4).astype(np.float32),
             "y": rs.randn(32).astype(np.float32)}

    finals = []
    for n_dev in (8, 2):
        mesh = mesh_mod.make_mesh(dp=n_dev,
                                  devices=jax.devices()[:n_dev])
        tr = ElasticTrainer(linear.loss_fn, linear.init_params(4),
                            optax.sgd(0.05), total_batch_size=32,
                            checkpoint_dir="", mesh=mesh,
                            max_per_device_batch=4)
        # world 8: per-device 4 -> accum 1; world 2: per-device 16 -> 4
        assert tr._grad_accum == (1 if n_dev == 8 else 4)
        for i in range(3):
            tr.train_step(batch, rng=jax.random.PRNGKey(i))
        finals.append(jax.tree_util.tree_leaves(
            jax.device_get(tr.train_state["params"])))
    for a, b in zip(*finals):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_prewarm_resize_aot_executable_cross_process(tmp_path):
    """The restart-latency lever (SURVEY §7): an 8-device trainer
    prewarns the 4-device step as a SERIALIZED AOT EXECUTABLE (the
    persistent compile cache cannot carry it — its key includes the
    platform topology); a FRESH 4-device process loads it at its first
    train_step and skips the compile, and training still converges. A
    2-device control — never prewarmed — must NOT report a hit."""
    import json
    import subprocess
    import sys

    from conftest import cpu_subprocess_env

    cache = tmp_path / "xla_cache"
    cache.mkdir()

    script = r"""
import json
import sys
import jax
import numpy as np
import optax
from edl_tpu.models import linear
from edl_tpu.runtime import trainer as trainer_mod
from edl_tpu.runtime.trainer import ElasticTrainer

hits = []
orig = ElasticTrainer._try_load_prewarmed_step
def spy(self):
    out = orig(self)
    hits.append(out is not None)
    return out
ElasticTrainer._try_load_prewarmed_step = spy

trainer = ElasticTrainer(linear.loss_fn, linear.init_params(),
                         optax.sgd(0.05), total_batch_size=16)
w_true = np.arange(1, 14, dtype=np.float32) / 10
rs = np.random.RandomState(0)
loss = None
for i in range(30):
    x = rs.randn(16, 13).astype(np.float32)
    batch = {"x": x, "y": x @ w_true}
    loss = float(trainer.train_step(batch))
if "--prewarm" in sys.argv:
    done = trainer.prewarm_resize_compiles([4])
    assert done == [4], done
print(json.dumps({"hit": bool(hits and hits[0]), "loss": loss,
                  "devices": jax.device_count()}))
"""

    def run(n_devices, *args):
        env = cpu_subprocess_env(n_devices,
                                 JAX_COMPILATION_CACHE_DIR=str(cache))
        r = subprocess.run([sys.executable, "-c", script] + list(args),
                           env=env, capture_output=True, text=True,
                           timeout=240)
        assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
        return json.loads(r.stdout.strip().splitlines()[-1])

    a = run(8, "--prewarm")
    assert not a["hit"]  # nothing to load the first time
    aot = cache / "aot_steps"
    assert aot.is_dir() and list(aot.glob("step_w4_*.pkl"))

    b = run(4)
    assert b["hit"], "4-device restart did not load the AOT step"
    assert b["loss"] < 0.1, b  # the loaded executable really trains

    c = run(2)  # control: never prewarmed -> no hit, still works
    assert not c["hit"]
    assert c["loss"] < 0.1, c


def test_prewarm_targets_respect_grad_accum_batch_axis(tmp_path,
                                                       monkeypatch):
    """Under grad accumulation the example batch is [k, rows/k, ...] —
    the prewarm divisibility check must follow the SHARDED axis (axis 1
    here), not axis 0 (code review r4): with k=2 and 32 rows, world 4
    must be accepted (16 sharded rows % 4 == 0), not rejected because
    2 % 4 != 0."""
    from edl_tpu.models import linear

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    trainer = ElasticTrainer(linear.loss_fn, linear.init_params(),
                             optax.sgd(0.01), total_batch_size=32,
                             grad_accum=2)
    batch = {"x": np.ones((32, 13), np.float32),
             "y": np.ones((32,), np.float32)}
    trainer.train_step(batch)
    done = trainer.prewarm_resize_compiles([4])
    assert done == [4], done
    aot = tmp_path / "cache" / "aot_steps"
    assert list(aot.glob("step_w4_*.pkl"))


#: sha256 (first 16 hex digits) of the jaxpr of loss and gradient as the
#: benchmark's dense cells trace them — the configuration's `tiny` sizes
#: through benchmark/program/<family>.py:train_parts — recorded on the
#: commit before PR 52 (ac8177a), which removed the second BatchNorm and
#: the scanned step builder beside this path. The two ResNet cells trace
#: the program they traced then; gpt2s-train's is PR 64's, whose loss reads
#: every row of the logits against shifted targets
#: (`ops/cross_entropy.py`; tests/test_cross_entropy.py holds it to the form
#: it had). The sparse families are
#: pinned by tests/test_gated_delta.py and tests/test_looped_decoder.py
DENSE_TRACED = {
    "resnet50-vd": ({}, "60924de21a9e3faa"),
    "gpt2-small": ({"seq_len": 32, "remat": False}, "887784a7c7184002"),
}


@pytest.mark.parametrize("config", sorted(DENSE_TRACED))
def test_dense_cells_differentiate_the_program_they_did(config):
    from jaxpr_kernels import traced_dense_gradient
    job, want = DENSE_TRACED[config]
    assert traced_dense_gradient(config, job) == want
