"""Zero-downtime live resize: the in-place reshard engine, the 2PC
store protocol, the generator/launcher integration, and the liveft
transition classifier.

The headline contract: a live 8→4→8 resize produces params + optimizer
state BYTE-IDENTICAL to a stop-resume (kill / respawn / restore) over
the same mesh sequence — the live path changes how fast a resize is,
never what it computes. (Neither path is bitwise-comparable to a
never-resized run: any world change reorders the allreduce.) The chaos
drill proves the other half: a fault mid-reshard rolls back to the old
mesh byte-identically and surfaces as LiveResizeError, so the
stop-resume ladder stays the safety net.

Runs on the conftest's 8 virtual CPU devices — single process, pure dp,
replicated state: exactly the live-resize scope.
"""

import json
import threading
import time

import jax
import numpy as np
import optax
import pytest

from edl_tpu.controller import cluster as cluster_mod
from edl_tpu.controller import constants
from edl_tpu.models import linear
from edl_tpu.obs import events as obs_events
from edl_tpu.obs import trace as obs_trace
from edl_tpu.robustness import faults
from edl_tpu.runtime import live_resize as live_mod
from edl_tpu.runtime import trainer as trainer_mod
from edl_tpu.runtime.mesh import make_mesh
from edl_tpu.runtime.trainer import ElasticTrainer
from edl_tpu.utils.errors import LiveResizeError

TOTAL_BATCH = 64
BATCHES = [linear.synthetic_batch(TOTAL_BATCH, seed=i) for i in range(8)]


def _trainer(n_devices, ckpt=None, coord=None, **kw):
    return ElasticTrainer(
        linear.loss_fn, linear.init_params(), optax.sgd(0.05),
        total_batch_size=TOTAL_BATCH,
        mesh=make_mesh(devices=jax.devices()[:n_devices]),
        checkpoint_dir=ckpt, coord=coord, **kw)


def _steps(trainer, batches):
    for b in batches:
        trainer.train_step(trainer.local_batch_slice(b))


def _state_bytes(trainer):
    return [np.asarray(x).tobytes()
            for x in jax.tree_util.tree_leaves(trainer.train_state)]


def _world(trainer):
    return len(list(trainer.mesh.devices.flat))


def _wait(pred, timeout=15.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(interval)
    raise AssertionError("condition not met within %ss" % timeout)


# -- the engine: byte identity, rollback, edges ----------------------------


def test_live_resize_byte_identical_to_stop_resume(tmp_path):
    """The acceptance contract: live 8→4→8 == stop-resume 8→4→8,
    byte for byte, over the same batch schedule."""
    live = _trainer(8)
    _steps(live, BATCHES[:2])
    rec_dn = live.live_resize(4)
    assert rec_dn["mode"] == "live"
    assert (rec_dn["from_devices"], rec_dn["to_devices"]) == (8, 4)
    assert _world(live) == 4
    _steps(live, BATCHES[2:4])
    rec_up = live.live_resize(8)
    assert (rec_up["from_devices"], rec_up["to_devices"]) == (4, 8)
    assert _world(live) == 8
    _steps(live, BATCHES[4:6])

    # the stop-resume chain: three incarnations over the same worlds
    ckpt = str(tmp_path / "ckpt")
    a = _trainer(8, ckpt=ckpt)
    _steps(a, BATCHES[:2])
    a.save()
    b = _trainer(4, ckpt=ckpt)
    assert b.resume()
    _steps(b, BATCHES[2:4])
    b.save()
    c = _trainer(8, ckpt=ckpt)
    assert c.resume()
    _steps(c, BATCHES[4:6])

    assert _state_bytes(live) == _state_bytes(c)


@pytest.mark.parametrize("point", ["resize.live.drain",
                                   "resize.live.reshard"])
def test_live_resize_fault_rolls_back_byte_identical(point):
    """The chaos drill: a fault at either live fault point rolls the
    trainer back to the OLD mesh with state untouched (zero
    divergence), raises LiveResizeError (the nack path), emits the
    fallback event, and the trainer keeps training."""
    tr = _trainer(8)
    _steps(tr, BATCHES[:2])
    before = _state_bytes(tr)
    mark = obs_events.emit("test.live_resize.mark")
    plane = faults.FaultPlane(seed=7)
    plane.inject(point, "error", error="RpcError")
    plane.install()
    try:
        with pytest.raises(LiveResizeError):
            tr.live_resize(4)
    finally:
        plane.uninstall()
    assert (point, "error") in plane.log  # the fault actually fired
    assert _world(tr) == 8
    assert _state_bytes(tr) == before
    kinds = [e["kind"] for e in obs_events.EVENTS.snapshot(since_id=mark)]
    assert "resize.live.fallback" in kinds
    # numerically untouched AND still functional on the old mesh
    _steps(tr, [BATCHES[2]])


def test_live_resize_single_survivor_and_back():
    """The 8→1→8 edge: one device is still a valid dp mesh; the reshard
    is the pure zero-wire fast path (no store, no peers, no FS)."""
    tr = _trainer(8)
    _steps(tr, BATCHES[:1])
    rec = tr.live_resize(1)
    assert _world(tr) == 1
    assert rec["restore_source"] == "local"
    assert rec["restore_peers"] == 0
    _steps(tr, BATCHES[1:2])
    rec_up = tr.live_resize(8)
    assert _world(tr) == 8
    assert rec_up["restore_source"] == "local"
    _steps(tr, BATCHES[2:3])


def test_live_resize_noop_and_scope_rejections():
    tr = _trainer(8)
    _steps(tr, BATCHES[:1])
    assert tr.live_resize(8).get("noop") is True
    before = _state_bytes(tr)
    for bad in (0, len(jax.devices()) + 1, 3):  # range, range, 64 % 3
        with pytest.raises(LiveResizeError):
            tr.live_resize(bad)
    assert _world(tr) == 8
    assert _state_bytes(tr) == before


def test_live_resize_prewarm_hit(tmp_path, monkeypatch):
    """With a compile cache and a prewarmed target world, the live
    swap takes the executable the prewarm kept instead of recompiling,
    and the grow back takes the step the job left there — the record
    says so, and that is what the doctor's prewarm_miss detector keys
    off. A trainer that does not hold it loads the artifact from disk."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    tr = _trainer(8)
    _steps(tr, BATCHES[:1])  # the prewarm needs the batch structure
    assert tr.prewarm_resize_compiles([4], block=True) == [4]
    rec = tr.live_resize(4)
    assert (rec["prewarm"], rec["step_source"]) == ("hit", "memory")
    _steps(tr, BATCHES[1:2])
    # the grow leg goes back to a world the job has run on
    rec = tr.live_resize(8)
    assert (rec["prewarm"], rec["step_source"]) == ("hit", "memory")
    _steps(tr, BATCHES[2:3])
    # a world never visited nor prewarmed is an honest miss, not "n/a"
    rec = tr.live_resize(2)
    assert (rec["prewarm"], rec["step_source"]) == ("miss", "compile")
    _steps(tr, BATCHES[3:4])
    # another trainer of this process: empty table, artifact on disk
    other = _trainer(8)
    _steps(other, BATCHES[:1])
    rec = other.live_resize(4)
    assert (rec["prewarm"], rec["step_source"]) == ("hit", "disk")
    _steps(other, BATCHES[1:2])
    # before any step there is no batch structure to key a step by
    fresh = _trainer(8)
    rec = fresh.live_resize(4)
    assert (rec["prewarm"], rec["step_source"]) == ("n/a", "compile")
    _steps(fresh, BATCHES[:1])


# -- the table of ready steps ----------------------------------------------


WALK = (2, 4, 2, 4)


def _walk(reuse):
    """4 -> 2 -> 4 -> 2 -> 4, two steps on every world: the losses, and
    per resize its record and what JAX did in its first dispatch."""
    from edl_tpu.obs import trace as obs_trace
    tr = _trainer(4)
    losses, records, jax_s = [], [], []
    batches = iter(BATCHES + BATCHES)

    def two_steps():
        for _ in range(2):
            b = next(batches)
            losses.append(np.asarray(
                tr.train_step(tr.local_batch_slice(b))).tobytes())

    two_steps()
    for world in WALK:
        if not reuse:
            tr._ready_steps.clear()
        obs_trace.TRACER.clear()
        records.append(tr.live_resize(world))
        two_steps()
        [d] = [s for s in obs_trace.TRACER.spans()
               if s["name"] == "resize.first_dispatch"]
        jax_s.append(d["tags"])
    tr.close()
    return {"losses": losses, "records": records, "jax_s": jax_s}


@pytest.fixture(scope="module")
def walks():
    return {reuse: _walk(reuse) for reuse in (True, False)}


@pytest.mark.parametrize("pause", [1, 2, 3])
def test_walk_back_to_a_world_fires_no_jax_event(walks, pause):
    """From the second pause on every target is a world the job has run
    on: the step comes from the table and its first dispatch traces,
    lowers, compiles and loads nothing."""
    walk = walks[True]
    assert walk["records"][0]["step_source"] == "compile"
    rec = walk["records"][pause]
    assert (rec["prewarm"], rec["step_source"]) == ("hit", "memory")
    assert set(walk["jax_s"][pause]) == {
        "jax_trace_s", "jax_lower_s", "jax_compile_s", "jax_cache_load_s"}
    assert sum(walk["jax_s"][pause].values()) == 0.0
    # with the table emptied the same pause builds the step again
    assert walks[False]["records"][pause]["step_source"] == "compile"
    assert walks[False]["jax_s"][pause]["jax_trace_s"] > 0


def test_walk_losses_equal_bit_for_bit_without_the_table(walks):
    """Reuse changes where the executable comes from, never what it
    computes: the same program on the same state."""
    assert len(walks[True]["losses"]) == 2 * (1 + len(WALK))
    assert walks[True]["losses"] == walks[False]["losses"]


def test_reuse_is_counted():
    from edl_tpu.runtime import trainer as trainer_mod
    tr = _trainer(4)
    _steps(tr, BATCHES[:1])
    before = trainer_mod._STEP_REUSES.value
    tr.live_resize(2)
    _steps(tr, BATCHES[1:2])
    assert trainer_mod._STEP_REUSES.value == before
    tr.live_resize(4)
    assert trainer_mod._STEP_REUSES.value == before + 1


def test_another_factorisation_of_the_same_devices_is_another_key():
    batches = _tp_batches()
    tr = _tp_trainer(4)
    _steps(tr, batches[:1])
    rec = tr.live_resize(4, mesh_shape={"dp": 2, "tp": 2})
    assert rec["step_source"] == "compile"
    _steps(tr, batches[1:2])
    # the same four devices as dp=1 x tp=4: never run, so not held
    rec = tr.live_resize(4, mesh_shape={"dp": 1, "tp": 4})
    assert rec["step_source"] == "compile"
    _steps(tr, batches[2:3])
    assert len(tr._ready_steps) == 2
    # each factorisation finds its own step again
    for shape in ({"dp": 2, "tp": 2}, {"dp": 4}, {"dp": 1, "tp": 4}):
        rec = tr.live_resize(4, mesh_shape=shape)
        assert rec["step_source"] == "memory", shape
        for a, n in shape.items():
            assert tr.mesh.shape[a] == n
        _steps(tr, batches[3:4])
    assert len(tr._ready_steps) == 3


def test_failing_ready_step_is_evicted_and_training_goes_on(
        tmp_path, monkeypatch):
    """An AOT entry takes exactly the inputs it was compiled for. One
    that refuses its first call (here: half the rows) does so before
    dispatch: it leaves the table, the jit path takes the step, and the
    jit step is what the world keeps from then on."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    tr = _trainer(8)
    _steps(tr, BATCHES[:1])
    assert tr.prewarm_resize_compiles([4], block=True) == [4]
    [key] = tr._ready_steps
    assert tr.live_resize(4)["step_source"] == "memory"
    aot = tr._jit_step
    assert tr._ready_steps[key] is aot
    half = linear.synthetic_batch(TOTAL_BATCH // 2, seed=9)
    loss = tr.train_step(half)
    assert np.isfinite(float(loss))
    assert key not in tr._ready_steps
    assert tr._jit_step is not aot
    _steps(tr, BATCHES[1:2])
    tr.live_resize(8)
    _steps(tr, BATCHES[2:3])
    # world 4 now keeps the jit step that ran there
    assert tr.live_resize(4)["step_source"] == "memory"
    assert tr._jit_step is not aot
    _steps(tr, BATCHES[3:4])


def test_prewarm_thread_racing_a_resize_takes_the_old_path(
        tmp_path, monkeypatch):
    """block=False: the entry appears when the thread finishes. A resize
    that comes first finds neither entry nor artifact, compiles as it
    always did, and does not wait for the thread."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    tr = _trainer(8)
    _steps(tr, BATCHES[:1])
    gate = threading.Event()
    lower = tr._step_lowered

    def held(world_n=None):
        if threading.current_thread().name == "resize-prewarm":
            assert gate.wait(60)
        return lower(world_n)

    monkeypatch.setattr(tr, "_step_lowered", held)
    assert tr.prewarm_resize_compiles([4], block=False) == [4]
    rec = tr.live_resize(4)
    assert (rec["prewarm"], rec["step_source"]) == ("miss", "compile")
    _steps(tr, BATCHES[1:2])
    assert tr._prewarm_thread.is_alive()
    assert len(tr._ready_steps) == 1    # the step left on world 8
    gate.set()
    tr._prewarm_thread.join(60)
    assert not tr._prewarm_thread.is_alive()
    assert len(tr._ready_steps) == 2    # and the thread's, for world 4
    assert tr.live_resize(8)["step_source"] == "memory"
    _steps(tr, BATCHES[2:3])
    assert tr.live_resize(4)["step_source"] == "memory"
    _steps(tr, BATCHES[3:4])


# -- the save in flight: waited for only where the reshard reads it ---------


class _Held(object):
    """A trainer two steps in, on 4 devices, whose asynchronous save is
    held in flight by a gate on the entry writes; `fail` makes every
    write raise once the gate opens."""

    def __init__(self, tmp_path, fail=None):
        self.tr = _trainer(4, ckpt=str(tmp_path / "ckpt"), async_save=True)
        _steps(self.tr, BATCHES[:2])
        self.gate = threading.Event()
        write = self.tr._ckpt._write_entry_file

        def held(path, arr):
            assert self.gate.wait(60)
            if fail is not None:
                raise fail
            return write(path, arr)

        self.tr._ckpt._write_entry_file = held
        self.tr.save()
        self.version = self.tr.global_step
        self.handle = self.tr._ckpt._inflight
        self.at_save = _state_bytes(self.tr)

    def in_flight(self):
        return (not self.handle.done()
                and self.tr._ckpt._inflight is self.handle
                and self.tr._ckpt.versions() == [])

    def restored(self):
        _, tree, _ = self.tr._ckpt.restore(
            self.version, target=dict(self.tr.train_state))
        return [np.asarray(x).tobytes()
                for x in jax.tree_util.tree_leaves(tree)]

    def blocks_until_the_gate_opens(self, fn, meanwhile=lambda: None):
        """Run `fn` on a thread: it must still be running after a while
        with the write held (`meanwhile` looks at what it has done by
        then), and end once the gate opens."""
        out = []
        t = threading.Thread(target=lambda: out.append(fn()))
        t.start()
        t.join(0.4)
        # (a drain that is waiting has already taken the handle)
        assert t.is_alive() and not self.handle.done()
        assert self.tr._ckpt.versions() == []
        meanwhile()
        self.gate.set()
        t.join(60)
        assert not t.is_alive() and self.handle.done()
        return out[0]


@pytest.fixture()
def held(tmp_path):
    h = _Held(tmp_path)
    yield h
    h.gate.set()
    h.tr.close()


def _root_drain_tags():
    return [s["tags"].get("drain")
            for s in obs_trace.TRACER.find(name="resize.live")]


def test_resize_leaves_the_persist_in_flight_running(held):
    """Fully addressable state: the resize returns and the first step on
    the new mesh completes while the save is still being written."""
    obs_trace.TRACER.clear()
    before = trainer_mod._DRAIN_DEFERRED.value
    rec = held.tr.live_resize(2)
    loss = held.tr.train_step(held.tr.local_batch_slice(BATCHES[2]))
    jax.block_until_ready(loss)
    assert held.in_flight()
    assert _world(held.tr) == 2
    assert rec["drain"] == held.tr.resize_timing["drain"] == "deferred"
    assert _root_drain_tags() == ["deferred"]
    assert trainer_mod._DRAIN_DEFERRED.value == before + 1
    # the drain span is there, and short: nothing was waited for
    [drain] = obs_trace.TRACER.find(name="resize.drain")
    assert drain["dur_ms"] == pytest.approx(rec["drain_s"] * 1e3, abs=2e-3)


def test_deferred_save_commits_the_state_at_the_save(held):
    held.tr.live_resize(2)
    _steps(held.tr, BATCHES[2:4])
    held.gate.set()
    held.tr.wait_for_save()
    assert held.tr._ckpt.versions() == [held.version]
    assert held.tr._ckpt._inflight is None
    assert held.restored() == held.at_save
    assert _state_bytes(held.tr) != held.at_save


def test_reshard_fault_with_the_persist_held_rolls_back_and_commits(held):
    plane = faults.FaultPlane(seed=7)
    plane.inject("resize.live.reshard", "error", error="RpcError")
    plane.install()
    try:
        with pytest.raises(LiveResizeError):
            held.tr.live_resize(2)
    finally:
        plane.uninstall()
    assert ("resize.live.reshard", "error") in plane.log
    assert _world(held.tr) == 4
    assert _state_bytes(held.tr) == held.at_save
    assert held.in_flight()       # the failed resize took no handle
    held.gate.set()
    held.tr.wait_for_save()
    assert held.tr._ckpt.versions() == [held.version]
    assert held.restored() == held.at_save
    _steps(held.tr, [BATCHES[2]])


@pytest.mark.parametrize("waiter", ["close", "save"])
def test_the_next_drain_waits_for_the_deferred_persist(held, waiter):
    """Whoever drains next collects the handle the resize left: the next
    save (max_inflight stays 1) and close()."""
    held.tr.live_resize(2)
    _steps(held.tr, BATCHES[2:3])
    held.blocks_until_the_gate_opens(getattr(held.tr, waiter))
    held.tr.wait_for_save()
    want = [held.version] + ([held.tr.global_step] if waiter == "save"
                             else [])
    assert held.tr._ckpt.versions() == want


def test_placed_reshard_waits_for_the_persist_before_it_moves(
        held, monkeypatch):
    """A leaf that is not fully addressable sends the reshard down the
    placed ladder, which reads the committed version: the wait is taken
    before the mesh is touched, as it always was."""
    monkeypatch.setattr(ElasticTrainer, "_fully_addressable",
                        staticmethod(lambda tree: False))
    obs_trace.TRACER.clear()
    before = trainer_mod._DRAIN_DEFERRED.value
    committed = []

    def resize():
        rec = held.tr.live_resize(2)
        committed.append(held.tr._ckpt.versions())
        return rec

    def nothing_has_moved():
        names = {s["name"] for s in obs_trace.TRACER.spans()}
        assert not names & {"resize.drain", "resize.mesh", "resize.live"}
        assert _world(held.tr) == 4

    rec = held.blocks_until_the_gate_opens(resize, nothing_has_moved)
    assert rec["drain"] == "waited" and _root_drain_tags() == ["waited"]
    assert committed == [[held.version]]
    assert trainer_mod._DRAIN_DEFERRED.value == before
    assert held.tr._ckpt._inflight is None
    assert _world(held.tr) == 2
    assert _state_bytes(held.tr) == held.at_save


def test_a_persist_that_fails_while_deferred_is_logged_by_the_next_drain(
        tmp_path, monkeypatch):
    from edl_tpu.runtime import checkpoint as checkpoint_mod
    h = _Held(tmp_path, fail=IOError("injected: disk full"))
    errors = []
    monkeypatch.setattr(checkpoint_mod.logger, "error",
                        lambda fmt, *a: errors.append(fmt % a))
    try:
        assert h.tr.live_resize(2)["drain"] == "deferred"
        _steps(h.tr, BATCHES[2:3])
        h.gate.set()
        assert h.handle.wait(60)
        # the resize did not swallow the handle, and nobody has read it
        assert h.tr._ckpt._inflight is h.handle and errors == []
        h.tr.wait_for_save()
        assert len(errors) == 1
        assert "v%d failed" % h.version in errors[0]
        assert "disk full" in errors[0]
        assert h.tr._ckpt._inflight is None
        assert h.tr._ckpt.versions() == []
    finally:
        h.gate.set()
        h.tr.close()


@pytest.mark.parametrize("saved", [False, True])
def test_resize_with_nothing_in_flight_reads_idle(tmp_path, saved):
    """No checkpoint directory, or a save whose write has finished: there
    is nothing to wait for and nothing deferred."""
    tr = _trainer(4, ckpt=str(tmp_path / "ckpt") if saved else None,
                  async_save=True)
    try:
        _steps(tr, BATCHES[:1])
        if saved:
            tr.save()
            assert tr._ckpt._inflight.wait(60)
        obs_trace.TRACER.clear()
        before = trainer_mod._DRAIN_DEFERRED.value
        assert tr.live_resize(2)["drain"] == "idle"
        assert _root_drain_tags() == ["idle"]
        assert trainer_mod._DRAIN_DEFERRED.value == before
        _steps(tr, BATCHES[1:2])
    finally:
        tr.close()


# -- the store protocol ----------------------------------------------------


def _cluster_key(coord):
    return (coord.service_prefix(constants.SERVICE_CLUSTER)
            + constants.CLUSTER_SERVER)


def test_intent_protocol_roundtrip(coord):
    coord.set_server_permanent(constants.SERVICE_LEADER,
                               constants.LEADER_SERVER, "gen_a")
    intent = live_mod.make_intent("i1", ["w1", "w2"],
                                  devices={"w1": 4, "w2": 4},
                                  leader="gen_a", cluster_json="{}")
    assert live_mod.publish_prepare(coord, "gen_a", intent)
    assert live_mod.read_intent(coord)["phase"] == live_mod.PREPARE
    # a deposed coordinator's writes are all no-ops
    assert not live_mod.publish_prepare(coord, "gen_b", intent)
    assert not live_mod.commit(coord, "gen_b", intent)
    assert not live_mod.abort(coord, "gen_b", intent)
    # acks are scoped by intent id: a stale ack from a previous resize
    # never satisfies this one
    live_mod.write_ack(coord, "w1", "i1", True, info={"world": 4})
    live_mod.write_ack(coord, "w2", "i0_stale", True)
    assert set(live_mod.read_acks(coord, "i1")) == {"w1"}
    live_mod.write_ack(coord, "w2", "i1", True)
    ok, acks = live_mod.wait_for_acks(coord, intent, timeout=5)
    assert ok and set(acks) == {"w1", "w2"}
    assert acks["w1"]["world"] == 4
    # commit flips the phase AND installs the cluster map in ONE txn
    assert live_mod.commit(coord, "gen_a", intent,
                           extra_puts=[(_cluster_key(coord), "MAP")])
    assert live_mod.read_intent(coord)["phase"] == live_mod.COMMIT
    assert coord.get_value(constants.SERVICE_CLUSTER,
                           constants.CLUSTER_SERVER) == "MAP"


def test_nack_wait_and_abort(coord):
    coord.set_server_permanent(constants.SERVICE_LEADER,
                               constants.LEADER_SERVER, "gen_a")
    intent = live_mod.make_intent("i2", ["w1", "w2"], leader="gen_a")
    assert live_mod.publish_prepare(coord, "gen_a", intent)
    live_mod.write_ack(coord, "w1", "i2", True)
    live_mod.write_ack(coord, "w2", "i2", False, reason="out of scope")
    ok, acks = live_mod.wait_for_acks(coord, intent, timeout=5)
    assert not ok and set(acks) == {"w1", "w2"}
    assert live_mod.abort(coord, "gen_a", intent, reason="nack w2")
    after = live_mod.read_intent(coord)
    assert after["phase"] == live_mod.ABORT
    assert after["abort_reason"] == "nack w2"
    # a missing ack times out to not-ok too
    intent3 = live_mod.make_intent("i3", ["w1", "ghost"], leader="gen_a")
    assert live_mod.publish_prepare(coord, "gen_a", intent3)
    live_mod.write_ack(coord, "w1", "i3", True)
    ok, acks = live_mod.wait_for_acks(coord, intent3, timeout=0.5)
    assert not ok and set(acks) == {"w1"}


def test_live_resize_watcher(coord):
    coord.set_server_permanent(constants.SERVICE_LEADER,
                               constants.LEADER_SERVER, "gen_a")
    # a pre-existing intent is picked up at construction, not just via
    # the watch
    i1 = live_mod.make_intent("w_i1", ["me"], devices=4, leader="gen_a")
    assert live_mod.publish_prepare(coord, "gen_a", i1)
    w = live_mod.LiveResizeWatcher(coord, "me")
    try:
        assert _wait(lambda: w.pending())["id"] == "w_i1"
        w.done("w_i1")
        assert w.pending() is None
        # a later intent arrives through the watch; one addressed to
        # someone else never surfaces; an expired one is dropped
        other = live_mod.make_intent("w_other", ["not_me"], leader="gen_a")
        assert live_mod.publish_prepare(coord, "gen_a", other)
        expired = live_mod.make_intent("w_exp", ["me"], leader="gen_a",
                                       deadline_s=-1.0)
        assert live_mod.publish_prepare(coord, "gen_a", expired)
        time.sleep(0.3)
        assert w.pending() is None
        i2 = live_mod.make_intent("w_i2", ["me"], devices=8,
                                  leader="gen_a")
        assert live_mod.publish_prepare(coord, "gen_a", i2)
        assert _wait(lambda: w.pending())["id"] == "w_i2"
        # handled ids never come back, even if the key is re-delivered
        w.done("w_i2")
        assert live_mod.publish_prepare(coord, "gen_a", i2)
        time.sleep(0.3)
        assert w.pending() is None
    finally:
        w.stop()


def test_capability_advertise_and_ready(coord):
    reg = live_mod.advertise_capability(coord, "w1",
                                        info={"devices": 8}, ttl=5)
    assert reg is not None
    try:
        assert _wait(lambda: "w1" in live_mod.ready_participants(coord))
    finally:
        reg.stop()
    _wait(lambda: "w1" not in live_mod.ready_participants(coord))


# -- the generator's two-phase commit --------------------------------------


def _pod():
    import os

    from edl_tpu.controller.env import JobEnv
    from edl_tpu.controller.pod import Pod
    os.environ["EDL_TPU_POD_IP"] = "127.0.0.1"
    args = type("A", (), dict(
        job_id="test_job", store_endpoints="x", nodes_range="1:4",
        nproc_per_node=1, pod_ip="127.0.0.1", checkpoint_path=None,
        log_dir=None, log_level=None))()
    return Pod.from_env(JobEnv(args))


def _cluster(pods):
    c = cluster_mod.Cluster()
    c.pods = list(pods)
    c.assign_ranks()
    return c


def _acker(coord, verdicts, stop):
    """Poll for a prepare intent and ack it like the survivors would."""
    while not stop.is_set():
        intent = live_mod.read_intent(coord)
        if intent and intent.get("phase") == live_mod.PREPARE:
            for who in intent["survivors"]:
                live_mod.write_ack(coord, who, intent["id"],
                                   verdicts.get(who, True),
                                   reason=None if verdicts.get(who, True)
                                   else "drill nack")
            return
        time.sleep(0.05)


def test_generator_live_commit_two_phase(coord):
    from edl_tpu.controller.cluster_generator import Generator
    pod_a, pod_b = _pod(), _pod()
    coord.set_server_permanent(constants.SERVICE_LEADER,
                               constants.LEADER_SERVER, pod_a.id)
    gen = Generator(coord, pod_a.id, min_nodes=1, max_nodes=2,
                    live_ack_timeout=5.0)
    new = _cluster([pod_a])  # shrink: pod_b leaves, pod_a survives
    stop = threading.Event()
    t = threading.Thread(target=_acker, args=(coord, {}, stop),
                         daemon=True)
    t.start()
    try:
        assert gen._try_live_commit(new, _cluster_key(coord))
    finally:
        stop.set()
        t.join(timeout=5)
    intent = live_mod.read_intent(coord)
    assert intent["phase"] == live_mod.COMMIT
    assert intent["survivors"] == [pod_a.id]
    assert intent["devices"][pod_a.id] >= 1
    # the cluster map landed in the SAME transaction
    installed = cluster_mod.load_from_store(coord)
    assert installed is not None
    assert installed.pod_ids() == [pod_a.id]


def test_generator_live_nack_aborts_to_stop_resume(coord):
    from edl_tpu.controller.cluster_generator import Generator
    pod_a = _pod()
    coord.set_server_permanent(constants.SERVICE_LEADER,
                               constants.LEADER_SERVER, pod_a.id)
    gen = Generator(coord, pod_a.id, min_nodes=1, max_nodes=2,
                    live_ack_timeout=5.0)
    new = _cluster([pod_a])
    stop = threading.Event()
    t = threading.Thread(target=_acker,
                         args=(coord, {pod_a.id: False}, stop),
                         daemon=True)
    t.start()
    try:
        assert gen._try_live_commit(new, _cluster_key(coord)) is False
    finally:
        stop.set()
        t.join(timeout=5)
    intent = live_mod.read_intent(coord)
    assert intent["phase"] == live_mod.ABORT
    assert pod_a.id in intent["abort_reason"]
    # no map installed: the caller falls through to stop-resume commit
    assert cluster_mod.load_from_store(coord) is None


def test_generator_aborts_stale_foreign_intent(coord):
    """Leader loss mid-reshard: the old coordinator published prepare
    and died; the NEW leader's first generation pass aborts the orphan
    so survivors stop draining and stop-resume runs."""
    from edl_tpu.controller.cluster_generator import Generator
    coord.set_server_permanent(constants.SERVICE_LEADER,
                               constants.LEADER_SERVER, "dead_gen")
    orphan = live_mod.make_intent("orphan", ["w1"], leader="dead_gen")
    assert live_mod.publish_prepare(coord, "dead_gen", orphan)
    # leadership moves
    coord.set_server_permanent(constants.SERVICE_LEADER,
                               constants.LEADER_SERVER, "gen_b")
    Generator(coord, "gen_b", min_nodes=1,
              max_nodes=2)._abort_stale_intent()
    after = live_mod.read_intent(coord)
    assert after["phase"] == live_mod.ABORT
    assert "dead_gen" in after["abort_reason"]
    # its own fresh prepare is NOT stale — a second pass leaves it alone
    own = live_mod.make_intent("own", ["w1"], leader="gen_b")
    assert live_mod.publish_prepare(coord, "gen_b", own)
    Generator(coord, "gen_b", min_nodes=1,
              max_nodes=2)._abort_stale_intent()
    assert live_mod.read_intent(coord)["phase"] == live_mod.PREPARE


def test_generator_live_eligibility(coord):
    from edl_tpu.controller.cluster_generator import Generator
    pod_a, pod_b, pod_c = _pod(), _pod(), _pod()
    gen = Generator(coord, pod_a.id, min_nodes=1, max_nodes=3)
    current = _cluster([pod_a, pod_b])
    shrink = _cluster([pod_a])
    grow = _cluster([pod_a, pod_b, pod_c])
    # no current cluster yet → cold start is stop-resume
    assert not gen._live_eligible(None, shrink)
    # a joining pod has no process to reshape
    assert not gen._live_eligible(current, grow)
    # survivors-only, but nobody advertises the capability
    assert not gen._live_eligible(current, shrink)
    regs = [live_mod.advertise_capability(coord, p.id)
            for p in (pod_a, pod_b)]
    try:
        assert _wait(lambda: gen._live_eligible(current, shrink))
        assert gen._live_eligible(current, current)
    finally:
        for r in regs:
            r.stop()


# -- the launcher's adoption gate ------------------------------------------


def test_launcher_live_intent_gating(coord):
    from edl_tpu.controller.launcher import Launcher
    pod = _pod()
    launcher = Launcher.__new__(Launcher)
    launcher._coord = coord
    launcher._pod = pod
    launcher._live_done = set()
    coord.set_server_permanent(constants.SERVICE_LEADER,
                               constants.LEADER_SERVER, "gen_a")
    assert launcher._live_intent_for_pod() is None  # no intent at all
    intent = live_mod.make_intent("L1", [pod.id], devices={pod.id: 4},
                                  leader="gen_a")
    assert live_mod.publish_prepare(coord, "gen_a", intent)
    assert launcher._live_intent_for_pod() is None  # prepare ≠ commit
    assert live_mod.commit(coord, "gen_a", intent)
    assert launcher._live_intent_for_pod() is None  # no ok ack yet
    live_mod.write_ack(coord, pod.id, "L1", False, reason="drill")
    assert launcher._live_intent_for_pod() is None  # nack ≠ ok
    live_mod.write_ack(coord, pod.id, "L1", True)
    got = launcher._live_intent_for_pod()
    assert got is not None and got["id"] == "L1"
    launcher._live_done.add("L1")
    assert launcher._live_intent_for_pod() is None  # consumed once
    # an intent that excludes this pod is never adopted
    foreign = live_mod.make_intent("L2", ["someone_else"], leader="gen_a")
    assert live_mod.publish_prepare(coord, "gen_a", foreign)
    assert live_mod.commit(coord, "gen_a", foreign)
    assert launcher._live_intent_for_pod() is None


# -- liveft: the transition classifier -------------------------------------


def test_classify_transition():
    from edl_tpu.liveft import elastic as el
    assert el.classify_transition(["a", "b"], ["a"], "a") == el.SHRINK
    assert el.classify_transition(["a"], ["a", "b"], "a") == el.GROW
    # mixed join+leave is conservatively a SHRINK for survivors
    assert el.classify_transition(["a", "b"], ["b", "c"], "b") == el.SHRINK
    assert el.classify_transition(["a", "b"], ["b", "c"],
                                  "a") == el.SELF_EVICTED
    assert el.classify_transition(["a"], ["a"], "a") == el.UNCHANGED
    assert el.classify_transition(None, ["a"], "b") == el.SELF_EVICTED


def _manager(coord, host, np_target, seen):
    from edl_tpu.liveft import elastic as el
    m = el.ElasticManager(
        coord, host, np_target,
        on_transition=lambda k, old, new: seen.append((k, old, new)))
    m._registered.set()  # no threads: drive watch() by hand
    return m


def _register_hosts(coord, hosts):
    from edl_tpu.liveft import elastic as el
    for h in hosts:
        coord.set_server_permanent(el.SERVICE_NODES, h, "1")


def test_elastic_manager_shrink_transition(coord):
    from edl_tpu.liveft import elastic as el
    seen = []
    m = _manager(coord, "h1", 2, seen)
    m._agreed_hosts = ["h1", "h2", "h3"]
    _register_hosts(coord, ["h1", "h2"])
    m._hosts_changed.set()
    assert m.watch(poll=0.01) == el.RESTART
    assert seen == [(el.SHRINK, ["h1", "h2", "h3"], ["h1", "h2"])]


def test_elastic_manager_grow_transition(coord):
    from edl_tpu.liveft import elastic as el
    seen = []
    m = _manager(coord, "h1", 2, seen)
    m._agreed_hosts = ["h1"]
    _register_hosts(coord, ["h1", "h2"])
    m._np = 2
    m._np_changed.set()
    assert m.watch(poll=0.01) == el.RESTART
    assert seen == [(el.GROW, ["h1"], ["h1", "h2"])]


def test_elastic_manager_self_eviction_is_error(coord):
    """The world settled at np WITHOUT us: ERROR, not the old
    HOLD-forever."""
    from edl_tpu.liveft import elastic as el
    seen = []
    m = _manager(coord, "h1", 2, seen)
    m._agreed_hosts = ["h1", "h2"]
    _register_hosts(coord, ["h2", "h3"])
    m._hosts_changed.set()
    assert m.watch(poll=0.01) == el.ERROR
    assert seen == [(el.SELF_EVICTED, ["h1", "h2"], ["h2", "h3"])]


def test_elastic_manager_flap_is_not_a_restart(coord):
    """A watch event that settles back to the agreed membership (lease
    blip, store failover) must neither RESTART nor notify."""
    from edl_tpu.liveft import elastic as el
    seen = []
    m = _manager(coord, "h1", 2, seen)
    m._agreed_hosts = ["h1", "h2"]
    _register_hosts(coord, ["h1", "h2"])
    m._hosts_changed.set()
    assert m.watch(poll=0.01) == el.HOLD
    assert not m._hosts_changed.is_set()  # the flap was consumed
    assert seen == []


# -- the doctor's live-resize detectors ------------------------------------


def test_job_doctor_live_resize_findings():
    from edl_tpu.tools import job_doctor
    events = [
        {"id": 1, "ts": 100.0, "kind": "resize.live.start", "cause": None,
         "attrs": {"from_devices": 8, "to_devices": 4}},
        {"id": 2, "ts": 101.0, "kind": "resize.live.fallback", "cause": 1,
         "attrs": {"reason": "RpcError: fault injected",
                   "from_devices": 8, "to_devices": 4}},
    ]
    obs_doc = {
        "schema": "obs_pub/v1", "events": events,
        "metrics": {"metrics": {"edl_resize_prewarm_misses_total": {
            "series": [{"value": 3.0}]}}},
    }
    report = job_doctor.diagnose({"job_id": "j", "job_status": None,
                                  "health": None,
                                  "obs": {"pod-00": obs_doc}})
    assert report["verdict"] == "unknown"
    assert [f["detector"] for f in report["findings"]] == [
        "live_resize_fallback", "prewarm_miss"]
    fall = report["findings"][0]
    assert fall["pod"] == "pod-00"
    assert "RpcError" in fall["summary"]
    # the chain links the fallback to its start event via the cause id
    assert any("resize.live.start" in step for step in fall["chain"])
    assert any("resize.live.fallback" in step for step in fall["chain"])
    miss = report["findings"][1]
    assert miss["metric"] == "edl_resize_prewarm_misses_total"
    assert "JAX_COMPILATION_CACHE_DIR" in miss["summary"]
    assert "doctor-local" in report["summary"]
    json.dumps(report)
    job_doctor.render(report)  # the human surface renders the chains


@pytest.mark.parametrize("counters,found", [
    ({"edl_resize_prewarm_misses_total": 2.0}, True),
    ({"edl_resize_prewarm_misses_total": 2.0,
      "edl_resize_prewarm_hits_total": 1.0}, False),
    # a step taken from the process's own table paid no compile either
    ({"edl_resize_prewarm_misses_total": 2.0,
      "edl_resize_step_reuses_total": 3.0}, False),
    ({"edl_resize_step_reuses_total": 3.0}, False),
])
def test_job_doctor_prewarm_miss_counts_memory_reuse_as_hit(counters,
                                                            found):
    from edl_tpu.tools import job_doctor
    obs_doc = {
        "schema": "obs_pub/v1", "events": [],
        "metrics": {"metrics": {name: {"series": [{"value": v}]}
                                for name, v in counters.items()}},
    }
    report = job_doctor.diagnose({"job_id": "j", "job_status": None,
                                  "health": None,
                                  "obs": {"pod-00": obs_doc}})
    assert [f["detector"] for f in report["findings"]] == (
        ["prewarm_miss"] if found else [])


# -- cross-mesh (model-parallel) transitions -------------------------------


def _tp_trainer(n_devices, mesh_shape=None, ckpt=None, feature_dim=16):
    """A trainer whose w is tp-sharded by rule (replicated while tp=1),
    so the SAME param table rides every factorization of the world."""
    from jax.sharding import PartitionSpec as P
    kw = dict(mesh_shape or {})
    return ElasticTrainer(
        linear.loss_fn, linear.init_params(feature_dim), optax.sgd(0.05),
        total_batch_size=TOTAL_BATCH,
        mesh=make_mesh(devices=jax.devices()[:n_devices], **kw),
        param_shardings=[(r"^w$", P("tp"))],
        checkpoint_dir=ckpt)


def _tp_batches(n=6, feature_dim=16):
    return [linear.synthetic_batch(TOTAL_BATCH, feature_dim=feature_dim,
                                   seed=i) for i in range(n)]


def test_live_resize_dp_to_dp_tp_byte_identical(tmp_path):
    """The tentpole arc at trainer level: a pure-dp world live-reshards
    onto a dp x tp factorization of the SAME device count (the intent's
    mesh_shape), byte-identical to a stop-resume over the same mesh
    sequence, and the record carries both factorizations."""
    batches = _tp_batches()
    live = _tp_trainer(4)
    _steps(live, batches[:2])
    rec = live.live_resize(4, mesh_shape={"dp": 2, "tp": 2})
    assert rec["mode"] == "live"
    assert rec["from_mesh"]["dp"] == 4 and rec["from_mesh"]["tp"] == 1
    assert _world(live) == 4
    assert live.mesh.shape["dp"] == 2 and live.mesh.shape["tp"] == 2
    # w really is tp-sharded on the new mesh
    assert live.train_state["params"]["w"].sharding.spec[0] == "tp"
    _steps(live, batches[2:4])

    ckpt = str(tmp_path / "ckpt")
    a = _tp_trainer(4, ckpt=ckpt)
    _steps(a, batches[:2])
    a.save()
    b = _tp_trainer(4, mesh_shape={"dp": 2, "tp": 2}, ckpt=ckpt)
    assert b.resume()
    _steps(b, batches[2:4])
    assert _state_bytes(live) == _state_bytes(b)

    # and back down to pure dp in the same process
    rec_back = live.live_resize(4, mesh_shape={"dp": 4})
    assert rec_back["mode"] == "live"
    assert live.mesh.shape["tp"] == 1
    _steps(live, batches[4:5])


def test_live_resize_tp_change_with_world_shrink():
    """World 4 -> 2 while keeping tp=2: dp absorbs the change (the
    default when no mesh_shape rides the intent), single process."""
    batches = _tp_batches()
    tr = _tp_trainer(4, mesh_shape={"dp": 2, "tp": 2})
    _steps(tr, batches[:2])
    tr.live_resize(2)  # no mesh_shape: model axes carry over
    assert _world(tr) == 2
    assert tr.mesh.shape["tp"] == 2 and tr.mesh.shape["dp"] == 1
    _steps(tr, batches[2:3])
    tr.live_resize(4, mesh_shape={"dp": 2, "tp": 2})
    assert tr.mesh.shape["dp"] == 2
    _steps(tr, batches[3:4])


def test_live_resize_uncomputable_spans_fallback_names_reason():
    """A target factorization whose spans are NOT computable (w dim 14
    divides tp=2 but not tp=4) must be rejected up front: state
    untouched, LiveResizeError raised, and the fallback event carrying
    scope=True + the exact per-leaf reason — the contract the doctor's
    reshard_fallback detector reads."""
    batches = _tp_batches(feature_dim=14)
    tr = _tp_trainer(4, mesh_shape={"dp": 2, "tp": 2}, feature_dim=14)
    _steps(tr, batches[:1])
    before = _state_bytes(tr)
    mark = obs_events.emit("test.reshard_scope.mark")
    with pytest.raises(LiveResizeError, match="uncomputable target"):
        tr.live_resize(4, mesh_shape={"dp": 1, "tp": 4})
    assert _world(tr) == 4
    assert tr.mesh.shape["tp"] == 2          # untouched factorization
    assert _state_bytes(tr) == before
    falls = [e for e in obs_events.EVENTS.snapshot(since_id=mark)
             if e["kind"] == "resize.live.fallback"]
    assert falls and falls[-1]["attrs"]["scope"] is True
    reason = falls[-1]["attrs"]["reason"]
    assert "uncomputable target spans" in reason
    assert "not divisible" in reason
    _steps(tr, batches[1:2])  # still training on the old mesh


# -- the reshard from inside: kept shards in place, one batched copy --------


#: (devices, model axes) of the world left and of the world taken, and
#: what the plan has to say of the tree of `_reshard_case`: arrays handed
#: to the runtime to cross, leaves that went through jax.device_put whole
RESHARDS = {
    # replicated leaves stay where they are; `rows` and `rows2` held
    # quarters and take halves: another index, re-sliced
    "4_to_2": ((4, {}), (2, {}), 0, 2),
    # a and a2 land as ONE stack on each of the two chips gained; b, c
    # and cols (alone in their moves) whole
    "2_to_4": ((2, {}), (4, {}), 8, 2),
    "1_to_4": ((1, {}), (4, {}), 12, 2),
    # dp stays 2: the stack of rows and rows2 keeps its halves (one in
    # place, three cross); a + a2, b and c cross twice each; `cols` is
    # cut along tp: re-sliced
    "dp_to_dp_tp": ((2, {}), (4, {"tp": 2}), 9, 1),
}
#: leaves that share their move with another: stacked where they cross
TWINS = {"a": "a2", "rows": "rows2"}


def _reshard_case(left, taken):
    """A tree with replicated leaves (two of them of one shape), two
    whose rows are split over dp and one whose columns are split over
    tp, placed on the world `left`, and the shardings of the same specs
    on `taken`."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    specs = {"a": P(), "a2": P(), "b": P(), "c": P(), "rows": P("dp"),
             "rows2": P("dp"), "cols": P(None, "tp")}
    rng = np.random.default_rng(54)
    host = {k: rng.standard_normal((8, 6)).astype(np.float32)
            for k in ("a", "a2", "rows", "rows2", "cols")}
    host.update(b=rng.standard_normal((5,)).astype(np.float32),
                c=np.int32(7))

    def on(world):
        n, axes = world
        mesh = make_mesh(devices=jax.devices()[:n], **axes)
        return {k: NamedSharding(mesh, spec) for k, spec in specs.items()}

    return jax.device_put(host, on(left)), on(taken)


def _shard_pointers(tree):
    return {(i, s.device): s.data.unsafe_buffer_pointer()
            for i, x in enumerate(jax.tree_util.tree_leaves(tree))
            for s in x.addressable_shards}


@pytest.fixture(scope="module")
def resharder():
    tr = _trainer(1)
    yield tr
    tr.close()


@pytest.mark.parametrize("case", list(RESHARDS))
def test_reshard_equals_device_put_bit_for_bit(resharder, case):
    left, taken, _, _ = RESHARDS[case]
    tree, shardings = _reshard_case(left, taken)
    want = jax.device_put(tree, shardings)
    got, _ = resharder._reshard_tree(tree, shardings)
    assert set(got) == set(want)
    for k in want:
        assert got[k].sharding == want[k].sharding, k
        assert got[k].dtype == want[k].dtype and got[k].shape == want[k].shape
        assert np.asarray(got[k]).tobytes() == np.asarray(want[k]).tobytes()
        ours = {s.device: np.asarray(s.data).tobytes()
                for s in got[k].addressable_shards}
        assert ours == {s.device: np.asarray(s.data).tobytes()
                        for s in want[k].addressable_shards}, k


@pytest.mark.parametrize("case", list(RESHARDS))
def test_reshard_counts_what_crossed_and_what_was_resliced(
        resharder, monkeypatch, case):
    left, taken, crossed, leafwise = RESHARDS[case]
    tree, shardings = _reshard_case(left, taken)
    before = _shard_pointers(tree)
    calls = []
    put = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda *a, **kw: calls.append(a) or put(*a, **kw))
    got, stats = resharder._reshard_tree(tree, shardings, account=True)
    monkeypatch.undo()
    assert stats["arrays_crossed"] == crossed
    assert stats["leaves_leafwise"] == leafwise
    assert stats["leaves"] == 7 and stats["source"] == "local"
    # one call for all that crosses, one for all that is re-sliced
    assert len(calls) == (crossed > 0) + (leafwise > 0)
    # every shard a device held under the index it keeps is the old
    # leaf's own buffer, but where the leaf crossed in a stack: then
    # every device holds a cut of the stack that arrived
    resliced = {"cols"} if taken[1] else {"rows", "rows2"}
    stacked = {k for pair in TWINS.items() for k in pair} if crossed else set()
    kept = 0
    for k in sorted(set(tree) - resliced):
        held = tree[k].sharding.devices_indices_map(tree[k].shape)
        takes = shardings[k].devices_indices_map(tree[k].shape)
        ours = {s.device: s.data.unsafe_buffer_pointer()
                for s in tree[k].addressable_shards}
        for s in got[k].addressable_shards:
            if held.get(s.device) == takes[s.device]:
                same = s.data.unsafe_buffer_pointer() == ours[s.device]
                assert same == (k not in stacked), k
                kept += same
    assert kept > 0
    # the plan only read the old tree
    assert _shard_pointers(tree) == before


def test_a_move_made_before_builds_no_program(resharder):
    """The two programs that stack and cut a move's leaves are kept by
    what they were built for: the same move again finds them."""
    tree, shardings = _reshard_case((2, {}), (4, {}))
    resharder._reshard_tree(tree, shardings)
    held = dict(resharder._reshard_programs)
    assert held
    [key] = [k for k in held if [g[4] for g in k] == [2]
             and k[0][2] == tree["a"].sharding]
    again, _ = _reshard_case((2, {}), (4, {}))
    resharder._reshard_tree(again, shardings)
    assert resharder._reshard_programs == held
    pack, cut = held[key]
    assert pack._cache_size() == 1 and cut._cache_size() == 1


def test_a_large_leaf_crosses_on_its_own(resharder, monkeypatch):
    """Stacking holds a second copy of what it stacks: leaves above the
    size where that is a bargain cross shard by shard."""
    tree, shardings = _reshard_case((2, {}), (4, {}))
    monkeypatch.setattr(trainer_mod, "_STACK_LEAF_BYTES", 8 * 6 * 4 - 1)
    got, stats = resharder._reshard_tree(tree, shardings)
    assert stats["arrays_crossed"] == 10       # a and a2 apart: 2 more
    assert np.array_equal(np.asarray(got["a2"]), np.asarray(tree["a2"]))


def test_a_shrink_moves_nothing_and_keeps_every_shard():
    """4 -> 2 of replicated state: each chip that stays holds the very
    buffers it held; nothing is handed to the runtime."""
    tr = _trainer(4)
    try:
        _steps(tr, BATCHES[:1])
        before = _shard_pointers(tr.train_state)
        obs_trace.TRACER.clear()
        tr.live_resize(2)
        after = _shard_pointers(tr.train_state)
        assert len(after) == len(before) // 2
        assert all(before[at] == ptr for at, ptr in after.items())
        _steps(tr, BATCHES[1:2])
        [put] = obs_trace.TRACER.find(name="resize.device_put")
        assert put["tags"]["arrays_crossed"] == 0
        assert put["tags"]["leaves_leafwise"] == 0
        assert put["tags"]["bytes_moved"] == 0
    finally:
        tr.close()


def test_a_grow_crosses_in_one_batched_call(monkeypatch):
    """2 -> 4 of three replicated leaves: six arrays cross, all in one
    `jax.device_put`; the chips that stay keep their buffers."""
    tr = _trainer(2)
    try:
        _steps(tr, BATCHES[:1])
        before = _shard_pointers(tr.train_state)
        obs_trace.TRACER.clear()
        calls = []
        put = jax.device_put
        monkeypatch.setattr(
            jax, "device_put",
            lambda *a, **kw: calls.append(a) or put(*a, **kw))
        tr.live_resize(4)
        monkeypatch.undo()
        assert 1 <= len(calls) <= 2
        assert [len(a[0]) for a in calls if isinstance(a[0], list)] == [6]
        after = _shard_pointers(tr.train_state)
        assert len(after) == 2 * len(before)
        assert all(after[at] == ptr for at, ptr in before.items())
        _steps(tr, BATCHES[1:2])
        [put_span] = obs_trace.TRACER.find(name="resize.device_put")
        tags = put_span["tags"]
        assert tags["leaves"] == 3
        assert tags["arrays_crossed"] == 6
        assert tags["leaves_leafwise"] == 0
        assert tags["bytes_moved"] == 2 * tags["bytes"]
    finally:
        tr.close()


def test_a_subtree_s_one_sharding_stands_for_its_leaves(resharder):
    """`shardings` may be a prefix of the tree, as `jax.device_put`
    takes it: one sharding for a whole subtree."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    tree, _ = _reshard_case((2, {}), (4, {}))
    repl = NamedSharding(make_mesh(devices=jax.devices()[:4]), P())
    nested = {"params": {k: tree[k] for k in "abc"}, "step": tree["cols"]}
    got, stats = resharder._reshard_tree(
        nested, {"params": repl, "step": repl}, account=True)
    assert stats["arrays_crossed"] == 8 and stats["leaves_leafwise"] == 0
    assert len(stats["placements"][2]) == 4
    for x in jax.tree_util.tree_leaves(got):
        assert x.sharding == repl


def test_job_doctor_reshard_fallback_finding():
    """scope=True fallbacks get their own detector, ranked apart from
    mid-flight rollbacks, with the _live_scope_check reason verbatim in
    the summary."""
    from edl_tpu.tools import job_doctor
    reason = ("uncomputable target spans: params/w: dim 0 of shape "
              "(14,) not divisible by target tp=4 for spec "
              "PartitionSpec('tp',)")
    events = [
        {"id": 1, "ts": 100.0, "kind": "resize.live.start", "cause": None,
         "attrs": {"from_devices": 4, "to_devices": 4}},
        {"id": 2, "ts": 101.0, "kind": "resize.live.fallback", "cause": 1,
         "attrs": {"reason": reason, "scope": True,
                   "from_devices": 4, "to_devices": 4}},
    ]
    obs_doc = {"schema": "obs_pub/v1", "events": events, "metrics": {}}
    report = job_doctor.diagnose({"job_id": "j", "job_status": None,
                                  "health": None,
                                  "obs": {"pod-00": obs_doc}})
    assert [f["detector"] for f in report["findings"]] == [
        "reshard_fallback"]
    f = report["findings"][0]
    assert f["pod"] == "pod-00"
    assert "uncomputable target spans" in f["summary"]
    assert "not divisible" in f["summary"]   # the EXACT reason, verbatim
    assert any("resize.live.start" in step for step in f["chain"])
    json.dumps(report)
    job_doctor.render(report)
