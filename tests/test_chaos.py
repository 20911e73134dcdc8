"""Chaos suite: deterministic fault injection (edl_tpu.robustness.faults)
driven through the real control plane, plus unit coverage for the unified
retry / deadline / circuit-breaker policy layer.

Every scenario arms a seeded FaultPlane, runs a real multi-actor drill
(liveft rendezvous, barrier, store failover, distill reads) and asserts
BOTH that the faults actually fired (``Fault.fired`` counters / the
plane's log) and that the system converged within its deadline — a chaos
test that cannot prove its faults fired is indistinguishable from a
green run with the chaos plane disabled.

Store-level fault points only exist in the Python store, so these tests
build their own EmbeddedStore rather than using the parametrized
``coord`` fixture (the native C++ backend has no hooks).
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from edl_tpu.controller import constants
from edl_tpu.controller.barrier import PodServer, barrier_wait
from edl_tpu.controller.cluster_generator import Generator
from edl_tpu.controller.env import JobEnv
from edl_tpu.controller.pod import Pod
from edl_tpu.controller.resource_pods import ResourceRegister
from edl_tpu.coordination.client import CoordClient
from edl_tpu.coordination.embedded import EmbeddedStore
from edl_tpu.coordination.server import StoreServer
from edl_tpu.coordination.standby import StandbyServer, WitnessServer
from edl_tpu.distill.distill_reader import DistillReader
from edl_tpu.distill.teacher_server import TeacherServer
from edl_tpu.liveft.elastic import ElasticManager
from edl_tpu.robustness import faults, policy
from edl_tpu.robustness.faults import (FaultPlane, FaultSpecError,
                                       plane_from_spec)
from edl_tpu.robustness.policy import CircuitBreaker, Deadline, RetryPolicy
from edl_tpu.utils import errors

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SEED = 20240805


@pytest.fixture()
def plane():
    """A fresh installed FaultPlane; ALWAYS uninstalled on teardown (the
    plane is process-global — leaking one would chaos every later test)."""
    p = FaultPlane(seed=SEED).install()
    yield p
    p.uninstall()
    assert faults.PLANE is None


def _wait(pred, timeout=15.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(interval)
    return False


def _pod():
    os.environ["EDL_TPU_POD_IP"] = "127.0.0.1"
    args = type("A", (), dict(
        job_id="chaos_job", store_endpoints="x", nodes_range="1:4",
        nproc_per_node=1, pod_ip="127.0.0.1", checkpoint_path=None,
        log_dir=None, log_level=None))()
    return Pod.from_env(JobEnv(args))


# ---------------------------------------------------------------------------
# the plane itself: gate, determinism, spec grammar, env activation
# ---------------------------------------------------------------------------


def test_plane_disabled_by_default():
    assert faults.PLANE is None


def test_same_seed_same_schedule():
    """The determinism contract: equal seeds driven through equal match
    sequences produce equal fault schedules, regardless of how many
    other faults are armed."""
    def drive(seed, extra_fault=False):
        p = FaultPlane(seed=seed)
        p.inject("x.point", "drop", prob=0.3)
        if extra_fault:
            # a second fault at the same point must not perturb the
            # first one's stream (per-fault RNG, not a shared plane RNG)
            p.inject("x.point", "delay", seconds=0.0, prob=0.5)
        for i in range(200):
            p.fire("x.point", idx=i)
        drop = p._faults["x.point"][0]
        return [e for e in p.log if e == ("x.point", "drop")], drop.fired

    assert drive(7) == drive(7)
    assert drive(7) == drive(7, extra_fault=True)
    assert drive(7) != drive(8)


def test_fault_filters_and_scheduling():
    p = FaultPlane(seed=1)
    f = p.inject("pt", "drop", method="barrier", after=2, times=2)
    for _ in range(3):
        assert p.fire("pt", method="store_put") is None  # filtered out
    hits = [p.fire("pt", method="barrier") for _ in range(6)]
    # after=2 skips the first two matches; times=2 caps firings
    assert [h is not None for h in hits] == [False, False, True, True,
                                             False, False]
    assert f.fired == 2 and f.matched == 6


def test_error_kind_raises_typed_errors():
    p = FaultPlane()
    p.inject("pt", "error_once", error="LeaseExpiredError")
    with pytest.raises(errors.LeaseExpiredError):
        p.fire("pt")
    assert p.fire("pt") is None  # error_once defaults to times=1


def test_fault_spec_grammar():
    p = plane_from_spec("seed=7;rpc.server.request:drop(method=barrier,"
                        "times=2);store.lease.refresh:delay(seconds=0.01)")
    assert p.seed == 7
    f = p._faults["rpc.server.request"][0]
    assert f.kind == "drop" and f.times == 2
    assert f.filters == {"method": "barrier"}
    d = p._faults["store.lease.refresh"][0]
    assert d.params["seconds"] == 0.01
    assert faults.PLANE is None  # parsing must not install


@pytest.mark.parametrize("bad", ["", "   ", "nonsense", "p:frobnicate",
                                 "p:drop(x", "p:drop(times)"])
def test_fault_spec_malformed_fails_loudly(bad):
    with pytest.raises(FaultSpecError):
        plane_from_spec(bad)


def test_env_spec_activates_plane_in_subprocess():
    """EDL_TPU_FAULT_SPEC places a whole process under chaos at import —
    the mechanism integration tests use on their worker subprocesses."""
    code = ("from edl_tpu.robustness import faults; "
            "assert faults.PLANE is not None; "
            "f = faults.PLANE._faults['rpc.frame.write'][0]; "
            "assert f.kind == 'drop' and f.times == 1; "
            "print(faults.PLANE.seed)")
    env = dict(os.environ, PYTHONPATH=REPO,
               EDL_TPU_FAULT_SPEC="seed=9;rpc.frame.write:drop(times=1)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    assert out.stdout.strip() == "9"


# ---------------------------------------------------------------------------
# policy layer units
# ---------------------------------------------------------------------------


def test_deadline_budget_cap_union():
    d = Deadline(10.0)
    assert 0 < d.remaining() <= 10.0
    assert d.remaining(cap=0.5) == 0.5
    assert not d.expired()
    tight = Deadline(0.0)
    assert tight.expired()
    with pytest.raises(errors.DeadlineExceededError):
        tight.check("op")
    assert not tight.sleep(1.0)  # no budget: no sleep, returns False
    assert d.union(tight) is tight  # budget intersection = the earlier
    assert policy.FOREVER.remaining() is None
    assert policy.FOREVER.remaining(cap=3.0) == 3.0
    assert policy.FOREVER.union(d) is d
    # DeadlineExceededError stays catchable as the pre-existing timeout
    assert issubclass(errors.DeadlineExceededError, errors.TimeoutError_)


def test_retry_policy_jitter_is_seeded_and_capped():
    mk = lambda: RetryPolicy(base_delay=0.1, max_delay=5.0,  # noqa: E731
                             multiplier=2.0, jitter=0.5, seed=3)
    a = [mk().delay(i) for i in range(1, 10)]
    b = [mk().delay(i) for i in range(1, 10)]
    assert a == b  # same seed, same jitter stream
    assert all(d <= 5.0 * 1.5 for d in a)  # max_delay * (1 + jitter)
    assert all(d >= 0.1 * 0.5 for d in a)  # base_delay * (1 - jitter)


def test_retry_call_retries_then_succeeds():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise errors.ConnectError("boom")
        return "ok"

    p = RetryPolicy(base_delay=0.01, max_delay=0.02, seed=1)
    assert p.call(flaky, deadline=Deadline(10.0)) == "ok"
    assert len(calls) == 3


def test_retry_call_deadline_exhaustion_raises_deadline_error():
    p = RetryPolicy(base_delay=0.05, max_delay=0.05, seed=1)

    def always():
        raise errors.ConnectError("down")

    t0 = time.monotonic()
    with pytest.raises(errors.DeadlineExceededError) as ei:
        p.call(always, deadline=Deadline(0.3))
    assert time.monotonic() - t0 < 5.0  # the budget bounded the loop
    assert isinstance(ei.value.__cause__, errors.ConnectError)


def test_retry_call_max_attempts_and_give_up():
    n = [0]

    def always():
        n[0] += 1
        raise errors.ConnectError("x")

    p = RetryPolicy(max_attempts=3, base_delay=0.01, seed=1)
    with pytest.raises(errors.ConnectError):
        p.call(always)
    assert n[0] == 3

    def stopper():
        raise errors.StopError("halt")

    with pytest.raises(errors.StopError):
        p.call(stopper)  # give_up_on short-circuits, no retries


def test_circuit_breaker_state_machine():
    t = [0.0]
    cb = CircuitBreaker(failure_threshold=2, reset_timeout=5.0,
                        half_open_max=1, clock=lambda: t[0])
    assert cb.allow("ep") and cb.state("ep") == cb.CLOSED
    cb.record_failure("ep")
    assert cb.allow("ep")  # one failure below threshold: still closed
    cb.record_failure("ep")
    assert cb.state("ep") == cb.OPEN and not cb.allow("ep")
    t[0] += 5.1  # reset window elapses -> half-open
    assert cb.allow("ep") and cb.state("ep") == cb.HALF_OPEN
    assert not cb.allow("ep")  # half_open_max=1: second probe denied
    cb.record_failure("ep")  # probe failed -> re-open, clock restarts
    assert cb.state("ep") == cb.OPEN and not cb.allow("ep")
    t[0] += 5.1
    assert cb.allow("ep")
    cb.record_success("ep")  # probe succeeded -> closed
    assert cb.state("ep") == cb.CLOSED and cb.allow("ep")


def test_circuit_breaker_prune_bounds_state():
    cb = CircuitBreaker()
    for i in range(100):
        cb.record_failure("ghost-%d" % i)
    cb.prune(["live-1", "ghost-7"])
    assert set(cb.keys()) == {"ghost-7"}  # live-1 never had state


def test_distill_breaker_state_is_pruned_to_live_teachers():
    """Regression for the unbounded ``_recent_failures`` map the breaker
    replaced: teacher endpoint churn must not grow reader state."""
    dr = DistillReader(ins=["img"], predicts=["p"], teacher_backoff=60)
    live = ["127.0.0.1:7001", "127.0.0.1:7002"]
    dr.set_fixed_teacher(live)
    for i in range(50):
        dr._breaker.record_failure("10.9.9.%d:1" % i)  # churned-away eps
    for ep in live:
        dr._breaker.record_failure(ep)  # open: _sync_workers won't dial
    dr._sync_workers()
    assert set(dr._breaker.keys()) == set(live)
    assert dr._workers == {}  # open circuits gated the dials
    dr.stop()


# ---------------------------------------------------------------------------
# scenario A: lease expiry during a liveft rendezvous
# ---------------------------------------------------------------------------


def test_chaos_liveft_lease_expiry_mid_wait(plane):
    """One manager's lease refreshes are dropped and its re-registration
    attempts error; its lease genuinely expires mid-run (the expiry
    sweep fires), membership visibly shrinks, and both managers still
    converge back to full strength within their deadlines."""
    with EmbeddedStore() as s:
        coord_a = s.client(root="chaos_liveft")
        coord_b = s.client(root="chaos_liveft")
        m1 = ElasticManager(coord_a, "h1:8470", 2, ttl=1.5).start()
        m2 = ElasticManager(coord_b, "h2:8470", 2, ttl=1.5).start()
        try:
            both = ["h1:8470", "h2:8470"]
            assert m1.wait(timeout=30) == both
            assert m2.wait(timeout=30) == both

            # pick the victim whose lease id can't substring-match the
            # survivor's (filters are substring matches)
            l1, l2 = str(m1._lease), str(m2._lease)
            victim = m1 if l1 not in l2 else m2
            drop = plane.inject("store.lease.refresh", "drop",
                                lease_id=str(victim._lease), times=50)
            grant_err = plane.inject("store.lease.grant", "error",
                                     error="RpcError", times=3)
            expired = plane.inject("store.lease.expire", "delay",
                                   seconds=0.0)  # observer: logs expiries

            # the victim's key must actually vanish: both watchers see
            # membership fall below the agreed set
            assert _wait(lambda: m1._hosts_changed.is_set()
                         or m2._hosts_changed.is_set(), timeout=20), \
                "lease never expired / watchers never saw the shrink"
            assert expired.fired >= 1
            assert drop.fired >= 1 and grant_err.fired >= 1

            # ...and the plane converges back to full strength
            assert m1.wait(timeout=30) == both
            assert m2.wait(timeout=30) == both
        finally:
            m1.stop()
            m2.stop()


# ---------------------------------------------------------------------------
# scenario B: barrier frames dropped during the resize rendezvous
# ---------------------------------------------------------------------------


def test_chaos_barrier_converges_through_dropped_frames(plane):
    """Barrier requests are dropped (server never answers) and errored at
    the dispatch layer; the jittered retry cadence still gets every pod
    the same cluster within the barrier deadline."""
    with EmbeddedStore() as s:
        coord = s.client(root="chaos_barrier")
        pod_a, pod_b = _pod(), _pod()
        reg_a = ResourceRegister(coord, pod_a)
        coord.set_server_permanent(constants.SERVICE_LEADER,
                                   constants.LEADER_SERVER, pod_a.id)
        server = PodServer(coord, pod_a).start()
        # re-register pod_a now that its barrier port is known
        reg_a.stop()
        regs = [ResourceRegister(coord, pod_a),
                ResourceRegister(coord, pod_b)]
        gen = Generator(coord, pod_a.id, min_nodes=2, max_nodes=2).start()

        # one silent drop (client eats a full socket timeout) + two
        # dispatch-layer errors (fast retries); method filter keeps the
        # store's own RPC server out of blast radius
        drop = plane.inject("rpc.server.request", "drop",
                            method="barrier", times=1)
        err = plane.inject("rpc.server.request", "error",
                           method="barrier", error="BarrierError", times=2)
        results = {}

        def arrive(pod):
            results[pod.id] = barrier_wait(coord, pod.id, timeout=60)

        try:
            threads = [threading.Thread(target=arrive, args=(p,))
                       for p in (pod_a, pod_b)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=70)
            assert set(results) == {pod_a.id, pod_b.id}, \
                "a pod never cleared the barrier under chaos"
            assert len({c.stage for c in results.values()}) == 1
            assert all(len(c.pods) == 2 for c in results.values())
            assert drop.fired == 1 and err.fired == 2
        finally:
            gen.stop()
            server.stop()
            for r in regs:
                r.stop()


# ---------------------------------------------------------------------------
# scenario C: store leader failover under client load
# ---------------------------------------------------------------------------


def test_chaos_store_failover_under_load(plane):
    """A writer streams permanent puts through a [primary, standby]
    client while the primary is killed; the standby promotes; every
    single write is acked exactly once in order and the final state is
    the last write — no lost acks, no error surfaced to the writer."""
    primary = StoreServer(host="127.0.0.1").start()
    sb = StandbyServer([primary.endpoint], host="127.0.0.1",
                       auto_promote=True, promote_after=1.0,
                       sync_poll=0.25).start()
    client = CoordClient([primary.endpoint, sb.endpoint],
                         root="chaos_ha", failover_grace=30.0)
    # chaos garnish on the data path: jittered per-call delays
    plane.inject("rpc.client.call", "delay", method="store_put",
                 seconds=0.005, times=20)

    n_writes = 120
    acked, write_errors = [], []

    def writer():
        for i in range(n_writes):
            try:
                client.set_server_permanent("seq", "k", str(i))
            except errors.EdlError as e:
                write_errors.append(e)
                return
            acked.append(i)
            time.sleep(0.01)

    t = threading.Thread(target=writer, name="chaos-writer", daemon=True)
    try:
        assert _wait(sb.synced.is_set)
        t.start()
        assert _wait(lambda: len(acked) >= 10)
        primary.stop()  # the outage, mid-stream
        assert _wait(lambda: sb.promoted, timeout=30)
        t.join(timeout=90)
        assert not t.is_alive(), "writer wedged across the failover"
        assert write_errors == []
        assert acked == list(range(n_writes))
        assert client.get_value("seq", "k") == str(n_writes - 1)
    finally:
        if t.ident is not None:
            t.join(timeout=1)
        sb.stop()


# ---------------------------------------------------------------------------
# scenario D: teacher endpoint flap during distill reads
# ---------------------------------------------------------------------------


def test_chaos_teacher_flap_during_distill_reads(plane):
    """Mid-epoch, predict calls error (workers retire, the breaker
    opens) and discovery briefly reports zero teachers (all workers torn
    down, in-flight tasks requeued); the epoch still yields every batch
    in order with correct values."""
    def echo(feed):
        return {"soft_label": feed["img"] * 2.0}

    teachers = [TeacherServer(echo, {"img": ([2], "<f4")},
                              {"soft_label": ([2], "<f4")},
                              max_batch=16, host="127.0.0.1").start()
                for _ in range(2)]

    def gen():
        for i in range(20):
            yield (np.full((4, 2), i, np.float32),)

    dr = DistillReader(ins=["img"], predicts=["soft_label"],
                       max_in_flight=4, teacher_backoff=0.5)
    dr.set_batch_generator(gen)
    dr.set_fixed_teacher([t.endpoint for t in teachers])
    predict_err = plane.inject("rpc.client.call", "error",
                               method="predict", error="ConnectError",
                               times=2)
    flap = plane.inject("distill.discovery", "drop", after=1, times=2)
    try:
        seen = []
        for batch in dr():
            img, soft = batch
            assert np.allclose(soft, img * 2.0)
            seen.append(int(img[0, 0]))
        assert seen == list(range(20))
        assert predict_err.fired == 2, "predict faults never fired"
        assert flap.fired >= 1, "discovery flap never fired"
    finally:
        dr.stop()
        for t in teachers:
            t.stop()


# ---------------------------------------------------------------------------
# satellite: witness-probe failover under injected RPC timeouts
# ---------------------------------------------------------------------------


def test_chaos_witness_probe_timeouts_fail_safe_then_promote(plane):
    """Injected timeouts on the witness probe path: with zero witness
    answers the standby must NOT promote (no evidence = fail safe); once
    the probes recover and the witness corroborates the dead primary,
    promotion proceeds and the sync loop has survived the fault storm."""
    primary = StoreServer(host="127.0.0.1").start()
    witness = WitnessServer(host="127.0.0.1").start()
    sb = StandbyServer([primary.endpoint], host="127.0.0.1",
                       auto_promote=True, promote_after=0.5,
                       sync_poll=0.25,
                       witness_endpoints=[witness.endpoint]).start()
    # two probe attempts = one full corroboration pass (retry policy
    # allows max_attempts=2): the first pass sees only timeouts
    probe_err = plane.inject("standby.witness.probe", "error",
                             error="TimeoutError_", times=2)
    try:
        assert _wait(sb.synced.is_set)
        primary.stop()
        assert _wait(lambda: sb.promoted, timeout=60), \
            "standby never promoted after probe faults cleared"
        assert probe_err.fired == 2
        # the denied pass really happened before the promoting one
        assert plane.log.count(("standby.witness.probe", "error")) == 2
    finally:
        sb.stop()
        witness.stop()


# ---------------------------------------------------------------------------
# satellite: rpc.frame.* faults against a pipelined connection
# ---------------------------------------------------------------------------


def test_chaos_frame_corrupt_fails_all_pipelined_inflight(plane):
    """A corrupted frame on a pipelined connection desyncs the whole
    stream: every call in flight fails with ConnectError (no silent
    loss, no misparse) and the next call dials a clean connection."""
    from edl_tpu.rpc.client import RpcClient
    from edl_tpu.rpc.server import RpcServer

    gate = threading.Event()
    srv = RpcServer(host="127.0.0.1", port=0)
    srv.register("echo", lambda x: x)
    srv.register("gated", lambda x: (gate.wait(10), x)[1])
    srv.start()
    c = RpcClient("127.0.0.1:%d" % srv.port, timeout=10)
    try:
        assert c.call("echo", 0) == 0  # connection warmed, fault unarmed
        # unlimited while armed: the point is process-global, so a
        # stray writer from another component must not eat the only
        # firing before our request goes out
        corrupt = plane.inject("rpc.frame.write", "corrupt")
        # (a send that meets the stream request 0 already killed is
        # refused with the same ConnectError at once, by _send's own
        # "connection died" or its failed write: no future to wait on)
        futs, failed = [], 0
        for i in range(4):
            try:
                futs.append(c.call_async("gated", i))
            except errors.ConnectError:
                failed += 1
        assert futs  # request 0 went out on the warmed connection
        gate.set()
        # the armed write replaced request 0 with a garbage magic: the
        # server kills the stream, so EVERY in-flight future fails
        for fut in futs:
            with pytest.raises(errors.ConnectError):
                fut.result(timeout=10)
            failed += 1
        assert failed == 4  # no request was lost or answered
        assert corrupt.fired >= 1
        plane.clear("rpc.frame.write")
        assert c.call("echo", "recovered") == "recovered"  # fresh dial
    finally:
        c.close()
        srv.stop()
        gate.set()


def test_chaos_frame_faults_during_pipelined_distill(plane):
    """rpc.frame.write corruption under a pipelined DistillReader with
    an adaptive-batching teacher: in-flight tasks are requeued, the
    epoch still delivers every batch exactly once, in order."""
    def fn(feed):
        return {"soft_label": feed["img"] * 2.0}

    teacher = TeacherServer(fn, {"img": ([2], "<f4")},
                            {"soft_label": ([2], "<f4")},
                            max_batch=16, host="127.0.0.1").start()

    def gen():
        for i in range(20):
            yield np.full((4, 2), i, np.float32),

    dr = DistillReader(ins=["img"], predicts=["soft_label"],
                       max_in_flight=8, pipeline_depth=4,
                       teacher_backoff=0.5, predict_timeout=10)
    dr.set_batch_generator(gen)
    dr.set_fixed_teacher([teacher.endpoint])
    # arm only after the reader's discovery/get_feed_fetch calls by
    # matching nothing until the data plane is live would be racy —
    # instead allow the first few frames through with after=
    corrupt = plane.inject("rpc.frame.write", "corrupt", after=4,
                           times=2)
    try:
        seen = []
        for img, soft in dr():
            np.testing.assert_allclose(soft, img * 2.0)
            seen.append(int(img[0, 0]))
        assert seen == list(range(20))  # exactly once, in order
        assert corrupt.fired == 2, "frame faults never fired"
    finally:
        dr.stop()
        teacher.stop()


def _peer_plane_fixture(tmp_path, root):
    """(store, coord, cm, srv, tree, target, shardings) for the peer
    restore chaos drills: one committed stream checkpoint, one live
    peer serving the same version."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from edl_tpu.runtime.checkpoint import CheckpointManager
    from edl_tpu.runtime.state_server import StateServer, snapshot_entries

    store = StoreServer(host="127.0.0.1", port=0).start()
    coord = CoordClient([store.endpoint], root=root)
    rng = np.random.RandomState(11)
    tree = {"w": rng.randn(32, 8).astype(np.float32),
            "b": rng.randn(8).astype(np.float32),
            "step": np.int32(5)}
    cm = CheckpointManager(str(tmp_path))
    cm.save_async(5, tree, meta={"state": {"epoch": 2}}).result(60.0)
    srv = StateServer(rank=0, host="127.0.0.1")
    entries, dtypes = snapshot_entries(tree)
    srv.publish(5, entries, dtypes, meta={"state": {"epoch": 2}})
    srv.advertise(coord)
    sh = SingleDeviceSharding(jax.devices("cpu")[0])
    target = {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
              for k, v in tree.items()}
    shardings = {k: sh for k in tree}
    return store, coord, cm, srv, tree, target, shardings


def _assert_tree_bytes_equal(got, tree):
    for k, v in tree.items():
        ga = np.asarray(got[k])
        assert ga.dtype == v.dtype and ga.tobytes() == v.tobytes(), k


def test_chaos_peer_connect_partition_wholesale_fs_fallback(plane,
                                                            tmp_path):
    """Every peer unreachable at dial time: the peer plane raises
    PeerRestoreError and the caller's wholesale shared-FS restore
    yields bit-identical state (the trainer's outermost ladder rung)."""
    from edl_tpu.runtime.state_server import PeerRestorer

    store, coord, cm, srv, tree, target, shardings = \
        _peer_plane_fixture(tmp_path, "chaos_peer_conn")
    cut = plane.inject("peer_restore.connect", "partition")
    try:
        with pytest.raises(errors.PeerRestoreError):
            PeerRestorer(coord, cm).restore_placed(5, target, shardings)
        assert cut.fired >= 1, "connect fault never fired"
        v, got, meta = cm.restore_placed(5, target, shardings)
        assert v == 5 and meta == {"state": {"epoch": 2}}
        _assert_tree_bytes_equal(got, tree)
    finally:
        srv.stop()
        cm.close()
        store.stop()


def test_chaos_peer_death_mid_fetch_per_span_fs_fill(plane, tmp_path):
    """Peer dies mid-fetch (every range read errors after a healthy
    manifest): the failed spans are re-filled per-key from the shared
    FS and the result is bit-identical to a pure FS restore."""
    from edl_tpu.runtime.state_server import PeerRestorer

    store, coord, cm, srv, tree, target, shardings = \
        _peer_plane_fixture(tmp_path, "chaos_peer_read")
    die = plane.inject("peer_restore.read", "error",
                       error="ConnectError")
    try:
        v, got, meta, stats = PeerRestorer(coord, cm).restore_placed(
            5, target, shardings)
        assert die.fired >= 1, "read fault never fired"
        assert v == 5 and stats["source"] == "peer+fs"
        assert set(stats["fs_keys"]) == set(tree)
        _assert_tree_bytes_equal(got, tree)
        _, fs_got, _ = cm.restore_placed(5, target, shardings)
        _assert_tree_bytes_equal(fs_got, tree)
    finally:
        srv.stop()
        cm.close()
        store.stop()


def test_chaos_peer_read_error_once_partial_then_peer(plane, tmp_path):
    """A single faulted read: only that key falls back to FS, the rest
    still comes off the peer, and the assembled state is unchanged."""
    from edl_tpu.runtime.state_server import PeerRestorer

    store, coord, cm, srv, tree, target, shardings = \
        _peer_plane_fixture(tmp_path, "chaos_peer_once")
    once = plane.inject("peer_restore.read", "error_once")
    try:
        v, got, meta, stats = PeerRestorer(coord, cm).restore_placed(
            5, target, shardings)
        assert once.fired == 1
        assert stats["source"] == "peer+fs"
        assert len(stats["fs_keys"]) == 1
        _assert_tree_bytes_equal(got, tree)
    finally:
        srv.stop()
        cm.close()
        store.stop()


# ---------------------------------------------------------------------------
# data plane: pipelined fetch under fetch faults (exact lost-batch
# accounting) and assignment faults (retry-absorbed, zero loss)
# ---------------------------------------------------------------------------


def _data_files(tmp_path, n_files, lines_per_file):
    paths = []
    for i in range(n_files):
        p = tmp_path / ("part-%02d.txt" % i)
        p.write_text("".join("file%d_rec%d\n" % (i, j)
                             for j in range(lines_per_file)))
        paths.append(str(p))
    return paths


def test_chaos_data_fetch_faults_exact_lost_accounting(plane, tmp_path):
    """data.fetch drill: the first 3 remote fetches fail (deterministic
    times=3). Those exact batches are logged lost — no duplicates, no
    wedge — the epoch still converges to END, and the completion pass
    behind the data checkpoint recovers exactly the lost records."""
    from edl_tpu.data.reader import ElasticReader
    from edl_tpu.data.splitter import TxtFileSplitter
    from edl_tpu.runtime.state import State

    paths = _data_files(tmp_path, 4, 20)  # 80 records, 10 batches
    total = ["file%d_rec%d" % (f, j) for f in range(4) for j in range(20)]
    fault = plane.inject("data.fetch", "error", times=3)
    state = State()

    prod = ElasticReader("prod", TxtFileSplitter(), batch_size=8,
                         file_list=paths, is_leader=True)
    cons = ElasticReader("cons", TxtFileSplitter(), batch_size=8,
                         produce=False, leader_endpoint=prod.endpoint)
    got_batches, got = [], []
    try:
        for batch in cons:
            ElasticReader.mark_consumed(state, batch)
            got_batches.append(batch)
            got.extend(batch["records"])
        lost = cons.stats()["lost"]
        stats = prod._leader.call("ds_stats")
    finally:
        cons.stop()
        prod.stop()

    assert fault.fired == 3                       # the chaos happened
    assert sorted(lost) == sorted(set(lost)) and len(lost) == 3
    assert len(got) == len(set(got))              # nothing duplicated
    # EXACT accounting: every assignment the leader handed out was
    # delivered or logged lost
    assert stats["consumed"] == len(got_batches) + len(lost)

    plane.clear()  # the completion pass runs chaos-free
    state2 = State().from_json(state.to_json())
    rest = []
    sweeper = ElasticReader("sweep", TxtFileSplitter(), batch_size=8,
                            file_list=paths, is_leader=True,
                            skip_record=state2.data_checkpoint.is_processed)
    try:
        for batch in sweeper:
            rest.extend(batch["records"])
    finally:
        sweeper.stop()
    assert sorted(got + rest) == sorted(total)    # exactly once overall
    assert not set(got) & set(rest)
    # the sweep is EXACTLY the lost batches: 20 lines at batch_size 8
    # split 8/8/4, so a file's _b2 tail holds 4 records
    assert len(rest) == sum(4 if b.endswith("_b2") else 8 for b in lost)


def test_chaos_data_assign_fault_absorbed_by_retry(plane, tmp_path):
    """data.assign drill: a one-shot assignment failure is absorbed by
    the fetch pipeline's RetryPolicy — the epoch completes with ZERO
    loss and the consumer never sees the error."""
    from edl_tpu.data.reader import ElasticReader
    from edl_tpu.data.splitter import TxtFileSplitter

    paths = _data_files(tmp_path, 2, 16)  # 32 records
    fault = plane.inject("data.assign", "error_once")

    prod = ElasticReader("prod", TxtFileSplitter(), batch_size=8,
                         file_list=paths, is_leader=True)
    cons = ElasticReader("cons", TxtFileSplitter(), batch_size=8,
                         produce=False, leader_endpoint=prod.endpoint)
    try:
        got = []
        for batch in cons:
            got.extend(batch["records"])
        assert fault.fired == 1
        assert cons.stats()["lost"] == []
        assert sorted(got) == sorted(
            "file%d_rec%d" % (f, j) for f in range(2) for j in range(16))
    finally:
        cons.stop()
        prod.stop()
