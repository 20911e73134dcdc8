"""Quorum-replicated coordination store: election, quorum-acked log
replication, linearizable follower reads, snapshot install, client
failover, keepalive coalescing — and the tier-1 chaos drill (leader
killed mid-elastic-resize under store.repl.* faults, zero
acknowledged-write loss).

Election timeouts here are tuned small (0.15-0.3s) so every scenario
converges in a couple of seconds on a loaded CI box.
"""

import json
import os
import threading
import time

import pytest

from edl_tpu.coordination import replica as replica_mod
from edl_tpu.coordination.client import CoordClient
from edl_tpu.coordination.keepalive import KeepaliveHub
from edl_tpu.coordination.replica import (ReplLog, ReplicatedStoreServer,
                                          start_local_replica_set,
                                          wait_for_leader)
from edl_tpu.rpc.client import RpcClient
from edl_tpu.utils import errors

ET = (0.15, 0.3)  # election timeout band for every in-test replica set


def _wait(pred, timeout=10.0, interval=0.02):
    gate = threading.Event()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        gate.wait(interval)
    return pred()


@pytest.fixture()
def rset(tmp_path):
    reps = start_local_replica_set(3, data_dir=str(tmp_path),
                                   election_timeout=ET)
    yield reps
    for r in reps:
        try:
            r.stop()
        except Exception:
            pass


def _survivors_logs_match(survivors):
    """Log-matching property over the committed prefix: every survivor
    holds the identical entry sequence up to the common commit index."""
    logs = [r.repl_log_dump() for r in survivors]
    common = min(l["commit"] for l in logs)
    sigs = [[(e["index"], e["term"], e["kind"], e.get("op_id"))
             for e in l["entries"] if e["index"] <= common]
            for l in logs]
    return all(s == sigs[0] for s in sigs[1:]), common


# -- replication log ---------------------------------------------------


def test_repl_log_roundtrip_and_torn_tail(tmp_path):
    path = str(tmp_path / "repl.log")
    lg = ReplLog(path)
    ents = [{"index": i, "term": 1, "kind": "put",
             "args": ["k%d" % i, b"v%d" % i, None]} for i in (1, 2, 3)]
    lg.append(ents)
    lg.close()
    # crash mid-write: a torn trailing record on disk
    with open(path, "ab") as f:
        f.write(b'{"op": "ent", "index": 4, "term": 1, "ki')
    lg2 = ReplLog(path)
    assert lg2.last_index == 3
    assert lg2.get(2)["args"][1] == b"v2"
    # the torn bytes were truncated: appending and re-replaying is clean
    lg2.append([{"index": 4, "term": 2, "kind": "noop", "args": []}])
    lg2.close()
    lg3 = ReplLog(path)
    assert lg3.last_index == 4 and lg3.last_term == 2
    lg3.close()


def test_repl_log_truncate_compact_reset(tmp_path):
    path = str(tmp_path / "repl.log")
    lg = ReplLog(path)
    lg.append([{"index": i, "term": 1, "kind": "noop", "args": []}
               for i in range(1, 6)])
    lg.truncate_from(4)                 # conflict resolution
    assert lg.last_index == 3
    lg.compact(2, 1, {"store": {"s": 1}, "dedup": []})
    assert (lg.base_index, lg.last_index) == (2, 3)
    lg.close()
    lg2 = ReplLog(path)                 # compaction survives restart
    assert (lg2.base_index, lg2.last_index) == (2, 3)
    assert lg2.snapshot["store"] == {"s": 1}
    lg2.reset(9, 4, {"store": {"s": 2}, "dedup": []})
    assert (lg2.base_index, lg2.last_index) == (9, 9)
    lg2.close()


# -- election + quorum replication ------------------------------------


def test_election_single_leader_and_quorum_write(rset):
    leader = wait_for_leader(rset, timeout=10.0)
    assert [r.repl_status()["role"] for r in rset].count("leader") == 1
    rev = leader.store_put("/j/a/nodes/x", b"v1")
    assert rev >= 1
    # quorum-committed: every replica converges to the same store state
    assert _wait(lambda: all(
        (r.store.get("/j/a/nodes/x") or {}).get("value") == b"v1"
        for r in rset))


def test_follower_rejects_mutations_with_leader_hint(rset):
    leader = wait_for_leader(rset, timeout=10.0)
    fol = next(r for r in rset if r is not leader)
    rpc = RpcClient(fol.endpoint, timeout=5.0)
    try:
        with pytest.raises(errors.NotLeaderError) as ei:
            rpc.call("store_put", "/j/a/nodes/k", b"v", None)
        assert "leader=%s" % leader.endpoint in str(ei.value)
    finally:
        rpc.close()


def test_linearizable_follower_read(rset):
    leader = wait_for_leader(rset, timeout=10.0)
    fol = next(r for r in rset if r is not leader)
    for i in range(5):
        leader.store_put("/j/lin/nodes/k", b"v%d" % i)
        # read-index: the follower may not serve a stale value for an
        # already-acknowledged write
        got = fol.store_get("/j/lin/nodes/k")
        assert got["value"] == b"v%d" % i


def test_client_redirects_to_leader(rset):
    leader = wait_for_leader(rset, timeout=10.0)
    eps = [r.endpoint for r in rset if r is not leader] + [leader.endpoint]
    c = CoordClient(eps, root="j", failover_grace=10.0)  # followers first
    c.set_server_permanent("svc", "a", b"v1")
    assert c.get_value("svc", "a") == b"v1"


def test_put_if_absent_op_id_applies_exactly_once(rset):
    leader = wait_for_leader(rset, timeout=10.0)
    r1 = leader.store_put_if_absent("/j/e/nodes/l", b"me", None,
                                    op_id="op-xyz")
    # the retry (same idempotency key) must replay the SAME result, not
    # re-execute and observe its own first attempt
    r2 = leader.store_put_if_absent("/j/e/nodes/l", b"me", None,
                                    op_id="op-xyz")
    assert list(r1) == list(r2) and r1[0] is True
    dump = leader.repl_log_dump()
    assert sum(1 for e in dump["entries"]
               if e.get("op_id") == "op-xyz") == 1


def test_failover_loses_no_acked_write(rset):
    leader = wait_for_leader(rset, timeout=10.0)
    c = CoordClient([r.endpoint for r in rset], root="j",
                    failover_grace=15.0)
    acked = {}
    for i in range(10):
        k = "/j/f/nodes/w%d" % i
        c.put(k, b"v%d" % i)
        acked[k] = b"v%d" % i
    leader.stop()
    survivors = [r for r in rset if r is not leader]
    # writes keep flowing through the client's breaker/redirect path
    for i in range(10, 20):
        k = "/j/f/nodes/w%d" % i
        c.put(k, b"v%d" % i)
        acked[k] = b"v%d" % i
    wait_for_leader(survivors, timeout=10.0)
    for k, v in acked.items():
        got = c.get_key(k)
        assert got is not None and got["value"] == v, k
    ok, common = _survivors_logs_match(survivors)
    assert ok and common >= 20


def test_replica_set_restart_recovers_from_logs(tmp_path):
    reps = start_local_replica_set(3, data_dir=str(tmp_path),
                                   election_timeout=ET)
    eps = [r.endpoint for r in reps]
    try:
        leader = wait_for_leader(reps, timeout=10.0)
        leader.store_put("/j/r/nodes/a", b"sticky")
        leader.store_put("/j/r/nodes/b", b"sticky2")
    finally:
        for r in reps:
            r.stop()
    # cold restart of the whole set on the same endpoints + logs
    reps2 = [ReplicatedStoreServer(
        ep, eps, data_dir=os.path.join(str(tmp_path), "r%d" % i),
        election_timeout=ET).start() for i, ep in enumerate(eps)]
    try:
        wait_for_leader(reps2, timeout=10.0)
        c = CoordClient(eps, root="j", failover_grace=10.0)
        assert c.get_value("r", "a") == b"sticky"
        assert c.get_value("r", "b") == b"sticky2"
    finally:
        for r in reps2:
            r.stop()


def test_snapshot_install_catches_up_wiped_replica(tmp_path, monkeypatch):
    # tiny compaction threshold so the leader's log no longer reaches
    # back to index 0 by the time the wiped replica returns
    monkeypatch.setattr(replica_mod, "COMPACT_THRESHOLD", 8)
    reps = start_local_replica_set(3, data_dir=str(tmp_path),
                                   election_timeout=ET)
    eps = [r.endpoint for r in reps]
    try:
        leader = wait_for_leader(reps, timeout=10.0)
        victim = next(r for r in reps if r is not leader)
        victim_ep = victim.endpoint
        victim.stop()
        reps.remove(victim)
        for i in range(24):
            leader.store_put("/j/s/nodes/w%d" % i, b"v%d" % i)
        assert _wait(lambda: leader.repl_status()["base_index"] > 0)
        # the replica returns WIPED (fresh data dir = lost disk)
        wiped_dir = str(tmp_path / "rewipe")
        back = ReplicatedStoreServer(victim_ep, eps, data_dir=wiped_dir,
                                     election_timeout=ET).start()
        reps.append(back)
        assert _wait(lambda: back.repl_status()["applied"]
                     >= leader.repl_status()["commit"] - 1, timeout=15.0)
        assert (back.store.get("/j/s/nodes/w3") or {}).get("value") == b"v3"
        assert back.repl_status()["base_index"] > 0  # came via snapshot
    finally:
        for r in reps:
            try:
                r.stop()
            except Exception:
                pass


# -- watches across failover + retention ------------------------------


def test_watch_longpoll_survives_leader_death(rset):
    """A watch in flight during leader death resumes on the survivors
    without missing or duplicating membership events (the watch is
    served by any replica; the client re-dials transparently)."""
    leader = wait_for_leader(rset, timeout=10.0)
    c = CoordClient([r.endpoint for r in rset], root="j",
                    failover_grace=15.0, timeout=10.0)
    adds = []
    w = c.watch_service("wsvc", lambda a, r, al: adds.extend(a.items()))
    try:
        c.set_server_permanent("wsvc", "pre", b"1")
        assert _wait(lambda: ("pre", b"1") in adds)
        leader.stop()
        survivors = [r for r in rset if r is not leader]
        c.set_server_permanent("wsvc", "post", b"2")
        wait_for_leader(survivors, timeout=10.0)
        assert _wait(lambda: ("post", b"2") in adds, timeout=15.0)
        # no duplicated delivery of either event
        assert adds.count(("pre", b"1")) == 1
        assert adds.count(("post", b"2")) == 1
    finally:
        w.stop()


def test_watch_catchup_past_retention_resets(monkeypatch):
    """A watcher whose since_rev predates Store event retention gets a
    reset event and rebuilds from a snapshot read — never a silent
    miss."""
    from edl_tpu.coordination.embedded import EmbeddedStore
    from edl_tpu.coordination.store import Store

    monkeypatch.setattr(Store, "EVENT_HISTORY", 8)
    with EmbeddedStore() as s:
        c = CoordClient([s.endpoint], root="j")
        c.set_server_permanent("rsvc", "a", b"v")
        stale_rev = c.revision()
        # blow past the retained-event window
        for i in range(20):
            c.set_server_permanent("rsvc", "k%d" % i, b"x")
        evs, rev = c.wait_events(c.service_prefix("rsvc"), stale_rev, 1.0)
        assert [e["type"] for e in evs] == ["reset"]
        # the Watcher turns the reset into a full re-list: it converges
        # to complete membership, missing none of the puts
        snaps = []
        w = c.watch_service("rsvc", lambda a, r, al: snaps.append(al))
        try:
            assert _wait(lambda: snaps and len(snaps[-1]) == 21)
        finally:
            w.stop()


# -- keepalive coalescing ----------------------------------------------


def test_keepalive_hub_single_timer_and_lost_callback():
    from edl_tpu.coordination.embedded import EmbeddedStore

    with EmbeddedStore() as s:
        c = CoordClient([s.endpoint], root="j")
        hub = KeepaliveHub(c)
        lost = []
        l1 = hub.add(c.lease_grant(30.0), 30.0,
                     on_lost=lambda: lost.append("l1"))
        l2 = hub.add(c.lease_grant(30.0), 30.0,
                     on_lost=lambda: lost.append("l2"))
        try:
            res = hub.refresh_now()          # ONE batched RPC
            assert res == {l1: True, l2: True}
            c.lease_revoke(l2)               # dies behind the hub's back
            res = hub.refresh_now()
            assert res[l2] is False and res[l1] is True
            assert lost == ["l2"]
            assert len(hub) == 1             # the lost lease was dropped
            assert hub.refresh_now() == {l1: True}
        finally:
            hub.stop()


def test_legacy_peer_lease_refresh_many_fallback():
    """Against a peer that lacks the batched RPC, the client degrades
    to per-id refreshes via __features__ negotiation."""
    from edl_tpu.coordination.embedded import EmbeddedStore

    with EmbeddedStore() as s:
        # simulate a pre-batching peer: unregister the method + feature
        s._server._rpc.methods.pop("store_lease_refresh_many")
        s._server._rpc.methods["__features__"] = lambda: ["rpc.pipeline"]
        c = CoordClient([s.endpoint], root="j")
        lids = [c.lease_grant(30.0) for _ in range(3)]
        assert c.lease_refresh_many(lids) == {lid: True for lid in lids}


# -- the tier-1 chaos drill --------------------------------------------


def test_chaos_drill_leader_kill_mid_resize(tmp_path):
    """Acceptance drill: a 2-pod elastic job runs against a 3-replica
    store; store.repl.* faults chew on the replication plane and the
    LEADER is killed while the job is mid-flight (the elastic join/
    resize machinery is live on the store: registrations, barriers,
    cluster maps). A new leader must be elected, the job must complete
    SUCCEED, and no acknowledged write may be lost — asserted by a
    linearizability check over the survivors' replicated logs."""
    import signal as signal_mod
    import subprocess
    import sys

    from edl_tpu.controller import cluster as cluster_mod
    from edl_tpu.controller import status
    from edl_tpu.robustness.faults import FaultPlane

    plane = FaultPlane(seed=11).install()
    try:
        # drop a couple of appends + votes: exercises the retry/re-
        # election paths while the job runs
        plane.inject("store.repl.append", "drop", times=2)
        plane.inject("store.repl.vote", "drop", times=1)
        reps = start_local_replica_set(3, data_dir=str(tmp_path / "rs"),
                                       election_timeout=ET)
        eps = [r.endpoint for r in reps]
        endpoints = ",".join(eps)
        job = "chaos_repl"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        trainer = os.path.join(repo, "tests", "fixtures",
                               "dummy_trainer.py")
        env = dict(os.environ)
        env.update({"PYTHONPATH": repo, "EDL_TPU_POD_IP": "127.0.0.1",
                    "EDL_TPU_TTL": "3", "JAX_PLATFORMS": "cpu"})

        def spawn(name):
            lg = open(str(tmp_path / (name + ".log")), "wb")
            p = subprocess.Popen(
                [sys.executable, "-u", "-m", "edl_tpu.controller.launch",
                 "--job_id", job, "--store_endpoints", endpoints,
                 "--nodes_range", "1:2",
                 "--log_dir", str(tmp_path / (name + "_logs")),
                 trainer, "12", "0"],
                env=env, stdout=lg, stderr=subprocess.STDOUT,
                preexec_fn=os.setsid)
            lg.close()
            return p

        pods = [spawn("pod1"), spawn("pod2")]
        c = CoordClient(eps, root=job, failover_grace=25.0, timeout=15.0)
        acked = {}
        try:
            assert _wait(lambda: cluster_mod.load_from_store(c)
                         is not None, timeout=30)
            time.sleep(2)  # the job is mid-flight (post-join, training)
            # acked writes straddling the kill: the loss-check corpus
            for i in range(5):
                k = "/%s/probe/nodes/a%d" % (job, i)
                c.put(k, b"pre%d" % i)
                acked[k] = b"pre%d" % i
            leader = wait_for_leader(reps, timeout=10.0)
            leader.stop()  # the outage, mid-job
            survivors = [r for r in reps if r is not leader]
            for i in range(5):
                k = "/%s/probe/nodes/b%d" % (job, i)
                c.put(k, b"post%d" % i)
                acked[k] = b"post%d" % i
            wait_for_leader(survivors, timeout=15.0)
            for p in pods:
                assert p.wait(timeout=150) == 0, \
                    (tmp_path / "pod1.log").read_text()[-3000:]
            assert status.load_job_status(c) == status.Status.SUCCEED
            # zero acknowledged-write loss, linearizably readable
            for k, v in acked.items():
                got = c.get_key(k)
                assert got is not None and got["value"] == v, k
            ok, common = _survivors_logs_match(survivors)
            assert ok and common > 0
            assert len(json.dumps(
                [e["kind"] for e in survivors[0].repl_log_dump()
                 ["entries"]])) > 0
        finally:
            for p in pods:
                try:
                    os.killpg(os.getpgid(p.pid), signal_mod.SIGKILL)
                except ProcessLookupError:
                    pass
            for r in reps:
                try:
                    r.stop()
                except Exception:
                    pass
    finally:
        plane.uninstall()
