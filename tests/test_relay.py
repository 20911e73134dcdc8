"""Watch relay tree tests: deterministic topology, lossless failover,
feature-negotiated fall-through, lease coalescing, and obs_agg/v1
round-trip through the health detectors.

The invariants pinned here are the ones the 10k-pod claim rests on:

- every pod derives the SAME B-ary tree from the cluster map alone
  (no negotiation), and the depth stays ⌈log_B N⌉;
- a relay kill (or a seeded ``relay.forward`` fault) can delay events
  but never lose one, because every consumer resumes from its OWN
  ``since_rev`` against the grandparent or the store;
- peers that predate ``coord.relay`` are permanently skipped and the
  client falls through to the direct store path (wire compat);
- the detectors see the identical per-pod picture whether docs arrive
  flat (``obs_pub/v1``) or relay-folded (``obs_agg/v1``).
"""

import json
import random
import time

from edl_tpu.controller import constants
from edl_tpu.coordination import relay as relay_mod
from edl_tpu.coordination.client import CoordClient
from edl_tpu.coordination.relay import (RelayAttachment, WatchRelay,
                                        tree_ancestors, tree_depth,
                                        tree_parent)
from edl_tpu.obs import events as obs_events
from edl_tpu.obs import health as obs_health
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.robustness import faults
from edl_tpu.tools import obs_bench

PREFIX = "/t/fleet/nodes/"


# -- helpers -------------------------------------------------------------


def _client(store, root="t"):
    return CoordClient([store.endpoint], root=root)


def _start_relay(store, pod_id, parents, **kw):
    """A relay with an explicit parent chain (no registry round-trips:
    tests that want the registry path use the default resolver)."""
    r = WatchRelay(_client(store), pod_id, obs_interval=3600.0,
                   parent_resolver=(lambda: list(parents)), **kw)
    return r.start(register=False)


def _drain(att, fallback, since, want, deadline=12.0):
    """Collect ``want`` distinct event keys through ``att``, falling
    through to the direct ``fallback`` client exactly like the wired
    CoordClient does; returns ({key: rev}, cursor)."""
    got = {}
    end = time.monotonic() + deadline
    while len(got) < want and time.monotonic() < end:
        try:
            out = att.wait_events(PREFIX, since, 0.5)
        except Exception:  # noqa: BLE001 — killed relay mid-poll
            continue
        if out is None:
            evs, since = fallback.wait_events(PREFIX, since, 0.5,
                                              relay=False)
        else:
            evs, since = out
        for e in evs or ():
            if e.get("type") != "reset":
                got[e["key"]] = e["rev"]
    return got, since


# -- the deterministic tree ----------------------------------------------


def test_tree_shape_deterministic_across_resizes():
    rng = random.Random(7)
    ids = ["pod-%03d" % i for i in range(97)]
    b = 4
    srt = sorted(ids)
    for _ in range(5):
        shuffled = list(ids)
        rng.shuffle(shuffled)
        # same parent regardless of the order the map arrived in
        for pod in ids:
            assert tree_parent(shuffled, pod, b) \
                == tree_parent(srt, pod, b)
    # root has no parent; everyone else's parent sorts strictly
    # earlier (the heap property — no cycles possible)
    assert tree_parent(srt, srt[0], b) is None
    children = {}
    for pod in srt[1:]:
        parent = tree_parent(srt, pod, b)
        assert parent < pod
        children.setdefault(parent, []).append(pod)
    # fan-out is capped at B and the ancestor chain is the depth bound
    assert max(len(c) for c in children.values()) <= b
    assert tree_depth(len(srt), b) == 4  # ceil(log4 97)
    for pod in srt:
        assert len(tree_ancestors(srt, pod, b)) <= tree_depth(
            len(srt), b)
    # a resize (pods leave AND join) yields the same tree for every
    # observer of the new map — determinism is what makes the relay
    # topology negotiation-free
    resized = sorted(srt[:40] + ["pod-%03d" % i for i in range(200,
                                                               230)])
    for pod in resized:
        again = list(resized)
        rng.shuffle(again)
        assert tree_parent(again, pod, b) == tree_parent(resized, pod,
                                                         b)


def test_service_relay_constant_matches_inlined_value():
    # relay.py inlines the registry name to stay below controller in
    # the layering; this is the drift guard the comment points at
    assert relay_mod.SERVICE_RELAY == constants.SERVICE_RELAY


def test_kill_switch_env(monkeypatch):
    monkeypatch.delenv("EDL_TPU_RELAY", raising=False)
    assert relay_mod.enabled()
    monkeypatch.setenv("EDL_TPU_RELAY", "0")
    assert not relay_mod.enabled()


# -- fan-out + failover --------------------------------------------------


def test_depth2_fanout_and_kill_reattach_lossless(store):
    """store -> root -> mid -> child; kill mid mid-stream: the child
    reattaches to the grandparent and replays from its own since_rev —
    zero loss, asserted from the relay metrics as well."""
    pub = _client(store)
    root = _start_relay(store, "p0", [])
    mid = _start_relay(store, "p1", [root.endpoint])
    att = RelayAttachment(lambda: [mid.endpoint, root.endpoint],
                          pod_id="leaf")
    reatt0 = relay_mod._REATTACHES.value
    fwd0 = relay_mod._FORWARDED.value
    try:
        since = pub.revision()
        keys = [PREFIX + "a%d" % i for i in range(4)]
        for k in keys:
            pub.put(k, b"v")
        got, since = _drain(att, pub, since, 4)
        assert sorted(got) == keys
        assert att.current() == mid.endpoint

        mid.stop()  # the kill drill: child is attached through mid
        keys2 = [PREFIX + "b%d" % i for i in range(4)]
        for k in keys2:
            pub.put(k, b"v")
        got2, since = _drain(att, pub, since, 4)
        # lossless: every post-kill event arrives via the grandparent
        assert sorted(got2) == keys2
        assert att.current() == root.endpoint
        # and the drill is provable from metrics alone: at least one
        # reattach, and both batches were served from relay caches
        assert relay_mod._REATTACHES.value >= reatt0 + 1
        assert relay_mod._FORWARDED.value >= fwd0 + 8
    finally:
        att.close()
        root.stop()


def test_forward_fault_forces_lossless_reattach(store):
    """Seeded ``relay.forward`` error: the child's poll fails at the
    mid relay, the attachment walks to the grandparent, and the event
    stream resumes from the child's own cursor with nothing missing."""
    pub = _client(store)
    root = _start_relay(store, "p0", [])
    mid = _start_relay(store, "p1", [root.endpoint])
    att = RelayAttachment(lambda: [mid.endpoint, root.endpoint],
                          pod_id="leaf", retry_bad_after=0.5)
    try:
        since = pub.revision()
        pub.put(PREFIX + "pre", b"v")
        got, since = _drain(att, pub, since, 1)
        assert att.current() == mid.endpoint

        plane = faults.FaultPlane(seed=11)
        # child="leaf" scopes the fault to OUR poll; error (not drop)
        # is the kind that drives the reattach path. times is
        # unbounded: mid must stay poisoned until the walk lands on
        # the grandparent.
        plane.inject("relay.forward", "error", child="leaf")
        plane.install()
        try:
            keys = [PREFIX + "c%d" % i for i in range(3)]
            for k in keys:
                pub.put(k, b"v")
            got, since = _drain(att, pub, since, 3)
            assert sorted(got) == keys  # nothing lost crossing relays
            # the grandparent also fires relay.forward for child
            # "leaf", so the attachment ends on the DIRECT store path
            # — fall-through is part of the lossless contract
        finally:
            plane.uninstall()
        # with the fault gone (and the bad marks expired) the next
        # adoption walk lands on a relay again
        time.sleep(0.6)
        pub.put(PREFIX + "post", b"v")
        got, since = _drain(att, pub, since, 1)
        assert list(got) == [PREFIX + "post"]
        assert att.current() in (mid.endpoint, root.endpoint)
    finally:
        att.close()
        mid.stop()
        root.stop()


def test_attach_fault_skips_candidate(store):
    """Seeded ``relay.attach`` error at the mid endpoint: adoption
    skips it and lands on the next ancestor without ever dialing."""
    pub = _client(store)
    root = _start_relay(store, "p0", [])
    mid = _start_relay(store, "p1", [root.endpoint])
    plane = faults.FaultPlane(seed=5)
    plane.inject("relay.attach", "error", endpoint=mid.endpoint)
    plane.install()
    att = RelayAttachment(lambda: [mid.endpoint, root.endpoint],
                          pod_id="leaf")
    try:
        since = pub.revision()
        pub.put(PREFIX + "x", b"v")
        got, _ = _drain(att, pub, since, 1)
        assert list(got) == [PREFIX + "x"]
        assert att.current() == root.endpoint
    finally:
        plane.uninstall()
        att.close()
        mid.stop()
        root.stop()


def test_legacy_peer_without_feature_goes_direct(store):
    """A registered endpoint that does not advertise ``coord.relay``
    (here: the store itself, standing in for a pre-relay peer) is
    permanently skipped — the client falls through to the direct
    store path and keeps working."""
    c = _client(store)
    att = c.attach_relay(RelayAttachment(lambda: [store.endpoint],
                                         pod_id="leaf"))
    try:
        since = c.revision()
        c.put(PREFIX + "legacy", b"v")
        evs, _ = c.wait_events(PREFIX, since, 2.0)  # relayed entry point
        assert [e["key"] for e in evs] == [PREFIX + "legacy"]
        assert att.current() is None  # never adopted the legacy peer
    finally:
        c.detach_relay()
        att.close()


def test_relay_cache_floor_resets_stale_child(store):
    """The relay mirrors the store's watch contract: a child whose
    cursor predates the cache floor gets a synthetic reset, not a
    silent gap."""
    pub = _client(store)
    root = _start_relay(store, "p0", [])
    att = RelayAttachment(lambda: [root.endpoint], pod_id="leaf")
    try:
        since = pub.revision()
        pub.put(PREFIX + "f", b"v")
        got, _ = _drain(att, pub, since, 1)  # feed floor is `since` now
        out = att.wait_events(PREFIX, since - 10_000, 0.5)
        assert out is not None
        evs, rev = out
        assert [e["type"] for e in evs] == ["reset"]
        assert rev > since - 10_000
    finally:
        att.close()
        root.stop()


def test_registry_based_parent_resolution(store):
    """The default resolver: ancestors come from the cluster map (the
    deterministic tree) joined with the SERVICE_RELAY registry."""
    ids = ["p%02d" % i for i in range(8)]
    root = WatchRelay(_client(store), ids[0], branching=4,
                      obs_interval=3600.0)
    root.update_tree(ids)
    root.start(register=True)
    mid = WatchRelay(_client(store), ids[1], branching=4,
                     obs_interval=3600.0)
    mid.update_tree(ids)
    mid.start(register=True)
    try:
        assert mid._parent_endpoints() == [root.endpoint]
        # a leaf pod's local candidates: its own relay first, then the
        # ancestors the map dictates
        assert mid.attachment_candidates()[0] == mid.endpoint
    finally:
        mid.stop()
        root.stop()


# -- upward: leases + obs ------------------------------------------------


def test_lease_coalescing_through_relay(store):
    c = _client(store)
    root = _start_relay(store, "p0", [])
    att = RelayAttachment(lambda: [root.endpoint], pod_id="leaf")
    try:
        lids = [c.lease_grant(30.0) for _ in range(3)]
        verdicts = att.lease_refresh_many(lids)
        assert verdicts == {lid: True for lid in lids}
        # the relay holds a verdict for each lease that beat through it
        assert root.stats()["child_leases"] == 3
        # a dead lease comes back False once the upstream batch runs
        c.lease_revoke(lids[0])
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            verdicts = att.lease_refresh_many(lids)
            if verdicts and verdicts[lids[0]] is False:
                break
            time.sleep(0.2)
        assert verdicts[lids[0]] is False
        assert verdicts[lids[1]] is True
    finally:
        att.close()
        root.stop()


def _beat(att, lid, period, until, read=None):
    """Beat ``lid`` through ``att`` every ``period`` s until the
    monotonic time ``until``; returns what ``read()`` saw at each beat
    (one entry a beat)."""
    seen = []
    while time.monotonic() < until:
        assert att.lease_refresh_many([lid]) == {lid: True}
        seen.append(read and read())
        time.sleep(period)
    return seen


def test_stopped_child_lease_dies_with_its_ttl(store):
    """A relay refreshes a lease upstream only for a beat that reached
    it: a leaf that falls silent loses its registration within its ttl
    (+ a window), however hard its sibling goes on beating through the
    same relay. Read from the registry — what the leader's generator
    sees — because a direct refresh would itself be a beat."""
    ttl = 3.0
    c = _client(store)
    root = _start_relay(store, "p0", [])
    atts = [RelayAttachment(lambda: [root.endpoint], pod_id=p)
            for p in ("a", "b")]
    try:
        lids = [c.set_server_with_lease("pods", p, "x", ttl)
                for p in ("a", "b")]
        assert atts[0].lease_refresh_many(lids[:1]) == {lids[0]: True}
        stopped = time.monotonic()  # leaf a's last beat
        _beat(atts[1], lids[1], ttl / 3, stopped + ttl + 2.0)
        assert dict(c.get_service("pods")) == {"b": "x"}
        assert c.lease_refresh_many(lids, relay=False) \
            == {lids[0]: False, lids[1]: True}
    finally:
        for att in atts:
            att.close()
        root.stop()


def test_live_child_lease_never_lapses_and_beats_coalesce(store):
    """The other half of the contract: leaves beating at ttl/3 through
    one relay for 10 ttls keep their keys at every read; the relay goes
    upstream at most once a LEASE_COALESCE_S window (plus the one
    synchronous batch per fresh id); and a lease rides upstream no more
    often than its owner beat — the faster sibling's batches do not
    carry the slower one's id."""
    import threading

    ttls = {"a": 6.0, "b": 3.0}
    c = _client(store)
    root = _start_relay(store, "p0", [])
    calls = []
    upstream = root._upstream_refresh

    def counted(ids):
        calls.append(list(ids))
        return upstream(ids)

    root._upstream_refresh = counted
    atts = {p: RelayAttachment(lambda: [root.endpoint], pod_id=p)
            for p in ttls}
    try:
        lids = {p: c.set_server_with_lease("pods", p, "x", ttl)
                for p, ttl in ttls.items()}
        t0 = time.monotonic()
        fast = threading.Thread(target=_beat, args=(
            atts["b"], lids["b"], ttls["b"] / 3, t0 + 30.0))
        fast.start()
        time.sleep(0.5)  # out of phase: a's beats land mid-window
        seen = _beat(atts["a"], lids["a"], ttls["a"] / 3, t0 + 30.0,
                     read=lambda: sorted(dict(c.get_service("pods"))))
        fast.join()
        elapsed = time.monotonic() - t0
        assert len(seen) >= 10 and all(s == ["a", "b"] for s in seen), \
            seen
        assert c.lease_refresh_many(list(lids.values()), relay=False) \
            == {lid: True for lid in lids.values()}
        assert len(calls) <= elapsed / root.LEASE_COALESCE_S + 2, \
            (len(calls), elapsed)
        assert sum(lids["a"] in b for b in calls) <= len(seen), calls
    finally:
        for att in atts.values():
            att.close()
        root.stop()


def test_obs_aggregation_one_store_doc(store):
    """Two leaves publish through mid; mid folds into obs_agg/v1 and
    pushes to root; root writes ONE store doc carrying both per-pod
    cells plus the fleet rollup."""
    c = _client(store)
    root = _start_relay(store, "p0", [])
    mid = _start_relay(store, "p1", [root.endpoint])
    att = RelayAttachment(lambda: [mid.endpoint], pod_id="leaf")
    try:
        for pod in ("p2", "p3"):
            doc = {"schema": "obs_pub/v1", "key": "obs_" + pod,
                   "ts": time.time(), "metrics": {}, "events": []}
            assert att.obs_publish("metrics", "obs_" + pod,
                                   json.dumps(doc))
        assert mid.flush_once() is not None   # -> pushed to root
        agg = root.flush_once()               # -> ONE store write
        assert agg["schema"] == "obs_agg/v1"
        assert set(agg["pods"]) == {"obs_p2", "obs_p3"}
        assert "fleet" in agg  # the root-only rollup
        stored = json.loads(c.get_value("metrics", "obs_agg_p0"))
        assert stored["schema"] == "obs_agg/v1"
        assert set(stored["pods"]) == {"obs_p2", "obs_p3"}
    finally:
        att.close()
        mid.stop()
        root.stop()


class _FakeCoord(object):
    """get_service-only stand-in for the monitor's _read_docs path."""

    def __init__(self):
        self.kvs = {}

    def get_service(self, service):
        return sorted(self.kvs.items())


def _expand_through_monitor(docs):
    """Round-trip {pod: obs_pub doc} through ONE obs_agg/v1 store doc
    and the monitor's _read_docs expansion."""
    fake = _FakeCoord()
    agg = {"schema": "obs_agg/v1", "key": "obs_agg_pod-00",
           "ts": max(d["ts"] for d in docs.values()), "relay": "pod-00",
           "pods": {"obs_" + pod: doc for pod, doc in docs.items()}}
    fake.kvs["obs_agg_pod-00"] = json.dumps(agg)
    reader = obs_health.HealthMonitor(coord=fake, pod_id="reader",
                                      events=obs_events.EventLog(),
                                      clock=lambda: 1_000_000.0)
    return reader._read_docs()


def test_health_monitor_flags_same_straggler_via_agg_docs():
    """The acceptance pin for the upward path: the straggler detector
    reaches the SAME verdict (same pod, same window — well inside the
    <=2-interval bound) whether the docs arrive flat or relay-folded,
    because obs_agg/v1 keeps per-pod cells instead of pre-averaging."""
    steps = {"pod-%02d" % p: (600.0 if p == 3 else 100.0)
             for p in range(4)}

    def flagged_window(fold):
        monitor = obs_health.HealthMonitor(
            coord=None, pod_id="m", interval=10.0,
            events=obs_events.EventLog(), clock=lambda: 1_000_000.0)
        state = {}
        for w in range(4):
            docs = obs_bench._synth_fleet_docs(4, w, steps, state,
                                               1_000_000.0, 10.0)
            if fold:
                expanded = _expand_through_monitor(docs)
                assert expanded == docs  # lossless per-pod round-trip
                docs = expanded
            report = monitor.evaluate(docs, now=1_000_000.0 + w * 10.0)
            if report["fleet"]["pods_degraded"]:
                return w, tuple(report["fleet"]["pods_degraded"])
        return None, ()

    flat_w, flat_pods = flagged_window(fold=False)
    agg_w, agg_pods = flagged_window(fold=True)
    assert flat_pods == agg_pods == ("pod-03",)
    assert flat_w == agg_w  # identical data -> identical window
    assert abs(agg_w - flat_w) <= 2  # the ISSUE's interval bound


def test_store_watch_dropped_counter(store):
    """The store.watch.deliver drop branch is observable: suppressed
    deliveries tick edl_store_watch_dropped_total."""
    from edl_tpu.coordination import store as store_mod

    c = _client(store)
    before = store_mod._WATCH_DROPPED.value
    plane = faults.FaultPlane(seed=3)
    plane.inject("store.watch.deliver", "drop", times=1)
    plane.install()
    try:
        evs, _ = c.wait_events(PREFIX, c.revision(), 0.1, relay=False)
        assert evs == []  # the drop looks like a timed-out poll
    finally:
        plane.uninstall()
    assert store_mod._WATCH_DROPPED.value == before + 1


def test_relay_counters_registered():
    """The zero-loss drill reads these families by name; renaming them
    breaks the bench and the ops docs."""
    fams = obs_metrics.REGISTRY.families()
    for name in ("edl_relay_children_total",
                 "edl_relay_events_forwarded_total",
                 "edl_relay_reattaches_total",
                 "edl_store_watch_dropped_total"):
        assert name in fams, name
