"""Watch relay trees: O(log N) control-plane fan-out at fleet scale.

Flat topology costs the store O(N) work per control-plane beat: every
pod long-polls ``store_wait_events`` directly, refreshes its leases
directly, and writes its own ``obs_pub/v1`` doc every tick.  This
module applies the two classic fixes on top of our revision-resumable
watch protocol — ZooKeeper-style observer fan-out for the downward
path and Astrolabe-style in-network aggregation for the upward path:

- **Downward (watch fan-out)**: each pod hosts a :class:`WatchRelay`
  that holds ONE upstream ``wait_events`` long-poll per watched prefix
  — against the store for the root relay, against its parent relay
  otherwise — and serves its children's long-polls from a local
  revision-ordered event cache.  The tree is a deterministic B-ary
  heap over the SORTED pod-id list (parent of index ``i`` is index
  ``(i - 1) // B``), so every pod derives the same depth-⌈log_B N⌉
  topology from the cluster map alone, with no negotiation round.

- **Upward (lease + obs coalescing)**: children's
  ``lease_refresh_many`` beats are folded into one upstream batch per
  coalesce window, and ``obs_pub/v1`` docs are folded into
  ``obs_agg/v1`` docs that KEEP per-pod cells (straggler/staleness
  detectors still see individual pods) — the root writes one store doc
  per tick instead of N.

Failover is lossless by construction: children attach via feature
negotiation (``coord.relay`` in ``__features__``; relays advertise
under a TTL lease in ``SERVICE_RELAY``) and fall through to the direct
store path whenever no relay answers.  Because every consumer resumes
from its OWN ``since_rev``, a relay kill can delay an event but never
lose one — the reattached child replays the gap from the grandparent
or the store.  Kill switch: ``EDL_TPU_RELAY=0`` disables hosting and
attaching entirely (the fleet reverts to flat long-polls).

Fault points: ``relay.attach`` (child side, when an attachment adopts
a relay endpoint; ctx: endpoint, pod) and ``relay.forward`` (relay
side, before a child long-poll is served; ctx: prefix, child — a
``drop`` looks like a timed-out poll, an ``error`` forces the child
through the reattach path).  See docs/fault_tolerance.md.
"""

import json
import math
import os
import threading
import time

from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.robustness import faults
from edl_tpu.robustness.policy import RetryPolicy
from edl_tpu.rpc.client import RpcClient
from edl_tpu.rpc.server import FEATURES, RpcServer
from edl_tpu.utils import errors
from edl_tpu.utils.logger import logger

#: feature-negotiation token: servers that can serve relayed
#: ``relay_wait_events`` / ``relay_obs_publish`` /
#: ``relay_lease_refresh_many`` advertise it via ``__features__``
FEATURE = "coord.relay"

#: value of controller.constants.SERVICE_RELAY, inlined so coordination
#: stays below controller in the layering (guarded by a drift test)
SERVICE_RELAY = "relay"

#: branching factor B of the relay tree (heap arity)
DEFAULT_BRANCHING = int(os.environ.get("EDL_TPU_RELAY_BRANCH", "16"))

# zero-loss accounting for the relay chaos drill: the drill asserts
# reattaches happened AND no event went missing, from metrics not logs
_CHILDREN = obs_metrics.counter(
    "edl_relay_children_total", "distinct children that attached to "
    "this relay")
_FORWARDED = obs_metrics.counter(
    "edl_relay_events_forwarded_total", "events served to children "
    "from the local cache")
_REATTACHES = obs_metrics.counter(
    "edl_relay_reattaches_total", "child-side endpoint switches: a "
    "relay died (or refused) and the attachment moved to the next "
    "ancestor / the direct store path")


def enabled():
    """The kill switch: ``EDL_TPU_RELAY=0`` turns the whole subsystem
    off (no hosting, no attaching — flat direct long-polls)."""
    return os.environ.get("EDL_TPU_RELAY", "1") != "0"


# -- the deterministic tree ---------------------------------------------


def tree_parent(pod_ids, pod_id, branching=None):
    """Parent pod id of ``pod_id`` in the B-ary heap over the sorted
    pod list; None for the root (index 0). Every pod computes the same
    tree from the same cluster map — no negotiation, no tie-breaks."""
    b = int(branching or DEFAULT_BRANCHING)
    ids = sorted(pod_ids)
    i = ids.index(pod_id)
    if i == 0:
        return None
    return ids[(i - 1) // b]


def tree_ancestors(pod_ids, pod_id, branching=None):
    """Ancestor chain parent → root (the reattach candidate order)."""
    out = []
    cur = pod_id
    while True:
        cur = tree_parent(pod_ids, cur, branching)
        if cur is None:
            return out
        out.append(cur)


def tree_depth(n, branching=None):
    """⌈log_B N⌉: levels below the root for an ``n``-pod fleet."""
    b = int(branching or DEFAULT_BRANCHING)
    if n <= 1:
        return 0
    return int(math.ceil(math.log(n) / math.log(b)))


# -- child side: the attachment -----------------------------------------


class RelayAttachment(object):
    """The child half of the protocol: routes a CoordClient's
    long-polls, keepalive beats, and obs publishes through the first
    live, feature-negotiated relay in ``resolver()``'s candidate list
    (parent first, then grandparent, ... root).

    Every method returns None when no relay is usable so the caller
    falls through to its direct store path — attachment failure is
    never an error, only a topology downgrade.  The adopted endpoint
    is sticky: ``resolver()`` is only re-invoked when the current
    endpoint fails (or :meth:`invalidate` is called after a resize),
    so the steady state adds zero store reads.
    """

    def __init__(self, resolver, pod_id=None, timeout=30.0,
                 retry_bad_after=10.0):
        self._resolver = resolver
        self._pod_id = None if pod_id is None else str(pod_id)
        self._timeout = float(timeout)
        self._retry_bad_after = float(retry_bad_after)
        self._lock = threading.Lock()
        self._bad = {}        # endpoint -> monotonic mark time
        self._legacy = set()  # endpoints that lack FEATURE (permanent)
        self._current = None
        self._local = threading.local()

    # -- transport (per-thread clients: a relayed long-poll must not
    # -- serialize against keepalive beats from other threads) ---------

    def _client_for(self, endpoint):
        cache = getattr(self._local, "rpcs", None)
        if cache is None:
            cache = self._local.rpcs = {}
        rpc = cache.get(endpoint)
        if rpc is None:
            rpc = cache[endpoint] = RpcClient(endpoint,
                                              timeout=self._timeout)
        return rpc

    def _drop_client(self, endpoint):
        cache = getattr(self._local, "rpcs", None)
        rpc = cache.pop(endpoint, None) if cache else None
        if rpc is not None:
            rpc.close()

    # -- candidate management ------------------------------------------

    def current(self):
        with self._lock:
            return self._current

    def invalidate(self):
        """Drop the sticky endpoint (topology changed — e.g. a resize
        recomputed the tree); the next call re-resolves candidates."""
        with self._lock:
            self._current = None
            self._bad.clear()

    def _candidates(self):
        try:
            eps = list(self._resolver() or ())
        except Exception as e:  # noqa: BLE001 — resolver is best-effort
            logger.debug("relay resolver failed: %r", e)
            return []
        now = time.monotonic()
        with self._lock:
            out = []
            for ep in eps:
                if ep in self._legacy:
                    continue
                marked = self._bad.get(ep)
                if marked is not None \
                        and now - marked < self._retry_bad_after:
                    continue
                out.append(ep)
            return out

    def _mark_bad(self, endpoint):
        with self._lock:
            self._bad[endpoint] = time.monotonic()
            was_current = self._current == endpoint
            if was_current:
                self._current = None
        self._drop_client(endpoint)
        if was_current:
            # the switch away from a previously-adopted relay IS the
            # reattach the chaos drill counts (whether the next stop is
            # an ancestor or the direct store path)
            _REATTACHES.inc()
            logger.warning("relay %s unusable; reattaching", endpoint)

    def _negotiated(self, endpoint, rpc):
        """Feature negotiation: a registered endpoint that does not
        advertise ``coord.relay`` (a legacy peer) is permanently
        skipped — its children use the direct path."""
        try:
            feats = rpc.server_features()
        except (errors.EdlError, ConnectionError, OSError):
            return False
        if FEATURE not in feats:
            with self._lock:
                self._legacy.add(endpoint)
            return False
        return True

    def _try_endpoint(self, endpoint, adopting, method, args, timeout):
        """(served, result): one attempt against one endpoint."""
        if adopting and faults.PLANE is not None:
            try:
                faults.PLANE.fire("relay.attach", endpoint=endpoint,
                                  pod=self._pod_id or "")
            except Exception:  # noqa: BLE001 — injected attach error
                self._mark_bad(endpoint)
                return False, None
        rpc = self._client_for(endpoint)
        if adopting and not self._negotiated(endpoint, rpc):
            return False, None
        try:
            out = rpc.call(method, *args,
                           timeout=timeout or self._timeout)
        except (errors.EdlError, ConnectionError, OSError):
            self._mark_bad(endpoint)
            return False, None
        if adopting:
            with self._lock:
                self._current = endpoint
        return True, out

    def _call(self, method, *args, timeout=None):
        """One relayed call with ancestor fall-through; None means no
        relay is usable and the caller must go direct. Fast path: the
        sticky adopted endpoint, no resolver invocation; slow path
        (adoption) walks ``resolver()``'s candidates in order."""
        cur = self.current()
        if cur is not None:
            served, out = self._try_endpoint(cur, False, method, args,
                                             timeout)
            if served:
                return out
        for endpoint in self._candidates():
            if endpoint == cur:
                continue
            served, out = self._try_endpoint(endpoint, True, method,
                                             args, timeout)
            if served:
                return out
        return None

    # -- the relayed surface -------------------------------------------

    def wait_events(self, prefix, since_rev, poll_timeout):
        """Relayed long-poll; None → caller falls through direct. The
        child keeps its own ``since_rev`` cursor, so a mid-stream
        reattach resumes exactly where the dead relay left it."""
        return self._call("relay_wait_events", prefix, since_rev,
                          poll_timeout, self._pod_id,
                          timeout=float(poll_timeout) + 30.0)

    def lease_refresh_many(self, lease_ids):
        """Relayed keepalive beat ({lease_id: ok}); None → go direct."""
        pairs = self._call("relay_lease_refresh_many", list(lease_ids),
                           self._pod_id)
        if pairs is None:
            return None
        return {int(lid): bool(ok) for lid, ok in pairs}

    def obs_publish(self, service, key, value):
        """Hand an obs doc to the relay for subtree aggregation; False
        → caller writes the store directly."""
        return bool(self._call("relay_obs_publish", service, key, value,
                               self._pod_id))

    def close(self):
        cache = getattr(self._local, "rpcs", None)
        for rpc in (cache or {}).values():
            try:
                rpc.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        if cache:
            cache.clear()


# -- relay side ----------------------------------------------------------


class _Feed(object):
    """Per-prefix event cache: a rev-ordered window mirrored from the
    upstream watch.  ``floor`` is the oldest rev the cache can replay
    from; a child whose ``since_rev`` fell below it is told to reset
    (re-list) exactly like the store would."""

    __slots__ = ("prefix", "events", "floor", "rev", "waiters",
                 "last_wait", "retired")

    def __init__(self, prefix, since_rev):
        self.prefix = prefix
        self.events = []
        self.floor = since_rev
        self.rev = since_rev
        self.waiters = 0
        self.last_wait = time.monotonic()
        self.retired = False


class WatchRelay(object):
    """One pod's relay: serves children from a local event cache fed
    by ONE upstream long-poll per prefix, coalesces children's lease
    beats into one upstream batch, and folds children's obs docs into
    one ``obs_agg/v1`` doc per tick.

    ``coord``: a CoordClient for DIRECT store access (registration,
    root-level upstream, root-level agg writes).  ``parent_resolver``:
    optional override returning candidate parent endpoints; by default
    ancestors are computed from :meth:`update_tree`'s pod list and the
    ``SERVICE_RELAY`` registry.
    """

    #: events kept per prefix before the floor advances (children
    #: falling further behind re-list, same contract as the store)
    EVENT_HISTORY = 4096
    #: upstream long-poll timeout (a pump holds one of these open)
    UPSTREAM_POLL_S = 20.0
    #: cap on a child's single long-poll wait
    MAX_CHILD_WAIT_S = 60.0
    #: a feed with no waiter for this long retires its pump
    FEED_IDLE_S = 90.0
    #: min gap between upstream lease batches; a ttl must exceed it
    LEASE_COALESCE_S = 1.0
    #: forget a lease's verdict this long after its last upstream batch
    LEASE_FORGET_S = 120.0
    #: drop obs cells whose publisher went silent for this long (far
    #: beyond the staleness detector's threshold, so dead pods are
    #: flagged stale long before their cell disappears)
    CELL_PRUNE_S = 900.0
    #: cache ttl for the default parent-endpoint resolution (bounds
    #: registry reads from the pumps)
    RESOLVE_CACHE_S = 5.0

    def __init__(self, coord, pod_id, branching=None, host="0.0.0.0",
                 service=SERVICE_RELAY, register_ttl=10.0,
                 obs_service="metrics", obs_interval=10.0,
                 parent_resolver=None):
        self._coord = coord
        self._pod_id = str(pod_id)
        self._branching = int(branching or DEFAULT_BRANCHING)
        self._service = service
        self._register_ttl = float(register_ttl)
        self._obs_service = obs_service
        self._obs_interval = float(obs_interval)
        self._agg_key = "obs_agg_" + self._pod_id
        self._rpc = RpcServer(host=host, port=0)
        self._rpc.register("relay_wait_events", self.relay_wait_events)
        self._rpc.register("relay_obs_publish", self.relay_obs_publish)
        self._rpc.register("relay_lease_refresh_many",
                           self.relay_lease_refresh_many)
        self._rpc.register("__features__",
                           lambda: list(FEATURES) + [FEATURE])
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._feeds = {}         # prefix -> _Feed
        self._children = set()   # child ids seen (metrics only)
        self._cells = {}         # obs key -> obs_pub/v1 doc
        self._child_leases = set()  # beat since the last upstream batch
        self._lease_verdicts = {}   # lease_id -> (ok, monotonic of batch)
        self._last_lease_beat = 0.0
        self._resolved = (0.0, [])  # (monotonic, endpoints) cache
        self._pod_ids = []
        self._lease = None
        self._stop = threading.Event()
        self._flush_thread = None
        self._retry = RetryPolicy(base_delay=0.25, max_delay=2.0,
                                  multiplier=2.0, jitter=0.5)
        self._up = RelayAttachment(
            parent_resolver if parent_resolver is not None
            else self._parent_endpoints,
            pod_id=self._pod_id)

    # -- lifecycle ------------------------------------------------------

    def start(self, register=True):
        self._rpc.start()
        # cache: the advertised endpoint must stay readable after
        # stop() — kill drills and resolvers hold it as a plain string
        self._endpoint = self._rpc.endpoint
        self._flush_thread = threading.Thread(
            target=self._flush_loop, daemon=True,
            name="relay-obs-%s" % self._pod_id)
        self._flush_thread.start()
        if register:
            self._register()
        return self

    def _register(self):
        from edl_tpu.coordination import keepalive
        try:
            self._lease = self._coord.set_server_with_lease(
                self._service, self._pod_id, self.endpoint,
                self._register_ttl)
            keepalive.hub_for(self._coord).add(
                self._lease, self._register_ttl, on_lost=self._relost)
        except errors.EdlError as e:
            # advertising is best-effort: an unregistered relay simply
            # never gets children; the fleet stays on the direct path
            logger.warning("relay %s failed to register: %r",
                           self._pod_id, e)

    def _relost(self):
        if not self._stop.is_set():
            logger.warning("relay %s registration lease lost; "
                           "re-registering", self._pod_id)
            self._register()

    @property
    def endpoint(self):
        ep = getattr(self, "_endpoint", None)
        return ep if ep is not None else self._rpc.endpoint

    @property
    def port(self):
        return self._rpc.port

    def update_tree(self, pod_ids):
        """Adopt a new cluster map: recompute ancestors and drop the
        sticky upstream so the next pump iteration re-resolves."""
        with self._lock:
            self._pod_ids = sorted(pod_ids)
            self._resolved = (0.0, [])
        self._up.invalidate()

    def stop(self):
        self._stop.set()
        with self._lock:
            for feed in self._feeds.values():
                feed.retired = True
            self._feeds.clear()
            self._cond.notify_all()
        if self._flush_thread is not None:
            self._flush_thread.join(timeout=5.0)
        if self._lease is not None:
            from edl_tpu.coordination import keepalive
            keepalive.hub_for(self._coord).remove(self._lease)
            try:
                self._coord.remove_server(self._service, self._pod_id)
            except errors.EdlError:
                pass
        self._up.close()
        self._rpc.stop()

    # -- upstream resolution -------------------------------------------

    def _parent_endpoints(self):
        """Default resolver: my ancestors' advertised endpoints, parent
        first.  Registry reads are cached for RESOLVE_CACHE_S and only
        happen on the slow path (no sticky upstream)."""
        now = time.monotonic()
        with self._lock:
            at, eps = self._resolved
            if now - at < self.RESOLVE_CACHE_S:
                return list(eps)
            ids = list(self._pod_ids)
        eps = []
        if ids and self._pod_id in ids:
            try:
                reg = dict(self._coord.get_service(self._service))
            except errors.EdlError:
                reg = {}
            for anc in tree_ancestors(ids, self._pod_id,
                                      self._branching):
                ep = reg.get(anc)
                if ep and ep != self.endpoint:
                    eps.append(ep)
        with self._lock:
            self._resolved = (now, list(eps))
        return eps

    def attachment_candidates(self):
        """Candidate list for THIS pod's local clients: the pod-local
        relay first, then its ancestors — so if the local relay dies
        the clients walk the same chain the relay itself would."""
        return [self.endpoint] + self._parent_endpoints()

    def _upstream_wait(self, prefix, since_rev, timeout):
        out = self._up.wait_events(prefix, since_rev, timeout)
        if out is not None:
            return out
        return self._coord.wait_events(prefix, since_rev, timeout,
                                       relay=False)

    # -- downward: the fan-out path ------------------------------------

    def _feed_for(self, prefix, since_rev):
        with self._lock:
            feed = self._feeds.get(prefix)
            if feed is None:
                feed = self._feeds[prefix] = _Feed(prefix, since_rev)
                threading.Thread(
                    target=self._pump, args=(feed,), daemon=True,
                    name="relay-pump-%s" % self._pod_id).start()
            feed.last_wait = time.monotonic()
            return feed

    def _pump(self, feed):
        """ONE upstream long-poll per prefix — the whole point: N
        children share this single store-side (or parent-side) poll."""
        attempts = 0
        while not self._stop.is_set():
            with self._lock:
                if feed.retired:
                    return
                if feed.waiters == 0 and (time.monotonic()
                                          - feed.last_wait
                                          > self.FEED_IDLE_S):
                    feed.retired = True
                    self._feeds.pop(feed.prefix, None)
                    return
                since = feed.rev
            try:
                events, rev = self._upstream_wait(
                    feed.prefix, since, self.UPSTREAM_POLL_S)
            except (errors.EdlError, ConnectionError, OSError) as e:
                attempts += 1
                logger.debug("relay %s pump %s upstream error: %r",
                             self._pod_id, feed.prefix, e)
                self._retry.sleep(min(attempts, 6))
                continue
            attempts = 0
            with self._lock:
                if events and any(e.get("type") == "reset"
                                  for e in events):
                    # upstream lost our position: our whole cache is
                    # unverifiable — raise the floor so every child
                    # re-lists (each from the store, which is exactly
                    # what the store itself would have told them)
                    feed.events = []
                    feed.floor = rev
                    feed.rev = rev
                elif events:
                    feed.events.extend(events)
                    feed.rev = max(feed.rev, rev)
                    overflow = len(feed.events) - self.EVENT_HISTORY
                    if overflow > 0:
                        feed.floor = feed.events[overflow - 1]["rev"]
                        del feed.events[:overflow]
                else:
                    feed.rev = max(feed.rev, rev)
                self._cond.notify_all()

    def relay_wait_events(self, prefix, since_rev, timeout, child=None):
        """The child-facing mirror of ``store_wait_events``: same
        (events, rev) shape, same timeout-means-empty, same synthetic
        reset when ``since_rev`` predates the cache floor."""
        since_rev = int(since_rev)
        if faults.PLANE is not None:
            f = faults.PLANE.fire("relay.forward", prefix=prefix,
                                  child=str(child or ""))
            if f is not None and f.kind == "drop":
                # dropped delivery == timed-out poll; the child keeps
                # its cursor and polls again (no loss, only delay)
                return [], since_rev
        if child:
            with self._lock:
                if child not in self._children:
                    self._children.add(child)
                    _CHILDREN.inc()
        feed = self._feed_for(prefix, since_rev)
        deadline = time.monotonic() + min(float(timeout),
                                          self.MAX_CHILD_WAIT_S)
        with self._lock:
            feed.waiters += 1
            try:
                while True:
                    if feed.retired:
                        # relay shutting down: look like a timeout; the
                        # child's next poll reattaches elsewhere
                        return [], since_rev
                    if since_rev < feed.floor:
                        return ([{"type": "reset", "key": prefix,
                                  "value": None, "rev": feed.rev}],
                                feed.rev)
                    evs = [e for e in feed.events
                           if e["rev"] > since_rev
                           and e.get("key", "").startswith(prefix)]
                    if evs:
                        _FORWARDED.inc(len(evs))
                        return evs, max(feed.rev, since_rev)
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        # never hand back a rev below the child's own
                        # cursor: a lagging cache must not regress it
                        return [], max(feed.rev, since_rev)
                    self._cond.wait(remaining)
            finally:
                feed.waiters -= 1
                feed.last_wait = time.monotonic()

    # -- upward: lease coalescing --------------------------------------

    def _upstream_refresh(self, lease_ids):
        res = self._up.lease_refresh_many(lease_ids)
        if res is None:
            res = self._coord.lease_refresh_many(lease_ids, relay=False)
        return {int(lid): bool(ok) for lid, ok in res.items()}

    def relay_lease_refresh_many(self, lease_ids, child=None):
        """Coalesced keepalive: children's beats are merged into ONE
        upstream ``lease_refresh_many`` per LEASE_COALESCE_S window. It
        carries the ids that beat since the previous batch and no
        others, so a silent owner's lease dies with its ttl. An id we
        have no verdict for yet forces a synchronous batch (fresh
        registrations must learn their fate immediately); known ids
        between windows are answered from the cached verdicts — one
        beat of staleness, inside the ttl/3 beat slack."""
        now = time.monotonic()
        ids = [int(lid) for lid in lease_ids]
        batch = None
        with self._lock:
            self._child_leases.update(ids)
            if (now - self._last_lease_beat >= self.LEASE_COALESCE_S
                    or not self._lease_verdicts.keys() >= set(ids)):
                batch = sorted(self._child_leases)
                self._last_lease_beat = now
        if batch is not None:
            verdicts = self._upstream_refresh(batch)
            with self._lock:  # a failed batch's beats ride the next one
                self._child_leases.difference_update(batch)
                self._lease_verdicts.update(
                    (lid, (ok, now)) for lid, ok in verdicts.items())
                for lid in [l for l, v in self._lease_verdicts.items()
                            if now - v[1] > self.LEASE_FORGET_S]:
                    del self._lease_verdicts[lid]
        with self._lock:
            return [[lid, self._lease_verdicts.get(lid, (True,))[0]]
                    for lid in ids]

    # -- upward: obs aggregation ---------------------------------------

    def relay_obs_publish(self, service, key, value, child=None):
        """Absorb one obs doc (a leaf's ``obs_pub/v1`` or a child
        relay's ``obs_agg/v1``) into the per-pod cell map; the flush
        loop folds the subtree upward."""
        try:
            doc = json.loads(value)
        except (ValueError, TypeError):
            return False
        if not isinstance(doc, dict):
            return False
        with self._lock:
            if service:
                self._obs_service = service
            if doc.get("schema") == "obs_agg/v1":
                for cell_key, cell in (doc.get("pods") or {}).items():
                    if not isinstance(cell, dict):
                        continue
                    prev = self._cells.get(cell_key)
                    if prev is None or ((cell.get("ts") or 0)
                                        >= (prev.get("ts") or 0)):
                        self._cells[cell_key] = cell
            else:
                self._cells[key] = doc
        return True

    def _flush_loop(self):
        while not self._stop.wait(self._obs_interval):
            try:
                self.flush_once()
            except Exception as e:  # noqa: BLE001 — obs is best-effort
                logger.debug("relay %s obs flush failed: %r",
                             self._pod_id, e)

    def flush_once(self):
        """Fold the subtree's cells into one ``obs_agg/v1`` doc and
        push it to the parent relay, or — at the root / with no parent
        reachable — write ONE doc to the store (the N→N/B^depth win)."""
        now = time.time()
        with self._lock:
            for k in [k for k, c in self._cells.items()
                      if now - (c.get("ts") or now) > self.CELL_PRUNE_S]:
                del self._cells[k]
            cells = dict(self._cells)
            service = self._obs_service
        if not cells:
            return None
        agg = {"schema": "obs_agg/v1", "key": self._agg_key, "ts": now,
               "relay": self._pod_id, "pods": cells}
        if self._up.obs_publish(service, self._agg_key,
                                json.dumps(agg)):
            return agg
        # root of the tree (or orphaned mid-relay): merge the per-pod
        # snapshots into a fleet rollup and write a single store doc
        from edl_tpu.obs import metrics as metrics_mod
        snaps = {k: (c.get("metrics") or {}) for k, c in cells.items()}
        agg["fleet"] = metrics_mod.merge_snapshots(snaps)
        self._coord.set_server_permanent(service, self._agg_key,
                                         json.dumps(agg))
        return agg

    # -- introspection (tests / bench) ---------------------------------

    def stats(self):
        with self._lock:
            return {"pod": self._pod_id,
                    "children": len(self._children),
                    "feeds": len(self._feeds),
                    "cells": len(self._cells),
                    "child_leases": len(self._lease_verdicts)}  # known
