"""ClientPool tests: shared-client checkout, traffic-class channels,
idle reaping vs leases, retire-on-error invalidation, and lifecycle.
The pool is the data plane's connection substrate (docs/data_plane.md);
these pin the lifecycle behaviors the readers rely on."""

import threading
import time

import pytest

from edl_tpu.rpc.pool import ClientPool
from edl_tpu.rpc.server import RpcServer
from edl_tpu.utils import errors


@pytest.fixture()
def server():
    srv = RpcServer()
    srv.register("echo", lambda x: x)
    srv.register("block", lambda s: time.sleep(s) or "done")
    srv.start()
    yield srv
    srv.stop()


def _wait(pred, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(interval)
    return False


def test_pool_shares_one_client_per_endpoint(server):
    with ClientPool() as pool:
        a = pool.get(server.endpoint)
        b = pool.get(server.endpoint)
        assert a is b                       # one client carries everyone
        assert pool.call(server.endpoint, "echo", 7) == 7
        assert pool.stats() == {"open": 1, "dials": 1, "reaps": 0,
                                "retires": 0}


def test_pool_channels_are_distinct_connections(server):
    with ClientPool() as pool:
        ctl = pool.get(server.endpoint, channel="ctl")
        assign = pool.get(server.endpoint, channel="assign")
        assert ctl is not assign
        # both channels work independently against the same endpoint
        assert pool.call(server.endpoint, "echo", 1, channel="ctl") == 1
        assert pool.call(server.endpoint, "echo", 2,
                         channel="assign") == 2
        assert pool.stats() == {"open": 2, "dials": 2, "reaps": 0,
                                "retires": 0}


def test_pool_call_async_pipelines(server):
    with ClientPool() as pool:
        futs = [pool.call_async(server.endpoint, "echo", i)
                for i in range(8)]
        assert [f.result() for f in futs] == list(range(8))
        assert pool.stats()["dials"] == 1   # all rode one connection


def test_pool_idle_reap_and_redial(server):
    with ClientPool(idle_ttl=0.3, reap_interval=0.05) as pool:
        assert pool.call(server.endpoint, "echo", 1) == 1
        assert pool.stats()["open"] == 1
        # idle past the ttl: the reaper closes and drops the client
        assert _wait(lambda: pool.stats()["open"] == 0)
        # next caller transparently redials
        assert pool.call(server.endpoint, "echo", 2) == 2
        assert pool.stats() == {"open": 1, "dials": 2, "reaps": 1,
                                "retires": 0}


def test_pool_lease_blocks_reaper(server):
    with ClientPool(idle_ttl=0.2, reap_interval=0.05) as pool:
        with pool.lease(server.endpoint) as client:
            time.sleep(0.6)  # well past the ttl while leased
            assert pool.stats()["open"] == 1
            assert client.call("echo", 3) == 3  # never closed under us
        # released: now the reaper may take it
        assert _wait(lambda: pool.stats()["open"] == 0)
        assert pool.stats()["dials"] == 1


def test_pool_features_probed_once_and_cached(server):
    with ClientPool() as pool:
        feats = pool.features(server.endpoint)
        assert "rpc.pipeline" in feats
        assert pool.features(server.endpoint) is feats  # cached object


def test_pool_features_empty_for_legacy_peer(server):
    # a pre-pipelining peer advertises nothing; the probe must come
    # back empty rather than raising (the negotiation fallback signal)
    server.register("__features__", lambda: [])
    with ClientPool() as pool:
        assert pool.features(server.endpoint) == ()


def test_pool_retire_drops_all_channels_and_features(server):
    with ClientPool() as pool:
        pool.call(server.endpoint, "echo", 1, channel="ctl")
        pool.call(server.endpoint, "echo", 1, channel="hb")
        assert pool.features(server.endpoint)  # default-channel probe
        assert pool.stats() == {"open": 3, "dials": 3, "reaps": 0,
                                "retires": 0}
        pool.retire(server.endpoint)
        assert pool.stats()["open"] == 0    # every channel dropped
        assert pool.stats()["retires"] == 3
        assert pool._features == {}         # cache invalidated
        # next checkout redials fresh (peer may be a new generation)
        assert pool.call(server.endpoint, "echo", 2) == 2
        assert pool.stats()["dials"] == 4


def test_pool_close_idempotent_and_rejects_checkout(server):
    pool = ClientPool()
    assert pool.call(server.endpoint, "echo", 1) == 1
    pool.close()
    pool.close()  # idempotent
    with pytest.raises(errors.StatusError, match="closed"):
        pool.get(server.endpoint)
    with pytest.raises(errors.StatusError, match="closed"):
        pool.call(server.endpoint, "echo", 1)


def test_pool_close_fails_inflight_calls(server):
    # an owner's stop() relies on this: closing the pool unblocks any
    # thread parked in a pooled RPC instead of waiting out its timeout
    pool = ClientPool(timeout=30.0)
    result = {}

    def blocked():
        try:
            result["v"] = pool.call(server.endpoint, "block", 5.0)
        except errors.EdlError as e:
            result["v"] = e

    t = threading.Thread(target=blocked)
    t.start()
    _wait(lambda: pool.stats()["open"] == 1)
    time.sleep(0.1)  # let the call get onto the wire
    t0 = time.monotonic()
    pool.close()
    t.join(timeout=4)
    assert not t.is_alive()
    assert time.monotonic() - t0 < 4  # did not sit out the 5s handler
    assert isinstance(result["v"], errors.EdlError)
