"""Async (snapshot-then-stream) checkpoint engine tests: two-phase
save handles, max_inflight=1 back-pressure, stream-format round-trips
(dense, sharded, placed), crash injection mid-persist (uncommitted →
cleaned → fallback), corrupt-entry CRC fallback, and the
PreemptionGuard drain-on-SIGTERM contract — parametrized over LocalFS
and the fake-GCS GCSFS where the fs shape matters."""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.runtime.checkpoint import CheckpointManager
from edl_tpu.runtime.fs import GCSFS, LocalFS


@pytest.fixture(params=["local", "gcs"])
def ckpt_fs(request, tmp_path):
    """(base_path, FileSystem) for each backend."""
    if request.param == "local":
        yield str(tmp_path), LocalFS()
    else:
        from edl_tpu.tools.fake_gcs import FakeGCSServer
        with FakeGCSServer() as srv:
            yield "gs://ckpt-bucket/job1/ckpt", GCSFS(endpoint=srv.endpoint)


class _WrapFS(object):
    """Delegating FileSystem wrapper for fault/latency injection."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _GatedFS(_WrapFS):
    """Entry-file writes block until the gate opens (persist stays
    in-flight for as long as the test needs)."""

    def __init__(self, inner, gate):
        super().__init__(inner)
        self._gate = gate

    def write_chunks(self, path, chunks):
        self._gate.wait(15)
        return self._inner.write_chunks(path, chunks)


class _FlakyFS(_WrapFS):
    """Every stream entry write dies — the writer-pool crash: data
    files fail, so the MANIFEST must never be written."""

    def write_chunks(self, path, chunks):
        raise IOError("injected writer-pool failure: %s" % path)


def _tree(seed):
    rng = np.random.RandomState(seed)
    return {
        "params": {
            "dense": {"w": rng.randn(4, 3).astype(np.float32),
                      "b": np.zeros(3, np.float32)},
            "emb": rng.randn(10, 4).astype(np.float32),
        },
        "step": np.int32(seed),
        "bf16": jnp.ones((2, 2), jnp.bfloat16) * seed,
    }


def _assert_trees_equal(a, b):
    assert np.array_equal(np.asarray(a["step"]), np.asarray(b["step"]))
    np.testing.assert_array_equal(a["params"]["dense"]["w"],
                                  b["params"]["dense"]["w"])
    np.testing.assert_array_equal(np.asarray(a["bf16"], np.float32),
                                  np.asarray(b["bf16"], np.float32))
    assert np.asarray(b["bf16"]).dtype == np.asarray(a["bf16"]).dtype


def test_async_save_restore_roundtrip(ckpt_fs):
    base, fs = ckpt_fs
    cm = CheckpointManager(base, keep=3, fs=fs)
    tree = _tree(5)
    handle = cm.save_async(5, tree, meta={"epoch": 1})
    assert handle.version == 5 and handle.blocked_s >= 0.0
    vdir = handle.result(30)
    assert handle.done() and handle.exception() is None
    assert handle.persist_s is not None
    with fs.open(vdir + "/MANIFEST", "r") as f:
        manifest = json.load(f)
    assert manifest["format"] == "stream"
    # per-entry files with per-file crcs, committed manifest-last
    assert manifest["entries"] and all(
        {"file", "crc", "dtype", "shape", "nbytes"} <= set(e)
        for e in manifest["entries"].values())
    version, restored, meta = cm.restore_latest()
    assert version == 5 and meta == {"epoch": 1}
    _assert_trees_equal(tree, restored)
    # structured restore into the original layout
    version, restored, _ = cm.restore(5, target=tree)
    _assert_trees_equal(tree, restored)
    cm.close()


def test_entries_are_written_largest_first_under_the_sorted_keys_names(
        tmp_path):
    """The order of the writes is the writer pool's business (the large
    ones, which leave the interpreter to the training thread, go first);
    the files' names, the manifest and what a restore reads are those of
    the keys in sorted order, as they always were."""
    written = []

    class _Recording(_WrapFS):
        def write_chunks(self, path, chunks):
            written.append(path.rsplit("/", 1)[1])
            return self._inner.write_chunks(path, chunks)

    cm = CheckpointManager(str(tmp_path), fs=_Recording(LocalFS()),
                           workers=1)
    tree = {"a_small": np.arange(3, dtype=np.float32),
            "b_large": np.arange(4096, dtype=np.float32),
            "c_mid": np.arange(64, dtype=np.float32),
            "d_large": np.arange(4096, dtype=np.float32) + 1.0,
            "e_none": np.zeros((0,), np.float32)}
    vdir = cm.save_async(3, tree).result(30)
    with open(vdir + "/MANIFEST") as f:
        manifest = json.load(f)
    keys = sorted(manifest["entries"])
    assert list(manifest["entries"]) == keys
    assert [manifest["entries"][k]["file"] for k in keys] == [
        "a%04d.bin" % i for i in range(5)]
    by_file = {e["file"]: e["nbytes"] for e in manifest["entries"].values()}
    # b before d: entries of one size keep the order of their keys
    assert written == ["a0001.bin", "a0003.bin", "a0002.bin", "a0000.bin",
                       "a0004.bin"]
    assert [by_file[f] for f in written] == sorted(by_file.values(),
                                                   reverse=True)
    _, restored, _ = cm.restore(3, target=tree)
    for k, v in tree.items():
        np.testing.assert_array_equal(restored[k], v)
    cm.close()


def test_async_backpressure_drains_previous(tmp_path):
    """max_inflight=1: a second save_async must BLOCK until the first
    persist lands (which is what makes host-buffer reuse safe)."""
    gate = threading.Event()
    cm = CheckpointManager(str(tmp_path), fs=_GatedFS(LocalFS(), gate))
    t1 = {"w": np.full(1024, 1.0, np.float32)}
    t2 = {"w": np.full(1024, 2.0, np.float32)}
    h1 = cm.save_async(1, t1)
    out = {}
    t = threading.Thread(
        target=lambda: out.update(h2=cm.save_async(2, t2)))
    t.start()
    time.sleep(0.3)
    assert t.is_alive() and not h1.done()  # drain() is waiting on v1
    gate.set()
    t.join(15)
    assert not t.is_alive()
    assert h1.result(15) and out["h2"].result(15)
    version, restored, _ = cm.restore_latest()
    assert version == 2
    np.testing.assert_array_equal(restored["w"], t2["w"])
    cm.close()


def test_async_snapshot_is_donation_safe(tmp_path):
    """Phase 1 copies into pooled host buffers: mutating (or donating)
    the source arrays after save_async returns must not change what is
    persisted."""
    gate = threading.Event()
    cm = CheckpointManager(str(tmp_path), fs=_GatedFS(LocalFS(), gate))
    src = {"w": np.arange(256, dtype=np.float32)}
    want = src["w"].copy()
    h = cm.save_async(3, src)
    src["w"][:] = -1.0  # "donated"/reused buffer, mid-persist
    gate.set()
    h.result(15)
    _, restored, _ = cm.restore_latest()
    np.testing.assert_array_equal(restored["w"], want)
    cm.close()


def test_async_crash_mid_persist_stays_uncommitted(ckpt_fs):
    """Writer-pool death mid-persist: the version must stay uncommitted
    (no MANIFEST), clean_uncommitted() removes it, restore_latest falls
    back to the previous committed version, and the failure surfaces
    through the handle — never into the training thread."""
    base, fs = ckpt_fs
    good = CheckpointManager(base, keep=3, fs=fs)
    tree1 = _tree(1)
    good.save(1, tree1, meta={"epoch": 0})  # committed baseline (npz)

    bad = CheckpointManager(base, keep=3, fs=_FlakyFS(fs))
    handle = bad.save_async(2, _tree(2), meta={"epoch": 1})
    assert handle.wait(30)
    assert isinstance(handle.exception(), IOError)
    with pytest.raises(IOError, match="injected"):
        handle.result(1)
    # drain() logs the failure instead of raising (trainer exit paths)
    assert bad.drain() is handle
    assert bad.drain() is None  # consumed: a second drain is a no-op
    assert not fs.exists(base + "/v_00000002/MANIFEST")
    assert good.versions() == [1]  # uncommitted => invisible
    good.clean_uncommitted()
    assert not fs.exists(base + "/v_00000002")
    version, restored, _ = good.restore_latest()
    assert version == 1
    _assert_trees_equal(tree1, restored)
    bad.close()
    good.close()


def test_async_corrupt_entry_crc_falls_back(tmp_path):
    """A committed stream version with a corrupted entry file must fail
    its per-file CRC on read and fall back to the older version."""
    cm = CheckpointManager(str(tmp_path), keep=3)
    tree1 = _tree(1)
    cm.save(1, tree1)
    cm.save_async(2, _tree(2)).result(30)
    vdir = tmp_path / "v_00000002"
    victim = sorted(vdir.glob("*.bin"))[0]
    victim.write_bytes(b"\xff" * victim.stat().st_size)
    version, restored, _ = cm.restore_latest()
    assert version == 1
    _assert_trees_equal(tree1, restored)
    cm.close()


def test_preemption_guard_drains_on_sigterm(tmp_path):
    """The SIGTERM contract: the flag-only handler never does I/O, and
    guard.drain() (the trainer's preemption exit hook) lands the
    in-flight async version before the process dies."""
    import os
    import signal

    from edl_tpu.runtime.preemption import PreemptionGuard

    gate = threading.Event()
    cm = CheckpointManager(str(tmp_path), fs=_GatedFS(LocalFS(), gate))
    guard = PreemptionGuard(drain=cm.drain)
    old = signal.getsignal(signal.SIGTERM)
    try:
        guard.install()
        tree = {"w": np.arange(64, dtype=np.float32)}
        h = cm.save_async(7, tree)
        assert not h.done()  # persist is gated, still in flight
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 10
        while not guard.preempted and time.time() < deadline:
            time.sleep(0.01)
        assert guard.preempted
        gate.set()
        guard.drain()
        assert h.done() and h.exception() is None
        assert (tmp_path / "v_00000007" / "MANIFEST").exists()
        version, restored, _ = cm.restore_latest()
        assert version == 7
        np.testing.assert_array_equal(restored["w"], tree["w"])
    finally:
        signal.signal(signal.SIGTERM, old)
        cm.close()


# -- sharded stream -----------------------------------------------------------


def _sharded_tree(seed):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
    rng = np.random.RandomState(seed)
    w = rng.randn(16, 4).astype(np.float32)
    moments = rng.randn(16, 4).astype(np.float32)
    bf = (rng.randn(8, 2) * seed).astype(np.float32)
    tree = {
        "params": {"w": jax.device_put(
            w, NamedSharding(mesh, P()))},            # replicated
        "opt": {"mu": jax.device_put(
            moments, NamedSharding(mesh, P("dp")))},  # zero1-style shard
        "bf16": jax.device_put(jnp.asarray(bf, jnp.bfloat16),
                               NamedSharding(mesh, P("dp"))),
        "step": np.int32(seed),                       # host leaf
    }
    host = {"params": {"w": w}, "opt": {"mu": moments},
            "bf16": bf, "step": np.int32(seed)}
    return tree, host, mesh


def _struct_target(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                       getattr(x, "dtype",
                                               np.asarray(x).dtype)),
        tree)


def test_sharded_async_roundtrip_and_placed(tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec as P

    cm = CheckpointManager(str(tmp_path), keep=3)
    tree, host, mesh = _sharded_tree(4)
    handle = cm.save_sharded_async(4, tree, meta={"epoch": 2})
    vdir = handle.result(30)
    manifest = json.load(open(vdir + "/MANIFEST"))
    assert manifest["sharded"] is True
    assert manifest["format"] == "stream" and manifest["ranks"] == 1
    version, restored, meta = cm.restore_latest(
        target=_struct_target(tree))
    assert version == 4 and meta == {"epoch": 2}
    np.testing.assert_array_equal(restored["params"]["w"],
                                  host["params"]["w"])
    np.testing.assert_array_equal(restored["opt"]["mu"], host["opt"]["mu"])
    np.testing.assert_array_equal(
        np.asarray(restored["bf16"], np.float32),
        np.asarray(jnp.asarray(host["bf16"], jnp.bfloat16), np.float32))
    assert restored["bf16"].dtype == jnp.bfloat16
    # placed restore assembles the sharded jax.Arrays straight from the
    # per-shard stream entries
    shardings = {"params": {"w": NamedSharding(mesh, P())},
                 "opt": {"mu": NamedSharding(mesh, P("dp"))},
                 "bf16": NamedSharding(mesh, P("dp")),
                 "step": NamedSharding(mesh, P())}
    version, placed, meta = cm.restore_placed(4, _struct_target(tree),
                                              shardings)
    assert version == 4 and meta == {"epoch": 2}
    np.testing.assert_array_equal(np.asarray(placed["opt"]["mu"]),
                                  host["opt"]["mu"])
    np.testing.assert_array_equal(np.asarray(placed["params"]["w"]),
                                  host["params"]["w"])
    cm.close()


def test_sharded_async_two_ranks_sentinel_protocol(tmp_path):
    """The STARTED/nonce sentinel protocol survives the move onto
    background persist threads: rank 1 (launched first, nothing to
    wait on but the sentinel) blocks until rank 0's background reset,
    and rank 0 commits a merged stream MANIFEST only after rank 1's
    done marker."""
    cm0 = CheckpointManager(str(tmp_path), keep=3)
    cm1 = CheckpointManager(str(tmp_path), keep=3)
    tree, host, _ = _sharded_tree(9)
    h1 = cm1.save_sharded_async(9, {}, rank=1, nranks=2, timeout=30)
    time.sleep(0.3)  # rank 1's persist is polling for STARTED
    assert not (tmp_path / "v_00000009" / "MANIFEST").exists()
    h0 = cm0.save_sharded_async(9, tree, meta={"k": 1}, rank=0,
                                nranks=2, timeout=30)
    assert h0.result(30) and h1.result(30)
    manifest = json.load(open(str(tmp_path / "v_00000009" / "MANIFEST")))
    assert manifest["ranks"] == 2 and manifest["format"] == "stream"
    # protocol state is retired at commit
    assert not (tmp_path / "v_00000009" / "STARTED").exists()
    version, restored, meta = cm0.restore_latest(
        target=_struct_target(tree))
    assert version == 9 and meta == {"k": 1}
    np.testing.assert_array_equal(restored["opt"]["mu"], host["opt"]["mu"])
    cm0.close()
    cm1.close()


#: crc32 of each entry file of `_fixed_state`, as the tree before the
#: snapshot asked one replica only (commit 1109e1a) wrote them
_FIXED_STATE_CRCS = {"opt/mu@0:8;0:12": 3763356796,
                     "params/b@0:12": 1461229387,
                     "params/w@0:8;0:12": 3541670813,
                     "step@": 1931258111}


def _replicated(dp):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from edl_tpu.runtime.mesh import make_mesh
    return NamedSharding(make_mesh(devices=jax.devices()[:dp]), P())


def _fixed_state(dp):
    """The same values whatever the world: replicated over ``dp`` chips."""
    return jax.device_put(
        {"params": {"w": jnp.arange(96.0).reshape(8, 12) / 7,
                    "b": jnp.arange(12, dtype=jnp.bfloat16)},
         "opt": {"mu": jnp.arange(96.0).reshape(8, 12) * 3e-3},
         "step": jnp.asarray(45, jnp.int32)}, _replicated(dp))


@pytest.mark.parametrize("engine", ["dense", "sharded"])
@pytest.mark.parametrize("first,then", [(4, 2), (2, 4)])
def test_state_crosses_worlds_and_back_with_the_same_bytes_on_disk(
        tmp_path, engine, first, then):
    """Saved on one world, restored onto the other, saved there and
    restored back: the values come back, and every save's entry files
    carry the bytes the parent's did for this state."""
    cm = CheckpointManager(str(tmp_path), keep=5)
    save = cm.save_async if engine == "dense" else cm.save_sharded_async

    def crcs(vdir):
        table = json.load(open(vdir + "/MANIFEST"))["entries"]
        return {key: entry["crc"] for key, entry in table.items()}

    def restored(version, dp):
        onto = _replicated(dp)
        return cm.restore_placed(
            version, _struct_target(state),
            jax.tree_util.tree_map(lambda _: onto, state))[1]

    state = _fixed_state(first)
    try:
        assert crcs(save(1, state).result(60)) == _FIXED_STATE_CRCS
        moved = restored(1, then)
        assert all(len(leaf.sharding.device_set) == then
                   for leaf in jax.tree_util.tree_leaves(moved))
        assert crcs(save(2, moved).result(60)) == _FIXED_STATE_CRCS
        back = restored(2, first)
    finally:
        cm.close()
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(back)):
        assert a.dtype == b.dtype and a.sharding == b.sharding
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
