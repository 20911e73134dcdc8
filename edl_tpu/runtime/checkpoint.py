"""Atomic, versioned pytree checkpointing with manifest-last commit.

Reference parity: Paddle Fleet's save/load_check_point with
write-temp-then-rename and version numbers (doc/fault_tolerance.md:20-25;
train_with_fleet.py:426-434,562-570). TPU twist: the commit protocol is
manifest-last (a version directory is valid iff its MANIFEST file exists and
checksums match), which also works on stores without atomic rename (GCS).

Layout (dense, the default):
    <dir>/v_00000012/arrays.npz   flat {path: ndarray} of the pytree leaves
    <dir>/v_00000012/meta.json    user metadata + dtype tags (bfloat16)
    <dir>/v_00000012/MANIFEST     written last: {"version", "crc"}

Layout (sharded — save_sharded/restore with a target):
    <dir>/v_00000012/STARTED             rank 0's go sentinel (dir reset
                                         done; other ranks may write)
    <dir>/v_00000012/arrays.r<k>.npz     rank k's owned array shards,
                                         keys "path@s0:e0;s1:e1;..."
    <dir>/v_00000012/shardmeta.r<k>.json rank k's crc + dtype tags
    <dir>/v_00000012/done.r<k>           rank k's publish marker, written
                                         after its data files close
    <dir>/v_00000012/meta.json, MANIFEST rank 0, after every rank's
                                         done marker is visible

Sharded mode is the scalable path: every host writes only its
addressable shards (no rank-0 gather, write bandwidth scales with host
count — the Orbax role); the commit stays manifest-last, with the
manifest recording every rank file's crc. Rank synchronization is by
filesystem visibility on the shared store (no device collectives — the
write may run from a background thread).

Layout (stream — the async snapshot-then-persist engine):
    <dir>/v_00000012/a0000.bin        one raw chunk-streamed file per
                                      array entry (r<k>_a<j>.bin sharded)
    <dir>/v_00000012/meta.json        user metadata + dtype tags
    <dir>/v_00000012/MANIFEST         written last: per-entry spans,
                                      files, crcs ("format": "stream")

The stream layout exists for the ASYNC save path (save_async /
save_sharded_async): phase 1 ("snapshot", on the training thread)
starts non-blocking device->host transfers for every owned shard and
copies them into reused host buffers, then returns a SaveHandle; phase
2 ("persist", a background writer pool) streams each entry straight to
its own file in fixed-size chunks — no monolithic npz BytesIO double
copy — computing crc32 incrementally over the stream, and commits the
MANIFEST only after every writer finishes. max_inflight is 1: a new
save first drains the previous one (which also makes the host-buffer
reuse safe). Crashed async attempts leave no MANIFEST and are removed
by clean_uncommitted() like any other uncommitted dir.
"""

import io
import json
import threading
import time
import uuid
import zlib
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from edl_tpu.obs import events as obs_events
from edl_tpu.obs import ledger as obs_ledger
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.runtime.fs import get_fs
from edl_tpu.utils.logger import logger

_SAVE_MS = obs_metrics.histogram(
    "edl_ckpt_save_ms", "checkpoint save wall time to manifest commit",
    labels=("mode",))
_RESTORE_MS = obs_metrics.histogram(
    "edl_ckpt_restore_ms", "checkpoint restore wall time")

try:
    import ml_dtypes
    _BFLOAT16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover
    _BFLOAT16 = None

_SEP = "/"


class MissingKeysError(IOError):
    """The checkpoint is valid but lacks keys the restore target needs
    (e.g. a legacy checkpoint without the model's extra state)."""

    def __init__(self, keys):
        super().__init__("checkpoint missing keys: %s" % sorted(keys))
        self.keys = frozenset(keys)


def _path_key(path):
    return _SEP.join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path)


def _flatten(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return {_path_key(p): np.asarray(leaf) for p, leaf in flat}, treedef


def to_host_tree(tree):
    """Fetch a (possibly sharded) device pytree to host numpy, multi-host
    safe: leaves that are not fully addressable from this process (e.g.
    tp-sharded across hosts) are all-gathered over jax.distributed first
    — the shared-FS checkpoint write needs the GLOBAL array (reference
    role: rank-0 fleet.save_check_point of the full model)."""
    def fetch(x):
        if getattr(x, "is_fully_addressable", True):
            return jax.device_get(x)
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return jax.tree_util.tree_map(fetch, tree)


def leaf_locally_fetchable(x):
    """True when ``x`` can reach host memory WITHOUT a collective: host
    data, fully addressable, or fully replicated (a complete local
    replica exists). The single predicate behind to_host_tree_local and
    the trainer's emergency-save eligibility check — they must agree."""
    return (not hasattr(x, "addressable_shards")
            or getattr(x, "is_fully_addressable", True)
            or getattr(x, "is_fully_replicated", False))


def to_host_tree_local(tree):
    """Fetch a device pytree to host numpy WITHOUT any collective: every
    leaf must satisfy leaf_locally_fetchable. This is the emergency-
    checkpoint fetch — preempted ranks cannot rendezvous, so a gather is
    off the table; raises ValueError on cross-host *sharded* leaves."""
    def fetch(x):
        if not leaf_locally_fetchable(x):
            raise ValueError("cross-host sharded leaf: no local replica "
                             "to fetch without a collective")
        if not hasattr(x, "addressable_shards"):
            return np.asarray(x)
        if getattr(x, "is_fully_addressable", True):
            return jax.device_get(x)
        return np.asarray(x.addressable_data(0))
    return jax.tree_util.tree_map(fetch, tree)


def _paths(tree):
    """Flat path keys + treedef without materializing leaves (target may
    hold ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return [_path_key(p) for p, _ in flat], treedef


# -- shard-span codec: the ONE encode/decode pair for "s0:e0;s1:e1;..." --

def _concrete_spans(index, shape):
    """Slices -> ((start, stop), ...) with shape-resolved bounds."""
    return tuple((0 if sl.start is None else int(sl.start),
                  dim if sl.stop is None else int(sl.stop))
                 for sl, dim in zip(index, shape))


def _spans_str(spans):
    return ";".join("%d:%d" % ab for ab in spans)


def _parse_spans(s):
    return tuple((int(a), int(b))
                 for part in s.split(";") if part
                 for a, b in [part.split(":")])


# -- sharding record: the saved PartitionSpec tree + mesh axes ------------
#
# A checkpoint's entry spans say WHERE each saved block lives; the
# sharding record says WHY — the mesh axis names/sizes and the per-leaf
# PartitionSpec that produced those spans. Restore never needs it
# (PlacedTarget intersects spans against whatever target sharding the
# caller asks for), but the resize planner does: with the record, a
# target mesh's reshard cost and live-eligibility are computable from
# metadata alone, before any data is read. It rides the existing
# meta.json ("sharding" key), so legacy checkpoints simply lack it.


def sharding_record(shardings):
    """JSON-able record of a sharding pytree: the mesh axis names and
    sizes plus per-leaf PartitionSpec entries keyed by path. Leaves
    without a NamedSharding (single-device, callables) record None and
    read back as replicated."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shardings)
    mesh = None
    specs = {}
    for path, sh in flat:
        key = _path_key(path)
        spec = getattr(sh, "spec", None)
        m = getattr(sh, "mesh", None)
        if spec is None or m is None:
            specs[key] = None
            continue
        if mesh is None:
            mesh = {"axes": [str(a) for a in m.axis_names],
                    "shape": {str(a): int(m.shape[a])
                              for a in m.axis_names}}
        specs[key] = [list(e) if isinstance(e, (tuple, list)) else e
                      for e in spec]
    return {"mesh": mesh, "specs": specs}


def spec_from_record(entry):
    """PartitionSpec from one ``sharding_record`` specs entry (None or
    missing -> fully replicated)."""
    from jax.sharding import PartitionSpec
    if not entry:
        return PartitionSpec()
    return PartitionSpec(*[tuple(e) if isinstance(e, list) else e
                           for e in entry])


# -- stream-format plumbing (the async snapshot/persist engine) -----------

_CHUNK = 4 << 20  # fixed-size streaming chunk for entry files


def _wire_entry(arr):
    """(wire_array, dtype_tag|None): dtypes without the buffer protocol
    ship as a POD view — bfloat16 as uint16, datetime/timedelta as
    int64 — and the tag restores the view on read."""
    if _BFLOAT16 is not None and arr.dtype == _BFLOAT16:
        return arr.view(np.uint16), "bfloat16"
    if arr.dtype.kind in "mM":
        return arr.view(np.int64), arr.dtype.str
    return arr, None


def _untag_array(arr, tag):
    """Inverse of _wire_entry's tagging (also decodes the legacy npz
    layout's bfloat16 tag)."""
    if not tag:
        return arr
    if tag == "bfloat16":
        if _BFLOAT16 is None:  # pragma: no cover
            raise IOError("bfloat16 checkpoint needs ml_dtypes")
        return arr.view(_BFLOAT16)
    return arr.view(np.dtype(tag))


def _device_read(x):
    """True for a leaf that ``np.asarray`` reads off this process's
    devices: a jax array whose every block is addressable here. A host
    leaf is there already, and a leaf that spans other processes is
    gathered first: neither is asked for anything."""
    return (hasattr(x, "copy_to_host_async")
            and getattr(x, "is_fully_addressable", False))


def _owned_shards(leaf):
    """The shards a sharded save reads of a jax leaf and this process
    writes: of the copies of a block, the one with ``replica_id`` 0.
    None for a host value, which rank 0 writes whole."""
    if not (hasattr(leaf, "addressable_shards")
            and hasattr(leaf, "sharding")):
        return None
    return [s for s in leaf.addressable_shards if s.replica_id == 0]


def _buffers_read(x):
    """Device buffers that ``np.asarray(x)`` reads, and that
    ``x.copy_to_host_async()`` therefore asks for: one of a replicated
    array, whatever the number of chips that hold it, else one a
    distinct block."""
    if x.is_fully_replicated:
        return 1
    blocks = x.sharding.addressable_devices_indices_map(x.shape).values()
    return len({_concrete_spans(index, x.shape) for index in blocks})


def _start_host_transfers(reads):
    """Kick off the non-blocking device->host DMAs of ``reads``, the jax
    arrays (leaves, or the data of shards) that the snapshot is about to
    fetch with ``np.asarray``, so that those fetches overlap instead of
    serializing (phase 1 of the async save). The array's own
    ``copy_to_host_async`` asks for the buffers its ``np.asarray`` reads
    and for no other — and it has to be THAT object's: the host copy is
    kept by the Python array that asked, not by the device buffer, so a
    transfer asked through a shard's ``data`` is lost on the
    ``np.asarray`` of its leaf, which then transfers again, blocking (on
    four v5e chips 0.5 ms a leaf). Returns (transfers started, their
    bytes). Best-effort: the first call that raises ends it, and the
    rest is then fetched serially."""
    # what a call will move is read before the first transfer is asked
    # for: between two of those calls the same read costs several times
    # more (on four v5e chips 7 us, 12 ms a save)
    asked = [(x, _buffers_read(x), int(x.nbytes)) for x in reads]
    started = nbytes = 0
    for x, buffers, size in asked:
        try:
            x.copy_to_host_async()
        except Exception as e:  # noqa: BLE001 — best-effort
            logger.debug("host transfers stopped after %d (%r): the rest "
                         "is fetched serially", started, e)
            break
        started += buffers
        nbytes += size
    return started, nbytes


class _HostBufferPool(object):
    """Reusable host staging buffers for snapshots, keyed by entry key.
    Reuse across versions avoids a fresh multi-GB allocation per save;
    it is safe exactly because max_inflight=1 — the previous persist is
    drained before a new snapshot touches the buffers."""

    def __init__(self):
        self._bufs = {}
        self.allocated = 0  # buffers made so far (a reuse makes none)

    def copy_in(self, key, arr):
        arr = np.asarray(arr)
        buf = self._bufs.get(key)
        if buf is None or buf.shape != arr.shape or buf.dtype != arr.dtype:
            buf = np.empty(arr.shape, arr.dtype)
            self._bufs[key] = buf
            self.allocated += 1
        np.copyto(buf, arr)
        return buf


class _SnapshotAccount(object):
    """What one ``save.snapshot`` is made of. Its three parts interleave
    per leaf, so only the first is a span of its own
    (``save.snapshot.start_transfers``); the other two are summed in
    seconds and handed to the span as tags, with the bytes kept and
    asked for. ``on`` False (a span ``EDL_TPU_OBS=0`` keeps out of the
    ring): the same calls, no account."""

    def __init__(self, pool, on):
        self._pool, self.on = pool, on
        self._bufs0 = pool.allocated
        self.fetch_s = self.copy_s = 0.0
        self.started = (0, 0)

    def start(self, reads):
        """Ask for the transfers of ``reads``: the arrays ``fetch`` will
        be handed, the same objects."""
        with obs_trace.span("save.snapshot.start_transfers", stage=True):
            self.started = _start_host_transfers(reads)

    def fetch(self, x):
        """np.asarray of a leaf or shard: the wait for the device's copy
        and the read of it."""
        if not self.on:
            return np.asarray(x)
        t = time.perf_counter()
        arr = np.asarray(x)
        self.fetch_s += time.perf_counter() - t
        return arr

    def keep(self, skey, arr):
        """The copy into the pool's buffer for ``skey``."""
        if not self.on:
            return self._pool.copy_in(skey, arr)
        t = time.perf_counter()
        buf = self._pool.copy_in(skey, arr)
        self.copy_s += time.perf_counter() - t
        return buf

    def tags(self, leaves, entries):
        return {"fetch_s": self.fetch_s, "copy_s": self.copy_s,
                "bytes": sum(int(a.nbytes) for a in entries.values()),
                "leaves": leaves,
                "transfers_started": self.started[0],
                "transfer_bytes_started": self.started[1],
                "bufs_new": self._pool.allocated - self._bufs0}


class SaveHandle(object):
    """Completion handle for an async checkpoint save.

    ``blocked_s`` is the training-thread (snapshot) time, read off the
    ``save.snapshot`` stage span; ``persist_s`` the background write
    time, set once the persist finishes. wait() blocks without raising;
    result() re-raises any persist failure."""

    def __init__(self, version):
        self.version = version
        self.blocked_s = 0.0
        self.persist_s = None
        self._evt = threading.Event()
        self._vdir = None
        self._exc = None

    def done(self):
        return self._evt.is_set()

    def wait(self, timeout=None):
        return self._evt.wait(timeout)

    def exception(self):
        return self._exc

    def result(self, timeout=None):
        if not self._evt.wait(timeout):
            raise TimeoutError("checkpoint v%d persist still running"
                               % self.version)
        if self._exc is not None:
            raise self._exc
        return self._vdir

    def _finish(self, vdir, exc=None, persist_s=None):
        self._vdir = vdir
        self._exc = exc
        self.persist_s = persist_s
        self._evt.set()


class PlacedTarget(object):
    """The per-process fill plan of a placed (locality-aware) restore.

    Built from (target, shardings); holds, per leaf, the UNIQUE device
    blocks this process must fill (replicated leaves map every device to
    the same span — one shared host buffer, not one per device) plus the
    device -> span mapping for final assembly. Both the shared-FS path
    (CheckpointManager.restore_placed / fill_placed_from_fs) and the
    peer restore plane (runtime/state_server.PeerRestorer) paste saved
    extents into the SAME instance, which is what lets a partial peer
    fetch be completed span-by-span from the FS instead of starting
    over. Callers untag wire dtypes before paste()."""

    def __init__(self, target, shardings):
        flat_t, treedef = jax.tree_util.tree_flatten_with_path(target)
        flat_s = jax.tree_util.tree_leaves(shardings)
        if len(flat_s) != len(flat_t):
            raise ValueError("shardings tree does not match target")
        self._flat_t = flat_t
        self._treedef = treedef
        # key -> (shape, dtype, sharding, {spans: [buffer, filled]},
        #         {device: spans})
        self.need = {}
        for (path, leaf), sharding in zip(flat_t, flat_s):
            key = _path_key(path)
            shape = tuple(leaf.shape)
            dtype = np.dtype(leaf.dtype)
            dev_map = sharding.addressable_devices_indices_map(shape)
            blocks = {}
            dev_spans = {}
            for dev, index in dev_map.items():
                spans = _concrete_spans(index, shape)
                dev_spans[dev] = spans
                if spans not in blocks:
                    bshape = tuple(e - s for s, e in spans)
                    blocks[spans] = [np.zeros(bshape, dtype), 0]
            self.need[key] = (shape, dtype, sharding, blocks, dev_spans)

    def check_bounds(self, key, entry_spans):
        """A saved extent beyond the target shape must raise, even when
        the offending entry overlaps none of our blocks — otherwise
        in-bounds entries can complete coverage and the restore silently
        truncates the stored tensor."""
        shape = self.need[key][0]
        if len(entry_spans) != len(shape) or any(
                b > dim or a < 0
                for (a, b), dim in zip(entry_spans, shape)):
            raise IOError(
                "checkpoint shape mismatch for %r: saved spans %s "
                "vs target shape %s" % (key, entry_spans, shape))

    def overlaps_local(self, key, entry_spans):
        blocks = self.need[key][3]
        return any(
            all(max(a, c) < min(b, d)
                for (a, b), (c, d) in zip(entry_spans, spans))
            for spans in blocks)

    def needed_rows(self, key, entry_spans):
        """The entry-local contiguous leading-axis row hull [r0, r1)
        this process needs from an entry saved at ``entry_spans``, or
        None when the entry overlaps no local block. The hull may cover
        rows between disjoint blocks — over-read, never under-read.
        Scalars (rank-0 entries) return (0, 1): whole-entry reads."""
        blocks = self.need[key][3]
        lo = hi = None
        for spans in blocks:
            if not all(max(a, c) < min(b, d)
                       for (a, b), (c, d) in zip(entry_spans, spans)):
                continue
            if not entry_spans:
                return (0, 1)
            (a0, b0), (c0, d0) = entry_spans[0], spans[0]
            lo0, hi0 = max(a0, c0) - a0, min(b0, d0) - a0
            lo = lo0 if lo is None else min(lo, lo0)
            hi = hi0 if hi is None else max(hi, hi0)
        return None if lo is None else (lo, hi)

    def paste(self, key, entry_spans, arr):
        """Paste an (already untagged) saved extent into every
        overlapping local block (scalars: all spans empty -> full
        overlap)."""
        _, dtype, _, blocks, _ = self.need[key]
        for spans, blk in blocks.items():
            buf = blk[0]
            # intersect the saved entry with this device block
            lo = [max(a, c) for (a, _), (c, _) in
                  zip(entry_spans, spans)]
            hi = [min(b, d) for (_, b), (_, d) in
                  zip(entry_spans, spans)]
            if any(x >= y for x, y in zip(lo, hi)):
                continue
            src = tuple(slice(x - a, y - a) for (a, _), x, y in
                        zip(entry_spans, lo, hi))
            dst = tuple(slice(x - c, y - c) for (c, _), x, y in
                        zip(spans, lo, hi))
            buf[dst] = np.asarray(arr[src], dtype)
            blk[1] += int(np.prod([y - x for x, y in zip(lo, hi)],
                                  dtype=np.int64))

    def reset_key(self, key):
        """Zero a key's fill counters (buffers are simply overwritten):
        call before re-filling a key from a DIFFERENT source, so
        coverage accounting never double-counts overlapping pastes."""
        for blk in self.need[key][3].values():
            blk[1] = 0

    def missing(self):
        """Keys whose local blocks are not fully covered yet."""
        return {key for key, (_, _, _, blocks, _) in self.need.items()
                if any(blk[1] < blk[0].size for blk in blocks.values())}

    def filled_nbytes(self):
        """Bytes pasted so far (restore-size metric for timing logs)."""
        return sum(blk[1] * spec[1].itemsize
                   for key, spec in self.need.items()
                   for blk in spec[3].values())

    def assemble(self):
        """device_put every block and build the sharded jax.Arrays in
        the target's tree structure."""
        leaves = []
        for path, _ in self._flat_t:
            shape, _, sharding, blocks, dev_spans = \
                self.need[_path_key(path)]
            bufs = [jax.device_put(blocks[spans][0], dev)
                    for dev, spans in dev_spans.items()]
            leaves.append(jax.make_array_from_single_device_arrays(
                shape, sharding, bufs))
        return jax.tree_util.tree_unflatten(self._treedef, leaves)


class CheckpointManager(object):
    def __init__(self, directory, keep=3, fs=None, workers=4):
        self._dir = str(directory)
        self._fs = fs or get_fs(directory)
        self._keep = keep
        self._workers = max(1, int(workers))
        self._pool = None           # lazy writer/reader thread pool
        self._host_bufs = _HostBufferPool()
        self._inflight = None       # the (single) in-flight SaveHandle
        self._async_lock = threading.Lock()

    # -- helpers -------------------------------------------------------------

    def _io_pool(self):
        """The shared writer/reader pool: persist fan-out AND the
        parallel restore reads ride the same threads."""
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self._workers, thread_name_prefix="ckpt-io")
        return self._pool

    def drain(self):
        """Block until the in-flight async save (if any) finishes;
        returns its SaveHandle or None. A persist failure is logged, not
        raised (the manifest-last invariant already keeps the failed
        version invisible) — callers that must see the exception hold
        the handle and call result()."""
        with self._async_lock:
            h, self._inflight = self._inflight, None
        if h is not None:
            # drain() runs on the TRAINING thread (step boundary, resize
            # drain): the wait is attributed checkpoint-blocked time.
            # The writer pool's own concurrency is never ledgered.
            with obs_ledger.LEDGER.state("ckpt_block"):
                h.wait()
            if h.exception() is not None:
                logger.error("async checkpoint v%d failed: %r",
                             h.version, h.exception())
        return h

    def persisting(self):
        """True while an async save's persist is still running. Takes
        nothing: the handle stays for the next drain() to collect."""
        h = self._inflight
        return h is not None and not h.done()

    def close(self):
        """Drain the in-flight save and shut the writer pool down."""
        self.drain()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def _vdir(self, version):
        return "%s/v_%08d" % (self._dir, version)

    def versions(self):
        """Committed (manifest-valid) versions, ascending."""
        out = []
        for name in self._fs.listdir(self._dir):
            if name.startswith("v_"):
                try:
                    v = int(name[2:])
                except ValueError:
                    continue
                if self._fs.exists("%s/%s/MANIFEST" % (self._dir, name)):
                    out.append(v)
        return sorted(out)

    def meta(self, version):
        """User metadata of a committed version (the ``meta=`` blob the
        saver passed), or None when the version/meta is unreadable."""
        try:
            with self._fs.open(self._vdir(version) + "/meta.json",
                               "r") as f:
                return json.load(f).get("meta")
        except (IOError, OSError, ValueError):
            return None

    def saved_sharding(self, version):
        """The :func:`sharding_record` saved with ``version`` (meta key
        ``"sharding"``), or None for legacy/recordless checkpoints —
        which restore as "everything replicated" for planning purposes,
        matching what they actually were."""
        m = self.meta(version)
        return m.get("sharding") if isinstance(m, dict) else None

    def clean_uncommitted(self):
        """Delete version dirs without a MANIFEST — garbage from crashed
        save attempts (the manifest-last invariant makes them invisible
        to restore, but a stale STARTED sentinel inside one could let a
        later sharded save at the SAME version mis-order its barrier).
        Call at process start, before any save; in multi-host jobs only
        rank 0 should call it (concurrent deletes race)."""
        removed = []
        for name in self._fs.listdir(self._dir):
            if not name.startswith("v_"):
                continue
            try:
                int(name[2:])
            except ValueError:
                continue
            if not self._fs.exists("%s/%s/MANIFEST" % (self._dir, name)):
                self._fs.delete_tree("%s/%s" % (self._dir, name))
                removed.append(name)
        if removed:
            logger.info("cleaned %d uncommitted checkpoint dir(s): %s",
                        len(removed), removed)
        return removed

    # -- save ---------------------------------------------------------------

    def save(self, version, tree, meta=None):
        """Write checkpoint ``version``; commit is the MANIFEST write."""
        with obs_ledger.LEDGER.state("ckpt_block"):
            return self._save(version, tree, meta=meta)

    def _save(self, version, tree, meta=None):
        t0 = time.monotonic()
        vdir = self._vdir(version)
        self._fs.delete_tree(vdir)  # clear any half-written attempt
        self._fs.makedirs(vdir)

        arrays, _ = _flatten(tree)
        dtypes = {}
        to_save = {}
        for key, arr in arrays.items():
            if _BFLOAT16 is not None and arr.dtype == _BFLOAT16:
                dtypes[key] = "bfloat16"
                arr = arr.view(np.uint16)
            to_save[key] = arr
        buf = io.BytesIO()
        np.savez(buf, **to_save)
        payload = buf.getvalue()
        crc = zlib.crc32(payload)
        with self._fs.open(vdir + "/arrays.npz", "wb") as f:
            f.write(payload)
        with self._fs.open(vdir + "/meta.json", "w") as f:
            json.dump({"meta": meta or {}, "dtypes": dtypes}, f)
        # the commit point:
        with self._fs.open(vdir + "/MANIFEST", "w") as f:
            json.dump({"version": version, "crc": crc,
                       "nbytes": len(payload)}, f)
        logger.info("checkpoint v%d committed (%d arrays, %.1f MB)", version,
                    len(to_save), len(payload) / 1e6)
        _SAVE_MS.labels("sync").observe((time.monotonic() - t0) * 1e3)
        obs_events.emit("ckpt.saved", version=version, mode="sync",
                        nbytes=len(payload))
        self._gc()
        return vdir

    def _gc(self):
        versions = self.versions()
        for v in versions[:-self._keep] if self._keep else []:
            self._fs.delete_tree(self._vdir(v))

    # -- async save: snapshot phase ------------------------------------------

    def _snapshot(self, take, tree, *args):
        """Phase 1 of an async save, the only part the training thread
        pays: the stage span ``save.snapshot`` round ``take`` (one of the
        two below), tagged with what the snapshot was made of. Returns
        (entries, dtypes, the span's seconds)."""
        with obs_trace.span("save.snapshot", stage=True) as sp:
            acct = _SnapshotAccount(self._host_bufs, sp.recorded)
            leaves, entries, dtypes = take(tree, acct, *args)
            if acct.on:
                sp.tag(**acct.tags(leaves, entries))
        return entries, dtypes, sp.seconds

    def _snapshot_dense(self, tree, acct):
        """Snapshot of a full tree: {span_key: host ndarray} (wire
        dtypes) + dtype tags, copied into the reused buffer pool so
        later steps may donate/mutate the originals."""
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        acct.start([leaf for _, leaf in flat if _device_read(leaf)])
        entries = {}
        dtypes = {}
        for path, leaf in flat:
            key = _path_key(path)
            if not getattr(leaf, "is_fully_addressable", True):
                from jax.experimental import multihost_utils
                leaf = multihost_utils.process_allgather(leaf, tiled=True)
            arr, tag = _wire_entry(acct.fetch(leaf))
            if tag:
                dtypes[key] = tag
            skey = self._shard_key(key, tuple(slice(0, d)
                                              for d in arr.shape),
                                   arr.shape)
            entries[skey] = acct.keep(skey, arr)
        return len(flat), entries, dtypes

    def _snapshot_sharded(self, tree, acct, rank):
        """Snapshot of this rank's OWNED shards (replica_id 0 dedup;
        host/replicated-only leaves land on rank 0), mirroring what the
        sync sharded writer persists."""
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        owned = [_owned_shards(leaf) for _, leaf in flat]
        acct.start([s.data for shards in owned for s in shards or ()])
        entries = {}
        dtypes = {}

        def add(key, index, shape, arr):
            arr, tag = _wire_entry(acct.fetch(arr))
            if tag:
                dtypes[key] = tag
            skey = self._shard_key(key, index, shape)
            entries[skey] = acct.keep(skey, arr)

        for (path, leaf), shards in zip(flat, owned):
            key = _path_key(path)
            if shards is not None:
                for s in shards:
                    add(key, s.index, leaf.shape, s.data)
            elif rank == 0:
                arr = acct.fetch(leaf)
                add(key, tuple(slice(0, d) for d in arr.shape),
                    arr.shape, arr)
        return len(flat), entries, dtypes

    # -- async save: persist phase -------------------------------------------

    def _write_entry_file(self, path, arr):
        """Stream one (contiguous, wire-dtype) array to its own file in
        fixed-size chunks with an incremental crc — no whole-payload
        BytesIO staging. Returns (nbytes, crc, chunk_crcs): the
        per-chunk crc list lands in the manifest so range reads (the
        placed restore / peer-restore FS fallback) can verify just the
        chunks they touch instead of the whole file."""
        arr = np.ascontiguousarray(arr)
        chunk_crcs = []
        if arr.nbytes == 0:
            nbytes, crc = self._fs.write_chunks(path, ())
            return nbytes, crc, chunk_crcs

        def chunks():
            view = memoryview(arr).cast("B")
            for off in range(0, len(view), _CHUNK):
                chunk = view[off:off + _CHUNK]
                chunk_crcs.append(zlib.crc32(chunk))
                yield chunk

        nbytes, crc = self._fs.write_chunks(path, chunks())
        return nbytes, crc, chunk_crcs

    def _read_entry_file(self, path, entry):
        """Read one stream entry back (chunked, incremental crc check),
        returning the wire-dtype array."""
        dtype = np.dtype(entry["dtype"])
        arr = np.empty(tuple(entry["shape"]), dtype)
        nbytes = int(entry["nbytes"])
        if arr.nbytes != nbytes:
            raise IOError("entry %s: %d bytes recorded vs %d expected"
                          % (path, nbytes, arr.nbytes))
        crc = 0
        got = 0
        view = memoryview(arr).cast("B") if nbytes else None
        with self._fs.open(path, "rb") as f:
            while got < nbytes:
                chunk = f.read(min(_CHUNK, nbytes - got))
                if not chunk:
                    raise IOError("entry %s truncated at %d/%d bytes"
                                  % (path, got, nbytes))
                view[got:got + len(chunk)] = chunk
                crc = zlib.crc32(chunk, crc)
                got += len(chunk)
        if crc != int(entry["crc"]):
            raise IOError("checksum mismatch in %s" % path)
        return arr

    def _read_entry_rows(self, path, entry, r0, r1):
        """Range-read rows [r0, r1) of a stream entry's LEADING axis via
        fs.read_range, chunk-aligned so the per-chunk crcs recorded at
        write time still verify (manifests from before the range-read
        extension lack chunk_crcs — callers route those through the
        whole-file _read_entry_file). Returns the wire-dtype array of
        shape (r1-r0,) + shape[1:]."""
        dtype = np.dtype(entry["dtype"])
        shape = tuple(entry["shape"])
        chunk_crcs = entry["chunk_crcs"]
        csize = int(entry.get("chunk", _CHUNK))
        nbytes = int(entry["nbytes"])
        rowbytes = (int(np.prod(shape[1:], dtype=np.int64))
                    * dtype.itemsize)
        b0, b1 = r0 * rowbytes, r1 * rowbytes
        c0 = b0 // csize
        c1 = min((b1 + csize - 1) // csize, len(chunk_crcs))
        off = c0 * csize
        want = min(c1 * csize, nbytes) - off
        data = self._fs.read_range(path, off, want) if want > 0 else b""
        if len(data) != want:
            raise IOError("entry %s: range read returned %d/%d bytes "
                          "at offset %d" % (path, len(data), want, off))
        for i in range(c0, c1):
            lo = i * csize - off
            hi = min((i + 1) * csize, nbytes) - off
            if zlib.crc32(data[lo:hi]) != int(chunk_crcs[i]):
                raise IOError("chunk %d checksum mismatch in %s"
                              % (i, path))
        out = np.frombuffer(data, np.uint8)[b0 - off:b1 - off]
        return out.view(dtype).reshape((r1 - r0,) + shape[1:])

    def _write_entries(self, vdir, prefix, entries):
        """Fan the entry files out across the writer pool; returns the
        manifest entry table {span_key: {file, dtype, shape, crc,
        nbytes, chunk, chunk_crcs}} and the total byte count.

        Files are named by the entries' sorted keys and SUBMITTED
        largest first. A large entry's write and checksum release the
        interpreter lock; a small one costs mostly interpreter time. The
        training thread is busiest on the interpreter right after the
        save returns (the next dispatches; a live resize's reshard), so
        it meets the writers while they need the lock least — and the
        longest writes start first, which keeps the pool's tail short."""
        pool = self._io_pool()
        order = sorted(entries)
        fname = {skey: "%sa%04d.bin" % (prefix, i)
                 for i, skey in enumerate(order)}
        futs = {skey: pool.submit(self._write_entry_file,
                                  "%s/%s" % (vdir, fname[skey]),
                                  entries[skey])
                for skey in sorted(order,
                                   key=lambda k: -entries[k].nbytes)}
        table = {}
        total = 0
        for skey in order:
            arr = entries[skey]
            nbytes, crc, chunk_crcs = futs[skey].result()
            table[skey] = {"file": fname[skey], "dtype": arr.dtype.str,
                           "shape": list(arr.shape), "crc": crc,
                           "nbytes": nbytes, "chunk": _CHUNK,
                           "chunk_crcs": chunk_crcs}
            total += nbytes
        return table, total

    def save_async(self, version, tree, meta=None, on_commit=None):
        """Two-phase async save. Snapshot runs HERE (fast device->host
        copies into pooled buffers), then control returns while a
        background driver streams the entries to per-array files and
        commits the MANIFEST last. Returns a SaveHandle; max_inflight
        is 1 — this call first drains any previous async save.
        ``on_commit`` (optional) runs on the driver thread right after
        the manifest commit — the hand-off point where the trainer
        publishes the committed snapshot to its StateServer and pushes
        erasure-coded shards to its redundancy partner ring
        (runtime/redundancy.py). Callbacks are best-effort observers
        of an already-durable commit: an on_commit failure is logged,
        never surfaced as a save failure."""
        self.drain()
        # the snapshot is the async save's only training-thread cost
        with obs_ledger.LEDGER.state("ckpt_block"):
            entries, dtypes, blocked_s = self._snapshot(
                self._snapshot_dense, tree)
        handle = SaveHandle(version)
        handle.blocked_s = blocked_s
        save_span = obs_trace.current()  # the trainer's `save`, or None

        def persist():
            p0 = time.perf_counter()
            try:
                with obs_trace.span("save.persist", stage=True,
                                    parent=save_span, version=version):
                    vdir = self._vdir(version)
                    self._fs.delete_tree(vdir)
                    self._fs.makedirs(vdir)
                    table, total = self._write_entries(vdir, "", entries)
                    with self._fs.open(vdir + "/meta.json", "w") as f:
                        json.dump({"meta": meta or {}, "dtypes": dtypes},
                                  f)
                    # the commit point:
                    with self._fs.open(vdir + "/MANIFEST", "w") as f:
                        json.dump({"version": version, "format": "stream",
                                   "entries": table, "nbytes": total}, f)
                logger.info("checkpoint v%d committed async (%d entries,"
                            " %.1f MB)", version, len(table),
                            total / 1e6)
                _SAVE_MS.labels("async").observe(
                    (time.perf_counter() - p0) * 1e3)
                obs_events.emit("ckpt.saved", version=version,
                                mode="async", nbytes=total)
                self._gc()
                if on_commit is not None:
                    # the manifest is already durable: a failing
                    # commit observer (state publish, redundancy
                    # shard push) must not mark the save failed
                    try:
                        on_commit()
                    except Exception:
                        logger.exception(
                            "on_commit callback for v%d failed",
                            version)
                handle._finish(vdir,
                               persist_s=time.perf_counter() - p0)
            except BaseException as e:  # noqa: BLE001 — surfaces via result()
                handle._finish(None, exc=e,
                               persist_s=time.perf_counter() - p0)

        with self._async_lock:
            self._inflight = handle
        threading.Thread(target=persist, daemon=False,
                         name="ckpt-persist-%d" % version).start()
        return handle

    # -- sharded save --------------------------------------------------------

    @staticmethod
    def _shard_key(key, index, shape):
        return "%s@%s" % (key, _spans_str(_concrete_spans(index, shape)))

    def _fs_wait(self, predicate, what, timeout):
        deadline = time.monotonic() + timeout
        delay = 0.02
        while not predicate():
            if time.monotonic() > deadline:
                raise IOError("sharded save: timed out waiting for %s"
                              % what)
            time.sleep(delay)
            delay = min(delay * 1.5, 0.5)

    def save_sharded(self, version, tree, meta=None, rank=0, nranks=1,
                     barrier=None, timeout=120.0):
        """Cooperative sharded save: EVERY rank calls this with the same
        ``version``/``tree``; each writes only the shards it owns; rank 0
        commits the MANIFEST recording all rank files + crcs. Returns the
        version dir (all ranks).

        Synchronization is by FILESYSTEM VISIBILITY on the shared store
        (the premise of elastic checkpoints), not device collectives:
        rank 0 resets the version dir and drops a STARTED sentinel;
        other ranks wait for it before writing; each rank publishes a
        done.r<k> marker strictly after its data files close, and rank 0
        waits for every done marker before committing. This keeps the
        save legal from background writer threads (no collective may run
        off the main stream) and identical on GCS (no rename needed). An
        explicit ``barrier`` callable replaces the sentinel protocol
        when the caller already has a rendezvous (tests, jax.distributed
        sync points).

        The STARTED sentinel carries a per-attempt NONCE: ranks echo it
        in their done markers and rank 0 only accepts markers from the
        current attempt, so a sentinel left by a crashed or older
        attempt at the same version (restore fell back to an older
        version, zero-step epoch re-save) cannot mis-pair two attempts.
        A non-rank-0 rank that wrote against a stale nonce detects the
        mismatch after publishing and rewrites its files under the new
        nonce instead of silently losing them to rank 0's reset. The
        sentinel and done markers are removed at commit so committed
        version dirs never carry live protocol state; trainers still
        call clean_uncommitted() at process start for crashed attempts."""
        vdir = self._vdir(version)

        def write_rank_files():
            flat, _ = jax.tree_util.tree_flatten_with_path(tree)
            dtypes = {}
            to_save = {}
            for path, leaf in flat:
                key = _path_key(path)
                shards = _owned_shards(leaf)
                if shards is not None:
                    # fully-replicated leaves land on every process with
                    # replica_id spread; only write replica 0's copy
                    for s in shards:
                        arr = np.asarray(s.data)
                        to_save[self._shard_key(key, s.index,
                                                leaf.shape)] = arr
                        if _BFLOAT16 is not None \
                                and arr.dtype == _BFLOAT16:
                            dtypes[key] = "bfloat16"
                elif rank == 0:
                    arr = np.asarray(leaf)
                    index = tuple(slice(0, d) for d in arr.shape)
                    to_save[self._shard_key(key, index, arr.shape)] = arr
                    if _BFLOAT16 is not None and arr.dtype == _BFLOAT16:
                        dtypes[key] = "bfloat16"
            packed = {}
            for k, arr in to_save.items():
                if _BFLOAT16 is not None and arr.dtype == _BFLOAT16:
                    arr = arr.view(np.uint16)
                packed[k] = arr
            buf = io.BytesIO()
            np.savez(buf, **packed)
            payload = buf.getvalue()
            with self._fs.open("%s/arrays.r%d.npz" % (vdir, rank),
                               "wb") as f:
                f.write(payload)
            with self._fs.open("%s/shardmeta.r%d.json" % (vdir, rank),
                               "w") as f:
                json.dump({"crc": zlib.crc32(payload), "dtypes": dtypes,
                           "nbytes": len(payload)}, f)

        def commit(nonce):
            crcs = {}
            dtypes_all = {}
            for r in range(nranks):
                with self._fs.open("%s/shardmeta.r%d.json" % (vdir, r),
                                   "r") as f:
                    sm = json.load(f)
                crcs[str(r)] = sm["crc"]
                dtypes_all.update(sm["dtypes"])
            with self._fs.open(vdir + "/meta.json", "w") as f:
                json.dump({"meta": meta or {}, "dtypes": dtypes_all}, f)
            with self._fs.open(vdir + "/MANIFEST", "w") as f:
                json.dump({"version": version, "sharded": True,
                           "ranks": nranks, "crcs": crcs,
                           "attempt": nonce}, f)

        return self._sharded_protocol(version, rank, nranks, barrier,
                                      timeout, write_rank_files, commit)

    def _sharded_protocol(self, version, rank, nranks, barrier, timeout,
                          write_rank_files, commit):
        """The sentinel/nonce commit protocol shared by the npz (sync)
        and stream (async) sharded writers. ``write_rank_files()``
        writes this rank's data + shardmeta files (idempotent: it may
        run again under a fresh nonce after a stale-attempt reset);
        ``commit(nonce)`` is rank 0's manifest assembly, run only once
        every done marker carries the current nonce. The MANIFEST the
        commit writes MUST record ``attempt: nonce`` — the non-rank-0
        resolution loop keys on it."""
        t0 = time.monotonic()
        vdir = self._vdir(version)
        use_sentinel = barrier is None and nranks > 1
        nonce = None
        if rank == 0:
            self._fs.delete_tree(vdir)
            self._fs.makedirs(vdir)
            if use_sentinel:
                nonce = uuid.uuid4().hex
                with self._fs.open(vdir + "/STARTED", "w") as f:
                    f.write(nonce)
        if barrier is not None:
            barrier()  # rank0's directory reset must precede any write

        def read_sentinel():
            try:
                with self._fs.open(vdir + "/STARTED", "r") as f:
                    return f.read() or None
            except (IOError, OSError):
                return None

        if rank == 0 or not use_sentinel:
            write_rank_files()
            if use_sentinel:
                with self._fs.open("%s/done.r%d" % (vdir, rank),
                                   "w") as f:
                    f.write(nonce)
        else:
            # Write-then-wait-for-resolution loop. A rank cannot tell a
            # stale sentinel (crashed/older attempt) from rank 0 merely
            # being slow, so after publishing against nonce N it waits
            # until either the MANIFEST commits with attempt == N (rank
            # 0 only commits once every done marker carries its nonce,
            # so a matching commit proves our files belong to it) or the
            # sentinel's nonce changes (rank 0 reset the attempt we had
            # joined and deleted our files — rewrite under the new one).

            def manifest_attempt():
                try:
                    with self._fs.open(vdir + "/MANIFEST", "r") as f:
                        return json.load(f).get("attempt")
                except (IOError, OSError, ValueError):
                    return None

            deadline = time.monotonic() + timeout
            committed = False
            while not committed:
                self._fs_wait(
                    lambda: read_sentinel() is not None,
                    "rank 0 STARTED sentinel (v%d)" % version,
                    max(0.01, deadline - time.monotonic()))
                nonce = read_sentinel()
                if nonce is None:
                    continue
                try:
                    write_rank_files()
                    # done marker is written (and closed) strictly
                    # AFTER the data files: rank 0 never json.loads a
                    # shardmeta that is still streaming to disk
                    with self._fs.open("%s/done.r%d" % (vdir, rank),
                                       "w") as f:
                        f.write(nonce)
                except (IOError, OSError):
                    # rank 0's delete_tree reset the dir under our open
                    # writes (we had joined a stale attempt): re-enter
                    # the loop and rewrite under the fresh nonce
                    if time.monotonic() > deadline:
                        raise
                    continue
                delay = 0.02
                while True:
                    if manifest_attempt() == nonce:
                        committed = True
                        break
                    cur = read_sentinel()
                    if cur is not None and cur != nonce:
                        break  # superseded: retry under the new nonce
                    if time.monotonic() > deadline:
                        raise IOError(
                            "sharded save v%d rank %d: no commit or "
                            "supersession for attempt %s"
                            % (version, rank, nonce))
                    time.sleep(delay)
                    delay = min(delay * 1.5, 0.25)

        if barrier is not None:
            barrier()  # every rank's file must exist before the commit
        if rank == 0:
            if use_sentinel:
                def done_current(r):
                    try:
                        with self._fs.open("%s/done.r%d" % (vdir, r),
                                           "r") as f:
                            return f.read() == nonce
                    except (IOError, OSError):
                        return False
                self._fs_wait(
                    lambda: all(done_current(r) for r in range(nranks)),
                    "all %d rank done markers (v%d, attempt %s)"
                    % (nranks, version, nonce), timeout)
            commit(nonce)
            if use_sentinel:
                # retire the attempt's protocol state so a later save
                # at this version can never pair with this one
                for name in (["STARTED"]
                             + ["done.r%d" % r for r in range(nranks)]):
                    try:
                        self._fs.delete("%s/%s" % (vdir, name))
                    except (IOError, OSError):
                        pass
            logger.info("sharded checkpoint v%d committed (%d ranks)",
                        version, nranks)
            obs_events.emit("ckpt.saved", version=version,
                            mode="sharded", ranks=nranks)
            self._gc()
        _SAVE_MS.labels("sharded").observe((time.monotonic() - t0) * 1e3)
        return vdir

    def save_sharded_async(self, version, tree, meta=None, rank=0,
                           nranks=1, barrier=None, timeout=120.0,
                           on_commit=None):
        """Async sharded save: phase-1 snapshot of this rank's owned
        shards runs here, then the whole sentinel/nonce protocol —
        including rank 0's directory reset and manifest commit — runs on
        a background driver, streaming per-shard entry files through the
        writer pool. Same visibility rules as save_sharded; the stream
        shardmeta/MANIFEST carry ``format: "stream"`` with the per-file
        entry tables instead of per-rank npz crcs."""
        self.drain()
        entries, dtypes, blocked_s = self._snapshot(
            self._snapshot_sharded, tree, rank)
        handle = SaveHandle(version)
        handle.blocked_s = blocked_s
        save_span = obs_trace.current()  # the trainer's `save`, or None
        vdir = self._vdir(version)

        def write_rank_files():
            table, total = self._write_entries(vdir, "r%d_" % rank,
                                               entries)
            with self._fs.open("%s/shardmeta.r%d.json" % (vdir, rank),
                               "w") as f:
                json.dump({"format": "stream", "entries": table,
                           "dtypes": dtypes, "nbytes": total}, f)

        def commit(nonce):
            entries_all = {}
            dtypes_all = {}
            total = 0
            for r in range(nranks):
                with self._fs.open("%s/shardmeta.r%d.json" % (vdir, r),
                                   "r") as f:
                    sm = json.load(f)
                entries_all.update(sm["entries"])
                dtypes_all.update(sm["dtypes"])
                total += sm["nbytes"]
            with self._fs.open(vdir + "/meta.json", "w") as f:
                json.dump({"meta": meta or {}, "dtypes": dtypes_all}, f)
            with self._fs.open(vdir + "/MANIFEST", "w") as f:
                json.dump({"version": version, "sharded": True,
                           "format": "stream", "ranks": nranks,
                           "entries": entries_all, "nbytes": total,
                           "attempt": nonce}, f)

        def persist():
            p0 = time.perf_counter()
            try:
                with obs_trace.span("save.persist", stage=True,
                                    parent=save_span, version=version):
                    out = self._sharded_protocol(version, rank, nranks,
                                                 barrier, timeout,
                                                 write_rank_files, commit)
                if on_commit is not None:
                    # same contract as save_async: commit observers
                    # are best-effort once the protocol completed
                    try:
                        on_commit()
                    except Exception:
                        logger.exception(
                            "on_commit callback for v%d failed",
                            version)
                handle._finish(out, persist_s=time.perf_counter() - p0)
            except BaseException as e:  # noqa: BLE001 — surfaces via result()
                handle._finish(None, exc=e,
                               persist_s=time.perf_counter() - p0)

        with self._async_lock:
            self._inflight = handle
        threading.Thread(target=persist, daemon=False,
                         name="ckpt-persist-%d.r%d" % (version, rank)
                         ).start()
        return handle

    def _restore_sharded(self, vdir, manifest, meta_blob, target):
        if target is None:
            raise IOError("sharded checkpoint restore needs a target "
                          "structure (shapes/dtypes)")
        flat, treedef = jax.tree_util.tree_flatten_with_path(target)
        specs = {}
        for path, leaf in flat:
            specs[_path_key(path)] = (tuple(leaf.shape),
                                      np.dtype(leaf.dtype))
        buffers = {}
        filled = {k: 0 for k in specs}

        def paste(skey, arr):
            key, _, spans = skey.rpartition("@")
            shape, dtype = specs[key]
            arr = _untag_array(arr, meta_blob["dtypes"].get(key))
            if key not in buffers:
                buffers[key] = np.zeros(shape, dtype)
            idx = tuple(slice(a, b) for a, b in _parse_spans(spans))
            buffers[key][idx] = arr
            filled[key] += arr.size

        if manifest.get("format") == "stream":
            pool = self._io_pool()
            futs = [(skey, pool.submit(self._read_entry_file,
                                       "%s/%s" % (vdir, entry["file"]),
                                       entry))
                    for skey, entry in manifest["entries"].items()
                    if skey.rpartition("@")[0] in specs]
            for skey, fut in futs:
                paste(skey, fut.result())
        else:
            def read_rank(r):
                with self._fs.open("%s/arrays.r%d.npz" % (vdir, r),
                                   "rb") as f:
                    payload = f.read()
                if zlib.crc32(payload) != manifest["crcs"][str(r)]:
                    raise IOError("checksum mismatch in %s rank %d"
                                  % (vdir, r))
                return payload
            payloads = list(self._io_pool().map(
                read_rank, range(int(manifest["ranks"]))))
            for payload in payloads:
                npz = np.load(io.BytesIO(payload))
                for skey in npz.files:
                    if skey.rpartition("@")[0] not in specs:
                        continue
                    paste(skey, npz[skey])
        missing = {k for k in specs if filled[k] < int(np.prod(
            specs[k][0], dtype=np.int64))}
        # scalars: prod(())==1, filled must be >= 1
        if missing:
            raise MissingKeysError(missing)
        keys = [_path_key(p) for p, _ in flat]
        return jax.tree_util.tree_unflatten(treedef,
                                            [buffers[k] for k in keys])

    # -- placed (locality-aware) restore -------------------------------------

    def load_manifest(self, version):
        """(vdir, manifest, meta_blob) of a committed version — the
        shared preamble of both placed restore paths (FS and peer)."""
        vdir = self._vdir(version)
        with self._fs.open(vdir + "/MANIFEST", "r") as f:
            manifest = json.load(f)
        with self._fs.open(vdir + "/meta.json", "r") as f:
            meta_blob = json.load(f)
        return vdir, manifest, meta_blob

    def _fill_stream(self, vdir, manifest, meta_blob, pt, keys=None):
        """Fill a PlacedTarget from a stream-format version dir,
        restricted to ``keys`` (None = every key). Entries whose
        manifest records chunk crcs and whose needed row hull is a
        strict subset of the entry are fetched with fs.read_range over
        just those leading-axis rows (chunk-aligned, per-chunk crc
        verified); everything else rides the whole-file reader."""
        pool = self._io_pool()
        todo = []
        for skey, entry in manifest["entries"].items():
            key, _, spans_s = skey.rpartition("@")
            if key not in pt.need or (keys is not None
                                      and key not in keys):
                continue
            entry_spans = _parse_spans(spans_s)
            pt.check_bounds(key, entry_spans)
            rows = pt.needed_rows(key, entry_spans)
            if rows is None:
                continue  # skip the file read entirely
            r0, r1 = rows
            nrows = (entry_spans[0][1] - entry_spans[0][0]
                     if entry_spans else 1)
            if entry.get("chunk_crcs") is not None and entry_spans \
                    and 0 < (r1 - r0) < nrows:
                a0 = entry_spans[0][0]
                sub = ((a0 + r0, a0 + r1),) + entry_spans[1:]
                todo.append((key, sub, pool.submit(
                    self._read_entry_rows,
                    "%s/%s" % (vdir, entry["file"]), entry, r0, r1)))
            else:
                todo.append((key, entry_spans, pool.submit(
                    self._read_entry_file,
                    "%s/%s" % (vdir, entry["file"]), entry)))
        for key, spans, fut in todo:
            pt.paste(key, spans, _untag_array(
                fut.result(), meta_blob["dtypes"].get(key)))

    def fill_placed_from_fs(self, version, pt, keys=None):
        """Fill a PlacedTarget's device blocks from ``version``'s STREAM
        files, restricted to ``keys`` (None = all): the per-span FS
        fallback of the peer restore plane. Raises IOError for
        non-stream layouts — the caller then falls back to a wholesale
        restore_placed. Returns the meta blob."""
        vdir, manifest, meta_blob = self.load_manifest(version)
        if manifest.get("format") != "stream":
            raise IOError("fill_placed_from_fs needs a stream-format "
                          "version (v%d is %s)" % (version,
                          "sharded npz" if manifest.get("sharded")
                          else "dense npz"))
        self._fill_stream(vdir, manifest, meta_blob, pt, keys)
        return meta_blob

    def restore_placed(self, version, target, shardings):
        """Restore ``version`` directly into sharded jax.Arrays laid out
        by ``shardings`` (a pytree matching ``target``).

        The scalable restore: host memory is O(local device blocks),
        not O(full model), and each process reads only the shard entries
        overlapping its own blocks — stream entries with recorded chunk
        crcs are fetched with fs.read_range over just the needed
        leading-axis rows, so a process that owns 1/Nth of a leaf pulls
        ~1/Nth of its bytes. Works over BOTH layouts — sharded files and
        dense files — and across RESHAPED shardings: any overlap between
        saved spans and needed device blocks is assembled, so an 8-way
        dp checkpoint restores onto a 4-way mesh or a different tp
        layout. A checkpoint whose saved extent EXCEEDS the target shape
        raises (never silently truncates); one that covers less raises
        MissingKeysError.
        """
        vdir, manifest, meta_blob = self.load_manifest(version)
        pt = PlacedTarget(target, shardings)

        if manifest.get("format") == "stream":
            # stream layout (dense OR sharded): bounds-check every entry
            # from the manifest table, then range-read ONLY the
            # overlapping spans, in parallel across the io pool
            self._fill_stream(vdir, manifest, meta_blob, pt)
        elif manifest.get("sharded"):
            def read_rank(r):
                with self._fs.open("%s/arrays.r%d.npz" % (vdir, r),
                                   "rb") as f:
                    payload = f.read()
                if zlib.crc32(payload) != manifest["crcs"][str(r)]:
                    raise IOError("checksum mismatch in %s rank %d"
                                  % (vdir, r))
                return payload
            for payload in self._io_pool().map(
                    read_rank, range(int(manifest["ranks"]))):
                npz = np.load(io.BytesIO(payload))
                for skey in npz.files:
                    key, _, spans_s = skey.rpartition("@")
                    if key not in pt.need:
                        continue
                    entry_spans = _parse_spans(spans_s)
                    pt.check_bounds(key, entry_spans)
                    if not pt.overlaps_local(key, entry_spans):
                        continue  # skip the decompress entirely
                    pt.paste(key, entry_spans, _untag_array(
                        npz[skey], meta_blob["dtypes"].get(key)))
        else:
            with self._fs.open(vdir + "/arrays.npz", "rb") as f:
                payload = f.read()
            if zlib.crc32(payload) != manifest["crc"]:
                raise IOError("checksum mismatch in %s" % vdir)
            npz = np.load(io.BytesIO(payload))
            for key in npz.files:
                if key not in pt.need:
                    continue
                # entry spans from the SAVED array's real shape: a
                # larger stored tensor must raise, not truncate
                arr = npz[key]
                entry_spans = tuple((0, d) for d in arr.shape)
                pt.check_bounds(key, entry_spans)
                pt.paste(key, entry_spans, _untag_array(
                    arr, meta_blob["dtypes"].get(key)))

        missing = pt.missing()
        if missing:
            raise MissingKeysError(missing)
        return version, pt.assemble(), meta_blob["meta"]

    # -- restore -------------------------------------------------------------

    def restore_latest(self, target=None):
        """Restore the newest valid checkpoint.

        Returns (version, tree, meta) or None. Corrupt versions (bad crc /
        missing manifest) are skipped, falling back to the previous one —
        the integrity contract of the reference (doc/fault_tolerance.md).
        If ``target`` is given, leaves are restored into its structure.
        """
        for version in reversed(self.versions()):
            try:
                return self.restore(version, target)
            except Exception as e:  # noqa: BLE001 — fall back to older ckpt
                logger.warning("checkpoint v%d unreadable (%r); trying older",
                               version, e)
        return None

    def restore(self, version, target=None):
        t0 = time.monotonic()
        try:
            out = self._restore(version, target)
        except Exception:
            obs_events.emit("ckpt.restore_failed", version=version)
            raise
        _RESTORE_MS.observe((time.monotonic() - t0) * 1e3)
        obs_events.emit("ckpt.restored", version=version)
        return out

    def _restore(self, version, target=None):
        vdir = self._vdir(version)
        with self._fs.open(vdir + "/MANIFEST", "r") as f:
            manifest = json.load(f)
        if manifest.get("sharded"):
            with self._fs.open(vdir + "/meta.json", "r") as f:
                meta_blob = json.load(f)
            tree = self._restore_sharded(vdir, manifest, meta_blob, target)
            return version, tree, meta_blob["meta"]
        if manifest.get("format") == "stream":
            with self._fs.open(vdir + "/meta.json", "r") as f:
                meta_blob = json.load(f)
            tree = self._restore_stream(vdir, manifest, meta_blob, target)
            return version, tree, meta_blob["meta"]
        with self._fs.open(vdir + "/arrays.npz", "rb") as f:
            payload = f.read()
        if zlib.crc32(payload) != manifest["crc"]:
            raise IOError("checksum mismatch in %s" % vdir)
        with self._fs.open(vdir + "/meta.json", "r") as f:
            meta_blob = json.load(f)
        npz = np.load(io.BytesIO(payload))
        arrays = {}
        for key in npz.files:
            arr = npz[key]
            if meta_blob["dtypes"].get(key) == "bfloat16":
                if _BFLOAT16 is None:  # pragma: no cover
                    raise IOError("bfloat16 checkpoint needs ml_dtypes")
                arr = arr.view(_BFLOAT16)
            arrays[key] = arr

        if target is None:
            tree = _unflatten_to_nested(arrays)
        else:
            keys, treedef = _paths(target)
            missing = set(keys) - set(arrays)
            if missing:
                raise MissingKeysError(missing)
            tree = jax.tree_util.tree_unflatten(treedef,
                                                [arrays[k] for k in keys])
        return version, tree, meta_blob["meta"]

    def _restore_stream(self, vdir, manifest, meta_blob, target):
        """Restore a dense stream-format version: every entry file is
        read (and CRC-checked) in parallel across the io pool. Dense
        stream entries are single full-span entries per key."""
        pool = self._io_pool()
        futs = [(skey, pool.submit(self._read_entry_file,
                                   "%s/%s" % (vdir, entry["file"]),
                                   entry))
                for skey, entry in manifest["entries"].items()]
        arrays = {}
        for skey, fut in futs:
            key, _, _ = skey.rpartition("@")
            arrays[key] = _untag_array(fut.result(),
                                       meta_blob["dtypes"].get(key))
        if target is None:
            return _unflatten_to_nested(arrays)
        keys, treedef = _paths(target)
        missing = set(keys) - set(arrays)
        if missing:
            raise MissingKeysError(missing)
        return jax.tree_util.tree_unflatten(treedef,
                                            [arrays[k] for k in keys])


def _unflatten_to_nested(arrays):
    """Rebuild a nested dict from flat path keys (lists come back as dicts
    keyed by index strings; fine for structure-free inspection)."""
    root = {}
    for key, arr in arrays.items():
        parts = key.split(_SEP)
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return root
