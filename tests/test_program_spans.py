"""The program's stage spans: what a live resize, the first step after it
and a save leave in the span ring (edl_tpu/obs/trace.py), that the
`_resize_timing` stamps and `SaveHandle.blocked_s` are read off those
spans, that the spans bound the seconds the TimeLedger charges to
`resize_pause`, and that a profiler capture holds them as `edl:`
annotations on the host line.

Runs on the conftest's virtual CPU devices: the names, links and ordering
of spans are facts of the program; no duration here is a device number.
"""

import glob
import os
import subprocess
import sys
import threading

import jax
import numpy as np
import optax
import pytest

from edl_tpu.models import linear
from edl_tpu.obs import ledger as obs_ledger
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.robustness import faults
from edl_tpu.runtime import trainer as trainer_mod
from edl_tpu.runtime.checkpoint import CheckpointManager
from edl_tpu.runtime.mesh import make_mesh
from edl_tpu.runtime.trainer import ElasticTrainer
from edl_tpu.utils.errors import LiveResizeError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOTAL_BATCH = 64
BATCHES = [linear.synthetic_batch(TOTAL_BATCH, seed=i) for i in range(8)]

RESIZE_STAGES = ["resize.drain", "resize.mesh", "resize.device_put",
                 "resize.build_step"]
#: what `resize.device_put` is made of: its children, in order
PUT_STAGES = ["resize.device_put.dispatch", "resize.device_put.wait"]
PREWARM_STAGES = ["resize.prewarm_fingerprint", "resize.prewarm_load"]


def _trainer(n_devices, ckpt=None, **kw):
    return ElasticTrainer(
        linear.loss_fn, linear.init_params(), optax.sgd(0.05),
        total_batch_size=TOTAL_BATCH,
        mesh=make_mesh(devices=jax.devices()[:n_devices]),
        checkpoint_dir=ckpt, **kw)


def _step(trainer, i):
    return trainer.train_step(trainer.local_batch_slice(BATCHES[i]))


def _end(span):
    return span["t0"] + span["dur_ms"] / 1e3


def _named(spans, name, **tags):
    return [s for s in spans if s["name"] == name
            and all(s["tags"].get(k) == v for k, v in tags.items())]


def _tags(span):
    """A span's tags less `gc_ms`, which any recorded stage span carries
    when the collector happened to run while it was open."""
    return {k: v for k, v in span["tags"].items() if k != "gc_ms"}


def _in_order_inside(parent, children, eps=1e-6):
    """`children` (sorted by start) lie inside `parent`, none overlaps."""
    assert children[0]["t0"] >= parent["t0"] - eps
    assert _end(children[-1]) <= _end(parent) + eps
    for a, b in zip(children, children[1:]):
        assert _end(a) <= b["t0"] + eps, (a["name"], b["name"])


@pytest.fixture()
def clean_ring():
    obs_trace.TRACER.clear()
    yield obs_trace.TRACER
    obs_trace.TRACER.clear()


#: the resizes of the `arc` fixture, in the order it makes them: where
#: each takes its step from, and what its root says
CASES = {
    "memory_shrink": {"from_devices": 4, "to_devices": 2,
                      "prewarm": "hit", "step_source": "memory"},
    "memory_grow": {"from_devices": 2, "to_devices": 4,
                    "prewarm": "hit", "step_source": "memory"},
    "disk": {"from_devices": 4, "to_devices": 2,
             "prewarm": "hit", "step_source": "disk"},
    "compile": {"from_devices": 2, "to_devices": 1,
                "prewarm": "miss", "step_source": "compile"},
}
ALL_CASES = pytest.mark.parametrize("case", list(CASES))


@pytest.fixture(scope="module")
def arc(tmp_path_factory):
    """One 4 -> 2 -> 4 arc with an async save before each resize and the
    2-chip step prewarmed in this process, as the benchmark's elastic
    cell runs it: both resizes take a step the trainer holds ready. Then
    a second trainer, whose table is empty: its 4 -> 2 finds the first
    one's artifact on disk, its 2 -> 1 goes to a world nobody compiled
    for. The ring's spans, the timing record after each first step, and
    what the ledger charged to `resize_pause` over each resize."""
    tmp = tmp_path_factory.mktemp("arc")
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp / "cache"))
    records, pauses, cases = {}, {}, iter(CASES)

    def resize(tr, world, batch_no):
        before = obs_ledger.LEDGER.totals()["resize_pause"]
        tr.live_resize(world)
        _step(tr, batch_no)
        case = next(cases)
        pauses[case] = obs_ledger.LEDGER.totals()["resize_pause"] - before
        records[case] = tr.resize_timing

    tr = _trainer(4, ckpt=str(tmp / "ckpt"), async_save=True)
    other = _trainer(4)
    try:
        _step(tr, 0)
        assert tr.prewarm_resize_compiles([2], block=True) == [2]
        _step(other, 0)
        obs_trace.TRACER.clear()
        for i, world in enumerate((2, 4)):
            tr.save()
            resize(tr, world, 1 + i)
        tr.wait_for_save()
        for i, world in enumerate((2, 1)):
            resize(other, world, 1 + i)
        spans = obs_trace.TRACER.spans()
    finally:
        tr.close()
        other.close()
        mp.undo()
        obs_trace.TRACER.clear()
    return {"spans": spans, "records": records, "pauses": pauses}


def _resize(arc, case):
    """(root, the spans of its trace) of the resize called `case`."""
    roots = sorted(_named(arc["spans"], "resize.live"),
                   key=lambda s: s["t0"])
    assert len(roots) == len(CASES)
    root = roots[list(CASES).index(case)]
    return root, [s for s in arc["spans"]
                  if s["trace_id"] == root["trace_id"]]


# -- a live resize ---------------------------------------------------------


@ALL_CASES
def test_resize_leaves_one_root_with_the_table_s_children(arc, case):
    root, trace = _resize(arc, case)
    assert root["parent_id"] is None
    tags = _tags(root)
    # the second trainer saves nothing; the first one's few bytes may be
    # written before its resize looks (tests/test_live_resize.py holds
    # the write open to tell the cases apart)
    assert tags.pop("drain") in (("deferred", "idle")
                                 if case.startswith("memory") else ("idle",))
    assert tags == CASES[case]
    children = [s for s in trace if s["parent_id"] == root["span_id"]
                and s["name"] != "resize.first_step"]
    # a step the trainer holds ready needs no name and no load, so
    # neither prewarm span is opened; nor is it when the glob finds no
    # artifact for the world. Only the trainer with an empty table and
    # an artifact on disk fingerprints and loads
    want = RESIZE_STAGES + (PREWARM_STAGES if case == "disk" else [])
    assert [s["name"] for s in sorted(children, key=lambda s: s["t0"])] \
        == want
    # the table's one stage with stages of its own: the reshard
    [put] = [s for s in children if s["name"] == "resize.device_put"]
    inner = [s for s in trace if s["parent_id"] == put["span_id"]]
    assert [s["name"] for s in sorted(inner, key=lambda s: s["t0"])] \
        == PUT_STAGES
    assert sorted(s["name"] for s in trace
                  if s["name"].startswith("resize.device_put.")) \
        == PUT_STAGES


@ALL_CASES
def test_resize_children_lie_inside_the_root_and_do_not_overlap(arc, case):
    root, trace = _resize(arc, case)
    children = sorted((s for s in trace
                       if s["parent_id"] == root["span_id"]
                       and s["name"] != "resize.first_step"),
                      key=lambda s: s["t0"])
    _in_order_inside(root, children)
    [put] = [s for s in children if s["name"] == "resize.device_put"]
    _in_order_inside(put, sorted((s for s in trace
                                  if s["parent_id"] == put["span_id"]),
                                 key=lambda s: s["t0"]))


@ALL_CASES
def test_first_step_joins_the_trace_of_its_resize(arc, case):
    root, trace = _resize(arc, case)
    [first] = [s for s in trace if s["name"] == "resize.first_step"]
    # it follows the resize it ends: linked to the root, begun after it
    assert first["parent_id"] == root["span_id"]
    assert first["t0"] >= _end(root) - 1e-6
    inner = sorted((s for s in trace if s["parent_id"] == first["span_id"]),
                   key=lambda s: s["t0"])
    assert [s["name"] for s in inner] == ["resize.first_dispatch",
                                          "resize.first_result"]
    assert inner[0]["t0"] >= first["t0"] - 1e-6
    assert _end(inner[0]) <= inner[1]["t0"] + 1e-6
    assert _end(inner[1]) <= _end(first) + 1e-6
    assert {s["name"] for s in trace} <= set(
        ["resize.live", "resize.first_step", "resize.first_dispatch",
         "resize.first_result"] + RESIZE_STAGES + PUT_STAGES
        + PREWARM_STAGES)


@ALL_CASES
def test_first_dispatch_says_what_jax_did(arc, case):
    _, trace = _resize(arc, case)
    [d] = [s for s in trace if s["name"] == "resize.first_dispatch"]
    jax_s = _tags(d)
    assert set(jax_s) == {"jax_trace_s", "jax_lower_s", "jax_compile_s",
                          "jax_cache_load_s"}
    total = sum(jax_s.values())
    assert total <= d["dur_ms"] / 1e3 + 1e-3
    if case != "compile":
        # an executable held ready or loaded: nothing to trace or build
        assert total == 0.0
    else:
        # a world nobody compiled for: the step is traced and built
        assert jax_s["jax_trace_s"] > 0
        assert jax_s["jax_lower_s"] > 0
        assert jax_s["jax_compile_s"] > 0


@ALL_CASES
def test_timing_record_is_read_off_the_spans(arc, case):
    _, trace = _resize(arc, case)
    rec = arc["records"][case]

    def total(names):
        return sum(s["dur_ms"] for s in trace if s["name"] in names) / 1e3

    assert rec["drain_s"] == pytest.approx(total(["resize.drain"]),
                                           abs=2e-6)
    assert rec["reshard_s"] == pytest.approx(
        total(RESIZE_STAGES[1:] + PREWARM_STAGES), abs=2e-6)
    assert rec["compile_s"] == pytest.approx(
        total(["resize.first_dispatch"]), abs=1e-9)
    assert rec["first_step_s"] == pytest.approx(
        total(["resize.first_result"]), abs=1e-9)
    assert rec["mode"] == "live"
    assert {k: rec[k] for k in CASES[case]} == CASES[case]


@ALL_CASES
def test_ledger_pause_is_bounded_by_the_spans(arc, case):
    """The ledger's `resize_pause` runs from the transition before
    `resize.live` opens to the one after `resize.first_step` closes: the
    two spans, plus what the caller did between them (here: nothing but
    placing the next batch)."""
    root, trace = _resize(arc, case)
    [first] = [s for s in trace if s["name"] == "resize.first_step"]
    spans_s = (root["dur_ms"] + first["dur_ms"]) / 1e3
    wall_s = _end(first) - root["t0"]
    pause = arc["pauses"][case]
    # the drain nests ckpt_block over the pause and takes its seconds
    [drain] = [s for s in trace if s["name"] == "resize.drain"]
    assert spans_s - drain["dur_ms"] / 1e3 - 5e-3 <= pause <= wall_s + 5e-3


@ALL_CASES
def test_device_put_says_what_it_moved_and_what_ran_beside_it(arc, case):
    _, trace = _resize(arc, case)
    [put] = [s for s in trace if s["name"] == "resize.device_put"]
    tags = _tags(put)
    assert set(tags) == {"source", "bytes", "leaves", "bytes_moved",
                         "persist_inflight", "arrays_crossed",
                         "leaves_leafwise"}
    assert tags["source"] == "local"
    state = _trainer(1)
    try:
        leaves = jax.tree_util.tree_leaves(state.train_state)
    finally:
        state.close()
    assert tags["leaves"] == len(leaves)
    assert tags["bytes"] == sum(x.nbytes for x in leaves) > 0
    # the state is replicated: a shrink leaves every chip that stays
    # with what it held, a grow lands the whole tree on each chip gained
    gained = CASES[case]["to_devices"] - CASES[case]["from_devices"]
    assert tags["bytes_moved"] == tags["bytes"] * max(0, gained)
    # and it crosses as one array a leaf a chip gained, none re-sliced
    assert tags["arrays_crossed"] == tags["leaves"] * max(0, gained)
    assert tags["leaves_leafwise"] == 0
    assert tags["persist_inflight"] in (True, False)
    if not case.startswith("memory"):
        assert tags["persist_inflight"] is False    # it saves nothing


@pytest.mark.parametrize("old,new,want", [
    # replicated, 4 -> 2 chips and back: nothing, then all of it twice
    ((4, None), (2, None), 0),
    ((2, None), (4, None), 2 * 8 * 6 * 4),
    # rows split over the chips: 4 -> 2 gives each chip that stays a
    # half where it held a quarter (the whole half lands: another index)
    ((4, "dp"), (2, "dp"), 2 * 4 * 6 * 4),
    # replicated -> split: each chip keeps a slice of what it held, but
    # under another index, so it counts as landed
    ((2, None), (2, "dp"), 2 * 4 * 6 * 4),
    ((2, "dp"), (2, "dp"), 0),
    # a leaf that was on the host lands whole on every chip
    (None, (2, None), 2 * 8 * 6 * 4),
])
def test_bytes_moved_counts_indices_a_device_did_not_hold(old, new, want):
    from jax.sharding import NamedSharding, PartitionSpec

    def sharding(spec):
        if spec is None:
            return None
        n, axis = spec
        return NamedSharding(make_mesh(devices=jax.devices()[:n]),
                             PartitionSpec(axis))

    aval = jax.ShapeDtypeStruct((8, 6), jax.numpy.float32)
    # a leaf with no abstract value (a host scalar) is not counted
    got = trainer_mod._bytes_moved([aval, None],
                                   [sharding(old), None],
                                   [sharding(new), sharding(new)])
    assert got == want


def test_bytes_moved_is_attached_at_the_first_step(clean_ring):
    """The account walks the leaves' placements, so it is kept out of
    `resize.live`: it runs behind the device's first step, and the span
    in the ring gains the tag then."""
    tr = _trainer(2)
    try:
        _step(tr, 0)
        tr.live_resize(4)
        [put] = clean_ring.find(name="resize.device_put")
        assert "bytes_moved" not in put["tags"]
        _step(tr, 1)
        [put] = clean_ring.find(name="resize.device_put")
        assert put["tags"]["bytes_moved"] == 2 * put["tags"]["bytes"]
        assert tr._put_account == (None, None)
    finally:
        tr.close()


def test_rollback_closes_the_spans_it_opened(clean_ring):
    tr = _trainer(4)
    _step(tr, 0)
    clean_ring.clear()
    plane = faults.FaultPlane(seed=3)
    plane.inject("resize.live.reshard", "error", error="RpcError")
    plane.install()
    try:
        with pytest.raises(LiveResizeError):
            tr.live_resize(2)
    finally:
        plane.uninstall()
        tr.close()
    names = [s["name"] for s in clean_ring.spans()]
    # the fault fires inside resize.mesh: drain and mesh closed, the
    # root closed over them, nothing later was opened
    assert names == ["resize.drain", "resize.mesh", "resize.live"]
    assert obs_trace.current() is None


def test_incarnation_first_step_is_a_trace_of_its_own(clean_ring):
    tr = _trainer(2)
    try:
        _step(tr, 0)
        rec = tr.resize_timing
        _step(tr, 1)    # a steady step opens no span
    finally:
        tr.close()
    spans = clean_ring.spans()
    assert sorted(s["name"] for s in spans) == [
        "resize.first_dispatch", "resize.first_result", "resize.first_step"]
    [first] = _named(spans, "resize.first_step")
    assert first["parent_id"] is None
    assert {s["trace_id"] for s in spans} == {first["trace_id"]}
    [d] = _named(spans, "resize.first_dispatch")
    assert rec["compile_s"] == pytest.approx(d["dur_ms"] / 1e3, abs=1e-9)
    assert rec["mode"] == "stop_resume"


def test_prewarm_spans_at_an_incarnation_s_first_step_are_roots(
        tmp_path, monkeypatch, clean_ring):
    """A restarted process finds the artifact an earlier incarnation
    prewarmed for its world: the two prewarm spans run before the step
    is dispatched, outside any live resize, and `compile_s` counts them
    with the dispatch."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    big = _trainer(4)
    try:
        _step(big, 0)
        assert big.prewarm_resize_compiles([2], block=True) == [2]
    finally:
        big.close()
    clean_ring.clear()
    tr = _trainer(2)
    try:
        _step(tr, 0)
        rec = tr.resize_timing
    finally:
        tr.close()
    spans = clean_ring.spans()
    [fp] = _named(spans, "resize.prewarm_fingerprint")
    [load] = _named(spans, "resize.prewarm_load")
    [d] = _named(spans, "resize.first_dispatch")
    assert fp["parent_id"] is None and load["parent_id"] is None
    assert _tags(fp) == {"world": 2}
    assert rec["compile_s"] == pytest.approx(
        (fp["dur_ms"] + load["dur_ms"] + d["dur_ms"]) / 1e3, abs=1e-9)
    assert sum(_tags(d).values()) == 0.0   # loaded, not built


# -- a save ----------------------------------------------------------------


def test_save_root_with_its_stages_and_the_writer_s_span(arc):
    roots = _named(arc["spans"], "save")
    assert len(roots) == 2
    for root in roots:
        assert root["parent_id"] is None
        assert set(_tags(root)) == {"version"}
        trace = [s for s in arc["spans"]
                 if s["trace_id"] == root["trace_id"]]
        assert sorted(s["name"] for s in trace) == [
            "save", "save.drain_prev", "save.persist", "save.snapshot",
            "save.snapshot.start_transfers", "save.state_json"]
        [snap] = [s for s in trace if s["name"] == "save.snapshot"]
        [start] = [s for s in trace
                   if s["name"] == "save.snapshot.start_transfers"]
        # the snapshot's one part that is a span: the first thing it does
        assert start["parent_id"] == snap["span_id"]
        _in_order_inside(snap, [start])
        assert all(s["parent_id"] == root["span_id"]
                   for s in trace if s not in (root, start))
        on_thread = sorted((s for s in trace
                            if s["name"] not in ("save", "save.persist")
                            and s is not start),
                           key=lambda s: s["t0"])
        assert [s["name"] for s in on_thread] == [
            "save.state_json", "save.drain_prev", "save.snapshot"]
        assert on_thread[0]["t0"] >= root["t0"] - 1e-6
        assert _end(on_thread[-1]) <= _end(root) + 1e-6
        for a, b in zip(on_thread, on_thread[1:]):
            assert _end(a) <= b["t0"] + 1e-6
        [persist] = [s for s in trace if s["name"] == "save.persist"]
        assert persist["tags"]["version"] == root["tags"]["version"]
        # the writer starts once the snapshot is taken and may outlive
        # the call that started it
        assert persist["t0"] >= on_thread[-1]["t0"]


@pytest.mark.parametrize("sharded", [False, True])
def test_blocked_s_is_the_snapshot_span(tmp_path, clean_ring, sharded):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": jax.numpy.arange(12.0).reshape(3, 4)}
    try:
        if sharded:
            handle = mgr.save_sharded_async(3, tree)
        else:
            handle = mgr.save_async(3, tree)
        handle.result(timeout=30)
    finally:
        mgr.close()
    [snap] = _named(clean_ring.spans(), "save.snapshot")
    [persist] = _named(clean_ring.spans(), "save.persist")
    assert handle.blocked_s == pytest.approx(snap["dur_ms"] / 1e3,
                                             abs=1e-9)
    # called outside a trainer's `save`: each is a root of its own
    assert snap["parent_id"] is None and persist["parent_id"] is None
    assert _tags(persist) == {"version": 3}


def _state_tree(dp, split=False):
    """A state held by ``dp`` chips. Replicated: every chip holds every
    leaf. ``split``: `w` is cut in two over the model axis and each half
    is held by ``dp`` / 2 chips; the other leaves stay replicated."""
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = make_mesh(tp=2 if split else 1, devices=jax.devices()[:dp])
    rep = NamedSharding(mesh, PartitionSpec())
    tree = jax.device_put(
        {"w": jax.numpy.arange(48.0).reshape(8, 6),
         "b": jax.numpy.arange(8, dtype=jax.numpy.bfloat16),
         "n": jax.numpy.zeros((), jax.numpy.int32)}, rep)
    if split:
        tree["w"] = jax.device_put(
            tree["w"], NamedSharding(mesh, PartitionSpec("tp")))
    return tree


@pytest.mark.parametrize("dp", [2, 4])
@pytest.mark.parametrize("split", [False, True])
@pytest.mark.parametrize("sharded", [False, True])
def test_snapshot_says_what_it_is_made_of(tmp_path, clean_ring, sharded,
                                          split, dp):
    mgr = CheckpointManager(str(tmp_path))
    tree = _state_tree(dp, split)
    try:
        take = (mgr._snapshot_sharded, tree, 0) if sharded \
            else (mgr._snapshot_dense, tree)
        entries, _, seconds = mgr._snapshot(*take)
        again, _, _ = mgr._snapshot(*take)
    finally:
        mgr.close()
    first, second = sorted(_named(clean_ring.spans(), "save.snapshot"),
                           key=lambda s: s["t0"])
    starts = sorted(_named(clean_ring.spans(),
                           "save.snapshot.start_transfers"),
                    key=lambda s: s["t0"])
    assert [s["parent_id"] for s in starts] == [first["span_id"],
                                                second["span_id"]]
    tags = _tags(first)
    assert set(tags) == {"fetch_s", "copy_s", "bytes", "leaves",
                         "transfers_started", "transfer_bytes_started",
                         "bufs_new"}
    assert seconds == pytest.approx(first["dur_ms"] / 1e3, abs=1e-9)
    # the host bytes kept: one copy of each leaf, whoever held it
    kept = sum(a.nbytes for a in entries.values())
    assert tags["bytes"] == kept == 48 * 4 + 8 * 2 + 4
    assert tags["leaves"] == 3
    # a transfer is asked once of each distinct block, from the one chip
    # the fetch reads it from, however many chips hold it: what is asked
    # for is what is kept
    blocks = (2 if split else 1) + 2
    assert tags["transfers_started"] == blocks
    assert tags["transfer_bytes_started"] == kept
    # the three parts lie inside the span and are not counted twice
    _in_order_inside(first, [starts[0]])
    assert tags["fetch_s"] > 0 and tags["copy_s"] > 0
    assert (tags["fetch_s"] + tags["copy_s"]
            + starts[0]["dur_ms"] / 1e3) <= first["dur_ms"] / 1e3 + 1e-6
    # the second save finds its buffers in the pool: the dense snapshot
    # keeps a leaf whole, the sharded one a buffer a block
    assert tags["bufs_new"] == len(entries) == (blocks if sharded else 3)
    assert _tags(second)["bufs_new"] == 0
    assert sorted(again) == sorted(entries)


@pytest.mark.parametrize("split", [False, True])
@pytest.mark.parametrize("sharded", [False, True])
def test_snapshot_asks_for_the_buffers_it_reads(tmp_path, clean_ring,
                                                monkeypatch, sharded, split):
    """The request pass and the fetch pass name the same buffers of the
    same array objects: every buffer a fetch reads was asked for once
    before the first fetch, through the object the fetch reads it from
    (the host copy a request brings is kept by the Python object that
    asked, so `np.asarray` of another object over the same device buffer
    transfers again), and nothing else was asked for."""
    from jax._src import array as jax_array

    from edl_tpu.runtime import checkpoint as ckpt_mod

    def held(single, by):
        return (id(by), single.unsafe_buffer_pointer())

    def read_by_asarray(x):
        """`ArrayImpl._value`: a replicated array reads its first buffer
        as itself, a cut one the first single-device array of each
        distinct block."""
        if not isinstance(x, jax.Array):
            return []
        if x.is_fully_replicated:
            return [held(x._arrays[0], x)]
        first = {}
        for shard in x.addressable_shards:
            first.setdefault(str(shard.index), held(shard.data, shard.data))
        return list(first.values())

    log = []
    ask = jax_array.ArrayImpl._copy_single_device_array_to_host_async
    fetch = ckpt_mod._SnapshotAccount.fetch

    def asking(self):
        log.append(("ask", [held(self._arrays[0], self)]))
        return ask(self)

    def fetching(self, x):
        log.append(("read", read_by_asarray(x)))
        return fetch(self, x)

    monkeypatch.setattr(jax_array.ArrayImpl,
                        "_copy_single_device_array_to_host_async", asking)
    monkeypatch.setattr(ckpt_mod._SnapshotAccount, "fetch", fetching)
    tree = dict(_state_tree(4, split), host=np.ones(3, np.float32), py=2.5)
    mgr = CheckpointManager(str(tmp_path))
    try:
        if sharded:
            entries, _, _ = mgr._snapshot(mgr._snapshot_sharded, tree, 0)
        else:
            entries, _, _ = mgr._snapshot(mgr._snapshot_dense, tree)
    finally:
        monkeypatch.undo()
        mgr.close()
    first_read = [what for what, _ in log].index("read")
    asked = [buf for _, bufs in log[:first_read] for buf in bufs]
    after = log[first_read:]
    read = [buf for what, bufs in after if what == "read" for buf in bufs]
    assert len(asked) == len(set(asked)) == (4 if split else 3)
    assert sorted(read) == sorted(asked)
    # jax asks again inside a split leaf's own read: for nothing new
    assert {buf for _, bufs in after for buf in bufs} <= set(asked)
    [snap] = _named(clean_ring.spans(), "save.snapshot")
    assert snap["tags"]["transfers_started"] == len(asked)
    assert snap["tags"]["leaves"] == 5
    assert snap["tags"]["transfer_bytes_started"] == 48 * 4 + 8 * 2 + 4
    assert snap["tags"]["bytes"] == 48 * 4 + 8 * 2 + 4 + 3 * 4 + 8
    assert len(entries) == (6 if sharded and split else 5)


def test_arc_snapshots_add_up(arc):
    snaps = _named(arc["spans"], "save.snapshot")
    assert len(snaps) == 2
    for snap in snaps:
        [start] = [s for s in arc["spans"]
                   if s["parent_id"] == snap["span_id"]]
        tags = snap["tags"]
        assert (tags["fetch_s"] + tags["copy_s"] + start["dur_ms"] / 1e3
                <= snap["dur_ms"] / 1e3 + 1e-6)
    # saved at dp=4, then at dp=2, the same replicated state: one replica
    # is asked for on either world
    at4, at2 = sorted(snaps, key=lambda s: s["t0"])
    assert at4["tags"]["bytes"] == at2["tags"]["bytes"] > 0
    for snap in (at4, at2):
        assert snap["tags"]["transfer_bytes_started"] == snap["tags"]["bytes"]
        assert snap["tags"]["transfers_started"] == snap["tags"]["leaves"]


def test_host_transfers_count_what_they_started_and_say_when_they_stop():
    import logging

    from edl_tpu.runtime import checkpoint as ckpt_mod

    class Array(object):
        """What the request pass sees of a jax array: replicated, or cut
        into ``blocks`` that two chips each hold."""
        nbytes = 16
        shape = (4,)
        is_fully_addressable = True

        def __init__(self, fail=False, blocks=1):
            self.is_fully_replicated = blocks == 1
            self.sharding = self
            self._blocks = blocks
            self._fail = fail

        def addressable_devices_indices_map(self, shape):
            cut = shape[0] // self._blocks
            return {chip: (slice(cut * (chip % self._blocks),
                                 cut * (chip % self._blocks + 1)),)
                    for chip in range(2 * self._blocks)}

        def copy_to_host_async(self):
            if self._fail:
                raise RuntimeError("no transfer")

    # one call an array; a transfer a distinct block
    assert ckpt_mod._start_host_transfers(
        [Array(), Array(blocks=2), Array()]) == (4, 48)
    # what a snapshot does not read off its devices is never handed over
    elsewhere = Array()
    elsewhere.is_fully_addressable = False
    assert [ckpt_mod._device_read(x)
            for x in (Array(), 3.0, np.ones(2), elsewhere)] == [
        True, False, False, False]
    records = []
    seen = logging.Handler(level=logging.DEBUG)
    seen.emit = records.append
    level = ckpt_mod.logger.level
    ckpt_mod.logger.addHandler(seen)
    ckpt_mod.logger.setLevel(logging.DEBUG)
    try:
        got = ckpt_mod._start_host_transfers(
            [Array(), Array(fail=True), Array(fail=True)])
    finally:
        ckpt_mod.logger.setLevel(level)
        ckpt_mod.logger.removeHandler(seen)
    assert got == (1, 16)
    # once, where it stopped: the third array is not tried
    [stopped] = records
    assert stopped.levelname == "DEBUG"
    assert "stopped after 1 " in stopped.getMessage()


# -- the span API ----------------------------------------------------------


@pytest.mark.parametrize("collect", [True, False])
def test_stage_span_says_how_long_the_collector_ran(clean_ring, collect):
    import gc
    was = gc.isenabled()
    gc.disable()        # no collection but the one asked for
    try:
        with obs_trace.span("gc.outer", stage=True):
            with obs_trace.span("gc.inner", stage=True):
                if collect:
                    gc.collect()
            with obs_trace.span("gc.quiet", stage=True):
                pass
            with obs_trace.span("gc.sampled"):    # not a stage span
                if collect:
                    gc.collect()
    finally:
        if was:
            gc.enable()
    [outer] = clean_ring.find(name="gc.outer")
    [inner] = clean_ring.find(name="gc.inner")
    [quiet] = clean_ring.find(name="gc.quiet")
    [sampled] = clean_ring.find(name="gc.sampled")
    assert "gc_ms" not in quiet["tags"] and "gc_ms" not in sampled["tags"]
    if collect:
        # both collections stalled the thread that held `outer` open
        assert 0 < inner["tags"]["gc_ms"] < outer["tags"]["gc_ms"]
        assert outer["tags"]["gc_ms"] <= outer["dur_ms"]
    else:
        assert "gc_ms" not in inner["tags"]
        assert "gc_ms" not in outer["tags"]


def test_collector_account_is_one_callback_for_the_process():
    import gc
    before = obs_trace.gc_seconds()
    n = gc.callbacks.count(obs_trace._on_gc)
    gc.collect()
    assert obs_trace.gc_seconds() > before
    assert n == gc.callbacks.count(obs_trace._on_gc) == 1



def test_span_carries_its_start_on_the_monotonic_clock(clean_ring):
    import time
    a = time.monotonic()
    with obs_trace.span("t0.check", stage=True) as sp:
        pass
    b = time.monotonic()
    [got] = clean_ring.find(name="t0.check")
    assert a <= got["t0"] <= b
    assert got["t0"] + got["dur_ms"] / 1e3 <= b + 1e-6
    assert sp.seconds == got["dur_ms"] / 1e3
    # the exports still agree on everything they had
    assert set(got) == {"trace_id", "span_id", "parent_id", "name", "kind",
                        "ts", "t0", "dur_ms", "tags", "pid"}


def test_stage_span_is_recorded_with_sampling_off(clean_ring):
    assert not clean_ring.enabled
    with obs_trace.span("sampled.one"):
        pass
    with obs_trace.span("stage.one", stage=True, k=1) as sp:
        sp.tag(more=2)
        with obs_trace.span("sampled.child"):   # a context is active
            pass
    names = [s["name"] for s in clean_ring.spans()]
    assert names == ["sampled.child", "stage.one"]
    [stage] = clean_ring.find(name="stage.one")
    assert _tags(stage) == {"k": 1, "more": 2}
    [child] = clean_ring.find(name="sampled.child")
    assert child["parent_id"] == stage["span_id"]
    assert child["trace_id"] == stage["trace_id"]


def test_span_on_another_thread_keeps_the_parent_s_trace(clean_ring):
    got = {}

    def worker(parent):
        with obs_trace.span("thread.child", stage=True, parent=parent):
            got["ctx"] = obs_trace.current()

    with obs_trace.span("thread.root", stage=True) as root:
        t = threading.Thread(target=worker, args=(obs_trace.current(),))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    [child] = clean_ring.find(name="thread.child")
    assert child["trace_id"] == root.trace_id
    assert child["parent_id"] == root.span_id
    assert got["ctx"] == (root.trace_id, child["span_id"])


def _no_account(monkeypatch):
    """Every account a recorded stage span keeps now raises."""
    from edl_tpu.runtime import checkpoint as ckpt_mod

    def ran(*a, **kw):
        raise AssertionError("an account ran under EDL_TPU_OBS=0")

    monkeypatch.setattr(obs_trace, "gc_seconds", ran)
    monkeypatch.setattr(trainer_mod, "_bytes_moved", ran)
    monkeypatch.setattr(ckpt_mod._SnapshotAccount, "tags", ran)


def test_obs_kill_switch_records_nothing_and_keeps_the_stamps(
        clean_ring, monkeypatch):
    tr = _trainer(4)
    prev = obs_metrics.set_enabled(False)
    try:
        _step(tr, 0)
        _no_account(monkeypatch)
        rec = tr.live_resize(2)
        assert tr._put_account[1] is None
        _step(tr, 1)
        rec = dict(rec, **tr.resize_timing)
    finally:
        monkeypatch.undo()
        obs_metrics.set_enabled(prev)
        tr.close()
    assert clean_ring.spans() == []
    # the span objects still time their callers
    assert rec["reshard_s"] > 0 and rec["compile_s"] > 0
    assert rec["first_step_s"] >= 0 and rec["drain_s"] >= 0


@pytest.mark.parametrize("sharded", [False, True])
def test_obs_kill_switch_keeps_blocked_s_and_runs_no_account(
        tmp_path, clean_ring, monkeypatch, sharded):
    mgr = CheckpointManager(str(tmp_path))
    tree = _state_tree(2)
    prev = obs_metrics.set_enabled(False)
    try:
        _no_account(monkeypatch)
        if sharded:
            entries, _, blocked_s = mgr._snapshot(mgr._snapshot_sharded,
                                                  tree, 0)
        else:
            entries, _, blocked_s = mgr._snapshot(mgr._snapshot_dense, tree)
    finally:
        monkeypatch.undo()
        obs_metrics.set_enabled(prev)
        mgr.close()
    assert clean_ring.spans() == []
    assert blocked_s > 0 and len(entries) == 3


def test_obs_stays_off_jax():
    """`obs` is a leaf: a control-plane process that opens spans never
    pays for jax, and a span finds no annotation to enter there."""
    code = (
        "import sys\n"
        "import edl_tpu.obs\n"
        "from edl_tpu.obs import trace\n"
        "with trace.span('cp.stage', stage=True) as sp:\n"
        "    with trace.span('cp.child'):\n"
        "        pass\n"
        "assert [s['name'] for s in trace.TRACER.spans()] == "
        "['cp.child', 'cp.stage']\n"
        "assert 'jax' not in sys.modules, 'obs imported jax'\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         env=dict(os.environ, PYTHONPATH=REPO),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "ok"


def test_profiler_capture_holds_the_stage_on_the_host_line(tmp_path,
                                                           clean_ring):
    """Under a jax.profiler session a stage span is an `edl:` event on
    the host thread's line, inside the annotation the caller had open:
    the clock it shares with the device operations."""
    from jax.profiler import ProfileData
    tr = _trainer(4)
    try:
        _step(tr, 0)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("test:outer"):
                tr.live_resize(2)
                _step(tr, 1)
        finally:
            jax.profiler.stop_trace()
    finally:
        tr.close()
    [path] = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                           / "*.xplane.pb"))
    lines = [line for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    found = {}
    for line in lines:
        events = {}
        for ev in line.events:
            if ev.name == "test:outer" or ev.name.startswith("edl:"):
                events.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
        if "test:outer" in events:
            found = events
    assert found, "the outer annotation is on no host line"
    [(o0, o1)] = found["test:outer"]
    for name in ["edl:resize.live", "edl:resize.device_put",
                 "edl:resize.first_step", "edl:resize.first_dispatch"]:
        [(a, b)] = found[name]
        assert o0 <= a <= b <= o1, name
    [(l0, l1)] = found["edl:resize.live"]
    [(p0, p1)] = found["edl:resize.device_put"]
    assert l0 <= p0 <= p1 <= l1


# -- what jax did inside a dispatch ---------------------------------------


@pytest.mark.parametrize("seen,want", [
    ([], {"jax_trace_s": 0.0, "jax_lower_s": 0.0, "jax_compile_s": 0.0,
          "jax_cache_load_s": 0.0}),
    # an inner jit traced inside the outer one's trace counts once
    ([("jax_trace_s", 1.0, 3.0), ("jax_trace_s", 1.5, 2.0),
      ("jax_trace_s", 4.0, 4.5)],
     {"jax_trace_s": 2.5, "jax_lower_s": 0.0, "jax_compile_s": 0.0,
      "jax_cache_load_s": 0.0}),
    # overlapping, not nested
    ([("jax_lower_s", 0.0, 2.0), ("jax_lower_s", 1.0, 3.0)],
     {"jax_trace_s": 0.0, "jax_lower_s": 3.0, "jax_compile_s": 0.0,
      "jax_cache_load_s": 0.0}),
    # the cache retrieval runs inside the backend-compile event
    ([("jax_compile_s", 0.0, 0.5), ("jax_cache_load_s", 0.1, 0.5)],
     {"jax_trace_s": 0.0, "jax_lower_s": 0.0, "jax_compile_s": 0.1,
      "jax_cache_load_s": 0.4}),
])
def test_jax_stage_seconds_add_up(seen, want):
    assert trainer_mod._jax_stage_seconds(seen) == pytest.approx(want)


def test_jax_listener_counts_only_inside_a_dispatch():
    event = "/jax/core/compile/jaxpr_trace_duration"
    trainer_mod._on_jax_duration(event, 0.25)    # no dispatch open: dropped
    trainer_mod._jax_stages.intervals = seen = []
    try:
        trainer_mod._on_jax_duration(event, 0.25)
        trainer_mod._on_jax_duration("/jax/some/other_event", 9.0)
    finally:
        trainer_mod._jax_stages.intervals = None
    [(tag, a, b)] = seen
    assert tag == "jax_trace_s" and b - a == pytest.approx(0.25)


def test_obs_bench_span_section_schema(clean_ring):
    import json

    from edl_tpu.tools import obs_bench
    out = obs_bench.bench_span(n=200, session_n=50)
    for key in ("silenced_ns", "recorded_ns", "recorded_in_session_ns"):
        assert out[key] > 0
    assert obs_metrics.enabled()  # the bench must restore the switch
    json.dumps(out)
