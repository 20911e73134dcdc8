"""The benchmark's readers of the program's stage spans
(benchmark/lib/progspans.py and the fifteen files under
benchmark/metrics/ that use it, thirteen of them listed in BENCHMARK.json
and two no longer; benchmark/lib/stagespans.py and the sixteen that read
what a save and a reshard are made of), each on a
hand-made ring and `view`: medians by direction, None without the span,
roots outside the window left out, the arithmetic of `resize_other_ms`.
The cell's own end-to-end test runs with the benchmark's tests
(benchmark/tests/); these are in tier-1 so that every PR counts them.
"""

import json
import os

import pytest

from benchmark.lib import harness, progspans, stagespans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "resnet50vd-dp4-elastic"
NEW = ["shrink_pause_ms", "grow_pause_ms", "shrink_device_put_ms",
       "grow_device_put_ms", "grow_first_trace_ms", "grow_first_load_ms",
       "shrink_first_run_ms", "grow_first_run_ms", "resize_other_ms",
       "save_drain_prev_ms", "save_snapshot_ms"]
#: readers whose files are still under benchmark/metrics/ and that
#: BENCHMARK.json no longer lists (PR 51): they go with the files
UNLISTED = ["shrink_fingerprint_ms", "shrink_step_load_ms"]
#: the wait a resize takes for the save in flight, and that save's write
LATER = ["resize_drain_ms", "save_persist_ms"]
#: `save.snapshot` and `resize.device_put` from inside (lib/stagespans.py):
#: name -> (unit, better)
INSIDE = {
    "save_snapshot_start_ms": ("ms", "lower"),
    "save_snapshot_fetch_ms": ("ms", "lower"),
    "save_snapshot_copy_ms": ("ms", "lower"),
    "save_snapshot_gb_s": ("GB/s", "higher"),
    "save_transfer_started_over_kept": ("ratio", "lower"),
    "shrink_put_dispatch_ms": ("ms", "lower"),
    "grow_put_dispatch_ms": ("ms", "lower"),
    "shrink_put_wait_ms": ("ms", "lower"),
    "grow_put_wait_ms": ("ms", "lower"),
    "shrink_put_moved_mb": ("MB", "lower"),
    "grow_put_moved_mb": ("MB", "lower"),
    "resize_put_beside_persist_pct": ("%", "lower"),
    "resize_gc_ms": ("ms", "lower"),
    "resize_drain_deferred_pct": ("%", "higher"),
    "resize_step_from_memory_pct": ("%", "higher"),
}


def _span(trace, name, t0, ms, parent="root", **tags):
    return {"trace_id": trace, "span_id": "%s/%s" % (trace, name),
            "parent_id": None if parent is None else "%s/%s" % (trace, parent),
            "name": name, "kind": "local", "ts": 0.0, "t0": t0,
            "dur_ms": ms, "tags": tags, "pid": 1}


def _shrink(trace, t0, put, fp, load, run, gap=10.0):
    """A 4 -> 2 resize starting at `t0` s: drain 5, mesh 1, device_put,
    build_step 2, fingerprint, load, then `gap` ms outside any span, a
    dispatch of 3 ms that built nothing, and the first result."""
    at, out = t0, []
    for name, ms in [("resize.drain", 5.0), ("resize.mesh", 1.0),
                     ("resize.device_put", put), ("resize.build_step", 2.0),
                     ("resize.prewarm_fingerprint", fp),
                     ("resize.prewarm_load", load)]:
        out.append(_span(trace, name, at, ms, parent="resize.live"))
        at += ms / 1e3
    live_ms = (at - t0) * 1e3
    out.append(_span(trace, "resize.live", t0, live_ms, parent=None,
                     from_devices=4, to_devices=2, prewarm="hit"))
    at += gap / 1e3
    out.append(_span(trace, "resize.first_step", at, 3.0 + run,
                     parent="resize.live"))
    out.append(_span(trace, "resize.first_dispatch", at, 3.0,
                     parent="resize.first_step", jax_trace_s=0.0,
                     jax_lower_s=0.0, jax_compile_s=0.0,
                     jax_cache_load_s=0.0))
    out.append(_span(trace, "resize.first_result", at + 3e-3, run,
                     parent="resize.first_step"))
    return out, live_ms + gap + 3.0 + run


def _grow(trace, t0, put, trace_ms, lower_ms, load_ms, run):
    """A 2 -> 4 resize: no prewarm spans; the first dispatch traces,
    lowers and loads, and takes 4 ms more than those."""
    at, out = t0, []
    for name, ms in [("resize.drain", 5.0), ("resize.mesh", 1.0),
                     ("resize.device_put", put), ("resize.build_step", 2.0)]:
        out.append(_span(trace, name, at, ms, parent="resize.live"))
        at += ms / 1e3
    live_ms = (at - t0) * 1e3
    out.append(_span(trace, "resize.live", t0, live_ms, parent=None,
                     from_devices=2, to_devices=4, prewarm="miss"))
    dispatch = trace_ms + lower_ms + load_ms + 4.0
    out.append(_span(trace, "resize.first_step", at, dispatch + run,
                     parent="resize.live"))
    out.append(_span(trace, "resize.first_dispatch", at, dispatch,
                     parent="resize.first_step",
                     jax_trace_s=trace_ms / 1e3, jax_lower_s=lower_ms / 1e3,
                     jax_compile_s=0.001, jax_cache_load_s=load_ms / 1e3
                     - 0.001))
    out.append(_span(trace, "resize.first_result", at + dispatch / 1e3, run,
                     parent="resize.first_step"))
    return out, live_ms + dispatch + run


def _save(trace, t0, drain, snap):
    return [_span(trace, "save", t0, 1.0 + drain + snap, parent=None,
                  version=7),
            _span(trace, "save.state_json", t0, 1.0, parent="save"),
            _span(trace, "save.drain_prev", t0 + 1e-3, drain, parent="save"),
            _span(trace, "save.snapshot", t0 + (1 + drain) / 1e3, snap,
                  parent="save"),
            _span(trace, "save.persist", t0 + 0.5, 400.0, parent="save",
                  version=7)]


@pytest.fixture()
def world(monkeypatch):
    """A window [100, 200] holding three shrinks, two grows and three
    saves; a warm-up period before it and a resize after it."""
    ring, pauses = [], {}
    for key, (t0, put, fp, load, run) in {
            "s1": (110.0, 200.0, 3000.0, 30.0, 60.0),
            "s2": (140.0, 240.0, 2800.0, 34.0, 50.0),
            "s3": (170.0, 220.0, 3400.0, 20.0, 70.0),
            "warm": (50.0, 900.0, 9000.0, 90.0, 900.0),
            "late": (205.0, 900.0, 9000.0, 90.0, 900.0)}.items():
        spans, pauses[key] = _shrink(key, t0, put, fp, load, run)
        ring += spans
    for key, (t0, put, tr, lo, ld, run) in {
            "g1": (120.0, 180.0, 2000.0, 1000.0, 500.0, 80.0),
            "g2": (150.0, 160.0, 2200.0, 1200.0, 300.0, 100.0)}.items():
        spans, pauses[key] = _grow(key, t0, put, tr, lo, ld, run)
        ring += spans
    for key, (t0, drain, snap) in {"v1": (105.0, 0.0, 430.0),
                                   "v2": (135.0, 12.0, 410.0),
                                   "v3": (165.0, 2.0, 450.0),
                                   "vwarm": (40.0, 500.0, 5000.0)}.items():
        ring += _save(key, t0, drain, snap)
    # a sampled span of the RPC layer shares the ring
    ring.append(_span("rpc", "rpc.client/put", 130.0, 1.0, parent=None))
    ring.sort(key=lambda s: s["t0"] + s["dur_ms"] / 1e3)
    monkeypatch.setattr(progspans, "ring", lambda: list(ring))
    return {"view": {"window": [100.0, 200.0]}, "pauses": pauses,
            "ring": ring}


def _read(name, view):
    return harness.load_module("metrics", name).read(view)


@pytest.mark.parametrize("name,want", [
    ("shrink_device_put_ms", 220.0),       # of 200, 240, 220
    ("grow_device_put_ms", 170.0),         # of 180, 160
    ("shrink_fingerprint_ms", 3000.0),
    ("shrink_step_load_ms", 30.0),
    ("grow_first_trace_ms", 3200.0),       # of 3000, 3400
    ("grow_first_load_ms", 400.0),         # of 500, 300
    ("shrink_first_run_ms", 60.0),
    ("grow_first_run_ms", 90.0),
    ("save_drain_prev_ms", 2.0),           # of 0, 12, 2
    ("save_snapshot_ms", 430.0),
    ("resize_drain_ms", 5.0),              # every resize of `world`
    ("save_persist_ms", 400.0),            # every save of `world`
])
def test_stage_medians_by_direction(world, name, want):
    assert _read(name, world["view"]) == pytest.approx(want)


def test_pauses_run_from_the_root_s_start_to_the_first_step_s_end(world):
    p = world["pauses"]
    assert _read("shrink_pause_ms", world["view"]) == pytest.approx(
        sorted([p["s1"], p["s2"], p["s3"]])[1])
    assert _read("grow_pause_ms", world["view"]) == pytest.approx(
        (p["g1"] + p["g2"]) / 2)
    # s1: drain 5 + mesh 1 + 200 + build 2 + 3000 + 30, 10 outside any
    # span, dispatch 3, result 60
    assert p["s1"] == pytest.approx(3311.0)


def test_resize_other_is_the_pause_less_the_named_stages(world):
    # a shrink: drain 5 + mesh 1 + build 2 + the 10 no span covers + the
    # 3 of a dispatch that built nothing = 21; a grow: 5 + 1 + 2 + the 4
    # of its dispatch beyond trace, lower and load = 12
    assert _read("resize_other_ms", world["view"]) == pytest.approx(21.0)
    recs = progspans.resizes(world["view"])
    assert sorted(r["direction"] for r in recs) == ["grow"] * 2 \
        + ["shrink"] * 3
    others = sorted(r["pause"] - sum(r[k] or 0.0 for k in progspans.NAMED)
                    for r in recs)
    assert others == pytest.approx([12.0, 12.0, 21.0, 21.0, 21.0])


def test_roots_outside_the_window_are_left_out(world):
    view = {"window": [0.0, 1000.0]}       # now warm-up and late count
    assert _read("shrink_device_put_ms", view) == pytest.approx(240.0)
    assert _read("save_drain_prev_ms", view) == pytest.approx(7.0)
    assert len(progspans.resizes(view)) == 7
    assert len(progspans.resizes({"window": [100.0, 125.0]})) == 2
    assert progspans.resizes({"window": [300.0, 400.0]}) == []


def test_traces_under_the_profiler_s_capture_are_left_out(world):
    """The harness's `trace_window` span covers the traced period: its
    resizes and saves ran on a host the profiler slowed."""
    view = {"window": [100.0, 200.0],
            "spans": [("steps", 100.0, 109.0), ("trace_window", 132.0, 168.0),
                      ("live_resize", 140.0, 143.3)]}
    got = progspans.resizes(view)       # s2 and g2 start inside it
    assert sorted((r["direction"], r["device_put"]) for r in got) == [
        ("grow", 180.0), ("shrink", 200.0), ("shrink", 220.0)]
    assert _read("shrink_device_put_ms", view) == pytest.approx(210.0)
    assert _read("grow_first_load_ms", view) == pytest.approx(500.0)
    assert _read("save_snapshot_ms", view) == pytest.approx(430.0)  # v1
    assert _read("save_drain_prev_ms", view) == pytest.approx(0.0)


def _with_durations(ring, name, by_trace):
    return [dict(s, dur_ms=by_trace[s["trace_id"]]) if s["name"] == name
            else s for s in ring]


@pytest.mark.parametrize("drains,want", [
    # every resize left the write running: what is left is the fault
    # point and the counters' mirror
    ({"s1": 0.4, "s2": 0.7, "s3": 0.6, "g1": 0.5, "g2": 0.3}, 0.5),
    # every resize waited for the disk, the grows longer
    ({"s1": 171.0, "s2": 269.0, "s3": 240.0, "g1": 396.0, "g2": 243.0},
     243.0),
    # shrinks and grows are ONE median: two that waited among five
    ({"s1": 0.4, "s2": 250.0, "s3": 0.6, "g1": 0.5, "g2": 300.0}, 0.6),
])
def test_drain_is_one_median_over_shrinks_and_grows(world, monkeypatch,
                                                    drains, want):
    drains = dict(drains, warm=900.0, late=900.0)
    ring = _with_durations(world["ring"], "resize.drain", drains)
    monkeypatch.setattr(progspans, "ring", lambda: ring)
    assert _read("resize_drain_ms", world["view"]) == pytest.approx(want)


def test_drain_counts_a_resize_whose_first_step_the_ring_lost(
        world, monkeypatch):
    ring = [s for s in _with_durations(
                world["ring"], "resize.drain",
                {"s1": 1.0, "s2": 200.0, "s3": 3.0, "g1": 2.0, "g2": 4.0,
                 "warm": 900.0, "late": 900.0})
            if not (s["trace_id"] == "s2"
                    and s["name"].startswith("resize.first"))]
    monkeypatch.setattr(progspans, "ring", lambda: ring)
    assert len(progspans.resizes(world["view"])) == 4
    assert _read("resize_drain_ms", world["view"]) == pytest.approx(3.0)


def test_persist_is_the_writer_s_span_and_skips_a_write_still_running(
        world, monkeypatch):
    ring = _with_durations(world["ring"], "save.persist",
                           {"v1": 200.0, "v2": 640.0, "v3": 380.0,
                            "vwarm": 5000.0})
    monkeypatch.setattr(progspans, "ring", lambda: ring)
    assert _read("save_persist_ms", world["view"]) == pytest.approx(380.0)
    # a span is recorded when it ends: the last save's write, still
    # running when the ring is read, is not there yet
    ring = [s for s in ring if not (s["trace_id"] == "v3"
                                    and s["name"] == "save.persist")]
    monkeypatch.setattr(progspans, "ring", lambda: ring)
    assert _read("save_persist_ms", world["view"]) == pytest.approx(420.0)
    assert _read("save_snapshot_ms", world["view"]) == pytest.approx(430.0)


@pytest.mark.parametrize("name", NEW + UNLISTED + LATER)
def test_none_without_the_span(monkeypatch, name):
    """The parent's program records no stage span: an empty ring, or
    spans without a monotonic start. Nothing is read and nothing raises."""
    view = {"window": [100.0, 200.0]}
    monkeypatch.setattr(progspans, "ring", lambda: [])
    assert _read(name, view) is None


def test_a_grow_has_no_fingerprint_and_a_shrink_no_first_trace(
        world, monkeypatch):
    ring = [s for s in world["ring"] if s["trace_id"] in ("g1", "g2")]
    monkeypatch.setattr(progspans, "ring", lambda: ring)
    assert _read("shrink_fingerprint_ms", world["view"]) is None
    assert _read("shrink_pause_ms", world["view"]) is None
    assert _read("grow_pause_ms", world["view"]) is not None
    for r in progspans.resizes(world["view"]):
        assert r["fingerprint"] is None and r["step_load"] is None


def test_a_resize_without_its_first_step_is_left_out(world, monkeypatch):
    ring = [s for s in world["ring"]
            if not (s["trace_id"] == "s2"
                    and s["name"].startswith("resize.first"))]
    monkeypatch.setattr(progspans, "ring", lambda: ring)
    assert len(progspans.resizes(world["view"])) == 4
    assert _read("shrink_device_put_ms", world["view"]) == pytest.approx(
        210.0)


def test_ring_reads_the_program_s_tracer_and_drops_clockless_spans(
        monkeypatch):
    from edl_tpu.obs import trace as obs_trace
    obs_trace.TRACER.clear()
    try:
        with obs_trace.span("resize.device_put", stage=True):
            pass
        [got] = progspans.ring()
        assert got["name"] == "resize.device_put" and got["t0"] > 0
        # an older program's span says only `ts`: no clock to place it by
        monkeypatch.setattr(obs_trace.Span, "to_dict",
                            lambda self: {"name": self.name, "ts": self.ts,
                                          "dur_ms": self.dur_ms})
        assert progspans.ring() == []
    finally:
        obs_trace.TRACER.clear()


def test_benchmark_names_every_new_reader_once_for_the_elastic_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    # appended together, in this order (later PRs append after them)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    for name in NEW:
        m = entries[name]
        assert m["source"] == "program_span" and m["unit"] == "ms"
        assert m["workloads"] == [CELL] and m["better"] == "lower"
        assert m["moves"] == "elastic_samples_s_chip"
        assert m["layer"] == ("checkpoint" if name.startswith("save_")
                              else "live resize")
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", name + ".py"))
    later = names.index(LATER[0])      # later PRs append after these too
    assert names[later:later + len(LATER)] == LATER
    for name, layer in [("resize_drain_ms", "live resize"),
                        ("save_persist_ms", "checkpoint")]:
        assert entries[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": layer,
            "moves": "elastic_samples_s_chip", "workloads": [CELL]}
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", name + ".py"))


# -- `save.snapshot` and `resize.device_put` from inside ---------------------


STATE = 2.0e8       # bytes of the job's state, one copy
#: arrays each grow of `deep` handed to the runtime to cross (PR 54)
CROSSED = {"g1": 890, "g2": 4}


@pytest.fixture()
def deep(world, monkeypatch):
    """`world`, run by a program that says what a save and a reshard are
    made of: each `resize.device_put` has its two children and its tags,
    each `save.snapshot` its child and its tags, the roots say what
    became of the save in flight and where the step came from, and the
    collector ran inside two of the resizes."""
    roots = {"s1": ("deferred", "memory"), "s2": ("deferred", "memory"),
             "s3": ("idle", "disk"), "g1": ("deferred", "memory"),
             "g2": ("waited", "compile"), "warm": ("idle", "compile"),
             "late": ("idle", "compile")}
    gc_ms = {("s1", "resize.live"): 3.0, ("s1", "resize.first_step"): 1.0,
             ("g1", "resize.live"): 2.0, ("s1", "resize.device_put"): 3.0}
    asked = {"v1": 4, "v2": 2, "v3": 4, "vwarm": 4}
    ring = []
    for sp in world["ring"]:
        sp = dict(sp, tags=dict(sp["tags"]))
        key, name = sp["trace_id"], sp["name"]
        if (key, name) in gc_ms:
            sp["tags"]["gc_ms"] = gc_ms[key, name]
        if name == "resize.live":
            sp["tags"].update(drain=roots[key][0], step_source=roots[key][1])
        elif name == "resize.device_put":
            grow = key.startswith("g")
            sp["tags"].update(source="local", bytes=STATE, leaves=445,
                              bytes_moved=2 * STATE if grow else 0,
                              persist_inflight=True,
                              arrays_crossed=CROSSED.get(key, 0),
                              leaves_leafwise=0)
            # the call is four fifths of it, the wait the rest less 1 ms
            ring.append(_span(key, "resize.device_put.dispatch", sp["t0"],
                              0.8 * sp["dur_ms"],
                              parent="resize.device_put"))
            ring.append(_span(key, "resize.device_put.wait",
                              sp["t0"] + 0.8 * sp["dur_ms"] / 1e3,
                              0.2 * sp["dur_ms"] - 1.0,
                              parent="resize.device_put"))
        elif name == "save.snapshot":
            # start 10% of the span, the fetches 70%, the copies 15%
            sp["tags"].update(fetch_s=0.7 * sp["dur_ms"] / 1e3,
                              copy_s=0.15 * sp["dur_ms"] / 1e3,
                              bytes=STATE, leaves=445,
                              transfers_started=445 * asked[key],
                              transfer_bytes_started=asked[key] * STATE,
                              bufs_new=0)
            ring.append(_span(key, "save.snapshot.start_transfers",
                              sp["t0"], 0.1 * sp["dur_ms"],
                              parent="save.snapshot"))
        ring.append(sp)
    ring.sort(key=lambda s: s["t0"] + s["dur_ms"] / 1e3)
    monkeypatch.setattr(progspans, "ring", lambda: list(ring))
    return dict(world, ring=ring)


@pytest.mark.parametrize("name,want", [
    ("save_snapshot_start_ms", 43.0),      # a tenth of 430, 410, 450
    ("save_snapshot_fetch_ms", 301.0),
    ("save_snapshot_copy_ms", 64.5),
    ("save_snapshot_gb_s", STATE / 1e9 / 0.430),
    ("save_transfer_started_over_kept", 10.0 / 3),   # 4, 2 and 4 copies
    ("shrink_put_dispatch_ms", 176.0),     # four fifths of 200, 240, 220
    ("grow_put_dispatch_ms", 136.0),       # of 180, 160
    ("shrink_put_wait_ms", 43.0),
    ("grow_put_wait_ms", 33.0),
    ("shrink_put_moved_mb", 0.0),
    ("grow_put_moved_mb", 400.0),
    ("resize_put_beside_persist_pct", 0.0),    # every write ended before
    ("resize_gc_ms", 0.0),                 # of 4, 0, 0, 2, 0
    ("resize_drain_deferred_pct", 60.0),   # s1, s2, g1 of five
    ("resize_step_from_memory_pct", 60.0),
])
def test_what_a_save_and_a_reshard_are_made_of(deep, name, want):
    assert _read(name, deep["view"]) == pytest.approx(want)


def test_gc_is_summed_per_resize_over_the_two_spans_of_the_pause(
        deep, monkeypatch):
    # s1: 3 inside `resize.live` (its device_put's 3 is the same
    # collection, not counted again) + 1 inside the first step; g1: 2
    ring = [s for s in deep["ring"] if s["trace_id"] in ("s1", "g1", "s2")]
    monkeypatch.setattr(progspans, "ring", lambda: ring)
    assert _read("resize_gc_ms", deep["view"]) == pytest.approx(2.0)
    ring = [s for s in ring if s["trace_id"] == "s1"]
    monkeypatch.setattr(progspans, "ring", lambda: ring)
    assert _read("resize_gc_ms", deep["view"]) == pytest.approx(4.0)


@pytest.mark.parametrize("name", list(INSIDE))
def test_inside_readers_give_none_on_an_empty_ring(monkeypatch, name):
    monkeypatch.setattr(progspans, "ring", lambda: [])
    assert _read(name, {"window": [100.0, 200.0]}) is None


@pytest.mark.parametrize("name", list(INSIDE))
def test_inside_readers_give_none_without_their_child_or_tag(
        world, monkeypatch, name):
    """`world` is the ring of a program from before: `save.snapshot` and
    `resize.device_put` are there, with nothing inside and no tag, and
    its tracer keeps no account of the collector. One reader lays spans
    side by side that such a program has."""
    monkeypatch.setattr(stagespans, "_program_counts_gc", lambda: False)
    got = _read(name, world["view"])
    if name == "resize_put_beside_persist_pct":
        assert got == 0.0
    else:
        assert got is None
    if name == "resize_gc_ms":
        # the same ring from a program that counts: no collection ran
        monkeypatch.setattr(stagespans, "_program_counts_gc", lambda: True)
        assert _read(name, world["view"]) == 0.0


def test_the_tracer_of_this_tree_counts_the_collector():
    assert stagespans._program_counts_gc()


@pytest.mark.parametrize("name,want", [
    # s2, g2, v2 and v3 start inside the capture: s1 and s3, g1, v1
    ("save_snapshot_start_ms", 43.0),
    ("save_snapshot_gb_s", STATE / 1e9 / 0.43),
    ("save_transfer_started_over_kept", 4.0),
    ("shrink_put_dispatch_ms", 168.0),     # of 160, 176
    ("grow_put_wait_ms", 35.0),
    ("grow_put_moved_mb", 400.0),
    ("resize_gc_ms", 2.0),                 # of 4, 0, 2
    ("resize_drain_deferred_pct", 100 * 2 / 3.0),
    ("resize_step_from_memory_pct", 100 * 2 / 3.0),
])
def test_inside_readers_leave_out_a_trace_under_the_capture(deep, name,
                                                            want):
    view = {"window": [100.0, 200.0],
            "spans": [("trace_window", 132.0, 168.0)]}
    assert _read(name, view) == pytest.approx(want)


@pytest.mark.parametrize("name", list(INSIDE))
def test_one_resize_of_each_kind_is_enough_for_every_reader(deep, name):
    """A traced window of the cell holds ONE period outside the capture
    (one shrink, one grow, two saves), and a metric the cell lists has
    to be in that run's line: no reader may need two of a kind."""
    view = {"window": [100.0, 125.0]}          # s1, g1 and v1 alone
    assert _read(name, view) is not None
    assert _read("shrink_put_dispatch_ms", view) == pytest.approx(160.0)


def test_a_resize_without_its_first_step_is_no_reshard_either(
        deep, monkeypatch):
    """The same resizes as the stage's own reader counts, so that the
    children's medians can be laid beside `*_device_put_ms`."""
    ring = [s for s in deep["ring"]
            if not (s["trace_id"] == "s2"
                    and s["name"].startswith("resize.first"))]
    monkeypatch.setattr(progspans, "ring", lambda: ring)
    assert _read("shrink_put_dispatch_ms", deep["view"]) == pytest.approx(
        0.8 * _read("shrink_device_put_ms", deep["view"]))


@pytest.mark.parametrize("writes,want", [
    # s1's reshard runs [110.006, 110.206]. A write that starts before
    # it and ends inside it, 50 ms in: a quarter of that reshard
    ({"v1": (109.0, 1056.0)}, 100 * 50.0 / 1000),
    # one that covers it whole, and the warm-up's write, which ended
    ({"v1": (109.0, 2000.0), "vwarm": (41.0, 900.0)}, 100 * 200.0 / 1000),
    # one that starts inside it and outlives it; s2's is covered whole
    ({"v1": (110.106, 5000.0), "v2": (139.0, 3000.0)},
     100 * (100.0 + 240.0) / 1000),
    # every reshard inside a write
    ({"v1": (100.0, 80000.0)}, 100.0),
    # a write that ends as the reshard begins
    ({"v1": (109.0, 1006.0)}, 0.0),
])
def test_put_beside_persist_is_the_overlap_with_the_ring_s_writes(
        deep, monkeypatch, writes, want):
    """200 + 240 + 220 + 180 + 160 = 1000 ms of reshard in the window."""
    ring = []
    for sp in deep["ring"]:
        if sp["name"] == "save.persist":
            if sp["trace_id"] not in writes:
                continue
            t0, ms = writes[sp["trace_id"]]
            sp = dict(sp, t0=t0, dur_ms=ms)
        ring.append(sp)
    monkeypatch.setattr(progspans, "ring", lambda: ring)
    assert _read("resize_put_beside_persist_pct",
                 deep["view"]) == pytest.approx(want)


@pytest.mark.parametrize("window,want", [
    ([100.0, 200.0], 447.0),               # the median of g1 and g2
    ([100.0, 125.0], 890.0),               # g1 alone: one grow is enough
])
def test_arrays_crossed_is_the_grows_median_of_the_tag(deep, window, want):
    assert _read("grow_put_arrays_crossed", {"window": window}) == want


def test_arrays_crossed_is_none_without_the_tag(world, monkeypatch):
    """A program from before PR 54 (`world`: `resize.device_put` with no
    tag) gives the reader nothing, as an empty ring does; a shrink's tag
    is not read."""
    assert _read("grow_put_arrays_crossed", world["view"]) is None
    shrinks = [dict(s, tags=dict(s["tags"], arrays_crossed=0))
               if s["name"] == "resize.device_put"
               and s["trace_id"].startswith("s") else s
               for s in world["ring"]]
    monkeypatch.setattr(progspans, "ring", lambda: shrinks)
    assert _read("grow_put_arrays_crossed", world["view"]) is None
    monkeypatch.setattr(progspans, "ring", lambda: [])
    assert _read("grow_put_arrays_crossed", {"window": [100.0, 200.0]}) is None


def test_benchmark_names_arrays_crossed_once_after_what_was_there():
    """Appended LAST by PR 54; what later PRs append (PR 57's six `ssd_*`,
    then PR 59's ten model-parts metrics, tests/test_devtime.py, then
    PR 61's six `kda_*`, then PR 63's three `shortconv_gate_*`) follows
    it."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.count("grow_put_arrays_crossed") == 1
    at = names.index("grow_put_arrays_crossed")
    assert at > names.index("moe_route_weight_sum")     # PR 53's last
    # counted from `at`, so what a later PR appends moves nothing here
    assert all(n.startswith("ssd_") for n in names[at + 1:at + 7])
    assert all(m["layer"] == "model parts"
               for m in bench["per_layer"][at + 7:at + 17])
    assert all(n.startswith("kda_") for n in names[at + 17:at + 23])
    assert all(n.startswith("shortconv_gate_")
               for n in names[at + 23:at + 26])
    assert len(names) >= at + 26
    assert bench["per_layer"][at] == {
        "name": "grow_put_arrays_crossed", "unit": "count",
        "better": "lower", "source": "program_span",
        "layer": "live resize", "moves": "elastic_samples_s_chip",
        "workloads": [CELL]}
    assert os.path.exists(os.path.join(
        REPO, "benchmark", "metrics", "grow_put_arrays_crossed.py"))


def test_benchmark_names_the_inside_readers_once_together_at_the_end():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    for name in INSIDE:
        assert names.count(name) == 1, name
    at = names.index(next(iter(INSIDE)))
    assert names[at:at + len(INSIDE)] == list(INSIDE)
    assert at > names.index(LATER[-1])     # appended after what was there
    for m in bench["per_layer"][at:at + len(INSIDE)]:
        unit, better = INSIDE[m["name"]]
        layer = ("checkpoint" if m["name"].startswith("save_")
                 else "live resize")
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "program_span", "layer": layer,
                     "moves": "elastic_samples_s_chip",
                     "workloads": [CELL]}
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", m["name"] + ".py"))
