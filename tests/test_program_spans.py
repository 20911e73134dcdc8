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
    tags = dict(root["tags"])
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


@ALL_CASES
def test_resize_children_lie_inside_the_root_and_do_not_overlap(arc, case):
    root, trace = _resize(arc, case)
    children = sorted((s for s in trace
                       if s["parent_id"] == root["span_id"]
                       and s["name"] != "resize.first_step"),
                      key=lambda s: s["t0"])
    eps = 1e-6
    assert children[0]["t0"] >= root["t0"] - eps
    assert _end(children[-1]) <= _end(root) + eps
    for a, b in zip(children, children[1:]):
        assert _end(a) <= b["t0"] + eps, (a["name"], b["name"])


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
         "resize.first_result"] + RESIZE_STAGES + PREWARM_STAGES)


@ALL_CASES
def test_first_dispatch_says_what_jax_did(arc, case):
    _, trace = _resize(arc, case)
    [d] = [s for s in trace if s["name"] == "resize.first_dispatch"]
    assert set(d["tags"]) == {"jax_trace_s", "jax_lower_s", "jax_compile_s",
                              "jax_cache_load_s"}
    total = sum(d["tags"].values())
    assert total <= d["dur_ms"] / 1e3 + 1e-3
    if case != "compile":
        # an executable held ready or loaded: nothing to trace or build
        assert total == 0.0
    else:
        # a world nobody compiled for: the step is traced and built
        assert d["tags"]["jax_trace_s"] > 0
        assert d["tags"]["jax_lower_s"] > 0
        assert d["tags"]["jax_compile_s"] > 0


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
    assert fp["tags"] == {"world": 2}
    assert rec["compile_s"] == pytest.approx(
        (fp["dur_ms"] + load["dur_ms"] + d["dur_ms"]) / 1e3, abs=1e-9)
    assert sum(d["tags"].values()) == 0.0   # loaded, not built


# -- a save ----------------------------------------------------------------


def test_save_root_with_its_stages_and_the_writer_s_span(arc):
    roots = _named(arc["spans"], "save")
    assert len(roots) == 2
    for root in roots:
        assert root["parent_id"] is None
        assert set(root["tags"]) == {"version"}
        trace = [s for s in arc["spans"]
                 if s["trace_id"] == root["trace_id"]]
        assert sorted(s["name"] for s in trace) == [
            "save", "save.drain_prev", "save.persist", "save.snapshot",
            "save.state_json"]
        assert all(s["parent_id"] == root["span_id"]
                   for s in trace if s["name"] != "save")
        on_thread = sorted((s for s in trace
                            if s["name"] not in ("save", "save.persist")),
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
    assert persist["tags"] == {"version": 3}


# -- the span API ----------------------------------------------------------


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
    assert stage["tags"] == {"k": 1, "more": 2}
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


def test_obs_kill_switch_records_nothing_and_keeps_the_stamps(clean_ring):
    tr = _trainer(4)
    prev = obs_metrics.set_enabled(False)
    try:
        _step(tr, 0)
        rec = tr.live_resize(2)
        _step(tr, 1)
        rec = dict(rec, **tr.resize_timing)
    finally:
        obs_metrics.set_enabled(prev)
        tr.close()
    assert clean_ring.spans() == []
    # the span objects still time their callers
    assert rec["reshard_s"] > 0 and rec["compile_s"] > 0
    assert rec["first_step_s"] >= 0 and rec["drain_s"] >= 0


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
