"""What a save and a reshard are made of, read from the same ring and by
the same rule as `benchmark/lib/progspans.py` (a trace counts when its
root starts inside the measured window and outside the profiler's
capture): the children and tags the program puts INSIDE `save.snapshot`
and `resize.device_put` (`docs/observability.md`), the collector's
seconds on a resize's spans, what ran beside a reshard, and the two tags
of a resize's root.

A program without them (an older commit: no child span, no tag, no
account of the collector) gives every reader nothing to read: it returns
None and the result line leaves the metric out.
"""

from benchmark.lib import progspans
from benchmark.lib.stats import median


def _direction(root):
    tags = root.get("tags") or {}
    return ("shrink" if tags.get("to_devices", 0)
            < tags.get("from_devices", 0) else "grow")


def _resizes(view, direction=None):
    """[(root, trace)] of the window's live resizes whose first step the
    ring holds — the resizes `progspans.resizes` reads — in one
    direction, or in both."""
    return [(root, trace)
            for root, trace in progspans._traces(view, "resize.live")
            if any(s["name"] == "resize.first_step" for s in trace)
            and direction in (None, _direction(root))]


def _spans(traces, name):
    """The spans called `name`, at most one a trace (a stage that runs
    once), in the traces' order."""
    return [s for _, trace in traces for s in trace if s["name"] == name]


def _tag(span, name):
    return (span.get("tags") or {}).get(name)


# -- a save: `save.snapshot` from inside ------------------------------------


def _snapshots(view):
    return _spans(progspans._traces(view, "save"), "save.snapshot")


def snapshot_tag_ms(view, tag):
    """Median over the window's saves of a tag of `save.snapshot` that
    holds seconds (`fetch_s`, `copy_s`), in ms."""
    got = [_tag(s, tag) for s in _snapshots(view)]
    return median([1e3 * v for v in got if v is not None])


def snapshot_gb_s(view):
    """Median over the window's saves of: host bytes kept (tag `bytes`)
    over the span's duration, in GB/s."""
    return median([_tag(s, "bytes") / 1e9 / (s["dur_ms"] / 1e3)
                   for s in _snapshots(view)
                   if _tag(s, "bytes") is not None and s["dur_ms"] > 0])


def transfer_started_over_kept(view):
    """Bytes the snapshots asked the device to send to the host over the
    bytes they kept, summed over the window's saves (a period saves once
    on each world, so a median would be a number no save had)."""
    got = [(_tag(s, "transfer_bytes_started"), _tag(s, "bytes"))
           for s in _snapshots(view)]
    got = [(a, b) for a, b in got if a is not None and b]
    if not got:
        return None
    return sum(a for a, _ in got) / float(sum(b for _, b in got))


# -- a reshard: `resize.device_put` from inside ------------------------------


def put_stage_ms(view, direction, stage):
    """Median of `resize.device_put.<stage>` over the window's resizes
    in one direction."""
    return median([s["dur_ms"] for s in _spans(
        _resizes(view, direction), "resize.device_put." + stage)])


def put_moved_mb(view, direction):
    """Median of the tag `bytes_moved` of `resize.device_put`, in MB."""
    got = [_tag(s, "bytes_moved") for s in _spans(
        _resizes(view, direction), "resize.device_put")]
    return median([v / 1e6 for v in got if v is not None])


def put_beside_persist_pct(view):
    """Of the time inside the window's `resize.device_put` spans, the
    share during which a `save.persist` span of the ring was open. At
    most one write is in flight at a time, so its spans do not overlap
    each other and their overlaps with a reshard add up."""
    puts = _spans(_resizes(view), "resize.device_put")
    total = sum(s["dur_ms"] for s in puts) / 1e3
    if total <= 0:
        return None
    writes = [(s["t0"], s["t0"] + s["dur_ms"] / 1e3)
              for s in progspans.ring() if s["name"] == "save.persist"]
    beside = 0.0
    for put in puts:
        a, b = put["t0"], put["t0"] + put["dur_ms"] / 1e3
        beside += sum(max(0.0, min(b, w1) - max(a, w0)) for w0, w1 in writes)
    return 100.0 * beside / total


# -- a resize as a whole ------------------------------------------------------


def _program_counts_gc():
    """Whether the program's tracer keeps the collector's account: only
    then does a span without `gc_ms` mean that no collection ran."""
    try:
        from edl_tpu.obs import trace
    except ImportError:
        return False
    return hasattr(trace, "gc_seconds")


def resize_gc_ms(view):
    """Median over ALL the window's resizes of: the tags `gc_ms` of
    `resize.live` and `resize.first_step`, summed (the two spans cover
    the pause; their children's tags repeat part of theirs). 0 where no
    collection ran."""
    if not _program_counts_gc():
        return None
    return median([sum(_tag(s, "gc_ms") or 0.0 for s in trace
                       if s["name"] in ("resize.live", "resize.first_step"))
                   for _, trace in _resizes(view)])


def root_tag_pct(view, tag, value):
    """Share of the window's resizes whose root's `tag` reads `value`."""
    got = [_tag(root, tag) for root, _ in _resizes(view)]
    got = [v for v in got if v is not None]
    if not got:
        return None
    return 100.0 * sum(v == value for v in got) / len(got)
