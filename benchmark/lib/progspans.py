"""The program's own stage spans, read where the benchmark runs it: the
ring of `edl_tpu.obs.trace.TRACER` in this process. A live resize is one
trace — a root `resize.live`, its stages as children, and the
`resize.first_step` that ends the pause — and a save is another (root
`save`). Spans carry their start on `time.monotonic()` (`t0`), the clock
of `view["window"]` and of the harness's own spans, so a trace is read
when its root starts inside the measured window and outside the harness's
`trace_window`: the profiler slows the host (on the chip a fingerprint
read 2.2 s outside a capture and 3.9 s inside), and a median over one
resize of each kind is a number no resize had. The window of a traced
run always holds an untraced period (benchmark/kinds/train.py).

A program without such spans (an older commit: no `t0` on a span, or an
empty ring) gives every reader nothing to read: it returns None and the
result line leaves the metric out.
"""

from benchmark.lib.stats import median

#: the stages a per-layer metric names; `resize_other_ms` is the pause
#: less these (so: drain, mesh, build_step, the rest of the first
#: dispatch, and whatever no span covers)
NAMED = ("device_put", "fingerprint", "step_load", "first_trace",
         "first_load", "first_run")


def ring():
    """Finished spans of this process's tracer that carry a monotonic
    start, oldest first; [] where the program has none."""
    try:
        from edl_tpu.obs.trace import TRACER
    except ImportError:
        return []
    return [s for s in TRACER.spans() if s.get("t0") is not None]


def _traces(view, root_name):
    """[(root, spans of its trace)] for the roots called `root_name` that
    start inside the measured window and outside a profiler capture."""
    w0, w1 = view["window"]
    captures = [(a, b) for name, a, b in view.get("spans") or ()
                if name == "trace_window"]
    spans = ring()
    out = []
    for root in spans:
        if root["name"] == root_name and w0 <= root["t0"] <= w1 \
                and not any(a <= root["t0"] <= b for a, b in captures):
            out.append((root, [s for s in spans
                               if s["trace_id"] == root["trace_id"]]))
    return out


def _ms(trace, name):
    """Milliseconds inside the spans called `name`; None without one."""
    hits = [s["dur_ms"] for s in trace if s["name"] == name]
    return sum(hits) if hits else None


def resizes(view):
    """One record per live resize of the window: its direction, the
    pause (start of `resize.live` to end of `resize.first_step`) and the
    named stages, all in ms; a stage the resize did not run is None. A
    resize whose first step the ring does not hold is left out."""
    out = []
    for root, trace in _traces(view, "resize.live"):
        first = [s for s in trace if s["name"] == "resize.first_step"]
        if not first:
            continue
        tags = root.get("tags") or {}
        end = first[0]["t0"] + first[0]["dur_ms"] / 1e3
        rec = {"direction": ("shrink" if tags.get("to_devices", 0)
                             < tags.get("from_devices", 0) else "grow"),
               "pause": (end - root["t0"]) * 1e3,
               "device_put": _ms(trace, "resize.device_put"),
               "fingerprint": _ms(trace, "resize.prewarm_fingerprint"),
               "step_load": _ms(trace, "resize.prewarm_load"),
               "first_run": _ms(trace, "resize.first_result"),
               "first_trace": None, "first_load": None}
        for s in trace:
            jax_s = s.get("tags") or {}
            if s["name"] == "resize.first_dispatch" \
                    and "jax_trace_s" in jax_s:
                rec["first_trace"] = 1e3 * (jax_s["jax_trace_s"]
                                            + jax_s["jax_lower_s"])
                rec["first_load"] = 1e3 * (jax_s["jax_compile_s"]
                                           + jax_s["jax_cache_load_s"])
        out.append(rec)
    return out


def resize_ms(view, direction, stage):
    """Median of one stage (or "pause") over the window's resizes in one
    direction; None where none of them has it."""
    return median([r[stage] for r in resizes(view)
                   if r["direction"] == direction and r[stage] is not None])


def resize_other_ms(view):
    """Median over ALL the window's resizes of: pause less the NAMED
    stages. Large means the spans miss something."""
    return median([r["pause"] - sum(r[k] or 0.0 for k in NAMED)
                   for r in resizes(view)])


def save_ms(view, stage):
    """Median of one stage of `trainer.save()` over the window's saves."""
    got = [_ms(trace, stage) for _, trace in _traces(view, "save")]
    return median([v for v in got if v is not None])
