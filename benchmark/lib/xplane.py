"""Reduction of a JAX profiler trace (.xplane.pb) to what the per-layer
metrics read: device busy time as the union of op intervals, time by op
class and by executable, collectives exposed or hidden, device busy time
inside each harness span, and every idle gap named by what the host was
doing in it.

Started from `edl_tpu/tools/profile_bench.py:xplane_op_breakdown` (sum of
"XLA Ops" durations by op class, averaged over device planes); extended to
interval unions, spans and gaps, and reading with `jax.profiler.ProfileData`
alone. `reduce_events` works on plain tuples so that a small recorded trace
can be checked without a profiler (tests/fixtures/trace_small.json).
"""

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: an idle gap shorter than this is the device's own hand-over between two
#: operations, not something the host did
BETWEEN_OPS_NS = 20e3
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute")


def load_events(path, span_prefix, cpu_as_device=False):
    """[(plane, line, name, start_ns, dur_ns)] of the device planes' op
    and module lines and of the host threads' harness spans. With
    `cpu_as_device` (the CPU tests only) the CPU client's executor threads
    stand in for a device plane."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            fake = (cpu_as_device and not device
                    and line.name.startswith("tf_XLAPjRtCpuClient"))
            for ev in line.events:
                if device or ev.name.startswith(span_prefix):
                    out.append((plane.name, line.name, ev.name,
                                float(ev.start_ns), float(ev.duration_ns)))
                elif fake and ev.duration_ns > 0:
                    out.append(("/device:CPU:0", OPS_LINE, ev.name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


def _merge(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


class _Cover(object):
    """Merged intervals with O(log n) 'covered length inside [a, b]'."""

    def __init__(self, intervals):
        self.iv = _merge(intervals)
        self.starts = [a for a, _ in self.iv]
        self.cum = [0.0]
        for a, b in self.iv:
            self.cum.append(self.cum[-1] + (b - a))

    def upto(self, x):
        i = bisect.bisect_right(self.starts, x)
        if i == 0:
            return 0.0
        a, b = self.iv[i - 1]
        return self.cum[i - 1] + (min(x, b) - a)

    def inside(self, a, b):
        return self.upto(b) - self.upto(a) if b > a else 0.0

    def gaps(self, a, b):
        out, at = [], a
        for s, e in self.iv:
            if e <= a or s >= b:
                continue
            if s > at:
                out.append((at, s))
            at = max(at, e)
        if at < b:
            out.append((at, b))
        return out


def _op_class(name):
    return re.sub(r"\.\d+", "", name.split(" = ")[0]).lstrip("%")


def _module_name(name):
    return re.sub(r"\(.*\)$", "", name)


def reduce_events(events, span_prefix, window_span="trace_window"):
    """See the module docstring. Times in seconds; `busy_s`, `ops` and
    `modules` are averages over the device planes; `spans[...].busy_chip_s`,
    `collective_s` and `collective_exposed_s` are sums over them."""
    ops, modules, spans = {}, {}, []
    for plane, line, name, start, dur in events:
        if plane.startswith("/device:"):
            (ops if line == OPS_LINE else modules).setdefault(
                plane, []).append((name, start, start + dur))
        elif name.startswith(span_prefix):
            spans.append((name[len(span_prefix):], start, start + dur))
    planes = sorted(ops)
    win = [s for s in spans if s[0] == window_span]
    if win:
        w0, w1 = win[0][1], win[0][2]
    elif planes:
        w0 = min(e[1] for p in planes for e in ops[p])
        w1 = max(e[2] for p in planes for e in ops[p])
    else:
        return {"window_s": 0.0, "busy_s": 0.0, "planes": 0, "ops": [],
                "modules": {}, "spans": {}, "idle_gaps": [],
                "collective_s": 0.0, "collective_exposed_s": 0.0}
    clip = lambda a, b: (max(a, w0), min(b, w1))
    n = float(len(planes))
    by_class, covers = {}, {}
    coll_s = exposed_s = 0.0
    for p in planes:
        evs = [(nm,) + clip(a, b) for nm, a, b in ops[p]]
        evs = [e for e in evs if e[2] > e[1]]
        covers[p] = _Cover([(a, b) for _, a, b in evs])
        for nm, a, b in evs:
            c = _op_class(nm)
            by_class[c] = by_class.get(c, 0.0) + (b - a)
        coll = [(a, b) for nm, a, b in evs if COLLECTIVE.search(nm)]
        comp = _Cover([(a, b) for nm, a, b in evs
                       if not COLLECTIVE.search(nm)])
        for a, b in _merge(coll):
            coll_s += b - a
            exposed_s += (b - a) - comp.inside(a, b)
    mods = {}
    for p in modules:
        for nm, a, b in modules[p]:
            a, b = clip(a, b)
            if b > a:
                m = mods.setdefault(_module_name(nm),
                                    {"count": 0, "seconds": 0.0})
                m["count"] += 1.0 / n
                m["seconds"] += (b - a) / 1e9 / n
    span_out = {}
    for nm, a, b in spans:
        a, b = clip(a, b)
        if b <= a:
            continue
        s = span_out.setdefault(nm, {"count": 0, "seconds": 0.0,
                                     "busy_chip_s": 0.0})
        s["count"] += 1
        s["seconds"] += (b - a) / 1e9
        s["busy_chip_s"] += sum(covers[p].inside(a, b)
                                for p in planes) / 1e9
    # idle gaps of the first device, each named by the SHORTEST harness
    # span that holds its midpoint
    named = sorted(((b - a, nm, a, b) for nm, a, b in spans
                    if nm != window_span))
    gaps, by_who = [], {}
    for a, b in covers[planes[0]].gaps(w0, w1):
        mid = 0.5 * (a + b)
        if b - a < BETWEEN_OPS_NS:
            who = "between_ops"
        else:
            who = next((nm for _, nm, sa, sb in named if sa <= mid <= sb),
                       "host_other")
        gaps.append([who, (b - a) / 1e9])
        by_who[who] = by_who.get(who, 0.0) + (b - a) / 1e9
    gaps.sort(key=lambda g: -g[1])
    totals = sorted((["total:" + k, v] for k, v in by_who.items()),
                    key=lambda g: -g[1])
    busy = sum(c.inside(w0, w1) for c in covers.values()) / 1e9 / n
    return {
        "window_s": (w1 - w0) / 1e9, "busy_s": busy, "planes": len(planes),
        "ops": [[k, v / 1e9 / n] for k, v in
                sorted(by_class.items(), key=lambda kv: -kv[1])],
        "modules": mods, "spans": span_out,
        "idle_gaps": gaps[:5] + totals[:5],
        "collective_s": coll_s / 1e9, "collective_exposed_s": exposed_s / 1e9,
    }


def reduce_dir(trace_dir, span_prefix, cpu_as_device=False):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return reduce_events(load_events(paths[-1], span_prefix, cpu_as_device),
                         span_prefix)
