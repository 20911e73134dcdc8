"""The readers of the model-parts metrics (benchmark/metrics/
step_*_device_ms.py, <part>_device_ms.py, scope_coverage_pct.py): device
SELF time per step and chip by the program's own `jax.named_scope`s, from
the run's own trace. The reduction — which scope and phase an operation
belongs to, and that a loop is charged only what its body does not cover —
is the program's (`edl_tpu/obs/devtime.py`, beside the scopes it reads);
here is only where the run's trace lies and what a metric sums.

A program without `edl_tpu.obs.devtime` (a parent commit) has nothing to
read: every reader returns None and the line leaves the metric out."""

import glob
import os
import time

from benchmark.lib import harness

#: the annotation lib/harness.py opens round the traced periods
WINDOW_SPAN = harness.SPAN_PREFIX + "trace_window"

_TABLES = {}    # trace file -> the run's table: ten metrics, one parse


def _trace_file(view):
    """The newest .xplane.pb the run wrote: `Run.scratch_dir("trace")`
    made <out>/trace/<cell>-<seed>/, and `finish()` removes it only after
    the metrics are read."""
    paths = glob.glob(os.path.join(
        harness.OUT, "trace", view["cell"]["name"] + "-*", "**",
        "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _devtime():
    """The program's reduction, or None on a program that lacks it."""
    try:
        from edl_tpu.obs import devtime
    except ImportError:
        return None
    return devtime


def table(view):
    """{(scope, phase): ms per step and chip} of the run's trace, or None
    where there is no trace file, no traced step or no such program."""
    path, devtime = _trace_file(view), _devtime()
    steps = view["counters"].get("traced_steps")
    if path is None or devtime is None or not steps:
        return None
    if path not in _TABLES:
        t0 = time.monotonic()
        sec = devtime.by_scope(devtime.load(path, WINDOW_SPAN))
        ms = _TABLES[path] = {k: v / steps * 1e3 for k, v in sec.items()}
        harness.log("device self time by (scope, phase), ms a step "
                    "(the trace read again in %.2f s):"
                    % (time.monotonic() - t0))
        for (scope, phase), v in sorted(ms.items(), key=lambda kv: -kv[1]):
            harness.log("  %-24s %-6s %10.3f" % (scope, phase, v))
    return _TABLES[path]


def phase_ms(view, phase):
    ms = table(view)
    return None if ms is None else _devtime().by_phase(ms)[phase]


def part_ms(view, part):
    """A part's self time in every phase (a part other than `optim` has
    none in the optimizer's); `other` holds `unscoped`."""
    ms = table(view)
    return None if ms is None else _devtime().by_part(ms)[part]


def coverage_pct(view):
    ms = table(view)
    return None if ms is None else _devtime().coverage_pct(ms)
