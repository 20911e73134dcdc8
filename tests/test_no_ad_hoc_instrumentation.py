"""Tier-1 wiring for tools/check_no_ad_hoc_instrumentation.py: a NEW
stopwatch-plus-print pair in one function fails the build — record a
registry histogram (edl_tpu.obs.metrics) or a span
(edl_tpu.obs.trace.span) so the sample lands on the fleet snapshot or
in the span ring."""

import ast
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "check_no_ad_hoc_instrumentation.py")


def test_no_new_ad_hoc_instrumentation():
    out = subprocess.run([sys.executable, TOOL], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr


def _finder(src):
    sys.path.insert(0, os.path.dirname(TOOL))
    try:
        import check_no_ad_hoc_instrumentation as lint
    finally:
        sys.path.pop(0)
    f = lint._Finder("x.py")
    f.visit(ast.parse(src))
    return f.hits


def test_lint_actually_detects_stopwatch_print():
    """The lint must not be a rubber stamp: it flags the timed-then-
    printed combination in both the attribute and the from-import
    spelling, via print and via sys.stderr.write."""
    hits = _finder(
        "import time\n"
        "def f():\n"
        "    t0 = time.monotonic()\n"
        "    print('took', time.monotonic() - t0)\n")
    assert hits == [("x.py", "f", 4)]
    hits = _finder(
        "import sys\n"
        "from time import perf_counter as pc\n"
        "def g():\n"
        "    t0 = pc()\n"
        "    sys.stderr.write('%f\\n' % (pc() - t0))\n")
    assert hits == [("x.py", "g", 5)]


def test_lint_ignores_benign_timing():
    """Timing into a variable/stats dict (no console write) and printing
    without a stopwatch are both fine — separately or in sibling
    functions."""
    assert _finder(
        "import time\n"
        "def f():\n"
        "    t0 = time.monotonic()\n"
        "    return time.monotonic() - t0\n"
        "def g():\n"
        "    print('hello')\n") == []


def _pair_finder(src, relpath="edl_tpu/runtime/x.py"):
    sys.path.insert(0, os.path.dirname(TOOL))
    try:
        import check_no_ad_hoc_instrumentation as lint
    finally:
        sys.path.pop(0)
    f = lint._Finder(relpath)
    f.visit(ast.parse(src))
    return f.pair_hits


def test_pair_rule_flags_unledgered_stopwatch_delta():
    """A raw t0 = perf_counter() … x - t0 pair whose delta lands in a
    plain variable (or a log line) is a ledger bypass in runtime/."""
    hits = _pair_finder(
        "import time\n"
        "def f():\n"
        "    t0 = time.perf_counter()\n"
        "    work()\n"
        "    elapsed = time.perf_counter() - t0\n"
        "    logger.info('took %.1fs', elapsed)\n")
    assert hits == [("edl_tpu/runtime/x.py", "f", 5)]


def test_pair_rule_out_of_scope_outside_runtime():
    """The pair rule applies to edl_tpu/runtime/ only — the same code
    elsewhere passes (the ledger invariant lives in runtime)."""
    src = ("import time\n"
           "def f():\n"
           "    t0 = time.monotonic()\n"
           "    d = time.monotonic() - t0\n"
           "    return d\n")
    assert _pair_finder(src) != []
    assert _pair_finder(src, relpath="edl_tpu/data/x.py") == []


def test_pair_rule_passes_deadline_math():
    """deadline = monotonic() + x is a BinOp assignment, never tracked,
    so deadline - monotonic() and remaining-time checks pass."""
    assert _pair_finder(
        "import time\n"
        "def f(timeout):\n"
        "    deadline = time.monotonic() + timeout\n"
        "    while time.monotonic() < deadline:\n"
        "        remaining = deadline - time.monotonic()\n"
        "        wait(remaining)\n") == []


def test_pair_rule_passes_sanctioned_sinks():
    """A delta consumed directly inside .observe()/.inc()/.set()/
    .time_ms() already lands in the registry — not a bypass."""
    assert _pair_finder(
        "import time\n"
        "def f():\n"
        "    t0 = time.perf_counter()\n"
        "    work()\n"
        "    _STEP_MS.observe(1000.0 * (time.perf_counter() - t0))\n"
        "    _RETRIES.inc(time.perf_counter() - t0)\n") == []


def test_pair_rule_tracking_is_per_function():
    """A stopwatch variable from one function must not taint a Sub in a
    sibling function that reuses the name."""
    assert _pair_finder(
        "import time\n"
        "def f():\n"
        "    t0 = time.perf_counter()\n"
        "    use(t0)\n"
        "def g(t0, t1):\n"
        "    return t1 - t0\n") == []
