#!/usr/bin/env python
"""Lint: no NEW ad-hoc stopwatch-and-print instrumentation.

A function that both reads a stopwatch (``time.monotonic()`` /
``time.perf_counter()``) and writes it straight to a console
(``print(...)`` / ``sys.stderr.write``) is hand-rolled instrumentation —
exactly what ``edl_tpu.obs`` replaces: the sample never reaches the
fleet snapshot, can't be aggregated by job_stats, and costs a syscall
on the hot path. Record it as a registry histogram (pre-bound handle +
``observe``) or a span (``edl_tpu.obs.trace.span``) instead.

Timing INTO a variable/stat dict is fine (most of the tree does that);
only the timed-then-printed combination in one function is flagged.
``edl_tpu/obs`` (the sanctioned sink) and ``edl_tpu/tools`` (benches
print reports by design) are out of scope.

A second, stricter rule applies to ``edl_tpu/runtime/`` and
``edl_tpu/serve/`` only: a raw stopwatch PAIR
(``t0 = time.monotonic()`` … ``<x> - t0``) whose delta goes anywhere
but a sanctioned sink (``observe`` / ``inc`` / ``set`` / ``time_ms`` /
a span's ``tag``) is wall-clock attribution bypassing the time ledger — the seconds it
measures are invisible to ``goodput/v1`` (in serve, to the decode
TTFT/ITL admission estimates). Route the
interval through :class:`edl_tpu.obs.ledger.TimeLedger`, a registry
histogram or — for a stage of a resize or a save — a stage span of
``edl_tpu.obs.trace`` (the span is the stopwatch: read ``.seconds`` off
it) instead. Deadline math (``deadline = monotonic() + x`` /
``deadline - monotonic()``) passes automatically: the deadline variable
is not a bare stopwatch read, so it is never tracked. Remaining
legitimate sites live in STOPWATCH_ALLOWLIST with a justification.

Pre-existing sites are grandfathered in ALLOWLIST, keyed by
``(relative path, enclosing function)`` so ordinary line drift does not
churn the list. Runs as a tier-1 test
(tests/test_no_ad_hoc_instrumentation.py).
"""

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCAN_ROOT = "edl_tpu"
EXCLUDE_DIRS = ("edl_tpu/obs", "edl_tpu/tools")

STOPWATCHES = {"monotonic", "perf_counter"}

# (relpath, enclosing function) -> why the stopwatch+console pair is OK.
# Empty today.
ALLOWLIST = {}

#: only these subtrees are held to the stopwatch-pair rule — runtime is
#: where the time ledger's exclusive-state invariant lives, and serve is
#: the decode data plane whose TTFT/ITL intervals must reach the
#: admission EWMAs and registry histograms, not ad-hoc prints
PAIR_SCAN_PREFIX = ("edl_tpu/runtime/", "edl_tpu/serve/")

#: calls whose argument position is a sanctioned destination for a
#: stopwatch delta (registry handles and the span tracer)
SINK_METHODS = {"observe", "inc", "set", "time_ms", "span", "tag"}

# (relpath, enclosing function) -> why this raw stopwatch pair may
# bypass the ledger. Keep justifications specific: the next reader
# decides whether a new site belongs here by analogy.
STOPWATCH_ALLOWLIST = {
    ("edl_tpu/runtime/trainer.py", "train_step"):
        "step_s feeds _STEP_MS.observe and the cadence estimator; the "
        "interval itself is ledgered as the compute state",
    ("edl_tpu/runtime/trainer.py", "compile_all"):
        "prewarm compiles run on a background thread (never ledgered "
        "by design); the duration is a log line only",
    ("edl_tpu/runtime/checkpoint.py", "persist"):
        "the async persist driver is a background thread whose "
        "concurrency is deliberately NOT ledgered; persist_s lands on "
        "the SaveHandle and _SAVE_MS",
    ("edl_tpu/runtime/checkpoint.py", "fetch"):
        "_SnapshotAccount: the per-leaf fetches of one save.snapshot "
        "interleave with its copies, so their seconds are summed and "
        "handed to that stage span as its tag fetch_s (a span per leaf "
        "would flood the ring); the interval itself is ledgered as "
        "ckpt_block by save_async",
    ("edl_tpu/runtime/checkpoint.py", "keep"):
        "_SnapshotAccount: as fetch, for the copies into the host "
        "pool: summed into the save.snapshot span's tag copy_s",
    ("edl_tpu/serve/decode_engine.py", "_prefill"):
        "prefill_ms feeds admission.observe_prefill_ms (the TTFT "
        "projection EWMA) and the _TTFT histogram; the serving device "
        "loop is outside the training time ledger by design",
    ("edl_tpu/serve/decode_engine.py", "_run_step"):
        "step_ms feeds admission.observe_itl_ms (the ITL shed EWMA), "
        "per-seq itl_ms reports and the _ITL histogram; the serving "
        "device loop is outside the training time ledger by design",
    ("edl_tpu/serve/decode_engine.py", "_prefill_suffix"):
        "suffix_ms feeds admission.observe_prefill_ms (per-token TTFT "
        "EWMA) like _prefill; the serving device loop is outside the "
        "training time ledger by design",
    ("edl_tpu/serve/decode_engine.py", "_run_chunk"):
        "quantum_ms feeds BOTH admission EWMAs (observe_prefill_ms for "
        "the chunk, observe_itl_ms via _finish_step for the fused "
        "rows); the serving device loop is outside the training time "
        "ledger by design",
}


class _Finder(ast.NodeVisitor):
    """Per-function pairing of stopwatch reads and console writes."""

    def __init__(self, relpath):
        self.relpath = relpath
        self.hits = []  # (relpath, func, lineno)
        self.pair_hits = []  # (relpath, func, lineno) — ledger-bypass
        # stack of [name, stopwatch_lineno, console_lineno]
        self._funcs = [["<module>", None, None]]
        # per-function sets of plain names assigned from a BARE
        # stopwatch read (deadline math assigns a BinOp, so deadline
        # variables never land here)
        self._tracked = [set()]
        self._sink_depth = 0
        self.check_pairs = relpath.startswith(PAIR_SCAN_PREFIX)
        self.time_aliases = {"time"}
        self.clock_aliases = set()

    def visit_Import(self, node):
        for a in node.names:
            if a.name == "time":
                self.time_aliases.add(a.asname or "time")

    def visit_ImportFrom(self, node):
        if node.module == "time":
            for a in node.names:
                if a.name in STOPWATCHES:
                    self.clock_aliases.add(a.asname or a.name)

    def _in_func(self, node):
        self._funcs.append([node.name, None, None])
        self._tracked.append(set())
        self.generic_visit(node)
        self._tracked.pop()
        name, clock, console = self._funcs.pop()
        if clock is not None and console is not None:
            self.hits.append((self.relpath, name, console))

    visit_FunctionDef = _in_func
    visit_AsyncFunctionDef = _in_func

    def _is_stopwatch(self, call):
        f = call.func
        if isinstance(f, ast.Attribute) and f.attr in STOPWATCHES \
                and isinstance(f.value, ast.Name) \
                and f.value.id in self.time_aliases:
            return True
        return isinstance(f, ast.Name) and f.id in self.clock_aliases

    @staticmethod
    def _is_console_write(call):
        f = call.func
        if isinstance(f, ast.Name) and f.id == "print":
            return True
        # sys.stderr.write / sys.stdout.write
        return (isinstance(f, ast.Attribute) and f.attr == "write"
                and isinstance(f.value, ast.Attribute)
                and f.value.attr in ("stderr", "stdout")
                and isinstance(f.value.value, ast.Name)
                and f.value.value.id == "sys")

    def visit_Call(self, node):
        frame = self._funcs[-1]
        if frame[1] is None and self._is_stopwatch(node):
            frame[1] = node.lineno
        if frame[2] is None and self._is_console_write(node):
            frame[2] = node.lineno
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in SINK_METHODS:
            # a delta consumed inside .observe()/.inc()/… is already
            # landing in the registry — not a ledger bypass
            self._sink_depth += 1
            try:
                self.generic_visit(node)
            finally:
                self._sink_depth -= 1
        else:
            self.generic_visit(node)

    def visit_Assign(self, node):
        if self.check_pairs and isinstance(node.value, ast.Call) \
                and self._is_stopwatch(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self._tracked[-1].add(t.id)
        self.generic_visit(node)

    def visit_BinOp(self, node):
        if self.check_pairs and self._sink_depth == 0 \
                and isinstance(node.op, ast.Sub) \
                and isinstance(node.right, ast.Name) \
                and node.right.id in self._tracked[-1]:
            self.pair_hits.append((self.relpath, self._funcs[-1][0],
                                   node.lineno))
        self.generic_visit(node)


def scan():
    hits = []
    pair_hits = []
    root = os.path.join(REPO, SCAN_ROOT)
    for dirpath, _, files in os.walk(root):
        rel_dir = os.path.relpath(dirpath, REPO)
        if any(rel_dir == ex or rel_dir.startswith(ex + os.sep)
               for ex in EXCLUDE_DIRS):
            continue
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            relpath = os.path.relpath(path, REPO)
            with open(path) as f:
                tree = ast.parse(f.read(), filename=relpath)
            finder = _Finder(relpath)
            finder.visit(tree)
            hits.extend(finder.hits)
            pair_hits.extend(finder.pair_hits)
    return hits, pair_hits


def main():
    hits, pair_hits = scan()
    violations = [(rel, func, line) for rel, func, line in hits
                  if (rel, func) not in ALLOWLIST]
    pair_violations = [(rel, func, line) for rel, func, line in pair_hits
                       if (rel, func) not in STOPWATCH_ALLOWLIST]
    stale = sorted(set(ALLOWLIST) - {(rel, func) for rel, func, _ in hits})
    stale_pairs = sorted(set(STOPWATCH_ALLOWLIST)
                         - {(rel, func) for rel, func, _ in pair_hits})
    if stale:
        print("stale ALLOWLIST entries (site no longer exists — remove "
              "them):")
        for rel, func in stale:
            print("  %s :: %s" % (rel, func))
    if stale_pairs:
        print("stale STOPWATCH_ALLOWLIST entries (site no longer exists "
              "— remove them):")
        for rel, func in stale_pairs:
            print("  %s :: %s" % (rel, func))
    if violations:
        print("ad-hoc instrumentation (stopwatch + console write in one "
              "function):")
        for rel, func, line in violations:
            print("  %s:%d in %s()" % (rel, line, func))
        print("record a registry histogram (edl_tpu.obs.metrics) or a "
              "span (edl_tpu.obs.trace.span) instead, or "
              "allowlist the site in "
              "tools/check_no_ad_hoc_instrumentation.py with a "
              "justification.")
    if pair_violations:
        print("raw stopwatch pair bypassing the time ledger (%s):"
              % " + ".join(PAIR_SCAN_PREFIX))
        for rel, func, line in pair_violations:
            print("  %s:%d in %s()" % (rel, line, func))
        print("attribute the interval through edl_tpu.obs.ledger "
              "(LEDGER.state/transition), a registry histogram or a "
              "stage span (edl_tpu.obs.trace), or add the site to STOPWATCH_ALLOWLIST with a "
              "justification.")
    if violations or pair_violations or stale or stale_pairs:
        return 1
    print("ok: no ad-hoc stopwatch+print instrumentation and no "
          "unledgered stopwatch pairs outside the allowlists")
    return 0


if __name__ == "__main__":
    sys.exit(main())
