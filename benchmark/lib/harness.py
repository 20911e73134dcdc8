"""What every kind of cell shares: finding a cell's files by name, the
compile cache, the device check, the compile watch, host spans, the
traced sub-window, the per-run file and the result line.

A kind (benchmark/kinds/<kind>.py) drives the system under test and hands
back end-to-end values and counters; nothing here knows a cell by name.
"""

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")
SPAN_PREFIX = "bench:"

#: jax.monitoring duration events that mean "a program was traced,
#: lowered, compiled or loaded from the persistent cache"
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class BenchError(Exception):
    """The run cannot produce a result: exit non-zero, print none."""


def log(msg):
    sys.stderr.write("[bench %7.2f] %s\n" % (time.monotonic() - _T0, msg))
    sys.stderr.flush()


_T0 = time.monotonic()


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(folder, name):
    """benchmark/<folder>/<name>.py as a module (names may hold `-`, `.`)."""
    path = os.path.join(BENCH, folder, name + ".py")
    if not os.path.exists(path):
        raise BenchError("no file %s" % os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(
        "bench_%s_%s" % (folder, name.replace("-", "_").replace(".", "_")),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload):
    """(benchmark, cell, config, traffic) for one entry of `workloads`;
    the config and traffic files are found by the names in the entry."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [c for c in bench["workloads"] if c["name"] == workload]
    if not cells:
        raise BenchError("no workload %r in BENCHMARK.json" % (workload,))
    cell = cells[0]
    entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def enable_compile_cache(tiny=False):
    """One fixed directory inside the checkout unless the environment
    names one; the program resolves the same path (utils/compile_cache).
    Must run before jax is imported. The CPU tests keep XLA's cache off
    (entries written by the CPU backend do not always load again) and
    only the trainer's AOT artifacts land in the directory."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax
    if tiny:
        jax.config.update("jax_enable_compilation_cache", False)
        return
    # cache every program, however quick its compile: set-up then loads
    # the same set in every run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileWatch(object):
    """Counts JAX trace / lower / compile / cache-load events between
    arm() and disarm(). Events inside an `excused()` block belong to a
    pause the cell measures by itself (a live resize) and are kept apart."""

    def __init__(self):
        self.window = []
        self.excused_events = []
        self.total = 0
        self._armed = False
        self._excused = 0
        self.during = lambda: None   # names the open harness span

    def install(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event not in COMPILE_EVENTS:
            return
        self.total += 1
        if self._armed:
            rec = {"event": event.rsplit("/", 1)[-1],
                   "fun": kw.get("fun_name"), "s": duration,
                   "t": time.monotonic(), "during": self.during()}
            (self.excused_events if self._excused else
             self.window).append(rec)

    def arm(self):
        self._armed = True

    def disarm(self):
        self._armed = False

    @contextlib.contextmanager
    def excused(self):
        self._excused += 1
        try:
            yield
        finally:
            self._excused -= 1


class Run(object):
    """One run of one cell: arguments, files, spans, counters, checks."""

    def __init__(self, args, t_process_start):
        self.bench, self.cell, self.config, self.traffic = cell_spec(
            args.workload)
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.traced = bool(int(args.trace))
        self.tiny = bool(args.cpu_tiny)
        if self.tiny:
            # toy sizes for the CPU tests: the files' own `tiny` blocks
            self.config = dict(self.config, **self.config.get("tiny", {}))
            self.traffic = dict(self.traffic,
                                **self.traffic.get("tiny", {}))
        self.t_start = t_process_start
        self.compiles = CompileWatch()
        self._open_spans = []
        self.compiles.during = lambda: list(self._open_spans)
        self.spans = []          # (name, t0, t1) on time.monotonic()
        self.counters = {}
        self.checks = []         # (name, value, limit, ok)
        self.window = None       # (t0, t1)
        self.setup_s = None
        self.trace_dir = None
        self._tracing = False
        self._gc = {"n": 0, "s": 0.0, "t0": None}
        os.makedirs(OUT, exist_ok=True)
        self.out_path = os.path.join(
            OUT, "%s-%d.jsonl" % (self.cell["name"], self.seed))
        self._out = open(self.out_path, "w")
        self.devices = None
        self.peaks = None

    # -- devices -----------------------------------------------------------

    def claim_devices(self, chips=None):
        """The cell's chips (tools/limits.py asks for one where it runs
        the reference alone); no result without them."""
        import jax
        from benchmark.lib.peaks import peaks
        devs = jax.devices()
        chips = chips or self.cell["chips"]
        if self.tiny:
            if devs[0].platform != "cpu":
                raise BenchError("--cpu_tiny is for the CPU tests only")
            self.peaks = {"bf16_flops": 1.0, "hbm_bytes_s": 1.0,
                          "hbm_bytes": 1.0}
        else:
            if devs[0].platform != "tpu":
                raise BenchError("no accelerator: jax reports %r"
                                 % (devs[0].platform,))
            self.peaks = peaks(devs[0].device_kind)
        if len(devs) < chips:
            raise BenchError("cell %s needs %d chips, jax reports %d"
                             % (self.cell["name"], chips, len(devs)))
        self.devices = devs[:chips]
        self.compiles.install()
        gc.callbacks.append(self._on_gc)
        return self.devices

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc["t0"] = time.monotonic()
        elif self._gc["t0"] is not None:
            self._gc["n"] += 1
            self._gc["s"] += time.monotonic() - self._gc["t0"]

    def gc_counts(self):
        return self._gc["n"], self._gc["s"]

    # -- files by name -----------------------------------------------------

    def program(self):
        return load_module("program", self.config["family"])

    def reference(self):
        return load_module("reference", self.cell["config"])

    def scratch_dir(self, name):
        """A fixed, per-cell directory under benchmark/out, emptied."""
        path = os.path.join(OUT, name, "%s-%d" % (self.cell["name"],
                                                  self.seed))
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path)
        return path

    # -- spans, counters, records -----------------------------------------

    @contextlib.contextmanager
    def span(self, name):
        """A host span around a call into a layer: kept on the host clock
        and, while the profiler runs, written into its trace."""
        ann = None
        if self._tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
            ann.__enter__()
        t0 = time.monotonic()
        self._open_spans.append(name)
        try:
            yield
        finally:
            self._open_spans.pop()
            self.spans.append((name, t0, time.monotonic()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def count(self, name, value=1):
        self.counters[name] = self.counters.get(name, 0) + value

    def record(self, **fields):
        self._out.write(json.dumps(fields) + "\n")
        self._out.flush()

    def check(self, name, value, limit):
        """One number compared beside its limit; every run prints it."""
        ok = value is not None and value == value and value <= limit
        self.checks.append((name, value, limit, ok))
        log("check %-28s %s  limit %s  %s"
            % (name, value, limit, "ok" if ok else "NOT CORRECT"))
        self.record(check=name, value=value, limit=limit, ok=ok)
        return ok

    # -- the measured window ----------------------------------------------

    def window_open(self):
        t = time.monotonic()
        self.setup_s = t - self.t_start
        self.window = [t, None]
        self.compiles.arm()
        log("set-up done in %.2f s; window opens" % self.setup_s)
        return t

    def window_close(self):
        self.compiles.disarm()
        self.window[1] = time.monotonic()
        return self.window[1]

    def trace_start(self):
        import jax
        self.trace_dir = self.scratch_dir("trace")
        jax.profiler.start_trace(self.trace_dir)
        self._tracing = True
        self._trace_ann = jax.profiler.TraceAnnotation(
            SPAN_PREFIX + "trace_window")
        self._trace_ann.__enter__()
        self._trace_t0 = time.monotonic()

    def trace_stop(self):
        import jax
        self._trace_ann.__exit__(None, None, None)
        self.spans.append(("trace_window", self._trace_t0,
                           time.monotonic()))
        self._tracing = False
        jax.profiler.stop_trace()

    # -- the result --------------------------------------------------------

    def finish(self, attempted, failed, end_to_end):
        """Build the contract's result object. Raises BenchError when a
        program compiled inside the window."""
        import jax
        from benchmark.lib import xplane
        if self.compiles.window:
            funs = sorted({"%s during %s" % (e["fun"], e["during"])
                           for e in self.compiles.window})[:20]
            self.record(window_compiles=self.compiles.window)
            raise BenchError(
                "%d JAX trace/compile/cache-load events inside the "
                "measured window, in: %s"
                % (len(self.compiles.window), ", ".join(funs)))
        devs = self.devices
        mem = 0
        for d in devs:
            # the TPU runtime keeps program temporaries in a region of
            # their own: reserved bytes are not part of bytes in use
            st = d.memory_stats() or {}
            mem = max(mem, int(st.get("peak_bytes_in_use", 0))
                      + int(st.get("peak_bytes_reserved", 0)))
            self.record(memory_stats={k: v for k, v in st.items()
                                      if isinstance(v, (int, float))})
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": mem}
        end_to_end = dict(end_to_end, setup_s=self.setup_s)
        result = {"correct": bool(self.checks) and all(c[3] for c in
                                                       self.checks),
                  "attempted": int(attempted), "failed": int(failed)}
        name = self.cell["name"]
        if not self.traced:
            metrics = {}
            for m in self.bench["end_to_end"]:
                if applies(m, name):
                    if end_to_end.get(m["name"]) is None:
                        raise BenchError("cell %s produced no %s"
                                         % (name, m["name"]))
                    metrics[m["name"]] = {"value": end_to_end[m["name"]],
                                          "unit": m["unit"]}
        else:
            trace = xplane.reduce_dir(self.trace_dir, SPAN_PREFIX,
                                      cpu_as_device=self.tiny)
            if trace["busy_s"] <= 0:
                raise BenchError("no device operation in the trace")
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {"device_ops": trace["ops"][:10],
                                   "idle_gaps": trace["idle_gaps"][:10]}
            view = {"cell": self.cell, "config": self.config,
                    "traffic": self.traffic, "peaks": self.peaks,
                    "spans": self.spans, "counters": self.counters,
                    "window": self.window, "trace": trace,
                    "end_to_end": end_to_end,
                    "window_compiles": len(self.compiles.window)}
            metrics = {}
            for m in self.bench["per_layer"]:
                if not applies(m, name):
                    continue
                value = load_module("metrics", m["name"]).read(view)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            self.record(trace={k: v for k, v in trace.items()
                               if k != "idle_gaps"})
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        result["metrics"] = metrics
        result["device"] = device
        self.record(result=result, end_to_end=end_to_end,
                    counters=self.counters,
                    excused_compiles=len(self.compiles.excused_events),
                    gc=self.gc_counts())
        self._out.close()
        return result


@contextlib.contextmanager
def stdout_to_stderr():
    """Everything but the result line goes to stderr, C-level writes
    included; yields a function that writes the one line to real stdout."""
    sys.stdout.flush()
    real = os.dup(1)
    os.dup2(2, 1)

    def emit(line):
        sys.stdout.flush()
        os.write(real, (line + "\n").encode())

    try:
        yield emit
    finally:
        sys.stdout.flush()
        os.dup2(real, 1)
        os.close(real)


def stop_threads(*threads):
    for t in threads:
        t.join(timeout=30.0)
        if t.is_alive():
            raise BenchError("thread %s did not stop" % t.name)


def key_from_seed(seed):
    """A PRNG key from any whole number up to 2**63 (a seed above 2**31
    does not fit the 32 bits PRNGKey takes)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)

