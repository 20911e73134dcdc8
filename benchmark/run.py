"""Run ONE cell of BENCHMARK.json once, in one process that holds the
cell's chips:

    python3 benchmark/run.py --workload <name> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

The last line of stdout is the result object and nothing else is written
there; everything else goes to stderr and to
benchmark/out/<workload>-<seed>.jsonl. The cell's configuration, traffic
mix, kind, program adapter, reference and per-layer metrics are files
found by the names in BENCHMARK.json (see benchmark/README.md); nothing
here branches on a cell's name.
"""

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu_tiny", action="store_true",
                    help="toy sizes on the CPU, for the tests only")
    args = ap.parse_args(argv)
    from benchmark.lib import harness
    with harness.stdout_to_stderr() as emit:
        try:
            import edl_tpu  # noqa: F401 — the system under test
            harness.enable_compile_cache(args.cpu_tiny)
            run = harness.Run(args, T_PROCESS_START)
            kind = harness.load_module("kinds", run.traffic["kind"])
            result = kind.run(run)
        except (harness.BenchError, ImportError) as e:
            sys.stderr.write("benchmark: no result: %s\n" % (e,))
            return 2
        emit(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
