"""Read, in ONE process, the numbers `correct` compares: the program's
over many seeds and the control's over a few, at the cell's own size. The
limits in the traffic files are set from what this prints (PERF.md, "How
correct is decided"); the benchmark's own runs never run the control.

    python3 benchmark/tools/limits.py --workload <name> \\
        --seeds 1,2,... --control_seeds 1,2,3 [--controls int8,...] \\
        [--cpu_tiny]

The controls are the kind's own (`CONTROLS` of benchmark/kinds/<kind>.py):
`int8` is the nearest precision below the configuration's — for a
training cell the reference with every product's operands rounded to
int8, put in the program's place; for a serving cell the program's own
int8 weight path (`ops/quant.quantize_tree`).
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control_seeds", default="")
    ap.add_argument("--controls", default="",
                    help="names of the kind's CONTROLS; default: all")
    ap.add_argument("--cpu_tiny", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from benchmark.lib import harness
    harness.enable_compile_cache(args.cpu_tiny)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    _, _, _, traffic = harness.cell_spec(args.workload)
    kind = harness.load_module("kinds", traffic["kind"])
    controls = ([c for c in args.controls.split(",") if c]
                or sorted(kind.CONTROLS))
    rows = []
    for seed, names in [(s, [None]) for s in seeds] + [
            (s, controls) for s in control_seeds]:
        run = harness.Run(argparse.Namespace(
            workload=args.workload, seed=seed, seconds=0, trace=0,
            cpu_tiny=args.cpu_tiny), time.monotonic())
        # no control runs across chips (a training cell's is the
        # reference alone), so one chip reads it, at a quarter the cost
        run.claim_devices(None if None in names else 1)
        for name, got in kind.compared_numbers(run, names).items():
            rows.append(dict(got, seed=seed, control=name))
            harness.log(json.dumps(rows[-1]))
        del run
        gc.collect()
    for name in sorted(k for k in rows[0] if k not in ("seed", "control")):
        for c in [None] + controls:
            vals = [r[name] for r in rows if r["control"] == c]
            if vals:
                print("%s: %s min %.6g max %.6g over %d seeds"
                      % (name, c or "sound", min(vals), max(vals),
                         len(vals)))
    out = args.out or os.path.join(harness.ROOT, "chiprun_out",
                                   "limits-%s.json" % args.workload)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
