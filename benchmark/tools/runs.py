"""Run one cell several times, one process per run as the driver does,
and print each metric's median and spread (distance between the first and
third quartile of `statistics.quantiles(values, n=4)` over the median) —
the numbers a bound is set from — and beside it the spread as the driver's
check takes it (`held`: largest less smallest over the median, the run
farthest from the median left out where that narrows it; a cell measured
anew may show at most half its bound as the mean of two sets' `held`, and
its second median within the bound of the first: ledger, PRs 33, 38). This
parent never touches JAX.

    python3 benchmark/tools/runs.py --workload <name> --seeds 1,2,3,4,5,6 \\
        [--sets 2] [--seconds S] [--trace 0|1] [--tag T]

Result lines go to chiprun_out/runs-<tag>.jsonl, stderr of each run to
chiprun_out/runs-<tag>-<set>-<seed>.err.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values):
    if len(values) < 2 or not statistics.median(values):
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def held_spread(values):
    """The spread the driver holds a bound to; None as `spread`."""
    if len(values) < 3 or not statistics.median(values):
        return None
    mid = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - mid))[:-1]
    return min(max(values) - min(values), max(rest) - min(rest)) / mid


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--tag", default=None)
    ap.add_argument("--cpu_tiny", action="store_true")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    tag = args.tag or args.workload
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    with open(os.path.join(out_dir, "runs-%s.jsonl" % tag), "w") as out:
        for k in range(args.sets):
            for seed in [int(s) for s in args.seeds.split(",")]:
                cmd = bench["command"] + [
                    "--workload", args.workload, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", str(args.trace)]
                if args.cpu_tiny:
                    cmd.append("--cpu_tiny")
                err = os.path.join(out_dir, "runs-%s-%d-%d.err"
                                   % (tag, k, seed))
                t = time.monotonic()
                with open(err, "w") as ef:
                    p = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                                       stderr=ef, text=True)
                wall = time.monotonic() - t
                last = (p.stdout.strip().splitlines() or [""])[-1]
                try:
                    res = json.loads(last)
                except ValueError:
                    res = None
                row = {"set": k, "seed": seed, "rc": p.returncode,
                       "wall_s": wall, "result": res}
                rows.append(row)
                out.write(json.dumps(row) + "\n")
                out.flush()
                vals = ({m: v["value"] for m, v in res["metrics"].items()}
                        if res else None)
                print("set %d seed %d rc %d wall %.1fs correct %s failed %s %s"
                      % (k, seed, p.returncode, wall,
                         res and res["correct"], res and res["failed"],
                         json.dumps(vals)), flush=True)
                if p.returncode != 0:
                    with open(err) as ef:
                        print("".join(ef.readlines()[-15:]), flush=True)
    for k in range(args.sets):
        good = [r["result"] for r in rows if r["set"] == k and r["result"]]
        if not good:
            continue
        for name in good[0]["metrics"]:
            vals = [g["metrics"][name]["value"] for g in good
                    if name in g["metrics"]]
            print("set %d %-28s median %.6g spread %s held %s n=%d"
                  % (k, name, statistics.median(vals),
                     *("-" if f(vals) is None else "%.4f" % f(vals)
                       for f in (spread, held_spread)), len(vals)))
    return 0 if all(r["rc"] == 0 for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
