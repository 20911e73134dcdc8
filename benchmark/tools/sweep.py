"""Find the knee of a serving cell ONCE, on the chip: the highest offered
rate with no shed and a waiting queue that does not grow over the window.
One process, one engine, the cell's own traffic at each rate in turn; the
engine drains between rates. The sweep, the knee and the rate chosen from
them are then written into the traffic file by hand (see its `rate_why`).

    python3 benchmark/tools/sweep.py --workload <name> --rates 4,8,12 \\
        --seconds 20 [--seed 1]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--cpu_tiny", action="store_true")
    args = ap.parse_args(argv)
    from benchmark.lib import harness, traffic
    from benchmark.lib.stats import percentile
    harness.enable_compile_cache(args.cpu_tiny)
    run = harness.Run(argparse.Namespace(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=0, cpu_tiny=args.cpu_tiny), time.monotonic())
    serve = harness.load_module("kinds", run.traffic["kind"])
    run.claim_devices()
    j = serve.make_job(run)
    cfg, job, engine = j["cfg"], j["job"], j["engine"]
    engine.start()
    rows = []
    try:
        serve.warm_and_check(run, j)
        for rate in [float(r) for r in args.rates.split(",")]:
            seconds = []

            def on_second(t, client, _s=seconds):
                st = engine.stats()
                _s.append((st["decode_slots_occupied"],
                           st["decode_waiting"], client.in_flight()))

            schedule = traffic.chat_schedule(job, cfg["vocab_size"],
                                             args.seed, args.seconds,
                                             rate=rate)
            client = serve.Client(run, engine, job["poll_ms"] / 1e3)
            run.compiles.window = []
            run.compiles.arm()
            reqs, t0, t1 = client.serve(schedule, args.seconds,
                                        drain_cap_s=60.0,
                                        on_second=on_second)
            run.compiles.disarm()
            s = serve.summarize(reqs, t0, t1, client)
            inwin = [x for x in seconds][:int(args.seconds)]
            half = len(inwin) // 2
            steps = np.diff([t for t in client.step_times if t <= t1])
            rows.append({
                "rate_rps": rate, "attempted": s["attempted"],
                "failed": s["failed"], "sheds": s["sheds"],
                "ttft_p50_ms": percentile(s["ttft_ms"], 0.5),
                "ttft_p95_ms": percentile(s["ttft_ms"], 0.95),
                "itl_p50_ms": percentile(s["itl_ms"], 0.5),
                "itl_p95_ms": percentile(s["itl_ms"], 0.95),
                "step_gap_p50_ms": 1e3 * float(np.median(steps))
                if len(steps) else None,
                "slots_mean": float(np.mean([x[0] for x in inwin])),
                "slots_max": max(x[0] for x in inwin),
                "waiting_max": max(x[1] for x in inwin),
                "in_flight_first_half": float(np.mean(
                    [x[2] for x in inwin[:half]])),
                "in_flight_second_half": float(np.mean(
                    [x[2] for x in inwin[half:]])),
                "gen_late_p95_ms": percentile(s["gen_late_ms"], 0.95),
                "window_compiles": len(run.compiles.window),
                "drain_s": time.monotonic() - t1})
            print(json.dumps(rows[-1]), flush=True)
    finally:
        engine.stop()
    out = os.path.join(harness.ROOT, "chiprun_out",
                       "sweep-%s.json" % args.workload)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
