"""The serving kind: one `DecodeEngine` in this process, open-loop
arrivals from the traffic file's schedule, every request through
`engine.submit` (the front door).

Times are the benchmark's own: a request's first token and each later
token are stamped when this process SEES them through the public handle
(`tokens_from`), on the host clock, from when the request was DUE.
`attempted` is the number of requests due in the window — fixed by the
traffic file, the rate and `--seconds`. After the window the engine
drains, untimed, so that each request can be classed as finished, failed
or (at the cap) still decoding without error.

`correct` (before the window): the tokens the engine emitted for the
warm-up requests — cold prefill in every bucket, prefix reuse with every
suffix width, decode at full slots — are scored with the plain float32
reference's teacher-forced logits: how far below the reference's best
logit the emitted token lies, in units of that position's logit spread,
averaged over all positions (`token_regret`). A right engine picks the
reference's best token or one within rounding of it; sampled tokens are
never compared for equality.
"""

import queue
import threading
import time

import numpy as np

from benchmark.lib import traffic as traffic_lib
from benchmark.lib.harness import (BenchError, key_from_seed, log,
                                   stop_threads)
from benchmark.lib.stats import percentile


class _Req(object):
    __slots__ = ("due", "tokens", "max_new", "handle", "sent", "first",
                 "stamps", "n", "done", "error", "shed")

    def __init__(self, due, tokens, max_new):
        self.due, self.tokens, self.max_new = due, tokens, max_new
        self.handle = self.sent = self.first = self.error = self.shed = None
        self.stamps = []   # host time of every token after the first
        self.n = 0
        self.done = False


class Client(object):
    """Sends a schedule and watches the tokens come back. One generator
    thread sends; the calling thread polls: every request still waiting
    for its first token, and ONE live request as a sentinel — the engine
    emits a step's tokens for all live requests at once, so the others
    are read only when the sentinel has advanced."""

    def __init__(self, run, engine, poll_s):
        self.run, self.engine, self.poll_s = run, engine, poll_s
        self.sent_q = queue.SimpleQueue()
        self.waiting_first, self.live, self.closed = [], [], []
        self.step_times = []       # host time of each observed step
        self.live_positions = []   # (time, cached positions in use)
        self.late_ms = []          # how late each request was sent
        self.sheds = {}

    def _generate(self, reqs, t0):
        from edl_tpu.utils import errors
        for r in reqs:
            delay = t0 + r.due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            with self.run.span("submit"):
                r.sent = time.monotonic()
                self.late_ms.append(1e3 * (r.sent - (t0 + r.due)))
                try:
                    r.handle = self.engine.submit(r.tokens, r.max_new)
                except errors.OverloadedError as e:
                    r.shed = str(e).split("(")[0].split(":")[-1].strip()
            self.sent_q.put(r)

    def _read(self, r, now):
        """New tokens of one request; True when it advanced or ended."""
        try:
            toks, done = r.handle.tokens_from(r.n)
        except Exception as e:  # noqa: BLE001 — the engine's typed error
            r.error, r.done = repr(e), True
            return True
        if toks:
            if r.first is None:
                r.first = now
                r.stamps.extend([now] * (len(toks) - 1))
            else:
                r.stamps.extend([now] * len(toks))
            r.n += len(toks)
        r.done = done
        return bool(toks) or done

    def poll(self, now):
        while True:
            try:
                r = self.sent_q.get_nowait()
            except queue.Empty:
                break
            if r.shed is not None:
                self.sheds[r.shed] = self.sheds.get(r.shed, 0) + 1
                self.closed.append(r)
            else:
                self.waiting_first.append(r)
        for r in list(self.waiting_first):
            if self._read(r, now):
                self.waiting_first.remove(r)
                (self.closed if r.done else self.live).append(r)
        if self.live and self._read(self.live[0], now):
            self.step_times.append(now)
            for r in self.live[1:]:
                self._read(r, now)
            for r in [r for r in self.live if r.done]:
                self.live.remove(r)
                self.closed.append(r)
            self.live_positions.append(
                (now, sum(len(r.tokens) + r.n for r in self.live)))

    def in_flight(self):
        return len(self.waiting_first) + len(self.live)

    def serve(self, schedule, seconds, trace_at=None, drain_cap_s=0.0,
              on_second=None):
        """Send `schedule` from now; poll until `seconds` have passed and
        then until every request has ended or `drain_cap_s` more have.
        Returns (requests, t0, t1)."""
        reqs = [_Req(*s) for s in schedule]
        t0 = time.monotonic()
        gen = threading.Thread(target=self._generate, args=(reqs, t0),
                               name="bench-generator", daemon=True)
        gen.start()
        t1 = t0 + seconds
        next_second, tracing = t0 + 1.0, False
        while True:
            now = time.monotonic()
            if trace_at and not tracing and now >= t0 + trace_at[0]:
                self.run.trace_start()
                # the fallback name of an idle gap: nothing of the
                # harness's own was running, requests were in flight
                tracing = self.run.span("requests_in_flight")
                tracing.__enter__()
            if tracing and now >= t0 + trace_at[1]:
                tracing.__exit__(None, None, None)
                self.run.trace_stop()
                tracing, trace_at = False, None
            self.poll(now)
            if on_second and now >= next_second:
                on_second(now - t0, self)
                next_second += 1.0
            ended = not gen.is_alive() and self.sent_q.empty() \
                and not self.in_flight()
            if now >= t1 and (ended or now >= t1 + drain_cap_s):
                break
            time.sleep(self.poll_s)
        stop_threads(gen)
        if tracing:
            tracing.__exit__(None, None, None)
            self.run.trace_stop()
        return reqs, t0, t1


def token_regret(run, j, served, q=None):
    """Score emitted tokens with the reference; see the module docstring.
    `served` is [(prompt tokens, emitted tokens)] of one shape group.
    Returns (sum of regrets, positions, positions that agree)."""
    import jax
    import jax.numpy as jnp
    cfg, ref = j["cfg"], j["ref"]
    k = max(len(e) for _, e in served)
    width = traffic_lib.prefill_buckets(
        1, max(len(p) + len(e) for p, e in served), cfg["n_positions"])[-1]
    ids = np.zeros((len(served), width), np.int32)
    pos = np.zeros((len(served), k), np.int32)
    emitted = np.zeros((len(served), k), np.int32)
    valid = np.zeros((len(served), k), bool)
    for i, (p, e) in enumerate(served):
        seq = list(p) + list(e)
        ids[i, :len(seq)] = seq
        pos[i, :len(e)] = np.arange(len(p) - 1, len(p) - 1 + len(e))
        emitted[i, :len(e)] = e
        valid[i, :len(e)] = True

    @jax.jit
    def score(w, ids, pos, emitted, valid):
        lg = ref.forced_logits(w, ids, pos, cfg, q)
        best = jnp.max(lg, -1)
        took = jnp.take_along_axis(lg, emitted[..., None], -1)[..., 0]
        regret = (best - took) / jnp.std(lg, -1)
        return (jnp.sum(jnp.where(valid, regret, 0.0)), jnp.sum(valid),
                jnp.sum(valid & (took >= best)))

    out = score(j["w"], ids, pos, emitted, valid)
    return float(out[0]), int(out[1]), int(out[2])


def make_job(run, quantized=False):
    """Seeded weights and the engine; `quantized` is the control (the
    program's own int8 weight path, tools/limits.py)."""
    import jax
    cfg, job = run.config, run.traffic
    fam, ref = run.program(), run.reference()

    @jax.jit
    def seeded(key):
        w = ref.init_weights(cfg, jax.random.fold_in(key, 0))
        return w, fam.to_program(w, cfg)[0]

    w, params = seeded(key_from_seed(run.seed))
    engine = fam.build_engine(cfg, job, params, quantized=quantized)
    return {"cfg": cfg, "job": job, "fam": fam, "ref": ref, "w": w,
            "engine": engine}


def _submit_patiently(engine, tokens, max_new, tries=2000):
    """Set-up's flood goes through the front door like any client: when
    admission sheds (its waiting queue is bounded), wait and send again."""
    from edl_tpu.utils import errors
    for _ in range(tries):
        try:
            return engine.submit(tokens, max_new)
        except errors.OverloadedError as e:
            time.sleep(min(0.05, e.retry_after_s or 0.01))
    raise BenchError("warm-up: the engine shed one request %d times" % tries)


def warm_and_check(run, j):
    """Warm every executable through the front door, and score what came
    back. Returns {"token_regret": ..., "token_agree": ...}."""
    cfg, job, engine = j["cfg"], j["job"], j["engine"]
    plan = traffic_lib.warm_plan(job, cfg["vocab_size"],
                                 cfg["n_positions"], run.seed)
    groups = []
    for name in ("cold", "shared"):
        with run.span("setup:warm_" + name):
            served = []
            for toks, new in plan[name]:   # one at a time: rows retire
                rep = engine.generate(toks, new, timeout=600.0)
                served.append((toks, rep["generated"]))
            groups.append(served)
    with run.span("setup:warm_flood"):
        handles = [_submit_patiently(engine, t, n) for t, n in plan["flood"]]
        groups.append([(t, h.result(600.0)["generated"])
                       for (t, _), h in zip(plan["flood"], handles)])
    st = engine.stats()
    want = len(plan["shared"])
    if st["decode_prefix"]["hits"] < want:
        raise BenchError("warm-up: %d prefix hits, expected %d"
                         % (st["decode_prefix"]["hits"], want))
    with run.span("setup:reference"):
        parts = [token_regret(run, j, groups[0] + groups[1]),
                 token_regret(run, j, groups[2])]
    total, n, agree = [sum(p[i] for p in parts) for i in range(3)]
    log("warm-up: %d requests, %d positions, %d agree with the "
        "reference's best token; engine traces: step %d prefill %d "
        "chunk %d" % (sum(len(g) for g in groups), n, agree,
                      st["decode_step_traces"], st["decode_prefill_traces"],
                      st["decode_chunk_traces"]))
    return {"token_regret": total / n, "token_agree": agree / float(n)}


#: tools/limits.py: the program's own int8 weight path serves as control
CONTROLS = {"int8": {"quantized": True}}


def compared_numbers(run, names):
    """tools/limits.py: {name: the numbers `correct` compares} for one
    seed; the name None is the program, any other one of CONTROLS."""
    out = {}
    for name in names:
        j = make_job(run, **(CONTROLS[name] if name else {}))
        j["engine"].start()
        try:
            out[name] = warm_and_check(run, j)
        finally:
            j["engine"].stop()
    return out


def second_recorder(run, engine):
    """Per second: what would explain a run that tips."""
    last = {"steps": 0, "compiles": run.compiles.total,
            "gc": run.gc_counts()[0], "gen": 0}

    def on_second(t, client):
        st = engine.stats()
        steps = client.step_times
        recent = steps[last["steps"]:]
        gaps = np.diff(recent) if len(recent) > 1 else [0.0]
        late = client.late_ms[last["gen"]:]
        run.record(
            t=round(t, 3), slots_occupied=st["decode_slots_occupied"],
            waiting=st["decode_waiting"],
            cached_rows=st["decode_prefix"].get("cached_rows"),
            prefix_hits=st["decode_prefix"].get("hits"),
            sheds=st["decode_admission"]["shed"],
            evicted=st["decode_evicted_total"],
            steps=len(recent), max_step_gap_ms=1e3 * float(np.max(gaps)),
            compile_events=run.compiles.total - last["compiles"],
            gen_late_max_ms=max(late) if late else 0.0,
            gc_collections=run.gc_counts()[0] - last["gc"],
            in_flight=client.in_flight())
        last.update(steps=len(steps), compiles=run.compiles.total,
                    gc=run.gc_counts()[0], gen=last["gen"] + len(late))

    return on_second


def summarize(reqs, t0, t1, client):
    """Classes and latency samples of one served window."""
    ttft, itl, late, failed, unfinished = [], [], [], 0, 0
    for r in reqs:
        if r.sent is not None:
            late.append(1e3 * (r.sent - (t0 + r.due)))
        if r.shed is not None or r.error is not None or r.first is None:
            failed += 1
            continue
        if not r.done:
            unfinished += 1
        ttft.append(1e3 * (r.first - (t0 + r.due)))
        prev = r.first
        for s in r.stamps:
            if s <= t1:
                itl.append(1e3 * (s - prev))
            prev = s
    return {"attempted": len(reqs), "failed": failed,
            "unfinished_at_cap": unfinished, "sheds": dict(client.sheds),
            "ttft_ms": ttft, "itl_ms": itl, "gen_late_ms": late}


def run(run):
    run.claim_devices()
    with run.span("setup:seed"):
        j = make_job(run)
    cfg, job, engine = j["cfg"], j["job"], j["engine"]
    engine.start()
    try:
        got = warm_and_check(run, j)
        run.check("token_regret", got["token_regret"],
                  job["limits"]["token_regret"])
        schedule = traffic_lib.chat_schedule(job, cfg["vocab_size"],
                                             run.seed, run.seconds)
        client = Client(run, engine, job["poll_ms"] / 1e3)
        rec = second_recorder(run, engine)
        shed_before = dict(engine.stats()["decode_admission"]["shed"])
        run.window_open()
        reqs, t0, t1 = client.serve(
            schedule, run.seconds,
            trace_at=job["trace_at_s"] if run.traced else None,
            drain_cap_s=job["drain_cap_s"], on_second=rec)
        run.window_close()
        st = engine.stats()
    finally:
        engine.stop()
    s = summarize(reqs, t0, t1, client)
    prefill_ms_tok = st["decode_admission"]["prefill_ms_per_token"] or 0.0
    queue_wait = [max(0.0, 1e3 * (r.first - (t0 + r.due))
                      - len(r.tokens) * prefill_ms_tok)
                  for r in reqs if r.first is not None]
    tw = [sp for sp in run.spans if sp[0] == "trace_window"]
    if tw:
        a, b = tw[0][1], tw[0][2]
        live = [n for t, n in client.live_positions if a <= t <= b]
        run.counters["traced_live_positions"] = (
            float(np.mean(live)) if live else 0.0)
    run.counters.update(
        queue_wait_ms=queue_wait, gen_late_ms=s["gen_late_ms"],
        steps_observed=len(client.step_times))
    run.record(attempted=s["attempted"], failed=s["failed"],
               unfinished_at_cap=s["unfinished_at_cap"], sheds=s["sheds"],
               engine_sheds=st["decode_admission"]["shed"],
               engine_sheds_before_window=shed_before,
               prefix=st["decode_prefix"], evicted=st["decode_evicted_total"],
               traces={k: st[k] for k in ("decode_step_traces",
                                          "decode_prefill_traces",
                                          "decode_chunk_traces")},
               ttft_samples=len(s["ttft_ms"]), itl_samples=len(s["itl_ms"]),
               ttft_p50_ms=percentile(s["ttft_ms"], 0.5),
               itl_p50_ms=percentile(s["itl_ms"], 0.5))
    end_to_end = {"ttft_p95_ms": percentile(s["ttft_ms"], 0.95),
                  "itl_p95_ms": percentile(s["itl_ms"], 0.95)}
    return run.finish(s["attempted"], s["failed"], end_to_end)
