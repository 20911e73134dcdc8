"""The one general generator of serving traffic. A traffic file gives the
parameters (rate, length distributions, clips); `--seed` gives the ORDER
and the token values. Every seed draws the same multiset of lengths and
of gaps between arrivals — the quantiles of the file's distributions — in
another order, so the work of a window does not depend on the seed, and
`attempted` is fixed by the file, the rate and `--seconds` alone.
"""

import math
import statistics

import numpy as np


def _lognormal_quantiles(n, median, sigma, lo, hi):
    nd = statistics.NormalDist()
    out = [median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))
           for i in range(n)]
    return np.clip(np.rint(out), lo, hi).astype(np.int64)


def chat_schedule(job, vocab, seed, seconds, rate=None):
    """[(due_s, prompt tokens (np.int32), max_new)] sorted by due time.
    Open loop: `due_s` is when the request is sent whatever the server
    is doing. Arrivals are Poisson: exponential gaps, taken at their
    quantiles and shuffled."""
    rate = float(rate if rate is not None else job["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(int(seed))
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    rng.shuffle(gaps)
    due = np.cumsum(gaps) / gaps.sum() * seconds * n / (n + 1.0)
    p, o = job["prompt"], job["output"]
    plen = _lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                p["max"])
    olen = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                o["max"])
    rng.shuffle(plen)
    rng.shuffle(olen)
    olen = np.minimum(olen, job["max_total"] - plen)
    return [(float(due[i]),
             rng.integers(0, vocab, size=int(plen[i]), dtype=np.int32),
             int(olen[i])) for i in range(n)]


def prefill_buckets(lo, hi, max_len):
    """The power-of-two prompt buckets a length RANGE can reach (the
    engine pads a prompt to the next power of two, capped at max_len)."""
    out, b = [], 1
    while b < lo:
        b <<= 1
    while True:
        out.append(min(b, max_len))
        if b >= hi:
            return out
        b <<= 1


def warm_plan(job, vocab, max_len, seed):
    """Requests that set-up sends through the front door so that every
    executable the RANGE of the traffic can reach is built before the
    window — never the lengths this seed happens to draw:

    cold:   one prompt per prefill bucket of the range;
    shared: per bucket, a prompt that shares its first token with the
            retired cold one, so the row copy and every suffix width run;
    flood:  `slots` short prompts as fast as admission takes them: every
            row ends up live or cached, so later arrivals reclaim cached
            rows by eviction, as the window's will.

    Returns {"cold": [...], "shared": [...], "flood": [...]} of
    (tokens, max_new)."""
    rng = np.random.default_rng(int(seed) + 1)
    lo, hi = job["prompt"]["min"], job["prompt"]["max"]
    new = job["warm_new_tokens"]
    cold, shared = [], []
    for b in prefill_buckets(lo, hi, max_len):
        n = min(b, hi)
        a = rng.integers(0, vocab, size=n, dtype=np.int32)
        s = rng.integers(0, vocab, size=n, dtype=np.int32)
        s[0] = a[0]
        if s[1] == a[1]:
            s[1] = (s[1] + 1) % vocab
        cold.append((a, new))
        shared.append((s, new))
    flood = [(rng.integers(0, vocab, size=lo, dtype=np.int32),
              job["flood_new_tokens"]) for _ in range(job["slots"])]
    return {"cold": cold, "shared": shared, "flood": flood}
