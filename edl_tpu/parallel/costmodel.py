"""Roofline cost model for (dp, tp, pp, ep) mesh factorizations.

Generalizes tools/roofline_resnet.py (a fixed-model HBM/FLOP budget)
into the elastic-resize planning question: *given a new world size,
which legal mesh factorization minimizes step time — counting what it
costs to GET there?* Three parts:

- a per-layer roofline (:func:`step_time_s`): compute and HBM floors
  plus per-axis collective volume — dp gradient all-reduce, tp
  activation all-reduce per layer, pp bubble + boundary activations,
  ep token all-to-all;
- an analytic reshard-cost model (:func:`tree_reshard_bytes`): for each
  target-device block under the new sharding, the bytes NOT already
  resident on that same device under the old sharding must move. This
  is exactly the span-overlap math PlacedTarget runs at restore time
  (checkpoint.py), evaluated on shapes alone — no devices needed, so
  the cluster generator can score hypothetical worlds;
- a scorer (:func:`best_factorization` / :func:`make_planner`) that
  ranks legal factorizations by step time + amortized reshard seconds,
  so the generator can prefer a marginally-slower mesh that reshards
  10x cheaper.

Everything here is pure numpy over plain tuples/dicts — PartitionSpecs
are accepted anywhere a spec is (they iterate as tuples), but jax is
never imported by the planner (only :func:`device_identity` touches it,
lazily), so the controller can plan meshes on machines with no
accelerator runtime.

Mesh convention: axes are an ordered {name: size} dict; devices are
numbered 0..N-1 in row-major order over that axis order — the same
enumeration runtime.mesh.make_mesh uses over jax.devices()[:N], which
is what makes the per-device overlap math agree with the real reshard.
"""

import json
import os

import numpy as np

# THE table of published per-chip peaks, keyed by the ``device_kind``
# JAX reports (libtpu 0.0.34 calls a v5e "TPU v5 lite"). Every other
# module reads its peaks from here; a device that is not in the table
# is an error (:func:`chip_peaks`), never a default.
#   v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
#   819 GB/s of HBM bandwidth, 16 GB of HBM, 1,600 Gbit/s (= 200 GB/s)
#   of chip-to-chip interconnect. Ring collectives keep all links busy,
#   so the aggregate ICI figure is the collective bandwidth term.
CHIP_PEAKS = {
    "TPU v5 lite": {
        "name": "v5e",
        "bf16_tflops": 197.0,
        "hbm_gbps": 819.0,
        "hbm_gb": 16.0,
        "ici_gbps": 200.0,
    },
}

# the planner's named target chip: it scores hypothetical worlds on
# machines with no accelerator, so it plans for a chip by name rather
# than for whatever happens to be attached
CHIP_V5E = CHIP_PEAKS["TPU v5 lite"]


def chip_peaks(device_kind):
    """Published peaks of the chip JAX calls ``device_kind``. Raises
    KeyError for a kind the table does not hold: code that turns a
    MEASURED time into a utilization or a "suspect" verdict must not
    assume a chip it is not running on."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "no published peaks for device_kind %r (known: %s); add it "
            "to costmodel.CHIP_PEAKS with its source"
            % (device_kind, ", ".join(sorted(CHIP_PEAKS)))) from None


def device_identity():
    """{"platform", "device_kind", "device_count"} of the devices this
    process runs on, as JAX reports them — stamped on every result line
    so a number can never be read without the device it came from.
    Imports jax (and so initialises the backend) on first use."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}

# -- measured calibration (tools/roofline_gap.py) --------------------------
#
# The roofline_gap bench fits ACHIEVED constants (sustained tflops, HBM
# and collective GB/s as the trainer actually sees them) and writes a
# "roofline_calib/v1" record; pointing CALIB_ENV at it makes every
# default-chip scorer plan against measured silicon instead of
# datasheet numbers. Fail-open per FIELD: a missing/corrupt file, wrong
# schema, or a fitted value outside sanity bounds keeps the builtin for
# that field — calibration can tune the planner, never brick it.

CALIB_ENV = "EDL_TPU_ROOFLINE_CALIB"
CALIB_SCHEMA = "roofline_calib/v1"
# a fitted constant this far off the builtin is a measurement artifact
# (e.g. an interpret-mode CPU run), not a chip
_CALIB_MIN_RATIO = 0.005
_CALIB_MAX_RATIO = 20.0
_calib_cache = {}


def load_calibration(path=None):
    """Parse a roofline_calib/v1 record from ``path`` (default: the
    ``CALIB_ENV`` env var). Returns the record dict, or None when unset,
    unreadable, or not the expected schema — never raises. Cached by
    (path, mtime) so the scorer's inner loop doesn't re-read the file."""
    path = path or os.environ.get(CALIB_ENV)
    if not path:
        return None
    try:
        key = (path, os.path.getmtime(path))
    except OSError:
        return None
    if key in _calib_cache:
        return _calib_cache[key]
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("schema") != CALIB_SCHEMA \
                or not isinstance(doc.get("chip"), dict):
            doc = None
    except Exception:  # noqa: BLE001 — fail-open is the contract
        doc = None
    _calib_cache.clear()
    _calib_cache[key] = doc
    return doc


def calibrated_chip(path=None):
    """CHIP_V5E with any sane fitted constants from the calibration
    record layered on top. With no record (or a bad one) this IS a copy
    of CHIP_V5E, so default-chip callers see identical scores until a
    calibration is installed."""
    chip = dict(CHIP_V5E)
    doc = load_calibration(path)
    if not doc:
        return chip
    fitted = doc["chip"]
    changed = False
    for field in ("bf16_tflops", "hbm_gbps", "ici_gbps"):
        try:
            val = float(fitted[field])
        except (KeyError, TypeError, ValueError):
            continue
        builtin = CHIP_V5E[field]
        # NaN fails both comparisons and is dropped with the rest
        if not (builtin * _CALIB_MIN_RATIO <= val
                <= builtin * _CALIB_MAX_RATIO):
            continue
        chip[field] = val
        changed = True
    if changed:
        chip["name"] = str(fitted.get("name",
                                      CHIP_V5E["name"] + "+calib"))
    return chip

# microbatches per pipeline round-trip when estimating the 1F1B bubble
PIPELINE_MICROBATCHES = 8

# default exchange rate between reshard bytes and score seconds: a
# resize pays its pause once, a step time is paid every step, so the
# reshard term is the wire time of the moved bytes amortized over this
# many steps
RESHARD_AMORTIZE_STEPS = 100.0


def transformer_profile(n_layers, d_model, n_heads, seq_len,
                        vocab_size=32000, n_experts=0, dtype_bytes=2,
                        name="transformer"):
    """Per-layer profile of a dense (or MoE) transformer: FLOPs and
    parameter/activation bytes per token for each layer, plus the
    head/layer/expert counts that bound tp/pp/ep legality."""
    d = int(d_model)
    ffn = 4 * d
    attn_flops = 2 * (4 * d * d) + 2 * 2 * seq_len * d  # qkvo + scores
    mlp_flops = 2 * (2 * d * ffn)
    layers = []
    for i in range(int(n_layers)):
        layers.append({
            "name": "layer_%d" % i,
            "flops_per_token": float(attn_flops + mlp_flops),
            "param_bytes": float((4 * d * d + 2 * d * ffn)
                                 * dtype_bytes),
            # activations crossing the tp collectives (attn out + mlp
            # out), per token
            "act_bytes_per_token": float(2 * d * dtype_bytes),
        })
    embed_bytes = float(vocab_size * d * dtype_bytes)
    return {
        "name": name,
        "layers": layers,
        "n_layers": int(n_layers),
        "n_heads": int(n_heads),
        "n_experts": int(n_experts),
        "seq_len": int(seq_len),
        "d_model": d,
        "dtype_bytes": int(dtype_bytes),
        "embed_param_bytes": embed_bytes,
        "param_bytes": embed_bytes + sum(l["param_bytes"]
                                         for l in layers),
        "flops_per_token": sum(l["flops_per_token"] for l in layers),
    }


def candidate_factorizations(world, max_tp=None, max_pp=None,
                             max_ep=None):
    """All (dp, tp, pp, ep) with dp*tp*pp*ep == world, as dicts."""
    world = int(world)
    out = []
    for tp in _divisors(world, max_tp):
        for pp in _divisors(world // tp, max_pp):
            for ep in _divisors(world // (tp * pp), max_ep):
                out.append({"dp": world // (tp * pp * ep), "tp": tp,
                            "pp": pp, "ep": ep})
    return out


def _divisors(n, cap=None):
    return [d for d in range(1, n + 1)
            if n % d == 0 and (cap is None or d <= cap)]


def legality_reason(factors, profile, total_batch):
    """Why ``factors`` is not a legal mesh for ``profile`` at
    ``total_batch`` — None when it is."""
    dp, tp = factors["dp"], factors["tp"]
    pp, ep = factors["pp"], factors["ep"]
    if total_batch % dp != 0:
        return "batch %d not divisible by dp=%d" % (total_batch, dp)
    if tp > 1 and profile["n_heads"] % tp != 0:
        return "tp=%d does not divide %d heads" % (tp,
                                                   profile["n_heads"])
    if pp > 1 and (pp > profile["n_layers"]
                   or profile["n_layers"] % pp != 0):
        return "pp=%d does not split %d layers evenly" % (
            pp, profile["n_layers"])
    if ep > 1 and (not profile["n_experts"]
                   or profile["n_experts"] % ep != 0):
        return "ep=%d does not divide %d experts" % (
            ep, profile["n_experts"])
    return None


def step_time_s(factors, profile, total_batch, chip=None):
    """Roofline step-time estimate: max(compute, HBM) floor with the
    pipeline bubble applied, plus the per-axis collective terms.
    Returns a breakdown dict; ``total_s`` is the score input.

    ``chip=None`` uses :func:`calibrated_chip` — the builtin CHIP_V5E
    constants unless a roofline_gap calibration record is installed via
    the ``EDL_TPU_ROOFLINE_CALIB`` env var."""
    chip = chip or calibrated_chip()
    dp, tp = factors["dp"], factors["tp"]
    pp, ep = factors["pp"], factors["ep"]
    world = dp * tp * pp * ep
    tokens = float(total_batch) * profile["seq_len"]
    ici = chip["ici_gbps"] * 1e9

    # fwd + bwd ~ 3x fwd FLOPs, spread over every chip
    flops = 3.0 * profile["flops_per_token"] * tokens
    compute_s = flops / (world * chip["bf16_tflops"] * 1e12)
    # params are read fwd+bwd and written once per step; each chip
    # holds 1/(tp*pp*ep) of them
    hbm_s = 3.0 * profile["param_bytes"] / (tp * pp * ep) \
        / (chip["hbm_gbps"] * 1e9)
    # 1F1B bubble: (pp-1) of PIPELINE_MICROBATCHES slots idle
    bubble = 1.0 + (pp - 1) / float(PIPELINE_MICROBATCHES)
    floor_s = max(compute_s, hbm_s) * bubble

    # dp: ring all-reduce of this replica's gradient shard
    grad_bytes = profile["param_bytes"] / (tp * pp * ep)
    dp_s = 2.0 * grad_bytes * (dp - 1) / dp / ici if dp > 1 else 0.0
    # tp: 2 activation all-reduces per layer fwd, 2 bwd, over the
    # tokens this (dp, pp) slice owns
    tp_s = 0.0
    if tp > 1:
        act = sum(l["act_bytes_per_token"] for l in profile["layers"])
        tp_s = 4.0 * act * (tokens / dp) * (tp - 1) / tp / ici
    # pp: boundary activations cross (pp-1) stage edges, fwd + bwd
    pp_s = 0.0
    if pp > 1:
        edge = profile["d_model"] * profile["dtype_bytes"] \
            * (tokens / dp)
        pp_s = 2.0 * (pp - 1) * edge / ici
    # ep: token all-to-all into and out of the experts, fwd + bwd
    ep_s = 0.0
    if ep > 1:
        tok_bytes = profile["d_model"] * profile["dtype_bytes"] \
            * (tokens / dp)
        ep_s = 4.0 * tok_bytes * (ep - 1) / ep / ici
    total = floor_s + dp_s + tp_s + pp_s + ep_s
    return {"total_s": total, "compute_s": compute_s, "hbm_s": hbm_s,
            "bubble": bubble, "dp_s": dp_s, "tp_s": tp_s, "pp_s": pp_s,
            "ep_s": ep_s}


# -- analytic span overlap (the reshard-cost half) -------------------------


def _spans_volume(spans):
    v = 1
    for lo, hi in spans:
        v *= max(0, hi - lo)
    return v


def _overlap_volume(a, b):
    v = 1
    for (alo, ahi), (blo, bhi) in zip(a, b):
        v *= max(0, min(ahi, bhi) - max(alo, blo))
    return v


def device_spans(shape, spec, axes):
    """{device_index: spans} for a leaf of ``shape`` sharded by
    ``spec`` on a mesh of ordered ``axes`` ({name: size}); device
    indices are row-major over the axis order (= make_mesh's
    enumeration of jax.devices()[:N]). Spans are ((lo, hi), ...) per
    dim, replicated dims spanning the whole extent."""
    shape = tuple(int(s) for s in shape)
    names = list(axes)
    sizes = [int(axes[a]) for a in names]
    ndev = int(np.prod(sizes)) if sizes else 1
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = {}
    for dev in range(ndev):
        coords = dict(zip(names, np.unravel_index(dev, sizes))) \
            if sizes else {}
        spans = []
        for d, entry in enumerate(entries):
            if entry is None:
                spans.append((0, shape[d]))
                continue
            sub = (entry,) if isinstance(entry, str) else tuple(entry)
            sub = [a for a in sub if int(axes.get(a, 1)) > 1]
            n, blk = 1, 0
            for a in sub:
                blk = blk * int(axes[a]) + int(coords[a])
                n *= int(axes[a])
            step = -(-shape[d] // n)
            lo = min(blk * step, shape[d])
            spans.append((lo, min(lo + step, shape[d])))
        out[dev] = tuple(spans)
    return out


def tree_reshard_bytes(leaves, src_axes, dst_axes):
    """Bytes that must move to reshard ``leaves`` from ``src_axes`` to
    ``dst_axes``. leaves: [(shape, itemsize, src_spec, dst_spec)].
    Per target device, the needed block minus what that same device
    already holds under the source sharding (the zero-wire device_put
    fast path) must arrive over the wire/FS. Returns (moved_bytes,
    needed_bytes); needed is the wholesale-restore volume the overlap
    fast path is saving against."""
    moved = needed = 0
    for shape, itemsize, src_spec, dst_spec in leaves:
        src = device_spans(shape, src_spec, src_axes)
        dst = device_spans(shape, dst_spec, dst_axes)
        for dev, dspans in dst.items():
            vol = _spans_volume(dspans)
            have = _overlap_volume(src[dev], dspans) \
                if dev in src else 0
            needed += vol * itemsize
            moved += (vol - have) * itemsize
    return int(moved), int(needed)


def mesh_axes(factors):
    """Ordered axes dict for a factorization, in make_mesh's canonical
    (pp, dp, ep, sp, tp) axis order."""
    return {"pp": factors.get("pp", 1), "dp": factors.get("dp", 1),
            "ep": factors.get("ep", 1), "sp": factors.get("sp", 1),
            "tp": factors.get("tp", 1)}


def _canonical_leaves(profile):
    """Synthetic per-layer leaves in the Megatron layout — tp-sharded
    kernels, dp-zero1 moments — for scoring a reshard between two
    factorizations without the real state tree."""
    d = profile["d_model"]
    ffn = 4 * d
    ib = profile["dtype_bytes"]
    leaves = []
    for _ in profile["layers"]:
        # attention qkv/out + mlp up/down kernels (tp-sharded)
        leaves.append(((d, 4 * d), ib, (None, "tp"), (None, "tp")))
        leaves.append(((4 * d, d), ib, ("tp", None), ("tp", None)))
        leaves.append(((d, ffn), ib, (None, "tp"), (None, "tp")))
        leaves.append(((ffn, d), ib, ("tp", None), ("tp", None)))
        # zero1 moments ride the dp axis on top of the param layout
        leaves.append(((d, 4 * d), ib, ("dp", "tp"), ("dp", "tp")))
        leaves.append(((d, ffn), ib, ("dp", "tp"), ("dp", "tp")))
    return leaves


def reshard_cost_bytes(profile, src_factors, dst_factors):
    """Analytic bytes moved by resharding ``profile``'s canonical state
    from ``src_factors`` to ``dst_factors`` (0 when src is None)."""
    if src_factors is None:
        return 0
    leaves = _canonical_leaves(profile)
    moved, _ = tree_reshard_bytes(leaves, mesh_axes(src_factors),
                                  mesh_axes(dst_factors))
    return moved


# -- the scorer ------------------------------------------------------------


def score_factorizations(world, profile, total_batch, current=None,
                         chip=None,
                         amortize_steps=RESHARD_AMORTIZE_STEPS,
                         max_tp=None, max_pp=None, max_ep=None):
    """Every legal factorization of ``world``, scored and sorted best
    first. score = step_time + reshard wire-seconds / amortize_steps,
    where the reshard term is the cost of moving from ``current`` (a
    factors dict, or None for a cold start)."""
    chip = chip or CHIP_V5E
    out = []
    for f in candidate_factorizations(world, max_tp=max_tp,
                                      max_pp=max_pp, max_ep=max_ep):
        why = legality_reason(f, profile, total_batch)
        if why is not None:
            continue
        t = step_time_s(f, profile, total_batch, chip=chip)
        moved = reshard_cost_bytes(profile, current, f)
        reshard_s = moved / (chip["ici_gbps"] * 1e9)
        score = t["total_s"] + reshard_s / float(amortize_steps)
        out.append(dict(f, score=score, step_time_s=t["total_s"],
                        reshard_bytes=moved, breakdown=t))
    # deterministic: ties go to the simplest mesh (least model
    # parallelism), then the larger dp
    out.sort(key=lambda r: (r["score"], r["tp"], r["pp"], r["ep"]))
    return out


def best_factorization(world, profile, total_batch, current=None,
                       chip=None,
                       amortize_steps=RESHARD_AMORTIZE_STEPS,
                       max_tp=None, max_pp=None, max_ep=None):
    """Top-scored legal factorization of ``world`` (None when nothing
    is legal, e.g. batch < every divisor)."""
    ranked = score_factorizations(
        world, profile, total_batch, current=current, chip=chip,
        amortize_steps=amortize_steps, max_tp=max_tp, max_pp=max_pp,
        max_ep=max_ep)
    return ranked[0] if ranked else None


def make_planner(profile, total_batch, chip=None,
                 amortize_steps=RESHARD_AMORTIZE_STEPS,
                 max_tp=None, max_pp=None, max_ep=None):
    """A ``mesh_planner(world, current=None) -> factors-or-None``
    callable for the cluster generator: remembers its previous answer
    so the reshard-cost term scores moves FROM the mesh the fleet is
    actually on."""
    state = {"current": None}

    def plan(world, current=None):
        cur = current if current is not None else state["current"]
        best = best_factorization(
            world, profile, total_batch, current=cur, chip=chip,
            amortize_steps=amortize_steps, max_tp=max_tp,
            max_pp=max_pp, max_ep=max_ep)
        if best is None:
            return None
        factors = {k: best[k] for k in ("dp", "tp", "pp", "ep")}
        state["current"] = factors
        return factors

    return plan
