"""Kernel-level attention benchmark: Pallas flash vs XLA dense, across
sequence lengths.

One JSON line per (seq_len, impl) with ms/call and achieved TFLOP/s,
each naming the device it ran on. A kernel time comes only from a chip:
off-TPU (or on a chip with no published peaks) the tool exits non-zero
and prints nothing that could be read as a device number, and a config
that fails is an error line AND a non-zero exit.

    python -m edl_tpu.tools.bench_flash --seqs 1024,2048,8192,32768

``--cells`` times the flash forward alone at the shapes the benchmark's
language-model cells run it (``CELL_SHAPES``), in bfloat16, at the tile the
kernel chooses from the shape and at each explicit tile of ``--tiles``: the
numbers a tile is chosen from before a cell is run.

    python -m edl_tpu.tools.bench_flash --cells --inner 8
"""

import argparse
import json
import sys
import time


#: name, batch, kv heads, query heads a kv head, sequence, head width,
#: window: gpt2s-train's 12 x 12 heads at 1024; smallthinker-moe-train-8k's
#: full and windowed layers (7 query heads on 1 kv head, k + v 4 MiB)
CELL_SHAPES = [
    ("gpt2s-train", 12, 12, 1, 1024, 64, None),
    ("smallthinker-moe-train-8k.full", 2, 1, 7, 8192, 128, None),
    ("smallthinker-moe-train-8k.window", 2, 1, 7, 8192, 128, 4096),
]


def _ms_a_call(fn, args, iters, warmup):
    """Host clock around ``iters`` calls that end in block_until_ready,
    after ``warmup`` calls (the first compiles)."""
    import jax
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def band_pairs(seq, window=None):
    """(query, key) pairs of the causal band: a query reads its own
    position and, with a window, the ``window - 1`` before it."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def bench_cell_forward(name, batch, kv_heads, group, seq, dim, window, tile,
                       iters, warmup, inner, peak_tflops):
    """The flash forward alone, bfloat16, ``inner`` applications chained in
    one executable (each result is the next one's q); ``tile`` is (block_q,
    block_k), None leaving the tile to the kernel. TFLOP/s counts the
    band's pairs only."""
    import jax
    import jax.numpy as jnp

    from edl_tpu.ops.flash_attention import flash_attention

    q = jax.random.normal(jax.random.PRNGKey(0),
                          (batch, kv_heads, group * seq, dim), jnp.bfloat16)
    k, v = (jax.random.normal(jax.random.PRNGKey(i),
                              (batch, kv_heads, seq, dim), jnp.bfloat16)
            for i in (1, 2))

    @jax.jit
    def fn(q, k, v):
        def body(carry, _):
            return flash_attention(carry, k, v, causal=True, window=window,
                                   group=group, block_q=tile and tile[0],
                                   block_k=tile and tile[1]), None
        return jax.lax.scan(body, q, None, length=inner)[0]

    ms = _ms_a_call(fn, (q, k, v), iters, warmup) / inner
    flops = 4.0 * batch * kv_heads * group * band_pairs(seq, window) * dim
    tflops = flops / (ms / 1e3) / 1e12
    rec = {"metric": "flash_fwd_ms", "cell": name,
           "tile": tile and "%dx%d" % tile,
           "batch": batch, "kv_heads": kv_heads, "group": group, "seq": seq,
           "dim": dim, "window": window, "inner": inner,
           "value": round(ms, 4), "unit": "ms", "tflops": round(tflops, 2),
           "peak_pct": round(100 * tflops / peak_tflops, 2)}
    if tflops > peak_tflops * 1.25:
        rec["suspect_fast_path"] = True
    return rec


def bench_one(impl, batch, heads, seq, dim, causal, iters, warmup,
              peak_tflops, grad=False, inner=1):
    import jax
    import jax.numpy as jnp

    from edl_tpu.ops.flash_attention import flash_attention

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i),
                                 (batch, heads, seq, dim), jnp.bfloat16)
               for i in range(3))

    if impl == "flash":
        def fwd(q, k, v):
            return flash_attention(q, k, v, causal=causal)
    else:
        def fwd(q, k, v):
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                                preferred_element_type=jnp.float32)
            scores = scores / (dim ** 0.5)
            if causal:
                s = scores.shape[-1]
                mask = jnp.tril(jnp.ones((s, s), bool))
                scores = jnp.where(mask, scores, -1e30)
            return jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(scores, axis=-1
                                             ).astype(q.dtype), v)
    if inner > 1:
        # Chain `inner` applications inside ONE executable (output of
        # step i feeds step i+1's query, so nothing can be elided).
        # Lifts per-call wall time above the host dispatch floor so
        # short kernels are timed, not the launch.
        base_fwd = fwd

        def fwd(q, k, v):
            def body(carry, _):
                return base_fwd(carry, k, v).astype(carry.dtype), None
            out, _ = jax.lax.scan(body, q, None, length=inner)
            return out

    if grad:
        # the TRAINING path: fwd + the attention backward (for flash,
        # the custom vjp's two Pallas kernels, flash_bwd_dq and
        # flash_bwd_dkv)
        fn = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            fwd(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2)))
    else:
        fn = jax.jit(fwd)

    ms = _ms_a_call(fn, (q, k, v), iters, warmup)
    # 4*b*h*s^2*d multiply-adds fwd (qk + av), causal halves it. The
    # backward: dense keeps the probs as residuals (no recompute) —
    # ~2x fwd of grad matmuls, 3x total; flash recomputes per block —
    # ~2.5x fwd, 3.5x total (the model's count: the two backward kernels
    # each rebuild the scores, seven products in all where five are
    # required).
    ms /= inner  # per-application, comparable across --inner settings
    flops = 4.0 * batch * heads * seq * seq * dim * (0.5 if causal
                                                     else 1.0)
    if grad:
        flops *= 3.5 if impl == "flash" else 3.0
    tflops = flops / (ms / 1e3) / 1e12
    rec = {"metric": ("attention_fwdbwd_ms" if grad
                      else "attention_fwd_ms"),
           "impl": impl, "seq": seq,
           "batch": batch, "heads": heads, "dim": dim,
           "causal": causal, "value": round(ms, 2), "unit": "ms",
           "tflops": round(tflops, 1)}
    if inner > 1:
        rec["inner"] = inner
    # Physics gate: a wall time at the dispatch floor measures the
    # launch, not the kernel, and implies a HARDWARE rate above the
    # running chip's published peak — mark the sample as untrustworthy
    # rather than letting it stand as a record.
    # The model flops above discount causal by 0.5, but dense executes
    # the full s^2 matmuls and masks after — undo the discount for the
    # physical-rate check.
    hw_tflops = tflops * (2.0 if (causal and impl == "dense") else 1.0)
    if hw_tflops > peak_tflops * 1.25:
        rec["suspect_fast_path"] = True
    return rec


def main(argv=None):
    p = argparse.ArgumentParser("flash vs dense attention bench")
    p.add_argument("--seqs", default="1024,2048,8192,32768")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--causal", action=argparse.BooleanOptionalAction,
                   default=True)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--grad", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="also time fwd+bwd (the training path)")
    def positive_int(s):
        v = int(s)
        if v < 1:
            raise argparse.ArgumentTypeError("must be >= 1")
        return v

    p.add_argument("--inner", type=positive_int, default=1,
                   help="chain N attention applications inside one "
                   "jit call (lax.scan) — lifts short kernels above "
                   "the host dispatch floor")
    p.add_argument("--cells", action="store_true",
                   help="time the flash forward at CELL_SHAPES instead")
    p.add_argument("--tiles", default="128,256,512",
                   help="with --cells: explicit tiles (512, or 512x256 for "
                   "block_q x block_k) to time beside the kernel's own "
                   "choice")
    args = p.parse_args(argv)
    from edl_tpu.parallel import costmodel
    device = costmodel.device_identity()
    if device["platform"] != "tpu":
        print("bench_flash: needs a TPU (found platform %r); the Pallas "
              "interpreter and the CPU backend give no device number"
              % device["platform"], file=sys.stderr)
        return 1
    # raises on a chip with no published peaks: the physics gate must
    # not judge a measurement against some other chip's roofline
    peak_tflops = costmodel.chip_peaks(device["device_kind"])["bf16_tflops"]
    rc = 0
    if args.cells:
        for shape in CELL_SHAPES:
            for tile in [None] + [
                    tuple(int(e) for e in (t.split("x") * 2)[:2])
                    for t in args.tiles.split(",") if t]:
                try:
                    out = bench_cell_forward(
                        *shape, tile, args.iters, args.warmup, args.inner,
                        peak_tflops)
                except Exception as e:  # noqa: BLE001 — a tile VMEM refuses
                    out = {"cell": shape[0], "tile": tile,
                           "error": repr(e)[:300]}
                    rc = 1
                print(json.dumps(dict(out, **device)), flush=True)
        return rc
    for seq in [int(s) for s in args.seqs.split(",") if s]:
        for impl in ("dense", "flash"):
            passes = (False, True) if args.grad else (False,)
            for grad in passes:
                try:
                    out = bench_one(impl, args.batch, args.heads, seq,
                                    args.dim, args.causal, args.iters,
                                    args.warmup, peak_tflops, grad=grad,
                                    inner=args.inner)
                except Exception as e:  # noqa: BLE001 — dense OOMs at 32k
                    out = {"impl": impl, "seq": seq, "grad": grad,
                           "error": repr(e)[:300]}
                    rc = 1
                print(json.dumps(dict(out, **device)), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
