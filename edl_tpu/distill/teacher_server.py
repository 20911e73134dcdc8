"""TPU teacher inference server — the in-tree replacement for the Paddle
Serving GPU servers the reference's distill plane called into
(SURVEY.md §2.6; client usage distill_worker.py:197-321).

Serves a jitted model function over the framed-RPC substrate:
- ``get_feed_fetch()`` — feed/fetch name+shape introspection (the contract
  the reference client discovered from serving conf files);
- ``predict(feed)`` — feed dict of ndarrays → fetch dict of ndarrays.
  Inputs are padded to a fixed batch size so XLA compiles once.
- ``stats()`` — device-batch occupancy counters for the bench/ops planes.

Adaptive batching (Clipper/ORCA style): handler threads no longer run
the model themselves behind one device lock — they enqueue (feed,
future) items and a single device thread coalesces queued requests from
ANY client into one compiled-batch program execution, copying rows into
a preallocated feed buffer (no per-request ``np.concatenate``) and
scattering row slices of the output back to each waiter. A half-full
student batch therefore shares its program execution with other
requests instead of burning a full-batch run alone; single-request
behavior, the read-only feed contract, and the wire protocol are
unchanged.

A teacher registers itself into the coordination store via
edl_tpu.distill.registry and is matched to students by the discovery/
balance layer.
"""

import argparse
import queue
import signal
import threading
import time

import numpy as np

from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.parallel.costmodel import device_identity
from edl_tpu.robustness import faults
from edl_tpu.robustness.policy import Deadline
from edl_tpu.rpc import ndarray as nd
from edl_tpu.rpc.server import FEATURES as _RPC_FEATURES
from edl_tpu.rpc.server import RpcServer
from edl_tpu.serve.admission import AdmissionController
from edl_tpu.utils import compile_cache, errors
from edl_tpu.utils.logger import logger

_DEVICE_BATCHES = obs_metrics.counter(
    "edl_teacher_batches_total", "teacher device-batch executions")
_DEVICE_ROWS = obs_metrics.counter(
    "edl_teacher_rows_total", "real (unpadded) rows served")
_BATCH_FILL = obs_metrics.histogram(
    "edl_teacher_batch_fill", "real rows per device execution as a "
    "fraction of max_batch",
    buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0))
_TEACHER_QUEUE = obs_metrics.gauge(
    "edl_teacher_queue_depth", "requests waiting for the device thread")


class _ItemFuture(object):
    """Rendezvous between a handler thread and the device thread."""

    __slots__ = ("_event", "_value", "_error")

    def __init__(self):
        self._event = threading.Event()
        self._value = None
        self._error = None

    def set(self, value=None, error=None):
        self._value, self._error = value, error
        self._event.set()

    def result(self, timeout):
        if not self._event.wait(timeout):
            raise errors.RpcError("device thread never served the batch")
        if self._error is not None:
            raise self._error
        return self._value


class _BatchItem(object):
    __slots__ = ("feed", "n", "future", "admitted_at", "deadline_ms")

    def __init__(self, feed, n, admitted_at=None, deadline_ms=None):
        self.feed = feed
        self.n = n
        self.future = _ItemFuture()
        self.admitted_at = admitted_at
        self.deadline_ms = deadline_ms


class TeacherServer(object):
    """Wrap ``predict_fn(feed: dict[str, np.ndarray]) -> dict`` behind RPC.

    Contract: ``predict_fn`` must treat the feed arrays as READ-ONLY
    (they may be zero-copy views into the decoded request, or — under
    adaptive batching — slices of a reused staging buffer that is only
    valid for the duration of the call); copy first to keep or mutate.

    ``feed_specs``/``fetch_specs``: {name: (shape_without_batch, dtype_str)}.
    ``max_batch``: server-side compiled batch size; requests are padded up
    and sliced back, so any client batch <= max_batch reuses one program.
    ``adaptive_batch``: coalesce concurrent requests into shared device
    batches on a single device thread (default). False restores the
    serial pad-and-lock path (the bench baseline / escape hatch).
    ``batch_timeout_ms``: how long the device thread may wait for more
    requests when a batch is still short of ``max_batch``. The default
    0 never delays — it coalesces whatever is already queued (pipelined
    clients keep the queue full), so a lone request pays no latency tax.
    """

    def __init__(self, predict_fn, feed_specs, fetch_specs, max_batch=128,
                 host="0.0.0.0", port=0, adaptive_batch=True,
                 batch_timeout_ms=0.0, admission=None,
                 decode_engine=None, device=None):
        self._fn = predict_fn
        # identity of the accelerator predict_fn runs on
        # (costmodel.device_identity()), reported by stats(); None for
        # host-only backends (the NOP teacher must not claim a chip)
        self._device = device
        # optional autoregressive plane (serve/decode_engine.py): adds
        # the lm_generate / lm_submit / lm_poll RPCs, folds engine
        # stats into stats(), and joins the drain protocol
        self._decode = decode_engine
        # admission control (serve/admission.py): None/True builds the
        # default controller (bounded queue only — no rate limit, no
        # projection shed until configured, so plain fleets behave as
        # before); False disables it; an AdmissionController instance
        # is used as-is (the serve-plane configuration surface)
        if admission is False:
            self._admission = None
        elif admission is None or admission is True:
            self._admission = AdmissionController()
        else:
            self._admission = admission
        self._feed_specs = {k: (list(s), d) for k, (s, d)
                            in feed_specs.items()}
        self._fetch_specs = {k: (list(s), d) for k, (s, d)
                             in fetch_specs.items()}
        self._max_batch = max_batch
        self._adaptive = bool(adaptive_batch)
        self._batch_timeout = max(0.0, float(batch_timeout_ms)) / 1000.0
        self._lock = threading.Lock()  # serializes device access (sync path)
        self._queue = queue.Queue()
        self._stop_ev = threading.Event()
        self._device_thread = None
        self._bufs = {}  # group key -> {name: staging array}
        self._stats_lock = threading.Lock()
        self._batches = 0   # device executions
        self._rows = 0      # real (unpadded) rows served
        self._rpc = RpcServer(host=host, port=port)
        self._rpc.register("get_feed_fetch", self.get_feed_fetch)
        self._rpc.register("predict", self._predict_rpc)
        self._rpc.register("stats", self.stats)
        self._rpc.register("set_knobs", self.apply_knobs)
        self._rpc.register("drain", self.drain)
        if self._decode is not None:
            self._rpc.register("lm_generate", self._lm_generate_rpc)
            self._rpc.register("lm_submit", self._lm_submit_rpc)
            self._rpc.register("lm_poll", self._lm_poll_rpc)

    def get_feed_fetch(self):
        features = list(_RPC_FEATURES)
        if self._adaptive:
            features.append("adaptive_batch")
        if self._admission is not None:
            features.append("serve.admission")
        out = {"feed": self._feed_specs, "fetch": self._fetch_specs,
               "max_batch": self._max_batch, "features": features,
               "batch_timeout_ms": self._batch_timeout * 1000.0}
        if self._decode is not None:
            features.append("decode.engine")
            out.update(self.decode_capacities())
        return out

    def decode_capacities(self):
        """Phase-disaggregated capacity weights for the balance table
        (distill/balance.py): ``capacity_prefill`` — how many one-shot
        forwards this server absorbs per scheduling quantum (the batch
        plane, same meaning as ``capacity``) — and ``capacity_decode`` —
        resident-sequence capacity, bounded by KV slots. Pass through
        ``TeacherRegister(info=...)`` so prefill-heavy and decode-heavy
        clients hash against the capacity that actually limits them.

        ``capacity_prefill`` is REUSE-ADJUSTED: a server whose prefix
        cache absorbs fraction f of prompt tokens does only (1-f) of
        the prefill work per nominal request, so it advertises
        1/(1-f) x the raw capacity (capped at 10x — a pathological
        reuse_frac must not zero out the denominator)."""
        if self._decode is None:
            return {}
        prefill = float(self._max_batch)
        try:
            pfx = self._decode.stats().get("decode_prefix") or {}
            if pfx.get("enabled"):
                reuse = min(0.9, max(0.0,
                                     float(pfx.get("reuse_frac") or 0.0)))
                prefill /= (1.0 - reuse)
        except Exception:  # noqa: BLE001 — capacity ad stays best-effort
            pass
        return {"capacity_prefill": prefill,
                "capacity_decode": float(self._decode.slots)}

    # -- the autoregressive plane (serve/decode_engine.py) -----------------

    def _lm_generate_rpc(self, prompt, max_new_tokens, deadline_ms=None):
        """Blocking generate: admit (or typed OverloadedError), decode
        to completion, return the report (tokens include the prompt).
        Ships on the pipelined plane — call_async keeps many sequences
        in flight per connection while each handler thread parks on its
        sequence future."""
        report = self._decode.generate(prompt, max_new_tokens,
                                       deadline_ms=deadline_ms,
                                       timeout=600.0)
        return report

    def _lm_submit_rpc(self, prompt, max_new_tokens, deadline_ms=None):
        h = self._decode.submit(prompt, max_new_tokens,
                                deadline_ms=deadline_ms)
        return {"seq": h.seq_id}

    def _lm_poll_rpc(self, seq, start=0):
        """Token streaming: tokens generated since ``start`` + done flag
        (raises the sequence's typed error once failed)."""
        tokens, done = self._decode.handle(seq).tokens_from(start)
        return {"tokens": tokens, "done": done}

    def apply_knobs(self, knobs):
        """Runtime tuning surface (``set_knobs`` RPC — the same contract
        as the reader's: apply known knobs, ignore unknown ones, return
        what was applied). ``batch_timeout_ms`` (clamped >= 0, <= 1000)
        retunes the device thread's coalescing wait on the fly; the
        thread reads it per batch, so the new value takes effect on the
        next coalescing round."""
        if not isinstance(knobs, dict):
            return {}
        applied = {}
        if "batch_timeout_ms" in knobs:
            try:
                ms = max(0.0, min(1000.0,
                                  float(knobs["batch_timeout_ms"])))
            except (TypeError, ValueError):
                ms = None
            if ms is not None:
                self._batch_timeout = ms / 1000.0
                applied["batch_timeout_ms"] = ms
        return applied

    def stats(self):
        """Batch-occupancy counters (``occupancy`` is the fraction of
        compiled-batch rows that carried real requests) plus — with
        admission control on — the serving-plane signals the
        ``ServeScaler`` folds: queue depth, pending rows, projected
        queue wait, shed counters, and the draining flag. Served as a
        plain (non-pipelined) RPC the substrate dispatches inline on
        the connection read thread, so this stays answerable while the
        device queue is saturated — observability survives overload."""
        with self._stats_lock:
            batches, rows = self._batches, self._rows
        cap = batches * self._max_batch
        out = {
            "batches": batches, "rows": rows,
            "max_batch": self._max_batch,
            "occupancy": (rows / cap) if cap else 0.0,
            "queue_depth": self._queue.qsize(),
        }
        if self._admission is not None:
            out.update(self._admission.stats())
        if self._decode is not None:
            out.update(self._decode.stats())
        out = obs_metrics.mirror_stats("edl_teacher", out)
        if self._device is not None:
            out.update(self._device)
        return out

    def drain(self, deadline_s=30.0):
        """Drain-safe shutdown, step 3 of the decommission protocol
        (serve/drain.py): flip admission to ``draining`` (new predicts
        get a typed OverloadedError the reader requeues elsewhere),
        then wait until the device queue and every admitted row have
        resolved. Returns a report; ``drained: False`` means in-flight
        work outlived ``deadline_s`` — the caller decides whether to
        stop anyway (the device loop's shutdown drain still resolves
        every queued future, so nothing is ever silently lost)."""
        if faults.PLANE is not None:
            faults.PLANE.fire("serve.drain", endpoint=self.endpoint,
                              pending=self._queue.qsize())
        if self._admission is not None:
            self._admission.set_draining(True)
        if self._decode is not None:
            # flip the decode front door too, then let BOTH planes
            # finish their in-flight work: resident sequences decode to
            # completion, waiting ones still get slots — zero stranded
            self._decode.admission.set_draining(True)
        deadline = Deadline(deadline_s if deadline_s else 30.0)
        served_before = self._rows
        while not self._drained():
            if not deadline.sleep(0.02):
                break
        with self._stats_lock:
            served = self._rows - served_before
        return {"drained": self._drained(),
                "endpoint": self.endpoint,
                "queue_depth": self._queue.qsize(),
                "pending_rows": (0 if self._admission is None
                                 else self._admission.stats()
                                 ["pending_rows"]),
                "served_during_drain": served}

    def _drained(self):
        if self._adaptive and self._queue.qsize() > 0:
            return False
        if self._decode is not None:
            st = self._decode.stats()
            if st["decode_waiting"] or st["decode_active"]:
                return False
        return self._admission is None or self._admission.idle()

    def _validate(self, feed):
        """Reject malformed feeds with a typed FeedSpecError naming the
        offending spec and shape. FeedSpecError subclasses
        DataAccessError, so the reader surfaces it to the consumer in
        order (poisoned task, never retried) — retrying a permanently
        bad feed against other teachers would ping-pong it forever."""
        missing = set(self._feed_specs) - set(feed)
        if missing:
            name = sorted(missing)[0]
            raise errors.FeedSpecError(
                "missing feeds: %s" % sorted(missing), spec=name,
                shape=tuple(self._feed_specs[name][0]))
        n, first = None, None
        for name, arr in feed.items():
            if n is None:
                n, first = len(arr), name
            elif len(arr) != n:
                raise errors.FeedSpecError(
                    "feed batch mismatch: %s has %d rows, %s has %d"
                    % (first, n, name, len(arr)), spec=name,
                    shape=tuple(np.asarray(arr).shape))
        if n == 0:
            raise errors.FeedSpecError("empty batch", spec=first,
                                       shape=(0,))
        if n > self._max_batch:
            raise errors.FeedSpecError(
                "batch %d exceeds max_batch %d" % (n, self._max_batch),
                spec=first, shape=tuple(np.asarray(feed[first]).shape))
        return n

    def _predict_rpc(self, feed_encoded, deadline_ms=None):
        # v2 tensor frames deliver feeds as owned arrays recv'd
        # straight off the socket (framing.py MAGIC_V2); decode_tree
        # is then a no-op but keeps pre-v2 senders (tagged-dict
        # payloads) working. Contract stays uniform: treat feeds as
        # immutable — copy first if an implementation must mutate.
        feed = nd.decode_tree(feed_encoded, copy=False)
        feed = {k: np.asarray(v) for k, v in feed.items()}
        n = self._validate(feed)
        # the admission decision (serve/admission.py): shed NOW with a
        # typed OverloadedError instead of queueing work the SLO has
        # already lost; ``deadline_ms`` is the caller's per-request
        # budget — the device loop sheds dead-on-arrival items
        admitted_at = None
        if self._admission is not None:
            admitted_at = self._admission.admit(n)
        if not self._adaptive:
            t0 = time.monotonic()
            try:
                return self._predict_serial(feed, n)
            finally:
                if self._admission is not None:
                    self._admission.release(
                        n, service_s=time.monotonic() - t0)
        item = _BatchItem(feed, n, admitted_at=admitted_at,
                          deadline_ms=deadline_ms)
        self._queue.put(item)
        _TEACHER_QUEUE.set(self._queue.qsize())
        # generous rendezvous bound: the device thread always resolves
        # every item it dequeues (success, error, or shutdown drain)
        return item.future.result(timeout=600.0)

    def _predict_serial(self, feed, n):
        """The pre-batching path: pad this request alone to max_batch
        behind the device lock. Kept as the bench baseline and the
        ``adaptive_batch=False`` escape hatch."""
        padded = {}
        for name, arr in feed.items():
            if n < self._max_batch:
                pad = np.zeros((self._max_batch - n,) + arr.shape[1:],
                               arr.dtype)
                arr = np.concatenate([arr, pad], axis=0)
            padded[name] = arr
        with self._lock:
            out = self._fn(padded)
            with self._stats_lock:
                self._batches += 1
                self._rows += n
        _DEVICE_BATCHES.inc()
        _DEVICE_ROWS.inc(n)
        _BATCH_FILL.observe(n / float(self._max_batch))
        # raw arrays: the v2 tensor frame ships them out-of-band with
        # no tobytes()/msgpack-bin copies (framing.py MAGIC_V2)
        return {k: np.asarray(v)[:n] for k, v in out.items()}

    # -- the device thread -------------------------------------------------

    @staticmethod
    def _group_key(feed):
        """Requests may only share a device batch when their feeds
        agree on everything but the row count."""
        return tuple(sorted((name, arr.shape[1:], arr.dtype.str)
                            for name, arr in feed.items()))

    def _buffers(self, key):
        bufs = self._bufs.get(key)
        if bufs is None:
            if len(self._bufs) >= 8:  # bound staging memory under churn
                self._bufs.pop(next(iter(self._bufs)))
            bufs = self._bufs[key] = {
                name: np.zeros((self._max_batch,) + tuple(trail),
                               np.dtype(dt))
                for name, trail, dt in key}
        return bufs

    def _dead_on_arrival(self, item):
        """Shed a queued item whose per-request deadline elapsed while
        it waited — running it would burn device time on a reply the
        caller has already abandoned."""
        if (self._admission is None or item.admitted_at is None
                or not self._admission.expired(item.admitted_at,
                                               item.deadline_ms)):
            return False
        item.future.set(error=self._admission.shed_expired(item.n))
        return True

    def _device_loop(self):
        carry = None
        while not self._stop_ev.is_set():
            if carry is not None:
                item, carry = carry, None
            else:
                try:
                    item = self._queue.get(timeout=0.2)
                except queue.Empty:
                    continue
            if self._dead_on_arrival(item):
                continue
            key = self._group_key(item.feed)
            group, rows = [item], item.n
            deadline = time.monotonic() + self._batch_timeout
            while rows < self._max_batch:
                # timeout 0 = drain only what is already queued; a
                # positive budget waits for stragglers but a full
                # batch always flushes immediately
                try:
                    nxt = self._queue.get(
                        timeout=max(0.0, deadline - time.monotonic()))
                except queue.Empty:
                    break
                if self._dead_on_arrival(nxt):
                    continue
                if (self._group_key(nxt.feed) != key
                        or rows + nxt.n > self._max_batch):
                    carry = nxt  # incompatible: heads the next batch
                    break
                group.append(nxt)
                rows += nxt.n
            self._run_group(key, group, rows)
        # shutdown drain: never leave a handler thread parked forever
        pending = [carry] if carry is not None else []
        while True:
            try:
                pending.append(self._queue.get_nowait())
            except queue.Empty:
                break
        for item in pending:
            item.future.set(error=errors.StopError("teacher stopping"))
            if self._admission is not None and item.admitted_at \
                    is not None:
                self._admission.release(item.n)

    def _run_group(self, key, group, rows):
        t0 = time.monotonic()
        try:
            if len(group) == 1 and rows == self._max_batch:
                feed = group[0].feed  # already full: run it in place
            else:
                bufs = self._buffers(key)
                lo = 0
                for item in group:
                    for name, arr in item.feed.items():
                        bufs[name][lo:lo + item.n] = arr
                    lo += item.n
                if rows < self._max_batch:
                    # zero the pad tail: stale rows from the previous
                    # batch must not leak into this execution (keeps
                    # outputs bit-identical with the serial zero-pad)
                    for name in bufs:
                        bufs[name][rows:] = 0
                feed = bufs
            out = self._fn(feed)
            outs = {}
            for k, v in out.items():
                v = np.asarray(v)
                if any(np.may_share_memory(v, b) for b in feed.values()):
                    # a passthrough fn returned (a view of) the staging
                    # buffer; the next batch would overwrite it while
                    # responses are still being serialized
                    v = v.copy()
                outs[k] = v
            with self._stats_lock:
                self._batches += 1
                self._rows += rows
            _DEVICE_BATCHES.inc()
            _DEVICE_ROWS.inc(rows)
            _BATCH_FILL.observe(rows / float(self._max_batch))
        except Exception as e:  # noqa: BLE001 — fail every waiter, keep serving
            for item in group:
                item.future.set(error=e)
            if self._admission is not None:
                self._admission.release(rows)
            return
        if self._admission is not None:
            # the device wall time of this batch feeds the queue-wait
            # projection (the EWMA admission sheds against)
            self._admission.release(rows,
                                    service_s=time.monotonic() - t0)
        lo = 0
        for item in group:
            item.future.set(value={k: v[lo:lo + item.n]
                                   for k, v in outs.items()})
            lo += item.n

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        if self._adaptive and self._device_thread is None:
            self._stop_ev.clear()
            self._device_thread = threading.Thread(
                target=self._device_loop, daemon=True,
                name="teacher-device")
            self._device_thread.start()
        if self._decode is not None and not self._decode.running:
            self._decode.start()
        self._rpc.start()
        logger.info("teacher serving on %s (max_batch=%d, adaptive=%s)",
                    self._rpc.endpoint, self._max_batch, self._adaptive)
        return self

    @property
    def endpoint(self):
        return self._rpc.endpoint

    @property
    def port(self):
        return self._rpc.port

    def stop(self):
        self._rpc.stop()
        if self._device_thread is not None:
            self._stop_ev.set()
            self._device_thread.join(timeout=5)
            self._device_thread = None
        if self._decode is not None:
            self._decode.stop()


def nop_teacher(fetch_specs, max_batch=128, host="0.0.0.0", port=0,
                feed_specs=None, **kwargs):
    """A fake teacher returning zeros — the test backend (reference parity:
    _TestNopPaddlePredictServer, distill_worker.py:324-333)."""
    feed_specs = feed_specs or {"ins": ([1], "<f4")}

    def predict(feed):
        n = max_batch
        return {name: np.zeros((n,) + tuple(shape), np.dtype(dtype))
                for name, (shape, dtype) in fetch_specs.items()}

    return TeacherServer(predict, feed_specs, fetch_specs,
                         max_batch=max_batch, host=host, port=port,
                         **kwargs)


def resnet_teacher(depth=50, num_classes=1000, image_size=224,
                   max_batch=64, host="0.0.0.0", port=0, feed_bf16=True,
                   groups=1, base_width=64, vd=True):
    """A real TPU teacher: ResNet/ResNeXt(depth) logits + softmax
    (groups=32, base_width=16, vd=False = the reference's distill
    teacher ResNeXt101_32x16d_wsl architecture — BASELINE.md).

    feed_bf16 halves the host→device feed bytes (the dominant serving cost
    on transfer-bound links) at negligible accuracy cost for soft labels.
    """
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    from edl_tpu.models import resnet

    compile_cache.enable()

    model = resnet.ResNet(depth=depth, num_classes=num_classes, vd=vd,
                          groups=groups, base_width=base_width,
                          dtype=jnp.bfloat16)
    dummy = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), dummy, train=False)

    @jax.jit
    def infer(image):
        logits = model.apply(variables, image, train=False)
        return logits, jax.nn.softmax(logits)

    def predict(feed):
        image = feed["image"]
        if feed_bf16:
            image = image.astype(ml_dtypes.bfloat16)
        logits, probs = infer(image)
        return {"logits": np.asarray(logits), "probs": np.asarray(probs)}

    return TeacherServer(
        predict,
        feed_specs={"image": ([image_size, image_size, 3], "<f4")},
        fetch_specs={"logits": ([num_classes], "<f4"),
                     "probs": ([num_classes], "<f4")},
        max_batch=max_batch, host=host, port=port,
        device=device_identity())


def gpt_teacher(num_layers=2, d_model=64, num_heads=4, mlp_dim=128,
                vocab_size=256, seq_len=32, max_batch=64, host="0.0.0.0",
                port=0, params=None, quantize=None, **kwargs):
    """A causal-LM teacher: per-position next-token logits + probs —
    sequence-level knowledge distillation (the LM counterpart of the
    reference's ERNIE→BOW soft-label serving). Fixed ``seq_len`` so XLA
    compiles one program; clients pad shorter sequences.

    ``params`` (a trained Gpt param tree) makes it a real teacher; the
    default random init serves as a shape-true stand-in for tests.

    ``quantize``: None | "int8" | "bf16" — serve from absmax
    per-channel int8 (or bf16) kernels (ops/quant.py); the dequant runs
    inside the jitted forward so the int8 arrays are what sit in HBM.
    Logits parity vs f32 is gated in tier-1
    (tests/test_decode_engine.py)."""
    import jax
    import jax.numpy as jnp

    from edl_tpu.models import gpt
    from edl_tpu.ops import quant

    compile_cache.enable()
    model = gpt.Gpt(num_layers=num_layers, d_model=d_model,
                    num_heads=num_heads, mlp_dim=mlp_dim,
                    vocab_size=vocab_size, max_len=max(seq_len, 16),
                    dtype=jnp.bfloat16)
    if params is None:
        dummy = jnp.zeros((1, seq_len), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), dummy)["params"]
    if quantize is not None:
        params = quant.quantize_tree(params, quantize)

    @jax.jit
    def infer(qparams, ids):
        p = quant.dequantize_tree(qparams)
        logits = model.apply({"params": p}, ids)
        return logits, jax.nn.softmax(logits)

    def predict(feed):
        ids = np.asarray(feed["input_ids"], np.int32)
        logits, probs = infer(params, ids)
        return {"logits": np.asarray(logits), "probs": np.asarray(probs)}

    return TeacherServer(
        predict,
        feed_specs={"input_ids": ([seq_len], "<i4")},
        fetch_specs={"logits": ([seq_len, vocab_size], "<f4"),
                     "probs": ([seq_len, vocab_size], "<f4")},
        max_batch=max_batch, host=host, port=port,
        device=device_identity(), **kwargs)


def lm_teacher(num_layers=2, d_model=64, num_heads=4, mlp_dim=128,
               vocab_size=256, max_len=128, slots=8, max_batch=16,
               host="0.0.0.0", port=0, params=None, quantize=None,
               decode_admission=None, **kwargs):
    """An autoregressive LM teacher: the one-shot per-position logits
    plane of :func:`gpt_teacher` PLUS the continuous-batching decode
    engine (serve/decode_engine.py) behind ``lm_generate`` /
    ``lm_submit`` / ``lm_poll``. Prefill-heavy clients use ``predict``;
    decode-heavy ones hold KV slots — the two capacities are advertised
    separately (``decode_capacities``) so the balance table can
    disaggregate the phases. ``quantize`` (None|"int8"|"bf16") applies
    to BOTH planes from one shared quantized param tree."""
    import jax
    import jax.numpy as jnp

    from edl_tpu.models import gpt
    from edl_tpu.ops import quant
    from edl_tpu.serve.decode_engine import DecodeEngine

    compile_cache.enable()
    # decode path runs f32: greedy sampling is gated token-identical
    # against models.gpt.generate, which bf16 activations would break
    model = gpt.Gpt(num_layers=num_layers, d_model=d_model,
                    num_heads=num_heads, mlp_dim=mlp_dim,
                    vocab_size=vocab_size, max_len=max_len,
                    dtype=jnp.float32)
    if params is None:
        dummy = jnp.zeros((1, 8), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), dummy)["params"]
    if quantize is not None:
        params = quant.quantize_tree(params, quantize)
    engine = DecodeEngine(model, params, slots=slots,
                          admission=decode_admission)

    @jax.jit
    def infer(qparams, ids):
        p = quant.dequantize_tree(qparams)
        logits = model.apply({"params": p}, ids)
        return logits, jax.nn.softmax(logits)

    def predict(feed):
        ids = np.asarray(feed["input_ids"], np.int32)
        logits, probs = infer(params, ids)
        return {"logits": np.asarray(logits), "probs": np.asarray(probs)}

    seq_len = max_len
    return TeacherServer(
        predict,
        feed_specs={"input_ids": ([seq_len], "<i4")},
        fetch_specs={"logits": ([seq_len, vocab_size], "<f4"),
                     "probs": ([seq_len, vocab_size], "<f4")},
        max_batch=max_batch, host=host, port=port,
        decode_engine=engine, device=device_identity(), **kwargs)


def main():
    p = argparse.ArgumentParser("edl_tpu teacher server")
    p.add_argument("--model", default="nop",
                   choices=["nop", "resnet", "resnext", "gpt"])
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--depth", type=int, default=None,
                   help="resnet depth (default 50; resnext default 101)")
    p.add_argument("--num_classes", type=int, default=1000)
    p.add_argument("--image_size", type=int, default=224)
    p.add_argument("--max_batch", type=int, default=64)
    p.add_argument("--vocab_size", type=int, default=256)
    p.add_argument("--seq_len", type=int, default=32)
    args = p.parse_args()
    if args.model == "resnet":
        server = resnet_teacher(args.depth or 50, args.num_classes,
                                args.image_size, args.max_batch,
                                port=args.port)
    elif args.model == "resnext":
        # the reference's distill teacher config: ResNeXt101_32x16d
        server = resnet_teacher(args.depth or 101, args.num_classes,
                                args.image_size, args.max_batch,
                                port=args.port, groups=32, base_width=16,
                                vd=False)
    elif args.model == "gpt":
        server = gpt_teacher(vocab_size=args.vocab_size,
                             seq_len=args.seq_len,
                             max_batch=args.max_batch, port=args.port)
    else:
        # image-shaped feeds so the NOP backend is interchangeable with
        # the resnet one (same student driver, model cost zeroed out)
        server = nop_teacher(
            {"logits": ([args.num_classes], "<f4"),
             "probs": ([args.num_classes], "<f4")},
            feed_specs={"image": ([args.image_size, args.image_size, 3],
                                  "<f4")},
            max_batch=args.max_batch, port=args.port)
    server.start()
    print("TEACHER_ENDPOINT=%s" % server.endpoint, flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    stop.wait()
    server.stop()


if __name__ == "__main__":
    main()
