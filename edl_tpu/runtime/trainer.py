"""The elastic JAX trainer harness — the in-tree replacement for what the
reference delegated to Paddle Fleet (SURVEY.md §2.6, §3.2): distributed
init, device mesh, pjit train step with gradient reduction over the mesh,
checkpoint save/restore, and train-status reporting to the control plane.

Design (TPU-first):
- one process per host (the JAX process model); `jax.distributed.initialize`
  wires processes using the launcher's env contract (coordinator = rank-0
  trainer endpoint) — there is no NCCL-style rendezvous to manage;
- params/opt state replicated, batch sharded over the `dp` mesh axis; the
  backward-pass gradient all-reduce is inserted by XLA from the sharding
  annotations (no hand-written psum for plain DP; shard_map paths live in
  edl_tpu.parallel for tp/sp);
- stop-resume elasticity: the launcher restarts this process on membership
  change; `resume()` restores the newest valid checkpoint and the State's
  adjust hooks re-tune hyperparameters for the new world size.
"""

import functools
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from edl_tpu.controller import train_status as train_status_mod
from edl_tpu.controller.env import TrainerEnv
from edl_tpu.coordination.client import CoordClient
from edl_tpu.obs import devtime as obs_devtime
from edl_tpu.obs import events as obs_events
from edl_tpu.obs import flight as obs_flight
from edl_tpu.obs import ledger as obs_ledger
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.robustness import faults
from edl_tpu.runtime import checkpoint as checkpoint_mod
from edl_tpu.runtime import state as state_mod
from edl_tpu.runtime.checkpoint import CheckpointManager, MissingKeysError
from edl_tpu.runtime.mesh import DATA_AXIS, data_sharding, make_mesh
from edl_tpu.utils import compile_cache
from edl_tpu.utils.logger import logger

_STEP_MS = obs_metrics.histogram(
    "edl_train_step_ms", "train_step wall time (host dispatch)")
# prewarm effectiveness: job_doctor names a cold compile cache from
# these (a first step in prewarm scope either loaded an AOT executable
# or paid a full XLA compile)
_PREWARM_HITS = obs_metrics.counter(
    "edl_resize_prewarm_hits_total",
    "first steps that loaded a prewarmed AOT step executable")
_PREWARM_MISSES = obs_metrics.counter(
    "edl_resize_prewarm_misses_total",
    "first steps in prewarm scope with no usable AOT artifact "
    "(full compile paid)")
_STEP_REUSES = obs_metrics.counter(
    "edl_resize_step_reuses_total",
    "live resizes that took their step executable from the table this "
    "process already held (no fingerprint, load or compile in the pause)")
_DRAIN_DEFERRED = obs_metrics.counter(
    "edl_resize_drain_deferred_total",
    "live resizes that met an async save's persist in flight and left it "
    "running (fully addressable state: the reshard reads no version)")

_MODEL_COUNTER = obs_metrics.gauge(
    "edl_train_model_counter",
    "what the model counts on the device in extra_state['counters'] (e.g. "
    "an expert layer's routing), as last mirrored at a save, a live "
    "resize or close(); index = position in the counter's vector",
    labels=("name", "index"))

#: what JAX did inside ``resize.first_dispatch``: the jax.monitoring
#: duration events that mean a program was traced, lowered, compiled or
#: loaded from the persistent cache, each under the tag it feeds
_JAX_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower_s",
    "/jax/core/compile/backend_compile_duration": "jax_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax_cache_load_s",
}
#: ``intervals`` is a list while this thread is inside a first dispatch
_jax_stages = threading.local()


def _on_jax_duration(event, duration, **_):
    seen = getattr(_jax_stages, "intervals", None)
    if seen is not None and event in _JAX_STAGE_EVENTS:
        end = time.monotonic()
        seen.append((_JAX_STAGE_EVENTS[event], end - duration, end))


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def _jax_stage_seconds(seen):
    """{tag: seconds} of the intervals ``_on_jax_duration`` collected,
    made to add up: a jitted function traced or lowered inside another
    fires an event of its own within the outer one's, so each tag is the
    length of its intervals' UNION; and the cache retrieval runs inside
    the backend-compile event, so ``jax_compile_s`` is that event less
    the retrieval — on a cache hit next to nothing."""
    out = {}
    for tag in _JAX_STAGE_EVENTS.values():
        total, covered = 0.0, float("-inf")
        for a, b in sorted((a, b) for t, a, b in seen if t == tag):
            if b > covered:
                total += b - max(a, covered)
                covered = b
        out[tag] = total
    out["jax_compile_s"] = max(
        0.0, out["jax_compile_s"] - out["jax_cache_load_s"])
    return {tag: round(v, 6) for tag, v in out.items()}


def _index_spans(sharding, shape):
    """{device: ((start, stop), ...)}: the index of an array of ``shape``
    that each device of ``sharding`` holds."""
    return {dev: tuple(sl.indices(n)[:2] for sl, n in zip(idx, shape))
            for dev, idx in sharding.devices_indices_map(shape).items()}


def _bytes_moved(avals, left, taken):
    """Bytes a reshard had to land on a device that did not hold that
    index of that leaf before: 0 for a shrink of replicated state, the
    tree's bytes times the chips gained for a grow of it. Per leaf: its
    abstract value, the sharding it left (None: it was on the host) and
    the one it took. ``devices_indices_map`` is walked once per distinct
    (shape, dtype, sharding left, sharding taken), not per leaf; still
    it is kept out of ``resize.live``: ``_first_step`` runs it behind
    the device's first step."""
    known = {}
    total = 0
    for aval, old, new in zip(avals, left, taken):
        if aval is None:
            continue
        key = (aval.shape, aval.dtype, old, new)
        if key not in known:
            held = (_index_spans(old, aval.shape).items()
                    if old is not None else ())
            known[key] = getattr(aval.dtype, "itemsize", 0) * sum(
                int(np.prod([b - a for a, b in placed[1]], dtype=np.int64))
                for placed in _index_spans(new, aval.shape).items()
                if placed not in held)
        total += known[key]
    return total


def _shard_sources(shape, dtype, old, new):
    """Where each shard of a leaf resharded ``old`` -> ``new`` comes
    from: [(target device, source device)], one per device of ``new`` —
    the target itself where it already holds that index of the leaf (the
    shard is reused in place), else a device that does (the shard is
    copied across, the holders taken in turn). None when the leaf has to
    be re-sliced: some target index is no index of ``old`` (a tp change,
    ZeRO-sharded optimizer state on another dp), or the shards are not
    plain device buffers of one memory kind."""
    if (jax.dtypes.issubdtype(dtype, jax.dtypes.extended)
            or getattr(old, "memory_kind", None)
            != getattr(new, "memory_kind", None)):
        return None
    holders = {}
    for dev, span in _index_spans(old, shape).items():
        holders.setdefault(span, []).append(dev)
    sources = []
    for dev, span in _index_spans(new, shape).items():
        held = holders.get(span)
        if not held:
            return None
        sources.append(
            (dev, dev if dev in held else held[len(sources) % len(held)]))
    return sources


#: a leaf larger than this crosses on its own. Stacking leaves buys the
#: runtime's cost of one more copied array (0.15 ms on a v5e host, PR
#: 54) at the price of a second copy of the leaf on the devices it
#: leaves and on those it takes, until the reshard is done: a bargain
#: for the many small leaves (8 MiB is 0.2 ms on the wire between two
#: chips of a host), a risk to the memory for the few large ones
_STACK_LEAF_BYTES = 8 << 20


def _stacked(sharding):
    """The sharding of a stack of leaves that each have ``sharding``
    (a NamedSharding): the new leading axis is not split."""
    return NamedSharding(sharding.mesh, P(None, *sharding.spec),
                         memory_kind=sharding.memory_kind)


def _stack_programs(groups):
    """(pack, cut) for ``groups`` of leaves that cross as one array
    each — per group (shape, dtype, sharding left, sharding taken, how
    many leaves): ``pack(leaves)`` stacks each group's leaves where
    they lie, ``cut(stacks)`` cuts the stacks, arrived on the new
    shardings, into the leaves again."""
    counts = [n for *_, n in groups]

    def pack(leaves):
        leaves = iter(leaves)
        return [jnp.stack([next(leaves) for _ in range(n)]) for n in counts]

    def cut(stacks):
        return [stack[j] for stack, n in zip(stacks, counts)
                for j in range(n)]

    return (jax.jit(pack, out_shardings=[_stacked(old)
                                         for _, _, old, _, _ in groups]),
            jax.jit(cut, out_shardings=[new for _, _, _, new, n in groups
                                        for _ in range(n)]))


def _reshard_local(leaves, targets, programs):
    """Move fully addressable ``leaves`` onto the shardings ``targets``
    in a handful of transfers: -> (new leaves, arrays crossed, leaves
    taken leaf by leaf). The move is planned once per distinct (shape,
    dtype, sharding left, sharding taken) (:func:`_shard_sources`), and
    only reads the old leaves:

    - a shard that lies on its target device is shared with the old
      leaf, as ``jax.device_put`` shares it: the whole of a shrink;
    - the leaves of one such move that has shards to cross are first
      stacked where they lie (one jitted program for all of them), so
      that they cross as ONE array: the runtime's cost is per array
      copied, not per byte. Small leaves only (``_STACK_LEAF_BYTES``),
      on NamedShardings, where the stack's sharding can be named;
    - every shard that has to cross, of all stacks and all other
      leaves, goes to the runtime in ONE ``jax.device_put`` of
      single-device arrays (a batched copy, not one slow-path call a
      leaf), and each array is put together from its shards;
    - one jitted program on the new shardings cuts the stacks into
      leaves again: every device takes its cut, those that stay too
      (their old buffers go with the old tree). ``programs`` keeps the
      two programs by what they were built for, so a move made before
      compiles nothing;
    - a leaf that has to be re-sliced, or is no device array yet, is
      left to ``jax.device_put(leaf, sharding)``, all in one call."""
    by_move, leafwise = {}, []
    for i, (x, new) in enumerate(zip(leaves, targets)):
        if isinstance(x, jax.Array):
            by_move.setdefault((x.shape, x.dtype, x.sharding, new),
                               []).append(i)
        else:
            leafwise.append(i)
    singles, stacks = [], []
    for move, members in by_move.items():
        shape, dtype, old, new = move
        sources = _shard_sources(*move)
        if sources is None:
            leafwise.extend(members)
        elif (len(members) > 1
              and any(src is not dev for dev, src in sources)
              and isinstance(old, NamedSharding)
              and isinstance(new, NamedSharding)
              and dtype.itemsize * int(np.prod(shape, dtype=np.int64))
              <= _STACK_LEAF_BYTES):
            stacks.append((move + (len(members),), sources, members))
        else:
            singles.extend((i, sources) for i in members)
    # what moves shard by shard: (array, sharding it takes, sources)
    movers = [(leaves[i], targets[i], sources) for i, sources in singles]
    if stacks:
        built_for = tuple(group for group, _, _ in stacks)
        if built_for not in programs:
            programs[built_for] = _stack_programs(built_for)
        pack, cut = programs[built_for]
        stacked = [i for _, _, members in stacks for i in members]
        movers.extend(
            (stack, _stacked(group[3]), sources) for stack, (group, sources, _)
            in zip(pack([leaves[i] for i in stacked]), stacks))
    onto, parts = {}, []
    crossing, dests, slots = [], [], []
    for x, _, sources in movers:
        held = {s.device: s.data for s in x.addressable_shards}
        shards = []
        for dev, src in sources:
            if src is not dev:
                if dev not in onto:
                    onto[dev] = SingleDeviceSharding(dev)
                slots.append((shards, len(shards)))
                crossing.append(held[src])
                dests.append(onto[dev])
            shards.append(held[src])
        parts.append(shards)
    if crossing:
        for (shards, at), copy in zip(slots,
                                      jax.device_put(crossing, dests)):
            shards[at] = copy
    moved = [jax.make_array_from_single_device_arrays(x.shape, new, shards)
             for (x, new, _), shards in zip(movers, parts)]
    out = list(leaves)
    for (i, _), x in zip(singles, moved):
        out[i] = x
    if stacks:
        for i, x in zip(stacked, cut(moved[len(singles):])):
            out[i] = x
    if leafwise:
        for i, x in zip(leafwise, jax.device_put(
                [leaves[i] for i in leafwise],
                [targets[i] for i in leafwise])):
            out[i] = x
    return out, len(crossing), len(leafwise)


_distributed_initialized = False


def make_train_state(params, tx, extra_state=None):
    """The canonical train-state pytree shared by ElasticTrainer, the
    benchmark (benchmark/run.py) and the driver dry-run."""
    return {
        "params": params,
        "opt_state": tx.init(params),
        "step": jnp.zeros((), jnp.int32),
        "extra": extra_state if extra_state is not None else {},
    }


# named activation-recompute policies for make_train_step/ElasticTrainer;
# per-LAYER recompute (the big lever) is the models' own `remat` flag —
# these whole-loss policies tune what the fwd/bwd boundary may save
_REMAT_POLICIES = {
    "full": lambda: None,  # jax.checkpoint default: save nothing
    "dots": lambda: jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch":
        lambda: jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


def _remat_wrapper(remat_policy):
    """Validate ``remat_policy`` eagerly and return the loss wrapper
    (identity for None) — shared by make_train_step/make_accum_step."""
    if remat_policy is not None and remat_policy not in _REMAT_POLICIES:
        raise ValueError("remat_policy %r not in %s"
                         % (remat_policy, sorted(_REMAT_POLICIES)))

    def wrap(fn):
        if remat_policy is None:
            return fn
        return jax.checkpoint(fn, policy=_REMAT_POLICIES[remat_policy]())

    return wrap


def make_train_step(loss_fn, tx, has_aux=False, remat_policy=None):
    """Build the canonical SGD step over a make_train_state pytree.

    loss_fn: (params, batch, rng) -> loss, or with has_aux
    (params, extra, batch, rng) -> (loss, new_extra). Returns
    step(train_state, batch, rng) -> (train_state, loss), jit-ready.

    remat_policy: None or one of "full"|"dots"|"dots_no_batch" — wraps the
    loss in jax.checkpoint with the named policy (activation recompute;
    reference knob train_with_fleet.py:322-325). Combine with the models'
    own per-layer ``remat`` flag for layer-boundary-only memory."""
    _maybe_remat = _remat_wrapper(remat_policy)

    def step(train_state, batch, rng):
        if has_aux:
            @_maybe_remat
            def compute(params):
                return loss_fn(params, train_state["extra"], batch, rng)
            (loss, extra), grads = jax.value_and_grad(
                compute, has_aux=True)(train_state["params"])
        else:
            @_maybe_remat
            def compute(params):
                return loss_fn(params, batch, rng)
            loss, grads = jax.value_and_grad(compute)(train_state["params"])
            extra = train_state["extra"]
        # a device-side scope (obs/devtime.py): whatever `tx` does to
        # the gradient — its norm, a clip, the moments — and the update
        with jax.named_scope("optim.update"):
            updates, opt_state = tx.update(grads, train_state["opt_state"],
                                           train_state["params"])
            params = optax.apply_updates(train_state["params"], updates)
        return {
            "params": params,
            "opt_state": opt_state,
            "step": train_state["step"] + 1,
            "extra": extra,
        }, loss

    return step


def make_accum_step(loss_fn, tx, accum_steps, has_aux=False,
                    remat_policy=None, overlap_axis=None, mesh=None):
    """Gradient accumulation: ONE optimizer update from ``accum_steps``
    microbatches, scanned in one dispatch.

    step(train_state, batches, rng) -> (train_state, loss) where every
    leaf of ``batches`` has a leading [accum_steps] axis (microbatch-
    major) and loss is the mean microbatch loss. Gradients are averaged
    over microbatches — for a mean-reduced loss this equals the full-
    batch gradient, so the update is independent of ``accum_steps`` (up
    to fp roundoff); ``extra`` state (e.g. BatchNorm running stats)
    chains through the microbatches sequentially, exactly as if they
    were separate steps.

    The elastic lever: on a scale-down the per-chip batch must absorb
    total_batch_size/world more rows; instead of growing activation
    memory, raise ``grad_accum`` — the global batch per UPDATE (and so
    convergence behavior) is unchanged across the resize. The reference
    kept global batch constant by resharding rows only
    (train_with_fleet.py:360-361); accumulation extends that policy past
    the per-device memory ceiling. The rng is folded per microbatch so
    dropout streams differ across microbatches.

    Collective–compute overlap (``overlap_axis``/``mesh``): with a data
    axis named, the step runs under shard_map over that axis and the
    gradient all-reduce for microbatch *i* is DELAYED into the scan
    carry — issued at the top of iteration *i+1*, where it has no data
    dependence on that iteration's fwd/bwd, so XLA schedules the pmean
    (one collective per leaf — naturally bucketed) behind the compute.
    The last microbatch's reduce runs after the scan. When the axis has
    size 1 (or ``mesh`` is None) there are no collectives to hide, so
    the EAGER step is returned unchanged and the no-op is logged —
    clean degradation (bitwise-identical updates by construction, and
    no 2x gradient carry), not an error. Incompatible with
    ``has_aux`` (per-shard extra state has no defined reduction), and
    the loss's rng stream is shared across shards (fine for rng-free or
    row-independent losses; dropout masks would repeat per shard)."""
    if accum_steps < 1:
        raise ValueError("accum_steps must be >= 1")
    _maybe_remat = _remat_wrapper(remat_policy)

    if overlap_axis is not None:
        if has_aux:
            raise ValueError(
                "overlap_axis is incompatible with has_aux: extra "
                "state is per-shard under shard_map and has no defined "
                "reduction")
        axes = ((overlap_axis,) if isinstance(overlap_axis, str)
                else tuple(overlap_axis))
        axis_size = 1
        if mesh is not None:
            axis_size = int(np.prod([mesh.shape[a] for a in axes
                                     if a in mesh.shape]))
        if mesh is not None and axis_size > 1:
            return _make_overlap_accum_step(loss_fn, tx, accum_steps,
                                            _maybe_remat, axes, mesh)
        logger.info(
            "make_accum_step: dp overlap over %s is a no-op (axis size "
            "%d) — no collectives to hide, returning the eager "
            "accumulation step unchanged", axes, axis_size)
        # fall through to the eager step below

    def step(train_state, batches, rng):
        params = train_state["params"]

        def body(carry, xs):
            extra, grad_acc, loss_acc = carry
            i, batch = xs
            rng_i = jax.random.fold_in(rng, i)
            if has_aux:
                @_maybe_remat
                def compute(p):
                    return loss_fn(p, extra, batch, rng_i)
                (loss, new_extra), grads = jax.value_and_grad(
                    compute, has_aux=True)(params)
            else:
                @_maybe_remat
                def compute(p):
                    return loss_fn(p, batch, rng_i)
                loss, grads = jax.value_and_grad(compute)(params)
                new_extra = extra
            grad_acc = jax.tree_util.tree_map(jnp.add, grad_acc, grads)
            return (new_extra, grad_acc, loss_acc + loss), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (extra, grad_sum, loss_sum), _ = lax.scan(
            body, (train_state["extra"], zeros, jnp.zeros((), jnp.float32)),
            (jnp.arange(accum_steps), batches), length=accum_steps)
        with jax.named_scope("optim.update"):
            grads = jax.tree_util.tree_map(lambda g: g / accum_steps,
                                           grad_sum)
            updates, opt_state = tx.update(grads, train_state["opt_state"],
                                           params)
            new_params = optax.apply_updates(params, updates)
        return {
            "params": new_params,
            "opt_state": opt_state,
            "step": train_state["step"] + 1,
            "extra": extra,
        }, loss_sum / accum_steps

    return step


def _make_overlap_accum_step(loss_fn, tx, accum_steps, _maybe_remat,
                             axes, mesh):
    """The delayed-reduction accumulation schedule (see make_accum_step's
    overlap paragraph). Only built when the overlap axes have size > 1 —
    the degenerate case returns the eager step from make_accum_step —
    and split out so the eager path stays byte-for-byte what it was."""

    def _fold(reduced, pending):
        pending = jax.tree_util.tree_map(
            lambda g: lax.pmean(g, axes), pending)
        return jax.tree_util.tree_map(jnp.add, reduced, pending)

    def step(train_state, batches, rng):
        params = train_state["params"]

        def body(carry, xs):
            reduced, pending, loss_acc = carry
            i, batch = xs
            # fold the PREVIOUS microbatch's unreduced grads into the
            # running sum before this microbatch's fwd/bwd: the pmean
            # has no data dependence on the compute below, so XLA
            # overlaps the wire time with it
            reduced = _fold(reduced, pending)
            rng_i = jax.random.fold_in(rng, i)

            @_maybe_remat
            def compute(p):
                return loss_fn(p, batch, rng_i)
            loss, grads = jax.value_and_grad(compute)(params)
            return (reduced, grads, loss_acc + loss), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (reduced, pending, loss_sum), _ = lax.scan(
            body,
            (zeros, jax.tree_util.tree_map(jnp.zeros_like, params),
             jnp.zeros((), jnp.float32)),
            (jnp.arange(accum_steps), batches), length=accum_steps)
        grad_sum = _fold(reduced, pending)  # the last microbatch's reduce
        grads = jax.tree_util.tree_map(lambda g: g / accum_steps,
                                       grad_sum)
        loss = lax.pmean(loss_sum / accum_steps, axes)
        with jax.named_scope("optim.update"):
            updates, opt_state = tx.update(grads, train_state["opt_state"],
                                           params)
            new_params = optax.apply_updates(params, updates)
        return {
            "params": new_params,
            "opt_state": opt_state,
            "step": train_state["step"] + 1,
            "extra": train_state["extra"],
        }, loss

    state_spec = P()
    batch_spec = P(None, axes)
    return jax.shard_map(step, mesh=mesh,
                         in_specs=(state_spec, batch_spec, state_spec),
                         out_specs=(state_spec, state_spec),
                         check_vma=False)


def auto_grad_accum(per_device_batch, max_per_device_batch):
    """Smallest microbatch count k (dividing ``per_device_batch``) whose
    per-device microbatch fits ``max_per_device_batch``.

    The elastic memory policy: state the per-device activation budget
    once; each stop-resume restart computes the accumulation that keeps
    total_batch_size (and so convergence) constant at the new world
    size. k = per_device_batch is always feasible (microbatch 1)."""
    if max_per_device_batch <= 0:
        raise ValueError("max_per_device_batch must be positive")
    if per_device_batch < 1:
        raise ValueError("per_device_batch must be >= 1")
    for k in range(1, per_device_batch + 1):
        if per_device_batch % k == 0 \
                and per_device_batch // k <= max_per_device_batch:
            return k
    raise AssertionError("unreachable: k == per_device_batch always fits")


def maybe_init_distributed(env=None):
    """Initialize jax.distributed from the launcher env contract (no-op for
    single-process runs)."""
    global _distributed_initialized
    env = env or TrainerEnv()
    # the persistent XLA cache (keyed by program incl. mesh shape) cuts
    # stop-resume recovery to O(restart) when the new world size was
    # seen before (SURVEY.md §7 'resize vs XLA reality'); before
    # jax.distributed.initialize, so it must not touch a backend
    compile_cache.enable()
    if _distributed_initialized or env.world_size <= 1:
        return env
    # idempotent with external bootstrap (a test rig or launcher that
    # already called jax.distributed.initialize)
    if jax.distributed.is_initialized():
        _distributed_initialized = True
        return env
    jax.distributed.initialize(
        coordinator_address=env.coordinator,
        num_processes=env.world_size,
        process_id=env.global_rank)
    _distributed_initialized = True
    logger.info("jax.distributed up: process %d/%d coordinator=%s",
                env.global_rank, env.world_size, env.coordinator)
    return env


class ElasticTrainer(object):
    """Data-parallel elastic trainer.

    Args:
      loss_fn: (params, batch, rng) -> scalar loss, or with has_aux=True
        (params, extra, batch, rng) -> (loss, new_extra) where ``extra`` is
        non-differentiated model state updated each step (e.g. BatchNorm
        running stats) — kept inside the donated train_state.
      params: initial parameter pytree.
      tx: an optax GradientTransformation.
      total_batch_size: GLOBAL batch size; kept constant across resizes
        (per-host batch = total / world) per the reference's policy
        (train_with_fleet.py:360-361, edl_collective_design_doc.md:14-17).
      checkpoint_dir: shared directory for elastic resume ('' disables).
      mesh: optional prebuilt Mesh (default: 1-D dp mesh over all devices).
      grad_accum: microbatches accumulated per optimizer update
        (make_accum_step); total_batch_size stays the per-UPDATE global
        batch, so raising grad_accum after a scale-down keeps both the
        update size and the per-chip activation memory constant.
      zero1: ZeRO-1 / weight-update sharding — optimizer moments sharded
        over the dp axis (composes with tensor-parallel param_shardings);
        XLA turns the grad all-reduce + update into reduce-scatter +
        sharded update + param all-gather. 1/dp the optimizer memory at
        unchanged wire bytes.
      max_per_device_batch: declarative alternative to grad_accum — a
        per-device batch budget; each restart picks the smallest
        accumulation that fits it at the current world size
        (auto_grad_accum).
      step_fn: a custom train step (train_state, batch, rng) ->
        (train_state, loss) replacing the canonical make_train_step —
        the hook that puts engines owning their own backward (the 1F1B
        pipeline's pipeline_value_and_grad) inside the elastic harness:
        checkpoint/resume, preemption, sharded saves and placed
        restores all apply to the custom step's state. Mutually
        exclusive with the loss-level knobs (has_aux / grad_accum /
        remat_policy / max_per_device_batch); pass param_shardings
        (e.g. stages over "pp") for the layout, and build the step with
        the SAME ``tx`` object passed here (it initializes the
        opt_state the step updates).
      dp_overlap: with grad_accum > 1, run the delayed-reduction
        accumulation schedule (make_accum_step's overlap path): the
        gradient all-reduce for microbatch i overlaps microbatch i+1's
        fwd/bwd. Plain-DP only (replicated params/opt state — no zero1
        or param_shardings, whose leaf-wise shard_map specs this path
        does not build) and no has_aux. On a 1-device data axis there
        are no collectives to hide, so the eager accumulation step runs
        unchanged (logged no-op). At
        grad_accum == 1 there is no cross-microbatch edge to hide the
        reduce behind, so the knob is ignored (logged).
    """

    def __init__(self, loss_fn, params, tx, total_batch_size,
                 checkpoint_dir=None, mesh=None, env=None, coord=None,
                 keep_checkpoints=3, extra_state=None, has_aux=False,
                 async_save=False, remat_policy=None,
                 param_shardings=None, grad_accum=1, zero1=False,
                 max_per_device_batch=None, step_fn=None,
                 dp_overlap=False):
        if step_fn is not None and (has_aux or grad_accum != 1
                                    or remat_policy is not None
                                    or max_per_device_batch is not None
                                    or dp_overlap):
            raise ValueError(
                "step_fn owns the whole step: has_aux/grad_accum/"
                "remat_policy/max_per_device_batch/dp_overlap do not "
                "apply")
        if dp_overlap and has_aux:
            raise ValueError("dp_overlap is incompatible with has_aux "
                             "(see make_accum_step)")
        if dp_overlap and (zero1 or param_shardings is not None):
            raise ValueError(
                "dp_overlap requires replicated params/opt state "
                "(plain DP): zero1/param_shardings shard the state, and "
                "the overlap shard_map only builds replicated specs")
        self._dp_overlap = dp_overlap
        self._step_fn = step_fn
        self.env = env or TrainerEnv()
        maybe_init_distributed(self.env)
        if checkpoint_dir is None:
            # default to the launcher-provided shared checkpoint path
            checkpoint_dir = self.env.checkpoint_path
        self.total_batch_size = total_batch_size
        # _bind_mesh consumes _grad_accum; bind at 1 first, rebind after
        # the accumulation is resolved (auto_grad_accum needs the
        # per-device batch the first binding computes)
        self._grad_accum = 1
        self._bind_mesh(mesh if mesh is not None else make_mesh())

        self._loss_fn = loss_fn
        self._tx = tx
        self._has_aux = has_aux
        self._remat_policy = remat_policy
        # gradient accumulation: total_batch_size stays the rows per
        # OPTIMIZER UPDATE; each update scans grad_accum microbatches
        # (see make_accum_step — the past-the-memory-ceiling elastic lever)
        if grad_accum < 1:
            raise ValueError("grad_accum must be >= 1")
        if max_per_device_batch is not None:
            if grad_accum != 1:
                raise ValueError(
                    "pass either grad_accum or max_per_device_batch, not "
                    "both — the budget exists to CHOOSE the accumulation")
            # the declarative form: a per-device batch budget instead of
            # an explicit k — recomputed per world size on every restart
            grad_accum = auto_grad_accum(self.per_device_batch,
                                         max_per_device_batch)
        if grad_accum > 1:
            # rebind: the batch sharding becomes microbatch-major and
            # the divisibility checks run against the accumulation
            self._grad_accum = grad_accum
            self._bind_mesh(self.mesh)
        if extra_state is not None:
            for leaf in jax.tree_util.tree_leaves(extra_state):
                # only explicit numpy 64-bit leaves are dangerous; Python
                # scalars are weak-typed to 32-bit with no real truncation
                if not isinstance(leaf, (np.ndarray, np.generic)):
                    continue
                dt = leaf.dtype
                if dt.kind in "iuf" and dt.itemsize == 8 \
                        and not jax.config.jax_enable_x64:
                    raise ValueError(
                        "extra_state leaf has 64-bit dtype %s which JAX "
                        "would silently truncate to 32-bit on device; keep "
                        "host-side metadata (file offsets, loader positions) "
                        "in trainer.state.user_defined instead" % dt)
        self.state = state_mod.State(total_batch_size=total_batch_size)

        # model parallelism: partition rules (regex, PartitionSpec) or an
        # explicit sharding pytree for the params; optimizer-state
        # shardings are derived by running tx.init under jit so moments
        # inherit their param's layout (net-new vs the reference: elastic
        # stop-resume composes with tp — SURVEY.md §2.7)
        if isinstance(param_shardings, (list, tuple)):
            from edl_tpu.parallel.sharding import shard_params
            params, param_shardings = shard_params(params, self.mesh,
                                                   param_shardings)
        if param_shardings is None and not zero1:
            self.train_state = make_train_state(params, tx, extra_state)
            self._state_shardings = jax.tree_util.tree_map(
                lambda _: self._repl, self.train_state)
        else:
            from edl_tpu.parallel.sharding import opt_state_shardings
            if param_shardings is None:
                # ZeRO-1 with replicated params: only the optimizer
                # state is dp-sharded (weight-update sharding)
                param_shardings = jax.tree_util.tree_map(
                    lambda _: self._repl, params)
            params = jax.device_put(params, param_shardings)
            # zero1 shards over the full data-replica set — (dcn, dp) on
            # hybrid meshes, matching the batch axes
            zero_axes = (self._batch_sharding_early.spec[0]
                         if self._batch_sharding_early.spec else DATA_AXIS)
            opt_shardings = opt_state_shardings(
                tx, params, param_shardings, self._repl,
                zero1_mesh=self.mesh if zero1 else None,
                zero1_axis=zero_axes or DATA_AXIS)
            # init the optimizer state DIRECTLY into its sharded layout —
            # never materialize the full replicated moments (the zero1
            # startup-peak would defeat the steady-state memory win)
            self.train_state = {
                "params": params,
                "opt_state": jax.jit(
                    tx.init, out_shardings=opt_shardings)(params),
                "step": jnp.zeros((), jnp.int32),
                "extra": extra_state if extra_state is not None else {},
            }
            self._state_shardings = jax.tree_util.tree_map(
                lambda _: self._repl, self.train_state)
            self._state_shardings["params"] = param_shardings
            self._state_shardings["opt_state"] = opt_shardings
        self.train_state = jax.device_put(self.train_state,
                                          self._state_shardings)

        self._ckpt = (CheckpointManager(checkpoint_dir,
                                        keep=keep_checkpoints)
                      if checkpoint_dir else None)
        if self._ckpt is not None and jax.process_index() == 0:
            # crashed-attempt garbage (incl. stale sharded-save STARTED
            # sentinels that would mis-order a later same-version save)
            try:
                self._ckpt.clean_uncommitted()
            except Exception:
                logger.exception("uncommitted-checkpoint cleanup failed")
        self.coord = coord
        if self.coord is None and self.env.under_launcher:
            self.coord = CoordClient(self.env.store_endpoints,
                                     root=self.env.job_id)

        # peer-served restore plane (runtime/state_server.py): serve the
        # latest committed snapshot to restarting peers and prefer live
        # peers over the shared FS on our own resume. Opt-out with
        # EDL_TPU_PEER_RESTORE=0; needs both a checkpoint dir (the FS
        # fallback) and a coordination store (peer discovery).
        self._state_server = None
        # per-incarnation resize timing record (docs/elastic_resize.md):
        # absolute unix timestamps so measure_resize can align them with
        # its own kill/detect clock. live_resize() replaces the record
        # (mode "live") without a process restart.
        self._resize_timing = {"t_construct": time.time(),
                               "mode": "stop_resume"}
        if (self._ckpt is not None and self.coord is not None
                and os.environ.get("EDL_TPU_PEER_RESTORE", "1") != "0"):
            try:
                from edl_tpu.runtime.state_server import StateServer
                self._state_server = StateServer(
                    rank=self.env.global_rank,
                    host=os.environ.get("EDL_TPU_POD_IP", "0.0.0.0"))
                self._state_server.advertise(self.coord)
                # diskless redundancy tier (runtime/redundancy.py):
                # accept partners' erasure-coded snapshot shards and
                # push our own on every commit, so a pod loss rebuilds
                # from survivors with zero FS reads. Kill switch:
                # EDL_TPU_REDUNDANCY=0.
                from edl_tpu.runtime import redundancy as redundancy_mod
                if redundancy_mod.enabled():
                    self._state_server.advertise_redundancy(
                        self.coord, key=str(self.env.global_rank))
            except Exception:
                logger.exception("state server failed to start; peer "
                                 "restore disabled for this process")
                self._state_server = None

        self._jit_step = self._build_step()
        # captured at the first step
        self._example_batch_sds = self._example_rng_sds = None
        # a reader of a device profile finds the step's operation names
        # here, by mesh; nothing is built until one asks
        self._scope_tables = {}
        obs_devtime.register(self.step_scope_table)
        # step executables this process holds ready, by _step_key():
        # what prewarm_resize_compiles compiled, what was loaded from
        # its file, and the step of every world live_resize has left.
        # What a step is built from (_loss_fn, _tx,
        # _grad_accum, _remat_policy, _step_fn) is set above and never
        # again, so an entry stays good for the life of the trainer.
        self._ready_steps = {}
        # and beside them the programs a live resize's reshard stacks
        # and cuts small leaves with, by the moves they were built for
        # (_reshard_local): a resize made before compiles nothing
        self._reshard_programs = {}
        # the step that next stamps compile_s/first_step_s into
        # _resize_timing: the first step of this incarnation, and the
        # first step after every live_resize() (same record semantics
        # as a restart, without the restart)
        self._stamp_first_step = True
        # [trace_id, span_id] of the last live resize's root span, for
        # the first step after it to join; None for a fresh incarnation
        self._resize_trace = None
        # (the span `resize.device_put`, the arguments of _bytes_moved)
        # of a live resize whose first step has not run yet
        self._put_account = (None, None)
        self._prewarm_s = 0.0  # see _try_load_prewarmed_step
        # live-resize protocol state (enable_live_resize)
        self._live_watcher = None
        self._live_register = None
        self._live_who = None
        self._prewarm_thread = None
        self._step_times = []
        # start-to-start wall intervals (NOT in-call durations: jit
        # dispatch returns in ~ms while the real cadence includes data
        # loading and device time) — the preemption stop margin must be
        # computed from the true step rate
        self._step_intervals = []
        self._last_step_start = None
        # host-side mirror of the step counter: seeds default rngs without
        # forcing a device sync on the donated step array every step
        self._host_step = 0
        # version this incarnation resumed from (-1 = fresh start): an
        # emergency checkpoint at or below it belongs to a PRIOR
        # preemption event, not the one being waited on
        self._resumed_version = -1
        # env override so launchers/benches can flip the save engine
        # without threading a flag through every example's CLI
        env_async = os.environ.get("EDL_TPU_ASYNC_SAVE")
        if env_async is not None:
            async_save = env_async not in ("0", "")
        self._async_save = async_save
        # flag-only SIGTERM handler + drain hook: every preemption exit
        # path drains the checkpoint engine's in-flight async persist
        from edl_tpu.runtime.preemption import PreemptionGuard
        self._guard = PreemptionGuard(drain=self.wait_for_save)
        self._preempt_armed = False
        self._coord_stop = None
        self._preempt_t0 = None
        self._coord_deadline = 15.0
        # non-daemon writer + atexit join: process exit must not lose the
        # final checkpoint mid-write (manifest-last keeps partials
        # invisible, but losing the last epoch silently is a regression).
        # Registered ONCE, via weakref: the atexit registry must not pin
        # discarded trainers (and their device state) for the process
        # lifetime when several are constructed (restarts, notebooks).
        import atexit
        import weakref
        ref = weakref.ref(self)
        atexit.register(lambda: (lambda t: t and t.wait_for_save())(ref()))

    # -- mesh binding --------------------------------------------------------

    def _bind_mesh(self, mesh):
        """Bind every mesh-derived attribute: batch shardings, the
        per-device/per-host batch math, host row spans, the replicated
        sharding. Called at construction and again by live_resize()
        with the new world's mesh. Validates before assigning anything,
        so a ValueError leaves the previous binding intact."""
        total = self.total_batch_size
        early = data_sharding(mesh)
        # batch divisibility is over the BATCH-SHARDED axes (dcn, dp) —
        # with model axes (tp/sp/pp) in the mesh, rows are replicated
        # across them, not split
        n_batch_shards = 1
        spec0 = early.spec[0] if early.spec else None
        for ax in ((spec0,) if isinstance(spec0, str)
                   else tuple(spec0 or ())):
            n_batch_shards *= mesh.shape[ax]
        if total % n_batch_shards != 0:
            raise ValueError(
                "total_batch_size %d not divisible by %d batch shards"
                % (total, n_batch_shards))
        per_device = total // n_batch_shards
        # rows THIS process must supply = the union of its devices' batch
        # spans (with cross-process model axes a process can own every
        # row; with pure dp it owns a contiguous slice)
        idx_map = early.addressable_devices_indices_map((total,))
        spans = sorted({(sl[0].start or 0,
                         total if sl[0].stop is None else sl[0].stop)
                        for sl in idx_map.values()})
        per_host = sum(b - a for a, b in spans)
        if self._grad_accum > 1:
            if per_host % self._grad_accum != 0:
                raise ValueError(
                    "per-host batch %d not divisible by grad_accum %d"
                    % (per_host, self._grad_accum))
            if per_device % self._grad_accum != 0:
                raise ValueError(
                    "per-device batch %d not divisible by grad_accum %d"
                    % (per_device, self._grad_accum))
        self.mesh = mesh
        self._batch_sharding_early = early
        self.per_device_batch = per_device
        self._host_row_spans = spans
        self.per_host_batch = per_host
        self._repl = NamedSharding(mesh, P())
        if self._grad_accum > 1:
            # microbatch-major [k, rows/k, ...]: scan axis replicated,
            # rows sharded over the same data axes as the flat layout
            row_axes = early.spec[0] if early.spec else None
            self._batch_sharding = NamedSharding(mesh, P(None, row_axes))
        else:
            self._batch_sharding = early

    # -- the compiled step ---------------------------------------------------

    def _raw_step(self):
        """The un-jitted step callable (shared by _build_step and the
        resize-prewarm AOT compiles)."""
        if self._step_fn is not None:
            return self._step_fn
        if self._grad_accum > 1:
            overlap_axis = None
            if self._dp_overlap:
                # the row axes of the microbatch-major layout — "dp",
                # or ("dcn", "dp") on hybrid meshes
                overlap_axis = (self._batch_sharding.spec[1]
                                or DATA_AXIS)
            return make_accum_step(self._loss_fn, self._tx,
                                   self._grad_accum, self._has_aux,
                                   remat_policy=self._remat_policy,
                                   overlap_axis=overlap_axis,
                                   mesh=self.mesh if overlap_axis
                                   else None)
        if self._dp_overlap:
            logger.info("dp_overlap ignored: grad_accum == 1 leaves no "
                        "next microbatch to overlap the gradient "
                        "all-reduce with")
        return make_train_step(self._loss_fn, self._tx, self._has_aux,
                               remat_policy=self._remat_policy)

    def _build_step(self):
        return jax.jit(
            self._raw_step(),
            in_shardings=(self._state_shardings, self._batch_sharding,
                          self._repl),
            out_shardings=(self._state_shardings, self._repl),
            donate_argnums=(0,))

    # -- resize prewarm (AOT executables across restarts) --------------------
    #
    # SURVEY §7 names restart latency as THE metric for elastic TPU
    # training: stop-resume pays tracing + XLA compile at every world-
    # size change, dominating recovery. A running job already holds the
    # devices any SMALLER world would use — so the step can be compiled
    # for that sub-mesh NOW and carried to the restarted process. The
    # persistent compilation cache cannot carry it (its key includes
    # the platform topology, which differs between an 8-device process
    # compiling for 4 devices and a genuine 4-device process — verified
    # empirically); AOT executable serialization
    # (jax.experimental.serialize_executable) can: the deserialized
    # executable runs in the smaller process directly, skipping compile
    # entirely. Staleness safety: files are keyed by a fingerprint of
    # the lowered computation + shapes + jaxlib version, recomputed by
    # the restarted process — a code or config change simply misses.

    def _step_key(self, mesh, state_shardings, batch_sharding):
        """What a step executable was built for, as far as this trainer
        can observe it: the mesh's devices and axes (another
        factorisation of the same devices is another key), every state
        leaf's PartitionSpec, the batch's structure and spec, the rng's
        aval. Needs the examples the first step captured."""
        batch, batch_tree = jax.tree_util.tree_flatten(
            self._example_batch_sds)
        return (tuple(d.id for d in mesh.devices.flat),
                tuple(mesh.axis_names), tuple(mesh.devices.shape),
                tuple(sh.spec for sh in
                      jax.tree_util.tree_leaves(state_shardings)),
                batch_sharding.spec, batch_tree,
                tuple((x.shape, x.dtype)
                      for x in batch + [self._example_rng_sds]))

    def _aot_step(self, executable, key, repl, jit_fallback):
        """An AOT executable of the step (kept from a prewarm's compile
        or loaded from its file) as a ``_jit_step``, entered in the
        table of ready steps under ``key``."""
        def step(state, batch, rng):
            # AOT executables take committed inputs with the EXACT
            # compiled signature; jax.jit would transparently recompile
            # on a changed rng type or a ragged tail batch — mirror that
            # by reverting to the jit path on an input mismatch
            try:
                return executable(state, batch, jax.device_put(rng, repl))
            except (TypeError, ValueError) as e:
                # ONLY argument-validation failures are safe to retry:
                # they reject before dispatch, so no buffer has been
                # donated yet. A post-dispatch failure (XlaRuntimeError
                # etc.) leaves state's donated buffers deleted —
                # retrying would mask the real error with a
                # use-after-donate; let it propagate.
                logger.warning(
                    "AOT step input mismatch (%r); reverting to the jit "
                    "path for this and later steps", e)
                self._ready_steps.pop(key, None)
                self._jit_step = jit_fallback
                return jit_fallback(state, batch, rng)

        self._ready_steps[key] = step
        return step

    def _step_lowered(self, world_n=None):
        """Lower the train step for ``world_n`` devices (None = the
        current mesh), returning (lowered, fingerprint, adopt):
        ``adopt(executable)`` is ``_aot_step`` for that world, the jit
        of this lowering as its fallback."""
        import hashlib

        if world_n is None:
            mesh_n = self.mesh
            state_sh = self._state_shardings
            data_sh = self._batch_sharding
            repl = self._repl
        else:
            # _target_mesh uses the PROCESS device list, not the
            # current mesh's: a trainer running on a shrunken sub-mesh
            # can then prewarm the grow direction too (the 4→8 leg of
            # the live-resize arc). Model axes keep their sizes; dp
            # absorbs the world change — the same mesh live_resize
            # will build.
            mesh_n = self._target_mesh(world_n)
            repl = NamedSharding(mesh_n, P())
            data_sh = NamedSharding(mesh_n, self._batch_sharding.spec)
            state_sh, why = self._transplant_shardings(mesh_n)
            if state_sh is None:
                raise ValueError("world %d: uncomputable target "
                                 "spans: %s" % (world_n, why))
        jitted = jax.jit(
            self._raw_step(),
            in_shardings=(state_sh, data_sh, repl),
            out_shardings=(state_sh, repl),
            donate_argnums=(0,))
        lowered = jitted.lower(
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                self.train_state),
            self._example_batch_sds, self._example_rng_sds)
        h = hashlib.sha256()
        h.update(jax.version.__version__.encode())
        h.update(lowered.as_text().encode())
        adopt = functools.partial(
            self._aot_step, key=self._step_key(mesh_n, state_sh, data_sh),
            repl=repl, jit_fallback=jitted)
        return lowered, h.hexdigest()[:24], adopt

    def step_scope_table(self):
        """{HLO instruction name: op_name} of the step this trainer runs
        on its current mesh: what `obs.devtime` needs to lay a device
        trace's operations against the program's `jax.named_scope`s (a
        trace names an operation, not the scope it was traced under).
        Built on the first call and kept for that mesh — the step is
        lowered as `_build_step` lowers it and compiled, which with the
        persistent compile cache on is a load — and NEVER on the training
        path: only a reader of a profile calls this (`devtime.register`).
        {} before the first step, whose batch names the shapes."""
        if self._example_batch_sds is None:
            return {}
        key = self._step_key(self.mesh, self._state_shardings,
                             self._batch_sharding)
        if key not in self._scope_tables:
            self._scope_tables[key] = obs_devtime.op_names(
                self._step_lowered()[0].compile().as_text())
        return self._scope_tables[key]

    def _prewarm_in_scope(self):
        """Same family as _live_scope_check: prewarm covers any mesh
        the in-place reshape can rebuild (model axes welcome — the AOT
        step is lowered with the transplanted state shardings); only
        multi-process worlds and unreproducible topologies are out."""
        if self._example_batch_sds is None:
            return "needs the batch structure (call after a train_step)"
        if jax.process_count() > 1:
            return "multi-process world"
        bad = [a for a in self.mesh.axis_names
               if a not in ("dp", "tp", "sp", "pp", "ep")]
        if bad:
            return ("mesh axes %s (hybrid/custom topology) cannot be "
                    "rebuilt in place" % (bad,))
        return None

    def prewarm_resize_compiles(self, world_sizes, block=True):
        """Compile the train step for OTHER world sizes and serialize
        the executables under compile_cache.aot_dir(), so the
        next resize restart LOADS its step instead of compiling it
        (picked up automatically at the restarted trainer's first
        train_step). Scope: single-process trainers on any
        make_mesh-shaped mesh — model axes keep their sizes and dp
        absorbs the world change, with state shardings transplanted
        (see _live_scope_check). Sizes out of range, not divisible by
        the model-parallel factor, or not dividing the batch are
        skipped with a log line. ``block=False`` runs on a background
        thread. Returns the target sizes (the compiled subset when
        blocking)."""
        import pickle

        why = self._prewarm_in_scope()
        if why is not None:
            logger.info("prewarm: %s — skipped", why)
            return []
        out_dir = compile_cache.aot_dir()
        devices = jax.devices()  # targets may exceed the CURRENT mesh
        current = len(list(self.mesh.devices.flat))
        # the DATA-SHARDED axis of the example batch (under grad
        # accumulation the leading axis is the microbatch count, and
        # the rows sit on axis 1 — follow the sharding spec, not a
        # hardcoded axis 0)
        spec = tuple(self._batch_sharding.spec)
        axis_index = 0
        for i, s in enumerate(spec):
            if s == DATA_AXIS or (isinstance(s, tuple) and DATA_AXIS in s):
                axis_index = i
                break
        batch_dim = jax.tree_util.tree_leaves(
            self._example_batch_sds)[0].shape[axis_index]
        # rows split over dp only; the model-parallel factor is fixed
        # across the resize, so world n implies dp = n / model_n
        model_n = 1
        for a in self.mesh.axis_names:
            if a != DATA_AXIS:
                model_n *= int(self.mesh.shape[a])
        targets = []
        for n in sorted(set(int(w) for w in world_sizes)):
            if n == current:
                continue
            if n < 1 or n > len(devices):
                logger.info("prewarm: world %d outside this process's "
                            "1..%d devices — skipped", n, len(devices))
                continue
            if n % model_n:
                logger.info("prewarm: world %d not divisible by the "
                            "model-parallel factor %d — skipped", n,
                            model_n)
                continue
            if batch_dim % (n // model_n):
                logger.info("prewarm: world %d (dp=%d) does not divide "
                            "the sharded batch dim %d — skipped", n,
                            n // model_n, batch_dim)
                continue
            targets.append(n)

        def compile_all():
            from jax.experimental import serialize_executable as se
            os.makedirs(out_dir, exist_ok=True)
            done = []
            for n in targets:
                try:
                    t0 = time.perf_counter()
                    lowered, fp, adopt = self._step_lowered(n)
                    compiled = lowered.compile()
                    payload, in_tree, out_tree = se.serialize(compiled)
                    path = os.path.join(out_dir,
                                        "step_w%d_%s.pkl" % (n, fp))
                    tmp = path + ".tmp.%d" % os.getpid()
                    with open(tmp, "wb") as f:
                        pickle.dump({"payload": payload,
                                     "in_tree": in_tree,
                                     "out_tree": out_tree}, f)
                    os.replace(tmp, path)
                    # the file is for a restart; this process keeps
                    # the executable itself for its own live resizes
                    adopt(compiled)
                    done.append(n)
                    logger.info(
                        "prewarm: world-%d step compiled + serialized "
                        "in %.1fs (%s)", n,
                        time.perf_counter() - t0, path)
                except Exception:
                    logger.exception("prewarm for world %d failed", n)
            return done

        if block:
            return compile_all()
        self._prewarm_thread = threading.Thread(
            target=compile_all, daemon=True, name="resize-prewarm")
        self._prewarm_thread.start()
        return targets

    def _try_load_prewarmed_step(self):
        """At the first train_step of an incarnation, and inside a live
        resize to a world whose step this process does not hold ready:
        if an earlier prewarm serialized THIS world size's step
        executable, load it and skip the compile. Returns a
        jit_step-compatible callable or None, and leaves in
        ``self._prewarm_s`` the seconds its two stage spans took:
        ``resize.prewarm_fingerprint``, a trace and lowering of the step
        that only names the artifact, and ``resize.prewarm_load``."""
        import pickle

        self._prewarm_s = 0.0
        if self._prewarm_in_scope() is not None:
            return None
        aot = compile_cache.aot_dir()
        if not os.path.isdir(aot):
            return None  # nothing was ever prewarmed into this cache
        # every early-out from here is a real miss (full compile paid)
        # and counts toward the doctor's compile-cache-cold finding
        n = len(list(self.mesh.devices.flat))
        # any candidate for this world at all? — checked BEFORE paying
        # a trace+lower just to compute the fingerprint (a miss here is
        # the common case, e.g. a same-world restart)
        import glob as glob_mod
        if not glob_mod.glob(os.path.join(aot, "step_w%d_*.pkl" % n)):
            _PREWARM_MISSES.inc()
            return None
        fp = None
        with obs_trace.span("resize.prewarm_fingerprint", stage=True,
                            world=n) as sp_fp:
            try:
                _, fp, adopt = self._step_lowered()
            except Exception:
                logger.exception("prewarm load: lowering failed")
        self._prewarm_s = sp_fp.seconds
        path = os.path.join(aot, "step_w%d_%s.pkl" % (n, fp))
        if fp is None or not os.path.exists(path):
            _PREWARM_MISSES.inc()
            return None
        from jax.experimental import serialize_executable as se
        loaded = None
        with obs_trace.span("resize.prewarm_load", stage=True,
                            world=n) as sp_load:
            try:
                with open(path, "rb") as f:
                    blob = pickle.load(f)
            except (OSError, EOFError, pickle.UnpicklingError):
                logger.exception(
                    "prewarm load: unreadable artifact %s (falling back "
                    "to the normal compile)", path)
            else:
                # NOT guarded: the fingerprint matched (same jax, same
                # lowered step), so an executable that will not
                # deserialize is a bug to surface, not a cache miss to
                # count. A sub-mesh executable must be told its devices,
                # or it expects one shard per process device.
                loaded = se.deserialize_and_load(
                    blob["payload"], blob["in_tree"], blob["out_tree"],
                    execution_devices=list(self.mesh.devices.flat))
        self._prewarm_s += sp_load.seconds
        if loaded is None:
            _PREWARM_MISSES.inc()
            return None
        logger.info("resize prewarm HIT: world-%d step loaded from %s in "
                    "%.2fs (compile skipped)", n, path, sp_load.seconds)
        _PREWARM_HITS.inc()
        return adopt(loaded)

    def local_batch_slice(self, full_batch):
        """Slice a FULL global batch down to the rows this process must
        supply (the complement of shard_batch): contiguous lo:hi under
        pure dp; every row when a model axis (tp/sp) crosses hosts."""
        def cut(x):
            return np.concatenate([x[a:b] for a, b in
                                   self._host_row_spans], axis=0)
        return jax.tree_util.tree_map(cut, full_batch)

    def shard_batch(self, host_batch):
        """Turn per-host numpy arrays into a globally-sharded jax.Array over
        the dp axis (multi-host safe)."""
        if jax.process_count() > 1:
            return jax.tree_util.tree_map(
                lambda x: jax.make_array_from_process_local_data(
                    self._batch_sharding, x), host_batch)
        return jax.device_put(host_batch, self._batch_sharding)

    def place_batch(self, host_batch):
        """Per-host rows -> the device batch exactly as train_step feeds
        it to the step (microbatch-major under gradient accumulation).
        Public so an entry point can report which devices really hold
        its batch."""
        if self._grad_accum > 1:
            k = self._grad_accum
            host_batch = jax.tree_util.tree_map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]),
                host_batch)
        return self.shard_batch(host_batch)

    @property
    def resize_timing(self):
        """This incarnation's per-stage timing record (compile_s,
        first_step_s, restore_s, ...; docs/elastic_resize.md) — a copy."""
        return dict(self._resize_timing)

    def _first_step(self, batch, rng):
        """The first step of an incarnation, and the first after every
        live_resize() (which re-arms ``_stamp_first_step``): the resize
        downtime's last stages, as stage spans in the trace of the
        resize they end. ``resize.first_dispatch`` is the call of the
        step (trace, lowering, compile or cache load, enqueue: its tags
        say how much of each), ``resize.first_result`` the wait for the
        first real step — a block_until_ready that costs nothing the
        caller would not pay anyway, once per resize (the account of what
        the reshard moved runs at its start, while the device works)."""
        prewarm_s = 0.0
        if self._example_batch_sds is None:
            self._example_batch_sds, self._example_rng_sds = \
                jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    (batch, rng))
            loaded = self._try_load_prewarmed_step()
            prewarm_s = self._prewarm_s
            if loaded is not None:
                self._jit_step = loaded
        with obs_trace.span("resize.first_step", stage=True,
                            parent=self._resize_trace):
            with obs_trace.span("resize.first_dispatch",
                                stage=True) as sp_dispatch:
                _jax_stages.intervals = seen = []
                try:
                    self.train_state, loss = self._jit_step(
                        self.train_state, batch, rng)
                finally:
                    _jax_stages.intervals = None
                sp_dispatch.tag(**_jax_stage_seconds(seen))
            with obs_trace.span("resize.first_result",
                                stage=True) as sp_result:
                sp_put, placements = self._put_account
                self._put_account = (None, None)
                if placements is not None:
                    # what the reshard had to move, worked out while the
                    # device runs the step: host time the wait hides
                    sp_put.tag(bytes_moved=_bytes_moved(*placements))
                jax.block_until_ready(loss)
        self._stamp_first_step = False
        self._resize_trace = None
        self._resize_timing["compile_s"] = prewarm_s + sp_dispatch.seconds
        self._resize_timing["first_step_s"] = sp_result.seconds
        self._resize_timing["t_first_step"] = time.time()
        # close the pause HERE so the published ledger snapshot
        # already carries the full resize_pause for this arc
        obs_ledger.LEDGER.transition("compute")
        self._publish_resize_timing()
        obs_events.emit("resize.first_step",
                        rank=self.env.global_rank,
                        compile_s=self._resize_timing["compile_s"],
                        first_step_s=self._resize_timing["first_step_s"])
        return loss

    _STEP_WINDOW = 8  # intervals kept for the cadence estimate

    def train_step(self, host_batch, rng=None):
        t0 = time.perf_counter()
        if not self._stamp_first_step:
            # steady state: the step boundary re-claims the clock for
            # compute. After a resize the clock stays on resize_pause /
            # restore until the first step's result is READY (stamped
            # in _first_step) — the ledger's pause must agree with
            # measure_resize, which measures to first-step completion,
            # not dispatch.
            obs_ledger.LEDGER.transition("compute")
        if self._last_step_start is not None:
            self._step_intervals.append(t0 - self._last_step_start)
            del self._step_intervals[:-self._STEP_WINDOW]
        self._last_step_start = t0
        if rng is None:
            rng = jax.random.PRNGKey(self._host_step)
        batch = self.place_batch(host_batch)
        if self._stamp_first_step:
            loss = self._first_step(batch, rng)
        else:
            self.train_state, loss = self._jit_step(self.train_state,
                                                    batch, rng)
        self._host_step += 1
        step_s = time.perf_counter() - t0
        self._step_times.append(step_s)
        _STEP_MS.observe(step_s * 1e3)
        if self._live_watcher is not None:
            # a published live-resize intent is handled HERE, at a step
            # boundary — the drain point of the drain/reshard/swap loop
            self._maybe_live_resize()
        if self._coord_stop is not None:
            if not self._coord_stop.started:
                # first boundary: the baseline is final (resume() ran
                # before any step), so stale keys are now rejectable
                self._coord_stop.min_step = max(self._coord_stop.min_step,
                                                self._host_step - 1)
                self._coord_stop.start()
            if self._preempted:
                self._coord_stop.request(self._host_step)
                if self._preempt_t0 is None:
                    self._preempt_t0 = time.monotonic()
            stop = self._coord_stop.stop_at
            if stop is not None and self._host_step >= stop:
                self._coordinated_save_and_raise(missed=self._host_step
                                                 > stop)
            elif self._preempted and (time.monotonic() - self._preempt_t0
                                      > self._coord_deadline):
                # no agreed stop within the deadline (store unreachable,
                # rank 0 dead): the local emergency path is strictly
                # better than training until SIGKILL with no checkpoint
                logger.warning("no coordinated stop within %.0fs; "
                               "falling back to the local emergency "
                               "save", self._coord_deadline)
                self._emergency_save()
        elif self._preempted:
            self._emergency_save()
        return loss

    # -- live resize (in-place reshard, no kill/respawn) ---------------------
    #
    # Stop-resume pays detect + kill + barrier + restore + compile per
    # membership change. A SURVIVING process holds the state on device,
    # a committed host snapshot on the peer plane, and (with prewarm)
    # the new world's AOT executable — so the only genuinely required
    # work is: drain to a step boundary, rebuild the mesh, reshard the
    # pytree, swap the step executable. Scope: single-process trainers
    # on a pure-dp mesh with replicated state (the JAX runtime cannot
    # re-run jax.distributed.initialize, so cross-process worlds keep
    # stop-resume). Protocol + the placed reshard engine live in
    # runtime/live_resize.py; docs/elastic_resize.md has the ladder.

    # everything the new mesh derives — snapshotted before a live
    # resize so ANY failure rolls back to a numerically untouched
    # trainer and the stop-resume ladder takes over
    _MESH_BOUND_ATTRS = ("mesh", "_batch_sharding_early",
                         "per_device_batch", "_host_row_spans",
                         "per_host_batch", "_repl", "_batch_sharding",
                         "_state_shardings", "_jit_step", "train_state")

    def _snapshot_bindings(self):
        return {a: getattr(self, a) for a in self._MESH_BOUND_ATTRS}

    def _restore_bindings(self, saved):
        for a, v in saved.items():
            setattr(self, a, v)

    def _target_mesh(self, n_devices, mesh_shape=None):
        """The live-resize target mesh over the first ``n_devices``
        process devices: ``mesh_shape`` ({axis: size} factors; dp may
        be omitted and fills the remainder) or, by default, the current
        mesh's model-parallel axes with dp rescaled. Raises ValueError
        when the factorization cannot be built (non-divisible,
        unknown axes, hybrid dcn topology)."""
        known = ("dp", "tp", "sp", "pp", "ep")
        if mesh_shape:
            bad = [a for a in mesh_shape if a not in known]
            if bad:
                raise ValueError("target mesh axes %s not buildable "
                                 "in place" % (bad,))
            kw = {a: int(s) for a, s in mesh_shape.items()
                  if a != DATA_AXIS}
            dp = mesh_shape.get(DATA_AXIS)
            if dp is not None:
                kw["dp"] = int(dp)
        else:
            bad = [a for a in self.mesh.axis_names if a not in known]
            if bad:
                raise ValueError(
                    "mesh axes %s (hybrid/custom topology) cannot be "
                    "rebuilt in place" % (bad,))
            kw = {a: int(self.mesh.shape[a])
                  for a in self.mesh.axis_names if a != DATA_AXIS}
        return make_mesh(devices=jax.devices()[:n_devices], **kw)

    def _transplant_shardings(self, new_mesh, shardings=None):
        """(shardings-on-new_mesh, reason): every state leaf's
        PartitionSpec re-rooted onto ``new_mesh``, or (None, why) when
        some leaf's target spans are not computable there — the reason
        names the leaf, the spec, and the failing axis/dim, and is what
        the fallback event journals."""
        from edl_tpu.parallel.sharding import spec_transplant_reason
        src = self._state_shardings if shardings is None else shardings
        reasons = []

        def move(path, sh, leaf):
            spec = getattr(sh, "spec", None)
            if spec is None:
                spec = P()
            why = spec_transplant_reason(spec, getattr(leaf, "shape",
                                                       ()), new_mesh)
            if why is not None:
                reasons.append("%s: %s"
                               % (checkpoint_mod._path_key(path), why))
            return NamedSharding(new_mesh, spec)

        out = jax.tree_util.tree_map_with_path(move, src,
                                               self.train_state)
        if reasons:
            return None, "; ".join(reasons[:3])
        return out, None

    def _live_scope_check(self, n_devices, mesh_shape=None):
        """Reason string when an in-place reshape to ``n_devices``
        (optionally a specific ``mesh_shape`` factorization) is
        impossible, else None. The same family as _prewarm_in_scope.
        The predicate is span computability, not replication: any state
        sharding whose PartitionSpecs transplant onto the target mesh
        (axes present, dims divisible) is in scope — a tp-degree
        change, a pp re-split, an expert re-balance all qualify; what
        does not (multi-process worlds, hybrid topologies, indivisible
        dims) degrades to stop-resume with the reason journaled."""
        if jax.process_count() > 1:
            return ("multi-process world (jax.distributed cannot "
                    "re-initialize in place)")
        n_all = len(jax.devices())
        if n_devices < 1 or n_devices > n_all:
            return ("target world %d outside this process's 1..%d "
                    "devices" % (n_devices, n_all))
        try:
            target = self._target_mesh(n_devices, mesh_shape)
        except ValueError as e:
            return str(e)
        n_rows = 1
        spec0 = data_sharding(target).spec
        spec0 = spec0[0] if spec0 else None
        for ax in ((spec0,) if isinstance(spec0, str)
                   else tuple(spec0 or ())):
            n_rows *= target.shape[ax]
        if self.total_batch_size % n_rows:
            return ("total batch %d not divisible by target dp=%d"
                    % (self.total_batch_size, n_rows))
        _, why = self._transplant_shardings(target)
        if why is not None:
            return "uncomputable target spans: %s" % why
        return None

    def _reshard_tree(self, tree, shardings, account=False):
        """Reshard the live pytree onto ``shardings``. Fully-addressable
        leaves (the single-process live scope) take the zero-wire fast
        path, :func:`_reshard_local`: the new placement is laid out
        straight from the live device arrays, kept shards in place and
        the crossing ones in one batched copy (``resize.device_put
        .dispatch``: planning and issuing, until the last call returns;
        ``resize.device_put.wait``: until the result is ready).
        Anything else runs the placed ladder — local-span paste, peer
        range-reads at the committed version, per-span FS fill
        (live_resize.reshard_placed). Returns (new_tree, stats); the
        fast path's stats count ``arrays_crossed`` (arrays handed to the
        runtime to copy across devices) and ``leaves_leafwise`` (leaves
        that had to be re-sliced and went through ``jax.device_put`` one
        by one), and ``account`` adds ``placements`` there: the
        arguments of :func:`_bytes_moved`, which waits for the first
        step."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if self._fully_addressable(tree):
            targets = jax.tree_util.tree_leaves(shardings)
            if len(targets) != len(leaves):
                # a sharding that stands for a whole subtree
                targets = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                    lambda sh, sub: jax.tree_util.tree_map(
                        lambda _: sh, sub), shardings, tree))
            with obs_trace.span("resize.device_put.dispatch", stage=True):
                moved, crossed, leafwise = _reshard_local(
                    leaves, targets, self._reshard_programs)
                out = jax.tree_util.tree_unflatten(treedef, moved)
            with obs_trace.span("resize.device_put.wait", stage=True):
                jax.block_until_ready(out)
            stats = {"source": "local", "leaves": len(leaves),
                     "local_bytes": sum(int(getattr(x, "nbytes", 0))
                                        for x in leaves),
                     "peer_bytes": 0, "peers": 0, "fs_keys": [],
                     "arrays_crossed": crossed, "leaves_leafwise": leafwise}
            if account:
                # objects the leaves hold already, in three lists: the
                # pause allocates next to nothing for the account
                stats["placements"] = (
                    [getattr(x, "aval", None) for x in leaves],
                    [getattr(x, "sharding", None) for x in leaves],
                    targets)
            return out, stats
        from edl_tpu.runtime import live_resize as live_mod
        version = (self._state_server.version
                   if self._state_server is not None else None)
        out, stats = live_mod.reshard_placed(
            tree, shardings, coord=self.coord, ckpt=self._ckpt,
            version=version,
            self_endpoint=(self._state_server.endpoint
                           if self._state_server is not None else None))
        return out, dict(stats, leaves=len(leaves))

    def live_resize(self, n_devices, mesh_shape=None):
        """Reshape the mesh to ``n_devices`` IN PLACE: wait for the
        in-flight async save where the reshard reads its result (below),
        rebuild the mesh (``mesh_shape``
        picks a (dp, tp, pp, ep) factorization — e.g. the cluster
        generator's roofline choice — default: keep the current model
        axes and rescale dp), transplant every state PartitionSpec onto
        it, reshard params + optimizer state, take the new world's step
        (the executable this process already holds for it — prewarmed
        here, or left behind when the job last ran there — else the
        prewarmed artifact on disk, else a fresh jit), and resume — the
        process never exits, so the kill/barrier/restore stages of the
        stop-resume budget are eliminated. Stamps a fresh
        ``_resize_timing`` record (mode "live", with the new
        ``reshard_s`` stage); the next train_step stamps
        compile/first-step and republishes it.

        The wait for an async save's persist is taken only when some
        state leaf is not fully addressable: that reshard (the placed
        ladder) range-reads peers and the file system at the committed
        version. Fully addressable state reshards from the live device
        arrays, so a persist in flight is left running: it commits and
        publishes on its own thread as after any save, and the next
        ``save()``, ``close()``, the preemption guard or ``atexit``
        collects its handle (and logs its failure). The span
        ``resize.drain`` is then short; the root span's tag ``drain``
        and ``_resize_timing["drain"]`` say which it was — "deferred",
        "waited", or "idle" (nothing in flight) — and
        ``edl_resize_drain_deferred_total`` counts the first.
        ``_resize_timing["version"]`` names the version the state server
        serves at that moment: the last COMMIT, which after a deferred
        drain may be the save before the one still being written.

        On ANY failure the trainer is rolled back to the old mesh —
        numerically untouched, still training — and LiveResizeError is
        raised; the caller (the intent ack path, or an operator) lets
        the stop-resume ladder handle the membership change instead.
        Chaos fault points: ``resize.live.drain`` (before any wait)
        and ``resize.live.reshard`` (after the new mesh is built,
        before any state moves)."""
        from edl_tpu.utils.errors import LiveResizeError

        n_devices = int(n_devices)
        t_start = time.time()
        old_n = len(list(self.mesh.devices.flat))
        start_id = obs_events.emit("resize.live.start",
                                   rank=self.env.global_rank,
                                   from_devices=old_n,
                                   to_devices=n_devices)
        why = self._live_scope_check(n_devices, mesh_shape)
        if why is not None:
            # scope=True marks "rejected before any state moved" (the
            # doctor's reshard_fallback finding), vs a mid-flight
            # rollback below
            obs_events.emit("resize.live.fallback", cause=start_id,
                            rank=self.env.global_rank, reason=why,
                            scope=True,
                            from_devices=old_n, to_devices=n_devices)
            raise LiveResizeError("live resize out of scope: %s" % why)
        same_shape = True
        if mesh_shape:
            same_shape = all(
                int(self.mesh.shape.get(a, 1)) == int(s)
                for a, s in mesh_shape.items())
        if n_devices == old_n and same_shape:
            obs_events.emit("resize.live.done", cause=start_id,
                            rank=self.env.global_rank, noop=True,
                            from_devices=old_n, to_devices=n_devices)
            return {"mode": "live", "noop": True,
                    "from_devices": old_n, "to_devices": n_devices}
        saved = self._snapshot_bindings()
        left_key = None
        if self._example_batch_sds is not None:
            left_key = self._step_key(self.mesh, self._state_shardings,
                                      self._batch_sharding)
        # training is paused from here until the first post-reshard
        # step result (train_step closes the pause when it stamps);
        # the drain below nests ckpt_block over this and returns here
        obs_ledger.LEDGER.transition("resize_pause")
        with obs_trace.span("resize.live", stage=True,
                            from_devices=old_n,
                            to_devices=n_devices) as sp_live:
            try:
                with obs_trace.span("resize.drain", stage=True) as sp_drain:
                    if faults.PLANE is not None:
                        faults.PLANE.fire("resize.live.drain",
                                          from_devices=str(old_n),
                                          to_devices=str(n_devices))
                    # the wait is taken only where its result is read:
                    # the placed ladder reads peers and the file system
                    # at the committed version, which must not move
                    # across the reshard. Fully addressable state
                    # reshards from the live device arrays and the
                    # persist reads only its own host copies: it is left
                    # running, for the next drain to collect
                    local = self._fully_addressable(self.train_state)
                    drain = "idle"
                    if self._ckpt is not None and self._ckpt.persisting():
                        drain = "deferred" if local else "waited"
                    sp_live.tag(drain=drain)
                    if not local:
                        self.wait_for_save()
                    elif drain == "deferred":
                        _DRAIN_DEFERRED.inc()
                    self.mirror_model_counters()
                with obs_trace.span("resize.mesh", stage=True) as sp_mesh:
                    new_mesh = self._target_mesh(n_devices, mesh_shape)
                    if faults.PLANE is not None:
                        faults.PLANE.fire("resize.live.reshard",
                                          from_devices=str(old_n),
                                          to_devices=str(n_devices))
                    new_shardings, why_t = self._transplant_shardings(
                        new_mesh, saved["_state_shardings"])
                    if new_shardings is None:
                        raise LiveResizeError(
                            "uncomputable target spans: %s" % why_t)
                    self._bind_mesh(new_mesh)
                with obs_trace.span("resize.device_put", stage=True) as sp_put:
                    inflight = (sp_put.recorded and self._ckpt is not None
                                and self._ckpt.persisting())
                    self.train_state, reshard_stats = self._reshard_tree(
                        self.train_state, new_shardings,
                        account=sp_put.recorded)
                    put_tags = {"source": reshard_stats["source"],
                                "bytes": (reshard_stats["local_bytes"]
                                          + reshard_stats["peer_bytes"])}
                    if sp_put.recorded:
                        put_tags.update(leaves=reshard_stats["leaves"],
                                        persist_inflight=inflight)
                        # the fast path says how the state crossed
                        put_tags.update(
                            (tag, reshard_stats[tag])
                            for tag in ("arrays_crossed", "leaves_leafwise")
                            if tag in reshard_stats)
                        if "placements" not in reshard_stats:
                            # the placed ladder counted what it fetched
                            put_tags["bytes_moved"] = (
                                reshard_stats["peer_bytes"]
                                + reshard_stats.get("parity_bytes", 0))
                    sp_put.tag(**put_tags)
                self._state_shardings = new_shardings
                # where the new world's step comes from: the table
                # ("memory": nothing to build, name, load or trace), a
                # prewarmed artifact ("disk"), or a fresh jit that the
                # first dispatch compiles ("compile")
                prewarm, prewarm_s, step_source = "n/a", 0.0, "compile"
                with obs_trace.span("resize.build_step",
                                    stage=True) as sp_build:
                    ready = None
                    if left_key is not None:
                        ready = self._ready_steps.get(self._step_key(
                            new_mesh, new_shardings, self._batch_sharding))
                    self._jit_step = ready or self._build_step()
                if ready is not None:
                    prewarm, step_source = "hit", "memory"
                    _STEP_REUSES.inc()
                elif left_key is not None:
                    loaded = self._try_load_prewarmed_step()
                    prewarm_s = self._prewarm_s
                    if loaded is not None:
                        self._jit_step = loaded
                        prewarm, step_source = "hit", "disk"
                    else:
                        prewarm = "miss"
                if left_key is not None and not self._stamp_first_step:
                    # the step that ran on the world being left stays
                    # ready (a jit wrapper keeps its compiled program)
                    self._ready_steps[left_key] = saved["_jit_step"]
                sp_live.tag(prewarm=prewarm, step_source=step_source)
            except Exception as e:  # noqa: BLE001 — ANY failure rolls back
                self._restore_bindings(saved)
                # black-box the rollback: the evidence (drain/reshard spans,
                # fault firings) lives in rings this incarnation may not
                # survive once the stop-resume ladder takes over
                obs_flight.dump("live_resize_rollback", e)
                reason = "%s: %s" % (type(e).__name__, e)
                obs_events.emit("resize.live.fallback", cause=start_id,
                                rank=self.env.global_rank, reason=reason,
                                from_devices=old_n, to_devices=n_devices)
                logger.exception("live resize %d -> %d failed; rolled back "
                                 "to the old mesh (stop-resume takes over)",
                                 old_n, n_devices)
                if isinstance(e, LiveResizeError):
                    raise
                raise LiveResizeError(
                    "live resize %d -> %d failed (%s); rolled back"
                    % (old_n, n_devices, reason)) from e
            drain_s = sp_drain.seconds
            # everything between the drain and the first step: the sum of
            # its stage spans (what no span covers is a glob and two
            # attribute stores)
            reshard_s = (sp_mesh.seconds + sp_put.seconds + sp_build.seconds
                         + prewarm_s)
            # a live resize begins a new timing "incarnation": the record
            # carries the same stages measure_resize reads, with
            # t_construct = the moment training paused, so the driver's
            # after_ts filter works unchanged
            self._resize_timing = {
                "t_construct": t_start, "mode": "live",
                "t_resume_start": t_start,
                "drain_s": round(drain_s, 6), "drain": drain,
                "reshard_s": round(reshard_s, 6),
                "from_devices": old_n, "to_devices": n_devices,
                "from_mesh": {str(a): int(s) for a, s in
                              zip(saved["mesh"].axis_names,
                                  saved["mesh"].devices.shape)},
                "prewarm": prewarm, "step_source": step_source,
                "restore_source": reshard_stats["source"],
                "restore_bytes": (reshard_stats["local_bytes"]
                                  + reshard_stats["peer_bytes"]),
                "restore_peers": reshard_stats["peers"],
            }
            if self._state_server is not None \
                    and self._state_server.version is not None:
                self._resize_timing["version"] = self._state_server.version
            self._stamp_first_step = True
            self._resize_trace = [sp_live.trace_id, sp_live.span_id]
            self._put_account = (sp_put, reshard_stats.get("placements"))
            obs_events.emit("resize.live.done", cause=start_id,
                            rank=self.env.global_rank,
                            from_devices=old_n, to_devices=n_devices,
                            reshard_s=reshard_s, prewarm=prewarm,
                            step_source=step_source,
                            source=reshard_stats["source"])
            logger.info("live resize %d -> %d: drain %.3fs (%s) reshard "
                        "%.3fs (%s, prewarm %s, step from %s) — process "
                        "stayed alive", old_n, n_devices, drain_s, drain,
                        reshard_s, reshard_stats["source"], prewarm,
                        step_source)
            return dict(self._resize_timing)

    def enable_live_resize(self, who=None):
        """Join the live-resize protocol: advertise the TTL-leased
        capability key (only while in scope — a dummy or multi-process
        trainer never advertises, so the generator's eligibility check
        routes it to stop-resume) and watch for prepare intents
        addressed to this participant. train_step handles a pending
        intent at the next step boundary: drain → reshard → swap →
        ack. Returns self."""
        from edl_tpu.runtime import live_resize as live_mod
        if self.coord is None:
            raise ValueError("live resize needs a coordination store "
                             "(coord=)")
        self._live_who = (str(who) if who is not None
                          else (self.env.pod_id
                                or "r%d" % self.env.global_rank))
        why = self._live_scope_check(len(list(self.mesh.devices.flat)))
        if why is None:
            self._live_register = live_mod.advertise_capability(
                self.coord, self._live_who,
                info={"devices": len(jax.devices()),
                      "rank": self.env.global_rank})
        else:
            logger.info("live resize out of scope (%s); capability not "
                        "advertised — stop-resume only", why)
            self._live_register = None
        self._live_watcher = live_mod.LiveResizeWatcher(self.coord,
                                                        self._live_who)
        return self

    def _maybe_live_resize(self):
        """Handle a pending prepare intent at this step boundary:
        live_resize + ack ok, or roll back + nack (the coordinator then
        aborts and stop-resume runs). Never raises — a failed live
        resize leaves the trainer training on its old mesh until the
        launcher's kill arrives."""
        from edl_tpu.runtime import live_resize as live_mod
        from edl_tpu.utils.errors import LiveResizeError
        rec = self._live_watcher.pending()
        if rec is None:
            return
        intent_id = rec.get("id")
        target = rec.get("devices")
        if isinstance(target, dict):
            target = target.get(self._live_who)
        mesh_shape = rec.get("mesh")  # generator's factorization, opt.
        ok, reason, info = False, None, None
        try:
            if target is None:
                raise LiveResizeError(
                    "intent %s carries no device target for %s"
                    % (intent_id, self._live_who))
            stats = self.live_resize(int(target),
                                     mesh_shape=mesh_shape)
            ok = True
            info = {"world": stats.get("to_devices"),
                    "reshard_s": stats.get("reshard_s"),
                    "prewarm": stats.get("prewarm"),
                    "step": self._host_step}
        except LiveResizeError as e:
            reason = str(e)
        self._live_watcher.done(intent_id)
        try:
            live_mod.write_ack(self.coord, self._live_who, intent_id,
                               ok, reason=reason, info=info)
        except Exception:
            logger.exception("live resize: ack write failed")

    # -- the high-level loop -------------------------------------------------

    def fit(self, epochs, batches_fn, eval_fn=None, resume=True,
            preemption_exit_code=101, log_fn=None, signals=None,
            coordinated=None):
        """The full elastic training loop in one call: arm the
        preemption handler, resume from the newest checkpoint, iterate
        epochs (begin → train_step over ``batches_fn(epoch)`` → end +
        save), rank-0 eval, and the final SUCCEED status report.

        batches_fn(epoch) -> iterable of per-host batches (use
        local_batch_slice/an input pipeline shard for multi-host).
        eval_fn(trainer, epoch) runs on rank 0 after each epoch's save.
        On preemption the emergency checkpoint is already written; the
        process exits with ``preemption_exit_code`` (the exit-101
        restart convention) — pass None to get PreemptedError raised
        instead. ``signals``/``coordinated`` forward to
        install_preemption_handler; a handler the caller armed earlier
        is left untouched. Returns {"resumed", "steps", "final_loss",
        "world"}.
        """
        from edl_tpu.utils.errors import PreemptedError

        if not self._preempt_armed:
            self.install_preemption_handler(signals=signals,
                                            coordinated=coordinated)
        # arm the black box for this incarnation: any death path out of
        # fit() (preemption exit, unhandled exception via the chained
        # excepthook) leaves a blackbox/v1 artifact behind
        if obs_flight.RECORDER is None:
            obs_flight.install("trainer_r%d" % self.env.global_rank,
                               coord=self.coord)
        obs_flight.RECORDER.register_provider(
            "resize_timing", lambda: dict(self._resize_timing))
        # the fleet view is built from obs_* publications, and the
        # launcher's PodServer publisher only covers the supervisor
        # process — the ledger/step counters that make goodput live
        # HERE, so the training process ships its own registry
        publisher = None
        if self.coord is not None:
            from edl_tpu.obs.publisher import MetricsPublisher
            pod_key = ("%s_r%d" % (self.env.pod_id,
                                   self.env.global_rank)
                       if self.env.pod_id
                       else "trainer_r%d" % self.env.global_rank)
            publisher = MetricsPublisher(self.coord, pod_key).start()
        resumed = self.resume() if resume else False
        start_epoch = self.state.next_epoch() if resumed else 0
        say = log_fn or logger.info
        say("fit: rank=%d world=%d start_epoch=%d resumed=%s"
            % (self.env.global_rank, self.world_size, start_epoch,
               resumed))
        loss = None
        try:
            for epoch in range(start_epoch, epochs):
                self.begin_epoch(epoch)
                if epoch == epochs - 1:
                    # AFTER begin_epoch: it reports RUNNING, which would
                    # clobber the scale-out-stopping NEARTHEEND verdict
                    self.report_status(train_status_mod.TrainStatus
                                       .NEARTHEEND)
                for batch in batches_fn(epoch):
                    loss = self.train_step(batch)
                self.end_epoch(save=True)
                say("fit: epoch %d done step=%d loss=%s"
                    % (epoch, self.global_step,
                       "%.5f" % float(loss) if loss is not None
                       else "n/a"))
                if eval_fn is not None and self.env.global_rank == 0:
                    eval_fn(self, epoch)
        except PreemptedError as e:
            # the exit-101 path never reaches sys.excepthook (SystemExit
            # is special-cased), so the box must be dumped here
            obs_flight.dump("preempted", e)
            say("fit: preempted: %s" % e)
            if preemption_exit_code is None:
                raise
            import sys
            sys.exit(preemption_exit_code)
        finally:
            # whatever happens, the training thread's clock is no
            # longer compute; close the interval so the final publish
            # (or the black box) carries the full attribution
            obs_ledger.LEDGER.transition("idle")
            obs_ledger.LEDGER.flush()
            if publisher is not None:
                publisher.stop()  # final flush ships the full ledger
        self.report_status(train_status_mod.TrainStatus.SUCCEED)
        return {"resumed": resumed, "steps": self.global_step,
                "final_loss": None if loss is None else float(loss),
                "world": self.world_size}

    # -- preemption (grace-window emergency checkpoint) ----------------------

    def install_preemption_handler(self, signals=None, coordinated=None):
        """Arm the grace-window emergency checkpoint.

        The launcher's kill path is process-tree SIGTERM, then SIGKILL
        after a grace period (train_process.terminate_trainers; k8s pod
        deletion behaves the same). The handler only sets a flag —
        async-signal-safe, and a save cannot run mid-XLA-dispatch — and
        the next step/epoch boundary writes a checkpoint at the CURRENT
        step, then raises PreemptedError. The restart resumes the model
        at that step and RE-RUNS the interrupted epoch from its start
        (State.next_epoch): no optimizer progress is lost, but batches
        the interrupted epoch already consumed are replayed (epoch-
        granular loops; an ElasticReader loop resumes exactly instead,
        via State.data_checkpoint record ranges). Returns self so it
        chains after construction.

        Multi-host: ``coordinated`` (default: auto-on when multi-process
        AND a coordination store is attached) runs the CoordinatedStop
        protocol — a flagged rank publishes its preemption to the store,
        rank 0 publishes an agreed stop step a few steps ahead, and
        EVERY rank stops at that exact boundary, where the normal
        cooperative save (per-host sharded write) is safe even for
        cross-host tp/sp-sharded state. Without a store, preempted ranks
        cannot rendezvous (signals land at different step boundaries, so
        neither a gather nor the sharded-save barrier is safe): with
        replicated(-or-host-only) state, rank 0 alone writes a complete
        dense checkpoint from its local replicas; with cross-host
        SHARDED state the save is skipped and the restart falls back to
        the last epoch-end checkpoint.
        """
        self._guard.install(signals)
        self._preempt_armed = True
        if coordinated is None:
            coordinated = jax.process_count() > 1 and self.coord is not None
        if coordinated and self._coord_stop is None:
            if self.coord is None:
                raise ValueError("coordinated preemption needs a "
                                 "coordination store (coord=)")
            from edl_tpu.runtime.preemption import CoordinatedStop
            # created here, STARTED at the first step boundary — by then
            # any resume() has fixed the baseline step, so a stale
            # stop_at from a prior incarnation can never be accepted
            self._coord_stop = CoordinatedStop(
                self.coord, jax.process_index(),
                stage=self.env.cluster_stage or "default",
                current_step=lambda: self._host_step,
                min_step=self._host_step,
                step_time=self._recent_step_time)
        return self

    def _recent_step_time(self):
        """Mean of the recent start-to-start step intervals (0.0 when
        unknown) — the preemption leader converts watcher poll latency
        into steps. Start-to-start MEAN, not in-call time or a median:
        async jit dispatch returns in milliseconds, and a loop that
        syncs only every k steps shows k-1 tiny gaps plus one gap
        carrying the device time — the mean recovers the true per-step
        cadence where a median would collapse to the dispatch gap."""
        tail = self._step_intervals
        return sum(tail) / len(tail) if tail else 0.0

    def _on_preempt_signal(self, signum, frame):
        self._guard._on_signal(signum, frame)

    @property
    def _preempted(self):
        return self._guard.preempted

    @_preempted.setter
    def _preempted(self, value):
        self._guard.preempted = bool(value)

    @property
    def preempted(self):
        return self._preempted

    def _coordinated_save_and_raise(self, missed=False):
        """All ranks reached the agreed stop step: the normal cooperative
        save (per-host sharded write, or rank-0 dense) is safe here —
        every rank sits at the SAME boundary, so the fs barrier aligns
        and the version numbers match.

        ``missed`` (this rank observed stop_at only after passing it —
        extreme skew): the aligned save is impossible; raise WITHOUT
        saving so the stopped ranks' barrier times out rather than
        committing a mixed-step checkpoint. Any save failure still exits
        via PreemptedError — the restart falls back to the last
        epoch-end checkpoint."""
        from edl_tpu.utils.errors import PreemptedError

        # FIRST drain the in-flight async persist: every coordinated
        # exit below (including the non-saving "missed" one) must leave
        # the previously started version committed, not lost
        self._guard.drain()
        self._coord_stop.stop()
        if missed:
            logger.warning("coordinated stop step %s observed late at "
                           "step %d; skipping the aligned save",
                           self._coord_stop.stop_at, self._host_step)
            self._record_missed_stop_metric()
            raise PreemptedError(
                "preempted; missed the coordinated stop step (%s < %d) — "
                "no emergency save, restart resumes from the last epoch "
                "checkpoint" % (self._coord_stop.stop_at, self._host_step))
        logger.info("coordinated preemption stop at step %d",
                    self._host_step)
        obs_events.emit("resize.coordinated_stop",
                        rank=self.env.global_rank, step=self._host_step)
        self.state.global_step = self.global_step
        self.wait_for_save()
        was_async, self._async_save = self._async_save, False
        try:
            self.save()
        except Exception as e:  # noqa: BLE001
            logger.exception("coordinated emergency save failed")
            raise PreemptedError(
                "preempted; coordinated emergency save failed (%r) — "
                "restart resumes from the last epoch checkpoint" % (e,))
        finally:
            self._async_save = was_async
        raise PreemptedError(
            "preempted (coordinated stop); checkpoint saved at step %d"
            % self._host_step)

    def _record_missed_stop_metric(self):
        """Operators need to SEE when the best-effort coordinated save
        degraded to the epoch fallback (pathological skew — the rank
        overshot the agreed step): a per-rank counter under the metrics
        service, scraped by job_stats (VERDICT r3 weak #8)."""
        if self.coord is None:
            return
        try:
            from edl_tpu.controller import constants
            import json as _json
            key = "preempt_missed_r%d" % self.env.global_rank
            raw = self.coord.get_value(constants.SERVICE_METRICS, key)
            rec = {}
            if raw:
                try:
                    rec = _json.loads(raw)
                except ValueError:
                    rec = {}
            rec = {"count": int(rec.get("count", 0)) + 1,
                   "last_step": self._host_step,
                   "last_stop_at": self._coord_stop.stop_at,
                   "ts": round(time.time(), 1)}
            self.coord.set_server_permanent(constants.SERVICE_METRICS,
                                            key, _json.dumps(rec))
        except Exception:
            logger.exception("missed-stop metric write failed")

    def _state_locally_fetchable(self):
        """True when every state leaf can reach host memory WITHOUT a
        collective (the same predicate to_host_tree_local enforces)."""
        return all(checkpoint_mod.leaf_locally_fetchable(x)
                   for x in jax.tree_util.tree_leaves(self.train_state))

    def _emergency_save(self, already_saved=False):
        """Write the grace-window checkpoint and raise PreemptedError.

        Preempted ranks cannot rendezvous — signals land at different
        step boundaries — so NO cooperative path (collective gather or
        the sharded-save fs barrier) is allowed here. Single process:
        the normal dense save. Multi-process with replicated(-or-host)
        state: rank 0 alone writes a complete dense checkpoint from its
        local replicas. Multi-process with cross-host SHARDED state: no
        single rank holds the model — skip, and the restart falls back
        to the last epoch-end checkpoint."""
        from edl_tpu.utils.errors import PreemptedError

        # drain before ANY exit below — the no-save paths (no ckpt dir,
        # cross-host sharded skip, non-rank-0 wait) must still land the
        # in-flight async version before the process dies
        self._guard.drain()
        if self._ckpt is None:
            raise PreemptedError(
                "preempted at step %d; no checkpoint dir configured — "
                "nothing saved, restart begins fresh" % self._host_step)
        if already_saved:
            raise PreemptedError(
                "preempted; checkpoint saved at step %d" % self._host_step)
        self.state.global_step = self.global_step  # else stale since the
        # last end_epoch — the store/meta snapshot must show real progress
        if jax.process_count() <= 1:
            logger.info("preemption signal: emergency checkpoint at "
                        "step %d", self._host_step)
            self.wait_for_save()
            was_async, self._async_save = self._async_save, False
            try:
                self.save()
            finally:
                self._async_save = was_async
            raise PreemptedError(
                "preempted; checkpoint saved at step %d" % self._host_step)
        if not self._state_locally_fetchable():
            logger.warning("preempted with cross-host sharded state; "
                           "skipping the emergency save (no rank holds "
                           "the full model and ranks cannot rendezvous)")
            raise PreemptedError(
                "preempted at step %d; emergency save skipped (cross-"
                "host sharded state) — restart resumes from the last "
                "epoch checkpoint" % self._host_step)
        if jax.process_index() != 0:
            # best-effort: wait briefly for rank 0's manifest so a fast
            # per-process restart (liveft exit-101) cannot resume an
            # older version than rank 0 does. The launcher's stop-resume
            # path re-barriers the whole cluster and needs no wait.
            # Rank 0 tags its emergency save with meta["emergency"], so
            # the wait keys on THAT — a recent epoch-end checkpoint at a
            # nearby version cannot satisfy it, and a rank-0 commit that
            # landed before we started waiting still does (no burned
            # grace window). In a PARTIAL preemption rank 0 may never
            # have received SIGTERM: then the wait times out and the
            # save simply did not happen — say so.
            # an emergency version must be from THIS preemption event:
            # >= the floor AND newer than the version this incarnation
            # resumed from — a prior event's emergency checkpoint kept
            # by _gc sits exactly at the resumed version and must not
            # satisfy the wait for the current one
            target_floor = self._host_step - 3
            found = False
            try:
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline:
                    vs = self._ckpt.versions()
                    recent = [v for v in vs
                              if v >= target_floor
                              and v > self._resumed_version]
                    if any((self._ckpt.meta(v) or {}).get("emergency")
                           for v in recent):
                        found = True
                        break
                    time.sleep(0.25)
            except Exception:
                logger.exception("waiting for rank-0 emergency manifest "
                                 "failed")
            if found:
                raise PreemptedError(
                    "preempted at step %d; emergency checkpoint is rank "
                    "0's (replicated state) — this rank wrote nothing"
                    % self._host_step)
            raise PreemptedError(
                "preempted at step %d; no rank-0 emergency checkpoint "
                "observed within the grace wait (rank 0 may not have "
                "been preempted) — restart resumes from the latest "
                "committed checkpoint" % self._host_step)
        logger.info("preemption signal: rank-0 local emergency "
                    "checkpoint at step %d", self._host_step)
        self.wait_for_save()
        import json
        state_snapshot = json.loads(self.state.to_json())
        self._ckpt.save(self.global_step,
                        checkpoint_mod.to_host_tree_local(
                            dict(self.train_state)),
                        meta={"state": state_snapshot,
                              "emergency": True})
        self._save_state_to_store(state_snapshot)
        raise PreemptedError(
            "preempted; checkpoint saved at step %d" % self._host_step)

    @property
    def global_step(self):
        return int(self.train_state["step"])

    @property
    def world_size(self):
        return jax.process_count()

    # -- epochs / status -----------------------------------------------------

    def begin_epoch(self, epoch_no):
        if self._preempted:
            # SIGTERM landed between epochs (eval, data setup): save at
            # this boundary rather than silently swallowing the stop.
            # Coordinated mode only REQUESTS here — all ranks reach the
            # stop inside the next epoch's step loop together
            if self._coord_stop is not None:
                self._coord_stop.request(self._host_step)
            else:
                self._emergency_save()
        self.state.begin_epoch(epoch_no, self.world_size)
        self._step_times = []
        self.report_status(train_status_mod.TrainStatus.RUNNING)

    def end_epoch(self, save=True):
        n = len(self._step_times)
        avg = sum(self._step_times) / n if n else 0.0
        self.state.end_epoch(n, avg)
        self.state.global_step = self.global_step
        if save:
            self.save()
        if self._preempted:
            if self._coord_stop is not None:
                self._coord_stop.request(self._host_step)
            else:
                # the epoch-end save (if any) already covers this step
                self._emergency_save(already_saved=save)

    def report_status(self, status):
        if self.coord is not None and self.env.pod_id:
            try:
                train_status_mod.save_train_status(self.coord,
                                                   self.env.pod_id, status)
            except Exception:
                logger.exception("train status report failed")

    # -- checkpoint / resume -------------------------------------------------

    @property
    def extra_state(self):
        return self.train_state["extra"]

    def mirror_model_counters(self):
        """Copy ``extra_state["counters"]`` — numbers a model keeps ON the
        device in the step's extra state, so that counting costs a step no
        host read — into the metrics registry
        (``edl_train_model_counter{name, index}``). Called where the
        trainer synchronises with the device anyway: a save, a live
        resize, ``close``. Returns what it read ({} where the model keeps
        none)."""
        extra = self.train_state["extra"]
        counters = extra.get("counters") if isinstance(extra, dict) else None
        if not counters:
            return {}
        host = {name: np.atleast_1d(np.asarray(value, np.float64))
                for name, value in jax.device_get(counters).items()}
        for name, values in host.items():
            for i, v in enumerate(values):
                _MODEL_COUNTER.labels(name, i).set(float(v))
        return host

    @staticmethod
    def _fully_addressable(tree):
        return all(getattr(x, "is_fully_addressable", True)
                   for x in jax.tree_util.tree_leaves(tree))

    def save(self):
        """Write the versioned checkpoint + State (reference: rank0
        fleet.save_check_point per epoch, train_with_fleet.py:562).

        Fully-addressable state (single process): rank 0 writes the
        dense checkpoint. Any cross-process state (is_fully_addressable
        is False for every multi-host jax.Array, replicated included):
        EVERY process calls this and writes only the shards it owns
        replica 0 of (CheckpointManager.save_sharded) — no gather
        collective, write bandwidth scales with host count (the Orbax
        role), and synchronization is filesystem visibility on the
        shared store, not device collectives. For replicated leaves the
        replica-0 dedup means rank 0 writes them once.

        With ``async_save=True`` the write rides the checkpoint engine's
        two-phase path (save_async/save_sharded_async): a fast host-side
        snapshot into pooled buffers runs here — later steps may donate
        the originals — and a background writer pool streams the entries
        out, committing the manifest last so partial writes stay
        invisible. The engine's max_inflight=1 back-pressure drains the
        previous save first."""
        if self._ckpt is None:
            return
        # the stages a save blocks training for, as stage spans under
        # one root; the background write joins the trace from its own
        # thread (save.snapshot and save.persist: runtime/checkpoint.py)
        with obs_trace.span("save", stage=True,
                            version=self.global_step):
            self._save()

    def _save(self):
        version = self.global_step
        self.mirror_model_counters()
        with obs_trace.span("save.state_json", stage=True):
            # deep-snapshot the control-plane state NOW — the background
            # writer must not see the live State's nested dicts mutating
            # under it
            import json
            state_snapshot = json.loads(self.state.to_json())
            # the sharding record (PartitionSpec tree + mesh axes) rides
            # meta.json through every save path — restore never needs it
            # (span intersection works blind) but the resize planner
            # reads it to cost a target mesh before touching any data
            meta = {"state": state_snapshot,
                    "sharding": checkpoint_mod.sharding_record(
                        self._state_shardings)}

        with obs_trace.span("save.drain_prev", stage=True):
            self.wait_for_save()
        # peer restore plane: capture SEPARATE host copies of this
        # process's shards NOW (the training thread — later steps may
        # donate the originals, and the engine's pooled staging buffers
        # are reused by the next save, so neither may be served) and
        # publish them only once the version COMMITS — a served version
        # is always also manifest-valid on the FS.
        publish = None
        if self._state_server is not None:
            from edl_tpu.runtime import redundancy as redundancy_mod
            from edl_tpu.runtime import state_server as state_server_mod
            entries, dtags = state_server_mod.snapshot_entries(
                dict(self.train_state))
            srv = self._state_server
            coord = self.coord
            owner = str(self.env.global_rank)

            def publish():
                srv.publish(version, entries, dtags, meta=meta)
                # commit-path hand-off to the redundancy tier: encode
                # the same committed host copies and push the shards
                # to this pod's partner ring. Runs on the persist
                # driver thread (never the training step) and is
                # strictly best-effort — the version is already
                # durable on the FS and served by the StateServer.
                if coord is not None and redundancy_mod.enabled():
                    try:
                        redundancy_mod.push_shards(
                            coord, owner, version, entries, dtags,
                            meta=meta, self_endpoint=srv.endpoint)
                    except Exception:
                        logger.exception(
                            "redundancy shard push for v%d failed; "
                            "this version has no parity cover", version)

        if not self._fully_addressable(self.train_state):
            # per-host sharded write; every rank participates
            rank = jax.process_index()
            nranks = jax.process_count()
            store_write = ((lambda: self._save_state_to_store(
                state_snapshot)) if rank == 0 else None)

            def on_commit(_store=store_write, _pub=publish):
                if _pub is not None:
                    _pub()
                if _store is not None:
                    _store()
            if self._async_save:
                self._ckpt.save_sharded_async(
                    version, dict(self.train_state), meta=meta,
                    rank=rank, nranks=nranks, on_commit=on_commit)
                return
            self._ckpt.save_sharded(version, dict(self.train_state),
                                    meta=meta, rank=rank, nranks=nranks)
            on_commit()
            return
        if self.env.global_rank != 0:
            return
        if self._async_save:
            def on_commit_dense(_pub=publish):
                if _pub is not None:
                    _pub()
                self._save_state_to_store(state_snapshot)
            self._ckpt.save_async(
                version, dict(self.train_state), meta=meta,
                on_commit=on_commit_dense)
            return
        self._ckpt.save(version,
                        checkpoint_mod.to_host_tree(
                            dict(self.train_state)), meta=meta)
        if publish is not None:
            publish()
        self._save_state_to_store(state_snapshot)

    def wait_for_save(self):
        """Block until any in-flight async checkpoint persist finishes
        (the engine's drain; a persist failure is logged there, and the
        manifest-last commit keeps the failed version invisible)."""
        if self._ckpt is not None:
            self._ckpt.drain()

    def close(self):
        """Release background resources: drain any in-flight async save,
        shut the checkpoint engine's writer pool down, and stop the
        preemption watcher thread and state server. Idempotent; the
        trainer remains usable for reads afterwards (notebooks
        constructing several trainers should close the ones they
        drop)."""
        self.wait_for_save()
        try:
            self.mirror_model_counters()
        except Exception:  # a state lost to a failed step must not
            logger.exception("model counters not mirrored")  # block close
        if self._live_register is not None:
            try:
                self._live_register.stop()
            except Exception:
                logger.exception("live-resize capability stop failed")
            self._live_register = None
        if self._live_watcher is not None:
            self._live_watcher.stop()
            self._live_watcher = None
        if self._state_server is not None:
            try:
                self._state_server.stop()
            except Exception:
                logger.exception("state server stop failed")
            self._state_server = None
        if self._ckpt is not None:
            self._ckpt.close()
        if self._coord_stop is not None:
            self._coord_stop.stop()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def _save_state_to_store(self, state_dict):
        if self.coord is not None:
            snap = state_mod.State()
            snap.from_dict(dict(state_dict))
            state_mod.save_to_store(self.coord, snap)

    def _restore_placed_any(self, version, target, shardings):
        """restore_placed walking the recovery ladder: live peer
        StateServers first (NIC bandwidth, host memory; the restorer
        itself decodes dead pods' parity shards for spans no peer
        serves), then a wholesale parity rebuild when NO peer serves
        the version at all, and only then the shared FS — the cold
        layer. MissingKeysError propagates either way — the caller's
        core-only retry must see it. Returns (version, tree, meta)."""
        if self._state_server is not None:
            from edl_tpu.runtime import redundancy as redundancy_mod
            from edl_tpu.runtime.state_server import PeerRestorer
            from edl_tpu.utils.errors import (PeerRestoreError,
                                              RedundancyError)
            restorer = PeerRestorer(
                self.coord, self._ckpt,
                self_endpoint=self._state_server.endpoint)
            try:
                v, tree, meta, stats = restorer.restore_placed(
                    version, target, shardings)
                self._resize_timing["restore_source"] = stats["source"]
                self._resize_timing["restore_bytes"] = \
                    stats["peer_bytes"]
                self._resize_timing["restore_peers"] = stats["peers"]
                logger.info("peer restore v%d: %.1f MB from %d peer(s)"
                            " (%s)", v, stats["peer_bytes"] / 1e6,
                            stats["peers"], stats["source"])
                return v, tree, meta or {}
            except MissingKeysError:
                raise
            except PeerRestoreError as e:
                logger.info("peer restore unavailable for v%d (%s); "
                            "trying the parity rung", version, e)
            except Exception:
                logger.exception("peer restore for v%d failed; "
                                 "trying the parity rung", version)
            if redundancy_mod.enabled() and self.coord is not None:
                try:
                    v, tree, meta, stats = redundancy_mod.restore_placed(
                        self.coord, version, target, shardings,
                        self_endpoint=self._state_server.endpoint)
                    self._resize_timing["restore_source"] = "parity"
                    self._resize_timing["restore_bytes"] = \
                        stats["parity_bytes"]
                    self._resize_timing["restore_peers"] = \
                        stats["holders"]
                    logger.info("parity restore v%d: %.1f MB decoded "
                                "from %d holder(s) (owners: %s)", v,
                                stats["parity_bytes"] / 1e6,
                                stats["holders"], stats["owners"])
                    return v, tree, meta or {}
                except MissingKeysError:
                    raise
                except RedundancyError as e:
                    logger.info("parity rung unavailable for v%d (%s);"
                                " restoring from the shared FS",
                                version, e)
                except Exception:
                    logger.exception("parity restore for v%d failed; "
                                     "restoring from the shared FS",
                                     version)
        out = self._ckpt.restore_placed(version, target, shardings)
        self._resize_timing["restore_source"] = "fs"
        return out

    def _publish_resize_timing(self):
        """Write this incarnation's per-stage resume timings to the
        coordination store (SERVICE_METRICS / resize_timing_r<rank>) so
        measure_resize can assemble the downtime breakdown without log
        scraping. Best-effort."""
        if self.coord is None:
            return
        import json as _json
        from edl_tpu.controller import constants
        # ride the ledger totals along: trainer subprocesses run no
        # MetricsPublisher, so this doc is how measure_resize (and the
        # pause-agreement test) reads the worker's time attribution
        doc = dict(self._resize_timing)
        # the CURRENT mesh factorization, so the driver can tell a
        # dp-only record from a dp x tp one without parsing shardings
        doc["mesh"] = {str(a): int(self.mesh.shape[a])
                       for a in self.mesh.axis_names}
        doc["ledger"] = {s: round(v, 6) for s, v
                        in obs_ledger.LEDGER.totals().items()}
        try:
            self.coord.set_server_permanent(
                constants.SERVICE_METRICS,
                "resize_timing_r%d" % self.env.global_rank,
                _json.dumps(doc))
        except Exception:
            logger.exception("resize timing publish failed")

    def resume(self):
        """Restore the newest valid checkpoint; apply resize adjust hooks if
        the world size changed. Returns True if something was restored."""
        if self._ckpt is None:
            return False
        # newest-first: per version, try the full state; when only the extra
        # keys are missing (legacy checkpoint), retry THAT version core-only
        # rather than falling back to an older checkpoint. The target is a
        # ShapeDtypeStruct tree — restore needs structure only, so no
        # gather of cross-host sharded leaves is ever required

        def _spec(x):
            a = x if hasattr(x, "shape") and hasattr(x, "dtype") \
                else np.asarray(x)
            return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)

        # placed restore: each process reads only the shard entries its
        # devices need and assembles the sharded jax.Arrays directly —
        # host memory stays O(local shards), no full-model materialize
        target = jax.tree_util.tree_map(_spec, dict(self.train_state))
        restored = None
        self._resize_timing["t_resume_start"] = time.time()
        obs_ledger.LEDGER.transition("restore")
        obs_events.emit("resize.resume_start", rank=self.env.global_rank,
                        world_size=self.world_size)
        for version in reversed(self._ckpt.versions()):
            try:
                restored = self._restore_placed_any(
                    version, target, self._state_shardings)
                break
            except Exception as e:  # noqa: BLE001
                if isinstance(e, MissingKeysError) \
                        and jax.tree_util.tree_leaves(target["extra"]):
                    core = dict(target)
                    core.pop("extra")
                    core_sh = dict(self._state_shardings)
                    core_sh.pop("extra")
                    try:
                        restored = self._restore_placed_any(
                            version, core, core_sh)
                        logger.info("checkpoint v%d has no extra state; "
                                    "keeping the initial one", version)
                        # the live (initial) extra arrays, already laid
                        # out by self._state_shardings
                        restored[1]["extra"] = self.train_state["extra"]
                        break
                    except Exception as e2:  # noqa: BLE001
                        e = e2
                logger.warning("checkpoint v%d unusable (%r); trying older",
                               version, e)
        if restored is None:
            obs_ledger.LEDGER.transition("idle")
            return False
        version, tree, meta = restored
        self.train_state = tree
        if meta.get("state"):
            # hooks are process-local: carry them onto the restored state
            self.state = self.state.carry_hooks_to(
                state_mod.State().from_dict(meta["state"]))
            self.state.total_batch_size = self.total_batch_size
        prev_world = (self.state.epochs.get(str(self.state.epoch_no), {})
                      .get("world_size", self.world_size))
        if prev_world != self.world_size:
            logger.info("world resized %s -> %s; applying adjust hooks",
                        prev_world, self.world_size)
            self.state.adjust(self.world_size)
        self._host_step = self.global_step
        self._resumed_version = version
        # restore is done; the remainder of the pause (compile + first
        # dispatch) is charged to resize_pause until train_step stamps
        obs_ledger.LEDGER.transition("resize_pause")
        self._resize_timing["t_resume_end"] = time.time()
        self._resize_timing["restore_s"] = (
            self._resize_timing["t_resume_end"]
            - self._resize_timing["t_resume_start"])
        self._resize_timing["version"] = version
        obs_events.emit("resize.resumed", rank=self.env.global_rank,
                        version=version,
                        restore_s=self._resize_timing["restore_s"],
                        source=self._resize_timing.get("restore_source"))
        if self._coord_stop is not None:
            # preempt keys published by the incarnation that wrote this
            # checkpoint are at or below its final step: stale from here
            self._coord_stop.min_step = self._host_step
        logger.info("resumed from checkpoint v%d (epoch %d, step %d)",
                    version, self.state.epoch_no, self.global_step)
        return True
