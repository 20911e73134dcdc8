"""Device-mesh construction for dp/tp/sp/pp axes + topology validity.

The TPU replacement for the reference's NCCL world bootstrap: there is no
rendezvous to manage — `jax.devices()` exposes the slice topology and pjit /
shard_map lower collectives onto ICI/DCN (SURVEY.md §2.7, §5.8). The
launcher contributes only host membership; this module turns the surviving
hosts' devices into a Mesh.
"""

import math

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "dp"
MODEL_AXIS = "tp"
SEQ_AXIS = "sp"
PIPE_AXIS = "pp"
EXPERT_AXIS = "ep"
DCN_AXIS = "dcn"  # the cross-slice (data-center network) axis


def make_mesh(dp=None, tp=1, sp=1, pp=1, ep=1, devices=None):
    """Build a Mesh with axes (pp, dp, ep, sp, tp) over ``devices``.

    dp=None ⇒ fill dp with whatever remains after the fixed axes. Axis order
    puts tp innermost so tensor-parallel collectives ride the fastest ICI
    links, and pp outermost (classic TPU layout; cf. the scaling-book
    recipe of mesh-then-annotate).
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    fixed = tp * sp * pp * ep
    if dp is None:
        if n % fixed != 0:
            raise ValueError("devices=%d not divisible by tp*sp*pp*ep=%d"
                             % (n, fixed))
        dp = n // fixed
    if dp * fixed != n:
        raise ValueError("mesh %dx%dx%dx%dx%d != %d devices"
                         % (pp, dp, ep, sp, tp, n))
    shape = (pp, dp, ep, sp, tp)
    try:
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except (ValueError, AssertionError):
        # a device set that is not a whole topology has no topology-
        # aware order, only the plain one. Established on a v5e 2x2
        # host (PR 21): create_device_mesh orders 4 chips ([0,1,3,2],
        # the ring), 2x2, and the first 2 or 1 — the live-resize
        # sub-meshes — itself; it asserts only on 3 of the 4.
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array,
                (PIPE_AXIS, DATA_AXIS, EXPERT_AXIS, SEQ_AXIS, MODEL_AXIS))


def _group_slices(devices):
    """Group devices into slices: by the TPU runtime's slice_index when it
    discriminates (real multi-slice systems, where one slice spans many
    host processes), else by owning process (multi-host CPU rigs report a
    constant slice_index 0)."""
    sids = {getattr(d, "slice_index", None) for d in devices}
    key = ((lambda d: d.slice_index) if len(sids) > 1 and None not in sids
           else (lambda d: d.process_index))
    groups = {}
    for d in devices:
        groups.setdefault(key(d), []).append(d)
    return [groups[k] for k in sorted(groups)]


def make_hybrid_mesh(dcn_dp=None, dp=None, tp=1, sp=1, pp=1, ep=1,
                     devices=None):
    """Multi-slice mesh: data parallelism over DCN (one row per slice),
    the other axes within each slice over ICI.

    Axes: (dcn, pp, dp, ep, sp, tp) — shard batches with
    ``data_sharding(mesh)`` (= P(("dcn", "dp"))); the gradient all-reduce
    XLA inserts then decomposes into a fast within-slice reduce over ICI
    plus a small cross-slice reduce over DCN (the hierarchical-allreduce
    the reference exposed as a fleet knob, train_with_fleet.py:372).

    Slices are discovered from device.slice_index (real multi-slice TPU)
    or process_index (multi-host CPU test rig). If all devices report ONE
    slice and ``dcn_dp`` > 1 is requested, the device list is split
    contiguously into dcn_dp virtual slices — the hermetic single-process
    test/dryrun mode.
    """
    devices = list(devices if devices is not None else jax.devices())
    slices = _group_slices(devices)
    if len(slices) == 1 and dcn_dp and dcn_dp > 1:
        n = len(devices)
        if n % dcn_dp != 0:
            raise ValueError("devices=%d not divisible into %d virtual "
                             "slices" % (n, dcn_dp))
        per = n // dcn_dp
        slices = [devices[i * per:(i + 1) * per] for i in range(dcn_dp)]
    if dcn_dp is None:
        dcn_dp = len(slices)
    if len(slices) != dcn_dp:
        raise ValueError("found %d slices, want dcn_dp=%d"
                         % (len(slices), dcn_dp))
    sizes = sorted({len(s) for s in slices})
    if len(sizes) != 1:
        raise ValueError("unequal slice sizes %s" % sizes)
    per = sizes[0]
    fixed = tp * sp * pp * ep
    if dp is None:
        if per % fixed != 0:
            raise ValueError("slice size %d not divisible by tp*sp*pp*ep=%d"
                             % (per, fixed))
        dp = per // fixed
    if dp * fixed != per:
        raise ValueError("per-slice mesh %dx%dx%dx%dx%d != %d devices"
                         % (pp, dp, ep, sp, tp, per))
    shape = (pp, dp, ep, sp, tp)
    rows = []
    for s in slices:
        try:
            rows.append(mesh_utils.create_device_mesh(shape, devices=s))
        except (ValueError, AssertionError):
            rows.append(np.asarray(s).reshape(shape))
    dev_array = np.stack(rows)  # [dcn, pp, dp, ep, sp, tp]
    return Mesh(dev_array, (DCN_AXIS, PIPE_AXIS, DATA_AXIS, EXPERT_AXIS,
                            SEQ_AXIS, MODEL_AXIS))


def parse_mesh_arg(s):
    """Parse a CLI mesh factorization: ``"dp,tp"`` or ``"dp=2,tp=4"`` ->
    {axis: size|None} suitable for ``make_mesh(**factors)``.

    A bare model axis (tp/sp/pp/ep) defaults to 2; a bare ``dp`` maps to
    None (make_mesh fills it with the remaining devices). Unknown axis
    names raise — the CLI should fail loudly, not build a mesh the
    trainer can't rebuild on resize."""
    known = (DATA_AXIS, MODEL_AXIS, SEQ_AXIS, PIPE_AXIS, EXPERT_AXIS)
    out = {}
    for part in s.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            axis, _, val = part.partition("=")
            axis = axis.strip()
            size = int(val)
        else:
            axis = part
            size = None if axis == DATA_AXIS else 2
        if axis not in known:
            raise ValueError("unknown mesh axis %r (want one of %s)"
                             % (axis, ", ".join(known)))
        out[axis] = size
    return out


def data_sharding(mesh):
    """Batch-dim sharding over the data axes present in the mesh: dp, plus
    dcn for hybrid (multi-slice) meshes."""
    axes = tuple(a for a in (DCN_AXIS, DATA_AXIS) if a in mesh.shape)
    if not axes:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(axes if len(axes) > 1 else axes[0]))


def replicated(mesh):
    return NamedSharding(mesh, P())


def topology_valid_power_of_two(n_hosts):
    """Default TPU validity: host counts must be powers of two (sub-slices
    of a pod slice). Replace per deployment topology. Used by the cluster
    generator's validity hook (SURVEY.md §7 'hard parts')."""
    return n_hosts > 0 and (n_hosts & (n_hosts - 1)) == 0


def largest_valid_world(n_hosts):
    if n_hosts <= 0:
        return 0
    return 2 ** int(math.floor(math.log2(n_hosts)))
