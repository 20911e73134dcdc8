"""Hybrid (multi-slice) mesh tests.

Single-process: virtual-slice construction, axis layout, data sharding.
Multi-process: REAL jax.distributed over 2 CPU processes x 4 local
devices, dp-over-DCN x tp-within-slice — a tp-sharded train step whose
gradient reduction crosses the process (DCN) boundary; both processes
must agree bitwise (VERDICT r1 #10; reference hierarchical-allreduce
knob train_with_fleet.py:372)."""

import os
import socket
import subprocess
import sys

import jax
import numpy as np
import pytest

from edl_tpu.runtime import mesh as mesh_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = """\
import sys
import jax
jax.config.update("jax_platforms", "cpu")
coordinator, nprocs, rank = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
jax.distributed.initialize(coordinator_address=coordinator,
                           num_processes=nprocs, process_id=rank)
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from edl_tpu.runtime.mesh import make_hybrid_mesh, data_sharding

mesh = make_hybrid_mesh(tp=2)   # slices from process_index
assert mesh.shape["dcn"] == nprocs and mesh.shape["tp"] == 2, mesh.shape
# every dcn row must be process-pure (dp/tp collectives stay inside a
# slice; only the dcn axis crosses processes)
for row_idx in range(mesh.devices.shape[0]):
    procs = {d.process_index for d in mesh.devices[row_idx].flat}
    assert len(procs) == 1, (row_idx, procs)

w = jnp.arange(16 * 8, dtype=jnp.float32).reshape(16, 8) / 100.0
w = jax.device_put(w, NamedSharding(mesh, P(None, "tp")))  # tp-sharded
batch_sh = data_sharding(mesh)
assert batch_sh.spec == P(("dcn", "dp")), batch_sh.spec

# global batch 8: each process contributes its local 4 rows
local = (jnp.arange(4 * 16, dtype=jnp.float32).reshape(4, 16) / 50.0
         + rank * 0.5)
x = jax.make_array_from_process_local_data(batch_sh, local)

def loss_fn(w, x):
    return (jnp.tanh(x @ w) ** 2).mean()

loss, grads = jax.jit(
    jax.value_and_grad(loss_fn),
    out_shardings=(NamedSharding(mesh, P()),
                   NamedSharding(mesh, P(None, "tp"))))(w, x)
gsum = float(jnp.abs(grads).sum())

# cross-process-sharded checkpoint: w's tp shards live on BOTH processes;
# to_host_tree must all-gather before the rank-0 write, and the restored
# tree must match the global array
from edl_tpu.runtime.checkpoint import CheckpointManager, to_host_tree
import numpy as np
host_tree = to_host_tree({"w": grads})
assert host_tree["w"].shape == (16, 8), host_tree["w"].shape
ckpt_dir = sys.argv[4]
if rank == 0:
    cm = CheckpointManager(ckpt_dir)
    cm.save(1, host_tree)
    _, restored, _ = cm.restore(1, target=host_tree)
    assert np.array_equal(restored["w"], host_tree["w"])
    print("CKPT OK", flush=True)

print("RESULT rank=%d loss=%.10f gsum=%.10f" % (rank, float(loss), gsum),
      flush=True)
"""


def test_virtual_slices_single_process():
    mesh = mesh_mod.make_hybrid_mesh(dcn_dp=2, tp=2,
                                     devices=jax.devices()[:8])
    assert mesh.shape["dcn"] == 2 and mesh.shape["dp"] == 2 \
        and mesh.shape["tp"] == 2
    assert mesh_mod.data_sharding(mesh).spec == \
        jax.sharding.PartitionSpec(("dcn", "dp"))
    # contiguous virtual slices
    row0 = [d.id for d in mesh.devices[0].flat]
    row1 = [d.id for d in mesh.devices[1].flat]
    assert sorted(row0) == [0, 1, 2, 3] and sorted(row1) == [4, 5, 6, 7]


def test_hybrid_mesh_rejects_bad_shapes():
    devs = jax.devices()[:8]
    with pytest.raises(ValueError):
        mesh_mod.make_hybrid_mesh(dcn_dp=3, devices=devs)  # 8 % 3
    with pytest.raises(ValueError):
        mesh_mod.make_hybrid_mesh(dcn_dp=2, tp=3, devices=devs)  # 4 % 3


def test_hybrid_train_step_grads_match_flat_mesh():
    """A dp-over-dcn x dp train step must produce the same grads as the
    flat 1-axis dp mesh (the decomposition is a layout, not a semantics,
    change)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    devs = jax.devices()[:8]
    w = jnp.arange(16 * 4, dtype=jnp.float32).reshape(16, 4) / 100.0
    x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16) / 50.0

    def loss_fn(w, x):
        return (jnp.tanh(x @ w) ** 2).mean()

    flat = mesh_mod.make_mesh(dp=8, devices=devs)
    hyb = mesh_mod.make_hybrid_mesh(dcn_dp=2, devices=devs)
    outs = {}
    for name, mesh in (("flat", flat), ("hybrid", hyb)):
        xs = jax.device_put(x, mesh_mod.data_sharding(mesh))
        ws = jax.device_put(w, NamedSharding(mesh, P()))
        loss, g = jax.jit(jax.value_and_grad(loss_fn))(ws, xs)
        outs[name] = (float(loss), np.asarray(g))
    assert outs["flat"][0] == pytest.approx(outs["hybrid"][0], rel=1e-6)
    np.testing.assert_allclose(outs["flat"][1], outs["hybrid"][1],
                               rtol=1e-6, atol=1e-7)


@pytest.mark.integration
def test_multiprocess_dcn_mesh(tmp_path):
    """2 real processes (jax.distributed over CPU), 4 local devices each:
    tp-sharded step with grad reduction across the DCN axis."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coordinator = "127.0.0.1:%d" % port
    worker_py = tmp_path / "worker.py"
    worker_py.write_text(WORKER)

    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    ckpt_dir = str(tmp_path / "ckpt")
    procs = [subprocess.Popen(
        [sys.executable, str(worker_py), coordinator, "2", str(rank),
         ckpt_dir],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for rank in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out.decode("utf-8", "replace"))
            assert p.returncode == 0, "\n".join(outs)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    results = sorted(ln for out in outs for ln in out.splitlines()
                     if ln.startswith("RESULT"))
    assert len(results) == 2, outs
    # identical loss and grad checksum on both processes → the cross-DCN
    # reduction really happened and agreed
    f0, f1 = (r.split(" ", 1)[1] for r in results)
    assert f0.split("loss=")[1] == f1.split("loss=")[1], results
    # the cross-process-sharded checkpoint gathered + round-tripped
    assert any("CKPT OK" in out for out in outs), outs
