"""Pod/Trainer/Cluster model tests (reference parity: test_pod.py,
test_cluster.py serialization roundtrips)."""

import os

from edl_tpu.controller.cluster import Cluster
from edl_tpu.controller.env import JobEnv, TrainerEnv
from edl_tpu.controller.pod import Pod


def _job_env(**over):
    os.environ.setdefault("EDL_TPU_POD_IP", "127.0.0.1")
    args = type("A", (), dict(
        job_id="job_x", store_endpoints="127.0.0.1:2379", nodes_range="2:4",
        nproc_per_node=over.get("nproc_per_node", 1), pod_ip="127.0.0.1",
        checkpoint_path=None, log_dir=None, log_level=None))()
    return JobEnv(args)


def test_pod_from_env_and_roundtrip():
    os.environ["EDL_TPU_DEVICES"] = "0,1,2,3"
    try:
        pod = Pod.from_env(_job_env())
    finally:
        del os.environ["EDL_TPU_DEVICES"]
    assert len(pod.trainers) == 1
    assert pod.trainers[0].devices == [0, 1, 2, 3]
    clone = Pod().from_json(pod.to_json())
    assert clone == pod
    assert clone.trainers[0].devices == [0, 1, 2, 3]


def test_pod_multi_proc_device_split():
    os.environ["EDL_TPU_DEVICES"] = "0,1,2,3"
    try:
        pod = Pod.from_env(_job_env(nproc_per_node=2))
    finally:
        del os.environ["EDL_TPU_DEVICES"]
    assert [t.devices for t in pod.trainers] == [[0, 1], [2, 3]]


def test_two_trainers_on_one_host_refused_unless_cpu_pinned():
    """One process per chip: a pod whose trainers would each claim every
    local chip is refused with a typed error; the CPU harness
    (JAX_PLATFORMS=cpu) keeps its multi-process pods."""
    import pytest

    from edl_tpu.controller.train_process import check_chip_ownership
    from edl_tpu.utils.errors import TrainProcessError

    os.environ["EDL_TPU_DEVICES"] = "0,1,2,3"
    try:
        two = Pod.from_env(_job_env(nproc_per_node=2))
        one = Pod.from_env(_job_env())
    finally:
        del os.environ["EDL_TPU_DEVICES"]
    check_chip_ownership(two, {"JAX_PLATFORMS": "cpu"})
    for environ in ({}, {"JAX_PLATFORMS": "tpu"},
                    {"JAX_PLATFORMS": "tpu,cpu"}):
        check_chip_ownership(one, environ)
        with pytest.raises(TrainProcessError):
            check_chip_ownership(two, environ)


def test_cluster_ranks_and_roundtrip():
    cluster = Cluster()
    for _ in range(3):
        os.environ["EDL_TPU_DEVICES"] = "0,1"
        pod = Pod.from_env(_job_env(nproc_per_node=2))
        del os.environ["EDL_TPU_DEVICES"]
        cluster.pods.append(pod)
    cluster.assign_ranks()
    assert [p.rank for p in cluster.pods] == [0, 1, 2]
    granks = [t.global_rank for p in cluster.pods for t in p.trainers]
    assert granks == list(range(6))
    assert cluster.world_size() == 6
    assert cluster.total_devices() == 6  # 2 devices / 2 procs × 3 pods

    clone = Cluster().from_json(cluster.to_json())
    assert clone == cluster
    assert clone.stage == cluster.stage
    assert clone.get_leader_endpoint() == cluster.get_leader_endpoint()


def test_trainer_env_contract_roundtrip():
    env = {
        "EDL_TPU_JOB_ID": "j", "EDL_TPU_STORE_ENDPOINTS": "a:1,b:2",
        "EDL_TPU_POD_ID": "p", "EDL_TPU_POD_RANK": "1",
        "EDL_TPU_TRAINER_ID": "t", "EDL_TPU_RANK_IN_POD": "0",
        "EDL_TPU_GLOBAL_RANK": "3", "EDL_TPU_WORLD_SIZE": "8",
        "EDL_TPU_COORDINATOR": "a:5000",
        "EDL_TPU_TRAINER_ENDPOINTS": "a:5000,b:5001",
        "EDL_TPU_LOCAL_DEVICES": "0,1", "EDL_TPU_CLUSTER_STAGE": "s1",
    }
    te = TrainerEnv(env)
    assert te.global_rank == 3 and te.world_size == 8
    assert te.store_endpoints == ["a:1", "b:2"]
    assert te.local_devices == [0, 1]
    assert not te.is_rank0 and te.under_launcher
