"""Aux subsystem tests: input pipeline, liveft layer."""

import time

import numpy as np
from PIL import Image

from edl_tpu.liveft import elastic


def _make_image_tree(tmp_path, classes=2, per_class=3, size=40):
    rng = np.random.RandomState(0)
    for c in range(classes):
        d = tmp_path / ("class_%d" % c)
        d.mkdir()
        for i in range(per_class):
            arr = rng.randint(0, 255, (size, size, 3), dtype=np.uint8)
            Image.fromarray(arr).save(str(d / ("img%d.jpg" % i)))
    return str(tmp_path)


def test_image_folder_pipeline(tmp_path):
    root = _make_image_tree(tmp_path)
    batches = list(elastic_free_pipeline(root))
    total = sum(len(b["label"]) for b in batches)
    assert total == 6
    b = batches[0]
    assert b["image"].shape[1:] == (32, 32, 3)
    assert b["image"].dtype == np.float32
    labels = np.concatenate([b["label"] for b in batches])
    assert set(labels.tolist()) == {0, 1}


def elastic_free_pipeline(root):
    from edl_tpu.data.input_pipeline import image_folder_pipeline
    return image_folder_pipeline(root, batch_size=2, image_size=32,
                                 train=False)


def test_image_pipeline_sharding(tmp_path):
    root = _make_image_tree(tmp_path, classes=2, per_class=4)
    from edl_tpu.data.input_pipeline import image_folder_pipeline
    n0 = sum(len(b["label"]) for b in image_folder_pipeline(
        root, 2, image_size=32, train=False, shard_index=0, shard_count=2))
    n1 = sum(len(b["label"]) for b in image_folder_pipeline(
        root, 2, image_size=32, train=False, shard_index=1, shard_count=2))
    assert n0 + n1 == 8 and n0 == n1 == 4


def test_synthetic_pipeline_deterministic():
    from edl_tpu.data.input_pipeline import synthetic_pipeline
    a = list(synthetic_pipeline(4, image_size=8, steps=3, seed=1))
    b = list(synthetic_pipeline(4, image_size=8, steps=3, seed=1))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["image"], y["image"])


def _wait(pred, timeout=20):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(0.2)
    raise AssertionError("condition not met")


def test_liveft_protocol(coord):
    m1 = elastic.ElasticManager(coord, "hostA", np_target=2, ttl=2).start()
    m2 = elastic.ElasticManager(coord, "hostB", np_target=2, ttl=2).start()
    try:
        hosts = m1.wait(timeout=20)
        assert hosts == ["hostA", "hostB"]
        assert m1.rank() == 0 and m2.rank() == 1
        assert m1.watch(poll=0.05) == elastic.HOLD

        # scale signal: np 2 -> 1 then hostB leaves -> RESTART for A
        m1.set_np(1)
        m2.stop()
        _wait(lambda: m1.hosts() == ["hostA"])
        _wait(lambda: m1.watch(poll=0.05) == elastic.RESTART, timeout=15)
        assert m1.wait(timeout=10) == ["hostA"]

        m1.complete()
        assert m1.watch(poll=0.05) == elastic.COMPLETED
    finally:
        m1.stop()
        m2.stop()


def test_profile_bench_breakdown_parser(tmp_path):
    """The xplane parser handles an empty logdir (no trace produced) and
    the CLI surface parses; the full trace path needs TPU hardware."""
    from edl_tpu.tools import profile_bench

    assert profile_bench.xplane_op_breakdown(str(tmp_path), 10) is None
