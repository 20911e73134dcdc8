"""Native (C++) image loader: decode fidelity vs the tf.data pipeline,
augmentation determinism, sharding, tail handling, error counting."""

import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def loader_lib():
    from edl_tpu.data.native_loader import ensure_loader_lib
    try:
        return ensure_loader_lib()
    except Exception as e:  # no toolchain -> skip, don't error
        pytest.skip("native loader unavailable: %r" % e)


@pytest.fixture(scope="module")
def image_tree(tmp_path_factory):
    """A small class-per-subdirectory JPEG tree with varied sizes."""
    from PIL import Image
    root = tmp_path_factory.mktemp("imgs")
    rng = np.random.RandomState(7)
    sizes = [(40, 40), (64, 48), (48, 64), (96, 96)]
    for c in range(3):
        d = root / ("class_%d" % c)
        d.mkdir()
        for i in range(8):
            w, h = sizes[(c + i) % len(sizes)]
            arr = rng.randint(0, 255, (h, w, 3)).astype(np.uint8)
            Image.fromarray(arr).save(str(d / ("img%02d.jpg" % i)),
                                      quality=92)
    return str(root)


def test_eval_matches_tf_pipeline(loader_lib, image_tree):
    """Same JPEGs, eval mode: the native decode+resize+normalize must
    agree with the tf.data pipeline (both sit on libjpeg; bilinear
    half-pixel resize on both sides) to small numeric tolerance."""
    from edl_tpu.data.input_pipeline import image_folder_pipeline
    from edl_tpu.data.native_loader import native_image_folder_pipeline

    tf_batches = list(image_folder_pipeline(
        image_tree, 8, image_size=32, train=False))
    nat_batches = list(native_image_folder_pipeline(
        image_tree, 8, image_size=32, train=False))
    assert len(tf_batches) == len(nat_batches)
    for tb, nb in zip(tf_batches, nat_batches):
        np.testing.assert_array_equal(tb["label"], nb["label"])
        assert tb["image"].shape == nb["image"].shape
        diff = np.abs(tb["image"] - nb["image"]).mean()
        assert diff < 0.05, diff  # normalized units (std ~58 raw)


def test_train_deterministic_and_augmenting(loader_lib, image_tree):
    from edl_tpu.data.native_loader import native_image_folder_pipeline

    a = list(native_image_folder_pipeline(image_tree, 8, image_size=32,
                                          train=True, epoch_seed=5))
    b = list(native_image_folder_pipeline(image_tree, 8, image_size=32,
                                          train=True, epoch_seed=5))
    c = list(native_image_folder_pipeline(image_tree, 8, image_size=32,
                                          train=True, epoch_seed=6))
    # train drops the ragged tail: 24 files -> 3 full batches
    assert len(a) == 3 and all(x["image"].shape == (8, 32, 32, 3)
                               for x in a)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["image"], y["image"])
        np.testing.assert_array_equal(x["label"], y["label"])
    # a different epoch seed reshuffles
    assert any(not np.array_equal(x["label"], z["label"])
               for x, z in zip(a, c))


def test_sharding_partitions_files(loader_lib, image_tree):
    from edl_tpu.data.native_loader import native_image_folder_pipeline

    whole = [b["label"] for b in native_image_folder_pipeline(
        image_tree, 4, image_size=16, train=False)]
    s0 = [b["label"] for b in native_image_folder_pipeline(
        image_tree, 4, image_size=16, train=False, shard_index=0,
        shard_count=2)]
    s1 = [b["label"] for b in native_image_folder_pipeline(
        image_tree, 4, image_size=16, train=False, shard_index=1,
        shard_count=2)]
    n_whole = sum(len(x) for x in whole)
    assert sum(len(x) for x in s0) + sum(len(x) for x in s1) == n_whole
    assert sorted(np.concatenate(s0 + s1)) == sorted(
        np.concatenate(whole))


def test_eval_tail_batch(loader_lib, image_tree):
    from edl_tpu.data.native_loader import native_image_folder_pipeline

    batches = list(native_image_folder_pipeline(
        image_tree, 5, image_size=16, train=False))
    rows = [len(b["label"]) for b in batches]
    assert sum(rows) == 24 and rows[-1] == 24 % 5


def test_decode_error_zero_fills_and_counts(loader_lib, tmp_path):
    from edl_tpu.data.native_loader import NativeImageLoader

    from PIL import Image
    good = tmp_path / "ok.jpg"
    Image.fromarray(np.full((20, 20, 3), 128, np.uint8)).save(str(good))
    bad = tmp_path / "bad.jpg"
    bad.write_bytes(b"not a jpeg at all")
    loader = NativeImageLoader([(str(good), 0), (str(bad), 1)], 2,
                               image_size=16, train=False, seed=0)
    batch = next(loader)
    assert loader.decode_errors == 1
    # the bad row is zero-filled, the good one is not
    assert np.abs(batch["image"][1]).sum() == 0
    assert np.abs(batch["image"][0]).sum() > 0
    loader.close()


def test_rejects_non_jpeg(loader_lib, tmp_path):
    from edl_tpu.data.native_loader import NativeImageLoader

    with pytest.raises(ValueError):
        NativeImageLoader([(str(tmp_path / "x.png"), 0)], 1)


@pytest.mark.integration
def test_resnet_example_trains_with_native_loader(loader_lib, tmp_path):
    """The --loader native path end-to-end: real JPEGs -> C++ decode ->
    ElasticTrainer steps -> benchmark-log JSON."""
    import json
    import subprocess
    import sys

    sys.path.insert(0, os.path.join(REPO, "tests"))
    from conftest import _make_real_dataset

    data = _make_real_dataset(str(tmp_path / "train"), classes=2,
                              per_class=16, size=40)
    from conftest import cpu_subprocess_env
    env = cpu_subprocess_env(2)
    proc = subprocess.run(
        [sys.executable, "-u",
         os.path.join(REPO, "examples/resnet/train.py"),
         "--depth", "18", "--epochs", "1", "--steps_per_epoch", "3",
         "--total_batch_size", "8", "--image_size", "32",
         "--data_dir", data, "--loader", "native"],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads([l for l in proc.stdout.splitlines()
                      if l.startswith("{")][-1])
    assert out["steps"] == 3 and out["model"] == "ResNet18_vd"
