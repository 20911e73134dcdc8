"""Test harness config.

Tests never touch real TPU hardware: JAX is forced onto CPU with 8 virtual
devices so multi-chip sharding (dp/tp/sp meshes) is exercised hermetically —
the TPU analogue of the reference's "many actors against a local etcd" test
strategy (SURVEY.md §4).
"""

import atexit
import json
import os
import shutil
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from edl_tpu.utils.cpu_mesh import force_cpu_env  # noqa: E402

# must run before jax backends initialize; children spawned by
# integration tests inherit the env and stay on CPU too
force_cpu_env(os.environ, 8)

# the program's default compile cache is a fixed directory inside the
# checkout (utils/compile_cache.py); a test run must leave nothing there
# (the chip tool copies the tree as it stands), so the harness — and
# every child it spawns — is pointed at a throwaway directory before
# jax reads the variable. XLA's persistent cache itself stays off, as
# it always was under test; only the AOT prewarm artifacts land here.
_TEST_CACHE = tempfile.mkdtemp(prefix="edl_tpu_test_cache_")
atexit.register(shutil.rmtree, _TEST_CACHE, ignore_errors=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = _TEST_CACHE
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from edl_tpu.coordination.embedded import (  # noqa: E402
    EmbeddedStore, set_global_endpoints)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cpu_subprocess_env(n_devices=2, **extra):
    """Environment for example/worker SUBPROCESSES on a hermetic
    n-device CPU platform: the force_cpu_env scrub recipe (the one true
    source — tests must not hand-roll JAX_PLATFORMS/XLA_FLAGS) plus
    PYTHONPATH, with ``extra`` vars merged on top."""
    env = force_cpu_env(dict(os.environ), n_devices)
    env["PYTHONPATH"] = REPO
    env.update(extra)
    return env


def _run_example(path, args, timeout=240, device_count=2):
    env = cpu_subprocess_env(device_count)
    proc = subprocess.run(
        [sys.executable, "-u", os.path.join(REPO, path)] + args,
        env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    result = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(result)


def _make_real_dataset(root, classes=4, per_class=48, size=48, seed=0):
    """Real JPEGs on disk with visually-learnable classes (distinct base
    colors + noise) in class-per-subdirectory layout."""
    from PIL import Image
    rng = np.random.RandomState(seed)
    palette = [(220, 40, 40), (40, 220, 40), (40, 40, 220), (220, 220, 40),
               (220, 40, 220), (40, 220, 220), (230, 140, 30),
               (130, 70, 200), (110, 190, 90), (160, 160, 160)]
    assert classes <= len(palette)
    for c in range(classes):
        d = os.path.join(root, "class_%d" % c)
        os.makedirs(d, exist_ok=True)
        for i in range(per_class):
            img = np.ones((size, size, 3), np.float32) * palette[c]
            img += rng.randn(size, size, 3) * 25.0
            Image.fromarray(np.clip(img, 0, 255).astype(np.uint8)).save(
                os.path.join(d, "img%03d.jpg" % i))
    return root


@pytest.fixture()
def store():
    """A fresh in-process coordination store per test."""
    with EmbeddedStore() as s:
        set_global_endpoints(s.endpoint)
        yield s


@pytest.fixture(params=["py", "native"])
def coord(request):
    """A CoordClient on an isolated root namespace, parametrized over both
    store backends: the Python StoreServer and the C++ edl_tpu_store binary
    (identical wire protocol)."""
    if request.param == "py":
        with EmbeddedStore() as s:
            set_global_endpoints(s.endpoint)
            client = s.client(root="test_job")
            yield client
            client.clean_root()
    else:
        from edl_tpu.coordination.client import CoordClient
        from edl_tpu.coordination.native import (NativeStoreServer,
                                                 ensure_binary)
        try:
            ensure_binary()
        except Exception as e:  # no C++ toolchain → skip, don't error
            pytest.skip("native store unavailable: %r" % e)
        with NativeStoreServer() as s:
            set_global_endpoints(s.endpoint)
            yield CoordClient([s.endpoint], root="test_job")
