"""Test harness config.

Tests never touch real TPU hardware: JAX is forced onto CPU with 8 virtual
devices so multi-chip sharding (dp/tp/sp meshes) is exercised hermetically —
the TPU analogue of the reference's "many actors against a local etcd" test
strategy (SURVEY.md §4).
"""

import atexit
import os
import shutil
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
