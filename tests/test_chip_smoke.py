"""Guards on chip_smoke.py that need no chip: the parent stays off jax
(a parent that has touched JAX holds the chip its children need), and
with no TPU the script fails fast, names the missing device and prints
no result line. The end-to-end run of its phases at the CPU debug size
is marked slow (tier-1 deselects it)."""

import ast
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import REPO

SMOKE = os.path.join(REPO, "chip_smoke.py")


def _top_level_imports(path):
    with open(path) as f:
        tree = ast.parse(f.read())
    names = []
    for node in tree.body:  # module level only: children import lazily
        if isinstance(node, ast.Import):
            names += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names.append(node.module or "")
    return names


def test_parent_imports_no_jax():
    # nothing of the repo at module level either: most of edl_tpu pulls
    # jax in, and the script must fail cleanly in a bare directory
    bad = [n for n in _top_level_imports(SMOKE)
           if n.split(".")[0] in ("jax", "jaxlib", "flax", "optax",
                                  "edl_tpu")]
    assert not bad, "chip_smoke.py imports %s at top level" % bad
    # the two modules the PARENT's phases import stay off jax too
    code = ("import sys; import edl_tpu.parallel.costmodel, "
            "edl_tpu.utils.compile_cache, edl_tpu.rpc.client; "
            "sys.exit('jax' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          timeout=60).returncode == 0


def _run_smoke(script, cwd, *args, env=None, timeout=120):
    # the harness env pins JAX_PLATFORMS=cpu; the smoke's chip-side
    # children override it with tpu, which this sandbox cannot satisfy
    return subprocess.run([sys.executable, script] + list(args), cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_no_tpu_fails_fast_and_names_the_device():
    r = _run_smoke(SMOKE, REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == "", "a result line without a chip"
    assert "phase probe FAILED" in r.stderr
    assert "backend 'tpu'" in r.stderr or "TPU" in r.stderr


def test_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, str(tmp_path / "chip_smoke.py"))
    r = _run_smoke(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == ""


@pytest.mark.slow
def test_phases_end_to_end_at_the_cpu_debug_size(tmp_path):
    # the smoke checks that its restart hits the compile cache, so the
    # cache the harness switches off (conftest) is on here, placed from
    # outside and out of the checkout
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    r = _run_smoke(SMOKE, REPO, "--cpu_tiny", env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # exactly the contract's keys: the driver refuses anything more
    assert set(out) == {"ok", "device"} and out["ok"] is True
    assert set(out["device"]) == {"platform", "kind", "count"}
    assert out["device"]["platform"] == "cpu"
    assert isinstance(out["device"]["kind"], str)
    assert isinstance(out["device"]["count"], int)
    with open(os.path.join(REPO, "chiprun_out", "chip_smoke",
                           "result.json")) as f:
        assert set(json.load(f)["phases"]) == {"train", "kernel", "serve"}
