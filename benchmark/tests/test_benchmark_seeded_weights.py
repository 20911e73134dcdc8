"""How the four sparse cells' weights are seeded (each configuration's
`assumed.weights`; PERF.md section 6, PRs 39 and 51): the embedding and the
routers at ranges of their own, everything else at `initializer_range`.
PLUMBING ONLY: that each reference reads the two keys and scales the two
kinds of tensor by them, on the CPU at the `tiny` shapes with the full
size's ranges (two `tiny` blocks keep 0.02, PERF.md section 7). What the
ranges cure — every row of layers 1 to 3 entering the router as nearly
one vector — needs thousands of tokens at width 2048 and shows only on
the chip, by benchmark/tools/routing.py.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_benchmark_seeded_weights.py
"""

import argparse
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import harness  # noqa: E402

CELLS = ["smallthinker-moe-train-8k", "keye-dsa-train-16k",
         "sdar-bd4-train-8k", "qwen3next-gdn-train-16k"]
OWN = ("embedding_initializer_range", "router_initializer_range")
SEED = 5100000501


@pytest.mark.parametrize("cell", CELLS)
def test_embedding_and_routers_are_seeded_at_their_own_ranges(cell):
    run = harness.Run(argparse.Namespace(
        workload=cell, seed=SEED, seconds=0, trace=0, cpu_tiny=True),
        time.monotonic())
    full = harness.cell_spec(cell)[2]
    cfg = dict(run.config, **{k: full[k] for k in OWN})
    assert (cfg["embedding_initializer_range"],
            cfg["router_initializer_range"]) == (1.0, 0.16)
    w = run.reference().init_weights(cfg, harness.key_from_seed(run.seed))
    std = lambda x: float(np.std(np.asarray(x)))  # noqa: E731
    assert std(w["embed"]) == pytest.approx(1.0, rel=0.1)
    routers = np.concatenate([np.ravel(v) for k, v in w.items()
                              if k.endswith("/w_r")])
    assert std(routers) == pytest.approx(0.16, rel=0.1)
    for name in ("head", "0/w_gate_up", "0/w_down"):
        assert std(w[name]) == pytest.approx(cfg["initializer_range"],
                                             rel=0.1)
