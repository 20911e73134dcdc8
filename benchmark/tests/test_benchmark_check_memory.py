"""What `correct` holds on the chip at once in a training cell whose
`check_steps` is 1 (kinds/train.py:check_first_steps): the trainer's state
— parameters and two moments, 12 bytes a parameter — beside the arguments,
outputs and temporaries of the reference's LAST step, whose only
parameter-sized argument is the seed's weights (4) and whose only
parameter-sized output is the gradient in the program's layout (4): 20
bytes a parameter, the temporaries and the program's image. The kind's OWN
program is lowered for a described (not attached) TPU v5e, one case a cell
— nothing runs there, and a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_benchmark_check_memory.py -s
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402

from benchmark.lib import harness  # noqa: E402

GIB = float(1 << 30)
HBM = 16e9
#: bytes a parameter beside the last step's own arguments and outputs:
#: the trainer's parameters and its two moments
TRAINER = 12.0
#: what the check held until PR 56: the trainer (12), the reference's
#: weights, its zero moments, the moment over its scale (4 each) and all
#: four outputs of one un-donated step (16), before any temporary
BEFORE = 40.0

#: SmallThinker's four-chip share (PERF.md section 7): 16 of 64 experts
#: and a quarter of the vocabulary, the sizes `smallthinker-21b-a3b` waits
#: to move to — with ONE sequence of 8192 tokens a step: at the cell's two
#: the reference's own temporaries are 5.88 GiB (each copy of its float32
#: logits is 2.49e9 bytes) and 20 bytes a parameter + those are 18.2e9;
#: at one, 3.21 GiB: 15.4e9 in all
FOUR_CHIP_SHARE = {"config": {"moe_num_primary_experts": 16,
                              "vocab_size": 37984},
                   "traffic": {"batch_per_chip": 1}}

#: (cell, sizes set here in place of its files', parameters)
CASES = [
    ("smallthinker-moe-train-8k", {}, 307632640),
    ("keye-dsa-train-16k", {}, 257772544),
    ("sdar-bd4-train-8k", {}, 248728576),
    ("qwen3next-gdn-train-16k", {}, 259468256),
    ("ouro-loop-train-8k", {}, 230723585),
    ("kanana-mla-train-8k", {}, 330589184),
    ("smallthinker-moe-train-8k", FOUR_CHIP_SHARE, 593615360),
]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(desc.devices[0])


def _on(chip, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        tree)


def _sizes(tree):
    return [x.size for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize(
    "workload,sizes,parameters", CASES,
    ids=[w + ("-four-chip-share" if s else "") for w, s, _ in CASES])
def test_reference_step_fits_beside_the_trainer(one_chip, workload, sizes,
                                                parameters):
    train = harness.load_module("kinds", "train")
    _, cell, cfg, job = harness.cell_spec(workload)
    assert job["check_steps"] == 1
    cfg = dict(cfg, **sizes.get("config", {}))
    job = dict(job, **sizes.get("traffic", {}))
    j = {"cfg": cfg, "job": job, "ref_steps": {},
         "fam": harness.load_module("program", cfg["family"]),
         "ref": harness.load_module("reference", cell["config"])}
    key = jax.random.PRNGKey(0)
    w = jax.eval_shape(lambda k: j["ref"].init_weights(cfg, k), key)
    batch = jax.eval_shape(lambda k: j["fam"].make_batch(
        cfg, job, k, job["batch_per_chip"]), key)
    n_params = sum(_sizes(w))
    last = train._reference_step_fns(j, None)["last"]
    again = [w] * train._WEIGHTS_SHOWN_AGAIN
    loss, grad = jax.eval_shape(last, w, batch, *again)
    # the gradient, leaf for leaf the program's parameters, and a scalar
    program_params = jax.eval_shape(lambda w: j["fam"].to_program(w, cfg),
                                    w)[0]
    assert loss.shape == () and _sizes(grad) == _sizes(program_params)
    assert sum(_sizes(grad)) == n_params
    m = last.lower(_on(one_chip, w), _on(one_chip, batch),
                   *_on(one_chip, again)).compile().memory_analysis()
    assert m.output_size_in_bytes < 4.0 * n_params * 1.001
    assert m.alias_size_in_bytes == 0      # j["w"] is never donated
    # the program's own image lies in the device's memory too; the
    # compiler sizes it by the arguments it sees, which is why it is shown
    # the weights again (the same buffers on the chip: counted once here)
    own = m.temp_size_in_bytes + m.generated_code_size_in_bytes
    assert m.generated_code_size_in_bytes < 0.3 * GIB
    held = (TRAINER * n_params + m.argument_size_in_bytes
            - 4.0 * n_params * len(again) + m.output_size_in_bytes + own)
    print("memory_analysis " + json.dumps({
        "program": "%s reference last step" % workload, "sizes": sizes,
        "parameters": n_params,
        "argument_gib": m.argument_size_in_bytes / GIB,
        "output_gib": m.output_size_in_bytes / GIB,
        "temp_gib": m.temp_size_in_bytes / GIB,
        "code_gib": m.generated_code_size_in_bytes / GIB,
        "check_resident_gib": held / GIB,
        "bytes_a_parameter_beside_the_program_s_own":
            (held - own) / n_params,
        "parameters_that_would_fit": int((HBM - own) / 20.0)}))
    assert n_params == parameters
    assert held - own < 20.01 * n_params
    if sizes:
        assert BEFORE * n_params > HBM     # it did NOT fit the old check
    assert held < HBM
