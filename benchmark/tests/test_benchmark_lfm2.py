"""The `lfm2-conv-train-8k` cell: end to end at its `tiny` sizes on the CPU
(one process, as the driver runs it) with every new reader that has
something to read on a CPU returning a number; its full-size step compiled
for a described (not attached) TPU v5e, with `memory_analysis` printed and
the kernels' calls a step counted; what `correct` holds at once — the kind's
own reference step beside the trainer (benchmark/tests/
test_benchmark_check_memory.py's arithmetic, whose `CASES` is the
benchmark's and is not edited); and the family's counts, the shares the
traffic file's `batch_why` quotes among them. Nothing runs there, and a
compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python3 -m pytest \
        benchmark/tests/test_benchmark_lfm2.py -s
"""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.lib import harness, kernel_readers, optim  # noqa: E402

CELL = "lfm2-conv-train-8k"
NEW_METRICS = ("shortconv_gate_device_ms", "shortconv_gate_roofline_pct",
               "shortconv_gate_absmax")
PARAMETERS = 507820288
GIB = float(1 << 30)
#: ISSUE 63's rule for the cut, as ISSUE 61's: the check and the step each
#: within 16.0e9 of the chip's 16.909e9 bytes
ROOM = 16.0e9


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


def _cell():
    _, cell, cfg, job = harness.cell_spec(CELL)
    return cell, cfg, job, harness.load_module("program", cfg["family"])


def _on(chip, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        tree)


def _sizes(tree):
    return [x.size for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_at_tiny_sizes(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "6300000063", "--seconds", "1", "--trace", str(trace),
         "--cpu_tiny"], cwd=ROOT, env=env, timeout=900,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1
    res = json.loads(lines[0])
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"   # never a device number
    if trace:
        # the counters went device -> trainer.close() -> registry -> reader
        got = res["metrics"]
        assert got["shortconv_gate_absmax"]["value"] > 0.0
        assert got["moe_route_weight_sum"]["value"] == pytest.approx(
            1.0, rel=1e-5)
        assert 0.0 < got["moe_bias_choice_flips_pct"]["value"] < 50.0
        assert got["moe_rows_dropped"]["value"] == 0.0
    else:
        assert res["metrics"]["train_samples_s_chip"]["value"] > 0


def test_benchmark_lists_the_cell_where_it_reports():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())]
    shared = [n for n in listed if n not in NEW_METRICS]
    assert len(shared) == len(listed) - 3   # found by name, not by place
    for name in NEW_METRICS:
        m = [m for m in bench["per_layer"] if m["name"] == name][0]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_samples_s_chip"
    assert sorted(shared) == sorted([
        "train_step_host_ms", "step_device_ms", "step_mfu_pct",
        "window_compiles.train", "device_idle_pct.train",
        "scope_coverage_pct", "step_fwd_device_ms", "step_bwd_device_ms",
        "step_remat_device_ms", "step_optim_device_ms", "attn_device_ms",
        "mixer_device_ms", "ffn_device_ms", "head_loss_device_ms",
        "other_device_ms",
        "moe_expert_load_max_over_mean", "moe_rows_dropped",
        "moe_gmm_device_ms", "moe_gmm_roofline_pct", "moe_tgmm_device_ms",
        "moe_tgmm_roofline_pct", "moe_rows_moved_over_served",
        "moe_bias_choice_flips_pct", "moe_route_weight_sum",
        "flash_bwd_device_ms", "flash_fwd_resident_device_ms",
        "flash_fwd_resident_roofline_pct"])
    cell = [c for c in bench["workloads"] if c["name"] == CELL][0]
    assert (cell["chips"], cell["traffic"]) == (1, "tokens-8192-conv")
    assert CELL in [m for m in bench["end_to_end"]
                    if m["name"] == "train_samples_s_chip"][0]["workloads"]


def test_train_flops_and_kernel_costs_count_what_they_say():
    """The family's counts, and the shares the traffic file's `batch_why`
    quotes, recomputed."""
    _, cfg, job, fam = _cell()
    t, d = 8192, 2048
    assert (job["seq_len"], job["batch_per_chip"], job["remat"]) == (
        t, 1, True)
    w = fam.matrix_weights_per_token(cfg)
    assert w == {"conv": 4 * d * d + 3 * d,
                 "attention": d * (32 + 2 * 8) * 64 + 32 * 64 * d,
                 "dense": 3 * d * 7168, "router": d * 32,
                 "head": d * 16384}
    rows = t * 4 * 8 / 32.0                         # a layer, even routing
    assert fam.expected_expert_rows(cfg, t) == rows == 8192.0
    core = 3.0 * (t * (t + 1) / 2.0) * 32 * 2 * 2 * 64
    routed = 4 * 6.0 * rows * 3 * d * 1792
    flops = fam.train_flops(cfg, job, 1)
    assert flops == pytest.approx(
        6.0 * t * (4 * w["conv"] + w["attention"] + w["dense"]
                   + 4 * w["router"] + w["head"]) + routed + core)
    assert 10.5e12 < flops < 10.8e12
    shares = {"conv": 4 * 6.0 * t * w["conv"] / flops,
              "experts": routed / flops,
              "dense": 6.0 * t * w["dense"] / flops,
              "head": 6.0 * t * w["head"] / flops,
              "attention": (6.0 * t * w["attention"] + core) / flops}
    print("shares " + json.dumps(shares))
    quoted = {"conv": 31, "experts": 20, "dense": 20, "head": 16,
              "attention": 13}
    for part, pct in quoted.items():
        assert round(100 * shares[part]) == pct, (part, shares[part])
        assert "%d%%" % pct in job["batch_why"]
    assert "%.2f TFLOP" % (flops / 1e12) in job["batch_why"]
    costs = fam.kernel_costs(cfg, job, 1)
    assert sorted(costs) == ["flash_fwd_resident", "moe_gmm", "moe_tgmm",
                             "shortconv_gate"]
    # ONE attention layer, its forward twice under remat
    assert costs["flash_fwd_resident"][0] == pytest.approx(2 * core / 3)
    assert costs["flash_fwd_resident"][1] == pytest.approx(
        2 * t * 64 * (2 * 32 + 2 * 8) * 2.0)
    weights = 3 * d * 1792
    assert costs["moe_gmm"][0] == pytest.approx(2 * 2.0 * 4 * rows * weights)
    assert costs["moe_tgmm"][0] == pytest.approx(2.0 * 4 * rows * weights)
    # the scope between a conv layer's two products: forward 4 arrays of
    # tokens x channels, twice under remat, backward 7; bfloat16
    ops, nbytes = costs["shortconv_gate"]
    assert nbytes == 4 * t * d * 2.0 * (2 * 4 + 7)
    assert ops / 197e12 < 0.01 * nbytes / 819e9     # the bytes decide
    bare = fam.kernel_costs(cfg, dict(job, remat=False), 1)
    assert bare["shortconv_gate"][1] == 4 * t * d * 2.0 * 11
    # THE 5-ENTRY AVERAGE: the readers hand over the mean of `rows_held`
    # over ALL the counters' entries, the dense layer's zero among them
    counters = {"rows_held": [0.0, 80000.0, 81000.0, 82000.0, 83000.0],
                "steps": [10.0]}
    mean = kernel_readers.expert_rows_per_step(counters)
    served = 8000 + 8100 + 8200 + 8300
    assert mean == pytest.approx(served / 5.0)
    got = fam.kernel_costs(cfg, job, 1, mean)
    assert got["moe_gmm"][0] == pytest.approx(2 * 2.0 * served * weights)
    assert got["shortconv_gate"] == costs["shortconv_gate"]


def test_readers_on_a_fixture_line(monkeypatch):
    """The two scope readers on a table as `scope_readers.table` gives it,
    against this family's `kernel_costs`; the counter reader on counters as
    the trainer mirrors them; and None, not an error, where the trace or
    the program has none (the parent commit); a share over 100 is
    reported as computed, for the driver's check to judge."""
    from benchmark.lib import scope_readers
    _, cfg, job, fam = _cell()
    view = {"trace": {"ops": []}, "counters": {"traced_steps": 10},
            "config": cfg, "traffic": job, "cell": {"chips": 1, "name": CELL},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}}
    monkeypatch.setattr(kernel_readers, "model_counters", lambda: {})
    pct_mod = harness.load_module("metrics", "shortconv_gate_roofline_pct")
    ms_mod = pct_mod._gate          # the one reader of the scope's time
    table = {("mixer.conv.gate", "fwd"): 1.0,
             ("mixer.conv.gate", "remat"): 1.0,
             ("mixer.conv.gate", "bwd"): 3.0,
             ("mixer.conv.out", "fwd"): 9.0, ("attn.full", "bwd"): 2.0}
    monkeypatch.setattr(ms_mod, "table", lambda view: table)
    assert ms_mod.read(view) == pytest.approx(5.0)
    nbytes = fam.kernel_costs(cfg, job, 1)["shortconv_gate"][1]
    assert pct_mod.read(view) == pytest.approx(
        100.0 * nbytes / 819e9 / 5e-3)
    assert 0.0 < pct_mod.read(view) < 100.0
    # no cut-off: a scope that kept less time than its bytes need reads
    # over 100, as computed
    table[("mixer.conv.gate", "bwd")] = 0.1
    assert ms_mod.read(view) == pytest.approx(2.1)
    assert pct_mod.read(view) == pytest.approx(
        100.0 * nbytes / 819e9 / 2.1e-3)
    assert pct_mod.read(view) > 100.0
    # no such scope in the trace; no trace
    for none in ({("attn.full", "bwd"): 2.0}, None):
        monkeypatch.setattr(ms_mod, "table", lambda view, t=none: t)
        assert ms_mod.read(view) is None and pct_mod.read(view) is None
    assert scope_readers.table(dict(view, counters={})) is None
    counters = {"conv_gate_absmax": [3.5, 0.0, 7.25, 2.0, 1.0],
                "steps": [10.0]}
    mod = harness.load_module("metrics", "shortconv_gate_absmax")
    monkeypatch.setattr(mod, "model_counters", lambda: counters)
    assert mod.read(view) == 7.25
    monkeypatch.setattr(mod, "model_counters", lambda: {})
    assert mod.read(view) is None


def _batch(job):
    shape = (job["batch_per_chip"], job["seq_len"])
    return {"input_ids": jax.ShapeDtypeStruct(shape, jnp.int32)}


def test_train_step_compiles_and_fits(one_chip, monkeypatch):
    """The cell's own step at published widths, 1 x 8192 tokens: k + v of a
    key-value head of 64 are 2 MiB, so the attention layer runs the
    RESIDENT forward twice (remat) and the one resident backward; the four
    expert layers run the grouped products; the conv layers call no kernel
    — and it fits."""
    from edl_tpu.runtime.trainer import make_train_state, make_train_step
    # the dispatches ask jax.default_backend(); this compile is for a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _, cfg, job, fam = _cell()
    loss_fn, has_aux, shapes = fam.train_parts(cfg, job)
    assert sum(_sizes(shapes[0])) == PARAMETERS
    tx = optim.make_tx(job["optimizer"])
    state = jax.eval_shape(lambda p, e: make_train_state(p, tx, e), *shapes)
    compiled = jax.jit(make_train_step(loss_fn, tx, has_aux),
                       donate_argnums=(0,)).lower(
        _on(one_chip, state), _on(one_chip, _batch(job)),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print("memory_analysis " + json.dumps({
        "program": "%s step" % CELL,
        "argument_gib": m.argument_size_in_bytes / GIB,
        "output_gib": m.output_size_in_bytes / GIB,
        "temp_gib": m.temp_size_in_bytes / GIB,
        "alias_gib": m.alias_size_in_bytes / GIB,
        "total_gib": total / GIB, "total_bytes": total}))
    text = compiled.as_text()
    kernels = sorted(set(re.findall(
        r"%?([\w.\-]+) = [^\n]*custom-call[^\n]*tpu_custom_call", text)))
    print("kernels " + json.dumps(kernels))
    calls = {name: sum(name in k for k in kernels)
             for name in ("flash_fwd_resident", "flash_fwd_stream",
                          "flash_bwd", "moe_gmm", "moe_tgmm", "kda_",
                          "gdn_", "ssd_", "dsa_", "bdiff_")}
    n_c = sum(fam.conv_layers(cfg))
    n_a = len(fam.conv_layers(cfg)) - n_c
    n_e = len(fam.dense_layers(cfg)) - sum(fam.dense_layers(cfg))
    assert (n_c, n_a, n_e) == (4, 1, 4)
    # an expert layer: up and down forward (saved under remat), their two
    # dx products and two dw products backward
    assert calls == {"flash_fwd_resident": 2 * n_a, "flash_fwd_stream": 0,
                     "flash_bwd": n_a, "moe_gmm": 4 * n_e,
                     "moe_tgmm": 2 * n_e, "kda_": 0, "gdn_": 0, "ssd_": 0,
                     "dsa_": 0, "bdiff_": 0}
    assert total < ROOM


def test_reference_step_fits_beside_the_trainer(one_chip):
    """test_benchmark_check_memory.py's case for this cell: the kind's OWN
    `last` lowered for a described v5e — the gradient its only
    parameter-sized output, nothing aliased, 20 bytes a parameter beside
    its temporaries and image, and the whole within the cut's room."""
    train = harness.load_module("kinds", "train")
    cell, cfg, job, fam = _cell()
    assert job["check_steps"] == 1
    j = {"cfg": cfg, "job": job, "ref_steps": {}, "fam": fam,
         "ref": harness.load_module("reference", cell["config"])}
    key = jax.random.PRNGKey(0)
    w = jax.eval_shape(lambda k: j["ref"].init_weights(cfg, k), key)
    batch = jax.eval_shape(lambda k: fam.make_batch(
        cfg, job, k, job["batch_per_chip"]), key)
    n_params = sum(_sizes(w))
    assert n_params == PARAMETERS
    last = train._reference_step_fns(j, None)["last"]
    again = [w] * train._WEIGHTS_SHOWN_AGAIN
    loss, grad = jax.eval_shape(last, w, batch, *again)
    program_params = jax.eval_shape(lambda w: fam.to_program(w, cfg), w)[0]
    assert loss.shape == () and sorted(_sizes(grad)) == sorted(
        _sizes(program_params))
    m = last.lower(_on(one_chip, w), _on(one_chip, batch),
                   *_on(one_chip, again)).compile().memory_analysis()
    assert m.output_size_in_bytes < 4.0 * n_params * 1.001
    assert m.alias_size_in_bytes == 0      # j["w"] is never donated
    own = m.temp_size_in_bytes + m.generated_code_size_in_bytes
    assert m.generated_code_size_in_bytes < 0.3 * GIB
    held = (12.0 * n_params + m.argument_size_in_bytes
            - 4.0 * n_params * len(again) + m.output_size_in_bytes + own)
    print("memory_analysis " + json.dumps({
        "program": "%s reference last step" % CELL,
        "parameters": n_params,
        "argument_gib": m.argument_size_in_bytes / GIB,
        "output_gib": m.output_size_in_bytes / GIB,
        "temp_gib": m.temp_size_in_bytes / GIB,
        "code_gib": m.generated_code_size_in_bytes / GIB,
        "check_resident_gib": held / GIB, "check_resident_bytes": held,
        "bytes_a_parameter_beside_the_program_s_own":
            (held - own) / n_params}))
    assert held - own < 20.01 * n_params
    assert held < ROOM
