"""The yardstick itself: trace reduction, traffic, the compile watch,
`attempted`, and the controls that `correct` has to refuse."""

import argparse
import collections
import json
import os
import time

import numpy as np
import pytest

from benchmark.lib import harness, traffic, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def _tiny(name, folder):
    d = harness.load_json(os.path.join(harness.BENCH, folder, name + ".json"))
    return dict(d, **d.get("tiny", {}))


def _serve_fixture():
    """The serving kind has no cell yet: its toy mix and the entries it
    would run under live in fixtures/serve-cell.json."""
    return harness.load_json(os.path.join(HERE, "fixtures",
                                          "serve-cell.json"))


@pytest.fixture
def serve_cell(monkeypatch):
    """`run.py --workload gpt2s-serve-steady` finds the fixture's cell."""
    fx = _serve_fixture()

    def cell_spec(workload):
        cell = [c for c in fx["bench"]["workloads"]
                if c["name"] == workload][0]
        config = harness.load_json(os.path.join(
            harness.ROOT, fx["bench"]["configs"][0]["file"]))
        return fx["bench"], cell, config, fx["traffic"]

    monkeypatch.setattr(harness, "cell_spec", cell_spec)
    return fx


def test_xplane_reduction_on_a_small_recorded_trace():
    events = [tuple(e) for e in harness.load_json(
        os.path.join(HERE, "fixtures", "trace_small.json"))["events"]]
    r = xplane.reduce_events(events, "bench:")
    ns = 1e-9
    assert r["planes"] == 1
    assert r["window_s"] == pytest.approx(1000 * ns)
    # union of [100,260) and [300,450): overlap counted once
    assert r["busy_s"] == pytest.approx(310 * ns)
    assert dict(map(tuple, r["ops"]))["fusion"] == pytest.approx(200 * ns)
    assert dict(map(tuple, r["ops"]))["all-reduce"] == pytest.approx(70 * ns)
    assert r["modules"]["jit_step"]["count"] == 1
    assert r["modules"]["jit_step"]["seconds"] == pytest.approx(350 * ns)
    assert r["collective_s"] == pytest.approx(70 * ns)
    assert r["collective_exposed_s"] == pytest.approx(50 * ns)
    assert r["spans"]["steps"]["busy_chip_s"] == pytest.approx(310 * ns)
    assert r["spans"]["steps"]["seconds"] == pytest.approx(450 * ns)
    assert "not_ours" not in r["spans"]
    xplane_gaps = xplane.BETWEEN_OPS_NS
    xplane.BETWEEN_OPS_NS = 0
    try:
        gaps = xplane.reduce_events(events, "bench:")["idle_gaps"]
    finally:
        xplane.BETWEEN_OPS_NS = xplane_gaps
    named = {(w, round(s / ns)) for w, s in gaps}
    # [260,300) lies in train_step (the shortest span that holds it),
    # [0,100) has its midpoint in steps, [450,1000) in no span of ours
    assert {("train_step", 40), ("steps", 100),
            ("host_other", 550)} <= named


def test_traffic_is_fixed_by_the_file_and_ordered_by_the_seed():
    job = _serve_fixture()["traffic"]
    a = traffic.chat_schedule(job, 211, 5000000011, 10.0)
    b = traffic.chat_schedule(job, 211, 5000000011, 10.0)
    c = traffic.chat_schedule(job, 211, 12, 10.0)
    assert len(a) == len(c) == round(job["rate_rps"] * 10.0)
    assert all(x[0] == y[0] and x[2] == y[2] and (x[1] == y[1]).all()
               for x, y in zip(a, b))
    lens = lambda s: collections.Counter(len(t) for _, t, _ in s)
    assert lens(a) == lens(c) and [x[0] for x in a] != [x[0] for x in c]
    assert all(0 < d < 10.0 for d, _, _ in a)
    assert all(job["prompt"]["min"] <= len(t) <= job["prompt"]["max"]
               and len(t) + n <= job["max_total"] for _, t, n in a)


def test_warm_up_covers_every_bucket_of_the_range():
    from edl_tpu.serve.decode_engine import _prefill_bucket
    toy = _serve_fixture()["traffic"]
    # and the range PR 24 proved on the chip: prompts of 16-768 tokens
    # into 192 slots of 1024 positions
    wide = dict(toy, slots=192, prompt=dict(toy["prompt"], min=16, max=768))
    for job, max_len in ((wide, 1024), (toy, 64)):
        plan = traffic.warm_plan(job, 50257, max_len, 3)
        lo, hi = job["prompt"]["min"], job["prompt"]["max"]
        reach = {_prefill_bucket(n, max_len) for n in range(lo, hi + 1)}
        assert reach == {_prefill_bucket(len(t), max_len)
                         for t, _ in plan["cold"]}
        # a chance hit reuses 1 token or more: suffix widths of n - 1
        suffix = {_prefill_bucket(n - 1, max_len)
                  for n in range(lo, hi + 1)}
        assert suffix <= {_prefill_bucket(len(t) - 1, max_len)
                          for t, _ in plan["shared"]}
        assert all(s[0] == c[0] and (s[1:] != c[1:]).any()
                   for (s, _), (c, _) in zip(plan["shared"], plan["cold"]))
        assert len(plan["flood"]) == job["slots"]
        assert job["slots"] > 2 * len(plan["cold"])  # donors stay cached


class _FakeHandle(object):
    def __init__(self, born, n, step_s):
        self.born, self.n, self.step_s = born, n, step_s

    def tokens_from(self, start):
        have = min(self.n, int((time.monotonic() - self.born)
                               / self.step_s))
        return list(range(start, have)), have >= self.n


class _FakeEngine(object):
    def __init__(self, step_s):
        self.step_s = step_s

    def submit(self, tokens, max_new):
        return _FakeHandle(time.monotonic(), max_new, self.step_s)


def _fake_run():
    import contextlib
    ns = argparse.Namespace()
    ns.span = lambda name: contextlib.nullcontext()
    return ns


@pytest.mark.parametrize("seed", [7, 5000000011])
def test_attempted_is_the_schedule_whatever_the_speed(seed):
    serve = harness.load_module("kinds", "serve")
    job = _serve_fixture()["traffic"]
    counts = []
    for step_s in (0.002, 0.08):   # a fast engine and a 40x slower one
        sched = traffic.chat_schedule(job, 211, seed, 1.0)
        client = serve.Client(_fake_run(), _FakeEngine(step_s), 0.001)
        reqs, t0, t1 = client.serve(sched, 1.0, drain_cap_s=0.2)
        s = serve.summarize(reqs, t0, t1, client)
        counts.append((s["attempted"], s["failed"]))
        assert s["attempted"] == len(sched)
    assert counts[0][0] == counts[1][0]
    # the slow engine leaves requests decoding at the cap: not failures
    assert counts[1][1] == 0


def test_a_compile_inside_the_window_refuses_the_run():
    import jax
    import jax.numpy as jnp
    run = harness.Run(argparse.Namespace(
        workload="gpt2s-train", seed=1, seconds=1, trace=0, cpu_tiny=True),
        time.monotonic())
    run.claim_devices()
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(3)).block_until_ready()
    run.window_open()
    f(jnp.ones(3)).block_until_ready()          # cached: no event
    assert run.compiles.window == []
    with run.compiles.excused():
        f(jnp.ones(5)).block_until_ready()      # inside a measured pause
    assert run.compiles.window == [] and run.compiles.excused_events
    f(jnp.ones(7)).block_until_ready()          # a forced retrace
    run.window_close()
    assert run.compiles.window
    with pytest.raises(harness.BenchError, match="inside the measured"):
        run.finish(1, 0, {"train_samples_s_chip": 1.0})


def test_no_result_for_an_unknown_cell(capfd):
    from benchmark import run as entry
    assert entry.main(["--workload", "nope", "--seed", "1", "--seconds",
                       "1"]) == 2
    assert capfd.readouterr().out == ""


def _tiny_run(workload, seed=3):
    run = harness.Run(argparse.Namespace(
        workload=workload, seed=seed, seconds=0, trace=0, cpu_tiny=True),
        time.monotonic())
    run.claim_devices()
    return run


def _train_numbers(workload, control):
    train = harness.load_module("kinds", "train")
    run = _tiny_run(workload)
    return (train.compared_numbers(run, [control]).get(control),
            run.traffic["limits"])


def _plain_steps(j, n_steps):
    """The oracle: ONE un-donated step that updates after every gradient,
    read or not — what kinds/train.py ran until PR 56."""
    import jax
    from benchmark.lib import optim
    cfg, ref, spec = j["cfg"], j["ref"], j["job"]["optimizer"]

    @jax.jit
    def step(w, m, v, t, batch):
        loss, g = ref.loss_and_grad(w, batch, cfg, None)
        w2, st = optim.ref_update(spec, w, g, {"m": m, "v": v, "t": t})
        return loss, j["fam"].to_program(g, cfg)[0], w2, st["m"], st["v"]

    w = j["w"]
    st = optim.ref_init(spec, w)
    m, v = st["m"], st["v"]
    losses, g0 = [], None
    for t in range(n_steps):
        loss, g, w, m, v = step(w, m, v, t, j["batch"])
        losses.append(float(loss))
        g0 = g if t == 0 else g0
    return losses, g0


@pytest.mark.parametrize("workload", ["gpt2s-train", "resnet50vd-train",
                                      "smallthinker-moe-train-8k"])
@pytest.mark.parametrize("n_steps", [1, 2])
def test_reference_steps_are_the_plain_loop_s(workload, n_steps):
    """`last` (one step, no update) and `advance` (several, m and v
    donated) give the losses and the first gradient of the plain loop,
    and the seed's weights outlive them: the controls read them again."""
    import jax
    train = harness.load_module("kinds", "train")
    j = train.make_job(_tiny_run(workload))
    want_losses, want_g0 = _plain_steps(j, n_steps)
    for _ in range(2):       # a second call finds j["w"] as it was
        losses, g0 = train.reference_steps(j, n_steps)
        assert losses == pytest.approx(want_losses, rel=1e-6)
        assert float(train._tree_rel_err(g0, want_g0)) <= 1e-6
    assert not any(x.is_deleted()
                   for x in jax.tree_util.tree_leaves(j["w"]))
    assert set(j["ref_steps"]) == {None}


@pytest.mark.parametrize("workload", ["gpt2s-train",
                                      "smallthinker-moe-train-8k"])
def test_program_then_controls_in_one_process(workload):
    """tools/limits.py's call: the program's check, then the sound
    reference and the control again, all on the one job's weights — at
    `check_steps` 2 and at 1."""
    train = harness.load_module("kinds", "train")
    run = _tiny_run(workload)
    got = train.compared_numbers(run, [None, "int8"])
    assert set(got) == {None, "int8"}
    for name, limit in run.traffic["limits"].items():
        assert got[None][name] <= limit
    assert got["int8"]["grad_rel_err"] > 3 * got[None]["grad_rel_err"]
    assert got["int8"]["grad_rel_err"] > run.traffic["limits"][
        "grad_rel_err"]
    with open(run.out_path) as f:
        peaks = [json.loads(line)["check_peak_bytes"] for line in f
                 if "check_peak_bytes" in line]
    assert len(peaks) == 1    # once a check; the CPU reports no memory


def test_int8_control_is_refused_for_a_training_cell():
    """The control — the reference with int8 operands in the program's
    place — reads at least three times the sound run, at test size, and
    the file's limit lies between them."""
    sound, limits = _train_numbers("gpt2s-train", None)
    control, _ = _train_numbers("gpt2s-train", "int8")
    for name, limit in limits.items():
        assert control[name] > 3 * sound[name]
        assert control[name] > limit > sound[name]


def test_token_regret_refuses_wrong_tokens(serve_cell):
    """At test size the int8 weight path picks the reference's tokens (the
    chip run at the cell's size is what separates it, PERF.md); what the
    test can hold is that tokens the reference ranks low read high."""
    import jax
    serve = harness.load_module("kinds", "serve")
    run = harness.Run(argparse.Namespace(
        workload="gpt2s-serve-steady", seed=3, seconds=0, trace=0,
        cpu_tiny=True), time.monotonic())
    run.claim_devices()
    cfg = run.config
    ref = run.reference()
    w = jax.jit(lambda k: ref.init_weights(cfg, k))(harness.key_from_seed(3))
    j = {"cfg": cfg, "ref": ref, "w": w}
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg["vocab_size"], size=(4, 12), dtype=np.int32)
    good = np.asarray(ref.greedy(
        w, np.pad(prompts, ((0, 0), (0, 20))), np.full(4, 12), 6, cfg))
    total, n, agree = serve.token_regret(
        run, j, [(p, list(g)) for p, g in zip(prompts, good)])
    assert n == 24 and agree == 24 and total == 0.0
    bad = rng.integers(0, cfg["vocab_size"], size=good.shape)
    total, n, agree = serve.token_regret(
        run, j, [(p, list(g)) for p, g in zip(prompts, bad)])
    assert total / n > 0.5 and agree < n


@pytest.mark.parametrize("trace", [0, 1])
def test_serving_kind_end_to_end(serve_cell, trace, capfd):
    """The serving kind through run.py at toy sizes, under the fixture's
    entries: the last line of stdout is the contract's object."""
    import json
    from benchmark import run as entry
    assert entry.main(["--workload", "gpt2s-serve-steady", "--seed",
                       "5000000011", "--seconds", "2", "--trace",
                       str(trace), "--cpu_tiny"]) == 0
    lines = capfd.readouterr().out.strip().splitlines()
    assert len(lines) == 1
    res = json.loads(lines[0])
    top = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(res) == (top | {"breakdown"} if trace else top)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 12   # 6 req/s for 2 s, whatever the seed
    names = {m["name"] for m in serve_cell["bench"][
        "per_layer" if trace else "end_to_end"]}
    if trace:
        assert "window_compiles.serve" in res["metrics"]
        assert set(res["metrics"]) <= names
    else:
        assert set(res["metrics"]) == names


def test_reference_matches_the_program_at_float32():
    """The relabelling (benchmark/program/gpt.py) and the reference agree
    with the program's own model when both compute in float32."""
    import jax
    import jax.numpy as jnp
    from edl_tpu.models import gpt
    cfg = _tiny("gpt2-small", "configs")
    ref = harness.load_module("reference", "gpt2-small")
    fam = harness.load_module("program", "gpt")
    w = jax.jit(lambda k: ref.init_weights(cfg, k))(jax.random.PRNGKey(1))
    params, _ = fam.to_program(w, cfg)
    model = gpt.Gpt(vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
                    d_model=cfg["n_embd"], num_heads=cfg["n_head"],
                    mlp_dim=4 * cfg["n_embd"], max_len=cfg["n_positions"],
                    dtype=jnp.float32, use_flash=False)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0,
                             cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids)
    want = ref.logits(w, ref.hidden(w, ids, cfg))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
