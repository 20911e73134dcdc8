"""`edl_tpu/obs/devtime.py`: the device's self time by the program's own
scopes. The name stacks and the recorded events come from a v5e trace of
the benchmark's cells (tests/fixtures/trace_scopes.json says which); the
registry tests keep three lists one list: the `jax.named_scope` literals
in the program, `devtime.SCOPES`, and docs/observability.md's table."""

import ast
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from edl_tpu.obs import devtime  # noqa: E402

FIXTURE = os.path.join(REPO, "tests", "fixtures", "trace_scopes.json")
_STACK = ("jit(step)/transpose(jvp(SparseDecoder))/SparseDecoder._stack/"
          "jvp(SparseDecoder)/SparseDecoder._stack/checkpoint/")
_LOOP = "SparseDecoder._looped/loop.pass/while/body/closed_call/"

#: (op_name as a v5e trace carries it, scope, phase)
NAME_STACKS = [
    # forward, inside the derivative
    ("jit(step)/jvp(SparseDecoder)/SparseDecoder._stack/layer_3/"
     "layer_3._attention/attn.window/bsd,dhk->bshk/dot_general",
     "attn.window", "fwd"),
    # backward: transpose(jvp(...)), under a checkpoint
    (_STACK + "layer_3/layer_3._attention/attn.window/bsd,dhk->bshk/"
     "transpose", "attn.window", "bwd"),
    # backward with the scope INSIDE transpose(jvp(...)): no checkpoint
    ("jit(step)/transpose(jvp(Gpt))/block_7/ffn.dense/mlp_up/dot_general",
     "ffn.dense", "bwd"),
    ("jit(step)/transpose(jvp(loss.next_token))/jit(take_along_axis)/"
     "scatter-add", "loss.next_token", "bwd"),
    # the forward run again inside the backward
    (_STACK + "rematted_computation/layer_3/layer_3._feed_forward/"
     "layer_3._own_norm/norm/norm_moe/rsqrt", "norm", "remat"),
    (_STACK + "rematted_computation/layer_0/layer_0._route/moe.route/mul",
     "moe.route", "remat"),
    # the optimizer
    ("jit(step)/optim.update/jit(_where)/select_n", "optim.update",
     "optim"),
    # Pallas kernels under the scope they were called from
    ("jit(step)/jvp(Gpt)/block_0/attn.full/attention/jit(_flash_fwd)/"
     "flash_fwd_resident/pallas_call", "attn.full", "fwd"),
    (_STACK + "layer_5/layer_5._feed_forward/moe.experts/moe_tgmm/"
     "pallas_call", "moe.experts", "bwd"),
    (_STACK + "rematted_computation/layer_1/layer_1._attention/attn.full/"
     "jit(_flash_fwd)/flash_fwd_resident/pallas_call", "attn.full",
     "remat"),
    # loop.pass round an inner scope: the innermost registered token wins
    ("jit(step)/jvp(SparseDecoder)/" + _LOOP + "SparseDecoder.one_pass/"
     "SparseDecoder._stack/layer_2/layer_2._attention/attn.full/"
     "jit(_flash_fwd)/flash_fwd_resident/pallas_call", "attn.full", "fwd"),
    ("jit(step)/transpose(jvp(SparseDecoder))/" + _LOOP + "dynamic_slice",
     "loop.pass", "bwd"),
    ("jit(step)/jvp(SparseDecoder)/SparseDecoder._stack/layer_9/"
     "layer_9._attention/attn.full/attn.gate/logistic", "attn.gate", "fwd"),
    # unregistered names, and none at all
    ("jit(step)/jvp(SparseDecoder)/SparseDecoder._stack/layer_4/add",
     "unscoped", "fwd"),
    ("jit(step)/transpose(jvp(SparseDecoder))/SparseDecoder._stack/"
     "jvp(SparseDecoder)/SparseDecoder._stack/remat2", "unscoped", "bwd"),
    ("", "unscoped", "fwd"),
    (None, "unscoped", "fwd"),
]


@pytest.mark.parametrize("op_name,scope,phase", NAME_STACKS,
                         ids=[str(i) for i in range(len(NAME_STACKS))])
def test_scope_of(op_name, scope, phase):
    assert devtime.scope_of(op_name) == (scope, phase)


def test_a_scope_s_name_inside_another_word_is_no_scope():
    # tokens, not substrings: `normalize` is not `norm`
    assert devtime.scope_of("jit(step)/jvp(M)/normalize/embedding/mul") == (
        "unscoped", "fwd")


# -- the recorded events ---------------------------------------------------


def _fixture():
    with open(FIXTURE) as f:
        doc = json.load(f)
    return doc, [tuple(e) for e in doc["events"]]


def _union_s(events):
    total = 0.0
    planes = {e[0] for e in events}
    for p in planes:
        end = None
        for _, _, _, start, dur in sorted(
                (e for e in events if e[0] == p), key=lambda e: e[3]):
            if end is None or start >= end:
                total += dur
                end = start + dur
            elif start + dur > end:
                total += start + dur - end
                end = start + dur
    return total / 1e9 / len(planes)


def test_self_times_add_up_to_the_union_of_the_intervals():
    _, events = _fixture()
    table = devtime.by_scope(events)
    assert sum(table.values()) == pytest.approx(_union_s(events), rel=1e-9)
    assert sum(devtime.by_class(events).values()) == pytest.approx(
        _union_s(events), rel=1e-9)
    # the plain sum counts a loop's body under the loop and again
    assert sum(e[4] for e in events) / 1e9 > 1.2 * _union_s(events)


@pytest.mark.parametrize("container", ["while", "cond.clone"])
def test_a_container_is_charged_only_what_its_children_leave(container):
    _, events = _fixture()
    holders = [e for e in events if devtime.op_class(e[1]) == container]
    assert holders, "the fixture holds a %s" % container
    by_class = devtime.by_class(events)
    whole = sum(e[4] for e in holders) / 1e9
    inside = 0.0
    for h in holders:
        kids = [e for e in events if e is not h and e[0] == h[0]
                and e[3] >= h[3] and e[3] + e[4] <= h[3] + h[4]]
        assert kids, "it holds other operations"
        inside += _union_s(kids)
    assert by_class[container] == pytest.approx(whole - inside, rel=1e-6)
    assert by_class[container] < 0.5 * whole


def test_the_fixture_s_kernel_lies_under_its_scope_inside_the_loop():
    doc, events = _fixture()
    table = devtime.by_scope(events)
    for key in doc["expect_keys"]:
        assert table.get(tuple(key), 0.0) > 0, key
    parts, phases = devtime.by_part(table), devtime.by_phase(table)
    assert sum(parts.values()) == pytest.approx(sum(table.values()))
    assert sum(phases.values()) == pytest.approx(sum(table.values()))
    assert parts["optim"] == pytest.approx(phases["optim"])
    assert 0.0 < devtime.coverage_pct(table) <= 100.0


def test_two_planes_are_averaged():
    _, events = _fixture()
    twin = [("/device:TPU:1",) + e[1:] for e in events]
    one, two = devtime.by_scope(events), devtime.by_scope(events + twin)
    assert two == pytest.approx(one)


def test_an_empty_table_reads_zero():
    assert devtime.by_scope([]) == {}
    assert devtime.coverage_pct({}) == 0.0
    assert set(devtime.by_part({})) == set(devtime.PARTS)
    assert set(devtime.by_phase({}).values()) == {0.0}


# -- one list of scopes ----------------------------------------------------


def _program_files():
    out = [os.path.join(REPO, "edl_tpu", "runtime", "trainer.py")]
    for folder in ("models", "parallel"):
        root = os.path.join(REPO, "edl_tpu", folder)
        out += [os.path.join(root, f) for f in sorted(os.listdir(root))
                if f.endswith(".py")]
    return out


def _scope_literals(path):
    """Every string inside the argument of a `named_scope(...)` call."""
    with open(path) as f:
        tree = ast.parse(f.read())
    found = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and getattr(node.func, "attr", None) == "named_scope"):
            names = [c.value for a in node.args for c in ast.walk(a)
                     if isinstance(c, ast.Constant)
                     and isinstance(c.value, str)]
            assert names, ("%s:%d: a scope the registry cannot see (write "
                           "its names as literals)" % (path, node.lineno))
            found += names
    return found


@pytest.mark.parametrize("path", _program_files(),
                         ids=lambda p: os.path.relpath(p, REPO))
def test_every_scope_the_program_names_has_a_part(path):
    for name in _scope_literals(path):
        assert name in devtime.SCOPES, (
            "%s names the scope %r: give it a part in "
            "edl_tpu/obs/devtime.py:SCOPES" % (path, name))


def test_every_registered_scope_is_named_by_the_program():
    named = {n for p in _program_files() for n in _scope_literals(p)}
    assert set(devtime.SCOPES) == named
    assert {part for part, _ in devtime.SCOPES.values()} <= set(
        devtime.PARTS)
    assert devtime.UNSCOPED not in devtime.SCOPES


def _documented():
    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        text = f.read()
    body = text.split("<!-- scopes:begin -->")[1].split(
        "<!-- scopes:end -->")[0]
    rows = re.findall(r"^\| `([^`]+)` \| (\w+) \| (\d+) \|", body, re.M)
    return {name: (part, int(pr)) for name, part, pr in rows}


def test_the_document_s_table_is_the_registry():
    assert _documented() == devtime.SCOPES


# -- the benchmark's ten readers ---------------------------------------------

TEN = ["scope_coverage_pct", "step_fwd_device_ms", "step_bwd_device_ms",
       "step_remat_device_ms", "step_optim_device_ms", "attn_device_ms",
       "mixer_device_ms", "ffn_device_ms", "head_loss_device_ms",
       "other_device_ms"]


@pytest.fixture
def view(monkeypatch, tmp_path):
    """A view over the recorded events, as `harness.finish` hands one to a
    metric: the trace file is found by the cell's name."""
    from benchmark.lib import harness, scope_readers
    doc, events = _fixture()
    run_dir = tmp_path / "trace" / "some-cell-7" / "plugins"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    monkeypatch.setattr(scope_readers, "_TABLES", {})
    monkeypatch.setattr(devtime, "load",
                        lambda path, span=None, names=None: events)
    return {"cell": {"name": "some-cell"},
            "counters": {"traced_steps": doc["steps"]}}


@pytest.mark.parametrize("name", TEN)
def test_a_metric_file_reads_the_recorded_trace(view, name):
    from benchmark.lib import harness
    value = harness.load_module("metrics", name).read(view)
    assert isinstance(value, float) and value >= 0.0
    doc, events = _fixture()
    ms = {k: v * 1e3 / doc["steps"]
          for k, v in devtime.by_scope(events).items()}
    want = dict(
        {"step_%s_device_ms" % p: v
         for p, v in devtime.by_phase(ms).items()},
        **{"%s_device_ms" % p: v for p, v in devtime.by_part(ms).items()})
    want["scope_coverage_pct"] = devtime.coverage_pct(ms)
    assert value == pytest.approx(want[name])
    if name in doc["absent"]:
        assert value == 0.0     # a number, never None


def test_the_parts_and_the_phases_each_add_up_to_the_whole(view):
    from benchmark.lib import harness
    read = lambda n: harness.load_module("metrics", n).read(view)
    _, events = _fixture()
    doc, _ = _fixture()
    whole = _union_s(events) * 1e3 / doc["steps"]
    phases = sum(read("step_%s_device_ms" % p) for p in devtime.PHASES)
    parts = sum(read(n) for n in (
        "attn_device_ms", "mixer_device_ms", "ffn_device_ms",
        "head_loss_device_ms", "other_device_ms", "step_optim_device_ms"))
    assert phases == pytest.approx(whole) and parts == pytest.approx(whole)


@pytest.mark.parametrize("name", TEN)
def test_a_metric_reads_none_where_the_run_left_no_trace(
        monkeypatch, tmp_path, name):
    from benchmark.lib import harness
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    assert harness.load_module("metrics", name).read(
        {"cell": {"name": "some-cell"},
         "counters": {"traced_steps": 10}}) is None


def test_the_ten_metrics_parse_the_trace_once(view, monkeypatch):
    from benchmark.lib import harness
    _, events = _fixture()
    calls = []
    monkeypatch.setattr(
        devtime, "load",
        lambda path, span=None, names=None: calls.append(span) or events)
    for name in TEN:
        harness.load_module("metrics", name).read(view)
    assert calls == ["bench:trace_window"]


def test_benchmark_json_lists_the_ten_for_cells_that_train_on_one_chip():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    one_chip = {c["name"] for c in bench["workloads"] if c["chips"] == 1}
    listed = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(TEN[0])     # later PRs append their own after them
    assert names[first:first + 10] == TEN
    for name in TEN:
        m = listed[name]
        assert m["source"] == "device_trace" and m["layer"] == "model parts"
        assert m["moves"] == "train_samples_s_chip"
        assert set(m["workloads"]) <= one_chip
    remat = {c["name"] for c in bench["workloads"] if json.load(open(
        os.path.join(REPO, "benchmark", "traffic",
                     c["traffic"] + ".json"))).get("remat")}
    assert set(listed["step_remat_device_ms"]["workloads"]) == remat
    assert set(listed["scope_coverage_pct"]["workloads"]) == one_chip


# -- the operator's two doors ----------------------------------------------


def test_profile_reply_carries_the_table_where_a_device_trace_was_captured(
        monkeypatch, tmp_path):
    from edl_tpu.rpc import server
    _, events = _fixture()
    assert server._device_by_scope(str(tmp_path)) is None   # no capture
    monkeypatch.setattr(devtime, "newest_trace", lambda d: "x.xplane.pb")
    monkeypatch.setattr(devtime, "load",
                        lambda path, span=None, names=None: events)
    table = server._device_by_scope(str(tmp_path))
    assert sum(table.values()) == pytest.approx(_union_s(events) * 1e3)
    assert list(table.values()) == sorted(table.values(), reverse=True)
    assert all(re.fullmatch(r"[\w.]+/(fwd|bwd|remat|optim)", k)
               for k in table)
    monkeypatch.setattr(server, "_try_jax_profile",
                        lambda s: ({"traceEvents": []}, table))
    assert server._profile_method(0.0)["device_by_scope"] == table
    monkeypatch.setattr(server, "_try_jax_profile",
                        lambda s: ({"traceEvents": []}, None))
    assert "device_by_scope" not in server._profile_method(0.0)
    monkeypatch.setattr(devtime, "load",
                        lambda path, span=None, names=None: [])
    assert server._device_by_scope(str(tmp_path)) is None   # host only


def test_profile_bench_reports_self_time_by_scope_and_by_class(
        monkeypatch, tmp_path, capsys):
    from edl_tpu.tools import profile_bench
    doc, events = _fixture()
    monkeypatch.setattr(devtime, "newest_trace", lambda d: "x.xplane.pb")
    monkeypatch.setattr(devtime, "load",
                        lambda path, span=None, names=None: events)
    tables = profile_bench.xplane_op_breakdown(str(tmp_path), doc["steps"])
    whole = _union_s(events) * 1e3 / doc["steps"]
    for rows in tables.values():
        assert sum(ms for _, ms in rows) == pytest.approx(whole)
        assert [ms for _, ms in rows] == sorted((ms for _, ms in rows),
                                                reverse=True)
    assert profile_bench.main(["--parse_only", "--logdir", str(tmp_path),
                               "--steps", str(doc["steps"])]) == 0
    assert "scope (self time)" in capsys.readouterr().out


def test_profile_bench_holds_no_protobuf_parse_of_its_own():
    with open(os.path.join(REPO, "edl_tpu", "tools",
                           "profile_bench.py")) as f:
        text = f.read()
    assert "xplane_pb2" not in text and "PROTOCOL_BUFFERS" not in text


def test_load_reads_a_real_profile_through_jax_alone(tmp_path):
    """A CPU capture has no device plane: `load` finds no event, the
    window's annotation or not, and `newest_trace` finds the file."""
    import jax
    import jax.numpy as jnp
    assert devtime.newest_trace(str(tmp_path)) is None
    f = jax.jit(lambda x: jnp.tanh(x) @ x)
    f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:trace_window"):
        f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    path = devtime.newest_trace(str(tmp_path))
    assert path.endswith(".xplane.pb")
    assert devtime.load(path, names={}) == []
    assert devtime.load(path, "bench:trace_window", names={}) == []


# -- where an operation's name comes from -------------------------------------

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.7 (param_0.1: f32[8,8]) -> f32[8,8] {
  %param_0.1 = f32[8,8]{1,0} parameter(0)
  %sub.3 = f32[8,8]{1,0} subtract(%param_0.1, %param_0.1), metadata={op_name="jit(step)/jvp(M)/layer_0/attn.full/sub" stack_frame_id=4}
  ROOT %tuple.9 = (f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(%sub.3, %param_0.1)
}

%body.2 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %gte.1 = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %fusion.5 = f32[8,8]{1,0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(M)/loop.pass/while/body/layer_1/ffn.dense/mul" stack_frame_id=9}
  ROOT %tuple.3 = (s32[], f32[8,8]{1,0}) tuple(%gte.0, %fusion.5)
}

%wide.body (q: (s32[], f32[64])) -> (s32[], f32[64]) {
  %q = (s32[], f32[64]{0}) parameter(0)
  %dynamic-update-slice.9 = f32[64]{0} dynamic-update-slice(%gte.7, %reshape.2, %add.1)
  ROOT %tuple.5 = (s32[], f32[64]{0}) tuple(%add.1, %dynamic-update-slice.9)
}

ENTRY %main.1 (w: f32[8,8]) -> f32[8,8] {
  %w = f32[8,8]{1,0} parameter(0), metadata={op_name="train_state['params']['w']"}
  %copy.4 = f32[8,8]{0,1} copy(%w)
  %copy-start.2 = (f32[8,8]{1,0}, f32[8,8]{1,0}, u32[]) copy-start(%w)
  %copy-done.2 = f32[8,8]{1,0} copy-done(%copy-start.2)
  %subtract_convert_fusion.1 = (f32[8,8]{1,0}, f32[8,8]{1,0}) fusion(%copy-done.2), kind=kLoop, calls=%fused_computation.7
  %while.6 = (s32[], f32[8,8]{1,0}) while(%tuple.1), condition=%cond.2, body=%body.2
  %fusion.8 = f32[8,8]{1,0} fusion(%copy.4), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(step)/optim.update/add" stack_frame_id=30}
  %iota.1 = s32[8]{0} iota(), iota_dimension=0
  %while.9 = (s32[], f32[64]{0}) while(%tuple.2), condition=%wide.cond, body=%wide.body
  ROOT %out = f32[8,8]{1,0} copy(%fusion.8), metadata={op_name="jit(step)/optim.update/add"}
}
"""


def test_op_names_reads_each_instruction_s_own_name_stack():
    names = devtime.op_names(HLO)
    assert names["fusion.5"].endswith("layer_1/ffn.dense/mul")
    assert names["fusion.8"] == names["out"] == "jit(step)/optim.update/add"
    assert names["sub.3"].endswith("attn.full/sub")
    assert devtime.scope_of("jit(step)/jvp(M)/layer_4/add")[0] == "unscoped"


@pytest.mark.parametrize("instruction,scope", [
    # a fusion of several outputs: what its fused computation holds
    ("subtract_convert_fusion.1", "attn.full"),
    # a loop: its body's last named instruction
    ("while.6", "ffn.dense"),
    # a layout copy: the operation that reads it
    ("copy.4", "optim.update"),
    # a prefetch, through its own second half and the fusion that calls
    ("copy-done.2", "attn.full"), ("copy-start.2", "attn.full"),
    # what neither calls nor feeds anything named: the one that ran before
    ("iota.1", "optim.update"),
    # a loop the compiler wrote whole, and everything in its body: the
    # loop's own neighbour in the computation that calls it
    ("while.9", "optim.update"), ("dynamic-update-slice.9", "optim.update"),
])
def test_a_compiler_made_instruction_takes_its_neighbour_s_name(
        instruction, scope):
    assert devtime.scope_of(devtime.op_names(HLO)[instruction])[0] == scope


def test_an_event_is_named_by_its_instruction_s_whole_text():
    name = ("%fusion.12 = bf16[8192,2048]{1,0:T(8,128)(2,1)} fusion(bf16[8192,"
            "2048]{1,0} %copy.3), kind=kLoop, calls=%fused_computation.12")
    assert devtime.instruction(name) == "fusion.12"
    assert devtime.op_class(name) == "fusion"
    assert devtime.instruction("%while.3") == "while.3"


class _Program(object):
    def __init__(self, table):
        self.table, self.calls = table, 0

    def names(self):
        self.calls += 1
        return self.table


def test_the_registry_holds_a_program_weakly_and_asks_it_late(monkeypatch):
    import gc
    monkeypatch.setattr(devtime, "_PROVIDERS", [])
    one, two = _Program({"fusion.1": "a/attn.full/b"}), _Program({"x": "y"})
    devtime.register(one.names)
    devtime.register(two.names)
    assert one.calls == two.calls == 0          # nothing built yet
    assert devtime.registered_op_names() == {"fusion.1": "a/attn.full/b",
                                             "x": "y"}
    del two
    gc.collect()
    assert devtime.registered_op_names() == {"fusion.1": "a/attn.full/b"}
    assert len(devtime._PROVIDERS) == 1 and one.calls == 2

    def broken():
        raise RuntimeError("no executable")

    one.names = broken      # a provider that fails costs its own table only
    holder = _Program({})
    holder.names = one.names
    assert devtime.registered_op_names() is not None


# -- nothing new runs while a job trains -------------------------------------


def _linear_trainer(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from edl_tpu.runtime.trainer import ElasticTrainer

    def loss_fn(params, batch, rng):
        with jax.named_scope("ffn.dense"):
            return jnp.mean((jnp.tanh(batch["x"] @ params["w"])
                             - batch["y"]) ** 2)

    trainer = ElasticTrainer(
        loss_fn, {"w": jnp.zeros(4)}, optax.adamw(0.1), total_batch_size=16,
        checkpoint_dir=str(tmp_path / "ckpt"))
    return trainer, {"x": np.ones((16, 4), np.float32),
                     "y": np.full((16,), 0.5, np.float32)}


def test_training_untraced_never_builds_the_table(monkeypatch, tmp_path):
    """Scopes are metadata on the lowered program: a trainer that steps,
    saves and closes renders no HLO text, builds no table and parses no
    file; the builder runs when a reader asks, and once a mesh."""
    from edl_tpu.runtime.trainer import ElasticTrainer
    built = []
    real = ElasticTrainer.step_scope_table

    def counted(self):
        built.append(1)
        return real(self)

    def boom(*a, **k):
        raise AssertionError("the reader ran on the training path")

    monkeypatch.setattr(ElasticTrainer, "step_scope_table", counted)
    for fn in ("load", "by_scope", "by_class", "self_times", "scope_of",
               "newest_trace", "op_names", "registered_op_names"):
        monkeypatch.setattr(devtime, fn, boom)
    trainer, batch = _linear_trainer(tmp_path)
    first = float(trainer.train_step(batch))
    for _ in range(5):
        loss = float(trainer.train_step(batch))
    trainer.save()
    trainer.close()
    assert loss < first and built == []
    monkeypatch.undo()
    # a reader asks, after close() as the benchmark's does
    table = devtime.registered_op_names()
    keys = {devtime.scope_of(op) for op in table.values()}
    assert {("ffn.dense", "fwd"), ("ffn.dense", "bwd"),
            ("optim.update", "optim")} <= keys
    assert trainer.step_scope_table() is trainer.step_scope_table()


def test_a_trainer_that_never_stepped_has_no_table(tmp_path):
    trainer, _ = _linear_trainer(tmp_path)
    assert trainer.step_scope_table() == {}
    trainer.close()


def test_the_step_s_update_lowers_under_its_scope():
    """`optim.update` holds the optimizer's whole update and nothing of
    the derivative; the loss's operations carry the model's scopes."""
    import jax
    import jax.numpy as jnp
    import optax
    from edl_tpu.runtime.trainer import make_train_state, make_train_step

    def loss_fn(params, batch, rng):
        with jax.named_scope("ffn.dense"):
            return jnp.mean(jnp.tanh(batch @ params["w"]) ** 2)

    tx = optax.adamw(0.1)
    state = make_train_state({"w": jnp.ones((8, 8))}, tx)
    text = jax.jit(make_train_step(loss_fn, tx)).lower(
        state, jnp.ones((4, 8)), jax.random.PRNGKey(0)).compile().as_text()
    keys = {devtime.scope_of(n)
            for n in re.findall(r'op_name="([^"]*)"', text)}
    assert ("optim.update", "optim") in keys
    assert ("ffn.dense", "fwd") in keys and ("ffn.dense", "bwd") in keys
    assert not [k for k in keys if k[1] == "optim" and k[0] != "optim.update"]
