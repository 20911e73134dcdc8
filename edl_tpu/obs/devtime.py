"""Device SELF time of a profile by the program's own scopes.

The models, the expert layer and the trainer's step run their parts under
``jax.named_scope``; the scope's name lies in the name stack (`op_name`)
that every operation of the compiled program carries, and a device trace
(`jax.profiler`, the `rpc` server's ``profile`` call, the benchmark's
``--trace 1``) names each operation it timed. This module turns one
``.xplane.pb`` into a table ``{(scope, phase): seconds}`` — which part of
the model held the device, forward, backward, recomputed or in the
optimizer — given the names of the program's operations, which the
trainer renders from its step's executable when, and only when, a profile
is read (`register`, `ElasticTrainer.step_scope_table`). It is the one
reader of those scopes: the benchmark's per-layer metrics
(`benchmark/lib/scope_readers.py`), the operator's ``profile`` call
(`rpc/server.py`) and `tools/profile_bench` all come here. Nothing of it
runs unless one of them asks.

`SCOPES` is the registry: a scope named in the program and not here fails
`tests/test_devtime.py`, as does one that `docs/observability.md` lists.
"""

import functools
import glob
import os
import re
import weakref

from edl_tpu.utils.logger import logger

#: scope -> (part, the PR that wrote it). A part is what a per-layer
#: metric sums: attn, mixer, ffn, head_loss, optim, other.
SCOPES = {
    "moe.route": ("ffn", 27),
    "moe.dispatch": ("ffn", 27),
    "moe.experts": ("ffn", 27),
    "moe.combine": ("ffn", 27),
    "moe.shared": ("ffn", 43),
    "ffn.dense": ("ffn", 46),
    "attn.full": ("attn", 27),
    "attn.window": ("attn", 27),
    "attn.select": ("attn", 34),
    "attn.index": ("attn", 34),
    "attn.index_loss": ("attn", 34),
    "attn.block_diffusion": ("attn", 39),
    "attn.gate": ("attn", 43),
    "attn.latent.down": ("attn", 53),
    "attn.latent.up": ("attn", 53),
    "attn.latent": ("attn", 53),
    "mixer.gdn.proj": ("mixer", 43),
    "mixer.gdn.conv": ("mixer", 43),
    "mixer.gdn.scan": ("mixer", 43),
    "mixer.gdn.out": ("mixer", 43),
    "ssm.in_proj": ("mixer", 57),
    "ssm.conv": ("mixer", 57),
    "ssm.scan": ("mixer", 57),
    "ssm.norm": ("mixer", 57),
    "ssm.out": ("mixer", 57),
    "mixer.kda.proj": ("mixer", 61),
    "mixer.kda.conv": ("mixer", 61),
    "mixer.kda.gate": ("mixer", 61),
    "mixer.kda.scan": ("mixer", 61),
    "mixer.kda.out": ("mixer", 61),
    "mixer.conv.in_proj": ("mixer", 63),
    "mixer.conv.gate": ("mixer", 63),
    "mixer.conv.out": ("mixer", 63),
    "lm_head": ("head_loss", 27),
    "loss.next_token": ("head_loss", 59),
    "loss.block_diffusion": ("head_loss", 39),
    "loss.exit_expectation": ("head_loss", 46),
    "loop.exit_gate": ("head_loss", 46),
    "loop.pass": ("other", 46),
    "embed": ("other", 59),
    "norm": ("other", 59),
    "conv.stem": ("other", 59),
    "conv.stage1": ("other", 59),
    "conv.stage2": ("other", 59),
    "conv.stage3": ("other", 59),
    "conv.stage4": ("other", 59),
    "head": ("other", 59),
    "optim.update": ("optim", 59),
}
PARTS = ("attn", "mixer", "ffn", "head_loss", "optim", "other")
PHASES = ("fwd", "bwd", "remat", "optim")
UNSCOPED = "unscoped"
OPS_LINE = "XLA Ops"

_SPLIT = re.compile(r"[/()]")


def part_of(scope):
    """The part a scope's time is summed under; `other` for `unscoped`."""
    return SCOPES.get(scope, ("other",))[0]


@functools.lru_cache(maxsize=1 << 16)
def scope_of(op_name):
    """(scope, phase) of one operation from its HLO ``op_name``, the JAX
    name stack, e.g. ``jit(step)/transpose(jvp(layer_2/attn.latent.up))/
    dot_general``.

    scope: the INNERMOST token of the stack that `SCOPES` holds — an
    operation under ``loop.pass/.../attn.full`` is `attn.full`'s, one under
    ``attn.full/attn.gate`` is `attn.gate`'s — or `unscoped`. A Pallas
    kernel is one operation like any other: its stack ends in
    ``.../attn.full/pallas_call`` or, from a `custom_vjp`'s backward rule,
    in ``transpose(jvp(.../attn.full))/pallas_call``.

    phase, the first of these that holds:

    - `optim`: the scope's part is `optim` (the update runs once, outside
      the derivative);
    - `remat`: the stack holds ``rematted_computation`` — what
      `jax.checkpoint` (and flax's `nn.remat`) names the forward it runs
      AGAIN inside the backward: ``transpose(jvp(...))/checkpoint/
      rematted_computation/layer_3/attn.full/dot_general``. The stack lies
      under ``transpose(`` too, so this case is read first;
    - `bwd`: the stack holds ``transpose(`` — the linearised program run
      backwards: ``transpose(jvp(layer_3/attn.full))/dot_general`` and,
      under a checkpoint, ``transpose(jvp(...))/checkpoint/layer_3/
      attn.full/mul``;
    - `fwd`: everything else — ``jvp(layer_3/attn.full)/dot_general``, and
      what lies outside the derivative (the counters, the step's count).
    """
    tokens = _SPLIT.split(op_name or "")
    scope = UNSCOPED
    for tok in reversed(tokens):
        if tok in SCOPES:
            scope = tok
            break
    if part_of(scope) == "optim":
        phase = "optim"
    elif "rematted_computation" in tokens:
        phase = "remat"
    elif "transpose" in tokens:
        phase = "bwd"
    else:
        phase = "fwd"
    return scope, phase


def instruction(event_name):
    """An op event's HLO instruction name: a TPU trace names the event by
    the instruction's whole text, ``%fusion.12 = bf16[...] fusion(...)``."""
    return event_name.split(" = ")[0].lstrip("%")


def op_class(event_name):
    """An operation's class: its instruction's name without the compiler's
    numbering (`fusion.123` -> `fusion`), as the by-class tables have it."""
    return re.sub(r"\.\d+", "", instruction(event_name))


def self_times(events, key):
    """{key(event): seconds of SELF time}, averaged over the device planes.

    events: ``(plane, name, op_name, start_ns, dur_ns)`` of the planes' op
    lines. On one plane an event that holds others (a `while`, a
    `conditional`, a `call`) is charged only the time its children do not
    cover: at any instant the time goes to the event that started LAST
    among those still running. So the sum over all keys is the union of
    the plane's op intervals — the device's busy time — however deep the
    nesting, and no loop's body is counted twice."""
    planes = {}
    for ev in events:
        planes.setdefault(ev[0], []).append(ev)
    out = {}
    for evs in planes.values():
        evs.sort(key=lambda e: (e[3], -e[4]))
        stack = []              # (end_ns, key), innermost last
        t = 0.0
        for ev in evs + [None]:
            until = ev[3] if ev else float("inf")
            while stack and t < until:
                end, k = stack[-1]
                if end <= t:
                    stack.pop()
                    continue
                seg = min(end, until)
                out[k] = out.get(k, 0.0) + (seg - t)
                t = seg
            if ev:
                t = until
                stack.append((ev[3] + ev[4], key(ev)))
    n = float(len(planes) or 1)
    return {k: v / 1e9 / n for k, v in out.items()}


def by_scope(events):
    """{(scope, phase): seconds of self time}, averaged over the device
    planes; a container's own self time goes to ITS scope."""
    return self_times(events, lambda ev: scope_of(ev[2]))


def by_class(events):
    """{op class: seconds of self time}: the by-class table without the
    containers' double count."""
    return self_times(events, lambda ev: op_class(ev[1]))


def by_part(table):
    """{part: seconds} of a `by_scope` table, every phase but `optim`'s
    under the scope's part, `unscoped` under `other`."""
    out = dict.fromkeys(PARTS, 0.0)
    for (scope, _), sec in table.items():
        out[part_of(scope)] += sec
    return out


def by_phase(table):
    """{phase: seconds} of a `by_scope` table."""
    out = dict.fromkeys(PHASES, 0.0)
    for (_, phase), sec in table.items():
        out[phase] += sec
    return out


def coverage_pct(table):
    """100 x (1 - unscoped / all); 0.0 for an empty table."""
    total = sum(table.values())
    bare = sum(sec for (scope, _), sec in table.items() if scope == UNSCOPED)
    return 100.0 * (1.0 - bare / total) if total > 0 else 0.0


_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_REFERENCE = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
#: how many unnamed instructions (a tuple, its element, a copy's two
#: halves) may lie between a compiler-made instruction and a named reader
_HOPS = 4


def op_names(hlo_text):
    """{HLO instruction name: op_name} of a compiled program's text
    (``compiled.as_text()``): each instruction's ``metadata={op_name=
    "..."}``, the JAX name stack it was traced under.

    The compiler makes instructions of its own and gives them no
    metadata: a fusion of several outputs, a `while`, a layout `copy`, a
    prefetch's `copy-done`, a buffer of zeros moved out of a loop, a
    loop's `dynamic-update-slice`. Such an instruction takes the name
    stack, in this order, of the computation it CALLS (the last named
    instruction of a fused computation or a loop's body: the nearest to
    its root); of the first named operation that READS its result,
    through at most `_HOPS` unnamed ones — a copy exists for the product
    that reads it; of its nearest named neighbour in its own computation,
    which the compiled text lists in the order they run (the one before
    it, else the one after); and, in a computation the compiler wrote
    whole (a loop round a copy), of the instruction that calls it. An
    instruction JAX traced keeps its own name
    stack whatever that is: outside every registered scope it reads
    `unscoped`."""
    named, bare, last_of, readers, order = {}, {}, {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if not found:
            head = _COMPUTATION.match(line)
            computation = head.group(1) if head else computation
            continue
        name = found.group(1)
        refs = _REFERENCE.findall(line[found.end():])
        op = _OP_NAME.search(line)
        if op:
            named[name] = last_of[computation] = op.group(1)
        else:
            bare[name] = refs
        order.setdefault(computation, []).append(name)
        for ref in refs:
            readers.setdefault(ref, []).append(name)
    for name, refs in bare.items():
        called = next((last_of[r] for r in refs if r in last_of), None)
        if called:
            named[name] = called
    for name in bare:
        if name in named:
            continue
        front, hit = [name], None
        for _ in range(_HOPS):
            front = [r for n in front for r in readers.get(n, ())]
            hit = next((named[r] for r in front if r in named), None)
            if hit or not front:
                break
        if hit:
            named[name] = hit
    for run in order.values():
        for walk in (run, run[::-1]):       # the one before, else after
            near = None
            for name in walk:
                if name in named:
                    near = named[name]
                elif near is not None:
                    named[name] = near
    # a computation with no named instruction at all (a loop the compiler
    # wrote round a copy): its caller's name, outermost first
    for _ in range(_HOPS):
        for name, refs in bare.items():
            for run in (order[r] for r in refs if r in order):
                if name in named and run[0] not in named:
                    named.update(dict.fromkeys(run, named[name]))
    return named


_PROVIDERS = []     # weak references to bound methods -> {name: op_name}


def register(provider):
    """A program that runs compiled steps says so — `ElasticTrainer` does,
    with its `step_scope_table` — and the readers of this process (a
    traced benchmark run, the `rpc` server's ``profile`` call) find its
    operations' names without a handle on it. `provider` is a bound
    method, held weakly and called only when a profile is read."""
    _PROVIDERS.append(weakref.WeakMethod(provider))


def registered_op_names():
    """The tables of every program still alive, merged."""
    table = {}
    for ref in list(_PROVIDERS):
        provider = ref()
        if provider is None:
            _PROVIDERS.remove(ref)
            continue
        try:
            table.update(provider())
        except Exception:  # noqa: BLE001 — a profile is still worth reading
            logger.exception("a program's operation names were not built")
    return table


def load(path, window_span=None, names=None):
    """The device planes' op events of one ``.xplane.pb`` as
    ``(plane, name, op_name, start_ns, dur_ns)``, clipped to the host
    annotation named `window_span` (the benchmark's is
    ``bench:trace_window``) where the trace holds one.

    Where `op_name` comes from: a v5e trace read through
    `jax.profiler.ProfileData` gives an op event its name — the HLO
    instruction's text — and three timing stats, and nothing of the
    instruction's metadata (looked at on the chip, PR 59: `tf_op` is not
    among an event's stats). So it is looked up by instruction name in
    `names`, a table from `op_names`; by default the merged tables of the
    programs registered in this process, built at that moment."""
    from jax.profiler import ProfileData
    if names is None:
        names = registered_op_names()
    events, window = [], None
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        if not device and not (window_span
                               and plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device:
                    events.append((plane.name, ev.name,
                                   names.get(instruction(ev.name), ""),
                                   float(ev.start_ns),
                                   float(ev.duration_ns)))
                elif window is None and ev.name == window_span:
                    window = (float(ev.start_ns),
                              float(ev.start_ns + ev.duration_ns))
    if window is None:
        return events
    w0, w1 = window
    clipped = []
    for plane, name, op, start, dur in events:
        a, b = max(start, w0), min(start + dur, w1)
        if b > a:
            clipped.append((plane, name, op, a, b - a))
    return clipped


def newest_trace(logdir):
    """The newest ``.xplane.pb`` under `logdir`, or None."""
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def table_ms(table):
    """A `by_scope` table as the operator's reply carries it:
    ``{"scope/phase": ms}``, largest first."""
    return {"%s/%s" % key: sec * 1e3
            for key, sec in sorted(table.items(), key=lambda kv: -kv[1])}
