"""The training kind: an `ElasticTrainer` job that follows the traffic
file's schedule of phases, counted in STEPS — `{"steps": n}`,
`{"save": true}` (asynchronous checkpoint), `{"resize": k}` (live resize
to k chips) — period after period until `--seconds` have passed. A plain
training cell is a schedule of steps alone; an elastic one saves and
resizes. Nothing is scheduled by the clock, so a seed does the same work
in every run.

`correct` (before the window): the program's first steps on the staged
batch against the plain float32 reference on the same batch — the
gradient of step 1, read from the optimizer's first moment, as a relative
L2 error over all parameters, and the loss of each of the first steps;
in a schedule with resizes, also that the loss after each resize
continues the loss before it (a state lost at a resize sends the loss
back to where the seed's weights had it).
"""

import collections
import shutil
import time

from benchmark.lib import optim
from benchmark.lib.harness import BenchError, key_from_seed, log
from benchmark.lib.stats import median


def _tree_rel_err(a, b, scale=1.0):
    """||a / scale - b|| / ||b|| over two trees of one structure, float32,
    summed leaf by leaf: nothing the size of a tree is made."""
    import jax
    import jax.numpy as jnp
    num = sum(jnp.sum(jnp.square(x.astype(jnp.float32) / scale - y))
              for x, y in zip(jax.tree_util.tree_leaves(a),
                              jax.tree_util.tree_leaves(b)))
    den = sum(jnp.sum(jnp.square(y)) for y in jax.tree_util.tree_leaves(b))
    return jnp.sqrt(num / den)


def make_job(run):
    """Seeded weights (reference layout and program layout) and the
    staged batch for this cell, made on the device in one jitted call."""
    import jax
    cfg, job = run.config, run.traffic
    fam, ref = run.program(), run.reference()
    key = key_from_seed(run.seed)
    rows = job["batch_per_chip"] * run.cell["chips"]

    @jax.jit
    def seeded(key):
        w = ref.init_weights(cfg, jax.random.fold_in(key, 0))
        return (w, fam.to_program(w, cfg),
                fam.make_batch(cfg, job, jax.random.fold_in(key, 1), rows))

    w, (params, extra), batch = seeded(key)
    return {"cfg": cfg, "job": job, "fam": fam, "ref": ref, "w": w,
            "params": params, "extra": extra, "batch": batch, "rows": rows,
            "ref_steps": {}}


def make_trainer(run, j):
    from edl_tpu.runtime.mesh import make_mesh
    from edl_tpu.runtime.trainer import ElasticTrainer
    import jax
    loss_fn, has_aux, shapes = j["fam"].train_parts(j["cfg"], j["job"])
    got = jax.eval_shape(lambda: (j["params"], j["extra"]))
    if (jax.tree_util.tree_structure(got)
            != jax.tree_util.tree_structure(shapes)
            or [x.shape for x in jax.tree_util.tree_leaves(got)]
            != [x.shape for x in jax.tree_util.tree_leaves(shapes)]):
        raise BenchError("the program's parameter tree no longer matches "
                         "benchmark/program/%s.py" % run.config["family"])
    saves = any("save" in p for p in j["job"]["schedule"])
    j["ckpt_dir"] = run.scratch_dir("ckpt") if saves else ""
    return ElasticTrainer(
        loss_fn, j["params"], optim.make_tx(j["job"]["optimizer"]),
        total_batch_size=j["rows"], checkpoint_dir=j["ckpt_dir"],
        mesh=make_mesh(devices=run.devices), extra_state=j["extra"],
        has_aux=has_aux, async_save=True)


#: `last` takes the weights this many times more, unused: the TPU compiler
#: sizes a program's image (it lies in the device's memory while the program
#: is loaded, and is read from the compile cache in every run's set-up) by
#: the arguments it sees, not by what the chip holds — 700 MB for the
#: weights alone, 110 MB with the 12 bytes a parameter of a trainer beside
#: them (PERF.md section 6, PR 56). The trainer IS beside them, so the
#: compiler is shown its size: the same buffers again, not a byte more.
_WEIGHTS_SHOWN_AGAIN = 3


def _reference_step_fns(j, q):
    """The plain step as the two programs a check can need, each traced
    once per (job, precision) and only when called: `last(w, batch, *w
    again) -> (loss, gradient in the program's layout)`, with no optimizer
    update in it, and `advance(w, m, v, t, batch) -> (loss, gradient, w',
    m', v')`, the whole step, with m and v donated (never w: the first
    step's is j["w"], which the controls read again)."""
    import jax
    if q not in j["ref_steps"]:
        cfg, ref, fam = j["cfg"], j["ref"], j["fam"]
        spec = j["job"]["optimizer"]

        def to_program(g):
            return fam.to_program(g, cfg)[0]

        def last(w, batch, *shown_again):
            loss, g = ref.loss_and_grad(w, batch, cfg, q)
            return loss, to_program(g)

        def advance(w, m, v, t, batch):
            loss, g = ref.loss_and_grad(w, batch, cfg, q)
            w2, st = optim.ref_update(spec, w, g, {"m": m, "v": v, "t": t})
            return loss, to_program(g), w2, st["m"], st["v"]

        j["ref_steps"][q] = {
            "last": jax.jit(last, keep_unused=True),
            "advance": jax.jit(advance, donate_argnums=(1, 2))}
    return j["ref_steps"][q]


def reference_steps(j, n_steps, q=None):
    """Losses of the first `n_steps` plain steps and the gradient of the
    first, in the program's parameter layout. One step alone (`check_steps`
    1: the cells whose parameters fill the chip) is `last`: no moments, no
    new weights. Several steps are all `advance`, the last one's update
    unread: ONE program to trace and load, as small cells' set-up had."""
    import jax
    import jax.numpy as jnp
    fns = _reference_step_fns(j, q)
    w, batch = j["w"], j["batch"]
    if n_steps == 1:
        loss, g0 = fns["last"](w, batch, *[w] * _WEIGHTS_SHOWN_AGAIN)
        return [float(loss)], g0
    # two trees of zeros, not optim.ref_init's one: each is donated
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, g0 = [], None
    for t in range(n_steps):
        loss, g, w, m, v = fns["advance"](w, m, v, t, batch)
        losses.append(float(loss))
        g0 = g if t == 0 else g0
    return losses, g0


def _peak_bytes(devices):
    """The fullest device's high-water mark, as the result line's
    `memory_peak_bytes` counts it (lib/harness.py)."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(st.get("peak_bytes_in_use", 0))
               + int(st.get("peak_bytes_reserved", 0)) for st in stats)


def check_first_steps(run, j, trainer, staged):
    """The comparison that decides `correct`; see the module docstring.
    Returns the numbers compared, for tools/limits.py. Where `check_steps`
    is 1 the device holds, while the reference's step runs, the trainer's
    state, the reference's weights and its gradient, and nothing else the
    size of the parameters: the first moment is compared where it lies.
    A later step donates the state, so there a copy is taken first."""
    import jax
    import jax.numpy as jnp
    job = j["job"]
    n = job["check_steps"]
    losses = []
    for i in range(n):
        loss = trainer.train_step(staged)
        jax.block_until_ready(loss)
        if i == 0:
            moment = optim.first_moment(trainer.train_state["opt_state"],
                                        trainer.train_state["params"])
            if n > 1:
                moment = jax.tree_util.tree_map(jnp.copy, moment)
        losses.append(float(loss))
    with run.span("setup:reference"):
        ref_losses, g0 = reference_steps(j, n)
    grad_err = float(jax.jit(_tree_rel_err)(
        moment, g0, optim.moment_scale(job["optimizer"])))
    run.record(check_peak_bytes=_peak_bytes(run.devices))
    loss_err = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    log("first losses: program %s reference %s" % (losses, ref_losses))
    return {"grad_rel_err": grad_err, "loss_rel_err": loss_err}


#: tools/limits.py: what is put in the program's place — the reference
#: with both operands of every product rounded to int8, the nearest
#: precision below the configuration's bf16
CONTROLS = {"int8": {"q": "int8"}}


def compared_numbers(run, names):
    """tools/limits.py: {name: the numbers `correct` compares} for one
    seed; the name None is the program, any other one of CONTROLS. A
    control is the reference alone: it needs one chip, whatever the cell
    holds."""
    import jax
    j = make_job(run)
    out = {}
    if None in names:
        trainer = make_trainer(run, j)
        try:
            out[None] = check_first_steps(run, j, trainer,
                                          trainer.place_batch(j["batch"]))
        finally:
            trainer.close()
    n = j["job"]["check_steps"]
    sound = None
    for name in [c for c in names if c is not None]:
        sound = sound or reference_steps(j, n)
        losses, g = reference_steps(j, n, **CONTROLS[name])
        out[name] = {
            "grad_rel_err": float(jax.jit(_tree_rel_err)(g, sound[1])),
            "loss_rel_err": max(abs(a - b) / abs(b)
                                for a, b in zip(losses, sound[0]))}
    return out


class _Driver(object):
    """Walks the schedule; keeps at most `depth` steps in flight."""

    def __init__(self, run, j, trainer):
        self.run, self.j, self.trainer = run, j, trainer
        self.depth = j["job"]["steps_in_flight"]
        self.world = len(run.devices)
        self.staged = {}
        self.samples = 0
        self.steps = 0
        self.chip_steps = 0
        self.pauses = []       # ms, one per live resize
        self.save_stalls = []  # ms
        self.resize_records = []
        self.jumps = []        # |loss after - loss before| / loss before
        self.last_loss = None
        self._pause_from = None
        self._excuse = None

    def batch(self):
        if self.world not in self.staged:
            self.staged[self.world] = self.trainer.place_batch(
                self.j["batch"])
        return self.staged[self.world]

    def _first_after_resize(self, loss):
        import jax
        jax.block_until_ready(loss)
        self.pauses.append((time.monotonic() - self._pause_from) * 1e3)
        self._excuse.__exit__(None, None, None)
        self._pause_from = self._excuse = None
        rec = self.trainer.resize_timing
        self.resize_records.append(
            {k: rec.get(k) for k in ("reshard_s", "compile_s", "drain_s",
                                     "first_step_s", "prewarm",
                                     "to_devices")})
        after = float(loss)
        self.jumps.append(abs(after - self.last_loss) / abs(self.last_loss))

    def phase(self, p):
        import jax
        run, tr = self.run, self.trainer
        if "steps" in p:
            batch = self.batch()
            with run.span("steps"):
                pending = collections.deque()
                for _ in range(p["steps"]):
                    with run.span("train_step" if self._pause_from is None
                                  else "resize_first_step"):
                        loss = tr.train_step(batch)
                    if self._pause_from is not None:
                        self._first_after_resize(loss)
                    pending.append(loss)
                    if len(pending) > self.depth:
                        jax.block_until_ready(pending.popleft())
                with run.span("drain_steps"):
                    jax.block_until_ready(loss)
            self.last_loss = float(loss)
            self.steps += p["steps"]
            self.chip_steps += p["steps"] * self.world
            self.samples += p["steps"] * self.j["rows"]
        elif "save" in p:
            t = time.monotonic()
            with run.span("save"):
                tr.save()
            self.save_stalls.append((time.monotonic() - t) * 1e3)
        elif "resize" in p:
            self._excuse = run.compiles.excused()
            self._excuse.__enter__()
            self._pause_from = time.monotonic()
            with run.span("live_resize"):
                tr.live_resize(p["resize"])
            self.world = p["resize"]
        else:
            raise BenchError("unknown phase %r in the schedule" % (p,))

    def period(self):
        for p in self.j["job"]["schedule"]:
            self.phase(p)
        if self._pause_from is not None or self.world != len(
                self.run.devices):
            raise BenchError("a period must end in steps, on the chips "
                             "it began on")


def run(run):
    run.claim_devices()
    with run.span("setup:seed"):
        j = make_job(run)
        trainer = make_trainer(run, j)
    job = j["job"]
    drv = _Driver(run, j, trainer)
    try:
        with run.span("setup:check"):
            got = check_first_steps(run, j, trainer, drv.batch())
        for name, value in sorted(got.items()):
            run.check(name, value, job["limits"][name])
        # the reference's weights and programs are not needed again
        for k in ("w", "params", "extra"):
            j.pop(k)
        j["ref_steps"].clear()
        smaller = sorted({p["resize"] for p in job["schedule"]
                          if "resize" in p and p["resize"] < drv.world})
        if smaller:
            with run.span("setup:prewarm"):
                trainer.prewarm_resize_compiles(smaller, block=True)
        with run.span("setup:warm_period"):
            drv.period()   # every phase once: its programs and paths
        warm_jumps, staged = drv.jumps, drv.staged
        drv = _Driver(run, j, trainer)
        drv.staged = staged
        t0 = run.window_open()
        periods = []
        traced = [1, 1 + job["trace_periods"]] if run.traced else None
        while True:
            if traced and len(periods) == traced[0]:
                run.trace_start()
                before = (drv.steps, drv.chip_steps)
            p0 = time.monotonic()
            drv.period()
            periods.append((p0, time.monotonic()))
            if traced and len(periods) == traced[1]:
                run.trace_stop()
                run.count("traced_steps", drv.steps - before[0])
                run.count("traced_chip_steps", drv.chip_steps - before[1])
                traced = None
            if time.monotonic() - t0 >= run.seconds and traced is None:
                break
        t1 = run.window_close()
    finally:
        trainer.close()
        if j["ckpt_dir"]:
            shutil.rmtree(j["ckpt_dir"], ignore_errors=True)
    if drv.jumps or warm_jumps:
        run.check("resize_loss_jump", max(drv.jumps + warm_jumps),
                  job["limits"]["resize_loss_jump"])
    chips = len(run.devices)
    flops = j["fam"].train_flops(j["cfg"], job, j["rows"])
    run.counters.update(
        steps=drv.steps, periods=len(periods), step_flops=flops,
        save_stall_ms=drv.save_stalls, resize_records=drv.resize_records,
        resize_pause_ms=drv.pauses)
    run.record(periods=[[a - t0, b - t0] for a, b in periods],
               pauses_ms=drv.pauses, save_stalls_ms=drv.save_stalls,
               resizes=drv.resize_records, loss_jumps=drv.jumps)
    # the file names the rate: a schedule that saves and resizes inside
    # the window measures goodput, another quantity than a plain job's
    end_to_end = {job["rate_metric"]: drv.samples / (t1 - t0) / chips}
    if drv.pauses:
        end_to_end["resize_pause_ms"] = median(drv.pauses)
    # attempted = optimizer steps asked of the trainer; a step that
    # raises ends the run, so none fails quietly
    return run.finish(drv.steps, 0, end_to_end)
