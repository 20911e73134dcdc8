"""Pipeline parallelism over the ``pp`` mesh axis with shard_map + ppermute.

Net-new vs the reference (model parallelism was only a roadmap bullet,
SURVEY.md §2.7) — completes the framework's mesh axes (dp/tp/sp/pp/ep).
Each pipeline stage's parameters live only on its pp slice; activations hop
stage-to-stage over ICI via `lax.ppermute`.

Two schedules:

- ``pipeline_apply``: GPipe forward (M microbatches over P stages in
  M + P - 1 ticks), differentiable through jax.grad (which replays the
  reverse schedule but stores every tick's activations — memory O(M)).
- ``pipeline_value_and_grad``: 1F1B (PipeDream-flush) training schedule.
  Each stage interleaves one forward with one backward per round trip, so
  at most P - stage_idx microbatch activations are live per stage
  (memory O(P), independent of M) — and only the stage INPUT is saved;
  the stage body is recomputed inside the backward vjp (remat). Gradients
  for stage params come out pp-sharded, ready for a pp-sharded optimizer.

Shape changes are handled at the pipeline ends: ``encode_fn`` (e.g. token
embedding: int ids → activations, evaluated on stage 0) and ``decode_fn``
(activations + labels → scalar loss, evaluated on the last stage). The
repeated stage body must map the activation pytree to itself — an inherent
property of an SPMD ring, not a restriction: any network of the form
encode → uniform-block^N → head fits (BERT/GPT/ViT/ResNet stages).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from edl_tpu.runtime.mesh import DATA_AXIS, PIPE_AXIS

_tmap = jax.tree_util.tree_map


def _pipeline_shard(stage_params, microbatches, *, stage_fn, num_stages,
                    num_micro, axis_name):
    """Runs on one pp slice. stage_params: this stage's params (leading
    stage axis of size 1); microbatches: [M, mb, ...] (replicated in)."""
    idx = lax.axis_index(axis_name)
    params = jax.tree_util.tree_map(lambda x: x[0], stage_params)
    mb_shape = microbatches.shape[1:]
    out0 = jnp.zeros((num_micro,) + mb_shape, microbatches.dtype)
    carry0 = jnp.zeros(mb_shape, microbatches.dtype)
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    def tick(t, state):
        carry, outs = state
        mb_idx = t - idx                       # which microbatch this stage
        active = jnp.logical_and(mb_idx >= 0, mb_idx < num_micro)
        fresh = microbatches[jnp.clip(t, 0, num_micro - 1)]
        x_in = jnp.where(idx == 0, fresh, carry)
        y = stage_fn(params, x_in)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # the last stage records its finished microbatch
        write = jnp.logical_and(active, idx == num_stages - 1)
        outs = lax.dynamic_update_index_in_dim(
            outs,
            jnp.where(write, y, outs[jnp.clip(mb_idx, 0, num_micro - 1)]),
            jnp.clip(mb_idx, 0, num_micro - 1), 0)
        carry = lax.ppermute(y, axis_name, perm)
        return carry, outs

    _, outs = lax.fori_loop(0, num_micro + num_stages - 1, tick,
                            (carry0, out0))
    # only the last stage holds real outputs; psum replicates them
    return lax.psum(outs, axis_name)


def pipeline_apply(stage_params, x, stage_fn, mesh, num_micro=None,
                   pipe_axis=PIPE_AXIS):
    """Apply ``num_stages`` sequential stages to ``x`` with the stages
    sharded over the pp mesh axis.

    stage_params: pytree with a leading stage axis [P, ...] (shard it over
    pp before calling, or pass host arrays and let shard_map split them).
    x: [batch, ...]; batch must divide into ``num_micro`` microbatches.
    Returns stage_{P-1}(...stage_0(x)), replicated.
    """
    num_stages = mesh.shape[pipe_axis]
    batch = x.shape[0]
    num_micro = num_micro or num_stages
    if batch % num_micro != 0:
        raise ValueError("batch %d not divisible by %d microbatches"
                         % (batch, num_micro))
    mb = batch // num_micro
    microbatches = x.reshape((num_micro, mb) + x.shape[1:])

    param_specs = jax.tree_util.tree_map(
        lambda _: P(pipe_axis), stage_params)
    fn = shard_map(
        functools.partial(_pipeline_shard, stage_fn=stage_fn,
                          num_stages=num_stages, num_micro=num_micro,
                          axis_name=pipe_axis),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False)
    out = fn(stage_params, microbatches)
    return out.reshape((batch,) + out.shape[2:])


def _pipe_1f1b_shard(params, xs, ys, *, encode_fn, stage_fn, decode_fn,
                     num_stages, num_micro, axis_name, batch_axes,
                     n_batch, seq_axes=()):
    """1F1B on one pp slice (all stages run this SPMD; ``idx`` picks the
    role). Schedule (fwd cost == bwd slot): stage s runs forward of
    microbatch m at tick s + 2m and backward of m at tick 2P-1-s + 2m —
    opposite parities, so each tick is exactly one of {fwd, bwd, idle},
    picked with lax.cond (real control flow under shard_map, not select).
    """
    nP, M = num_stages, num_micro
    idx = lax.axis_index(axis_name)
    p_enc, p_dec = params["encode"], params["decode"]
    p_stage = _tmap(lambda a: a[0], params["stages"])  # this slice's stage

    mb = xs.shape[0] // M
    xmb = _tmap(lambda a: a.reshape((M, mb) + a.shape[1:]), xs)
    ymb = _tmap(lambda a: a.reshape((M, mb) + a.shape[1:]), ys)

    def take(tree, m):
        return _tmap(lambda a: a[m], tree)

    # activation template: everything the ring carries is act-shaped
    act = jax.eval_shape(encode_fn, p_enc, take(xmb, 0))
    out_shape = jax.eval_shape(stage_fn, p_stage, act)
    if (jax.tree_util.tree_structure(out_shape)
            != jax.tree_util.tree_structure(act) or
        any(a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree_util.tree_leaves(act),
                            jax.tree_util.tree_leaves(out_shape)))):
        raise ValueError(
            "stage_fn must map the activation pytree to itself "
            "(encode output %s, stage output %s)" % (act, out_shape))

    zeros_act = _tmap(lambda s: jnp.zeros(s.shape, s.dtype), act)
    fwd_perm = [(i, (i + 1) % nP) for i in range(nP)]
    bwd_perm = [((i + 1) % nP, i) for i in range(nP)]

    # ring buffer of saved stage INPUTS: the skew-1 1F1B schedule holds
    # <= P microbatches in flight; the seq-parallel PAIR schedule's
    # skew-2 window holds <= 2P-1 (stage s spans pairs m+s .. m+2P-2-s,
    # so the max slot distance is 2P-2 — 2P-1 slots collision-free)
    n_slots = 2 * nP - 1 if seq_axes else nP
    state = dict(
        fwd_carry=zeros_act,
        bwd_carry=zeros_act,
        buf=_tmap(lambda s: jnp.zeros((n_slots,) + s.shape, s.dtype), act),
        g_enc=_tmap(jnp.zeros_like, p_enc),
        g_stage=_tmap(jnp.zeros_like, p_stage),
        g_dec=_tmap(jnp.zeros_like, p_dec),
        loss=jnp.zeros((), jnp.float32),
    )

    def masked_add(acc, new, valid):
        return _tmap(lambda a, n: a + jnp.where(valid, n, 0).astype(a.dtype),
                     acc, new)

    def tick_pair(k, state):
        """Seq-parallel PAIR schedule: stage_fn/decode_fn contain
        collectives over seq_axes, and collectives must execute on EVERY
        device in the same order each tick — different pp stages taking
        different lax.cond branches would leave subgroup collectives
        with missing participants. Instead of computing BOTH roles every
        skew-1 tick and mask-selecting (the round-2 design: 2x the
        arithmetic and 2(M+P) ticks), each pair-iteration runs ONE
        unconditioned forward subtick then ONE unconditioned backward
        subtick, each valid for (almost) every iteration of its ramp:
        stage s forwards microbatch m at pair m+s and backwards it at
        pair m + 2P-2-s (a skew of one full fwd+bwd pair per stage).
        Same FLOPs as the divergent 1F1B, M + 2P-2 iterations, no
        conditioned collectives; the price is an activation stash of
        <= 2P-1 microbatch inputs instead of <= P."""
        def sel(pred, a, b):
            return _tmap(lambda u, v: jnp.where(pred, u, v), a, b)

        # ---- forward subtick -----------------------------------------
        m_f = k - idx
        f_valid = jnp.logical_and(m_f >= 0, m_f < M)
        mf = jnp.clip(m_f, 0, M - 1)
        enc_out = encode_fn(p_enc, take(xmb, mf))
        x_in = sel(idx == 0, enc_out, state["fwd_carry"])
        y = stage_fn(p_stage, x_in)
        buf = _tmap(
            lambda b_, v: jnp.where(
                f_valid,
                lax.dynamic_update_index_in_dim(b_, v, mf % n_slots, 0),
                b_),
            state["buf"], x_in)
        fwd_carry = _tmap(
            lambda v: lax.ppermute(v, axis_name, fwd_perm),
            sel(f_valid, y, zeros_act))

        # ---- backward subtick ----------------------------------------
        # ONE stage vjp serves both roles: the last stage chains the
        # decode head's cotangent into it, mid stages chain the ring
        # carry — mask-selecting the COTANGENT instead of running
        # separate full vjps for comp(stage∘decode) and stage
        m_b = k - (2 * nP - 2 - idx)
        b_valid = jnp.logical_and(m_b >= 0, m_b < M)
        mb_ = jnp.clip(m_b, 0, M - 1)
        x_saved = _tmap(lambda b_: b_[mb_ % n_slots], buf)
        y_saved, vjp_stage = jax.vjp(stage_fn, p_stage, x_saved)
        loss_m, vjp_dec = jax.vjp(
            lambda pd, y_: decode_fn(pd, y_, take(ymb, mb_)),
            p_dec, y_saved)
        gd_l, gy_l = vjp_dec(jnp.float32(1.0 / M))
        is_last = idx == nP - 1
        gs, gx = vjp_stage(sel(is_last, gy_l, state["bwd_carry"]))
        gd = sel(is_last, gd_l, _tmap(jnp.zeros_like, p_dec))
        _, vjp_enc = jax.vjp(
            lambda p: encode_fn(p, take(xmb, mb_)), p_enc)
        ge = sel(idx == 0, vjp_enc(gx)[0], _tmap(jnp.zeros_like, p_enc))

        return dict(
            buf=buf,
            fwd_carry=fwd_carry,
            bwd_carry=_tmap(
                lambda v: lax.ppermute(v, axis_name, bwd_perm),
                sel(b_valid, gx, zeros_act)),
            g_stage=masked_add(state["g_stage"], gs, b_valid),
            g_dec=masked_add(state["g_dec"], gd, b_valid),
            g_enc=masked_add(state["g_enc"], ge, b_valid),
            loss=state["loss"] + jnp.where(
                jnp.logical_and(b_valid, is_last), loss_m,
                0).astype(jnp.float32) / M)

    def tick(t, state):
        tf = t - idx                   # forward clock of this stage

        def do_fwd(state):
            m_f = tf // 2
            valid = jnp.logical_and(m_f >= 0, m_f < M)
            m = jnp.clip(m_f, 0, M - 1)
            x_in = lax.cond(
                idx == 0,
                lambda: encode_fn(p_enc, take(xmb, m)),
                lambda: state["fwd_carry"])
            y = stage_fn(p_stage, x_in)
            slot = m % nP
            buf = _tmap(
                lambda b, v: jnp.where(
                    valid, lax.dynamic_update_index_in_dim(b, v, slot, 0), b),
                state["buf"], x_in)
            out = dict(state, buf=buf)
            return out, y, zeros_act

        def do_bwd(state):
            tb = t - (2 * nP - 1 - idx)    # backward clock
            m_b = tb // 2
            valid = jnp.logical_and(tb >= 0, m_b < M)
            m = jnp.clip(m_b, 0, M - 1)
            slot = m % nP
            x_saved = _tmap(lambda b: b[slot], state["buf"])

            def last_stage():
                # fold the head + loss into the last stage's backward;
                # seed 1/M so accumulated grads are the microbatch mean
                def comp(ps, pd, x):
                    return decode_fn(pd, stage_fn(ps, x), take(ymb, m))
                loss_m, vjp = jax.vjp(comp, p_stage, p_dec, x_saved)
                gs, gd, gx = vjp(jnp.float32(1.0 / M))
                return loss_m, gs, gd, gx

            def mid_stage():
                _, vjp = jax.vjp(stage_fn, p_stage, x_saved)
                gs, gx = vjp(state["bwd_carry"])
                return (jnp.zeros((), jnp.float32), gs,
                        _tmap(jnp.zeros_like, p_dec), gx)

            loss_m, gs, gd, gx = lax.cond(idx == nP - 1, last_stage,
                                          mid_stage)
            ge = lax.cond(
                idx == 0,
                lambda: jax.vjp(
                    lambda p: encode_fn(p, take(xmb, m)), p_enc)[1](gx)[0],
                lambda: _tmap(jnp.zeros_like, p_enc))
            out = dict(
                state,
                g_stage=masked_add(state["g_stage"], gs, valid),
                g_dec=masked_add(state["g_dec"], gd, valid),
                g_enc=masked_add(state["g_enc"], ge, valid),
                loss=state["loss"]
                + jnp.where(valid, loss_m, 0).astype(jnp.float32) / M)
            return out, zeros_act, gx

        state, y_send, g_send = lax.cond(tf % 2 == 0, do_fwd, do_bwd, state)
        state["fwd_carry"] = _tmap(
            lambda v: lax.ppermute(v, axis_name, fwd_perm), y_send)
        state["bwd_carry"] = _tmap(
            lambda v: lax.ppermute(v, axis_name, bwd_perm), g_send)
        return state

    if seq_axes:
        state = lax.fori_loop(0, M + 2 * nP - 2, tick_pair, state)
    else:
        state = lax.fori_loop(0, 2 * (nP + M) - 2, tick, state)

    # encode/decode grads + loss live on one stage each → share over pp;
    # reduce over the batch axes (mean: /n_batch) and the seq axes (sum:
    # each sp shard computed a PARTIAL contribution from its seq slice)
    reduce_axes = (axis_name,) + tuple(batch_axes) + tuple(seq_axes)
    g_enc = _tmap(lambda g: lax.psum(g, reduce_axes) / n_batch,
                  state["g_enc"])
    g_dec = _tmap(lambda g: lax.psum(g, reduce_axes) / n_batch,
                  state["g_dec"])
    loss = lax.psum(state["loss"], reduce_axes) / n_batch
    g_stage = _tmap(lambda g: g[None], state["g_stage"])
    stage_reduce = tuple(batch_axes) + tuple(seq_axes)
    if stage_reduce:
        g_stage = _tmap(
            lambda g: lax.psum(g, stage_reduce) / n_batch, g_stage)
    return loss, {"encode": g_enc, "stages": g_stage, "decode": g_dec}


def make_pipeline_train_step(tx, *, encode_fn, stage_fn, decode_fn, mesh,
                             num_micro=None, seq_axes=None,
                             num_chunks=None,
                             x_key="input_ids", y_key="label"):
    """An ElasticTrainer ``step_fn`` driving the 1F1B engine: the hook
    that puts pipeline-parallel training inside the elastic harness —
    stop-resume checkpointing (stage params stay pp-sharded through the
    sharded save and the placed restore), preemption, and fit() all
    apply. The train state is the canonical make_train_state pytree
    whose "params" is the pipeline tree {"encode", "stages", "decode"};
    pass param_shardings placing "stages" on the pp axis. ``tx`` MUST
    be the same GradientTransformation object given to ElasticTrainer —
    the trainer's tx.init builds the opt_state this step updates, and a
    mismatched transform trains with the wrong hyperparameters (or
    fails with an opaque pytree error for different structures).

    num_chunks selects the interleaved (circular) engine with that many
    virtual stages per device ("stages" then carries the device-major
    [P*V, ...] layout from device_major_stage_params); the interleaved
    engine does not take seq_axes."""
    import optax

    if num_chunks is not None and seq_axes:
        raise ValueError("the interleaved engine does not compose with "
                         "seq_axes (use the 1F1B pair schedule)")

    def step(train_state, batch, rng):
        del rng  # the pipelined stacks are deterministic (no dropout)
        if num_chunks is not None:
            loss, grads = pipeline_value_and_grad_interleaved(
                train_state["params"], batch[x_key], batch[y_key],
                encode_fn=encode_fn, stage_fn=stage_fn,
                decode_fn=decode_fn, mesh=mesh, num_chunks=num_chunks,
                num_micro=num_micro)
        else:
            loss, grads = pipeline_value_and_grad(
                train_state["params"], batch[x_key], batch[y_key],
                encode_fn=encode_fn, stage_fn=stage_fn,
                decode_fn=decode_fn, mesh=mesh, num_micro=num_micro,
                seq_axes=seq_axes)
        updates, opt_state = tx.update(grads, train_state["opt_state"],
                                       train_state["params"])
        return {
            "params": optax.apply_updates(train_state["params"], updates),
            "opt_state": opt_state,
            "step": train_state["step"] + 1,
            "extra": train_state["extra"],
        }, loss

    return step


def pipeline_value_and_grad(params, x, y, *, encode_fn, stage_fn, decode_fn,
                            mesh, num_micro=None, pipe_axis=PIPE_AXIS,
                            batch_axes=None, seq_axes=None):
    """(loss, grads) of a pipelined network on the 1F1B schedule.

    params: {"encode": pytree, "stages": pytree with leading stage axis
    [P, ...] (sharded over pp), "decode": pytree}. The network is
    ``decode_fn(p_dec, stage^P(encode_fn(p_enc, x)), y)``; loss is the
    mean over microbatches (decode_fn must return a per-microbatch mean).
    x/y batch dims are sharded over ``batch_axes`` (defaults to ("dp",)
    when present in the mesh); grads are psum-reduced over them and
    returned with "stages" still pp-sharded.

    seq_axes: sequence parallelism COMPOSED with the pipeline — x's dim 1
    (and the activations) shard over these mesh axes; stage/encode/decode
    fns run on seq slices and may use lax collectives over the axis names
    directly (e.g. the in-shard ring attention). decode_fn must return
    this shard's CONTRIBUTION to the loss (sum of per-shard terms ÷
    global counts); the engine sums contributions over seq_axes.
    """
    num_stages = mesh.shape[pipe_axis]
    if batch_axes is None:
        batch_axes = tuple(
            ax for ax in (DATA_AXIS,)
            if ax in mesh.shape and mesh.shape[ax] > 1)
    if seq_axes is None:
        seq_axes = ()
    num_micro = num_micro or num_stages
    batch = jax.tree_util.tree_leaves(x)[0].shape[0]
    shard = 1
    for ax in batch_axes:
        shard *= mesh.shape[ax]
    if (batch // shard) % num_micro != 0:
        raise ValueError(
            "per-shard batch %d not divisible by %d microbatches"
            % (batch // shard, num_micro))

    bspec = tuple(batch_axes) if batch_axes else None
    x_spec = (P(bspec, tuple(seq_axes)) if seq_axes else P(bspec))
    y_spec = P(bspec)
    param_specs = {
        "encode": _tmap(lambda _: P(), params["encode"]),
        "stages": _tmap(lambda _: P(pipe_axis), params["stages"]),
        "decode": _tmap(lambda _: P(), params["decode"]),
    }
    fn = shard_map(
        functools.partial(_pipe_1f1b_shard, encode_fn=encode_fn,
                          stage_fn=stage_fn, decode_fn=decode_fn,
                          num_stages=num_stages, num_micro=num_micro,
                          axis_name=pipe_axis, batch_axes=tuple(batch_axes),
                          n_batch=shard, seq_axes=tuple(seq_axes)),
        mesh=mesh,
        in_specs=(param_specs, x_spec, y_spec),
        out_specs=(P(), {"encode": P(), "stages": P(pipe_axis),
                         "decode": P()}),
        check_vma=False)
    return fn(params, x, y)


def _pipe_interleaved_shard(params, xs, ys, tables, *, encode_fn,
                            stage_fn, decode_fn, sched, axis_name,
                            batch_axes, n_batch):
    """Interleaved (circular) schedule on one pp slice: V virtual stages
    per device, ops driven by the static tables (pipeline_schedule.py).
    Buffers are sized by the schedule's true high-water marks."""
    nP = sched["num_stages"]
    M = sched["num_micro"]
    V = sched["num_chunks"]
    T = sched["n_ticks"]
    idx = lax.axis_index(axis_name)
    p_enc, p_dec = params["encode"], params["decode"]
    # local chunks: leading axis V (device-major global layout)
    p_chunks = params["stages"]

    mb_sz = xs.shape[0] // M
    xmb = _tmap(lambda a: a.reshape((M, mb_sz) + a.shape[1:]), xs)
    ymb = _tmap(lambda a: a.reshape((M, mb_sz) + a.shape[1:]), ys)

    def take(tree, m):
        return _tmap(lambda a: a[m], tree)

    p_chunk0 = _tmap(lambda a: a[0], p_chunks)
    act = jax.eval_shape(encode_fn, p_enc, take(xmb, 0))
    out_shape = jax.eval_shape(stage_fn, p_chunk0, act)
    if jax.tree_util.tree_structure(out_shape) \
            != jax.tree_util.tree_structure(act):
        raise ValueError("stage_fn must map the activation pytree to "
                         "itself")
    zeros_act = _tmap(lambda s: jnp.zeros(s.shape, s.dtype), act)

    def buf(n):
        return _tmap(lambda s: jnp.zeros((n,) + s.shape, s.dtype), act)

    fwd_perm = [(i, (i + 1) % nP) for i in range(nP)]
    bwd_perm = [((i + 1) % nP, i) for i in range(nP)]

    state = dict(
        fwd_carry=zeros_act, bwd_carry=zeros_act,
        save=buf(sched["n_save_slots"]),
        rxf=buf(sched["n_rxf_slots"]),
        rxb=buf(sched["n_rxb_slots"]),
        g_enc=_tmap(jnp.zeros_like, p_enc),
        g_stages=_tmap(jnp.zeros_like, p_chunks),
        g_dec=_tmap(jnp.zeros_like, p_dec),
        loss=jnp.zeros((), jnp.float32),
    )

    def tick(t, state):
        # phase 1: deposit ring arrivals into the receive buffers
        rxf = _tmap(
            lambda b, v: jnp.where(
                tables["recv_f"][t, idx] > 0,
                lax.dynamic_update_index_in_dim(
                    b, v, tables["rxf_w"][t, idx], 0), b),
            state["rxf"], state["fwd_carry"])
        rxb = _tmap(
            lambda b, v: jnp.where(
                tables["recv_b"][t, idx] > 0,
                lax.dynamic_update_index_in_dim(
                    b, v, tables["rxb_w"][t, idx], 0), b),
            state["rxb"], state["bwd_carry"])
        state = dict(state, rxf=rxf, rxb=rxb)

        kind = tables["op"][t, idx]
        v = tables["chunk"][t, idx]
        m = tables["mb"][t, idx]
        sigma = v * nP + idx
        p_v = _tmap(lambda a: a[v], p_chunks)

        def do_idle(state):
            return state, zeros_act, zeros_act

        def do_fwd(state):
            x_in = lax.cond(
                sigma == 0,
                lambda: encode_fn(p_enc, take(xmb, m)),
                lambda: _tmap(lambda b: b[tables["rxf_r"][t, idx]],
                              state["rxf"]))
            y = stage_fn(p_v, x_in)
            save = _tmap(
                lambda b, val: lax.dynamic_update_index_in_dim(
                    b, val, tables["save_slot"][t, idx], 0),
                state["save"], x_in)
            return dict(state, save=save), y, zeros_act

        def do_bwd(state):
            x_saved = _tmap(lambda b: b[tables["save_slot"][t, idx]],
                            state["save"])

            def last_stage():
                def comp(ps, pd, x):
                    return decode_fn(pd, stage_fn(ps, x), take(ymb, m))
                loss_m, vjp = jax.vjp(comp, p_v, p_dec, x_saved)
                gs, gd, gx = vjp(jnp.float32(1.0 / M))
                return loss_m, gs, gd, gx

            def mid_stage():
                dy = _tmap(lambda b: b[tables["rxb_r"][t, idx]],
                           state["rxb"])
                _, vjp = jax.vjp(stage_fn, p_v, x_saved)
                gs, gx = vjp(dy)
                return (jnp.zeros((), jnp.float32), gs,
                        _tmap(jnp.zeros_like, p_dec), gx)

            loss_m, gs, gd, gx = lax.cond(
                sigma == nP * V - 1, last_stage, mid_stage)
            ge = lax.cond(
                sigma == 0,
                lambda: jax.vjp(
                    lambda p: encode_fn(p, take(xmb, m)), p_enc)[1](
                        gx)[0],
                lambda: _tmap(jnp.zeros_like, p_enc))
            g_stages = _tmap(lambda G, g: G.at[v].add(g),
                             state["g_stages"], gs)
            out = dict(
                state, g_stages=g_stages,
                g_dec=_tmap(lambda a, b: a + b, state["g_dec"], gd),
                g_enc=_tmap(lambda a, b: a + b, state["g_enc"], ge),
                loss=state["loss"] + loss_m / M)
            return out, zeros_act, gx

        state, y_send, g_send = lax.switch(kind, [do_idle, do_fwd, do_bwd],
                                           state)
        state["fwd_carry"] = _tmap(
            lambda val: lax.ppermute(val, axis_name, fwd_perm), y_send)
        state["bwd_carry"] = _tmap(
            lambda val: lax.ppermute(val, axis_name, bwd_perm), g_send)
        return state

    state = lax.fori_loop(0, T, tick, state)

    reduce_axes = (axis_name,) + tuple(batch_axes)
    g_enc = _tmap(lambda g: lax.psum(g, reduce_axes) / n_batch,
                  state["g_enc"])
    g_dec = _tmap(lambda g: lax.psum(g, reduce_axes) / n_batch,
                  state["g_dec"])
    loss = lax.psum(state["loss"], reduce_axes) / n_batch
    g_stages = state["g_stages"]
    if batch_axes:
        g_stages = _tmap(
            lambda g: lax.psum(g, tuple(batch_axes)) / n_batch, g_stages)
    return loss, {"encode": g_enc, "stages": g_stages, "decode": g_dec}


def device_major_stage_params(stage_params, num_stages, num_chunks):
    """Reorder a [S, ...] virtual-stage-major pytree into the device-major
    layout the interleaved engine shards over pp: global index
    j = (σ % P) * V + σ // P, so device s's contiguous block holds its
    chunks σ = s, s+P, ..., s+(V-1)P in chunk order."""
    perm = [0] * (num_stages * num_chunks)
    for sigma in range(num_stages * num_chunks):
        perm[(sigma % num_stages) * num_chunks + sigma // num_stages] = \
            sigma
    order = jnp.asarray(perm)
    return _tmap(lambda a: a[order], stage_params)


def virtual_stage_major_stage_params(stage_params, num_stages,
                                     num_chunks):
    """Inverse of device_major_stage_params."""
    inv = [0] * (num_stages * num_chunks)
    for sigma in range(num_stages * num_chunks):
        inv[sigma] = (sigma % num_stages) * num_chunks \
            + sigma // num_stages
    order = jnp.asarray(inv)
    return _tmap(lambda a: a[order], stage_params)


def pipeline_value_and_grad_interleaved(params, x, y, *, encode_fn,
                                        stage_fn, decode_fn, mesh,
                                        num_chunks, num_micro=None,
                                        pipe_axis=PIPE_AXIS,
                                        batch_axes=None):
    """(loss, grads) on the interleaved (circular) pipeline schedule.

    params["stages"] has leading axis S = P * num_chunks in DEVICE-MAJOR
    order (device_major_stage_params converts from σ order); each device
    runs its V chunks per the static tables from
    pipeline_schedule.build_schedule, shrinking the warmup bubble from
    O(P) to O(P/V). Grads come back in the same layout, pp-sharded.
    """
    from edl_tpu.parallel.pipeline_schedule import build_schedule

    num_stages = mesh.shape[pipe_axis]
    if batch_axes is None:
        batch_axes = tuple(
            ax for ax in (DATA_AXIS,)
            if ax in mesh.shape and mesh.shape[ax] > 1)
    num_micro = num_micro or num_stages
    batch = jax.tree_util.tree_leaves(x)[0].shape[0]
    shard = 1
    for ax in batch_axes:
        shard *= mesh.shape[ax]
    if (batch // shard) % num_micro != 0:
        raise ValueError("per-shard batch %d not divisible by %d "
                         "microbatches" % (batch // shard, num_micro))
    n_stage_leaves = jax.tree_util.tree_leaves(params["stages"])
    if n_stage_leaves[0].shape[0] != num_stages * num_chunks:
        raise ValueError(
            "stages leading axis %d != P*V = %d"
            % (n_stage_leaves[0].shape[0], num_stages * num_chunks))

    sched = build_schedule(num_stages, num_micro, num_chunks)
    tables = {k: jnp.asarray(sched[k])
              for k in ("op", "chunk", "mb", "recv_f", "recv_b",
                        "save_slot", "rxf_w", "rxf_r", "rxb_w", "rxb_r")}

    data_spec = P(tuple(batch_axes) if batch_axes else None)
    param_specs = {
        "encode": _tmap(lambda _: P(), params["encode"]),
        "stages": _tmap(lambda _: P(pipe_axis), params["stages"]),
        "decode": _tmap(lambda _: P(), params["decode"]),
    }
    table_specs = _tmap(lambda _: P(), tables)
    fn = shard_map(
        functools.partial(_pipe_interleaved_shard, encode_fn=encode_fn,
                          stage_fn=stage_fn, decode_fn=decode_fn,
                          sched=sched, axis_name=pipe_axis,
                          batch_axes=tuple(batch_axes), n_batch=shard),
        mesh=mesh,
        in_specs=(param_specs, data_spec, data_spec, table_specs),
        out_specs=(P(), {"encode": P(), "stages": P(pipe_axis),
                         "decode": P()}),
        check_vma=False)
    return fn(params, x, y, tables)


def sequential_apply(stage_params, x, stage_fn):
    """Reference implementation: apply stages one after another."""
    num_stages = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    for s in range(num_stages):
        params = jax.tree_util.tree_map(lambda p: p[s], stage_params)
        x = stage_fn(params, x)
    return x
