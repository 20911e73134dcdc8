"""What the tests read off a jaxpr about its Pallas kernels (imported by
test files, like fake_k8s.py: no test of its own)."""

import collections
import hashlib
import os
import re

import jax

from benchmark.lib import harness
from edl_tpu.models import sparse_decoder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pallas_call_names(jaxpr):
    """The ``name=`` of every `pallas_call` a jaxpr holds, its sub-jaxprs
    (remat, custom_vjp, pjit) included: one entry a call."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += pallas_call_names(sub)
    return names


def gradient_jaxpr(fam, cfg, w, batch, remat):
    """The jaxpr of a sparse decoder's gradient (``fam``: its
    benchmark/program module), the Pallas kernels forced: on the CPU the
    interpreter's, traced and not run."""
    model = fam.build_model(cfg, {"remat": remat}).clone(use_flash=True)
    _, _, extra, loss_fn = sparse_decoder.create_model_and_loss(model)
    params, _ = fam.to_program(w, cfg)
    return jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, extra, batch, None)[0]))(params).jaxpr


def gradient_kernel_calls(fam, cfg, w, batch, remat):
    """{kernel name: calls} in :func:`gradient_jaxpr`."""
    return collections.Counter(pallas_call_names(
        gradient_jaxpr(fam, cfg, w, batch, remat)))


def equations_outside_kernels(jaxpr):
    """Every equation of a jaxpr and of its sub-jaxprs (remat, custom_vjp,
    pjit), a `pallas_call`'s own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations_outside_kernels(sub)


def chunk_local_algebra(jaxpr, chunk, sub, dk, dv, scope=""):
    """What a jaxpr holds of a vector-decay delta rule's chunk-local algebra
    (ops/gated_delta.py) OUTSIDE its kernels' bodies, in the equations whose
    name stack holds ``scope``: a loop (`scan`, `while`: the plain path's
    `lax.map`s), an `exp` over a diagonal block's [sub, sub, dk] or a
    chunk's [chunk, chunk] pairs, an array laid a (head, chunk) — [..., n,
    chunk, chunk] (A, T) or [heads, n, chunk, dk or dv] (U, Wk, Qg, Kg).
    One line a finding."""
    found = []
    for eqn in equations_outside_kernels(jaxpr):
        if scope not in str(eqn.source_info.name_stack):
            continue
        name = eqn.primitive.name
        if name in ("scan", "while"):
            found.append(name)
        for v in eqn.invars + eqn.outvars:
            shape = tuple(getattr(v.aval, "shape", ()))
            if shape[-3:] == (sub, sub, dk) or shape[-2:] == (chunk, chunk):
                found.append("%s %s" % (name, shape))
            elif len(shape) == 4 and shape[2:] in ((chunk, dk), (chunk, dv)):
                found.append("%s %s" % (name, shape))
    return found


def _tiny_config(config):
    """A configuration of the benchmark at its `tiny` sizes."""
    cfg = harness.load_json(os.path.join(REPO, "benchmark", "configs",
                                         config + ".json"))
    return dict(cfg, **cfg["tiny"])


def _program_digest(traced):
    """A jaxpr's text, addresses scrubbed: sha256's first 16 hex digits."""
    text = re.sub(r"0x[0-9a-f]+", "0x", str(traced))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def traced_gradient(config, remat, kernels=False):
    """(parameter tree of shapes, sha256's first 16 hex digits of the
    gradient's jaxpr — the whole traced program, loss and counters) of a
    sparse-decoder configuration at its `tiny` sizes: equal text is an
    equal program, so equal bits on any machine. The model's own parameter
    tree must be the one `to_program` gives. ``kernels``: the Pallas
    kernels forced (the program a TPU traces), not the CPU's plain paths."""
    cfg = _tiny_config(config)
    ref = harness.load_module("reference", config)
    fam = harness.load_module("program", cfg["family"])
    w = jax.eval_shape(lambda: ref.init_weights(cfg, jax.random.PRNGKey(0)))
    batch = jax.eval_shape(lambda: fam.make_batch(
        cfg, {"seq_len": 32}, jax.random.PRNGKey(1), 2))
    model = fam.build_model(cfg, {"remat": remat})
    if kernels:
        model = model.clone(use_flash=True)
    _, own, extra, loss_fn = sparse_decoder.create_model_and_loss(model)
    params = jax.eval_shape(lambda w: fam.to_program(w, cfg)[0], w)
    assert (jax.tree_util.tree_structure(own)
            == jax.tree_util.tree_structure(params))
    return params, _program_digest(jax.make_jaxpr(jax.value_and_grad(
        lambda p, b: loss_fn(p, extra, b, None), has_aux=True))(params,
                                                               batch))


def traced_dense_gradient(config, job):
    """The digest `traced_gradient` gives the sparse decoders, of a dense
    cell's program as the benchmark hands it over: the configuration's
    `tiny` sizes through its family's `train_parts` and `make_batch`, two
    rows, loss and gradient (and the new BatchNorm statistics, where the
    loss carries them)."""
    cfg = _tiny_config(config)
    fam = harness.load_module("program", cfg["family"])
    loss_fn, has_aux, (params, extra) = fam.train_parts(cfg, job)
    batch = jax.eval_shape(lambda: fam.make_batch(
        cfg, job, jax.random.PRNGKey(1), 2))
    if has_aux:
        traced = jax.make_jaxpr(jax.value_and_grad(
            lambda p, e, b: loss_fn(p, e, b, None), has_aux=True))(
                params, extra, batch)
    else:
        traced = jax.make_jaxpr(jax.value_and_grad(
            lambda p, b: loss_fn(p, b, None)))(params, batch)
    return _program_digest(traced)
