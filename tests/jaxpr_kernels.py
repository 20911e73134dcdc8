"""What the tests read off a jaxpr about its Pallas kernels (imported by
test files, like fake_k8s.py: no test of its own)."""

import collections

import jax

from edl_tpu.models import sparse_decoder


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


def gradient_kernel_calls(fam, cfg, w, batch, remat):
    """{kernel name: calls} in the jaxpr of a sparse decoder's gradient
    (``fam``: its benchmark/program module), the Pallas kernels forced: on
    the CPU the interpreter's, traced and not run."""
    model = fam.build_model(cfg, {"remat": remat}).clone(use_flash=True)
    _, _, extra, loss_fn = sparse_decoder.create_model_and_loss(model)
    params, _ = fam.to_program(w, cfg)
    return collections.Counter(pallas_call_names(jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, extra, batch, None)[0]))(params).jaxpr))
