"""The optimizer a traffic file names, twice: as the optax transformation
handed to the program's trainer, and as plain update rules for the
reference (which owes the program nothing, optax state included)."""

import jax
import jax.numpy as jnp


def make_tx(spec):
    import optax
    if spec["name"] == "sgd":
        return optax.sgd(spec["lr"], momentum=spec["momentum"])
    if spec["name"] == "adamw":
        return optax.adamw(spec["lr"], b1=spec["b1"], b2=spec["b2"],
                           eps=spec["eps"],
                           weight_decay=spec["weight_decay"])
    raise ValueError("unknown optimizer %r" % (spec["name"],))


def moment_scale(spec):
    """first moment after ONE step = moment_scale * gradient."""
    return 1.0 if spec["name"] == "sgd" else 1.0 - spec["b1"]


def ref_init(spec, w):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, w)
    return {"m": zeros, "v": zeros, "t": 0}


def ref_update(spec, w, g, st):
    """One plain optimizer step; returns (w', state')."""
    tm = jax.tree_util.tree_map
    t = st["t"] + 1
    if spec["name"] == "sgd":
        m = tm(lambda m_, g_: spec["momentum"] * m_ + g_, st["m"], g)
        return (tm(lambda w_, m_: w_ - spec["lr"] * m_, w, m),
                {"m": m, "v": st["v"], "t": t})
    b1, b2 = spec["b1"], spec["b2"]
    m = tm(lambda m_, g_: b1 * m_ + (1 - b1) * g_, st["m"], g)
    v = tm(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, st["v"], g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def step(w_, m_, v_):
        upd = (m_ / c1) / (jnp.sqrt(v_ / c2) + spec["eps"])
        return w_ - spec["lr"] * (upd + spec["weight_decay"] * w_)

    return tm(step, w, m, v), {"m": m, "v": v, "t": t}


def first_moment(opt_state, params):
    """The optax state's first params-shaped subtree: sgd's `trace`,
    adam's `mu`."""
    want = jax.tree_util.tree_structure(params)
    found = []

    def is_leaf(x):
        if jax.tree_util.tree_structure(x) == want:
            found.append(x)
            return True
        return False

    jax.tree_util.tree_leaves(opt_state, is_leaf=is_leaf)
    if not found:
        raise ValueError("no first-moment tree in the optimizer state")
    return found[0]
