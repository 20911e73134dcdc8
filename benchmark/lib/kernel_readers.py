"""Arithmetic shared by the per-kernel and routing-counter readers
(benchmark/metrics/<kernel>_device_ms.py, <kernel>_roofline_pct.py,
moe_*.py): a Pallas kernel is found in the trace by the `name=` the
program gave it (autodiff and remat wrap the name: `jvp_moe_gmm_`,
`transpose_jvp_moe_tgmm__`), its required operations and bytes come from
benchmark/program/<family>.py:kernel_costs, and the routing counters from
the program's metrics registry, where the trainer mirrors them at
`close()`. Every reader returns None where there is nothing to read."""


def model_counters():
    """{counter name: [value per layer]} of `edl_train_model_counter`, or
    {} where the program has no such gauge or never set it."""
    try:
        from edl_tpu.obs import metrics
        fam = metrics.REGISTRY.snapshot()["metrics"].get(
            "edl_train_model_counter")
    except Exception:  # noqa: BLE001 — a program without the registry
        return {}
    out = {}
    for s in (fam or {}).get("series", []):
        out.setdefault(s["labels"]["name"], {})[
            int(s["labels"]["index"])] = s["value"]
    return {k: [v[i] for i in sorted(v)] for k, v in out.items()}


def kernel_seconds(view, name):
    """Device seconds, per chip, of the op classes whose name holds
    `name`, over the traced window; None where there is none."""
    hits = [sec for cls, sec in view["trace"]["ops"] if name in cls]
    return sum(hits) if hits else None


def kernel_device_ms(view, name):
    sec = kernel_seconds(view, name)
    steps = view["counters"].get("traced_steps")
    if sec is None or not steps:
        return None
    return sec / steps * 1e3


def expert_rows_per_step(counters):
    """Mean rows the held experts served per layer and step, or None."""
    rows, steps = counters.get("rows_held"), counters.get("steps")
    if not rows or not steps or not steps[0]:
        return None
    return sum(rows) / len(rows) / steps[0]


def kernel_roofline_pct(view, name):
    """The least time the chip could take for the kernel's calls of one
    step — the larger of required operations over the bf16 peak and
    required bytes over the memory peak (benchmark/lib/peaks.py) — over
    the time the trace shows for them."""
    ms = kernel_device_ms(view, name)
    if not ms:
        return None
    from benchmark.lib.harness import load_module
    rows = view["traffic"]["batch_per_chip"]
    family = load_module("program", view["config"]["family"])
    if not hasattr(family, "kernel_costs"):
        return None
    costs = family.kernel_costs(view["config"], view["traffic"], rows,
                                expert_rows_per_step(model_counters()))
    if name not in costs:
        return None
    ops, nbytes = costs[name]
    least_s = max(ops / view["peaks"]["bf16_flops"],
                  nbytes / view["peaks"]["hbm_bytes_s"])
    return 100.0 * least_s / (ms / 1e3)


def load_max_over_mean(counters):
    """Worst layer's (largest load of a held expert in any step) over
    (its mean load over the steps)."""
    top, mean, steps = (counters.get(k) for k in ("load_max", "load_mean",
                                                  "steps"))
    if not top or not mean or not steps or not steps[0]:
        return None
    ratios = [t / (m / steps[0]) for t, m in zip(top, mean) if m > 0]
    return max(ratios) if ratios else None
