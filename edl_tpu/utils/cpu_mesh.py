"""Force a virtual n-device CPU platform for hermetic multi-chip tests.

XLA only honours --xla_force_host_platform_device_count before backends
initialize, and JAX_PLATFORMS=cpu keeps a test process (and the children
it spawns) off any real chip. Both tests/conftest.py and
``__graft_entry__.dryrun_multichip`` need the same recipe, so it lives
here (no jax import — callers must apply it before jax initializes).
"""


def force_cpu_env(env, n_devices):
    """Mutate ``env`` (a dict, e.g. os.environ or a subprocess env copy)
    so that a fresh Python process sees ``n_devices`` virtual CPU devices
    and never claims an accelerator. Returns ``env``."""
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=%d" % n_devices)
    env["XLA_FLAGS"] = " ".join(flags)
    return env
