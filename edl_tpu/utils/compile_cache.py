"""Where the persistent XLA compilation cache lives, and the one place
that turns it on.

``JAX_COMPILATION_CACHE_DIR`` is JAX's own variable: when it is set JAX
already uses it, and nothing here overrides what JAX was told. When it
is not set the cache goes to a fixed, git-ignored directory at the root
of the checkout — fixed because the directory is part of how a later
process finds the entries again, so a path built from a pid, the time or
a temporary name never hits. A tool that wants a cold-versus-warm
comparison hands its children ``JAX_COMPILATION_CACHE_DIR``.

No jax import at module level: launchers and bench parents resolve the
path without touching a backend.
"""

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir():
    """The resolved cache directory (not created here)."""
    return os.environ.get(ENV) or DEFAULT_DIR


def aot_dir():
    """Serialized AOT step executables (trainer resize prewarm) live
    beside the XLA cache, under whichever directory was resolved."""
    return os.path.join(cache_dir(), "aot_steps")


def enable():
    """Turn the persistent cache on for this process. Only sets a config
    value, so it is safe before ``jax.distributed.initialize`` (no
    backend is initialised)."""
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
