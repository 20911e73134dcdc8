"""checkpoint layer: span `save.persist` (the writer's thread: every entry file,
`meta.json`, then the manifest; off the training thread), median over the
window's saves whose write has ended."""
from benchmark.lib import progspans


def read(view):
    return progspans.save_ms(view, "save.persist")
