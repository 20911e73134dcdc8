"""The benchmark's own tests run on the CPU with four virtual devices,
at the toy sizes of the files' `tiny` blocks. They are not part of the
repository's tier-1 run (which collects tests/ only):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
