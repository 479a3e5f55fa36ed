"""The benchmark's own tests: CPU rehearsals at tiny sizes.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

Four virtual CPU devices stand for the 2x2 host, and the CPU pin is what
lets the program's "tpu" coder run here (it refuses other devices unless
the process pinned the CPU itself).
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
