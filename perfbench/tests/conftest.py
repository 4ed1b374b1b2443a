"""Self-tests of the benchmark, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
