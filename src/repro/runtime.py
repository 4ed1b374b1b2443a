"""Process-wide JAX set-up that every FedNL entry point runs first.

FedNL is an FP64 algorithm end to end, so x64 is enabled.  The persistent
compilation cache is switched on so that a repeated run does not compile
the round programs again (on the chip most of a short run's set-up is
compilation):

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  module sets no other directory;
* otherwise, where the default backend is an accelerator, the cache lives
  at ``<checkout>/.jax_cache`` (listed in ``.gitignore``) — one fixed path,
  since the path is part of what makes a later run find the entries again.
  A CPU-only process (the test suite, the star-tcp workers) gets no cache
  from here: its compiles are short, and XLA:CPU executables read back from
  disk log a machine-feature mismatch on every load.

Programs partitioned over several devices (the sharded backend, a
device-sharded sweep) use the GSPMD partitioner, not Shardy: with Shardy
the TPU compiler refuses the master's f64 Cholesky factorization ("A tuple
parameter that is being flattened shouldn't have frontend attributes";
seen on four v5e chips and in a compile for a described v5e:2x2), while
GSPMD compiles it.  A single-device program is not partitioned, so this
changes nothing there.

``LIBTPU_INIT_ARGS`` is never written here: the TPU runtime's own flags
stay as the machine sets them.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_jax() -> None:
    """Enable x64 and the persistent compilation cache (idempotent)."""
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_use_shardy_partitioner", False)  # module docstring
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    if jax.config.jax_compilation_cache_dir is None:
        if jax.default_backend() != "cpu":
            jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
