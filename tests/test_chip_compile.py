"""Described-chip compiles of the FedNL Pallas kernels (no chip needed).

Each kernel is compiled by the TPU compiler for one chip of a described
``v5e:2x2`` topology, in f32 at the paper's w8a width (d = 301, 348 samples
per client, T = d(d+1)/2 packed entries, K = 8d): what Mosaic would refuse
on the chip fails here.  Nothing runs, so this says nothing about results
or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around the
compiles (an entry for a described chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.compressor_select import (
    select_randseqk_pallas,
    select_topk_pallas,
)

D, N = 301, 348  # w8a: d with intercept, samples per client
T, K = D * (D + 1) // 2, 8 * D


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


KERNELS = {
    "hessian_syrk": (
        lambda z, h: ops.hessian_syrk(z, h, interpret=False),
        [((N, D), jnp.float32), ((N,), jnp.float32)],
    ),
    "select_topk": (
        lambda u: select_topk_pallas(u, K, interpret=False),
        [((T,), jnp.float32)],
    ),
    "select_randseqk": (
        lambda u, s: select_randseqk_pallas(u, K, s, interpret=False),
        [((T,), jnp.float32), ((), jnp.int32)],
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
