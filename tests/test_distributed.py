"""Distributed FedNL: shard_map round parity with the single-node round, both
aggregation strategies, and an 8-fake-device integration run in a subprocess
(device count must be set before jax initializes, so it cannot run in-process)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedNLConfig, fednl_init, make_fednl_round
from repro.data import make_synthetic_logreg, add_intercept, partition_clients
from repro.distributed import (
    make_sharded_fednl_round,
    shard_problem,
    sharded_fednl_init,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _z(n_clients=8, n_i=40, seed=1):
    x, y = make_synthetic_logreg((24, n_clients, n_i), seed=seed)
    return jnp.asarray(partition_clients(add_intercept(x), y, n_clients, n_i, seed=seed))


def test_sharded_round_matches_single_node_on_1_device_mesh():
    """On a 1-device mesh with the deterministic TopK compressor, the sharded
    round must be bit-compatible with the vmapped single-node round."""
    z = _z()
    mesh = jax.make_mesh((1,), ("data",))
    cfg = FedNLConfig(compressor="topk", lam=1e-3)

    st_ref = fednl_init(z, cfg, seed=0)
    ref_round = jax.jit(make_fednl_round(z, cfg))

    zs = shard_problem(z, mesh)
    st_sh = sharded_fednl_init(zs, cfg, mesh, seed=0)
    sh_round = jax.jit(make_sharded_fednl_round(zs, cfg, mesh))

    for _ in range(5):
        st_ref, m_ref = ref_round(st_ref)
        st_sh, m_sh = sh_round(st_sh)
    # PRNG streams differ (per-device fold_in) but TopK is deterministic:
    np.testing.assert_allclose(
        np.asarray(st_sh.x), np.asarray(st_ref.x), rtol=1e-12
    )
    np.testing.assert_allclose(float(m_sh["grad_norm"]), float(m_ref.grad_norm), rtol=1e-10)


@pytest.mark.parametrize("agg", ["dense_psum", "sparse_allgather"])
def test_aggregation_strategies_agree(agg):
    z = _z()
    mesh = jax.make_mesh((1,), ("data",))
    cfg = FedNLConfig(compressor="topk", lam=1e-3)
    zs = shard_problem(z, mesh)
    st = sharded_fednl_init(zs, cfg, mesh, seed=0)
    rf = jax.jit(make_sharded_fednl_round(zs, cfg, mesh, aggregate=agg))
    for _ in range(20):
        st, m = rf(st)
    assert float(m["grad_norm"]) < 1e-12


def test_sparse_allgather_rejects_dense_compressor():
    z = _z()
    mesh = jax.make_mesh((1,), ("data",))
    cfg = FedNLConfig(compressor="natural", lam=1e-3)
    zs = shard_problem(z, mesh)
    with pytest.raises(ValueError):
        make_sharded_fednl_round(zs, cfg, mesh, aggregate="sparse_allgather")


@pytest.mark.parametrize("agg", ["dense_psum", "sparse_allgather"])
def test_multidevice_integration_subprocess(agg):
    """Real 8-device shard_map execution (fake CPU devices, own process)."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.data import make_synthetic_logreg, add_intercept, partition_clients
        from repro.core import FedNLConfig
        from repro.distributed import (
            make_sharded_fednl_round, shard_problem, sharded_fednl_init)

        assert jax.device_count() == 8
        x, y = make_synthetic_logreg((24, 8, 40), seed=1)
        z = jnp.asarray(partition_clients(add_intercept(x), y, 8, 40, seed=1))
        mesh = jax.make_mesh((8,), ("data",))
        zs = shard_problem(z, mesh)
        cfg = FedNLConfig(compressor="randseqk", lam=1e-3)
        st = sharded_fednl_init(zs, cfg, mesh, seed=0)
        rf = jax.jit(make_sharded_fednl_round(zs, cfg, mesh, aggregate="{agg}"))
        for _ in range(30):
            st, m = rf(st)
        gn = float(m["grad_norm"])
        assert gn < 1e-12, gn
        print("OK", gn)
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_sharded_init_maps_clients_per_shard_subprocess():
    """Above d = 128 `fednl_init` maps clients with lax.map, which cannot map
    a sharded client axis: the sharded init runs it per shard, and the
    shifts it produces sit on every device and equal the single-node ones."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        jax.config.update("jax_enable_x64", True)
        import numpy as np
        from repro.api import DataSpec
        from repro.core import FedNLConfig
        from repro.core.fednl import FUSED_VMAP_MAX_D, fednl_init
        from repro.distributed import (
            make_sharded_fednl_round, shard_problem, sharded_fednl_init)

        d = FUSED_VMAP_MAX_D + 22
        z = DataSpec(shape=(d, 8, 40), seed=1).build()
        mesh = jax.make_mesh((4,), ("data",))
        zs = shard_problem(z, mesh)
        cfg = FedNLConfig(compressor="topk")
        st = sharded_fednl_init(zs, cfg, mesh, seed=0)
        assert len(st.h_local.sharding.device_set) == 4
        ref = fednl_init(z, cfg, seed=0)
        np.testing.assert_array_equal(np.asarray(st.h_local), np.asarray(ref.h_local))
        st, m = jax.jit(make_sharded_fednl_round(zs, cfg, mesh))(st)
        print("OK", float(m["grad_norm"]))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
