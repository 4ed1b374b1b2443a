"""Multi-node FedNL: clients sharded across mesh devices via shard_map.

The paper's multi-node setting (Section 7, 9.3) is a star topology: n clients
uplink (grad_i, S_i, l_i) over TCP to one master.  On a TPU mesh the natural
mapping is:

  * clients -> the `data` mesh axis (each device simulates/hosts a block of
    clients and runs the vmapped client body locally);
  * the master reduction -> ICI collectives;
  * the Newton solve -> replicated on every device (d is small; cheaper than
    sharding a (d, d) Cholesky and avoids a broadcast of x afterwards).

Two aggregation strategies (the collective is THE communication cost here —
the roofline collective term):

  dense_psum       faithful-to-paper semantics: every client's correction is
                   densified locally and `psum`-ed as a length-T vector.
                   Collective bytes per round ~ T * 8 * (ring factor).

  sparse_allgather beyond-paper (DESIGN.md §7): sparsifying compressors uplink
                   only (idx: int32, val: f64) pairs of length k per client;
                   devices `all_gather` the pairs and scatter-add locally.
                   Collective bytes ~ n_clients * k * 12 — a T/(k * n_local)
                   -fold reduction whenever k << T.  Exactly the paper's §5.6
                   "use sparsity from FedNL compressors" trick, applied to the
                   collective instead of the CPU master loop.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api.accounting import payload_bits_fn, wire_bits_fn
from repro.compressors import get_compressor
from repro.compressors.core import scatter_add_sparse
from repro.core.fednl import (
    FedNLConfig,
    FedNLState,
    client_round,
    fednl_init,
    master_step,
)
from repro.linalg import triu_size, frob_norm_from_packed
from repro.objectives.logreg import logreg_oracles_packed


def shard_problem(z, mesh: Mesh, axis: str = "data"):
    """Place (n_clients, n_i, d) data with clients sharded over `axis`."""
    return jax.device_put(z, NamedSharding(mesh, P(axis, None, None)))


def sharded_fednl_init(z, cfg: FedNLConfig, mesh: Mesh, axis: str = "data", seed: int = 0):
    """Initial state with each device initializing its own clients' H_i^0
    (`fednl_init` per shard): the shifts never leave their shard, and the
    per-client ``lax.map`` that `fednl_init` uses above d = 128 cannot map a
    sharded client axis."""
    h_local = jax.jit(jax.shard_map(
        lambda z_loc: fednl_init(z_loc, cfg, seed=seed).h_local,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(axis),
        check_vma=False,
    ))(z)
    rep = NamedSharding(mesh, P())
    return FedNLState(
        x=jax.device_put(jnp.zeros(z.shape[-1], dtype=z.dtype), rep),
        h_local=h_local,
        h_global=jax.device_put(jnp.mean(h_local, axis=0), rep),
        key=jax.device_put(jax.random.PRNGKey(seed), rep),
        round=jax.device_put(jnp.asarray(0), rep),
    )


def make_sharded_fednl_step(
    n_clients: int, d: int, cfg: FedNLConfig, mesh: Mesh, axis: str = "data",
    aggregate: str = "dense_psum", payload_dtype=None,
):
    """Shape-only builder: returns `step(z, h_local, x, h_global, key)`.

    Used both by make_sharded_fednl_round (with concrete data) and by the
    production-mesh dry-run (with ShapeDtypeStruct stand-ins).

    payload_dtype: optional cast applied to the sparse collective VALUES
    before the all_gather (e.g. jnp.float32 halves the wire payload; the
    accuracy consequence is measured in EXPERIMENTS.md §Perf).
    """
    t = triu_size(d)
    comp = get_compressor(cfg.compressor, t, cfg.k_for(d))
    alpha = comp.alpha if cfg.alpha is None else cfg.alpha
    pay_fn = payload_bits_fn(comp, d)
    wire_fn = wire_bits_fn(comp, d)
    n_dev = mesh.shape[axis]
    if n_clients % n_dev:
        raise ValueError(f"n_clients={n_clients} not divisible by mesh axis {axis}={n_dev}")
    if aggregate == "sparse_allgather" and comp.compress_sparse is None:
        raise ValueError(f"{cfg.compressor} has no sparse form; use dense_psum")

    def body(z_loc, h_loc, x, h_global, key):
        # per-device PRNG stream: fold in the device's position on the axis
        dev = jax.lax.axis_index(axis)
        key_dev = jax.random.fold_in(key, dev)
        n_loc = z_loc.shape[0]
        client_keys = jax.random.split(key_dev, n_loc)

        if aggregate == "dense_psum":
            f_i, grad_i, s_i, l_i, h_loc_new, sent_i = jax.vmap(
                lambda zi, hi, ki: client_round(
                    zi, hi, x, ki, comp, alpha, cfg.lam, cfg.hessian_impl
                )
            )(z_loc, h_loc, client_keys)
            s = jax.lax.psum(jnp.sum(s_i, axis=0), axis) / n_clients
        else:  # sparse_allgather
            def client_sparse(zi, hi, ki):
                f_i, grad_i, hp = logreg_oracles_packed(
                    zi, x, cfg.lam, hessian=cfg.hessian_impl
                )
                delta = hp - hi
                idx, vals, sent = comp.compress_sparse(ki, delta)
                s_dense_local = scatter_add_sparse(idx, vals, t)
                l_i = frob_norm_from_packed(delta, d)
                return f_i, grad_i, idx, vals, l_i, hi + alpha * s_dense_local, sent

            f_i, grad_i, idx_i, vals_i, l_i, h_loc_new, sent_i = jax.vmap(
                client_sparse
            )(z_loc, h_loc, client_keys)
            # the compressed collective: gather only (idx, val) pairs
            if payload_dtype is not None:
                vals_i = vals_i.astype(payload_dtype)
            idx_all = jax.lax.all_gather(idx_i, axis, tiled=True)
            vals_all = jax.lax.all_gather(vals_i, axis, tiled=True)
            vals_all = vals_all.astype(x.dtype)
            s = scatter_add_sparse(idx_all, vals_all, t) / n_clients

        grad = jax.lax.psum(jnp.sum(grad_i, axis=0), axis) / n_clients
        l = jax.lax.psum(jnp.sum(l_i), axis) / n_clients
        f = jax.lax.psum(jnp.sum(f_i), axis) / n_clients
        sent = jax.lax.psum(jnp.sum(sent_i), axis)
        # uplink wire bits under the Section-7 encodings (repro.api.accounting);
        # cfg.accounting selects payload-only vs full-frame accounting
        bits_payload = jax.lax.psum(jnp.sum(jax.vmap(pay_fn)(sent_i)), axis)
        bits_wire = jax.lax.psum(jnp.sum(jax.vmap(wire_fn)(sent_i)), axis)

        x_new = master_step(x, h_global, grad, l, cfg)
        h_global_new = h_global + alpha * s
        gn = jnp.linalg.norm(grad)
        return (h_loc_new, x_new, h_global_new, gn, f, l, sent,
                bits_payload, bits_wire)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(), P()),
        out_specs=(P(axis), P(), P(), P(), P(), P(), P(), P(), P()),
        check_vma=False,
    )


def make_sharded_fednl_round(
    z, cfg: FedNLConfig, mesh: Mesh, axis: str = "data",
    aggregate: str = "dense_psum", payload_dtype=None,
) -> Callable[[FedNLState], tuple[FedNLState, dict]]:
    """Build the shard_mapped round; `z` must already be sharded over `axis`."""
    n_clients, _, d = z.shape
    sharded = make_sharded_fednl_step(
        n_clients, d, cfg, mesh, axis, aggregate, payload_dtype
    )

    def round_fn(state: FedNLState):
        key, sub = jax.random.split(state.key)
        (h_loc_new, x_new, h_global_new, gn, f, l, sent,
         bits_payload, bits_wire) = sharded(
            z, state.h_local, state.x, state.h_global, sub
        )
        new_state = FedNLState(
            x=x_new, h_local=h_loc_new, h_global=h_global_new,
            key=key, round=state.round + 1,
        )
        bits = bits_payload if cfg.accounting == "payload" else bits_wire
        return new_state, {"grad_norm": gn, "f": f, "l": l,
                           "sent_elems": sent, "sent_bits": bits,
                           "sent_bits_payload": bits_payload,
                           "sent_bits_wire": bits_wire}

    return round_fn
