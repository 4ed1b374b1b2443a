"""FedNL — Federated Newton Learn (paper Algorithm 1, faithful reproduction).

One round (clients i = 1..n in parallel, then the master):

  client i: grad_i = ∇f_i(x^k);  D_i = ∇²f_i(x^k)
            S_i = C_i^k(D_i - H_i^k)              (compressed Hessian correction)
            l_i = ||H_i^k - D_i||_F               (Frobenius error)
            H_i^{k+1} = H_i^k + alpha S_i
  master:   S = mean_i S_i;  l = mean_i l_i;  grad = mean_i grad_i
            H^{k+1} = H^k + alpha S
            Option A: x^{k+1} = x^k - [H^k]_mu^{-1} grad
            Option B: x^{k+1} = x^k - (H^k + l^k I)^{-1} grad

Design notes
------------
* All Hessian-shaped state (H_i, S_i, H) lives in packed upper-triangle form
  (T = d(d+1)/2): the paper's symmetry exploitation (§5.8/§5.10/§5.13) — halves
  memory, halves compression work, halves communication.
* Clients are a vmapped axis; `repro.distributed` shard_maps the same round
  body across mesh devices for the multi-node setting.
* The master step follows the printed Algorithm 1 and uses the *pre-update*
  H^k together with the freshly aggregated l^k / grad.
* `hess0="exact"` initializes H_i^0 = ∇²f_i(x^0) (the original FedNL
  experiments' choice, giving superlinear behaviour from the start);
  `"zero"` reproduces the cold start.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.compressors import get_compressor, Compressor
from repro.linalg import (
    unpack_triu,
    triu_size,
    frob_norm_from_packed,
    newton_solve_optionA,
    newton_solve_optionB,
)
from repro.objectives.logreg import logreg_oracles_packed


@dataclasses.dataclass(frozen=True)
class FedNLConfig:
    """Hyper-parameters of a FedNL run (defaults = paper's single-node setup)."""

    compressor: str = "topk"
    k_multiplier: float = 8.0  # paper's K = 8d entries of the Hessian
    alpha: float | None = None  # None -> compressor-recommended (1.0 scaled form)
    option: str = "B"  # master step rule: "A" (projection) | "B" (l-shift)
    mu: float = 1e-3  # strong-convexity lower bound for Option A
    lam: float = 1e-3  # L2 regularization of the logistic objective
    hess0: str = "exact"  # "exact" | "zero"
    # which SYRK realizes the Hessian oracle (repro.objectives.logreg):
    # "fused" (default) = kernels.ops.hessian_fused (the XLA strip SYRK for
    # f64 on every backend, Pallas for f32/bf16 on TPU — DESIGN.md §12;
    # bit-identical to "jnp" for d <= 128); "jnp" = the single-dot_general
    # parity reference; "pallas" forces the Pallas wrapper (compiled on TPU,
    # interpret mode elsewhere — validation only)
    hessian: str = "fused"
    use_kernel: bool = False  # deprecated spelling of hessian="pallas"
    # line-search parameters (FedNL-LS; paper: c = 0.49, gamma = 0.5)
    ls_c: float = 0.49
    ls_gamma: float = 0.5
    ls_max_steps: int = 30
    # accept the unit Newton step without backtracking once ||grad|| is below
    # this tolerance (FP64 plateau: Armijo trials there only burn f-round-trips)
    ls_tol: float = 1e-12
    # sent_bits accounting: "payload" = Section-7 Hessian payload bits (equal
    # to the measured wire payload — see repro.comm.wire); "wire" = full
    # framed uplink bytes incl. protocol header + grad + l + f sections
    accounting: str = "payload"

    def __post_init__(self):
        # inline (not repro.api.ACCOUNTINGS): config construction must not
        # pull the api package into the core layer as a side effect
        if self.accounting not in ("payload", "wire"):
            raise ValueError(
                f"unknown accounting {self.accounting!r}; use 'payload' | 'wire'"
            )
        if self.hessian not in ("fused", "jnp", "pallas"):
            raise ValueError(
                f"unknown hessian {self.hessian!r}; use 'fused' | 'jnp' | 'pallas'"
            )

    @property
    def hessian_impl(self) -> str:
        """The effective Hessian SYRK implementation (use_kernel back-compat)."""
        return "pallas" if self.use_kernel else self.hessian

    def k_for(self, d: int) -> int:
        return max(1, min(triu_size(d), int(self.k_multiplier * d)))


class FedNLState(NamedTuple):
    x: jax.Array  # (d,) model
    h_local: jax.Array  # (n_clients, T) packed client Hessian shifts H_i^k
    h_global: jax.Array  # (T,) packed master estimate H^k = mean_i H_i^k
    key: jax.Array  # PRNG state
    round: jax.Array  # scalar int


def _client_oracles(z: jax.Array, x: jax.Array, lam: float, hessian: str):
    """(f, grad, packed_hess) — the packed oracle emits the upper triangle
    directly off the SYRK strips on the fused path (no mirrored (d, d)
    matrix; bit-identical — see repro.objectives.logreg)."""
    return logreg_oracles_packed(z, x, lam, hessian=hessian)


# one output tile of the blocked SYRK: up to here the fused Hessian is the
# single-dot_general expression (bit-identical to hessian="jnp") and the
# clients stay a vmapped axis; above it the round maps clients with lax.map,
# which keeps each client's strip matmuls and threshold selection
# un-batched — vmap batches the strips into slower layouts and turns the
# selection's compare/sum passes into batched sorts' worst case (w8a,
# 1-core CPU: hessian sweep 435 ms mapped vs 775 ms vmapped; topk selection
# 180 ms mapped mask vs 291 ms vmapped sort — DESIGN.md §12)
FUSED_VMAP_MAX_D = 128


def _map_clients(body: Callable, fused: bool, d: int, *args):
    """vmap or lax.map the per-client round body (see FUSED_VMAP_MAX_D)."""
    if fused and d > FUSED_VMAP_MAX_D:
        return jax.lax.map(lambda a: body(*a), args)
    return jax.vmap(body)(*args)


def fednl_init(
    z: jax.Array, cfg: FedNLConfig, x0: jax.Array | None = None, seed: int = 0
) -> FedNLState:
    """Initial state for problem data z: (n_clients, n_i, d)."""
    n_clients, _, d = z.shape
    t = triu_size(d)
    x = jnp.zeros(d, dtype=z.dtype) if x0 is None else x0.astype(z.dtype)
    if cfg.hess0 == "exact":
        _, _, h_local = _map_clients(
            lambda zi: _client_oracles(zi, x, cfg.lam, cfg.hessian_impl),
            cfg.hessian_impl == "fused",
            d,
            z,
        )
    elif cfg.hess0 == "zero":
        h_local = jnp.zeros((n_clients, t), dtype=z.dtype)
    else:
        raise ValueError(f"unknown hess0 {cfg.hess0!r}")
    h_global = jnp.mean(h_local, axis=0)
    return FedNLState(
        x=x,
        h_local=h_local,
        h_global=h_global,
        key=jax.random.PRNGKey(seed),
        round=jnp.asarray(0),
    )


class RoundMetrics(NamedTuple):
    grad_norm: jax.Array
    f: jax.Array
    l: jax.Array
    sent_elems: jax.Array  # total payload elements uplinked this round
    sent_bits: jax.Array  # total uplink bits under FedNLConfig.accounting
    sent_bits_payload: jax.Array  # Section-7 payload model (repro.api.accounting)
    sent_bits_wire: jax.Array  # full framed uplink model


def make_bits_fn(comp: Compressor, d: int, accounting: str) -> Callable:
    """Deprecated alias of :func:`repro.api.accounting.make_bits_fn` (non-PP
    form), kept for back-compat; new code should import from repro.api."""
    from repro.api.accounting import make_bits_fn as _make_bits_fn

    return _make_bits_fn(comp, d, accounting, pp=False)


def client_round(
    z_i: jax.Array,
    h_i: jax.Array,
    x: jax.Array,
    key: jax.Array,
    comp: Compressor,
    alpha: float,
    lam: float,
    hessian: str,
):
    """Lines 3-7 of Algorithm 1 for one client (vmapped / shard_mapped)."""
    d = z_i.shape[-1]
    f_i, grad_i, hess_i = _client_oracles(z_i, x, lam, hessian)
    delta = hess_i - h_i
    s_i, sent_i = comp.compress(key, delta)
    l_i = frob_norm_from_packed(delta, d)
    h_i_new = h_i + alpha * s_i
    return f_i, grad_i, s_i, l_i, h_i_new, sent_i


def master_step(
    x: jax.Array,
    h_global_packed: jax.Array,
    grad: jax.Array,
    l: jax.Array,
    cfg: FedNLConfig,
) -> jax.Array:
    """Line 11 of Algorithm 1: the Newton-type model update."""
    d = x.shape[0]
    h = unpack_triu(h_global_packed, d)
    if cfg.option == "A":
        dx = newton_solve_optionA(h, grad, cfg.mu)
    elif cfg.option == "B":
        dx = newton_solve_optionB(h, grad, l)
    else:
        raise ValueError(f"unknown option {cfg.option!r}")
    return x - dx


def fednl_round_kernel(
    cfg: FedNLConfig,
    comp: Compressor,
    alpha: float,
    pay_fn: Callable,
    wire_fn: Callable,
) -> Callable[[jax.Array, FedNLState], tuple[FedNLState, RoundMetrics]]:
    """Algorithm-1 round body with the problem data as an explicit operand.

    ``make_fednl_round`` closes it over a fixed ``z`` (the single-experiment
    path); the sweep batch engine (``repro.core.fednl_batch``) instead maps it
    over a stacked spec axis, substituting a ``lax.switch``-dispatched
    compressor and bit models.  The body is shared so the two paths cannot
    drift: the batched trajectory is the sequential trajectory, op for op.
    """

    def round_fn(z: jax.Array, state: FedNLState) -> tuple[FedNLState, RoundMetrics]:
        n_clients, _, d = z.shape
        key, sub = jax.random.split(state.key)
        client_keys = jax.random.split(sub, n_clients)
        f_i, grad_i, s_i, l_i, h_local_new, sent_i = _map_clients(
            lambda zi, hi, ki: client_round(
                zi, hi, state.x, ki, comp, alpha, cfg.lam, cfg.hessian_impl
            ),
            cfg.hessian_impl == "fused",
            d,
            z,
            state.h_local,
            client_keys,
        )

        grad = jnp.mean(grad_i, axis=0)
        s = jnp.mean(s_i, axis=0)
        l = jnp.mean(l_i)
        f = jnp.mean(f_i)

        x_new = master_step(state.x, state.h_global, grad, l, cfg)
        h_global_new = state.h_global + alpha * s

        sent_total = jnp.sum(sent_i)
        bits_payload = jnp.sum(jax.vmap(pay_fn)(sent_i))
        bits_wire = jnp.sum(jax.vmap(wire_fn)(sent_i))
        metrics = RoundMetrics(
            grad_norm=jnp.linalg.norm(grad),
            f=f,
            l=l,
            sent_elems=sent_total,
            sent_bits=bits_payload if cfg.accounting == "payload" else bits_wire,
            sent_bits_payload=bits_payload,
            sent_bits_wire=bits_wire,
        )
        new_state = FedNLState(
            x=x_new,
            h_local=h_local_new,
            h_global=h_global_new,
            key=key,
            round=state.round + 1,
        )
        return new_state, metrics

    return round_fn


def make_fednl_round(
    z: jax.Array, cfg: FedNLConfig
) -> Callable[[FedNLState], tuple[FedNLState, RoundMetrics]]:
    """Build the jittable single-round transition for problem data `z`."""
    _, _, d = z.shape
    comp = get_compressor(
        cfg.compressor, triu_size(d), cfg.k_for(d),
        fused=cfg.hessian_impl == "fused",
    )
    alpha = comp.alpha if cfg.alpha is None else cfg.alpha
    from repro.api.accounting import payload_bits_fn, wire_bits_fn

    body = fednl_round_kernel(
        cfg, comp, alpha, payload_bits_fn(comp, d), wire_bits_fn(comp, d)
    )
    return lambda state: body(z, state)
