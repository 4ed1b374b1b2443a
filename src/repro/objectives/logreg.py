"""L2-regularized logistic regression oracles (paper Eq. 2-5).

Data layout follows the paper's §5.13 optimization: labels b_ij are absorbed
into the design matrix, i.e. each client holds Z in R^{n_i x d} with rows
z_j = b_ij * a_ij.  Then with margins m = Z x:

    f_i(x)    = (1/n_i) sum_j log(1 + exp(-m_j)) + (lambda/2) ||x||^2
    grad f_i  = -(1/n_i) Z^T (1 - sigma(m)) + lambda x
    hess f_i  = (1/n_i) Z^T diag(sigma(m) (1 - sigma(m))) Z + lambda I

§5.7 ("Reuse Computation from Oracles", x1.50): the margins and sigmoid values
are computed ONCE and shared by all three oracles — `logreg_oracles` is the
fused oracle; the individual functions exist for testing / autodiff parity.

Numerical care: log(1+exp(-m)) is evaluated as softplus(-m) via
`jax.nn.softplus` (stable for large |m|), and sigma*(1-sigma) is formed from
sigma directly (paper §5.7: g(-z)*g(z) reuse).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class LogRegProblem:
    """A federated logistic-regression instance.

    z: (n_clients, n_i, d)  label-absorbed design matrices (rows b_ij * a_ij)
    lam: L2 regularization coefficient
    """

    z: jax.Array
    lam: float

    @property
    def n_clients(self) -> int:
        return self.z.shape[0]

    @property
    def n_i(self) -> int:
        return self.z.shape[1]

    @property
    def dim(self) -> int:
        return self.z.shape[2]


def logreg_margin_stats(z: jax.Array, x: jax.Array):
    """margins m = Z x and sigmoid values (the §5.7 shared quantities)."""
    m = z @ x
    sigma = jax.nn.sigmoid(m)
    return m, sigma


def logreg_f(z: jax.Array, x: jax.Array, lam: float) -> jax.Array:
    m = z @ x
    return jnp.mean(jax.nn.softplus(-m)) + 0.5 * lam * jnp.sum(x * x)


def logreg_grad(z: jax.Array, x: jax.Array, lam: float) -> jax.Array:
    _, sigma = logreg_margin_stats(z, x)
    n_i = z.shape[0]
    return -(z.T @ (1.0 - sigma)) / n_i + lam * x


def logreg_hess(z: jax.Array, x: jax.Array, lam: float) -> jax.Array:
    _, sigma = logreg_margin_stats(z, x)
    n_i, d = z.shape
    h = sigma * (1.0 - sigma) / n_i  # (n_i,)
    return z.T @ (h[:, None] * z) + lam * jnp.eye(d, dtype=z.dtype)


HESSIAN_IMPLS = ("fused", "jnp", "pallas")


def logreg_oracles(
    z: jax.Array,
    x: jax.Array,
    lam: float,
    *,
    use_kernel: bool = False,
    hessian: str | None = None,
):
    """Fused (f, grad, hess) sharing one margin/sigmoid computation (§5.7).

    hessian: which SYRK realizes Z^T diag(h) Z (DESIGN.md §12):
      "fused"   (default) repro.kernels.ops.hessian_fused — the
                tile-equivalent XLA program for f64 on every backend (the
                Pallas SYRK only for f32/bf16 on TPU).
                For d <= 128 (one tile) the XLA program is literally the
                "jnp" expression, so the default is bit-identical to the
                historical path there; for larger d the blocked accumulation
                drifts by O(1) ulp (documented).
      "jnp"     the single-dot_general expression — the parity reference
                every fused variant is pinned against.
      "pallas"  force the Pallas wrapper (interpret mode off-TPU) — the
                kernel-validation path, not a CPU hot path.
    use_kernel=True is the deprecated spelling of hessian="pallas".
    """
    if hessian is None:
        hessian = "pallas" if use_kernel else "fused"
    if hessian not in HESSIAN_IMPLS:
        raise ValueError(
            f"unknown hessian {hessian!r}; use {' | '.join(HESSIAN_IMPLS)}"
        )
    n_i, d = z.shape
    m, sigma = logreg_margin_stats(z, x)
    f = jnp.mean(jax.nn.softplus(-m)) + 0.5 * lam * jnp.sum(x * x)
    grad = -(z.T @ (1.0 - sigma)) / n_i + lam * x
    h = sigma * (1.0 - sigma) / n_i
    reg = lam * jnp.eye(d, dtype=z.dtype)
    if hessian == "fused":
        from repro.kernels import ops as kops

        hess = kops.hessian_fused(z, h) + reg
    elif hessian == "pallas":
        from repro.kernels import ops as kops

        hess = kops.hessian_syrk(z, h) + reg
    else:
        hess = z.T @ (h[:, None] * z) + reg
    return f, grad, hess


@functools.lru_cache(maxsize=64)
def _packed_eye(d: int) -> np.ndarray:
    """pack_triu(eye(d)) as a host numpy constant (embedded at trace time)."""
    from repro.linalg import triu_indices

    rows, cols = triu_indices(d)
    return np.where(rows == cols, 1.0, 0.0)


def logreg_oracles_packed(
    z: jax.Array,
    x: jax.Array,
    lam: float,
    *,
    hessian: str = "fused",
):
    """Fused client oracle: (f, grad, pack_triu(hess)) in one pass.

    The FedNL round consumes the Hessian exclusively in packed
    upper-triangle form (T = d(d+1)/2); for ``hessian="fused"`` the packed
    vector is gathered straight off the SYRK block strips
    (:func:`repro.kernels.ops.hessian_syrk_packed`) — the mirrored (d, d)
    matrix is never materialized, and the regularization is added packed
    (``lam * pack_triu(eye)``), replaying the historical
    ``pack_triu(hess + lam*eye)`` per-element op order bit-for-bit.  The
    "jnp" / "pallas" reference paths build the full matrix and pack it,
    exactly as :func:`logreg_oracles` callers always have.
    """
    if hessian not in HESSIAN_IMPLS:
        raise ValueError(
            f"unknown hessian {hessian!r}; use {' | '.join(HESSIAN_IMPLS)}"
        )
    n_i, d = z.shape
    m, sigma = logreg_margin_stats(z, x)
    f = jnp.mean(jax.nn.softplus(-m)) + 0.5 * lam * jnp.sum(x * x)
    grad = -(z.T @ (1.0 - sigma)) / n_i + lam * x
    h = sigma * (1.0 - sigma) / n_i
    if hessian == "fused":
        from repro.kernels import ops as kops

        hp = kops.hessian_syrk_packed(z, h)
        return f, grad, hp + lam * jnp.asarray(_packed_eye(d), dtype=z.dtype)
    from repro.linalg import pack_triu

    reg = lam * jnp.eye(d, dtype=z.dtype)
    if hessian == "pallas":
        from repro.kernels import ops as kops

        hess = kops.hessian_syrk(z, h) + reg
    else:
        hess = z.T @ (h[:, None] * z) + reg
    return f, grad, pack_triu(hess)
