"""Plain FedNL (Algorithm 1 of arXiv:2106.02969, Option B) in NumPy.

The benchmark's reference for a FedNL solve of L2-regularised logistic
regression.  It imports nothing of the program under test and takes nothing
it made: only the problem data the benchmark generated and the
configuration's stated semantics.

* Oracles (per client i, rows of z_i carry the label): margins m = z_i x,
  grad_i = -z_i^T (1 - s(m)) / n_i + lam x,
  hess_i = z_i^T diag(s(m)(1 - s(m)) / n_i) z_i + lam I.
* Hessian-shaped state is the packed upper triangle, row-major
  (T = d(d+1)/2 entries); the Frobenius norm counts off-diagonals twice.
* Selection over a client's packed difference u:
  TopK keeps the k entries with the largest float32 magnitudes, ties to the
  lowest packed index.  TopLEK (the paper's adaptive compressor) ranks the
  same way, then keeps m* - 1 or m* of the ranked entries, where m* is the
  smallest prefix whose energy share reaches k/T, taking m* - 1 when a
  uniform draw falls below (a_hi - k/T) / (a_hi - a_lo).
* Master, Option B: x <- x - (H + l I)^{-1} grad with the pre-update
  estimate H, then H <- H + mean_i S_i; client shifts H_i <- H_i + S_i.
* A round records the gradient norm at the iterate it starts from; the run
  stops after the first round whose norm is below ``tol``, or at the cap.

The one random quantity, TopLEK's uniform draw per client and round, comes
from the configuration's stated stream: ``jax.random.PRNGKey(seed)`` split
once per round into (carry, sub), ``sub`` split into one key per client, and
one float64 uniform from each.  Those draws are integer operations and come
out the same on any device; the reference makes them on the host CPU.

``dtype`` selects the working precision: float64 is the reference, float32
is the control that the comparison has to refuse.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

IDX_BITS = 32


@dataclasses.dataclass
class RefSolve:
    x: np.ndarray
    grad_norms: np.ndarray
    sent_elems: np.ndarray
    sent_bits: np.ndarray

    @property
    def rounds(self) -> int:
        return len(self.grad_norms)


@functools.lru_cache(maxsize=8)
def _triu(d: int):
    rows, cols = np.triu_indices(d)
    weights = np.where(rows == cols, 1.0, 2.0)
    return rows, cols, weights


def _sigmoid(m):
    return 0.5 * (1.0 + np.tanh(0.5 * m))


def oracles(z: np.ndarray, zt: np.ndarray, x: np.ndarray, lam: float):
    """Per-client gradients (n, d) and packed Hessians (n, T); ``zt`` is
    z with its last two axes swapped, stored contiguously."""
    n, n_i, d = z.shape
    rows, cols, _ = _triu(d)
    s = _sigmoid(z @ x)  # (n, n_i)
    grad = -np.einsum("cjd,cj->cd", z, 1.0 - s) / n_i + lam * x
    w = s * (1.0 - s) / n_i
    hess = np.matmul(zt, w[..., None] * z)
    packed = hess[:, rows, cols]
    packed[:, rows == cols] += lam
    return grad, packed


def _topk_mask(keys: np.ndarray, k: int) -> np.ndarray:
    """Keep-mask of the k largest keys per row, ties to the lowest index."""
    t = keys.shape[1]
    thr = np.partition(keys, t - k, axis=1)[:, t - k][:, None]
    gt = keys > thr
    eq = keys == thr
    room = k - gt.sum(axis=1, keepdims=True)
    if np.all(eq.sum(axis=1, keepdims=True) <= room):  # no tie at the cut
        return gt | eq
    return gt | (eq & (np.cumsum(eq, axis=1) <= room))


def select_topk(u: np.ndarray, k: int):
    keep = _topk_mask(np.abs(u).astype(np.float32), k)
    kept = np.full(u.shape[0], k, dtype=np.int64)
    return np.where(keep, u, 0.0).astype(u.dtype), kept


def select_toplek(u: np.ndarray, k: int, unif: np.ndarray):
    n, t = u.shape
    keys = np.abs(u).astype(np.float32)
    keep = _topk_mask(keys, k)
    idx = np.nonzero(keep)[1].reshape(n, k)  # ascending index per row
    r = np.arange(n)[:, None]
    order = np.argsort(-keys[r, idx], axis=1, kind="stable")
    idx = idx[r, order]  # descending key, lowest index first among ties
    vals = u[r, idx]
    target = k / t
    total = np.sum(u * u, axis=1)
    safe = np.where(total > 0, total, 1.0)
    alphas = np.cumsum(vals * vals, axis=1) / safe[:, None]
    m_star = np.minimum(np.sum(alphas < target, axis=1) + 1, k)
    a_hi = alphas[np.arange(n), m_star - 1]
    a_lo = np.where(m_star > 1, alphas[np.arange(n), np.maximum(m_star - 2, 0)], 0.0)
    gap = a_hi - a_lo
    p = np.clip(np.where(gap > 0, (a_hi - target) / np.where(gap > 0, gap, 1.0), 0.0), 0.0, 1.0)
    kept = np.where(unif < p, m_star - 1, m_star)
    kept = np.where(total > 0, kept, 0)
    out = np.zeros_like(u)
    mask = np.arange(k)[None, :] < kept[:, None]
    out[np.broadcast_to(r, idx.shape)[mask], idx[mask]] = vals[mask]
    return out, kept.astype(np.int64)


BITS = {
    # payload bits per sent element, per-message header bits (Section 7)
    "topk": (64 + IDX_BITS, 0),
    "toplek": (64 + IDX_BITS, IDX_BITS),
}


def toplek_uniforms(seed: int, rounds: int, n_clients: int) -> np.ndarray:
    """(rounds, n_clients) float64 uniforms of the stated PRNG stream."""
    import jax

    if not jax.config.jax_enable_x64:
        raise RuntimeError("float64 uniforms need jax_enable_x64")
    cpu = jax.devices("cpu")[0]

    @jax.jit
    def draw(key):
        def one(key, _):
            key, sub = jax.random.split(key)
            keys = jax.random.split(sub, n_clients)
            u = jax.vmap(lambda kk: jax.random.uniform(kk, (), dtype=np.float64))(keys)
            return key, u

        return jax.lax.scan(one, key, None, length=rounds)[1]

    with jax.default_device(cpu):
        return np.asarray(draw(jax.random.PRNGKey(seed)))


def fednl_solve(
    z: np.ndarray,
    *,
    lam: float,
    compressor: str,
    k: int,
    tol: float,
    round_cap: int,
    seed: int,
    dtype=np.float64,
) -> RefSolve:
    """Solve from x = 0 with exact initial shifts, as stated in the module doc."""
    z = np.asarray(z, dtype=dtype)
    zt = np.ascontiguousarray(np.swapaxes(z, 1, 2))
    n, _, d = z.shape
    rows, cols, weights = _triu(d)
    weights = weights.astype(dtype)
    unif = toplek_uniforms(seed, round_cap, n) if compressor == "toplek" else None
    per_elem, header = BITS[compressor]

    x = np.zeros(d, dtype=dtype)
    _, h_local = oracles(z, zt, x, lam)
    h_global = h_local.mean(axis=0)
    gnorms, elems, bits = [], [], []
    for r in range(round_cap):
        grad_i, hess_i = oracles(z, zt, x, lam)
        delta = hess_i - h_local
        if compressor == "topk":
            s_i, kept = select_topk(delta, k)
        elif compressor == "toplek":
            s_i, kept = select_toplek(delta, k, unif[r])
        else:
            raise ValueError(f"reference has no compressor {compressor!r}")
        l = np.mean(np.sqrt(np.sum(weights * delta * delta, axis=1)))
        grad = grad_i.mean(axis=0)
        h = np.zeros((d, d), dtype=dtype)
        h[rows, cols] = h_global
        h = h + h.T - np.diag(np.diag(h))
        x = x - np.linalg.solve(h + l * np.eye(d, dtype=dtype), grad)
        h_global = h_global + s_i.mean(axis=0)
        h_local = h_local + s_i
        gnorm = float(np.linalg.norm(grad))
        gnorms.append(gnorm)
        elems.append(int(kept.sum()))
        bits.append(int((kept * per_elem + header).sum()))
        if gnorm < tol:
            break
    return RefSolve(
        x=x.astype(np.float64),
        grad_norms=np.asarray(gnorms),
        sent_elems=np.asarray(elems),
        sent_bits=np.asarray(bits),
    )
