"""Operations and bytes that one FedNL round needs, from its shapes alone.

These count the work of the algorithm (arXiv:2106.02969, Algorithm 1, with
the paper's packed upper-triangle Hessians), not of any implementation, so
a change to the program cannot change them.  n clients, n_i samples per
client, d features with the intercept, T = d(d+1)/2 packed entries, k the
selection budget.  A multiply-add counts as two operations; float64 words
are 8 bytes.  Selection performs comparisons, not floating-point
arithmetic, so it contributes bytes and no FLOPs.
"""

from __future__ import annotations

import dataclasses

WORD = 8


def triu_size(d: int) -> int:
    return d * (d + 1) // 2


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float


def oracle_margins_grad(n: int, n_i: int, d: int) -> Work:
    """m = z_i x and z_i^T (1 - s(m)) for every client."""
    return Work(flops=2 * (2 * n * n_i * d), bytes=WORD * (n * n_i * d + 2 * n * d))


def oracle_syrk(n: int, n_i: int, d: int) -> Work:
    """Packed z_i^T diag(w) z_i for every client: n_i multiply-adds for each
    of the T upper-triangle entries, plus the scaling of z_i by w."""
    t = triu_size(d)
    return Work(
        flops=n * (2 * n_i * t + n_i * d),
        bytes=WORD * n * (n_i * d + n_i + t),
    )


def selection(n: int, d: int, k: int) -> Work:
    """Selection over each client's T packed differences: read u, write the
    kept entries (dense), k indices of 4 bytes on the wire."""
    t = triu_size(d)
    return Work(flops=0.0, bytes=n * (2 * WORD * t + 4 * k))


def master_solve(d: int) -> Work:
    """Cholesky of the d x d shifted estimate and two triangular solves."""
    return Work(flops=d**3 / 3 + 2 * 2 * d**2, bytes=WORD * (d * d + 2 * d))


def differences_and_aggregation(n: int, d: int) -> Work:
    """Per client: the difference D_i - H_i (T), its weighted Frobenius norm
    (3T), the shift update H_i += S_i (T) and the sums into the means of S,
    the gradients and the errors (T + d + 1); then H += S (T)."""
    t = triu_size(d)
    return Work(flops=n * (6 * t + d + 1) + t, bytes=WORD * (3 * n * t + 2 * t))


def round_work(n: int, n_i: int, d: int, k: int) -> Work:
    parts = (
        oracle_margins_grad(n, n_i, d),
        oracle_syrk(n, n_i, d),
        selection(n, d, k),
        master_solve(d),
        differences_and_aggregation(n, d),
    )
    return Work(
        flops=sum(p.flops for p in parts), bytes=sum(p.bytes for p in parts)
    )
