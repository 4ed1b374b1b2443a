"""Pinned selection primitives shared by the jnp compressors and the Pallas
selection kernel (DESIGN.md §12).

The selection contract
----------------------
Every sparsifying compressor path — pure-jnp (`repro.compressors.core`),
fused (`repro.kernels.compressor_select`), sparse wire form — MUST select the
same index set, defined as:

  * rank keys are ``f32(|u|)`` (:func:`rank_keys`) — NOT the f64 magnitudes.
    Ranking in f64 is ~9x slower through ``lax.top_k`` on CPU and is not the
    TPU-native sort width; more importantly, *mixing* widths across paths is
    a parity bug: f64 entries that are distinct but collide when rounded to
    f32 would be ordered differently by an f64-ranking kernel, silently
    selecting a different index set than the f32-ranking jnp path.  Both
    paths therefore rank in f32, always.
  * ties (equal f32 keys — including the near-tie collisions above) break
    toward the LOWEST packed-triu index.  ``jax.lax.top_k`` guarantees this
    stable order; :func:`threshold_keep_mask` reproduces the identical set
    without a sort (the Pallas-kernel formulation).  The regression tests in
    tests/test_kernels.py pin set equality on adversarial near-tie inputs.

The TopLEK randomization consumes its PRNG key as a single uniform draw in
the payload dtype: ``jax.random.bernoulli(key, p)`` lowers to exactly
``uniform(key, (), p.dtype) < p``, so :func:`toplek_from_uniform` takes the
uniform as an operand and fused/unfused paths replay the same PRNG stream
bit-for-bit (verified in tests).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

RANK_DTYPE = jnp.float32


def rank_keys(u: jax.Array) -> jax.Array:
    """The pinned selection keys: f32 magnitudes (see module docstring)."""
    return jnp.abs(u).astype(RANK_DTYPE)


def topk_indices(u: jax.Array, k: int) -> jax.Array:
    """Indices of the k largest-magnitude entries, lowest-index tie-break,
    in descending key order (the canonical ranking both paths pin to)."""
    _, idx = jax.lax.top_k(rank_keys(u), k)
    return idx


def topk_dense(u: jax.Array, k: int) -> jax.Array:
    """Dense TopK sparsification C(u): zeros everywhere but the selected set."""
    idx = topk_indices(u, k)
    return jnp.zeros_like(u).at[idx].set(u[idx])


def topk_dense_masked(u: jax.Array, k: int) -> jax.Array:
    """Dense TopK via :func:`threshold_keep_mask` — the sort-free formulation
    the Pallas selection kernel runs, bit-identical to :func:`topk_dense`
    (same selected set by the pinned contract; values are pure copies).

    On CPU the two formulations trade places with the mapping strategy: the
    mask (31 compare/sum passes, no data movement) beats the batched sort
    inside a per-client ``lax.map`` (~1.6x on w8a's T=45451) but loses under
    ``vmap`` — the fused round picks it together with ``lax.map``
    (repro.core.fednl.FUSED_VMAP_MAX_D)."""
    keep = threshold_keep_mask(rank_keys(u), k)
    return jnp.where(keep, u, jnp.zeros_like(u))


def threshold_keep_mask(keys: jax.Array, k: int) -> jax.Array:
    """Boolean keep-mask selecting the same set as ``top_k(keys, k)`` without
    a sort — the formulation the Pallas selection kernel runs.

    ``keys`` must be the non-negative f32 :func:`rank_keys`; positions are
    the flat index order.  See :func:`keep_mask_from_bits`."""
    bits = jax.lax.bitcast_convert_type(keys, jnp.int32)
    pos = jnp.arange(keys.shape[0], dtype=jnp.int32)
    return keep_mask_from_bits(bits, pos, keys.shape[0], k, jax.lax.fori_loop)


def _static_loop(lo: int, hi: int, body, init):
    """``fori_loop`` unrolled in Python (Pallas kernel bodies)."""
    val = init
    for i in range(lo, hi):
        val = body(i, val)
    return val


def keep_mask_from_bits(bits, pos, t: int, k: int, loop=_static_loop):
    """The sort-free TopK keep-mask over int32 key bit patterns.

    ``bits`` holds the int32 bit patterns of the f32 rank keys (padding
    slots set to -1, below every key), ``pos`` each slot's flat index, ``t``
    the number of real slots.  Non-negative f32 keys order like their bit
    patterns (IEEE-754), so the k-th largest key is found by a 31-step
    binary search on the bit pattern.  Entries strictly above that
    threshold are kept; of the entries EQUAL to it, the first ``k - n_gt``
    in index order are kept — exactly ``lax.top_k``'s stable lowest-index
    tie-break.  That prefix is found by a second binary search, on the
    largest index bound ``p`` with ``#{eq & pos < p} <= k - n_gt``.  Both
    searches are compares and full-array sums only: no sort, no cumsum, no
    gather, no 64-bit integers — so the same function runs as jnp
    (``loop=fori_loop``) and inside a Mosaic kernel (static loop).  Counts
    are f32 sums of 0/1 (Mosaic refuses integer reductions under x64); every
    partial sum is an integer below 2**24, so they are exact in any order.
    Set equality, ties included, is pinned by tests/test_kernels.py.
    """
    if t >= 1 << 24:
        raise ValueError(f"selection over T={t} >= 2**24 slots: f32 counts "
                         "would be inexact")
    i32 = jnp.int32

    def count(m):
        return jnp.sum(m.astype(jnp.float32))

    def thr_step(i, thr):
        cand = thr | jnp.left_shift(i32(1), jnp.asarray(30 - i, i32))
        return jnp.where(count(bits >= cand) >= k, cand, thr)

    thr = loop(0, 31, thr_step, i32(0))
    gt = bits > thr
    eq = bits == thr
    room = k - count(gt)
    nb = int(t).bit_length()

    def pos_step(i, p):
        cand = p | jnp.left_shift(i32(1), jnp.asarray(nb - 1 - i, i32))
        ok = (cand <= t) & (count(eq & (pos < cand)) <= room)
        return jnp.where(ok, cand, p)

    p = loop(0, nb, pos_step, i32(0))
    return gt | (eq & (pos < p))


def randseqk_window_mask(t: int, k: int, s: jax.Array) -> jax.Array:
    """Membership mask of the circular window {s, ..., s+k-1 mod T} — the
    gather-free form of RandSeqK's contiguous slice."""
    return window_mask(jnp.arange(t, dtype=jnp.int32), t, k, s)


def window_mask(pos, t: int, k: int, s):
    """``(pos - s) mod t < k`` for ``0 <= s < t``, ``k <= t`` as compares
    only (no modulo, int32 throughout) — shared with the Pallas kernel."""
    s = jnp.asarray(s, jnp.int32)
    return ((pos >= s) & (pos < s + k)) | (pos < s + (k - t))


def randseqk_dense(u: jax.Array, k: int, s: jax.Array) -> jax.Array:
    """Dense RandSeqK given the start draw ``s``: roll + prefix slice + roll
    back (the paper's contiguous single-PRG-draw window, Appendix C).  Values
    are pure copies, so this is bit-identical to masking with
    :func:`randseqk_window_mask`."""
    rolled = jnp.roll(u, -s)
    window = jnp.zeros_like(u).at[:k].set(rolled[:k])
    return jnp.roll(window, s)


def randseqk_dense_masked(u: jax.Array, k: int, s: jax.Array) -> jax.Array:
    """Dense RandSeqK via :func:`randseqk_window_mask` — the gather-free
    formulation the Pallas kernel runs; bit-identical to
    :func:`randseqk_dense` (values are pure copies)."""
    return jnp.where(
        randseqk_window_mask(u.shape[0], k, s), u, jnp.zeros_like(u)
    )


def toplek_from_uniform(
    u: jax.Array, k: int, unif: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """TopLEK (paper Algorithm 4) with the Bernoulli draw supplied as a
    uniform ``unif`` in u's dtype: ``unif < p`` replays
    ``jax.random.bernoulli(key, p)`` bit-for-bit (module docstring), letting
    the fused kernel consume the same PRNG stream as the jnp path.

    Target contraction delta = k/T.  Let alpha_m be the energy fraction of
    the top-m entries; find m* with alpha_{m*-1} < delta <= alpha_{m*}, keep
    m*-1 entries w.p. p = (alpha_hi - delta)/(alpha_hi - alpha_lo) else m*,
    so E||C(u)-u||^2 = (1-delta)||u||^2 holds with equality.
    """
    t = u.shape[0]
    delta = k / t
    # only the top-k prefix can ever be kept (alpha_k >= k/T always), so a
    # partial top-k selection suffices — no full T-sort (paper §5.11 spirit).
    idx = topk_indices(u, k)
    vals = u[idx]  # descending by rank key, lowest-index ties first
    s2 = vals.astype(jnp.float64) ** 2 if u.dtype == jnp.float64 else vals**2
    # a parallel-prefix scan, not jnp.cumsum: cumsum lowers to an O(k^2)
    # reduce_window that the TPU compiler takes minutes over in f64 (w8a,
    # k = 2408: ~200 s per round program, against ~2 s for this scan)
    csum = jax.lax.associative_scan(jnp.add, s2)
    total = jnp.sum(u * u)
    safe_total = jnp.where(total > 0, total, 1.0)
    alphas = (csum / safe_total).astype(u.dtype)  # alphas[m-1] = alpha_m
    # smallest m (1-indexed) with alpha_m >= delta
    m_star = jnp.searchsorted(alphas, delta, side="left") + 1
    m_star = jnp.minimum(m_star, k)
    alpha_hi = alphas[m_star - 1]
    alpha_lo = jnp.where(m_star > 1, alphas[jnp.maximum(m_star - 2, 0)], 0.0)
    gap = alpha_hi - alpha_lo
    p = jnp.where(gap > 0, (alpha_hi - delta) / jnp.where(gap > 0, gap, 1.0), 0.0)
    p = jnp.clip(p, 0.0, 1.0)
    take_lo = unif < p
    kept = jnp.where(take_lo, m_star - 1, m_star)
    kept = jnp.where(total > 0, kept, 0)
    keep_mask = jnp.arange(k) < kept
    u_hat = jnp.zeros_like(u).at[idx].set(jnp.where(keep_mask, vals, 0.0))
    return u_hat, kept


def toplek_uniform(key: jax.Array, dtype) -> jax.Array:
    """The single TopLEK PRNG draw, in the dtype ``bernoulli`` would use (the
    probability's dtype == the payload dtype here)."""
    return jax.random.uniform(key, (), dtype=dtype)
