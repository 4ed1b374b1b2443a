"""FedNL matrix compressors on packed upper-triangle vectors.

The six compressors of the paper (Section 8, Appendices C & D):

  - TopK      : keep the k largest-magnitude entries (contractive, delta = k/T)
  - RandK     : keep k entries u.a.r. without replacement (unbiased, omega = T/k - 1)
  - RandSeqK  : *cache-aware* RandK (Appendix C, NEW in paper): one random start
                index, k *contiguous* entries (mod T).  Same expectation and
                variance as RandK, but a single PRG invocation and a contiguous
                memory access pattern.  On TPU this is `jnp.roll` + a prefix
                slice — a sublane-aligned contiguous VMEM read instead of RandK's
                random gather.
  - TopLEK    : adaptive Top-<=K (Appendix D, NEW in paper): sends k' <= k entries,
                randomizing between the two adjacent prefix sizes so that the
                contractive inequality E||C(x)-x||^2 <= (1-delta)||x||^2 holds with
                *tight equality* at delta = k/T.
  - Natural   : probabilistic rounding to powers of two (Horvath et al.);
                unbiased with omega = 1/8.  Implemented with frexp/ldexp-style
                mantissa ops (the paper uses free CPU byte addressing; TPU/JAX
                has no such luxury — assumption change noted in DESIGN.md).
  - Identity  : C(x) = x.

Conventions
-----------
All compressors consume/produce the packed upper-triangle vector u of length
T = d(d+1)/2 (see repro.linalg.triu).  Off-diagonal entries represent two matrix
elements; selection probabilities are uniform over the T packed slots, exactly as
in the paper's Appendix C (which samples from the upper-triangle sequence E).

FedNL theory runs with *contractive* compressors.  Unbiased compressors C with
variance parameter omega are used through their scaled form C/(1+omega), which is
contractive with delta = 1/(1+omega) (standard FedNL reduction).  `get_compressor`
returns the scaled form by default and reports:
    alpha  - recommended Hessian learning rate (1.0 for the scaled/contractive form)
    delta  - contraction parameter of the returned operator

Each `compress(key, u)` returns `(u_hat, sent_elems)` where `u_hat` is the dense
(decompressed) result used by the simulation and `sent_elems` is the number of
scalar payload entries a real network transfer would carry (TopLEK makes this
data-dependent).  `message_bits(comp, sent_elems)` converts to wire bits using
the paper's Section 7 encodings (32-bit indices; PRG-seed reconstruction for
RandK/RandSeqK; sign+exponent-only payload for Natural); the byte-level
encoder/decoder pairs realizing exactly these bit counts on a real transport
live in `repro.comm.wire` (DESIGN.md §3).
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Callable

import jax
import jax.numpy as jnp

from repro.compressors import select

FP_BITS = 64  # paper uses FP64 end-to-end
IDX_BITS = 32  # paper: "fixed-width 32-bit integer format surpassed varying sizes"
NATURAL_BITS = 12  # sign + 11-bit FP64 exponent per entry


# ---------------------------------------------------------------------------
# raw compressors (unscaled)
# ---------------------------------------------------------------------------

def _rank_keys(u: jax.Array) -> jax.Array:
    """f32 magnitude keys for selection — the PINNED contract shared with the
    fused kernel path, re-exported from :mod:`repro.compressors.select`.

    lax.top_k over f64 keys is ~9x slower than f32 on the CPU backend (and
    f32 sort keys are the TPU-native path); ranking in f32 while keeping the
    f64 PAYLOAD preserves the contractive property up to f32 rounding of
    near-ties.  Both the jnp and Pallas paths rank in f32 with a stable
    lowest-index tie-break — ranking widths MUST NOT diverge between paths,
    or near-tie entries silently select different index sets (DESIGN.md §12;
    regression-tested on adversarial near-ties in tests/test_kernels.py).
    """
    return select.rank_keys(u)


def topk(u: jax.Array, k: int, *, fused: bool = False) -> tuple[jax.Array, jax.Array]:
    """Deterministic TopK by magnitude.  Contractive with delta = k/T.

    Routed through the fused selection entry point (`repro.kernels.ops`):
    the Pallas kernel for f32/bf16 payloads on TPU, the canonical jnp
    primitives (bit-identical by the selection contract) for f64 and
    everywhere else.  ``fused=True`` picks the
    sort-free threshold-mask formulation the fused round maps per client
    (see `repro.compressors.select.topk_dense_masked`); outputs are
    bit-identical either way.
    """
    from repro.kernels import ops as kops

    return kops.select_topk(u, k, fused=fused)


def randk(key: jax.Array, u: jax.Array, k: int, *, scaled: bool = True):
    """RandK: k slots u.a.r. without replacement.

    scaled=True  -> C/(1+omega): plain masking (delta = k/T)
    scaled=False -> unbiased form, entries scaled by T/k (omega = T/k - 1)
    """
    t = u.shape[0]
    # uniform k-subset without replacement via top-k of iid uniform keys
    # (jax.random.choice's permutation path is an order of magnitude slower)
    keys = jax.random.uniform(key, (t,), dtype=jnp.float32)
    _, idx = jax.lax.top_k(keys, k)
    u_hat = jnp.zeros_like(u).at[idx].set(u[idx])
    if not scaled:
        u_hat = u_hat * (t / k)
    return u_hat, jnp.asarray(k)


def randseqk(key: jax.Array, u: jax.Array, k: int, *, scaled: bool = True,
             fused: bool = False):
    """Cache-aware RandK (paper Appendix C).

    One PRG draw s ~ U[T]; keep slots {s, s+1, ..., s+k-1 mod T}.  Marginal
    inclusion probability is k/T for every slot, hence the same expectation and
    variance bound as RandK (paper Observations 1 & 2).  The contiguous window is
    realized as roll + prefix slice (or, ``fused=True``, the bit-identical
    gather-free window mask): a sequential memory access on TPU.
    """
    t = u.shape[0]
    if scaled:
        from repro.kernels import ops as kops

        return kops.select_randseqk(key, u, k, fused=fused)
    s = jax.random.randint(key, (), 0, t)
    return select.randseqk_dense(u, k, s) * (t / k), jnp.asarray(k)


def toplek(key: jax.Array, u: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Adaptive Top-Less-Equal-K (paper Algorithm 4).

    Target contraction delta = k/T (TopK's worst case).  Let alpha_m be the
    energy fraction captured by the top-m entries.  Find the prefix size m* with
    alpha_{m*-1} < delta <= alpha_{m*}; keep m*-1 entries with probability
    p = (alpha_hi - delta) / (alpha_hi - alpha_lo) and m* entries otherwise, so
    that E||C(u)-u||^2 = (1-delta)||u||^2 exactly.

    The body lives in :func:`repro.compressors.select.toplek_from_uniform`
    with the Bernoulli draw hoisted to ``uniform(key)`` (bit-identical to
    ``jax.random.bernoulli`` — verified in tests), shared verbatim by the
    Pallas kernel; routing goes through `repro.kernels.ops.select_toplek`.
    """
    from repro.kernels import ops as kops

    return kops.select_toplek(key, u, k)


def natural(key: jax.Array, u: jax.Array, *, scaled: bool = True):
    """Natural compression: probabilistic rounding to the nearest powers of two.

    |u| = 2^(e-1) * t with t in [1, 2); round down to 2^(e-1) w.p. (2 - t),
    up to 2^e w.p. (t - 1).  Unbiased with omega = 1/8.
    """
    mant, exp = jnp.frexp(jnp.abs(u))  # |u| = mant * 2^exp, mant in [0.5, 1)
    t2 = 2.0 * mant  # in [1, 2)
    p_up = t2 - 1.0
    up = jax.random.bernoulli(key, jnp.clip(p_up, 0.0, 1.0), shape=u.shape)
    pow2 = jnp.ldexp(jnp.ones_like(u), exp - 1 + up.astype(exp.dtype))
    out = jnp.where(u == 0, 0.0, jnp.sign(u) * pow2)
    if scaled:
        out = out * (8.0 / 9.0)
    return out, jnp.asarray(u.shape[0])


def identity(u: jax.Array) -> tuple[jax.Array, jax.Array]:
    return u, jnp.asarray(u.shape[0])


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# sparse (index, value) forms — used by the compressed-collective aggregation
# (repro.distributed): instead of psum-ing dense length-T vectors, devices
# all_gather only the k (idx, val) pairs per client and scatter-add on the
# master.  Padding entries carry val=0 (scatter-add of zero is a no-op).
# ---------------------------------------------------------------------------

def topk_sparse(u: jax.Array, k: int):
    idx = select.topk_indices(u, k)
    return idx.astype(jnp.int32), u[idx], jnp.asarray(k)


def randk_sparse(key: jax.Array, u: jax.Array, k: int):
    t = u.shape[0]
    keys = jax.random.uniform(key, (t,), dtype=jnp.float32)
    _, idx = jax.lax.top_k(keys, k)
    return idx.astype(jnp.int32), u[idx], jnp.asarray(k)


def randseqk_sparse(key: jax.Array, u: jax.Array, k: int):
    t = u.shape[0]
    s = jax.random.randint(key, (), 0, t)
    idx = ((s + jnp.arange(k)) % t).astype(jnp.int32)
    rolled = jnp.roll(u, -s)  # contiguous window read
    return idx, rolled[:k], jnp.asarray(k)


def toplek_sparse(key: jax.Array, u: jax.Array, k: int):
    """TopLEK with a fixed-size k buffer; entries past `kept` are zero-padded."""
    u_hat, kept = toplek(key, u, k)
    _, idx = jax.lax.top_k(_rank_keys(u_hat), k)
    pos_mask = jnp.arange(k) < kept
    return (
        jnp.where(pos_mask, idx, 0).astype(jnp.int32),
        jnp.where(pos_mask, u_hat[idx], 0.0),
        kept,
    )


def scatter_add_sparse(idx: jax.Array, vals: jax.Array, t: int) -> jax.Array:
    """Decompress-and-accumulate a batch of sparse messages into one (T,) vector."""
    return jnp.zeros((t,), dtype=vals.dtype).at[idx.ravel()].add(vals.ravel())


@dataclasses.dataclass(frozen=True)
class Compressor:
    """A configured compressor: `compress(key, u) -> (u_hat, sent_elems)`.

    `compress_sparse(key, u) -> (idx, vals, sent_elems)` exists for
    sparsification compressors (TopK/RandK/RandSeqK/TopLEK) and is None for
    dense ones (Natural/Identity).
    """

    name: str
    compress: Callable[[jax.Array, jax.Array], tuple[jax.Array, jax.Array]]
    alpha: float  # recommended Hessian learning rate for FedNL
    delta: float  # contraction parameter of the returned (scaled) operator
    bits_per_elem: float  # payload bits per sent element
    header_bits: float  # per-message constant (seed / count)
    compress_sparse: Callable | None = None
    k: int = 0


@dataclasses.dataclass(frozen=True)
class CompressorSpec:
    name: str
    make: Callable[..., Compressor]  # (T, k, fused=False) -> Compressor


def _make_topk(t: int, k: int, fused: bool = False) -> Compressor:
    return Compressor("topk", lambda key, u: topk(u, k, fused=fused), alpha=1.0,
                      delta=k / t, bits_per_elem=FP_BITS + IDX_BITS, header_bits=0,
                      compress_sparse=lambda key, u: topk_sparse(u, k), k=k)


def _make_randk(t: int, k: int, fused: bool = False) -> Compressor:
    del fused  # RandK's uniform-subset gather has no masked formulation
    return Compressor("randk", lambda key, u: randk(key, u, k), alpha=1.0,
                      delta=k / t, bits_per_elem=FP_BITS, header_bits=FP_BITS,
                      compress_sparse=lambda key, u: randk_sparse(key, u, k), k=k)


def _make_randseqk(t: int, k: int, fused: bool = False) -> Compressor:
    return Compressor("randseqk", lambda key, u: randseqk(key, u, k, fused=fused),
                      alpha=1.0,
                      delta=k / t, bits_per_elem=FP_BITS, header_bits=IDX_BITS,
                      compress_sparse=lambda key, u: randseqk_sparse(key, u, k), k=k)


def _make_toplek(t: int, k: int, fused: bool = False) -> Compressor:
    del fused  # the adaptive prefix is order-dependent: one sorted body
    return Compressor("toplek", lambda key, u: toplek(key, u, k), alpha=1.0,
                      delta=k / t, bits_per_elem=FP_BITS + IDX_BITS,
                      header_bits=IDX_BITS,
                      compress_sparse=lambda key, u: toplek_sparse(key, u, k), k=k)


def _make_natural(t: int, k: int, fused: bool = False) -> Compressor:
    del k, fused
    return Compressor("natural", lambda key, u: natural(key, u), alpha=1.0,
                      delta=8.0 / 9.0, bits_per_elem=NATURAL_BITS, header_bits=0)


def _make_identity(t: int, k: int, fused: bool = False) -> Compressor:
    del k, fused
    return Compressor("identity", lambda key, u: identity(u), alpha=1.0,
                      delta=1.0, bits_per_elem=FP_BITS, header_bits=0)


COMPRESSORS: dict[str, CompressorSpec] = {
    "topk": CompressorSpec("topk", _make_topk),
    "randk": CompressorSpec("randk", _make_randk),
    "randseqk": CompressorSpec("randseqk", _make_randseqk),
    "toplek": CompressorSpec("toplek", _make_toplek),
    "natural": CompressorSpec("natural", _make_natural),
    "identity": CompressorSpec("identity", _make_identity),
}


def get_compressor(name: str, t: int, k: int = 0, *, fused: bool = False) -> Compressor:
    """Build a compressor for packed-triu length `t` with sparsity budget `k`.

    ``fused=True`` binds the kernel-layer selection formulations (threshold
    mask for TopK, window mask for RandSeqK) that the fused round maps per
    client — bit-identical outputs to the default sorted/rolled forms
    (DESIGN.md §12), different performance profile (faster under lax.map,
    slower under vmap on CPU).  Registered factories keep the legacy
    ``(t, k)`` contract: ``fused`` is only forwarded to factories whose
    signature accepts a third argument, so user compressors (which have no
    masked formulation to select) are called exactly as before.
    """
    if name not in COMPRESSORS:
        raise KeyError(f"unknown compressor {name!r}; have {sorted(COMPRESSORS)}")
    if name in ("topk", "randk", "randseqk", "toplek") and not (0 < k <= t):
        raise ValueError(f"{name} needs 0 < k <= T, got k={k}, T={t}")
    make = COMPRESSORS[name].make
    try:
        takes_fused = len(inspect.signature(make).parameters) >= 3
    except (TypeError, ValueError):  # builtins / C callables: legacy form
        takes_fused = False
    return make(t, k, fused) if takes_fused else make(t, k)


def message_bits(c: Compressor, sent_elems: jax.Array) -> jax.Array:
    """Wire bits for one compressed Hessian message (Section 7 encodings)."""
    return sent_elems * c.bits_per_elem + c.header_bits
