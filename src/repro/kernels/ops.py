"""Jit'd public wrappers around the Pallas kernels, and the one place that
routes the round hot path between them and their XLA formulations.

The route (DESIGN.md §12) is keyed on the operand dtype and the requested
implementation — never on ``jax.default_backend()`` alone:

* ``hessian="fused"`` / the fused selection: **f64 operands** (the default
  FedNL round) run the XLA strip SYRK (:func:`hessian_syrk_xla`) and the
  masked jnp selection (`repro.compressors.select`) on every backend, so
  the CPU tests cover the exact formulation the chip runs.  f32/bf16
  operands run the Mosaic-compiled Pallas kernels where the computation
  targets a TPU, the same XLA/jnp formulations elsewhere.
* ``hessian="pallas"`` (:func:`hessian_syrk`) always runs the Pallas
  kernel: compiled on a TPU — where an f64 operand raises a TypeError
  naming the dtype — and in interpret mode off-TPU or where a caller passes
  ``interpret=True``.  Interpret mode is never a TPU default.

The target platform is that of a concrete operand's device; under a trace
(no device yet) it is the default device (``jax.default_device``), else the
default backend.  It is resolved eagerly, outside jit, and passed through
static arguments, so interpret and Mosaic variants occupy distinct cache
entries.

The strict-upper/diagonal block masks used by the SYRK mirror epilogue are
built ONCE per (dp, block_d) with numpy at trace time (`_mirror_masks`,
lru_cached) and embedded in the compiled program as constants — the hot
path carries no O(d^2) mask rebuild.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import MOSAIC_DTYPES
from repro.kernels.hessian_syrk import hessian_syrk_pallas
from repro.kernels.flash_attention import flash_attention_pallas


def target_platform(*arrays) -> str:
    """Platform the computation on ``arrays`` targets (module docstring)."""
    for a in arrays:
        if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
            return next(iter(a.devices())).platform
    dev = jax.config.jax_default_device
    if dev is not None:
        return dev if isinstance(dev, str) else dev.platform
    return jax.default_backend()


def resolve_interpret(interpret: bool | None, *arrays) -> bool:
    """Eagerly resolve an ``interpret=None`` default: interpret off-TPU."""
    if interpret is None:
        return target_platform(*arrays) != "tpu"
    return bool(interpret)


def runs_mosaic(*arrays) -> bool:
    """True when the fused hot path takes the compiled Pallas kernel:
    f32/bf16 operands on a TPU.  f64 never does (module docstring)."""
    return all(
        jnp.dtype(a.dtype) in MOSAIC_DTYPES for a in arrays
    ) and target_platform(*arrays) == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.lru_cache(maxsize=32)
def _mirror_masks(dp: int, block_d: int) -> tuple[np.ndarray, np.ndarray]:
    """(strict_upper, diag_block) boolean tile masks as numpy constants.

    Built once per (dp, block_d) on the host; inside a traced function they
    embed as compile-time constants, so the mirror epilogue costs two
    selects and a transpose — no per-call iota/compare mask construction.
    """
    blk = np.arange(dp) // block_d
    strict_upper = blk[None, :] > blk[:, None]
    diag_block = blk[None, :] == blk[:, None]
    return strict_upper, diag_block


@functools.partial(jax.jit, static_argnames=("block_d", "block_n", "interpret"))
def _hessian_syrk_jit(
    z: jax.Array,
    h: jax.Array,
    block_d: int,
    block_n: int,
    interpret: bool,
) -> jax.Array:
    n, d = z.shape
    zp = _pad_to(_pad_to(z, 0, block_n), 1, block_d)
    hp = _pad_to(h, 0, block_n)
    u = hessian_syrk_pallas(
        zp, hp, block_d=block_d, block_n=block_n, interpret=interpret
    )
    dp = zp.shape[1]
    # mirror strict-upper block tiles; diagonal tiles are already full blocks
    strict_upper, diag_block = _mirror_masks(dp, block_d)
    us = jnp.where(strict_upper, u, 0.0)
    full = us + us.T + jnp.where(diag_block, u, 0.0)
    return full[:d, :d]


def hessian_syrk(
    z: jax.Array,
    h: jax.Array,
    *,
    block_d: int = 128,
    block_n: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """H = Z^T diag(h) Z via the upper-triangular Pallas SYRK kernel.

    z: (n, d) design matrix, h: (n,) nonneg sample weights -> (d, d) symmetric.
    Zero-pads to tile multiples (zero-weight rows are exact no-ops; padded
    feature columns are sliced away), mirrors the strict-upper tiles.

    ``interpret=None`` resolves to the target platform *at call time*
    (module docstring): compiled on a TPU, where a float64 operand raises a
    TypeError naming the dtype; interpret mode elsewhere.
    """
    return _hessian_syrk_jit(
        z, h, block_d, block_n, resolve_interpret(interpret, z, h)
    )


def _syrk_blockform(z: jax.Array, h: jax.Array, block_d: int) -> jax.Array:
    """Upper block-row strips of H = Z^T diag(h) Z, concatenated to (d, d).

    Row strip i multiplies only against columns j >= lo_i — the paper's
    §5.10 half-work trick at tile granularity, the same schedule as the
    Pallas kernel's ``pl.when(j >= i)``.  Strips use EXACT slice widths: NO
    column padding.  Padding d up to a tile multiple inflates the strip
    flops past the plain full product for d just above a boundary (w8a's
    d=301 padded to 384 does 2n*98304 flops vs the full product's
    2n*90601 — measured *slower*), while exact slices do
    2n*sum_i w_i*(d - lo_i) ~ 0.69 * 2n*d^2 here.

    The result agrees with H at every (i, j) the strips cover — in
    particular the ENTIRE upper triangle and the full diagonal blocks — so
    both the mirrored dense form and the packed-triu gather read true
    entries straight off it.
    """
    _, d = z.shape
    zsc = h[:, None] * z
    strips = []
    for lo in range(0, d, block_d):
        w = min(block_d, d - lo)
        strip = z[:, lo : lo + w].T @ zsc[:, lo:]
        strips.append(jnp.pad(strip, ((0, 0), (lo, 0))) if lo else strip)
    return jnp.concatenate(strips, axis=0)


def _hessian_syrk_xla(z: jax.Array, h: jax.Array, block_d: int) -> jax.Array:
    n, d = z.shape
    if d <= block_d:
        # single tile: the whole-matrix expression IS the tile program —
        # bit-identical to the pure-jnp oracle (DESIGN.md §12)
        return z.T @ (h[:, None] * z)
    u = _syrk_blockform(z, h, block_d)
    strict_upper, diag_block = _mirror_masks(d, block_d)
    us = jnp.where(strict_upper, u, 0.0)
    return us + us.T + jnp.where(diag_block, u, 0.0)


def hessian_syrk_xla(z: jax.Array, h: jax.Array, *, block_d: int = 128) -> jax.Array:
    """H = Z^T diag(h) Z as an upper-block-triangular XLA program.

    The same tile schedule as the Pallas kernel (compute row strips j >= i,
    mirror once) expressed as plain dot_generals, so it runs at full speed on
    backends where Pallas only has interpret mode.  For d <= block_d the
    program is literally ``z.T @ (h[:, None] * z)`` — bit-identical to the
    pure-jnp oracle; for larger d the blocked accumulation order differs
    from the single dot_general by O(1) ulp (documented, DESIGN.md §12).

    Deliberately NOT jitted here: the round programs trace it inline (a
    nested pjit call could shift fusion boundaries and cost the d <= block_d
    bit-identity guarantee); standalone callers wrap it in jax.jit.
    """
    return _hessian_syrk_xla(z, h, block_d)


def hessian_fused(
    z: jax.Array,
    h: jax.Array,
    *,
    block_d: int = 128,
    block_n: int = 128,
) -> jax.Array:
    """The default fused-Hessian entry point of the round hot path.

    Routes eagerly (host-side) on dtype and target (module docstring): the
    Mosaic-compiled Pallas SYRK for f32/bf16 operands on a TPU, the
    tile-equivalent XLA program (:func:`hessian_syrk_xla`) otherwise — in
    particular for every f64 round, on every backend.  Interpret-mode
    Pallas is a validation path, never taken here (`repro.objectives.logreg`
    routes here with ``hessian="fused"``; ``hessian="pallas"`` forces the
    wrapper above).
    """
    if runs_mosaic(z, h):
        return hessian_syrk(z, h, block_d=block_d, block_n=block_n, interpret=False)
    return hessian_syrk_xla(z, h, block_d=block_d)


def hessian_syrk_packed(
    z: jax.Array,
    h: jax.Array,
    *,
    block_d: int = 128,
) -> jax.Array:
    """``pack_triu(Z^T diag(h) Z)`` without materializing the mirrored matrix.

    The round hot path only ever consumes the Hessian in packed
    upper-triangle form (compression, Frobenius norms, the H_i updates all
    operate on the (T,) vector — DESIGN.md §12), so the fused client oracle
    gathers the packed entries straight off the block-row strips: every
    (i, j >= i) entry already lives there, and the mirror epilogue would
    only add +0.0 to each before ``pack_triu`` re-extracts it.  Bit-identical
    to ``pack_triu(hessian_fused(z, h))`` (+0.0 can only flip a -0.0 to +0.0,
    and callers add the regularization term packed, replaying the historical
    ``hess + lam*eye`` per-element op order exactly).  Routed like
    :func:`hessian_fused`.
    """
    from repro.linalg import pack_triu

    if runs_mosaic(z, h):
        return pack_triu(hessian_syrk(z, h, block_d=block_d, interpret=False))
    _, d = z.shape
    if d <= block_d:
        return pack_triu(z.T @ (h[:, None] * z))
    return pack_triu(_syrk_blockform(z, h, block_d))


# ---------------------------------------------------------------------------
# fused compressor selection (TopK / TopLEK ranking, RandSeqK window)
# ---------------------------------------------------------------------------

def select_topk(u: jax.Array, k: int, *, fused: bool = False):
    """TopK selection: ``(u_hat, sent)``.

    Selection contract (DESIGN.md §12): rank by f32(|u|), ties broken toward
    the lowest packed index — pinned in `repro.compressors.select`.  f32/bf16
    payloads on a TPU run the Pallas selection kernel; everything else (the
    f64 round on every backend) the jnp primitives — bit-identical output by
    the pinned contract.

    ``fused=True`` picks the sort-free threshold-mask formulation — the
    algorithm the Pallas kernel runs, and the one the fused round maps per
    client; the reference round keeps the sorted form.
    """
    if runs_mosaic(u):
        from repro.kernels.compressor_select import select_topk_pallas

        u_hat, sent = select_topk_pallas(u, k, interpret=False)
        return u_hat, sent[0].astype(jnp.asarray(k).dtype)
    from repro.compressors import select as csel

    if fused:
        return csel.topk_dense_masked(u, k), jnp.asarray(k)
    return csel.topk_dense(u, k), jnp.asarray(k)


def select_toplek(key: jax.Array, u: jax.Array, k: int, *, fused: bool = False):
    """TopLEK: TopK ranking + the Algorithm-4 adaptive prefix, as jnp on
    every backend (the prefix needs the ranked ORDER — a sort — so it has
    no Pallas kernel and no sort-free form; ``fused`` is accepted for
    call-site symmetry and ignored).

    The Bernoulli draw is ``uniform(key)`` in u's dtype — exactly what
    ``jax.random.bernoulli(key, p)`` lowers to.
    """
    del fused
    from repro.compressors import select as csel

    return csel.toplek_from_uniform(u, k, csel.toplek_uniform(key, u.dtype))


def select_randseqk(key: jax.Array, u: jax.Array, k: int, *, fused: bool = False):
    """RandSeqK (Appendix C): one PRG draw, contiguous window keep.

    ``fused=True`` uses the gather-free circular-window mask (the Pallas
    kernel's formulation) instead of roll + prefix slice; values are pure
    copies either way, so the outputs are bit-identical.  Routed like
    :func:`select_topk`.
    """
    t = u.shape[0]
    s = jax.random.randint(key, (), 0, t)
    if runs_mosaic(u):
        from repro.kernels.compressor_select import select_randseqk_pallas

        u_hat, sent = select_randseqk_pallas(u, k, s, interpret=False)
        return u_hat, sent[0].astype(jnp.asarray(k).dtype)
    from repro.compressors import select as csel

    if fused:
        return csel.randseqk_dense_masked(u, k, s), jnp.asarray(k)
    return csel.randseqk_dense(u, k, s), jnp.asarray(k)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def _flash_attention_jit(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    window: int | None,
    scale: float | None,
    block_q: int,
    block_k: int,
    interpret: bool,
) -> jax.Array:
    sq, hn, dh = q.shape
    sk = k.shape[0]
    qt = _pad_to(jnp.swapaxes(q, 0, 1), 1, block_q)
    kt = _pad_to(jnp.swapaxes(k, 0, 1), 1, block_k)
    vt = _pad_to(jnp.swapaxes(v, 0, 1), 1, block_k)
    out = flash_attention_pallas(
        qt, kt, vt, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret, kv_len=sk,
    )
    return jnp.swapaxes(out[:, :sq], 0, 1)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash attention, (seq, heads, head_dim) layout (matches ref.py).

    Pads seq to block multiples (padded queries are discarded; padded keys are
    masked out by causality/window because they sit at positions >= seq).
    ``interpret=None`` resolves eagerly at call time (see module docstring).
    """
    return _flash_attention_jit(
        q, k, v, causal, window, scale, block_q, block_k,
        resolve_interpret(interpret, q, k, v),
    )
