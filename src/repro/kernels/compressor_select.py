"""Pallas kernels: fused compressor selection over the packed upper triangle.

One ``pallas_call`` per compressed message covers the selection pipeline
that the jnp path spells as several XLA ops (rank keys -> top_k -> gather
-> scatter): the length-T packed-triu correction vector is resident in VMEM
once, and ranking + keep-mask + dense masked copy happen in that single
pass.  Two variants:

  TopK      magnitude ranking via
            :func:`repro.compressors.select.keep_mask_from_bits` — binary
            searches on the int32 bit patterns of the f32 rank keys and on
            the tie prefix (compares + full-array int32 sums only; no sort,
            no cumsum, no gather), then a masked select.
  RandSeqK  the Appendix-C contiguous window as a membership mask
            (:func:`repro.compressors.select.window_mask`) — compares only.

Layout: the wrapper pads T to a multiple of 8 x 128 and reshapes it to a
lane-dense ``(rows, 128)`` tile (a 1-D block would not match Mosaic's
vector layout); padding slots carry key bits -1 and can never be kept.
All index arithmetic is int32 (Mosaic has no 64-bit types, and under x64 an
``arange`` or a sum would default to int64).

Mosaic has no f64 either: compiled kernels take f32/bf16 payloads and raise
a ``TypeError`` naming the dtype otherwise; interpret mode (CPU tests) takes
any float.  The f64 FedNL round runs the jnp masked forms instead
(`repro.kernels.ops`, DESIGN.md §12).  The PRNG draw (RandSeqK's start
index) is made OUTSIDE the kernel and passed as an SMEM scalar, so fused and
unfused paths consume identical key streams.

TopLEK has no kernel: its adaptive prefix needs the kept values in rank
order (a sort), which Mosaic does not lower at these widths.

Selection parity contract (DESIGN.md §12): identical index set — f32 rank
keys, lowest-index tie-break — and bit-identical dense output vs the
`repro.compressors.core` reference; pinned by tests/test_kernels.py on
adversarial near-tie inputs, and compiled for a v5e by
tests/test_chip_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compressors.select import keep_mask_from_bits, window_mask
from repro.kernels import check_mosaic_dtype

LANES = 128
_TILE = 8 * LANES


def _positions(shape) -> jax.Array:
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return rows * LANES + lanes


def _to_tile(u: jax.Array) -> jax.Array:
    t = u.shape[0]
    return jnp.pad(u, (0, (-t) % _TILE)).reshape(-1, LANES)


def _topk_kernel(u_ref, o_ref, *, t: int, k: int):
    u = u_ref[...]
    pos = _positions(u.shape)
    keys = jnp.abs(u).astype(jnp.float32)
    bits = jnp.where(
        pos < t, jax.lax.bitcast_convert_type(keys, jnp.int32), jnp.int32(-1)
    )
    keep = keep_mask_from_bits(bits, pos, t, k)
    o_ref[...] = jnp.where(keep, u, jnp.zeros_like(u))


def select_topk_pallas(
    u: jax.Array, k: int, *, interpret: bool = False
) -> tuple[jax.Array, jax.Array]:
    """Fused dense TopK: ``(u_hat, sent)`` in one VMEM-resident pass."""
    if not interpret:
        check_mosaic_dtype("select_topk_pallas", u)
    t = u.shape[0]
    ut = _to_tile(u)
    out = pl.pallas_call(
        functools.partial(_topk_kernel, t=t, k=k),
        out_shape=jax.ShapeDtypeStruct(ut.shape, u.dtype),
        interpret=interpret,
    )(ut)
    return out.reshape(-1)[:t], jnp.full((1,), k, jnp.int32)


def _randseqk_kernel(u_ref, s_ref, o_ref, *, t: int, k: int):
    u = u_ref[...]
    keep = window_mask(_positions(u.shape), t, k, s_ref[0])
    o_ref[...] = jnp.where(keep, u, jnp.zeros_like(u))


def select_randseqk_pallas(
    u: jax.Array, k: int, s: jax.Array, *, interpret: bool = False
) -> tuple[jax.Array, jax.Array]:
    """Fused dense RandSeqK given the start draw ``s``: the circular window
    becomes a membership mask — no roll, no gather, pure copies (so output
    bits match the jnp roll formulation exactly)."""
    if not interpret:
        check_mosaic_dtype("select_randseqk_pallas", u)
    t = u.shape[0]
    ut = _to_tile(u)
    out = pl.pallas_call(
        functools.partial(_randseqk_kernel, t=t, k=k),
        out_shape=jax.ShapeDtypeStruct(ut.shape, u.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        interpret=interpret,
    )(ut, jnp.reshape(s, (1,)).astype(jnp.int32))
    return out.reshape(-1)[:t], jnp.full((1,), k, jnp.int32)
