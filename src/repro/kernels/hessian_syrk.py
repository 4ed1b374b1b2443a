"""Pallas TPU kernel: symmetric rank-n_i update H = Z^T diag(h) Z.

This is the paper's dominant compute hot-spot (§5.10 "Hessian and Gradients
Oracles: x3.072").  The paper's CPU strategy: evaluate the Hessian as a sum of
symmetric rank-1 matrices, compute ONLY the upper-diagonal part, symmetrize
once at the end, and tile for the L1/L2 caches.

TPU adaptation (DESIGN.md §2): the same idea re-derived for the MXU + VMEM
hierarchy —

  * the (d, d) output is computed in (bd, bd) MXU-aligned tiles (bd multiple
    of 128 for f32);
  * a 3D grid (i, j, k) marches over output tiles x sample chunks; the k axis
    accumulates partial SYRK products in the VMEM-resident output tile
    ("arbitrary" dimension semantics: megacore partitions i/j only);
  * tiles strictly BELOW the diagonal are skipped with `pl.when` — half the
    MXU work and half the HBM writes, exactly the paper's upper-triangle
    trick at tile granularity;
  * diag(h) is fused into the right operand load (one multiply in VMEM, no
    materialized (n, d) scaled copy in HBM); h travels as an (n, 1) column
    so its block is 2-D (a 1-D (block_n,) block does not match Mosaic's
    vector layout).

Mosaic has no f64: the compiled kernel takes f32/bf16 operands (bf16
accumulates and returns f32) and raises a TypeError naming any other dtype.
Interpret mode takes any float.

The jit'd wrapper (ops.hessian_syrk) pads (n, d) to tile multiples, mirrors
the strict upper tiles after the call, and slices the padding away.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import check_mosaic_dtype


def _syrk_kernel(z_i_ref, z_j_ref, h_ref, o_ref, *, grid_k: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # skip tiles strictly below the block diagonal: their values are the
    # mirror of (j, i) and never read by the wrapper.
    @pl.when(j >= i)
    def _compute():
        zi = z_i_ref[...]  # (bk, bd) chunk of Z for row-tile i
        zj = z_j_ref[...]  # (bk, bd) chunk of Z for col-tile j
        hh = h_ref[...]  # (bk, 1) sample weights
        zj_scaled = zj * hh
        acc = jax.lax.dot_general(
            zi,
            zj_scaled,
            dimension_numbers=(((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,  # full f32 passes on the MXU
            preferred_element_type=o_ref.dtype,
        )
        o_ref[...] += acc


def hessian_syrk_pallas(
    z: jax.Array,
    h: jax.Array,
    *,
    block_d: int = 128,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Upper-block-triangular H = Z^T diag(h) Z; inputs must be pre-padded to
    multiples of the block sizes.  Returns the raw tile output (strictly-lower
    tiles are zero); see ops.hessian_syrk for the symmetrized public API.
    """
    n, d = z.shape
    assert n % block_n == 0 and d % block_d == 0, (n, d, block_n, block_d)
    if not interpret:
        check_mosaic_dtype("hessian_syrk_pallas", z, h)
    out_dtype = jnp.float32 if z.dtype == jnp.bfloat16 else z.dtype
    grid = (d // block_d, d // block_d, n // block_n)
    kernel = functools.partial(_syrk_kernel, grid_k=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, i)),
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_n, 1), lambda i, j, k: (k, jnp.int32(0))),
        ],
        out_specs=pl.BlockSpec((block_d, block_d), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(z, z, h.reshape(n, 1))
