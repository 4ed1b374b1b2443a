"""Pallas kernels for the paper's compute hot spots (SYRK Hessian oracle,
compressor selection) plus the LM zoo's flash attention.

``check_mosaic_dtype`` is the one guard every compiled (non-interpret)
kernel applies to its operands: Mosaic lowers f32/bf16, never f64.
"""

from __future__ import annotations

import jax.numpy as jnp

MOSAIC_DTYPES = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def check_mosaic_dtype(what: str, *arrays) -> None:
    """Raise a TypeError naming the dtype when a compiled Pallas kernel
    would get an operand Mosaic cannot lower."""
    for a in arrays:
        if jnp.dtype(a.dtype) not in MOSAIC_DTYPES:
            raise TypeError(
                f"{what}: compiled Pallas kernels take float32/bfloat16 "
                f"operands, got {jnp.dtype(a.dtype).name} (Mosaic has no "
                "float64; the f64 round runs the XLA formulation — "
                "DESIGN.md §12)"
            )
