"""Pallas TPU kernel: flash attention (online softmax) with causal and
sliding-window masking.

This serves the architecture-zoo side of the framework: 32k-token prefill
cannot materialize (S, S) score matrices (25 GB/layer for nemotron shapes), so
attention must be computed blockwise with an online softmax.  The models use a
pure-jnp chunked scan (models/attention.py) that XLA lowers on any backend —
this kernel is the TPU-native version of the same computation and is validated
against ref.flash_attention_ref in interpret mode.

Layout: q, k, v are (heads, seq, head_dim); the grid is
(heads, q_blocks, kv_blocks) with the kv axis innermost ("arbitrary"
semantics) accumulating into VMEM scratch (running max m, denominator l,
weighted accumulator acc).  Blocks that the causal/sliding-window mask fully
zeroes are skipped with `pl.when` — for window W << S the kernel does
O(S * W) work, which is what makes long_500k decodable architectures
(mixtral/llava SWA) trainable at long context.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale, causal, window, block_q, block_k, kv_blocks, kv_len,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = kj * block_k

    # block-level skip: fully-masked (q_block, kv_block) pairs do no work
    skip = False
    if causal:
        skip = k_start > q_start + block_q - 1
    if window is not None:
        skip = jnp.logical_or(
            skip, k_start + block_k - 1 < q_start - (window - 1)
        )

    @pl.when(jnp.logical_not(skip))
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, dh)
        k = k_ref[0].astype(jnp.float32)  # (bk, dh)
        v = v_ref[0].astype(jnp.float32)  # (bk, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < kv_len  # padded keys are never attended
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[...]  # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(s <= _NEG / 2, 0.0, jnp.exp(s - m_new))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(kj == kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    kv_len: int | None = None,
) -> jax.Array:
    """q, k, v: (heads, seq, head_dim), seq divisible by the block sizes.

    kv_len: true (unpadded) number of keys; positions >= kv_len are masked.
    """
    hn, sq, dh = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    s = dh**-0.5 if scale is None else scale
    grid = (hn, sq // block_q, sk // block_k)
    kernel = functools.partial(
        _flash_kernel,
        scale=s,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        kv_blocks=grid[2],
        kv_len=sk if kv_len is None else kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((hn, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
