"""Flash attention forward kernel (TPU Pallas).

Online-softmax tiling: grid (batch·heads, q_blocks, kv_blocks) with the
kv axis innermost ("arbitrary" semantics) carrying (acc, m, l) scratch in
VMEM.  Causal + sliding-window masking by absolute positions.  Block
shapes are MXU-aligned (block_q × head_dim and block_k × head_dim tiles);
VMEM working set ≈ (2·block_k + block_q)·hd + block_q·block_k floats.

Validated against kernels.ref.attention_ref in interpret mode on CPU
(tests/test_kernels.py sweeps shapes/dtypes).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                 # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                 # (bk, hd)
    v = v_ref[0].astype(jnp.float32)                 # (bk, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    pos_q = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    pos_k = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= pos_k <= pos_q
    if window is not None:
        mask &= pos_k > pos_q - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                              # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                           # (bq, bk)
    l_new = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         window: Optional[int] = None, block_q: int = 128,
                         block_k: int = 128, interpret: bool = False):
    """q, k, v: (BH, S, hd) — same head count (caller expands GQA groups)."""
    BH, S, hd = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk)

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom l
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
