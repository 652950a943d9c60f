"""Flash-decode kernel (TPU Pallas): one query token per sequence against
a long KV cache — the decode_32k / long_500k hot spot.

Grid (batch·heads, ctx_blocks) with the ctx axis innermost ("arbitrary"),
carrying (acc, m, l) online-softmax state in VMEM; invalid cache slots
(beyond ``pos``, or outside the sliding window for ring buffers) are
masked by absolute position.  VMEM per step ≈ 2·block_s·hd + hd floats.

Oracle: kernels/ref.py::decode_attention_ref (tests sweep ctx/block/hd).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale: float, block_s: int, ns: int,
                   window: Optional[int]):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[0]
    q = q_ref[0].astype(jnp.float32)                 # (1, hd)
    k = k_ref[0].astype(jnp.float32)                 # (bs, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # absolute positions of this cache block's slots
    idx = si * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    if window is not None:
        # ring buffer: slot i holds the latest position ≡ i (mod ctx)
        ctx = ns * block_s
        key_pos = pos - ((pos - idx) % ctx)
        valid = (key_pos >= 0) & (key_pos <= pos) & (key_pos > pos - window)
    else:
        valid = idx <= pos
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_bhd(q, k, v, pos, *, window: Optional[int] = None,
                     block_s: int = 512, interpret: bool = False):
    """q: (BH, 1, hd); k/v: (BH, ctx, hd); pos: scalar int32.

    Returns (BH, 1, hd).  ``window`` set => the cache is a ring buffer of
    size ctx (== window allocation) and masking follows absolute order.
    """
    BH, ctx, hd = k.shape
    block_s = min(block_s, ctx)
    assert ctx % block_s == 0
    ns = ctx // block_s
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_decode_kernel, scale=scale, block_s=block_s,
                               ns=ns, window=window)
    return pl.pallas_call(
        kernel,
        grid=(BH, ns),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, hd), lambda b, s: (b, 0, 0)),
            pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0)),
            pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda b, s: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32)[None], q, k, v)
