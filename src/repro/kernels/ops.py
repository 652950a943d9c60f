"""jit'd public wrappers for the Pallas kernels.

Backend selection: on TPU the compiled Pallas kernel runs; elsewhere the
wrapper falls back to the jnp oracle (CPU dry-runs lower pure-XLA HLO) or,
when ``interpret=True`` is forced, executes the kernel body in Python —
that is how tests validate the kernels on CPU.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import sbc as _sbc
from repro.kernels import ssd_scan as _ssd
from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd(q, k, v, causal, window, block_q, block_k, interpret):
    """Pallas forward; the backward pass is the VJP of the jnp oracle,
    recomputed from (q, k, v)."""
    return _fa.flash_attention_bhsd(q, k, v, causal=causal, window=window,
                                    block_q=block_q, block_k=block_k,
                                    interpret=interpret)


def _flash_bhsd_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    out = _flash_bhsd(q, k, v, causal, window, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bhsd_bwd(causal, window, block_q, block_k, interpret, res, g):
    _, vjp = jax.vjp(partial(_ref.attention_ref, causal=causal,
                             window=window), *res)
    return vjp(g)


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


def flash_attention(q, k, v, pos_q=None, pos_k=None, *, causal: bool = True,
                    window: Optional[int] = None, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    """q: (B,S,Hq,hd); k/v: (B,S,Hkv,hd) — GQA groups expanded internally.

    Positions are assumed contiguous from 0 (full-sequence train/prefill).
    Differentiable: the kernel runs forward, the oracle's VJP backward.
    """
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hq, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hq, S, hd)
    if interpret is None and not _on_tpu():
        out = _ref.attention_ref(qf, kf, vf, causal=causal, window=window)
    else:
        out = _flash_bhsd(qf, kf, vf, causal, window, min(block_q, S),
                          min(block_k, S), bool(interpret))
    return out.reshape(B, Hq, S, hd).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# flash decode (one token vs long cache)
# ---------------------------------------------------------------------------


def flash_decode(q, k, v, pos, *, window=None, block_s: int = 512,
                 interpret: Optional[bool] = None):
    """q: (B,1,Hq,hd); k/v caches: (B,ctx,Hkv,hd); pos: scalar."""
    from repro.kernels import flash_decode as _fd
    B, _, Hq, hd = q.shape
    ctx, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, 1, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hq, ctx, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hq, ctx, hd)
    if interpret is None and not _on_tpu():
        out = _ref.decode_attention_ref(qf, kf, vf, pos, window=window)
    else:
        out = _fd.flash_decode_bhd(
            qf, kf, vf, pos, window=window, block_s=min(block_s, ctx),
            interpret=bool(interpret) if interpret is not None else False)
    return out.reshape(B, Hq, 1, hd).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_kernel(x, dt, A, Bm, Cm, chunk, interpret):
    """Pallas forward; the backward pass is the VJP of ``ssd_reference``,
    recomputed from the inputs."""
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


def _ssd_kernel_fwd(x, dt, A, Bm, Cm, chunk, interpret):
    return _ssd_kernel(x, dt, A, Bm, Cm, chunk, interpret), (x, dt, A, Bm, Cm)


def _ssd_kernel_bwd(chunk, interpret, res, g):
    _, vjp = jax.vjp(partial(_ref.ssd_ref, chunk=chunk), *res)
    return vjp(g)


_ssd_kernel.defvjp(_ssd_kernel_fwd, _ssd_kernel_bwd)


def ssd(x, dt, A, Bm, Cm, *, chunk: int = 256,
        interpret: Optional[bool] = None):
    """Differentiable SSD scan: the kernel runs forward, the oracle's VJP
    backward."""
    chunk = min(chunk, x.shape[1])
    if interpret is None and not _on_tpu():
        return _ref.ssd_ref(x, dt, A, Bm, Cm, chunk)
    return _ssd_kernel(x, dt, A, Bm, Cm, chunk, bool(interpret))


# ---------------------------------------------------------------------------
# SBC compression
# ---------------------------------------------------------------------------


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def sbc_compress(x, ratio: float = 0.005, *, block: int = 65536,
                 interpret: Optional[bool] = None):
    """Dense SBC approximation of one tensor via the kernel pipeline: the
    exact top-k threshold in XLA, then :func:`sbc_binarize`."""
    if interpret is None and not _on_tpu():
        return _ref.sbc_ref(x, ratio)
    flat = x.reshape(-1).astype(jnp.float32)
    k = max(1, int(round(flat.shape[0] * ratio)))
    thr = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
    return sbc_binarize(x, thr, block=block, interpret=bool(interpret))


def sbc_binarize(x, thr, *, block: int = 65536, interpret: bool = False):
    """The kernel half of SBC: given the magnitude threshold ``thr``, keep
    the heavier sign group of the survivors at its mean magnitude.

    The tensor is zero-padded into a ``(rows, 128)`` slab cut into blocks
    of ``block`` elements (rounded to whole 8-row tiles); padding zeros
    join neither sign group, so they change no statistic.
    """
    lanes = _sbc.LANES
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    rows = _round_up(-(-n // lanes), 8)
    block_rows = min(_round_up(max(block // lanes, 1), 8), rows)
    rows = _round_up(rows, block_rows)
    slab = jnp.pad(flat, (0, rows * lanes - n)).reshape(rows, lanes)
    part = _sbc.sbc_stats(slab, jnp.full((1, lanes), thr),
                          block_rows=block_rows, interpret=interpret)
    pos_sum, neg_sum, pos_cnt, neg_cnt = jnp.sum(part[:4], axis=1)
    use_pos = pos_sum >= neg_sum
    mean_mag = jnp.where(use_pos,
                         pos_sum / jnp.maximum(pos_cnt, 1.0),
                         neg_sum / jnp.maximum(neg_cnt, 1.0))
    scalars = jnp.stack([jnp.asarray(thr, jnp.float32),
                         jnp.where(use_pos, mean_mag, 0.0),
                         jnp.where(use_pos, 0.0, -mean_mag)])
    out = _sbc.sbc_apply(slab, jnp.broadcast_to(scalars[:, None], (3, lanes)),
                         block_rows=block_rows, interpret=interpret)
    return out.reshape(-1)[:n].reshape(x.shape).astype(x.dtype)
