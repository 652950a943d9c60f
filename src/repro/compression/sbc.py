"""Sparse Binary Compression (Sattler et al. [24]) — the paper's gradient
compression substrate (r = 0.005, §VI-A).

Per tensor: (1) magnitude top-k sparsification at rate ``ratio``;
(2) among survivors, keep only the sign group (positive or negative) with
the larger magnitude sum; (3) binarize survivors to that group's mean
magnitude.  With error feedback (residual accumulation) this preserves
convergence.  ``compressed_bits`` reproduces the paper's payload model
s = r·d·p.

``compress_dense`` returns the *dense decompressed* gradient — the form the
in-graph federated all-reduce consumes (DESIGN.md §3: uplink compression
becomes a transform around the data-parallel mean).  The Pallas kernels
(kernels/sbc.py, dispatched through ``kernels.ops.sbc_compress``) compute
the per-block magnitude stats + binarize step on TPU; this module is their
jnp oracle.  ``sbc_uplink`` is the backend-dispatching entry point: the
kernel path on accelerators, bitwise ``compress_dense`` on CPU.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def topk_threshold(mag: jnp.ndarray, k: int) -> jnp.ndarray:
    """Exact k-th largest magnitude (XLA top_k — O(n·k) on CPU)."""
    return jax.lax.top_k(mag, k)[0][-1]


# Bisection steps resolved per read of the magnitudes.  On a TPU v5e a
# pass over the first MLP layer of 384 clients is bound by HBM while it
# counts against 2**_LEVELS - 1 = 7 thresholds; at 15 the compares bind it
# instead, and 4 levels take longer than 3.
_LEVELS = 3


def _subtree(lo, hi, levels: int, level: int = 0) -> list:
    """The binary loop's midpoints for ``levels`` steps below ``(lo, hi)``,
    ascending, each with the step (0 for ``0.5 * (lo + hi)``) at which the
    loop would compute it — from the same operands, so bitwise the same."""
    if level == levels:
        return []
    mid = 0.5 * (lo + hi)
    return (_subtree(lo, mid, levels, level + 1) + [(mid, level)]
            + _subtree(mid, hi, levels, level + 1))


def count_passes(iters: int = 20) -> int:
    """Count passes over one leaf's magnitudes that
    :func:`topk_threshold_bisect` makes (its ``max`` pass aside)."""
    return -(-iters // _LEVELS)


def topk_threshold_bisect(mag: jnp.ndarray, k: int,
                          iters: int = 20) -> jnp.ndarray:
    """~k-th largest magnitude by value-domain bisection: ``iters`` O(n)
    count passes instead of a sort/top_k, which is what makes in-graph SBC
    affordable inside the scanned training loop.  Returns the largest
    threshold t with ``|{mag >= t}| >= k`` up to ``max(mag)/2^iters``
    resolution (survivor count can exceed k only by boundary ties).

    Each read of ``mag`` resolves ``_LEVELS`` steps of the binary loop
    (``lo, hi = (mid, hi) if |{mag >= mid}| >= k else (lo, mid)``): it
    counts against every midpoint of the subtree below ``(lo, hi)`` as
    sibling reductions, then descends with the exact counts.  The
    midpoints ascend, so the steps that go right are a prefix: the
    descent's ``lo`` is the largest midpoint whose count reaches ``k``
    and its ``hi`` the smallest that does not.  The result is bitwise the
    binary loop's; the last pass takes the remaining steps.  The passes
    read a ``(rows, 128)`` view of ``mag`` where it fills whole (8, 128)
    tiles of the TPU, so that under ``vmap`` the lanes' axes stay out of
    the tile (six clients a row would pad to eight); a smaller view would
    pad its own rows instead."""
    if mag.size % (8 * 128) == 0:
        mag = mag.reshape(-1, 128)
    lo = jnp.zeros((), jnp.float32)
    hi = jnp.max(mag) * (1.0 + 1e-6) + 1e-30

    def body(i, lohi):
        lo, hi = lohi
        mids, level = zip(*_subtree(lo, hi, _LEVELS))
        geq = jnp.stack([jnp.sum(mag >= t) for t in mids]) >= k
        live = jnp.asarray(level) < iters - i * _LEVELS
        mids = jnp.stack(mids)
        return (jnp.max(jnp.where(live & geq, mids, lo)),
                jnp.min(jnp.where(live & ~geq, mids, hi)))

    lo, hi = jax.lax.fori_loop(0, count_passes(iters), body, (lo, hi))
    return lo


def sbc_tensor(g: jnp.ndarray, ratio: float,
               exact: bool = True) -> jnp.ndarray:
    """Dense SBC approximation of one tensor (jnp oracle).

    ``exact=True`` uses the literal top-k threshold (the Pallas kernels'
    oracle contract); ``exact=False`` uses the bisection threshold — the
    training hot path's choice.
    """
    flat = g.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    k = max(1, int(round(n * ratio)))
    mag = jnp.abs(flat)
    # threshold = k-th largest magnitude
    thr = topk_threshold(mag, k) if exact else topk_threshold_bisect(mag, k)
    keep = mag >= thr
    pos = keep & (flat > 0)
    neg = keep & (flat < 0)
    pos_sum = jnp.sum(jnp.where(pos, mag, 0.0))
    neg_sum = jnp.sum(jnp.where(neg, mag, 0.0))
    use_pos = pos_sum >= neg_sum
    grp = jnp.where(use_pos, pos, neg)
    grp_sum = jnp.where(use_pos, pos_sum, neg_sum)
    cnt = jnp.maximum(jnp.sum(grp), 1)
    mean_mag = grp_sum / cnt
    val = jnp.where(use_pos, mean_mag, -mean_mag)
    out = jnp.where(grp, val, 0.0)
    return out.reshape(g.shape).astype(g.dtype)


def compress_dense(grads, ratio: float = 0.005, residual=None,
                   exact: bool = False):
    """Apply SBC to every leaf; with error-feedback residuals when given.

    Defaults to the bisection threshold (``exact=False``): error feedback
    absorbs its boundary-tie slack, and it is orders of magnitude cheaper
    than top_k/sort on every backend, which matters because this runs once
    per period inside the compiled training scan.

    Returns (approx_grads, new_residual).
    """
    if residual is None:
        residual = jax.tree_util.tree_map(jnp.zeros_like, grads)
    acc = jax.tree_util.tree_map(lambda g, r: g + r, grads, residual)
    approx = jax.tree_util.tree_map(
        lambda t: sbc_tensor(t, ratio, exact=exact), acc)
    new_res = jax.tree_util.tree_map(lambda a, ap: a - ap, acc, approx)
    return approx, new_res


def sbc_uplink(grads, ratio: float = 0.005, residual=None):
    """Error-feedback SBC routed through the accelerator kernel path.

    On TPU each leaf goes through the two-kernel composition in
    ``kernels/sbc.py`` (``sbc_stats`` + ``sbc_apply`` via
    ``kernels.ops.sbc_compress``); on CPU this *is* ``compress_dense`` —
    bitwise, not merely allclose — so the engine path and the oracle are
    interchangeable in CPU CI.  Returns ``(approx_grads, new_residual)``
    with the same error-feedback contract as ``compress_dense``.
    """
    from repro.kernels import ops as kops  # lazy: kernels.ref imports us

    if not kops._on_tpu():
        return compress_dense(grads, ratio, residual)
    if residual is None:
        residual = jax.tree_util.tree_map(jnp.zeros_like, grads)
    acc = jax.tree_util.tree_map(lambda g, r: g + r, grads, residual)
    approx = jax.tree_util.tree_map(
        lambda t: kops.sbc_compress(t, ratio), acc)
    new_res = jax.tree_util.tree_map(lambda a, ap: a - ap, acc, approx)
    return approx, new_res


def compressed_bits(n_params: int, ratio: float = 0.005,
                    bits_per_term: int = 64) -> float:
    """Paper's payload model: s = r·d·p."""
    return ratio * bits_per_term * n_params
