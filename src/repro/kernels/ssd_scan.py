"""Mamba2 SSD chunked-scan kernel (TPU Pallas).

One grid step processes one (batch, head, chunk) tile: the intra-chunk
quadratic block (chunk × chunk, MXU-friendly) plus the inter-chunk state
recurrence carried in a VMEM scratch (P × N floats per (b,h) — the chunk
axis is innermost/"arbitrary" so the scratch persists across chunks).

Layout: the wrapper moves heads in front of the sequence and splits the
sequence into chunks, so every operand is ``(B, H|G, nc, chunk, ·)`` and
each block is one whole ``(chunk, P)`` / ``(chunk, N)`` / ``(chunk, 1)`` /
``(1, chunk)`` tile.  A block's last two dims then always equal the
array's, which is the TPU tiling rule's full-dim case at any chunk size
and under any number of leading ``vmap`` axes.  The elementwise set-up
(``x·dt``, ``dt·A`` and its within-chunk cumulative sum) runs in XLA
ahead of the kernel; the kernel owns the matmuls and the recurrence.

VMEM working set per step ≈ chunk·(P + 2N + 2) + chunk² + P·N floats
(chunk=256, P=64, N=128: ~0.4 MB) — far under the ~16 MiB budget, leaving
room for double buffering.

Oracle: repro.models.mamba2.ssd_reference (tests sweep shapes/dtypes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, cc_ref, cr_ref, b_ref, c_ref, y_ref, state_ref):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xdt = x_ref[...]                              # (l, P)  x·dt
    cs_c = cc_ref[...]                            # (l, 1)  cumsum(dt·A)
    cs_r = cr_ref[...]                            # (1, l)  same, as a row
    Bm = b_ref[...].astype(jnp.float32)           # (l, N)
    Cm = c_ref[...].astype(jnp.float32)           # (l, N)
    l = xdt.shape[0]

    # segment sums exp(cs_i - cs_j) on and below the diagonal
    causal = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1) <= \
        jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    Lmat = jnp.exp(jnp.where(causal, cs_c - cs_r, -jnp.inf))
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot_general(scores * Lmat, xdt,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    state = state_ref[...]                        # (P, N)
    y_off = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_off = y_off * jnp.exp(cs_c)                 # (l, P)

    total = cs_r[:, l - 1:]                       # (1, 1) chunk decay
    decay_out = jnp.exp(total - cs_c)             # (l, 1)
    upd = jax.lax.dot_general(xdt, Bm * decay_out,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    state_ref[...] = state * jnp.exp(total) + upd

    y_ref[...] = (y_diag + y_off).astype(y_ref.dtype)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 256, interpret: bool = False):
    """x: (B,S,H,P), dt: (B,S,H), A: (H,), Bm/Cm: (B,S,G,N) -> y (B,S,H,P).

    Returns only y (the final state is re-derivable; the train path does
    not need it).
    """
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"chunk {chunk} must divide the sequence {S}")
    nc = S // chunk

    dtf = dt.astype(jnp.float32)
    xdt = x.astype(jnp.float32) * dtf[..., None]            # (B,S,H,P)
    cs = jnp.cumsum((dtf * A.astype(jnp.float32)).reshape(B, nc, chunk, H),
                    axis=2)                                 # (B,nc,l,H)
    xdt = xdt.reshape(B, nc, chunk, H, P).transpose(0, 3, 1, 2, 4)
    cs_col = cs.transpose(0, 3, 1, 2)[..., None]            # (B,H,nc,l,1)
    cs_row = cs.transpose(0, 3, 1, 2)[..., None, :]         # (B,H,nc,1,l)
    Bh = Bm.reshape(B, nc, chunk, G, N).transpose(0, 3, 1, 2, 4)
    Ch = Cm.reshape(B, nc, chunk, G, N).transpose(0, 3, 1, 2, 4)

    def tile(*shape):
        return pl.BlockSpec((None, None, None) + shape,
                            lambda b, h, c: (b, h, c, 0, 0))

    def group_tile(*shape):
        return pl.BlockSpec((None, None, None) + shape,
                            lambda b, h, c: (b, h // rep, c, 0, 0))

    y = pl.pallas_call(
        _ssd_kernel,
        grid=(B, H, nc),
        in_specs=[tile(chunk, P), tile(chunk, 1), tile(1, chunk),
                  group_tile(chunk, N), group_tile(chunk, N)],
        out_specs=tile(chunk, P),
        out_shape=jax.ShapeDtypeStruct((B, H, nc, chunk, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xdt, cs_col, cs_row, Bh, Ch)
    return y.transpose(0, 2, 3, 1, 4).reshape(B, S, H, P)
