"""Operations and bytes from shapes, and the chips' peaks.

Kernel operations are those the algorithm needs at the call's shapes, and
its bytes those of its operands and result at their shapes and dtypes
(each read or written once), not what a compiler or a tile layout adds:
a kernel that moves padded tiles reads as far from its roofline, which is
the point.  A roofline charges memory time only for the bytes that cross
HBM (``hbm_bytes``): an operand or result XLA keeps in on-chip memory
costs none, so a kernel's share stays a lower bound of what the chip
could do.

The peaks table (``peaks.json``) is keyed by ``device_kind`` as JAX
reports it; a device that is not in the table is an error.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds, which bound) for work of ``flops`` and ``nbytes``."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "u8": 1,
            "s32": 4, "u32": 4, "pred": 1}


def hbm_bytes(shapes) -> float:
    """Bytes of the (dtype, dims, memory space) shapes that live in HBM
    (space 0), each read or written once."""
    total = 0
    for dtype, dims, space in shapes:
        if space == 0:
            n = ITEMSIZE[dtype]
            for d in dims:
                n *= d
            total += n
    return float(total)


def ssd_scan(batch: int, seq: int, heads: int, head_dim: int,
             state: int, chunk: int) -> float:
    """Operations of one chunked-SSD forward kernel call.  Per (batch,
    head, chunk) step of length l: C·Bᵀ (2l²N), the masked decay product
    (l²), its product with x·dt (2l²P), the carried state's read-out
    C·stateᵀ (2lNP) and its scaling (lP), the state update
    (x·dt)ᵀ·(B·decay) (2lNP, lN) and decay (2PN), and the sum of the two
    outputs (lP)."""
    l = chunk
    per_step = (2 * l * l * state + l * l + 2 * l * l * head_dim
                + 2 * l * state * head_dim + l * head_dim
                + 2 * l * state * head_dim + l * state
                + 2 * head_dim * state + l * head_dim)
    return float(batch * heads * (seq // chunk) * per_step)


def sbc_stats(rows: int, lanes: int = 128) -> float:
    """Operations of ``sbc_stats`` over a (rows, lanes) slab: per element a
    magnitude, a compare with the threshold, two sign tests and two
    masked sums and counts (8)."""
    return float(8 * rows * lanes)


def sbc_apply(rows: int, lanes: int = 128) -> float:
    """Operations of ``sbc_apply``: per element a magnitude, a compare and
    a sign select (3)."""
    return float(3 * rows * lanes)
