"""Share of their roofline, in percent, that the two Pallas SBC kernels
(``kernels/sbc.py``: ``sbc_stats`` and ``sbc_apply``) reach together: the
least time their calls could take on this chip over their summed device
time in the trace.  The least time of a call is the larger of its
operations (``counts.sbc_stats`` / ``counts.sbc_apply``, from its
``(…, rows, 128)`` slab) over peak FLOP/s and its HBM bytes
(``counts.hbm_bytes``: the operands and result not kept on chip) over
peak bandwidth.

The kernels carry no names of their own yet: their calls are the
``tpu_custom_call`` operations on a slab (in the trace, ``%vmap__.N``),
told apart by the second operand: the threshold row ``(…, 1, 128)`` for
``sbc_stats``, three scalar rows ``(…, 3, 128)`` for ``sbc_apply``.  No
such call in the trace: no reading."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import counts      # noqa: E402
import tracefile   # noqa: E402

KERNELS = {(1, 128): counts.sbc_stats, (3, 128): counts.sbc_apply}
OPERANDS = 2


def read(ctx):
    peak = ctx["peak"]
    least = spent = 0.0
    for op in ctx["reduction"].matching(
            r'custom_call_target="tpu_custom_call"'):
        found = tracefile.shapes(op.name)[:1 + OPERANDS]
        if len(found) < 1 + OPERANDS:
            continue
        count = KERNELS.get(found[2][1][-2:])
        slab = found[1][1]
        if count is None or slab[-1] != 128:
            continue
        rows = 1
        for d in slab[:-1]:
            rows *= d
        flops = count(rows)
        least += counts.roofline_s(flops, counts.hbm_bytes(found), peak)[0]
        spent += op.dur_ns * 1e-9
    if spent <= 0:
        return None
    return 100.0 * least / spent
