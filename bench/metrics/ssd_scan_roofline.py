"""Share of its roofline, in percent, that the Pallas SSD forward kernel
(``kernels/ssd_scan.py``) reaches: the least time its calls could take on
this chip over their summed device time in the trace.  The least time of
a call is the larger of its operations (``counts.ssd_scan``, from its
shapes) over peak FLOP/s and its HBM bytes (``counts.hbm_bytes``: the
operands and result not kept on chip) over peak bandwidth.

The kernel carries no name of its own yet: its calls are the
``tpu_custom_call`` operations whose result is ``(…, H, chunks, chunk,
P)`` with the configuration's chunk and head size (in the trace,
``%closed_call.N = f32[K,B,H,nc,l,P] custom-call(5 operands)``).  No such
call in the trace: no reading."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import counts      # noqa: E402
import tracefile   # noqa: E402

OPERANDS = 5


def read(ctx):
    cfg, peak = ctx["config"], ctx["peak"]
    chunk_p = (int(cfg["chunk"]), int(cfg["head_dim"]))
    least = spent = 0.0
    for op in ctx["reduction"].matching(
            r'custom_call_target="tpu_custom_call"'):
        found = tracefile.shapes(op.name)[:1 + OPERANDS]
        if len(found) < 1 + OPERANDS or found[0][1][-2:] != chunk_p:
            continue
        *lead, heads, nc, chunk, p = found[0][1]
        batch = 1
        for d in lead:
            batch *= d
        flops = counts.ssd_scan(batch, nc * chunk, heads, p,
                                int(cfg["d_state"]), chunk)
        least += counts.roofline_s(flops, counts.hbm_bytes(found),
                                   peak)[0]
        spent += op.dur_ns * 1e-9
    if spent <= 0:
        return None
    return 100.0 * least / spent
