"""Host Algorithm 1 and slot allocation (the program's ``repro.plan.solve``
spans: each outermost ``optimize_batch_rows`` / ``solve_period_rows`` /
``fixed_slot_rows`` call of the scheduler) in milliseconds per simulated
period of a grid call: the window's span seconds over (grid calls ×
periods).  A program without the span: no reading."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import progtrace   # noqa: E402


def read(ctx):
    prog = progtrace.from_ctx(ctx)
    if prog is None:
        return None
    return progtrace.per_period(ctx, prog.span_seconds("repro.plan.solve"))
