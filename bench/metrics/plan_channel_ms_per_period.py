"""Host channel Monte-Carlo (the program's ``repro.plan.channel`` spans:
``Cell.avg_rate_updown_rows``, the per-period rate draws of every planner
row) in milliseconds per simulated period of a grid call: the window's
span seconds over (grid calls × periods).  A program without the span: no
reading."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import progtrace   # noqa: E402


def read(ctx):
    prog = progtrace.from_ctx(ctx)
    if prog is None:
        return None
    return progtrace.per_period(ctx, prog.span_seconds("repro.plan.channel"))
