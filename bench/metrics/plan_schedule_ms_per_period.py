"""Host schedule build (the program's ``repro.plan.schedule`` spans: the
planner's ``engine.build_schedule`` + ``pad_schedule`` loop, i.e. every
row's per-client sample draws, and the active mask) in milliseconds per
simulated period of a grid call: the window's span seconds over (grid
calls × periods).  A program without the span: no reading."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import progtrace   # noqa: E402


def read(ctx):
    prog = progtrace.from_ctx(ctx)
    if prog is None:
        return None
    return progtrace.per_period(ctx,
                                prog.span_seconds("repro.plan.schedule"))
