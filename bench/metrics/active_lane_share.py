"""Share of the computed client lanes, in percent, that did work: the
``lanes_used`` stat of the program's ``repro.plan`` spans ((client, period)
pairs active with B_k > 0, the rule ``client_periods_per_s`` counts by)
over their ``lanes`` stat (rows × periods × padded K computed), summed
over the window.  A program without the span: no reading."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import progtrace   # noqa: E402


def read(ctx):
    prog = progtrace.from_ctx(ctx)
    if prog is None:
        return None
    lanes = prog.stat_sum("repro.plan", "lanes")
    used = prog.stat_sum("repro.plan", "lanes_used")
    if not lanes or used is None:
        return None
    return 100.0 * used / lanes
