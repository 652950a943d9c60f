"""Host-to-device upload per grid call, in MB (10^6 bytes): the ``bytes``
stat of the program's ``repro.dispatch.upload`` spans (every host array
that ``engine.host_to_device`` copied in the dispatch: dataset, test set,
schedules, masks) summed over the window, over its grid calls.  A program
without the span: no reading."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import progtrace   # noqa: E402


def read(ctx):
    prog = progtrace.from_ctx(ctx)
    if prog is None:
        return None
    total = prog.stat_sum("repro.dispatch.upload", "bytes")
    if total is None:
        return None
    return total / 1e6 / ctx["n_calls"]
