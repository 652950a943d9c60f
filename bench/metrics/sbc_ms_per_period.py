"""Device time of the period step's ``sbc`` scope (``compress_dense``
under the client ``vmap``: the bisection threshold passes, sign groups,
binarization and error feedback) in milliseconds per simulated period of
a grid call: seconds of the device operations whose ``tf_op`` names the
scope (control flow that contains others left out), over (grid calls ×
periods).  A program whose operations carry no scope: no reading."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import progtrace   # noqa: E402


def read(ctx):
    return progtrace.scope_per_period(ctx, "sbc")
