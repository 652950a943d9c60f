"""Device time of the period step's ``sbc`` scope in a language-model cell
(each client's exact top-k threshold, ``sbc_stats`` and ``sbc_apply``, and
error feedback over every parameter) in milliseconds per simulated
period: ``sbc_ms_per_period``'s reading, imported, not copied."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from sbc_ms_per_period import read   # noqa: E402,F401
