"""Device time of the period step's ``grad`` scope in a language-model
cell (each client's blocked forward and backward, SSD kernel and its
backward included) in milliseconds per simulated period:
``grad_ms_per_period``'s reading, imported, not copied."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from grad_ms_per_period import read   # noqa: E402,F401
