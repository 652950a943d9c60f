"""The FEEL period step's model FLOP utilization in a language-model cell,
in percent: ``step_mfu``'s reading (imported, not copied), with training
operations a sequence from the configuration's reference model
(``train_flops_per_example``: forward and backward of every position)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from step_mfu import read   # noqa: E402,F401
