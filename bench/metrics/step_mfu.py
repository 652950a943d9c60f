"""The FEEL period step's model FLOP utilization, in percent: training
(forward and backward) operations of the examples whose gradients were
aggregated — Σ B_k of the participating clients; padded slots, masked
lanes, the loss passes before and after the update and the evaluation
are not counted — over the device time of the traced window (the union
of the device's operation intervals, ``busy_s``, summed over the chips)
at the chips' bf16 peak.  Host time, in which the device runs nothing,
is left out: ``device_idle_share`` reads it.  Operations per example come
from the configuration's reference model (``reference.config_module``)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import reference   # noqa: E402


def read(ctx):
    red = ctx["reduction"]
    if red.busy_s <= 0:
        return None
    cfg = ctx["config"]
    per_ex = reference.config_module(cfg).train_flops_per_example(cfg)
    flops = ctx["per_call"]["examples"] * ctx["n_calls"] * per_ex
    device_s = red.busy_s * len(red.devices)
    return 100.0 * flops / (device_s * ctx["peak"]["bf16_flops_per_s"])
