"""The comparison catches a broken timed path, and the control.

Each run drives the rest of a benchmark run (``run.run_cell``: set-up,
window, comparison) on the CPU at a size a test holds, with the harness's
look for a chip skipped, and with the cell's own limits.  A fault is
planted in the program's period step (``faults.py``); the run must then
come out not correct, and a sound run correct.  The control (the
reference computed in bfloat16 in the program's place) must fail too.
"""
import argparse

import jax
import jax.numpy as jnp
import pytest

import faults
import judge
import run
import workload

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
CELLS = ("feel_mlp.fig45_gpu6",)


def _run_cell(root, name, trace=0):
    cell = workload.find_cell(root, name, root / "bench")
    args = argparse.Namespace(workload=name, seed=2**32 + 12345,
                              seconds=0.1, trace=trace)
    return run.run_cell(cell, args, jax.devices()[:1], PEAK,
                        metrics_dir=root / "bench" / "metrics",
                        trace_dir=root / ".bench_trace")


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", (None,) + faults.FAULTS)
def test_fault_makes_the_run_incorrect(tiny_root, cell, fault):
    root = tiny_root(cell)
    if fault is None:
        out, lines = _run_cell(root, cell)
    else:
        with faults.planted(fault):
            out, lines = _run_cell(root, cell)
    assert out["correct"] is (fault is None), lines
    assert list(out)[-1] == "checks"
    assert out["checks"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, cell):
    root = tiny_root(cell)
    c = workload.find_cell(root, cell, root / "bench")
    train, test = workload.make_data(c.config, 21)
    from repro.api import Experiment
    spans = workload.Spans()
    res = Experiment(train, test, workload.make_specs(
        c.config, c.traffic, 21)).run(
            int(c.traffic["periods"]),
            executor=workload.make_executor(c.traffic["executor"]))
    judged = judge.Judge(c, spans.records, res, 21, train.y)
    sound, _ = judge.verdict(judged.numbers(train, test), c.limits)
    control, lines = judge.verdict(
        judged.numbers(train, test, jnp.bfloat16, program=False), c.limits)
    assert sound
    assert not control, lines


def test_traced_run_reports_per_layer_metrics(tiny_root):
    cell = "feel_mlp.fig45_gpu6"
    out, _ = _run_cell(tiny_root(cell), cell, trace=1)
    assert {"plan_ms_per_period", "dispatch_ms_per_grid"} <= set(
        out["metrics"])
    # the CPU's trace has no device plane: the device readers find
    # nothing to read and the metrics are left out, never read as 0
    assert not {"step_mfu", "device_idle_share"} & set(out["metrics"])
    assert "breakdown" in out and "window_s" in out["device"]
