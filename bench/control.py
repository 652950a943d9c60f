"""Readings from which a cell's limits are set, on the chip at the cell's
own size.  The benchmark's own runs never run this.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... \
        [--control] [--fault <name>]

For each seed, in one process: the cell's data and grid from the seed,
one grid call through the timed path (``Experiment.run`` with the
benchmark's executor), and the numbers of ``judge.py`` against the
float32 reference — the lower readings.  ``--control`` also compares the
reference computed in bfloat16 with the float32 one (the control, the
nearest precision below the configuration's float32).  ``--fault`` plants
one of ``faults.FAULTS`` in the program first.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import workload  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--periods", type=int, default=None,
                    help="compare this many leading periods in place of "
                         "the limits file's")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import faults
    import judge
    from repro.api import Experiment
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    use_compile_cache()
    cell = workload.find_cell(ROOT, args.workload)
    if args.periods is not None:
        cell.limits = dict(cell.limits, periods=args.periods)
    cfg, tr = cell.config, cell.traffic
    workload.set_precision(cfg)
    periods = int(tr["periods"])
    spans = workload.Spans()
    ctx = (faults.planted(args.fault) if args.fault
           else contextlib.nullcontext())
    with ctx:
        for seed in args.seeds:
            t0 = time.perf_counter()
            train, test = workload.make_data(cfg, seed)
            exp = Experiment(train, test, workload.make_specs(cfg, tr, seed))
            spans.records = []
            res = exp.run(periods, executor=workload.make_executor(
                tr["executor"]))
            judged = judge.Judge(cell, spans.records, res, seed, train.y)
            spans.records = []
            del res, exp
            gc.collect()
            line = {"seed": seed, "fault": args.fault,
                    "program": judged.numbers(train, test),
                    "worst_leaf": judged.worst_leaf,
                    "program_by_period": judged.by_period}
            if args.control:
                line["control"] = judged.numbers(train, test, jnp.bfloat16,
                                                 program=False)
                line["control_worst_leaf"] = judged.worst_leaf
                line["control_by_period"] = judged.by_period
            line["wall_s"] = time.perf_counter() - t0
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
