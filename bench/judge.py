"""The numbers that decide ``correct``, from one grid call's records.

For a sample of rows drawn from the seed, the plain reference
(``reference.py``) retrains each row from its seed on the row's host plan;
the program's loss and test-accuracy series and its final weights are
compared with the reference's:

* ``loss_gap`` — the largest |loss − reference loss| over the sampled
  rows and the compared periods;
* ``loss_gap_median`` — the median over the sampled rows of each row's
  largest |loss − reference loss|: steady from seed to seed where SBC's
  discrete choices (the kept entries, the sign group) flip on round-off
  in one row and move that row's loss alone;
* ``acc_gap`` — the same for test accuracy;
* ``change_gap`` — per weight leaf, the gap between the norms of the
  change the program and the reference made to it over the run, as a
  share of the reference's change of that leaf or of the median leaf,
  whichever is larger; the worst leaf of the worst row.  Only where every
  period is compared (the final weights are the run's);
* ``ledger_faults`` — violations of the host ledger's invariants, over
  every row (``reference.ledger_faults``).

A cell's limits file (``limits/<cell>.json``) names the numbers it
compares, each with its limit, and ``periods``: how many leading periods
of each row are compared (all, where it is absent).  Where training
amplifies round-off over a long horizon, only the leading periods carry a
reading that is steady from seed to seed (``PERF.md`` gives the look).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import reference
import workload

NUMBERS = ("loss_gap", "loss_gap_median", "acc_gap", "change_gap",
           "ledger_faults")


def compared(limits: dict) -> List[str]:
    """The numbers a cell's limits file holds a limit for, in order."""
    return [n for n in NUMBERS if n in limits]


def sample_rows(n_rows: int, n_sample: int, seed: int) -> List[int]:
    """``n_sample`` of ``n_rows`` row positions, drawn from the seed."""
    (s,) = workload.derived_seeds(seed, 1, workload.STREAM_SAMPLE)
    rng = np.random.default_rng(s)
    return sorted(rng.permutation(n_rows)[:min(n_sample, n_rows)].tolist())


class Judge:
    """Holds what the comparison needs after the program's state is
    freed: the host plans, the program's series and the sampled rows'
    final weights (on the host)."""

    def __init__(self, cell, records, results, seed: int,
                 train_labels: np.ndarray):
        self.cell = cell
        self.labels = np.asarray(train_labels)
        tr = cell.traffic
        if len(records) != 1:
            raise ValueError(f"expected one bucket, got {len(records)}")
        rec = records[0]
        self.rows = list(rec.plan.bucket.rows)
        self.arrays = workload.plan_arrays(rec.plan)
        self.series = tuple(np.asarray(a) for a in rec.series)
        self.results_times = np.asarray(results.times)
        self.results_gb = np.asarray(results.global_batch)
        # the bucket's row order is the results' row order for a grid
        # without duplicate specs
        self.sample = sample_rows(len(self.rows),
                                  int(tr["reference_rows"]), seed)
        self.cohort_size = (int(tr["sampling"]["size"])
                            if tr.get("sampling") else int(tr["fleet"]["k"]))
        horizon = self.arrays["batch"].shape[1]
        self.periods = min(int(cell.limits.get("periods", horizon)),
                           horizon)
        self.whole = self.periods == horizon
        # the final weights are the run's only where every period is
        # compared
        params = rec.handle.state.params
        self.final = [reference.leaves_by_path(
            jax.device_get(jax.tree_util.tree_map(lambda a: a[i], params)))
            for i in self.sample] if self.whole else []

    def numbers(self, train, test, dtype=jnp.float32,
                program: bool = True) -> Dict[str, float]:
        """Compare with the reference computed in ``dtype``.  With
        ``program=False`` the reference computed in ``dtype`` is compared
        with the float32 reference instead (the control)."""
        cfg, tr = self.cell.config, self.cell.traffic
        model = reference.config_module(cfg)
        train_in = model.train_inputs(cfg, train)
        test_in = model.test_inputs(cfg, test)
        run_ref = reference.make_trajectory(
            model, cfg, float(cfg["compression"]), bool(tr["compress"]),
            jnp.float32)
        run_dt = (run_ref if dtype == jnp.float32 else
                  reference.make_trajectory(
                      model, cfg, float(cfg["compression"]),
                      bool(tr["compress"]), dtype))
        n = self.periods
        loss_by = np.zeros(n)
        row_worst = []
        acc_by = np.zeros(n)
        change_gap, worst_leaf = 0.0, ""
        for j, i in enumerate(self.sample):
            seed = self.rows[i].seed
            params0 = model.init(cfg, seed)
            plan = {k: v[:n] for k, v in
                    reference.row_plan(self.arrays, i).items()}
            l_ref, a_ref, p_ref, first = jax.device_get(
                run_ref(params0, plan, train_in, test_in))
            if program:
                l_got, a_got = self.series[0][i][:n], self.series[1][i][:n]
                p_got = self.final[j] if self.whole else None
            else:
                l_got, a_got, p_got, _ = jax.device_get(
                    run_dt(params0, plan, train_in, test_in))
                p_got = reference.leaves_by_path(p_got)
            row_gap = np.abs(np.asarray(l_got, np.float64) - l_ref)
            loss_by = np.maximum(loss_by, row_gap)
            row_worst.append(float(row_gap.max()))
            acc_by = np.maximum(acc_by, np.abs(
                np.asarray(a_got, np.float64) - a_ref))
            if self.whole:
                gaps = reference.change_gaps(
                    reference.leaves_by_path(params0), p_got,
                    reference.leaves_by_path(p_ref),
                    {k: float(v) for k, v in
                     reference.leaves_by_path(first).items()})
                leaf, gap = max(gaps.items(), key=lambda kv: kv[1])
                if gap >= change_gap:
                    change_gap, worst_leaf = gap, leaf
        self.worst_leaf = worst_leaf
        self.by_period = {"loss_gap": loss_by.tolist(),
                          "acc_gap": acc_by.tolist()}
        self.ledger_lines = self.ledger()
        out = {"loss_gap": float(loss_by.max()),
               "loss_gap_median": float(np.median(row_worst)),
               "acc_gap": float(acc_by.max()),
               "ledger_faults": float(len(self.ledger_lines))}
        if self.whole:
            out["change_gap"] = change_gap
        return out

    def ledger(self) -> List[str]:
        parts = [reference.partition(r.spec.partition, self.labels,
                                     r.spec.k, r.seed) for r in self.rows]
        return reference.ledger_faults(
            self.arrays, parts, self.results_times, self.results_gb,
            int(self.cell.config["b_max"]), self.cohort_size,
            [r.spec.policy for r in self.rows],
            float(self.cell.traffic["base_lr"]),
            float(self.cell.config["lr_ref_batch"]))


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> tuple:
    """(correct, lines): every number the limits name is finite and at or
    under its limit."""
    ok, lines = True, []
    for name in compared(limits):
        v, lim = numbers.get(name, float("nan")), float(limits[name])
        good = bool(np.isfinite(v) and v <= lim)
        ok &= good
        lines.append(f"{name} {v!r} limit {lim!r} "
                     f"{'ok' if good else 'FAIL'}")
    return ok, lines
