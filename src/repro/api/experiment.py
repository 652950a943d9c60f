"""The declarative experiment driver: specs in, named Results out.

    from repro.api import AsyncExecutor, Experiment, ScenarioSpec, grid

    study = grid(ScenarioSpec(fleet=fleet, name="cpu6", seeds=range(8)),
                 policy=("proposed", "online", "full"),
                 **{"cell.radius_m": [100.0, 200.0, 400.0]})
    res = Experiment(data, test, study).run(periods=100,
                                            executor=AsyncExecutor())
    res.sel(policy="proposed", cell_radius_m=200.0).speed(0.6)

``run`` lowers the whole grid through ``api.lowering``: rows (spec × seed)
are deduplicated (a spec declared twice is computed once and fanned back
out) and grouped into shape-compatible buckets, each bucket executing as
ONE jitted ``vmap(lax.scan)`` over the flattened (scenario × seed) axis.
*How* buckets are scheduled is the executor's policy (``api.executor``):
serial reference, async cross-bucket pipelining, or mesh-sharded — all
bit-identical in results.  ``stream`` yields cumulative partial
``Results`` as each bucket collects, for long grids where early buckets
are worth looking at before the last one retires.
"""
from __future__ import annotations

from dataclasses import dataclass, replace as _dc_replace
from typing import Iterator, List, Optional, Sequence

import numpy as np

from repro import obs
from repro.api.executor import Executor, SerialExecutor
from repro.api.lowering import Bucket, group_rows
from repro.api.results import (COORD_NAMES, Results, ResultsBuilder,
                               assign_row_coords, empty_coords)
from repro.api.spec import ScenarioSpec
from repro.data.pipeline import ClassificationData


@dataclass
class Experiment:
    """A family of scenarios over one dataset, lowered bucket-by-bucket.

    ``specs`` may be any spec sequence, including a
    :class:`repro.api.study.Study` — swept study axes then surface as
    extra ``Results`` coordinates.  Device placement is the executor's
    job: ``run(executor=MeshExecutor(...))`` (the former
    ``Experiment(mesh=...)`` shim is gone).
    """
    data: ClassificationData
    test: ClassificationData
    specs: Sequence[ScenarioSpec]

    def lower(self, replan: Optional[int] = None,
              bands: bool = False) -> List[Bucket]:
        """The bucketed row plan (introspection / tests): which rows share
        a compiled program, in execution order.  Duplicate (spec, seed)
        rows collapse onto one computed row (``Row.indices`` fans out).
        ``replan`` applies the run-level closed-loop override and
        ``bands`` the power-of-two K-band sub-bucketing (see
        :meth:`run`)."""
        return group_rows(self.specs, replan=replan, bands=bands)

    def run(self, periods: int, executor: Optional[Executor] = None,
            replan: Optional[int] = None, audit: bool = False,
            bands: bool = False) -> Results:
        """Run the whole grid and return the complete ``Results``.

        ``replan=R`` turns every FEEL-family bucket closed-loop for this
        run: horizons execute as R-period chunks and each chunk's
        realized loss decays update the ξ estimator before the next chunk
        is planned (Algorithm 1 with live feedback — overriding any
        per-spec ``ScenarioSpec.replan``).  Dev-family buckets have no ξ
        loop and ignore the override.

        ``audit=True`` runs the static-analysis passes alongside the
        computation (see :mod:`repro.analysis`): the padding-taint
        certificate and compile-hygiene checks over every bucket's
        lowered program (probed under ``engine.suspend_trace_count`` —
        no device work, but host planning runs once more per bucket),
        the determinism lint, and a trace-ledger audit scoped to this
        run proving zero retraces across chunks and replan rounds.  The
        report attaches as ``Results.audit``; error-severity findings
        raise :class:`repro.analysis.AuditError`.  Audit composes with
        any executor — the passes inspect programs and ledgers, not the
        execution schedule.

        ``bands=True`` splits each bucket by power-of-two K band
        (``repro.topology.band_width``) so a mixed-K grid pads each row
        to its band instead of the grid max — one compiled program per
        band, bit-identical results (the band is invisible to
        ``Results``), order-of-magnitude less padded compute when fleet
        sizes span decades.
        """
        if audit:
            from repro.fed import engine as _engine
            mark = len(_engine.trace_events())
        builder = None
        for builder in self._collected(periods, executor, replan,
                                       bands=bands):
            pass
        with obs.span("repro.run.results"):
            res = builder.build()
        if audit:
            report = self._audit(periods, replan, mark, bands=bands)
            res = _dc_replace(res, audit=report)
            report.raise_on_error()
        return res

    def _audit(self, periods: int, replan: Optional[int], mark: int,
               bands: bool = False):
        """The ``run(audit=True)`` pass bundle (see :mod:`repro.analysis`)."""
        from repro.analysis import compile_audit, determinism, taint
        from repro.analysis.report import AuditReport
        from repro.api import lowering
        from repro.fed import engine as _engine

        report = AuditReport()
        compile_audit.audit_traces(_engine.trace_events()[mark:],
                                   label="trace-ledger", report=report)
        for bucket in self.lower(replan=replan, bands=bands):
            plan = lowering.plan_bucket(bucket, self.data, periods)
            traced = lowering.trace_bucket(plan, self.data, self.test)
            taint.analyze_jaxpr(traced.closed, traced.in_labels,
                                traced.out_contracts,
                                program=traced.program, report=report)
            compile_audit.audit_jaxpr_hygiene(
                traced.closed, program=traced.program, report=report)
        determinism.lint_sources(report=report)
        return report

    def stream(self, periods: int, executor: Optional[Executor] = None,
               replan: Optional[int] = None,
               bands: bool = False) -> Iterator[Results]:
        """Yield a cumulative partial ``Results`` after each bucket
        collection (the final yield is the complete result).

        With an :class:`~repro.api.executor.AsyncExecutor` every bucket
        is already dispatched before the first yield, so consuming the
        stream slowly does not serialize the device work.
        """
        for builder in self._collected(periods, executor, replan,
                                       bands=bands):
            yield builder.partial()

    def _collected(self, periods: int, executor: Optional[Executor],
                   replan: Optional[int] = None, bands: bool = False
                   ) -> Iterator[ResultsBuilder]:
        """Drive the executor, yielding the builder after each bucket
        lands (``run`` assembles once at the end; ``stream`` snapshots a
        partial per yield).  Grouping and result assembly run as the
        ``repro.run.group`` and ``repro.run.results`` spans."""
        with obs.span("repro.run.group"):
            buckets = self.lower(replan=replan, bands=bands)
            if not buckets:
                raise ValueError("Experiment has no specs")
            if executor is None:
                executor = SerialExecutor()
            builder = ResultsBuilder(coords=self._coords(buckets),
                                     n_rows=self._n_rows(buckets),
                                     n_buckets=len(buckets))
        for bucket, (bl, ba, bt, bg) in executor.execute(
                buckets, self.data, self.test, periods):
            with obs.span("repro.run.results"):
                idx = np.array([i for row in bucket.rows
                                for i in row.indices], np.int64)
                take = np.array([j for j, row in enumerate(bucket.rows)
                                 for _ in row.indices], np.int64)
                builder.add_rows(idx, bl[take], ba[take], bt[take],
                                 bg[take])
            yield builder

    @staticmethod
    def _n_rows(buckets: Sequence[Bucket]) -> int:
        return sum(len(r.indices) for b in buckets for r in b.rows)

    def _coords(self, buckets: Sequence[Bucket]):
        """Per-output-row coordinate columns: the standard labels plus, for
        Study specs, one column per swept axis (``axis_coords``)."""
        n_rows = self._n_rows(buckets)
        axis_coords = getattr(self.specs, "axis_coords", None)
        extra = [n for n in getattr(self.specs, "coord_names", ())
                 if n not in COORD_NAMES] if axis_coords else []
        coords = empty_coords(n_rows, extra=extra)
        for bucket in buckets:
            for row in bucket.rows:
                axes = axis_coords(row.spec) if axis_coords else {}
                for i in row.indices:
                    assign_row_coords(coords, i, row.spec, row.seed)
                    for name in extra:
                        if name in axes:
                            coords[name][i] = axes[name]
        return coords
