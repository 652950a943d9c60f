"""Compile-hygiene audit: trace discipline + lowering hygiene.

Two surfaces:

* **Trace ledger** (:func:`audit_traces`) — consumes the engine's
  structured :func:`repro.fed.engine.trace_events` ledger and proves the
  one-trace-per-bucket contract: no (kind, cache-key, arg-signature)
  triple ever traces twice.  Chunked horizons legitimately trace once
  per distinct chunk *length* (different shapes → different programs);
  a duplicate triple is a retrace the jit cache should have absorbed —
  e.g. an argument donated/committed differently per call, or a
  non-hashable static arg defeating ``lru_cache``.
* **Jaxpr hygiene** (:func:`audit_jaxpr_hygiene`) — walks a lowered
  program (recursing into scan/jit/custom-call sub-jaxprs) and flags
  (a) 64-bit dtypes anywhere in the program — host planners work in
  float64 and must cross ``engine.host_to_device`` before dispatch —
  and (b) large constants folded into the jaxpr (captured arrays compile
  into the executable and defeat donation/caching; datasets must be
  passed as arguments).
"""
from __future__ import annotations

from collections import Counter
from typing import Optional

import numpy as np
from jax.extend.core import ClosedJaxpr

from repro.analysis.report import AuditReport, Severity

__all__ = ["audit_traces", "audit_jaxpr_hygiene", "iter_subjaxprs"]

# one 64-bit scalar is harmless; a folded dataset is not
_CONST_ELEMENT_LIMIT = 4096


def audit_traces(events=None, *, label: str = "trace-ledger",
                 expect_total: Optional[int] = None,
                 report: Optional[AuditReport] = None) -> AuditReport:
    """Audit a trace-event ledger for retraces.

    ``events`` defaults to the engine's full process ledger; pass a
    slice (``engine.trace_events()[mark:]``) to audit one run.
    ``expect_total`` additionally pins the exact number of traces (the
    per-Experiment contract: one per (bucket, chunk-length) program).
    """
    if report is None:
        report = AuditReport()
    if events is None:
        from repro.fed import engine
        events = engine.trace_events()
    counts = Counter(events)
    n_dup = 0
    for ev, n in counts.items():
        if n > 1:
            n_dup += n - 1
            report.add(
                "compile.retrace", Severity.ERROR, f"{label}:{ev.kind}",
                f"program {ev.kind}{ev.key} traced {n}x for identical "
                f"argument signature — the jit cache should have "
                f"absorbed {n - 1} of these; signature={ev.signature}")
    if expect_total is not None and len(events) != expect_total:
        report.add(
            "compile.trace-count", Severity.ERROR, label,
            f"expected exactly {expect_total} trace(s), ledger has "
            f"{len(events)}: {[(e.kind, e.key) for e in events]}")
    report.programs[label] = {
        "pass": "compile",
        "n_traces": len(events),
        "n_unique_programs": len(counts),
        "n_retraces": n_dup,
        "ok": n_dup == 0 and (expect_total is None
                              or len(events) == expect_total),
    }
    return report


def iter_subjaxprs(jaxpr, path: str = ""):
    """Yield (path, jaxpr) for a jaxpr and every nested sub-jaxpr."""
    yield path, jaxpr
    for i, eqn in enumerate(jaxpr.eqns):
        for key, val in eqn.params.items():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for j, v in enumerate(vals):
                inner = None
                if isinstance(v, ClosedJaxpr):
                    inner = v.jaxpr
                elif hasattr(v, "eqns") and hasattr(v, "invars"):
                    inner = v
                if inner is not None:
                    sub = f"{path}/{i}:{eqn.primitive.name}.{key}"
                    if len(vals) > 1:
                        sub += f"[{j}]"
                    yield from iter_subjaxprs(inner, sub)


def _closed_consts(jaxpr):
    """(path, const) pairs for every ClosedJaxpr constant in the tree."""
    stack = [("", jaxpr)]
    while stack:
        path, cj = stack.pop()
        if isinstance(cj, ClosedJaxpr):
            for i, c in enumerate(cj.consts):
                yield f"{path}.consts[{i}]", c
            inner = cj.jaxpr
        else:
            inner = cj
        for j, eqn in enumerate(inner.eqns):
            for key, val in eqn.params.items():
                vals = val if isinstance(val, (tuple, list)) else (val,)
                for v in vals:
                    if isinstance(v, ClosedJaxpr):
                        stack.append(
                            (f"{path}/{j}:{eqn.primitive.name}.{key}", v))


def audit_jaxpr_hygiene(closed: ClosedJaxpr, *, program: str = "program",
                        report: Optional[AuditReport] = None) -> AuditReport:
    """64-bit-leak and folded-constant audit over one lowered program."""
    if report is None:
        report = AuditReport()
    n_wide = 0
    n_vals = 0
    for path, jaxpr in iter_subjaxprs(closed.jaxpr):
        for var in (*jaxpr.invars, *jaxpr.constvars,
                    *(v for eqn in jaxpr.eqns for v in eqn.outvars)):
            aval = getattr(var, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is None:
                continue
            n_vals += 1
            dt = np.dtype(dtype)
            if dt.itemsize == 8 and dt.kind in "fiuc":
                n_wide += 1
                report.add(
                    "compile.x64-leak", Severity.ERROR,
                    f"{program}:{path or '/'}",
                    f"{dt} value inside the device program "
                    f"(shape {tuple(aval.shape)}) — host float64 planning "
                    "leaked past engine.host_to_device")
    n_large = 0
    for path, const in _closed_consts(closed):
        size = int(np.size(const))
        if size > _CONST_ELEMENT_LIMIT:
            n_large += 1
            nbytes = getattr(const, "nbytes", size * 8)
            report.add(
                "compile.folded-constant", Severity.WARN,
                f"{program}:{path}",
                f"constant of {size} elements ({nbytes} bytes) folded "
                "into the jaxpr — pass large arrays as arguments so "
                "they are donated/shared, not baked into the executable")
    report.programs[f"{program}/hygiene"] = {
        "pass": "compile",
        "n_values_checked": n_vals,
        "n_x64_leaks": n_wide,
        "n_large_constants": n_large,
        "ok": n_wide == 0,
    }
    return report
