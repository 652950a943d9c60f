"""The static-analysis subsystem (repro.analysis): padding-taint
property tests on synthetic jaxprs (mask-dominated reductions certify,
the seeded poisoned-padding mutant is rejected), the acceptance sweep
(all four Table-II scheme programs + the ragged users=[4,8,16] padded
program certify), compile hygiene (x64 leak, folded constants, trace
ledger), the determinism lint, the ``Experiment.run(audit=True)`` hook,
the ``no_retrace`` guard, and the host↔device dtype boundary."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import AuditError, AuditReport, Severity
from repro.analysis import compile_audit, determinism, taint
from repro.analysis.report import Finding
from repro.analysis.taint import LaneLabel, NO_LABEL, OutContract
from repro.api import Experiment, ScenarioSpec, SerialExecutor, grid
from repro.api.lowering import group_rows, plan_bucket, trace_bucket
from repro.core import DeviceProfile
from repro.data.pipeline import ClassificationData
from repro.fed import engine
from repro.testing import no_retrace
from repro.testing.proptest import given, settings, strategies as st

# distinctive shapes (no other test module uses dim=20 / hidden=24 /
# b_max=10) so the lru-cached engine programs are fresh here and the
# trace assertions below are exact
DIM, HIDDEN, BMAX = 20, 24, 10
PERIODS = 3


@pytest.fixture(scope="module")
def dataset():
    full = ClassificationData.synthetic(n=260, dim=DIM, seed=0, spread=6.0)
    return full.split(60)


def _fleet(k):
    return tuple(DeviceProfile(kind="cpu", f_cpu=(0.7 + 0.35 * (i % 3)) * 1e9)
                 for i in range(k))


def _spec(k, **kw):
    kw.setdefault("name", f"K{k}")
    kw.setdefault("b_max", BMAX)
    kw.setdefault("base_lr", 0.15)
    kw.setdefault("hidden", HIDDEN)
    kw.setdefault("seeds", (0,))
    return ScenarioSpec(fleet=_fleet(k), **kw)


def _certify(specs, users=None):
    """Lower the specs' buckets and run the taint pass over each."""
    full = ClassificationData.synthetic(n=260, dim=DIM, seed=0, spread=6.0)
    data, test = full.split(60)
    report = AuditReport()
    rows = grid(specs[0], users=users) if users else specs
    names = []
    for bucket in group_rows(rows):
        plan = plan_bucket(bucket, data, PERIODS)
        traced = trace_bucket(plan, data, test)
        taint.analyze_jaxpr(traced.closed, traced.in_labels,
                            traced.out_contracts, program=traced.program,
                            report=report)
        names.append(traced.program)
    return report, names


# ---------------------------------------------------------------------------
# taint lattice: property tests on synthetic jaxprs
# ---------------------------------------------------------------------------


def _analyze(fn, args, labels, contracts=None, program="synthetic"):
    report = AuditReport()
    closed = jax.make_jaxpr(fn)(*args)
    taint.analyze_jaxpr(closed, labels, contracts, program=program,
                        report=report)
    return report


@settings(max_examples=30, deadline=None)
@given(k=st.integers(2, 6), feat=st.integers(1, 6),
       op=st.sampled_from(["sum", "reshape-sum", "dot"]))
def test_prop_mask_dominated_reductions_certify(k, feat, op):
    """Any cross-user reduction whose operand is mask-multiplied (padded
    lanes provably the monoid identity) certifies, including through a
    reshape that merges the user axis and through dot contraction."""
    def good(x, mask):
        xm = x * mask[:, None]
        if op == "sum":
            return xm.sum(axis=0) / (mask.sum() + 1.0)
        if op == "reshape-sum":
            return xm.reshape(-1).sum() / (mask.sum() + 1.0)
        return jnp.dot(mask, x)
    report = _analyze(
        good, (np.zeros((k, feat), np.float32), np.zeros(k, np.float32)),
        [LaneLabel(0), LaneLabel(0, 0.0)])
    assert report.ok, [f.detail for f in report.errors()]
    summary = report.programs["synthetic"]
    assert summary["n_certified_reductions"] >= 1
    assert summary["n_poisoned_outputs"] == 0


@settings(max_examples=30, deadline=None)
@given(k=st.integers(2, 6), feat=st.integers(1, 6),
       op=st.sampled_from(["sum", "reshape-sum", "dot"]))
def test_prop_poisoned_padding_mutant_rejected(k, feat, op):
    """The seeded mutant — the mask dropped from one reduction — must
    fail loudly with an unmasked-reduction (or contraction) finding."""
    def poisoned(x, mask):
        if op == "sum":
            return x.sum(axis=0) / (mask.sum() + 1.0)
        if op == "reshape-sum":
            return x.reshape(-1).sum() / (mask.sum() + 1.0)
        return jnp.dot(jnp.ones(k, np.float32) * 1.0 + 0.0 * mask, x)
    report = _analyze(
        poisoned, (np.zeros((k, feat), np.float32),
                   np.zeros(k, np.float32)),
        [LaneLabel(0), LaneLabel(0, 0.0)])
    assert not report.ok
    checks = {f.check for f in report.errors()}
    assert checks & {"taint.unmasked-reduction",
                     "taint.unmasked-contraction"}, checks


def test_identityless_reduction_never_certifies():
    """Known(0) lanes prove a SUM safe but not a MAX (identity is -inf):
    the monoid rule must reject op/identity mismatches."""
    report = _analyze(
        lambda x, m: (x * m[:, None]).max(axis=0),
        (np.zeros((4, 3), np.float32), np.zeros(4, np.float32)),
        [LaneLabel(0), LaneLabel(0, 0.0)])
    assert not report.ok
    assert any(f.check == "taint.unmasked-reduction"
               for f in report.errors())


def test_output_contract_violation_detected():
    """An output contracted to Known(0) on padded lanes fails when the
    program leaves those lanes variant."""
    report = _analyze(
        lambda x: x * 2.0, (np.zeros((4, 3), np.float32),),
        [LaneLabel(0)], contracts={0: OutContract(axis=0, value=0.0)})
    assert not report.ok
    assert any(f.check == "taint.output-contract" for f in report.errors())


def test_poisoned_output_detected():
    """A poisoned value reaching an output (even without a reduction) is
    an error: garbage escapes to the host."""
    report = _analyze(
        lambda x, m: x.sum(axis=0),
        (np.zeros((4, 3), np.float32), np.zeros(4, np.float32)),
        [LaneLabel(0), LaneLabel(0, 0.0)])
    assert any(f.check == "taint.poisoned-output" for f in report.errors())


def test_same_lane_cancellation():
    """The local-steps delta rule: broadcast(p) - p_k is Known(0) on
    every lane, so its cross-user sum certifies with no mask at all."""
    def delta(p, pk):
        return (pk - p[None, :]).sum(axis=0)
    report = _analyze(
        delta, (np.zeros(3, np.float32), np.zeros((4, 3), np.float32)),
        [NO_LABEL, LaneLabel(0, "variant")])
    # pk's lanes are variant, yet pk - broadcast(p) of ITSELF cancels
    # only when both sides alias; here they don't — expect failure...
    assert not report.ok


def test_same_lane_cancellation_through_broadcast():
    """...but when the padded lanes of pk provably EQUAL the broadcast
    source (the Same lattice element), the difference is Known(0)."""
    def delta(p):
        pk = jnp.broadcast_to(p[None, :], (4, 3))
        return (pk - p[None, :]).sum(axis=0)
    report = _analyze(delta, (np.zeros(3, np.float32),), [NO_LABEL])
    assert report.ok, [f.detail for f in report.errors()]


# ---------------------------------------------------------------------------
# acceptance: the real bucket programs certify
# ---------------------------------------------------------------------------


def test_table2_scheme_programs_certify():
    """ISSUE-6 acceptance: the four Table-II scheme programs (feel ==
    gradient_fl+SBC, the uncompressed gradient_fl variant, individual,
    model_fl) all pass the taint certificate with certified reductions."""
    specs = [_spec(4, scheme="feel"),
             _spec(4, scheme="feel", compress=False),
             _spec(4, scheme="individual"),
             _spec(4, scheme="model_fl")]
    report, names = _certify(specs)
    assert report.ok, [f.detail for f in report.errors()]
    assert len(names) == 4
    for name in names:
        summary = report.programs[name]
        assert summary["ok"], name
        assert summary["n_certified_reductions"] >= 1, name
        assert summary["n_poisoned_outputs"] == 0, name


def test_ragged_users_program_certifies():
    """ISSUE-6 acceptance: the ONE padded program behind the ragged
    users=[4,8,16] sweep certifies — the masking is proven for every
    fleet size the program will ever run at."""
    report, names = _certify([_spec(4, scheme="feel", seeds=(0,))],
                             users=[4, 8, 16])
    assert len(names) == 1                        # one bucket, k_pad=16
    assert report.ok, [f.detail for f in report.errors()]
    assert report.programs[names[0]]["n_certified_reductions"] >= 1


def test_feel_bucket_carries_residual_contract(dataset):
    """trace_bucket pins the SBC residual carry to Known(0) on padded
    lanes (the chunk-resumption induction) — the contract must exist and
    must hold."""
    data, test = dataset
    bucket = group_rows([_spec(3, scheme="feel")])[0]
    plan = plan_bucket(bucket, data, PERIODS)
    traced = trace_bucket(plan, data, test)
    assert traced.out_contracts                    # non-empty for FEEL
    assert all(c.axis == 1 and c.value == 0.0
               for c in traced.out_contracts.values())
    report = taint.analyze_jaxpr(traced.closed, traced.in_labels,
                                 traced.out_contracts,
                                 program=traced.program)
    assert report.ok, [f.detail for f in report.errors()]


# ---------------------------------------------------------------------------
# compile hygiene
# ---------------------------------------------------------------------------


def test_trace_ledger_flags_retrace_and_count():
    ev = engine.TraceEvent("feel", (1, True), (("f32", (2, 3)),))
    ok = compile_audit.audit_traces([ev], label="t1", expect_total=1)
    assert ok.ok and ok.programs["t1"]["n_retraces"] == 0
    bad = compile_audit.audit_traces([ev, ev], label="t2")
    assert not bad.ok
    assert any(f.check == "compile.retrace" for f in bad.errors())
    miscount = compile_audit.audit_traces([ev], label="t3", expect_total=2)
    assert any(f.check == "compile.trace-count" for f in miscount.errors())


def test_hygiene_flags_x64_leak():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: x * 2.0)(np.float64(1.0))
    report = compile_audit.audit_jaxpr_hygiene(closed, program="x64")
    assert not report.ok
    assert any(f.check == "compile.x64-leak" for f in report.errors())


def test_hygiene_flags_folded_constant():
    big = np.zeros(5000, np.float32)
    closed = jax.make_jaxpr(lambda x: x + jnp.asarray(big))(
        np.float32(1.0))
    report = compile_audit.audit_jaxpr_hygiene(closed, program="folded")
    assert report.ok                               # WARN, not ERROR
    assert any(f.check == "compile.folded-constant"
               for f in report.warnings())


def test_real_programs_pass_hygiene(dataset):
    data, test = dataset
    report = AuditReport()
    for bucket in group_rows([_spec(3, scheme="feel"),
                              _spec(3, scheme="individual")]):
        plan = plan_bucket(bucket, data, PERIODS)
        traced = trace_bucket(plan, data, test)
        compile_audit.audit_jaxpr_hygiene(traced.closed,
                                          program=traced.program,
                                          report=report)
    assert report.ok, [f.detail for f in report.errors()]


# ---------------------------------------------------------------------------
# determinism lint
# ---------------------------------------------------------------------------


def test_determinism_lint_on_library_sources():
    """The repo's host planning passes the lint with zero errors; the
    one known PRNG seed-sharing group surfaces as an advisory WARN."""
    report = determinism.lint_sources()
    assert not report.errors(), [f.detail for f in report.errors()]
    assert report.programs["determinism-lint"]["ok"]
    assert any(f.check == "det.prng-stream-collision"
               for f in report.warnings())


def test_determinism_lint_catches_unseeded_cumsum(tmp_path):
    (tmp_path / "repro").mkdir()
    (tmp_path / "repro" / "bad.py").write_text(
        "import numpy as np\n"
        "def ledger(x, offset):\n"
        "    return np.cumsum(x) + offset\n")
    report = determinism.lint_sources(root=tmp_path / "repro")
    assert any(f.check == "det.unseeded-cumsum" for f in report.errors())


# ---------------------------------------------------------------------------
# the run(audit=True) hook and the report surface
# ---------------------------------------------------------------------------


def test_run_audit_attaches_clean_report(dataset):
    """run(audit=True) on a chunked closed-loop grid: Results.audit is a
    passing AuditReport whose scoped trace ledger proves zero retraces
    across chunks and replan rounds."""
    data, test = dataset
    specs = [_spec(3, scheme="feel", seeds=(0, 1)),
             _spec(3, scheme="individual")]
    res = Experiment(data, test, specs).run(
        periods=PERIODS, executor=SerialExecutor(), replan=2, audit=True)
    report = res.audit
    assert isinstance(report, AuditReport) and report.ok
    ledger = report.programs["trace-ledger"]
    assert ledger["n_retraces"] == 0
    assert ledger["n_traces"] == ledger["n_unique_programs"]
    taint_progs = [p for p in report.programs.values()
                   if p["pass"] == "taint"]
    assert taint_progs and all(p["ok"] for p in taint_progs)
    # the report survives row selection
    assert res.sel(scheme="individual").audit is report
    # ...and serializes
    js = report.to_json()
    assert js["ok"] and js["programs"]["trace-ledger"]["n_retraces"] == 0


def test_audit_error_raises_with_findings():
    report = AuditReport()
    report.add("taint.unmasked-reduction", Severity.ERROR, "x", "boom")
    assert not report.ok
    with pytest.raises(AuditError):
        report.raise_on_error()
    f = report.findings[0]
    assert isinstance(f, Finding) and f.to_json()["severity"] == "error"


def test_audit_cli_static_passes(tmp_path):
    """The packaged CLI (static passes on a reduced grid) exits 0 and
    writes the machine-readable report artifact."""
    from repro.analysis.audit import main
    out = tmp_path / "AUDIT_report.json"
    rc = main(["--out", str(out), "--users", "3,5", "--periods", "2",
               "--skip-run"])
    assert rc == 0 and out.exists()
    import json
    js = json.loads(out.read_text())
    assert js["ok"] and js["n_errors"] == 0


# ---------------------------------------------------------------------------
# no_retrace guard + dtype boundary
# ---------------------------------------------------------------------------


def test_no_retrace_counts_and_passes(dataset):
    data, test = dataset
    exp = Experiment(data, test, [_spec(3, scheme="model_fl",
                                       seeds=(0,))])
    with no_retrace(expect=1):                    # cold: exactly one trace
        exp.run(periods=PERIODS)
    with no_retrace():                            # warm: zero traces
        exp.run(periods=PERIODS)


def test_no_retrace_fails_on_unexpected_trace(dataset):
    data, test = dataset
    exp = Experiment(data, test, [_spec(5, scheme="model_fl",
                                       seeds=(0,))])
    with pytest.raises(AssertionError, match="trace"):
        with no_retrace():                        # cold path declared warm
            exp.run(periods=PERIODS)


def test_host_to_device_casts_and_gate_rejects_x64():
    tree = {"a": np.arange(4, dtype=np.float64),
            "b": np.arange(4, dtype=np.int64),
            "c": np.ones(2, dtype=np.bool_)}
    cast = engine.host_to_device(tree)
    assert cast["a"].dtype == jnp.float32
    assert cast["b"].dtype == jnp.int32
    assert cast["c"].dtype == jnp.bool_
    engine.assert_device_safe(cast, "test")       # casts pass the gate
    with pytest.raises(TypeError, match="float64"):
        engine.assert_device_safe({"x": np.zeros(3, np.float64)}, "test")


# ---------------------------------------------------------------------------
# taint lattice: per-primitive handler battery (synthetic jaxprs)
# ---------------------------------------------------------------------------


def _ok(fn, args, labels, program="prim"):
    report = _analyze(fn, args, labels, program=program)
    assert report.ok, [f.detail for f in report.errors()]
    return report


def _fails(fn, args, labels, check):
    report = _analyze(fn, args, labels)
    assert not report.ok
    assert any(f.check == check for f in report.errors()), \
        {f.check for f in report.errors()}
    return report


_X = np.zeros((4, 3), np.float32)
_M = np.zeros(4, np.float32)
_XM_LABELS = [LaneLabel(0), LaneLabel(0, 0.0)]


def test_prim_where_mask_certifies():
    """select_n with a Known-lane predicate picks that case: the
    jnp.where masking idiom certifies like w*=active does."""
    _ok(lambda x, m: jnp.where(m[:, None] > 0, x, 0.0).sum(axis=0),
        (_X, _M), _XM_LABELS)


def test_prim_clamp_convert_preserve_known_zero():
    _ok(lambda x, m: jnp.clip(x * m[:, None], 0.0, 1.0).sum(axis=0),
        (_X, _M), _XM_LABELS)
    _ok(lambda x, m: (x * m[:, None]).astype(jnp.int32).sum(axis=0),
        (_X, _M), _XM_LABELS)


def test_prim_structural_ops_preserve_lanes():
    """flip/pad/slice/dynamic-slice/concat on non-user axes keep the
    padded-lane facts; the downstream reduction still certifies."""
    _ok(lambda x, m: jnp.flip(x * m[:, None], axis=1).sum(axis=0),
        (_X, _M), _XM_LABELS)
    _ok(lambda x, m: jnp.pad(x * m[:, None],
                             ((0, 0), (1, 1))).sum(axis=0),
        (_X, _M), _XM_LABELS)
    _ok(lambda x, m: (x * m[:, None])[:, 1:].sum(axis=0),
        (_X, _M), _XM_LABELS)
    _ok(lambda x, m: jax.lax.dynamic_slice(
            x * m[:, None], (0, 0), (4, 2)).sum(axis=0),
        (_X, _M), _XM_LABELS)
    _ok(lambda x, m: jnp.concatenate(
            [x * m[:, None], x * m[:, None]], axis=1).sum(axis=0),
        (_X, _M), _XM_LABELS)


def test_prim_split_transfers_like_slices():
    """``jnp.split`` (the ``split`` primitive) keeps the lane facts off the
    split axis; cut along the user axis, only Known(0) lanes survive."""
    _ok(lambda x, m: sum(p.sum(axis=0)
                         for p in jnp.split(x * m[:, None], 3, axis=1)),
        (_X, _M), _XM_LABELS)
    _ok(lambda x, m: sum(p.sum(axis=0)
                         for p in jnp.split(x * m[:, None], 2, axis=0)),
        (_X, _M), _XM_LABELS)
    _fails(lambda x, m: sum(p.sum(axis=0) for p in jnp.split(x, 2, axis=0)),
           (_X, _M), _XM_LABELS, "taint.unmasked-reduction")


def test_prim_dynamic_update_slice():
    _ok(lambda x, m: jax.lax.dynamic_update_slice(
            x * m[:, None], jnp.zeros((4, 1), jnp.float32),
            (0, 0)).sum(axis=0),
        (_X, _M), _XM_LABELS)


def test_prim_sort_topk_within_lane_ok_across_lanes_flagged():
    # a within-lane sort keeps the user digits but conservatively drops
    # Known(0): no cross-lane finding, yet downstream sums won't certify
    report = _analyze(
        lambda x, m: jnp.sort(x * m[:, None], axis=1).sum(axis=0),
        (_X, _M), _XM_LABELS)
    assert not any(f.check == "taint.sort-over-user-axis"
                   for f in report.findings)
    assert not report.ok  # conservative lanes degrade → unmasked
    _fails(lambda x, m: jnp.sort(x, axis=0),
           (_X, _M), _XM_LABELS, "taint.sort-over-user-axis")
    _fails(lambda x, m: jax.lax.top_k(x.T, 2)[0],
           (_X, _M), _XM_LABELS, "taint.topk-over-user-axis")


def test_prim_cumsum_within_lane_ok_over_user_axis_flagged():
    _ok(lambda x, m: jnp.cumsum(x * m[:, None], axis=1).sum(axis=0),
        (_X, _M), _XM_LABELS)
    _fails(lambda x, m: jnp.cumsum(x, axis=0),
           (_X, _M), _XM_LABELS, "taint.cumulative-over-user-axis")


def test_prim_gather_within_lane_ok_over_user_axis_flagged():
    idx = np.array([2, 0], np.int32)
    _ok(lambda x, m: (x * m[:, None])[:, idx].sum(axis=0),
        (_X, _M), _XM_LABELS)
    _fails(lambda x, m: x[jnp.array([0, 1]), :],
           (_X, _M), _XM_LABELS, "taint.gather-over-user-axis")


def test_prim_scatter_add_across_user_lanes_flagged():
    _fails(lambda x, m: jnp.zeros((6, 3), np.float32)
                           .at[jnp.array([1, 3, 0, 2])].add(x),
           (_X, _M), _XM_LABELS, "taint.scatter-across-user-axis")


def test_prim_cond_joins_branches():
    _ok(lambda x, m: jax.lax.cond(
            (m.sum() > 0), lambda v: v * 2.0, lambda v: v * 3.0,
            x * m[:, None]).sum(axis=0),
        (_X, _M), _XM_LABELS)


def test_prim_scan_over_user_axis_flagged():
    _fails(lambda x, m: jax.lax.scan(
               lambda c, xi: (c + xi.sum(), c), 0.0, x)[0],
           (_X, _M), _XM_LABELS, "taint.scan-over-user-axis")


def test_prim_dot_free_user_axis_maps_to_output():
    """User axis as a FREE (non-contracted) dot dimension: the output
    inherits the digit and Known(0) lanes, so the later reduction over
    it still certifies."""
    w = np.ones((3, 5), np.float32)
    _ok(lambda x, m: ((x * m[:, None]) @ w).sum(axis=0),
        (_X, _M), _XM_LABELS)


def test_prim_custom_vjp_recurses():
    @jax.custom_vjp
    def f(v):
        return v * 2.0

    f.defvjp(lambda v: (v * 2.0, None), lambda _, g: (g * 2.0,))
    _ok(lambda x, m: f(x * m[:, None]).sum(axis=0),
        (_X, _M), _XM_LABELS)
