"""The readers of the program's spans and scopes, on a small trace recorded
on one TPU v5e chip by ``record_program_trace.py`` (committed as
``data/program.xplane.pb``): inside the host span ``window`` one FEEL
bucket of 2 rows (K = 2, SBC on) trained for 3 periods.  Every number is
checked against a sum worked out here by another path: the span totals
from ``ProfileData`` directly, the device operations from
``tracefile.reduce``, the bytes from the recorded shapes.  The trace of a
program without spans (``data/small.xplane.pb``) reads nothing."""
import os
from pathlib import Path

import pytest

import progtrace
import record_program_trace as rec
import run
import tracefile

DATA = Path(__file__).resolve().parent / "data"
PROGRAM = DATA / "program.xplane.pb"
SMALL = DATA / "small.xplane.pb"
NEW = ("plan_channel_ms_per_period", "plan_solve_ms_per_period",
       "plan_schedule_ms_per_period", "upload_mb_per_grid",
       "grad_ms_per_period", "sbc_ms_per_period", "eval_ms_per_period",
       "active_lane_share")


def _ctx(path):
    red = tracefile.reduce(tracefile.load(str(path)))
    return {"trace": str(path), "reduction": red, "n_calls": 1,
            "per_call": {"periods": rec.PERIODS}}


@pytest.fixture(scope="module")
def ctx():
    return _ctx(PROGRAM)


@pytest.fixture(scope="module")
def prog(ctx):
    return progtrace.from_ctx(ctx)


def _span_totals(path, lo, hi):
    """name → (count, seconds) of the program's spans in [lo, hi], read
    straight from ``ProfileData``."""
    out = {}
    for plane in tracefile.load(str(path)).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name.startswith("repro.") and end > lo and \
                        ev.start_ns < hi:
                    n, s = out.get(ev.name, (0, 0.0))
                    out[ev.name] = (n + 1, s + ev.duration_ns * 1e-9)
    return out


@pytest.mark.parametrize("path", [PROGRAM, SMALL], ids=["program", "small"])
def test_wire_reader_finds_the_operations_profile_data_finds(path):
    red = tracefile.reduce(tracefile.load(str(path)))
    prog = progtrace.read(str(path), window=tuple(red.window))
    assert prog.devices == red.devices
    mine = sorted((o.device, o.start_ns, o.dur_ns, o.name)
                  for o in prog.ops)
    theirs = sorted((o.device, o.start_ns, o.dur_ns, o.name)
                    for o in red.ops)
    assert mine == theirs and mine


def test_span_totals(ctx, prog):
    totals = _span_totals(PROGRAM, *prog.window)
    assert {"repro.plan", "repro.plan.channel", "repro.plan.solve",
            "repro.plan.schedule", "repro.dispatch",
            "repro.dispatch.upload", "repro.dispatch.enqueue",
            "repro.collect.wait"} <= set(totals)
    for name, (n, secs) in totals.items():
        assert len(prog.named(name)) == n
        assert prog.span_seconds(name) == pytest.approx(secs, rel=1e-12)
    for metric, span in (("plan_channel_ms_per_period", "channel"),
                         ("plan_solve_ms_per_period", "solve"),
                         ("plan_schedule_ms_per_period", "schedule")):
        want = 1000.0 * totals[f"repro.plan.{span}"][1] / rec.PERIODS
        assert run.metric_reader(metric)(ctx) == pytest.approx(want)
    # the leaves are disjoint parts of the plan span
    leaves = sum(totals[f"repro.plan.{s}"][1]
                 for s in ("channel", "solve", "schedule"))
    assert leaves <= totals["repro.plan"][1]


def test_plan_counters(prog):
    (plan,) = prog.named("repro.plan")
    assert plan.stats["rows"] == rec.ROWS
    assert plan.stats["periods"] == rec.PERIODS
    assert plan.stats["k_pad"] == rec.K
    assert plan.stats["lanes"] == rec.ROWS * rec.PERIODS * rec.K
    assert plan.stats["lanes_used"] == plan.stats["lanes"]


def test_upload_bytes_from_the_shapes(ctx):
    f32 = 4
    data = (rec.N_TRAIN * rec.DIM + rec.N_TRAIN
            + rec.N_TEST * rec.DIM + rec.N_TEST) * f32
    per_row = (2 * rec.PERIODS * rec.K * rec.B_MAX      # idx, weight
               + rec.PERIODS * rec.K                     # batch
               + 2 * rec.PERIODS) * f32                  # lr, aggden
    active = rec.ROWS * rec.K * f32
    want = data + rec.ROWS * per_row + active
    assert run.metric_reader("upload_mb_per_grid")(ctx) == pytest.approx(
        want / 1e6, rel=1e-12)


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(traced)/vmap()/while/body/closed_call/sbc/vmap()/abs:", "sbc"),
    ("jit(traced)/vmap()/while/body/closed_call/grad/vmap(transpose("
     "jvp()))/dot_general:", "grad"),
    ("loss/broadcast_in_dim;grad/mul:", "loss"),
    ("jit(traced)/vmap()/while/body/dynamic_update_slice:", None),
    ("jit(loss_fn)/evaluate/dot_general:", None),
])
def test_scope_of(tf_op, scope):
    assert progtrace.scope_of(tf_op) == scope


def test_a_fusion_of_two_scopes_goes_to_the_first_listed():
    tf_op = "loss/broadcast_in_dim;grad/mul;loss/add:"
    assert progtrace.scopes_in(tf_op) == ["loss", "grad"]
    assert progtrace.scopes_in("jit(x)/while/body/dynamic_update_slice:") \
        == []


def test_multi_scope_seconds(prog):
    """The time given to a first-listed scope by rule: the leaf operations
    whose ``tf_op`` names two or more scopes, summed here by hand."""
    want = 0.0
    for op in prog.ops:
        if tracefile.CONTAINER.search(op.name):
            continue
        named = {t for t in progtrace.TOKEN.findall(op.tf_op)
                 if t in progtrace.SCOPES}
        if len(named) > 1:
            want += op.dur_ns * 1e-9 / len(prog.devices)
    assert prog.multi_scope_seconds() == pytest.approx(want, rel=1e-12)
    cov = progtrace.coverage(prog)
    assert cov["multi_scope_s"] == prog.multi_scope_seconds()
    assert cov["multi_scope_s"] <= sum(
        v for k, v in prog.scope_seconds().items() if k is not None)


def test_a_trace_of_another_run_reads_nothing(ctx, tmp_path, monkeypatch):
    """Without ``ctx["trace"]`` the readers take the newest trace under
    ``TRACE_DIR`` only if its ``window`` is the reduction's."""
    monkeypatch.setattr(progtrace, "TRACE_DIR", tmp_path)
    no_path = {k: v for k, v in ctx.items() if k != "trace"}
    assert progtrace.from_ctx(no_path) is None             # no trace
    stale = tmp_path / "old" / "small.xplane.pb"
    stale.parent.mkdir()
    stale.write_bytes(SMALL.read_bytes())
    assert progtrace.from_ctx(no_path) is None
    for name in NEW:
        assert run.metric_reader(name)(no_path) is None, name
    fresh = tmp_path / "new" / "program.xplane.pb"
    fresh.parent.mkdir()
    fresh.write_bytes(PROGRAM.read_bytes())
    os.utime(fresh, ns=(stale.stat().st_mtime_ns + 1,) * 2)
    prog = progtrace.from_ctx(no_path)
    assert prog is not None and prog.window == tuple(ctx["reduction"].window)
    assert run.metric_reader("upload_mb_per_grid")(no_path) == \
        run.metric_reader("upload_mb_per_grid")(ctx)


def test_scope_attribution(ctx, prog):
    secs = prog.scope_seconds()
    for scope in progtrace.SCOPES:
        assert secs.get(scope, 0.0) > 0, scope
    # every operation lands in exactly one bucket: the scopes and the
    # unscoped rest add up to the reduction's operation time
    assert sum(secs.values()) == pytest.approx(
        sum(ctx["reduction"].op_seconds().values()), rel=1e-9)
    for scope in ("grad", "sbc", "eval"):
        want = 1000.0 * secs[scope] / rec.PERIODS
        assert run.metric_reader(f"{scope}_ms_per_period")(ctx) == \
            pytest.approx(want)


def test_scopes_of_a_cached_executable_come_from_its_hlo_proto(
        monkeypatch):
    """An executable loaded from the compile cache leaves ``tf_op`` off
    its events: the op's ``op_name`` in the program's HLO proto gives the
    same scope to every operation."""
    data = PROGRAM.read_bytes()
    _, ops = progtrace.device_ops(data)
    real = progtrace._meta_stats

    def without_tf_op(buf, stat_names):
        name, stats = real(buf, stat_names)
        stats.pop("tf_op", None)
        return name, stats

    monkeypatch.setattr(progtrace, "_meta_stats", without_tf_op)
    _, from_hlo = progtrace.device_ops(data)
    assert [progtrace.scope_of(o.tf_op) for o in from_hlo] == [
        progtrace.scope_of(o.tf_op) for o in ops]
    assert {progtrace.scope_of(o.tf_op) for o in from_hlo} >= set(
        progtrace.SCOPES)


def test_active_lane_share(ctx):
    assert run.metric_reader("active_lane_share")(ctx) == 100.0


def test_a_program_without_spans_reads_nothing():
    ctx = _ctx(SMALL)
    for name in NEW:
        assert run.metric_reader(name)(ctx) is None, name
