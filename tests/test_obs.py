"""Program spans and counters (``repro.obs``) under a CPU profiler session.

Every executor emits the same span tree: ``repro.plan`` (leaves
``channel`` / ``solve`` / ``schedule``) → ``repro.dispatch`` (``init`` /
``upload`` / ``check`` / ``enqueue``) → ``repro.collect.wait``, each carrying its
bucket serial and chunk index; the counters agree with the plan arrays
and the bytes that crossed; results are bitwise equal with the profiler
on and off; no counter is computed outside a session; and the scanned
period step carries its five named scopes into the compiled program.
"""
from collections import defaultdict
from dataclasses import dataclass

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.api import (AsyncExecutor, Experiment, ScenarioSpec,
                       SerialExecutor)
from repro.api import lowering
from repro.api.lowering import group_rows
from repro.compression import sbc
from repro.core import DeviceProfile
from repro.data.pipeline import ClassificationData
from repro.fed import engine
from repro.topology import Sampling

# distinctive shapes so engine program caches never collide across modules
DIM, HIDDEN, BMAX = 22, 30, 8
PERIODS = 4
LEAVES = ("repro.plan.channel", "repro.plan.solve", "repro.plan.schedule")
SCOPES = ("grad", "sbc", "aggregate", "loss", "eval")


@pytest.fixture(scope="module")
def dataset():
    full = ClassificationData.synthetic(n=300, dim=DIM, seed=3, spread=6.0)
    return full.split(60)


@pytest.fixture(scope="module")
def fleet():
    return tuple(DeviceProfile(kind="cpu", f_cpu=f * 1e9)
                 for f in [0.7, 1.4, 2.1])


def _spec(fleet, **kw):
    kw.setdefault("name", "obs3")
    kw.setdefault("b_max", BMAX)
    kw.setdefault("hidden", HIDDEN)
    return ScenarioSpec(fleet=fleet, **kw)


def _specs(fleet):
    """A ragged FEEL bucket (K 3 and 2, two policies), a sampled FEEL
    bucket (2 of 3 per period, so some lanes go unused) and a dev
    bucket."""
    return [_spec(fleet, policy="proposed", seeds=(0, 1)),
            _spec(fleet[:2], name="obs2", partition="noniid",
                  policy="full", seeds=(2,)),
            _spec(fleet, policy="random", sampling=Sampling(size=2),
                  seeds=(4,)),
            _spec(fleet, scheme="individual", seeds=(5,))]


@dataclass
class Ev:
    name: str
    start: float
    end: float
    stats: dict
    line: str

    def inside(self, other: "Ev") -> bool:
        return (self.line == other.line and other.start <= self.start
                and self.end <= other.end)


def _profiled(tmp_path, fn):
    """Run ``fn`` inside a profiler session; return its result and the
    program's spans read back from the trace."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    pb = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    evs = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    evs.append(Ev(ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats), line.name))
    return out, evs


def _named(evs, name):
    return [e for e in evs if e.name == name]


def _ids(e):
    return e.stats.get("bucket"), e.stats.get("chunk")


def _expected_lanes(bucket, data):
    """(lanes, lanes_used) of one bucket's whole horizon, from its plan
    arrays (planning is a pure function of the bucket)."""
    plan = lowering.plan_bucket(bucket, data, PERIODS)
    n = len(bucket.rows)
    active = np.asarray(plan.payload["active"]) > 0
    if active.ndim == 2:
        active = np.repeat(active[:, None, :], PERIODS, axis=1)
    if bucket.kind == "feel":
        batch = np.stack([s.batch for s in plan.payload["schedules"]])
        active = active & (batch > 0)
    return n * PERIODS * bucket.k_pad, int(active.sum())


EXECUTORS = {
    "serial": lambda: SerialExecutor(),
    "async": lambda: AsyncExecutor(),
    "chunked": lambda: AsyncExecutor(chunk_periods=2),
}


@pytest.mark.parametrize("which", sorted(EXECUTORS))
def test_executors_emit_the_span_tree(tmp_path, dataset, fleet, which):
    data, test = dataset
    specs = _specs(fleet)
    exp = Experiment(data, test, specs)
    _, evs = _profiled(tmp_path, lambda: exp.run(
        PERIODS, executor=EXECUTORS[which]()))
    buckets = group_rows(specs)
    n_chunks = 2 if which == "chunked" else 1

    plans = _named(evs, "repro.plan")
    serials = sorted({e.stats["bucket"] for e in plans})
    assert len(serials) == len(buckets)
    for serial, bucket in zip(serials, buckets):
        mine = [e for e in plans if e.stats["bucket"] == serial]
        assert sorted(e.stats["chunk"] for e in mine) == list(
            range(n_chunks))
        for e in mine:
            assert e.stats["rows"] == len(bucket.rows)
            assert e.stats["k_pad"] == bucket.k_pad
            assert e.stats["periods"] == PERIODS // n_chunks
        lanes, used = _expected_lanes(bucket, data)
        assert sum(e.stats["lanes"] for e in mine) == lanes
        assert sum(e.stats["lanes_used"] for e in mine) == used
        setup = [e for e in _named(evs, "repro.plan.setup")
                 if e.stats["bucket"] == serial]
        assert len(setup) == 1
        # the threshold search's passes: 20 bisection steps a leaf, one w
        # and one b per layer, where the period step runs SBC
        passes = {e.stats.get("sbc_count_passes")
                  for e in _named(evs, "repro.dispatch")
                  if e.stats["bucket"] == serial}
        want = (2 * bucket.rows[0].spec.depth * sbc.count_passes()
                if bucket.kind == "feel" else None)
        assert passes == {want}

    # a sampled bucket computes lanes it does not use
    assert any(e.stats["lanes_used"] < e.stats["lanes"] for e in plans)

    # the leaves nest under repro.plan, inherit its ids, and are disjoint
    leaves = [e for e in evs if e.name in LEAVES]
    assert {e.name for e in leaves} == set(LEAVES)
    for leaf in leaves:
        parent = [p for p in plans if leaf.inside(p)]
        assert len(parent) == 1
        assert _ids(leaf) == _ids(parent[0])
    by_line = defaultdict(list)
    for leaf in leaves:
        by_line[leaf.line].append(leaf)
    for group in by_line.values():
        group.sort(key=lambda e: e.start)
        for a, b in zip(group, group[1:]):
            assert a.end <= b.start

    # one dispatch, one upload, one enqueue and one wait per planned chunk;
    # init only where a trajectory starts
    plan_ids = sorted(_ids(e) for e in plans)
    for name in ("repro.dispatch", "repro.dispatch.upload",
                 "repro.dispatch.check", "repro.dispatch.enqueue",
                 "repro.collect.wait"):
        assert sorted(_ids(e) for e in _named(evs, name)) == plan_ids
    assert sorted(_ids(e) for e in _named(evs, "repro.dispatch.init")) == [
        i for i in plan_ids if i[1] == 0]
    dispatches = _named(evs, "repro.dispatch")
    for name in ("repro.dispatch.init", "repro.dispatch.upload",
                 "repro.dispatch.check", "repro.dispatch.enqueue"):
        for e in _named(evs, name):
            assert any(e.inside(d) and _ids(d) == _ids(e)
                       for d in dispatches)
    assert _named(evs, "repro.run.group")
    assert _named(evs, "repro.run.results")


def _device_nbytes(a) -> int:
    a = np.asarray(a)
    return a.size * min(a.dtype.itemsize, 4)     # 64-bit lands as 32-bit


def test_upload_counts_the_bytes_that_crossed(tmp_path, dataset, fleet):
    data, test = dataset
    spec = _spec(fleet, policy="proposed", seeds=(0, 1))
    (bucket,) = group_rows([spec])
    plan = lowering.plan_bucket(bucket, data, PERIODS)
    host = [test.x, test.y, data.x, data.y, plan.payload["active"]]
    for s in plan.payload["schedules"]:
        host += [s.idx, s.weight, s.batch, s.lr,
                 np.zeros(PERIODS, np.float32)]
    exp = Experiment(data, test, [spec])
    _, evs = _profiled(tmp_path, lambda: exp.run(
        PERIODS, executor=SerialExecutor()))
    (up,) = _named(evs, "repro.dispatch.upload")
    assert up.stats["arrays"] == len(host)
    assert up.stats["bytes"] == sum(_device_nbytes(a) for a in host)
    (enq,) = _named(evs, "repro.dispatch.enqueue")
    assert enq.stats["jit_traces"] in (0, 1)


@pytest.mark.parametrize("chunk", [None, 2], ids=["monolithic", "chunked"])
def test_results_bitwise_equal_with_profiler_on_and_off(tmp_path, dataset,
                                                        fleet, chunk):
    data, test = dataset
    exp = Experiment(data, test, _specs(fleet))
    off = exp.run(PERIODS, executor=AsyncExecutor(chunk_periods=chunk))
    on, evs = _profiled(tmp_path, lambda: exp.run(
        PERIODS, executor=AsyncExecutor(chunk_periods=chunk)))
    assert evs
    for field in ("losses", "accs", "times"):
        np.testing.assert_array_equal(np.asarray(getattr(off, field)),
                                      np.asarray(getattr(on, field)))


def test_no_counter_is_computed_outside_a_session(tmp_path, dataset, fleet,
                                                  monkeypatch):
    data, test = dataset
    exp = Experiment(data, test, _specs(fleet))

    def refuse(*_a, **_k):
        raise AssertionError("a counter was computed")

    monkeypatch.setattr(obs.span, "stat", refuse)
    monkeypatch.setattr(lowering, "lane_counts", refuse)
    monkeypatch.setattr(engine, "trace_count", refuse)
    assert not obs.enabled()
    for chunk in (None, 2):
        exp.run(PERIODS, executor=AsyncExecutor(chunk_periods=chunk))
    # the same paths do compute them inside a session
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert obs.enabled()
        with pytest.raises(AssertionError, match="counter"):
            exp.run(PERIODS, executor=SerialExecutor())
    finally:
        jax.profiler.stop_trace()


def test_period_step_carries_its_scopes_into_the_compiled_program(
        dataset, fleet):
    """The fig45 program (FEEL family, SBC on, every row's clients under
    one vmapped scan), at a tiny size: each phase's scope reaches the op
    metadata of the compiled program."""
    data, test = dataset
    specs = [_spec(fleet, policy=pol, partition=part, compress=True,
                   seeds=(0,))
             for pol in ("proposed", "full") for part in ("iid", "noniid")]
    (bucket,) = group_rows(specs)
    plan = lowering.plan_bucket(bucket, data, PERIODS)
    params0 = lowering._init_params_batch(bucket.rows, DIM)
    residual0 = jax.tree_util.tree_map(
        lambda p: jax.numpy.zeros((p.shape[0], bucket.k_pad) + p.shape[1:],
                                  p.dtype), params0)
    active = engine._normalize_active_batch(
        plan.payload["active"], len(bucket.rows), PERIODS, bucket.k_pad)
    xs = engine.stack_schedules(plan.payload["schedules"])
    fn = engine.trajectory_program(1, True, specs[0].compression)
    with engine.suspend_trace_count():
        text = fn.lower(params0, residual0, active, xs,
                        *engine.host_to_device(
                            (data.x, data.y, test.x, test.y))
                        ).compile().as_text()
    names = [ln.split('op_name="', 1)[1].split('"', 1)[0]
             for ln in text.splitlines() if 'op_name="' in ln]
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
