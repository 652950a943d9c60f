"""Declarative experiment API: bucketing rule, one-trace-per-bucket
lowering, equivalence against the per-cell PR-1 paths (bit-for-bit for the
planner ledgers), executor runtimes (serial == async == meshed,
bit-for-bit), duplicate-spec dedup + fan-out, streaming collection, NaN
speed masking, and the mesh-sharded batch axis."""
import math
import warnings

import numpy as np
import pytest

from repro.api import (AsyncExecutor, Experiment, MeshExecutor,
                       ScenarioSpec, SerialExecutor, time_to_target)
from repro.channels.model import Cell
from repro.core import DeviceProfile, FeelScheduler
from repro.core.latency import period_latency, uplink_latency
from repro.core.scheduler import plan_horizons_batch
from repro.data.pipeline import ClassificationData, partition_noniid
from repro.fed import engine
from repro.fed.sweep import SweepCell, run_seed_batch, run_sweep
from repro.testing import no_retrace
from repro.fed.trainer import FeelSimulation, RunResult, run_scheme
from repro.launch.mesh import make_batch_mesh

# deliberately distinctive shapes: no other test module uses dim=40 /
# hidden=96 / b_max=24, so the lru-cached engine programs are fresh and
# the trace-count assertions below are exact.
DIM, HIDDEN, BMAX = 40, 96, 24


@pytest.fixture(scope="module")
def dataset():
    full = ClassificationData.synthetic(n=520, dim=DIM, seed=0, spread=6.0)
    return full.split(100)


@pytest.fixture(scope="module")
def fleet():
    return tuple(DeviceProfile(kind="cpu", f_cpu=f * 1e9)
                 for f in [0.7, 1.4, 2.1])


def _spec(fleet, **kw):
    kw.setdefault("name", "cpu3")
    kw.setdefault("b_max", BMAX)
    kw.setdefault("base_lr", 0.15)
    kw.setdefault("hidden", HIDDEN)
    return ScenarioSpec(fleet=fleet, **kw)


# ---------------------------------------------------------------------------
# bucketing rule
# ---------------------------------------------------------------------------


def test_bucketing_rule(dataset, fleet):
    """Partition/policy/seed/base_lr — and, since the ragged-fleet
    redesign, fleet size/composition — vary values only → one bucket;
    shape- or structure-changing knobs (b_max, scheme, local_steps)
    split."""
    data, test = dataset
    same = [_spec(fleet, partition=p, policy=pol, base_lr=lr, seeds=(0, 1))
            for p, pol, lr in [("iid", "proposed", 0.15),
                               ("noniid", "full", 0.1),
                               ("noniid", "random", 0.2)]]
    same.append(_spec(fleet[:2], name="cpu2"))    # smaller fleet: same bucket
    exp = Experiment(data, test, same)
    buckets = exp.lower()
    assert len(buckets) == 1
    assert len(buckets[0].rows) == 7              # 3 specs × 2 seeds + K2
    assert buckets[0].k_pad == len(fleet)
    mask = buckets[0].active_mask()
    assert mask.shape == (7, 3)
    np.testing.assert_array_equal(mask[-1], [1.0, 1.0, 0.0])

    split = same + [
        _spec(fleet, b_max=BMAX * 2),             # slot width
        _spec(fleet, local_steps=2),              # scan-body structure
        _spec(fleet, scheme="individual"),        # dev-family program
        _spec(fleet, scheme="model_fl"),          # averaging compiled in
        _spec(fleet[:2], name="cpu2i", scheme="individual"),  # dev: merges
    ]
    keys = [b.key for b in Experiment(data, test, split).lower()]
    assert len(keys) == len(set(keys)) == 5       # base bucket + 4 splits


def test_spec_validation(fleet):
    with pytest.raises(ValueError):
        ScenarioSpec(fleet=fleet, scheme="nope")
    with pytest.raises(ValueError):
        ScenarioSpec(fleet=fleet, seeds=())
    with pytest.raises(ValueError):
        ScenarioSpec(fleet=fleet, partition="sorted")
    with pytest.raises(ValueError):                # typo fails at build time,
        ScenarioSpec(fleet=fleet, policy="propsed")  # not deep in planning
    # hashable + usable as static jit metadata
    assert hash(ScenarioSpec(fleet=fleet)) == hash(ScenarioSpec(fleet=fleet))


# ---------------------------------------------------------------------------
# one compiled program per bucket
# ---------------------------------------------------------------------------


def test_grid_compiles_to_single_program_per_bucket(dataset, fleet):
    """ISSUE-2 acceptance: N shape-compatible cells → ONE trace, and a
    second same-shape grid with different values reuses it (0 traces)."""
    data, test = dataset
    grid = [_spec(fleet, partition=p, policy=pol, seeds=(0, 1))
            for p in ("iid", "noniid") for pol in ("proposed", "online")]
    with no_retrace(expect=1):                    # 4 cells, one program
        res = Experiment(data, test, grid).run(periods=4)
    assert res.n_buckets == 1

    other = [_spec(fleet, partition="noniid", policy="random",
                   base_lr=0.3, seeds=tuple(range(3, 11)))]  # 8 rows again
    with no_retrace():                            # same shapes: cache hit
        Experiment(data, test, other).run(periods=4)


# ---------------------------------------------------------------------------
# equivalence: bucketed lowering == per-cell PR-1 paths
# ---------------------------------------------------------------------------


def test_bucket_matches_per_cell_run_seed_batch(dataset, fleet):
    """2-cell × 2-seed bucket reproduces each cell's run_seed_batch."""
    data, test = dataset
    grid = [_spec(fleet, partition=p, policy="proposed", seeds=(0, 1))
            for p in ("iid", "noniid")]
    res = Experiment(data, test, grid).run(periods=5)
    for part in ("iid", "noniid"):
        sims = [FeelSimulation(list(fleet), data, test, partition=part,
                               policy="proposed", b_max=BMAX, base_lr=0.15,
                               seed=s, hidden=HIDDEN) for s in (0, 1)]
        losses, accs, times, gb = run_seed_batch(sims, 5)
        cell = res.sel(partition=part)
        np.testing.assert_array_equal(cell.times, times)   # host ledger
        np.testing.assert_array_equal(cell.global_batch, gb)
        np.testing.assert_allclose(cell.losses, losses, atol=1e-5)
        np.testing.assert_allclose(cell.accs, accs, atol=1e-5)


def test_horizon_dedup_rescales_lr_exactly(dataset, fleet):
    """Rows that are scheduler-identical modulo partition/base_lr share ONE
    planned horizon (the lowering's dedup); the per-row lr rescale must
    keep every row bit-equal (ledger) / tolerance-equal (series) to its
    standalone per-cell run."""
    data, test = dataset
    grid = [_spec(fleet, name=f"cpu3-lr{lr}", partition=p,
                  policy="proposed", base_lr=lr, seeds=(0,))
            for p in ("iid", "noniid") for lr in (0.1, 0.15)]
    res = Experiment(data, test, grid).run(periods=5)
    assert res.n_buckets == 1
    for p in ("iid", "noniid"):
        for lr in (0.1, 0.15):
            sims = [FeelSimulation(list(fleet), data, test, partition=p,
                                   policy="proposed", b_max=BMAX,
                                   base_lr=lr, seed=0, hidden=HIDDEN)]
            losses, accs, times, gb = run_seed_batch(sims, 5)
            row = res.sel(fleet=f"cpu3-lr{lr}", partition=p)
            assert row.rows == 1
            np.testing.assert_array_equal(row.times[0], times[0])
            np.testing.assert_array_equal(row.global_batch[0], gb[0])
            np.testing.assert_allclose(row.losses[0], losses[0], atol=1e-5)
            np.testing.assert_allclose(row.accs[0], accs[0], atol=1e-5)


def test_dev_schemes_bit_for_bit_vs_pr1_ledger(dataset, fleet):
    """individual/model_fl under the vectorized DevScheduler reproduce the
    PR-1 run_scheme trajectories bit-for-bit: the time ledger below is the
    PR-1 loop verbatim (interleaved rng draws, downlink via a second
    uplink_latency call — numerically identical to eq. (11))."""
    data, test = dataset
    periods, seed, k = 6, 3, len(fleet)

    def pr1_times(scheme):
        parts = partition_noniid(data.y, k, seed=seed)
        cell = Cell.make(seed)
        dist = cell.drop_users(k)
        rng = np.random.default_rng(seed)
        batch = 64
        n_params = sum((i * o + o) for i, o in
                       zip([DIM, 256, 256], [256, 256, 10]))
        s_bits = 32.0 * n_params
        times, t = np.empty(periods), 0.0
        for p in range(periods):
            np.stack([rng.choice(pp, size=batch, replace=len(pp) < batch)
                      for pp in parts])
            rates_up = cell.avg_rate(dist)
            rates_down = cell.avg_rate(dist)
            t_local = np.array([d.local_grad_latency(batch)
                                * max(1, len(pp) // batch)
                                for d, pp in zip(fleet, parts)])
            if scheme == "model_fl":
                tau_u = np.full(k, cell.cfg.frame_up_s / k)
                tau_d = np.full(k, cell.cfg.frame_down_s / k)
                t_up = uplink_latency(s_bits, tau_u, cell.cfg.frame_up_s,
                                      rates_up)
                t_down = uplink_latency(s_bits, tau_d, cell.cfg.frame_down_s,
                                        rates_down)
                t_upd = np.array([d.update_latency() for d in fleet])
                t += period_latency(t_local, t_up, t_down, t_upd)
            else:
                t += float(np.max(t_local))
            times[p] = t
        return times

    for scheme in ("individual", "model_fl"):
        with pytest.warns(DeprecationWarning):
            r = run_scheme(scheme, list(fleet), data, test, "noniid",
                           periods, seed=seed, eval_every=2)
        want = pr1_times(scheme)[[0, 2, 4, 5]]
        np.testing.assert_array_equal(np.array(r.times), want)
        assert np.all(np.isfinite(r.losses)) and np.all(np.isfinite(r.accs))


def test_dev_bucket_matches_run_scheme(dataset, fleet):
    """The batched dev-family lowering agrees with the per-run shim on the
    full loss/acc/time series."""
    data, test = dataset
    specs = [_spec(fleet, scheme=s, partition="noniid", base_lr=0.05,
                   b_max=128, hidden=256, seeds=(0, 1))
             for s in ("individual", "model_fl")]
    res = Experiment(data, test, specs).run(periods=5)
    for s in ("individual", "model_fl"):
        for seed in (0, 1):
            with pytest.warns(DeprecationWarning):
                r = run_scheme(s, list(fleet), data, test, "noniid", 5,
                               seed=seed, eval_every=2)
            row = res.sel(scheme=s, seed=seed)
            np.testing.assert_array_equal(row.times[0][[0, 2, 4]], r.times)
            np.testing.assert_allclose(row.losses[0][[0, 2, 4]], r.losses,
                                       atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(row.accs[0][[0, 2, 4]], r.accs,
                                       atol=1e-5, rtol=1e-5)


def test_plan_horizons_batch_bitwise(fleet):
    """Fused shared-fleet Algorithm-1 rows == per-scheduler planning."""
    mk = lambda: [FeelScheduler(devices=list(fleet), n_params=37000,  # noqa
                                policy=pol, b_max=BMAX, seed=s)
                  for s in (0, 1) for pol in ("proposed", "full")]
    fused, solo = mk(), mk()
    hs_fused = plan_horizons_batch(fused, 7)
    hs_solo = [s.plan_horizon(7) for s in solo]
    for a, b in zip(hs_fused, hs_solo):
        np.testing.assert_array_equal(a.batch, b.batch)
        np.testing.assert_array_equal(a.latency, b.latency)
        np.testing.assert_array_equal(a.lr, b.lr)
        np.testing.assert_array_equal(a.global_batch, b.global_batch)
    for a, b in zip(fused, solo):
        assert a._b_cache == b._b_cache and a._period == b._period


# ---------------------------------------------------------------------------
# executor runtimes: serial == async (bit-for-bit), streaming, dedup
# ---------------------------------------------------------------------------


def _multibucket_specs(fleet):
    """Three shape buckets: FEEL family (2 cells × 2 seeds), individual,
    model_fl."""
    return ([_spec(fleet, partition=p, policy="proposed", seeds=(0, 1))
             for p in ("iid", "noniid")]
            + [_spec(fleet, scheme="individual", seeds=(0,)),
               _spec(fleet, scheme="model_fl", seeds=(0,))])


def test_async_executor_bit_identical_to_serial(dataset, fleet):
    """ISSUE-3 acceptance: AsyncExecutor results are bit-for-bit identical
    to SerialExecutor on a multi-bucket grid — scheduling policy must not
    touch values."""
    data, test = dataset
    specs = _multibucket_specs(fleet)
    exp = Experiment(data, test, specs)
    assert len(exp.lower()) == 3
    serial = exp.run(periods=4, executor=SerialExecutor())
    done = exp.run(periods=4, executor=AsyncExecutor())
    default = exp.run(periods=4)                  # default == serial
    for got in (done, default):
        np.testing.assert_array_equal(
            np.asarray(serial.losses), np.asarray(got.losses))
        np.testing.assert_array_equal(
            np.asarray(serial.accs), np.asarray(got.accs))
        np.testing.assert_array_equal(serial.times, got.times)
        np.testing.assert_array_equal(serial.global_batch, got.global_batch)
    assert serial.n_buckets == done.n_buckets == 3


def test_stream_yields_cumulative_partials(dataset, fleet):
    """stream() hands back one cumulative partial Results per bucket; the
    final partial equals run()'s complete Results."""
    data, test = dataset
    specs = _multibucket_specs(fleet)
    exp = Experiment(data, test, specs)
    partials = list(exp.stream(periods=4, executor=AsyncExecutor()))
    assert len(partials) == 3
    assert [p.rows for p in partials] == [4, 5, 6]  # 4 feel rows, then +1, +1
    full = exp.run(periods=4)
    np.testing.assert_array_equal(np.asarray(partials[-1].losses),
                                  np.asarray(full.losses))
    np.testing.assert_array_equal(partials[-1].times, full.times)
    # early partials carry the already-collected rows in output order
    np.testing.assert_array_equal(np.asarray(partials[0].losses),
                                  np.asarray(full.losses[:4]))


def test_duplicate_specs_dedupe_and_fan_out(dataset, fleet):
    """The same ScenarioSpec declared twice is computed ONCE (one row per
    (spec, seed) in the lowering) and fanned back out to both output
    positions."""
    data, test = dataset
    spec = _spec(fleet, partition="iid", policy="full", seeds=(0, 1))
    other = _spec(fleet, partition="noniid", policy="full", seeds=(0,))
    exp = Experiment(data, test, [spec, other, spec])
    buckets = exp.lower()
    assert len(buckets) == 1
    assert len(buckets[0].rows) == 3              # 2 unique + 1, not 5
    fan = [r.indices for r in buckets[0].rows]
    assert fan == [(0, 3), (1, 4), (2,)]
    res = exp.run(periods=4)
    assert res.rows == 5                          # output keeps both copies
    np.testing.assert_array_equal(np.asarray(res.losses[0]),
                                  np.asarray(res.losses[3]))
    np.testing.assert_array_equal(np.asarray(res.losses[1]),
                                  np.asarray(res.losses[4]))
    np.testing.assert_array_equal(res.times[0], res.times[3])
    assert res.coords["spec"][0] == res.coords["spec"][3] == spec


def test_legacy_mesh_kwarg_is_gone(dataset, fleet):
    """The PR-3 ``Experiment(mesh=...)`` / ``run(mesh=...)`` shim has been
    removed: meshes belong to executors now."""
    data, test = dataset
    specs = [_spec(fleet, seeds=(0,))]
    mesh = make_batch_mesh()
    with pytest.raises(TypeError):
        Experiment(data, test, specs, mesh=mesh)
    with pytest.raises(TypeError):
        Experiment(data, test, specs).run(periods=2, mesh=mesh)


def test_run_sweep_and_run_scheme_warn_deprecation(dataset, fleet):
    """The legacy drivers must emit DeprecationWarning."""
    data, test = dataset
    with pytest.warns(DeprecationWarning, match="run_sweep is deprecated"):
        run_sweep({"cpu3": list(fleet)}, data, test, policies=("full",),
                  partitions=("iid",), seeds=(0,), periods=2, b_max=BMAX,
                  base_lr=0.15)
    with pytest.warns(DeprecationWarning, match="run_scheme is deprecated"):
        run_scheme("individual", list(fleet), data, test, "noniid", 2,
                   seed=0)


# ---------------------------------------------------------------------------
# NaN speed masking (python engine leaves NaN at non-eval periods)
# ---------------------------------------------------------------------------


def test_speed_masks_nan_explicitly():
    accs = np.array([[np.nan, 0.4, np.nan, 0.7],
                     [np.nan, np.nan, np.nan, np.nan],
                     [0.9, np.nan, 0.2, 0.3]])
    times = np.arange(1.0, 5.0) * np.ones((3, 1))
    with warnings.catch_warnings():
        warnings.simplefilter("error")            # invalid-compare leaks
        got = time_to_target(accs, times, 0.6)
    np.testing.assert_array_equal(got, [4.0, np.inf, 1.0])

    cell = SweepCell(name="c", fleet="f", partition="iid", policy="full",
                     seeds=(0, 1, 2), losses=np.zeros_like(accs), accs=accs,
                     times=times, global_batch=np.ones_like(accs))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        np.testing.assert_array_equal(cell.speed(0.6), [4.0, np.inf, 1.0])

    r = RunResult(scheme="feel", losses=[0, 0, 0],
                  accs=[float("nan"), 0.65, 0.7], times=[1.0, 2.0, 3.0])
    assert r.speed(0.6) == 2.0
    r_never = RunResult(scheme="feel", losses=[0], accs=[float("nan")],
                        times=[1.0])
    assert math.isinf(r_never.speed(0.6))


# ---------------------------------------------------------------------------
# Results axes + reductions, shims, mesh
# ---------------------------------------------------------------------------


def test_results_named_axes(dataset, fleet):
    data, test = dataset
    grid = [_spec(fleet, partition=p, policy=pol, seeds=(0, 1))
            for p in ("iid", "noniid") for pol in ("proposed", "online")]
    res = Experiment(data, test, grid).run(periods=4)
    assert res.rows == 8 and res.periods == 4
    assert set(res.coords) == {"fleet", "partition", "policy", "scheme",
                               "seed", "spec"}
    sub = res.sel(partition="iid", seed=1)
    assert sub.rows == 2 and set(sub.coords["policy"]) == {"proposed",
                                                           "online"}
    assert res.speed(2.0).shape == (8,)           # unreachable => inf
    assert np.all(np.isinf(res.speed(2.0)))
    assert res.final_acc.shape == (8,)
    cells = list(res.cells())
    assert len(cells) == 4
    labels, rows = cells[0]
    assert rows.rows == 2 and labels["scheme"] == "feel"
    # the spec coordinate isolates exactly one scenario's seed rows
    one = res.sel(spec=grid[2])
    assert one.rows == 2
    assert set(one.coords["partition"]) == {grid[2].partition}
    with pytest.raises(KeyError):
        res.sel(flavor="wrong")


def test_policy_coordinate_excludes_dev_schemes(dataset, fleet):
    """individual/model_fl carry policy="none": a sel on a FEEL policy
    must never mix per-device-parameter rows into the selection."""
    data, test = dataset
    specs = [_spec(fleet, partition="noniid", policy="proposed"),
             _spec(fleet, partition="noniid", scheme="individual"),
             _spec(fleet, partition="noniid", scheme="model_fl")]
    res = Experiment(data, test, specs).run(periods=4)
    prop = res.sel(policy="proposed", partition="noniid")
    assert set(prop.coords["scheme"]) == {"feel"}
    assert set(res.sel(policy="none").coords["scheme"]) == {"individual",
                                                            "model_fl"}


def test_spec_coordinate_separates_label_twins(dataset, fleet):
    """Two specs differing only in base_lr share every label coordinate;
    cells()/sel(spec=...) still keep them apart."""
    data, test = dataset
    twins = [_spec(fleet, partition="iid", policy="full", base_lr=lr)
             for lr in (0.1, 0.2)]
    res = Experiment(data, test, twins).run(periods=4)
    assert len(list(res.cells())) == 2            # not merged into one cell
    a = res.sel(spec=twins[0])
    b = res.sel(spec=twins[1])
    assert a.rows == b.rows == 1
    assert not np.allclose(a.losses, b.losses)    # different lr trajectories
    assert np.array_equal(a.times, b.times)       # shared (deduped) ledger


def test_run_sweep_shim_unchanged(dataset, fleet):
    """Shim returns per-cell SweepCells matching run_seed_batch values."""
    data, test = dataset
    with pytest.warns(DeprecationWarning):
        sw = run_sweep({"cpu3": list(fleet)}, data, test,
                       policies=("proposed",), partitions=("iid",),
                       seeds=(0, 1), periods=4, b_max=BMAX, base_lr=0.15)
    cell = sw["cpu3/iid/proposed"]
    sims = [FeelSimulation(list(fleet), data, test, partition="iid",
                           policy="proposed", b_max=BMAX, base_lr=0.15,
                           seed=s) for s in (0, 1)]
    losses, accs, times, gb = run_seed_batch(sims, 4)
    np.testing.assert_array_equal(cell.times, times)
    np.testing.assert_allclose(cell.losses, losses, atol=1e-5)
    np.testing.assert_allclose(cell.accs, accs, atol=1e-5)
    rr = cell.run_result(seed_i=1, eval_every=2)
    assert len(rr.accs) == 3                      # periods 0, 2, 3


def test_pad_rows_wraps_cyclically_when_pad_exceeds_rows():
    """A mesh larger than the bucket needs cyclic row repetition, not a
    single wrap of the first ``pad`` rows (regression: pad > n used to
    under-pad and fail the divisibility check at device_put)."""
    from repro.api.lowering import _pad_rows
    a = np.arange(6).reshape(3, 2)
    padded = _pad_rows(a, 3, 5)                    # 3 rows onto an 8-mesh
    assert padded.shape == (8, 2)
    np.testing.assert_array_equal(padded, a[np.arange(8) % 3])


def test_mesh_multi_device_sharding():
    """End-to-end on a real 8-device mesh (forced host devices, so this
    must run in a subprocess): sharded == plain for MeshExecutor and the
    async-with-mesh combination, including a ragged feel bucket (two
    fleet sizes padded into one program) and a dev bucket, both smaller
    than the mesh."""
    import subprocess
    import sys
    prog = """
import numpy as np
from repro.api import AsyncExecutor, Experiment, MeshExecutor, ScenarioSpec
from repro.core import DeviceProfile
from repro.data.pipeline import ClassificationData
from repro.launch.mesh import make_batch_mesh
full = ClassificationData.synthetic(n=300, dim=24, seed=0, spread=6.0)
data, test = full.split(60)
fleet = tuple(DeviceProfile(kind="cpu", f_cpu=f * 1e9) for f in (0.7, 2.1))
wide = fleet + (DeviceProfile(kind="cpu", f_cpu=1.4e9),)
specs = [ScenarioSpec(fleet=fleet, partition=p, policy="full", b_max=8,
                      base_lr=0.15, hidden=32, seeds=(0,))
         for p in ("iid", "noniid")]
specs.append(ScenarioSpec(fleet=wide, name="K3", partition="iid",
                          policy="full", b_max=8, base_lr=0.15, hidden=32,
                          seeds=(0,)))        # ragged row: padded K2 -> K3
specs.append(ScenarioSpec(fleet=fleet, scheme="individual", b_max=8,
                          hidden=32, seeds=(0,)))
mesh = make_batch_mesh()
assert mesh.devices.size == 8, mesh.devices.size
plain = Experiment(data, test, specs).run(periods=3)
for runner in (lambda e: e.run(periods=3, executor=MeshExecutor()),
               lambda e: e.run(periods=3,
                               executor=AsyncExecutor(mesh=mesh)),
               lambda e: e.run(periods=3,
                               executor=AsyncExecutor(mesh=mesh,
                                                      max_in_flight=1))):
    sharded = runner(Experiment(data, test, specs))
    assert np.array_equal(plain.times, sharded.times)
    assert np.allclose(plain.losses, sharded.losses, atol=1e-5)
    assert np.allclose(plain.accs, sharded.accs, atol=1e-5)
print("OK")
"""
    import os
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH", "")) if p)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_mesh_one_device_fallback(dataset, fleet):
    """Sharded lowering on a 1-device mesh == plain lowering (values and
    the dev-family path both), including non-divisible row padding."""
    data, test = dataset
    specs = [_spec(fleet, partition="noniid", policy="proposed",
                   seeds=(0, 1, 2)),              # 3 rows: padding exercised
             _spec(fleet, scheme="individual", seeds=(0,))]
    plain = Experiment(data, test, specs).run(periods=4)
    sharded = Experiment(data, test, specs).run(
        periods=4, executor=MeshExecutor())       # lazy make_batch_mesh()
    np.testing.assert_array_equal(plain.times, sharded.times)
    np.testing.assert_allclose(plain.losses, sharded.losses, atol=1e-6)
    np.testing.assert_allclose(plain.accs, sharded.accs, atol=1e-6)


def test_mesh_sharded_trace_is_not_a_retrace(dataset, fleet):
    """jit traces a mesh-sharded bucket apart from the plain one of the
    same shapes; the ledger's signature carries the sharding, so the pair
    is two programs and not a retrace (7 rows: shapes no other test
    traces)."""
    data, test = dataset
    specs = [_spec(fleet, partition="iid", policy="proposed",
                   seeds=tuple(range(7)))]
    with no_retrace(expect=2):
        Experiment(data, test, specs).run(periods=5)
        Experiment(data, test, specs).run(
            periods=5, executor=MeshExecutor(make_batch_mesh(1)))


def test_mesh_executor_rejects_non_batch_mesh(dataset, fleet):
    """Executors validate their mesh up front: a mesh without a 'batch'
    axis fails fast instead of deep inside device_put."""
    data, test = dataset
    specs = [_spec(fleet, partition="iid", policy="full", seeds=(0,))]
    from repro.launch.mesh import make_host_mesh
    with pytest.raises(ValueError, match="batch"):
        Experiment(data, test, specs).run(
            periods=3, executor=MeshExecutor(make_host_mesh()))


def test_async_max_in_flight_validation():
    with pytest.raises(ValueError, match="max_in_flight"):
        AsyncExecutor(max_in_flight=0)


def test_async_max_in_flight_bit_equal(dataset, fleet):
    """The dispatch-backlog cap is pure scheduling policy: capped (1 and
    2 in flight) vs uncapped AsyncExecutor runs are bit-equal on a
    3-bucket grid."""
    data, test = dataset
    specs = _multibucket_specs(fleet)
    exp = Experiment(data, test, specs)
    assert len(exp.lower()) == 3
    uncapped = exp.run(periods=4, executor=AsyncExecutor())
    for cap in (1, 2):
        capped = exp.run(periods=4,
                         executor=AsyncExecutor(max_in_flight=cap))
        np.testing.assert_array_equal(np.asarray(uncapped.losses),
                                      np.asarray(capped.losses))
        np.testing.assert_array_equal(np.asarray(uncapped.accs),
                                      np.asarray(capped.accs))
        np.testing.assert_array_equal(uncapped.times, capped.times)
        np.testing.assert_array_equal(uncapped.global_batch,
                                      capped.global_batch)
    # streaming still yields one cumulative partial per bucket
    partials = list(exp.stream(periods=4,
                               executor=AsyncExecutor(max_in_flight=1)))
    assert len(partials) == 3
    np.testing.assert_array_equal(np.asarray(partials[-1].losses),
                                  np.asarray(uncapped.losses))
