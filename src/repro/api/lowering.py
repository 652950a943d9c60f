"""ScenarioSpec → compiled-program lowering, in three phases.

``group_rows(...)`` flattens the (spec × seed) grid of an experiment into
shape-compatible buckets (``ScenarioSpec.bucket_key``); duplicate
(spec, seed) occurrences collapse onto one computed row whose
``Row.indices`` fan the result back out to every output position.  Each
bucket then executes as ONE jitted program via three composable phases —
the split is what lets ``api.executor`` runtimes schedule buckets
differently without re-implementing the lowering:

* :func:`plan_bucket` — **host only** (pure NumPy): vectorized channel
  Monte-Carlo draws, Algorithm-1 bisections
  (``core.scheduler.plan_horizons_batch`` — shared-fleet rows fused into
  one lockstep solve), horizon dedup across rows that are
  scheduler-identical modulo partition/base_lr (``_plan_key``), batcher
  sampling, the cumulative latency ledger.  No device work, so an async
  runtime can overlap this with another bucket's device execution.
* :func:`dispatch_bucket` — enqueue the bucket's device program and
  return immediately (jax dispatch is asynchronous): one ``vmap(init)``
  over stacked per-row PRNG keys (bit-identical to per-row init —
  counter-based PRNG), then ``engine.run_trajectory_batch`` /
  ``run_dev_trajectory_batch``, a ``vmap(lax.scan)`` over the flattened
  (scenario × seed) axis, optionally sharded across a 1-D device mesh
  (``launch.mesh.make_batch_mesh``; rows padded cyclically, sliced back
  at collection).
* :func:`collect_bucket` — block on the device values and return host
  ``(losses, accs, times, global_batch)`` series, one row per *computed*
  row (callers fan out via ``Row.indices``).

Per-row rng streams (partitioner, batcher, scheduler channel draws) are
consumed in exactly the order the per-simulation path uses, so lowering a
grid produces bit-identical schedules to running each cell alone — and
the phases are pure functions of the bucket, so every executor schedule
(serial, async, meshed) produces bit-identical results.

Fleet size is NOT structural (``spec.bucket_key``): a bucket's rows may
carry different fleets.  Planning always runs at each row's true K (same
rng streams and ledgers as a solo run; Algorithm-1 rows fuse across
fleets via the masked ``core.solver.FleetRows`` path), then schedules /
index blocks are zero-padded to the bucket's ``k_pad`` and a per-row
``active`` mask ({0,1} per user row) rides into the device program,
where padded users contribute zero weight, zero batch and are excluded
from every parameter average — padded rows are bit-identical to solo
unpadded runs (test-enforced).

Chunked horizons (:class:`BucketRun`)
-------------------------------------
The phases also run *per chunk*: a bucket's horizon splits into
``chunk``-period pieces, each planned (host), dispatched (device, with
the engine's explicit :class:`~repro.fed.engine.EngineState` carried
between chunks) and collected independently.  Planner state — scheduler
rng streams / ``_b_cache`` / ``_period``, batcher rng streams, per-row
time offsets — persists across chunks, and every chunked accumulation
(the time ledger's seeded cumsum, the carried scan state) is arranged so
that with ξ frozen the chunked run is **bit-identical** to the monolithic
one (test-enforced across executors and meshes).  Because planning now
happens *between* chunks, a bucket whose specs set ``replan=`` closes the
Algorithm-1 loop: chunk *c*'s realized loss decays feed each row's ξ
estimator (``observe_series``) before chunk *c+1* is planned — the
paper's adaptive re-planning, with warm-started B* grids
(``plan_horizons_batch(..., warm_start=True)``).  Closed-loop rows each
own their scheduler (realized decays are per-trajectory, so the
``_plan_key`` horizon dedup does not apply).
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.spec import ScenarioSpec
from repro.channels.model import Cell
from repro.compression import sbc
from repro.core.scheduler import (DevScheduler, FeelScheduler,
                                  plan_horizons_batch)
from repro.data.pipeline import (FederatedBatcher, partition_iid,
                                 partition_noniid)
from repro.fed import engine, feel_model, model_engine
from repro.launch.mesh import pad_batch
from repro.topology import band_width

tree_map = jax.tree_util.tree_map


@dataclass(frozen=True)
class Row:
    """One *computed* (spec, seed) pair of a bucket's batch axis.

    ``indices`` are the experiment-output row positions this computation
    feeds: more than one when the same ``ScenarioSpec`` was declared
    twice — the duplicate is computed once and fanned back out.
    """
    spec: ScenarioSpec
    seed: int
    indices: Tuple[int, ...]

    @property
    def index(self) -> int:
        return self.indices[0]


@dataclass
class Bucket:
    """All rows sharing one ``bucket_key`` → one compiled program.

    Rows may carry fleets of different sizes (fleet is not structural —
    see ``spec.bucket_key``): the plan/dispatch phases pad every row's
    user axis to :attr:`k_pad` and thread a per-row active mask, so the
    compiled shape is one (padded) family for the whole bucket.

    ``replan`` is the bucket's closed-loop ξ interval (``None`` = open
    loop): it comes from the rows' specs (structural, so all rows agree)
    or from a run-level override, and executors must execute such a
    bucket as ``replan``-period chunks via :class:`BucketRun`.

    ``band`` is the K-band sub-bucketing width (``group_rows(...,
    bands=True)``): rows pad to the power-of-two band instead of the
    bucket max, so a mixed-K grid compiles one program per *band* — a
    K=8 row stops paying for a K=10240 neighbour's padding — while bands
    of equal width keep sharing one compiled program (``program_key``
    already carries ``k_pad``).  ``None`` (the default) is the PR-4
    single-program behaviour.
    """
    key: tuple
    rows: List[Row]
    replan: Optional[int] = None
    band: Optional[int] = None

    @property
    def kind(self) -> str:
        return self.key[0]      # "feel" | "dev"

    @property
    def k_pad(self) -> int:
        """The padded user-axis width: the K band when sub-bucketed,
        else max K over the bucket's rows."""
        if self.band is not None:
            return self.band
        return max(r.spec.k for r in self.rows)

    def active_mask(self) -> np.ndarray:
        """(n, k_pad) f32 {0,1}: row r's first ``spec.k`` users active."""
        mask = np.zeros((len(self.rows), self.k_pad), np.float32)
        for i, r in enumerate(self.rows):
            mask[i, :r.spec.k] = 1.0
        return mask


def group_rows(specs: Sequence[ScenarioSpec],
               replan: Optional[int] = None,
               bands: bool = False) -> List[Bucket]:
    """Flatten specs × seeds into rows, grouped into first-seen-order
    buckets by shape compatibility.

    Duplicate (spec, seed) pairs — the same spec declared twice —
    deduplicate onto one row carrying every output index, so an
    experiment never runs one trajectory twice.

    ``replan`` overrides every FEEL-family spec's own ``replan`` for this
    lowering (the ``Experiment.run(replan=...)`` convenience — one knob
    for a whole grid).  Dev-family specs have no ξ loop and silently keep
    open-loop execution, so a mixed grid accepts the override.

    ``bands=True`` further splits each bucket by the power-of-two K band
    (``repro.topology.band_width``) of its rows: one :class:`Bucket` —
    and hence one compiled program — per band, each padded to the band
    width instead of the grid max.  Results are bit-identical to the
    unbanded lowering (each row's plan and trajectory never depended on
    its neighbours' padding); only compile-shape economics change.
    """
    if replan is not None and (not isinstance(replan, int)
                               or isinstance(replan, bool) or replan < 1):
        raise ValueError(
            f"replan must be a positive int (periods per closed-loop "
            f"chunk), got {replan!r}")
    entries: Dict[tuple, List[list]] = {}
    seen: Dict[tuple, list] = {}
    replans: Dict[tuple, Optional[int]] = {}
    index = 0
    for spec in specs:
        if spec.is_dev_scheme:
            eff = None
            eff_spec = spec
        else:
            eff = spec.replan if replan is None else replan
            # dedup and group on the spec AS EXECUTED: under a run-level
            # override, specs differing only in replan are one trajectory
            eff_spec = (spec if eff == spec.replan
                        else replace(spec, replan=eff))
        key = eff_spec.bucket_key()
        band = band_width(eff_spec.k) if bands else None
        replans[key] = eff
        for seed in spec.seeds:
            row_key = (eff_spec, seed)
            if row_key in seen:
                seen[row_key].append(index)
            else:
                # Row keeps the first-seen ORIGINAL spec (coords and
                # Study.axis_coords lookups are keyed by declared specs)
                entry = [spec, seed, [index]]
                seen[row_key] = entry[2]
                entries.setdefault((key, band), []).append(entry)
            index += 1
    return [Bucket(key=key,
                   rows=[Row(spec=s, seed=sd, indices=tuple(ix))
                         for s, sd, ix in rows],
                   replan=replans[key], band=band)
            for (key, band), rows in entries.items()]


def _partition(spec: ScenarioSpec, data, seed: int):
    if spec.partition == "iid":
        return partition_iid(len(data.y), spec.k, seed)
    return partition_noniid(data.y, spec.k, seed=seed)


def _n_params(spec: ScenarioSpec, input_dim: int, classes: int = 10) -> int:
    if spec.model_family != "feel_mlp":
        # the big-model families price the uplink at the true parameter
        # count of the spec's ArchConfig (named, cut to ``depth`` layers,
        # or derived)
        return model_engine.family_n_params(
            spec.model_family, spec.hidden, spec.depth, spec.arch)
    dims = [input_dim] + [spec.hidden] * (spec.depth - 1) + [classes]
    return sum(i * o + o for i, o in zip(dims[:-1], dims[1:]))


def _init_params_batch(rows: Sequence[Row], input_dim: int):
    """One vmapped init over the stacked per-row keys (bit-identical to
    per-row ``feel_model.init`` — threefry is counter-based)."""
    spec = rows[0].spec
    keys = jnp.stack([jax.random.key(r.seed) for r in rows])
    if spec.model_family != "feel_mlp":
        return model_engine.init_params_batch(
            spec.model_family, spec.hidden, spec.depth, keys, spec.arch)
    return jax.vmap(lambda k: feel_model.init(
        k, spec.hidden, depth=spec.depth, input_dim=input_dim))(keys)


def _pad_rows(trees, n: int, pad: int):
    """Cyclically repeat rows along every leading axis so a bucket divides
    the mesh — valid even when the mesh is larger than the bucket
    (pad > n); callers slice outputs back to ``n``."""
    if pad == 0:
        return trees
    wrap = np.arange(n + pad) % n
    return tree_map(
        lambda a: a[wrap] if hasattr(a, "ndim") else a, trees)


def _plan_key(r: Row) -> tuple:
    """Scheduler identity modulo ``base_lr``: two rows with equal keys
    consume identical rng streams and produce identical horizons (the
    partition only affects the *batcher*, and base_lr only rescales the
    lr row — rebuilt per row below), so the whole-grid lowering plans each
    unique key ONCE.  The full frozen ``CellConfig`` is part of the key:
    distinct wireless geometries (radius, bandwidth, tx power, frames)
    never share a planned horizon.  This is a structural win a per-cell
    driver cannot have: it never sees that its cells share planning
    work."""
    s = r.spec
    return (s.fleet, s.effective_policy, s.b_max, s.compression, s.cell,
            s.hidden, s.depth, r.seed, s.sampling, s.topology,
            s.fading, s.faults, s.energy, s.adapt_tau, s.model_family,
            s.arch, s.seq_len)


def _rescale_lr(horizon, base_lr: float, ref_batch: float):
    """Per-row lr row for a shared horizon: η = η₀·√(B/B_ref), identical
    to what a scheduler constructed with this base_lr would emit."""
    return replace(horizon, lr=base_lr * np.sqrt(
        horizon.global_batch / ref_batch))


# ---------------------------------------------------------------------------
# phase containers
# ---------------------------------------------------------------------------


@dataclass
class BucketPlan:
    """Phase-1 output: everything host planning produced for one bucket.

    ``times``/``global_batch`` are final host-side results (one row per
    computed row); ``payload`` holds the kind-specific arrays the dispatch
    phase feeds the device program.
    """
    bucket: Bucket
    input_dim: int
    times: np.ndarray            # (n, P) cumulative simulated seconds
    global_batch: np.ndarray     # (n, P) int64
    payload: dict
    ids: dict = field(default_factory=dict)   # span ids: bucket, chunk


@dataclass
class BucketHandle:
    """Phase-2 output: in-flight device values + finished host ledgers.

    ``losses``/``accs`` are (possibly padded) device arrays whose
    computation has been *dispatched* but not necessarily finished —
    :func:`collect_bucket` blocks and slices.  ``decays`` (FEEL family)
    are the realized per-period loss decays — the closed-loop ξ feedback
    signal — and ``state`` is the engine carry after this dispatch, which
    the chunked path resumes from without blocking.
    """
    bucket: Bucket
    losses: object               # (n+pad, P) device array
    accs: object                 # (n+pad, P) device array
    times: np.ndarray
    global_batch: np.ndarray
    decays: object = None        # (n+pad, P) device array (feel only)
    state: object = None         # engine.EngineState after this chunk
    energy: object = None        # (n, P, k_pad) host joules ledger, or None
    ids: dict = field(default_factory=dict)   # span ids: bucket, chunk


# ---------------------------------------------------------------------------
# phase 1: plan (pure host NumPy) — stateful planners shared by the
# monolithic path (one plan() covering the whole horizon) and the chunked
# path (one plan() per chunk, rng streams / time offsets carried)
# ---------------------------------------------------------------------------


def lane_counts(plan: BucketPlan) -> dict:
    """The ``repro.plan`` span's counters: ``lanes`` computed (rows ×
    periods × k_pad) and ``lanes_used``, the (client, period) pairs
    active with B_k > 0 (every active dev-family lane trains)."""
    n, periods = plan.times.shape
    k_pad = plan.bucket.k_pad
    active = np.asarray(plan.payload["active"]) > 0
    if active.ndim == 2:
        active = active[:, None, :]
    if plan.bucket.kind == "feel":
        batch = np.stack([s.batch for s in plan.payload["schedules"]])
        used = active & (batch > 0)
    else:
        used = np.broadcast_to(active, (n, periods, k_pad))
    return {"rows": n, "periods": periods, "k_pad": k_pad,
            "lanes": n * periods * k_pad, "lanes_used": int(used.sum())}


class _Planner:
    """Every ``plan()`` of a bucket run is one ``repro.plan`` span; its
    ids (the bucket serial :func:`_make_planner` gives and the chunk
    index) ride the plan into the dispatch and collect spans."""

    serial = 0
    chunk = 0

    def plan(self, periods: int, warm_start: bool = False) -> BucketPlan:
        ids = {"bucket": self.serial, "chunk": self.chunk}
        with obs.span("repro.plan", **ids) as sp:
            plan = self._plan(periods, warm_start)
            if sp.on:
                sp.stat(**lane_counts(plan))
        plan.ids = ids
        self.chunk += 1
        return plan


class _FeelPlanner(_Planner):
    """Host planning state for one FEEL bucket, resumable chunk by chunk.

    ``per_row=False`` (open loop): one scheduler — and one planned
    horizon — per unique ``_plan_key`` (scheduler-identical rows modulo
    partition/base_lr share a plan; lr rebuilt per row).  Successive
    ``plan()`` calls continue every rng stream and time offset, so N
    chunked plans are bit-identical to one monolithic plan.

    ``per_row=True`` (closed loop): every row owns its scheduler and ξ
    estimator — realized decays are per-trajectory, so horizon sharing
    would feed one EWMA from diverging series.  ``observe()`` lands chunk
    *c*'s decays before ``plan()`` produces chunk *c+1*.
    """

    def __init__(self, bucket: Bucket, data, per_row: bool = False):
        rows = bucket.rows
        self.bucket = bucket
        self.per_row = per_row
        self.input_dim = data.x.shape[1]
        n_params = _n_params(rows[0].spec, self.input_dim)

        def make_scheduler(r: Row) -> FeelScheduler:
            return FeelScheduler(
                devices=r.spec.fleet, n_params=n_params,
                policy=r.spec.effective_policy, b_max=r.spec.b_max,
                base_lr=r.spec.base_lr, compression=r.spec.compression,
                cell_cfg=r.spec.cell, seed=r.seed,
                sampling=r.spec.sampling, topology=r.spec.topology,
                fading=r.spec.fading, faults=r.spec.faults,
                energy=r.spec.energy)

        self.schedulers: List[FeelScheduler] = []
        self._sched_of: List[int] = []
        if per_row:
            for r in rows:
                self._sched_of.append(len(self.schedulers))
                self.schedulers.append(make_scheduler(r))
        else:
            unique: Dict[tuple, int] = {}
            for r in rows:
                key = _plan_key(r)
                if key not in unique:
                    unique[key] = len(self.schedulers)
                    self.schedulers.append(make_scheduler(r))
                self._sched_of.append(unique[key])
        self.batchers = [
            FederatedBatcher(_partition(r.spec, data, r.seed),
                             r.spec.b_max, r.seed) for r in rows]
        self._offsets = np.zeros(len(rows))
        # adaptive local steps: the bucket-consensus τ the NEXT chunk
        # executes (starts at the structural local_steps; re-scored at
        # every plan() once ξ feedback has landed)
        self._tau = rows[0].spec.local_steps

    def _plan(self, periods: int, warm_start: bool) -> BucketPlan:
        rows = self.bucket.rows
        spec0 = rows[0].spec
        tau = None
        if spec0.adapt_tau is not None:
            # bucket consensus: every row scores the candidate set with
            # its own realized comm/comp split and ξ estimate; the bucket
            # takes the MIN (conservative — never more local compute than
            # the most communication-starved row wants), because τ shapes
            # the scan body and the whole bucket must agree per chunk
            tau = min(s.recommend_tau(spec0.adapt_tau.choices, self._tau)
                      for s in self.schedulers)
            self._tau = tau
        # per_row IS the closed loop: the decay-cap steer only applies
        # once rows own their estimators (and only after feedback landed)
        planned = plan_horizons_batch(self.schedulers, periods,
                                      warm_start=warm_start,
                                      closed_loop=self.per_row)
        with obs.span("repro.plan.schedule") as sp:
            plan = self._schedule(planned, periods, tau)
            if sp.on:
                sp.stat(rows=len(rows))
        return plan

    def _schedule(self, planned, periods: int, tau) -> BucketPlan:
        """Every row's sample schedule from its planned horizon, padded to
        the bucket's K, and the device payload around them."""
        rows = self.bucket.rows
        # per-row planning runs at the row's TRUE fleet size (identical
        # rng streams and ledgers to a solo run); only the finished
        # schedules are zero-padded to the bucket's K so one program fits
        # every row
        k_pad = self.bucket.k_pad
        schedules = []
        parts: List[Optional[np.ndarray]] = []
        clouds: List[Optional[np.ndarray]] = []
        energies: List[Optional[np.ndarray]] = []
        for i, r in enumerate(rows):
            sched = self.schedulers[self._sched_of[i]]
            horizon = planned[self._sched_of[i]]
            if r.spec.base_lr != sched.base_lr:
                horizon = _rescale_lr(horizon, r.spec.base_lr,
                                      sched.ref_batch)
            parts.append(horizon.participation)
            clouds.append(horizon.cloud)
            energies.append(horizon.energy)
            s = engine.build_schedule(
                sched, self.batchers[i], r.spec.fleet, periods,
                r.spec.local_steps if tau is None else tau,
                horizon=horizon,
                time_offset=float(self._offsets[i]))
            self._offsets[i] = s.times[-1]
            schedules.append(engine.pad_schedule(s, k_pad))
        # static (n, k_pad) padding mask unless some row sampled this
        # chunk — then the realized cohorts ride a time-varying
        # (n, P, k_pad) mask whose padded columns stay exactly 0
        active = self.bucket.active_mask()
        if any(p is not None for p in parts):
            active = np.repeat(active[:, None, :], periods, axis=1)
            for i, (r, p) in enumerate(zip(rows, parts)):
                if p is not None:
                    active[i, :, :r.spec.k] = p
        payload = {"schedules": schedules, "active": active}
        if tau is not None:
            payload["tau"] = tau
        if any(e is not None for e in energies):
            # host-only per-user joules ledger (never crosses the device
            # boundary); padded columns stay exactly 0
            en = np.zeros((len(rows), periods, k_pad))
            for i, (r, e) in enumerate(zip(rows, energies)):
                if e is not None:
                    en[i, :, :r.spec.k] = e
            payload["energy"] = en
        if rows[0].spec.topology is not None:   # structural: all rows agree
            payload["member"] = np.stack([
                r.spec.topology.member_matrix(r.spec.k, k_pad)
                for r in rows])
            payload["cloud"] = np.stack(clouds).astype(np.float32)
        return BucketPlan(
            bucket=self.bucket, input_dim=self.input_dim,
            times=np.stack([s.times for s in schedules]),
            global_batch=np.stack([s.global_batch for s in schedules]),
            payload=payload)

    def observe(self, decays: np.ndarray, global_batch: np.ndarray):
        """Feed one collected chunk's realized per-period loss decays —
        (n, P_c) row-major — into each row's ξ estimator."""
        assert self.per_row, "closed-loop feedback needs per-row schedulers"
        for i in range(len(self.bucket.rows)):
            self.schedulers[i].observe_series(decays[i], global_batch[i])


class _DevPlanner(_Planner):
    """Host planning state for one dev-family bucket (chunk-resumable;
    no ξ loop — ``observe`` does not exist by design)."""

    def __init__(self, bucket: Bucket, data, per_row: bool = False):
        rows = bucket.rows
        spec0 = rows[0].spec
        self.bucket = bucket
        self.input_dim = data.x.shape[1]
        self.batch = spec0.dev_epoch_batch
        n_params = _n_params(spec0, self.input_dim)
        self.schedulers = [
            DevScheduler(
                devices=r.spec.fleet, parts=_partition(r.spec, data, r.seed),
                batch=self.batch,
                # model-based FL uploads the raw parameters: d·p bits
                payload_bits=32.0 * n_params,
                upload=(r.spec.scheme == "model_fl"),
                seed=r.seed, cell=Cell.make(r.seed, r.spec.cell),
                sampling=r.spec.sampling)
            for r in rows]
        self._offsets = np.zeros(len(rows))

    def _plan(self, periods: int, warm_start: bool) -> BucketPlan:
        rows = self.bucket.rows
        k_pad = self.bucket.k_pad
        horizons = []
        for i, s in enumerate(self.schedulers):
            h = s.plan_horizon(periods, time_offset=float(self._offsets[i]))
            self._offsets[i] = h.times[-1]
            horizons.append(h)
        n = len(rows)
        # rows plan at their true K; pad idx user rows with index 0 (the
        # active mask keeps those devices out of every parameter average)
        with obs.span("repro.plan.schedule") as sp:
            idx = np.zeros((n, periods, k_pad, self.batch), np.int64)
            for i, (r, h) in enumerate(zip(rows, horizons)):
                idx[i, :, :r.spec.k] = h.idx
            if sp.on:
                sp.stat(rows=n)
        active = self.bucket.active_mask()
        if any(h.participation is not None for h in horizons):
            active = np.repeat(active[:, None, :], periods, axis=1)
            for i, (r, h) in enumerate(zip(rows, horizons)):
                if h.participation is not None:
                    active[i, :, :r.spec.k] = h.participation
            gb = np.stack([
                (self.batch * h.participation.astype(np.int64).sum(1)
                 if h.participation is not None
                 else np.full(periods, self.batch * r.spec.k, np.int64))
                for r, h in zip(rows, horizons)])
        else:
            gb = np.stack([
                np.full(periods, self.batch * r.spec.k, np.int64)
                for r in rows])
        return BucketPlan(
            bucket=self.bucket, input_dim=self.input_dim,
            times=np.stack([h.times for h in horizons]),
            global_batch=gb,
            payload={"idx": idx,
                     "lr": np.array([r.spec.base_lr for r in rows],
                                    np.float32),
                     "active": active})


def _make_planner(bucket: Bucket, data, per_row: bool = False):
    """A fresh planner with a new bucket serial; building it (partitions,
    schedulers, batchers) is the ``repro.plan.setup`` span."""
    cls = _FeelPlanner if bucket.kind == "feel" else _DevPlanner
    serial = obs.next_bucket()
    with obs.span("repro.plan.setup", bucket=serial):
        planner = cls(bucket, data, per_row=per_row)
    planner.serial = serial
    return planner


def plan_bucket(bucket: Bucket, data, periods: int) -> BucketPlan:
    """Host-side planning for one bucket (no device work dispatched)."""
    return _make_planner(bucket, data).plan(periods)


# ---------------------------------------------------------------------------
# compiled-program identity (the serve-layer compile cache key)
# ---------------------------------------------------------------------------


def chunk_lengths(periods: int, chunk: Optional[int]) -> Tuple[int, ...]:
    """The per-chunk period counts a ``chunk``-chunked horizon dispatches:
    ``chunk_lengths(7, 3) == (3, 3, 1)`` — one compiled program per
    *distinct* length (``None`` → one monolithic chunk)."""
    if chunk is None:
        return (periods,)
    chunk = min(max(1, chunk), periods)
    out = [chunk] * (periods // chunk)
    if periods % chunk:
        out.append(periods % chunk)
    return tuple(out)


def program_key(bucket: Bucket, n_rows: int, periods: int,
                data, test) -> tuple:
    """Hashable identity of the compiled program one dispatch would run.

    Two dispatches with equal keys hit the same jitted executable (zero
    new traces — the warm-admission contract ``repro.serve``'s
    :class:`~repro.serve.ProgramCache` keeps counters on); two dispatches
    with different keys *may* still share one (the key is deliberately an
    over-approximation, never the reverse).  Soundness rests on
    ``bucket.key`` carrying every static-config knob of the engine's
    program caches (scheme family, ``b_max``/epoch batch = the slot
    width, ``local_steps``, compression, model dims, ``replan``) while
    the remaining axes of the abstract trace signature are exactly
    ``n_rows`` (the padded batch axis), ``k_pad``, the chunk's period
    count, and the dataset/test shapes — all named here.  Dtypes never
    vary: every input crosses ``engine.host_to_device``.

    ``n_rows`` is the batch axis *as dispatched* (mesh-padded when the
    executor pads the bucket to a device mesh).
    """
    return (bucket.key, int(n_rows), bucket.k_pad, int(periods),
            tuple(data.x.shape), tuple(data.y.shape),
            tuple(test.x.shape), tuple(test.y.shape))


def bucket_program_keys(bucket: Bucket, n_rows: int, periods: int,
                        chunk: Optional[int], data, test) -> Tuple[tuple, ...]:
    """Every distinct :func:`program_key` a chunked run of this bucket
    will dispatch (first-use order, deduplicated): one per distinct
    chunk length."""
    out, seen = [], set()
    for p_c in chunk_lengths(periods, chunk):
        key = program_key(bucket, n_rows, p_c, data, test)
        if key not in seen:
            seen.add(key)
            out.append(key)
    return tuple(out)


# ---------------------------------------------------------------------------
# phase 2: dispatch (enqueue the device program, return without blocking)
# ---------------------------------------------------------------------------


def _dispatch_feel(plan: BucketPlan, data, test, mesh,
                   state=None) -> BucketHandle:
    rows = plan.bucket.rows
    spec0 = rows[0].spec
    schedules = plan.payload["schedules"]
    active = plan.payload["active"]
    member = plan.payload.get("member")      # hierarchical buckets only
    # adaptive buckets execute the chunk at the planner's consensus τ
    local_steps = plan.payload.get("tau", spec0.local_steps)
    k_pad = plan.bucket.k_pad

    n = len(rows)
    pad = 0 if mesh is None else pad_batch(n, mesh)
    if state is None:
        with obs.span("repro.dispatch.init"):
            params0 = _init_params_batch(rows, plan.input_dim)
            residual0 = tree_map(
                lambda p: jnp.zeros((p.shape[0], k_pad) + p.shape[1:],
                                    p.dtype), params0)
            if member is not None:
                # every edge replica starts from the row's global init
                params0 = tree_map(
                    lambda a: jnp.broadcast_to(
                        a[:, None],
                        (a.shape[0], member.shape[1]) + a.shape[1:]),
                    params0)
            if pad:
                params0, residual0 = _pad_rows((params0, residual0), n, pad)
            state = engine.EngineState(params=params0, residual=residual0)
    if pad:
        active = _pad_rows(active, n, pad)
        schedules = [schedules[i % n] for i in range(n + pad)]
    if member is not None:
        cloud = plan.payload["cloud"]
        if pad:
            member, cloud = _pad_rows((member, cloud), n, pad)
        state, (losses, accs, decays) = engine.resume_hier_trajectory_batch(
            state, member, cloud, schedules, data, test,
            local_steps=local_steps, compress=spec0.compress,
            ratio=spec0.compression, mesh=mesh, active=active)
    elif spec0.model_family != "feel_mlp":
        # big-model families: the transformer / mamba2 train-step scan
        state, (losses, accs, decays) = \
            model_engine.resume_model_trajectory_batch(
                state, schedules, data, test,
                model_family=spec0.model_family, hidden=spec0.hidden,
                depth=spec0.depth, compress=spec0.compress,
                ratio=spec0.compression, mesh=mesh, active=active,
                arch=spec0.arch, seq_len=spec0.seq_len)
    else:
        state, (losses, accs, decays) = engine.resume_trajectory_batch(
            state, schedules, data, test,
            local_steps=local_steps, compress=spec0.compress,
            ratio=spec0.compression, mesh=mesh, active=active)
    return BucketHandle(bucket=plan.bucket, losses=losses, accs=accs,
                        times=plan.times, global_batch=plan.global_batch,
                        decays=decays, state=state,
                        energy=plan.payload.get("energy"), ids=plan.ids)


def _dispatch_dev(plan: BucketPlan, data, test, mesh,
                  state=None) -> BucketHandle:
    rows = plan.bucket.rows
    spec0 = rows[0].spec
    k_pad = plan.bucket.k_pad
    idx, lr = plan.payload["idx"], plan.payload["lr"]
    active = plan.payload["active"]

    n = len(rows)
    pad = 0 if mesh is None else pad_batch(n, mesh)
    if state is None:
        with obs.span("repro.dispatch.init"):
            p0 = _init_params_batch(rows, plan.input_dim)
            dev_params0 = tree_map(
                lambda a: jnp.broadcast_to(
                    a[:, None], (a.shape[0], k_pad) + a.shape[1:]), p0)
            if pad:
                dev_params0 = _pad_rows(dev_params0, n, pad)
            state = engine.EngineState(params=dev_params0)
    if pad:
        idx, lr, active = _pad_rows((idx, lr, active), n, pad)
    state, (losses, accs) = engine.resume_dev_trajectory_batch(
        state, idx, lr, data, test,
        average=(spec0.scheme == "model_fl"), mesh=mesh, active=active)
    return BucketHandle(bucket=plan.bucket, losses=losses, accs=accs,
                        times=plan.times, global_batch=plan.global_batch,
                        state=state, ids=plan.ids)


def dispatch_bucket(plan: BucketPlan, data, test, mesh=None,
                    state=None) -> BucketHandle:
    """Enqueue one planned bucket's device program; returns immediately
    with in-flight device values (jax dispatch is asynchronous).

    ``state`` resumes from a previous chunk's engine carry (chunked
    horizons); ``None`` initializes a fresh trajectory.  Runs as one
    ``repro.dispatch`` span (children ``init``, ``upload``, ``enqueue``)
    carrying the plan's ids; where the period step runs
    ``compress_dense`` (the MLP family with SBC on) its stat
    ``sbc_count_passes`` counts the threshold search's passes over the
    gradients per client-period, summed over the model's leaves.  Where
    the spec names an architecture, its stats are ``arch_params`` (the
    cut architecture's parameter count; a counter is a number, since
    trace readers sum them), ``seq_len``, ``tokens`` (Σ B_k × seq_len of
    the participants), ``client_turns`` (steps of the client scan: rows
    × periods × padded K) and ``logit_block`` (sequences of a client's
    gradient a logit block holds)."""
    dispatcher = (_dispatch_feel if plan.bucket.kind == "feel"
                  else _dispatch_dev)
    with obs.span("repro.dispatch", **plan.ids) as sp:
        spec0 = plan.bucket.rows[0].spec
        if (sp.on and plan.bucket.kind == "feel" and spec0.compress
                and spec0.model_family == "feel_mlp"):
            leaves = jax.tree_util.tree_leaves(jax.eval_shape(
                lambda: feel_model.init(jax.random.key(0), spec0.hidden,
                                        depth=spec0.depth,
                                        input_dim=plan.input_dim)))
            sp.stat(sbc_count_passes=len(leaves) * sbc.count_passes())
        if sp.on and spec0.arch is not None:
            sp.stat(**_text_counters(plan))
        return dispatcher(plan, data, test, mesh, state=state)


def _text_counters(plan: BucketPlan) -> dict:
    """The ``repro.dispatch`` stats of a named architecture's bucket."""
    spec0 = plan.bucket.rows[0].spec
    schedules = plan.payload["schedules"]
    n, periods = plan.times.shape
    active = np.asarray(plan.payload["active"], np.float64)
    if active.ndim == 2:
        active = active[:, None, :]
    batch = np.stack([s.batch for s in schedules])
    return {"arch_params": _n_params(spec0, plan.input_dim),
            "seq_len": spec0.seq_len,
            "tokens": int((batch * active).sum()) * spec0.seq_len,
            "client_turns": n * periods * plan.bucket.k_pad,
            "logit_block": model_engine.logit_block(
                spec0.model_family, spec0.hidden, spec0.depth, spec0.arch,
                spec0.seq_len, schedules[0].idx.shape[-1])}


# ---------------------------------------------------------------------------
# phase 2b: probe (lower the bucket program WITHOUT running it — the
# static-analysis entry point)
# ---------------------------------------------------------------------------


@dataclass
class TracedBucket:
    """One bucket program lowered for inspection, with taint labels.

    ``closed`` is the closed jaxpr of the exact jitted program the
    dispatch phase would run; ``in_labels`` / ``out_contracts`` are the
    padding-taint annotations aligned with its flattened inputs/outputs
    (see :mod:`repro.analysis.taint`).  Built by :func:`trace_bucket`
    under ``engine.suspend_trace_count`` so probing never pollutes the
    trace ledger the compile audit certifies.
    """
    program: str
    closed: object               # jax.extend.core.ClosedJaxpr
    in_labels: list
    out_contracts: dict
    bucket: Bucket
    periods: int


def _flat_labels(label_tree) -> list:
    return jax.tree_util.tree_leaves(label_tree)


def trace_bucket(plan: BucketPlan, data, test) -> TracedBucket:
    """Lower one planned bucket's device program to a labeled jaxpr.

    Mirrors the dispatch phase's argument assembly exactly (fresh-state
    form, no mesh — sharding does not change program semantics), then
    traces with ``jax.make_jaxpr`` instead of executing.  The labels
    state the padded-lane facts the schedule construction guarantees:

    * FEEL: ``residual0`` and ``active`` hold exact zeros on padded
      lanes; ``idx``/``weight``/``batch`` padded lanes are *variant* —
      deliberately weaker than ``pad_schedule`` provides, so the
      certificate also covers hand-built (garbage) schedules and rests
      only on the program's own ``w*=active`` / ``bk*=active`` masking;
    * dev: per-device params are variant on padded lanes, ``active`` is
      zero; the program's masked means must do all the work.

    The FEEL output contract pins the SBC ``residual`` carry to
    ``Known(0)`` on padded lanes — the inductive step that extends the
    single-program certificate across chunked/replanned horizons (the
    next chunk's ``residual0`` label is exactly this output's contract).
    """
    from repro.analysis.taint import LaneLabel, NO_LABEL, OutContract

    rows = plan.bucket.rows
    spec0 = rows[0].spec
    k_pad = plan.bucket.k_pad
    n = len(rows)
    periods = plan.times.shape[1]
    # adaptive buckets: probe the program variant THIS chunk would run
    local_steps = plan.payload.get("tau", spec0.local_steps)
    name = f"{plan.bucket.key}/P{periods}"
    if plan.bucket.band is not None:
        name += f"/B{plan.bucket.band}"
    if "tau" in plan.payload:
        name += f"/T{local_steps}"
    with engine.suspend_trace_count():
        if plan.bucket.kind == "feel":
            schedules = plan.payload["schedules"]
            # the engine always hands the scan a time-varying (n, P, K)
            # mask (a static mask broadcasts) — trace what it runs.  The
            # label states only the structural fact: padded-user lanes
            # are exact zeros (a sampled-out participant is data, not a
            # lane, so it needs no certificate).
            active = engine._normalize_active_batch(
                plan.payload["active"], n, periods, k_pad)
            if spec0.arch is None:
                params0 = _init_params_batch(rows, plan.input_dim)
                residual0 = tree_map(
                    lambda p: jnp.zeros((p.shape[0], k_pad) + p.shape[1:],
                                        p.dtype), params0)
            else:
                # a named architecture at its published widths is traced
                # from shapes: its weights and residuals are not built
                params0 = jax.eval_shape(
                    lambda: _init_params_batch(rows, plan.input_dim))
                residual0 = tree_map(
                    lambda p: jax.ShapeDtypeStruct(
                        (p.shape[0], k_pad) + p.shape[1:], p.dtype),
                    params0)
            member = plan.payload.get("member")
            xs = engine.stack_schedules(schedules)
            data_args = engine.host_to_device(
                (data.x, data.y, test.x, test.y))
            if member is not None:
                params_e0 = tree_map(
                    lambda a: jnp.broadcast_to(
                        a[:, None],
                        (a.shape[0], member.shape[1]) + a.shape[1:]),
                    params0)
                member_d = engine.host_to_device(np.asarray(member))
                cloud = engine.host_to_device(
                    np.asarray(plan.payload["cloud"]))
                fn = engine.hier_trajectory_program(
                    local_steps, spec0.compress, spec0.compression,
                    n_edges=member.shape[1])
                closed = jax.make_jaxpr(fn)(
                    params_e0, residual0, member_d, active, cloud, xs,
                    *data_args)
                # member's padded-user columns are all-zero one-hots —
                # the monoid identity of the routing contraction — and
                # active's padded lanes are zero; per-edge replicas are
                # global values (no user lane), so NO_LABEL
                labels = (
                    tree_map(lambda _: NO_LABEL, params_e0),
                    tree_map(lambda _: LaneLabel(1, 0.0), residual0),
                    LaneLabel(2, 0.0),
                    LaneLabel(2, 0.0),
                    NO_LABEL,
                    {"idx": LaneLabel(2), "weight": LaneLabel(2),
                     "batch": LaneLabel(2), "lr": NO_LABEL,
                     "aggden": NO_LABEL},
                    NO_LABEL, NO_LABEL, NO_LABEL, NO_LABEL)
                n_leaves = len(jax.tree_util.tree_leaves(params_e0))
            elif spec0.model_family != "feel_mlp":
                # big-model families trace against the datasets as the
                # program takes them but share the MLP scan's
                # label/contract story verbatim: the program's own masking
                # must re-establish padding safety from variant schedule
                # lanes
                data_args = engine.host_to_device(model_engine.program_data(
                    data, test, spec0.arch, spec0.seq_len))
                fn = model_engine.model_trajectory_program(
                    spec0.model_family, spec0.hidden, spec0.depth,
                    spec0.compress, spec0.compression, arch=spec0.arch,
                    seq_len=spec0.seq_len)
                closed = jax.make_jaxpr(fn)(
                    params0, residual0, active, xs, *data_args)
                labels = (
                    tree_map(lambda _: NO_LABEL, params0),
                    tree_map(lambda _: LaneLabel(1, 0.0), residual0),
                    LaneLabel(2, 0.0),
                    {"idx": LaneLabel(2), "weight": LaneLabel(2),
                     "batch": LaneLabel(2), "lr": NO_LABEL,
                     "aggden": NO_LABEL},
                    *(NO_LABEL for _ in data_args))
                n_leaves = len(jax.tree_util.tree_leaves(params0))
            else:
                fn = engine.trajectory_program(
                    local_steps, spec0.compress, spec0.compression)
                closed = jax.make_jaxpr(fn)(
                    params0, residual0, active, xs, *data_args)
                # aggden is a per-period scalar (no user lane): NO_LABEL
                labels = (
                    tree_map(lambda _: NO_LABEL, params0),
                    tree_map(lambda _: LaneLabel(1, 0.0), residual0),
                    LaneLabel(2, 0.0),
                    {"idx": LaneLabel(2), "weight": LaneLabel(2),
                     "batch": LaneLabel(2), "lr": NO_LABEL,
                     "aggden": NO_LABEL},
                    NO_LABEL, NO_LABEL, NO_LABEL, NO_LABEL)
                n_leaves = len(jax.tree_util.tree_leaves(params0))
            # outputs: (params, residual, (losses, accs, decays))
            contracts = {n_leaves + i: OutContract(axis=1, value=0.0)
                         for i in range(n_leaves)}
        else:
            idx, lr = plan.payload["idx"], plan.payload["lr"]
            active = engine._normalize_active_batch(
                plan.payload["active"], n, periods, k_pad)
            p0 = _init_params_batch(rows, plan.input_dim)
            dev_params0 = tree_map(
                lambda a: jnp.broadcast_to(
                    a[:, None], (a.shape[0], k_pad) + a.shape[1:]), p0)
            idx = engine.host_to_device(np.asarray(idx))
            batched = (dev_params0, idx, *engine.host_to_device(
                (np.asarray(lr), active)))
            data_args = engine.host_to_device(
                (data.x, data.y, test.x, test.y))
            fn = engine.dev_trajectory_program(
                average=(spec0.scheme == "model_fl"))
            closed = jax.make_jaxpr(fn)(*batched, *data_args)
            labels = (
                tree_map(lambda _: LaneLabel(1, "variant"), dev_params0),
                LaneLabel(2), NO_LABEL, LaneLabel(2, 0.0),
                NO_LABEL, NO_LABEL, NO_LABEL, NO_LABEL)
            contracts = {}
    return TracedBucket(program=name, closed=closed,
                        in_labels=_flat_labels(labels),
                        out_contracts=contracts, bucket=plan.bucket,
                        periods=periods)


def audit_bucket_taint(plan: BucketPlan, data, test, report=None):
    """Run the padding-taint pass over one planned bucket's program."""
    from repro.analysis import taint
    traced = trace_bucket(plan, data, test)
    return taint.analyze_jaxpr(
        traced.closed, traced.in_labels, traced.out_contracts,
        program=traced.program, report=report)


# ---------------------------------------------------------------------------
# phase 3: collect (block, slice padding, hand back host arrays)
# ---------------------------------------------------------------------------


def collect_bucket(handle: BucketHandle):
    """Block until the bucket's device values are ready; returns
    ``(losses, accs, times, global_batch)`` — (n, P) host arrays, one row
    per computed row (fan out duplicates via ``Row.indices``)."""
    n = len(handle.bucket.rows)
    with obs.span("repro.collect.wait", **handle.ids):
        losses = np.asarray(handle.losses)[:n]
        accs = np.asarray(handle.accs)[:n]
    return losses, accs, handle.times, handle.global_batch


# ---------------------------------------------------------------------------
# chunked horizons: the per-chunk phase loop as a resumable state machine
# ---------------------------------------------------------------------------


@dataclass
class BucketRun:
    """Chunked, resumable execution of one bucket — the intra-bucket
    pipeline.

    The horizon splits into ``chunk``-period pieces; each piece runs the
    plan → dispatch → collect phases with all host state (scheduler /
    batcher rng streams, time offsets) and device state (the engine's
    :class:`~repro.fed.engine.EngineState` carry) threaded through.  The
    executor composes three operations:

    * :meth:`advance` — plan the next chunk (host NumPy) and dispatch its
      device program (non-blocking).  Because jax dispatch is
      asynchronous, calling ``advance`` while the previous chunk is still
      executing overlaps chunk *c+1*'s bisections and channel Monte-Carlo
      behind chunk *c*'s device work.
    * :meth:`collect` — block on the oldest in-flight chunk and bank its
      series.  When the bucket is closed-loop (``bucket.replan``), this is
      also where the chunk's realized loss decays feed every row's ξ
      estimator — so the *next* ``advance`` re-plans Algorithm 1 with the
      updated estimate (warm-started B* grids).
    * :attr:`can_advance` — scheduling guard: closed-loop buckets must
      collect chunk *c* before planning chunk *c+1* (the feedback is the
      point); open-loop buckets may run arbitrarily far ahead.

    With ξ frozen (open loop) any chunk size — and any interleaving of
    ``advance``/``collect`` the guard admits — is bit-identical to the
    monolithic three-phase path (test-enforced).
    """
    bucket: Bucket
    data: object
    test: object
    periods: int
    chunk: int
    mesh: object = None
    planned: int = 0
    dispatched: int = 0
    collected: int = 0
    _planner: object = None
    _state: object = None
    _pending: deque = field(default_factory=deque)
    _chunks: list = field(default_factory=list)
    _decays: list = field(default_factory=list)
    _energy: list = field(default_factory=list)

    def __post_init__(self):
        if self.chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {self.chunk}")
        self.chunk = min(self.chunk, self.periods)
        self.closed_loop = (self.bucket.replan is not None
                            and self.bucket.kind == "feel")
        self._planner = _make_planner(self.bucket, self.data,
                                      per_row=self.closed_loop)

    @property
    def done(self) -> bool:
        return self.collected >= self.periods

    @property
    def can_advance(self) -> bool:
        """Whether the next chunk can be planned+dispatched right now
        (without a blocking collect first)."""
        if self.dispatched >= self.periods:
            return False
        return not (self.closed_loop and self._pending)

    def advance(self) -> None:
        """Plan and dispatch the next chunk (host work + async enqueue)."""
        if not self.can_advance:
            raise RuntimeError(
                "cannot advance: horizon fully dispatched, or a "
                "closed-loop chunk awaits collection")
        p_c = min(self.chunk, self.periods - self.planned)
        warm = self.closed_loop and self.planned > 0
        plan = self._planner.plan(p_c, warm_start=warm)
        self.planned += p_c
        handle = dispatch_bucket(plan, self.data, self.test,
                                 mesh=self.mesh, state=self._state)
        self._state = handle.state
        self._pending.append((p_c, handle))
        self.dispatched += p_c

    def collect(self) -> tuple:
        """Block on the oldest in-flight chunk; bank its host series and
        (closed loop) feed its realized decays to the ξ estimators.
        Returns the banked ``(losses, accs, times, global_batch)`` chunk —
        each ``(n, P_c)`` — so streaming consumers (``repro.serve``) can
        forward per-chunk results without reaching into the run."""
        if not self._pending:
            raise RuntimeError("no chunk in flight to collect")
        p_c, handle = self._pending.popleft()
        n = len(self.bucket.rows)
        with obs.span("repro.collect.wait", **handle.ids):
            losses = np.asarray(handle.losses)[:n]
            accs = np.asarray(handle.accs)[:n]
            if self.closed_loop:
                decays = np.asarray(handle.decays)[:n]
        if self.closed_loop:
            self._decays.append(decays)
            self._planner.observe(decays, handle.global_batch)
        chunk = (losses, accs, handle.times, handle.global_batch)
        self._chunks.append(chunk)
        if handle.energy is not None:
            self._energy.append(handle.energy)
        self.collected += p_c
        return chunk

    def park(self) -> list:
        """Suspend the run at the current chunk boundary: collect every
        in-flight chunk (returned, oldest first, so the caller can still
        stream them) and fence the engine carry
        (:meth:`~repro.fed.engine.EngineState.block_until_ready`).  A
        parked run holds only finished host/device buffers — resuming it
        later (plain :meth:`advance`) is bit-identical to never having
        parked, because chunked execution is interleaving-invariant by
        construction."""
        banked = []
        while self._pending:
            banked.append(self.collect())
        if self._state is not None:
            self._state.block_until_ready()
        return banked

    @property
    def realized_decays(self) -> Optional[np.ndarray]:
        """(n, collected) realized per-period loss decays banked so far
        (closed-loop runs only — ``None`` open loop)."""
        if not self._decays:
            return None
        return np.concatenate(self._decays, axis=1)

    @property
    def energy_ledger(self) -> Optional[np.ndarray]:
        """(n, collected, k_pad) per-user joules spent per period, banked
        chunk by chunk (``None`` unless the bucket's specs set an
        ``EnergyBudget``).  A host-side ledger like ``times`` — it never
        crosses the device boundary."""
        if not self._energy:
            return None
        return np.concatenate(self._energy, axis=1)

    def result(self):
        """The full-horizon ``(losses, accs, times, global_batch)`` —
        chunk series concatenated along the period axis."""
        if not self.done:
            raise RuntimeError(
                f"bucket not fully collected: {self.collected} of "
                f"{self.periods} periods")
        return tuple(np.concatenate([c[j] for c in self._chunks], axis=1)
                     for j in range(4))

    def run_serial(self):
        """The reference schedule: strictly plan → dispatch → collect one
        chunk at a time.  Returns :meth:`result`."""
        while not self.done:
            if self.can_advance:
                self.advance()
            self.collect()
        return self.result()

    def drain(self):
        """Finish the bucket with maximal plan-ahead: dispatch whatever
        the closed-loop guard admits, collect otherwise.  Returns
        :meth:`result`."""
        while not self.done:
            while self.can_advance:
                self.advance()
            self.collect()
        return self.result()
