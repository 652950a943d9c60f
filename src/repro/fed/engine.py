"""Device-resident FEEL trajectory engine.

The seed trainer executed one Python iteration per period with ``float()``
host syncs on every step, so even the tier-1 benchmarks crawled.  This
module compiles the whole trajectory into ONE jitted program:

  * host side (cheap numpy, done once up front): the scheduler plans the
    full horizon (``FeelScheduler.plan_horizon``), the batcher pre-samples
    every period's indices/masks, and the latency ledger is cumsum'd into
    a time axis — that is the :class:`Schedule`;
  * device side: ``jax.lax.scan`` over periods runs gather → per-device
    grads → SBC compression with error-feedback residuals → eq. (1)
    aggregation → SGD update → test metrics, with zero per-period host
    transfers;
  * ``vmap`` over the leading seed axis turns the same program into a
    batched multi-seed sweep (see ``repro.fed.sweep``).

ξ feedback becomes open-loop within a horizon (the paper's known-constant
treatment of ξ) and is applied post-hoc from the realized decay series, so
the trajectory is a pure function of the pre-generated schedule — which is
exactly what makes it scan-compilable and vmap-able.

The scan is *resumable*: the carry is an explicit :class:`EngineState`
(params + SBC residuals for the FEEL family, per-device params for the
dev family) that every ``run_*`` function accepts in and hands back out,
so a horizon may run as N chunked scans — each consuming one slice of the
schedule — bit-identical to one monolithic scan (the per-period step is a
pure function of carry and inputs, and ``lax.scan`` never re-associates
across steps; test-enforced).  That is what lets ``api.lowering`` plan
chunk *c+1* while chunk *c* executes, and re-plan with a ξ estimate
updated from chunk *c*'s realized decays (closed-loop Algorithm 1).
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression.sbc import compress_dense
from repro.fed import feel_model

tree_map = jax.tree_util.tree_map

# Incremented inside the traced bodies below, i.e. exactly once per jit
# trace.  ``api.Experiment`` buckets assert on this: a whole grid of
# shape-compatible scenarios must cost ONE trace, not one per cell.
# ``events`` is the structured ledger behind the count: one TraceEvent per
# trace, carrying the program-cache key and the abstract argument
# signature, so ``analysis.compile_audit`` can prove not just *how many*
# traces happened but that no (key, signature) pair ever traced twice —
# a duplicate is a retrace the jit cache should have absorbed.
_TRACES = {"n": 0, "events": [], "suspended": 0}


class TraceEvent(NamedTuple):
    """One jit trace of a trajectory program.

    ``kind`` names the program family (``feel`` / ``dev``); ``key`` is the
    ``lru_cache`` key that selects the compiled program (static config);
    ``signature`` is the flattened (shape, dtype, sharding) tuple of the
    traced arguments.  Two events with identical (kind, key, signature) mean the
    same program traced twice for the same abstract inputs — a retrace.
    """
    kind: str
    key: tuple
    signature: tuple


def trace_count() -> int:
    """Total number of trajectory-program traces so far in this process."""
    return _TRACES["n"]


def trace_events() -> tuple:
    """The structured trace ledger (one :class:`TraceEvent` per trace)."""
    return tuple(_TRACES["events"])


@contextlib.contextmanager
def suspend_trace_count():
    """Hide traces from the ledger while the context is active.

    The audit probes (``api.lowering.trace_bucket``) call ``jax.make_jaxpr``
    on the very programs whose trace discipline the ledger certifies;
    tracing for *inspection* must not look like a retrace, so probes run
    under this context.
    """
    _TRACES["suspended"] += 1
    try:
        yield
    finally:
        _TRACES["suspended"] -= 1


def _record_trace(kind: str, key: tuple, args) -> None:
    """Called from INSIDE traced bodies, i.e. exactly once per jit trace."""
    if _TRACES["suspended"]:
        return
    _TRACES["n"] += 1
    # jit keys its trace cache on each argument's sharding too (a committed
    # mesh-sharded input traces apart from a plain one of the same shape)
    sig = tuple((tuple(a.shape), str(a.dtype), str(jax.typeof(a).sharding))
                for a in jax.tree_util.tree_leaves(args))
    _TRACES["events"].append(TraceEvent(kind=kind, key=key, signature=sig))


# ---------------------------------------------------------------------------
# host -> device dtype boundary
# ---------------------------------------------------------------------------
#
# Host planners (core/scheduler.py, channels/model.py) deliberately work in
# numpy float64 — the latency ledgers are cumulative sums where 32-bit
# drift would change simulated-time results — but device programs are
# strictly 32-bit.  ``host_to_device`` below is the ONE sanctioned
# crossing: every jitted trajectory entry point funnels its array inputs
# through it, and ``assert_device_safe`` (also called by the
# compile-hygiene pass on lowered jaxprs) enforces that nothing 64-bit
# leaks past it.  ``times``/``global_batch`` never cross: they are
# host-side ledgers joined to device series only after collection.

_DEVICE_DTYPES = {"f": jnp.float32, "i": jnp.int32, "u": jnp.uint32,
                  "b": jnp.bool_, "c": jnp.complex64}


def host_to_device(tree):
    """Cast a pytree of host (numpy) arrays to device-safe dtypes.

    Floats → float32, ints → int32, bools pass through.  This is the
    single documented host↔device boundary; planners stay float64 on the
    host side and nothing 64-bit crosses it.
    """
    def cast(a):
        a = jnp.asarray(a)
        kind = np.dtype(a.dtype).kind
        target = _DEVICE_DTYPES.get(kind)
        if target is not None and a.dtype != target:
            a = a.astype(target)
        return a
    return tree_map(cast, tree)


def assert_device_safe(tree, where: str = "jit boundary"):
    """Raise if any leaf about to enter a jitted program is 64-bit."""
    for leaf in jax.tree_util.tree_leaves(tree):
        dtype = np.dtype(getattr(leaf, "dtype", np.asarray(leaf).dtype))
        if dtype.itemsize == 8 and dtype.kind in "fiuc":
            raise TypeError(
                f"64-bit array ({dtype}) reached {where}; host planners "
                "must cross through engine.host_to_device first")
    return tree


def _shard_batch_args(mesh, batched_args, replicated_args):
    """Lay a bucket out on a device mesh: leading (scenario × seed) batch
    axis sharded, datasets replicated.  Single-device meshes degenerate to
    plain device placement, so this is safe as a CPU fallback."""
    from repro.launch.mesh import batch_sharding, replicated_sharding
    batched_args = jax.device_put(batched_args, batch_sharding(mesh))
    replicated_args = jax.device_put(replicated_args,
                                     replicated_sharding(mesh))
    return batched_args, replicated_args


@dataclass(frozen=True)
class Schedule:
    """Everything host-generated that one trajectory consumes."""
    idx: np.ndarray           # (P, K, slot) int32 — per-device sample indices
    weight: np.ndarray        # (P, K, slot) f32 — eq. (1) masks realizing B_k
    batch: np.ndarray         # (P, K) f32 — B_k (aggregation weights)
    lr: np.ndarray            # (P,) f32 — η per period
    times: np.ndarray         # (P,) f64 — cumulative simulated seconds
    global_batch: np.ndarray  # (P,) int
    # (P,) f32 fixed aggregation denominator, or None.  Horvitz-Thompson
    # weighted sampling plans batchsizes for the FULL fleet and divides
    # each cohort's eq. (1) sum by p·Σ_all b̄_k instead of the realized
    # Σ_cohort b_k; zero entries (the None default) fall back to the
    # realized sum inside the step, so unweighted schedules are bitwise
    # unchanged.
    aggden: Optional[np.ndarray] = None

    @property
    def periods(self) -> int:
        return self.idx.shape[0]

    def stacked_xs(self):
        """The per-period scan inputs, crossed through the device boundary.

        The scheduler plans in float64 (host precision); this is where the
        plan becomes device data — one cast, via :func:`host_to_device`.
        ``times``/``global_batch`` stay host-side and never cross.
        ``aggden`` always crosses (zeros when unset) so weighted and
        unweighted schedules share one program signature.
        """
        aggden = (np.zeros(self.idx.shape[0], np.float32)
                  if self.aggden is None else self.aggden)
        return host_to_device({
            "idx": self.idx,
            "weight": self.weight,
            "batch": self.batch,
            "lr": self.lr,
            "aggden": aggden,
        })


def slice_schedule(schedule: Schedule, lo: int, hi: int) -> Schedule:
    """The ``[lo, hi)`` period window of a schedule (chunked execution).

    ``times`` keeps its absolute cumulative values — a sliced schedule's
    ledger is the matching window of the monolithic ledger, so chunked
    results concatenate back bit-identically.
    """
    return Schedule(idx=schedule.idx[lo:hi], weight=schedule.weight[lo:hi],
                    batch=schedule.batch[lo:hi], lr=schedule.lr[lo:hi],
                    times=schedule.times[lo:hi],
                    global_batch=schedule.global_batch[lo:hi],
                    aggden=None if schedule.aggden is None
                    else schedule.aggden[lo:hi])


@dataclass
class EngineState:
    """Explicit scan carry, in and out of every trajectory function.

    ``params`` are the global model parameters (FEEL family) or the
    per-device parameter stacks (dev family, where ``residual`` stays
    ``None``); ``residual`` is the SBC error-feedback state.  Leaves are
    (possibly batched, possibly sharded) device arrays and may still be
    in flight — resuming a scan from an uncollected state is exactly how
    chunked dispatch pipelines without host round-trips.

    Because the carry is explicit, a *suspended* trajectory is nothing
    but a parked ``EngineState`` (plus the host planner's rng/offset
    state): the serving layer (``repro.serve``) preempts a long horizon
    at a chunk boundary by simply holding onto this state and resumes it
    later bit-identically.  :meth:`block_until_ready` is the park
    operation — it fences the in-flight device work so a suspended run
    holds finished buffers rather than a growing dispatch queue while
    other requests use the device.
    """
    params: object
    residual: object = None

    def block_until_ready(self) -> "EngineState":
        """Fence the carry: block until every in-flight leaf has been
        computed (the parked-state lifecycle used when a run is
        preempted).  Values are unchanged — parking is purely a
        synchronization point, never a semantic one."""
        jax.block_until_ready((self.params, self.residual))
        return self

    @property
    def is_ready(self) -> bool:
        """Whether every leaf has finished computing (best-effort: hosts
        arrays without an ``is_ready`` probe count as ready)."""
        return all(bool(leaf.is_ready()) if hasattr(leaf, "is_ready")
                   else True
                   for leaf in jax.tree_util.tree_leaves(
                       (self.params, self.residual)))


def build_schedule(scheduler, batcher, devices, periods: int,
                   local_steps: int = 1, horizon=None,
                   time_offset: float = 0.0) -> Schedule:
    """Pre-generate one run's plans, sample indices and time axis.

    Consumes the scheduler/batcher rng streams in the same per-period order
    as the seed's interleaved loop (the two streams are independent), so a
    fresh simulation reproduces the seed's sampling sequence exactly.
    ``horizon`` short-circuits planning when the caller already planned it
    (e.g. ``core.scheduler.plan_horizons_batch`` across a whole bucket).
    ``time_offset`` seeds the cumulative time axis for chunked horizons:
    the cumsum accumulates *from* the offset (not adds it afterwards —
    float addition is non-associative, and only the seeded form is
    bit-identical to the monolithic ledger; offset 0.0 degenerates to the
    plain cumsum bitwise since ``0.0 + x == x``).

    A sampled horizon (``horizon.participation`` set) masks the
    ``local_steps > 1`` compute-latency max to the period's participants —
    a sampled-out straggler cannot stretch a round it does not join.
    """
    if horizon is None:
        horizon = scheduler.plan_horizon(periods)
    idx = np.empty((periods, batcher.k, batcher.slot), np.int32)
    w = np.empty((periods, batcher.k, batcher.slot), np.float32)
    for p in range(periods):
        i_p, w_p = batcher.sample(horizon.batch[p])
        idx[p] = i_p
        w[p] = w_p
    per_period = horizon.latency.copy()
    if local_steps > 1:
        # tau local steps multiply the local-compute subperiod (paper §VII)
        part = getattr(horizon, "participation", None)
        slow = getattr(horizon, "slowdown", None)
        if slow is None:
            slow = np.ones_like(np.asarray(horizon.batch, np.float64))
        if part is None:
            per_period += (local_steps - 1) * np.array(
                [max(float(sl) * float(d.local_grad_latency(b))
                     for d, b, sl in zip(devices, bp, sp))
                 for bp, sp in zip(horizon.batch, slow)])
        else:
            # sampled horizon: only the period's participants compete in
            # the straggler max (a GPU's b=0 floor latency is nonzero, so
            # an unmasked max would charge absent users' idle floors)
            per_period += (local_steps - 1) * np.array(
                [max(float(sl) * float(d.local_grad_latency(b))
                     for d, b, m, sl in zip(devices, bp, mp, sp) if m > 0.5)
                 for bp, mp, sp in zip(horizon.batch, part, slow)])
    times = np.cumsum(np.concatenate([[time_offset], per_period]))[1:]
    aggden = getattr(horizon, "aggden", None)
    return Schedule(idx=idx, weight=w,
                    batch=horizon.batch.astype(np.float32),
                    lr=horizon.lr.astype(np.float32),
                    times=times,
                    global_batch=horizon.global_batch,
                    aggden=None if aggden is None
                    else aggden.astype(np.float32))


def zero_residual(params, k: int):
    """Fresh SBC error-feedback state: one residual per device per leaf."""
    return tree_map(lambda p: jnp.zeros((k,) + p.shape, p.dtype), params)


def pad_schedule(schedule: Schedule, k: int) -> Schedule:
    """Zero-pad a schedule's user axis to ``k`` rows (the ragged-fleet
    bucket contract): padded users get index 0, weight 0 and batch 0, so
    they gather real samples but contribute exactly nothing to any
    weighted loss, gradient, or eq. (1) aggregation.  Host ledgers
    (times/lr/global_batch) are per-period and untouched."""
    kk = schedule.idx.shape[1]
    if kk == k:
        return schedule
    pad3 = ((0, 0), (0, k - kk), (0, 0))
    return Schedule(idx=np.pad(schedule.idx, pad3),
                    weight=np.pad(schedule.weight, pad3),
                    batch=np.pad(schedule.batch, ((0, 0), (0, k - kk))),
                    lr=schedule.lr, times=schedule.times,
                    global_batch=schedule.global_batch,
                    aggden=schedule.aggden)


# ---------------------------------------------------------------------------
# the scanned period step (Steps 1-5 of the paper's §II-A loop, pure jnp)
# ---------------------------------------------------------------------------


def _period_step(data_x, data_y, test_x, test_y, local_steps,
                 compress, ratio, carry, xs):
    params, residual = carry
    idx, w, bk, lr = xs["idx"], xs["weight"], xs["batch"], xs["lr"]
    # active: (K,) f32 {0,1} — THIS period's user mask, a per-step scan
    # input (time-varying per-round participation; the PR-4 static padded
    # mask is the constant special case).  The schedule already carries
    # zero weights/batch for inactive users; multiplying keeps that
    # invariant even for hand-built schedules (x * 1.0 == x bitwise, so
    # fully-active rows are unchanged).
    active = xs["active"]
    w = w * active[:, None]
    bk = bk * active
    x = data_x[idx]                              # (K, slot, D)
    y = data_y[idx]
    xf = x.reshape(-1, x.shape[-1])
    yf = y.reshape(-1)
    wf = w.reshape(-1)
    loss_before = feel_model.loss_fn(params, xf, yf, wf)

    if local_steps == 1:
        grads = jax.vmap(jax.grad(feel_model.loss_fn),
                         in_axes=(None, 0, 0, 0))(params, x, y, w)
    else:
        # tau>1: per-device local SGD; upload the cumulative update
        # (parameter delta) as the "gradient" (paper §VII extension)
        dev_params = tree_map(
            lambda a: jnp.broadcast_to(a, (x.shape[0],) + a.shape), params)
        for _ in range(local_steps):
            g = jax.vmap(jax.grad(feel_model.loss_fn))(dev_params, x, y, w)
            dev_params = tree_map(lambda p, gg: p - lr * gg, dev_params, g)
        grads = tree_map(lambda p0, pk: (p0[None] - pk) / lr,
                         params, dev_params)

    if compress:
        # per-device SBC: every device sparsifies its OWN upload (the
        # paper's per-device uplink compression), which also makes the
        # top-k fraction a function of the device payload alone — a padded
        # (all-zero-gradient) user row compresses to exact zeros and the
        # active rows compress identically at any fleet padding.
        grads, residual = jax.vmap(
            lambda g, r: compress_dense(g, ratio, r))(grads, residual)
    # eq. (1): weighted average by B_k (padded rows carry B_k = 0).  A
    # positive ``aggden`` fixes the denominator (Horvitz-Thompson
    # weighted sampling: p·Σ_all b̄_k); zero falls back to the realized
    # cohort sum, which is the classic (biased-under-sampling) estimator
    # and bitwise identical to the pre-aggden step.
    den = xs["aggden"]
    wk = bk / jnp.where(den > 0, den, jnp.sum(bk))
    agg = tree_map(lambda g: jnp.tensordot(wk, g, axes=1), grads)
    params = tree_map(lambda p, g: p - lr * g, params, agg)

    loss_after = feel_model.loss_fn(params, xf, yf, wf)
    acc = feel_model.accuracy(params, test_x, test_y)
    return (params, residual), (loss_after, acc, loss_before - loss_after)


@lru_cache(maxsize=None)
def _trajectory_fn(local_steps: int, compress: bool, ratio: float,
                   batched: bool):
    key = (local_steps, compress, ratio, batched)

    def run(params0, residual0, active, xs, data_x, data_y, test_x, test_y):
        # active (P, K) rides the scan next to the schedule arrays
        step = partial(_period_step, data_x, data_y, test_x, test_y,
                       local_steps, compress, ratio)
        (params, residual), series = jax.lax.scan(
            step, (params0, residual0), dict(xs, active=active))
        return params, residual, series

    if batched:
        run = jax.vmap(run, in_axes=(0, 0, 0, 0, None, None, None, None))

    def traced(params0, residual0, active, xs, *data):
        # host side effect at trace time: ledger entry (exactly one/trace).
        # Must sit OUTSIDE the vmap so the signature keeps the batch axis
        # (inside, distinct-N programs would collide into one triple).
        _record_trace("feel", key, (params0, residual0, active, xs, *data))
        return run(params0, residual0, active, xs, *data)

    return jax.jit(traced)


def trajectory_program(local_steps: int = 1, compress: bool = True,
                       ratio: float = 0.005, batched: bool = True):
    """The (cached) jitted FEEL trajectory program for a static config.

    Public accessor for introspection — ``analysis``' probes call
    ``jax.make_jaxpr`` on this under :func:`suspend_trace_count`.
    """
    return _trajectory_fn(local_steps, compress, float(ratio), batched)


def dev_trajectory_program(average: bool, batched: bool = True):
    """The (cached) jitted dev-family program (see
    :func:`trajectory_program`)."""
    return _dev_trajectory_fn(bool(average), batched)


def run_trajectory(params0, residual0, schedule: Schedule, data, test, *,
                   local_steps: int = 1, compress: bool = True,
                   ratio: float = 0.005, active=None):
    """One trajectory as a single jitted ``lax.scan``.

    ``active``: optional f32 {0,1} user mask (default all-active) — either
    static ``(K,)`` (broadcast to every period: ragged-fleet padding) or
    time-varying ``(P, K)`` (per-round participation).  Zero entries
    contribute nothing to that period.  Returns (final params, final
    residuals, (losses, accs, decays)) where the series are per-period
    device arrays of length ``schedule.periods``.
    """
    if active is None:
        active = jnp.ones((schedule.periods, schedule.idx.shape[1]),
                          jnp.float32)
    else:
        active = jnp.asarray(active)
        if active.ndim == 1:
            active = jnp.broadcast_to(
                active[None, :], (schedule.periods, active.shape[0]))
    fn = _trajectory_fn(local_steps, compress, float(ratio), False)
    args = (params0, residual0, host_to_device(active),
            schedule.stacked_xs(), *host_to_device(
                (data.x, data.y, test.x, test.y)))
    return fn(*assert_device_safe(args, "run_trajectory"))


def stack_schedules(schedules: Sequence[Schedule]):
    """Stack per-scenario schedules along a leading batch axis → scan xs."""
    per_seed = [s.stacked_xs() for s in schedules]
    return {k: jnp.stack([p[k] for p in per_seed])
            for k in ("idx", "weight", "batch", "lr", "aggden")}


def _normalize_active_batch(active, n: int, periods: int, k: int):
    """Normalize a batched ``active`` argument to the (N, P, K) the scan
    consumes: ``None`` → all ones; a static (N, K) mask broadcasts across
    periods (the PR-4 ragged-padding case — value-identical, since the
    per-period multiply reuses the same {0,1} row every step)."""
    if active is None:
        return jnp.ones((n, periods, k), jnp.float32)
    active = jnp.asarray(active)
    if active.ndim == 2:
        active = jnp.broadcast_to(active[:, None, :], (n, periods, k))
    return host_to_device(active)


def run_trajectory_batch(params0, residual0, schedules: Sequence[Schedule],
                         data, test, *, local_steps: int = 1,
                         compress: bool = True, ratio: float = 0.005,
                         mesh=None, active=None):
    """Batched sweep: one compiled program advances every (scenario, seed).

    ``params0``/``residual0`` carry a leading batch axis (stack pytrees with
    ``jax.tree_util.tree_map(lambda *a: jnp.stack(a), *per_entry)``);
    ``schedules`` is one pre-generated :class:`Schedule` per batch entry —
    the axis may flatten an arbitrary (scenario × seed) grid, not just
    seeds.  Entries need not share a fleet size: pad each schedule to the
    common K (:func:`pad_schedule`) and pass ``active`` — an (N, K)
    static or (N, P, K) time-varying f32 {0,1} per-row user mask (default
    all-active) whose zero entries are padded / sampled-out users
    contributing nothing to any reduction.  With ``mesh``
    (a 1-D "batch" mesh from ``launch.mesh.make_batch_mesh``) the batch
    axis is sharded across its devices (batch size must divide evenly;
    pad upstream) and the datasets are replicated; ``mesh=None`` keeps the
    single-device layout.
    """
    xs = stack_schedules(schedules)
    active = _normalize_active_batch(active, len(schedules),
                                     schedules[0].periods,
                                     schedules[0].idx.shape[1])
    data_args = host_to_device((data.x, data.y, test.x, test.y))
    if mesh is not None:
        (params0, residual0, active, xs), data_args = _shard_batch_args(
            mesh, (params0, residual0, active, xs), data_args)
    fn = _trajectory_fn(local_steps, compress, float(ratio), True)
    assert_device_safe((params0, residual0, active, xs, data_args),
                       "run_trajectory_batch")
    return fn(params0, residual0, active, xs, *data_args)


# ---------------------------------------------------------------------------
# per-device-parameter schemes (individual / model_fl) — same engine idea
# ---------------------------------------------------------------------------


def _dev_step(data_x, data_y, test_x, test_y, lr, average,
              dev_params, xs):
    # active: (K,) f32 {0,1} — THIS period's user mask (time-varying, a
    # scan input alongside the indices).  The update itself is masked, so
    # a sampled-out user's parameters hold still until it participates
    # again; for the always-active case g * 1.0 == g keeps the trained
    # rows bitwise unchanged.
    idx, active = xs
    x = data_x[idx]
    y = data_y[idx]
    g = jax.vmap(jax.grad(feel_model.loss_fn))(dev_params, x, y)
    dev_params = tree_map(
        lambda p, gg: p - lr * (gg * active.reshape(
            (-1,) + (1,) * (gg.ndim - 1))), dev_params, g)
    # masked device mean: padded / sampled-out user rows (active 0) must
    # never enter a parameter average — denominator is the active count
    # (for an all-active mask this is sum(a)/K == mean bitwise)
    n_active = jnp.sum(active)

    def masked_mean(a):
        m = active.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.sum(a * m, axis=0) / n_active

    if average:
        # FedAvg: replace every device copy with the parameter mean
        dev_params = tree_map(
            lambda a: jnp.broadcast_to(masked_mean(a), a.shape), dev_params)
    avg = tree_map(masked_mean, dev_params)
    loss = feel_model.loss_fn(avg, test_x, test_y)
    acc = feel_model.accuracy(avg, test_x, test_y)
    return dev_params, (loss, acc)


@lru_cache(maxsize=None)
def _dev_trajectory_fn(average: bool, batched: bool = False):
    key = (average, batched)

    def run(dev_params0, idx, lr, active, data_x, data_y, test_x, test_y):
        # active (P, K) rides the scan next to the period indices
        step = partial(_dev_step, data_x, data_y, test_x, test_y, lr,
                       average)
        return jax.lax.scan(step, dev_params0, (idx, active))

    if batched:
        run = jax.vmap(run, in_axes=(0, 0, 0, 0, None, None, None, None))

    def traced(dev_params0, idx, lr, active, *data):
        # trace-time ledger entry — outside the vmap, see _trajectory_fn
        _record_trace("dev", key, (dev_params0, idx, lr, active, *data))
        return run(dev_params0, idx, lr, active, *data)

    return jax.jit(traced)


def run_dev_trajectory(dev_params0, idx: np.ndarray, lr: float, data, test,
                       *, average: bool, active=None):
    """scan-compiled individual / model_fl (``average=True``) trajectory.

    ``idx``: (P, K, batch) pre-sampled indices; ``active``: optional (K,)
    static or (P, K) time-varying f32 {0,1} user mask (default
    all-active).  Returns (final per-device params, (test losses, test
    accs)) per period.
    """
    idx = np.asarray(idx)
    if active is None:
        active = jnp.ones(idx.shape[:2], jnp.float32)
    else:
        active = jnp.asarray(active)
        if active.ndim == 1:
            active = jnp.broadcast_to(active[None, :], idx.shape[:2])
    fn = _dev_trajectory_fn(bool(average))
    args = (dev_params0, *host_to_device((np.asarray(idx),
                                          np.float32(lr), active,
                                          data.x, data.y, test.x, test.y)))
    return fn(*assert_device_safe(args, "run_dev_trajectory"))


def resume_trajectory_batch(state: EngineState, schedules: Sequence[Schedule],
                            data, test, *, local_steps: int = 1,
                            compress: bool = True, ratio: float = 0.005,
                            mesh=None, active=None):
    """Advance a batched FEEL trajectory by one schedule chunk.

    ``state`` is the carry from the previous chunk (or a fresh
    :class:`EngineState` of stacked init params + ``zero_residual``-style
    residuals).  Returns ``(EngineState, (losses, accs, decays))`` — a
    horizon run as N chunked calls is bit-identical to one monolithic
    :func:`run_trajectory_batch` (test-enforced).  The returned state's
    leaves may be in flight: resuming from them pipelines chunk *c+1*
    behind chunk *c* without blocking.
    """
    params, residual, series = run_trajectory_batch(
        state.params, state.residual, schedules, data, test,
        local_steps=local_steps, compress=compress, ratio=ratio,
        mesh=mesh, active=active)
    return EngineState(params=params, residual=residual), series


def run_dev_trajectory_batch(dev_params0, idx: np.ndarray, lr: np.ndarray,
                             data, test, *, average: bool, mesh=None,
                             active=None):
    """Batched individual / model_fl: one program for a whole bucket.

    ``dev_params0`` leaves are (N, K, ...), ``idx`` is (N, P, K, batch),
    ``lr`` is (N,) — N the flattened (scenario × seed) axis; ``active`` is
    an optional (N, K) static or (N, P, K) time-varying f32 {0,1} per-row
    user mask (zero entries = padded / sampled-out users, excluded from
    every parameter average).  ``mesh`` shards N across devices as in
    :func:`run_trajectory_batch`.
    """
    idx = host_to_device(np.asarray(idx))
    active = _normalize_active_batch(active, idx.shape[0], idx.shape[1],
                                     idx.shape[2])
    batched = (dev_params0, idx, *host_to_device((np.asarray(lr), active)))
    data_args = host_to_device((data.x, data.y, test.x, test.y))
    if mesh is not None:
        batched, data_args = _shard_batch_args(mesh, batched, data_args)
    fn = _dev_trajectory_fn(bool(average), batched=True)
    assert_device_safe((batched, data_args), "run_dev_trajectory_batch")
    return fn(*batched, *data_args)


def resume_dev_trajectory_batch(state: EngineState, idx: np.ndarray,
                                lr: np.ndarray, data, test, *,
                                average: bool, mesh=None, active=None):
    """Advance a batched dev-family trajectory by one index chunk.

    The dev carry is the per-device parameter stack alone (``residual``
    stays ``None``).  Returns ``(EngineState, (losses, accs))``; chunked
    calls are bit-identical to one monolithic
    :func:`run_dev_trajectory_batch` (test-enforced).
    """
    dev_params, series = run_dev_trajectory_batch(
        state.params, idx, lr, data, test, average=average, mesh=mesh,
        active=active)
    return EngineState(params=dev_params), series


# ---------------------------------------------------------------------------
# hierarchical FEEL (cell → edge-server → cloud, repro.topology.Topology)
# ---------------------------------------------------------------------------
#
# The flat FEEL scan keeps ONE global model; the hierarchical scan keeps
# one model replica PER EDGE SERVER (leaves grow a leading E axis) and the
# ``member`` one-hot (E, K) matrix routes users to replicas.  Every period
# each edge aggregates its own users' (compressed) gradients eq.-(1)-style
# into its replica; on cloud rounds (``xs["cloud"]`` = 1, cadence
# ``Topology.agg_every``) the replicas merge into the batch-weighted
# global average.  Reported metrics always evaluate that global average,
# so the series join the same Results surface as the flat family.
# Padded users are all-zero ``member`` columns AND active-mask zeros, so
# both the routing contraction and the weight normalization see the
# monoid identity — the PR-4 padded-row contract carries over unchanged.


def _hier_period_step(data_x, data_y, test_x, test_y, member, local_steps,
                      compress, ratio, carry, xs):
    params_e, residual = carry                    # leaves (E, ...) / (K, ...)
    idx, w, bk, lr = xs["idx"], xs["weight"], xs["batch"], xs["lr"]
    active, cloud = xs["active"], xs["cloud"]
    w = w * active[:, None]
    bk = bk * active
    # edge bookkeeping: s_e — per-edge batch mass; wk — per-edge eq. (1)
    # weights (a participant-free edge gets all-zero weights and a guard
    # denominator, so its replica simply holds still this period); beta —
    # batch share per edge, the cloud-merge and evaluation weights
    s_e = jnp.tensordot(member, bk, axes=1)                       # (E,)
    wk = member * bk[None, :] / jnp.where(s_e > 0, s_e, 1.0)[:, None]
    beta = s_e / jnp.sum(s_e)                                     # (E,)

    def cloud_view(tree):
        return tree_map(lambda a: jnp.tensordot(beta, a, axes=1), tree)

    # each user trains from ITS edge's replica (one-hot gather)
    user_params = tree_map(
        lambda a: jnp.tensordot(member, a, axes=((0,), (0,))), params_e)
    x = data_x[idx]                                # (K, slot, D)
    y = data_y[idx]
    xf = x.reshape(-1, x.shape[-1])
    yf = y.reshape(-1)
    wf = w.reshape(-1)
    global_before = cloud_view(params_e)
    loss_before = feel_model.loss_fn(global_before, xf, yf, wf)

    if local_steps == 1:
        grads = jax.vmap(jax.grad(feel_model.loss_fn))(user_params, x, y, w)
    else:
        dev_params = user_params
        for _ in range(local_steps):
            g = jax.vmap(jax.grad(feel_model.loss_fn))(dev_params, x, y, w)
            dev_params = tree_map(lambda p, gg: p - lr * gg, dev_params, g)
        grads = tree_map(lambda p0, pk: (p0 - pk) / lr,
                         user_params, dev_params)

    if compress:
        grads, residual = jax.vmap(
            lambda g, r: compress_dense(g, ratio, r))(grads, residual)
    # per-edge eq. (1) aggregation and SGD step on each replica
    agg = tree_map(lambda g: jnp.tensordot(wk, g, axes=1), grads)  # (E, ...)
    params_e = tree_map(lambda p, g: p - lr * g, params_e, agg)
    # cloud round: replicas -> batch-weighted global average, broadcast back
    params_e = tree_map(
        lambda a: jnp.where(cloud > 0.5,
                            jnp.broadcast_to(jnp.tensordot(beta, a, axes=1),
                                             a.shape), a), params_e)
    global_after = cloud_view(params_e)
    loss_after = feel_model.loss_fn(global_after, xf, yf, wf)
    acc = feel_model.accuracy(global_after, test_x, test_y)
    return (params_e, residual), (loss_after, acc, loss_before - loss_after)


@lru_cache(maxsize=None)
def _hier_trajectory_fn(local_steps: int, compress: bool, ratio: float,
                        n_edges: int, batched: bool):
    key = (local_steps, compress, ratio, n_edges, batched)

    def run(params_e0, residual0, member, active, cloud, xs,
            data_x, data_y, test_x, test_y):
        # member (E, K) is scan-invariant; active (P, K) and cloud (P,)
        # ride the scan with the schedule arrays
        step = partial(_hier_period_step, data_x, data_y, test_x, test_y,
                       member, local_steps, compress, ratio)
        (params_e, residual), series = jax.lax.scan(
            step, (params_e0, residual0),
            dict(xs, active=active, cloud=cloud))
        return params_e, residual, series

    if batched:
        run = jax.vmap(run, in_axes=(0, 0, 0, 0, 0, 0,
                                     None, None, None, None))

    def traced(params_e0, residual0, member, active, cloud, xs, *data):
        # trace-time ledger entry — outside the vmap, see _trajectory_fn
        _record_trace("hier", key,
                      (params_e0, residual0, member, active, cloud, xs,
                       *data))
        return run(params_e0, residual0, member, active, cloud, xs, *data)

    return jax.jit(traced)


def hier_trajectory_program(local_steps: int = 1, compress: bool = True,
                            ratio: float = 0.005, n_edges: int = 1,
                            batched: bool = True):
    """The (cached) jitted hierarchical trajectory program (see
    :func:`trajectory_program`)."""
    return _hier_trajectory_fn(local_steps, compress, float(ratio),
                               int(n_edges), batched)


def run_hier_trajectory_batch(params0, residual0, member, cloud,
                              schedules: Sequence[Schedule], data, test, *,
                              local_steps: int = 1, compress: bool = True,
                              ratio: float = 0.005, mesh=None, active=None):
    """Batched hierarchical sweep (cell→edge→cloud; see module section).

    ``params0`` leaves carry (N, E, ...) — one model replica per edge
    server per row; ``member`` is (N, E, K) user→edge one-hot (padded
    users: all-zero columns); ``cloud`` is (N, P) f32 {0,1} cloud-round
    flags (``Topology.cloud_rounds``); ``active`` as in
    :func:`run_trajectory_batch`.
    """
    xs = stack_schedules(schedules)
    active = _normalize_active_batch(active, len(schedules),
                                     schedules[0].periods,
                                     schedules[0].idx.shape[1])
    member = host_to_device(np.asarray(member))
    cloud = host_to_device(np.asarray(cloud))
    data_args = host_to_device((data.x, data.y, test.x, test.y))
    if mesh is not None:
        (params0, residual0, member, active, cloud, xs), data_args = \
            _shard_batch_args(
                mesh, (params0, residual0, member, active, cloud, xs),
                data_args)
    fn = _hier_trajectory_fn(local_steps, compress, float(ratio),
                             int(member.shape[1]), True)
    assert_device_safe((params0, residual0, member, active, cloud, xs,
                        data_args), "run_hier_trajectory_batch")
    return fn(params0, residual0, member, active, cloud, xs, *data_args)


def resume_hier_trajectory_batch(state: EngineState, member, cloud,
                                 schedules: Sequence[Schedule], data, test,
                                 *, local_steps: int = 1,
                                 compress: bool = True, ratio: float = 0.005,
                                 mesh=None, active=None):
    """Advance a batched hierarchical trajectory by one schedule chunk
    (the per-edge replicas + SBC residuals are the carry; chunked calls
    are bit-identical to one monolithic
    :func:`run_hier_trajectory_batch`)."""
    params_e, residual, series = run_hier_trajectory_batch(
        state.params, state.residual, member, cloud, schedules, data, test,
        local_steps=local_steps, compress=compress, ratio=ratio,
        mesh=mesh, active=active)
    return EngineState(params=params_e, residual=residual), series
