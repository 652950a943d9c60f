"""Declarative scenario specification.

A :class:`ScenarioSpec` is everything that defines one experimental cell of
the paper's scenario family — device fleet, wireless cell, data partition,
batchsize policy, Table-II training scheme, compression, learning-rate
base, local-step count, and the seed set — as one frozen, hashable value.
Specs carry no arrays and no rng state: they are *static* configuration,
registered with jax as a static pytree node so they can ride through jit
boundaries untouched.

The bucketing rule (what makes two specs shape-compatible)
----------------------------------------------------------
``Experiment`` lowers each group of shape-compatible specs to ONE compiled
program; :meth:`ScenarioSpec.bucket_key` is that grouping rule.  Two specs
share a bucket iff every quantity that is *structural* for the compiled
trajectory matches:

* scheme family — ``feel``/``gradient_fl`` run the masked-slot FEEL scan;
  ``individual``/``model_fl`` run the per-device-parameter scan (and the
  FedAvg averaging flag is compiled in, so those two never merge);
* slot width (``b_max``, or the dev schemes' fixed epoch batch) — array
  shapes;
* ``local_steps``, ``compress`` and ``compression`` — scan-body structure
  (static python branching / top-k fraction inside the jitted step);
* model architecture (``model_family``, ``hidden``, ``depth``, and for a
  named architecture ``arch`` and ``seq_len``) — the per-device train
  step itself (MLP scan vs big-model transformer/mamba2 step) and the
  parameter pytree shapes;
* ``replan`` (FEEL family) — the closed-loop ξ re-plan interval: the
  horizon executes as ``replan``-period chunked scans with estimator
  feedback between chunks, and every row of a bucket must chunk on the
  same boundary.

The fleet is deliberately NOT part of the key: fleet size and composition
are *sweepable* axes, not structural ones.  The lowering pads every
member's user axis to the bucket's max K and threads an ``active_mask``
({0,1} per user row) end to end — through the channel Monte-Carlo draws,
the masked Algorithm-1 rows solver, the schedules and the engine's
reductions — so a K-heterogeneous grid (``grid(base, users=[...])``)
still costs one compiled program, and every padded row stays bit-identical
to its solo unpadded run.  Device *profiles* never reach the device
program at all (they only shape host planning), so profile-heterogeneous
fleets are shape-compatible by construction.

Everything else — partition, policy, cell geometry, base_lr, seeds — only
changes *values* fed to the program (schedules, initial params), so specs
differing in those still share one bucket and one trace.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax

from repro.channels.model import CellConfig
from repro.core.baselines import POLICIES
from repro.core.latency import DeviceProfile
from repro.dynamics import EnergyBudget, Fading, Faults, TauAdapt
from repro.topology import Sampling, Topology

SCHEMES = ("feel", "gradient_fl", "model_fl", "individual")
# Per-device train-step families the FEEL engine can lower.  ``feel_mlp``
# is the paper's MLP scan; ``transformer`` / ``mamba2`` run the big-model
# train step (fed/train_step.py) with the pallas kernels in the hot path.
MODEL_FAMILIES = ("feel_mlp", "transformer", "mamba2")
# The ``model_family`` path a named architecture (``ScenarioSpec.arch``, a
# ``repro.configs.ARCHS`` entry) trains on, by its ``ArchConfig.family``.
ARCH_FAMILIES = {"ssm": "mamba2"}
# The dev-family schemes train full local epochs with a fixed per-device
# batch; PR-1 capped it at 64 — kept as the lowering rule.
DEV_EPOCH_BATCH_CAP = 64


@dataclass(frozen=True)
class ScenarioSpec:
    """One cell of the scenario family (all four Table-II schemes)."""
    fleet: Tuple[DeviceProfile, ...]
    name: str = ""                       # fleet/cell label for Results axes
    scheme: str = "feel"                 # feel|gradient_fl|model_fl|individual
    partition: str = "noniid"            # iid | noniid
    policy: str = "proposed"             # core.baselines key (feel only)
    cell: CellConfig = field(default_factory=CellConfig)
    compress: bool = True
    compression: float = 0.005           # SBC ratio r
    b_max: int = 128
    base_lr: float = 0.05
    local_steps: int = 1
    seeds: Tuple[int, ...] = (0,)
    hidden: int = 256
    depth: int = 3
    replan: Optional[int] = None         # closed-loop ξ re-plan interval
    sampling: Optional[Sampling] = None  # per-round S-of-K participation
    topology: Optional[Topology] = None  # cell→edge→cloud hierarchy
    fading: Optional[Fading] = None      # block-fading Markov channel drift
    faults: Optional[Faults] = None      # straggler slowdowns + dropout
    energy: Optional[EnergyBudget] = None  # per-user per-period energy caps
    adapt_tau: Optional[TauAdapt] = None   # re-planned local-steps knob
    model_family: str = "feel_mlp"       # feel_mlp | transformer | mamba2
    arch: Optional[str] = None           # a repro.configs.ARCHS name
    seq_len: Optional[int] = None        # tokens a sequence (with arch)

    def __post_init__(self):
        object.__setattr__(self, "fleet", tuple(self.fleet))
        object.__setattr__(self, "seeds", tuple(self.seeds))
        if self.scheme not in SCHEMES:
            raise ValueError(f"scheme {self.scheme!r} not in {SCHEMES}")
        if self.partition not in ("iid", "noniid"):
            raise ValueError(f"partition {self.partition!r}")
        if self.policy not in POLICIES:
            raise ValueError(
                f"policy {self.policy!r} not in {tuple(POLICIES)}")
        if not self.seeds:
            raise ValueError("seeds must be non-empty")
        if self.replan is not None:
            if self.is_dev_scheme:
                raise ValueError(
                    "replan= is the FEEL family's closed-loop ξ interval; "
                    f"the {self.scheme!r} scheme has no batchsize policy "
                    "to re-plan")
            if not isinstance(self.replan, int) or \
                    isinstance(self.replan, bool) or self.replan < 1:
                raise ValueError(
                    f"replan must be a positive int (periods per "
                    f"closed-loop chunk), got {self.replan!r}")
        if self.sampling is not None and \
                not isinstance(self.sampling, Sampling):
            raise TypeError(
                f"sampling= expects a repro.topology.Sampling, got "
                f"{type(self.sampling).__name__}")
        if self.topology is not None:
            if not isinstance(self.topology, Topology):
                raise TypeError(
                    f"topology= expects a repro.topology.Topology, got "
                    f"{type(self.topology).__name__}")
            if self.is_dev_scheme:
                raise ValueError(
                    "topology= hierarchizes the server aggregation; the "
                    f"{self.scheme!r} scheme keeps per-device parameters "
                    "and has no aggregation tier to split")
            if self.k < self.topology.cells:
                raise ValueError(
                    f"fleet of {self.k} users cannot populate the "
                    f"topology's {self.topology.cells} cells")
        for fld, typ in (("fading", Fading), ("faults", Faults),
                         ("energy", EnergyBudget), ("adapt_tau", TauAdapt)):
            val = getattr(self, fld)
            if val is not None and not isinstance(val, typ):
                raise TypeError(
                    f"{fld}= expects a repro.dynamics.{typ.__name__}, got "
                    f"{type(val).__name__}")
        if self.has_dynamics:
            if self.is_dev_scheme:
                raise ValueError(
                    "dynamics (fading/faults/energy/adapt_tau) act through "
                    f"the FEEL planner; the {self.scheme!r} scheme has no "
                    "planner to perturb")
            if self.topology is not None:
                raise ValueError(
                    "dynamics are not threaded through the hierarchical "
                    "per-cell solves yet; drop topology= or the dynamics "
                    "fields")
        if self.adapt_tau is not None:
            if self.replan is None:
                raise ValueError(
                    "adapt_tau= re-plans local steps at closed-loop chunk "
                    "boundaries; set replan= on the spec")
            if self.local_steps not in self.adapt_tau.choices:
                raise ValueError(
                    f"local_steps={self.local_steps} is the starting point "
                    "of the adaptive schedule and must appear in adapt_tau "
                    f"choices {self.adapt_tau.choices!r}")
        if self.model_family not in MODEL_FAMILIES:
            raise ValueError(
                f"model_family {self.model_family!r} not in {MODEL_FAMILIES}")
        if self.model_family != "feel_mlp":
            if self.is_dev_scheme:
                raise ValueError(
                    "big-model families run the FEEL train step; the "
                    f"{self.scheme!r} scheme keeps per-device MLPs")
            if self.topology is not None:
                raise ValueError(
                    "the hierarchical scan is feel_mlp-only; drop "
                    "topology= or use model_family='feel_mlp'")
            if self.local_steps != 1 or self.adapt_tau is not None:
                raise ValueError(
                    "big-model families take one aggregated step per "
                    "period (local_steps=1, no adapt_tau); the local-SGD "
                    "delta-upload loop is feel_mlp-only")
            if self.hidden % 4 != 0:
                raise ValueError(
                    f"model_family={self.model_family!r} derives its "
                    f"ArchConfig from hidden={self.hidden}, which must be "
                    "divisible by 4 (attention heads / SSM head grouping)")
        if self.arch is not None:
            self._check_arch()
        elif self.seq_len is not None:
            raise ValueError(
                "seq_len= is the token length of a named architecture's "
                "text; set arch= too")
        if self.sampling is not None and self.sampling.weighted:
            if self.topology is not None:
                raise ValueError(
                    "weighted (1/p) sampling corrects the flat server "
                    "aggregation; the hierarchical path does not support it")
            if self.energy is not None:
                raise ValueError(
                    "weighted (1/p) sampling needs probabilistic "
                    "inclusion; deterministic energy drops break the "
                    "Horvitz-Thompson correction")

    def _check_arch(self):
        """With ``arch`` set, ``hidden`` is the architecture's d_model,
        ``depth`` the number of its layers kept, and ``seq_len`` the
        tokens of a training sequence."""
        from repro.configs import ARCHS
        arch = ARCHS.get(self.arch)
        if arch is None:
            raise ValueError(
                f"arch {self.arch!r} is not in repro.configs.ARCHS "
                f"({sorted(ARCHS)})")
        family = ARCH_FAMILIES.get(arch.family)
        if family is None:
            raise ValueError(
                f"arch {self.arch!r} is a {arch.family!r} architecture; "
                f"model_family trains only {sorted(ARCH_FAMILIES)} ones")
        if self.model_family != family:
            raise ValueError(
                f"model_family {self.model_family!r} must be {family!r}, "
                f"the family path of arch {self.arch!r} ({arch.family})")
        if self.hidden != arch.d_model:
            raise ValueError(
                f"hidden={self.hidden} must equal arch {self.arch!r}'s "
                f"d_model {arch.d_model}")
        if not 1 <= self.depth <= arch.n_layers:
            raise ValueError(
                f"depth={self.depth} (layers kept) must lie in "
                f"[1, {arch.n_layers}] for arch {self.arch!r}")
        if (not isinstance(self.seq_len, int)
                or isinstance(self.seq_len, bool) or self.seq_len < 1):
            raise ValueError(
                f"seq_len must be a positive int with arch=, got "
                f"{self.seq_len!r}")
        if arch.ssm is not None and self.seq_len % arch.ssm.chunk:
            raise ValueError(
                f"seq_len={self.seq_len} must be divisible by arch "
                f"{self.arch!r}'s SSD chunk {arch.ssm.chunk}")

    # ---- derived lowering attributes -------------------------------------
    @property
    def k(self) -> int:
        return len(self.fleet)

    @property
    def is_dev_scheme(self) -> bool:
        """True for the per-device-parameter schemes (no gradient fusion)."""
        return self.scheme in ("individual", "model_fl")

    @property
    def has_dynamics(self) -> bool:
        """True when any time-varying-world process is configured."""
        return (self.fading is not None or self.faults is not None
                or self.energy is not None or self.adapt_tau is not None)

    @property
    def effective_policy(self) -> str:
        """The batchsize policy the lowering actually applies.

        gradient_fl [40] is the full-batch policy on the FEEL engine; the
        per-device-parameter schemes have no batchsize policy at all —
        they report ``"none"`` so ``Results.sel(policy=...)`` never mixes
        them into FEEL-policy selections."""
        if self.is_dev_scheme:
            return "none"
        return "full" if self.scheme == "gradient_fl" else self.policy

    @property
    def dev_epoch_batch(self) -> int:
        return min(self.b_max, DEV_EPOCH_BATCH_CAP)

    @property
    def label(self) -> str:
        base = self.name or f"K{self.k}"
        return f"{base}/{self.partition}/{self.scheme}/{self.effective_policy}"

    def bucket_key(self) -> tuple:
        """Shape-compatibility class (see module docstring).

        The fleet is absent on purpose: K is padded to the bucket max at
        lowering time (active-mask contract), so fleet size/composition
        sweep *within* a bucket.  ``compression`` is structural only while
        ``compress`` is on (it sets the static top-k fraction inside the
        jitted step); with compression off it affects nothing but the
        *planned* payload bits, so compress-off specs merge regardless of
        ratio — a ``grid(base, compression=[...], compress=[True,
        False])`` ablation costs one program for the whole off column.

        ``replan`` is structural for the FEEL family: a closed-loop spec
        executes its horizon as ``replan``-period chunked scans (the chunk
        boundary is where ξ feedback lands), and a bucket's rows must
        chunk together — one device program per chunk covers the whole
        bucket.

        ``topology`` contributes its structural part — ``(cells, edges,
        agg_every)`` shape the hierarchical scan (number of edge replicas,
        cloud cadence), while ``backhaul_bps`` only changes ledger values
        and is absent.  ``sampling`` is deliberately NOT structural: a
        participation mask is per-period *data* through the same active
        machinery as fleet padding, so sampled and unsampled scenarios
        share one program.

        Dynamics (PR 9): ``faults`` and ``energy`` are value-only (they
        arrive as schedule values and masks), as are a ``Fading`` spec's
        gain values — but the fading *state count* and the ``adapt_tau``
        choice set are structural program-family coordinates: the
        auditor certifies per family, and an adaptive bucket compiles
        one scan-body variant per realized τ, so only rows agreeing on
        the candidate set may chunk together.

        ``model_family`` is structural: the scan body is a different
        program per family (MLP scan vs the big-model train step on the
        pallas kernels), so a ``grid(base, model_family=[...])`` sweep
        lowers to exactly one program per family-bucket; so are ``arch``
        and ``seq_len``, the named architecture and its text's shape."""
        if self.is_dev_scheme:
            return ("dev", self.scheme, self.dev_epoch_batch,
                    self.hidden, self.depth)
        topo = (None if self.topology is None
                else self.topology.structural_key())
        return ("feel", self.b_max, self.local_steps,
                self.compress, self.compression if self.compress else None,
                self.hidden, self.depth, self.replan, topo,
                None if self.fading is None else self.fading.states,
                None if self.adapt_tau is None else self.adapt_tau.choices,
                self.arch, self.seq_len, self.model_family)


jax.tree_util.register_static(ScenarioSpec)
