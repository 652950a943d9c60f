"""Everything one cell needs before its window, built from files and a seed.

A cell is found by name in ``BENCHMARK.json``; its configuration file
(``configs/<config>.json``) gives the model and the dataset shape, its
traffic file (``traffic/<traffic>.json``) the fleet, sampling, grid axes,
horizon and executor, and its limits file (``limits/<cell>.json``) the
limits of the numbers that decide ``correct``.  Nothing here names a cell:
a new cell is new files plus a ``BENCHMARK.json`` entry.

A configuration file may hold three optional keys, so that a model
configuration needs no edit here either.  Where all three are absent,
every function computes what it computes for the MLP configuration:

* ``spec``: keyword arguments added to every ``ScenarioSpec`` of the cell
  (JSON arrays as tuples, objects as dicts; the program decides what it
  accepts).  A keyword the harness sets itself (``HARNESS_SPEC_KEYS``, and
  ``hidden``/``depth`` where the configuration has them) or one that
  ``ScenarioSpec`` rejects is a ``CellError``.  Absent: none added.
* ``data``: what ``make_data`` draws, ``{"kind": "clusters"}`` (Gaussian
  class clusters) or ``{"kind": "markov_tokens", "topics": T, "branch":
  F, "zipf": s}`` (next-token text at the configuration's ``vocab`` and
  ``seq_len``).  Absent: clusters.
* ``reference``: the file under ``models/`` that holds the plain model
  (``reference.config_module``).  Absent: ``models/<model_family>.py``.

The program is driven only through its public entry point,
``Experiment(data, test, specs).run(periods, executor=...)`` with the
program's own executor that the traffic names; ``Spans`` puts a host span
around each bucket phase that executor calls (``plan_bucket`` →
``dispatch_bucket`` → ``collect_bucket``).
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass
from functools import wraps
from pathlib import Path
from typing import Dict, List

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent

# the phases a span is recorded for, in execution order
SPANS = ("plan_bucket", "dispatch_bucket", "collect_bucket")


class CellError(ValueError):
    """A cell, configuration, traffic or limits file that cannot be used."""


# ---------------------------------------------------------------------------
# discovery: every cell resolves to its files by name
# ---------------------------------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: Path) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise CellError(f"no BENCHMARK.json in {root}")
    return json.loads(path.read_text())


def _read_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise CellError(f"{what} file {path} does not exist")
    return json.loads(path.read_text())


def find_cell(root: Path, name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """Resolve one ``workloads`` entry to its configuration, traffic and
    limits files and the metrics it reports."""
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise CellError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _read_json(root / configs[w["config"]]["file"], "config")
    traffic = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json",
                         "traffic")
    limits = _read_json(bench_dir / "limits" / f"{name}.json", "limits")

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def set_precision(config: dict):
    """Run the program at the matmul precision its configuration states
    (``matmul_precision``): on the TPU, JAX's default computes a float32
    matmul in one bfloat16 pass, which is not the float32 training the
    configuration states."""
    import jax
    if config.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          config["matmul_precision"])


# ---------------------------------------------------------------------------
# seeds: every number a run draws comes from --seed
# ---------------------------------------------------------------------------


def derived_seeds(seed: int, n: int, stream: int) -> List[int]:
    """``n`` non-negative 31-bit integers from (seed, stream).  Any whole
    number may be a seed; the program's row seeds and ``jax.random.key``
    get values that fit 32 signed bits."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), stream])
    return [int(v) for v in ss.generate_state(n, np.uint32) & 0x7FFFFFFF]


# stream tags for derived_seeds (one per use, so no two uses share draws)
STREAM_DATA, STREAM_ROWS, STREAM_SAMPLE = 1, 2, 3


# ---------------------------------------------------------------------------
# data: drawn from the seed on the device, of the kind the configuration
# names
# ---------------------------------------------------------------------------


@dataclass
class Dataset:
    x: np.ndarray          # (N, D) float32 features, or (N, S+1) int32 tokens
    y: np.ndarray          # (N,) int32 class or topic


def make_data(config: dict, seed: int):
    """Train and test splits drawn from ``seed``, of the kind the
    configuration's ``data`` object names (``{"kind": "clusters"}`` where
    it has none): ``clusters`` (``_clusters``) or ``markov_tokens``
    (``_markov_tokens``).  Drawn on the default device in one jitted call
    and copied to the host once: the program takes host arrays."""
    import jax

    data = config.get("data") or {"kind": "clusters"}
    draw = DATA_KINDS.get(data.get("kind"))
    if draw is None:
        raise CellError(f"data kind {data.get('kind')!r} is none of "
                        f"{sorted(DATA_KINDS)}")
    n_train, n_test = int(config["n_train"]), int(config["n_test"])
    (s,) = derived_seeds(seed, 1, STREAM_DATA)
    x, y = jax.device_get(draw(config, data, n_train + n_test,
                               jax.random.key(s)))
    x, y = np.asarray(x), np.asarray(y)
    return (Dataset(x[:n_train], y[:n_train]),
            Dataset(x[n_train:], y[n_train:]))


def _clusters(config: dict, data: dict, n: int, key):
    """``classes`` Gaussian centres of norm ``spread`` in ``input_dim``
    dimensions, unit noise around them (the shape of CIFAR-10: 3,072
    features, 10 classes)."""
    import jax
    import jax.numpy as jnp

    dim, classes = int(config["input_dim"]), int(config["classes"])
    spread = float(config["spread"])

    @jax.jit
    def draw(key):
        kc, ky, kx = jax.random.split(key, 3)
        centers = jax.random.normal(kc, (classes, dim)) * (spread
                                                           / dim ** 0.5)
        y = jax.random.randint(ky, (n,), 0, classes)
        x = centers[y] + jax.random.normal(kx, (n, dim))
        return x, y.astype(jnp.int32)

    return draw(key)


def _markov_tokens(config: dict, data: dict, n: int, key):
    """Next-token text: ``n`` sequences of ``seq_len + 1`` ids in
    [0, ``vocab``), each with a topic y uniform over ``topics``.  Every
    (topic, token) has ``branch`` successor ids drawn from a Zipf law of
    exponent ``zipf`` over the vocabulary (ranks mapped to ids by a seeded
    permutation); a sequence starts at a uniform token, and each next
    token is successor j of the current (topic, token), j drawn with
    weights ∝ 1/(j+1).  Both draws are by inverse CDF.  Unigram counts
    then fall off as in real text, and a client that holds two topics
    (the §VI-A non-IID split by y) holds two topics' text."""
    import jax
    import jax.numpy as jnp

    missing = {"topics", "branch", "zipf"} - set(data)
    if missing:
        raise CellError(f"markov_tokens data needs {sorted(missing)}")
    vocab, seq = int(config["vocab"]), int(config["seq_len"])
    topics, branch = int(data["topics"]), int(data["branch"])
    zipf = float(data["zipf"])

    def inverse_cdf(weights, u):
        cdf = jnp.cumsum(weights) / jnp.sum(weights)
        return jnp.minimum(jnp.searchsorted(cdf, u), weights.shape[0] - 1)

    @jax.jit
    def draw(key):
        kp, ks, ky, k0, kj = jax.random.split(key, 5)
        rank_w = 1.0 / jnp.arange(1, vocab + 1, dtype=jnp.float32) ** zipf
        ids = jax.random.permutation(kp, vocab).astype(jnp.int32)
        succ = ids[inverse_cdf(rank_w, jax.random.uniform(
            ks, (topics, vocab, branch)))]
        succ_w = 1.0 / jnp.arange(1, branch + 1, dtype=jnp.float32)
        y = jax.random.randint(ky, (n,), 0, topics, jnp.int32)
        first = jax.random.randint(k0, (n,), 0, vocab, jnp.int32)
        picks = inverse_cdf(succ_w, jax.random.uniform(kj, (seq, n)))

        def step(tok, j):
            nxt = succ[y, tok, j]
            return nxt, nxt

        _, rest = jax.lax.scan(step, first, picks)
        return jnp.concatenate([first[:, None], rest.T], 1), y

    return draw(key)


DATA_KINDS = {"clusters": _clusters, "markov_tokens": _markov_tokens}


# ---------------------------------------------------------------------------
# specs: the traffic file's grid, at the configuration's model
# ---------------------------------------------------------------------------

# ScenarioSpec keywords the harness sets from the traffic and configuration
# itself; a configuration's ``spec`` may not set them again
HARNESS_SPEC_KEYS = ("fleet", "name", "partition", "policy", "compress",
                     "compression", "b_max", "base_lr", "seeds", "sampling",
                     "model_family")


def make_fleet(fleet: dict):
    """``k`` devices cycling round-robin through the traffic's tiers (each
    tier is a ``DeviceProfile``'s keyword arguments)."""
    from repro.core import DeviceProfile
    tiers = fleet["tiers"]
    return tuple(DeviceProfile(**tiers[i % len(tiers)])
                 for i in range(int(fleet["k"])))


def _spec_value(v):
    """A JSON value as a ScenarioSpec keyword takes it: arrays as tuples,
    objects as dicts, strings and numbers as they are."""
    if isinstance(v, list):
        return tuple(_spec_value(a) for a in v)
    if isinstance(v, dict):
        return {k: _spec_value(a) for k, a in v.items()}
    return v


def make_specs(config: dict, traffic: dict, seed: int):
    """The cell's grid: one spec per (partition, policy), each carrying the
    traffic's number of row seeds, drawn from ``seed``.  ``hidden`` and
    ``depth`` pass where the configuration has them, and the keywords of
    its ``spec`` object as they are (none where it has none)."""
    from repro.api import ScenarioSpec
    from repro.topology import Sampling

    fleet = make_fleet(traffic["fleet"])
    n_seeds = int(traffic["seeds_per_spec"])
    sampling = (None if traffic.get("sampling") is None
                else Sampling(**traffic["sampling"]))
    extra = {k: int(config[k]) for k in ("hidden", "depth") if k in config}
    spec = config.get("spec") or {}
    taken = sorted(set(spec) & (set(HARNESS_SPEC_KEYS) | set(extra)))
    if taken:
        raise CellError(f"the configuration's spec sets {taken}, which the "
                        f"harness sets from the traffic and configuration")
    extra.update((k, _spec_value(v)) for k, v in spec.items())
    cells = [(part, pol) for part in traffic["partitions"]
             for pol in traffic["policies"]]
    row_seeds = derived_seeds(seed, n_seeds * len(cells), STREAM_ROWS)
    specs = []
    for i, (part, pol) in enumerate(cells):
        try:
            specs.append(ScenarioSpec(
                fleet=fleet, name=traffic["fleet"]["name"], partition=part,
                policy=pol, compress=bool(traffic["compress"]),
                compression=float(config["compression"]),
                b_max=int(config["b_max"]),
                base_lr=float(traffic["base_lr"]),
                seeds=tuple(row_seeds[i * n_seeds:(i + 1) * n_seeds]),
                sampling=sampling, model_family=config["model_family"],
                **extra))
        except (TypeError, ValueError) as e:
            raise CellError(f"ScenarioSpec rejects the configuration's spec "
                            f"{sorted(spec)}: {e}") from e
    return specs


# ---------------------------------------------------------------------------
# the executor: the program's own, with a span around each bucket phase
# ---------------------------------------------------------------------------


@dataclass
class BucketRecord:
    """What one bucket of a grid call produced, for the correctness
    comparison: the host plan the device ran and the device values."""
    plan: object
    handle: object = None
    series: tuple = None


class Spans:
    """Host spans around the bucket phases the program's executors
    compose.  ``repro.api.executor`` calls ``plan_bucket`` →
    ``dispatch_bucket`` → ``collect_bucket`` by these names; installing
    wraps each name in that module, so every executor runs as it is and
    each phase runs inside a profiler annotation of its name and adds its
    host seconds to ``span_s``.  ``records`` keeps the buckets planned
    since it was last cleared."""

    def __init__(self):
        from repro.api import executor as ex_mod
        self.span_s: Dict[str, float] = {s: 0.0 for s in SPANS}
        self.records: List[BucketRecord] = []
        for name in SPANS:
            fn = getattr(ex_mod, name)
            setattr(ex_mod, name,
                    self._wrap(name, getattr(fn, "__wrapped__", fn)))

    def _wrap(self, name, fn):
        import jax

        @wraps(fn)
        def phase(*args, **kw):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                out = fn(*args, **kw)
            self.span_s[name] += time.perf_counter() - t0
            self._record(name, args[0], out)
            return out

        return phase

    def _record(self, name, arg, out):
        if name == "plan_bucket":
            self.records.append(BucketRecord(out))
            return
        key = "plan" if name == "dispatch_bucket" else "handle"
        for rec in self.records:
            if getattr(rec, key) is arg:
                if name == "dispatch_bucket":
                    rec.handle = out
                else:
                    rec.series = out

    def reset(self):
        for k in self.span_s:
            self.span_s[k] = 0.0


def make_executor(spec: dict):
    """The program's executor that the traffic names: ``{"class":
    "<name in repro.api>", <its keyword arguments>}``."""
    import repro.api as api
    kwargs = {k: v for k, v in spec.items() if k != "class"}
    cls = getattr(api, spec["class"], None)
    if not (isinstance(cls, type) and issubclass(cls, api.Executor)):
        raise CellError(f"{spec['class']!r} is no executor of repro.api")
    return cls(**kwargs)


# ---------------------------------------------------------------------------
# counts from the host plan (no device work)
# ---------------------------------------------------------------------------


def plan_arrays(plan) -> dict:
    """The (n, P, K) participation, per-client batch, and schedule arrays
    of one bucket plan, as host numpy."""
    schedules = plan.payload["schedules"]
    n = len(schedules)
    periods, k = schedules[0].batch.shape
    active = np.asarray(plan.payload["active"], np.float32)
    if active.ndim == 2:
        active = np.broadcast_to(active[:, None, :], (n, periods, k))
    return {
        "active": active,
        "batch": np.stack([s.batch for s in schedules]).astype(np.float64),
        "idx": np.stack([s.idx for s in schedules]),
        "weight": np.stack([s.weight for s in schedules]),
        "lr": np.stack([s.lr for s in schedules]),
        "aggden": np.stack([np.zeros(periods, np.float32)
                            if s.aggden is None else s.aggden
                            for s in schedules]),
        "times": np.asarray(plan.times),
        "global_batch": np.asarray(plan.global_batch),
    }


def call_counts(records: List[BucketRecord]) -> dict:
    """Per grid call: ``client_periods`` — (client, period) pairs whose
    update entered the B_k aggregate (participating, B_k > 0; padded and
    sampled-out lanes do not count) — and ``examples``, the Σ B_k of
    those pairs (the examples whose gradients were aggregated)."""
    if not records or any(r.series is None for r in records):
        raise CellError("a bucket ran in chunks (``replan`` or "
                        "``chunk_periods``): its phases bypass the spans")
    cp, ex, periods = 0, 0.0, 0
    for rec in records:
        a = plan_arrays(rec.plan)
        entered = (a["active"] > 0) & (a["batch"] > 0)
        cp += int(entered.sum())
        ex += float((a["batch"] * entered).sum())
        periods = max(periods, a["batch"].shape[1])
    return {"client_periods": cp, "examples": ex, "periods": periods,
            "rows": sum(len(r.plan.bucket.rows) for r in records)}
