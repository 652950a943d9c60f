"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout that holds the program (``src/repro``).  The
cell, its configuration, traffic and limits are found by name from
``BENCHMARK.json`` (``workload.find_cell``).  A configuration's optional
``spec`` (extra ``ScenarioSpec`` keywords; absent: none), ``data`` (what
the data is drawn as; absent: Gaussian class clusters) and ``reference``
(the plain model under ``bench/models/``; absent: the one named by
``model_family``) are read as ``workload``'s docstring says.

Set-up (counted in ``setup_s``, from process start): data and every row
seed from ``--seed``, JAX's persistent compile cache inside the checkout,
and one warm-up grid call at the cell's own shapes.  The window then calls
``Experiment(data, test, specs).run(periods, executor=...)`` back to back
until ``--seconds`` have passed; the last call ends the window.  Backend
compiles inside the window are counted and reported.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` profiles
the window and prints its per-layer metrics (each read by
``metrics/<name>.py``), the device's busy and window seconds and a
breakdown.  Both then free the program's state and compare what the last
grid call produced with the plain reference (``judge.py``).  The last
line of standard output is one JSON object; the numbers compared, each
beside its limit, are the last lines of standard error and the last key
of that object.

Exit codes: 0 with a result; 2 when the checkout or the cell cannot be
used; 3 when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                           # noqa: E402
import gc                                                 # noqa: E402
import importlib.util                                     # noqa: E402
import json                                               # noqa: E402
import os                                                 # noqa: E402
import shutil                                             # noqa: E402
import sys                                                # noqa: E402
from pathlib import Path                                  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
# libtpu logs under /tmp unless told otherwise; a run writes only inside
# its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import workload                                           # noqa: E402

METRICS_DIR = BENCH / "metrics"
TRACE_DIR = ROOT / ".bench_trace"


def fail(code: int, msg: str):
    print(f"bench: {msg}", file=sys.stderr)
    raise SystemExit(code)


def require_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(3, f"needs a TPU; JAX found platform {devices[0].platform!r}")
    if len(devices) < chips:
        fail(3, f"the cell needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def metric_reader(name: str, metrics_dir: Path = METRICS_DIR):
    path = metrics_dir / f"{name}.py"
    if not path.is_file():
        raise workload.CellError(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Backend compiles (or compiled programs loaded from the cache)
    while ``on`` is set, through JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if self.on and event == self.EVENT:
            self.n += 1


def peak_bytes(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(2, f"no program under {ROOT / 'src' / 'repro'}")
    try:
        cell = workload.find_cell(ROOT, args.workload)
    except workload.CellError as e:
        fail(2, str(e))
    sys.path.insert(0, str(ROOT / "src"))
    devices = require_chips(cell.chips)
    import counts
    try:
        peak = counts.peaks(devices[0].device_kind)
    except KeyError as e:
        fail(3, str(e))
    try:
        out, lines = run_cell(cell, args, devices, peak)
    except workload.CellError as e:
        fail(2, str(e))
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


def run_cell(cell, args, devices, peak, metrics_dir: Path = METRICS_DIR,
             trace_dir: Path = TRACE_DIR):
    """Set up, measure and judge one run of ``cell`` on ``devices``.
    Returns the result object (keys in the order printed) and the lines
    of numbers compared, each beside its limit."""
    import jax
    from repro.api import Experiment
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    cfg, tr = cell.config, cell.traffic
    workload.set_precision(cfg)
    periods = int(tr["periods"])
    train, test = workload.make_data(cfg, args.seed)
    specs = workload.make_specs(cfg, tr, args.seed)
    exp = Experiment(train, test, specs)
    spans = workload.Spans()
    ex = workload.make_executor(tr["executor"])
    exp.run(periods, executor=ex)                      # warm-up: compiles
    per_call = workload.call_counts(spans.records)
    setup_s = time.perf_counter() - T_START

    compiles = CompileCounter()
    spans.reset()
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    compiles.on = True
    n_calls = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        while True:
            spans.records = []
            with jax.profiler.TraceAnnotation("grid_call"):
                res = exp.run(periods, executor=ex)
            n_calls += 1
            if time.perf_counter() - t0 >= args.seconds:
                break
    window_s = time.perf_counter() - t0
    compiles.on = False
    if args.trace:
        jax.profiler.stop_trace()
    mem = peak_bytes(devices)
    print(f"bench: window {window_s!r} s, {n_calls} grid calls, "
          f"{compiles.n} compiles inside the window", file=sys.stderr)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    metrics, extra = {}, {}
    if args.trace:
        import tracefile
        pbs = sorted(trace_dir.rglob("*.xplane.pb"))
        red = tracefile.reduce(tracefile.load(str(pbs[-1])))
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        ctx = dict(reduction=red, span_s=dict(spans.span_s),
                   n_calls=n_calls, per_call=per_call, window_s=window_s,
                   config=cfg, traffic=tr, peak=peak, chips=cell.chips)
        for m in cell.per_layer:
            value = metric_reader(m["name"], metrics_dir)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        extra["breakdown"] = tracefile.breakdown(red)
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                value = setup_s
            elif m["name"] == "client_periods_per_s":
                value = per_call["client_periods"] * n_calls / window_s
            else:
                raise workload.CellError(
                    f"no measurement for end-to-end metric {m['name']!r}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the comparison: keep what it needs on the host, free the rest
    import judge
    judged = judge.Judge(cell, spans.records, res, args.seed, train.y)
    spans.records = []
    del ex, res, exp
    gc.collect()
    numbers = judged.numbers(train, test)
    correct, lines = judge.verdict(numbers, cell.limits)
    for line in judged.ledger_lines[:10]:
        print(f"bench: ledger: {line}", file=sys.stderr)
    if judged.worst_leaf:
        print(f"bench: worst leaf of change_gap: {judged.worst_leaf}",
              file=sys.stderr)
    checks = {n: {"value": numbers.get(n, float("nan")),
                  "limit": float(cell.limits[n])}
              for n in judge.compared(cell.limits)}
    checks["window_compiles"] = {"value": compiles.n, "limit": 0}
    lines.append(f"window_compiles {compiles.n} limit 0 "
                 f"{'ok' if compiles.n == 0 else 'FAIL'}")
    out = {"correct": bool(correct and compiles.n == 0),
           "attempted": n_calls, "failed": 0, "metrics": metrics,
           "device": device, **extra, "checks": checks}
    return out, lines


if __name__ == "__main__":
    sys.exit(main())
