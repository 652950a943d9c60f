"""Record the small program trace that ``test_progtrace.py`` reads.

    python bench/tests/record_program_trace.py <output .xplane.pb>  # TPU
    python bench/tests/record_program_trace.py --trim <raw> <output>

One FEEL bucket of ``ROWS`` rows (the ``proposed`` policy, K = ``K``
GPU clients, SBC on) trains for ``PERIODS`` periods through
``Experiment.run`` once outside the profiler (to compile) and once inside
a host span ``window``, with the Python tracer and the runtime's own host
events left out.  The trace is then trimmed (:func:`trim`) to what the
readers use, so the committed file stays small: the device plane's
``XLA Ops`` line with each operation's name, ``tf_op`` and
``program_id``; the host thread lines that hold ``window`` and the
program's ``repro.*`` spans; and the HLO protos of the programs that ran,
cut to the name and ``op_name`` of each instruction that ran.  ``--trim``
applies only that step to a raw trace.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

import progtrace    # noqa: E402
import tracefile    # noqa: E402

N_TRAIN, N_TEST, DIM, CLASSES = 400, 100, 32, 10
HIDDEN, B_MAX = 16, 8
ROWS, K, PERIODS = 2, 2, 3
KEEP_STATS = ("tf_op", "program_id", progtrace.HLO_STAT)


def experiment():
    from repro.api import Experiment, ScenarioSpec
    from repro.core import DeviceProfile
    from repro.data.pipeline import ClassificationData

    full = ClassificationData.synthetic(n=N_TRAIN + N_TEST, dim=DIM,
                                        classes=CLASSES, seed=7,
                                        spread=6.0)
    train, test = full.split(N_TEST)
    fleet = tuple(DeviceProfile(kind="gpu", gpu_t_low=t, gpu_slope=4e-4,
                                gpu_b_th=16) for t in (0.02, 0.03))
    spec = ScenarioSpec(fleet=fleet, name="gpu2", policy="proposed",
                        compress=True, b_max=B_MAX, hidden=HIDDEN,
                        seeds=tuple(range(ROWS)))
    return Experiment(train, test, [spec])


# ---------------------------------------------------------------------------
# trimming: re-encode the protobuf keeping only the fields the readers use
# ---------------------------------------------------------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        low, n = n & 0x7F, n >> 7
        out.append(low | 0x80 if n else low)
        if not n:
            return bytes(out)


def _message(fields) -> bytes:
    """Encode (field number, value) pairs: ints as varints, the rest as
    length-delimited bytes."""
    out = bytearray()
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        else:
            value = bytes(value)
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return bytes(out)


def _keep(buf, numbers):
    return [(n, v) for n, v in progtrace._fields(buf) if n in numbers]


def _stat_metadata(stat_names: dict):
    return [(5, _message([(1, sid), (2, _message([(1, sid),
                                                  (2, name.encode())]))]))
            for sid, name in stat_names.items()]


def _trim_hlo(proto, ran) -> bytes:
    """module name, id and computations → the name and op_name of each
    instruction named in ``ran``."""
    modules = []
    for _, module in _keep(proto, (1,)):
        fields = _keep(module, (1, 2, 5))
        for _, comp in _keep(module, (3,)):
            cf = _keep(comp, (1, 5))
            for _, ins in _keep(comp, (2,)):
                name = _keep(ins, (1,))
                if progtrace._text(name[0][1]) not in ran:
                    continue
                cf.append((2, _message(name + [
                    (7, _message(_keep(meta, (2,))))
                    for _, meta in _keep(ins, (7,))])))
            fields.append((3, _message(cf)))
        modules.append((1, _message(fields)))
    return _message(modules)


def _device_plane(name, lines, event_meta, stat_names) -> bytes:
    keep = {k for k, v in stat_names.items() if v in KEEP_STATS}
    fields = [(2, name.encode())]
    for line in lines:
        if progtrace._text(dict(_keep(line, (2,))).get(2, b"")) != \
                tracefile.OPS_LINE:
            continue
        lf = _keep(line, (1, 2, 3, 9, 10, 11)) + [
            (4, _message(_keep(ev, (1, 2, 3))))
            for _, ev in _keep(line, (4,))]
        fields.append((3, _message(lf)))
    for mid, buf in event_meta.items():
        meta = [(n, v) for n, v in progtrace._fields(buf)
                if n in (1, 2, 4)
                or (n == 5 and dict(progtrace._fields(v)).get(1) in keep)]
        fields.append((4, _message([(1, mid), (2, _message(meta))])))
    return _message(fields + _stat_metadata(stat_names))


def _host_plane(name, lines, event_meta, stat_names) -> bytes:
    names = {mid: progtrace._meta_stats(buf, stat_names)[0]
             for mid, buf in event_meta.items()}
    want = {mid for mid, nm in names.items() if nm == tracefile.WINDOW_SPAN
            or nm.startswith(progtrace.PREFIX)}
    fields = [(2, name.encode())]
    for line in lines:
        mine = [(4, ev) for _, ev in _keep(line, (4,))
                if dict(progtrace._fields(ev)).get(1) in want]
        if mine:
            fields.append((3, _message(
                [(n, v) for n, v in progtrace._fields(line) if n != 4]
                + mine)))
    fields += [(4, _message([(1, mid), (2, event_meta[mid])]))
               for mid in sorted(want)]
    return _message(fields + _stat_metadata(stat_names))


def _hlo_plane(name, lines, event_meta, stat_names, programs) -> bytes:
    hlo_id = next(k for k, v in stat_names.items()
                  if v == progtrace.HLO_STAT)
    fields = [(2, name.encode())]
    for mid, buf in event_meta.items():
        if mid not in programs:
            continue
        module, stats = progtrace._meta_stats(buf, stat_names)
        proto = _trim_hlo(stats[progtrace.HLO_STAT], programs[mid])
        meta = _message([(1, mid), (2, module.encode()),
                         (5, _message([(1, hlo_id), (6, proto)]))])
        fields.append((4, _message([(1, mid), (2, meta)])))
    return _message(fields + _stat_metadata(stat_names))


def trim(data: bytes) -> bytes:
    """The trace cut to the planes, lines and stats the readers use."""
    planes = [progtrace._plane(buf)
              for num, buf in progtrace._fields(memoryview(data))
              if num == 1]
    devices = [p for p in planes if tracefile.DEVICE_PLANE.match(p[0])]
    programs = {}       # program id → names of its instructions that ran
    for p in devices:
        for buf in p[2].values():
            name, stats = progtrace._meta_stats(buf, p[3])
            programs.setdefault(stats.get("program_id"), set()).add(
                name.split(" = ", 1)[0].lstrip("%"))
    out = [(1, _device_plane(*p)) for p in devices]
    for plane in planes:
        if plane[0] == "/host:CPU":
            out.append((1, _host_plane(*plane)))
        elif plane[0] == progtrace.HLO_PLANE:
            out.append((1, _hlo_plane(*plane, programs)))
    return _message(out)


def trim_file(raw: str, out_path: str) -> int:
    Path(out_path).write_bytes(trim(Path(raw).read_bytes()))
    print(f"{out_path}: {Path(out_path).stat().st_size} bytes "
          f"(raw {Path(raw).stat().st_size})")
    return 0


def main(out_path: str) -> int:
    import jax
    from repro.api import SerialExecutor

    exp = experiment()
    exp.run(PERIODS, executor=SerialExecutor())          # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation(tracefile.WINDOW_SPAN):
            exp.run(PERIODS, executor=SerialExecutor())
        jax.profiler.stop_trace()
        (pb,) = Path(tmp).rglob("*.xplane.pb")
        raw = str(Path(out_path).with_suffix(".raw"))
        shutil.copy(pb, raw)
    return trim_file(raw, out_path)


if __name__ == "__main__":
    if sys.argv[1] == "--trim":
        sys.exit(trim_file(sys.argv[2], sys.argv[3]))
    sys.exit(main(sys.argv[1]))
