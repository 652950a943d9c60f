"""Read the program's own spans and the device operations' scopes from a
profiler trace (``.xplane.pb``).

    python bench/progtrace.py <file.xplane.pb>     # totals per span, scope

The program (``repro.obs``) marks its host phases with ``repro.*`` spans
whose stats carry its counters (``rows``, ``lanes``, ``bytes``, …), and its
scanned period step with named scopes (``grad``, ``sbc``, ``aggregate``,
``loss``, ``eval``).  A scope reaches each device operation as its
``tf_op`` stat on the device plane's event metadata or, for an executable
loaded from the compile cache, whose events carry no ``tf_op``, as the
``metadata.op_name`` of its instruction in the program's HLO proto, which
the ``/host:metadata`` plane keeps by program id.  ``ProfileData`` exposes
neither, so :func:`device_ops` reads them with a short protobuf wire
reader (the XPlane schema: ``XSpace.planes`` 1, ``XPlane`` name 2 / lines
3 / event_metadata 4 / stat_metadata 5, ``XLine`` name 2 / timestamp_ns 3
/ events 4, ``XEvent`` metadata_id 1 / offset_ps 2 / duration_ps 3,
``XEventMetadata`` name 2 / stats 5, ``XStat`` metadata_id 1 / uint64 3 /
int64 4 / str 5 / bytes 6 / ref 7; the HLO proto's fields are named in
:func:`_hlo_op_names`).

:func:`read` returns a :class:`Program`: the spans and the device
operations inside the traced window.  A trace of a program that has no
such spans or scopes reads as empty, and each metric then reads nothing.
"""
from __future__ import annotations

import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import tracefile

TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_trace"
PREFIX = "repro."
SCOPES = ("grad", "sbc", "aggregate", "loss", "eval")
IDS = ("bucket", "chunk")                  # span stats that are ids
HLO_PLANE, HLO_STAT = "/host:metadata", "Hlo Proto"
TOKEN = re.compile(r"[A-Za-z_][\w.]*")


@dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    stats: dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclass
class DeviceOp:
    device: str
    name: str
    start_ns: float
    dur_ns: float
    tf_op: str


@dataclass
class Program:
    window: Tuple[float, float]
    spans: List[Span] = field(default_factory=list)
    ops: List[DeviceOp] = field(default_factory=list)
    devices: List[str] = field(default_factory=list)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def span_seconds(self, name: str) -> Optional[float]:
        """Summed seconds of the spans named ``name``; None if none."""
        found = self.named(name)
        return sum(s.seconds for s in found) if found else None

    def stat_sum(self, name: str, stat: str) -> Optional[int]:
        found = [s.stats[stat] for s in self.named(name) if stat in s.stats]
        return sum(found) if found else None

    def leaf_ops(self) -> List[DeviceOp]:
        """The operations that do work: control flow that contains other
        operations is left out, as ``tracefile.Reduction.op_seconds``
        does."""
        return [op for op in self.ops
                if not tracefile.CONTAINER.search(op.name)]

    def scope_seconds(self) -> Dict[Optional[str], float]:
        """Device seconds per scope (``None``: no scope) of the leaf
        operations, averaged over devices."""
        out: Dict[Optional[str], float] = {}
        n = max(len(self.devices), 1)
        for op in self.leaf_ops():
            key = scope_of(op.tf_op)
            out[key] = out.get(key, 0.0) + op.dur_ns * 1e-9 / n
        return out

    def multi_scope_seconds(self) -> float:
        """Device seconds, averaged over devices, of the leaf operations
        whose ``tf_op`` names more than one scope: the time that
        :meth:`scope_seconds` gives to the first listed by rule."""
        n = max(len(self.devices), 1)
        return sum(op.dur_ns for op in self.leaf_ops()
                   if len(scopes_in(op.tf_op)) > 1) * 1e-9 / n


def scopes_in(tf_op: str) -> List[str]:
    """The period-step scopes an op's ``tf_op`` names, first listed first
    (a fusion lists every op it fused, ``;``-separated)."""
    out: List[str] = []
    for tok in TOKEN.findall(tf_op or ""):
        if tok in SCOPES and tok not in out:
            out.append(tok)
    return out


def scope_of(tf_op: str) -> Optional[str]:
    """The first listed period-step scope in an op's ``tf_op``: a fusion
    that names several gives its whole time to the first (``coverage``
    reports how much time that is)."""
    found = scopes_in(tf_op)
    return found[0] if found else None


# ---------------------------------------------------------------------------
# protobuf wire format: just enough to walk an XSpace
# ---------------------------------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, a
    memoryview for length-delimited fields; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield num, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(buf) -> Tuple[int, object]:
    key, value = 0, b""
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _stat_value(stat: dict, stat_names: dict):
    """An ``XStat``'s value: str (5, or 7 → an interned stat name),
    bytes (6) or int (3, 4)."""
    if 5 in stat:
        return _text(stat[5])
    if 7 in stat:
        return stat_names.get(stat[7], "")
    if 6 in stat:
        return stat[6]
    return stat.get(3, stat.get(4))


def _plane(buf) -> Tuple[str, list, dict, dict]:
    """(name, lines, event metadata id → buffer, stat metadata id → name)
    of one ``XPlane``."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            k, val = _map_entry(v)
            event_meta[k] = val
        elif f == 5:
            k, val = _map_entry(v)
            stat_names[k] = next((_text(x) for n, x in _fields(val)
                                  if n == 2), "")
    return name, lines, event_meta, stat_names


def _meta_stats(buf, stat_names: dict) -> Tuple[str, dict]:
    """(name, {stat name: value}) of one ``XEventMetadata``."""
    name, stats = "", {}
    for num, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 5:
            stat = dict(_fields(v))
            stats[stat_names.get(stat.get(1), "")] = _stat_value(
                stat, stat_names)
    return name, stats


def _hlo_op_names(proto) -> Dict[str, str]:
    """instruction name → ``metadata.op_name`` of one serialized
    ``HloProto`` (hlo_module 1 → computations 3 → instructions 2 →
    name 1, metadata 7 → op_name 2)."""
    out = {}
    for f, module in _fields(proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, ins in _fields(comp):
                if h != 2:
                    continue
                name, op_name = "", ""
                for k, v in _fields(ins):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        op_name = next((_text(x) for n, x in _fields(v)
                                        if n == 2), "")
                out[name] = op_name
    return out


def device_ops(data: bytes) -> Tuple[List[str], List[DeviceOp]]:
    """Every operation on a device plane's ``XLA Ops`` line, with its
    ``tf_op``: (device names, operations).  Where the event metadata
    carries no ``tf_op`` (an executable loaded from the compile cache),
    it is the op's ``metadata.op_name`` in its program's HLO proto, which
    the ``/host:metadata`` plane keeps by program id."""
    device_planes, protos = [], {}
    for num, buf in _fields(memoryview(data)):
        if num != 1:
            continue
        plane = _plane(buf)
        if tracefile.DEVICE_PLANE.match(plane[0]):
            device_planes.append(plane)
        elif plane[0] == HLO_PLANE:
            for mid, meta in plane[2].items():
                stats = _meta_stats(meta, plane[3])[1]
                if isinstance(stats.get(HLO_STAT), memoryview):
                    protos[mid] = stats[HLO_STAT]
    op_names: Dict[int, Dict[str, str]] = {}
    devices, ops = [], []
    for name, lines, event_meta, stat_names in device_planes:
        devices.append(name)
        meta = {}
        for mid, buf in event_meta.items():
            op_name, stats = _meta_stats(buf, stat_names)
            tf_op = stats.get("tf_op") or ""
            program = stats.get("program_id")
            if not tf_op and program in protos:
                if program not in op_names:
                    op_names[program] = _hlo_op_names(protos[program])
                tf_op = op_names[program].get(
                    op_name.split(" = ", 1)[0].lstrip("%"), "")
            meta[mid] = (op_name, tf_op)
        for line in lines:
            lf, events = {}, []
            for f, v in _fields(line):
                if f == 4:
                    events.append(v)
                elif f in (2, 3):
                    lf[f] = v
            if _text(lf.get(2, b"")) != tracefile.OPS_LINE:
                continue
            t0 = lf.get(3, 0)
            for ev in events:
                e = dict(_fields(ev))
                op_name, tf_op = meta.get(e.get(1, 0), ("", ""))
                # whole nanoseconds, as ``ProfileData`` reports them
                ops.append(DeviceOp(name, op_name,
                                    float(t0 + e.get(2, 0) // 1000),
                                    float(e.get(3, 0) // 1000), tf_op))
    return sorted(devices), ops


# ---------------------------------------------------------------------------
# the program's view of one trace
# ---------------------------------------------------------------------------

_CACHE: Dict[tuple, Program] = {}


def read(path: str, window: Optional[Tuple[float, float]] = None,
         window_span: str = tracefile.WINDOW_SPAN) -> Program:
    """The program's spans and the device operations of the window (the
    host span ``window``, or the ``(start, end)`` ns given)."""
    p = Path(path)
    key = (str(p.resolve()), p.stat().st_mtime_ns, window)
    if key in _CACHE:
        return _CACHE[key]
    profile = tracefile.load(str(p))
    spans, wins = [], []
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                nm = ev.name
                if nm == window_span:
                    wins.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                elif nm.startswith(PREFIX):
                    spans.append(Span(nm, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
    if window is None:
        if not wins:
            raise ValueError(f"trace has no host span {window_span!r}")
        wins.sort()
        window = (wins[0][0], wins[-1][1])
    lo, hi = window
    devices, ops = device_ops(p.read_bytes())
    prog = Program(
        window=window, devices=devices,
        spans=[s for s in spans if s.end_ns > lo and s.start_ns < hi],
        ops=[o for o in ops if o.start_ns + o.dur_ns > lo
             and o.start_ns < hi])
    _CACHE[key] = prog
    return prog


def latest(trace_dir: Optional[Path] = None) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``trace_dir`` (``TRACE_DIR``), or
    None."""
    found = sorted(Path(trace_dir or TRACE_DIR).rglob("*.xplane.pb"),
                   key=lambda q: q.stat().st_mtime_ns)
    return str(found[-1]) if found else None


def from_ctx(ctx) -> Optional[Program]:
    """The traced run's program view for a metric reader: the trace at
    ``ctx["trace"]``, cut to the reduction's window.  Without that key it
    is the newest trace under ``TRACE_DIR``, where ``run.py`` writes, and
    only if its own ``window`` span is the reduction's window: a trace
    left there by another run reads as None, as does no trace."""
    red = ctx.get("reduction")
    window = None if red is None else tuple(red.window)
    if ctx.get("trace"):
        return read(ctx["trace"], window=window)
    path = latest()
    if path is None:
        return None
    try:
        prog = read(path)
    except ValueError:                       # no ``window`` span
        return None
    if window is not None and tuple(prog.window) != window:
        return None
    return prog


def per_period(ctx, seconds: Optional[float]) -> Optional[float]:
    """Milliseconds per simulated period of a grid call."""
    if seconds is None:
        return None
    return 1000.0 * seconds / (ctx["n_calls"] * ctx["per_call"]["periods"])


def scope_per_period(ctx, scope: str) -> Optional[float]:
    """A scope's device milliseconds per simulated period of a grid call;
    None when no operation of the window carries any scope."""
    prog = from_ctx(ctx)
    if prog is None:
        return None
    secs = prog.scope_seconds()
    if all(k is None for k in secs):
        return None
    return per_period(ctx, secs.get(scope, 0.0))


PLAN_LEAVES = ("repro.plan.channel", "repro.plan.solve",
               "repro.plan.schedule")


def _measure(intervals: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def _intersect(a, b) -> List[Tuple[float, float]]:
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            lo, hi = max(s, b[k][0]), min(e, b[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def coverage(prog: Program) -> dict:
    """How much the program's own marks explain: the plan leaves' share
    of ``repro.plan`` time, the scoped share of device operation time
    (with the unscoped seconds, and the seconds of operations that name
    more than one scope), and the share of the window's device-idle time
    that falls inside some program span."""
    out = {}
    plan_s = prog.span_seconds("repro.plan")
    if plan_s:
        leaves = sum(prog.span_seconds(n) or 0.0 for n in PLAN_LEAVES)
        out["plan_leaf_share"] = leaves / plan_s
    secs = prog.scope_seconds()
    total = sum(secs.values())
    if total > 0:
        out["scoped_share"] = 1.0 - secs.get(None, 0.0) / total
        out["unscoped_s"] = secs.get(None, 0.0)
        out["multi_scope_s"] = prog.multi_scope_seconds()
    lo, hi = prog.window
    if prog.devices:
        dev = prog.devices[0]
        busy = tracefile._clip(tracefile._union(
            [(o.start_ns, o.start_ns + o.dur_ns) for o in prog.ops
             if o.device == dev]), lo, hi)
        gaps = tracefile.idle_gaps(busy, lo, hi, [])
        idle = [(s, e) for s, e, _ in gaps]
        marked = tracefile._clip(tracefile._union(
            [(s.start_ns, s.end_ns) for s in prog.spans]), lo, hi)
        idle_s = _measure(idle)
        if idle_s > 0:
            out["idle_s"] = idle_s * 1e-9
            out["idle_in_span_share"] = _measure(
                _intersect(idle, marked)) / idle_s
    return out


def summary(prog: Program) -> str:
    names = sorted({s.name for s in prog.spans})
    out = [f"window {(prog.window[1] - prog.window[0]) * 1e-9:.6f} s, "
           f"devices {prog.devices}"]
    for nm in names:
        counters = "".join(
            f", {st} {prog.stat_sum(nm, st)}" for st in sorted(
                {k for s in prog.named(nm) for k in s.stats} - set(IDS)))
        out.append(f"  {nm}: {len(prog.named(nm))} spans, "
                   f"{prog.span_seconds(nm):.6f} s{counters}")
    for scope, sec in sorted(prog.scope_seconds().items(),
                             key=lambda kv: -kv[1]):
        out.append(f"  scope {scope}: {sec:.6f} device s")
    for key, value in coverage(prog).items():
        out.append(f"  {key}: {value!r}")
    return "\n".join(out)


if __name__ == "__main__":
    print(summary(read(sys.argv[1])))
