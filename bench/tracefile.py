"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

    python bench/tracefile.py <file.xplane.pb>   # summary of planes/lines

A trace holds planes: one per device (``/device:TPU:<n>``) and one for the
host (``/host:CPU``).  On a device plane the line ``XLA Ops`` holds one
event per operation that ran, with its start and duration in
nanoseconds; host lines hold the ``TraceAnnotation`` spans of each thread.
All share one clock, so a gap between device operations can be put beside
the host span that was open at the time.

:func:`reduce` returns a :class:`Reduction`: the traced window (the host
span named ``window``), each device's busy time (the union of its
operations' intervals inside the window), every device operation with its
name, duration and string stats (the HLO text that gives its shapes),
and the host spans.
"""
from __future__ import annotations

import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW_SPAN = "window"
# the benchmark's own host spans, by which an idle gap is labelled
HOST_SPANS = ("plan_bucket", "dispatch_bucket", "collect_bucket",
              "grid_call")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"


@dataclass
class Op:
    device: str
    name: str
    start_ns: float
    dur_ns: float
    text: str            # the event's string stats, joined (HLO text etc.)


@dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclass
class Reduction:
    window: Tuple[float, float]          # (start, end) ns
    devices: List[str]
    busy_ns: Dict[str, float]            # per device, inside the window
    ops: List[Op]                        # device operations in the window
    spans: List[Span]                    # host annotation spans
    gaps: List[Tuple[float, float, str]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(self.busy_ns.values()) * 1e-9 / len(self.devices)

    def op_seconds(self) -> Dict[str, float]:
        """Device seconds per operation kind (``short_name``), averaged
        over devices; control flow that contains other operations
        (``while``, ``call``, ``conditional``) is left out, so no time is
        counted twice."""
        out: Dict[str, float] = {}
        n = max(len(self.devices), 1)
        for op in self.ops:
            if CONTAINER.search(op.name):
                continue
            key = short_name(op.name)
            out[key] = out.get(key, 0.0) + op.dur_ns * 1e-9 / n
        return out

    def matching(self, pattern: str) -> List[Op]:
        """Operations whose name or stats text matches ``pattern``."""
        rx = re.compile(pattern)
        return [op for op in self.ops
                if rx.search(op.name) or rx.search(op.text)]


# an HLO instruction whose opcode runs other instructions inside it
CONTAINER = re.compile(r"[\]\})]\s(while|call|conditional)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[…] fusion(…)`` → ``%fusion``; a custom call
    gets its target: ``%closed_call tpu_custom_call``."""
    name = re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0])
    target = TARGET.search(hlo)
    return f"{name} {target.group(1)}" if target else name


SHAPE = re.compile(r"\b(bf16|f16|f32|f64|s8|s32|u8|u32|pred)"
                   r"\[([0-9,]*)\](\{[^}]*\})?")
SPACE = re.compile(r"S\((\d+)\)")


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...], int]]:
    """Every ``dtype[d0,d1,...]{layout}`` in an HLO text, in order (the
    result first, then the operands): (dtype, dims, memory space).  The
    space is the layout's ``S(n)``: 0 is HBM; XLA marks buffers it keeps
    in on-chip memory ``S(1)``."""
    out = []
    for dt, dims, layout in SHAPE.findall(text):
        space = SPACE.search(layout or "")
        out.append((dt, tuple(int(d) for d in dims.split(",") if d),
                    int(space.group(1)) if space else 0))
    return out


def _stats_text(event) -> str:
    parts = []
    for name, value in event.stats:
        if isinstance(value, (str, bytes)):
            value = value.decode() if isinstance(value, bytes) else value
            parts.append(f"{name}={value}")
    return " ".join(parts)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def reduce(profile, window_span: str = WINDOW_SPAN) -> Reduction:
    """Reduce a ``ProfileData`` to the window's device work and spans."""
    spans: List[Span] = []
    dev_events: Dict[str, list] = {}
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(line.events)
            dev_events[plane.name] = evs
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    spans.append(Span(ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    wins = [s for s in spans if s.name == window_span]
    if not wins:
        raise ValueError(f"trace has no host span named {window_span!r}")
    lo, hi = wins[0].start_ns, wins[-1].end_ns
    devices = sorted(dev_events)
    busy: Dict[str, float] = {}
    ops: List[Op] = []
    first_busy = None
    for dev in devices:
        ivs = []
        for ev in dev_events[dev]:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= lo or s >= hi:
                continue
            ivs.append((s, e))
            ops.append(Op(dev, ev.name, s, ev.duration_ns, _stats_text(ev)))
        merged = _clip(_union(ivs), lo, hi)
        busy[dev] = sum(e - s for s, e in merged)
        if first_busy is None:
            first_busy = merged
    red = Reduction(window=(lo, hi), devices=devices, busy_ns=busy,
                    ops=ops, spans=[s for s in spans
                                    if s.end_ns > lo and s.start_ns < hi])
    red.gaps = idle_gaps(first_busy or [], lo, hi, red.spans)
    return red


def _label(s: float, e: float, spans: List[Span]) -> str:
    """The innermost benchmark span open at the gap's midpoint; ``no
    span`` where none is."""
    mid = 0.5 * (s + e)
    open_ = [sp for sp in spans if sp.name in HOST_SPANS
             and sp.start_ns <= mid <= sp.end_ns]
    if not open_:
        return "no span"
    return min(open_, key=lambda sp: sp.end_ns - sp.start_ns).name


def idle_gaps(busy: List[Tuple[float, float]], lo: float, hi: float,
              spans: List[Span]) -> List[Tuple[float, float, str]]:
    """Every interval of the window in which the device ran nothing, with
    the host span that was open in it."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return [(s, e, _label(s, e, spans)) for s, e in gaps]


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The device operations that took most time and the longest idle
    gaps by the host span open in them: ``[[name, seconds], ...]``."""
    ops = sorted(red.op_seconds().items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red.gaps, key=lambda g: -(g[1] - g[0]))[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[lab, (e - s) * 1e-9] for s, e, lab in gaps]}


def summary(profile, max_events: int = 12) -> str:
    """Planes, lines and the most frequent event names, for a first look."""
    out = []
    for plane in profile.planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            names: Dict[str, int] = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0) + 1
            common = sorted(names.items(), key=lambda kv: -kv[1])
            out.append(f"  line {line.name!r}: {len(evs)} events; "
                       f"{common[:max_events]}")
            for ev in evs[:2]:
                out.append(f"    e.g. {ev.name} dur={ev.duration_ns} "
                           f"stats={_stats_text(ev)[:400]}")
    return "\n".join(out)


if __name__ == "__main__":
    print(summary(load(sys.argv[1])))
