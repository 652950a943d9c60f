"""The trace reduction, on a small trace recorded on one TPU v5e chip by
``record_trace.py`` (committed as ``data/small.xplane.pb``): inside the
host span ``window`` a matmul chain ran three times, and before the third
the host slept 50 ms inside ``plan_bucket`` with nothing queued."""
from pathlib import Path

import pytest

import tracefile

TRACE = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return tracefile.reduce(tracefile.load(str(TRACE)))


def test_window_and_device(red):
    assert red.devices == ["/device:TPU:0"]
    assert 0.05 < red.window_s < 5.0
    assert 0.0 < red.busy_s < red.window_s
    assert red.ops and all(op.dur_ns > 0 for op in red.ops)


def test_idle_gap_is_put_beside_the_host_span(red):
    gaps = [(e - s) * 1e-9 for s, e, lab in red.gaps
            if lab == "plan_bucket"]
    assert gaps and max(gaps) >= 0.045
    longest = max(red.gaps, key=lambda g: g[1] - g[0])
    assert longest[2] == "plan_bucket"


def test_busy_is_a_union(red):
    ops_s = sum(op.dur_ns for op in red.ops) * 1e-9
    assert red.busy_s <= ops_s + 1e-9
    idle = sum(e - s for s, e, _ in red.gaps) * 1e-9
    assert red.busy_s + idle == pytest.approx(red.window_s, rel=1e-6)


def test_breakdown(red):
    b = tracefile.breakdown(red)
    assert 0 < len(b["device_ops"]) <= 10
    assert b["idle_gaps"][0][0] == "plan_bucket"
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_shapes_from_hlo_text():
    text = ("%vmap__.7 = f32[6,8,128]{2,1,0:T(8,128)S(1)} custom-call("
            "f32[6,7168,128]{2,1,0:T(8,128)} %b, bf16[6,1,128]{2,1,0:T(1,"
            "128)S(1)} %c), custom_call_target=\"tpu_custom_call\"")
    found = tracefile.shapes(text)
    assert found == [("f32", (6, 8, 128), 1), ("f32", (6, 7168, 128), 0),
                     ("bf16", (6, 1, 128), 1)]
    assert tracefile.short_name(text) == "%vmap__ tpu_custom_call"
