"""The trace reduction on hand-made events and on a trace recorded on a
v5e (``fixtures/v5e_trace.json.gz``)."""

import gzip
import json
import os

import pytest

import tracing

DEV = "/device:TPU:0"


def _ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start": start,
            "dur": dur}


def test_union_window_and_gaps():
    ev = [
        _ev("/host:CPU", "main", tracing.WINDOW_SPAN, 100, 1000),
        _ev("/host:CPU", "main", "bench.unit", 100, 1000),
        _ev("program", "spans", "lfproc.write", 600, 300),
        _ev(DEV, tracing.OPS_LINE, "a", 50, 150),   # clipped to 100..200
        _ev(DEV, tracing.OPS_LINE, "b", 150, 100),  # overlaps a: 200..250
        _ev(DEV, tracing.OPS_LINE, "c", 500, 50),
        _ev(DEV, tracing.OPS_LINE, "d", 1050, 200),  # clipped to ..1100
        _ev(DEV, "XLA Modules", "jit_x", 0, 5000),   # not an op line
    ]
    red = tracing.reduce(ev)
    assert red["window_s"] == pytest.approx(1e-6)
    assert red["busy_s"][DEV] == pytest.approx((150 + 50 + 50) / 1e9)
    # ops are summed whole, for those that start inside the window
    assert red["ops"] == pytest.approx({"b": 1e-7, "c": 5e-8, "d": 2e-7})
    gaps = dict(tracing.breakdown(red)["idle_gaps"])
    # 250..500 under bench.unit; 550..1050 under bench.unit and lfproc.write
    assert gaps["bench.unit"] == pytest.approx(750e-9)
    assert gaps["lfproc.write"] == pytest.approx(500e-9)


def test_no_window_or_no_device_reads_nothing():
    assert tracing.reduce([_ev(DEV, tracing.OPS_LINE, "a", 0, 1)]) is None
    assert tracing.reduce([_ev("/host:CPU", "m", tracing.WINDOW_SPAN, 0, 9)]) \
        is None


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_trace.json.gz")


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_recorded_v5e_trace():
    with gzip.open(FIXTURE, "rt") as fh:
        fx = json.load(fh)
    red = tracing.reduce(fx["events"])
    want = fx["expect"]
    # busy again, by marking each microsecond of the window that any op
    # on the first device covers
    import numpy as np

    win = [e for e in fx["events"] if e["name"] == tracing.WINDOW_SPAN][0]
    lo, n = win["start"], int(win["dur"] // 1000)
    mark = np.zeros(n + 1, bool)
    for e in fx["events"]:
        if e["plane"] == want["devices"][0] and e["line"] == tracing.OPS_LINE:
            a = max(0, int((e["start"] - lo) // 1000))
            b = min(n, int((e["start"] + e["dur"] - lo) // 1000))
            mark[a:b] = True
    assert red["busy_s"][want["devices"][0]] == pytest.approx(
        mark.sum() * 1e-6, abs=2e-6 * max(1, len(fx["events"]) / 100))
    assert red["devices"] == want["devices"]
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    for dev, busy in want["busy_s"].items():
        assert red["busy_s"][dev] == pytest.approx(busy, rel=1e-9)
        assert 0 < busy < red["window_s"]
    top = tracing.breakdown(red)["device_ops"]
    assert [k for k, _ in top[:3]] == want["top_ops"]


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_rooflines_on_recorded_trace():
    """The recorded edge trace holds fused-kernel ops: the reader finds
    them and reads a share inside (0, 100]; the low-pass reader, with no
    batch windows, reads nothing rather than 0, and with them reads a
    share of the whole busy time."""
    import harness
    from conftest import with_held_out

    with gzip.open(FIXTURE, "rt") as fh:
        red = tracing.reduce(json.load(fh)["events"])
    cfg = harness.load_cell(harness.ROOT, "edge-2048-60s",
                            with_held_out())["cfg"]
    ctx = {"trace": red, "cfg": cfg, "peak": harness.peak_row("TPU v5 lite")}
    share = harness.load_module("metrics", "fused_kernel_roofline").read(ctx)
    assert 0 < share <= 100
    lowpass = harness.load_module("metrics", "lowpass_roofline")
    assert lowpass.read(ctx) is None
    busy = red["busy_s"][red["devices"][0]]
    ctx.update(windows=[60001], cfg=dict(cfg, edge_buff_size=10))
    need = 60001 * 2048 * 2 + 60 * 2048 * 4
    assert lowpass.read(ctx) == pytest.approx(
        100 * need / busy / ctx["peak"]["hbm_bytes_per_s"], rel=1e-12)
