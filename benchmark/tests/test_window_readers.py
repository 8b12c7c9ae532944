"""The readers of the batch window loop's spans and H2D byte counter, on
hand-built registries: the value, and nothing when nothing was
recorded (as at a program without the spans)."""

import pytest

import harness
from tpudas.obs.registry import MetricsRegistry


def _read(metric, reg):
    return harness.load_module("metrics", metric).read({"registry": reg})


def _spans(reg, name, *secs):
    h = reg.histogram("tpudas_span_seconds", "span seconds",
                      labelnames=("name",))
    for s in secs:
        h.observe(s, name=name)


def test_h2d_gb_per_s():
    reg = MetricsRegistry()
    _spans(reg, "lfproc.h2d", 0.25, 0.5, 0.25)
    _spans(reg, "lfproc.dispatch", 9.0)  # another span: not read
    reg.counter("tpudas_window_h2d_bytes_total", "bytes").inc(4.8e9)
    assert _read("h2d_gb_per_s", reg) == pytest.approx(4.8)


@pytest.mark.parametrize("recorded", ["nothing", "spans only", "bytes only"])
def test_h2d_gb_per_s_reads_nothing(recorded):
    reg = MetricsRegistry()
    if recorded == "spans only":
        _spans(reg, "lfproc.h2d", 0.25)
    if recorded == "bytes only":
        _spans(reg, "lfproc.window", 1.0)
        reg.counter("tpudas_window_h2d_bytes_total", "bytes").inc(1e9)
    assert _read("h2d_gb_per_s", reg) is None


def test_dispatch_ms():
    reg = MetricsRegistry()
    _spans(reg, "lfproc.dispatch", 0.5, 0.25, 0.75, 0.5)
    _spans(reg, "lfproc.sync", 20.0)
    assert _read("dispatch_ms", reg) == pytest.approx(500.0)


@pytest.mark.parametrize("recorded", ["nothing", "other spans"])
def test_dispatch_ms_reads_nothing(recorded):
    reg = MetricsRegistry()
    if recorded == "other spans":
        _spans(reg, "lfproc.process_time_range", 80.0)
    assert _read("dispatch_ms", reg) is None
