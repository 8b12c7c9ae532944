"""Span tracing: nested timed spans into a bounded ring buffer.

``with span("stream.round", round=3): ...`` records a wall-clock span
with attributes; spans nest per-thread (each span knows its parent and
depth), land in a process-wide ring buffer (bounded — the edge box
must never grow memory with uptime), feed the
``tpudas_span_seconds{name=...}`` histogram, and export one
``log_event("span", ...)`` line each through the existing JSONL
pipeline (skipped wholesale when no log handler is installed, so the
default cost is one perf_counter pair, a ring append, one histogram
update and the profiler annotation below — a hand-rolled context
manager, not ``@contextmanager``, keeps that under ~10 us on the
stream hot path; tests/test_window_spans.py holds it there).

Each span is also a ``jax.profiler.TraceAnnotation`` of its name, once
the process has imported jax: under an active profiler trace it lands
on the profiler's host plane, on the thread that ran it, on the same
clock as the device's ops (a span never imports jax itself, so
host-only processes stay free of it).  Attributes stay on the ring
and JSONL record only.

``TPUDAS_OBS=0`` disables recording entirely (same kill-switch as the
registry); ``TPUDAS_SPAN_RING`` sizes the ring (default 2048 finished
spans).
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from collections import deque

from tpudas.obs import registry as _registry_mod
from tpudas.utils import logging as _logging

__all__ = [
    "add_span_sink",
    "remove_span_sink",
    "span",
    "get_spans",
    "clear_spans",
    "span_ring_capacity",
]

_DEFAULT_RING = 2048


def span_ring_capacity() -> int:
    try:
        cap = int(os.environ.get("TPUDAS_SPAN_RING", _DEFAULT_RING))
    except ValueError:
        cap = _DEFAULT_RING
    return max(1, cap)


_lock = threading.Lock()
_ring: deque = deque(maxlen=span_ring_capacity())
_local = threading.local()
_ids = itertools.count(1)
# finished-span sinks (e.g. the flight recorder's thread-scoped
# capture, tpudas.obs.flight) — called with each finished span record
_sinks: list = []


def add_span_sink(fn) -> None:
    """Register ``fn(record)`` to receive every finished span (after
    the ring append).  A raising sink is counted
    (``tpudas_obs_spans_dropped_total{reason="sink_error"}``) and
    skipped — a trace consumer must never break the traced code."""
    with _lock:
        if fn not in _sinks:
            _sinks.append(fn)


def remove_span_sink(fn) -> None:
    with _lock:
        if fn in _sinks:
            _sinks.remove(fn)
# jax.profiler.TraceAnnotation, resolved once jax is imported (None =
# not yet, False = unavailable)
_annotation_cls = None


def _span_stack():
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def _trace_annotation():
    global _annotation_cls
    if _annotation_cls is None:
        if "jax" not in sys.modules:
            return None  # not imported here: a span never imports it
        try:
            from jax.profiler import TraceAnnotation

            _annotation_cls = TraceAnnotation
        except Exception:  # pragma: no cover - backend specific
            _annotation_cls = False
    return _annotation_cls


def _span_metrics(reg):
    """(histogram, eviction_counter, dropped_counter) handles, memoized
    on the registry instance — the per-span cost must not include
    get-or-create (once the ring is full, EVERY span exit counts an
    eviction)."""
    handles = getattr(reg, "_span_metric_handles", None)
    if handles is None:
        handles = (
            reg.histogram(
                "tpudas_span_seconds",
                "span wall-clock duration by span name",
                labelnames=("name",),
            ),
            reg.counter(
                "tpudas_spans_evicted_total",
                "finished spans dropped from the full ring buffer",
            ),
            reg.counter(
                "tpudas_obs_spans_dropped_total",
                "finished spans lost before reaching a consumer "
                "(ring eviction, or a raising span sink)",
                labelnames=("reason",),
            ),
        )
        try:
            reg._span_metric_handles = handles
        except AttributeError:  # pragma: no cover - exotic registry
            pass
    return handles


class _Span:
    """Hand-rolled context manager (no ``@contextmanager`` generator
    machinery) for the hot path.  Yields the mutable span record."""

    __slots__ = ("name", "attrs", "rec", "_cm", "_t0", "_reg", "_stack")

    def __init__(self, name, attrs):
        self.name = name
        self.attrs = attrs
        self.rec = None

    def __enter__(self):
        # same gate as the registry: TPUDAS_OBS=0 disables spans
        # unless an explicit use_registry scope asked for measurements
        reg = _registry_mod.get_registry()
        if reg is _registry_mod._NOOP_REGISTRY:
            return None
        stack = _span_stack()
        parent = stack[-1] if stack else None
        rec = self.rec = {
            "name": str(self.name),
            "id": next(_ids),
            "parent": None if parent is None else parent["id"],
            "depth": len(stack),
            "attrs": self.attrs,
        }
        stack.append(rec)
        self._stack = stack
        self._reg = reg
        # the name alone: ``#k=v#`` metadata would split the trace's
        # by-name sums
        ann = _annotation_cls or _trace_annotation()
        self._cm = ann(rec["name"]) if ann and ann.is_enabled() else None
        if self._cm is not None:
            self._cm.__enter__()
        rec["start"] = time.time()
        self._t0 = time.perf_counter()
        return rec

    def __exit__(self, exc_type, exc, tb):
        rec = self.rec
        if rec is None:
            return False
        dur = time.perf_counter() - self._t0
        if self._cm is not None:
            self._cm.__exit__(None, None, None)
        if exc is not None:
            rec["error"] = repr(exc)[:200]
        rec["duration_s"] = dur
        self._stack.pop()
        with _lock:
            evicted = len(_ring) == _ring.maxlen
            _ring.append(rec)
        hist, evictions, dropped = _span_metrics(self._reg)
        # one hold of the registry's lock, which its metrics share, for
        # every update: a full ring counts an eviction on each exit.
        # Silent trace loss must be visible in metrics.prom (the
        # obs-wide dropped-spans counter)
        with hist._lock:
            if evicted:
                evictions._add((), 1.0)
                dropped._add(("ring_full",), 1.0)
            hist._record((rec["name"],), dur)
        for sink in tuple(_sinks):
            try:
                sink(rec)
            except Exception:
                dropped.inc(reason="sink_error")
        # JSONL export through the existing pipeline (skipped wholesale
        # when no handler is installed)
        if _logging._handler is not None:
            fields = {
                **rec["attrs"],  # attrs first: the envelope keys win
                "span": rec["name"],
                "id": rec["id"],
                "parent": rec["parent"],
                "depth": rec["depth"],
                "duration_s": round(dur, 6),
            }
            if "error" in rec:
                fields["error"] = rec["error"]
            _logging.log_event("span", **fields)
        return False  # never swallow the body's exception


def span(name: str, **attrs) -> _Span:
    """Record a named, attributed, nested timed span around the block.

    Exceptions propagate; the span is still recorded with
    ``error=<repr prefix>`` so a crashed round leaves its trace."""
    return _Span(name, attrs)


def annotate(**attrs) -> None:
    """Add ``attrs`` to the innermost span open on this thread, for
    what is known only once its block has run (none open: no-op)."""
    stack = _span_stack()
    if stack:
        stack[-1]["attrs"].update(attrs)


def get_spans(name: str | None = None) -> list:
    """Finished spans currently in the ring (oldest first), optionally
    filtered by name.  Returns copies — callers cannot corrupt the
    ring."""
    with _lock:
        recs = list(_ring)
    if name is not None:
        recs = [r for r in recs if r["name"] == name]
    return [dict(r) for r in recs]


def clear_spans() -> None:
    """Empty the ring and re-read ``TPUDAS_SPAN_RING`` (tests resize
    the ring this way)."""
    global _ring
    with _lock:
        _ring = deque(maxlen=span_ring_capacity())
