"""Profiler trace: recording, and its reduction to busy time and kernels.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
plain event records ``{"plane", "line", "name", "start", "dur",
"stats"}`` (ns).  :func:`reduce` turns those records into what the
per-layer metrics read:

- ``window`` — the host span named ``bench.window`` (the harness's own
  annotation around the measured window), in the trace's clock;
- ``busy`` — per device plane, the union of the op intervals on its
  ``XLA Ops`` line clipped to the window, so overlapping ops count once;
- ``ops`` — per op name, summed device seconds on the first device;
- ``gaps`` — the first device's idle intervals inside the window, each
  with the names of every host span open at its midpoint (host threads
  overlap, so one gap can count for several names, or for ``none``).

Nothing here knows the program: kernel names are matched by the
metric readers (``benchmark/metrics/*_roofline.py``).
"""

from __future__ import annotations

import glob
import os

import numpy as np

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"


def options():
    from jax import profiler

    opts = profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python calls are not recorded
    opts.host_tracer_level = 1  # TraceAnnotation spans, not runtime internals
    return opts


def _stats(ev) -> dict:
    out = {}
    try:
        for k, v in ev.stats:
            out[str(k)] = v if isinstance(v, (int, float, str)) else str(v)
    except (TypeError, ValueError):
        pass
    return out


def load(log_dir: str) -> list:
    from jax import profiler

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = profiler.ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, "XLA Modules"):
                continue
            for ev in line.events:
                rec = {"plane": plane.name, "line": line.name,
                       "name": ev.name, "start": float(ev.start_ns),
                       "dur": float(ev.duration_ns)}
                if device and line.name == OPS_LINE:
                    rec["stats"] = _stats(ev)
                events.append(rec)
    return events


def _union(intervals) -> list:
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def reduce(events: list) -> dict | None:
    """The trace's window, busy and idle per device, op totals and idle
    gaps; ``None`` when the window span or a device is missing."""
    win = [e for e in events if e["name"] == WINDOW_SPAN]
    devices = sorted({e["plane"] for e in events
                      if e["plane"].startswith("/device:")
                      and e["line"] == OPS_LINE})
    if not win or not devices:
        return None
    lo = win[0]["start"]
    hi = lo + win[0]["dur"]
    busy, merged = {}, {}
    for dev in devices:
        iv = [(max(e["start"], lo), min(e["start"] + e["dur"], hi))
              for e in events
              if e["plane"] == dev and e["line"] == OPS_LINE
              and e["start"] < hi and e["start"] + e["dur"] > lo]
        merged[dev] = _union([i for i in iv if i[1] > i[0]])
        busy[dev] = sum(b - a for a, b in merged[dev]) / 1e9
    first = devices[0]
    ops = {}
    for e in events:
        if e["plane"] == first and e["line"] == OPS_LINE and \
                lo <= e["start"] < hi:
            ops[e["name"]] = ops.get(e["name"], 0.0) + e["dur"] / 1e9
    host = [e for e in events if not e["plane"].startswith("/device:")
            and e["name"] != WINDOW_SPAN and e["dur"] > 0]
    h_lo = np.array([e["start"] for e in host])
    h_hi = h_lo + np.array([e["dur"] for e in host])
    gaps, prev = [], lo
    for a, b in merged[first] + [[hi, hi]]:
        if a > prev:
            mid = (a + prev) / 2
            hit = np.nonzero((h_lo <= mid) & (mid < h_hi))[0]
            open_ = sorted({host[i]["name"] for i in hit})
            gaps.append((open_ or ["none"], (a - prev) / 1e9))
        prev = max(prev, b)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy,
        "devices": devices,
        "ops": ops,
        "gaps": gaps,
        "op_events": [e for e in events if e["plane"] == first
                      and e["line"] == OPS_LINE and lo <= e["start"] < hi],
    }


def breakdown(red: dict, top: int = 10) -> dict:
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    by_span = {}
    for names, s in red["gaps"]:
        for name in names:
            by_span[name] = by_span.get(name, 0.0) + s
    gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
