"""One run of one cell: set-up, the measured window, the check, the line.

Everything a cell is made of is found by name: the workload entry in
``BENCHMARK.json`` names its configuration and traffic mix; the
configuration's file (``benchmark/configs/<config>.json``) names the
driver (``benchmark/drivers/<driver>.py``) and every processing
parameter; the mix is ``benchmark/workloads/<traffic>.json``; each
metric that
``BENCHMARK.json`` declares for the cell is read by
``benchmark/metrics/<metric>.py`` (``read(ctx)`` returns a number, or
``None`` when it finds nothing to read, and is then left out).
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
N_COLS = 8  # channels the check compares, drawn from the seed
MAX_WARM = 8  # warm-up units at most; stops at the first that builds nothing


class NoDevice(SystemExit):
    pass


def load_bench(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def load_cell(root: str, name: str, bench: dict | None = None) -> dict:
    """The cell's entry, configuration, mix and metrics, from
    ``BENCHMARK.json`` or from ``bench`` (tests: cells held out)."""
    bench = bench or load_bench(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as fh:
        cfg = json.load(fh)
    import traffic

    mix = traffic.load_mix(BENCH_DIR, cell["traffic"])

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "cell": cell, "cfg": cfg, "mix": mix,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def require_device(chips: int, allow_cpu: bool) -> dict:
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as exc:
        raise NoDevice(f"no accelerator: JAX could not start ({exc})")
    if not allow_cpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's devices are {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class Builds:
    """Counts programs built (``count``: compiled, or loaded from the
    persistent cache) and those loaded (``loaded``).  JAX times a cache
    load as a backend compile too, so ``count - loaded`` were compiled.
    A unit that compiled nothing has run every program it needs: a run
    whose cache is warm then needs one warm-up unit."""

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.loaded = 0

        def on_duration(event, duration, **_):
            if event.endswith("backend_compile_duration"):
                self.count += 1

        def on_event(event, **_):
            if event.endswith("cache_hits"):
                self.loaded += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def check_cols(seed: int, n_ch: int) -> list:
    import numpy as np

    rng = np.random.default_rng([int(seed) & (2**63 - 1), 7])
    mid = rng.choice(np.arange(1, n_ch - 1), size=min(N_COLS, n_ch) - 2,
                     replace=False)
    return sorted({0, n_ch - 1, *(int(c) for c in mid)})


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, imported by path."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_mesh(cfg: dict):
    """The configuration's channel mesh (``mesh_devices``), or None."""
    n = cfg.get("mesh_devices")
    if not n:
        return None
    from tpudas.parallel.mesh import make_mesh

    return make_mesh(int(n))


def peak_row(kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def memory_peak(n: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run(workload, seed, seconds, trace, allow_cpu=False, overrides=None,
        bench=None):
    """One run; returns ``(result, checks)``.  ``overrides`` (the CPU
    rehearsal) replaces configuration and mix keys; ``bench`` (tests)
    stands for ``BENCHMARK.json``."""
    t_start = time.perf_counter()
    for path in (ROOT, BENCH_DIR):  # the system under test, the harness
        if path not in sys.path:
            sys.path.insert(0, path)
    spec = load_cell(ROOT, workload, bench)
    cfg, mix, cell = spec["cfg"], spec["mix"], spec["cell"]
    for k, v in (overrides or {}).items():
        (mix if k in mix else cfg)[k] = v
    if importlib.util.find_spec("tpudas") is None:
        raise NoDevice("the tpudas package is not importable from here")
    # JAX's persistent cache at a fixed path inside the checkout, every
    # program kept, and no LRU eviction: eviction keeps an access-time
    # file per entry, and one entry without it fails every later write
    # (seen on the chip machine's file system, PR 22)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    def say(msg):
        print(f"[bench] {time.perf_counter() - t_start:8.3f} {msg}",
              file=sys.stderr, flush=True)

    work = tempfile.mkdtemp(prefix="tpudas_bench_")
    # the stager starts first: it builds its payloads while JAX starts
    driver = load_module("drivers", cfg["driver"]).Driver(cfg, mix, seed, work)
    stager = driver.stager
    try:
        device = require_device(cell["chips"], allow_cpu)
        import jax

        jax.config.update("jax_compilation_cache_max_size", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        import tracing
        from tpudas.obs.registry import MetricsRegistry, use_registry

        say(f"jax up: {device}")
        builds = Builds()
        driver.start()
        say(f"traffic staged (pool {stager.pool_s} s)")
        warm = 0
        for warm in range(1, MAX_WARM + 1):
            b0, l0 = builds.count, builds.loaded
            driver.unit()
            loaded = builds.loaded - l0
            compiled = builds.count - b0 - loaded
            say(f"warm-up unit {warm}: {compiled} programs compiled, "
                f"{loaded} loaded from the cache")
            if warm >= mix["min_warm_units"] and compiled == 0:
                break
        driver.mark()
        stager.wait_s, stager.min_lead = 0.0, None
        setup_s = time.perf_counter() - t_start
        say(f"set-up {setup_s:.3f} s, {warm} warm-up units, "
            f"{builds.count} programs built")

        reg = MetricsRegistry()
        trace_dir = os.path.join(work, "trace") if trace else None
        spans, failed, units = [], 0, 0
        b0 = builds.count
        with use_registry(reg), contextlib.ExitStack() as stack:
            if trace_dir:
                from tpudas.obs.trace import add_span_sink, remove_span_sink

                jax.profiler.start_trace(trace_dir,
                                         profiler_options=tracing.options())
                stack.callback(jax.profiler.stop_trace)
                add_span_sink(spans.append)
                stack.callback(remove_span_sink, spans.append)
                stack.enter_context(
                    jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN))
            wall0 = time.time()
            w0 = w1 = time.perf_counter()
            try:
                while True:
                    driver.unit()
                    units += 1
                    w1 = time.perf_counter()
                    if w1 - w0 >= seconds:
                        break
            except Exception as exc:  # the run reports it as not correct
                failed = 1
                say(f"unit {units + 1} failed: {type(exc).__name__}: {exc}")
        window_s = w1 - w0
        built_in_window = builds.count - b0
        ctx = {
            "cfg": cfg, "mix": mix, "cell": cell, "setup_s": setup_s,
            "window_s": window_s, "units": units,
            "samples": units * driver.samples_per_unit(),
            "registry": reg, "trace": None, "peak": None,
            **driver.readings(),
        }
        say(f"window {window_s:.3f} s, {units} units, {failed} failed, "
            f"{built_in_window} programs built in it, stager waited "
            f"{stager.wait_s:.3f} s, min lead {stager.min_lead} files, "
            f"{stager.bytes_written} bytes staged")
        device["memory_peak_bytes"] = memory_peak(cell["chips"])
        if trace_dir:
            events = tracing.load(trace_dir)
            events += _program_spans(events, spans, wall0)
            red = tracing.reduce(events)
            ctx["trace"] = red
            if red is not None:
                ctx["peak"] = None if allow_cpu else peak_row(device["kind"])
                devs = red["devices"][: cell["chips"]]
                device["busy_s"] = sum(red["busy_s"][d] for d in devs) / len(devs)
                device["window_s"] = red["window_s"]
            _dump_trace(events, workload, seed)
        checks = {}
        if not failed:
            cols = check_cols(seed, cfg["channels"])
            t_chk = time.perf_counter()
            try:
                got = driver.check(cols)
            except Exception as exc:  # a check that cannot read is a fail
                say(f"check failed: {type(exc).__name__}: {exc}")
                got = {k: float("inf") for k in cfg["limits"]}
                got["units_checked"] = 0
            say(f"check took {time.perf_counter() - t_chk:.3f} s over "
                f"{got.pop('units_checked')} units, channels {cols}")
            for name, limit in cfg["limits"].items():
                checks[name] = {"value": got[name], "limit": limit}
    finally:
        stager.close()
        shutil.rmtree(work, ignore_errors=True)
    correct = not failed and all(c["value"] <= c["limit"]
                                 for c in checks.values())
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec[kind]:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": units, "failed": failed,
              "metrics": metrics, "device": device}
    if trace and ctx["trace"] is not None:
        result["breakdown"] = tracing.breakdown(ctx["trace"])
    result["checks"] = checks
    return result, checks


def _program_spans(events, spans, wall0) -> list:
    """The program's own spans (``tpudas.obs.trace``, wall-clock
    stamped) moved onto the trace's clock by the window annotation."""
    import tracing

    win = [e for e in events if e["name"] == tracing.WINDOW_SPAN]
    if not win:
        return []
    base = win[0]["start"]
    return [{"plane": "program", "line": "spans", "name": s["name"],
             "start": base + (s["start"] - wall0) * 1e9,
             "dur": s.get("duration_s", 0.0) * 1e9} for s in spans]


def _dump_trace(events, workload, seed) -> None:
    """Keep the trace's events beside the run when ``chiprun_out`` exists
    (the chip tool brings it back; ``tests/fixtures`` is cut from one);
    nothing otherwise."""
    import gzip

    out = os.path.join(ROOT, "chiprun_out")
    if os.path.isdir(out):
        with gzip.open(os.path.join(out, f"events_{workload}_{seed}.json.gz"),
                       "wt") as fh:
            json.dump(events[:60000], fh)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, checks = run(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    except NoDevice as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 2
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
