"""Fleet scale bench (ISSUE 8): N concurrent streams on one host.

Four measurements, written to ``BENCH_pr08.json``:

1. **Scale sweep** — N ∈ {1, 2, 4, 8} same-config streams at
   1 kHz × 256 channels, each N in a FRESH subprocess (cold jit, so
   compile sharing is measured honestly per run).  Per N: aggregate
   real-time factor (total stream-seconds processed / run wall),
   per-stream head-lag spread, per-stream FIRST processing-round wall
   (the compile-sharing evidence: stream 1 pays the jit, streams 2..N
   warm-start from the in-process cache — ≤ 1 compile per kernel,
   counted directly off jax's monitoring events), and scheduler
   overhead (deficit-round-robin bookkeeping wall / total step wall,
   acceptance < 2%).
2. **Byte identity** — a fleet of 4 same-config streams (pyramid +
   detect on, identical per-stream feeds) versus ONE single-stream
   driver control: outputs, parsed stream carry, pyramid tree, and
   events ledger must be byte-identical per stream (the acceptance
   criterion, in-process form).
3. **Fleet crash drill** — ``tools/crash_drill.py`` ``--streams 4``:
   seeded SIGKILL cycles against the fleet worker, every stream
   audit-clean and byte-identical to its single-stream control.
4. The headline gauges read back from the metrics registry
   (``tpudas_fleet_*`` — OBSERVABILITY.md).

Run (CPU):

    JAX_PLATFORMS=cpu python tools/fleet_bench.py --out BENCH_pr08.json

Knobs: ``--streams 1,2,4,8``  ``--fs 1000``  ``--channels 256``
``--file-sec 10``  ``--drill-cycles 6`` (0 skips the drill).

**Batched A/B (ISSUE 16).**  ``--batched 1`` runs the scale sweep
(and the byte-identity leg) under the ragged-batched scheduler;
``--batched ab`` runs every scale point twice — sequential then
batched, fresh subprocess each — and records the head-to-head
(aggregate realtime factor, stacked/solo launches per round, lag
spread).  Both batched modes also run the OPS-LEVEL stacked-vs-
sequential microbench (``ops_stacked``): N same-plan device steps as
N solo launches versus ONE stacked launch, the isolated form of the
launch-overhead claim (the end-to-end fleet on CPU is host-bound —
spool IO, HDF5 writes, pyramid appends — so the device-step win is
measured where it lives; PERF.md §6 (PR 16)).  The PR 16 artifact:

    JAX_PLATFORMS=cpu python tools/fleet_bench.py \
        --streams 16,64,256 --batched ab --poll-jitter 0 \
        --channels 8 --fs 100 --drill-cycles 2 --drill-batched 1 \
        --out BENCH_pr16.json

(``--poll-jitter 0`` keeps same-config streams due in lockstep so
batch groups persist past round 1 — the backlog-drain regime batching
targets; with default jitter, idle-tail polls de-synchronize and
service solo, by design.)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

T0 = "2023-03-22T00:00:00"
DT_OUT = 1.0
EDGE_SEC = 5.0
PATCH_OUT = 20


def _feed(directory, start_index, count, fs, n_ch, file_sec,
          noise=0.01):
    import numpy as np

    from tpudas.testing import make_synthetic_spool

    make_synthetic_spool(
        directory, n_files=count, file_duration=file_sec, fs=fs,
        n_ch=n_ch, noise=noise,
        start=np.datetime64(T0)
        + np.timedelta64(int(start_index * file_sec * 1e9), "ns"),
        prefix=f"raw{start_index:04d}",
    )


def _install_compile_counter():
    """Count backend compiles via jax's monitoring events (any event
    whose name mentions compilation).  Private-API tolerant: on drift
    the bench falls back to the first-round-wall evidence."""
    counts: dict = {}
    try:
        from jax._src import monitoring

        def _on_event(event, **kw):
            if "compil" in event:
                counts[event] = counts.get(event, 0) + 1

        def _on_duration(event, duration, **kw):
            if "compil" in event:
                counts[event] = counts.get(event, 0) + 1

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception:
        pass
    return counts


def _metric(name, **labels) -> float:
    from tpudas.obs.registry import get_registry

    try:
        return float(get_registry().value(name, **labels))
    except Exception:
        return 0.0


def _metric_total(name, **match) -> float:
    """Sum a labeled counter over every series (optionally filtered by
    exact label values) — the registry read for the devprof totals,
    whose series are keyed ``{engine, stacked, stream}``."""
    from tpudas.obs.registry import get_registry

    m = get_registry().get(name)
    if m is None or not hasattr(m, "_series"):
        return 0.0
    total = 0.0
    for labels, value in m._series():
        if all(labels.get(k) == v for k, v in match.items()):
            total += float(value)
    return total


def _devprof_stats(rounds: int) -> dict:
    """The device-telemetry column (ISSUE 17) read back from the
    registry after a fleet run: true launch counts and device-execute
    seconds (stacked launches count 1/N per member, so the sums are
    launch-true), plus the per-stream live classification."""
    from tpudas.obs import devprof

    launches = _metric_total("tpudas_devprof_launches_total")
    device_s = _metric_total("tpudas_devprof_device_seconds_total")
    stacked_launches = _metric_total(
        "tpudas_devprof_launches_total", stacked="1"
    )
    snap = devprof.devprof_snapshot(calibrate=True)
    return {
        "launches_total": round(launches, 3),
        "stacked_launches_total": round(stacked_launches, 3),
        "device_seconds_total": round(device_s, 6),
        "launches_per_round": round(launches / rounds, 3),
        "device_seconds_per_round": round(device_s / rounds, 6),
        "compiles": snap["compile"]["count"],
        "compile_seconds": snap["compile"]["seconds"],
        "streams": snap["streams"],
    }


def run_scale_child(n_streams, fs, n_ch, file_sec, feeds=2,
                    batched=False, poll_jitter=None) -> dict:
    """One fresh-process scale point: an N-stream fleet, 2 files
    upfront + ``feeds`` mid-run feeds per stream.  ``batched`` runs
    the ragged-batched scheduler (ISSUE 16) and reads the
    ``tpudas_fleet_batch_*`` counters back into the report."""
    from tpudas.fleet import FleetEngine, StreamConfig, StreamSpec

    compile_counts = _install_compile_counter()
    workdir = tempfile.mkdtemp(prefix=f"fleet_bench_{n_streams}_")
    root = os.path.join(workdir, "root")
    jitter_kw = (
        {} if poll_jitter is None
        else {"poll_jitter": float(poll_jitter)}
    )
    config = StreamConfig(
        kind="lowpass",
        start_time=T0,
        output_sample_interval=DT_OUT,
        edge_buffer=EDGE_SEC,
        process_patch_size=PATCH_OUT,
        poll_interval=0.0,
        **jitter_kw,
    )
    specs = []
    sources = []
    for i in range(n_streams):
        src = os.path.join(workdir, f"src{i:02d}")
        _feed(src, 0, 2, fs, n_ch, file_sec)
        sources.append(src)
        specs.append(
            StreamSpec(
                stream_id=f"s{i:02d}", source=src, config=config
            )
        )
    fed = {"n": 0}

    def feeder(_wait):
        if fed["n"] < feeds:
            fed["n"] += 1
            for src in sources:
                _feed(src, 1 + fed["n"], 1, fs, n_ch, file_sec)

    eng = FleetEngine(root, specs, sleep_fn=feeder, batched=batched)
    t0 = time.perf_counter()
    summary = eng.run()
    wall = time.perf_counter() - t0
    rounds = max(int(summary["rounds_total"]), 1)
    stacked = _metric("tpudas_fleet_batch_stacked_launches_total")
    solo = _metric("tpudas_fleet_batch_solo_launches_total")
    batch_stats = {
        "enabled": bool(batched),
        "groups_total": _metric("tpudas_fleet_batch_groups_total"),
        "members_total": _metric("tpudas_fleet_batch_members_total"),
        "stacked_launches_total": stacked,
        "stacked_members_total": _metric(
            "tpudas_fleet_batch_stacked_members_total"
        ),
        "solo_launches_total": solo,
        "launches_per_round": round((stacked + solo) / rounds, 3),
        "mean_stack_width": round(
            _metric("tpudas_fleet_batch_stacked_members_total")
            / stacked, 2
        ) if stacked else None,
    }
    files_total = 2 + feeds
    data_sec_per_stream = files_total * file_sec
    # first PROCESSING step wall per stream, in service order — the
    # compile-sharing evidence (stream 1 cold, the rest warm)
    first_walls = {}
    for sid, status, w in eng.service_log:
        if status == "processed" and sid not in first_walls:
            first_walls[sid] = round(w, 4)
    step_wall = sum(w for _sid, _st, w in eng.service_log)
    lags = [
        s["head_lag_seconds"]
        for s in summary["streams"].values()
        if s["head_lag_seconds"] is not None
    ]
    return {
        "streams": n_streams,
        "fs_hz": fs,
        "channels": n_ch,
        "batched": bool(batched),
        "batch": batch_stats,
        "devprof": _devprof_stats(rounds),
        "data_seconds_per_stream": data_sec_per_stream,
        "rounds_total": summary["rounds_total"],
        "wall_seconds": round(wall, 3),
        "aggregate_realtime_factor": round(
            n_streams * data_sec_per_stream / wall, 2
        ),
        "per_stream_realtime_factor": {
            sid: s["realtime_factor"]
            for sid, s in summary["streams"].items()
        },
        "head_lag_seconds": {
            "min": round(min(lags), 3) if lags else None,
            "max": round(max(lags), 3) if lags else None,
            "spread": round(max(lags) - min(lags), 3) if lags else None,
        },
        "first_round_wall_seconds": first_walls,
        "compile_share": _compile_share(first_walls),
        "compile_events": compile_counts,
        "sched_seconds": summary["sched_seconds"],
        "sched_overhead_pct": round(
            100.0 * summary["sched_seconds"] / step_wall, 4
        )
        if step_wall
        else 0.0,
        "parked": summary["parked"],
    }


def _compile_share(first_walls: dict) -> dict:
    """Cold-vs-warm first-round evidence: the first-served stream pays
    the jit compile, later same-shape streams reuse it."""
    walls = list(first_walls.values())
    if len(walls) < 2:
        return {"cold_s": walls[0] if walls else None, "warm_max_s": None,
                "shared": None}
    cold, rest = walls[0], walls[1:]
    return {
        "cold_s": round(cold, 4),
        "warm_max_s": round(max(rest), 4),
        "shared": bool(max(rest) < 0.5 * cold),
    }


def bench_ops_stacked(n_list, fs=1000.0, n_ch=8, block_sec=2.0,
                      repeats=3) -> list:
    """The launch-overhead claim, isolated (ISSUE 16): N same-plan
    streams' device steps as N sequential ``cascade_decimate_stream``
    launches versus ONE ``cascade_decimate_stream_stacked`` launch —
    identical math, identical bytes (pinned by tier-1), only the
    launch count differs.  Compile excluded (one warm call per path);
    best-of-``repeats`` walls, aggregate throughput in processed
    stream-seconds per wall-second."""
    import jax
    import numpy as np

    from tpudas.ops.fir import (
        cascade_decimate_stream,
        cascade_decimate_stream_stacked,
        cascade_stream_init,
        design_cascade,
    )

    from tpudas.obs import devprof

    # fresh telemetry state: the launch-floor / peak calibration
    # probes re-run HERE, adjacent to the measurement, instead of
    # inheriting figures measured under whatever load earlier legs
    # left behind (stale peaks skew utilization both ways)
    devprof.reset()
    ratio = int(round(fs * DT_OUT))
    plan = design_cascade(fs, ratio, 0.45 / DT_OUT, 4)
    T = int(round(block_sec * fs))
    rng = np.random.default_rng(0)
    results = []
    for n in n_list:
        blocks = [
            rng.standard_normal((T, n_ch)).astype(np.float32)
            for _ in range(n)
        ]
        carries = [cascade_stream_init(plan, n_ch) for _ in range(n)]

        def run_seq():
            return [
                cascade_decimate_stream(b, c, plan, "xla")
                for b, c in zip(blocks, carries)
            ]

        def run_stacked():
            return cascade_decimate_stream_stacked(
                blocks, carries, plan, "xla"
            )

        def timed(fn):
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                out = fn()
                jax.block_until_ready([y for y, _c in out])
                best = min(best, time.perf_counter() - t0)
            return best

        jax.block_until_ready(
            [y for y, _c in run_seq()] + [y for y, _c in run_stacked()]
        )  # compile both paths outside the timed region
        # warm solo launches under a devprof stream scope: the live
        # launch-bound vs compute-bound read for THIS geometry, to be
        # checked against the measured stacking speedup (ISSUE 17
        # acceptance: classification agrees with the PR 16 crossover)
        dev_sid = f"ops_{n_ch}ch_{T}r"
        with devprof.stream_scope(dev_sid):
            t_seq = timed(run_seq)
        t_stk = timed(run_stacked)
        devprof.round_collect(dev_sid)
        cls = devprof.classify_stream(dev_sid) or {}
        data_sec = n * block_sec
        entry = {
            "streams": n,
            "rows": T,
            "channels": n_ch,
            "launches_sequential": n,
            "launches_stacked": 1,
            "sequential_wall_s": round(t_seq, 5),
            "stacked_wall_s": round(t_stk, 5),
            "speedup": round(t_seq / t_stk, 2),
            "sequential_aggregate_rt": round(data_sec / t_seq, 1),
            "stacked_aggregate_rt": round(data_sec / t_stk, 1),
            "devprof": {
                "mean_launch_seconds": cls.get("mean_launch_seconds"),
                "launch_ratio": cls.get("launch_ratio"),
                "bound": cls.get("bound"),
                "utilization": cls.get("utilization"),
            },
        }
        results.append(entry)
        print(
            f"fleet_bench: ops_stacked N={n} "
            f"seq={entry['sequential_wall_s']}s "
            f"stacked={entry['stacked_wall_s']}s "
            f"speedup={entry['speedup']}x "
            f"bound={entry['devprof']['bound']} "
            f"launch_ratio={entry['devprof']['launch_ratio']}"
        )
    return results


def bench_byte_identity(streams=4, fs=200.0, n_ch=16,
                        file_sec=20.0, batched=False) -> dict:
    """The acceptance criterion, in-process: a fleet of N same-config
    streams (pyramid + detect + health on, identical feeds) versus
    ONE single-stream driver control — outputs, parsed carry, pyramid
    tree, and events ledger byte-identical per stream."""
    import hashlib

    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from crash_drill import DETECT_OPS, _pyramid_tree

    from tpudas.fleet import FleetEngine, StreamConfig, StreamSpec
    from tpudas.proc.streaming import run_lowpass_realtime

    workdir = tempfile.mkdtemp(prefix="fleet_bench_ident_")
    root = os.path.join(workdir, "root")
    config = StreamConfig(
        kind="lowpass",
        start_time=T0,
        output_sample_interval=DT_OUT,
        edge_buffer=EDGE_SEC,
        process_patch_size=PATCH_OUT,
        poll_interval=0.0,
        pyramid=True,
        detect=True,
        detect_operators=DETECT_OPS,
        health=True,
        # lockstep polling under batched mode so the identity claim
        # covers rounds that actually ran stacked
        **({"poll_jitter": 0.0} if batched else {}),
    )
    specs = []
    for i in range(streams):
        src = os.path.join(workdir, f"src{i:02d}")
        _feed(src, 0, 2, fs, n_ch, file_sec)
        specs.append(
            StreamSpec(stream_id=f"s{i:02d}", source=src,
                       config=config)
        )
    sources = [s.source for s in specs]
    fed = {"done": False}

    def feeder(_wait):
        if not fed["done"]:
            fed["done"] = True
            for src in sources:
                _feed(src, 2, 1, fs, n_ch, file_sec)

    FleetEngine(root, specs, sleep_fn=feeder, batched=batched).run()
    # one control (identical feeds): the legacy single-stream driver
    ctrl_src = os.path.join(workdir, "ctrl_src")
    ctrl = os.path.join(workdir, "ctrl")
    _feed(ctrl_src, 0, 2, fs, n_ch, file_sec)
    state = {"done": False}

    def ctrl_sleep(_):
        if not state["done"]:
            state["done"] = True
            _feed(ctrl_src, 2, 1, fs, n_ch, file_sec)

    run_lowpass_realtime(
        source=ctrl_src, output_folder=ctrl, start_time=T0,
        output_sample_interval=DT_OUT, edge_buffer=EDGE_SEC,
        process_patch_size=PATCH_OUT, poll_interval=0.0,
        sleep_fn=ctrl_sleep, pyramid=True, detect=True,
        detect_operators=DETECT_OPS, health=True,
    )

    def output_shas(folder):
        out = {}
        for name in sorted(os.listdir(folder)):
            if name.startswith("LFDAS_") and name.endswith(".h5"):
                with open(os.path.join(folder, name), "rb") as fh:
                    out[name] = hashlib.sha256(fh.read()).hexdigest()
        return out

    def carry_digest(folder):
        from tpudas.proc.stream import load_carry

        c = load_carry(folder)
        if c is None:
            return None
        h = hashlib.sha256()
        h.update(json.dumps(c._meta(), sort_keys=True).encode())
        return h.hexdigest()

    def ledger_sha(folder):
        path = os.path.join(folder, ".detect", "events.jsonl")
        if not os.path.isfile(path):
            return None
        with open(path, "rb") as fh:
            return hashlib.sha256(fh.read()).hexdigest()

    want = (
        output_shas(ctrl), carry_digest(ctrl), _pyramid_tree(ctrl),
        ledger_sha(ctrl),
    )
    per_stream = {}
    for spec in specs:
        sdir = os.path.join(root, spec.stream_id)
        got = (
            output_shas(sdir), carry_digest(sdir), _pyramid_tree(sdir),
            ledger_sha(sdir),
        )
        per_stream[spec.stream_id] = {
            "outputs_match": got[0] == want[0],
            "carry_match": got[1] == want[1] and got[1] is not None,
            "pyramid_match": got[2] == want[2],
            "events_match": got[3] == want[3] and got[3] is not None,
        }
        per_stream[spec.stream_id]["ok"] = all(
            per_stream[spec.stream_id].values()
        )
    return {
        "streams": streams,
        "batched": bool(batched),
        "per_stream": per_stream,
        "ok": all(s["ok"] for s in per_stream.values()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--streams", default="1,2,4,8")
    ap.add_argument("--fs", type=float, default=1000.0)
    ap.add_argument("--channels", type=int, default=256)
    ap.add_argument("--file-sec", type=float, default=10.0)
    ap.add_argument(
        "--batched", default="0", choices=("0", "1", "ab"),
        help="0: sequential scheduler (PR 8 behavior); 1: ragged-"
        "batched scheduler; ab: run every scale point BOTH ways and "
        "record the head-to-head (ISSUE 16)",
    )
    ap.add_argument(
        "--poll-jitter", type=float, default=None,
        help="per-stream poll jitter fraction for the scale sweep "
        "(0 keeps same-config streams in lockstep so batch groups "
        "persist; default: the engine's jitter)",
    )
    ap.add_argument("--drill-cycles", type=int, default=6)
    ap.add_argument("--drill-streams", type=int, default=4)
    ap.add_argument(
        "--drill-batched", type=int, default=0,
        help="run the fleet crash drill's batched leg (ISSUE 16)",
    )
    ap.add_argument("--out", default=None)
    ap.add_argument("--child", type=int, default=0,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child:
        rep = run_scale_child(
            args.child, args.fs, args.channels, args.file_sec,
            batched=(args.batched == "1"),
            poll_jitter=args.poll_jitter,
        )
        print("FLEET_CHILD_JSON:" + json.dumps(rep))
        return 0

    payload: dict = {
        "bench": "fleet",
        "fs_hz": args.fs,
        "channels": args.channels,
        "batched_mode": args.batched,
        "scale": [],
    }
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"  # cold per child
    n_list = [int(x) for x in args.streams.split(",") if x]
    legs = {"0": (False,), "1": (True,), "ab": (False, True)}[
        args.batched
    ]
    for n in n_list:
        for leg_batched in legs:
            print(
                f"fleet_bench: scale N={n} "
                f"batched={int(leg_batched)} ..."
            )
            cmd = [
                sys.executable, os.path.abspath(__file__),
                "--child", str(n),
                "--fs", str(args.fs),
                "--channels", str(args.channels),
                "--file-sec", str(args.file_sec),
                "--batched", "1" if leg_batched else "0",
            ]
            if args.poll_jitter is not None:
                cmd += ["--poll-jitter", str(args.poll_jitter)]
            proc = subprocess.run(
                cmd, env=env, capture_output=True, text=True,
                timeout=3600,
            )
            if proc.returncode != 0:
                print(proc.stdout + proc.stderr)
                raise RuntimeError(f"scale child N={n} failed")
            line = [
                ln for ln in proc.stdout.splitlines()
                if ln.startswith("FLEET_CHILD_JSON:")
            ][-1]
            rep = json.loads(line.split(":", 1)[1])
            payload["scale"].append(rep)
            print(
                f"fleet_bench: N={n} batched={int(leg_batched)} "
                f"aggregate_rt={rep['aggregate_realtime_factor']} "
                f"launches_per_round="
                f"{rep['devprof']['launches_per_round']} "
                f"device_s_per_round="
                f"{rep['devprof']['device_seconds_per_round']} "
                f"sched_overhead={rep['sched_overhead_pct']}% "
                f"compile_share={rep['compile_share']}"
            )
    if args.batched == "ab":
        # head-to-head per N: sequential vs batched end-to-end walls
        by_n: dict = {}
        for rep in payload["scale"]:
            by_n.setdefault(rep["streams"], {})[
                "batched" if rep["batched"] else "sequential"
            ] = rep
        payload["ab"] = {
            str(n): {
                "sequential_rt": v["sequential"][
                    "aggregate_realtime_factor"
                ],
                "batched_rt": v["batched"]["aggregate_realtime_factor"],
                "end_to_end_speedup": round(
                    v["batched"]["aggregate_realtime_factor"]
                    / v["sequential"]["aggregate_realtime_factor"], 2
                ),
                "batched_launches_per_round": v["batched"]["batch"][
                    "launches_per_round"
                ],
                # devprof columns (ISSUE 17): true launch counts and
                # device-execute seconds from the telemetry plane's
                # registry counters — the sequential leg finally has a
                # launch count too (the tpudas_fleet_batch_* counters
                # only ever saw the batch executor's dispatches)
                "sequential_launches_per_round": v["sequential"][
                    "devprof"
                ]["launches_per_round"],
                "batched_devprof_launches_per_round": v["batched"][
                    "devprof"
                ]["launches_per_round"],
                "sequential_device_s_per_round": v["sequential"][
                    "devprof"
                ]["device_seconds_per_round"],
                "batched_device_s_per_round": v["batched"]["devprof"][
                    "device_seconds_per_round"
                ],
                "lag_spread_sequential": v["sequential"][
                    "head_lag_seconds"
                ]["spread"],
                "lag_spread_batched": v["batched"]["head_lag_seconds"][
                    "spread"
                ],
            }
            for n, v in sorted(by_n.items())
            if "sequential" in v and "batched" in v
        }

    if args.batched != "0":
        print("fleet_bench: ops-level stacked vs sequential launches")
        # headline: the launch-bound regime batching targets (many
        # small streams — 8 ch, 2 s blocks)
        payload["ops_stacked"] = bench_ops_stacked(n_list)
        # the crossover evidence: heavier per-stream work, where the
        # stacked program's compute dominates and batching stops
        # paying (PERF.md §6 (PR 16) "when batching loses")
        print("fleet_bench: ops-level crossover (heavy per-stream work)")
        payload["ops_stacked_heavy"] = bench_ops_stacked(
            n_list, n_ch=16, block_sec=4.0
        )

    batched_identity = args.batched != "0"
    print(
        "fleet_bench: byte identity (fleet of 4 vs single control, "
        f"batched={int(batched_identity)})"
    )
    payload["byte_identity"] = bench_byte_identity(
        batched=batched_identity
    )
    print(f"fleet_bench: byte_identity ok={payload['byte_identity']['ok']}")

    if args.drill_cycles > 0:
        print(
            f"fleet_bench: crash drill --streams {args.drill_streams} "
            f"({args.drill_cycles} cycles)"
        )
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from crash_drill import run_fleet_drill

        drill = run_fleet_drill(
            engine="cascade", streams=args.drill_streams,
            cycles=args.drill_cycles, seed=0,
            batched=bool(args.drill_batched),
        )
        drill.pop("cycle_log", None)
        payload["crash_drill_streams"] = drill
        print(
            f"fleet_bench: drill kills={drill['kills']} "
            f"audit_clean={drill['audit_clean']} ok={drill['ok']}"
        )

    sched_ok = all(
        s["sched_overhead_pct"] < 2.0 for s in payload["scale"]
    )
    # ISSUE 16 acceptance: stacked aggregate throughput >= 3x the
    # sequential launches at N=64 (the ops-level A/B — same plan,
    # same blocks, only the launch count differs)
    stacked_ok = True
    for entry in payload.get("ops_stacked", []):
        if entry["streams"] == 64:
            payload["stacked_3x_at_64"] = bool(entry["speedup"] >= 3.0)
            stacked_ok = payload["stacked_3x_at_64"]
    payload["ok"] = bool(
        sched_ok
        and stacked_ok
        and payload["byte_identity"]["ok"]
        and payload.get("crash_drill_streams", {}).get("ok", True)
    )
    text = json.dumps(payload, indent=1)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    print(text)
    print(f"fleet_bench: {'OK' if payload['ok'] else 'FAILED'}")
    return 0 if payload["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
