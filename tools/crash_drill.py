"""Process-level crash drill: SIGKILL the realtime driver at seeded
random points, then prove the folder audits clean and resumes
byte-identically.

The missing end-to-end proof behind the crash-only claims: PR 3/4
killed the driver with injected exceptions at chosen fault sites; this
drill kills the *process* (``SIGKILL`` — no handlers, no cleanup, the
power-cut model) at points drawn from a seeded RNG, so the kill can
land inside any write: mid-``np.savez``, between a tile and its
manifest, halfway through an HDF5 output flush.

One drill (per engine):

1. seed a source spool, run uninterrupted worker cycles to calibrate
   the processing wall time;
2. for each of N cycles: feed one more interrogator file — but only
   when the PREVIOUS cycle ran to completion (epoch gating, below) —
   spawn the driver in a fresh subprocess (pyramid + health +
   stateful carry + detect operators on), SIGKILL it
   ``uniform(0.02, 0.95 * calib)`` seconds after it becomes ready;
3. right after the kill cycles — BEFORE the drain — assert the
   on-disk flight recorder (ISSUE 13, ``tpudas.obs.flight``) replays
   the final committed round: its ``round`` record carries all eight
   phases and is preceded by that round's spans (``stream.round``
   included) in the surviving ring;
4. run one final uninterrupted cycle to drain, then assert
   ``tpudas.integrity.audit`` reports **clean** (each worker already
   audited + repaired at startup — this run must find nothing left);
5. replay the SAME epoch schedule uninterrupted into a fresh control
   folder and assert:

   - the merged OUTPUT CONTENT (time grid + float32 samples) is
     byte-identical — output *file boundaries* are round-schedule
     dependent, so files are compared by merged content, not name;
   - the tile pyramid is byte-identical file-by-file (tiles, tails,
     manifest);
   - the detect state matches: the events ledger byte-identical, the
     score tiles byte-identical file-by-file, and the operator
     carries content-identical (meta + every state array — the
     ``.npz`` container embeds zip timestamps, so the parsed content
     is the comparable form).

**Epoch gating.**  The carry only advances when a round completes, so
every processing attempt spans exactly [end of last completed epoch →
end of fed data]: holding the fed data fixed until a cycle completes
it makes the killed run's effective consumption schedule identical to
an uninterrupted run over the same epochs — which is precisely what
crash-only resume promises, and the strongest claim that CAN hold
byte-for-byte: the FFT engine's per-block frequency masking is
chunk-schedule dependent by design (a cascade-only drill without the
gating also passes, because the FIR cascade is bit-exact under any
chunking).

CLI (the full acceptance drill — ``BENCH_pr05.json`` records a run):

    JAX_PLATFORMS=cpu python tools/crash_drill.py \
        [--cycles 25] [--seed 0] [--engines cascade,fft] [--out PATH] \
        [--mesh 4]

``--engines`` accepts any LFProc engine literal; ``fused`` (ISSUE 10)
drills the fused streaming kernel — the worker clears the fused size
threshold (``TPUDAS_FUSED_MIN_ELEMS=0``) so the tiny drill stream
actually runs the fused path, and the control replay runs it too, so
the byte-identity claim covers the fused carry save/resume cycle.

``--mesh N`` (ISSUE 7) channel-shards every drilled cycle over N
CPU-virtualized devices (``TPUDAS_MESH`` resolution in the driver)
while the control replay stays single-device: one run then proves
both that SIGKILL cycles on the SHARDED path end audit-clean and that
the sharded path is byte-identical to the unsharded engines.

``--streams N`` (ISSUE 8) drills the FLEET: every cycle spawns one
process running a :class:`tpudas.fleet.FleetEngine` over N streams
(identical per-epoch feeds into N separate source spools, per-stream
state under ``out/<stream_id>/``), SIGKILLs it mid-interleave, and at
the end asserts ``tpudas.integrity.audit.audit_fleet`` is clean and
EVERY stream's merged outputs, pyramid tree, and detect state are
byte-identical to a SINGLE-STREAM control replay of the same epoch
schedule — the fleet scheduler may interleave N carries, quarantines,
and pyramids through one process and one SIGKILL, but each stream
must crash-resume exactly as if it ran alone.  (``--streams`` and
``--mesh`` are mutually exclusive.)

``--batched`` (ISSUE 16) runs the DRILLED fleet cycles with the
ragged-batched scheduler (``TPUDAS_FLEET_BATCHED=1``: same-plan
streams stacked into one device program per wave) while the
single-stream control replay is by construction unbatched — SIGKILLs
land mid-stacked-launch, proving the batched path's durable bytes
equal the solo path's.  Requires ``--streams``.

``--async-ingest`` (ISSUE 15) drills the ASYNC PIPELINED INGEST
path: every drilled cycle runs with ``TPUDAS_INGEST_PREFETCH=2`` (so
SIGKILLs land with prefetched-but-uncommitted slices in flight and
with deferred-sync blocks pending) while the control replay runs the
synchronous slice loop — the byte-identity comparison then proves
both that a prefetched slice is crash-equivalent to a never-read one
AND that the async path's durable bytes equal the sync path's.

``--live`` (ISSUE 19) drills the LIVE PUSH PLANE: every drilled cycle
runs with ``TPUDAS_LIVE=1`` and a roster of in-process subscribers
attached from round 2 on (``TPUDAS_CRASH_DRILL_SUBS``, never drained —
so SIGKILLs land while the degrade ladder is mid-shed) while the
control replay runs live-off.  The live plane holds ZERO durable
state, so the existing byte-identity comparisons (outputs, pyramid,
detect state) are exactly the crash-only claim for it: publishing to
a thousand slow clients and dying mid-fanout must leave the same
bytes as never having had a subscriber.  Not supported with
``--streams``.

``tests/test_integrity.py`` runs a small seeded smoke in tier-1 and
the full drill under ``-m slow``; ``tests/test_fleet.py`` smokes the
fleet drill.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

T0 = "2023-03-22T00:00:00"
FS = 50.0
FILE_SEC = 20.0
N_CH = 4
DT_OUT = 1.0
EDGE_SEC = 5.0
PATCH_OUT = 20
# thresholds tuned so the drill's noisy synthetic stream actually
# produces ledger events (an empty ledger would vacuously "match")
DETECT_OPS = (
    ("stalta", {"sta": 2.0, "lta": 10.0, "on": 2.0, "off": 1.2}),
    ("rms", {"window": 5.0, "step": 2.0, "thresh": 1.5,
             "baseline": 20.0}),
)


# ---------------------------------------------------------------------------
# the worker (runs in the subprocess being killed)

def _worker(src: str, out: str, engine: str) -> int:
    import time as _t

    if engine == "fused":
        # the drill stream is tiny (4 ch); drop the fused size
        # threshold so the drilled path IS the fused kernel, not the
        # per-stage fallback the crossover gate would pick
        os.environ.setdefault("TPUDAS_FUSED_MIN_ELEMS", "0")

    from tpudas.proc.streaming import run_lowpass_realtime

    # --live leg: attach a never-drained subscriber roster once the
    # hub exists, so SIGKILLs land while the degrade ladder is
    # mid-shed (the live plane is memory-only; nothing durable may
    # change because of it)
    n_subs = int(os.environ.get("TPUDAS_CRASH_DRILL_SUBS", "0"))
    attached = {"subs": None}

    def _attach(_rnd, _lfp):
        if attached["subs"] is not None:
            return
        from tpudas.live.hub import find_hub

        hub = find_hub(folder=out)
        if hub is not None:
            attached["subs"] = [
                hub.subscribe() for _ in range(n_subs)
            ]

    # ready marker BESIDE the output folder: the parent starts its
    # kill timer only after the interpreter/jax warm-up is done, so
    # kills land in processing, not in `import jax`
    os.makedirs(out, exist_ok=True)
    with open(out + ".ready", "w") as fh:
        fh.write(str(os.getpid()))
    run_lowpass_realtime(
        source=src,
        output_folder=out,
        start_time=T0,
        output_sample_interval=DT_OUT,
        edge_buffer=EDGE_SEC,
        process_patch_size=PATCH_OUT,
        poll_interval=0.0,
        sleep_fn=lambda _s: _t.sleep(0.01),
        engine=engine,
        pyramid=True,
        health=True,
        detect=True,
        detect_operators=DETECT_OPS,
        max_rounds=8,
        on_round=_attach if n_subs else None,
    )
    return 0


def _fleet_worker(src_root: str, out: str, engine: str,
                  n_streams: int) -> int:
    """The fleet drill's subprocess: one FleetEngine over N streams,
    same per-stream config as :func:`_worker` (so each stream's
    single-stream control is the plain worker)."""
    import time as _t

    from tpudas.fleet import FleetEngine, StreamConfig, StreamSpec

    os.makedirs(out, exist_ok=True)
    config = StreamConfig(
        kind="lowpass",
        start_time=T0,
        output_sample_interval=DT_OUT,
        edge_buffer=EDGE_SEC,
        process_patch_size=PATCH_OUT,
        poll_interval=0.0,
        engine=engine,
        pyramid=True,
        health=True,
        detect=True,
        detect_operators=DETECT_OPS,
    )
    specs = [
        StreamSpec(
            stream_id=f"s{i:02d}",
            source=os.path.join(src_root, f"s{i:02d}"),
            config=config,
        )
        for i in range(int(n_streams))
    ]
    with open(out + ".ready", "w") as fh:
        fh.write(str(os.getpid()))
    FleetEngine(
        out, specs, max_rounds=8,
        sleep_fn=lambda _s: _t.sleep(0.01),
    ).run()
    return 0


# ---------------------------------------------------------------------------
# the parent harness

def _feed(src: str, first_index: int, n_files: int) -> None:
    import numpy as np

    from tpudas.testing import make_synthetic_spool

    make_synthetic_spool(
        src, n_files=n_files, file_duration=FILE_SEC, fs=FS, n_ch=N_CH,
        noise=0.01,
        start=np.datetime64(T0)
        + np.timedelta64(int(first_index * FILE_SEC * 1e9), "ns"),
        prefix=f"raw{first_index:04d}",
    )


def _rm_ready(out: str) -> None:
    try:
        os.remove(out + ".ready")
    except OSError:
        pass


def _run_cycle(src, out, engine, kill_after, log_fh=None,
               mesh=0, streams=0, env_extra=None) -> dict:
    """One worker subprocess; ``kill_after`` seconds after READY send
    SIGKILL (None = let it finish).  ``mesh`` > 0 runs the worker
    channel-sharded over that many CPU-virtualized devices
    (``TPUDAS_MESH`` + ``--xla_force_host_platform_device_count``) —
    the driver resolves the env var itself.  ``streams`` > 0 runs the
    FLEET worker (``src`` is then the source root holding one spool
    per stream).  ``env_extra`` overlays the worker environment (the
    async-ingest leg pins ``TPUDAS_INGEST_PREFETCH`` per side).
    Returns {killed, wall}."""
    _rm_ready(out)
    env = dict(os.environ)
    if env_extra:
        env.update(env_extra)
    env["JAX_PLATFORMS"] = "cpu"
    if mesh:
        env["TPUDAS_MESH"] = str(int(mesh))
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={int(mesh)}"
            ).strip()
    else:
        env.pop("TPUDAS_MESH", None)
    # share one persistent XLA cache across worker processes: after
    # the cold calibration cycle every worker warm-starts, so kills
    # land in real processing/write windows instead of jit compiles
    env.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(out), "xla_cache"),
    )
    argv = (
        ["--fleet-worker", src, out, engine, str(int(streams))]
        if streams
        else ["--worker", src, out, engine]
    )
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), *argv],
        env=env,
        stdout=log_fh if log_fh is not None else subprocess.DEVNULL,
        stderr=subprocess.STDOUT if log_fh is not None else (
            subprocess.DEVNULL
        ),
    )
    t0 = time.time()
    ready = out + ".ready"
    while not os.path.isfile(ready):
        if proc.poll() is not None:
            raise RuntimeError(
                f"crash-drill worker exited rc={proc.returncode} "
                "before becoming ready (see --log)"
            )
        if time.time() - t0 > 300:
            proc.kill()
            raise RuntimeError("crash-drill worker never became ready")
        time.sleep(0.01)
    t_ready = time.time()
    killed = False
    if kill_after is None:
        proc.wait(timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"uninterrupted crash-drill worker failed "
                f"rc={proc.returncode}"
            )
    else:
        while proc.poll() is None and time.time() - t_ready < kill_after:
            time.sleep(0.002)
        if proc.poll() is None:
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait()
            killed = True
    return {"killed": killed, "wall": round(time.time() - t_ready, 3)}


def _content_hash(folder: str) -> str:
    """sha256 of the merged output content: the ns time grid plus the
    float32 samples, independent of how emission chunked the files."""
    import numpy as np

    from tpudas.io.spool import spool as make_spool

    h = hashlib.sha256()
    sp = make_spool(folder).sort("time").update()
    for patch in sp.chunk(time=None):
        d = patch.host_data()
        ax = patch.axis_of("time")
        if ax != 0:
            d = np.moveaxis(d, ax, 0)
        times = (
            np.asarray(patch.coords["time"])
            .astype("datetime64[ns]")
            .astype(np.int64)
        )
        h.update(times.tobytes())
        h.update(
            np.ascontiguousarray(np.asarray(d, np.float32)).tobytes()
        )
    return h.hexdigest()


def _pyramid_tree(folder: str) -> dict:
    """{relpath: sha256} of the pyramid files (``.prev`` history and
    tmp leftovers excluded — they are append-schedule dependent)."""
    from tpudas.serve.tiles import TILE_DIRNAME
    from tpudas.utils.atomicio import is_tmp_name

    tiles = os.path.join(folder, TILE_DIRNAME)
    out = {}
    for dirpath, _dirnames, filenames in os.walk(tiles):
        for name in sorted(filenames):
            if ".prev" in name or is_tmp_name(name):
                continue
            path = os.path.join(dirpath, name)
            with open(path, "rb") as fh:
                digest = hashlib.sha256(fh.read()).hexdigest()
            out[os.path.relpath(path, tiles)] = digest
    return out


def _detect_state(folder: str) -> dict:
    """The committed detect state, comparison-ready: the ledger's raw
    bytes (deterministic canonical lines), a digest of every score
    tile/tails file, and a digest of the PARSED carry (meta + array
    bytes — the ``.npz`` container embeds zip timestamps, so raw
    bytes cannot be compared across runs).  ``.prev`` rungs are
    commit-schedule dependent and excluded, like the pyramid's."""
    from tpudas.detect.ledger import DETECT_DIRNAME, ScoreStore
    from tpudas.detect.runner import load_detect_carry
    from tpudas.utils.atomicio import is_tmp_name

    det = os.path.join(folder, DETECT_DIRNAME)
    out: dict = {"present": os.path.isdir(det)}
    if not out["present"]:
        return out
    ledger = os.path.join(det, "events.jsonl")
    if os.path.isfile(ledger):
        with open(ledger, "rb") as fh:
            out["ledger_sha"] = hashlib.sha256(fh.read()).hexdigest()
    carry = load_detect_carry(folder)
    if carry is not None:
        h = hashlib.sha256()
        h.update(
            json.dumps(carry["meta"], sort_keys=True).encode()
        )
        for st in carry["states"]:
            for key in sorted(st):
                import numpy as np

                arr = np.asarray(st[key])
                h.update(key.encode())
                h.update(str(arr.dtype).encode())
                h.update(arr.tobytes())
        out["carry_sha"] = h.hexdigest()
    scores = ScoreStore.scores_dir(folder)
    tree = {}
    if os.path.isdir(scores):
        for name in sorted(os.listdir(scores)):
            if ".prev" in name or is_tmp_name(name):
                continue
            path = os.path.join(scores, name)
            if not os.path.isfile(path):
                continue
            with open(path, "rb") as fh:
                tree[name] = hashlib.sha256(fh.read()).hexdigest()
    out["scores"] = tree
    return out


def _flight_replay_check(folder: str) -> dict:
    """The ISSUE 13 flight-recorder leg: called right after the kill
    cycles (BEFORE the drain), so it asserts what the on-disk ring
    holds at the moment an operator would arrive at a SIGKILLed box —
    the final committed round's record (all phases present) preceded
    by that round's spans.  The recorder flushes a round's spans and
    its ``round`` record in one write, so any surviving round record
    implies its spans survived too; this verifies that end to end."""
    from tpudas.obs.flight import read_flight
    from tpudas.obs.phases import PHASES

    recs = read_flight(folder)
    rounds = [r for r in recs if r.get("kind") == "round"]
    if not rounds:
        return {"ok": False, "rounds": 0,
                "reason": "no committed round records in the ring"}
    last = rounds[-1]
    spans = [
        r for r in recs
        if r.get("kind") == "span" and r.get("round") == last["round"]
    ]
    has_round_span = any(r.get("name") == "stream.round" for r in spans)
    phases_complete = sorted(last.get("phases", {})) == sorted(PHASES)
    return {
        "ok": bool(has_round_span and phases_complete),
        "rounds": len(rounds),
        "last_round": last.get("round"),
        "last_round_spans": len(spans),
        "phases_complete": phases_complete,
        "records_total": len(recs),
    }


def run_drill(
    engine: str = "cascade",
    cycles: int = 25,
    seed: int = 0,
    workdir: str | None = None,
    files_init: int = 2,
    files_per_cycle: int = 1,
    log_path: str | None = None,
    mesh: int = 0,
    async_ingest: bool = False,
    live: bool = False,
    live_subs: int = 32,
) -> dict:
    """One full drill for ``engine``; returns the report dict with
    ``ok`` True when the audit is clean and both comparisons match.

    ``mesh`` > 0 (ISSUE 7) runs every DRILLED cycle channel-sharded
    over that many CPU-virtualized devices while the CONTROL replay
    stays single-device — so one drill proves both that SIGKILL
    cycles on the sharded path end audit-clean AND that the sharded
    path is byte-identical to the unsharded cascade/fft.

    ``async_ingest`` (ISSUE 15) runs every DRILLED cycle with the
    async pipelined ingest on (``TPUDAS_INGEST_PREFETCH=2``) while
    the CONTROL replay runs the synchronous slice loop — SIGKILLs
    land with prefetched-but-uncommitted slices in flight, and the
    byte-identity comparison then proves a prefetched slice is
    crash-equivalent to a never-read one.

    ``live`` (ISSUE 19) runs every DRILLED cycle with the live push
    plane on and ``live_subs`` never-drained in-process subscribers
    (``TPUDAS_LIVE=1`` + ``TPUDAS_CRASH_DRILL_SUBS``) while the
    CONTROL replay runs live-off — the comparison proves fanning out
    to stalled clients and dying mid-publish changes no durable
    byte."""
    import numpy as np

    from tpudas.integrity.audit import audit

    tag = f"crash_drill_{engine}_mesh{mesh}_" if mesh else (
        f"crash_drill_{engine}_"
    )
    if async_ingest:
        tag = tag[:-1] + "_async_"
    if live:
        tag = tag[:-1] + "_live_"
    workdir = workdir or tempfile.mkdtemp(prefix=tag)
    src = os.path.join(workdir, "src")
    out = os.path.join(workdir, "out")
    ctrl = os.path.join(workdir, "ctrl")
    log_fh = open(log_path, "ab") if log_path else None
    drill_env: dict = {}
    ctrl_env: dict = {}
    if async_ingest:
        drill_env["TPUDAS_INGEST_PREFETCH"] = "2"
        ctrl_env["TPUDAS_INGEST_PREFETCH"] = "0"
    if live:
        drill_env["TPUDAS_LIVE"] = "1"
        drill_env["TPUDAS_CRASH_DRILL_SUBS"] = str(int(live_subs))
        ctrl_env["TPUDAS_LIVE"] = "0"
    drill_env = drill_env or None
    ctrl_env = ctrl_env or None
    try:
        # epochs: every feed event, replayed verbatim for the control
        epochs = [(0, files_init)]
        _feed(src, 0, files_init)
        # cold calibration: seeds the carry AND the shared XLA cache
        cold = _run_cycle(src, out, engine, None, log_fh, mesh=mesh,
                          env_extra=drill_env)
        # warm calibration: the est the kill distribution draws from
        epochs.append((files_init, files_per_cycle))
        _feed(src, files_init, files_per_cycle)
        warm = _run_cycle(src, out, engine, None, log_fh, mesh=mesh,
                          env_extra=drill_env)
        est = max(warm["wall"], 0.2)
        rng = np.random.default_rng(seed)
        n_files = files_init + files_per_cycle
        kills = 0
        cycle_log = []
        advance = True  # the last cycle completed its epoch
        for _c in range(int(cycles)):
            if advance:
                epochs.append((n_files, files_per_cycle))
                _feed(src, n_files, files_per_cycle)
                n_files += files_per_cycle
            kill_after = float(rng.uniform(0.02, est * 0.95))
            r = _run_cycle(src, out, engine, kill_after, log_fh,
                           mesh=mesh, env_extra=drill_env)
            kills += int(r["killed"])
            advance = not r["killed"]
            if not r["killed"]:
                # the worker outran the timer: track the real wall so
                # later draws keep landing inside the work window
                est = max(0.5 * est + 0.5 * r["wall"], 0.2)
            cycle_log.append({"kill_after": round(kill_after, 3), **r})
        # flight-recorder replay (ISSUE 13): inspected NOW, after the
        # SIGKILL cycles and before the drain — the on-disk ring must
        # already replay the final committed round's spans + phases
        flight = _flight_replay_check(out)
        # drain: the resumed run finishes everything the kills left
        _run_cycle(src, out, engine, None, log_fh, mesh=mesh,
                   env_extra=drill_env)
        # the drained folder must audit clean (each worker already
        # audited at startup; this run may not find anything new)
        report = audit(out, repair=True)
        # control: replay the SAME epoch schedule, uninterrupted — and
        # ALWAYS single-device, so a mesh drill also pins
        # sharded == unsharded byte-identity end to end
        ctrl_src = os.path.join(workdir, "ctrl_src")
        for first, count in epochs:
            _feed(ctrl_src, first, count)
            _run_cycle(ctrl_src, ctrl, engine, None, log_fh,
                       env_extra=ctrl_env)
        outputs_match = _content_hash(out) == _content_hash(ctrl)
        pyr_out, pyr_ctrl = _pyramid_tree(out), _pyramid_tree(ctrl)
        pyramid_match = pyr_out == pyr_ctrl
        det_out, det_ctrl = _detect_state(out), _detect_state(ctrl)
        detect_match = det_out == det_ctrl
        detect_events = 0
        if det_out.get("ledger_sha"):
            from tpudas.detect.ledger import load_events

            detect_events = len(load_events(out))
        return {
            "engine": engine,
            "mesh": int(mesh),
            "async_ingest": bool(async_ingest),
            "live": bool(live),
            "live_subs": int(live_subs) if live else 0,
            "cycles": int(cycles),
            "seed": int(seed),
            "kills": kills,
            "epochs": len(epochs),
            "cold_wall_s": cold["wall"],
            "warm_wall_s": warm["wall"],
            "audit_clean": bool(report["clean"]),
            "audit_issues": len(report["issues"]),
            "outputs_match": bool(outputs_match),
            "pyramid_match": bool(pyramid_match),
            "pyramid_files": len(pyr_out),
            "detect_match": bool(detect_match),
            "detect_events": int(detect_events),
            "flight": flight,
            "cycle_log": cycle_log,
            "workdir": workdir,
            "ok": bool(
                report["clean"] and outputs_match and pyramid_match
                and detect_match and flight["ok"]
            ),
        }
    finally:
        if log_fh is not None:
            log_fh.close()


def run_fleet_drill(
    engine: str = "cascade",
    streams: int = 4,
    cycles: int = 12,
    seed: int = 0,
    workdir: str | None = None,
    files_init: int = 2,
    files_per_cycle: int = 1,
    log_path: str | None = None,
    batched: bool = False,
) -> dict:
    """The fleet drill (ISSUE 8): SIGKILL a ``streams``-wide
    :class:`tpudas.fleet.FleetEngine` mid-interleave for ``cycles``
    seeded cycles, then prove ``audit_fleet`` is clean and EVERY
    stream's post-crash state is byte-identical to a single-stream
    control replay of the same epoch schedule.

    Every stream is fed the SAME synthetic files each epoch (separate
    source spools, identical bytes), so ONE single-stream control
    covers all N comparisons; epoch gating holds the feed until a
    cycle runs uninterrupted, exactly as :func:`run_drill` does (and
    for the same chunk-schedule reason).

    ``batched`` overlays ``TPUDAS_FLEET_BATCHED=1`` on every DRILLED
    cycle (the ragged-batched scheduler, ISSUE 16); the single-stream
    control replay never batches, so the comparison pins the batched
    path's crash-surviving bytes to the solo path's."""
    import numpy as np

    from tpudas.integrity.audit import audit_fleet

    drill_env = {"TPUDAS_FLEET_BATCHED": "1"} if batched else None
    streams = int(streams)
    workdir = workdir or tempfile.mkdtemp(
        prefix=f"crash_drill_fleet{streams}_{engine}_"
    )
    src_root = os.path.join(workdir, "src")
    out = os.path.join(workdir, "out")
    ctrl = os.path.join(workdir, "ctrl")
    log_fh = open(log_path, "ab") if log_path else None
    sids = [f"s{i:02d}" for i in range(streams)]

    def feed_all(first, count):
        for sid in sids:
            _feed(os.path.join(src_root, sid), first, count)

    try:
        epochs = [(0, files_init)]
        feed_all(0, files_init)
        cold = _run_cycle(src_root, out, engine, None, log_fh,
                          streams=streams, env_extra=drill_env)
        epochs.append((files_init, files_per_cycle))
        feed_all(files_init, files_per_cycle)
        warm = _run_cycle(src_root, out, engine, None, log_fh,
                          streams=streams, env_extra=drill_env)
        est = max(warm["wall"], 0.2)
        rng = np.random.default_rng(seed)
        n_files = files_init + files_per_cycle
        kills = 0
        cycle_log = []
        advance = True
        for _c in range(int(cycles)):
            if advance:
                epochs.append((n_files, files_per_cycle))
                feed_all(n_files, files_per_cycle)
                n_files += files_per_cycle
            kill_after = float(rng.uniform(0.02, est * 0.95))
            r = _run_cycle(src_root, out, engine, kill_after, log_fh,
                           streams=streams, env_extra=drill_env)
            kills += int(r["killed"])
            advance = not r["killed"]
            if not r["killed"]:
                est = max(0.5 * est + 0.5 * r["wall"], 0.2)
            cycle_log.append({"kill_after": round(kill_after, 3), **r})
        # drain, then the whole fleet root must audit clean
        _run_cycle(src_root, out, engine, None, log_fh, streams=streams,
                   env_extra=drill_env)
        report = audit_fleet(out, repair=True)
        # ONE single-stream control (identical feeds): the plain
        # worker over the same epoch schedule
        ctrl_src = os.path.join(workdir, "ctrl_src")
        for first, count in epochs:
            _feed(ctrl_src, first, count)
            _run_cycle(ctrl_src, ctrl, engine, None, log_fh)
        ctrl_hash = _content_hash(ctrl)
        ctrl_pyr = _pyramid_tree(ctrl)
        ctrl_det = _detect_state(ctrl)
        detect_events = 0
        if ctrl_det.get("ledger_sha"):
            from tpudas.detect.ledger import load_events

            detect_events = len(load_events(ctrl))
        per_stream = {}
        all_match = True
        for sid in sids:
            sdir = os.path.join(out, sid)
            entry = {
                "outputs_match": _content_hash(sdir) == ctrl_hash,
                "pyramid_match": _pyramid_tree(sdir) == ctrl_pyr,
                "detect_match": _detect_state(sdir) == ctrl_det,
            }
            entry["ok"] = all(entry.values())
            all_match = all_match and entry["ok"]
            per_stream[sid] = entry
        return {
            "engine": engine,
            "streams": streams,
            "batched": bool(batched),
            "cycles": int(cycles),
            "seed": int(seed),
            "kills": kills,
            "epochs": len(epochs),
            "cold_wall_s": cold["wall"],
            "warm_wall_s": warm["wall"],
            "audit_clean": bool(report["clean"]),
            "audit_issues": report["issues_total"],
            "streams_match": per_stream,
            "detect_events": int(detect_events),
            "cycle_log": cycle_log,
            "workdir": workdir,
            "ok": bool(report["clean"] and all_match),
        }
    finally:
        if log_fh is not None:
            log_fh.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cycles", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--engines", default="cascade,fft",
        help="comma-separated engine list",
    )
    ap.add_argument("--out", default=None, help="write JSON report here")
    ap.add_argument("--log", default=None, help="worker stdout log file")
    ap.add_argument(
        "--mesh", type=int, default=0,
        help="channel-shard the DRILLED cycles over N CPU-virtualized "
        "devices (the control replay stays single-device)",
    )
    ap.add_argument(
        "--streams", type=int, default=0,
        help="drill a FLEET of N streams in one process per cycle "
        "(each stream compared to a single-stream control replay); "
        "mutually exclusive with --mesh",
    )
    ap.add_argument(
        "--codec", default=None,
        help="tile-codec spec (e.g. bitshuffle-deflate or "
        "quantize-deflate:max_error=1e-3): sets TPUDAS_CODEC for "
        "BOTH the drilled workers and the control replay, so the "
        "pyramid byte-identity claim covers the compressed store "
        "(ISSUE 11)",
    )
    ap.add_argument(
        "--batched", action="store_true",
        help="run the DRILLED fleet cycles under the ragged-batched "
        "scheduler (TPUDAS_FLEET_BATCHED=1) while the single-stream "
        "control replay stays solo — SIGKILLs land mid-stacked-launch "
        "(ISSUE 16); requires --streams",
    )
    ap.add_argument(
        "--workdir", default=None,
        help="drill scratch directory (default: a fresh mkdtemp)",
    )
    ap.add_argument(
        "--async-ingest", action="store_true",
        help="run the DRILLED cycles with async pipelined ingest "
        "(TPUDAS_INGEST_PREFETCH=2) while the control replay stays "
        "synchronous — SIGKILLs land with prefetched-but-uncommitted "
        "slices in flight, proving prefetched == never-read "
        "(ISSUE 15); not supported with --streams",
    )
    ap.add_argument(
        "--live", action="store_true",
        help="run the DRILLED cycles with the live push plane on "
        "(TPUDAS_LIVE=1) and --live-subs never-drained subscribers "
        "attached, while the control replay runs live-off — SIGKILLs "
        "land mid-fanout with the degrade ladder shedding, proving "
        "the memory-only push plane changes no durable byte "
        "(ISSUE 19); not supported with --streams",
    )
    ap.add_argument(
        "--live-subs", type=int, default=32,
        help="in-process subscribers per drilled cycle for --live",
    )
    args = ap.parse_args(argv)
    if args.streams and args.live:
        ap.error("--live drills the single-stream worker; combine "
                 "with --mesh or plain engines")
    if args.streams and args.async_ingest:
        ap.error("--async-ingest drills the single-stream worker; "
                 "combine with --mesh or plain engines")
    if args.streams and args.mesh:
        ap.error("--streams and --mesh are mutually exclusive")
    if args.batched and not args.streams:
        ap.error("--batched drills the fleet scheduler; requires "
                 "--streams")
    if args.codec:
        # workers inherit os.environ (_run_cycle copies it), so one
        # assignment covers every drilled cycle AND the control
        os.environ["TPUDAS_CODEC"] = args.codec
    results = {}
    ok = True
    for engine in [e for e in args.engines.split(",") if e]:
        # a shared --workdir gets one subdirectory per engine leg
        wd = (
            os.path.join(args.workdir, engine) if args.workdir else None
        )
        if args.streams:
            print(
                f"crash_drill: engine={engine} cycles={args.cycles} "
                f"seed={args.seed} streams={args.streams} "
                f"batched={int(args.batched)}"
            )
            rep = run_fleet_drill(
                engine=engine, streams=args.streams,
                cycles=args.cycles, seed=args.seed, log_path=args.log,
                workdir=wd, batched=args.batched,
            )
            results[engine] = rep
            ok = ok and rep["ok"]
            matched = sum(
                 1 for s in rep["streams_match"].values() if s["ok"]
            )
            print(
                f"crash_drill: {engine}: kills={rep['kills']} "
                f"audit_clean={rep['audit_clean']} "
                f"streams_match={matched}/{rep['streams']} "
                f"(events={rep['detect_events']})"
            )
            continue
        print(f"crash_drill: engine={engine} cycles={args.cycles} "
              f"seed={args.seed} mesh={args.mesh} "
              f"async_ingest={args.async_ingest} live={args.live}")
        rep = run_drill(
            engine=engine, cycles=args.cycles, seed=args.seed,
            log_path=args.log, mesh=args.mesh,
            async_ingest=args.async_ingest, workdir=wd,
            live=args.live, live_subs=args.live_subs,
        )
        results[engine] = rep
        ok = ok and rep["ok"]
        print(
            f"crash_drill: {engine}: kills={rep['kills']} "
            f"audit_clean={rep['audit_clean']} "
            f"outputs_match={rep['outputs_match']} "
            f"pyramid_match={rep['pyramid_match']} "
            f"detect_match={rep['detect_match']} "
            f"flight_replay={rep['flight']['ok']} "
            f"(events={rep['detect_events']}, "
            f"flight_rounds={rep['flight']['rounds']})"
        )
    payload = {"cycles": args.cycles, "seed": args.seed,
               "mesh": args.mesh, "streams": args.streams,
               "batched": args.batched, "codec": args.codec,
               "async_ingest": args.async_ingest, "live": args.live,
               "ok": ok, "engines": results}
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(payload, fh, indent=1)
    print(f"crash_drill: {'OK' if ok else 'FAILED'}")
    return 0 if ok else 1


if __name__ == "__main__":
    if len(sys.argv) >= 5 and sys.argv[1] == "--worker":
        sys.exit(_worker(sys.argv[2], sys.argv[3], sys.argv[4]))
    if len(sys.argv) >= 6 and sys.argv[1] == "--fleet-worker":
        sys.exit(
            _fleet_worker(
                sys.argv[2], sys.argv[3], sys.argv[4], int(sys.argv[5])
            )
        )
    sys.exit(main())
