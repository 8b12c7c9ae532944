"""Multi-worker backfill chaos drill: SIGKILL + injected claim/commit
faults against one shared queue, then prove exactly-once came true.

The cluster-scale sibling of ``tools/crash_drill.py`` (ISSUE 12): one
synthetic archive, one :mod:`tpudas.backfill` queue, N worker
subprocesses draining it concurrently.  The parent:

1. plans the queue over a seeded synthetic archive;
2. runs a 1-worker **uninterrupted control** (separate root, same
   plan) and a plain **sequential reference** (the realtime driver
   with pyramid + detect over the same archive);
3. keeps N chaos workers alive against the drill root, SIGKILLing a
   seeded-random live worker ``kills`` times (kill timers start at
   worker READY, so kills land in claim/drain/commit windows, not in
   ``import jax``), and handing every third/fourth spawn an injected
   fault plan that raises at ``backfill.claim`` / ``backfill.commit``
   — a worker dying at the two nastiest protocol points;
4. respawns replacements until every shard is committed and the
   stitch lands (stale leases from killed workers must be reclaimed
   by the survivors — that IS the mechanism under test);
5. asserts ``audit_backfill`` is **clean** and the drill's stitched
   result is **byte-identical** to both the 1-worker control (merged
   output content, pyramid tree file-by-file, events-ledger bytes,
   score tiles, parsed detect carry) and the sequential reference;
6. reports the lease/claim/renew/commit overhead fraction from the
   done markers (the <2%-of-shard-wall acceptance budget).

CLI (the acceptance drill — BENCH_pr12.json records a run)::

    JAX_PLATFORMS=cpu python tools/backfill_drill.py \
        [--workers 4] [--kills 6] [--shards 8] [--seed 0] [--out PATH]

``--store`` (ISSUE 18) runs the drill on the OBJECT-STORE queue
(:mod:`tpudas.backfill.objqueue`) instead: worker subprocesses share
NOTHING but a ``file://`` object store (each drains into a private
scratch directory), SIGKILLs land the same way, every worker's store
plane additionally rides a scripted network-fault storm
(``store.op`` raises absorbed by the retry layer), and a second
in-process leg replays the job on the fault-injected FAKE backend
(5xx storms, lost responses, torn uploads, latency spikes) asserting
its stitched result byte-identical to an unfaulted POSIX-store
control.  ``audit_backfill_store`` must come back clean and the
materialized result byte-identical to the sequential realtime run.

``--store --replicas N`` (ISSUE 20) runs the chaos leg against a
``replica:`` store — one ``file://`` primary + N posix mirrors.  One
mirror is SEVERED (its root replaced by a plain file, so every write
from every worker subprocess fails into the hinted-handoff journal)
for the whole SIGKILL window, healed after the queue resolves, and
converged by drain + anti-entropy scrub; the drill then asserts every
replica tree is byte-identical to the primary and the result
byte-identical to the single-store control.  The in-process
:func:`run_replica_drill` is the same story on fault-injected fakes
(a ``partition`` rule severs one mirror) — it additionally proves the
drain is zero-re-upload (a second drain moves nothing, the scrub
repairs nothing).

``tests/test_integrity.py`` runs a 2-worker/2-kill smoke in tier-1;
``tests/test_store_replica.py`` runs the replica smoke.
"""

from __future__ import annotations

import argparse
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
SHARD_SEC = 60.0
LEASE_TTL = 15.0
DETECT_OPS = (
    ("stalta", {"sta": 2.0, "lta": 10.0, "on": 2.0, "off": 1.2}),
    ("rms", {"window": 5.0, "step": 2.0, "thresh": 1.5,
             "baseline": 20.0}),
)


# ---------------------------------------------------------------------------
# the worker subprocess

def _worker_main(root: str, worker_id: str, fault: str,
                 settle: float = 0.02) -> int:
    """One chaos worker: optionally install an injected fault plan
    (``site:at[xN]`` — an uncaught raise at a claim/commit protocol
    point, i.e. a worker dying there), mark READY, drain the queue."""
    from tpudas.backfill import run_worker
    from tpudas.resilience.faults import (
        FaultPlan,
        FaultSpec,
        install_fault_plan,
    )

    ready_dir = os.path.join(root, ".workers")
    os.makedirs(ready_dir, exist_ok=True)
    if fault:
        site, _, rest = fault.partition(":")
        at, _, times = rest.partition("x")
        install_fault_plan(
            FaultPlan(
                FaultSpec(
                    site, "raise", at=int(at or 1),
                    times=int(times or 1),
                )
            )
        )
    with open(os.path.join(ready_dir, worker_id + ".ready"), "w") as fh:
        fh.write(str(os.getpid()))
    run_worker(
        root, worker=worker_id, stitch=True,
        lease_ttl=LEASE_TTL, settle=float(settle), idle_poll=0.1,
    )
    return 0


def _spawn(root, worker_id, fault="", log_fh=None, settle=0.02):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(root), "xla_cache"),
    )
    proc = subprocess.Popen(
        [
            sys.executable, os.path.abspath(__file__),
            "--worker", root, worker_id, fault, str(settle),
        ],
        env=env,
        stdout=log_fh if log_fh is not None else subprocess.DEVNULL,
        stderr=subprocess.STDOUT if log_fh is not None else (
            subprocess.DEVNULL
        ),
    )
    return proc


def _ready(root, worker_id) -> bool:
    return os.path.isfile(
        os.path.join(root, ".workers", worker_id + ".ready")
    )


# ---------------------------------------------------------------------------
# the parent harness

def _build_archive(src: str, n_files: int) -> None:
    import numpy as np

    from tpudas.testing import make_synthetic_spool

    make_synthetic_spool(
        src, n_files=n_files, file_duration=FILE_SEC, fs=FS,
        n_ch=N_CH, noise=0.01, start=np.datetime64(T0),
    )


def _plan(root: str, src: str, n_files: int) -> dict:
    import numpy as np

    from tpudas.backfill import plan_backfill

    t_end = np.datetime64(T0) + np.timedelta64(
        int(n_files * FILE_SEC * 1e9), "ns"
    )
    return plan_backfill(
        root, src, T0, t_end, shard_seconds=SHARD_SEC,
        output_sample_interval=DT_OUT, edge_buffer=EDGE_SEC,
        process_patch_size=PATCH_OUT, pyramid=True, detect=True,
        detect_operators=DETECT_OPS, ingest_limit_sec=40.0,
    )


def _overhead_fraction(root: str) -> tuple:
    """(overhead_s, shard_wall_s) summed over the done markers."""
    from tpudas.backfill.queue import DONE_DIRNAME
    from tpudas.integrity.checksum import read_json_verified

    done_dir = os.path.join(root, DONE_DIRNAME)
    over = wall = 0.0
    for name in sorted(os.listdir(done_dir)):
        if not name.endswith(".json"):
            continue
        try:
            payload, _ = read_json_verified(
                os.path.join(done_dir, name), "backfill_done"
            )
        except (OSError, ValueError):
            continue
        over += float(payload.get("overhead_s", 0.0))
        wall += float(payload.get("wall_s", 0.0))
    return over, wall


def run_backfill_drill(
    workers: int = 4,
    kills: int = 6,
    shards: int = 8,
    seed: int = 0,
    workdir: str | None = None,
    log_path: str | None = None,
    max_wall: float = 1200.0,
) -> dict:
    """One full chaos drill; returns the report dict with ``ok`` True
    when the audit is clean and every byte-identity comparison holds."""
    import numpy as np

    from tools.crash_drill import (
        _content_hash,
        _detect_state,
        _pyramid_tree,
    )
    from tpudas.backfill import BackfillQueue, run_worker
    from tpudas.integrity.audit import audit_backfill

    workers = int(workers)
    n_files = int(round(shards * SHARD_SEC / FILE_SEC))
    workdir = workdir or tempfile.mkdtemp(
        prefix=f"backfill_drill_w{workers}_"
    )
    src = os.path.join(workdir, "src")
    root = os.path.join(workdir, "queue")
    ctrl_root = os.path.join(workdir, "ctrl")
    seq = os.path.join(workdir, "seq")
    log_fh = open(log_path, "ab") if log_path else None
    try:
        _build_archive(src, n_files)
        _plan(root, src, n_files)
        _plan(ctrl_root, src, n_files)
        # the 1-worker uninterrupted control (in-process, no faults)
        t0 = time.time()
        run_worker(
            ctrl_root, worker="ctrl", settle=0.0,
            lease_ttl=LEASE_TTL, max_wall=max_wall,
        )
        ctrl_wall = time.time() - t0
        # the sequential reference: the realtime driver, pyramid +
        # detect on — the stitched result must match a LIVE run too
        from tpudas.proc.streaming import run_lowpass_realtime

        run_lowpass_realtime(
            source=src, output_folder=seq, start_time=T0,
            output_sample_interval=DT_OUT, edge_buffer=EDGE_SEC,
            process_patch_size=PATCH_OUT, poll_interval=0.0,
            sleep_fn=lambda _s: None, pyramid=True, detect=True,
            detect_operators=DETECT_OPS,
        )
        # chaos: keep `workers` live against the queue, kill on a
        # seeded schedule, hand every 3rd spawn a claim fault and
        # every 4th a commit fault (an uncaught raise = a worker
        # dying at the protocol's nastiest points)
        rng = np.random.default_rng(seed)
        est = max(ctrl_wall / max(shards, 1), 0.4)
        queue = BackfillQueue(root, worker="parent", settle=0.0)
        procs: dict = {}
        spawn_i = 0
        kills_done = 0
        faults_injected = []
        deadline = time.time() + max_wall

        def spawn_one():
            nonlocal spawn_i
            wid = f"w{spawn_i:03d}"
            fault = ""
            if spawn_i % 3 == 1:
                fault = f"backfill.claim:{int(rng.integers(1, 4))}"
            elif spawn_i % 4 == 2:
                fault = f"backfill.commit:{int(rng.integers(1, 3))}"
            if fault:
                faults_injected.append(f"{wid}={fault}")
            procs[wid] = _spawn(root, wid, fault, log_fh)
            spawn_i += 1

        for _ in range(workers):
            spawn_one()
        while True:
            if time.time() > deadline:
                raise TimeoutError(
                    f"backfill drill exceeded {max_wall}s; queue "
                    f"counts {queue.counts()}"
                )
            for wid in list(procs):
                if procs[wid].poll() is not None:
                    del procs[wid]
            resolved = queue.resolved()
            stitched = os.path.isfile(
                os.path.join(root, "result.done.json")
            )
            if resolved and stitched and not procs:
                break
            if resolved and stitched:
                time.sleep(0.1)
                continue
            if kills_done < kills and procs:
                live_ready = [w for w in sorted(procs) if _ready(root, w)]
                if live_ready:
                    victim = live_ready[
                        int(rng.integers(0, len(live_ready)))
                    ]
                    time.sleep(float(rng.uniform(0.05, est)))
                    if procs[victim].poll() is None:
                        os.kill(procs[victim].pid, signal.SIGKILL)
                        procs[victim].wait()
                        kills_done += 1
                    del procs[victim]
            # keep the pool at strength until the queue resolves AND
            # the stitch lands — a kill landing on the last live
            # worker mid-stitch must still get a successor (which
            # adopts or re-stitches)
            while len(procs) < workers and not (resolved and stitched):
                spawn_one()
            time.sleep(0.05)
        # a final clean pass picks up anything the last kill dropped
        # (also exercises the "nothing to do" worker path)
        final = run_worker(
            root, worker="final", settle=0.0, lease_ttl=LEASE_TTL,
            max_wall=max_wall,
        )
        report = audit_backfill(root, repair=True)
        res = os.path.join(root, "result")
        ctrl_res = os.path.join(ctrl_root, "result")
        over_s, wall_s = _overhead_fraction(root)
        comp = {
            "outputs_match_control": (
                _content_hash(res) == _content_hash(ctrl_res)
            ),
            "pyramid_match_control": (
                _pyramid_tree(res) == _pyramid_tree(ctrl_res)
            ),
            "detect_match_control": (
                _detect_state(res) == _detect_state(ctrl_res)
            ),
            "outputs_match_sequential": (
                _content_hash(res) == _content_hash(seq)
            ),
            "pyramid_match_sequential": (
                _pyramid_tree(res) == _pyramid_tree(seq)
            ),
            "detect_match_sequential": (
                _detect_state(res) == _detect_state(seq)
            ),
        }
        ok = bool(
            report["clean"]
            and not report["parked"]
            and all(comp.values())
            and kills_done >= min(kills, 1)
        )
        return {
            "workers": workers,
            "kills": kills_done,
            "kills_requested": int(kills),
            "shards": int(shards),
            "seed": int(seed),
            "spawns": spawn_i,
            "faults_injected": faults_injected,
            "audit_clean": bool(report["clean"]),
            "audit_issues": report["issues_total"],
            "parked": report["parked"],
            **comp,
            "final_worker": {
                k: final[k]
                for k in ("committed", "adopted", "lost", "parked")
            },
            "overhead_s": round(over_s, 4),
            "shard_wall_s": round(wall_s, 4),
            "overhead_fraction": (
                round(over_s / wall_s, 5) if wall_s else None
            ),
            "ctrl_wall_s": round(ctrl_wall, 3),
            "workdir": workdir,
            "ok": ok,
        }
    finally:
        if log_fh is not None:
            log_fh.close()


# ---------------------------------------------------------------------------
# the object-store drill (ISSUE 18): same chaos, no shared filesystem

def _store_worker_main(url: str, prefix: str, scratch: str,
                       ready_dir: str, worker_id: str,
                       fault: str) -> int:
    """One object-store chaos worker: private scratch, store built
    from the URL.  ``fault`` is either a protocol-point death
    (``backfill.claim:2`` — an uncaught raise, i.e. the worker dying
    there) or a network storm (``store:AT xN`` — StoreNetworkError at
    the ``store.op`` site, absorbed by the retry layer)."""
    from tpudas.backfill.objqueue import run_store_worker
    from tpudas.resilience.faults import (
        FaultPlan,
        FaultSpec,
        install_fault_plan,
    )
    from tpudas.store import StoreNetworkError, store_from_url

    os.makedirs(ready_dir, exist_ok=True)
    if fault:
        site, _, rest = fault.partition(":")
        at, _, times = rest.partition("x")
        spec_kwargs = {}
        if site == "store":
            site = "store.op"
            spec_kwargs["exc"] = StoreNetworkError
        install_fault_plan(
            FaultPlan(
                FaultSpec(
                    site, "raise", at=int(at or 1),
                    times=int(times or 1), **spec_kwargs,
                )
            )
        )
    with open(os.path.join(ready_dir, worker_id + ".ready"), "w") as fh:
        fh.write(str(os.getpid()))
    run_store_worker(
        store_from_url(url), prefix, scratch=scratch,
        worker=worker_id, stitch=True, lease_ttl=LEASE_TTL,
        idle_poll=0.1,
    )
    return 0


def _spawn_store(url, prefix, scratch_root, ready_dir, worker_id,
                 fault="", log_fh=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(scratch_root), "xla_cache"),
    )
    return subprocess.Popen(
        [
            sys.executable, os.path.abspath(__file__),
            "--store-worker", url, prefix,
            os.path.join(scratch_root, worker_id), ready_dir,
            worker_id, fault,
        ],
        env=env,
        stdout=log_fh if log_fh is not None else subprocess.DEVNULL,
        stderr=subprocess.STDOUT if log_fh is not None else (
            subprocess.DEVNULL
        ),
    )


def _plan_store(store, prefix: str, src: str, n_files: int) -> dict:
    import numpy as np

    from tpudas.backfill.objqueue import plan_backfill_store

    t_end = np.datetime64(T0) + np.timedelta64(
        int(n_files * FILE_SEC * 1e9), "ns"
    )
    return plan_backfill_store(
        store, prefix, src, T0, t_end, shard_seconds=SHARD_SEC,
        output_sample_interval=DT_OUT, edge_buffer=EDGE_SEC,
        process_patch_size=PATCH_OUT, pyramid=True, detect=True,
        detect_operators=DETECT_OPS, ingest_limit_sec=40.0,
    )


def _materialize_result(store, prefix: str, dest: str) -> int:
    """Token-verified download of the stitched result objects."""
    from tpudas.backfill.objqueue import (
        RESULT_MANIFEST_KEY,
        RESULT_PREFIX,
        StoreBackfillQueue,
    )

    queue = StoreBackfillQueue(store, prefix, worker="drill-reader")
    manifest = queue._get_verified(queue._key(RESULT_MANIFEST_KEY))[0]
    if manifest is None:
        raise RuntimeError(f"no verifying result manifest under {prefix}")
    base = queue._key(RESULT_PREFIX)
    os.makedirs(dest, exist_ok=True)
    n = 0
    for rel, tok in manifest["objects"].items():
        data, got = store.get(f"{base}/{rel}")
        if got != tok:
            raise RuntimeError(
                f"result object {rel!r} token {got!r} != manifest {tok!r}"
            )
        path = os.path.join(dest, *rel.split("/"))
        os.makedirs(os.path.dirname(path) or dest, exist_ok=True)
        with open(path, "wb") as fh:
            fh.write(data)
        n += 1
    return n


def _store_overhead(store, prefix: str) -> tuple:
    """(overhead_s, shard_wall_s) summed over the done markers."""
    from tpudas.backfill.objqueue import (
        DONE_PREFIX,
        StoreBackfillQueue,
    )

    queue = StoreBackfillQueue(store, prefix, worker="drill-reader")
    over = wall = 0.0
    for key in store.list(queue._key(DONE_PREFIX)):
        payload = queue._get_verified(key)[0]
        if payload is None:
            continue
        over += float(payload.get("overhead_s", 0.0))
        wall += float(payload.get("wall_s", 0.0))
    return over, wall


def _run_store_control(bucket: str, src: str, n_files: int,
                       scratch: str, max_wall: float) -> str:
    """The uninterrupted POSIX-store control: plan + 1 worker over a
    ``file://`` store, result materialized locally.  Returns the
    materialized result directory."""
    from tpudas.backfill.objqueue import run_store_worker
    from tpudas.store import store_from_url

    store = store_from_url(f"file://{bucket}")
    _plan_store(store, "job", src, n_files)
    run_store_worker(
        store, "job", scratch=scratch, worker="ctrl",
        lease_ttl=LEASE_TTL, max_wall=max_wall, idle_poll=0.05,
    )
    dest = bucket + ".result"
    _materialize_result(store, "job", dest)
    return dest


def run_store_fault_matrix(src: str, n_files: int, workdir: str,
                           ctrl_res: str, max_wall: float) -> dict:
    """The fake-backend fault matrix: two in-process workers drain
    the job through a retry-wrapped fake store under scripted 5xx
    storms, lost responses (CAS included), torn uploads, and latency
    spikes — then the stitched result must be byte-identical to the
    unfaulted POSIX-store control and the audit clean."""
    import threading

    from tools.crash_drill import _content_hash, _pyramid_tree
    from tpudas.backfill.objqueue import run_store_worker
    from tpudas.integrity.audit import audit_backfill_store
    from tpudas.store import (
        FakeObjectStore,
        FaultInjector,
        FaultRule,
        RetryingStore,
    )

    raw = FakeObjectStore(FaultInjector(
        # three 5xx storms scattered over the run, any op
        FaultRule(kind="unavailable", at=5, times=3),
        FaultRule(kind="unavailable", at=60, times=3),
        FaultRule(kind="unavailable", at=200, times=2),
        # lost responses on mutations, the CAS path included
        FaultRule(kind="lost", op="cas", at=2, times=1),
        FaultRule(kind="lost", op="cas", at=9, times=1),
        FaultRule(kind="lost", op="put", at=20, times=1),
        # torn uploads of shard objects (retries re-put clean)
        FaultRule(kind="torn", op="put", match="shards/", at=4,
                  times=1),
        FaultRule(kind="torn", op="put", match="shards/", at=30,
                  times=1),
        # latency spikes on reads
        FaultRule(kind="latency", op="get", at=3, times=4,
                  seconds=0.02),
    ))
    store = RetryingStore(raw, sleep_fn=lambda _s: None)
    _plan_store(store, "job", src, n_files)

    tallies = {}

    def _drain(name):
        tallies[name] = run_store_worker(
            store, "job",
            scratch=os.path.join(workdir, f"fake-scratch-{name}"),
            worker=name, max_wall=max_wall, idle_poll=0.02,
            sleep_fn=lambda _s: None, lease_ttl=LEASE_TTL,
        )

    threads = [
        threading.Thread(target=_drain, args=(f"fw{i}",))
        for i in (1, 2)
    ]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.time() - t0

    report = audit_backfill_store(store, "job", repair=True)
    res = os.path.join(workdir, "fake-result")
    _materialize_result(store, "job", res)
    fired = {}
    for kind, _op, _key, _hit in raw.injector.fired:
        fired[kind] = fired.get(kind, 0) + 1
    return {
        "faults_fired": fired,
        "audit_clean": bool(report["clean"]),
        "audit_issues": report["issues_total"],
        "committed": sum(
            t["committed"] + t["adopted"] for t in tallies.values()
        ),
        "outputs_match_posix_control": (
            _content_hash(res) == _content_hash(ctrl_res)
        ),
        "pyramid_match_posix_control": (
            _pyramid_tree(res) == _pyramid_tree(ctrl_res)
        ),
        "wall_s": round(wall, 3),
    }


def _store_tree(store, prefix: str = "") -> dict:
    """{key: sha256(bytes)} of every committed object — the replica
    byte-identity comparison (tokens are crc-len; the drill compares
    actual content digests)."""
    import hashlib

    return {
        key: hashlib.sha256(store.get(key)[0]).hexdigest()
        for key in store.list(prefix)
    }


def run_replica_drill(
    shards: int = 2,
    workers: int = 2,
    workdir: str | None = None,
    max_wall: float = 600.0,
) -> dict:
    """The in-process replication drill (tier-1 smoke): drain a small
    job through a ``ReplicatedStore`` over three fakes with ONE MIRROR
    SEVERED (an injector ``partition`` rule) for the whole run, then
    heal → drain the hinted-handoff journal → anti-entropy scrub, and
    assert:

    - every deferred mirror write landed in the journal and drained
      (``handoff_fully_drained``);
    - the drain was zero-re-upload: a second drain moves nothing and
      the scrub repairs nothing (``drain_idempotent``);
    - all three replica trees are byte-identical to each other AND to
      an unfaulted single-store control (``replicas_identical``,
      ``outputs_match_control``);
    - exactly ``shards`` commits happened across the workers — CAS
      pinned to the primary lost/doubled nothing
      (``commits_exact``)."""
    import threading

    from tpudas.backfill.objqueue import run_store_worker
    from tpudas.integrity.audit import audit_backfill_store
    from tpudas.store import (
        FakeObjectStore,
        RetryingStore,
    )
    from tpudas.store.replica import ReplicatedStore

    n_files = int(round(shards * SHARD_SEC / FILE_SEC))
    workdir = workdir or tempfile.mkdtemp(prefix="replica_drill_")
    src = os.path.join(workdir, "src")
    _build_archive(src, n_files)

    # unfaulted single-store control
    ctrl = RetryingStore(FakeObjectStore(), sleep_fn=lambda _s: None)
    _plan_store(ctrl, "job", src, n_files)
    run_store_worker(
        ctrl, "job", scratch=os.path.join(workdir, "ctrl-scratch"),
        worker="ctrl", max_wall=max_wall, idle_poll=0.02,
        sleep_fn=lambda _s: None, lease_ttl=LEASE_TTL,
    )
    ctrl_res = os.path.join(workdir, "ctrl-result")
    _materialize_result(ctrl, "job", ctrl_res)

    # the replicated store: primary + 2 mirrors, each retry-wrapped
    raws = [FakeObjectStore() for _ in range(3)]
    members = [
        RetryingStore(r, sleep_fn=lambda _s: None) for r in raws
    ]
    repl = ReplicatedStore(
        members[0], members[1:],
        journal_dir=os.path.join(workdir, "journal"),
    )
    _plan_store(repl, "job", src, n_files)
    # sever mirror 0 AFTER the plan fanned out — mid-job, every
    # subsequent write to it must fail into the handoff journal
    rule = raws[1].injector.partition()

    tallies = {}

    def _drain_job(name):
        tallies[name] = run_store_worker(
            repl, "job",
            scratch=os.path.join(workdir, f"scratch-{name}"),
            worker=name, max_wall=max_wall, idle_poll=0.02,
            sleep_fn=lambda _s: None, lease_ttl=LEASE_TTL,
        )

    threads = [
        threading.Thread(target=_drain_job, args=(f"rw{i}",))
        for i in range(int(workers))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    journaled = repl.journal.pending_counts()[0]
    # heal, drain, prove idempotence, scrub
    raws[1].injector.heal(rule)
    drained = repl.drain_handoff()
    drained_again = repl.drain_handoff()
    scrub = repl.scrub("", repair=True)
    audit = audit_backfill_store(repl, "job", repair=True)

    res = os.path.join(workdir, "result")
    _materialize_result(repl, "job", res)
    trees = [_store_tree(m) for m in members]
    commits = sum(
        t["committed"] + t["adopted"] for t in tallies.values()
    )
    checks = {
        "mirror_writes_journaled": journaled > 0,
        "handoff_fully_drained": (
            drained["copied"] + drained["deleted"]
            + drained["already_synced"] + drained["vanished"] > 0
            and drained["failed"] == 0
            and not any(repl.journal.pending_counts().values())
        ),
        "drain_idempotent": (
            all(v == 0 for v in drained_again.values())
            and sum(scrub["repairs"].values()) == 0
        ),
        "scrub_clean": bool(scrub["clean"]),
        "audit_clean": bool(audit["clean"]),
        "replicas_identical": (
            trees[0] == trees[1] == trees[2]
            and repl.verify_identical()
        ),
        "outputs_match_control": (
            _store_tree(repl, "job/result")
            == _store_tree(ctrl, "job/result")
        ),
        "commits_exact": commits == int(shards),
    }
    return {
        "mode": "replica",
        "shards": int(shards),
        "workers": int(workers),
        "journaled": journaled,
        "drained": drained,
        "scrub": {
            k: scrub[k] for k in ("repairs", "clean", "objects")
        },
        "tallies": {
            k: {kk: t[kk] for kk in ("committed", "adopted", "lost")}
            for k, t in tallies.items()
        },
        **checks,
        "workdir": workdir,
        "ok": all(checks.values()),
    }


def _sever_posix(root: str) -> None:
    """Sever a posix mirror: its root becomes a plain FILE, so every
    op from every process fails (makedirs/open raise OSError) instead
    of silently recreating a fresh tree."""
    os.rename(root, root + ".severed")
    with open(root, "w") as fh:
        fh.write("severed by backfill_drill\n")


def _heal_posix(root: str) -> None:
    os.unlink(root)
    os.rename(root + ".severed", root)


def run_store_backfill_drill(
    workers: int = 3,
    kills: int = 4,
    shards: int = 4,
    seed: int = 0,
    workdir: str | None = None,
    log_path: str | None = None,
    max_wall: float = 1200.0,
    replicas: int = 0,
) -> dict:
    """The object-store chaos drill: worker subprocesses sharing only
    a ``file://`` object store, SIGKILLed on a seeded schedule, with
    protocol-point deaths and per-worker network storms injected —
    then the audit must be clean and the result byte-identical to the
    POSIX-store control AND the sequential realtime run; the fake
    fault-matrix leg rides on the same archive.

    ``replicas=N`` (N >= 1) runs the same chaos against a
    ``replica:`` store with N posix mirrors; mirror 1 is severed (root
    replaced by a file) for the whole SIGKILL window and healed after
    the queue resolves — the audit's scrub must then converge every
    replica tree byte-identical to the primary."""
    import numpy as np

    from tools.crash_drill import (
        _content_hash,
        _detect_state,
        _pyramid_tree,
    )
    from tpudas.backfill.objqueue import StoreBackfillQueue
    from tpudas.integrity.audit import audit_backfill_store
    from tpudas.store import store_from_url

    workers = int(workers)
    replicas = int(replicas)
    n_files = int(round(shards * SHARD_SEC / FILE_SEC))
    workdir = workdir or tempfile.mkdtemp(
        prefix=f"store_drill_w{workers}_"
    )
    src = os.path.join(workdir, "src")
    bucket = os.path.join(workdir, "bucket")
    ctrl_bucket = os.path.join(workdir, "bucket_ctrl")
    scratch_root = os.path.join(workdir, "scratch")
    ready_dir = os.path.join(workdir, ".workers")
    seq = os.path.join(workdir, "seq")
    url = f"file://{bucket}"
    mirror_roots = []
    env_before = os.environ.get("TPUDAS_REPLICA_JOURNAL")
    if replicas:
        mirror_roots = [
            os.path.join(workdir, f"bucket_m{i + 1}")
            for i in range(replicas)
        ]
        url = "replica:" + ",".join(
            [f"file://{bucket}"]
            + [f"file://{r}" for r in mirror_roots]
        )
        # one shared journal dir: worker subprocesses append their
        # own m<i>-<pid>.jsonl files there, the parent drains them —
        # a killed worker's deferred writes survive its death
        os.environ["TPUDAS_REPLICA_JOURNAL"] = os.path.join(
            workdir, "handoff-journal"
        )
    prefix = "job"
    severed = False
    log_fh = open(log_path, "ab") if log_path else None
    try:
        _build_archive(src, n_files)
        store = store_from_url(url)
        _plan_store(store, prefix, src, n_files)
        t0 = time.time()
        ctrl_res = _run_store_control(
            ctrl_bucket, src, n_files,
            os.path.join(workdir, "ctrl-scratch"), max_wall,
        )
        ctrl_wall = time.time() - t0
        from tpudas.proc.streaming import run_lowpass_realtime

        run_lowpass_realtime(
            source=src, output_folder=seq, start_time=T0,
            output_sample_interval=DT_OUT, edge_buffer=EDGE_SEC,
            process_patch_size=PATCH_OUT, poll_interval=0.0,
            sleep_fn=lambda _s: None, pyramid=True, detect=True,
            detect_operators=DETECT_OPS,
        )
        rng = np.random.default_rng(seed)
        est = max(ctrl_wall / max(shards, 1), 0.4)
        queue = StoreBackfillQueue(store, prefix, worker="parent")
        done_key = queue._key("result.done.json")
        procs: dict = {}
        spawn_i = 0
        kills_done = 0
        faults_injected = []
        deadline = time.time() + max_wall

        def spawn_one():
            nonlocal spawn_i
            wid = f"w{spawn_i:03d}"
            fault = ""
            if spawn_i % 3 == 1:
                fault = f"backfill.claim:{int(rng.integers(1, 4))}"
            elif spawn_i % 4 == 2:
                fault = f"backfill.commit:{int(rng.integers(1, 3))}"
            elif spawn_i % 4 == 3:
                # a network storm this worker's retry layer must absorb
                fault = f"store:{int(rng.integers(3, 40))}x3"
            if fault:
                faults_injected.append(f"{wid}={fault}")
            procs[wid] = _spawn_store(
                url, prefix, scratch_root, ready_dir, wid, fault,
                log_fh,
            )
            spawn_i += 1

        for _ in range(workers):
            spawn_one()
        if replicas:
            # sever mirror 1 for the ENTIRE chaos window: every
            # worker's fan-out writes to it must fail into the
            # shared handoff journal (or, for writes a SIGKILL raced,
            # be found by the scrub's token diff)
            _sever_posix(mirror_roots[0])
            severed = True
        while True:
            if time.time() > deadline:
                raise TimeoutError(
                    f"store drill exceeded {max_wall}s; queue counts "
                    f"{queue.counts()}"
                )
            for wid in list(procs):
                if procs[wid].poll() is not None:
                    del procs[wid]
            resolved = queue.resolved()
            stitched = store.head(done_key) is not None
            if resolved and stitched and not procs:
                break
            if resolved and stitched:
                time.sleep(0.1)
                continue
            if kills_done < kills and procs:
                live_ready = [
                    w for w in sorted(procs)
                    if os.path.isfile(
                        os.path.join(ready_dir, w + ".ready")
                    )
                ]
                if live_ready:
                    victim = live_ready[
                        int(rng.integers(0, len(live_ready)))
                    ]
                    time.sleep(float(rng.uniform(0.05, est)))
                    if procs[victim].poll() is None:
                        os.kill(procs[victim].pid, signal.SIGKILL)
                        procs[victim].wait()
                        kills_done += 1
                    del procs[victim]
            while len(procs) < workers and not (resolved and stitched):
                spawn_one()
            time.sleep(0.05)
        replica_block = None
        if replicas:
            # heal the severed mirror; the audit below runs the
            # anti-entropy scrub (journal drain + token-diff repair)
            _heal_posix(mirror_roots[0])
            severed = False
        report = audit_backfill_store(store, prefix, repair=True)
        if replicas:
            from tpudas.store.replica import find_replicated

            repl = find_replicated(store)
            trees = [
                _store_tree(m)
                for m in (repl.primary, *repl.mirrors)
            ]
            replica_block = {
                "replicas": replicas,
                "scrub": {
                    k: report["replication"][k]
                    for k in ("drained", "repairs", "clean")
                },
                "handoff_pending": repl.journal.pending_counts(),
                "replicas_identical": all(
                    t == trees[0] for t in trees[1:]
                ),
            }
        res = os.path.join(workdir, "result")
        _materialize_result(store, prefix, res)
        over_s, wall_s = _store_overhead(store, prefix)
        comp = {
            "outputs_match_control": (
                _content_hash(res) == _content_hash(ctrl_res)
            ),
            "pyramid_match_control": (
                _pyramid_tree(res) == _pyramid_tree(ctrl_res)
            ),
            "detect_match_control": (
                _detect_state(res) == _detect_state(ctrl_res)
            ),
            "outputs_match_sequential": (
                _content_hash(res) == _content_hash(seq)
            ),
            "pyramid_match_sequential": (
                _pyramid_tree(res) == _pyramid_tree(seq)
            ),
            "detect_match_sequential": (
                _detect_state(res) == _detect_state(seq)
            ),
        }
        matrix = run_store_fault_matrix(
            src, n_files, workdir, ctrl_res, max_wall,
        )
        ok = bool(
            report["clean"]
            and not report["parked"]
            and all(comp.values())
            and kills_done >= min(kills, 1)
            and matrix["audit_clean"]
            and matrix["outputs_match_posix_control"]
            and matrix["pyramid_match_posix_control"]
            and (replica_block is None or (
                replica_block["replicas_identical"]
                and replica_block["scrub"]["clean"]
                and not any(
                    replica_block["handoff_pending"].values()
                )
            ))
        )
        return {
            "mode": "store" if not replicas else "store-replica",
            "replication": replica_block,
            "workers": workers,
            "kills": kills_done,
            "kills_requested": int(kills),
            "shards": int(shards),
            "seed": int(seed),
            "spawns": spawn_i,
            "faults_injected": faults_injected,
            "audit_clean": bool(report["clean"]),
            "audit_issues": report["issues_total"],
            "parked": report["parked"],
            **comp,
            "fault_matrix": matrix,
            "overhead_s": round(over_s, 4),
            "shard_wall_s": round(wall_s, 4),
            "overhead_fraction": (
                round(over_s / wall_s, 5) if wall_s else None
            ),
            "ctrl_wall_s": round(ctrl_wall, 3),
            "workdir": workdir,
            "ok": ok,
        }
    finally:
        if severed:
            _heal_posix(mirror_roots[0])
        if replicas:
            if env_before is None:
                os.environ.pop("TPUDAS_REPLICA_JOURNAL", None)
            else:
                os.environ["TPUDAS_REPLICA_JOURNAL"] = env_before
        if log_fh is not None:
            log_fh.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--kills", type=int, default=6)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="write JSON report here")
    ap.add_argument("--log", default=None, help="worker stdout log file")
    ap.add_argument(
        "--store", action="store_true",
        help="drill the object-store queue (file:// chaos leg + "
             "fault-injected fake backend leg) instead of the "
             "shared-filesystem queue",
    )
    ap.add_argument(
        "--replicas", type=int, default=0, metavar="N",
        help="with --store: drill a replica: store with N posix "
             "mirrors, one severed for the SIGKILL window then "
             "healed + scrubbed (ISSUE 20)",
    )
    args = ap.parse_args(argv)
    if args.replicas and not args.store:
        ap.error("--replicas requires --store")
    run = run_store_backfill_drill if args.store else run_backfill_drill
    kwargs = {}
    if args.store:
        kwargs["replicas"] = args.replicas
    rep = run(
        workers=args.workers, kills=args.kills, shards=args.shards,
        seed=args.seed, log_path=args.log, **kwargs,
    )
    print(json.dumps(
        {k: v for k, v in rep.items() if k != "workdir"}, indent=1
    ))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(rep, fh, indent=1)
    print(f"backfill_drill: {'OK' if rep['ok'] else 'FAILED'}")
    return 0 if rep["ok"] else 1


if __name__ == "__main__":
    if len(sys.argv) >= 7 and sys.argv[1] == "--store-worker":
        sys.exit(
            _store_worker_main(
                sys.argv[2], sys.argv[3], sys.argv[4],
                sys.argv[5], sys.argv[6],
                sys.argv[7] if len(sys.argv) > 7 else "",
            )
        )
    if len(sys.argv) >= 4 and sys.argv[1] == "--worker":
        sys.exit(
            _worker_main(
                sys.argv[2], sys.argv[3],
                sys.argv[4] if len(sys.argv) > 4 else "",
                float(sys.argv[5]) if len(sys.argv) > 5 else 0.02,
            )
        )
    sys.exit(main())
