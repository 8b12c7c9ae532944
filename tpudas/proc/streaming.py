"""Real-time ("edge") streaming drivers.

Library form of the two *_edge notebooks' polling loops (SURVEY.md
§3.2): poll the source directory, process what's new, sleep, repeat;
terminate when the spool stops growing. State is only the output
directory (crash-only): kill the process anywhere and the next run
resumes from ``get_last_processed_time`` with the edge-buffer rewind
``t1 = t_last - (ceil(edge/dt) - 1) * dt``
(low_pass_dascore_edge.ipynb:228-231) — which lands exactly one output
sample past the last emitted one, so resumed output is seam-free.

``poll_interval`` defaults to the reference's cadence clamp
``max(125 s, file duration, 3 * edge_buffer)``
(low_pass_dascore_edge.ipynb:165-173); tests inject ``sleep_fn`` and
``max_rounds``.

Stateful streaming (default): instead of the rewind, the low-pass
driver carries each filter stage's O(1) state across rounds
(tpudas.proc.stream) — no re-read, no re-filter; per-round work drops
from O(window + 2*edge) to O(window) full-rate samples and the carry
serializes beside the outputs so a crash resumes from O(1) state.
``TPUDAS_STREAM_STATEFUL=0`` (or ``stateful=False``) restores the
reference's rewind behavior; joint/mesh/window-DP runs and legacy
output folders (outputs but no carry) use the rewind path
automatically.

Fault tolerance (tpudas.resilience): each polling round runs inside a
per-round fault boundary.  Transient IO failures (an NFS hiccup, a
file the interrogator is still flushing) are retried with capped
exponential backoff + deterministic jitter; a file whose read/decode
keeps failing is quarantined in a ``.quarantine.json`` ledger beside
the carry and excluded from the spool index (slow-schedule re-probe);
only genuinely fatal errors — config/programming mistakes, the
reference's ``on_gap="raise"`` — propagate.  A retried round resumes
exactly like a crash does: the in-memory carry is dropped and
re-resolved from disk (reconcile included), so the crash-only
invariant is untouched.  See RESILIENCE.md.

Since ISSUE 8 the round loops themselves live in the fleet round
engine (:mod:`tpudas.fleet.engine`): both drivers here are thin,
kwarg-compatible shims — a :class:`tpudas.fleet.StreamConfig` + a
runner + :func:`tpudas.fleet.engine.drive` — and the SAME runner code
schedules N concurrent streams under one process via
:class:`tpudas.fleet.FleetEngine` (see FLEET.md).
``tools/check_driver_parity.py`` lints that these shims and
``StreamConfig`` can never drift apart.
"""

from __future__ import annotations

import re
import time as _time

from tpudas.fleet.config import StreamConfig, StreamSpec
from tpudas.fleet.engine import (  # noqa: F401 - re-exported legacy API
    POLL_FLOOR_SEC,
    _ROLLING_BATCH_CHUNK,
    _append_pyramid,
    _covered_workload,
    _EdgeHealth,
    _finite,
    _head_lag_seconds,
    _startup_audit,
    build_runner,
    clamp_poll_interval,
    drive,
)
from tpudas.proc.lfproc import resolve_gap_tolerance

__all__ = ["clamp_poll_interval", "run_lowpass_realtime", "run_rolling_realtime"]


def _shim_stream_id(output_folder) -> str:
    """A jitter-seed/bookkeeping id for a single-stream driver run,
    derived from the output folder (sanitized to the StreamSpec id
    alphabet; the id has no on-disk effect here — the shim passes the
    output folder explicitly)."""
    import os
    import zlib

    path = os.path.normpath(str(output_folder))
    base = re.sub(r"[^A-Za-z0-9._-]", "-", os.path.basename(path))
    # StreamSpec ids must start alphanumeric and fit in 64 chars; any
    # basename must sanitize into that alphabet (never raise).  The
    # full-path hash keeps ids — and so the jitter seeds — distinct
    # for co-located drivers whose basenames collide (/a/out, /b/out)
    base = re.sub(r"^[^A-Za-z0-9]+", "", base)[:55] or "stream"
    return f"{base}-{zlib.crc32(path.encode()):08x}"


def run_lowpass_realtime(
    source,
    output_folder,
    start_time,
    output_sample_interval,
    edge_buffer,
    process_patch_size,
    distance=None,
    poll_interval=125.0,
    file_duration=0.0,
    max_rounds=None,
    sleep_fn=_time.sleep,
    on_round=None,
    engine=None,
    on_gap=None,
    filter_order=None,
    data_gap_tolorance=None,
    data_gap_tolerance=None,
    window_dp=None,
    counters=None,
    mesh=None,
    rolling_output_folder=None,
    rolling_window=None,
    rolling_step=None,
    stateful=None,
    carry_save_every=None,
    health=None,
    fault_policy=None,
    quarantine=True,
    pyramid=None,
    detect=None,
    detect_operators=None,
    poll_jitter=None,
    flight=None,
    live=None,
):
    """Poll ``source`` and keep the low-pass output current.

    ``engine`` / ``on_gap`` / ``filter_order`` / ``data_gap_tolorance``
    / ``window_dp`` are forwarded to :class:`LFProc` (None keeps its
    defaults), so the
    streaming path can run the cascade engine and gap policies the batch
    path has. ``mesh`` runs the round's device compute mesh-sharded: a
    :class:`jax.sharding.Mesh`, an int ``N`` (channel sharding over the
    first N devices), or — when None — ``TPUDAS_MESH=N`` from the
    environment (see :func:`tpudas.parallel.mesh.resolve_mesh`).  A
    channel-only mesh (no ``time`` axis > 1) keeps the STATEFUL path:
    the stream carry lives as a sharded, donated, device-resident
    pytree between rounds and outputs are byte-identical to the
    single-device run (PERF.md §6 (PR 7)); a time-sharded
    mesh falls back to the window/rewind path, which owns the halo
    exchange — see :attr:`LFProc.mesh`.  Pass a
    :class:`tpudas.utils.profiling.Counters` to
    accumulate throughput; each processing round also emits a
    ``realtime_round`` event with its own real-time factor.

    ``rolling_output_folder`` (with ``rolling_window`` /
    ``rolling_step``, seconds) switches the round processor to
    :class:`tpudas.proc.joint.JointProc`: every round emits BOTH the
    low-pass product and the seam-free trailing rolling mean from one
    ingest pass (BASELINE config 5, streaming form). For cross-round
    rolling-grid alignment use a ``rolling_step`` that divides
    ``output_sample_interval`` (each round's grid is anchored at its
    own resume point, which sits on the output grid).

    ``stateful`` selects the carried-filter-state execution mode
    (default: on, via ``TPUDAS_STREAM_STATEFUL`` — "0" restores the
    rewind): each round processes ONLY new full-rate samples through
    :meth:`LFProc.process_stream_increment` and persists the O(1)
    carry beside the outputs for crash-only resume.  Joint products,
    time-sharded meshes, and window-DP stay on the rewind path, as
    does a legacy output folder that has files but no carry.

    ``carry_save_every`` (default 1, or ``TPUDAS_CARRY_SAVE_EVERY``)
    persists the carry every Nth processing round instead of every
    round, so steady-state rounds skip the device→host gather + crc
    write entirely (the carry pytree stays on-device; at 10k channels
    this is the dominant per-round host traffic).  Crash-resume is
    unaffected in kind: a crash loses at most N-1 rounds of carry
    progress, and :func:`tpudas.proc.stream.reconcile_outputs` deletes
    the outputs past the saved carry on resume — they are regenerated
    byte-identically.  A clean shutdown always flushes a final save.

    ``health`` (default: ``TPUDAS_HEALTH=1``) drops an atomic
    ``health.json`` + ``metrics.prom`` in ``output_folder`` after every
    processing round (and on a crash), so a cron/node-exporter on the
    interrogator box can scrape stream liveness without touching the
    process — see tpudas.obs.health and OBSERVABILITY.md.

    ``data_gap_tolerance`` is the correctly spelled form of the
    reference's ``data_gap_tolorance``; the legacy spelling remains a
    deprecated alias (warns once) and passing both with different
    values is an error.

    ``pyramid`` (default: ``TPUDAS_PYRAMID=1``) keeps the
    :mod:`tpudas.serve.tiles` multi-resolution tile pyramid in
    ``output_folder`` current: after every processing round the rows
    newer than the pyramid head are appended and the coarser
    mean/min/max levels cascaded, so the serve stack
    (:mod:`tpudas.serve`) answers window queries at any zoom without
    re-reading output files.  The append is crash-only like the carry
    (manifest written after its tiles) and failures are counted and
    swallowed — the pyramid must never take down the stream that
    feeds it.

    ``detect`` (default: ``TPUDAS_DETECT=1``) runs the registered
    streaming detection operators (:mod:`tpudas.detect`) over each
    round's decimated output — STA/LTA triggers and rolling-RMS
    anomaly scores by default, or the ``detect_operators`` spec list
    (names / ``(name, params)`` / instances).  Results land in the
    crc-stamped events ledger and score tiles under
    ``<output_folder>/.detect/`` (queryable via ``GET /events``); the
    hook is crash-only like the pyramid (carry-committed, replayed via
    file-backed catch-up after any failure) and an operator failure is
    counted and skipped — it never takes down the stream.  See
    DETECTION.md.

    ``flight`` (default: on, ``TPUDAS_FLIGHT=0`` disables) keeps the
    crash-surviving flight recorder (:mod:`tpudas.obs.flight`): a
    bounded, segmented, crc-stamped on-disk ring of the round's spans,
    per-phase timeline records, and faults under
    ``<output_folder>/.flight/`` — flushed once per committed round,
    so after any SIGKILL the final rounds replay from disk
    (``tools/crash_drill.py`` drills it; see OBSERVABILITY.md
    "Flight recorder format").

    ``live`` (default: off, ``TPUDAS_LIVE=1`` enables) attaches the
    round loop to the push plane (:mod:`tpudas.live`): each round's
    emit-captured output rows plus the detect ledger's new events are
    published as one sequenced frame to the stream's
    :class:`~tpudas.live.LiveHub`, fanned out to ``GET /live`` SSE
    subscribers over per-client bounded queues.  The hub holds no
    durable state and the publish is swallowed-on-failure and shed
    under disk pressure (``should_shed("live")``), so any number of
    subscribers leaves the round loop byte-identical to running with
    none.  See SERVING.md "Live subscriptions".

    ``fault_policy`` (a :class:`tpudas.resilience.RetryPolicy`; None =
    defaults) governs the per-round fault boundary: transient/corrupt
    round failures are retried with capped exponential backoff instead
    of killing the driver, repeat-offender files are quarantined (the
    ``.quarantine.json`` ledger beside the carry; ``quarantine=False``
    disables the ledger), and only fatal errors propagate.  A retried
    round resumes exactly like a crash: the in-memory carry is dropped
    and re-resolved from disk.  See RESILIENCE.md for the taxonomy and
    the operator runbook.

    ``poll_jitter`` (fraction, default 0 / ``TPUDAS_POLL_JITTER``)
    stretches each poll interval by up to that fraction, drawn from a
    deterministic per-stream LCG seeded by the output folder's name —
    co-located streams (and fleet members, where the default is 0.1)
    de-synchronize their spool scans instead of thundering-herding the
    filesystem.  See :class:`tpudas.fleet.PollJitter`.

    Returns the number of rounds that processed data. Terminates when a
    poll sees no new files (reference semantics) or after
    ``max_rounds`` polls (retries consume polls, so a bounded test can
    never spin forever).
    """
    gap_tol = resolve_gap_tolerance(data_gap_tolerance, data_gap_tolorance)
    config = StreamConfig(
        kind="lowpass",
        start_time=start_time,
        output_sample_interval=output_sample_interval,
        edge_buffer=edge_buffer,
        process_patch_size=process_patch_size,
        distance=distance,
        poll_interval=poll_interval,
        file_duration=file_duration,
        engine=engine,
        on_gap=on_gap,
        filter_order=filter_order,
        data_gap_tolerance=gap_tol,
        window_dp=window_dp,
        mesh=mesh,
        rolling_output_folder=rolling_output_folder,
        rolling_window=rolling_window,
        rolling_step=rolling_step,
        stateful=stateful,
        carry_save_every=carry_save_every,
        health=health,
        fault_policy=fault_policy,
        quarantine=quarantine,
        pyramid=pyramid,
        detect=detect,
        detect_operators=detect_operators,
        poll_jitter=poll_jitter,
        flight=flight,
        live=live,
    )
    spec = StreamSpec(
        stream_id=_shim_stream_id(output_folder),
        source=source,
        config=config,
        output_folder=str(output_folder),
    )
    runner = build_runner(spec, counters=counters, on_round=on_round)
    return drive(runner, max_rounds=max_rounds, sleep_fn=sleep_fn)


def run_rolling_realtime(
    source,
    output_folder,
    window,
    step,
    scale=1.0,
    distance=None,
    poll_interval=None,
    file_duration=30.0,
    max_rounds=None,
    sleep_fn=_time.sleep,
    engine=None,
    mesh=None,
    fault_policy=None,
    quarantine=True,
    pyramid=None,
    detect=None,
    detect_operators=None,
    poll_jitter=None,
    flight=None,
    live=None,
):
    """Poll ``source`` and rolling-mean each NEW patch (stateless per
    file — rolling_mean_dascore_edge.ipynb:209-221). Returns rounds
    that processed data.

    ``mesh`` (a :class:`jax.sharding.Mesh`, an int device count, or
    ``TPUDAS_MESH=N`` from the environment — see
    :func:`tpudas.parallel.mesh.resolve_mesh`) batches each round's
    fresh patches over the mesh's ``ch``
    axis (pure data parallelism, no collectives) in bounded chunks,
    whenever the chunk is shape-uniform and ``engine`` is not a host
    engine ("numpy"/"host" forces the per-patch host path);
    non-uniform chunks fall back to the per-patch device path.

    Rounds run inside the same per-round fault boundary as
    :func:`run_lowpass_realtime` (``fault_policy`` /
    ``quarantine`` — see RESILIENCE.md): transient/corrupt failures
    are retried with backoff, repeat-offender files quarantined.
    Patches written before a mid-round failure are in the ``processed``
    set already, so a retry resumes at the first unwritten patch.

    Driver parity with :func:`run_lowpass_realtime`: each round's
    output patches are captured in memory at their write site and fed
    to the same per-round append hooks — ``pyramid`` (default
    ``TPUDAS_PYRAMID=1``) keeps the :mod:`tpudas.serve.tiles` pyramid
    current over the rolling output, and ``detect`` (default
    ``TPUDAS_DETECT=1``, operators via ``detect_operators``) runs the
    :mod:`tpudas.detect` streaming operators over it.  Both hooks are
    crash-only, shed under disk pressure, and swallowed on failure.
    ``poll_jitter`` stretches the poll cadence with the same
    deterministic per-stream LCG as the low-pass driver.
    Note the rolling grid is anchored per file: for a globally uniform
    grid (what the pyramid and detect consumers assume) use a ``step``
    that divides the file duration.
    """
    config = StreamConfig(
        kind="rolling",
        window=window,
        step=step,
        scale=scale,
        distance=distance,
        poll_interval=poll_interval,
        file_duration=file_duration,
        engine=engine,
        mesh=mesh,
        fault_policy=fault_policy,
        quarantine=quarantine,
        pyramid=pyramid,
        detect=detect,
        detect_operators=detect_operators,
        poll_jitter=poll_jitter,
        flight=flight,
        live=live,
    )
    spec = StreamSpec(
        stream_id=_shim_stream_id(output_folder),
        source=source,
        config=config,
        output_folder=str(output_folder),
    )
    runner = build_runner(spec)
    return drive(runner, max_rounds=max_rounds, sleep_fn=sleep_fn)
