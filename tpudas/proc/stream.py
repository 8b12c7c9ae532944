"""Stateful streaming execution for LFProc: carry filter state across
polling rounds instead of rewinding the edge buffer.

The classic crash-only resume (tpudas.proc.streaming) rewinds
``t1 = t_last - (ceil(edge/dt) - 1) * dt`` every round, so every round
re-reads and re-filters ~2x the filter's edge support of FULL-RATE
data just to rebuild transient state the previous round already
computed.  This module carries that state explicitly — O(1) per filter
stage — so each input sample is read and filtered exactly once:

- cascade engine: the per-stage trailing-sample carry of
  :func:`tpudas.ops.fir.cascade_decimate_stream`;
- FFT engine: the overlap-save carry of
  :func:`tpudas.ops.filter.fft_pass_filter_stream` plus the last
  filtered row (lerp continuity across block seams).

The engine buffers are DEVICE-RESIDENT between rounds: each stream
step returns jax arrays that are fed back verbatim (donated on
accelerator backends, so steady-state streaming neither double-buffers
the carry update nor round-trips it through host memory).  Under a
channel-sharding mesh (``LFProc(mesh=...)`` with a ``time`` axis of
size 1 — see tpudas.parallel) the leaves live sharded on the mesh in
the pad-and-mask layout and each device runs the identical kernels on
its local channel block, byte-identical to the single-device step.
The pytree crosses to host only on the save cadence below, gathered
and trimmed to the logical channel width so the serialized form never
depends on the execution layout.

Crash-only property preserved: the carry serializes to ONE ``.npz``
beside the output files (meta embedded as JSON for atomicity, written
tmp-then-rename with a crc32 ``.crc`` sidecar and a ``.prev`` double
buffer — tpudas.integrity — plus a human-readable checksummed
``.json`` sidecar).  The save
happens AFTER the round's output writes, so on a crash the carry is
never ahead of the outputs; :func:`reconcile_outputs` deletes output
files newer than the carry on resume (the crashed round's partial
emission — regenerated identically, filenames are deterministic).  A
folder with outputs but no carry is a legacy rewind-mode folder; the
driver falls back to rewind for it.

Ingest is PIPELINED (ISSUE 15, PERF.md §6 (PR 15)): a bounded
prefetch thread (tpudas.proc.ingest) reads + merges + decodes the
next slice while the device computes the current one, raw int16
payloads ship to the device undecoded (dequantization is the first
traced op of the stream kernels, matching the batch path), and the
per-block host sync is deferred so placing the next donated input
block overlaps the previous block's compute.  Feed order and math
are byte-identical to the synchronous loop (``TPUDAS_INGEST_PREFETCH=0``).

Emission alignment (shared by both engines): the output grid is
``start + k * step`` (ms-quantized, the batch contract).  A cold
stream anchors at the first grid point covered by data and discards
the first ``edge_buff_size`` outputs — exactly the stream-start edge
the batch scheduler discards — plus, for the cascade, the carry's
mechanical warm-up (:func:`tpudas.ops.fir.stream_warmup_outputs`).
After that, every emitted output has its full filter support and the
stream head lags live data by only the filter's causal support, not a
window schedule.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass

import numpy as np

from tpudas.core.timeutils import to_datetime64
from tpudas.obs.registry import get_registry
from tpudas.obs.trace import span
from tpudas.utils.logging import log_event

__all__ = [
    "StreamCarry",
    "CARRY_FILENAME",
    "save_carry",
    "load_carry",
    "discard_carry",
    "reconcile_outputs",
    "open_stream",
    "process_increment",
]

CARRY_FILENAME = ".stream_carry.npz"
CARRY_SIDECAR = ".stream_carry.json"
_VERSION = 1


@dataclass
class StreamCarry:
    """The O(1) resume state of a stateful stream.

    Configuration fields are fixed at :func:`open_stream`; engine
    fields stay ``None`` until the first data arrives (``kind`` is the
    open marker).  ``bufs`` holds jax or numpy arrays interchangeably
    (serialization converts to numpy).
    """

    # configuration (validated against the driver's parameters on resume)
    start_ns: int  # output-grid anchor (the run's start_time)
    step_ns: int  # ms-quantized output grid step
    dt_out: float  # output_sample_interval seconds
    buff_out: int  # edge_buff_size (output samples discarded cold)
    order: int
    engine_req: str  # "auto" | "cascade" | "fft" | "fused"
    patch_out: int  # process_patch_size (stream chunk sizing)
    # engine state (None/zero until the stream sees data)
    kind: str | None = None  # "cascade" | "fft"
    d_ns: int | None = None  # input sample step
    n_ch: int | None = None
    ratio: int | None = None  # cascade only
    edge_in: int | None = None  # fft only: overlap-save halo, input samples
    bufs: tuple = ()
    residual: np.ndarray | None = None  # read-but-unconsumed rows
    # dequant scale of the rows held in ``residual`` (None = float32
    # rows): raw int16 payloads stay int16 end to end — host pool,
    # H2D transfer, first kernel read — and dequantize inside the
    # first device kernel, so the residual must remember its scale
    residual_scale: float | None = None
    skip_left: int = 0  # outputs still to discard (warm-up + cold edge)
    next_ingest_ns: int | None = None  # next input sample to read
    next_emit_ns: int | None = None  # next output grid time to emit
    last_emit_ns: int | None = None  # newest output written (reconcile key)
    consumed: int = 0  # full-rate samples fed through the filter
    emitted: int = 0  # output samples written

    def _meta(self) -> dict:
        return {
            "version": _VERSION,
            "start_ns": int(self.start_ns),
            "step_ns": int(self.step_ns),
            "dt_out": float(self.dt_out),
            "buff_out": int(self.buff_out),
            "order": int(self.order),
            "engine_req": self.engine_req,
            "patch_out": int(self.patch_out),
            "kind": self.kind,
            "d_ns": None if self.d_ns is None else int(self.d_ns),
            "n_ch": None if self.n_ch is None else int(self.n_ch),
            "ratio": None if self.ratio is None else int(self.ratio),
            "edge_in": None if self.edge_in is None else int(self.edge_in),
            "n_bufs": len(self.bufs),
            "residual_scale": (
                None if self.residual_scale is None
                else float(self.residual_scale)
            ),
            "skip_left": int(self.skip_left),
            "next_ingest_ns": _opt_int(self.next_ingest_ns),
            "next_emit_ns": _opt_int(self.next_emit_ns),
            "last_emit_ns": _opt_int(self.last_emit_ns),
            "consumed": int(self.consumed),
            "emitted": int(self.emitted),
        }


def _opt_int(v):
    return None if v is None else int(v)


def save_carry(carry: StreamCarry, folder: str) -> str:
    """Atomically persist the carry beside the output files: one
    crc32-stamped ``.npz`` (meta embedded, unique tmp + rename,
    ``.crc`` sidecar) plus a readable checksummed ``.json`` sidecar.
    The outgoing primary survives as ``.prev`` — the middle rung of
    the verified-read ladder (:func:`load_carry`): a resume from
    ``.prev`` is one round back, and :func:`reconcile_outputs`
    regenerates that round byte-identically.  Returns the npz path."""
    import io as _io

    from tpudas.integrity.checksum import (
        rotate_prev,
        write_bytes_checksummed,
        write_json_checksummed,
    )
    from tpudas.resilience.faults import fault_point

    from tpudas.parallel.sharding import gather_leaves

    path = os.path.join(folder, CARRY_FILENAME)
    fault_point("carry.save", folder=folder)
    with span("stream.carry_save"):
        # the only point the engine buffers cross back to host: sharded
        # (pad-and-masked) device leaves gather + trim to the logical
        # channel width here, so the serialized .npz is byte-identical
        # to a single-device run's.  D2H traffic is counted under
        # tpudas_parallel_transfer_bytes_total{direction="gather"} —
        # raise TPUDAS_CARRY_SAVE_EVERY to amortize it at 10k channels.
        arrays = {"meta": np.asarray(json.dumps(carry._meta()))}
        for i, b in enumerate(gather_leaves(carry.bufs, carry.n_ch)):
            arrays[f"buf_{i}"] = b
        if carry.residual is not None:
            res = np.asarray(carry.residual)
            if res.dtype != np.int16:  # raw quantized rows stay int16
                res = res.astype(np.float32, copy=False)
            arrays["residual"] = res
        buf = _io.BytesIO()
        np.savez(buf, **arrays)
        rotate_prev(path)
        write_bytes_checksummed(path, buf.getvalue())
        write_json_checksummed(
            os.path.join(folder, CARRY_SIDECAR), carry._meta()
        )
    get_registry().counter(
        "tpudas_stream_carry_saves_total", "stream carry persists"
    ).inc()
    return path


def discard_carry(folder: str) -> bool:
    """Remove a persisted carry (both files).  Any non-stateful round
    that emits into the folder MUST call this: the carry's validity
    rests on 'no output is newer than the carry', and a rewind-mode
    write breaks that — a later stateful resume against the stale
    carry would reconcile away valid (possibly irreplaceable) output
    files.  Returns True when a carry was removed."""
    removed = False
    for name in (
        CARRY_FILENAME,
        CARRY_FILENAME + ".crc",
        CARRY_FILENAME + ".prev",
        CARRY_FILENAME + ".prev.crc",
        CARRY_SIDECAR,
    ):
        path = os.path.join(folder, name)
        if os.path.isfile(path):
            os.remove(path)
            if name in (CARRY_FILENAME, CARRY_FILENAME + ".prev"):
                removed = True
    if removed:
        log_event("stream_carry_discarded", folder=folder)
        get_registry().counter(
            "tpudas_stream_carry_discards_total",
            "persisted carries invalidated by a non-stateful write",
        ).inc()
    return removed


def _parse_carry(path: str) -> StreamCarry:
    """Parse one carry ``.npz`` into a :class:`StreamCarry`, raising
    on ANY defect (unreadable zip, bad meta JSON, version skew,
    missing keys).  Shared by the :func:`load_carry` ladder and the
    startup audit — everything, including the ``StreamCarry``
    construction, happens under the caller's try so a truncated meta
    can never escape as a bare ``KeyError`` and kill the driver."""
    with np.load(path) as f:
        meta = json.loads(str(f["meta"]))
        if meta.get("version") != _VERSION:
            raise ValueError(
                f"carry version skew: {meta.get('version')!r} != "
                f"{_VERSION}"
            )
        bufs = tuple(f[f"buf_{i}"] for i in range(int(meta["n_bufs"])))
        residual = f["residual"] if "residual" in f else None
        return StreamCarry(
            start_ns=meta["start_ns"],
            step_ns=meta["step_ns"],
            dt_out=meta["dt_out"],
            buff_out=meta["buff_out"],
            order=meta["order"],
            engine_req=meta["engine_req"],
            patch_out=meta["patch_out"],
            kind=meta["kind"],
            d_ns=meta["d_ns"],
            n_ch=meta["n_ch"],
            ratio=meta["ratio"],
            edge_in=meta["edge_in"],
            bufs=bufs,
            residual=residual,
            residual_scale=meta.get("residual_scale"),
            skip_left=meta["skip_left"],
            next_ingest_ns=meta["next_ingest_ns"],
            next_emit_ns=meta["next_emit_ns"],
            last_emit_ns=meta["last_emit_ns"],
            consumed=meta["consumed"],
            emitted=meta["emitted"],
        )


def load_carry(folder: str) -> StreamCarry | None:
    """Load a previously saved carry through the verified-read ladder:
    checksum-verified primary, then the ``.prev`` double buffer (one
    round back — :func:`reconcile_outputs` regenerates that round
    byte-identically), then None (the driver degrades to rewind mode).
    A corrupt carry must never crash the realtime loop; every rejected
    rung is counted (``tpudas_integrity_fallback_total``)."""
    from tpudas.integrity.checksum import (
        count_fallback,
        count_unstamped,
        verify_file_checksum,
    )

    path = os.path.join(folder, CARRY_FILENAME)
    prev = path + ".prev"
    if not os.path.isfile(path) and not os.path.isfile(prev):
        return None
    for cand in (path, prev):
        if not os.path.isfile(cand):
            if cand == path:
                # a primary missing while .prev exists is the crash
                # window between the save's rotate and write
                count_fallback("carry", "primary missing", cand)
            continue
        try:
            status = verify_file_checksum(cand, artifact="carry")
            if status == "mismatch":
                raise ValueError("carry checksum mismatch")
            if status == "unstamped":
                count_unstamped("carry")
            carry = _parse_carry(cand)
        except Exception as exc:
            log_event(
                "stream_carry_unreadable", path=cand,
                error=f"{type(exc).__name__}: {str(exc)[:200]}",
            )
            get_registry().counter(
                "tpudas_stream_carry_unreadable_total",
                "corrupt/unreadable carries degraded to .prev or "
                "rewind mode",
            ).inc()
            count_fallback(
                "carry", f"{type(exc).__name__}: {str(exc)[:120]}", cand
            )
            continue
        get_registry().counter(
            "tpudas_stream_carry_loads_total", "stream carries loaded"
        ).inc()
        return carry
    return None


def reconcile_outputs(folder: str, carry: StreamCarry) -> int:
    """Delete output files newer than the carry (a crash between the
    round's output writes and its carry save leaves such files; they
    are regenerated identically on resume).  Returns the count."""
    if carry.last_emit_ns is None:
        cutoff = None  # nothing emitted yet: every output is stale
    else:
        cutoff = np.datetime64(int(carry.last_emit_ns), "ns")
    from tpudas.io.spool import spool as make_spool

    try:
        contents = make_spool(folder).update().get_contents()
    except Exception:
        return 0
    removed = 0
    for _, row in contents.iterrows():
        t_min = np.datetime64(row["time_min"], "ns")
        if cutoff is None or t_min > cutoff:
            path = row.get("path")
            if path and not os.path.isabs(path):
                path = os.path.join(folder, path)
            if path and os.path.isfile(path):
                os.remove(path)
                removed += 1
    if removed:
        log_event("stream_reconcile_removed", files=removed)
        get_registry().counter(
            "tpudas_stream_reconcile_removed_total",
            "crashed-round output files removed on carry resume",
        ).inc(removed)
    return removed


# ---------------------------------------------------------------------------
# the resumable engine


def open_stream(lfp, start_time) -> StreamCarry:
    """A fresh (unopened) carry for this LFProc's parameters, anchored
    at ``start_time``.  Engine choice and buffer allocation happen on
    first data (:func:`process_increment`)."""
    from tpudas.core.timeutils import quantize_step

    para = lfp.parameters
    dt = float(para["output_sample_interval"])
    step_ns = int(
        quantize_step(dt).astype("timedelta64[ns]").astype(np.int64)
    )
    if step_ns <= 0:
        raise ValueError(
            f"output_sample_interval {dt} quantizes to a non-positive "
            "ms grid step"
        )
    start_ns = int(
        to_datetime64(start_time).astype("datetime64[ns]").astype(np.int64)
    )
    return StreamCarry(
        start_ns=start_ns,
        step_ns=step_ns,
        dt_out=dt,
        buff_out=int(para["edge_buff_size"]),
        order=int(para["filter_order"]),
        engine_req=str(para["engine"]),
        patch_out=int(para["process_patch_size"]),
    )


# engine requests that share the cascade carry layout byte-for-byte:
# a stream may cross between them mid-run (resume a "cascade" carry
# under "fused" and vice versa — ISSUE 10) because the per-stage
# trailing-sample pytree is identical.  "fft" stays exclusive: its
# overlap-save carry is a different object.
_CASCADE_FAMILY = ("auto", "cascade", "fused")


def _engines_compatible(old: str, new: str, kind) -> bool:
    """Whether a carry produced under engine request ``old`` may
    resume under ``new``.  Within the cascade family any crossover is
    allowed unless the carry already opened the FFT engine (possible
    only under ``old == "auto"``) — a cascade-only request cannot
    continue an FFT carry."""
    if old == new:
        return True
    if old in _CASCADE_FAMILY and new in _CASCADE_FAMILY:
        return kind != "fft" or new == "auto"
    return False


def carry_matches(carry: StreamCarry, lfp, start_time=None) -> bool:
    """Resume guard: the loaded carry must have been produced by the
    same output-grid/filter/engine configuration — and, when
    ``start_time`` is given, the same stream anchor (a moved start
    cannot be honored by a continuing grid; the caller raises so the
    operator deletes the carry instead of being silently ignored).
    ``process_patch_size`` is NOT compared: it only shapes chunking,
    and the caller refreshes it from the live parameters — likewise a
    compatible ``engine`` change (:func:`_engines_compatible`: the
    cascade <-> fused crossover) is honored by refreshing
    ``carry.engine_req``, not rejected."""
    para = lfp.parameters
    from tpudas.core.timeutils import quantize_step

    step_ns = int(
        quantize_step(float(para["output_sample_interval"]))
        .astype("timedelta64[ns]")
        .astype(np.int64)
    )
    if start_time is not None:
        start_ns = int(
            to_datetime64(start_time)
            .astype("datetime64[ns]")
            .astype(np.int64)
        )
        if carry.start_ns != start_ns:
            return False
    return (
        carry.step_ns == step_ns
        and carry.buff_out == int(para["edge_buff_size"])
        and carry.order == int(para["filter_order"])
        and _engines_compatible(
            carry.engine_req, str(para["engine"]), carry.kind
        )
    )


def _corner(dt: float) -> float:
    from tpudas.proc.lfproc import output_corner

    return output_corner(dt)


class _EmitPipeline:
    """FIFO of dispatched-but-unsynced stream blocks (the
    double-buffer of donated input blocks): each entry is a closure
    that syncs the block's device output and emits it.  With JAX's
    async dispatch, deferring the host sync by ``depth`` blocks lets
    the placement + compute of block N+1 run while block N's output
    is synced and written — ``depth`` 0 is the classic synchronous
    behavior (every dispatch flushed immediately).  Flushes run in
    dispatch order on the consumer thread, so every carry/emission
    mutation happens in exactly the synchronous sequence; an
    exception simply abandons the un-flushed suffix, which is the
    crash shape the resume path already reconciles (outputs are a
    prefix of the feed order, the carry was not saved)."""

    __slots__ = ("depth", "_pending")

    def __init__(self, depth: int):
        self.depth = max(0, int(depth))
        self._pending: list = []

    def push(self, flush_fn) -> None:
        self._pending.append(flush_fn)
        while len(self._pending) > self.depth:
            self._pending.pop(0)()

    def flush(self) -> None:
        while self._pending:
            self._pending.pop(0)()


def process_increment(lfp, carry: StreamCarry, edtime) -> int:
    """Process all new data up to ``edtime`` through the carried
    filter state; write outputs; update ``carry`` in place.  Returns
    the number of output samples emitted.

    Data is loaded in bounded time slices (one ``process_patch_size``
    window's worth of inputs each) so a large backlog never materializes
    at once; each slice flows through the stateful engine exactly once.

    With ``TPUDAS_INGEST_PREFETCH`` > 0 (default 2) the slice loop is
    a bounded producer/consumer pipeline: a host thread reads, merges
    and decodes the NEXT slice (:class:`tpudas.proc.ingest.
    SlicePrefetcher` — speculated, validated, byte-identical) while
    the device computes the current one, and the per-block host sync
    is deferred (:class:`_EmitPipeline`) so placement of the next
    donated input block overlaps the previous block's compute.  The
    feed order, the math, and every durable byte are identical to the
    synchronous loop — only the wall-clock overlap changes."""
    from tpudas.proc.ingest import SlicePrefetcher, decode_payload, \
        ingest_depth

    on_gap = lfp.parameters["on_gap"]
    t2_ns = int(
        to_datetime64(edtime).astype("datetime64[ns]").astype(np.int64)
    )
    emitted0 = carry.emitted
    slice_ns = max(carry.patch_out, 4) * carry.step_ns
    reg = get_registry()
    depth = ingest_depth()
    pipe = _EmitPipeline(depth)
    prefetcher = None
    try:
        with span("stream.increment", upto=str(edtime)):
            cursor0 = (
                carry.next_ingest_ns
                if carry.next_ingest_ns is not None
                else carry.start_ns
            )
            if depth > 0 and cursor0 <= t2_ns:
                prefetcher = SlicePrefetcher(
                    lfp, t2_ns, slice_ns, on_gap, depth,
                    cursor0, carry.d_ns,
                )
            while True:
                t_lo_ns = (
                    carry.next_ingest_ns
                    if carry.next_ingest_ns is not None
                    else carry.start_ns
                )
                if t_lo_ns > t2_ns:
                    break
                t_hi_ns = min(t2_ns, t_lo_ns + slice_ns)
                t_lo = np.datetime64(int(t_lo_ns), "ns")
                t_hi = np.datetime64(int(t_hi_ns), "ns")
                payload = None
                missed = False
                item = (
                    prefetcher.get(t_lo_ns, t_hi_ns)
                    if prefetcher is not None
                    else None
                )
                if item is not None:
                    patch = item.patch
                    payload = item.payload
                else:
                    # synchronous load: prefetch off, or a validated
                    # MISS (the speculation diverged — re-read here,
                    # resync the producer after the feed)
                    missed = prefetcher is not None
                    t0 = time.perf_counter()
                    with span("stream.load_slice"):
                        patch = lfp._load_window(t_lo, t_hi, on_gap)
                    lfp.timings["assemble_s"] += time.perf_counter() - t0
                    if patch is not None:
                        payload = decode_payload(lfp, patch)
                if patch is None:
                    # unmergeable slice under a tolerant gap policy:
                    # skip it and cold-restart the engine at the next
                    # data (stream analogue of the batch path's
                    # skipped/split windows).  Pending blocks flush
                    # first — the reset re-anchors the emission grid.
                    pipe.flush()
                    log_event(
                        "stream_gap_skipped", t_lo=str(t_lo),
                        t_hi=str(t_hi),
                    )
                    reg.counter(
                        "tpudas_stream_gap_skips_total",
                        "stream slices skipped over unmergeable gaps",
                    ).inc()
                    _reset_engine(carry)
                    carry.next_ingest_ns = t_hi_ns + 1
                    if missed:
                        prefetcher.resync(
                            carry.next_ingest_ns, carry.d_ns
                        )
                    if t_hi_ns >= t2_ns:
                        break
                    continue
                _feed_patch(lfp, carry, patch, on_gap, pipe, payload)
                if (
                    carry.next_ingest_ns is None
                    or carry.next_ingest_ns <= t_lo_ns
                ):
                    # the slice produced no ingest progress (e.g. a
                    # selection quirk returned only already-consumed
                    # samples) — forcing the cursor forward beats
                    # spinning on the same slice
                    log_event("stream_no_progress", t_lo=str(t_lo))
                    carry.next_ingest_ns = t_hi_ns + 1
                if missed:
                    prefetcher.resync(carry.next_ingest_ns, carry.d_ns)
                if t_hi_ns >= t2_ns:
                    break
            # every dispatched block must be written before the caller
            # saves the carry (outputs-before-carry is the crash-only
            # ordering contract)
            pipe.flush()
    finally:
        if prefetcher is not None:
            prefetcher.close()
    emitted = carry.emitted - emitted0
    reg.counter(
        "tpudas_stream_samples_emitted_total",
        "output samples emitted by the stateful stream",
    ).inc(emitted)
    return emitted


def _reset_engine(carry: StreamCarry) -> None:
    carry.kind = None
    carry.bufs = ()
    carry.residual = None
    carry.residual_scale = None
    carry.skip_left = 0
    carry.ratio = None
    carry.edge_in = None


def _feed_patch(lfp, carry: StreamCarry, patch, on_gap, pipe,
                payload=None) -> None:
    """Feed one loaded window into the carried engine, emitting output
    files for every grid point whose support is now complete.

    ``payload`` is the pre-decoded ``(host, qscale)`` pair when the
    prefetch stage already ran the decode (``tpudas.proc.ingest.
    decode_payload`` — the same function the synchronous fallback
    uses, so the fed bytes cannot depend on who loaded the slice).
    Raw int16 payloads are fed RAW: dequantization happens inside the
    first device kernel (the batch path's contract,
    ``lfproc._lowpass_resample_kernel``), halving the host-side copy
    traffic and the H2D bytes."""
    if payload is None:
        from tpudas.proc.ingest import decode_payload

        payload = decode_payload(lfp, patch)
    host, qs = payload
    t_ns = (
        np.asarray(patch.coords["time"])
        .astype("datetime64[ns]")
        .astype(np.int64)
    )
    if t_ns.size == 0:
        return
    if carry.kind is None:
        d_sec = patch.get_sample_step("time")
        i0 = _open_engine(lfp, carry, host, t_ns, float(d_sec), qs)
    else:
        if host.shape[1] != carry.n_ch:
            raise ValueError(
                f"stream channel count changed: {host.shape[1]} vs "
                f"carry {carry.n_ch}"
            )
        d = carry.d_ns
        i0 = int(np.searchsorted(t_ns, carry.next_ingest_ns - d // 2))
        if i0 >= t_ns.size:
            return  # slice contained only already-consumed samples
        if t_ns[i0] - carry.next_ingest_ns > d // 2:
            # data is missing between the carry position and this
            # window — a real gap at full rate
            log_event(
                "stream_gap_detected",
                expected=str(np.datetime64(int(carry.next_ingest_ns), "ns")),
                got=str(np.datetime64(int(t_ns[i0]), "ns")),
            )
            get_registry().counter(
                "tpudas_stream_gaps_detected_total",
                "full-rate gaps that cold-restarted the stream engine",
            ).inc()
            if on_gap == "raise":
                raise Exception("patch merge failed! Gap in data exists")
            # pending blocks carry the PRE-GAP emission grid: flush
            # them before the engine reset re-anchors it
            pipe.flush()
            _reset_engine(carry)
            d_sec = patch.get_sample_step("time")
            i0 = _open_engine(
                lfp, carry, host[i0:], t_ns[i0:], float(d_sec), qs
            ) + i0
    new = host[i0:]
    new_t = t_ns[i0:]
    if new.shape[0] == 0:
        return
    carry.next_ingest_ns = int(new_t[-1]) + carry.d_ns
    if carry.kind == "cascade":
        _consume_cascade(lfp, carry, patch, new, qs, pipe)
    else:
        _consume_fft(lfp, carry, patch, new, int(new_t[0]), qs, pipe)


def _grid_ceil(carry: StreamCarry, t_ns: int) -> int:
    """First output-grid time >= both t_ns and the grid anchor."""
    k = max(0, -(-(int(t_ns) - carry.start_ns) // carry.step_ns))
    return carry.start_ns + k * carry.step_ns


def _open_engine(lfp, carry: StreamCarry, host, t_ns, d_sec,
                 qs=None) -> int:
    """Choose and initialize the engine at the stream's first data.
    Returns the index of the first input row to feed.  ``qs`` is the
    payload's dequant scale (None = float32): the cascade's warm-up
    prepad is created in the payload's own dtype so a quantized
    stream's pool stays raw int16 (int16 zeros dequantize to exact
    0.0f — identical to the float32 zeros the host path fed)."""
    d_ns = int(round(d_sec * 1e9))
    if d_ns <= 0:
        raise ValueError(f"non-positive input sample step {d_sec}")
    t0 = int(t_ns[0])
    g_e = _grid_ceil(carry, t0)  # first emittable grid point
    step = carry.step_ns
    n_ch = int(host.shape[1])
    corner = _corner(carry.dt_out)

    aligned = step % d_ns == 0 and (g_e - t0) % d_ns == 0
    ratio = step // d_ns if aligned else 0
    if aligned:
        from tpudas.ops.fir import factor_ratio

        try:
            factor_ratio(ratio)
        except ValueError:
            aligned = False
    if carry.engine_req == "fft":
        aligned = False
    if not aligned and carry.engine_req in ("cascade", "fused"):
        raise ValueError(
            f"engine={carry.engine_req!r} requires the output grid to "
            "land on input samples with an integer small-prime "
            "decimation ratio; use engine='auto' or 'fft'"
        )
    carry.d_ns = d_ns
    carry.n_ch = n_ch
    if aligned:
        from tpudas.ops.fir import (
            cascade_stream_init,
            design_cascade,
            edge_support_samples,
            stream_warmup_outputs,
        )

        plan = design_cascade(1e9 / d_ns, int(ratio), corner, carry.order)
        supp = edge_support_samples(plan, 1e-3)
        if carry.buff_out * step < supp * d_ns:
            print(
                "Warning: edge_buff_size halo is smaller than the "
                f"cascade filter support ({supp} input samples); the "
                "stream's first emitted samples may carry start "
                "artifacts"
            )
            log_event("stream_halo_small", support=supp)
        carry.kind = "cascade"
        carry.ratio = int(ratio)
        carry.skip_left = stream_warmup_outputs(plan) + carry.buff_out
        carry.next_emit_ns = g_e + carry.buff_out * step
        carry.bufs = cascade_stream_init(plan, n_ch)
        # feed origin so that stream output (warmup + k) lands on grid
        # point g_e + k*step: first fed sample at g_e - delay*d
        t_feed0 = g_e - plan.delay * d_ns
        res_dtype = host.dtype if qs is not None else np.float32
        if t_feed0 < t0:
            prepad = (t0 - t_feed0) // d_ns
            carry.residual = np.zeros((int(prepad), n_ch), res_dtype)
            carry.residual_scale = qs
            i0 = 0
        else:
            carry.residual = np.zeros((0, n_ch), res_dtype)
            carry.residual_scale = qs
            i0 = int((t_feed0 - t0) // d_ns)
    else:
        from tpudas.ops.filter import fft_stream_init

        carry.kind = "fft"
        carry.edge_in = int(-(-carry.buff_out * step // d_ns))
        carry.next_emit_ns = g_e + carry.buff_out * step
        carry.bufs = (
            fft_stream_init(carry.edge_in, n_ch),
            np.zeros((0, n_ch), np.float32),  # last-row lerp seam
        )
        carry.residual = None
        i0 = 0
    log_event(
        "stream_open",
        kind=carry.kind,
        ratio=carry.ratio,
        edge_in=carry.edge_in,
        skip_left=carry.skip_left,
        first_emit=str(np.datetime64(int(carry.next_emit_ns), "ns")),
    )
    return i0


def _emit(lfp, carry: StreamCarry, patch, out, rows, ran) -> None:
    """Write ``out`` (n, C) at the carry's emission cursor."""
    n = int(out.shape[0])
    if n == 0:
        return
    times = (
        carry.next_emit_ns + carry.step_ns * np.arange(n, dtype=np.int64)
    ).astype("datetime64[ns]")
    carry.next_emit_ns = int(carry.next_emit_ns + n * carry.step_ns)
    carry.last_emit_ns = int(times[-1].astype(np.int64))
    carry.emitted += n
    t_w0 = time.perf_counter()
    lfp._emit_window_output(patch, times, carry.dt_out, out, ran, rows=rows)
    lfp.timings["write_s"] += time.perf_counter() - t_w0


def _pow2_blocks(n_units: int, cap: int) -> list:
    """Block sizes covering ``n_units``: whole ``cap``-sized blocks
    first, then a descending power-of-two decomposition of the
    remainder.  Every emitted size is either ``cap`` or a power of
    two, so the jitted stream step compiles O(log) distinct shapes per
    configuration instead of one per arrival size (a fresh trace per
    round would cost more on TPU than the rewind this module
    eliminates)."""
    out = [cap] * (n_units // cap)
    rem = n_units % cap
    b = 1 << max(rem.bit_length() - 1, 0)
    while rem:
        if b <= rem:
            out.append(b)
            rem -= b
        b >>= 1
    return out


def _count_block(rows: int, engine: str, t_dev: float) -> None:
    """Per-dispatched-block observability shared by both stream
    engines: block count + consumed full-rate rows by engine, and the
    synced device latency distribution."""
    reg = get_registry()
    reg.counter(
        "tpudas_stream_blocks_total",
        "stream filter blocks dispatched",
        labelnames=("engine",),
    ).inc(engine=engine)
    reg.counter(
        "tpudas_stream_samples_consumed_total",
        "full-rate samples fed through the carried filter state",
        labelnames=("engine",),
    ).inc(int(rows), engine=engine)
    reg.histogram(
        "tpudas_stream_block_seconds",
        "per-block device dispatch+sync latency",
        labelnames=("engine",),
    ).observe(t_dev, engine=engine)


def _stream_mesh(lfp):
    """The channel-sharding mesh the stream step runs under: the
    LFProc's mesh when it is pure channel sharding (a ``time`` axis of
    size 1 — time-sharded meshes stay on the window path, which owns
    the halo exchange), else None.  With a mesh, every engine carry
    leaf lives as a sharded device array between rounds and only
    crosses to host on the save cadence (:func:`save_carry`)."""
    mesh = getattr(lfp, "_mesh", None)
    if mesh is None or int(mesh.shape.get("time", 1)) > 1:
        return None
    return mesh


def _pool_with_residual(carry: StreamCarry, new, qs):
    """(pool, pool_qscale): the residual rows prepended to the fresh
    payload.  Homogeneous payloads (same dtype, same dequant scale)
    concatenate RAW — a quantized pool ships int16 to the device and
    dequantizes in-kernel.  A mid-stream dtype/scale change (rare:
    interrogator reconfiguration) degrades that one seam to a counted
    host-side dequant so the pool stays uniform."""
    residual = carry.residual
    if residual is None or residual.size == 0:
        return new, qs
    r_qs = carry.residual_scale
    if residual.dtype == new.dtype and (
        (r_qs is None and qs is None)
        or (r_qs is not None and qs is not None and float(r_qs) == float(qs))
    ):
        return np.concatenate([residual, new], axis=0), qs
    get_registry().counter(
        "tpudas_stream_ingest_host_dequant_total",
        "stream slices dequantized on host because the payload "
        "dtype/scale changed mid-stream (the uniform-payload fast "
        "path dequantizes in-kernel)",
    ).inc()
    r = (
        residual.astype(np.float32) * np.float32(r_qs)
        if r_qs is not None
        else np.asarray(residual, np.float32)
    )
    n = (
        new.astype(np.float32) * np.float32(qs)
        if qs is not None
        else np.asarray(new, np.float32)
    )
    return np.concatenate([r, n], axis=0), None


def _consume_cascade(lfp, carry: StreamCarry, patch, new, qs,
                     pipe) -> None:
    from tpudas.ops.fir import (
        cascade_decimate_stream,
        design_cascade,
        stream_stage_engines,
    )

    plan = design_cascade(
        1e9 / carry.d_ns, carry.ratio, _corner(carry.dt_out), carry.order
    )
    mesh = _stream_mesh(lfp)
    pool, pool_qs = _pool_with_residual(carry, new, qs)
    usable = pool.shape[0] - pool.shape[0] % carry.ratio
    # the fused selector: fused-pallas on TPU / fused-xla elsewhere,
    # per-stage chain below the measured size threshold or where the
    # kernel's VMEM does not fit (tpudas.ops.fir.resolve_stream_engine)
    eng_req = "fused" if carry.engine_req == "fused" else "auto"
    # engine thresholds see what one device actually traces: the LOCAL
    # (padded) channel count under a mesh
    n_ch_eng = (
        carry.n_ch
        if mesh is None
        else -(-carry.n_ch // int(mesh.shape["ch"]))
    )
    off = 0
    for n_out in _pow2_blocks(usable // carry.ratio, carry.patch_out):
        blk = pool[off : off + n_out * carry.ratio]
        rows = int(blk.shape[0])
        stages = stream_stage_engines(
            plan, rows, n_ch_eng, eng_req
        )
        if stages and stages[0].startswith("fused"):
            ran = stages[0]
        else:
            ran = "cascade-pallas" if "pallas" in stages else "cascade-xla"
        # deferred-sync pipeline: dispatch now (JAX queues the
        # compute; the next block's pad-and-place overlaps it),
        # sync + emit when the block reaches the pipeline head —
        # same order, same math, just overlapped wall clock
        t0 = time.perf_counter()
        bx = getattr(lfp, "_batch_executor", None)
        if bx is not None and mesh is None and not ran.endswith("pallas"):
            # ragged-batched fleet service (ISSUE 16): rendezvous
            # with the other batch-group members so co-shaped
            # blocks stack into ONE device program.  The engine is
            # resolved HERE at this stream's own width (`ran` is
            # already the solo decision), so stacking never flips
            # a threshold; byte-identical either way.
            y_dev, bufs = bx.cascade_step(
                blk, carry.bufs, plan,
                ran if ran == "fused-xla" else "xla",
                qscale=pool_qs,
            )
        else:
            y_dev, bufs = cascade_decimate_stream(
                blk, carry.bufs, plan, eng_req, mesh=mesh,
                qscale=pool_qs,
            )
        t_disp = time.perf_counter() - t0
        carry.bufs = bufs

        def _flush(y_dev=y_dev, rows=rows, ran=ran, t_disp=t_disp):
            t1 = time.perf_counter()
            y = np.asarray(y_dev)
            t_dev = t_disp + time.perf_counter() - t1
            lfp.timings["device_s"] += t_dev
            _count_block(rows, ran, t_dev)
            carry.consumed += rows
            s = min(carry.skip_left, y.shape[0])
            carry.skip_left -= s
            _emit(lfp, carry, patch, y[s:], rows=rows, ran=ran)

        pipe.push(_flush)
        off += rows
    carry.residual = np.ascontiguousarray(pool[usable:])
    carry.residual_scale = pool_qs


# FFT stream feed quantum (input samples): block sizes are multiples
# of this, power-of-two decomposed, so the filter kernel compiles a
# bounded set of shapes; up to QUANTUM-1 samples wait in the residual
# until the next feed (bounded, sub-second extra head lag)
_FFT_QUANTUM = 128


def _consume_fft(lfp, carry: StreamCarry, patch, new, t_new0_ns, qs,
                 pipe) -> None:
    from tpudas.ops.filter import fft_pass_filter_stream

    d = carry.d_ns
    corner = _corner(carry.dt_out)
    mesh = _stream_mesh(lfp)
    q = _FFT_QUANTUM
    pool, pool_qs = _pool_with_residual(carry, new, qs)
    t_pool0_ns = t_new0_ns - (pool.shape[0] - new.shape[0]) * d
    usable = pool.shape[0] - pool.shape[0] % q
    cap_units = max(
        1, carry.patch_out * max(1, carry.step_ns // d) // q
    )
    off = 0
    for n_units in _pow2_blocks(usable // q, cap_units):
        blk = pool[off : off + n_units * q]
        blk_rows = int(blk.shape[0])
        # dispatch the filter now — the overlap-save carry chains on
        # DEVICE (bufs[0], sharded under a mesh), so the next block's
        # dispatch never waits on this block's host sync; the 1-row
        # lerp seam (bufs[1], host) is updated at flush, strictly
        # before the next flush reads it (FIFO)
        t0 = time.perf_counter()
        bx = getattr(lfp, "_batch_executor", None)
        if bx is not None and mesh is None:
            # ragged-batched fleet service (ISSUE 16): stack with the
            # batch group's co-parameter FFT blocks (same T, edge,
            # corner, order, dtype, qscale — the executor's wave key)
            filt_dev, fcarry = bx.fft_step(
                blk, carry.bufs[0], d / 1e9, corner, carry.order,
                qscale=pool_qs,
            )
        else:
            filt_dev, fcarry = fft_pass_filter_stream(
                blk, carry.bufs[0], d / 1e9, high=corner,
                order=carry.order, mesh=mesh, qscale=pool_qs,
            )
        t_disp = time.perf_counter() - t0
        carry.bufs = (fcarry, carry.bufs[1])
        # row j of the flushed block is the filtered stream at the
        # position edge_in samples behind its input; the stored tail
        # row extends the seam left
        t_blk0 = t_pool0_ns + off * d - carry.edge_in * d
        off += blk_rows

        def _flush(filt_dev=filt_dev, blk_rows=blk_rows, t_blk0=t_blk0,
                   t_disp=t_disp):
            t1 = time.perf_counter()
            filt = np.asarray(filt_dev)
            t_dev = t_disp + time.perf_counter() - t1
            lfp.timings["device_s"] += t_dev
            _count_block(blk_rows, "fft", t_dev)
            tail = carry.bufs[1]
            rows = (
                np.concatenate([tail, filt], axis=0) if tail.size
                else filt
            )
            t_row0 = t_blk0 - tail.shape[0] * d
            t_last = t_row0 + (rows.shape[0] - 1) * d
            carry.bufs = (carry.bufs[0], rows[-1:].copy())
            carry.consumed += blk_rows
            if t_last < carry.next_emit_ns or rows.shape[0] < 2:
                return
            n = int((t_last - carry.next_emit_ns) // carry.step_ns) + 1
            g = carry.next_emit_ns + carry.step_ns * np.arange(
                n, dtype=np.int64
            )
            offs = g - t_row0
            idx = offs // d
            w = (offs - idx * d) / float(d)
            sel = idx >= rows.shape[0] - 1
            idx[sel] = rows.shape[0] - 2
            w[sel] = 1.0
            out = rows[idx] * (1.0 - w[:, None]).astype(np.float32) + rows[
                idx + 1
            ] * w[:, None].astype(np.float32)
            s = min(carry.skip_left, out.shape[0])
            carry.skip_left -= s
            _emit(
                lfp, carry, patch, out[s:].astype(np.float32, copy=False),
                rows=blk_rows, ran="fft",
            )

        pipe.push(_flush)
    carry.residual = np.ascontiguousarray(pool[usable:])
    carry.residual_scale = pool_qs
