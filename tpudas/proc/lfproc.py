"""LFProc: the chunked overlap-save low-pass + decimate engine.

TPU-first re-design of the reference engine (lf_das.py:182-295). The
*contracts* are identical — the ms-quantized time grid, the overlap-save
window schedule and its seam-freeness invariant (SURVEY.md §3.1), the
``LFDAS_*.h5`` naming, parameters dict semantics, and crash-only resume
from the output folder (lf_das.py:214-217). The *execution* differs:

- per window, the host assembles ``(T, C)`` float32 data from the spool
  (range-sliced HDF5 reads) while the device processes the previous
  window (one-deep prefetch pipeline);
- filter + decimate run as ONE fused jitted kernel: rfft → Butterworth²
  response multiply → irfft → gather-lerp resample. Datetime math never
  enters jit; gather indices/weights are computed host-side in exact
  float64;
- FFT length is padded to the next power of two
  (``tpudas.ops.fftlen.next_tpu_fft_len``: other lengths can lower to
  a dense DFT matmul on the TPU) and window shapes are constant in
  steady state, so XLA compiles the kernel at most a few times per run
  (first/steady/tail).

The per-window corner frequency is ``0.45 / dt`` — 0.9x the
post-decimation Nyquist, matching lf_das.py:223.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import os
import shutil
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from tpudas.ops.fftlen import (
    fft_channel_blocks,
    map_channel_blocks,
    next_tpu_fft_len,
)

from tpudas.core.mapping import FrozenDict
from tpudas.core.timeutils import (
    build_time_grid,
    quantize_step,
    to_datetime64,
)
from tpudas.io.spool import spool as make_spool
from tpudas.obs.registry import get_registry
from tpudas.obs.trace import span
from tpudas.ops.resample import interp_indices_weights
from tpudas.proc.naming import get_filename
from tpudas.utils.logging import log_event

__all__ = ["LFProc", "PallasVerificationError", "check_merge",
           "resolve_gap_tolerance", "schedule_windows", "lowpass_resample"]


_GAP_ALIAS_WARNED = False  # the deprecated spelling warns once per process
# process_time_range calls of this process, in order: the ``job`` attr
# of every span a call opens (joins a prefetch-thread span to the
# lfproc.window it fed)
_JOBS = itertools.count(1)


def resolve_gap_tolerance(correct=None, legacy=None):
    """One value from the correctly spelled ``data_gap_tolerance`` and
    the reference's ``data_gap_tolorance`` (lf_das.py:202 — the
    misspelling IS the reference surface, kept as a deprecated alias).
    Passing both with different values is an error; using only the
    legacy spelling warns ``DeprecationWarning`` once per process.
    Returns None when neither is given."""
    global _GAP_ALIAS_WARNED
    if legacy is None:
        return correct
    if correct is not None:
        if float(correct) != float(legacy):
            raise ValueError(
                "data_gap_tolerance and its deprecated alias "
                f"data_gap_tolorance disagree ({correct!r} vs {legacy!r}); "
                "pass only data_gap_tolerance"
            )
        return correct
    if not _GAP_ALIAS_WARNED:
        _GAP_ALIAS_WARNED = True
        import warnings

        warnings.warn(
            "data_gap_tolorance is the reference's misspelling, kept as "
            "a deprecated alias; use data_gap_tolerance",
            DeprecationWarning,
            stacklevel=3,
        )
    return legacy

# first-window cross-check tolerance: the v2 kernel's 3-pass bf16 dot
# splits land ~1e-5 from the f32 XLA formulation (PERF.md §6, PR 21) and the
# cascade's design tolerance is 1e-4; a Mosaic miscompile produces
# garbage, not 1e-3-level error, so 1e-3 separates the two cleanly
_PALLAS_VERIFY_TOL = 1e-3


class PallasVerificationError(RuntimeError):
    """The Pallas kernel compiled but its first-window output disagrees
    with the XLA formulation beyond tolerance; raised out of the run."""


def _pallas_crosscheck(got, ref, what):
    """Raise :class:`PallasVerificationError` if ``got`` disagrees with
    the XLA reference beyond ``_PALLAS_VERIFY_TOL``; returns the error.

    Normalized PER CHANNEL (time axis 0): every channel flows through
    the FIR independently, so the kernel's bf16 error scales with each
    channel's own amplitude — and corruption of a quiet channel must
    not pass under a loud channel's peak.  Dead/near-zero channels are
    floored at 1e-7 of the window scale so roundoff on silence does
    not false-positive while O(window-scale) garbage still trips.  The
    1e-12 term only matters for an ALL-zero reference window (fiber
    silence), where it tolerates denormal-level kernel residue without
    being large enough to hide real output in any physical unit system
    (strain signals are ~1e-9); on zero input a correct kernel returns
    exact zeros, so anything above denormal scale should trip."""
    got = np.asarray(got)
    ref = np.asarray(ref)
    err_c = np.abs(got - ref).max(axis=0)
    scale_c = np.abs(ref).max(axis=0)
    floor = max(float(scale_c.max()) * 1e-7, 1e-12)
    rel = float((err_c / np.maximum(scale_c, floor)).max())
    if not np.isfinite(rel) or rel > _PALLAS_VERIFY_TOL:
        raise PallasVerificationError(
            f"{what} pallas-vs-xla rel err {rel:.2e} exceeds "
            f"{_PALLAS_VERIFY_TOL:g}"
        )
    return rel


def check_merge(plist):
    """Gap detector: a merged window must be exactly one patch
    (reference lf_das.py:16-20, message preserved)."""
    if len(plist) > 1:
        raise Exception("patch merge failed! Gap in data exists")
    return plist[0]


def output_corner(dt_out: float) -> float:
    """The engine's per-window filter corner: 0.9x the post-decimation
    Nyquist (reference lf_das.py:223).  Single definition shared by the
    batch path and the stateful stream path (tpudas.proc.stream) — the
    two must stay numerically identical or stateful output diverges
    from the batch oracle."""
    return 1.0 / float(dt_out) / 2.0 * 0.9


def schedule_windows(n_grid: int, patch_size: int, buff_size: int):
    """The overlap-save schedule over a time grid of ``n_grid`` points.

    Returns (sel_lo, sel_hi, emit_lo, emit_hi) index tuples into the
    grid: the window reads ``[grid[sel_lo], grid[sel_hi]]`` and emits
    output samples ``grid[emit_lo:emit_hi]``. Invariants (SURVEY.md
    §3.1): consecutive windows overlap by ``2*buff_size`` grid steps and
    emit disjoint interiors that tile ``[buff_size, ...)`` contiguously;
    the stream-start edge (first ``buff_size`` samples) is discarded.
    """
    windows = []
    if n_grid < 2:
        return windows
    if patch_size >= n_grid:
        patch_size = n_grid - 1
    if patch_size <= 2 * buff_size:
        raise ValueError(
            f"process_patch_size ({patch_size}) must exceed twice the "
            f"edge_buff_size ({buff_size}); increase the chunk length or "
            "reduce the edge buffer"
        )
    windows.append((0, patch_size, buff_size, patch_size - buff_size))
    data_end = patch_size
    new_data_end = data_end + patch_size - 2 * buff_size
    while new_data_end < n_grid:
        windows.append(
            (
                data_end - 2 * buff_size,
                new_data_end,
                data_end - buff_size,
                new_data_end - buff_size,
            )
        )
        data_end = new_data_end
        new_data_end = data_end + patch_size - 2 * buff_size
    if (n_grid - data_end) > 1:  # tail shorter than a full window
        new_data_end = n_grid - 1
        windows.append(
            (
                data_end - 2 * buff_size,
                new_data_end,
                data_end - buff_size,
                new_data_end - buff_size,
            )
        )
    return windows


@functools.partial(jax.jit, static_argnames=("nfft", "order", "ch_blocks"))
def _lowpass_resample_kernel(data, d_sec, corner, idx, w, nfft, order,
                             scale=None, ch_blocks=1):
    """Fused window kernel: zero-phase low-pass + gather-lerp decimate.

    data: (T, C) f32 — or raw int16 with ``scale``, in which case the
    dequantizing cast*scale is the kernel's first traced op so XLA
    fuses it into the FFT input read (the quantized tdas ingest path:
    half the H2D bytes, no materialized f32 intermediate).
    idx/w: (K,) gather plan into the filtered rows.
    ch_blocks: channel blocks the transform runs in, one after another
    (``fftlen.fft_channel_blocks``), so that a long window fits.
    """
    from tpudas.ops.filter import fft_lowpass_response

    def filter_resample(data):
        if scale is not None:
            data = data.astype(jnp.float32) * scale
        spec = jnp.fft.rfft(data, n=nfft, axis=0)
        resp = fft_lowpass_response(nfft, d_sec, corner, order)
        filt = jnp.fft.irfft(spec * resp[:, None], n=nfft, axis=0)
        lo = jnp.take(filt, idx, axis=0)
        hi = jnp.take(filt, idx + 1, axis=0)
        return (lo + (hi - lo) * w[:, None]).astype(jnp.float32)

    return map_channel_blocks(filter_resample, data, ch_blocks)


def lowpass_resample(data, d_sec, corner, idx, w, order=4, qscale=None,
                     nfft=None):
    """Jittable fused pipeline (also the graft-entry/bench step).
    ``nfft`` defaults to ``next_tpu_fft_len`` of the rows."""
    from tpudas.ops.fir import _check_quantized

    if qscale is not None:
        data = jnp.asarray(data)
        _check_quantized(data, qscale)
    else:
        data = jnp.asarray(data, jnp.float32)
    if nfft is None:
        nfft = next_tpu_fft_len(int(data.shape[0]))
    return _lowpass_resample_kernel(
        data,
        jnp.float32(d_sec),
        jnp.float32(corner),
        jnp.asarray(idx, jnp.int32),
        jnp.asarray(w, jnp.float32),
        nfft,
        int(order),
        scale=None if qscale is None else jnp.float32(qscale),
        ch_blocks=fft_channel_blocks(nfft, data),
    )


class LFProc:
    """Low-frequency processing engine over a source spool.

    Public surface matches the reference class exactly: construction
    from a spool, ``set_output_folder``, ``update_processing_parameter``,
    ``get_last_processed_time``, ``process_time_range``, ``parameters``.
    """

    def __init__(self, sp=None, mesh=None):
        # persistent XLA compilation cache so a restarted deployment
        # (or the next polling-round process) skips the first-window
        # compile (placement rule: tpudas.utils.compile_cache)
        from tpudas.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        self._spool = sp
        self._para = self._default_process_parameters()
        self._output_folder = None
        self.mesh = mesh  # validated by the setter below
        # windows ingested via the native tdas assembler (observability:
        # lets tests and ops confirm the fast path is actually taken)
        self.native_windows = 0
        # per-window count of the engine that ACTUALLY ran (config may
        # say "auto"; operators and the e2e bench need the ground truth
        # without enabling the log handler): a cascade window counts as
        # "cascade-pallas" when any of its stages ran the Pallas kernel,
        # "cascade-xla" otherwise; FFT-path windows count as "fft"
        self.engine_counts = {"cascade-pallas": 0, "cascade-xla": 0,
                              "fused-pallas": 0, "fused-xla": 0,
                              "fft": 0}
        # cumulative per-phase wall seconds (SURVEY.md §5 tracing row:
        # "device-time breakdown per window"): assemble = waiting on
        # the prefetch thread's window read, device = kernel dispatch
        # through host-side result sync, write = HDF5 output
        self.timings = {"assemble_s": 0.0, "device_s": 0.0, "write_s": 0.0}
        # window shapes whose Pallas output has passed the first-window
        # cross-check against the XLA formulation (jit caches per
        # shape, so each new shape is checked once).  A Pallas failure
        # of any kind raises: nothing here swaps in another engine.
        self._pallas_proven = set()
        # emission listeners: each called with every output patch
        # AFTER its HDF5 write (the realtime driver feeds the
        # serve-side tile pyramid AND the detect operators from here,
        # so neither per-round consumer re-reads the files it just
        # watched being written, and registering one cannot clobber
        # another).  Listener failures are counted and swallowed — a
        # read-side consumer must not take down the write path.
        self._emit_listeners: list = []
        # listeners that raised THIS round: skipped for the remaining
        # emissions of the round so one broken consumer fails once,
        # not once per output patch (cleared by the driver's next
        # round via clear_emit_failures)
        self._failed_listeners: set = set()
        # cross-check the first Pallas window of each shape against the
        # XLA formulation (off: TPUDAS_PALLAS_VERIFY=0) — a Mosaic
        # miscompile returning silently wrong numbers must not ship
        self._pallas_verify = (
            os.environ.get("TPUDAS_PALLAS_VERIFY", "1") != "0"
        )
        self._run_origin_ns = None  # set per process_time_range run
        # attrs of the main thread's window-loop spans: the run's job,
        # and the window in hand once the loop has one
        self._span_tags = {}
        self._job_windows = 0  # windows scheduled so far in the job
        self._first_window_of_run = True
        self._dp_proven = set()  # DP keys whose batched kernel passed

    # configuration ----------------------------------------------------
    def _default_process_parameters(self):
        # the four reference keys (lf_das.py:197-204; the
        # "data_gap_tolorance" spelling is the reference's, kept for
        # compat — see on_gap for the implemented gap policy) plus
        # tpudas extensions.
        return {
            "output_sample_interval": 1.0,  # seconds
            "process_patch_size": 100,  # output samples per window
            "edge_buff_size": 10,  # output samples of trimmed halo
            # ONE meaning everywhere (the reference declares this key
            # but never reads it, lf_das.py:202 — tpudas implements the
            # promise): a hole between consecutive files of at most
            # this many seconds is NOT a gap. The window merge bridges
            # it by linear interpolation (event "gap_filled"; harmless
            # to the LF band this pipeline extracts), and the split
            # planner keeps the schedule in one segment across it.
            # Anything wider IS a gap, handled per on_gap below.
            "data_gap_tolorance": 10.0,
            # "raise": reference behavior (merge failure halts the run,
            # lf_das.py:16-20). "skip": drop windows touching a gap.
            # "split": segment the time grid at index-detected gaps
            # wider than data_gap_tolorance and run overlap-save per
            # segment, emitting per-segment output (the behavior the
            # reference's dead data_gap_tolorance parameter promises,
            # lf_das.py:202, SURVEY.md §5).
            "on_gap": "raise",
            "filter_order": 4,
            # "auto": multistage polyphase FIR cascade (tpudas.ops.fir,
            # Pallas on TPU) when the target grid is sample-aligned and
            # the ratio factors; FFT engine otherwise. "fft"/"cascade"
            # force one path. "fused" = cascade whose STREAM path runs
            # the fused single-kernel formulation (ISSUE 10: all stage
            # states resident, no per-stage HBM intermediates); batch
            # windows under "fused" run the ordinary cascade.
            "engine": "auto",
            # window-level DATA parallelism (BASELINE "spool chunks
            # pmapped"): with a mesh whose "time" axis has size > 1,
            # batches of same-shape cascade-aligned windows run
            # together, one window per time-axis slot, channels still
            # sharded over "ch". Windows that do not line up (edges,
            # gaps, FFT-path grids) fall back to per-window execution.
            # Repurposes the time axis: window-internal time sharding
            # is off while this is set.
            "window_dp": False,
        }

    _ENGINES = ("auto", "fft", "cascade", "fused")
    _GAP_MODES = ("raise", "skip", "split")

    # mesh execution ----------------------------------------------------
    @property
    def mesh(self):
        """Optional :class:`jax.sharding.Mesh` the per-window kernels
        run over (BASELINE configs 4-5 made first-class): channels are
        split over the mesh's ``"ch"`` axis (zero communication), and —
        when the mesh has a ``"time"`` axis of size > 1 and the window
        is cascade-aligned — the time axis is sharded too, with halo
        exchange over ICI neighbors (tpudas.parallel.pipeline). The
        stateful stream path (:meth:`process_stream_increment`) shards
        over a channel-only mesh with a device-resident carry. ``None``
        (default) runs single-device, as the reference does
        (lf_das.py:236 single-process select/broadcast)."""
        return self._mesh

    @mesh.setter
    def mesh(self, mesh):
        if mesh is not None and "ch" not in mesh.shape:
            raise ValueError(
                "LFProc mesh needs a 'ch' axis (use "
                "tpudas.parallel.mesh.make_mesh); got axes "
                f"{tuple(mesh.shape)}"
            )
        self._mesh = mesh

    def update_processing_parameter(self, **kwargs):
        if "data_gap_tolerance" in kwargs or "data_gap_tolorance" in kwargs:
            # the parameters dict keeps the reference's key (compat);
            # the correctly spelled kwarg is the public spelling
            v = resolve_gap_tolerance(
                kwargs.pop("data_gap_tolerance", None),
                kwargs.pop("data_gap_tolorance", None),
            )
            if v is not None:
                kwargs["data_gap_tolorance"] = v
        for key, value in kwargs.items():
            if key not in self._para:
                print(f"{key} is not default parameter key")
            elif key == "engine" and value not in self._ENGINES:
                raise ValueError(
                    f"engine must be one of {self._ENGINES}, got {value!r}"
                )
            elif key == "on_gap" and value not in self._GAP_MODES:
                raise ValueError(
                    f"on_gap must be one of {self._GAP_MODES}, got {value!r}"
                )
            else:
                self._para[key] = value
        return self.parameters

    @property
    def parameters(self):
        return FrozenDict(self._para)

    # output folder / resume ------------------------------------------
    @staticmethod
    def _setup_folder(folder, delete_existing):
        """Shared create/wipe behavior for every output folder (the LF
        product here, joint products in subclasses) — messages match
        the reference (lf_das.py:188-195)."""
        if delete_existing and os.path.isdir(folder):
            shutil.rmtree(folder)
            print(f"original {folder} deleted")
        if not os.path.isdir(folder):
            os.makedirs(folder)
            print(f"{folder} created")

    def set_output_folder(self, folder, delete_existing=False):
        self._output_folder = folder
        self._setup_folder(folder, delete_existing)

    def add_emit_listener(self, fn) -> None:
        """Subscribe ``fn(result_patch)`` to every output emission
        (called after the HDF5 write).  Multiple subscribers coexist —
        the realtime driver registers one capture per consumer
        (pyramid append, detect operators); failures are counted and
        swallowed at the emit site."""
        self._emit_listeners.append(fn)

    def clear_emit_failures(self) -> None:
        """Re-arm listeners skipped after raising (the per-round
        reset: a consumer that failed on round N's emissions gets a
        fresh chance on round N+1)."""
        self._failed_listeners.clear()

    def get_last_processed_time(self):
        """Resume primitive: progress state lives entirely in the output
        files (crash-only design, lf_das.py:214-217)."""
        out_sp = make_spool(self._output_folder).sort("time").update()
        return out_sp[-1].attrs["time_max"]

    # stateful streaming ----------------------------------------------
    def open_stream(self, start_time):
        """A fresh :class:`tpudas.proc.stream.StreamCarry` for this
        engine's parameters, anchored at ``start_time`` — the resumable
        alternative to the window path: instead of padding + trimming
        edges every call, the carry holds each filter stage's O(1)
        trailing state and :meth:`process_stream_increment` extends the
        output without re-reading anything."""
        from tpudas.proc.stream import open_stream

        return open_stream(self, start_time)

    def process_stream_increment(self, carry, edtime):
        """Process all NEW data up to ``edtime`` through the carried
        filter state (cascade per-stage carry or FFT overlap-save
        carry), writing output files and advancing ``carry`` in place.
        Returns the number of output samples emitted.  Numerically
        matches :meth:`process_time_range` over the same span (the
        batch path is the oracle; see tests/test_stream_state.py).

        With a channel-only :attr:`mesh`, the stream steps run under
        ``shard_map`` with channels split over ``"ch"`` and the carry
        leaves stay SHARDED on the mesh between calls (pad-and-mask at
        non-divisible widths; byte-identical to the single-device run
        — tests/test_parallel.py pins it end to end).

        Ingest is pipelined (``TPUDAS_INGEST_PREFETCH``, default 2): a
        bounded prefetch thread reads + decodes the next slice while
        the device computes the current one, and raw int16 payloads
        dequantize inside the first kernel exactly like this class's
        batch windows do — byte-identical to the synchronous loop
        (PERF.md §6, PR 15; tests/test_ingest.py pins it)."""
        if self._output_folder is None:
            raise Exception("Please setup output folder first")
        from tpudas.proc.stream import process_increment

        before = dict(self.timings)
        try:
            return process_increment(self, carry, edtime)
        finally:
            self._mirror_timings(before)

    def _mirror_timings(self, before: dict) -> None:
        """Mirror this run's phase-timing DELTAS (``self.timings`` is
        cumulative per LFProc) into the obs registry — one call per
        driver entry point keeps the per-window hot paths free of
        registry traffic."""
        reg = get_registry()
        for key, metric, help_ in (
            ("assemble_s", "tpudas_window_assemble_seconds_total",
             "wall seconds waiting on window read + H2D staging"),
            ("device_s", "tpudas_window_device_seconds_total",
             "wall seconds in kernel dispatch through host sync"),
            ("write_s", "tpudas_window_write_seconds_total",
             "wall seconds writing HDF5 outputs"),
        ):
            delta = self.timings.get(key, 0.0) - before.get(key, 0.0)
            if delta > 0:
                reg.counter(metric, help_).inc(delta)

    @contextlib.contextmanager
    def _step(self, name, key, **attrs):
        """One step of the window loop: ``span(name)`` with the
        window's attrs (and ``attrs``), its duration added to
        ``timings[key]`` from the span's own clock reads (a
        perf_counter pair of its own when spans are off)."""
        t0 = time.perf_counter()
        with span(name, **self._span_tags, **attrs) as rec:
            yield
        self.timings[key] += (
            rec["duration_s"] if rec is not None
            else time.perf_counter() - t0
        )

    @staticmethod
    def _put_window(host, sharding=None, **tags):
        """H2D of one window's payload: the ``lfproc.h2d`` span from
        the ``device_put`` call until the array is ready, and the bytes
        shipped as handed over (raw int16 for quantized windows)."""
        with span("lfproc.h2d", **tags):
            arr = jax.device_put(host, sharding)
            arr.block_until_ready()
        get_registry().counter(
            "tpudas_window_h2d_bytes_total",
            "window payload bytes handed to device_put, as shipped",
        ).inc(host.nbytes)
        return arr

    # the engine -------------------------------------------------------
    def _load_window(self, t_lo, t_hi, on_gap, **tags):
        """Host side: read + merge one window from the source spool
        (``tags``: the window's span attrs).

        tdas-backed directory spools take the native fast path: per-file
        row segments are planned from the index alone and the C++
        threaded assembler fills ONE contiguous buffer (no per-file
        Patch objects, no numpy merge copy), its reader threads pulling
        row chunks of every segment, and puts the chunk and thread
        counts on the ``lfproc.load_window`` span; the block goes
        straight to the device kernels (SURVEY.md §3.1 hot loops #2/#3;
        reference lf_das.py:236-239).
        """
        plan_fn = getattr(self._spool, "native_window_plan", None)
        if plan_fn is not None:
            plan = plan_fn(t_lo, t_hi)
            if plan is not None:
                from tpudas.io.tdas import assemble_window_patch

                self.native_windows += 1
                with span(
                    "lfproc.load_window", native=True,
                    files=len(plan["segments"]), rows=plan["total_rows"],
                    payload=plan.get("payload", "float32"), **tags,
                ):
                    return assemble_window_patch(plan)
        with span("lfproc.load_window", native=False, **tags):
            selected = self._spool.select(time=(t_lo, t_hi))
            # data_gap_tolorance's single meaning (see
            # _default_process_parameters): holes up to that many
            # seconds are not gaps — the merge bridges them by linear
            # interpolation (the native planner above already declined
            # such windows, so gappy windows always take this path)
            plist = make_spool(selected).chunk(
                time=None,
                max_fill=float(self._para["data_gap_tolorance"]),
            )
            if len(plist) == 0:
                if on_gap == "raise":
                    raise Exception(
                        "patch merge failed! Gap in data exists"
                    )
                return None
            try:
                return check_merge(plist)
            except Exception:
                if on_gap == "raise":
                    raise
                return None

    def _split_grid_at_gaps(self, time_grid):
        """[(g_lo, g_hi), ...] index ranges of ``time_grid`` covered by
        contiguous data, split at gaps wider than data_gap_tolorance
        seconds (detected from the spool index — no payload IO)."""
        if len(time_grid) == 0:
            return []
        tol_ns = float(self._para["data_gap_tolorance"]) * 1e9
        df = self._spool.get_contents()
        if df is None or len(df) == 0:
            return []
        mins = df["time_min"].to_numpy().astype("datetime64[ns]")
        maxs = df["time_max"].to_numpy().astype("datetime64[ns]")
        order = np.argsort(mins, kind="stable")
        mins, maxs = mins[order].astype(np.int64), maxs[order].astype(
            np.int64
        )
        # merge file intervals into coverage runs; a separation wider
        # than the tolerance starts a new run
        runs = []
        run_lo, run_hi = mins[0], maxs[0]
        for lo, hi in zip(mins[1:], maxs[1:]):
            if lo - run_hi > tol_ns:
                runs.append((run_lo, run_hi))
                run_lo, run_hi = lo, hi
            else:
                run_hi = max(run_hi, hi)
        runs.append((run_lo, run_hi))
        grid_ns = time_grid.astype("datetime64[ns]").astype(np.int64)
        segments = []
        for lo, hi in runs:
            g_lo = int(np.searchsorted(grid_ns, lo, side="left"))
            g_hi = int(np.searchsorted(grid_ns, hi, side="right"))
            if g_hi - g_lo >= 2:
                segments.append((g_lo, g_hi))
        return segments

    def process_time_range(self, bgtime, edtime):
        """Chunked overlap-save low-pass + decimate over [bg, ed)."""
        if self._output_folder is None:
            raise Exception("Please setup output folder first")
        dt = self._para["output_sample_interval"]
        on_gap = self._para["on_gap"]

        bgtime = to_datetime64(bgtime)
        edtime = to_datetime64(edtime)
        # run anchor for joint products whose output grid is phased in
        # input samples (see tpudas.proc.joint); also marks the run's
        # first window (whose rolling warm-up may legitimately clamp)
        self._run_origin_ns = int(
            bgtime.astype("datetime64[ns]").astype(np.int64)
        )
        self._first_window_of_run = True
        time_grid = build_time_grid(bgtime, edtime, dt)
        if on_gap == "split":
            # a globally invalid patch/buff relation must fail loudly
            # here — per-segment scheduling errors are otherwise
            # swallowed as "segment too short"
            patch_size = self._para["process_patch_size"]
            buff_size = self._para["edge_buff_size"]
            if patch_size <= 2 * buff_size:
                raise ValueError(
                    f"process_patch_size ({patch_size}) must exceed "
                    f"2*edge_buff_size ({2 * buff_size})"
                )
            segments = self._split_grid_at_gaps(time_grid)
            if not segments:
                # completing silently here would look exactly like a
                # successful run with output — say loudly that nothing
                # in [bg, ed) was processable
                print(
                    "Warning: no data coverage found in "
                    f"[{bgtime} .. {edtime}) — nothing was processed "
                    "(on_gap='split')"
                )
                log_event(
                    "split_no_coverage",
                    bgtime=str(bgtime),
                    edtime=str(edtime),
                    grid_points=len(time_grid),
                )
        else:
            segments = [(0, len(time_grid))]
        # TPUDAS_TRACE_DIR: capture a device profiler trace of the whole
        # run (jax.profiler; SURVEY.md §5 tracing row)
        trace_dir = os.environ.get("TPUDAS_TRACE_DIR")
        if trace_dir:
            from tpudas.utils.profiling import device_trace

            trace_cm = device_trace(trace_dir)
        else:
            trace_cm = contextlib.nullcontext()
        before = dict(self.timings)
        self._span_tags = {"job": next(_JOBS)}
        self._job_windows = 0
        try:
            with trace_cm, span(
                "lfproc.process_time_range",
                grid_points=len(time_grid),
                segments=len(segments),
                **self._span_tags,
            ):
                total_windows = self._process_segments(
                    time_grid, segments, on_gap
                )
        finally:
            # the run anchor must not leak into later direct
            # _process_window use (whose documented fallback is a
            # window-local origin)
            self._run_origin_ns = None
            self._first_window_of_run = True
            self._span_tags = {}
            self._mirror_timings(before)
        log_event(
            "process_time_range_done",
            windows=total_windows,
            grid_points=len(time_grid),
            segments=len(segments),
            timings={k: round(v, 4) for k, v in self.timings.items()},
        )

    def _process_segments(self, time_grid, segments, on_gap) -> int:
        total_windows = 0
        for s_i, (g_lo, g_hi) in enumerate(segments):
            if len(segments) > 1:
                print(
                    f"Processing segment {s_i + 1}/{len(segments)} "
                    f"[{time_grid[g_lo]} .. {time_grid[g_hi - 1]}]"
                )
                log_event(
                    "segment_start",
                    index=s_i + 1,
                    segments=len(segments),
                    grid_points=g_hi - g_lo,
                )
            total_windows += self._process_segment(
                time_grid[g_lo:g_hi], on_gap
            )
        return total_windows

    def _process_segment(self, time_grid, on_gap) -> int:
        """Overlap-save over one contiguous grid segment; returns the
        number of scheduled windows."""
        dt = self._para["output_sample_interval"]
        patch_size = self._para["process_patch_size"]
        buff_size = self._para["edge_buff_size"]
        order = self._para["filter_order"]
        if on_gap == "split" and len(time_grid) - 1 <= 2 * buff_size:
            # a between-gaps segment too short for the halo: nothing
            # recoverable there, but the run must go on (the global
            # patch/buff config was validated in process_time_range)
            log_event("segment_too_short", grid_points=len(time_grid))
            return 0
        windows = schedule_windows(len(time_grid), patch_size, buff_size)
        corner = output_corner(dt)

        if (
            self._para.get("window_dp")
            and self._mesh is not None
            and self._mesh.shape.get("time", 1) > 1
        ):
            return self._process_segment_dp(
                time_grid, windows, on_gap, dt, corner, order
            )

        with contextlib.closing(self._iter_windows(
            time_grid, windows, on_gap, self._load_and_stage
        )) as loop:
            for i, loaded, emit_times in loop:
                window_patch, staged = loaded
                if window_patch is None:
                    log_event("window_skipped_gap", index=i + 1)
                    continue
                self._process_window(
                    window_patch, emit_times, dt, corner, order,
                    staged=staged,
                )
        return len(windows)

    def _iter_windows(self, time_grid, windows, on_gap, loader):
        """Prefetching window iterator shared by the serial and
        window-DP drivers: ``loader(bg, ed, on_gap, **tags)`` runs one
        window ahead on the worker thread; yields ``(i, loaded,
        emit_times)``.  Each window's main-thread time is one
        ``lfproc.window`` span, open from before the wait on its load
        (``lfproc.ingest_wait``, accounted as ``assemble_s``) until the
        consumer asks for the next window — close the iterator
        (``contextlib.closing``) so a failing window closes it too."""
        outer = self._span_tags  # the job's
        base = self._job_windows
        self._job_windows += len(windows)

        def submit(k):
            bg, ed = windows[k][0], windows[k][1]
            return pool.submit(loader, time_grid[bg], time_grid[ed], on_gap,
                               **outer, window=base + k)

        with ThreadPoolExecutor(max_workers=1) as pool:
            future = submit(0) if windows else None
            try:
                for i, (_, _, emit_lo, emit_hi) in enumerate(windows):
                    print("Processing patch ", str(i + 1))
                    self._span_tags = {**outer, "window": base + i}
                    with span("lfproc.window", **self._span_tags):
                        with self._step("lfproc.ingest_wait", "assemble_s"):
                            loaded = future.result()
                        if i + 1 < len(windows):
                            future = submit(i + 1)
                        yield i, loaded, time_grid[emit_lo:emit_hi]
            finally:
                self._span_tags = outer

    def _dp_window_info(self, window_patch, target_times, dt, corner, order):
        """Batchability probe for the window-DP driver: the (plan,
        phase, n_out, shape, dtype, qscale) key a window must share
        with its batch — or ``None`` when the window needs the full
        per-window path (FFT-aligned grids, undersized halos, engine
        config 'fft')."""
        if self._para.get("engine", "auto") not in (
            "auto", "cascade", "fused"
        ):
            return None
        if target_times.size == 0:
            return None
        from tpudas.ops.fir import design_cascade, edge_support_samples

        host, qs = self._time_major_payload(window_patch)
        taxis = window_patch.coords["time"]
        d_sec = window_patch.get_sample_step("time")
        align = self._cascade_alignment(taxis, target_times, d_sec, dt)
        if align is None:
            return None
        ratio, phase = align
        plan = design_cascade(1.0 / d_sec, ratio, corner, int(order))
        supp = edge_support_samples(plan, 1e-3)
        tail = host.shape[0] - (phase + (target_times.size - 1) * ratio)
        if supp > phase or supp >= tail:
            return None  # edge-artifact window: per-window path warns
        # host-residency budget (the serial path's _STAGE_MAX_BYTES
        # analogue): at flush time all nb pending windows are resident
        # PLUS their nb-window np.stack copy -> peak ~2*nb windows
        nb = self._mesh.shape["time"]
        if host.nbytes * nb * 2 > self._DP_MAX_BATCH_BYTES:
            return None
        key = (
            plan, phase, int(target_times.size), host.shape,
            str(host.dtype), qs,
        )
        return {"key": key, "host": host, "plan": plan, "phase": phase,
                "n_out": int(target_times.size), "qs": qs}

    # cap on (batch windows + stack copy) host bytes before window-DP
    # degrades to per-window execution — mirrors _STAGE_MAX_BYTES
    _DP_MAX_BATCH_BYTES = 8 << 30

    def _process_segment_dp(self, time_grid, windows, on_gap, dt, corner,
                            order) -> int:
        """Window-level data parallelism over the overlap-save
        schedule: consecutive windows sharing one (plan, phase, n_out,
        shape, dtype, scale) batch over the mesh's "time" axis (one
        window per slot, channels still over "ch") and are bit-equal
        to per-window execution; anything that does not line up takes
        the normal per-window path."""
        from tpudas.ops.fir import stage_engines
        from tpudas.parallel.batch import batched_cascade_decimate

        mesh = self._mesh
        nb = mesh.shape["time"]
        pending = []  # [(patch, emit_times, info)]

        def run_batch():
            """Device compute only — emission happens in flush(), so a
            failure here cannot double-emit already-written windows."""
            infos = [p[2] for p in pending]
            plan = infos[0]["plan"]
            phase = infos[0]["phase"]
            n_out = infos[0]["n_out"]
            qs = infos[0]["qs"]
            stack = np.stack([i["host"] for i in infos])
            n_ch_local = -(-stack.shape[2] // mesh.shape["ch"])
            stages = stage_engines(plan, n_out, n_ch_local, "auto")
            ran = "cascade-pallas" if "pallas" in stages else "cascade-xla"
            with self._step("lfproc.dispatch", "device_s"):
                out = batched_cascade_decimate(
                    mesh, stack, plan, phase, n_out, engine="auto",
                    batch_axis="time", ch_axis="ch", qscale=qs,
                )
            with self._step("lfproc.sync", "device_s"):
                out = np.asarray(out)
            key = infos[0]["key"]
            if (
                self._pallas_verify
                and ran == "cascade-pallas"
                and key not in self._dp_proven
            ):
                # the batched kernel is a different lowering (extra
                # window axis) than the per-window path, so it gets its
                # own first-batch cross-check: window 0 of the batch vs
                # the unbatched XLA formulation.  A mismatch raises
                # PallasVerificationError out of the run.
                from tpudas.ops.fir import cascade_decimate

                # mesh=mesh: the reference must shard channels the same
                # way the per-window path does, or window 0 of a wide
                # (north-star-scale) config lands whole on one device
                # and OOMs
                ref = cascade_decimate(
                    stack[0], plan, phase, n_out, "xla", mesh=mesh,
                    qscale=qs,
                )
                rel = _pallas_crosscheck(out[0], ref, "window-DP batch")
                log_event("pallas_crosscheck_dp", rel_err=rel)
                self._dp_proven.add(key)
            return out, ran, int(stack.shape[1])

        def flush():
            if not pending:
                return
            if len(pending) == 1:
                patch, emit_times, _ = pending[0]
                self._process_window(patch, emit_times, dt, corner, order)
                pending.clear()
                return
            out, ran, rows = run_batch()
            log_event(
                "window_dp_batch", windows=len(pending), engine=ran,
                rows=rows, emitted=int(pending[0][2]["n_out"]),
            )
            for i, (patch, emit_times, info) in enumerate(pending):
                # joint extras run here too (the per-window hook is
                # bypassed by batched execution); before the LF write,
                # same crash-ordering contract as _process_window
                with self._step("lfproc.write", "write_s"):
                    self._emit_window_extras(
                        patch, info["host"], info["qs"],
                        patch.coords["time"], emit_times, dt,
                        patch.get_sample_step("time"),
                    )
                    self._emit_window_output(
                        patch, emit_times, dt, out[i], ran, rows=rows,
                    )
            pending.clear()

        with contextlib.closing(self._iter_windows(
            time_grid, windows, on_gap, self._load_window
        )) as loop:
            for i, window_patch, emit_times in loop:
                if window_patch is None:
                    flush()
                    log_event("window_skipped_gap", index=i + 1)
                    continue
                info = self._dp_window_info(
                    window_patch, emit_times, dt, corner, order
                )
                if info is None:
                    flush()
                    self._process_window(
                        window_patch, emit_times, dt, corner, order
                    )
                    continue
                if pending and info["key"] != pending[0][2]["key"]:
                    flush()
                pending.append((window_patch, emit_times, info))
                if len(pending) == nb:
                    flush()
        flush()
        return len(windows)

    @staticmethod
    def _time_major_payload(window_patch):
        """(time-major host array, qscale-or-None): the single source
        of the quantized-ingest predicate and axis normalization,
        shared by the prefetch-thread staging and _process_window so
        the staged dtype can never desync from the ``qs`` flag."""
        ax = window_patch.axis_of("time")
        host = window_patch.host_data()
        if ax != 0:
            host = np.moveaxis(host, ax, 0)
        qscale = window_patch.attrs.get("data_scale")
        if host.dtype == np.int16 and qscale is not None:
            return host, float(qscale)
        return host, None

    # windows larger than this are not pre-staged: staging keeps TWO
    # windows resident (the computing one + the transferring one), and
    # doubling a huge window's footprint can OOM configurations the
    # serial path fits.  TPUDAS_H2D_STAGE=0 disables staging outright.
    _STAGE_MAX_BYTES = 2 << 30

    def _load_and_stage(self, bg, ed, on_gap, **tags):
        """Prefetch-thread body: assemble the window, then transfer it
        to the device so H2D overlaps the previous window's device
        compute and output write (the ingest pipeline is assemble ->
        stage -> compute -> write; the reference's loop is fully
        serial, lf_das.py:291-306).  This thread waits for the transfer
        (``lfproc.h2d``), so the main thread waits only in
        ``lfproc.ingest_wait``.  Returns (patch, staged): ``staged`` is
        the time-major device array (raw int16 for quantized windows)
        or None when staging does not apply (mesh runs place data with
        their own shardings)."""
        window_patch = self._load_window(bg, ed, on_gap, **tags)
        if (
            window_patch is None
            or self._mesh is not None
            or os.environ.get("TPUDAS_H2D_STAGE", "1") == "0"
        ):
            return window_patch, None
        host, qscale = self._time_major_payload(window_patch)
        # budget-check the PROJECTED device footprint before paying the
        # host-side conversion copy (2 B/sample raw int16, else f32)
        es = 2 if qscale is not None else 4
        if host.size * es > self._STAGE_MAX_BYTES:
            return window_patch, None
        if qscale is None:
            host = np.ascontiguousarray(host, dtype=np.float32)
        try:
            staged = self._put_window(host, **tags)
        except Exception as exc:  # pragma: no cover - backend-specific
            log_event("stage_h2d_failed", error=str(exc)[:200])
            return window_patch, None
        return window_patch, staged

    def _cascade_alignment(self, taxis, target_times, d_sec, dt):
        """If the (ms-quantized) target grid lands exactly on input
        samples and the decimation ratio is a small-prime integer,
        return (ratio, phase) for the cascade engine; else None.

        The ratio is derived from the actual target-grid spacing (the
        quantized step from build_time_grid), NOT the configured float
        interval — the two differ when dt is not a whole ms.  A final
        tail window can emit a single grid point (schedule_windows
        yields emit size 1 when ``n_grid - data_end == 2``); with no
        second sample to difference, the step falls back to the
        run-level quantized grid step, which is what the slice was cut
        from — the cascade stays usable instead of raising mid-run.
        """
        if target_times.size == 0:
            return None
        t_ns = target_times.astype("datetime64[ns]").astype(np.int64)
        if target_times.size >= 2:
            step_ns = t_ns[1] - t_ns[0]
        else:
            step_ns = int(
                quantize_step(dt).astype("timedelta64[ns]").astype(np.int64)
            )
        if step_ns <= 0 or np.any(np.diff(t_ns) != step_ns):
            return None
        dsec_ns = float(d_sec) * 1e9
        ratio_f = step_ns / dsec_ns
        ratio = int(round(ratio_f))
        if ratio < 1 or abs(ratio_f - ratio) > 1e-6 * max(ratio, 1):
            return None
        t0 = taxis[0].astype("datetime64[ns]").astype(np.int64)
        f0 = (t_ns[0] - t0) / dsec_ns
        phase = int(round(f0))
        if phase < 0 or abs(f0 - phase) > 1e-3:
            return None
        try:
            from tpudas.ops.fir import factor_ratio

            factor_ratio(ratio)
        except ValueError:
            return None
        return ratio, phase

    def _process_window(self, window_patch, target_times, dt, corner, order,
                        staged=None):
        """Device side: fused filter+decimate, then write the interior.

        ``staged`` is the window's time-major device array when the
        prefetch thread already started the H2D transfer
        (:meth:`_load_and_stage`); host-side decisions still read the
        numpy view, only the device payload is substituted."""
        if target_times.size == 0:
            return
        ax = window_patch.axis_of("time")
        host, qs = self._time_major_payload(window_patch)
        taxis = window_patch.coords["time"]
        d_sec = window_patch.get_sample_step("time")
        # coverage invariant: every emitted grid point must lie inside
        # the loaded data (one input step of slack for the stream-tail
        # grid point that lands just past the final sample).  Without
        # this, a hole whose edges align with window selection bounds
        # slips past the merge's gap detection and the engine silently
        # extrapolates output where there is no data.
        slack = np.timedelta64(int(round(d_sec * 1e9)), "ns")
        cov_lo = taxis[0].astype("datetime64[ns]") - slack
        cov_hi = taxis[-1].astype("datetime64[ns]") + slack
        if (
            target_times[0].astype("datetime64[ns]") < cov_lo
            or target_times[-1].astype("datetime64[ns]") > cov_hi
        ):
            log_event(
                "window_coverage_gap",
                data=[str(taxis[0]), str(taxis[-1])],
                emit=[str(target_times[0]), str(target_times[-1])],
            )
            if self._para.get("on_gap", "raise") == "raise":
                raise Exception("patch merge failed! Gap in data exists")
            print(
                "Warning: window data does not cover its output range; "
                "skipping (on_gap)"
            )
            return
        engine = self._para.get("engine", "auto")
        if engine not in self._ENGINES:
            raise ValueError(
                f"engine must be one of {self._ENGINES}, got {engine!r}"
            )
        align = None
        if engine in ("auto", "cascade", "fused"):
            align = self._cascade_alignment(taxis, target_times, d_sec, dt)
            if align is None and engine in ("cascade", "fused"):
                raise ValueError(
                    f"engine={engine!r} requires the output grid to land "
                    "on input samples with an integer small-prime "
                    "decimation ratio; use engine='auto' or 'fft'"
                )
        if align is not None:
            from tpudas.ops.fir import (
                cascade_decimate,
                design_cascade,
                edge_support_samples,
            )

            ratio, phase = align
            plan = design_cascade(1.0 / d_sec, ratio, corner, int(order))
            # the edge halo must cover the cascade's (tol-thresholded)
            # filter support on both sides, or the emitted interior
            # carries edge artifacts — same contract the reference's
            # probe enforces for the buffer (lf_das.py:79-85)
            supp = edge_support_samples(plan, 1e-3)
            # samples strictly after the last emitted output's index:
            # its support needs i_last + supp <= T-1, i.e. supp < tail
            tail = host.shape[0] - (phase + (target_times.size - 1) * ratio)
            if supp > phase or supp >= tail:
                log_event(
                    "cascade_halo_too_small",
                    support=supp,
                    phase=phase,
                    tail=int(tail),
                )
                if engine in ("cascade", "fused"):
                    print(
                        "Warning: edge_buff_size halo is smaller than the "
                        f"cascade filter support ({supp} input samples); "
                        "emitted edges may carry artifacts"
                    )
                else:
                    align = None  # auto: fall back to the FFT engine
        mesh = self._mesh
        n_out = int(target_times.size)
        # which execution layout will this window take? decided up
        # front so the engine observability below reports exactly what
        # each device traces: under a mesh the Pallas size threshold
        # sees the LOCAL channel count, and under time sharding the
        # LOCAL output count
        time_layout = None
        if (
            align is not None
            and mesh is not None
            and mesh.shape.get("time", 1) > 1
        ):
            from tpudas.parallel.pipeline import sharded_cascade_layout

            time_layout = sharded_cascade_layout(
                mesh, plan, phase, n_out, int(host.shape[0]),
                n_ch_local=-(-int(host.shape[1]) // mesh.shape["ch"]),
                engine="auto",
            )
        # which engine will this window run under? (config says
        # "auto"/"cascade"; the layout decides per stage from shape)
        n_ch_decide = int(host.shape[1])
        if mesh is not None:
            n_ch_decide = -(-n_ch_decide // mesh.shape["ch"])
        if align is not None:
            from tpudas.ops.fir import stage_engines

            n_out_decide = time_layout[0] if time_layout else n_out
            stages = stage_engines(plan, n_out_decide, n_ch_decide, "auto")
            ran = (
                "cascade-pallas" if "pallas" in stages else "cascade-xla"
            )
        else:
            ran = "fft"
        # quantized windows (qs set by _time_major_payload) ship the
        # raw int16 payload and dequantize INSIDE the first device
        # kernel — half the transfer bytes AND half the first stage's
        # HBM read, with no intermediate f32 round trip
        if staged is not None:
            host32 = staged  # already on the device (prefetch thread)
        elif qs is not None:
            host32 = host
        else:
            host32 = host.astype(np.float32, copy=False)
        # an FFT window's dispatch span says which transform length ran
        fft_attrs = (
            {"nfft": next_tpu_fft_len(int(host.shape[0]))}
            if ran == "fft" else {}
        )
        with self._step("lfproc.dispatch", "device_s", **fft_attrs):
            if align is not None:
                def _run_cascade(eng):
                    if time_layout is not None:
                        from tpudas.parallel.pipeline import (
                            sharded_cascade_decimate,
                        )

                        o = sharded_cascade_decimate(
                            mesh, host32, plan, phase, n_out, engine=eng,
                            qscale=qs,
                        )
                        if o is not None:
                            return o
                    return cascade_decimate(
                        host32, plan, phase, n_out, eng, mesh=mesh, qscale=qs
                    )

                shape_key = (
                    plan.ratio, plan.delay, int(host.shape[0]), n_out,
                    int(host.shape[1]), time_layout is not None,
                    str(host.dtype),  # int16 vs f32 payloads compile apart
                )

                def _run_checked(eng):
                    o = _run_cascade(eng)
                    if (
                        self._pallas_verify
                        and ran == "cascade-pallas"
                        and shape_key not in self._pallas_proven
                    ):
                        # first window of an unproven shape: cross-check
                        # the Pallas output against the XLA formulation on
                        # the SAME window.  A Mosaic miscompile that returns
                        # silently wrong numbers raises here instead of
                        # shipping.  Costs one extra XLA run on the first
                        # window of each shape.
                        rel = _pallas_crosscheck(
                            o, np.asarray(_run_cascade("xla")), "first window"
                        )
                        log_event(
                            "pallas_crosscheck", rel_err=rel,
                            shape=list(host.shape),
                        )
                    return o

                out = _run_checked("auto")
                if ran == "cascade-pallas":
                    self._pallas_proven.add(shape_key)
            else:
                idx, w = interp_indices_weights(taxis, target_times)
                data = host32
                n_ch = data.shape[1]
                pad_c = 0
                if mesh is not None:
                    from jax.sharding import NamedSharding, PartitionSpec as P

                    # channel sharding only: the FFT runs along the
                    # replicated time axis, so XLA partitions it over the
                    # channel batch dimension with zero collectives.
                    # Channels are zero-padded to the shard multiple (each
                    # channel is independent, so real columns are
                    # unaffected) and trimmed below.
                    pad_c = -n_ch % mesh.shape["ch"]
                    if pad_c:
                        pad_fn = (
                            jnp.pad if isinstance(data, jax.Array) else np.pad
                        )
                        data = pad_fn(data, ((0, 0), (0, pad_c)))
                    data = self._put_window(
                        data, NamedSharding(mesh, P(None, "ch")),
                        **self._span_tags,
                    )
                out = lowpass_resample(
                    data, d_sec, corner, idx, w, order=order, qscale=qs,
                    nfft=fft_attrs["nfft"],
                )
                if pad_c:
                    out = out[:, :n_ch]
        with self._step("lfproc.sync", "device_s"):
            out = np.asarray(out)
        # joint products (tpudas.proc.joint.JointProc): additional
        # outputs computed from the SAME loaded window/payload — one
        # ingest pass, several products.  No-op in the base engine.
        # Emitted BEFORE the LF file: resume state is the LF output
        # folder, so a crash between the two writes must leave the
        # window unmarked-as-done (the rolling file is then simply
        # rewritten on resume — filenames are deterministic) rather
        # than leave a permanent hole in the rolling stream.
        with self._step("lfproc.write", "write_s"):
            self._emit_window_extras(
                window_patch, staged if staged is not None else host, qs,
                taxis, target_times, dt, d_sec,
            )
            self._emit_window_output(
                window_patch, target_times, dt, out, ran,
                rows=int(host.shape[0]),
            )

    def _emit_window_extras(self, window_patch, payload, qs, taxis,
                            target_times, dt, d_sec):
        """Hook for subclasses emitting extra per-window products.
        ``payload`` is the time-major window — the already-staged
        DEVICE array when the prefetch thread transferred it (no second
        H2D), the host array otherwise.  It runs inside the window's
        ``lfproc.write`` step, so its time counts as ``write_s``."""

    def _emit_window_output(self, window_patch, target_times, dt, out, ran,
                            rows):
        """Shared tail of window processing: observability, coords,
        attrs, the HDF5 write and the emit listeners — used by the
        serial path, the window-DP driver (which computes ``out`` in a
        batch) and the stream; each caller times it as ``write_s``."""
        ax = window_patch.axis_of("time")
        mesh = self._mesh
        # ground truth of what ran, counted after execution
        self.engine_counts[ran] += 1
        get_registry().counter(
            "tpudas_windows_total",
            "processed windows by the engine that actually ran",
            labelnames=("engine",),
        ).inc(engine=ran)
        log_event(
            "window_engine",
            engine=ran,
            rows=rows,
            emitted=int(target_times.size),
            mesh=None if mesh is None else dict(mesh.shape),
        )
        if ax != 0:
            out = np.moveaxis(out, 0, ax)
        coords = dict(window_patch.coords)
        coords["time"] = target_times
        attrs = window_patch.attrs.to_dict()
        # the output is decoded float32 — a quantization scale inherited
        # from an int16 ingest window would misdescribe it
        attrs.pop("data_scale", None)
        result = window_patch.new(data=out, coords=coords, attrs=attrs)
        result = result.update_attrs(d_time=dt)
        filename = get_filename(
            result.attrs["time_min"], result.attrs["time_max"]
        )
        result.io.write(os.path.join(self._output_folder, filename), "dasdae")
        for listener in self._emit_listeners:
            if id(listener) in self._failed_listeners:
                continue  # raised earlier this round: skip, don't re-fail
            try:
                listener(result)
            except Exception as exc:
                self._failed_listeners.add(id(listener))
                get_registry().counter(
                    "tpudas_lfproc_listener_errors_total",
                    "output-emission listener callbacks that raised "
                    "(swallowed and skipped for the rest of the "
                    "round; the commit path is never poisoned)",
                ).inc()
                log_event(
                    "emit_listener_failed",
                    error=f"{type(exc).__name__}: {str(exc)[:200]}",
                )
