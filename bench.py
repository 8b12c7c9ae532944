"""tpudas benchmark: sustained channel-samples/sec of the flagship
low-pass + decimate pipeline on one TPU chip.

Workload (BASELINE.md config 4 scaled to one chip): overlap-save
windows of a 1 kHz interrogator stream, C channels x T samples float32
per window, zero-phase low-pass at 0.45x the post-decimation Nyquist +
1000x decimation to 1 Hz — the per-window inner loop of
``LFProc.process_time_range`` (SURVEY.md §3.1 hot loop #1; reference
hot loop ``lf_das.py:223-225``).

Everything runs in ONE process: the resident-kernel measurement, then
(kernel mode) the full product path.  A phase whose failure is caught
and recorded in the JSON (an ``error`` entry) still makes the run exit
non-zero; a Pallas failure is not caught at all.

Engines (BENCH_ENGINE):
  cascade  (default) multistage polyphase FIR, response-matched to the
           Butterworth-squared reference filter (tpudas.ops.fir);
           BENCH_PALLAS=1 (TPU default) runs the Pallas strided-FIR
           kernel for the big stages, 0 the XLA polyphase formulation
  fft      the rfft -> response multiply -> irfft -> gather engine
           (tpudas.proc.lfproc), kept as the parity baseline

Measurement methodology: the timed loop runs ENTIRELY on device as one
dispatch — a lax.scan over several distinct resident windows, repeated
to cover BENCH_ITERS — so host dispatch and sync costs are not charged
to the kernel. Distinct windows per scan step keep XLA from hoisting
the loop-invariant kernel (which otherwise yields "bandwidths" above
HBM peak); RNG runs before the timer; window length is sized to the
cascade's exact chain need so no stage pads (an internal pad
materializes a full input copy — one extra HBM round-trip at the
full-rate stage). Host->device ingest is excluded by default
(BENCH_INCLUDE_H2D=1 includes it; the e2e sub-object measures the
whole product path).

Prints ONE JSON line:
  metric           channel_samples_per_sec
  value            sustained input channel-samples processed per wall-sec
  vs_baseline      value / 1e8 — BASELINE.md's north star as a rate (10x
                   real time on a 10,000-channel 1 kHz spool = 1e8
                   channel-samples/sec, targeted for a v5e-8); >1.0 means
                   this single chip alone beats the 8-chip target
  realtime_factor  stream-seconds processed per wall-second at the
                   benchmarked (fs, C) — the SURVEY §6 north-star metric
  device           platform, device_kind and count as JAX reports them
  flops_est / mfu  analytic flop count of the filter math and the
                   resulting fraction of the chip's published fp32 peak
                   (keyed by device_kind; an estimate, not a profiler
                   readout)
  hbm_gbps / hbm_frac  analytic minimum HBM traffic per window divided
                   by wall time, and its fraction of the chip's HBM
                   peak — the honest roofline for this ~5 flop/byte
                   kernel (MFU is the wrong lens)
  stages           per-stage [engine, emitted] ground truth of the
                   cascade layout that actually ran
  engines          present when BENCH_COMPARE=1 (TPU default) and
                   budget allows: measured ch-samp/s for cascade-xla /
                   cascade-pallas / fft so the 'auto' default is chosen
                   from data
  failed_phases    present (and the exit code is 1) when a caught
                   phase failed

BENCH_MODE=e2e measures the WHOLE product path instead of the resident
kernel: a native tdas spool is synthesized on local disk and
``LFProc.process_time_range`` runs over it — index planning, C++
threaded window assembly on the prefetch thread, H2D, the fused device
kernel, and HDF5 output writes all inside the timed region.  ``value``
is then input channel-samples per wall-second of the full pipeline and
``realtime_factor`` is the SURVEY §6 north-star number.

A default (kernel-mode) run ALSO appends an ``e2e`` sub-object to the
JSON line — the full product path on a local tdas spool, in the same
process — so every artifact records the pipeline real-time factor
beside the resident-kernel number.

A kernel-mode run also records (TPU defaults) an ``int16`` sub-object:
the same cascade fed RAW int16 windows with the dequantize fused into
the first stage (tpudas quantized tdas ingest) — half the HBM read
bytes of the f32 headline, the realistic edge-interrogator payload.

Env knobs: BENCH_T, BENCH_C, BENCH_ITERS, BENCH_ENGINE,
BENCH_PALLAS=0/1, BENCH_INCLUDE_H2D=0/1, BENCH_COMPARE=0/1,
BENCH_QUANT=0/1 (int16-payload kernel measurement),
BENCH_PROFILE=0/1 (per-stage cascade breakdown),
BENCH_MODE=kernel/e2e, BENCH_E2E_SEC, BENCH_E2E_FS,
BENCH_E2E_JOINT=0/1 (joint low-pass + rolling products, config 5;
geometry via BENCH_E2E_ROLL_W / BENCH_E2E_ROLL_S seconds),
BENCH_REMAINING (seconds the optional phases may use).
The persistent compile cache follows tpudas.utils.compile_cache.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

# Published per-chip peaks by jax ``device_kind``: fp32-on-MXU FLOP/s
# (half the bf16 MXU rate — fp32 inputs take the passes path) and HBM
# bytes/s.  Source: Google Cloud TPU documentation, "TPU v4" / "TPU
# v5e" / "TPU v5p" system architecture pages.  A device kind missing
# here is an error, never a default.
_PEAKS = {
    "TPU v4": {"fp32": 275e12 / 2, "hbm": 1228e9},
    "TPU v5 lite": {"fp32": 197e12 / 2, "hbm": 819e9},
    "TPU v5p": {"fp32": 459e12 / 2, "hbm": 2765e9},
}

# wall seconds the engine shoot-out needs before it is attempted
_COMPARE_MIN_LEFT = 240


def device_peaks(device) -> dict | None:
    """Peaks for ``device`` (None on the CPU backend, which has no
    published chip peak); an unknown accelerator kind raises."""
    if device.platform == "cpu":
        return None
    try:
        return _PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device.device_kind!r}; "
            "add it to bench._PEAKS with its source"
        ) from None


def _device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
    }


# ---------------------------------------------------------------- measure


def _build_fft_step(T, C, fs, dt_out, order):
    import jax
    import jax.numpy as jnp

    from tpudas.ops.fftlen import next_tpu_fft_len
    from tpudas.proc.lfproc import _lowpass_resample_kernel

    corner = 1.0 / dt_out / 2.0 * 0.9
    ratio = int(round(dt_out * fs))
    nfft = next_tpu_fft_len(T)
    idx = jnp.asarray(np.arange(0, T - 1, ratio), jnp.int32)
    w = jnp.zeros((idx.shape[0],), jnp.float32)

    def kernel(data):
        return _lowpass_resample_kernel(
            data, jnp.float32(1.0 / fs), jnp.float32(corner), idx, w, nfft,
            order,
        )

    # rfft + irfft dominate: ~2.5*n*log2(n) real flops each, + the
    # response multiply (6 flops/bin) and gather-lerp (~4 flops/out)
    nlog = nfft * np.log2(nfft)
    flops = C * (5.0 * nlog + 3.0 * nfft + 4.0 * (T // ratio))
    return kernel, flops


def _build_cascade_step(T, C, fs, dt_out, order, use_pallas, mesh=None,
                        time_shards=1, quantized=False):
    """(kernel, analytic flops/window, T_used, report).

    ``T_used`` is the pad-free window length closest to T (never below
    the filter's receptive-field floor): the input is sized to the
    cascade's exact chain need (tpudas.ops.fir.chain_layout) so no
    stage materializes a padded copy of its input — at the full-rate
    stage that copy is a whole extra HBM round-trip and was the largest
    single overhead in the r03-era measurement. ``report`` carries the
    per-stage layout that ACTUALLY runs (per-shard under a mesh) plus
    the shard multiplier for traffic/flops accounting.
    """
    from tpudas.ops.fir import _build_cascade_fn, chain_layout, design_cascade

    corner = 1.0 / dt_out / 2.0 * 0.9
    ratio = int(round(dt_out * fs))
    plan = design_cascade(fs, ratio, corner, order)
    engine = "pallas" if use_pallas else "xla"
    nc = mesh.shape["ch"] if mesh is not None else 1
    c_local = -(-C // nc)
    # decisions inside the kernel trace on the LOCAL channel count
    _, floor_rows = chain_layout(plan, 1, c_local, engine)
    n_out = max(1, (T - floor_rows) // ratio + 1)
    layout, rows = chain_layout(plan, n_out, c_local, engine)
    while rows > T and n_out > 1:
        n_out = max(1, n_out - max(1, (rows - T) // ratio))
        layout, rows = chain_layout(plan, n_out, c_local, engine)
    T_used = rows
    if T_used > T * 1.05:
        print(
            f"[bench] BENCH_T={T} is below this filter's receptive-"
            f"field floor; windows of {T_used} rows will be measured",
            file=sys.stderr,
            flush=True,
        )
    shards = 1
    if mesh is not None and time_shards > 1:
        from tpudas.parallel.pipeline import (
            sharded_cascade_decimate,
            sharded_cascade_layout,
        )

        T_used = T  # the sharded path sizes its own per-shard grid
        sl = sharded_cascade_layout(
            mesh, plan, plan.delay, n_out, T,
            n_ch_local=c_local, engine=engine,
        )
        if sl is None:
            raise ValueError(
                f"time_shards={time_shards} does not fit this "
                f"window/filter (T={T}); lower BENCH_TIME_SHARDS"
            )
        # what each device actually traces: n_loc outputs, local C
        layout, _ = chain_layout(plan, sl[0], c_local, engine)
        shards = time_shards

        def fn(data):
            out = sharded_cascade_decimate(
                mesh, data, plan, plan.delay, n_out, engine=engine
            )
            assert out is not None  # layout checked above
            return out
    elif mesh is not None:
        from tpudas.ops.fir import cascade_decimate

        # cascade_decimate's mesh wrapper pads C to the shard multiple
        # (phase=delay -> zero pre-shift, same as the direct fn)
        def fn(data):
            return cascade_decimate(
                data, plan, plan.delay, n_out, engine, mesh=mesh
            )
    elif quantized:
        # raw int16 windows (the realistic interrogator payload): the
        # scale is a traced operand of the same compiled cascade
        import jax.numpy as jnp

        fnq = _build_cascade_fn(plan, n_out, engine, quantized=True)

        def fn(data, _fnq=fnq, _s=jnp.float32(1e-3)):
            return _fnq(data, _s)

    else:
        fn = _build_cascade_fn(plan, n_out, engine)

    # per stage: a polyphase FIR emitting k outputs from `taps` MACs
    # each -> 2*taps flops per output sample per channel; under a mesh
    # each of `shards` time-shards runs the per-shard layout over the
    # full channel width (c_local * nc ~= C)
    flops = 0.0
    for (R, taps), (_, k) in zip(plan.stages, layout):
        flops += 2.0 * len(taps) * k * C * shards
    report = {
        "stages": [[e, k] for e, k in layout],
        "stages_scope": "per_shard" if shards > 1 else "global",
        "emitted_k_factor": shards,
        # for BENCH_PROFILE: the exact plan/layout the headline number
        # measured (re-deriving them would silently drift)
        "plan": plan,
        "layout": layout,
    }
    return (lambda data: fn(data)), flops, T_used, report


def _measure(kernel, T, C, iters, include_h2d, dtype="float32"):
    """Wall time for ``iters`` windows through ``kernel``.

    Resident-kernel mode runs the ENTIRE measured loop on device as one
    dispatch: a scan over NW distinct resident windows, repeated until
    ``iters`` is covered, so no per-window host dispatch or sync is
    charged to the kernel. Distinct windows
    per inner step keep XLA from hoisting the kernel out of the loop
    (with one window the whole body is loop-invariant and the measured
    "bandwidth" exceeds HBM peak). RNG runs before the timer.
    """
    import jax
    import jax.numpy as jnp

    if include_h2d:
        host_window = (
            np.random.default_rng(0).standard_normal((T, C)).astype(np.float32)
        )
        jax.device_get(kernel(jnp.asarray(host_window)))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jax.device_get(kernel(jnp.asarray(host_window)))
        elapsed = time.perf_counter() - t0
        assert np.isfinite(out).all()
        return elapsed, iters, None

    # NW resident windows within ~9 GB of HBM; rep covers iters
    es = 2 if dtype == "int16" else 4
    nw = max(1, min(6, int(9e9 // (T * C * es))))
    if nw == 1:
        # a single resident window makes the scan body loop-invariant —
        # XLA may hoist it and the number inflates past HBM peak. Never
        # silently: the caller reports windows_resident and this warns.
        print(
            "[bench] WARNING: window too large for >1 resident copy; "
            "single-window loop is hoistable and the result may be "
            "inflated — reduce BENCH_T/BENCH_C",
            file=sys.stderr,
            flush=True,
        )
    rep = max(1, -(-iters // nw))
    if dtype == "int16":
        gen = jax.jit(
            lambda key: jax.random.randint(
                key, (nw, T, C), -3000, 3000, jnp.int16
            )
        )
    else:
        gen = jax.jit(
            lambda key: jax.random.normal(key, (nw, T, C), jnp.float32)
        )
    stack = gen(jax.random.PRNGKey(0))
    jax.block_until_ready(stack)

    @jax.jit
    def run(st):
        def body(tot, w):
            return tot + jnp.sum(jnp.abs(kernel(w))), None

        def outer(tot, _):
            t, _ = jax.lax.scan(body, tot, st)
            return t, None

        tot, _ = jax.lax.scan(
            outer, jnp.zeros((), jnp.float32), None, length=rep
        )
        return tot

    checksum = float(run(stack))  # compile + settle
    assert np.isfinite(checksum)
    elapsed = 1e30
    for _ in range(2):
        t0 = time.perf_counter()
        checksum = float(run(stack))
        elapsed = min(elapsed, time.perf_counter() - t0)
        assert np.isfinite(checksum)
    return elapsed, nw * rep, nw


def measure_e2e(default_c: int = 1024) -> dict:
    """The full product path on a local tdas spool (BENCH_MODE=e2e, or
    the ``e2e`` sub-object of a kernel-mode run)."""
    import tempfile

    import numpy as _np

    from tpudas import spool as make_spool
    from tpudas.proc.lfproc import LFProc
    from tpudas.testing import make_synthetic_spool

    C = int(os.environ.get("BENCH_C", default_c))
    sec = int(os.environ.get("BENCH_E2E_SEC", 120))
    fs = float(os.environ.get("BENCH_E2E_FS", 1000.0))
    engine = os.environ.get("BENCH_ENGINE", "auto")
    # int16: quantized spool -> raw native assembly -> device decode
    # (half the H2D bytes; the realistic edge-interrogator payload)
    dtype = os.environ.get("BENCH_E2E_DTYPE", "float32")
    write_kwargs = (
        {"dtype": "int16", "scale": 1e-3} if dtype == "int16" else None
    )
    file_sec = 30.0
    # the timed range must equal the synthesized data span exactly, or
    # the reported rate would credit samples never read
    n_files = max(1, round(sec / file_sec))
    sec = int(n_files * file_sec)
    start = "2023-03-22T00:00:00"

    joint = os.environ.get("BENCH_E2E_JOINT", "0") == "1"
    with tempfile.TemporaryDirectory() as td:
        src = os.path.join(td, "src")
        out = os.path.join(td, "out")
        out_roll = os.path.join(td, "out_roll")
        print(
            f"[bench] e2e: synthesizing {sec}s x {C}ch @ {fs:.0f}Hz tdas "
            "spool",
            file=sys.stderr,
            flush=True,
        )
        make_synthetic_spool(
            src, n_files=n_files, file_duration=file_sec,
            fs=fs, n_ch=C, noise=0.01, lf_freq=0.05, hf_freq=40.0,
            format="tdas", write_kwargs=write_kwargs,
        )
        roll_w = float(os.environ.get("BENCH_E2E_ROLL_W", 5.0))
        roll_s = float(os.environ.get("BENCH_E2E_ROLL_S", 1.0))
        if joint:
            # BENCH_E2E_JOINT=1: BOTH products (low-pass + rolling
            # mean) from the one ingest pass — BASELINE config 5's
            # workload shape
            from tpudas.proc.joint import JointProc

            lfp = JointProc(make_spool(src).sort("time").update())
            lfp.update_processing_parameter(
                rolling_window=roll_w, rolling_step=roll_s,
            )
        else:
            lfp = LFProc(make_spool(src).sort("time").update())
        lfp.update_processing_parameter(
            output_sample_interval=1.0,
            process_patch_size=60,
            edge_buff_size=10,
            engine=engine,
        )
        lfp.set_output_folder(out, delete_existing=True)
        if joint:
            lfp.set_rolling_output_folder(out_roll, delete_existing=True)
        t0 = _np.datetime64(start)
        t1 = t0 + _np.timedelta64(sec, "s")
        # measured through the obs registry (Counters mirrors into
        # tpudas_proc_*) so the headline below and metrics.prom report
        # the same numbers (ISSUE 2 satellite); a FRESH registry scope
        # per run, so repeated in-process invocations (tests) do not
        # accumulate
        from tpudas.obs.registry import (
            MetricsRegistry as _MetricsRegistry,
            headline as _headline,
            use_registry as _use_registry,
        )
        from tpudas.utils.profiling import Counters as _Counters

        counters = _Counters()
        with _use_registry(_MetricsRegistry()) as _reg:
            with counters.measure(int(sec * fs * C), float(sec)):
                lfp.process_time_range(t0, t1)
        elapsed = counters.last_wall
        n_out = len(os.listdir(out))
        n_roll = len(os.listdir(out_roll)) if joint else None

    h = _headline(_reg)
    value = h["channel_samples_per_sec"]
    samples = h["channel_samples"]
    # per-phase wall seconds from LFProc's own accounting (assemble =
    # waiting on the prefetch thread's window read+H2D staging, device
    # = kernel dispatch through host sync, write = HDF5 output) and the
    # rate each phase would sustain ALONE — locating the bottleneck
    # (an assemble rate far below the device rate means ingest, not
    # the chip, sets the pace)
    timings = {k: round(v, 3) for k, v in lfp.timings.items()}
    phase_rates = {
        k.replace("_s", ""): round(samples / v, 1) if v > 0 else None
        for k, v in lfp.timings.items()
    }
    return {
                "metric": "channel_samples_per_sec",
                "value": round(value, 1),
                "unit": "channel_samples/sec",
                "vs_baseline": round(value / 1e8, 4),
                "realtime_factor": round(h["realtime_factor"], 2),
                "headline_source": "tpudas.obs.registry",
                "device": _device_info(),
                "engine": engine,
                "mode": "e2e",
                "payload": dtype,
                "shape": [int(sec * fs), C],
                "native_windows": lfp.native_windows,
                "engine_counts": lfp.engine_counts,
                "output_files": n_out,
                **({"joint": True, "rolling_files": n_roll,
                    "rolling_window_s": roll_w, "rolling_step_s": roll_s}
                   if joint else {}),
                "timings_s": timings,
                "phase_rates": phase_rates,
    }


def measure_kernel() -> dict:
    """The resident-kernel measurement (and its optional phases); a
    caught phase failure is listed under ``failed_phases``."""
    import jax

    t_start = time.monotonic()
    backend = jax.default_backend()
    on_tpu = backend != "cpu"
    failed = []
    print(f"[bench] backend={backend}", file=sys.stderr, flush=True)

    T = int(os.environ.get("BENCH_T", 131072))  # ~131 s @ 1 kHz
    C = int(os.environ.get("BENCH_C", 2048))
    # scan-loop iterations: one final host sync amortizes over all of
    # them, so TPU defaults run enough windows to make that overhead a
    # small fraction of the measurement
    iters = int(os.environ.get("BENCH_ITERS", 256 if on_tpu else 16))
    engine = os.environ.get("BENCH_ENGINE", "cascade")
    # TPU defaults flip the fast path and the shoot-out ON (the
    # recorded JSON must carry pallas + engine-compare numbers)
    use_pallas = (
        os.environ.get("BENCH_PALLAS", "1" if on_tpu else "0") == "1"
    )
    include_h2d = os.environ.get("BENCH_INCLUDE_H2D", "0") == "1"
    compare = (
        os.environ.get("BENCH_COMPARE", "1" if on_tpu else "0") == "1"
    )
    remaining = float(os.environ.get("BENCH_REMAINING", 1e9))

    fs, dt_out, order = 1000.0, 1.0, 4
    mesh = None
    mesh_info = None
    n_mesh = int(os.environ.get("BENCH_MESH", 0))
    time_shards = int(os.environ.get("BENCH_TIME_SHARDS", 1))
    if n_mesh:
        from tpudas.parallel.mesh import make_mesh

        n_mesh = min(n_mesh, len(jax.devices()))
        mesh = make_mesh(n_mesh, time_shards=time_shards)
        mesh_info = dict(mesh.shape)
        if engine != "cascade":
            print(
                "[bench] BENCH_MESH supports the cascade engine only",
                file=sys.stderr,
                flush=True,
            )
            mesh = None
            mesh_info = None  # never report a mesh that did not run
    report = None
    if engine == "cascade":
        kernel, flops_win, T_used, report = _build_cascade_step(
            T, C, fs, dt_out, order, use_pallas, mesh, time_shards
        )
    else:
        kernel, flops_win = _build_fft_step(T, C, fs, dt_out, order)
        T_used = T

    # which Pallas implementation the headline ran (None when Pallas
    # did not run); a Pallas failure raises out of the run
    pallas_impl = (
        os.environ.get("TPUDAS_PALLAS_IMPL", "v2")
        if engine == "cascade" and use_pallas
        else None
    )
    elapsed, iters_done, n_resident = _measure(
        kernel, T_used, C, iters, include_h2d
    )

    # headline through the obs registry: the measured loop is absorbed
    # into the tpudas_proc_* counters (Counters.add_measured) and the
    # reported numbers are read back from there, the same substrate a
    # deployment's metrics.prom scrapes (ISSUE 2 satellite); fresh
    # registry scope so in-process re-runs (tests) don't accumulate
    from tpudas.obs.registry import (
        MetricsRegistry as _MetricsRegistry,
        headline as _headline,
        use_registry as _use_registry,
    )
    from tpudas.utils.profiling import Counters as _Counters

    with _use_registry(_MetricsRegistry()) as _reg:
        _Counters().add_measured(
            T_used * C * iters_done, T_used * iters_done / fs, elapsed
        )
    _h = _headline(_reg)
    channel_samples = _h["channel_samples"]
    value = _h["channel_samples_per_sec"]
    flops_per_sec = flops_win * iters_done / elapsed
    peaks = device_peaks(jax.devices()[0])
    result = {
        "metric": "channel_samples_per_sec",
        "value": round(value, 1),
        "unit": "channel_samples/sec",
        "vs_baseline": round(value / 1e8, 4),
        "realtime_factor": round(_h["realtime_factor"], 2),
        "headline_source": "tpudas.obs.registry",
        "device": _device_info(),
        "engine": engine + ("-pallas" if use_pallas else ""),
        "shape": [T_used, C],
        "iters": iters_done,
        "windows_resident": n_resident,
        "flops_est": round(flops_per_sec / 1e12, 3),
        "flops_unit": "TFLOP/s",
    }
    if report is not None:
        # ground truth of what ran, plus the achieved fraction of the
        # bandwidth roofline (this kernel is HBM-bound by design: ~5
        # flops/byte; MFU is the wrong lens)
        result["stages"] = report["stages"]
        if report["stages_scope"] != "global":
            result["stages_scope"] = report["stages_scope"]
        emitted = sum(k for _, k in report["stages"])
        emitted *= report["emitted_k_factor"]
        bytes_win = 4.0 * C * (T_used + 2.0 * emitted)
        hbm = bytes_win * iters_done / elapsed
        result["hbm_gbps"] = round(hbm / 1e9, 1)
        if peaks:
            result["hbm_frac"] = round(hbm / peaks["hbm"], 4)
    if pallas_impl is not None:
        result["pallas_impl"] = pallas_impl
    if n_resident == 1:
        result["warning"] = (
            "single resident window: the scan body is loop-invariant "
            "and XLA hoisting may inflate this number"
        )
    if mesh_info is not None:
        result["mesh"] = mesh_info
    if peaks:
        result["mfu"] = round(flops_per_sec / peaks["fp32"], 4)

    # Optional per-stage breakdown (BENCH_PROFILE=1): each cascade
    # stage measured alone at its in-chain input shape, same scan
    # harness — shows where the window's time goes on real hardware.
    # Budget-gated like the compare block (BENCH_REMAINING).
    profile = (
        os.environ.get("BENCH_PROFILE", "0") == "1"
        and engine == "cascade"
        and mesh is None
        and not include_h2d
    )
    if profile:
        left = remaining - (time.monotonic() - t_start)
        if left <= _COMPARE_MIN_LEFT:
            result["profile_skipped"] = (
                f"budget: {left:.0f}s left < {_COMPARE_MIN_LEFT}s"
            )
            profile = False
    if profile:
        from tpudas.ops.fir import (
            _blocked_taps,
            _pallas_interpret,
            _polyphase_stage_xla,
        )
        from tpudas.ops.pallas_fir import fir_decimate_pallas

        # profile exactly the plan/layout the headline measured
        plan = report["plan"]
        layout_s = report["layout"]
        interpret = _pallas_interpret()
        stage_ms = []
        t_in = T_used
        prof_iters = max(8, iters // 4)
        for (R, hb), (eng2, k) in zip(_blocked_taps(plan), layout_s):
            if eng2 == "pallas":
                def stage_fn(x, hb=hb, R=R, k=k):
                    return fir_decimate_pallas(
                        x, hb, int(R), n_out=k, interpret=interpret
                    )
            else:
                def stage_fn(x, hb=hb, R=R, k=k):
                    return _polyphase_stage_xla(x, hb, int(R), k)
            try:
                dt_s, n_done, _ = _measure(stage_fn, t_in, C, prof_iters,
                                           False)
                stage_ms.append(
                    [eng2, int(t_in), round(dt_s / n_done * 1e3, 3)]
                )
            except Exception as exc:
                stage_ms.append([eng2, int(t_in), f"error: {exc}"[:80]])
                failed.append(f"profile stage {len(stage_ms) - 1}")
            t_in = k
        result["stage_times_ms"] = stage_ms
        print(f"[bench] stage profile: {stage_ms}", file=sys.stderr,
              flush=True)

    # Quantized-payload kernel (BENCH_QUANT=1, TPU default): the same
    # cascade fed raw int16 windows with an in-kernel dequantize — the
    # realistic edge-interrogator payload, at half the HBM read bytes.
    quant = (
        os.environ.get("BENCH_QUANT", "1" if on_tpu else "0") == "1"
        and engine == "cascade"
        and mesh is None
        and not include_h2d
    )
    if quant:
        left = remaining - (time.monotonic() - t_start)
        if left <= 120:
            result["int16_skipped"] = f"budget: {left:.0f}s left"
        else:
            try:
                qk, _, t_q, q_report = _build_cascade_step(
                    T, C, fs, dt_out, order, use_pallas, quantized=True
                )
                dt_q, n_q, _ = _measure(
                    qk, t_q, C, max(4, iters // 4), False, dtype="int16"
                )
                q_val = t_q * C * n_q / dt_q
                emitted_q = sum(k for _, k in q_report["stages"])
                emitted_q *= q_report["emitted_k_factor"]
                bytes_q = C * (2.0 * t_q + 8.0 * emitted_q)
                sub = {
                    "value": round(q_val, 1),
                    "vs_baseline": round(q_val / 1e8, 4),
                    "realtime_factor": round(t_q * n_q / fs / dt_q, 2),
                    "hbm_gbps": round(bytes_q * n_q / dt_q / 1e9, 1),
                }
                if peaks:
                    sub["hbm_frac"] = round(
                        bytes_q * n_q / dt_q / peaks["hbm"], 4
                    )
                result["int16"] = sub
                print(
                    f"[bench] int16 kernel: {q_val:.1f}",
                    file=sys.stderr,
                    flush=True,
                )
            except Exception as exc:
                result["int16"] = {"error": str(exc)[:200]}
                failed.append("int16")

    # Optional engine shoot-out (small iters) so 'auto' is data-driven.
    # Gate on the time ACTUALLY left (the main measurement above may
    # have eaten most of BENCH_REMAINING).
    left = remaining - (time.monotonic() - t_start)
    run_compare = left > _COMPARE_MIN_LEFT and not include_h2d
    if compare and not run_compare:
        # a requested-but-skipped compare must be visible in the JSON,
        # not just absent (round-2 advisor finding)
        reason = (
            "include_h2d times host transfers, not the engines"
            if include_h2d
            else f"budget: {left:.0f}s left < {_COMPARE_MIN_LEFT}s"
        )
        result["engines_skipped"] = reason
        print(f"[bench] compare skipped: {reason}", file=sys.stderr, flush=True)
    if compare and run_compare:
        cmp_iters = max(4, iters // 4)
        if engine == "cascade":
            primary = "cascade-pallas" if use_pallas else "cascade-xla"
        else:
            primary = "fft"
        engines = {primary: round(value, 1)}  # already measured above
        for name, builder in (
            ("cascade-xla", lambda: _build_cascade_step(
                T, C, fs, dt_out, order, False)[:3]),
            ("cascade-pallas", lambda: _build_cascade_step(
                T, C, fs, dt_out, order, True)[:3]),
            ("fft", lambda: _build_fft_step(T, C, fs, dt_out, order) + (T,)),
        ):
            if name == primary:
                continue
            if remaining - (time.monotonic() - t_start) < 120:
                engines[name] = "skipped: budget"
                continue
            try:
                k, _, t_used = builder()
                dt, n_done, _ = _measure(k, t_used, C, cmp_iters, False)
                engines[name] = round(t_used * C * n_done / dt, 1)
            except Exception as exc:
                engines[name] = f"error: {exc}"[:120]
                failed.append(f"compare {name}")
            print(
                f"[bench] compare {name}: {engines[name]}",
                file=sys.stderr,
                flush=True,
            )
        result["engines"] = engines

    if failed:
        result["failed_phases"] = failed
    return result


def main():
    if os.environ.get("BENCH_MODE") == "stream":
        # steady-state streaming bench (stateful carry vs edge-buffer
        # rewind): a pure-CPU tool, run as the one JAX process
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        tool = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "tools",
            "stream_bench.py",
        )
        args = [sys.executable, tool]
        out = os.environ.get("BENCH_STREAM_OUT")
        if out:
            args += ["--out", out]
        sys.exit(subprocess.call(args, env=env))

    from tpudas.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if os.environ.get("BENCH_MODE", "kernel") == "e2e":
        result = measure_e2e()
    else:
        result = measure_kernel()
        result["e2e"] = measure_e2e(default_c=256)
    print(json.dumps(result))
    sys.exit(1 if result.get("failed_phases") else 0)


if __name__ == "__main__":
    main()
