"""chip_smoke.py — drive tpudas's two main paths once on a TPU chip.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the channel-sharded paths only

Default run, one process on one chip, at an interrogator's width
(2,048 channels at 1 kHz, int16 tdas payload in 60 s files, 6 min):

0. kernels — stage 0 (v2 Pallas f32 and int16, XLA) against float64;
1. batch  — ``LFProc.process_time_range``, 1000x to 1 Hz, engine
   ``auto``: every window must run ``cascade-pallas``; the output must
   recover the synthetic LF component and match the host scipy
   reference (``_host_sosfiltfilt``) on a channel subset;
2. cache  — the same batch run again after dropping every in-process
   compile cache: its compiles must hit the persistent cache;
3. edge   — ``run_lowpass_realtime`` in stateful mode, engine
   ``fused``, fed in three rounds (each call resumes from the persisted
   carry): one seam-free output patch equal to the batch output within
   5e-3 of its scale, no redundant samples, and the engine the shape
   selector picks (``fused-pallas`` on the 60-output blocks);
4. native — the C++ tdas reader assembled the windows.

No phase may fire a fallback event.  ``--chips 4`` runs only the
channel-sharded stream (``run_lowpass_realtime(mesh=4)``) and the
meshed ``LFProc`` against single-device runs of the same spool (10,000
channels at 1 kHz, 3 min: BASELINE config 4 cut to one 4-chip host),
and checks that the carry and output shards sit on 4 devices.

The last line of stdout is one JSON object, printed only when every
check passed: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Without a TPU the script says so and exits 1; it has no CPU path.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

# the one fallback left on these paths: LFProc hands a window to the
# compute step unstaged when the prefetch thread's device_put fails
FALLBACK_EVENTS = ("stage_h2d_failed",)
T0 = "2023-03-22T00:00:00"
FS = 1000.0
LF_FREQ = 0.05
QSCALE = 1e-3
# streaming equals batch within this fraction of the output scale
# (.claude/skills/verify/SKILL.md's edge-drive tolerance)
STREAM_TOL = 5e-3
# LFProc vs the host scipy reference: the cascade's frequency response
# matches Butterworth-squared to ~1e-4 (README "Parity caveats"); the
# outputs are held to 1e-3 of their scale
HOST_TOL = 1e-3
# one stage vs float64: the v2 kernel's 3-pass bf16 split is ~1e-5
KERNEL_TOL = 1e-4
LF_TOL = 1e-2
# sharded vs single device: the per-device programs trace other channel
# widths, and XLA may then order a stage's sums differently (README
# "Multi-chip"): a few f32 ulps of the output scale
MESH_ULPS = 4


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    log(f"ok: {what}")


def require_tpu(n_chips: int) -> dict:
    """The device dict for the result line; exits 1 without a TPU."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as exc:
        sys.exit(f"chip_smoke: no TPU found (JAX could not start: {exc})")
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU found: JAX's default device is "
            f"{d.platform!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); this script has "
            "no CPU path"
        )
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": n_chips}


class CompileClock:
    """Sums XLA backend compile seconds (JAX's own monitoring event)."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.count = 0

        def _on(event, duration, **_):
            if event.endswith("backend_compile_duration"):
                self.seconds += duration
                self.count += 1

        monitoring.register_event_duration_secs_listener(_on)


def make_spool(directory, n_files, n_ch):
    from tpudas.testing import make_synthetic_spool

    return make_synthetic_spool(
        directory, n_files=n_files, file_duration=60.0, fs=FS, n_ch=n_ch,
        start=T0, noise=0.02, lf_freq=LF_FREQ, hf_freq=40.0,
        format="tdas", write_kwargs={"dtype": "int16", "scale": QSCALE},
    )


def merged(folder):
    """(time-major data, times) of the folder's outputs as ONE patch."""
    from tpudas.io.spool import spool

    sp = spool(folder).update().chunk(time=None)
    check(len(sp) == 1, f"{os.path.basename(folder)}: one seam-free patch")
    p = sp[0]
    data = np.asarray(p.host_data())
    if p.dims[0] != "time":
        data = data.T
    return data, np.asarray(p.coords["time"]).astype("datetime64[ns]")


def run_batch(src, out, t1, mesh=None):
    from tpudas.io.spool import spool
    from tpudas.proc.lfproc import LFProc

    lfp = LFProc(spool(src).sort("time").update(), mesh=mesh)
    lfp.update_processing_parameter(
        output_sample_interval=1.0, process_patch_size=60,
        edge_buff_size=10, engine="auto",
    )
    lfp.set_output_folder(out, delete_existing=True)
    lfp.process_time_range(np.datetime64(T0), t1)
    return lfp


def run_edge(src, out, counters, mesh=None):
    from tpudas.proc.streaming import run_lowpass_realtime

    return run_lowpass_realtime(
        source=src, output_folder=out, start_time=T0,
        output_sample_interval=1.0, edge_buffer=10.0,
        process_patch_size=60, poll_interval=0.0, file_duration=0.0,
        sleep_fn=lambda _s: None, stateful=True, engine="fused",
        counters=counters, mesh=mesh,
    )


def rel_err(got, ref) -> float:
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def seconds_since_t0(times) -> np.ndarray:
    t0 = np.datetime64(T0, "ns")
    return (times - t0).astype(np.int64) / 1e9


def lf_truth(times, n_ch) -> np.ndarray:
    """tpudas.testing's LF component: a channel-ramped 0.05 Hz sine."""
    dists = np.arange(n_ch, dtype=np.float64) * 5.0
    amp = 1.0 + dists / (dists.max() + 1.0)
    t = seconds_since_t0(times)
    return np.sin(2 * np.pi * LF_FREQ * t)[:, None] * amp[None, :]


def host_reference(paths, channels, out_times):
    """scipy sosfiltfilt of the full-rate stream on ``channels``,
    sampled at the output times (the reference's exact numerics)."""
    from tpudas.io.tdas import read_tdas_block, read_tdas_header
    from tpudas.ops.filter import _host_sosfiltfilt
    from tpudas.proc.lfproc import output_corner

    cols = []
    for c in channels:
        x = np.concatenate([
            read_tdas_block(p, 0, read_tdas_header(p)["n_time"], c, c + 1)
            for p in paths
        ])
        cols.append(x[:, 0])
    full = _host_sosfiltfilt(
        np.stack(cols, axis=1), 1.0 / FS, None, output_corner(1.0), 4
    )
    idx = np.rint(seconds_since_t0(out_times) * FS).astype(np.int64)
    return full[idx]


def kernel_probe(n_out=2048, n_ch=256):
    """Stage 0 of the flagship plan on a random block, three ways: the
    v2 Pallas kernel (f32 and raw int16), the XLA polyphase stage, and
    a float64 host reference.  Returns the relative errors."""
    import jax.numpy as jnp

    from tpudas.ops.fir import (
        _blocked_taps,
        _pallas_interpret,
        _polyphase_stage_xla,
        design_cascade,
    )
    from tpudas.ops.pallas_fir import fir_decimate_pallas, stage_input_rows
    from tpudas.proc.lfproc import output_corner

    plan = design_cascade(FS, 1000, output_corner(1.0), 4)
    R, hb = _blocked_taps(plan)[0]
    B = hb.shape[0]
    T = stage_input_rows(B, R, n_out)
    rng = np.random.default_rng(0)
    q = rng.integers(-3000, 3000, size=(T, n_ch)).astype(np.int16)
    x = q.astype(np.float32)
    xr = x[: (n_out + B) * R].astype(np.float64).reshape(n_out + B, R, n_ch)
    u = np.einsum("mrc,br->bmc", xr, hb.astype(np.float64))
    ref = sum(u[b, b : b + n_out] for b in range(B))
    interp = _pallas_interpret()
    got = {
        "pallas-f32": fir_decimate_pallas(
            jnp.asarray(x), hb, R, n_out, interpret=interp),
        "pallas-int16": fir_decimate_pallas(
            jnp.asarray(q), hb, R, n_out, interpret=interp),
        "xla": _polyphase_stage_xla(jnp.asarray(x), hb, R, n_out),
    }
    return {k: rel_err(np.asarray(v, np.float64), ref)
            for k, v in got.items()}


def phase(name, clock, fn):
    c0, n0, w0 = clock.seconds, clock.count, time.perf_counter()
    out = fn()
    log(
        f"phase {name}: wall {time.perf_counter() - w0:.3f} s, "
        f"{clock.count - n0} compiles taking {clock.seconds - c0:.3f} s"
    )
    return out


def smoke_one_chip(work, clock, n_ch=2048, n_files=6):
    import jax

    from tpudas.native import load_streamio
    from tpudas.obs.registry import MetricsRegistry, use_registry
    from tpudas.ops.fir import _clear_cascade_caches
    from tpudas.proc.stream import load_carry
    from tpudas.utils.profiling import Counters

    src = os.path.join(work, "spool")
    paths = phase(
        "spool", clock, lambda: make_spool(src, n_files, n_ch)
    )
    log(f"spool: {n_files} x 60 s int16 tdas files, {n_ch} ch @ {FS:.0f} Hz")
    t1 = np.datetime64(T0) + np.timedelta64(int(n_files * 60), "s")

    # 0. kernels against a float64 host reference
    errs = phase("kernels", clock, kernel_probe)
    log(f"stage-0 rel err vs float64 host: {errs}")
    check(max(errs.values()) < KERNEL_TOL,
          f"stage-0 kernels within {KERNEL_TOL} of the host reference")

    # 1. batch
    reg1 = MetricsRegistry()
    with use_registry(reg1):
        lfp = phase("batch", clock, lambda: run_batch(
            src, os.path.join(work, "batch"), t1))
    log(f"batch engines {lfp.engine_counts}, native windows "
        f"{lfp.native_windows}, timings {lfp.timings}")
    n_win = sum(lfp.engine_counts.values())
    check(n_win > 0 and lfp.engine_counts["cascade-pallas"] == n_win,
          "every batch window ran cascade-pallas")
    batch, b_times = merged(os.path.join(work, "batch"))
    keep = (seconds_since_t0(b_times) >= 30) & (
        seconds_since_t0(b_times) <= n_files * 60 - 30
    )
    lf_err = rel_err(batch[keep], lf_truth(b_times[keep], n_ch))
    log(f"LF recovery rel err {lf_err!r}")
    check(lf_err < LF_TOL, f"LF component recovered within {LF_TOL}")
    chans = [0, n_ch // 2, n_ch - 1]
    ref = host_reference(paths, chans, b_times[keep])
    host_err = rel_err(batch[keep][:, chans], ref)
    log(f"host sosfiltfilt rel err on channels {chans}: {host_err!r}")
    check(host_err < HOST_TOL, f"batch matches host scipy within {HOST_TOL}")

    # 2. persistent compile cache: drop the in-process caches, run again
    jax.clear_caches()
    _clear_cascade_caches()
    reg2 = MetricsRegistry()
    with use_registry(reg2):
        phase("batch-again", clock, lambda: run_batch(
            src, os.path.join(work, "batch2"), t1))
    hits = reg2.value("tpudas_compile_cache_hits_total")
    misses = reg2.value("tpudas_compile_cache_misses_total")
    log(f"second batch pass: {hits:.0f} persistent-cache hits, "
        f"{misses:.0f} misses")
    check(hits > 0, "second pass hit the persistent compile cache")
    again, _ = merged(os.path.join(work, "batch2"))
    check(np.array_equal(again, batch), "second pass byte-equal to first")

    # 3. edge stream, fed in three rounds
    edge_src = os.path.join(work, "edge_src")
    os.makedirs(edge_src)
    out_e = os.path.join(work, "edge")
    counters = Counters()
    reg3 = MetricsRegistry()
    per_round = n_files // 3
    for r in range(3):
        for p in paths[r * per_round:(r + 1) * per_round]:
            os.link(p, os.path.join(edge_src, os.path.basename(p)))
        with use_registry(reg3):
            phase(f"edge-round-{r + 1}", clock,
                  lambda: run_edge(edge_src, out_e, counters))
    blocks = {
        e: reg3.value("tpudas_stream_blocks_total", engine=e)
        for e in ("fused-pallas", "fused-xla", "cascade-pallas",
                  "cascade-xla")
    }
    log(f"edge stream blocks by engine {blocks}")
    check(blocks["fused-pallas"] > 0,
          "the selector ran fused-pallas on the edge stream")
    log(f"samples_redundant {counters.samples_redundant}")
    check(counters.samples_redundant == 0, "no redundant samples")
    stream, s_times = merged(out_e)
    common, ib, i_s = np.intersect1d(b_times, s_times,
                                     return_indices=True)
    common_keep = keep[ib]
    s_err = rel_err(stream[i_s][common_keep], batch[ib][common_keep])
    log(f"stream vs batch: {common.size} common outputs, rel err {s_err!r}")
    check(common_keep.sum() > 0 and s_err <= STREAM_TOL,
          f"streaming equals batch within {STREAM_TOL}")
    carry = load_carry(out_e)
    check(carry is not None and carry.consumed > 0, "carry persisted")
    with open(os.path.join(out_e, ".stream_carry.json")) as fh:
        meta = json.load(fh)
    meta = meta.get("meta", meta)
    check("pallas_ok" not in meta,
          "the carry holds no Pallas latch (pallas_ok)")

    # 4. native ingest
    check(load_streamio() is not None and lfp.native_windows == n_win,
          "the C++ tdas reader assembled every batch window")


def smoke_four_chips(work, clock, n_ch=10000, n_files=3, n_mesh=4):
    import jax

    from tpudas.testing import max_ulps
    from tpudas.ops.fir import (
        cascade_decimate_stream,
        design_cascade,
        stream_carry_sizes,
    )
    from tpudas.parallel.mesh import make_mesh
    from tpudas.proc.lfproc import output_corner
    from tpudas.proc.stream import load_carry
    from tpudas.utils.profiling import Counters

    src = os.path.join(work, "spool")
    phase("spool", clock, lambda: make_spool(src, n_files, n_ch))
    t1 = np.datetime64(T0) + np.timedelta64(int(n_files * 60), "s")
    mesh = make_mesh(n_mesh)
    log(f"mesh {dict(mesh.shape)} over {mesh.devices.ravel().tolist()}")
    outs = {}
    for label, m in (("single", None), ("mesh", mesh)):
        lfp = phase(f"batch-{label}", clock, lambda m=m, label=label:
                    run_batch(src, os.path.join(work, f"b_{label}"), t1,
                              mesh=m))
        log(f"batch-{label} engines {lfp.engine_counts}")
        outs[f"batch-{label}"] = merged(os.path.join(work, f"b_{label}"))
        out_e = os.path.join(work, f"e_{label}")
        phase(f"edge-{label}", clock, lambda m=m, out_e=out_e:
              run_edge(src, out_e, Counters(), mesh=m))
        outs[f"edge-{label}"] = merged(out_e)
    for kind in ("batch", "edge"):
        a, ta = outs[f"{kind}-single"]
        b, tb = outs[f"{kind}-mesh"]
        check(np.array_equal(ta, tb) and a.shape == b.shape,
              f"{kind}: mesh and single-device output grids agree")
        ulps = max_ulps(b, a)
        log(f"{kind}: mesh vs single byte-equal {np.array_equal(a, b)}, "
            f"max |diff| {ulps!r} ulps of the output scale")
        check(ulps <= MESH_ULPS,
              f"{kind}: {n_mesh}-chip channel-sharded output within "
              f"{MESH_ULPS} ulps of single device")
    # where the sharded state lives: one more stream step from the
    # persisted carry, as the stream runs it
    carry = load_carry(os.path.join(work, "e_mesh"))
    plan = design_cascade(FS, 1000, output_corner(1.0), 4)
    check(tuple(int(b.shape[0]) for b in carry.bufs)
          == stream_carry_sizes(plan), "mesh carry matches the plan")
    x = np.zeros((60 * int(FS), n_ch), np.float32)
    y, bufs = cascade_decimate_stream(x, carry.bufs, plan, "fused",
                                      mesh=mesh)
    jax.block_until_ready((y, bufs))
    for what, arr in [("output", y)] + [
        (f"carry leaf {i}", b) for i, b in enumerate(bufs)
    ]:
        devs = {d.id for d in arr.sharding.device_set}
        log(f"{what}: shards on devices {sorted(devs)}")
        check(len(devs) == n_mesh, f"{what} sharded over {n_mesh} devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    device = require_tpu(args.chips)
    log(f"device {device}")
    try:
        from tpudas.utils.compile_cache import enable_compile_cache
        from tpudas.utils.logging import set_log_handler
    except ImportError as exc:
        sys.exit(f"chip_smoke: the tpudas package is not importable "
                 f"here ({exc}); run from a checkout of the repo")
    log(f"compile cache {enable_compile_cache()}")
    events = []
    set_log_handler(events.append)
    clock = CompileClock()
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            smoke_four_chips(work, clock)
        else:
            smoke_one_chip(work, clock)
        fired = sorted({e["event"] for e in events} & set(FALLBACK_EVENTS))
        check(not fired, f"no fallback event fired ({fired})")
    except SmokeFailure as exc:
        log(f"FAILED: {exc}")
        return 1
    finally:
        set_log_handler(None)
        shutil.rmtree(work, ignore_errors=True)
    log(f"total wall {time.perf_counter() - t0:.3f} s, {clock.count} "
        f"compiles taking {clock.seconds:.3f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
