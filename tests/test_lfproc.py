"""LFProc engine: naming contracts, parameters, scheduling invariants,
edge calibration, seam-freeness, resume idempotency (SURVEY.md §4)."""

import os

import numpy as np
import pytest

from tpudas import spool
from tpudas.proc.edge import get_edge_effect_time
from tpudas.proc.lfproc import LFProc, schedule_windows
from tpudas.proc.memory import get_patch_time
from tpudas.proc.naming import get_filename, get_timestr
from tpudas.testing import lowfreq_truth, make_synthetic_spool

FS = 100.0
N_CH = 8
FILE_SEC = 30.0
N_FILES = 8  # 4 minutes of stream
DT_OUT = 1.0  # output interval (s): corner 0.45 Hz


@pytest.fixture(scope="module")
def spool_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("raw")
    make_synthetic_spool(
        d, n_files=N_FILES, file_duration=FILE_SEC, fs=FS, n_ch=N_CH,
        noise=0.01,
    )
    return str(d)


def run_lfproc(src, out_dir, t1, t2, patch_size=60, buff=10):
    lfp = LFProc(spool(src).sort("time").update())
    lfp.update_processing_parameter(
        output_sample_interval=DT_OUT,
        process_patch_size=patch_size,
        edge_buff_size=buff,
    )
    lfp.set_output_folder(str(out_dir), delete_existing=True)
    lfp.process_time_range(np.datetime64(t1), np.datetime64(t2))
    return lfp


class TestNaming:
    def test_timestr_exact_contract(self):
        # str(dt64[ms])[:21] with ":" removed (lf_das.py:23-26): one
        # sub-second digit survives
        t = np.datetime64("2023-01-02T03:04:05.123", "ms")
        assert get_timestr(t) == "2023-01-02T030405.1"

    def test_filename_exact_contract(self):
        t0 = np.datetime64("2023-01-02T03:04:05.123")
        t1 = np.datetime64("2023-01-02T03:05:45.900")
        assert (
            get_filename(t0, t1)
            == "LFDAS_2023-01-02T030405.1_2023-01-02T030545.9.h5"
        )


class TestMemoryModel:
    def test_closed_form(self):
        # 10 GB, 1 kHz, 1000 ch → 48 MB/s → ≈208 s (lf_das.py:98-106)
        t = get_patch_time(10000, 1000, 1000)
        assert abs(t - 10000 / 48.0) < 1e-9


class TestParameters:
    def test_defaults_and_frozen_view(self):
        lfp = LFProc()
        p = lfp.parameters
        assert p["output_sample_interval"] == 1.0
        assert p["process_patch_size"] == 100
        assert p["edge_buff_size"] == 10
        assert "data_gap_tolorance" in p  # reference-compat key
        with pytest.raises(TypeError):
            p["edge_buff_size"] = 3  # type: ignore[index]

    def test_unknown_key_warns_not_raises(self, capsys):
        lfp = LFProc()
        lfp.update_processing_parameter(bogus_key=1)
        assert "bogus_key is not default parameter key" in capsys.readouterr().out
        assert "bogus_key" not in lfp.parameters

    def test_output_folder_required(self):
        with pytest.raises(Exception, match="output folder"):
            LFProc().process_time_range(
                np.datetime64("2023-01-01"), np.datetime64("2023-01-02")
            )


class TestH2DStaging:
    def test_staged_run_byte_equal_to_unstaged(self, tmp_path, monkeypatch):
        """The prefetch-thread H2D staging (assemble -> stage ->
        compute -> write pipeline) must not change a single output
        byte vs the serial path (TPUDAS_H2D_STAGE=0)."""
        from tpudas import spool
        from tpudas.proc.lfproc import LFProc
        from tpudas.testing import make_synthetic_spool

        d = tmp_path / "raw"
        make_synthetic_spool(
            d, n_files=4, file_duration=30.0, fs=100.0, n_ch=6, noise=0.01
        )
        results = {}
        for label, env in (("staged", "1"), ("serial", "0")):
            monkeypatch.setenv("TPUDAS_H2D_STAGE", env)
            lfp = LFProc(spool(str(d)).sort("time").update())
            lfp.update_processing_parameter(
                output_sample_interval=1.0,
                process_patch_size=60,
                edge_buff_size=10,
            )
            out = tmp_path / f"out_{label}"
            lfp.set_output_folder(str(out), delete_existing=True)
            lfp.process_time_range(
                np.datetime64("2023-03-22T00:00:00"),
                np.datetime64("2023-03-22T00:02:00"),
            )
            results[label] = (
                spool(str(out)).update().chunk(time=None)[0].host_data()
            )
        assert np.array_equal(results["staged"], results["serial"])

    def test_stage_skips_oversized_windows(self, tmp_path, monkeypatch):
        from tpudas import spool
        from tpudas.proc.lfproc import LFProc
        from tpudas.testing import make_synthetic_spool

        d = tmp_path / "raw"
        make_synthetic_spool(
            d, n_files=2, file_duration=30.0, fs=100.0, n_ch=4, noise=0.01
        )
        monkeypatch.delenv("TPUDAS_H2D_STAGE", raising=False)
        lfp = LFProc(spool(str(d)).sort("time").update())
        monkeypatch.setattr(LFProc, "_STAGE_MAX_BYTES", 8)
        patch, staged = lfp._load_and_stage(
            np.datetime64("2023-03-22T00:00:00"),
            np.datetime64("2023-03-22T00:00:30"),
            "raise",
        )
        assert patch is not None
        assert staged is None  # over the two-resident-windows budget


class TestQuantizedFFTPath:
    def test_lowpass_resample_qscale_bitwise_matches_decoded(self):
        """The FFT engine's fused in-jit cast*scale is the same float
        op sequence as host decode — bit-identical results."""
        import jax.numpy as jnp

        from tpudas.proc.lfproc import lowpass_resample

        rng = np.random.default_rng(5)
        q = rng.integers(-3000, 3000, size=(4096, 8)).astype(np.int16)
        s = 2e-3
        idx = np.arange(0, 4095, 8, dtype=np.int32)
        w = np.zeros(idx.shape, np.float32)
        dec = q.astype(np.float32) * np.float32(s)
        ref = np.asarray(lowpass_resample(dec, 1e-3, 50.0, idx, w))
        got = np.asarray(
            lowpass_resample(jnp.asarray(q), 1e-3, 50.0, idx, w, qscale=s)
        )
        assert np.array_equal(got, ref)

    def test_lowpass_resample_qscale_dtype_validation(self):
        from tpudas.proc.lfproc import lowpass_resample

        idx = np.arange(0, 100, 8, dtype=np.int32)
        w = np.zeros(idx.shape, np.float32)
        with pytest.raises(ValueError, match="dtype"):
            lowpass_resample(
                np.zeros((512, 4), np.float32), 1e-3, 50.0, idx, w,
                qscale=0.5,
            )


class TestSchedule:
    def test_overlap_save_invariants(self):
        n, ps, buff = 500, 100, 10
        wins = schedule_windows(n, ps, buff)
        # emitted interiors tile [buff, ...) contiguously, no overlap
        assert wins[0][2] == buff
        for (pl, ph, el, eh), (nl, nh, nel, neh) in zip(wins, wins[1:]):
            assert nel == eh  # seamless
            assert nl == ph - 2 * buff  # window overlap = 2*buff
        # selections never exceed the grid
        assert all(0 <= a < b < n for a, b, _, _ in wins)

    def test_small_grid_shrinks_patch(self):
        wins = schedule_windows(50, 100, 5)
        assert wins[0][1] == 49

    def test_rejects_buffer_dominated_window(self):
        with pytest.raises(ValueError, match="edge_buff_size"):
            schedule_windows(500, 20, 10)


class TestEdgeCalibration:
    def test_probe_measures_fft_filter(self):
        edge = get_edge_effect_time(1 / FS, 60.0, tol=1e-3, freq=1 / DT_OUT)
        assert 0.5 < edge < 30.0

    def test_smaller_tol_wider_edge(self):
        e1 = get_edge_effect_time(1 / FS, 60.0, tol=1e-2, freq=1 / DT_OUT)
        e2 = get_edge_effect_time(1 / FS, 60.0, tol=1e-4, freq=1 / DT_OUT)
        assert e2 >= e1

    def test_chunk_too_small_raises(self):
        with pytest.raises(ValueError, match="edge_t value"):
            get_edge_effect_time(1 / FS, 4.0, tol=1e-9, freq=1.0)


class TestEndToEnd:
    def test_output_files_and_naming(self, spool_dir, tmp_path):
        out = tmp_path / "results"
        run_lfproc(
            spool_dir, out, "2023-03-22T00:00:00", "2023-03-22T00:04:00"
        )
        files = sorted(os.listdir(out))
        assert files and all(f.startswith("LFDAS_") and f.endswith(".h5") for f in files)

    def test_output_is_contiguous_and_decimated(self, spool_dir, tmp_path):
        out = tmp_path / "results"
        run_lfproc(
            spool_dir, out, "2023-03-22T00:00:00", "2023-03-22T00:04:00"
        )
        merged = spool(str(out)).update().chunk(time=None)
        assert len(merged) == 1
        p = merged[0]
        assert p.attrs["time_step"] == np.timedelta64(1, "s")
        steps = np.diff(p.coords["time"].astype(np.int64))
        assert np.all(steps == 1_000_000_000)

    def test_recovers_lowfreq_signal(self, spool_dir, tmp_path):
        out = tmp_path / "results"
        run_lfproc(
            spool_dir, out, "2023-03-22T00:00:00", "2023-03-22T00:04:00"
        )
        p = spool(str(out)).update().chunk(time=None)[0]
        data = p.host_data()
        truth_times = p.coords["time"]
        # rebuild the known LF component with the stream phase origin
        origin = np.datetime64("2023-03-22T00:00:00", "ns")
        t_sec = (truth_times - origin).astype(np.int64) / 1e9
        dists = p.coords["distance"]
        amp = 1.0 + dists / (dists.max() + 1.0)
        truth = np.sin(2 * np.pi * 0.05 * t_sec)[:, None] * amp[None, :]
        interior = slice(15, -15)
        err = np.abs(data[interior] - truth[interior])
        assert err.max() < 0.05

    def test_seam_freeness(self, spool_dir, tmp_path):
        """Chunked overlap-save output must equal single-shot whole-range
        processing — the invariant the scheduler exists to preserve."""
        chunked_dir = tmp_path / "chunked"
        single_dir = tmp_path / "single"
        t1, t2 = "2023-03-22T00:00:00", "2023-03-22T00:04:00"
        run_lfproc(spool_dir, chunked_dir, t1, t2, patch_size=60, buff=10)
        run_lfproc(spool_dir, single_dir, t1, t2, patch_size=239, buff=10)
        a = spool(str(chunked_dir)).update().chunk(time=None)[0]
        b = spool(str(single_dir)).update().chunk(time=None)[0]
        ta, tb = a.coords["time"], b.coords["time"]
        lo, hi = max(ta[0], tb[0]), min(ta[-1], tb[-1])
        asel = a.select(time=(lo, hi))
        bsel = b.select(time=(lo, hi))
        assert asel.shape == bsel.shape
        scale = np.abs(bsel.host_data()).max()
        assert np.abs(asel.host_data() - bsel.host_data()).max() < 5e-3 * scale

    def test_resume_with_overlap_is_seamless(self, spool_dir, tmp_path):
        """Kill-and-resume (the edge-loop contract, §3.2) must produce
        the same contiguous output as one uninterrupted run."""
        out_resumed = tmp_path / "resumed"
        out_full = tmp_path / "full"
        t1, tmid, t2 = (
            "2023-03-22T00:00:00",
            "2023-03-22T00:02:00",
            "2023-03-22T00:04:00",
        )
        buff = 10
        # phase 1: process the first half, then "crash"
        lfp = run_lfproc(spool_dir, out_resumed, t1, tmid, buff=buff)
        # phase 2: fresh engine resumes from output state with rewind
        lfp2 = LFProc(spool(spool_dir).sort("time").update())
        lfp2.update_processing_parameter(
            output_sample_interval=DT_OUT,
            process_patch_size=60,
            edge_buff_size=buff,
        )
        lfp2.set_output_folder(str(out_resumed), delete_existing=False)
        t_last = lfp2.get_last_processed_time()
        rewind = int((buff - 1) * DT_OUT)
        lfp2.process_time_range(
            t_last - np.timedelta64(rewind, "s"), np.datetime64(t2)
        )
        run_lfproc(spool_dir, out_full, t1, t2)
        a = spool(str(out_resumed)).update().chunk(time=None)
        assert len(a) == 1  # no seam, no gap
        b = spool(str(out_full)).update().chunk(time=None)[0]
        ta, tb = a[0].coords["time"], b.coords["time"]
        lo, hi = max(ta[0], tb[0]), min(ta[-1], tb[-1])
        asel = a[0].select(time=(lo, hi))
        bsel = b.select(time=(lo, hi))
        scale = np.abs(bsel.host_data()).max()
        assert np.abs(asel.host_data() - bsel.host_data()).max() < 5e-3 * scale

    def test_gap_skip_mode(self, tmp_path):
        d = tmp_path / "gappy"
        make_synthetic_spool(
            d, n_files=2, file_duration=30.0, fs=FS, n_ch=4, noise=0.0
        )
        make_synthetic_spool(
            d, n_files=2, file_duration=30.0, fs=FS, n_ch=4, noise=0.0,
            start="2023-03-22T00:02:00", prefix="late",
        )
        lfp = LFProc(spool(str(d)).sort("time").update())
        lfp.update_processing_parameter(
            output_sample_interval=DT_OUT,
            process_patch_size=40,
            edge_buff_size=5,
            on_gap="skip",
        )
        lfp.set_output_folder(str(tmp_path / "out"), delete_existing=True)
        lfp.process_time_range(
            np.datetime64("2023-03-22T00:00:00"),
            np.datetime64("2023-03-22T00:03:00"),
        )
        merged = spool(str(tmp_path / "out")).update().chunk(time=None)
        assert len(merged) >= 1  # produced output on both sides of the gap

    def test_cascade_single_sample_tail_window(self, spool_dir, tmp_path):
        # n_grid=142 with patch=60/buff=10 schedules a final window
        # emitting exactly ONE grid point; the forced cascade engine
        # must derive the ratio from the run-level grid step instead of
        # raising "grid not sample-aligned" mid-run
        from tpudas.proc.lfproc import schedule_windows

        wins = schedule_windows(142, 60, 10)
        assert wins[-1][3] - wins[-1][2] == 1  # precondition holds
        lfp = LFProc(spool(spool_dir).sort("time").update())
        lfp.update_processing_parameter(
            output_sample_interval=DT_OUT,
            process_patch_size=60,
            edge_buff_size=10,
            engine="cascade",
        )
        out = tmp_path / "tail1"
        lfp.set_output_folder(str(out), delete_existing=True)
        lfp.process_time_range(
            np.datetime64("2023-03-22T00:00:00"),
            np.datetime64("2023-03-22T00:02:22"),
        )
        merged = spool(str(out)).update().chunk(time=None)
        assert len(merged) == 1  # contiguous incl. the 1-sample tail
        times = merged[0].coords["time"]
        # emitted coverage = [first emit_lo, last emit_hi) of the schedule
        assert times.size == wins[-1][3] - wins[0][2]

    def test_gap_split_mode(self, tmp_path):
        # 60 s of data, a 60 s gap (> data_gap_tolorance), 60 s more:
        # on_gap="split" must emit one contiguous run per side of the
        # gap and never raise (lf_das.py:202's promised semantics)
        d = tmp_path / "gappy3"
        make_synthetic_spool(
            d, n_files=2, file_duration=30.0, fs=FS, n_ch=4, noise=0.0
        )
        make_synthetic_spool(
            d, n_files=2, file_duration=30.0, fs=FS, n_ch=4, noise=0.0,
            start="2023-03-22T00:02:00", prefix="late",
        )
        lfp = LFProc(spool(str(d)).sort("time").update())
        lfp.update_processing_parameter(
            output_sample_interval=DT_OUT,
            process_patch_size=40,
            edge_buff_size=5,
            on_gap="split",
            data_gap_tolorance=10.0,
        )
        out = tmp_path / "out3"
        lfp.set_output_folder(str(out), delete_existing=True)
        lfp.process_time_range(
            np.datetime64("2023-03-22T00:00:00"),
            np.datetime64("2023-03-22T00:03:00"),
        )
        merged = spool(str(out)).update().chunk(time=None)
        assert len(merged) == 2  # one contiguous run per segment
        runs = sorted(
            (p.coords["time"][0], p.coords["time"][-1]) for p in merged
        )
        # each run is interior to its segment (edge buffer trimmed at
        # the segment start, tail reaching the segment end)
        assert runs[0][0] == np.datetime64("2023-03-22T00:00:05")
        assert runs[0][1] <= np.datetime64("2023-03-22T00:01:00")
        assert runs[1][0] == np.datetime64("2023-03-22T00:02:05")
        assert runs[1][1] <= np.datetime64("2023-03-22T00:03:00")

    def test_gap_split_single_segment_matches_contiguous(self, spool_dir,
                                                         tmp_path):
        # with no gaps, split mode must be byte-identical to the default
        outs = {}
        for mode in ("raise", "split"):
            lfp = LFProc(spool(spool_dir).sort("time").update())
            lfp.update_processing_parameter(
                output_sample_interval=DT_OUT,
                process_patch_size=60,
                edge_buff_size=10,
                on_gap=mode,
            )
            out = tmp_path / f"split_{mode}"
            lfp.set_output_folder(str(out), delete_existing=True)
            lfp.process_time_range(
                np.datetime64("2023-03-22T00:00:00"),
                np.datetime64("2023-03-22T00:02:00"),
            )
            outs[mode] = spool(str(out)).update().chunk(time=None)[0]
        assert np.array_equal(
            outs["raise"].host_data(), outs["split"].host_data()
        )

    def test_invalid_on_gap_rejected(self):
        lfp = LFProc()
        with pytest.raises(ValueError, match="on_gap"):
            lfp.update_processing_parameter(on_gap="bogus")

    def test_window_timing_breakdown(self, spool_dir, tmp_path):
        # SURVEY §5 tracing row: per-phase wall breakdown on the
        # instance (assemble wait / device / HDF5 write)
        lfp = run_lfproc(
            spool_dir, tmp_path / "t", "2023-03-22T00:00:00",
            "2023-03-22T00:02:00",
        )
        t = lfp.timings
        assert set(t) == {"assemble_s", "device_s", "write_s"}
        assert t["device_s"] > 0 and t["write_s"] > 0
        assert all(v >= 0 for v in t.values())

    @pytest.mark.slow
    def test_trace_dir_writes_profile(self, spool_dir, tmp_path,
                                      monkeypatch):
        # TPUDAS_TRACE_DIR captures a jax.profiler device trace of the
        # whole run
        trace = tmp_path / "trace"
        monkeypatch.setenv("TPUDAS_TRACE_DIR", str(trace))
        run_lfproc(
            spool_dir, tmp_path / "out", "2023-03-22T00:00:00",
            "2023-03-22T00:01:00",
        )
        files = [f for _, _, fs in os.walk(trace) for f in fs]
        assert files, "no profiler trace written"

    def test_split_no_coverage_warns_loudly(self, spool_dir, tmp_path,
                                            capsys):
        # a split run whose range holds no data at all must say so —
        # silently completing looks like a successful run (round-2
        # advisor finding)
        lfp = LFProc(spool(spool_dir).sort("time").update())
        lfp.update_processing_parameter(
            output_sample_interval=DT_OUT,
            process_patch_size=60,
            edge_buff_size=10,
            on_gap="split",
        )
        out = tmp_path / "empty"
        lfp.set_output_folder(str(out), delete_existing=True)
        lfp.process_time_range(
            np.datetime64("2024-01-01T00:00:00"),  # a year off the data
            np.datetime64("2024-01-01T00:02:00"),
        )
        captured = capsys.readouterr().out
        assert "no data coverage" in captured
        assert not [f for f in os.listdir(out) if f.endswith(".h5")]

    def test_split_mode_invalid_patch_buff_raises(self, spool_dir,
                                                  tmp_path):
        # an invalid global config must fail loudly, not be swallowed
        # per segment as "too short"
        lfp = LFProc(spool(spool_dir).sort("time").update())
        lfp.update_processing_parameter(
            output_sample_interval=DT_OUT,
            process_patch_size=20,
            edge_buff_size=10,
            on_gap="split",
        )
        lfp.set_output_folder(str(tmp_path / "bad"), delete_existing=True)
        with pytest.raises(ValueError, match="process_patch_size"):
            lfp.process_time_range(
                np.datetime64("2023-03-22T00:00:00"),
                np.datetime64("2023-03-22T00:02:00"),
            )

    @pytest.mark.slow
    def test_10k_channel_window_config4_shapes(self, tmp_path):
        """BASELINE config 4 shapes on CPU: one overlap-save window of a
        10,000-channel 1 kHz stream through schedule_windows ->
        _process_window, both engines — exercises the static-shape /
        memory story at production channel count before hardware."""
        from tpudas.core.patch import Patch
        from tpudas.core.timeutils import build_time_grid

        fs, n_ch, d_t = 1000.0, 10_000, 1.0
        patch_size, buff = 16, 2
        t0 = np.datetime64("2023-03-22T00:00:00")
        grid = build_time_grid(
            t0, t0 + np.timedelta64(patch_size + 1, "s"), d_t
        )
        wins = schedule_windows(len(grid), patch_size, buff)
        assert len(wins) == 1
        sel_lo, sel_hi, emit_lo, emit_hi = wins[0]
        T = sel_hi * 1000 + 1  # rows covering [grid[0], grid[sel_hi]]
        rng = np.random.default_rng(0)
        data = rng.standard_normal((T, n_ch)).astype(np.float32)
        times = t0.astype("datetime64[ns]") + np.arange(T) * np.timedelta64(
            1_000_000, "ns"
        )
        window = Patch(
            data=data,
            coords={
                "time": times,
                "distance": np.arange(n_ch, dtype=np.float64),
            },
            dims=("time", "distance"),
        )
        corner = 1.0 / d_t / 2.0 * 0.9
        for engine in ("cascade", "fft"):
            lfp = LFProc()
            lfp.update_processing_parameter(engine=engine)
            out = tmp_path / f"big_{engine}"
            lfp.set_output_folder(str(out), delete_existing=True)
            lfp._process_window(
                window, grid[emit_lo:emit_hi], d_t, corner, 4
            )
            (fname,) = os.listdir(out)
            (result,) = spool(str(out)).update()
            assert result.host_data().shape == (emit_hi - emit_lo, n_ch)
            assert np.isfinite(result.host_data()).all()

    def test_gap_raise_mode(self, tmp_path):
        d = tmp_path / "gappy2"
        make_synthetic_spool(d, n_files=1, file_duration=30.0, fs=FS, n_ch=4)
        make_synthetic_spool(
            d, n_files=1, file_duration=30.0, fs=FS, n_ch=4,
            start="2023-03-22T00:02:00", prefix="late",
        )
        lfp = LFProc(spool(str(d)).sort("time").update())
        lfp.update_processing_parameter(
            output_sample_interval=DT_OUT, process_patch_size=40,
            edge_buff_size=5,
        )
        lfp.set_output_folder(str(tmp_path / "out2"), delete_existing=True)
        with pytest.raises(Exception, match="Gap in data exists"):
            lfp.process_time_range(
                np.datetime64("2023-03-22T00:00:00"),
                np.datetime64("2023-03-22T00:03:00"),
            )


class TestGapTolerance:
    """data_gap_tolorance's single meaning (the key the reference
    declares but never reads, lf_das.py:202): a hole of at most that
    many seconds between consecutive files is NOT a gap — the window
    merge bridges it by linear interpolation — while anything wider is
    a gap handled per on_gap."""

    def _gappy_spool(self, d, hole_s):
        # 2 files, a hole, 2 more files (contiguous inside each half)
        make_synthetic_spool(
            d, n_files=2, file_duration=30.0, fs=FS, n_ch=4, noise=0.0
        )
        t2 = np.datetime64("2023-03-22T00:01:00") + np.timedelta64(
            int(hole_s * 1e9), "ns"
        )
        make_synthetic_spool(
            d, n_files=2, file_duration=30.0, fs=FS, n_ch=4, noise=0.0,
            start=str(t2), prefix="late",
        )

    def test_sub_tolerance_hole_is_filled_not_raised(self, tmp_path):
        from tpudas.utils.logging import set_log_handler

        d = tmp_path / "gappy"
        self._gappy_spool(d, hole_s=5.0)  # < default tolerance 10 s
        lfp = LFProc(spool(str(d)).sort("time").update())
        lfp.update_processing_parameter(
            output_sample_interval=DT_OUT, process_patch_size=40,
            edge_buff_size=5,  # on_gap stays "raise" (the default)
        )
        out = tmp_path / "out"
        lfp.set_output_folder(str(out), delete_existing=True)
        events = []
        set_log_handler(events.append)
        try:
            lfp.process_time_range(
                np.datetime64("2023-03-22T00:00:00"),
                np.datetime64("2023-03-22T00:02:00"),
            )
        finally:
            set_log_handler(None)
        merged = spool(str(out)).update().chunk(time=None)
        assert len(merged) == 1  # contiguous output across the hole
        assert any(e["event"] == "gap_filled" for e in events)

    def test_tolerance_zero_restores_strict_raise(self, tmp_path):
        d = tmp_path / "gappy0"
        self._gappy_spool(d, hole_s=5.0)
        lfp = LFProc(spool(str(d)).sort("time").update())
        lfp.update_processing_parameter(
            output_sample_interval=DT_OUT, process_patch_size=40,
            edge_buff_size=5, data_gap_tolorance=0.0,
        )
        lfp.set_output_folder(str(tmp_path / "out"), delete_existing=True)
        with pytest.raises(Exception, match="Gap in data exists"):
            lfp.process_time_range(
                np.datetime64("2023-03-22T00:00:00"),
                np.datetime64("2023-03-22T00:02:00"),
            )

    def test_wider_than_tolerance_hole_still_raises(self, tmp_path):
        d = tmp_path / "gappy2"
        self._gappy_spool(d, hole_s=30.0)  # > default tolerance 10 s
        lfp = LFProc(spool(str(d)).sort("time").update())
        lfp.update_processing_parameter(
            output_sample_interval=DT_OUT, process_patch_size=40,
            edge_buff_size=5,
        )
        lfp.set_output_folder(str(tmp_path / "out"), delete_existing=True)
        with pytest.raises(Exception, match="Gap in data exists"):
            lfp.process_time_range(
                np.datetime64("2023-03-22T00:00:00"),
                np.datetime64("2023-03-22T00:02:30"),
            )

    def test_merge_fill_values_are_linear(self):
        from tpudas.core.patch import Patch
        from tpudas.io.spool import merge_patches

        def mk(t0, vals):
            vals = np.asarray(vals, dtype=np.float32)[:, None]
            times = np.datetime64(t0, "ns") + np.arange(
                len(vals)
            ) * np.timedelta64(100_000_000, "ns")  # 10 Hz
            return Patch(
                data=vals,
                coords={"time": times, "distance": np.array([0.0])},
                dims=("time", "distance"),
                attrs={"d_time": 0.1, "d_distance": 1.0},
            )

        a = mk("2023-01-01T00:00:00", [0.0, 1.0, 2.0])
        # hole of 3 missing samples: last a-sample at 0.2 s, b starts
        # at 0.6 s -> fills at 0.3/0.4/0.5 s, linear from 2.0 to 6.0
        b = mk("2023-01-01T00:00:00.6", [6.0, 7.0])
        out = merge_patches([a, b], max_fill=1.0)
        assert len(out) == 1
        got = out[0].host_data()[:, 0]
        np.testing.assert_allclose(
            got, [0, 1, 2, 3, 4, 5, 6, 7], rtol=1e-6
        )
        # off-grid hole (not a multiple of the step): NOT filled
        c = mk("2023-01-01T00:00:00.65", [6.0, 7.0])
        assert len(merge_patches([a, c], max_fill=1.0)) == 2
        # hole longer than max_fill: NOT filled
        d = mk("2023-01-01T00:00:01.6", [6.0, 7.0])
        assert len(merge_patches([a, d], max_fill=1.0)) == 2


class TestNorthStarWidthIngest:
    @pytest.mark.slow
    def test_10k_channel_full_product_path(self, tmp_path):
        """BASELINE config-4 WIDTH through the ENTIRE product path —
        tdas int16 spool -> index planning -> native C++ window
        assembly -> device kernel -> HDF5 emission -> merge — not just
        the window shapes. Slow CPU run at reduced rate/duration; the
        on-chip rate for this path is the campaign's e2e step."""
        from tpudas import spool

        fs, n_ch = 50.0, 10_000
        d = tmp_path / "raw"
        make_synthetic_spool(
            d, n_files=3, file_duration=30.0, fs=fs, n_ch=n_ch,
            noise=0.01, format="tdas",
            write_kwargs={"dtype": "int16", "scale": 1e-3},
        )
        lfp = LFProc(spool(str(d)).sort("time").update())
        lfp.update_processing_parameter(
            output_sample_interval=1.0,
            process_patch_size=40,
            edge_buff_size=5,
        )
        out = tmp_path / "out"
        lfp.set_output_folder(str(out), delete_existing=True)
        lfp.process_time_range(
            np.datetime64("2023-03-22T00:00:00"),
            np.datetime64("2023-03-22T00:01:30"),
        )
        # the native (C++ assembler) fast path must have carried the
        # windows — a silent fallback to per-file numpy merge at this
        # width is exactly what this test exists to catch
        assert lfp.native_windows == sum(lfp.engine_counts.values()) > 0
        merged = spool(str(out)).update().chunk(time=None)
        assert len(merged) == 1
        p = merged[0]
        assert p.host_data().shape[p.dims.index("distance")] == n_ch
        assert np.isfinite(p.host_data()).all()
