"""Streaming drivers: polling, incremental processing, resume-with-
overlap seam-freeness, termination — with injected sleep/clock."""

import os

import numpy as np
import pytest

from tpudas import spool
from tpudas.core.units import s as sec
from tpudas.io.registry import write_patch
from tpudas.core.timeutils import to_datetime64
from tpudas.proc.streaming import (
    clamp_poll_interval,
    run_lowpass_realtime,
    run_rolling_realtime,
)
from tpudas.testing import make_synthetic_spool, synthetic_patch

FS = 100.0
FILE_SEC = 30.0
NCH = 6


def _append_files(directory, start_index, count):
    t0 = to_datetime64("2023-03-22T00:00:00").astype("datetime64[ns]")
    step = np.timedelta64(int(round(1e9 / FS)), "ns")
    n = int(FILE_SEC * FS)
    for i in range(start_index, start_index + count):
        p = synthetic_patch(
            t0=t0 + i * n * step, duration=FILE_SEC, fs=FS, n_ch=NCH,
            seed=i, phase_origin=t0, noise=0.01,
        )
        write_patch(p, os.path.join(directory, f"raw_{i:04d}.h5"))


class TestClamp:
    def test_reference_cadence_guard(self):
        # max(125, requested, file_len, 3x edge buffer) — the 125 s
        # floor is absolute (low_pass_dascore_edge.ipynb:165-173), even
        # when the caller requests a faster cadence
        assert clamp_poll_interval(125, 30, 10) == 125
        assert clamp_poll_interval(125, 300, 10) == 300
        assert clamp_poll_interval(10, 5, 40) == 125.0
        assert clamp_poll_interval(5, 1, 1) == 125.0
        assert clamp_poll_interval(500, 30, 10) == 500.0
        assert clamp_poll_interval(10, 30, 60) == 180.0


class TestCoveredWorkload:
    def test_nan_index_cells_degrade_not_crash(self):
        """A legacy/heterogeneous index row with NaN ntime/ndistance
        must degrade the round metric to zero samples for that file,
        never crash the processing loop (round-2 advisor finding)."""
        import pandas as pd

        from tpudas.proc.streaming import _covered_workload

        t0 = np.datetime64("2023-03-22T00:00:00")
        contents = pd.DataFrame(
            [
                {
                    "time_min": t0,
                    "time_max": t0 + np.timedelta64(30, "s"),
                    "ntime": 3000,
                    "ndistance": 6,
                },
                {
                    "time_min": t0 + np.timedelta64(30, "s"),
                    "time_max": t0 + np.timedelta64(60, "s"),
                    "ntime": float("nan"),
                    "ndistance": float("nan"),
                },
                {
                    "time_min": t0 + np.timedelta64(60, "s"),
                    "time_max": t0 + np.timedelta64(90, "s"),
                    "ntime": None,
                    "ndistance": 6,
                },
            ]
        )
        data_sec, samples = _covered_workload(
            contents, t0, t0 + np.timedelta64(90, "s")
        )
        assert np.isfinite(samples)
        int(samples)  # what the realtime loop does with it
        assert data_sec == 90.0
        # only the well-formed first file contributes samples
        assert samples == pytest.approx(30.0 * (2999 / 30.0) * 6)


class TestLowpassRealtime:
    def test_rounds_resume_and_terminate(self, tmp_path):
        src = str(tmp_path / "raw")
        out = str(tmp_path / "results")
        make_synthetic_spool(
            src, n_files=3, file_duration=FILE_SEC, fs=FS, n_ch=NCH, noise=0.01
        )
        # feed two more files between rounds via the injected sleep
        state = {"fed": 0}

        def fake_sleep(_):
            if state["fed"] < 1:
                _append_files(src, 3, 2)
                state["fed"] += 1

        rounds = run_lowpass_realtime(
            source=src,
            output_folder=out,
            start_time="2023-03-22T00:00:00",
            output_sample_interval=1.0,
            edge_buffer=8.0,
            process_patch_size=40,
            poll_interval=0.0,
            file_duration=0.0,
            sleep_fn=fake_sleep,
        )
        assert rounds == 2  # initial + one resume, then clean termination
        merged = spool(out).update().chunk(time=None)
        assert len(merged) == 1  # resumed output is seam-free
        p = merged[0]
        steps = np.diff(p.coords["time"].astype(np.int64))
        assert np.all(steps == 1_000_000_000)
        # covers (nearly) the whole 150 s stream minus edges
        assert p.shape[0] > 120

    @pytest.mark.slow
    def test_fractional_dt_resume_is_seam_free(self, tmp_path):
        # regression: the resume rewind must stay on the output grid
        # for non-integer-second output intervals
        src = str(tmp_path / "raw")
        out = str(tmp_path / "results")
        make_synthetic_spool(
            src, n_files=2, file_duration=FILE_SEC, fs=FS, n_ch=NCH, noise=0.01
        )
        state = {"fed": 0}

        def fake_sleep(_):
            if state["fed"] < 1:
                _append_files(src, 2, 1)
                state["fed"] += 1

        rounds = run_lowpass_realtime(
            source=src,
            output_folder=out,
            start_time="2023-03-22T00:00:00",
            output_sample_interval=0.5,
            edge_buffer=8.0,
            process_patch_size=60,
            poll_interval=0.0,
            sleep_fn=fake_sleep,
        )
        assert rounds == 2
        merged = spool(out).update().chunk(time=None)
        assert len(merged) == 1
        steps = np.diff(merged[0].coords["time"].astype(np.int64))
        assert np.all(steps == 500_000_000)

    @pytest.mark.slow
    def test_engine_and_gap_params_plumbed_with_rt_events(self, tmp_path):
        # the streaming driver must reach the
        # cascade engine and report per-round real-time factor
        from tpudas.utils.logging import set_log_handler
        from tpudas.utils.profiling import Counters

        src = str(tmp_path / "raw")
        out = str(tmp_path / "results")
        make_synthetic_spool(
            src, n_files=3, file_duration=FILE_SEC, fs=FS, n_ch=NCH,
            noise=0.01,
        )
        events = []
        set_log_handler(events.append)
        counters = Counters()
        try:
            rounds = run_lowpass_realtime(
                src,
                out,
                "2023-03-22T00:00:00",
                output_sample_interval=1.0,
                edge_buffer=10.0,
                process_patch_size=40,
                sleep_fn=lambda _: None,
                max_rounds=3,
                engine="cascade",
                on_gap="split",
                filter_order=6,
                counters=counters,
            )
        finally:
            set_log_handler(None)
        assert rounds >= 1
        # ground truth: the cascade engine actually ran the windows
        # (window_engine now names the sub-engine: cascade-xla on CPU)
        ran = [e for e in events if e["event"] == "window_engine"]
        assert ran and all(e["engine"] == "cascade-xla" for e in ran)
        # per-round real-time factor is reported and accumulated
        rts = [
            e for e in events if e["event"] == "realtime_round"
        ]
        assert rts and all(e["realtime_factor"] > 0 for e in rts)
        assert all(e["engine"] == "cascade" for e in rts)
        # engine_counts ride along each round event (ground truth for
        # operators without the log handler)
        assert all(
            sum(e["engine_counts"].values()) > 0
            and e["engine_counts"]["fft"] == 0
            for e in rts
        )
        assert counters.realtime_factor > 0
        assert counters.wall_seconds > 0

    def test_empty_source_terminates_with_max_rounds(self, tmp_path):
        src = tmp_path / "empty_raw"
        src.mkdir()
        rounds = run_lowpass_realtime(
            source=str(src),
            output_folder=str(tmp_path / "out"),
            start_time="2023-03-22T00:00:00",
            output_sample_interval=1.0,
            edge_buffer=8.0,
            process_patch_size=40,
            poll_interval=0.0,
            sleep_fn=lambda _: None,
            max_rounds=3,
        )
        assert rounds == 0

    def test_max_rounds_cap(self, tmp_path):
        src = str(tmp_path / "raw")
        out = str(tmp_path / "results")
        make_synthetic_spool(
            src, n_files=3, file_duration=FILE_SEC, fs=FS, n_ch=NCH
        )
        rounds = run_lowpass_realtime(
            source=src,
            output_folder=out,
            start_time="2023-03-22T00:00:00",
            output_sample_interval=1.0,
            edge_buffer=8.0,
            process_patch_size=40,
            poll_interval=0.0,
            sleep_fn=lambda _: None,
            max_rounds=1,
        )
        assert rounds == 1


class TestRollingRealtime:
    def test_processes_only_new_patches(self, tmp_path):
        src = str(tmp_path / "raw")
        out = str(tmp_path / "results")
        make_synthetic_spool(
            src, n_files=2, file_duration=FILE_SEC, fs=FS, n_ch=NCH
        )
        state = {"fed": 0}

        def fake_sleep(_):
            if state["fed"] < 1:
                _append_files(src, 2, 1)
                state["fed"] += 1

        rounds = run_rolling_realtime(
            source=src,
            output_folder=out,
            window=1.0 * sec,
            step=1.0 * sec,
            scale=2.0,
            poll_interval=0.0,
            sleep_fn=fake_sleep,
        )
        assert rounds == 2
        outs = [f for f in os.listdir(out) if f.endswith(".h5")]
        assert len(outs) == 3  # one output file per input patch
        # stateless per-file: each output has its own NaN warm-up row
        for p in spool(out).update():
            host = p.host_data()
            assert np.isnan(host[0]).all() and np.isfinite(host[1:]).all()

    def test_out_of_order_arrival_still_processed(self, tmp_path):
        # regression: a late-arriving file with an EARLIER timestamp
        # must be processed (positional high-water marks skip it)
        src = str(tmp_path / "raw")
        out = str(tmp_path / "results")
        os.makedirs(src)
        _append_files(src, 2, 1)  # only the third file exists initially
        state = {"fed": 0}

        def fake_sleep(_):
            if state["fed"] < 1:
                _append_files(src, 0, 2)  # earlier files arrive late
                state["fed"] += 1

        rounds = run_rolling_realtime(
            source=src,
            output_folder=out,
            window=1.0 * sec,
            step=1.0 * sec,
            poll_interval=0.0,
            sleep_fn=fake_sleep,
        )
        assert rounds == 2
        outs = [f for f in os.listdir(out) if f.endswith(".h5")]
        assert len(outs) == 3  # all three inputs processed exactly once


class TestTerminationAndRecovery:
    def test_empty_source_terminates_without_max_rounds(self, tmp_path):
        """A source that never produces files must end the loop on the
        second empty poll, not spin forever (reference semantics: the
        loop ends when the spool stops growing)."""
        src = tmp_path / "raw"
        src.mkdir()
        polls = {"n": 0}

        def guarded_sleep(_):
            polls["n"] += 1
            if polls["n"] > 5:
                raise AssertionError("realtime loop failed to terminate")

        rounds = run_lowpass_realtime(
            source=str(src),
            output_folder=str(tmp_path / "out"),
            start_time="2023-03-22T00:00:00",
            output_sample_interval=1.0,
            edge_buffer=5.0,
            process_patch_size=40,
            poll_interval=0.0,
            sleep_fn=guarded_sleep,
        )
        assert rounds == 0

    def test_resume_after_round_with_no_output(self, tmp_path):
        """A round that completes without emitting files (stream still
        behind start_time) must not crash the next round's resume — it
        retries from start_time instead (crash-only contract)."""
        src = str(tmp_path / "raw")
        out = str(tmp_path / "out")
        # file 0 covers 00:00:00-00:00:30, far before start_time
        make_synthetic_spool(
            src, n_files=1, file_duration=FILE_SEC, fs=FS, n_ch=NCH
        )

        def feed_late(_):
            # file at index 20 covers 00:10:00-00:10:30 (= start_time)
            if not any(f.startswith("raw_0020") for f in os.listdir(src)):
                _append_files(src, 20, 1)

        rounds = run_lowpass_realtime(
            source=src,
            output_folder=out,
            start_time="2023-03-22T00:10:00",
            output_sample_interval=1.0,
            edge_buffer=3.0,
            process_patch_size=20,
            poll_interval=0.0,
            sleep_fn=feed_late,
        )
        assert rounds == 2
        produced = [f for f in os.listdir(out) if f.endswith(".h5")]
        assert produced  # the second round recovered and emitted output


class TestJointRealtime:
    @pytest.mark.slow
    def test_joint_streaming_rolls_and_resumes(self, tmp_path):
        """The realtime loop with a rolling_output_folder emits BOTH
        products each round (config 5, streaming form); across resumed
        rounds the rolling product stays seam-free and matches a batch
        JointProc run over the full stream interior."""
        from tpudas.proc.joint import JointProc

        src = str(tmp_path / "raw")
        out = str(tmp_path / "results")
        roll = str(tmp_path / "rolling")
        make_synthetic_spool(
            src, n_files=3, file_duration=FILE_SEC, fs=FS, n_ch=NCH,
            noise=0.01,
        )
        state = {"fed": 0}

        def fake_sleep(_):
            if state["fed"] < 1:
                _append_files(src, 3, 2)
                state["fed"] += 1

        rounds = run_lowpass_realtime(
            source=src,
            output_folder=out,
            start_time="2023-03-22T00:00:00",
            output_sample_interval=1.0,
            edge_buffer=8.0,
            process_patch_size=40,
            poll_interval=0.0,
            file_duration=0.0,
            sleep_fn=fake_sleep,
            rolling_output_folder=roll,
            rolling_window=3.0,
            rolling_step=1.0,
        )
        assert rounds == 2
        merged = spool(roll).update().chunk(time=None)
        assert len(merged) == 1, "streamed rolling product has a seam"
        got = merged[0]
        assert np.isfinite(got.host_data()).all()
        steps = np.diff(got.coords["time"].astype(np.int64))
        assert np.all(steps == 1_000_000_000)
        # batch joint run over the same (final) stream for comparison
        jp = JointProc(spool(src).sort("time").update())
        jp.update_processing_parameter(
            output_sample_interval=1.0,
            process_patch_size=40,
            edge_buff_size=8,
            rolling_window=3.0,
            rolling_step=1.0,
        )
        jp.set_output_folder(str(tmp_path / "blf"), delete_existing=True)
        jp.set_rolling_output_folder(
            str(tmp_path / "broll"), delete_existing=True
        )
        jp.process_time_range(
            np.datetime64("2023-03-22T00:00:00"),
            np.datetime64(
                spool(src).update().get_contents()["time_max"].max()
            ),
        )
        ref = spool(str(tmp_path / "broll")).update().chunk(time=None)[0]
        ta, tb = got.coords["time"], ref.coords["time"]
        lo, hi = max(ta[0], tb[0]), min(ta[-1], tb[-1])
        a = got.select(time=(lo, hi)).host_data()
        b = ref.select(time=(lo, hi)).host_data()
        assert a.shape == b.shape
        assert np.abs(a - b).max() < 1e-6 * np.abs(b).max() + 1e-7

    def test_rolling_params_without_folder_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="rolling_output_folder"):
            run_lowpass_realtime(
                source=str(tmp_path),
                output_folder=str(tmp_path / "out"),
                start_time="2023-03-22T00:00:00",
                output_sample_interval=1.0,
                edge_buffer=8.0,
                process_patch_size=40,
                rolling_window=3.0,
            )
