"""ISSUE 10: the fused O(1) streaming cascade kernel.

Pins the fused-engine contracts:
- ops level: the fused-xla scan is BYTE-IDENTICAL to the per-stage
  reference cascade — outputs and every carry leaf — across uneven
  block schedules, and the fused-pallas v3 kernel (interpret mode on
  CPU) matches within the pinned tolerance with a NaN set no wider
  than the reference's;
- the carry layout is shared, so a stream crosses cascade <-> fused
  mid-run (ops level and full-driver level, both directions) with no
  seam and byte-identity against a single-engine control;
- serialized carry: save/load round-trips the fused stream's carry
  bit-exactly and resumes seam-free;
- mesh: the fused step under a 4-device CPU channel mesh is
  byte-identical to the single-device fused step (and therefore to
  the reference cascade);
- the stale-knob fix: TPUDAS_FUSED_* / TPUDAS_PALLAS_* /
  TPUDAS_STREAM_PALLAS changes apply mid-process with no cache clear
  (every dispatch cache keys on tpudas.ops.fir.knob_fingerprint).
"""

import os

import numpy as np
import pytest

from tpudas.ops.fir import (
    cascade_decimate_stream,
    cascade_stream_init,
    design_cascade,
    fused_chunk_outputs,
    fused_intermediate_bytes,
    knob_fingerprint,
    resolve_stream_engine,
    stream_carry_sizes,
)

# the fused-pallas v3 kernel runs exact-f32 VPU arithmetic but groups
# the per-tap sums by shifted frames, so it is tolerance-pinned (the
# fused-XLA scan is byte-identical and asserted as such); measured
# interpret-mode worst case 2.3e-7 relative (PERF.md §6 (PR 10))
PALLAS_RTOL = 5e-7

PLANS = [(100.0, 100), (200.0, 40), (50.0, 7)]


def _run_stream(plan, blocks, engine, n_ch, mesh=None):
    carry = cascade_stream_init(plan, n_ch)
    outs = []
    for b in blocks:
        y, carry = cascade_decimate_stream(b, carry, plan, engine,
                                           mesh=mesh)
        outs.append(np.asarray(y))
    from tpudas.parallel.sharding import gather_leaves

    return np.concatenate(outs), gather_leaves(carry, n_ch)


def _blocks(plan, seed=0, n_ch=5, nan_gap=False):
    rng = np.random.default_rng(seed)
    blocks = [
        rng.standard_normal((n * plan.ratio, n_ch)).astype(np.float32)
        for n in (50, 13, 1, 27, 40)
    ]
    if nan_gap:
        # gap-fill style NaN runs, one spanning a block seam
        blocks[1][plan.ratio : 2 * plan.ratio, 2] = np.nan
        blocks[3][-plan.ratio // 2 :, 0] = np.nan
        blocks[4][: plan.ratio // 2, 0] = np.nan
    return blocks


class TestFusedOps:
    @pytest.mark.parametrize("fs,ratio", PLANS)
    @pytest.mark.parametrize("nan_gap", [False, True])
    @pytest.mark.slow
    def test_fused_xla_byte_identical(self, fs, ratio, nan_gap):
        """The fused scan replays the per-stage arithmetic chunk by
        chunk: outputs AND every carry leaf byte-identical to the
        reference cascade, NaN-gap blocks included."""
        plan = design_cascade(fs, ratio, 0.45 * fs / ratio, 4)
        blocks = _blocks(plan, nan_gap=nan_gap)
        y0, c0 = _run_stream(plan, blocks, "xla", 5)
        y1, c1 = _run_stream(plan, blocks, "fused-xla", 5)
        np.testing.assert_array_equal(y0, y1)
        for a, b in zip(c0, c1):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("fs,ratio", PLANS)
    @pytest.mark.slow
    def test_fused_pallas_pinned_tolerance(self, fs, ratio):
        """The v3 kernel (interpret mode on CPU = exact f32 dots)
        matches the reference within PALLAS_RTOL, outputs and carry —
        the recorded tolerance of PERF.md §6 (PR 10)."""
        plan = design_cascade(fs, ratio, 0.45 * fs / ratio, 4)
        blocks = _blocks(plan)
        y0, c0 = _run_stream(plan, blocks, "xla", 5)
        y2, c2 = _run_stream(plan, blocks, "fused-pallas", 5)
        scale = np.abs(y0).max()
        assert np.abs(y0 - y2).max() / scale < PALLAS_RTOL
        for a, b in zip(c0, c2):
            if a.size:
                s = max(np.abs(a).max(), scale)
                assert np.abs(a - b).max() / s < PALLAS_RTOL

    def test_fused_pallas_nan_subset(self):
        """NaN-gap blocks through the v3 kernel: the NaN set is a
        SUBSET of the reference's (the kernel's tap window is exactly
        the receptive field — the polyphase formulation additionally
        smears NaN through its zero-padded tap slack) and all
        mutually-finite samples agree within tolerance."""
        plan = design_cascade(100.0, 100, 0.45, 4)
        blocks = _blocks(plan, seed=2, nan_gap=True)
        y0, _ = _run_stream(plan, blocks, "xla", 5)
        y2, _ = _run_stream(plan, blocks, "fused-pallas", 5)
        n0, n2 = np.isnan(y0), np.isnan(y2)
        assert n0.any()  # the gap actually produced NaNs
        assert np.all(~n2 | n0), "kernel smeared NaN wider than the ref"
        both = ~n0 & ~n2
        scale = np.nanmax(np.abs(y0))
        assert np.abs(y0[both] - y2[both]).max() / scale < PALLAS_RTOL

    def test_ops_level_crossover_mid_stream(self):
        """The carry tuple moves between engines freely: alternating
        per-stage / fused steps equals the pure reference run
        byte-for-byte."""
        plan = design_cascade(100.0, 100, 0.45, 4)
        blocks = _blocks(plan, seed=3)
        y0, c0 = _run_stream(plan, blocks, "xla", 5)
        engines = ["xla", "fused-xla", "fused-xla", "xla", "fused-xla"]
        carry = cascade_stream_init(plan, 5)
        outs = []
        for b, eng in zip(blocks, engines):
            y, carry = cascade_decimate_stream(b, carry, plan, eng)
            outs.append(np.asarray(y))
        np.testing.assert_array_equal(y0, np.concatenate(outs))
        for a, b in zip(c0, carry):
            np.testing.assert_array_equal(a, np.asarray(b))

    def test_resolver_threshold_and_literals(self, monkeypatch):
        plan = design_cascade(100.0, 100, 0.45, 4)
        with pytest.raises(ValueError, match="stream engine"):
            resolve_stream_engine("warp", plan, 100, 4)
        monkeypatch.setenv("TPUDAS_FUSED_MIN_ELEMS", "1000000")
        # below threshold: a "fused" request degrades to the chain
        assert resolve_stream_engine("fused", plan, 100, 4) == "xla"
        # explicit variants are forced regardless of size
        assert (
            resolve_stream_engine("fused-xla", plan, 100, 4)
            == "fused-xla"
        )
        monkeypatch.setenv("TPUDAS_FUSED_MIN_ELEMS", "1")
        assert resolve_stream_engine("fused", plan, 100, 4) == "fused-xla"

    def test_chunking_divides_blocks(self, monkeypatch):
        plan = design_cascade(1000.0, 1000, 0.45, 4)
        assert fused_chunk_outputs(plan, 20) in (4, 5, 8, 10, 20)
        for n_out in (1, 7, 20, 64, 40):
            c = fused_chunk_outputs(plan, n_out)
            assert n_out % c == 0
        monkeypatch.setenv("TPUDAS_FUSED_CHUNK", "4")
        assert fused_chunk_outputs(plan, 20) == 4

    def test_intermediate_bytes_proxy(self):
        plan = design_cascade(1000.0, 1000, 0.45, 4)  # R = 8,5,5,5
        T, C = 8000, 10
        # stage outputs at 1000, 200, 40 rows are the intermediates
        assert fused_intermediate_bytes(plan, T, C) == (
            (1000 + 200 + 40) * C * 4
        )


class TestKnobFingerprint:
    """The stale-knob fix: env changes take effect mid-process."""

    def test_fingerprint_tracks_env(self, monkeypatch):
        monkeypatch.delenv("TPUDAS_FUSED_CHUNK", raising=False)
        a = knob_fingerprint()
        monkeypatch.setenv("TPUDAS_FUSED_CHUNK", "16")
        b = knob_fingerprint()
        assert a != b

    def test_stream_pallas_selector_applies_live(self, monkeypatch):
        """TPUDAS_STREAM_PALLAS flips the per-stage kernel routing
        with NO cache clear or restart — the mid-process-change
        footgun the knob fingerprint closes."""
        from tpudas.ops.fir import stream_stage_engines

        plan = design_cascade(100.0, 100, 0.45, 4)
        monkeypatch.setenv("TPUDAS_STREAM_PALLAS", "1")
        monkeypatch.setenv("TPUDAS_PALLAS_MIN_ELEMS", "1")
        # small taps stages fit the kernel sub-block -> pallas routed
        eng_on = stream_stage_engines(plan, 100 * 128, 4, "pallas")
        assert "pallas" in eng_on
        monkeypatch.setenv("TPUDAS_STREAM_PALLAS", "0")
        eng_off = stream_stage_engines(plan, 100 * 128, 4, "pallas")
        assert "pallas" not in eng_off

    def test_fused_threshold_applies_live_through_dispatch(
        self, monkeypatch
    ):
        """A retuned TPUDAS_FUSED_MIN_ELEMS changes what an engine
        "fused" DISPATCH actually runs, mid-process: the compiled-fn
        caches key on the fingerprint, so no stale executable is
        reused."""
        from tpudas.obs.registry import MetricsRegistry, use_registry

        plan = design_cascade(100.0, 100, 0.45, 4)
        blocks = _blocks(plan, seed=4, n_ch=3)[:1]
        reg = MetricsRegistry()
        with use_registry(reg):
            monkeypatch.setenv("TPUDAS_FUSED_MIN_ELEMS", str(1 << 40))
            _run_stream(plan, blocks, "fused", 3)
            assert reg.value(
                "tpudas_fir_fused_rounds_total", engine="fused-xla"
            ) == 0.0
            monkeypatch.setenv("TPUDAS_FUSED_MIN_ELEMS", "1")
            _run_stream(plan, blocks, "fused", 3)
            assert reg.value(
                "tpudas_fir_fused_rounds_total", engine="fused-xla"
            ) == 1.0
        # and the bytes-saved proxy counted the eliminated traffic
        assert reg.value(
            "tpudas_fir_fused_intermediate_bytes_saved_total"
        ) == fused_intermediate_bytes(plan, blocks[0].shape[0], 3)

    def test_pallas_geometry_reads_call_time(self, monkeypatch):
        from tpudas.ops.pallas_fir import (
            channel_block,
            kernel_quantum,
            pallas_p,
        )

        monkeypatch.delenv("TPUDAS_PALLAS_P", raising=False)
        monkeypatch.delenv("TPUDAS_PALLAS_CB", raising=False)
        assert pallas_p() == 4
        assert kernel_quantum() == 512
        assert channel_block() == 128
        monkeypatch.setenv("TPUDAS_PALLAS_P", "2")
        monkeypatch.setenv("TPUDAS_PALLAS_CB", "256")
        assert pallas_p() == 2
        assert kernel_quantum() == 256
        assert channel_block() == 256


@pytest.mark.usefixtures("cpu_mesh4")
class TestFusedMesh:
    @pytest.mark.slow
    def test_mesh_fused_byte_identical(self, cpu_mesh4):
        """4-device CPU-mesh equivalence: the fused step under a
        channel mesh == single-device fused == reference cascade,
        byte-identically, with the returned carry leaves sharded
        device arrays fed back verbatim."""
        plan = design_cascade(100.0, 100, 0.45, 4)
        blocks = _blocks(plan, seed=5, n_ch=6, nan_gap=True)
        y0, c0 = _run_stream(plan, blocks, "xla", 6)
        y1, c1 = _run_stream(plan, blocks, "fused-xla", 6,
                             mesh=cpu_mesh4)
        np.testing.assert_array_equal(y0, y1)
        for a, b in zip(c0, c1):
            np.testing.assert_array_equal(a, b)

    def test_mesh_fused_carry_stays_device_resident(self, cpu_mesh4):
        from tpudas.parallel.sharding import is_device_resident

        plan = design_cascade(100.0, 100, 0.45, 4)
        carry = cascade_stream_init(plan, 6)
        x = np.zeros((20 * plan.ratio, 6), np.float32)
        _y, carry = cascade_decimate_stream(
            x, carry, plan, "fused-xla", mesh=cpu_mesh4
        )
        assert all(is_device_resident(b) for b in carry)
        # feed the sharded leaves back verbatim: no re-placement
        _y, carry = cascade_decimate_stream(
            x, carry, plan, "fused-xla", mesh=cpu_mesh4
        )
        assert all(is_device_resident(b) for b in carry)


FS = 100.0
FILE_SEC = 30.0
NCH = 6
T0 = np.datetime64("2023-03-22T00:00:00")


def _append_files(directory, start_index, count):
    from tpudas.io.registry import write_patch
    from tpudas.testing import synthetic_patch

    t0 = T0.astype("datetime64[ns]")
    step = np.timedelta64(int(round(1e9 / FS)), "ns")
    n = int(FILE_SEC * FS)
    for i in range(start_index, start_index + count):
        p = synthetic_patch(
            t0=t0 + i * n * step, duration=FILE_SEC, fs=FS, n_ch=NCH,
            seed=i, phase_origin=t0, noise=0.01,
        )
        write_patch(p, os.path.join(directory, f"raw_{i:04d}.h5"))


def _drive(src, out, engine):
    from tpudas.proc.streaming import run_lowpass_realtime

    return run_lowpass_realtime(
        source=src,
        output_folder=out,
        start_time=str(T0),
        output_sample_interval=1.0,
        edge_buffer=8.0,
        process_patch_size=40,
        poll_interval=0.0,
        file_duration=0.0,
        sleep_fn=lambda _: None,
        stateful=True,
        engine=engine,
    )


@pytest.fixture()
def fused_env(monkeypatch):
    """The realtime tests run tiny streams — clear the fused size
    threshold so engine='fused' really exercises the fused path."""
    monkeypatch.setenv("TPUDAS_FUSED_MIN_ELEMS", "0")


class TestFusedRealtime:
    @pytest.fixture()
    def source(self, tmp_path):
        from tpudas.testing import make_synthetic_spool

        src = str(tmp_path / "src")
        make_synthetic_spool(
            src, n_files=3, file_duration=FILE_SEC, fs=FS, n_ch=NCH,
            noise=0.01,
        )
        return src

    def _merged(self, out):
        from tpudas.io.spool import spool

        merged = spool(out).update().chunk(time=None)
        assert len(merged) == 1, "stream has a seam"
        return (
            merged[0].host_data(),
            np.asarray(merged[0].coords["time"]),
        )

    @pytest.mark.slow
    def test_driver_fused_matches_cascade(self, source, tmp_path,
                                          fused_env):
        """Full realtime driver under engine='fused': outputs
        byte-identical to engine='cascade' over the same feed, and
        the fused path really ran (fused rounds counted)."""
        from tpudas.obs.registry import MetricsRegistry, use_registry

        outs = {}
        reg = MetricsRegistry()
        for eng in ("cascade", "fused"):
            out = str(tmp_path / eng)
            if eng == "fused":
                with use_registry(reg):
                    assert _drive(source, out, eng) == 1
            else:
                assert _drive(source, out, eng) == 1
            outs[eng] = self._merged(out)
        np.testing.assert_array_equal(outs["cascade"][0],
                                      outs["fused"][0])
        np.testing.assert_array_equal(outs["cascade"][1],
                                      outs["fused"][1])
        assert reg.value(
            "tpudas_fir_fused_rounds_total", engine="fused-xla"
        ) > 0

    def test_serialized_carry_roundtrip_and_resume(self, source,
                                                   tmp_path, fused_env):
        """Kill/resume on the fused engine: the persisted carry
        round-trips bit-exactly and a fresh process resumes seam-free,
        byte-identical to an uninterrupted cascade control."""
        from tpudas.proc.stream import load_carry

        out = str(tmp_path / "fused")
        assert _drive(source, out, "fused") == 1
        c = load_carry(out)
        assert c is not None and c.engine_req == "fused"
        assert c.kind == "cascade"
        # round-trip: the serialized leaves reload bit-exactly
        c2 = load_carry(out)
        for a, b in zip(c.bufs, c2.bufs):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        plan_sizes = stream_carry_sizes(
            design_cascade(FS, 100, 0.45, 4)
        )
        assert tuple(int(np.shape(b)[0]) for b in c.bufs) == plan_sizes
        # two more files arrive while "down"; a fresh driver resumes
        _append_files(source, 3, 2)
        assert _drive(source, out, "fused") == 1
        got = self._merged(out)
        ctrl = str(tmp_path / "ctrl")
        assert _drive(source, ctrl, "cascade") == 1
        want = self._merged(ctrl)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])

    @pytest.mark.parametrize("first,second", [("cascade", "fused"),
                                              ("fused", "cascade")])
    @pytest.mark.slow
    def test_driver_crossover_both_directions(self, source, tmp_path,
                                              first, second, fused_env):
        """Resume a cascade carry under fused and vice versa: the
        shared carry layout makes the crossover seam-free and
        byte-identical to a single-engine control."""
        out = str(tmp_path / "xover")
        assert _drive(source, out, first) == 1
        _append_files(source, 3, 2)
        assert _drive(source, out, second) == 1
        got = self._merged(out)
        ctrl = str(tmp_path / "ctrl")
        assert _drive(source, ctrl, "cascade") == 1
        want = self._merged(ctrl)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])

    def test_fft_carry_cannot_resume_under_fused(self, tmp_path,
                                                 fused_env):
        """An FFT-kind carry (auto on a non-aligned grid) must reject
        a fused resume instead of silently reinterpreting state."""
        from tpudas.testing import make_synthetic_spool

        src = str(tmp_path / "src")
        make_synthetic_spool(
            src, n_files=2, file_duration=FILE_SEC, fs=FS, n_ch=NCH,
            noise=0.01,
        )
        out = str(tmp_path / "out")

        def drive(engine):
            from tpudas.proc.streaming import run_lowpass_realtime

            return run_lowpass_realtime(
                source=src, output_folder=out, start_time=str(T0),
                output_sample_interval=1.1,  # ratio 110 = 2*5*11: fft
                edge_buffer=8.0, process_patch_size=40,
                poll_interval=0.0, file_duration=0.0,
                sleep_fn=lambda _: None, stateful=True, engine=engine,
            )

        assert drive("auto") == 1
        from tpudas.proc.stream import load_carry

        assert load_carry(out).kind == "fft"
        _append_files(src, 2, 1)
        with pytest.raises(ValueError, match="start_time or processing"):
            drive("fused")
