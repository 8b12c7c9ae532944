"""Multistage FIR cascade engine (tpudas.ops.fir / pallas_fir):
design-response match to the reference's Butterworth-squared filter,
XLA/Pallas agreement, and LFProc engine equivalence (SURVEY.md §4:
filter kernel vs golden outputs, tolerance-based)."""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from tpudas.ops.filter import fft_pass_filter
from tpudas.ops.fir import (
    butter2_mag,
    cascade_decimate,
    design_cascade,
    edge_support_samples,
    factor_ratio,
    impulse_response,
)

FS = 1000.0
CORNER = 0.45


class TestDesign:
    def test_factor_ratio(self):
        assert factor_ratio(1000) == [8, 5, 5, 5]
        assert factor_ratio(100) == [5, 5, 4]
        assert factor_ratio(10) == [5, 2]
        assert factor_ratio(8) == [8]
        assert factor_ratio(1) == []

    def test_factor_ratio_large_prime_rejected(self):
        with pytest.raises(ValueError, match="prime factor"):
            factor_ratio(13)

    @pytest.mark.parametrize(
        "fs,ratio,corner",
        [(1000.0, 1000, 0.45), (100.0, 100, 0.45), (100.0, 10, 4.5)],
    )
    def test_composite_response_matches_butter2(self, fs, ratio, corner):
        """|H_cascade(f)| == butter2_mag(f) on the retained band to
        ~1e-4 — the engine-parity contract with tpudas.ops.filter."""
        plan = design_cascade(fs, ratio, corner, 4)
        h = impulse_response(plan)
        nfft = 1 << 18
        H = np.abs(np.fft.rfft(h, nfft))
        freqs = np.arange(nfft // 2 + 1) / nfft * fs
        band = freqs <= 0.5 * fs / ratio
        err = np.abs(H[band] - butter2_mag(freqs[band], corner, 4))
        assert err.max() < 1e-4

    def test_delay_is_symmetry_center(self):
        plan = design_cascade(FS, 1000, CORNER, 4)
        h = impulse_response(plan)
        # linear phase: response symmetric about the composite delay
        d = plan.delay
        w = min(d, len(h) - 1 - d)
        left = h[d - w : d]
        right = h[d + 1 : d + 1 + w][::-1]
        assert np.abs(left - right).max() < 1e-12
        assert plan.receptive_field == 2 * d + 1

    def test_edge_support_shrinks_with_looser_tol(self):
        plan = design_cascade(FS, 1000, CORNER, 4)
        assert edge_support_samples(plan, 1e-2) <= edge_support_samples(
            plan, 1e-4
        )
        # support is inside the receptive field
        assert edge_support_samples(plan, 1e-3) <= plan.delay


def _bandlimited(T, C, fs, seed=0):
    rng = np.random.default_rng(seed)
    t = np.arange(T) / fs
    x = np.zeros((T, C), np.float32)
    for c in range(C):
        for f, a in [(0.05, 1.0), (0.21, 0.7), (0.38, 0.4)]:
            x[:, c] += a * np.sin(
                2 * np.pi * f * t + rng.uniform(0, 2 * np.pi)
            ).astype(np.float32)
    x += rng.standard_normal((T, C)).astype(np.float32) * 0.1
    return x


class TestApply:
    def test_matches_fft_engine_interior(self):
        """Cascade output == FFT-engine zero-phase filter at the
        decimated sample points, away from edges."""
        ratio, T, C = 1000, 40000, 4
        plan = design_cascade(FS, ratio, CORNER, 4)
        x = _bandlimited(T, C, FS)
        ref_full = np.asarray(
            fft_pass_filter(jnp.asarray(x), 1.0 / FS, high=CORNER, order=4)
        )
        phase, n_out = 14000, 12
        ref = ref_full[phase : phase + n_out * ratio : ratio]
        got = np.asarray(cascade_decimate(x, plan, phase, n_out, engine="xla"))
        scale = np.abs(ref).max()
        assert np.abs(got - ref).max() < 1e-4 * scale

    def test_phase_shift_consistency(self):
        """Outputs at the same absolute sample index agree regardless of
        the window phase — the property that makes chunked processing
        seam-free."""
        ratio = 100
        plan = design_cascade(100.0, ratio, CORNER, 4)
        x = _bandlimited(8000, 3, 100.0, seed=1)
        a = np.asarray(cascade_decimate(x, plan, 3000, 10, engine="xla"))
        b = np.asarray(cascade_decimate(x, plan, 3000 + 2 * ratio, 8, engine="xla"))
        assert np.abs(a[2:] - b[:8]).max() < 1e-6

    @pytest.mark.slow
    def test_pallas_interpret_matches_xla(self):
        ratio = 100
        plan = design_cascade(100.0, ratio, CORNER, 4)
        x = _bandlimited(30000, 130, 100.0, seed=2)  # non-multiple C
        a = np.asarray(cascade_decimate(x, plan, 6000, 16, engine="xla"))
        b = np.asarray(cascade_decimate(x, plan, 6000, 16, engine="pallas"))
        assert np.abs(a - b).max() < 1e-6

    def test_left_pad_when_phase_before_delay(self):
        plan = design_cascade(100.0, 100, CORNER, 4)
        x = _bandlimited(4000, 2, 100.0, seed=3)
        out = np.asarray(cascade_decimate(x, plan, 0, 4, engine="xla"))
        assert out.shape == (4, 2)
        assert np.isfinite(out).all()


class TestPallasKernel:
    def test_strided_fir_exact(self):
        """Kernel output == direct numpy correlation at stride R."""
        from tpudas.ops.pallas_fir import fir_decimate_pallas

        rng = np.random.default_rng(0)
        T, C, R, L = 2048, 140, 8, 33
        x = rng.standard_normal((T, C)).astype(np.float32)
        h = rng.standard_normal(L).astype(np.float32)
        B = -(-L // R)
        hp = np.zeros(B * R, np.float32)
        hp[:L] = h
        n_out = T // R - B
        got = np.asarray(
            fir_decimate_pallas(
                jnp.asarray(x),
                jnp.asarray(hp.reshape(B, R)),
                R,
                n_out=n_out,
                interpret=True,
            )
        )
        ref = np.zeros((n_out, C), np.float32)
        for k in range(n_out):
            seg = x[k * R : k * R + L]
            ref[k] = (h[:, None] * seg).sum(0)
        assert np.abs(got - ref).max() < 1e-4 * np.abs(ref).max()

    def test_too_many_taps_rejected(self):
        from tpudas.ops.pallas_fir import fir_decimate_pallas

        x = jnp.zeros((4096, 128), jnp.float32)
        hb = jnp.zeros((200, 2), jnp.float32)  # 200 frames > 128 block
        with pytest.raises(ValueError, match="tap frames"):
            fir_decimate_pallas(x, hb, 2, n_out=64, interpret=True)

    @pytest.mark.parametrize(
        "env",
        [
            {"TPUDAS_PALLAS_GRID": "ck"},
            {"TPUDAS_PALLAS_DIMSEM": "parallel"},
            {"TPUDAS_PALLAS_DIMSEM": "arbitrary,parallel"},
            {
                "TPUDAS_PALLAS_GRID": "ck",
                "TPUDAS_PALLAS_DIMSEM": "arbitrary,arbitrary",
                "TPUDAS_PALLAS_VMEM_MB": "12",
            },
        ],
    )
    @pytest.mark.slow
    def test_mosaic_knob_variants_bit_equal(self, monkeypatch, env):
        """The Mosaic experiment knobs (grid order, dimension
        semantics, VMEM cap — chip sweep knobs)
        must not change kernel OUTPUT, only its schedule: every
        variant is bit-equal to the default lowering."""
        from tpudas.ops.pallas_fir import (
            fir_decimate_pallas,
            stage_input_rows,
        )

        rng = np.random.default_rng(3)
        R, L, n_out = 8, 43, 512
        B = -(-L // R)
        hp = np.zeros(B * R, np.float32)
        hp[:L] = rng.standard_normal(L).astype(np.float32)
        hb = jnp.asarray(hp.reshape(B, R))
        T = stage_input_rows(B, R, n_out, 512)
        x = rng.standard_normal((T, 130)).astype(np.float32)
        base = np.asarray(
            fir_decimate_pallas(
                jnp.asarray(x), hb, R, n_out, interpret=True,
                kb=512, cb=128,
            )
        )
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        got = np.asarray(
            fir_decimate_pallas(
                jnp.asarray(x), hb, R, n_out, interpret=True,
                kb=512, cb=128,
            )
        )
        np.testing.assert_array_equal(got, base)

    def test_stage_ok_min_elems_env_override(self, monkeypatch):
        """TPUDAS_PALLAS_MIN_ELEMS applies a measured crossover
        without a code edit (tools/retune_stage_ok.py's output)."""
        from tpudas.ops.fir import _pallas_stage_ok
        from tpudas.ops.pallas_fir import kernel_quantum

        # k*R*n_ch = 2**19: below 2**24
        k, R, n_ch, B = kernel_quantum(), 8, 128, 6
        monkeypatch.delenv("TPUDAS_PALLAS_MIN_ELEMS", raising=False)
        assert not _pallas_stage_ok(k, R, n_ch, B)
        monkeypatch.setenv("TPUDAS_PALLAS_MIN_ELEMS", str(1 << 19))
        assert _pallas_stage_ok(k, R, n_ch, B)
        monkeypatch.setenv("TPUDAS_PALLAS_MIN_ELEMS", str(1 << 20))
        assert not _pallas_stage_ok(k, R, n_ch, B)

    def test_mosaic_knob_validation(self, monkeypatch):
        from tpudas.ops.pallas_fir import _mosaic_knobs

        monkeypatch.setenv("TPUDAS_PALLAS_GRID", "zz")
        with pytest.raises(ValueError, match="TPUDAS_PALLAS_GRID"):
            _mosaic_knobs()
        monkeypatch.setenv("TPUDAS_PALLAS_GRID", "kc")
        monkeypatch.setenv("TPUDAS_PALLAS_DIMSEM", "bogus")
        with pytest.raises(ValueError, match="TPUDAS_PALLAS_DIMSEM"):
            _mosaic_knobs()

    def test_env_geometry_knob_validation(self, monkeypatch):
        """TPUDAS_PALLAS_P/CB: empty means default; bad values fail
        fast naming the variable (not mid-run at a lazy import)."""
        from tpudas.ops.pallas_fir import _env_geom

        monkeypatch.delenv("TPUDAS_TEST_GEOM", raising=False)
        assert _env_geom("TPUDAS_TEST_GEOM", 4) == 4
        monkeypatch.setenv("TPUDAS_TEST_GEOM", "  ")
        assert _env_geom("TPUDAS_TEST_GEOM", 4) == 4
        monkeypatch.setenv("TPUDAS_TEST_GEOM", "8")
        assert _env_geom("TPUDAS_TEST_GEOM", 4) == 8
        monkeypatch.setenv("TPUDAS_TEST_GEOM", "abc")
        with pytest.raises(ValueError, match="TPUDAS_TEST_GEOM"):
            _env_geom("TPUDAS_TEST_GEOM", 4)
        monkeypatch.setenv("TPUDAS_TEST_GEOM", "0")
        with pytest.raises(ValueError, match="positive"):
            _env_geom("TPUDAS_TEST_GEOM", 4)
        monkeypatch.setenv("TPUDAS_TEST_GEOM", "100")
        with pytest.raises(ValueError, match="multiple"):
            _env_geom("TPUDAS_TEST_GEOM", 128, multiple_of=128)

    def test_3x_split_dot_accuracy(self):
        """The TPU kernel's 3-pass bf16 matmul emulation (interpret
        mode runs exact f32 instead, so this exercises the split
        arithmetic directly): ~1e-5 absolute on unit-scale data, well
        inside the cascade's 1e-4 design tolerance."""
        from tpudas.ops.pallas_fir import _dot_3x

        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.standard_normal((64, 256)).astype(np.float32))
        x = jnp.asarray(rng.standard_normal((256, 128)).astype(np.float32))
        exact = np.asarray(a) @ np.asarray(x)
        got = np.asarray(_dot_3x(a, x))
        scale = np.abs(exact).max()
        assert np.abs(got - exact).max() < 1e-4 * scale

    @pytest.mark.slow
    def test_v1_impl_matches_v2(self, monkeypatch):
        """TPUDAS_PALLAS_IMPL=v1 (the proven-on-hardware VPU kernel)
        agrees with the default v2 MXU kernel in interpret mode."""
        from tpudas.ops.fir import _block_taps
        from tpudas.ops.pallas_fir import fir_decimate_pallas

        rng = np.random.default_rng(4)
        T, C, R, L = 6000, 70, 4, 19
        x = rng.standard_normal((T, C)).astype(np.float32)
        hb = _block_taps(rng.standard_normal(L).astype(np.float32), R)
        monkeypatch.delenv("TPUDAS_PALLAS_IMPL", raising=False)
        v2 = np.asarray(
            fir_decimate_pallas(jnp.asarray(x), hb, R, 600, interpret=True)
        )
        monkeypatch.setenv("TPUDAS_PALLAS_IMPL", "v1")
        v1 = np.asarray(
            fir_decimate_pallas(jnp.asarray(x), hb, R, 600, interpret=True)
        )
        scale = np.abs(v2).max()
        assert np.abs(v1 - v2).max() < 1e-5 * scale
        # int16 input path exists on both
        q = rng.integers(-3000, 3000, size=(T, C)).astype(np.int16)
        v1q = np.asarray(
            fir_decimate_pallas(jnp.asarray(q), hb, R, 600, interpret=True)
        )
        assert np.isfinite(v1q).all()

    def test_multi_stream_grid_quantum(self):
        """n_out that is not a multiple of the 512-frame grid quantum
        still yields exact results (pad + trim path)."""
        from tpudas.ops.fir import _block_taps
        from tpudas.ops.pallas_fir import fir_decimate_pallas

        rng = np.random.default_rng(1)
        T, C, R, L = 6000, 64, 4, 19
        x = rng.standard_normal((T, C)).astype(np.float32)
        h = rng.standard_normal(L).astype(np.float32)
        hb = _block_taps(h, R)
        n_out = 700  # crosses one 512-frame step, not a multiple
        got = np.asarray(
            fir_decimate_pallas(
                jnp.asarray(x), hb, R, n_out=n_out, interpret=True
            )
        )
        ref = np.zeros((n_out, C), np.float32)
        for k in range(n_out):
            seg = np.zeros((L, C), np.float32)
            avail = x[k * R : k * R + L]
            seg[: len(avail)] = avail
            ref[k] = (h[:, None] * seg).sum(0)
        assert np.abs(got - ref).max() < 1e-4 * np.abs(ref).max()


class TestQuantizedIngest:
    """int16 windows flow through the cascade undecoded: the first
    kernel dequantizes (Pallas: scale folded into the tap matrix;
    XLA: fused cast*scale) — tpudas.io.tdas raw ingest fast path."""

    def _quantized(self, T, C, seed=0, scale=1e-3):
        rng = np.random.default_rng(seed)
        q = rng.integers(-3000, 3000, size=(T, C)).astype(np.int16)
        return q, np.float32(scale)

    def test_pallas_kernel_raw_int16_matches_decoded(self):
        """The kernel filters the raw int16 payload (bare cast in
        VMEM); the caller scales the decimated output — linearity."""
        from tpudas.ops.fir import _block_taps
        from tpudas.ops.pallas_fir import fir_decimate_pallas

        rng = np.random.default_rng(2)
        T, C, R, L = 6000, 64, 4, 19
        q, s = self._quantized(T, C)
        h = rng.standard_normal(L).astype(np.float32)
        hb = _block_taps(h, R)
        dec = (q.astype(np.float32) * s).astype(np.float32)
        ref = np.asarray(
            fir_decimate_pallas(
                jnp.asarray(dec), hb, R, n_out=512, interpret=True
            )
        )
        got = s * np.asarray(
            fir_decimate_pallas(
                jnp.asarray(q), hb, R, n_out=512, interpret=True
            )
        )
        scale_ref = np.abs(ref).max()
        assert np.abs(got - ref).max() < 1e-6 * scale_ref

    def test_cascade_qscale_single_compile_across_scales(self):
        """Different quantization scales must NOT trigger distinct
        cascade compiles: the scale is a traced operand."""
        from tpudas.ops.fir import _build_cascade_fn

        plan = design_cascade(100.0, 20, CORNER, 4)
        q, _ = self._quantized(8000, 10, seed=4)
        _build_cascade_fn.cache_clear()
        for s in (1e-3, 2e-3, 5e-4):
            cascade_decimate(
                jnp.asarray(q), plan, 300, 200, "xla", qscale=s
            )
        info = _build_cascade_fn.cache_info()
        assert info.misses == 1, info

    def test_cascade_qscale_bitwise_matches_decoded(self):
        """On the XLA path the fused cast*scale is the same sequence of
        float ops as decode-then-cascade: results are bit-identical."""
        plan = design_cascade(100.0, 20, CORNER, 4)
        q, s = self._quantized(8000, 10, seed=3)
        dec = q.astype(np.float32) * s
        ref = np.asarray(cascade_decimate(dec, plan, 300, 200, "xla"))
        got = np.asarray(
            cascade_decimate(
                jnp.asarray(q), plan, 300, 200, "xla", qscale=float(s)
            )
        )
        assert np.array_equal(got, ref)

    def test_cascade_qscale_dtype_validation(self):
        plan = design_cascade(100.0, 20, CORNER, 4)
        with pytest.raises(ValueError, match="dtype"):
            cascade_decimate(
                np.zeros((4000, 4), np.float32), plan, 10, 8, "xla",
                qscale=0.5,
            )


class TestStageEngines:
    def test_decision_matches_build_predicate(self):
        from tpudas.ops.fir import design_cascade, stage_engines

        plan = design_cascade(1000.0, 1000, 0.45, 4)
        # big shapes: the full-rate stages qualify for the Pallas kernel
        eng = stage_engines(plan, 128, 2048, engine="pallas")
        assert eng[0] == "pallas", eng
        # tiny shapes never do; forced-xla never does
        assert set(stage_engines(plan, 4, 8, engine="pallas")) == {"xla"}
        assert set(stage_engines(plan, 128, 2048, engine="xla")) == {"xla"}
        # 'auto' resolves by backend: CPU under the test conftest
        assert set(stage_engines(plan, 128, 2048)) == {"xla"}

    @pytest.mark.slow
    def test_lfproc_engine_counts_ground_truth(self, tmp_path):
        """LFProc.engine_counts reports what actually ran, without the
        log handler — config 'auto' on CPU runs cascade-xla windows."""
        from tpudas import spool
        from tpudas.proc.lfproc import LFProc
        from tpudas.testing import make_synthetic_spool

        d = tmp_path / "raw"
        make_synthetic_spool(
            d, n_files=6, file_duration=30.0, fs=100.0, n_ch=6, noise=0.01
        )
        for engine, expect_key in (("auto", "cascade-xla"), ("fft", "fft")):
            lfp = LFProc(spool(str(d)).sort("time").update())
            lfp.update_processing_parameter(
                output_sample_interval=1.0,
                process_patch_size=60,
                edge_buff_size=10,
                engine=engine,
            )
            out = tmp_path / f"counts_{engine}"
            lfp.set_output_folder(str(out), delete_existing=True)
            lfp.process_time_range(
                np.datetime64("2023-03-22T00:00:00"),
                np.datetime64("2023-03-22T00:03:00"),
            )
            assert lfp.engine_counts[expect_key] == 4, lfp.engine_counts
            assert sum(lfp.engine_counts.values()) == 4


class TestPallasFailureRaises:
    """A Pallas failure on the batch or stream path raises: no code
    path catches it and carries on under another engine."""

    @pytest.fixture()
    def forced_pallas(self, monkeypatch):
        """Route every cascade stage to the Pallas kernel on the CPU
        backend (interpret mode) and clear the compiled caches around
        the test."""
        import tpudas.ops.fir as fir_mod

        fir_mod._clear_cascade_caches()
        monkeypatch.setattr(
            fir_mod, "resolve_cascade_engine",
            lambda e="auto": "pallas" if e == "auto" else e,
        )
        monkeypatch.setattr(fir_mod, "_pallas_stage_ok", lambda *a: True)
        yield
        fir_mod._clear_cascade_caches()

    @staticmethod
    def _run_batch(tmp_path):
        from tpudas import spool
        from tpudas.proc.lfproc import LFProc
        from tpudas.testing import make_synthetic_spool

        d = tmp_path / "raw"
        make_synthetic_spool(
            d, n_files=4, file_duration=30.0, fs=100.0, n_ch=6, noise=0.01
        )
        lfp = LFProc(spool(str(d)).sort("time").update())
        lfp.update_processing_parameter(
            output_sample_interval=1.0,
            process_patch_size=60,
            edge_buff_size=10,
        )
        lfp.set_output_folder(str(tmp_path / "out"), delete_existing=True)
        lfp.process_time_range(
            np.datetime64("2023-03-22T00:00:00"),
            np.datetime64("2023-03-22T00:02:00"),
        )
        return lfp

    def test_lfproc_pallas_compile_failure_raises(
        self, tmp_path, monkeypatch, forced_pallas
    ):
        import tpudas.ops.pallas_fir as pf_mod

        def boom(*a, **k):
            raise RuntimeError("mosaic compile failure (synthetic)")

        monkeypatch.setattr(pf_mod, "fir_decimate_pallas", boom)
        with pytest.raises(RuntimeError, match="mosaic compile failure"):
            self._run_batch(tmp_path)
        assert not list((tmp_path / "out").iterdir())

    def test_lfproc_silently_wrong_pallas_numbers_raise(
        self, tmp_path, monkeypatch, forced_pallas
    ):
        """A miscompile that RETURNS wrong numbers is caught by the
        first-window cross-check against the XLA formulation and
        raised; nothing is written."""
        import tpudas.ops.pallas_fir as pf_mod
        from tpudas.proc.lfproc import PallasVerificationError

        real = pf_mod.fir_decimate_pallas

        def corrupt(x, hb, R, n_out, **kw):
            return real(x, hb, R, n_out=n_out, **kw) * 1.7

        monkeypatch.setattr(pf_mod, "fir_decimate_pallas", corrupt)
        with pytest.raises(PallasVerificationError,
                           match="pallas-vs-xla rel err"):
            self._run_batch(tmp_path)
        assert not list((tmp_path / "out").iterdir())

    def test_stream_fused_pallas_failure_raises(self, tmp_path,
                                                monkeypatch):
        """The realtime stream's fused-pallas step failing raises out
        of run_lowpass_realtime (the fault boundary classes it fatal);
        the persisted carry has not advanced past the failed block."""
        import tpudas.ops.fir as fir_mod
        import tpudas.ops.pallas_fir as pf_mod
        from tpudas.proc.streaming import run_lowpass_realtime
        from tpudas.testing import make_synthetic_spool

        src = str(tmp_path / "src")
        make_synthetic_spool(
            src, n_files=3, file_duration=30.0, fs=100.0, n_ch=6,
            noise=0.01,
        )

        def boom(*a, **k):
            raise RuntimeError("fused kernel rejected (synthetic)")

        real_resolve = fir_mod.resolve_stream_engine

        def to_pallas(engine, plan=None, T=0, n_ch=0):
            got = real_resolve(engine, plan, T, n_ch)
            return "fused-pallas" if got == "fused-xla" else got

        monkeypatch.setenv("TPUDAS_FUSED_MIN_ELEMS", "0")
        monkeypatch.setattr(fir_mod, "resolve_stream_engine", to_pallas)
        monkeypatch.setattr(pf_mod, "fused_cascade_pallas", boom)
        fir_mod._clear_cascade_caches()
        out = str(tmp_path / "out")
        try:
            with pytest.raises(RuntimeError, match="fused kernel rejected"):
                run_lowpass_realtime(
                    source=src, output_folder=out,
                    start_time="2023-03-22T00:00:00",
                    output_sample_interval=1.0, edge_buffer=8.0,
                    process_patch_size=40, poll_interval=0.0,
                    file_duration=0.0, sleep_fn=lambda _: None,
                    stateful=True, engine="fused",
                )
        finally:
            fir_mod._clear_cascade_caches()
        from tpudas.proc.stream import load_carry

        assert load_carry(out).consumed == 0


class TestLFProcEngines:
    def test_cascade_equals_fft_engine(self, tmp_path):
        """Full chunked runs with engine='fft' vs engine='cascade' agree
        on the interior — engine choice is an implementation detail."""
        from tpudas import spool
        from tpudas.proc.lfproc import LFProc
        from tpudas.testing import make_synthetic_spool

        d = tmp_path / "raw"
        make_synthetic_spool(
            d, n_files=6, file_duration=30.0, fs=100.0, n_ch=6, noise=0.01
        )
        outs = {}
        for engine in ("fft", "cascade"):
            lfp = LFProc(spool(str(d)).sort("time").update())
            lfp.update_processing_parameter(
                output_sample_interval=1.0,
                process_patch_size=60,
                edge_buff_size=10,
                engine=engine,
            )
            out_dir = tmp_path / engine
            lfp.set_output_folder(str(out_dir), delete_existing=True)
            lfp.process_time_range(
                np.datetime64("2023-03-22T00:00:00"),
                np.datetime64("2023-03-22T00:03:00"),
            )
            outs[engine] = spool(str(out_dir)).update().chunk(time=None)[0]
        a, b = outs["fft"], outs["cascade"]
        lo = max(a.coords["time"][0], b.coords["time"][0])
        hi = min(a.coords["time"][-1], b.coords["time"][-1])
        da = a.select(time=(lo, hi)).host_data()
        db = b.select(time=(lo, hi)).host_data()
        scale = np.abs(da).max()
        assert np.abs(da - db).max() < 5e-3 * scale

    def test_cascade_engine_rejects_misaligned(self, tmp_path):
        """engine='cascade' on a non-sample-aligned grid raises with
        guidance (engine='auto' would silently fall back to FFT)."""
        from tpudas import spool
        from tpudas.proc.lfproc import LFProc
        from tpudas.testing import make_synthetic_spool

        d = tmp_path / "raw"
        make_synthetic_spool(
            d, n_files=2, file_duration=30.0, fs=100.0, n_ch=4, noise=0.0
        )
        lfp = LFProc(spool(str(d)).sort("time").update())
        lfp.update_processing_parameter(
            output_sample_interval=0.333,  # 333 ms: non-integer ratio
            process_patch_size=60,
            edge_buff_size=10,
            engine="cascade",
        )
        lfp.set_output_folder(str(tmp_path / "out"), delete_existing=True)
        with pytest.raises(ValueError, match="cascade"):
            lfp.process_time_range(
                np.datetime64("2023-03-22T00:00:00"),
                np.datetime64("2023-03-22T00:01:00"),
            )
