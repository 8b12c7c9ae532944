"""Kernel golden tests: filter vs scipy, interp vs numpy, rolling vs
pandas, median vs scipy (SURVEY.md §4 test plan)."""

import numpy as np
import pandas as pd
import pytest
import scipy.ndimage
import scipy.signal

from tpudas.ops.filter import fft_pass_filter
from tpudas.ops.median import median_filter
from tpudas.ops.resample import interp_indices_weights, gather_lerp
from tpudas.ops.rolling import rolling_reduce
from tpudas.testing import synthetic_patch


class TestFFTFilter:
    fs = 200.0

    def _sig(self, n=4000, c=3, seed=0):
        rng = np.random.default_rng(seed)
        t = np.arange(n) / self.fs
        sig = (
            np.sin(2 * np.pi * 0.3 * t)[:, None]
            + 0.5 * np.sin(2 * np.pi * 30.0 * t)[:, None]
            + 0.05 * rng.standard_normal((n, c))
        )
        return sig.astype(np.float32), t

    def test_matches_sosfiltfilt_interior(self):
        """filtfilt magnitude is |H|^2 — our FFT filter must agree away
        from the chunk edges (tolerance-based: numerics differ)."""
        data, _ = self._sig()
        corner = 2.0
        ours = np.asarray(fft_pass_filter(data, 1 / self.fs, high=corner))
        sos = scipy.signal.butter(4, corner / (self.fs / 2), "lowpass", output="sos")
        ref = scipy.signal.sosfiltfilt(sos, data.astype(np.float64), axis=0)
        interior = slice(800, -800)
        err = np.abs(ours[interior] - ref[interior])
        assert err.max() < 2e-2 * np.abs(ref[interior]).max()

    def test_zero_phase_impulse(self):
        n = 2001
        x = np.zeros((n, 1), np.float32)
        x[n // 2] = 1.0
        h = np.asarray(fft_pass_filter(x, 1 / self.fs, high=5.0))[:, 0]
        # symmetric response around the impulse == zero phase
        assert np.allclose(h[: n // 2][::-1], h[n // 2 + 1 :], atol=1e-5)
        assert np.argmax(np.abs(h)) == n // 2

    def test_stopband_rejection_passband_unity(self):
        data, t = self._sig(c=1, seed=1)
        out = np.asarray(fft_pass_filter(data, 1 / self.fs, high=2.0))[:, 0]
        interior = slice(600, -600)
        lf = np.sin(2 * np.pi * 0.3 * t)[interior]
        # LF component preserved
        assert np.corrcoef(out[interior], lf)[0, 1] > 0.999
        # 30 Hz component crushed: residual power tiny
        resid = out[interior] - lf
        assert np.sqrt(np.mean(resid**2)) < 0.05

    def test_highpass_and_bandpass(self):
        data, t = self._sig(c=1)
        hp = np.asarray(fft_pass_filter(data, 1 / self.fs, low=10.0))[:, 0]
        interior = slice(600, -600)
        hf = 0.5 * np.sin(2 * np.pi * 30.0 * t)
        assert np.corrcoef(hp[interior], hf[interior])[0, 1] > 0.99
        bp = np.asarray(
            fft_pass_filter(data, 1 / self.fs, low=20.0, high=40.0)
        )[:, 0]
        assert np.corrcoef(bp[interior], hf[interior])[0, 1] > 0.99

    def test_patch_pass_filter_engines_agree(self):
        p = synthetic_patch(duration=20, fs=self.fs, n_ch=4, noise=0.1)
        a = p.pass_filter(time=(None, 2.0))
        b = p.pass_filter(time=(None, 2.0), engine="numpy")
        interior = slice(400, -400)
        assert (
            np.abs(
                np.asarray(a.data)[interior] - np.asarray(b.data)[interior]
            ).max()
            < 2e-2 * np.abs(np.asarray(b.data)).max()
        )

    def test_corner_validation(self):
        p = synthetic_patch(duration=5, fs=self.fs, n_ch=2)
        with pytest.raises(ValueError):
            p.pass_filter(time=(None, 1000.0))  # above Nyquist


class TestInterpolate:
    def test_matches_np_interp(self):
        rng = np.random.default_rng(0)
        src = np.sort(rng.uniform(0, 100, 200))
        src[0], src[-1] = 0.0, 100.0
        vals = rng.standard_normal(200).astype(np.float32)
        dst = rng.uniform(-5, 105, 500)  # includes out-of-range clamps
        idx, w = interp_indices_weights(src, dst)
        ours = np.asarray(gather_lerp(vals[:, None], idx, w))[:, 0]
        ref = np.interp(dst, src, vals)
        assert np.allclose(ours, ref, atol=1e-5)

    def test_datetime_axes_exact(self):
        p = synthetic_patch(duration=10, fs=100.0, n_ch=3)
        t = p.coords["time"]
        new_t = t[::10]
        q = p.interpolate(time=new_t)
        assert np.array_equal(q.coords["time"], new_t)
        # on-grid targets are exact sample picks
        assert np.allclose(q.host_data(), p.host_data()[::10], atol=1e-6)
        assert q.attrs["time_step"] == np.timedelta64(100, "ms")

    def test_patch_interp_engines_agree(self):
        p = synthetic_patch(duration=10, fs=100.0, n_ch=3, noise=0.2)
        t0 = p.coords["time"][0]
        new_t = t0 + np.arange(1, 90) * np.timedelta64(107, "ms")
        a = p.interpolate(time=new_t)
        b = p.interpolate(time=new_t, engine="numpy")
        assert np.allclose(np.asarray(a.data), np.asarray(b.data), atol=1e-5)


class TestRolling:
    @pytest.mark.parametrize("n,w,s", [(100, 10, 10), (101, 7, 3), (50, 12, 5), (30, 40, 10)])
    @pytest.mark.parametrize("op", ["mean", "sum", "min", "max"])
    def test_matches_pandas(self, n, w, s, op):
        rng = np.random.default_rng(1)
        x = rng.standard_normal((n, 2)).astype(np.float32)
        ref = getattr(
            pd.DataFrame(x.astype(np.float64)).rolling(window=w, step=s), op
        )().to_numpy()
        ours_jax = rolling_reduce(x, w, s, op)
        ours_np = rolling_reduce(x, w, s, op, engine="numpy")
        assert ours_jax.shape == ref.shape
        assert np.allclose(np.asarray(ours_jax), ref, atol=1e-4, equal_nan=True)
        assert np.allclose(ours_np, ref, atol=1e-12, equal_nan=True)

    def test_patch_roller_decimation_semantics(self):
        # window == step == d_t: mean-decimation with NaN warm-up prefix
        from tpudas.core.units import s as sec

        p = synthetic_patch(duration=30, fs=100.0, n_ch=4)
        out = p.rolling(time=1.0 * sec, step=1.0 * sec, engine="numpy").mean()
        assert out.shape[0] == 30 * 100 // 100
        assert np.isnan(out.host_data()[0]).all()
        assert np.isfinite(out.host_data()[1:]).all()
        # time coord subsamples the input axis
        assert np.array_equal(out.coords["time"], p.coords["time"][::100])
        # dropna strips exactly the warm-up row
        assert out.dropna("time").shape[0] == out.shape[0] - 1

    def test_decimated_patch_attrs_refresh(self):
        # regression: rolling with step>1 must update time_step, or any
        # downstream Nyquist/window/contiguity math is 100x off
        from tpudas.core.units import s as sec

        p = synthetic_patch(duration=30, fs=100.0, n_ch=4)
        out = p.rolling(time=1.0 * sec, step=1.0 * sec).mean()
        assert out.attrs["time_step"] == np.timedelta64(1, "s")
        assert out.get_sample_step("time") == 1.0
        # merged spool of two consecutive rolling outputs stays contiguous
        from tpudas.io.spool import merge_patches

        t = p.coords["time"]
        a = p.select(time=(t[0], t[1499])).rolling(
            time=1.0 * sec, step=1.0 * sec
        ).mean()
        b = p.select(time=(t[1500], t[2999])).rolling(
            time=1.0 * sec, step=1.0 * sec
        ).mean()
        assert len(merge_patches([a, b])) == 1

    def test_jax_engine_matches_numpy_engine(self):
        from tpudas.core.units import s as sec

        p = synthetic_patch(duration=30, fs=100.0, n_ch=4, noise=0.3)
        a = p.rolling(time=1.0 * sec, step=1.0 * sec).mean()
        b = p.rolling(time=1.0 * sec, step=1.0 * sec, engine="numpy").mean()
        assert np.allclose(
            a.host_data(), b.host_data(), atol=1e-4, equal_nan=True
        )

    @pytest.mark.slow
    def test_std_matches_pandas(self):
        from tpudas.core.units import s as sec

        p = synthetic_patch(duration=30, fs=100.0, n_ch=3, noise=0.5)
        out = p.rolling(time=2.0 * sec, step=1.0 * sec).std()
        x = pd.DataFrame(p.host_data().astype(np.float64))
        ref = (
            x.rolling(window=200, step=100).std(ddof=0).to_numpy()
        )
        assert np.allclose(
            out.host_data(), ref, atol=1e-4, equal_nan=True
        )

    def test_std_survives_large_dc_offset(self):
        # regression: the raw E[x^2]-E[x]^2
        # identity cancels catastrophically in f32 when the data rides
        # a large DC offset — raw counts commonly do
        from tpudas.core.units import s as sec

        p = synthetic_patch(duration=30, fs=100.0, n_ch=3, noise=0.5)
        data = p.host_data()
        shifted = p.new(data=data + np.float32(1e6))
        true_std = (
            pd.DataFrame(data.astype(np.float64))
            .rolling(window=200, step=100)
            .std(ddof=0)
            .to_numpy()
        )
        for engine in (None, "numpy"):
            out = shifted.rolling(
                time=2.0 * sec, step=1.0 * sec, engine=engine
            ).std()
            got = np.asarray(out.host_data(), np.float64)
            # the offset must not destroy the estimate (raw identity
            # yields ~0 or wild garbage here)
            err = np.nanmax(np.abs(got - true_std) / np.nanmax(true_std))
            assert err < 0.05, (engine, err)


class TestMedian:
    def test_1d_matches_scipy(self):
        rng = np.random.default_rng(2)
        x = rng.standard_normal((200, 3)).astype(np.float32)
        ours = np.asarray(median_filter(x, 9, axes=(0,)))
        ref = scipy.ndimage.median_filter(x, size=(9, 1))
        assert np.allclose(ours, ref, atol=1e-6)

    def test_2d_matches_scipy(self):
        rng = np.random.default_rng(3)
        x = rng.standard_normal((40, 30)).astype(np.float32)
        ours = np.asarray(median_filter(x, 5))
        ref = scipy.ndimage.median_filter(x, size=5)
        assert np.allclose(ours, ref, atol=1e-6)

    def test_patch_method(self):
        p = synthetic_patch(duration=5, fs=50.0, n_ch=4, noise=0.5)
        a = p.median_filter(size=5, dim="time")
        b = p.median_filter(size=5, dim="time", engine="scipy")
        assert np.allclose(a.host_data(), b.host_data(), atol=1e-6)


class TestMedianTupleSize:
    def test_per_axis_footprint_matches_scipy(self):
        import scipy.ndimage

        rng = np.random.default_rng(3)
        x = rng.standard_normal((40, 6)).astype(np.float32)
        ours = np.asarray(median_filter(x, (3, 1)))
        ref = scipy.ndimage.median_filter(x, size=(3, 1))
        assert np.abs(ours - ref).max() < 1e-6

    def test_even_size_rejected(self):
        import pytest

        with pytest.raises(ValueError, match="odd"):
            median_filter(np.zeros((8, 4), np.float32), (2, 1))
