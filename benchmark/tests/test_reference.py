"""The plain reference against scipy on known signals, its FIR form,
and the output reader."""

import h5py
import numpy as np
from scipy.signal import butter, sosfiltfilt

import reference as ref

CFG = {"sample_rate_hz": 1000.0, "output_sample_interval_s": 1.0,
       "filter_order": 4, "payload_scale": 1e-3}


def test_matches_scipy_directly():
    rng = np.random.default_rng(0)
    x = rng.integers(-3000, 3000, size=(20000, 3)).astype(np.float64)
    sos = butter(4, 0.45 / 500.0, btype="lowpass", output="sos")
    want = sosfiltfilt(sos, x * float(np.float32(1e-3)), axis=0)
    idx = np.arange(0, 20000, 1000)
    assert np.array_equal(ref.reference(CFG, x, idx), want[idx])


def test_known_signal_passes_and_tone_is_removed():
    t = np.arange(200_000) / 1000.0
    lf = 1000 * np.sin(2 * np.pi * 0.05 * t)
    hf = 1000 * np.sin(2 * np.pi * 40.0 * t)
    idx = np.arange(50_000, 150_000, 1000)
    scale = float(np.float32(1e-3))
    got_lf = ref.reference(CFG, lf[:, None], idx)[:, 0]
    # |H(0.05 Hz)|^2 of a 0.45 Hz order-4 Butterworth is 1 - ~1e-8
    assert np.abs(got_lf - lf[idx] * scale).max() < 1e-6
    assert np.abs(ref.reference(CFG, hf[:, None], idx)).max() < 1e-9


def test_fir_form_is_the_reference():
    rng = np.random.default_rng(1)
    x = rng.integers(-3000, 3000, size=(90_000, 2)).astype(np.float64)
    h = ref.fir_response(CFG)
    idx = np.arange(30_000, 60_001, 1000)
    scale = float(np.float32(1e-3))
    got = ref.fir_outputs(x, idx, h, scale)
    assert ref.max_gap(got, ref.reference(CFG, x, idx)) < 1e-9


def test_read_outputs_both_axis_orders(tmp_path):
    t = (np.arange(5) * 1_000_000_000).astype(np.int64)
    data = np.arange(5 * 4, dtype=np.float32).reshape(5, 4)
    for i, (dims, arr) in enumerate((("time,distance", data),
                                     ("distance,time", data.T))):
        with h5py.File(tmp_path / f"o{i}.h5", "w") as f:
            f.attrs["dims"] = dims
            f.create_dataset("data", data=arr)
            f.create_group("coords").create_dataset("time", data=t + i * 5e9)
    times, vals = ref.read_outputs(str(tmp_path), [1, 3])
    assert times.size == 10 and np.all(np.diff(times) > 0)
    assert np.array_equal(vals[:5], data[:, [1, 3]])
    assert np.array_equal(vals[5:], data[:, [1, 3]])
