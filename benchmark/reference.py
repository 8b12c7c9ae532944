"""The plain reference, the output reader, and the comparison.

The reference is the deployment's stated filter written out with
scipy alone: a Butterworth low-pass of the configured order at 0.9x the
output Nyquist (``lf_das.py``'s corner rule), run forward and backward
(``sosfiltfilt``) in float64 over the full-rate stream, and sampled at
the output times.  It imports nothing of tpudas.

The outputs are read back with h5py from the DASDAE files the timed
path wrote (``data`` plus ``coords/time`` in int64 ns, ``dims`` naming
the axis order), again without tpudas.

:func:`fir_response` turns the same filter into its two-sided impulse
response; :func:`fir_outputs` applies it in a chosen precision.  The
control (``benchmark/control.py``) runs that in bfloat16 in the
program's place.
"""

from __future__ import annotations

import glob
import os

import numpy as np

MARGIN_S = 30.0  # outputs this close to a stream end are counted, not compared


def grid_ns(cfg: dict) -> int:
    return int(round(cfg["output_sample_interval_s"] * 1e9))


def corner_hz(cfg: dict) -> float:
    return 0.9 * 0.5 / cfg["output_sample_interval_s"]


def butter_sos(cfg: dict):
    from scipy.signal import butter

    nyq = 0.5 * cfg["sample_rate_hz"]
    return butter(cfg["filter_order"], corner_hz(cfg) / nyq, btype="lowpass",
                  output="sos")


def reference(cfg: dict, stream: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """sosfiltfilt of ``stream`` (rows x channels of int16 counts, as
    float64) in physical units, at stream rows ``idx``."""
    from scipy.signal import sosfiltfilt

    x = stream * float(np.float32(cfg["payload_scale"]))
    return sosfiltfilt(butter_sos(cfg), x, axis=0)[idx]


def fir_response(cfg: dict, half_s: float = 25.0) -> np.ndarray:
    """The filter's two-sided impulse response, ``2*half+1`` taps,
    centred: sosfiltfilt of a unit impulse.  At the configurations'
    corner it has decayed to about 1e-12 of its peak by 25 s, so an
    output at least that far from a stream end has its full window."""
    from scipy.signal import sosfiltfilt

    half = int(round(half_s * cfg["sample_rate_hz"]))
    e = np.zeros(4 * half + 1)
    e[2 * half] = 1.0
    return sosfiltfilt(butter_sos(cfg), e)[half: 3 * half + 1]


def fir_outputs(stream, idx, h, scale, dtype="float64", chunk=16):
    """``sum_k h[k] x[i + k - half]`` at rows ``idx`` (each with a full
    window), with ``x`` and ``h`` cast to ``dtype`` and the products
    summed in float64, or in float32 on the device for a lower
    ``dtype``; ``chunk`` outputs at a time."""
    half = (len(h) - 1) // 2
    win = np.lib.stride_tricks.sliding_window_view(stream, len(h), axis=0)
    lo = np.asarray(idx) - half
    if lo.min() < 0 or lo.max() >= len(win):
        raise ValueError("an output lacks a full window of the stream")
    if dtype == "float64":
        def f(w):
            return np.einsum("oct,t->oc", w, h)
    else:
        # rounded on the host, so no compiler can keep the wider values
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(dtype)
        hq = jnp.asarray(np.asarray(h, dt))
        g = jax.jit(lambda w, k: jnp.einsum(
            "oct,t->oc", w, k, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST))

        def f(w):
            return np.asarray(g(jnp.asarray(np.asarray(w, dt)), hq),
                              np.float64)
    return np.concatenate([f(win[lo[i:i + chunk]])
                           for i in range(0, len(lo), chunk)]) * scale


def read_outputs(folder: str, cols) -> tuple:
    """(times int64 ns, values float64 rows x len(cols)) of every DASDAE
    file in ``folder``, sorted by time.  Duplicated times raise."""
    import h5py

    times, vals = [], []
    for path in sorted(glob.glob(os.path.join(folder, "*.h5"))):
        with h5py.File(path, "r") as f:
            dims = f.attrs["dims"]
            dims = dims.decode() if isinstance(dims, bytes) else dims
            dims = dims.split(",")
            data = f["data"]
            if dims[0] == "time":
                block = data[:, :][:, cols]
            else:
                block = data[:, :][cols, :].T
            times.append(f["coords"]["time"][()].astype(np.int64))
            vals.append(np.asarray(block, np.float64))
    if not times:
        return np.zeros(0, np.int64), np.zeros((0, len(cols)))
    t = np.concatenate(times)
    v = np.concatenate(vals)
    order = np.argsort(t, kind="stable")
    t, v = t[order], v[order]
    if np.any(np.diff(t) == 0):
        raise ValueError(f"{folder}: duplicated output times")
    return t, v


def max_gap(got: np.ndarray, ref: np.ndarray) -> float:
    """Widest ``|got - ref|`` as a share of the reference's scale
    (its largest magnitude)."""
    return float(np.abs(got - ref).max() / np.abs(ref).max())
