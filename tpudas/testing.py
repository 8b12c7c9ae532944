"""Synthetic DAS data and the fault-injection harness for tests,
examples and benchmarks.

The reference ships no fixtures beyond its impulse probe (SURVEY.md §4);
tpudas provides a deterministic interrogator simulator: contiguous
dasdae files of a (time x distance) strain-rate stream containing a
known low-frequency component (recoverable after low-pass + decimate),
high-frequency interference (must be rejected), and noise.

Fault injection (re-exported from :mod:`tpudas.resilience.faults` —
the hooks live there so production IO modules never import this
module): build a :class:`FaultPlan` of :class:`FaultSpec` entries
(raise / truncate / delay at the named :data:`FAULT_SITES` — spool
read, index update, round body, carry save, serve tile read / queue
full, integrity verify, fs write ENOSPC) and scope it with
:func:`install_fault_plan`; every degradation path in the realtime
drivers is then exercisable deterministically.
:func:`write_corrupt_file` fabricates the classic bad input — a file
with valid HDF5 magic and garbage after it (a truncated interrogator
flush); :func:`enospc_error` is the ready-made full-disk ``OSError``
for the ``fs.write_enospc`` site.
"""

from __future__ import annotations

import errno
import os

import numpy as np

from tpudas.core.patch import Patch
from tpudas.core.timeutils import to_datetime64
from tpudas.io.registry import write_patch
from tpudas.resilience.faults import (  # noqa: F401 - re-exported harness
    FAULT_SITES,
    FaultPlan,
    FaultSpec,
    TransientFaultError,
    install_fault_plan,
)

__all__ = [
    "synthetic_patch",
    "make_synthetic_spool",
    "lowfreq_truth",
    "write_corrupt_file",
    "enospc_error",
    "FAULT_SITES",
    "FaultPlan",
    "FaultSpec",
    "TransientFaultError",
    "install_fault_plan",
]

DEFAULT_T0 = "2023-03-22T00:00:00"

# the HDF5 signature — a half-written interrogator file usually has a
# valid header and garbage (or nothing) after it
_HDF5_MAGIC = b"\x89HDF\r\n\x1a\n"


def enospc_error(msg: str = "injected: no space left on device") -> OSError:
    """An ``OSError`` carrying ``errno.ENOSPC`` — pass as
    ``FaultSpec("fs.write_enospc", exc=enospc_error())`` to simulate a
    full disk at any atomic state write (the taxonomy classifies it
    ``"resource"`` and the driver sheds non-essential writers)."""
    return OSError(errno.ENOSPC, msg)


def write_corrupt_file(path, nbytes=512, seed=0) -> str:
    """A deterministic un-decodable DAS file: valid HDF5 magic (so the
    suffix and sniffer both say "dasdae"), garbage payload (so the scan
    fails) — the shape of a file the interrogator died mid-flush on.
    Returns ``path``."""
    rng = np.random.default_rng(seed)
    body = rng.integers(0, 256, size=max(int(nbytes) - 8, 0), dtype=np.uint8)
    with open(path, "wb") as fh:
        fh.write(_HDF5_MAGIC)
        fh.write(body.tobytes())
    return str(path)


def _time_axis(t0, n, fs):
    start = to_datetime64(t0).astype("datetime64[ns]")
    step = np.timedelta64(int(round(1e9 / fs)), "ns")
    return start + np.arange(n) * step


def _signal(t_sec, dists, lf_freq, hf_freq, noise, rng):
    """(T, C) strain-rate: channel-ramped LF sine + HF sine + noise."""
    amp = 1.0 + dists / (dists.max() + 1.0)
    lf = np.sin(2 * np.pi * lf_freq * t_sec)[:, None] * amp[None, :]
    hf = 0.5 * np.sin(2 * np.pi * hf_freq * t_sec)[:, None]
    out = lf + hf
    if noise:
        out = out + noise * rng.standard_normal(out.shape)
    return out.astype(np.float32)


def lowfreq_truth(times, dists, lf_freq=0.05):
    """The recoverable low-frequency component at given datetimes."""
    t_sec = (
        times.astype("datetime64[ns]") - times[0].astype("datetime64[ns]")
    ).astype(np.int64) / 1e9
    amp = 1.0 + np.asarray(dists) / (np.asarray(dists).max() + 1.0)
    return np.sin(2 * np.pi * lf_freq * t_sec)[:, None] * amp[None, :]


def synthetic_patch(
    t0=DEFAULT_T0,
    duration=30.0,
    fs=200.0,
    n_ch=16,
    d_ch=5.0,
    gauge_length=10.0,
    lf_freq=0.05,
    hf_freq=25.0,
    noise=0.0,
    seed=0,
    phase_origin=None,
) -> Patch:
    """One interrogator file's worth of synthetic data.

    ``phase_origin`` makes the LF/HF phases continuous across files when
    set to the stream start time.
    """
    n = int(round(duration * fs))
    times = _time_axis(t0, n, fs)
    origin = to_datetime64(phase_origin if phase_origin is not None else t0)
    t_sec = (times - origin.astype("datetime64[ns]")).astype(np.int64) / 1e9
    dists = np.arange(n_ch, dtype=np.float64) * d_ch
    rng = np.random.default_rng(seed)
    data = _signal(t_sec, dists, lf_freq, hf_freq, noise, rng)
    return Patch(
        data=data,
        coords={"time": times, "distance": dists},
        dims=("time", "distance"),
        attrs={
            "gauge_length": gauge_length,
            "d_time": 1.0 / fs,
            "d_distance": d_ch,
        },
    )


def make_synthetic_spool(
    directory,
    n_files=4,
    file_duration=30.0,
    fs=200.0,
    n_ch=16,
    start=DEFAULT_T0,
    format="dasdae",
    prefix="raw",
    write_kwargs=None,
    **kwargs,
):
    """Write ``n_files`` contiguous files into ``directory`` in the
    given IO format ("dasdae" HDF5 or the native "tdas" stream).

    Files are synthesized on a thread per file (numpy releases the GIL
    in the bulk math; a deployment-width spool is minutes of work on
    one core); each file's seed is its index, so the bytes do not
    depend on the threading.

    ``prefix`` names the files ``<prefix>_<i>.<ext>`` — pass a distinct
    prefix when appending a later batch into an existing directory
    (streaming tests), or the new files would overwrite the old.
    ``write_kwargs`` forwards to the format writer (e.g.
    ``{"dtype": "int16", "scale": 1e-3}`` for a quantized tdas spool).
    """
    os.makedirs(directory, exist_ok=True)
    t0 = to_datetime64(start).astype("datetime64[ns]")
    step = np.timedelta64(int(round(1e9 / fs)), "ns")
    n = int(round(file_duration * fs))
    suffix = ".tdas" if format == "tdas" else ".h5"

    def one(i):
        patch = synthetic_patch(
            t0=t0 + i * n * step,
            duration=file_duration,
            fs=fs,
            n_ch=n_ch,
            seed=i,
            phase_origin=t0,
            **kwargs,
        )
        path = os.path.join(directory, f"{prefix}_{i:04d}{suffix}")
        write_patch(patch, path, format=format, **(write_kwargs or {}))
        return path

    from concurrent.futures import ThreadPoolExecutor

    workers = max(1, min(n_files, os.cpu_count() or 1))
    with ThreadPoolExecutor(workers) as pool:
        return list(pool.map(one, range(n_files)))


def max_ulps(got, ref) -> float:
    """Largest ``|got - ref|`` in f32 ulps of ``ref``'s scale (its
    largest magnitude): how sharded or batched runs, whose programs
    trace other shapes, are compared with the single-device run."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        raise ValueError(f"shapes differ: {got.shape} vs {ref.shape}")
    eps = float(np.finfo(np.float32).eps)
    return float(np.abs(got - ref).max()) / (eps * float(np.abs(ref).max()))
