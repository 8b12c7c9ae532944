"""``tdas``: flat binary stream format for the real-time ingest path.

Where the reference funnels all interrogator output through HDF5
(``patch.io.write(path, "dasdae")``, lf_das.py:232), tdas is the
edge-deployment alternative this framework adds: a 64-byte header + a
row-major (time, channel) payload (float32, or int16 with a scale for
2x ingest bandwidth). Range reads are exact byte offsets — no chunk
B-trees — executed by the threaded C++ runtime
(tpudas/native/streamio.cpp) when available, with a numpy fallback of
identical semantics.

The format registers in the IO registry, so spools index and read
``*.tdas`` interrogator directories exactly like dasdae ones, and the
whole engine (LFProc, streaming loops) runs on them unchanged.
"""

from __future__ import annotations

import ctypes
import os
import struct

import numpy as np

from tpudas.core.patch import Patch
from tpudas.core.timeutils import to_datetime64
from tpudas.native import load_streamio
from tpudas.obs.registry import get_registry
from tpudas.obs.trace import annotate

FORMAT_NAME = "tdas"
_MAGIC = b"TDAS"
_HEADER = struct.Struct("<4sIQQIIIfddQ")  # 64 bytes
_HEADER_SIZE = 64
_DTYPES = {0: np.float32, 1: np.int16}


def _default_threads() -> int:
    n = os.cpu_count() or 1
    return max(1, min(8, n - 1))


def _pack_header(t0_ns, dt_ns, n_time, n_ch, dtype_code, scale, d0, dx):
    return _HEADER.pack(
        _MAGIC, 1, t0_ns, dt_ns, n_time, n_ch, dtype_code, scale, d0, dx, 0
    )


def _unpack_header(raw: bytes) -> dict:
    magic, version, t0_ns, dt_ns, n_time, n_ch, dtype_code, scale, d0, dx, _ = (
        _HEADER.unpack(raw)
    )
    if magic != _MAGIC:
        raise ValueError("not a tdas file (bad magic)")
    if version != 1:
        raise ValueError(f"unsupported tdas version {version}")
    if dtype_code not in _DTYPES:
        # keep failure identical across the numpy and native readers: a
        # corrupt/future file raises here (and EINVAL in C++) rather
        # than decoding the payload as float32 garbage
        raise ValueError(f"unsupported tdas dtype code {dtype_code}")
    return dict(
        t0_ns=t0_ns,
        dt_ns=dt_ns,
        n_time=n_time,
        n_ch=n_ch,
        dtype_code=dtype_code,
        scale=scale,
        d0=d0,
        dx=dx,
    )


def read_tdas_header(path) -> dict:
    with open(path, "rb") as fh:
        raw = fh.read(_HEADER_SIZE)
    if len(raw) != _HEADER_SIZE:
        raise ValueError("truncated tdas header")
    return _unpack_header(raw)


# ---------------------------------------------------------------------------
# write


def write_tdas(patch, path, dtype="float32", scale=None, **_):
    """Write a 2-D (time, distance) Patch. ``dtype="int16"`` quantizes
    by ``scale`` (default: max|x|/32000, stored in the header)."""
    taxis = np.asarray(patch.coords["time"]).astype("datetime64[ns]")
    if taxis.size < 2:
        raise ValueError("tdas requires >= 2 time samples")
    steps = np.diff(taxis.astype(np.int64))
    if not np.all(steps == steps[0]):
        raise ValueError("tdas requires a uniform time axis")
    dist = np.asarray(patch.coords["distance"], np.float64)
    dx = float(dist[1] - dist[0]) if dist.size > 1 else 0.0
    if dist.size > 2 and not np.allclose(np.diff(dist), dx):
        raise ValueError("tdas requires a uniform distance axis")

    data = np.asarray(patch.host_data())
    ax = patch.axis_of("time")
    if ax != 0:
        data = np.moveaxis(data, ax, 0)
    data = np.ascontiguousarray(data, np.float32)

    if dtype == "int16":
        code = 1
        if scale is None:
            peak = float(np.abs(data).max()) or 1.0
            scale = peak / 32000.0
        payload = np.clip(
            np.round(data / scale), -32768, 32767
        ).astype(np.int16)
    elif dtype == "float32":
        code = 0
        scale = 1.0
        payload = data
    else:
        raise ValueError(f"tdas dtype must be float32|int16, got {dtype!r}")

    t0_ns = int(taxis[0].astype(np.int64))
    dt_ns = int(steps[0])
    lib = load_streamio()
    if lib is not None:
        rc = lib.tdas_write(
            os.fsencode(path),
            t0_ns,
            dt_ns,
            data.shape[0],
            data.shape[1],
            code,
            float(scale),
            float(dist[0]) if dist.size else 0.0,
            dx,
            payload.ctypes.data_as(ctypes.c_void_p),
        )
        if rc != 0:
            raise OSError(rc, f"tdas_write failed for {path}")
    else:
        with open(path, "wb") as fh:
            fh.write(
                _pack_header(
                    t0_ns, dt_ns, data.shape[0], data.shape[1], code,
                    float(scale), float(dist[0]) if dist.size else 0.0, dx,
                )
            )
            fh.write(payload.tobytes())
    return path


# ---------------------------------------------------------------------------
# read / scan


def _row_range(hdr, time):
    """[lo, hi) row range selected by a (t_lo, t_hi) datetime window —
    inclusive bounds, matching Patch.select semantics."""
    n = hdr["n_time"]
    lo, hi = 0, n
    if time is not None:
        t_lo, t_hi = time
        if t_lo is not None:
            t = to_datetime64(t_lo).astype("datetime64[ns]").astype(np.int64)
            lo = max(
                0, int(np.ceil((t - hdr["t0_ns"]) / hdr["dt_ns"]))
            )
        if t_hi is not None:
            t = to_datetime64(t_hi).astype("datetime64[ns]").astype(np.int64)
            hi = min(
                n, int(np.floor((t - hdr["t0_ns"]) / hdr["dt_ns"])) + 1
            )
    return lo, max(lo, hi)


def _ch_range(hdr, distance):
    n = hdr["n_ch"]
    lo, hi = 0, n
    if distance is not None and hdr["dx"] != 0:
        d_lo, d_hi = distance
        if d_lo is not None:
            lo = max(0, int(np.ceil((float(d_lo) - hdr["d0"]) / hdr["dx"])))
        if d_hi is not None:
            hi = min(
                n, int(np.floor((float(d_hi) - hdr["d0"]) / hdr["dx"])) + 1
            )
    return lo, max(lo, hi)


def _read_rows_raw_numpy(path, hdr, t_lo, t_hi, c_lo, c_hi):
    """Raw payload rows (no numeric conversion), channel-sliced."""
    dt = _DTYPES[hdr["dtype_code"]]
    es = dt().itemsize
    n_ch = hdr["n_ch"]
    rows = t_hi - t_lo
    with open(path, "rb") as fh:
        fh.seek(_HEADER_SIZE + t_lo * n_ch * es)
        raw = np.fromfile(fh, dtype=dt, count=rows * n_ch)
    return raw.reshape(rows, n_ch)[:, c_lo:c_hi]


def _read_block_numpy(path, hdr, t_lo, t_hi, c_lo, c_hi):
    raw = _read_rows_raw_numpy(path, hdr, t_lo, t_hi, c_lo, c_hi)
    if hdr["dtype_code"] == 1:
        return raw.astype(np.float32) * np.float32(hdr["scale"])
    return np.ascontiguousarray(raw, np.float32)


def read_tdas_block(path, t_lo, t_hi, c_lo, c_hi, n_threads=None):
    """(t_hi-t_lo, c_hi-c_lo) float32 block; native threaded reader
    when available."""
    hdr = read_tdas_header(path)
    if not (0 <= t_lo <= t_hi <= hdr["n_time"]):
        raise ValueError(f"row range [{t_lo}, {t_hi}) out of bounds")
    if not (0 <= c_lo <= c_hi <= hdr["n_ch"]):
        raise ValueError(f"channel range [{c_lo}, {c_hi}) out of bounds")
    lib = load_streamio()
    if lib is None:
        return _read_block_numpy(path, hdr, t_lo, t_hi, c_lo, c_hi)
    out = np.empty((t_hi - t_lo, c_hi - c_lo), np.float32)
    rc = lib.tdas_read_block(
        os.fsencode(path),
        int(t_lo),
        int(t_hi),
        int(c_lo),
        int(c_hi),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        int(n_threads or _default_threads()),
    )
    if rc != 0:
        raise OSError(rc, f"tdas_read_block failed for {path}")
    return out


def _patch_from_block(hdr, block, t_lo, c_lo):
    t0 = np.datetime64(hdr["t0_ns"] + t_lo * hdr["dt_ns"], "ns")
    taxis = t0 + np.arange(block.shape[0]) * np.timedelta64(
        hdr["dt_ns"], "ns"
    )
    dist = hdr["d0"] + (c_lo + np.arange(block.shape[1])) * hdr["dx"]
    return Patch(
        data=block,
        coords={"time": taxis, "distance": dist},
        dims=("time", "distance"),
    )


def read_tdas(path, time=None, distance=None, **_):
    """Read (a range of) a tdas file -> [Patch]."""
    hdr = read_tdas_header(path)
    t_lo, t_hi = _row_range(hdr, time)
    c_lo, c_hi = _ch_range(hdr, distance)
    if t_hi - t_lo == 0 or c_hi - c_lo == 0:
        return []
    block = read_tdas_block(path, t_lo, t_hi, c_lo, c_hi)
    return [_patch_from_block(hdr, block, t_lo, c_lo)]


def scan_tdas(path):
    """Metadata record for the directory index (no payload IO).

    Verifies the payload length against the header before trusting the
    record: tdas is a fixed-layout format, so a file the interrogator is
    still writing (or a torn copy) has ``size != 64 + n_time*n_ch*es``
    and raises here — the index skips it and re-scans once its
    (mtime, size) settles, instead of surfacing a short-read error at
    window-assembly time.

    The record carries the exact header ``dx`` so downstream planning
    (:func:`plan_window_from_records`) selects channels with the same
    float the per-file reader uses — reconstructing ``dx`` from
    ``(distance_max - d0) / (n - 1)`` is ulp-inexact and breaks byte
    parity on exact channel-boundary selects. (``distance_min`` already
    IS the exact header ``d0``.)
    """
    hdr = read_tdas_header(path)
    es = _DTYPES[hdr["dtype_code"]]().itemsize
    expected = _HEADER_SIZE + hdr["n_time"] * hdr["n_ch"] * es
    actual = os.path.getsize(path)
    if actual != expected:
        raise ValueError(
            f"tdas payload size mismatch for {path}: header promises "
            f"{expected} bytes, file has {actual} (still being written?)"
        )
    t0 = np.datetime64(hdr["t0_ns"], "ns")
    dt = np.timedelta64(hdr["dt_ns"], "ns")
    return [
        {
            "path": str(path),
            "format": FORMAT_NAME,
            "dims": "time,distance",
            "time_min": t0,
            "time_max": t0 + (hdr["n_time"] - 1) * dt,
            "time_step": dt,
            "distance_min": float(hdr["d0"]),
            "distance_max": float(
                hdr["d0"] + (hdr["n_ch"] - 1) * hdr["dx"]
            ),
            "ntime": int(hdr["n_time"]),
            "ndistance": int(hdr["n_ch"]),
            "dx": float(hdr["dx"]),
            # payload dtype + quantization scale: lets the window
            # planner route uniform-int16 spools through the raw
            # assembler (device-side decode, half the H2D bytes)
            "dtype_code": int(hdr["dtype_code"]),
            "scale": float(hdr["scale"]),
        }
    ]


def plan_window_from_records(records, t_lo, t_hi, distance=None):
    """Plan a contiguous window assembly straight from index records.

    ``records``: iterable of directory-index rows (dicts) sorted by
    ``time_min``, each carrying path/format/time_min/time_step/ntime/
    distance_min/distance_max/ndistance — everything needed to compute
    per-file row segments WITHOUT opening any file.  Returns a plan
    dict for :func:`assemble_window` (segments, c_lo, c_hi, total_rows,
    t0_ns, dt_ns, d0, dx) or None when the fast path does not apply
    (non-tdas files, mixed geometry, or a coverage gap — the generic
    merge path then handles gap policy).

    Row selection matches :func:`_row_range` (inclusive bounds) so the
    assembled window is byte-identical to per-file read + merge.
    """
    recs = list(records)
    if not recs:
        return None
    first = recs[0]
    if any(r.get("format") != FORMAT_NAME for r in recs):
        return None
    dt_ns = np.timedelta64(first["time_step"], "ns").astype(np.int64)
    if dt_ns <= 0:
        return None
    nd = int(first["ndistance"])
    d0 = float(first["distance_min"])
    d_max = float(first["distance_max"])

    def _exact_dx(rec):
        # prefer the exact header dx carried by the scan record; an
        # index built before the field existed reconstructs it (and
        # may be a ulp off on boundary selects — re-index to fix)
        v = rec.get("dx")
        if v is not None and np.isfinite(v):
            return float(v)
        n = int(rec["ndistance"])
        return (
            (float(rec["distance_max"]) - float(rec["distance_min"]))
            / (n - 1)
            if n > 1
            else 0.0
        )

    dx = _exact_dx(first)
    for r in recs:
        if (
            np.timedelta64(r["time_step"], "ns").astype(np.int64) != dt_ns
            or int(r["ndistance"]) != nd
            or float(r["distance_min"]) != d0
            or float(r["distance_max"]) != d_max
            or _exact_dx(r) != dx
        ):
            return None
    # uniform int16 payload (same quantization scale everywhere, known
    # for every record) -> the raw fast path: assemble int16, decode on
    # device. Anything else (f32, mixed, or pre-dtype index records)
    # assembles decoded float32 as before.
    codes = {r.get("dtype_code") for r in recs}
    scales = {r.get("scale") for r in recs}
    if codes == {1} and len(scales) == 1:
        (scale,) = scales
        payload = (
            ("int16", float(scale))
            if scale is not None and np.isfinite(scale)
            else ("float32", None)
        )
    else:
        payload = ("float32", None)
    c_lo, c_hi = _ch_range(
        {"n_ch": nd, "d0": d0, "dx": dx}, distance
    )
    if c_hi - c_lo == 0:
        return None
    segments, total, next_ns, t0_out = [], 0, None, None
    for r in recs:
        f0 = np.datetime64(r["time_min"], "ns").astype(np.int64)
        # structural parity with the generic path: the same _row_range
        # that read_tdas uses picks this file's rows
        r_lo, r_hi = _row_range(
            {"n_time": int(r["ntime"]), "t0_ns": f0, "dt_ns": dt_ns},
            (t_lo, t_hi),
        )
        if r_hi <= r_lo:
            continue
        seg_t0 = f0 + r_lo * dt_ns
        if next_ns is None:
            t0_out = seg_t0
        elif seg_t0 != next_ns:
            return None  # coverage gap or overlap: generic path decides
        segments.append((r["path"], r_lo, r_hi, total))
        total += r_hi - r_lo
        next_ns = f0 + r_hi * dt_ns
    if total == 0:
        return None
    return {
        "segments": segments,
        "c_lo": c_lo,
        "c_hi": c_hi,
        "total_rows": total,
        "t0_ns": int(t0_out),
        "dt_ns": int(dt_ns),
        "d0": d0,
        "dx": dx,
        "payload": payload[0],
        "scale": payload[1],
    }


def assemble_window_patch(plan, n_threads=None) -> Patch:
    """Execute a :func:`plan_window_from_records` plan: one native
    threaded multi-file read into a single contiguous buffer, wrapped
    as a Patch (the overlap-save hot-loop ingest, SURVEY.md §3.1 hot
    loops #2/#3).

    An ``int16`` plan assembles the RAW quantized payload and returns
    an int16 Patch carrying its quantization scale as the
    ``data_scale`` attr — the engine transfers half the bytes to the
    device and runs the (cast * scale) decode there. Such quantized
    patches exist only inside the engine's window path; the public
    read API (:func:`read_tdas`) always decodes to float32.
    """
    if plan.get("payload") == "int16":
        data = assemble_window_raw(
            plan["segments"], plan["c_lo"], plan["c_hi"],
            plan["total_rows"], dtype_code=1, n_threads=n_threads,
        )
        patch = _patch_from_block(plan, data, 0, plan["c_lo"])
        return patch.update_attrs(data_scale=float(plan["scale"]))
    data = assemble_window(
        plan["segments"], plan["c_lo"], plan["c_hi"], plan["total_rows"],
        n_threads=n_threads,
    )
    # plan carries t0_ns/dt_ns/d0/dx — exactly the header keys
    # _patch_from_block reads, so coordinate construction stays single-
    # sourced with the per-file reader
    return _patch_from_block(plan, data, 0, plan["c_lo"])


def _segment_arrays(segments):
    """ctypes marshaling shared by the native assemblers and planner."""
    n = len(segments)
    return (
        (ctypes.c_char_p * n)(*[os.fsencode(s[0]) for s in segments]),
        (ctypes.c_uint64 * n)(*[int(s[1]) for s in segments]),
        (ctypes.c_uint64 * n)(*[int(s[2]) for s in segments]),
        (ctypes.c_uint64 * n)(*[int(s[3]) for s in segments]),
        n,
    )


def window_chunks(segments, row_bytes):
    """The row chunks the native assemblers cut ``segments`` into, for
    output rows of ``row_bytes``: ``(segment, row_lo, row_hi,
    out_row0)`` each, in the order the workers pull them; None without
    the native runtime."""
    lib = load_streamio()
    if lib is None:
        return None
    _, row_lo, row_hi, out_r0, n = _segment_arrays(segments)
    count = lib.tdas_plan_window_chunks(
        row_lo, row_hi, out_r0, n, int(row_bytes), None, 0
    )
    flat = (ctypes.c_uint64 * (4 * count))()
    lib.tdas_plan_window_chunks(
        row_lo, row_hi, out_r0, n, int(row_bytes), flat, count
    )
    return [tuple(flat[4 * k : 4 * k + 4]) for k in range(count)]


def _record_read(chunks, threads):
    """A window read's chunk and worker counts, on the span open around
    it (``lfproc.load_window``) and in the registry."""
    annotate(chunks=chunks, threads=threads)
    get_registry().counter(
        "tpudas_window_read_chunks_total",
        "row chunks read by the window assemblers",
    ).inc(chunks)


def _assemble_native(lib, fn, segments, *args, n_threads):
    """Call native assembler ``fn`` (``args``: its arguments between the
    segments and the thread count), record its counts, raise on error."""
    paths, row_lo, row_hi, out_r0, n = _segment_arrays(segments)
    chunks, threads = ctypes.c_int(0), ctypes.c_int(0)
    rc = getattr(lib, fn)(
        paths, row_lo, row_hi, out_r0, n, *args,
        int(n_threads or _default_threads()),
        ctypes.byref(chunks), ctypes.byref(threads),
    )
    if rc != 0:
        raise OSError(rc, f"{fn} failed")
    _record_read(chunks.value, threads.value)


def assemble_window_raw(
    segments, c_lo, c_hi, total_rows, dtype_code, n_threads=None
):
    """Fill one contiguous (total_rows, c_hi-c_lo) buffer of the RAW
    payload dtype (no numeric conversion) from per-file row segments —
    the half-bandwidth half of the device-decode ingest path. Every
    file must carry ``dtype_code`` (the planner guarantees it; the
    native runtime re-checks per file)."""
    out = np.empty((total_rows, c_hi - c_lo), _DTYPES[dtype_code])
    lib = load_streamio()
    if lib is None:
        for path, r_lo, r_hi, o0 in segments:
            hdr = read_tdas_header(path)
            if hdr["dtype_code"] != dtype_code:
                raise ValueError(
                    f"{path}: payload dtype {hdr['dtype_code']} != "
                    f"planned {dtype_code}"
                )
            out[o0 : o0 + (r_hi - r_lo)] = _read_rows_raw_numpy(
                path, hdr, r_lo, r_hi, c_lo, c_hi
            )
        _record_read(len(segments), 1)
        return out
    _assemble_native(
        lib, "tdas_assemble_window_raw", segments, int(c_lo), int(c_hi),
        int(dtype_code), out.ctypes.data_as(ctypes.c_void_p),
        n_threads=n_threads,
    )
    return out


def assemble_window(segments, c_lo, c_hi, total_rows, n_threads=None):
    """Fill one contiguous (total_rows, c_hi-c_lo) float32 window from
    per-file row segments ``(path, row_lo, row_hi, out_row0)`` — the
    native-parallel host half of the overlap-save pipeline."""
    out = np.empty((total_rows, c_hi - c_lo), np.float32)
    lib = load_streamio()
    if lib is None:
        for path, r_lo, r_hi, o0 in segments:
            hdr = read_tdas_header(path)
            out[o0 : o0 + (r_hi - r_lo)] = _read_block_numpy(
                path, hdr, r_lo, r_hi, c_lo, c_hi
            )
        _record_read(len(segments), 1)
        return out
    _assemble_native(
        lib, "tdas_assemble_window", segments, int(c_lo), int(c_hi),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n_threads=n_threads,
    )
    return out
