"""Native tdas stream format + C++ streamio runtime: roundtrips,
range reads, int16 quantization, native/numpy parity, window assembly,
and the full engine running on a tdas spool."""

import os

import numpy as np
import pytest

from tpudas import spool
from tpudas.io import tdas
from tpudas.io.registry import read_file, scan_file, write_patch
from tpudas.testing import make_synthetic_spool, synthetic_patch


@pytest.fixture()
def patch():
    return synthetic_patch(duration=10.0, fs=100.0, n_ch=12, noise=0.05)


class TestRoundtrip:
    def test_float32_exact(self, patch, tmp_path):
        path = str(tmp_path / "a.tdas")
        write_patch(patch, path, format="tdas")
        (back,) = read_file(path, format="tdas")
        assert np.array_equal(back.host_data(), patch.host_data())
        assert np.array_equal(back.coords["time"], patch.coords["time"])
        assert np.allclose(back.coords["distance"], patch.coords["distance"])

    def test_int16_quantized(self, patch, tmp_path):
        path = str(tmp_path / "a.tdas")
        write_patch(patch, path, format="tdas", dtype="int16")
        (back,) = read_file(path, format="tdas")
        hdr = tdas.read_tdas_header(path)
        assert hdr["dtype_code"] == 1
        # quantization error bounded by half an LSB
        err = np.abs(back.host_data() - patch.host_data()).max()
        assert err <= hdr["scale"] * 0.5 + 1e-7
        # int16 payload is half the size of the float32 one
        p32 = str(tmp_path / "b.tdas")
        write_patch(patch, p32, format="tdas")
        assert os.path.getsize(path) < 0.6 * os.path.getsize(p32)

    def test_unknown_dtype_code_rejected(self, patch, tmp_path):
        # a corrupt/future dtype code must fail identically in the
        # numpy and native readers, not decode as float32 garbage
        path = str(tmp_path / "bad.tdas")
        write_patch(patch, path, format="tdas")
        with open(path, "r+b") as fh:
            fh.seek(32)  # dtype_code field (<4sIQQII|I|fddQ)
            fh.write((7).to_bytes(4, "little"))
        with pytest.raises(ValueError, match="dtype code"):
            tdas.read_tdas_header(path)
        from tpudas.native import load_streamio

        lib = load_streamio()
        if lib is not None:
            import ctypes
            import errno

            u64, u32, f32, f64 = (
                ctypes.c_uint64, ctypes.c_uint32, ctypes.c_float,
                ctypes.c_double,
            )
            args = [u64(), u64(), u32(), u32(), u32(), f32(), f64(), f64()]
            rc = lib.tdas_read_header(
                os.fsencode(path), *(ctypes.byref(a) for a in args)
            )
            assert rc == errno.EINVAL

    def test_nonuniform_time_rejected(self, patch, tmp_path):
        coords = dict(patch.coords)
        t = coords["time"].copy()
        t[3] += np.timedelta64(1, "ms")
        coords["time"] = t
        bad = patch.new(coords=coords)
        with pytest.raises(ValueError, match="uniform time"):
            write_patch(bad, str(tmp_path / "x.tdas"), format="tdas")


class TestRangeReads:
    def test_time_range_matches_slice(self, patch, tmp_path):
        path = str(tmp_path / "a.tdas")
        write_patch(patch, path, format="tdas")
        t = patch.coords["time"]
        (sub,) = read_file(path, format="tdas", time=(t[100], t[399]))
        full = patch.host_data()
        assert sub.host_data().shape == (300, 12)
        assert np.array_equal(sub.host_data(), full[100:400])
        assert sub.coords["time"][0] == t[100]

    def test_distance_range(self, patch, tmp_path):
        path = str(tmp_path / "a.tdas")
        write_patch(patch, path, format="tdas")
        d = patch.coords["distance"]
        (sub,) = read_file(path, format="tdas", distance=(d[3], d[7]))
        assert sub.host_data().shape[1] == 5
        assert np.array_equal(sub.host_data(), patch.host_data()[:, 3:8])

    def test_block_out_of_bounds(self, patch, tmp_path):
        path = str(tmp_path / "a.tdas")
        write_patch(patch, path, format="tdas")
        with pytest.raises(ValueError, match="out of bounds"):
            tdas.read_tdas_block(path, 0, 10**6, 0, 1)


class TestNativeParity:
    def test_numpy_fallback_identical(self, patch, tmp_path, monkeypatch):
        path = str(tmp_path / "a.tdas")
        write_patch(patch, path, format="tdas", dtype="int16")
        native = tdas.read_tdas_block(path, 50, 750, 2, 11)
        monkeypatch.setattr(tdas, "load_streamio", lambda: None)
        fallback = tdas.read_tdas_block(path, 50, 750, 2, 11)
        assert np.array_equal(native, fallback)

    def test_write_fallback_readable_by_native(self, patch, tmp_path,
                                               monkeypatch):
        path = str(tmp_path / "a.tdas")
        monkeypatch.setattr(tdas, "load_streamio", lambda: None)
        tdas.write_tdas(patch, path)
        monkeypatch.undo()
        (back,) = read_file(path, format="tdas")
        assert np.array_equal(back.host_data(), patch.host_data())


class TestAssembleWindow:
    def test_multi_file_window(self, tmp_path):
        paths = make_synthetic_spool(
            tmp_path, n_files=3, file_duration=10.0, fs=100.0, n_ch=8,
            noise=0.05, format="tdas",
        )
        # window spanning the tail of file 0, all of file 1, head of 2
        segs = [
            (paths[0], 600, 1000, 0),
            (paths[1], 0, 1000, 400),
            (paths[2], 0, 300, 1400),
        ]
        win = tdas.assemble_window(segs, 1, 7, 1700)
        assert win.shape == (1700, 6)
        a = tdas.read_tdas_block(paths[0], 600, 1000, 1, 7)
        b = tdas.read_tdas_block(paths[1], 0, 1000, 1, 7)
        c = tdas.read_tdas_block(paths[2], 0, 300, 1, 7)
        assert np.array_equal(win, np.concatenate([a, b, c]))


class TestSpoolIntegration:
    def test_index_scan_and_select(self, tmp_path):
        make_synthetic_spool(
            tmp_path, n_files=4, file_duration=15.0, fs=50.0, n_ch=6,
            format="tdas",
        )
        sp = spool(str(tmp_path)).sort("time").update()
        assert len(sp) == 4
        df = sp.get_contents()
        assert set(df["format"]) == {"tdas"}
        merged = sp.chunk(time=None)
        assert len(merged) == 1
        assert merged[0].host_data().shape == (4 * 750, 6)

    def test_corrupt_file_skipped(self, tmp_path):
        make_synthetic_spool(
            tmp_path, n_files=2, file_duration=15.0, fs=50.0, n_ch=6,
            format="tdas",
        )
        with open(tmp_path / "junk.tdas", "wb") as fh:
            fh.write(b"not a tdas file at all")
        sp = spool(str(tmp_path)).update()
        assert len(sp) == 2

    def test_legacy_cache_version_discarded_and_rescanned(self, tmp_path):
        """A pre-dx index cache (version 1) must be discarded whole on
        load: mixed legacy/new records would fail the planner's
        geometry check and silently disable the native fast path."""
        import json

        from tpudas.io.index import INDEX_FILENAME, DirectoryIndex

        make_synthetic_spool(
            tmp_path, n_files=2, file_duration=10.0, fs=50.0, n_ch=8,
            d_ch=0.1, format="tdas",
        )
        DirectoryIndex(str(tmp_path)).update()
        cache = tmp_path / INDEX_FILENAME
        raw = json.loads(cache.read_text())
        # fabricate the legacy cache: version 1, no dx field
        raw["version"] = 1
        for rec in raw["files"].values():
            rec.pop("dx", None)
        cache.write_text(json.dumps(raw))
        sp = spool(str(tmp_path)).sort("time").update()
        df = sp.get_contents()
        assert len(df) == 2
        assert all(np.isfinite(v) for v in df["dx"])  # rescanned
        plan = sp.native_window_plan(
            np.datetime64("2023-03-22T00:00:02"),
            np.datetime64("2023-03-22T00:00:18"),
        )
        assert plan is not None  # fast path alive across the upgrade

    def test_v2_cache_discarded_so_int16_fast_path_fires(self, tmp_path):
        """A v2 cache (pre dtype_code/scale) must be discarded whole,
        or an int16 spool indexed before the upgrade would never plan
        the raw device-decode path (round-4 review)."""
        import json

        from tpudas.io.index import INDEX_FILENAME, DirectoryIndex

        make_synthetic_spool(
            tmp_path, n_files=2, file_duration=10.0, fs=100.0, n_ch=4,
            format="tdas", write_kwargs={"dtype": "int16", "scale": 1e-3},
        )
        DirectoryIndex(str(tmp_path)).update()
        cache = tmp_path / INDEX_FILENAME
        raw = json.loads(cache.read_text())
        raw["version"] = 2
        for rec in raw["files"].values():
            rec.pop("dtype_code", None)
            rec.pop("scale", None)
        cache.write_text(json.dumps(raw))
        sp = spool(str(tmp_path)).sort("time").update()
        plan = sp.native_window_plan(
            np.datetime64("2023-03-22T00:00:02"),
            np.datetime64("2023-03-22T00:00:18"),
        )
        assert plan is not None and plan["payload"] == "int16"

    def test_truncated_indexed_file_record_dropped(self, tmp_path):
        """A file that was indexed complete and later truncated in
        place must lose its (now stale) index record — not serve a
        short read at window-assembly time."""
        make_synthetic_spool(
            tmp_path, n_files=2, file_duration=10.0, fs=50.0, n_ch=4,
            format="tdas",
        )
        sp = spool(str(tmp_path)).update()
        assert len(sp) == 2
        victim = sorted(tmp_path.glob("*.tdas"))[0]
        full = victim.read_bytes()
        victim.write_bytes(full[: len(full) - 64])  # truncate in place
        assert len(spool(str(tmp_path)).update()) == 1
        victim.write_bytes(full)  # writer finishes: record returns
        assert len(spool(str(tmp_path)).update()) == 2

    def test_torn_file_rejected_then_indexed_when_complete(self, tmp_path):
        """A file whose payload is shorter than the header promises (an
        interrogator mid-write / torn copy) is rejected at scan time —
        not surfaced as a short read at window-assembly time — and is
        picked up once its bytes settle."""
        make_synthetic_spool(
            tmp_path, n_files=1, file_duration=10.0, fs=50.0, n_ch=4,
            format="tdas",
        )
        (name,) = [p for p in os.listdir(tmp_path) if p.endswith(".tdas")]
        full = (tmp_path / name).read_bytes()
        torn = tmp_path / "torn.tdas"
        torn.write_bytes(full[: len(full) - 128])
        with pytest.raises(ValueError, match="size mismatch"):
            scan_file(str(torn), format="tdas")
        sp = spool(str(tmp_path)).update()
        assert len(sp) == 1  # torn file skipped, valid one indexed
        torn.write_bytes(full)  # "interrogator finished writing"
        assert len(spool(str(tmp_path)).update()) == 2

    def test_scan_carries_exact_dx(self, tmp_path):
        """Scan records carry the header's exact dx: reconstructing it
        from (distance_max - d0)/(n-1) is ulp-inexact and moves exact
        channel-boundary selects (round-2 advisor finding)."""
        patch = synthetic_patch(
            duration=10.0, fs=50.0, n_ch=49, d_ch=0.1
        )
        path = str(tmp_path / "a.tdas")
        write_patch(patch, path, format="tdas")
        hdr = tdas.read_tdas_header(path)
        rec = scan_file(path, format="tdas")[0]
        assert rec["dx"] == hdr["dx"]
        recon = (rec["distance_max"] - rec["distance_min"]) / (
            rec["ndistance"] - 1
        )
        assert recon != hdr["dx"]  # the reconstruction really is off

    def test_plan_channel_bounds_match_reader_on_exact_boundary(
        self, tmp_path
    ):
        """A distance select landing exactly on a channel must pick the
        same channels through the planned fast path as through the
        per-file reader (byte parity on boundary selects)."""
        make_synthetic_spool(
            tmp_path, n_files=2, file_duration=10.0, fs=50.0, n_ch=49,
            d_ch=0.1, format="tdas",
        )
        first = sorted(
            p for p in os.listdir(tmp_path) if p.endswith(".tdas")
        )[0]
        hdr = tdas.read_tdas_header(str(tmp_path / first))
        dx = hdr["dx"]
        sel = (3 * dx, 40 * dx)  # k=3 flips under ulp-off dx
        sp = spool(str(tmp_path)).sort("time").update().select(distance=sel)
        t_lo = np.datetime64("2023-03-22T00:00:02")
        t_hi = np.datetime64("2023-03-22T00:00:18")
        plan = sp.native_window_plan(t_lo, t_hi)
        assert plan is not None
        fast = tdas.assemble_window_patch(plan)
        merged = spool(sp.select(time=(t_lo, t_hi))).chunk(time=None)[0]
        assert np.array_equal(fast.host_data(), merged.host_data())
        assert np.array_equal(
            fast.coords["distance"], merged.coords["distance"]
        )

    @pytest.mark.slow
    def test_lfproc_end_to_end_on_tdas(self, tmp_path):
        """The full chunked engine runs unchanged on a native-format
        spool and matches the dasdae-format result exactly."""
        from tpudas.proc.lfproc import LFProc

        results = {}
        for fmt in ("tdas", "dasdae"):
            src = tmp_path / fmt
            make_synthetic_spool(
                src, n_files=4, file_duration=30.0, fs=100.0, n_ch=6,
                noise=0.01, format=fmt,
            )
            lfp = LFProc(spool(str(src)).sort("time").update())
            lfp.update_processing_parameter(
                output_sample_interval=1.0, process_patch_size=50,
                edge_buff_size=10,
            )
            out = tmp_path / (fmt + "_out")
            lfp.set_output_folder(str(out), delete_existing=True)
            lfp.process_time_range(
                np.datetime64("2023-03-22T00:00:00"),
                np.datetime64("2023-03-22T00:02:00"),
            )
            results[fmt] = spool(str(out)).update().chunk(time=None)[0]
            # tdas spools must take the native window-assembly fast
            # path for every window; dasdae spools never do
            expect = {"tdas": lambda n: n > 0, "dasdae": lambda n: n == 0}
            assert expect[fmt](lfp.native_windows), (fmt, lfp.native_windows)
        assert np.array_equal(
            results["tdas"].host_data(), results["dasdae"].host_data()
        )


class TestWindowPlan:
    def test_plan_matches_merge(self, tmp_path):
        make_synthetic_spool(
            tmp_path, n_files=3, file_duration=10.0, fs=100.0, n_ch=8,
            noise=0.05, format="tdas",
        )
        sp = spool(str(tmp_path)).sort("time").update()
        t_lo = np.datetime64("2023-03-22T00:00:04")
        t_hi = np.datetime64("2023-03-22T00:00:27.5")
        plan = sp.native_window_plan(t_lo, t_hi)
        assert plan is not None
        assert len(plan["segments"]) == 3
        fast = tdas.assemble_window_patch(plan)
        merged = spool(sp.select(time=(t_lo, t_hi))).chunk(time=None)[0]
        assert np.array_equal(fast.host_data(), merged.host_data())
        assert np.array_equal(
            fast.coords["time"], merged.coords["time"]
        )
        assert np.allclose(
            fast.coords["distance"], merged.coords["distance"]
        )

    def test_plan_honors_distance_selection(self, tmp_path):
        make_synthetic_spool(
            tmp_path, n_files=2, file_duration=10.0, fs=100.0, n_ch=8,
            d_ch=5.0, format="tdas",
        )
        sp = spool(str(tmp_path)).update().select(distance=(10.0, 25.0))
        plan = sp.native_window_plan(
            np.datetime64("2023-03-22T00:00:00"),
            np.datetime64("2023-03-22T00:00:15"),
        )
        assert plan is not None
        assert (plan["c_lo"], plan["c_hi"]) == (2, 6)

    def test_plan_none_for_gap(self, tmp_path):
        make_synthetic_spool(
            tmp_path, n_files=1, file_duration=10.0, fs=100.0, n_ch=4,
            format="tdas",
        )
        make_synthetic_spool(
            tmp_path, n_files=1, file_duration=10.0, fs=100.0, n_ch=4,
            format="tdas", start="2023-03-22T00:01:00", prefix="late",
        )
        sp = spool(str(tmp_path)).sort("time").update()
        plan = sp.native_window_plan(
            np.datetime64("2023-03-22T00:00:00"),
            np.datetime64("2023-03-22T00:01:05"),
        )
        assert plan is None  # gap -> generic path decides on_gap policy

    def test_int16_plan_assembles_raw_with_scale(self, tmp_path):
        """Uniform-int16 spools plan a raw (device-decode) assembly:
        int16 payload + data_scale attr, byte-identical to the decoded
        read path after host-side dequantization."""
        make_synthetic_spool(
            tmp_path, n_files=3, file_duration=10.0, fs=100.0, n_ch=8,
            noise=0.05, format="tdas",
            write_kwargs={"dtype": "int16", "scale": 1e-3},
        )
        sp = spool(str(tmp_path)).sort("time").update()
        t_lo = np.datetime64("2023-03-22T00:00:04")
        t_hi = np.datetime64("2023-03-22T00:00:27.5")
        plan = sp.native_window_plan(t_lo, t_hi)
        assert plan is not None
        assert plan["payload"] == "int16"
        assert plan["scale"] == pytest.approx(1e-3)
        qpatch = tdas.assemble_window_patch(plan)
        assert qpatch.host_data().dtype == np.int16
        assert qpatch.attrs["data_scale"] == pytest.approx(1e-3)
        decoded = qpatch.host_data().astype(np.float32) * np.float32(
            plan["scale"]
        )
        merged = spool(sp.select(time=(t_lo, t_hi))).chunk(time=None)[0]
        assert np.array_equal(decoded, merged.host_data())

    def test_int16_raw_numpy_fallback_identical(self, tmp_path,
                                                monkeypatch):
        make_synthetic_spool(
            tmp_path, n_files=2, file_duration=10.0, fs=100.0, n_ch=8,
            noise=0.05, format="tdas",
            write_kwargs={"dtype": "int16", "scale": 2e-3},
        )
        sp = spool(str(tmp_path)).sort("time").update().select(
            distance=(10.0, 30.0)
        )
        plan = sp.native_window_plan(
            np.datetime64("2023-03-22T00:00:02"),
            np.datetime64("2023-03-22T00:00:18"),
        )
        assert plan is not None and plan["payload"] == "int16"
        native = tdas.assemble_window_raw(
            plan["segments"], plan["c_lo"], plan["c_hi"],
            plan["total_rows"], dtype_code=1,
        )
        monkeypatch.setattr(tdas, "load_streamio", lambda: None)
        fallback = tdas.assemble_window_raw(
            plan["segments"], plan["c_lo"], plan["c_hi"],
            plan["total_rows"], dtype_code=1,
        )
        assert native.dtype == np.int16
        assert np.array_equal(native, fallback)

    def test_mixed_scale_int16_falls_back_to_float32(self, tmp_path):
        # default int16 writing picks a per-file peak scale -> scales
        # differ -> the raw path must NOT fire (a single scale cannot
        # decode the window); decoded-f32 assembly still applies
        make_synthetic_spool(
            tmp_path, n_files=3, file_duration=10.0, fs=100.0, n_ch=4,
            noise=0.05, format="tdas", write_kwargs={"dtype": "int16"},
        )
        sp = spool(str(tmp_path)).sort("time").update()
        plan = sp.native_window_plan(
            np.datetime64("2023-03-22T00:00:02"),
            np.datetime64("2023-03-22T00:00:28"),
        )
        assert plan is not None
        assert plan["payload"] == "float32"
        patch = tdas.assemble_window_patch(plan)
        assert patch.host_data().dtype == np.float32

    @pytest.mark.slow
    def test_lfproc_device_decode_matches_host_decode(self, tmp_path):
        """The engine on a uniform-int16 spool (device decode) produces
        byte-identical output to the same engine fed host-decoded f32
        patches of the same quantized data."""
        from tpudas.io.spool import MemorySpool
        from tpudas.proc.lfproc import LFProc

        src = tmp_path / "q"
        make_synthetic_spool(
            src, n_files=4, file_duration=30.0, fs=100.0, n_ch=6,
            noise=0.01, format="tdas",
            write_kwargs={"dtype": "int16", "scale": 1e-3},
        )
        t0 = np.datetime64("2023-03-22T00:00:00")
        t1 = np.datetime64("2023-03-22T00:02:00")
        results = {}
        for label, sp in (
            ("device", spool(str(src)).sort("time").update()),
            (
                "host",
                MemorySpool(
                    list(spool(str(src)).sort("time").update())
                ),  # read path host-decodes to f32
            ),
        ):
            lfp = LFProc(sp)
            lfp.update_processing_parameter(
                output_sample_interval=1.0, process_patch_size=50,
                edge_buff_size=10,
            )
            out = tmp_path / f"out_{label}"
            lfp.set_output_folder(str(out), delete_existing=True)
            lfp.process_time_range(t0, t1)
            if label == "device":
                assert lfp.native_windows > 0  # raw fast path fired
            results[label] = (
                spool(str(out)).update().chunk(time=None)[0].host_data()
            )
        assert np.array_equal(results["device"], results["host"])

    def test_plan_none_for_dasdae(self, tmp_path):
        make_synthetic_spool(
            tmp_path, n_files=2, file_duration=10.0, fs=100.0, n_ch=4,
            format="dasdae",
        )
        sp = spool(str(tmp_path)).update()
        assert (
            sp.native_window_plan(
                np.datetime64("2023-03-22T00:00:00"),
                np.datetime64("2023-03-22T00:00:15"),
            )
            is None
        )


# Window shapes wide enough that a chunk is the 2,048-row floor (an output
# row of >= 8 KiB), so a 2,500-row file reads in a full chunk and a tail.
_CHUNK_ROWS = 2048
_FILE_ROWS = 2500
_WIDTH = {"int16": 4096, "float32": 2048}
_LAYOUTS = {
    "one_file": [(0, 100, 2400)],
    "two_files": [(0, 1200, 2500), (1, 0, 2300)],
    "three_files": [(0, 2000, 2500), (1, 0, 2500), (2, 0, 700)],
    "one_row_segment": [(0, 0, 2500), (1, 0, 1)],
}

native_only = pytest.mark.skipif(
    tdas.load_streamio() is None, reason="no native streamio runtime"
)


@pytest.fixture(scope="module", params=sorted(_WIDTH))
def wide_spool(request, tmp_path_factory):
    """Three contiguous 2,500-row tdas files of one payload dtype."""
    from tpudas.core.patch import Patch

    payload, n_ch = request.param, _WIDTH[request.param]
    root = tmp_path_factory.mktemp(f"wide_{payload}")
    rng = np.random.default_rng(7)
    dt = np.timedelta64(1, "ms")
    paths = []
    for i in range(3):
        data = rng.integers(-30000, 30000, (_FILE_ROWS, n_ch)).astype(
            np.float32
        ) * np.float32(1e-3)
        t0 = np.datetime64("2023-03-22T00:00:00", "ns") + i * _FILE_ROWS * dt
        patch = Patch(
            data=data,
            coords={
                "time": t0 + np.arange(_FILE_ROWS) * dt,
                "distance": np.arange(n_ch) * 1.0,
            },
            dims=("time", "distance"),
        )
        path = str(root / f"w{i}.tdas")
        tdas.write_tdas(patch, path, dtype=payload, scale=1e-3)
        paths.append(path)
    return payload, n_ch, paths


def _segments(paths, layout):
    segs, out_row = [], 0
    for f, lo, hi in _LAYOUTS[layout]:
        segs.append((paths[f], lo, hi, out_row))
        out_row += hi - lo
    return segs, out_row


def _per_file_merge(segs, c_lo, c_hi, monkeypatch):
    """The window as ``read_tdas`` of each file's rows, merged, on the
    numpy reader (no native code in the reference)."""
    with monkeypatch.context() as mp:
        mp.setattr(tdas, "load_streamio", lambda: None)
        parts = []
        for path, lo, hi, _ in segs:
            hdr = tdas.read_tdas_header(path)
            t = hdr["t0_ns"] + np.array([lo, hi - 1]) * hdr["dt_ns"]
            (p,) = tdas.read_tdas(
                path,
                time=tuple(np.datetime64(int(v), "ns") for v in t),
                distance=(float(c_lo), float(c_hi - 1)),
            )
            parts.append(p.host_data())
    return np.concatenate(parts)


@native_only
class TestChunkedAssembly:
    @pytest.mark.parametrize("n_threads", [1, 2, 8])
    @pytest.mark.parametrize("span", ["full", "sub"])
    @pytest.mark.parametrize("layout", sorted(_LAYOUTS))
    def test_byte_identical_to_per_file_merge(
        self, wide_spool, layout, span, n_threads, monkeypatch
    ):
        payload, n_ch, paths = wide_spool
        segs, rows = _segments(paths, layout)
        # the sub-span reads through the bounce buffer
        c_lo, c_hi = (0, n_ch) if span == "full" else (3, n_ch - 5)
        want = _per_file_merge(segs, c_lo, c_hi, monkeypatch)
        got = tdas.assemble_window(
            segs, c_lo, c_hi, rows, n_threads=n_threads
        )
        assert got.dtype == np.float32
        assert got.tobytes() == want.tobytes()
        code = 1 if payload == "int16" else 0
        raw = tdas.assemble_window_raw(
            segs, c_lo, c_hi, rows, dtype_code=code, n_threads=n_threads
        )
        want_raw = np.concatenate([
            tdas._read_rows_raw_numpy(
                p, tdas.read_tdas_header(p), lo, hi, c_lo, c_hi
            )
            for p, lo, hi, _ in segs
        ])
        assert raw.dtype == want_raw.dtype
        assert raw.tobytes() == want_raw.tobytes()
        # the window is more than one chunk a segment wherever a
        # segment passes the chunk floor
        chunks = tdas.window_chunks(segs, raw.strides[0])
        assert len(chunks) == len(segs) + sum(
            (hi - lo - 1) // _CHUNK_ROWS for _, lo, hi, _ in segs
        )


def _within(seconds, fn, *args, **kwargs):
    """``fn(*args, **kwargs)`` on a thread joined with a timeout: its
    result, or its exception re-raised; a hang fails the test."""
    import threading

    box = {}

    def run():
        try:
            box["value"] = fn(*args, **kwargs)
        except Exception as exc:  # re-raised on the test thread below
            box["error"] = exc

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), f"{fn.__name__} still running after {seconds} s"
    if "error" in box:
        raise box["error"]
    return box["value"]


@native_only
def test_more_workers_than_cores_on_many_segments(tmp_path):
    """48 files of 64 rows: one chunk a segment, pulled by 32 workers
    (more than this host's cores) off one counter, with fd reuse across
    chunks; the window is the per-file merge, in order, every time."""
    paths = make_synthetic_spool(
        tmp_path, n_files=48, file_duration=0.64, fs=100.0, n_ch=16,
        noise=0.05, format="tdas",
        write_kwargs={"dtype": "int16", "scale": 1e-3},
    )
    segs = [(p, 0, 64, 64 * i) for i, p in enumerate(paths)]
    want = np.concatenate([
        tdas._read_rows_raw_numpy(p, tdas.read_tdas_header(p), 0, 64, 0, 16)
        for p in paths
    ])
    for _ in range(20):
        got = _within(60, tdas.assemble_window_raw, segs, 0, 16, 64 * 48,
                      dtype_code=1, n_threads=32)
        assert got.tobytes() == want.tobytes()


_PLANS = {
    # the cell's four window layouts over 60 s files at 1 kHz
    "cell_head": ([(0, 60000), (0, 1)], 20000),
    "cell_mid": ([(40000, 60000), (0, 40001)], 20000),
    "cell_late": ([(20000, 60000), (0, 20001)], 20000),
    "cell_last": ([(1000, 60000)], 20000),
    "edge_2048_10s": ([(50000, 60000)], 4096),
    "edge_500_1s": ([(0, 1000)], 1000),
    "three_files_f32_sub": ([(5, 7000), (0, 7000), (0, 3)], 4 * 1234),
}


@native_only
class TestWindowChunkPlan:
    @pytest.mark.parametrize("name", sorted(_PLANS))
    def test_chunks_tile_the_window(self, name):
        spans, row_bytes = _PLANS[name]
        segs, out_row = [], 0
        for i, (lo, hi) in enumerate(spans):
            segs.append((f"f{i}.tdas", lo, hi, out_row))
            out_row += hi - lo
        chunks = tdas.window_chunks(segs, row_bytes)
        covered = np.zeros(out_row, np.int64)
        for seg, lo, hi, o0 in chunks:
            _, s_lo, s_hi, s_o0 = segs[seg]
            assert s_lo <= lo < hi <= s_hi  # inside its segment
            assert o0 == s_o0 + (lo - s_lo)
            covered[o0 : o0 + hi - lo] += 1
            if hi != s_hi:  # only a segment's tail may be short
                assert hi - lo >= _CHUNK_ROWS
        assert (covered == 1).all()

    def test_chunk_count_follows_the_bytes(self):
        big = tdas.window_chunks(
            [("a", 0, 60000, 0), ("b", 0, 1, 60000)], 10000 * 2
        )
        assert len(big) > 8
        assert len(tdas.window_chunks([("a", 0, 1000, 0)], 500 * 2)) == 1
        # at the same rows, wider rows never mean fewer chunks
        counts = [
            len(tdas.window_chunks([("a", 0, 60000, 0)], w))
            for w in (1000, 8192, 40000, 400000)
        ]
        assert counts == sorted(counts) and counts[0] < counts[-1]

    @pytest.mark.parametrize("n_threads", [1, 3, 8, 64])
    def test_threads_bounded_by_chunks(self, wide_spool, n_threads):
        from tpudas.obs.trace import span

        payload, n_ch, paths = wide_spool
        segs, rows = _segments(paths, "three_files")
        with span("test.read") as rec:
            tdas.assemble_window(segs, 0, n_ch, rows, n_threads=n_threads)
        if rec is None:
            pytest.skip("spans are off")
        chunks = len(tdas.window_chunks(segs, n_ch * 4))
        assert rec["attrs"]["chunks"] == chunks
        assert 1 <= rec["attrs"]["threads"] <= min(n_threads, chunks)


@native_only
class TestChunkedAssemblyErrors:
    @pytest.mark.parametrize("n_threads", [1, 8])
    @pytest.mark.parametrize("fault", ["truncated", "removed"])
    @pytest.mark.parametrize("payload", ["int16", "float32"])
    def test_second_file_fault_raises(
        self, tmp_path, payload, fault, n_threads
    ):
        n_ch = _WIDTH[payload]
        paths = make_synthetic_spool(
            tmp_path, n_files=3, file_duration=2.5, fs=1000.0, n_ch=n_ch,
            noise=0.05, format="tdas",
            write_kwargs={"dtype": payload, "scale": 1e-3},
        )
        segs, rows = _segments(paths, "three_files")
        bad = paths[1]
        if fault == "removed":
            os.remove(bad)
        else:  # the header stays valid; the payload ends mid-chunk
            es = 2 if payload == "int16" else 4
            os.truncate(bad, 64 + 3000 // 2 * n_ch * es)
        hdr = tdas.read_tdas_header(paths[0])
        plan = {
            "segments": segs, "c_lo": 0, "c_hi": n_ch, "total_rows": rows,
            "t0_ns": hdr["t0_ns"] + 2000 * hdr["dt_ns"],
            "dt_ns": hdr["dt_ns"], "d0": hdr["d0"], "dx": hdr["dx"],
            "payload": payload, "scale": 1e-3 if payload == "int16" else None,
        }
        with pytest.raises(OSError):
            _within(60, tdas.assemble_window_patch, plan, n_threads=n_threads)
        with pytest.raises(OSError):
            _within(60, tdas.assemble_window, segs, 0, n_ch, rows,
                    n_threads=n_threads)
