"""Measure the HOST half of the e2e path at north-star width — no TPU.

The 10k-channel e2e breakdown has two independent
halves: the C++ windowed assembly (tdas index -> threaded read ->
merged window) and the device cascade.  The device half is measured by
bench.py on the chip; this tool measures the assembly half on whatever
host it runs on, so the bottleneck table in PERF.md §6 can be filled
in without a chip.

Methodology: synthesize an int16 tdas spool at (HAR_FS, HAR_C) for
HAR_SEC seconds of stream, then assemble the same overlap-save windows
LFProc would schedule (HAR_PATCH patch + 2*HAR_EDGE halo) and report
channel-samples/sec and MB/s of assembled window bytes.  Synthesis is
excluded from the timed region.  NOTE the host core count in the
output: the assembler is thread-parallel, so single-digit-core dev
boxes report a lower bound.

Run: python tools/host_assembly_rate.py
Env: HAR_C (10000), HAR_SEC (60), HAR_FS (1000), HAR_PATCH (60),
     HAR_EDGE (10), HAR_DTYPE (int16)
"""

from __future__ import annotations

import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, ".")


def main() -> None:
    C = int(os.environ.get("HAR_C", 10000))
    sec = int(os.environ.get("HAR_SEC", 60))
    fs = float(os.environ.get("HAR_FS", 1000.0))
    patch = float(os.environ.get("HAR_PATCH", 60.0))
    edge = float(os.environ.get("HAR_EDGE", 10.0))
    dtype = os.environ.get("HAR_DTYPE", "int16")

    from tpudas import spool as make_spool
    from tpudas.io.tdas import assemble_window_patch
    from tpudas.native import load_streamio
    from tpudas.testing import make_synthetic_spool

    native = load_streamio() is not None
    ncpu = os.cpu_count() or 1
    print(f"host: {ncpu} cores, native streamio: {native}", flush=True)

    file_sec = 30.0
    n_files = max(1, round(sec / file_sec))
    sec = n_files * file_sec
    wk = {"dtype": "int16", "scale": 1e-3} if dtype == "int16" else None
    with tempfile.TemporaryDirectory() as td:
        t0 = time.perf_counter()
        make_synthetic_spool(
            td, n_files=n_files, file_duration=file_sec, fs=fs, n_ch=C,
            noise=0.01, lf_freq=0.05, format="tdas", write_kwargs=wk,
        )
        print(f"synthesized {sec:.0f}s x {C}ch @ {fs:.0f}Hz {dtype} in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        sp = make_spool(td).sort("time").update()
        frame = sp.get_contents()
        t_start = frame["time_min"].min()
        t_end = frame["time_max"].max()

        window = patch + 2 * edge
        starts = []
        t = t_start
        while t < t_end:
            starts.append(t)
            t = t + np.timedelta64(int(patch * 1e9), "ns")

        total_rows = 0
        total_bytes = 0
        w0 = time.perf_counter()
        for s in starts:
            e = s + np.timedelta64(int(window * 1e9), "ns")
            plan = sp.native_window_plan(s, min(e, t_end))
            assert plan is not None, "native fast path did not apply"
            p = assemble_window_patch(plan)
            total_rows += p.data.shape[0]
            total_bytes += p.data.nbytes
        elapsed = time.perf_counter() - w0

    rate = total_rows * C / elapsed
    print(
        f"assembled {len(starts)} windows ({total_rows} rows, "
        f"{total_bytes / 1e9:.2f} GB f32-out) in {elapsed:.2f}s",
        flush=True,
    )
    print(
        f"host assembly rate: {rate / 1e9:.2f} G ch-samp/s  "
        f"({total_bytes / elapsed / 1e9:.2f} GB/s out)  "
        f"[{ncpu} cores, {dtype} payload]",
        flush=True,
    )
    # realtime factor of the ASSEMBLY phase alone at this (fs, C)
    print(
        f"assembly-alone realtime factor @ {C}ch/{fs:.0f}Hz: "
        f"{rate / (fs * C):.2f}x",
        flush=True,
    )


if __name__ == "__main__":
    main()
