"""Quantized (int16) tdas ingest: the realistic edge-interrogator path.

Interrogators commonly emit 16-bit samples; tdas stores them raw with a
quantization scale. The engine then keeps the payload int16 through the
whole ingest pipeline — native C++ window assembly, the prefetch
thread's staged H2D transfer, and the sharded halo exchange all move
half the bytes — and dequantizes INSIDE the first device kernel (Pallas
in-VMEM cast, or an XLA-fused cast*scale). The decoded results are
byte-identical to writing float32 and processing that (asserted below;
the quantization itself, 1e-3 here, is the only loss and happens at
write time).

Run:  python examples/quantized_ingest.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tempfile
import time

import numpy as np

import dascore as dc
from lf_das import LFProc
from tpudas.io.spool import MemorySpool
from tpudas.testing import make_synthetic_spool


def main():
    workdir = tempfile.mkdtemp(prefix="tpudas_quant_")
    src = os.path.join(workdir, "raw_q")
    make_synthetic_spool(
        src, n_files=6, file_duration=30.0, fs=500.0, n_ch=64,
        noise=0.02, format="tdas",
        write_kwargs={"dtype": "int16", "scale": 1e-3},
    )
    q_bytes = sum(
        os.path.getsize(os.path.join(src, f)) for f in os.listdir(src)
    )
    print(f"quantized spool: {q_bytes / 1e6:.1f} MB on disk (int16)")

    t0 = np.datetime64("2023-03-22T00:00:00")
    t1 = t0 + np.timedelta64(180, "s")
    results = {}
    for label, sp in (
        # device path: raw int16 assembly, in-kernel dequantize
        ("device-decode", dc.spool(src).update().sort("time")),
        # host path: the reader decodes to f32 before the engine
        ("host-decode", MemorySpool(list(dc.spool(src).update().sort("time")))),
    ):
        lfp = LFProc(sp)
        lfp.update_processing_parameter(
            output_sample_interval=1.0,
            process_patch_size=60,
            edge_buff_size=10,
        )
        out = os.path.join(workdir, label.replace("-", "_"))
        lfp.set_output_folder(out, delete_existing=True)
        w0 = time.perf_counter()
        lfp.process_time_range(t0, t1)
        wall = time.perf_counter() - w0
        merged = dc.spool(out).update().chunk(time=None)[0]
        results[label] = np.asarray(merged.data)
        print(
            f"{label:14s} {wall:6.2f}s  native_windows={lfp.native_windows}  "
            f"engines={lfp.engine_counts}"
        )

    assert np.array_equal(
        results["device-decode"], results["host-decode"]
    ), "device decode diverged from host decode!"
    print("in-kernel dequantize is byte-identical to host decode ✓")
    print(f"outputs in {workdir}")


if __name__ == "__main__":
    main()
