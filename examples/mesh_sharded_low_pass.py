"""Mesh-sharded batch low-pass (BASELINE configs 4-5 made concrete).

The same LFProc workflow as examples/batch_low_pass.py, but every
per-window kernel runs over a (time, ch) device mesh: channels split
with zero communication; cascade-aligned windows also shard the time
axis with a one-sided ICI halo exchange. Output matches the
single-device run to a few f32 ulps (asserted below; channel-only
sharding is bit-identical).

On a v5e-8 use the real chips; anywhere else this demonstrates on
virtual CPU devices:

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/mesh_sharded_low_pass.py [--time-shards 2]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tempfile
import time

import numpy as np

import dascore as dc
from lf_das import LFProc
from tpudas.parallel.mesh import device_count, make_mesh
from tpudas.testing import make_synthetic_spool


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workdir", default=None)
    ap.add_argument(
        "--time-shards", type=int, default=None,
        help="explicit time shards (must divide the device count); "
        "default: 2 when the device count allows, else 1",
    )
    ap.add_argument("--fs", type=float, default=500.0)
    ap.add_argument("--n-ch", type=int, default=64)
    ap.add_argument(
        "--window-dp", action="store_true",
        help="batch windows over the mesh time axis (window-level "
        "data parallelism) instead of sharding inside each window",
    )
    args = ap.parse_args()

    n_dev = device_count()
    if args.time_shards is None:
        time_shards = 2 if n_dev >= 2 and n_dev % 2 == 0 else 1
    elif args.time_shards < 1 or n_dev % args.time_shards != 0:
        ap.error(
            f"--time-shards must be a positive divisor of the device "
            f"count ({n_dev}); got {args.time_shards}"
        )
    else:
        time_shards = args.time_shards
    mesh = make_mesh(n_dev, time_shards=time_shards)
    print(f"mesh: {dict(mesh.shape)} over {n_dev} devices")

    workdir = args.workdir or tempfile.mkdtemp(prefix="tpudas_mesh_")
    src = os.path.join(workdir, "raw")
    make_synthetic_spool(
        src, n_files=6, file_duration=30.0, fs=args.fs, n_ch=args.n_ch,
        noise=0.02, format="tdas",
    )
    sp = dc.spool(src).update().sort("time")
    t0 = np.datetime64("2023-03-22T00:00:00")
    t1 = t0 + np.timedelta64(180, "s")

    results = {}
    for label, m in (("single-device", None), ("mesh", mesh)):
        lfp = LFProc(sp, mesh=m)
        lfp.update_processing_parameter(
            output_sample_interval=1.0,
            process_patch_size=60,
            edge_buff_size=10,
            window_dp=bool(args.window_dp and m is not None),
        )
        out = os.path.join(workdir, label.replace("-", "_"))
        lfp.set_output_folder(out, delete_existing=True)
        w0 = time.perf_counter()
        lfp.process_time_range(t0, t1)
        wall = time.perf_counter() - w0
        merged = dc.spool(out).update().chunk(time=None)[0]
        results[label] = np.asarray(merged.data)
        print(
            f"{label:14s} {wall:6.2f}s  engines={lfp.engine_counts}  "
            f"timings={ {k: round(v, 3) for k, v in lfp.timings.items()} }"
        )

    from tpudas.testing import max_ulps

    ulps = max_ulps(results["mesh"], results["single-device"])
    assert ulps <= 4, f"sharded output diverged! ({ulps} ulps)"
    print(f"sharded output matches single-device ({ulps:.2f} ulps) ✓")
    print(f"outputs in {workdir}")


if __name__ == "__main__":
    main()
