"""Joint low-pass + rolling-mean workflow (BASELINE config 5).

The reference computes these as two separate passes over the spool
(low_pass_dascore.ipynb + rolling_mean_dascore.ipynb); JointProc emits
both products from ONE ingest pass, with the rolling product seam-free
across chunk boundaries. At multi-well scale the spool read + H2D
dominate, which is the whole point of sharing the pass.

Run:  python examples/joint_low_pass_rolling.py [--workdir DIR] [--quick]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tempfile
import time

import numpy as np

import dascore as dc
from tpudas.proc.joint import JointProc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--quick", action="store_true", help="small spool")
    ap.add_argument("--fs", type=float, default=None)
    ap.add_argument("--n-ch", type=int, default=None)
    args = ap.parse_args()

    workdir = args.workdir or tempfile.mkdtemp(prefix="tpudas_joint_")
    data_path = os.path.join(workdir, "raw")
    lf_folder = os.path.join(workdir, "results_lf")
    roll_folder = os.path.join(workdir, "results_rolling")

    fs = args.fs or (100.0 if args.quick else 500.0)
    n_ch = args.n_ch or (16 if args.quick else 512)
    n_files = 4 if args.quick else 8
    from tpudas.testing import make_synthetic_spool

    make_synthetic_spool(
        data_path, n_files=n_files, file_duration=30.0, fs=fs,
        n_ch=n_ch, noise=0.02, format="tdas",
        write_kwargs={"dtype": "int16", "scale": 1e-3},
    )

    sp = dc.spool(data_path).sort("time").update()
    df = sp.get_contents()
    t1 = np.datetime64(df["time_min"].min())
    t2 = np.datetime64(df["time_max"].max())

    jp = JointProc(sp)
    jp.update_processing_parameter(
        output_sample_interval=1.0,
        process_patch_size=60,
        edge_buff_size=10,
        rolling_window=5.0,
        rolling_step=1.0,
    )
    jp.set_output_folder(lf_folder, delete_existing=True)
    jp.set_rolling_output_folder(roll_folder, delete_existing=True)

    tic = time.time()
    jp.process_time_range(t1, t2)
    wall = time.time() - tic
    n_win = sum(jp.engine_counts.values())
    print(
        f"{n_win} windows, {jp.rolling_windows} rolling files in "
        f"{wall:.2f}s ({(t2 - t1) / np.timedelta64(1, 's') / wall:.1f}x "
        "real time, both products)"
    )

    for name, folder in (("low-pass", lf_folder), ("rolling", roll_folder)):
        merged = dc.spool(folder).update().chunk(time=None)
        assert len(merged) == 1, f"{name} product is not contiguous"
        p = merged[0]
        print(
            f"{name}: {p.shape} from {p.attrs['time_min']} to "
            f"{p.attrs['time_max']}"
        )
    print(f"outputs in {workdir}")


if __name__ == "__main__":
    main()
