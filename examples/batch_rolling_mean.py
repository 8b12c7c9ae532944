"""Batch rolling-mean workflow (reference: rolling_mean_dascore.ipynb).

Per-patch trailing-window mean decimation, NaN warm-up prefix handling
via dropna, merged result plot.

Run:  python examples/batch_rolling_mean.py [--workdir DIR] [--quick]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tempfile

import numpy as np

import dascore as dc
from dascore.units import s
from lf_das import _get_filename


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    workdir = args.workdir or tempfile.mkdtemp(prefix="tpudas_rolling_")
    data_path = os.path.join(workdir, "raw")
    output_data_folder = os.path.join(workdir, "results")
    os.makedirs(output_data_folder, exist_ok=True)

    fs = 200.0 if args.quick else 1000.0
    n_ch = 32 if args.quick else 256
    from tpudas.testing import make_synthetic_spool

    make_synthetic_spool(
        data_path, n_files=4, file_duration=30.0, fs=fs, n_ch=n_ch, noise=0.02
    )

    sp = dc.spool(data_path).sort("time").update()
    patch_0 = sp[0]
    gauge_length = patch_0.attrs["gauge_length"]
    sampling_interval = patch_0.attrs["d_time"]
    sampling_rate = 1 / (sampling_interval / np.timedelta64(1, "s"))

    d_t = 1.0
    window = d_t * s
    step = d_t * s
    scale_iDAS = float((116 * sampling_rate / gauge_length) / 1e9)

    for i, patch in enumerate(sp):
        print("working on patch ", i)
        rolling_mean_patch = patch.rolling(time=window, step=step).mean()
        new_scaled_patch = rolling_mean_patch.new(
            data=np.asarray(rolling_mean_patch.data) * scale_iDAS
        )
        filename = _get_filename(
            new_scaled_patch.attrs["time_min"], new_scaled_patch.attrs["time_max"]
        )
        new_scaled_patch.io.write(
            os.path.join(output_data_folder, filename), "dasdae"
        )

    rolling_spool = dc.spool(output_data_folder).chunk(time=None)
    merged = rolling_spool[0]
    no_nans = merged.dropna("time")
    print(
        f"merged {merged.data.shape} -> {no_nans.data.shape} after dropna "
        f"(NaN warm-up rows stripped)"
    )
    print("outputs in", output_data_folder)


if __name__ == "__main__":
    main()
