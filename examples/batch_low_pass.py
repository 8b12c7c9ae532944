"""Batch low-pass workflow (reference: low_pass_dascore.ipynb).

End-to-end: synthetic interrogator spool → metadata → memory-model
chunk sizing → edge calibration → LFProc overlap-save processing →
merge → QC waterfall + median-filtered waterfall.

Run:  python examples/batch_low_pass.py [--workdir DIR] [--quick]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tempfile
import time

import numpy as np

import dascore as dc
from lf_das import LFProc, get_edge_effect_time, get_patch_time, waterfall_plot


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--quick", action="store_true", help="small spool")
    ap.add_argument("--fs", type=float, default=None)
    ap.add_argument("--n-ch", type=int, default=None)
    args = ap.parse_args()

    workdir = args.workdir or tempfile.mkdtemp(prefix="tpudas_batch_")
    data_path = os.path.join(workdir, "raw")
    output_data_folder = os.path.join(workdir, "results")
    output_figure_folder = os.path.join(workdir, "figures")
    os.makedirs(output_figure_folder, exist_ok=True)

    fs = args.fs or (200.0 if args.quick else 1000.0)
    n_ch = args.n_ch or (32 if args.quick else 256)
    n_files = 4 if args.quick else 8
    from tpudas.testing import make_synthetic_spool

    make_synthetic_spool(
        data_path, n_files=n_files, file_duration=30.0, fs=fs, n_ch=n_ch,
        noise=0.02,
    )

    # --- the notebook flow ---
    sp = dc.spool(data_path).sort("time").update()
    print(sp.get_contents().head().to_string())

    patch_0 = sp[0]
    gauge_length = patch_0.attrs["gauge_length"]
    sampling_interval = patch_0.attrs["time_step"]
    sampling_rate = 1 / (sampling_interval / np.timedelta64(1, "s"))

    d_t = 1.0
    memory_size = 2000  # MB
    patch_length = get_patch_time(
        memory_size=memory_size, sampling_rate=sampling_rate, num_ch=n_ch
    )
    patch_length = min(patch_length, n_files * 30.0)
    edge_buffer = get_edge_effect_time(
        sampling_interval=1 / sampling_rate,
        total_T=patch_length,
        tol=1e-3,
        freq=1 / d_t,
    )
    print(f"patch_length={patch_length:.1f}s edge_buffer={edge_buffer:.2f}s")

    lfp = LFProc(sp)
    lfp.update_processing_parameter(
        output_sample_interval=d_t,
        process_patch_size=int(patch_length / d_t),
        edge_buff_size=int(np.ceil(edge_buffer / d_t)),
    )
    lfp.set_output_folder(output_data_folder, delete_existing=True)

    t_1 = np.datetime64("2023-03-22T00:00:00")
    t_2 = t_1 + np.timedelta64(int(n_files * 30), "s")
    tic = time.time()
    lfp.process_time_range(t_1, t_2)
    toc = time.time()
    data_sec = n_files * 30.0
    print(
        f"processing time (sec): {toc - tic:.2f} "
        f"({data_sec:.0f} s x {n_ch} ch -> {data_sec / (toc - tic):.1f}x real time)"
    )

    sp_result = dc.spool(output_data_folder).chunk(time=None)
    result = sp_result[0]
    print("merged result:", result.data.shape)

    # QC: strain-rate scaling + waterfall (+ median-filtered version)
    scale_iDAS = float((116 * sampling_rate / gauge_length) / 1e9)
    scaled = np.asarray(result.data) * scale_iDAS
    waterfall_plot(
        scaled.T, 0, scaled.shape[0] - 1, 0, scaled.shape[1], 0, 5.0, 0.0,
        1 / d_t, "tpudas low-freq DAS", output_figure_folder, "low_freq_raster",
    )
    despiked = result.median_filter(size=5, dim="time")
    waterfall_plot(
        (np.asarray(despiked.data) * scale_iDAS).T, 0, scaled.shape[0] - 1, 0,
        scaled.shape[1], 0, 5.0, 0.0, 1 / d_t,
        "tpudas low-freq DAS (median filtered)", output_figure_folder,
        "low_freq_raster_median",
    )
    print("figures in", output_figure_folder)
    print("outputs in", output_data_folder)


if __name__ == "__main__":
    main()
