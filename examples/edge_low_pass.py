"""Real-time ("edge") low-pass workflow
(reference: low_pass_dascore_edge.ipynb).

A simulated interrogator appends files while the polling loop keeps the
low-frequency output current; kill and re-run to see crash-only resume.

Run:  python examples/edge_low_pass.py [--workdir DIR]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tempfile
import threading
import time

import numpy as np

from lf_das import get_edge_effect_time
from tpudas.proc.streaming import run_lowpass_realtime
from tpudas.testing import make_synthetic_spool, synthetic_patch
from tpudas.io.registry import write_patch
from tpudas.core.timeutils import to_datetime64


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--fs", type=float, default=250.0)
    ap.add_argument("--n-ch", type=int, default=64)
    ap.add_argument("--extra-files", type=int, default=4)
    args = ap.parse_args()

    workdir = args.workdir or tempfile.mkdtemp(prefix="tpudas_edge_")
    data_path = os.path.join(workdir, "raw")
    output = os.path.join(workdir, "results")
    fs, n_ch, file_sec = args.fs, args.n_ch, 30.0

    make_synthetic_spool(
        data_path, n_files=4, file_duration=file_sec, fs=fs, n_ch=n_ch,
        noise=0.01,
    )

    def interrogator():
        t0 = to_datetime64("2023-03-22T00:00:00").astype("datetime64[ns]")
        step = np.timedelta64(int(round(1e9 / fs)), "ns")
        n = int(file_sec * fs)
        # wait until round 1 has produced output (first-round jit
        # compile would otherwise swallow the whole feed)
        while not (
            os.path.isdir(output)
            and any(f.endswith(".h5") for f in os.listdir(output))
        ):
            time.sleep(0.5)
        for i in range(4, 4 + args.extra_files):
            time.sleep(3.0)
            p = synthetic_patch(
                t0=t0 + i * n * step, duration=file_sec, fs=fs, n_ch=n_ch,
                seed=i, phase_origin=t0, noise=0.01,
            )
            write_patch(p, os.path.join(data_path, f"raw_{i:04d}.h5"))
            print(f"[interrogator] wrote file {i}", flush=True)

    feeder = threading.Thread(target=interrogator, daemon=True)
    feeder.start()

    d_t = 1.0
    edge_buffer = get_edge_effect_time(
        sampling_interval=1 / fs, total_T=60.0, tol=1e-3, freq=1 / d_t
    )
    rounds = run_lowpass_realtime(
        source=data_path,
        output_folder=output,
        start_time="2023-03-22T00:00:00",
        output_sample_interval=d_t,
        edge_buffer=edge_buffer,
        process_patch_size=60,
        poll_interval=5.0,  # demo cadence; production uses >=125 s
        file_duration=0.0,
    )
    feeder.join()
    print(f"done after {rounds} rounds; output in {output}")


if __name__ == "__main__":
    main()
