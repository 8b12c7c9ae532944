"""Real-time ("edge") rolling-mean workflow
(reference: rolling_mean_dascore_edge.ipynb).

Stateless per-file processing of newly appended interrogator files.

Run:  python examples/edge_rolling_mean.py [--workdir DIR]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tempfile
import threading
import time

import numpy as np

from dascore.units import s
from tpudas.proc.streaming import run_rolling_realtime
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

    workdir = args.workdir or tempfile.mkdtemp(prefix="tpudas_edge_roll_")
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
        # wait until round 1 has produced output before feeding more
        while not (
            os.path.isdir(output)
            and any(f.endswith(".h5") for f in os.listdir(output))
        ):
            time.sleep(0.5)
        for i in range(4, 4 + args.extra_files):
            time.sleep(2.0)
            p = synthetic_patch(
                t0=t0 + i * n * step, duration=file_sec, fs=fs, n_ch=n_ch,
                seed=i, phase_origin=t0, noise=0.01,
            )
            write_patch(p, os.path.join(data_path, f"raw_{i:04d}.h5"))
            print(f"[interrogator] wrote file {i}", flush=True)

    feeder = threading.Thread(target=interrogator, daemon=True)
    feeder.start()

    d_t = 1.0
    gauge_length = 10.0
    scale_iDAS = float((116 * fs / gauge_length) / 1e9)
    rounds = run_rolling_realtime(
        source=data_path,
        output_folder=output,
        window=d_t * s,
        step=d_t * s,
        scale=scale_iDAS,
        poll_interval=4.0,
        file_duration=file_sec,
    )
    feeder.join()
    print(f"done after {rounds} rounds; output in {output}")


if __name__ == "__main__":
    main()
