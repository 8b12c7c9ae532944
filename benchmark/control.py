"""The control: the reference put in the program's place, in bfloat16.

The configurations state float32 compute.  The nearest precision below
it is bfloat16, the step that would tempt a later change (one bf16
pass of a matrix unit).  The control computes each compared output as
the reference filter's impulse response (``reference.fir_response``)
applied to the stream, with stream and taps in bfloat16 and products
summed in float32 on the device, and is judged by the very comparison
a run makes (``reference.max_gap`` against the float64 ``sosfiltfilt``
at the same outputs, channels drawn from the same seed).  It also
prints the float64 form of the same sum, which must sit at rounding
(the FIR form is the reference, not an approximation of it).

    python3 benchmark/control.py --workload batch-10k --seeds 1,2,3
    python3 benchmark/control.py --workload edge-2048-60s --files 40 --seeds 1,2,3

A cell's limit lies between the program's widest reading over a dozen
seeds and the smallest reading printed here (PERF.md gives both).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def readings(workload, seed, n_files=None, channels=None, bench=None):
    """(control gap, float64 FIR-form gap, outputs compared) for one
    seed, over the outputs a run of ``workload`` compares."""
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    import harness
    import reference as ref
    import traffic as tr

    spec = harness.load_cell(ROOT, workload, bench)
    cfg, mix = spec["cfg"], spec["mix"]
    if channels:
        cfg["channels"] = channels
    rows, g = tr.file_rows(cfg, mix), int(cfg["output_sample_interval_s"] * 1e9)
    if cfg["driver"] == "batch":
        n_files = int(cfg["archive_seconds"] // mix["file_seconds"])
    head = tr.T0_NS + n_files * rows * tr.dt_ns(cfg)
    m = int(ref.MARGIN_S * 1e9)
    times = np.arange(tr.T0_NS + m, head - m + 1, g)
    cols = harness.check_cols(seed, cfg["channels"])
    stream = tr.stream_columns(cfg, mix, seed, n_files, cols)
    idx = (times - tr.T0_NS) // tr.dt_ns(cfg)
    want = ref.reference(cfg, stream, idx)
    h = ref.fir_response(cfg)
    scale = float(np.float32(cfg["payload_scale"]))
    got = ref.fir_outputs(stream, idx, h, scale, "bfloat16")
    exact = ref.fir_outputs(stream, idx, h, scale, "float64")
    return ref.max_gap(got, want), ref.max_gap(exact, want), int(idx.size)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--files", type=int, default=None,
                    help="edge cells: files delivered in a run")
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    for seed in (int(s) for s in args.seeds.split(",")):
        gap, exact, n = readings(args.workload, seed, args.files)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_gap": gap,
                          "fir_float64_gap": exact, "outputs": n,
                          "device": {"platform": dev.platform,
                                     "kind": dev.device_kind}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
