"""CPU rehearsal of one cell at a tiny width: the same set-up, window,
check and metric readers as ``run.py``, with JAX on the CPU.  It prints
the check and what was counted, never a speed or device metric.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload batch-10k --seed 3
"""

import argparse
import json
import os
import sys

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--channels", type=int, default=16)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import harness

    result, _ = harness.run(args.workload, args.seed, args.seconds,
                            bool(args.trace), allow_cpu=True,
                            overrides={"channels": args.channels})
    print(json.dumps({
        "rehearsal": "cpu", "correct": result["correct"],
        "attempted": result["attempted"], "failed": result["failed"],
        "metrics_found": sorted(result["metrics"]),
        "checks": result["checks"],
    }))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
