"""Run one benchmark cell once on the accelerator this machine holds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``); the numbers compared with the
reference, each beside its limit, are the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.  ``benchmark/rehearse.py`` is the CPU
rehearsal at tiny sizes.
"""

import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import harness

    sys.exit(harness.main())
