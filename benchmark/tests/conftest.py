import os
import sys

# four CPU devices, so the mesh cell rehearses on its own layout; set
# before anything imports JAX
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def with_held_out() -> dict:
    """``BENCHMARK.json`` with the cells held out of it
    (``held_out.json``: the cascade cells the program's aliasing keeps
    out, PERF.md section 7), so their drivers and readers still
    rehearse."""
    import json

    import harness

    bench = harness.load_bench(ROOT)
    with open(os.path.join(BENCH, "tests", "held_out.json")) as fh:
        held = json.load(fh)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] = bench[key] + held[key]
    return bench
