"""Per-round compute bench for the fused streaming cascade (ISSUE 10)
-> BENCH_pr10.json.

Times the carry-threaded STREAM STEP — the unit one realtime round
dispatches per block — across interrogator widths (256 / 2048 / 10000
channels) and block sizes, for every engine in the stream dispatch
matrix:

- ``cascade``: the per-stage chain (each stage materializes its
  full-rate intermediate before the next consumes it);
- ``fused-xla``: the lax.scan formulation (all stage states threaded
  through one jitted step; intermediates exist only at chunk size);
- ``fused-pallas``: the v3 VMEM-resident kernel — interpret mode off
  TPU, so off-TPU it is benched only at the smallest width as a
  correctness-shaped data point, clearly flagged (interpret-mode times
  say nothing about silicon).

Headline counters come from the obs registry (``use_registry`` scope:
``tpudas_fir_fused_rounds_total`` proves the fused path really ran,
``tpudas_fir_fused_intermediate_bytes_saved_total`` is the HBM-traffic
proxy — the per-stage intermediate bytes the fused path never
materializes, re-read traffic excluded).  Equivalence is asserted in
the run: fused-xla output and carry within ``FUSED_XLA_ULPS`` f32
ulps of their scale of the cascade chain on a verification feed
(whether they are byte-identical is recorded: on the installed XLA
CPU backend the last block's final stage differs, by 0.02 ulps of the
output scale and 1.18 ulps of that stage's carry scale, so the scan
does not reproduce the chain bit for bit on every block shape).

    JAX_PLATFORMS=cpu python tools/kernel_bench.py [--out BENCH_pr10.json]
        [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tpudas.obs.registry import MetricsRegistry, use_registry  # noqa: E402
from tpudas.ops.fir import (  # noqa: E402
    cascade_decimate_stream,
    cascade_stream_init,
    design_cascade,
    fused_chunk_outputs,
    fused_intermediate_bytes,
    fused_min_elems,
)

# the flagship workload: 1 kHz interrogator -> 1 Hz low-frequency
FS_IN = 1000.0
RATIO = 1000
CHANNELS = (256, 2048, 10000)
BLOCKS = (16, 64)  # output samples per stream step
TARGET_10K = 1.3  # acceptance: fused >= 1.3x at 10k ch
# fused-xla vs the chain: a few f32 ulps of the output (and each carry
# leaf's) scale, as sharded runs are held to the single-device run
FUSED_XLA_ULPS = 4


def _measure(plan, n_out, C, engine, iters):
    """Best-of wall seconds per carry-threaded step, measured warm
    (compile excluded), carry fed back each round — through the REAL
    dispatch surface (cascade_decimate_stream), so the obs counters
    the report cites witness exactly the measured rounds."""
    T = n_out * plan.ratio
    carry = cascade_stream_init(plan, C)
    rng = np.random.default_rng(0)
    x_host = rng.standard_normal((T, C)).astype(np.float32)
    # the step donates its input on accelerator backends — there a
    # fresh device buffer is required per round; on CPU (no donation)
    # the block is reused, as the realtime driver's pool slices are
    donating = jax.default_backend() not in ("cpu",)
    x = jnp.asarray(x_host)
    y, carry = cascade_decimate_stream(x, carry, plan, engine)
    jax.block_until_ready(y)
    best = 1e30
    for _ in range(iters):
        if donating:
            x = jnp.asarray(x_host)
        t0 = time.perf_counter()
        y, carry = cascade_decimate_stream(x, carry, plan, engine)
        jax.block_until_ready(y)
        best = min(best, time.perf_counter() - t0)
    return best


def _equivalence(plan, n_ch=8) -> dict:
    """fused-xla vs cascade on a multi-block feed (ulps, and whether
    byte-identical), and the fused-pallas interpret tolerance —
    recorded, not just claimed (tests/test_fused.py pins byte-identity
    on its own feeds)."""
    from tpudas.testing import max_ulps

    rng = np.random.default_rng(7)
    blocks = [
        rng.standard_normal((n * plan.ratio, n_ch)).astype(np.float32)
        for n in (16, 5, 11)
    ]

    def run(engine):
        carry = cascade_stream_init(plan, n_ch)
        outs = []
        for b in blocks:
            y, carry = cascade_decimate_stream(b, carry, plan, engine)
            outs.append(np.asarray(y))
        return np.concatenate(outs), tuple(np.asarray(c) for c in carry)

    y0, c0 = run("xla")
    y1, c1 = run("fused-xla")
    out_eq = bool(np.array_equal(y0, y1))
    carry_eq = all(np.array_equal(a, b) for a, b in zip(c0, c1))
    ulps = max([max_ulps(y1, y0)]
               + [max_ulps(b, a) for a, b in zip(c0, c1) if a.size])
    y2, c2 = run("fused-pallas")
    scale = float(np.abs(y0).max())
    pallas_rel = float(np.abs(y0 - y2).max() / scale)
    return {
        "fused_xla_output_byte_identical": out_eq,
        "fused_xla_carry_byte_identical": bool(carry_eq),
        "fused_xla_max_ulps": ulps,
        "fused_xla_ulps_pinned": FUSED_XLA_ULPS,
        "fused_pallas_rel_err": pallas_rel,
        "fused_pallas_tolerance_pinned": 5e-7,
    }


def run(out_path, quick=False) -> dict:
    backend = jax.default_backend()
    plan = design_cascade(FS_IN, RATIO, 0.45, 4)
    on_tpu = backend == "tpu"
    channels = CHANNELS if not quick else (256,)
    blocks = BLOCKS if not quick else (16,)
    iters = 4 if quick else 6
    sweep = []
    for C in channels:
        for n_out in blocks:
            T = n_out * plan.ratio
            engines = ["cascade", "fused-xla"]
            # off-TPU the v3 kernel runs interpret mode: time it only
            # at the smallest point, flagged — interpret wall time is
            # not a kernel statement
            if on_tpu or (C == min(channels) and n_out == min(blocks)):
                engines.append("fused-pallas")
            point = {
                "n_ch": C,
                "n_out": n_out,
                "rows": T,
                "elems": T * C,
                "chunk_out": fused_chunk_outputs(plan, n_out),
                "engines": {},
            }
            for eng in engines:
                reg = MetricsRegistry()
                real = "xla" if eng == "cascade" else eng
                with use_registry(reg):
                    dt = _measure(plan, n_out, C, real, iters)
                rec = {
                    "seconds_per_round": dt,
                    "channel_samples_per_sec": T * C / dt,
                    "interpret_mode": bool(
                        eng == "fused-pallas" and not on_tpu
                    ),
                }
                if eng != "cascade":
                    # the registry is the witness the fused path ran
                    # and the HBM-traffic proxy source
                    rec["fused_rounds"] = reg.value(
                        "tpudas_fir_fused_rounds_total", engine=real
                    )
                    rec["intermediate_bytes_saved_per_round"] = (
                        fused_intermediate_bytes(plan, T, C)
                    )
                else:
                    rec["intermediate_bytes_per_round"] = (
                        fused_intermediate_bytes(plan, T, C)
                    )
                point["engines"][eng] = rec
                print(
                    f"kernel_bench: C={C} n_out={n_out} {eng}: "
                    f"{dt * 1e3:.2f} ms/round"
                    + (" (interpret)" if rec["interpret_mode"] else ""),
                    flush=True,
                )
            cas = point["engines"]["cascade"]["seconds_per_round"]
            fx = point["engines"]["fused-xla"]["seconds_per_round"]
            point["speedup_fused_xla"] = cas / fx
            sweep.append(point)
    big = [p for p in sweep if p["n_ch"] >= 2048]
    ten_k = [p for p in sweep if p["n_ch"] >= 10000]
    acceptance = {
        # None when the sweep did not reach the width (--quick)
        "fused_beats_cascade_at_2048plus": (
            all(p["speedup_fused_xla"] > 1.0 for p in big)
            if big else None
        ),
        "best_speedup_10k": max(
            (p["speedup_fused_xla"] for p in ten_k), default=None
        ),
        "target_speedup_10k": TARGET_10K,
        "equivalence": _equivalence(plan),
        # structural: the fused scan's largest live intermediate is
        # one CHUNK, never the block — zero per-stage full-rate HBM
        # intermediates by construction
        "fused_max_live_intermediate_rows": (
            max(p["chunk_out"] for p in sweep) * plan.ratio
        ),
    }
    report = {
        "bench": "kernel_bench (ISSUE 10 fused streaming cascade)",
        "backend": backend,
        "host_cpus": os.cpu_count(),
        "plan": {
            "fs_in": FS_IN,
            "ratio": RATIO,
            "stages": [[int(R), len(h)] for R, h in plan.stages],
        },
        "fused_min_elems": fused_min_elems(),
        "headline_source": "tpudas.obs.registry",
        "sweep": sweep,
        "acceptance": acceptance,
    }
    ok = acceptance["fused_beats_cascade_at_2048plus"] is not False and (
        not ten_k or acceptance["best_speedup_10k"] >= TARGET_10K
    )
    eq = acceptance["equivalence"]
    ok = ok and eq["fused_xla_max_ulps"] <= eq["fused_xla_ulps_pinned"]
    ok = ok and eq["fused_pallas_rel_err"] <= eq[
        "fused_pallas_tolerance_pinned"
    ]
    report["ok"] = bool(ok)
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(report, fh, indent=1)
        print(f"kernel_bench: wrote {out_path}")
    print(f"kernel_bench: {'OK' if ok else 'FAILED'}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_pr10.json"))
    ap.add_argument(
        "--quick", action="store_true",
        help="smallest width only (the tier-1 smoke)",
    )
    args = ap.parse_args(argv)
    report = run(args.out, quick=args.quick)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
