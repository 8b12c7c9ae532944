"""The XLA polyphase stages at three einsum precisions, on the chip.

For each stage of the flagship plan (1 kHz -> 1 Hz, R = 8/5/5/5) at a
batch window's shape, runs ``tpudas.ops.fir._polyphase_stage_xla``
at "default", "high" and "highest" and reports the error against a
float64 host chain (each stage fed the previous stage's device output,
so the last stage's error is the whole chain's) and the best-of-10
warm wall time of each stage and of the jitted XLA chain.  This is the
measurement behind ``fir._XLA_STAGE_PRECISION``.

Run: python tools/xla_precision_probe.py   (on a TPU; exits 1 without)
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

PRECISIONS = ("default", "high", "highest")


def _ref_stage(x64, hb, R, n_out):
    B = hb.shape[0]
    need = (n_out + B) * R
    if need > x64.shape[0]:
        x64 = np.pad(x64, ((0, need - x64.shape[0]), (0, 0)))
    xr = x64[:need].reshape(n_out + B, R, x64.shape[1])
    u = np.einsum("mrc,br->bmc", xr, hb.astype(np.float64))
    return sum(u[b, b : b + n_out] for b in range(B))


def _best(f, x, n=10):
    import jax

    y = f(x)
    jax.block_until_ready(y)
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        y = f(x)
        jax.block_until_ready(y)
        best = min(best, time.perf_counter() - t0)
    return y, best


def probe(T=80000, C=2048, C_err=128, seed=0) -> list:
    """One row per (precision, stage) and per (precision, chain)."""
    import jax
    import jax.numpy as jnp

    from tpudas.ops import fir
    from tpudas.proc.lfproc import output_corner

    plan = fir.design_cascade(1000.0, 1000, output_corner(1.0), 4)
    blocked = fir._blocked_taps(plan)
    x_full = np.random.default_rng(seed).standard_normal((T, C))
    x_full = x_full.astype(np.float32)
    kept = fir._XLA_STAGE_PRECISION
    rows = []
    try:
        for prec in PRECISIONS:
            fir._XLA_STAGE_PRECISION = prec
            jax.clear_caches()
            fir._clear_cascade_caches()
            x, x64 = x_full, x_full[:, :C_err].astype(np.float64)
            for i, (R, hb) in enumerate(blocked):
                n_out = x.shape[0] // R - hb.shape[0]
                f = jax.jit(
                    lambda v, hb=hb, R=R, n=n_out:
                    fir._polyphase_stage_xla(v, hb, R, n)
                )
                y, dt = _best(f, jnp.asarray(x))
                y = np.asarray(y)
                ref = _ref_stage(x64, hb, R, n_out)
                err = np.abs(y[:, :C_err] - ref).max() / np.abs(ref).max()
                rows.append({
                    "precision": prec, "stage": i, "R": int(R),
                    "rows_in": int(x.shape[0]), "n_out": int(n_out),
                    "chain_rel_err": float(err), "seconds": dt,
                })
                x, x64 = y, ref
            n_out = (T - 2 * plan.delay) // plan.ratio
            _, dt = _best(
                lambda v: fir.cascade_decimate(
                    v, plan, plan.delay, n_out, "xla"),
                jnp.asarray(x_full),
            )
            rows.append({"precision": prec, "stage": "chain",
                         "n_out": int(n_out), "seconds": dt})
    finally:
        fir._XLA_STAGE_PRECISION = kept
        jax.clear_caches()
        fir._clear_cascade_caches()
    return rows


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"xla_precision_probe: no TPU ({dev.platform})",
              file=sys.stderr)
        return 1
    rows = probe()
    for r in rows:
        print(json.dumps(r))
    print(json.dumps({"device": dev.device_kind, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
