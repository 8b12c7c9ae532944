"""Probe: Pallas pipeline read bandwidth vs block geometry.

The main-block DMA for a (ROWS, CB) block of a (T, 2048) f32 array
moves ROWS chunks of CB*4 contiguous bytes (row stride 8 KB).  Measures
how achieved HBM read bandwidth depends on chunk width and grid order.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

C = 2048
T = 129024  # 16128 * 8


sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from scan_harness import measure as _measure


def measure(fn, T, iters=96):
    return _measure(fn, T, C, iters)


def copy_kernel(rows, cb, k_fastest=False):
    """Read (rows, cb) blocks, emit head (rows//8, cb) rows."""
    nk = T // rows
    nc = C // cb
    out_rows = rows // 8

    def body(xm_ref, out_ref):
        out_ref[:] = xm_ref[:out_rows]

    if k_fastest:
        grid = (nc, nk)
        in_map = lambda c, k: (k, c)
        out_map = lambda c, k: (k, c)
    else:
        grid = (nk, nc)
        in_map = lambda k, c: (k, c)
        out_map = lambda k, c: (k, c)

    def fn(x):
        return pl.pallas_call(
            body,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (rows, cb), in_map, memory_space=pltpu.VMEM
                )
            ],
            out_specs=pl.BlockSpec(
                (out_rows, cb), out_map, memory_space=pltpu.VMEM
            ),
            out_shape=jax.ShapeDtypeStruct(
                (T // 8, C), jnp.float32
            ),
        )(x)

    return fn


def copy_kernel_pstream(P, rows, cb):
    """P parallel input streams — the v2 kernel's exact input pattern:
    each grid step reads P (rows, cb) blocks at consecutive k-indices
    through P separate inputs, so P auto-pipelined DMAs are in flight
    per step.  Pure copy (no compute): isolates whether multiple
    streams lift the ~185 GB/s single-stream wall toward the ~510 GB/s
    harness read ceiling — the central hypothesis behind v2's P=4
    design (PERF.md §5)."""
    nk = T // (rows * P)
    nc = C // cb
    out_rows = rows // 8
    # rows actually read per call: T may not divide by rows*P (e.g.
    # P=8 at T=129024), and crediting unread bytes would inflate
    # exactly the P-scaling comparison this probe exists to settle —
    # so the output is sized to the read coverage and the caller
    # reports bandwidth over t_eff, not T
    t_eff = nk * rows * P

    def body(*refs):
        mains = refs[:P]
        out_ref = refs[P]
        for j in range(P):
            out_ref[j * out_rows : (j + 1) * out_rows] = (
                mains[j][:out_rows]
            )

    def fn(x):
        return pl.pallas_call(
            body,
            grid=(nk, nc),
            in_specs=[
                pl.BlockSpec(
                    (rows, cb),
                    (lambda k, c, j=j: (k * P + j, c)),
                    memory_space=pltpu.VMEM,
                )
                for j in range(P)
            ],
            out_specs=pl.BlockSpec(
                (P * out_rows, cb),
                lambda k, c: (k, c),
                memory_space=pltpu.VMEM,
            ),
            out_shape=jax.ShapeDtypeStruct((t_eff // 8, C), jnp.float32),
        )(*([x] * P))

    return fn, t_eff


def main():
    for rows, cb, kf in [
        (1024, 128, False),
        (1024, 512, False),
        (1024, 1024, False),
        (512, 2048, False),
        (256, 2048, False),
        (1024, 2048, False),
        (2048, 2048, False),
        (1024, 128, True),
        (1024, 512, True),
    ]:
        try:
            dt = measure(copy_kernel(rows, cb, kf), T)
            gbps = T * C * 4 / dt / 1e9
            print(
                f"rows={rows:5d} cb={cb:5d} kfast={int(kf)}  "
                f"{dt * 1e3:7.3f} ms  {gbps:6.1f} GB/s "
                f"({gbps / 819 * 100:4.1f}%)",
                flush=True,
            )
        except Exception as exc:
            print(
                f"rows={rows} cb={cb} kfast={int(kf)}: {str(exc)[:120]}",
                flush=True,
            )

    # the P-stream question, isolated from all compute
    for P, rows, cb in [
        (1, 1024, 128),
        (2, 1024, 128),
        (4, 1024, 128),
        (8, 1024, 128),
        (4, 512, 128),
        (4, 1024, 256),
    ]:
        try:
            fn, t_eff = copy_kernel_pstream(P, rows, cb)
            dt = measure(fn, T)
            gbps = t_eff * C * 4 / dt / 1e9
            print(
                f"P={P} rows={rows:5d} cb={cb:4d}       "
                f"{dt * 1e3:7.3f} ms  {gbps:6.1f} GB/s "
                f"({gbps / 819 * 100:4.1f}%)",
                flush=True,
            )
        except Exception as exc:
            print(
                f"P={P} rows={rows} cb={cb}: {str(exc)[:120]}",
                flush=True,
            )


if __name__ == "__main__":
    main()
