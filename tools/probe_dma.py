"""Probe: manual-DMA read bandwidth with N outstanding copies.

The auto-pipelined Pallas grid reads ~185 GB/s regardless of block
geometry (probe_pipeline.py) while an XLA reduce reads ~510 GB/s on the
same array.  Hypothesis: one-deep DMA lookahead can't cover HBM
latency; issuing several async copies concurrently should close the
gap.  Single grid step, fori_loop over chunks, NBUF slots with NBUF-1
outstanding DMAs.

WARNING: in the round-5 session manual ``pltpu.make_async_copy``
kernels hung, even a single static HBM->VMEM copy and even under
``interpret=True`` on CPU.  Not re-run on today's chip or JAX; a hang
there costs a chip call.  The product kernel achieves multi-stream DMA
within the supported auto-pipeline instead: P main-block inputs per
grid step (tpudas.ops.pallas_fir).
"""

from __future__ import annotations

import functools
import os
import sys

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

C = 2048
T = 129024


sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from scan_harness import measure as _measure


def measure(fn, T, iters=96):
    return _measure(fn, T, C, iters)


def manual_reader(rows, nbuf):
    n = T // rows

    def body(x_hbm, out_ref, buf, sems):
        def start(i):
            slot = lax.rem(i, nbuf)
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(i * rows, rows), :],
                buf.at[slot],
                sems.at[slot],
            ).start()

        def wait(i):
            slot = lax.rem(i, nbuf)
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(i * rows, rows), :],
                buf.at[slot],
                sems.at[slot],
            ).wait()

        for i in range(min(nbuf - 1, n)):
            start(jnp.int32(i))

        def loop(i, acc):
            @pl.when(i + nbuf - 1 < n)
            def _():
                start(i + nbuf - 1)

            wait(i)
            slot = lax.rem(i, nbuf)
            return acc + jnp.sum(buf[slot, 0, :])

        acc = lax.fori_loop(0, n, loop, jnp.float32(0.0))
        out_ref[0, 0] = acc

    @functools.partial(jax.jit)
    def fn(x):
        return pl.pallas_call(
            body,
            in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
            out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((nbuf, rows, C), jnp.float32),
                pltpu.SemaphoreType.DMA((nbuf,)),
            ],
        )(x)

    return fn


def main():
    for rows, nbuf in [
        (512, 2),
        (256, 2),
        (256, 4),
        (128, 4),
        (128, 8),
        (64, 8),
        (512, 4),
        (256, 8),
    ]:
        try:
            dt = measure(manual_reader(rows, nbuf), T)
            gbps = T * C * 4 / dt / 1e9
            print(
                f"rows={rows:4d} nbuf={nbuf}  {dt * 1e3:7.3f} ms  "
                f"{gbps:6.1f} GB/s ({gbps / 819 * 100:4.1f}%)",
                flush=True,
            )
        except Exception as exc:
            print(f"rows={rows} nbuf={nbuf}: {str(exc)[:140]}", flush=True)


if __name__ == "__main__":
    main()
