"""Stage-0 kernel harness: measure the product Pallas FIR on the chip.

The flagship cascade's first stage (R=8 guard FIR at full rate)
carries ~85% of the window's HBM traffic, so it is the tuning target.
This harness measures, under bench.py's resident scan methodology:

  read-ceiling    jnp.sum over the resident window — the practical
                  HBM read bandwidth visible to this harness (~500
                  GB/s of the v5e's 819 on the 2026-07-30 session)
  pallas stage0   the product kernel (tpudas.ops.pallas_fir) across
                  (kb, cb) grid geometries, f32 and raw int16 input
  xla stage0      the XLA polyphase formulation for reference

History (PERF.md §5 keeps the figures): the v1 VPU kernel measured
compute-bound at ~174 GB/s; single-stream auto-pipelined DMA capped at
~185 GB/s regardless of block geometry (probe_pipeline.py), which
motivated the v2 MXU banded-matmul kernel with P parallel input
streams.

Run: python tools/perf_stage0.py   (on the TPU; each config compiles)
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, ".")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from scan_harness import measure as _measure
from tpudas.ops.fir import _block_taps, design_cascade, _polyphase_stage_xla
from tpudas.ops.pallas_fir import fir_decimate_pallas, stage_input_rows

C = 2048


def measure(fn, T, iters=96, dtype="float32"):
    return _measure(fn, T, C, iters, dtype)


def report(name, T, dt, in_bytes=4.0, extra_bytes_per_in=0.0):
    gsps = T * C / dt / 1e9
    gbps = T * C * (in_bytes + extra_bytes_per_in) / dt / 1e9
    print(
        f"{name:34s} {dt * 1e3:8.3f} ms/win  {gsps:7.2f} G ch-samp/s  "
        f"{gbps:6.1f} GB/s ({gbps / 819 * 100:4.1f}% peak)",
        flush=True,
    )


def main():
    print(f"backend={jax.default_backend()}", flush=True)
    plan = design_cascade(1000.0, 1000, 0.45, 4)
    R, h0 = plan.stages[0]
    hb = _block_taps(np.asarray(h0), R)
    B = int(hb.shape[0])
    print(f"stage0: R={R} taps={len(h0)} B={B}", flush=True)

    # STAGE0_QUICK=1 (one geometry per run) skips the read-ceiling and
    # XLA reference sections.
    quick = os.environ.get("STAGE0_QUICK", "0") == "1"
    T0 = 129088
    if not quick:
        dt = measure(lambda x: jnp.sum(x, axis=0), T0)
        report("read-ceiling (sum)", T0, dt)

    # STAGE0_CONV=1: measure the pure-XLA conv formulations of stage 0
    # instead of the Pallas geometries — if XLA's native conv emitter
    # streams anywhere near the ~510 GB/s its reduce does, it beats
    # the Pallas path without any Mosaic tuning.  Two mappings of the
    # same depthwise-with-shared-taps op (taps identical per channel):
    #   conv-batch:     channels as the conv BATCH dim (N=C, feat=1)
    #   conv-depthwise: channels as grouped FEATURES (groups=C)
    if os.environ.get("STAGE0_CONV", "0") == "1":
        taps_full = jnp.asarray(np.asarray(hb, np.float32).reshape(-1))
        L = int(taps_full.shape[0])
        n_out = 16128
        T = (n_out - 1) * R + L

        def conv_batch(x, _t=taps_full, _R=R, _n=n_out, _L=L):
            lhs = x.T[:, None, :]  # (C, 1, T): N=C, feature=1
            rhs = _t[None, None, :]  # (O=1, I=1, L)
            y = jax.lax.conv_general_dilated(
                lhs, rhs, window_strides=(_R,), padding="VALID",
                dimension_numbers=("NCH", "OIH", "NCH"),
            )
            return y[:, 0, :_n].T

        def conv_depthwise(x, _t=taps_full, _R=R, _n=n_out):
            Cx = x.shape[1]
            lhs = x.T[None, :, :]  # (1, C, T)
            rhs = jnp.broadcast_to(
                taps_full[None, None, :], (Cx, 1, taps_full.shape[0])
            )
            y = jax.lax.conv_general_dilated(
                lhs, rhs, window_strides=(_R,), padding="VALID",
                dimension_numbers=("NCH", "OIH", "NCH"),
                feature_group_count=Cx,
            )
            return y[0, :, :_n].T

        for name, fn in (
            ("conv-batch", conv_batch),
            ("conv-depthwise", conv_depthwise),
        ):
            try:
                dt = measure(fn, T)
                report(f"{name} f32", T, dt, 4.0, 2 * 4 / 8)
            except Exception as exc:
                print(f"{name} f32: {str(exc)[:120]}", flush=True)
        return

    # product kernel: (kb, cb) sweep; kb=512 is the product default
    # (P=4 parallel 128-frame sub-blocks per grid step).  Geometry
    # lists are env-overridable so a live session can widen or narrow
    # the sweep without code edits: STAGE0_KBS / STAGE0_CBS are
    # comma-separated (all kb x cb combinations are measured).
    kbs = [int(v) for v in os.environ.get(
        "STAGE0_KBS", "256,512,1024").split(",")]
    cbs = [int(v) for v in os.environ.get(
        "STAGE0_CBS", "128,256").split(",")]
    geoms = [(kb, cb) for kb in kbs for cb in cbs]
    # STAGE0_TAG labels experiment rows (e.g. the Mosaic-knob A/Bs the
    # campaign sweep runs via TPUDAS_PALLAS_* envs) so log lines from
    # different configurations at the same geometry stay distinct
    tag = os.environ.get("STAGE0_TAG", "").strip()
    tag = f" [{tag}]" if tag else ""
    for kb, cb in geoms:
        n_out = -(-16000 // kb) * kb
        T = stage_input_rows(B, R, n_out, kb)
        try:
            dt = measure(
                lambda x, kb=kb, cb=cb, n_out=n_out: fir_decimate_pallas(
                    x, hb, R, n_out=n_out, kb=kb, cb=cb
                ),
                T,
            )
            report(f"pallas f32 kb={kb} cb={cb}{tag}", T, dt,
                   4.0, 2 * 4 / 8)
        except Exception as exc:
            print(f"pallas kb={kb} cb={cb}{tag}: {str(exc)[:120]}",
                  flush=True)

    # raw int16 payload (the quantized tdas ingest): half the read —
    # swept over the same geometries (the winning f32 geometry is not
    # necessarily the winning int16 one: the DMA is half-width but the
    # in-kernel cast adds VPU work)
    for kb, cb in geoms:
        n_out = -(-16000 // kb) * kb
        T = stage_input_rows(B, R, n_out, kb)
        try:
            dt = measure(
                lambda x, kb=kb, cb=cb, n_out=n_out: fir_decimate_pallas(
                    x, hb, R, n_out=n_out, kb=kb, cb=cb
                ),
                T,
                dtype="int16",
            )
            report(f"pallas i16 kb={kb} cb={cb}{tag}", T, dt,
                   2.0, 2 * 4 / 8)
        except Exception as exc:
            print(f"pallas i16 kb={kb} cb={cb}{tag}: {str(exc)[:120]}",
                  flush=True)

    # XLA polyphase reference
    if not quick:
        n_out = 16128
        T = (n_out + B) * R
        dt = measure(lambda x: _polyphase_stage_xla(x, hb, R, n_out), T)
        report("xla polyphase", T, dt, 4.0, 2 * 4 / 8)


if __name__ == "__main__":
    main()
