"""Measure the engine crossovers the dispatch thresholds encode.

Two sweeps:

- **stage sweep** (default; TPU only): the pallas-vs-XLA single-stage
  crossover behind ``tpudas.ops.fir._pallas_stage_ok`` (elements >=
  2**24 and a full first grid step).  Re-measures both engines across
  a (n_out, n_ch) grid on the CURRENT kernel and prints per-point
  times plus the measured crossover, so retuning is reading a table
  instead of guesswork.
- **fused sweep** (``--fused``; meaningful on CPU too): the
  per-stage-chain vs fused-kernel crossover behind
  ``tpudas.ops.fir.fused_min_elems`` (ISSUE 10).  Times the full
  carry-threaded STREAM STEP — cascade chain, fused-xla scan, and
  (TPU) the fused-pallas v3 kernel — across (n_out, n_ch) on the
  flagship 1 kHz -> 1 Hz plan and prints the suggested
  ``TPUDAS_FUSED_MIN_ELEMS``.

Either threshold applies LIVE through the env knob (every dispatch
cache keys on ``tpudas.ops.fir.knob_fingerprint``) — no restart.

Run: ``python tools/retune_stage_ok.py [--fused]``
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, ".")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from scan_harness import measure as _measure
from tpudas.ops.fir import _block_taps, _polyphase_stage_xla, design_cascade
from tpudas.ops.pallas_fir import fir_decimate_pallas, stage_input_rows

# the flagship cascade's stage-0 filter (R=8) — the routing decision
# that matters; smaller-R later stages scale the same way
K_GRID = [2048, 4096, 8192, 16384, 32768]
C_GRID = [128, 512, 2048]


def _measure_stream_step(plan, n_out, C, engine, iters=6):
    """Best-of wall seconds per carry-threaded stream step (the fused
    dispatch unit): the carry is fed back each iteration, so this
    times exactly what one realtime round pays per block."""
    from tpudas.ops.fir import (
        _build_fused_stream_fn,
        _build_stream_cascade_fn,
        cascade_stream_init,
        knob_fingerprint,
    )

    T = n_out * plan.ratio
    carry = tuple(
        jnp.asarray(b) for b in cascade_stream_init(plan, C)
    )
    rng = np.random.default_rng(0)
    x_host = rng.standard_normal((T, C)).astype(np.float32)
    knobs = knob_fingerprint()
    if engine.startswith("fused"):
        fn = _build_fused_stream_fn(plan, T, C, engine, knobs=knobs)
    else:
        fn = _build_stream_cascade_fn(plan, T, C, engine, knobs=knobs)
    # the step donates its input on accelerator backends — a fresh
    # device buffer per round there; on CPU (no donation) reuse
    donating = jax.default_backend() not in ("cpu",)
    x = jnp.asarray(x_host)
    y, carry = fn(x, carry)
    jax.block_until_ready(y)
    best = 1e30
    for _ in range(iters):
        if donating:
            x = jnp.asarray(x_host)
        t0 = time.perf_counter()
        y, carry = fn(x, carry)
        jax.block_until_ready(y)
        best = min(best, time.perf_counter() - t0)
    return best


def fused_sweep() -> None:
    """The cascade-chain vs fused crossover (ISSUE 10)."""
    backend = jax.default_backend()
    print(f"backend={backend}", flush=True)
    engines = ["xla", "fused-xla"]
    if backend == "tpu":
        engines.append("fused-pallas")
    plan = design_cascade(1000.0, 1000, 0.45, 4)
    print(f"plan: stages={[(R, len(h)) for R, h in plan.stages]}",
          flush=True)
    hdr = " ".join(f"{e + ' ms':>14}" for e in engines)
    print(f"{'n_out':>6} {'n_ch':>6} {'elems':>12} {hdr}  winner",
          flush=True)
    rows = []
    for C in (64, 256, 2048, 10000):
        for n_out in (4, 16, 64):
            times = {}
            for e in engines:
                try:
                    times[e] = _measure_stream_step(plan, n_out, C, e)
                except Exception as exc:
                    print(f"{n_out:>6} {C:>6}  {e} failed: "
                          f"{str(exc)[:80]}", flush=True)
            if "xla" not in times:
                continue
            elems = n_out * plan.ratio * C
            win = min(times, key=times.get)
            rows.append((elems, win))
            cells = " ".join(
                f"{times[e] * 1e3:>14.2f}" if e in times else
                f"{'-':>14}" for e in engines
            )
            print(f"{n_out:>6} {C:>6} {elems:>12} {cells}  {win}",
                  flush=True)
    fused_wins = sorted(e for e, w in rows if w.startswith("fused"))
    chain_wins = sorted(e for e, w in rows if not w.startswith("fused"))
    if fused_wins:
        print(f"\nsmallest fused win: {fused_wins[0]} elements "
              f"(2**{np.log2(fused_wins[0]):.1f})")
    if chain_wins:
        print(f"largest chain win:  {chain_wins[-1]} elements "
              f"(2**{np.log2(chain_wins[-1]):.1f})")
    from tpudas.ops.fir import fused_min_elems

    print(f"current threshold:  {fused_min_elems()} "
          f"(2**{np.log2(fused_min_elems()):.1f}) — if the crossover "
          "moved, set TPUDAS_FUSED_MIN_ELEMS (applies live) and/or "
          "adjust fused_min_elems (tpudas/ops/fir.py)")


def main() -> None:
    if "--fused" in sys.argv[1:]:
        fused_sweep()
        return
    backend = jax.default_backend()
    print(f"backend={backend}", flush=True)
    if backend == "cpu":
        print("cpu backend: interpret-mode stage times are meaningless "
              "here; run on the TPU (the --fused sweep DOES run on "
              "CPU)")
        return
    plan = design_cascade(1000.0, 1000, 0.45, 4)
    R, h0 = plan.stages[0]
    hb = _block_taps(np.asarray(h0), R)
    B = int(hb.shape[0])
    print(f"stage0: R={R} B={B}", flush=True)
    print(f"{'n_out':>7} {'n_ch':>6} {'elems':>12} "
          f"{'pallas ms':>10} {'xla ms':>9}  winner", flush=True)
    crossover = []
    for C in C_GRID:
        for k in K_GRID:
            T = stage_input_rows(B, R, k)
            iters = 32
            dt_p = None
            try:
                dt_p = _measure(
                    lambda w: fir_decimate_pallas(w, hb, R, n_out=k),
                    T, C, iters,
                )
            except Exception as exc:
                print(f"{k:>7} {C:>6}  pallas failed: {str(exc)[:80]}",
                      flush=True)
            T_x = (k + B) * R
            try:
                dt_x = _measure(
                    lambda w: _polyphase_stage_xla(w, hb, R, k), T_x, C,
                    iters,
                )
            except Exception as exc:
                # capture-early: one dead grid point must not lose the
                # rest of the table or the crossover summary
                print(f"{k:>7} {C:>6}  xla failed: {str(exc)[:80]}",
                      flush=True)
                continue
            elems = k * R * C
            # an unrunnable pallas point counts as an XLA win: the
            # threshold must route it away from the kernel
            win = "pallas" if dt_p is not None and dt_p < dt_x else "xla"
            crossover.append((elems, k, C, win))
            p_ms = f"{dt_p * 1e3:>10.3f}" if dt_p is not None else "     -    "
            print(
                f"{k:>7} {C:>6} {elems:>12} {p_ms} "
                f"{dt_x * 1e3:>9.3f}  {win}",
                flush=True,
            )
    wins = sorted(e for e, _, _, w in crossover if w == "pallas")
    loses = sorted(e for e, _, _, w in crossover if w == "xla")
    if wins:
        print(f"\nsmallest pallas win: {wins[0]} elements "
              f"(2**{np.log2(wins[0]):.1f})")
    if loses:
        print(f"largest xla win:     {loses[-1]} elements "
              f"(2**{np.log2(loses[-1]):.1f})")
    print("current threshold:   2**24 — if the crossover moved, set "
          "TPUDAS_PALLAS_MIN_ELEMS (live override) and/or adjust "
          "_pallas_stage_ok (tpudas/ops/fir.py)")


if __name__ == "__main__":
    main()
