"""Pallas TPU kernel: strided (decimating) FIR along the time axis.

This is the hot inner loop of the cascade engine (tpudas.ops.fir): for
a (T, C) block and frame-blocked taps ``hb`` (B, R),

    y[k, c] = sum_{b, r} hb[b, r] * x[(k + b) * R + r, c]

i.e. a causal FIR of length <= B*R evaluated only at stride-R output
positions — the op the reference executes as full-rate ``sosfiltfilt``
+ decimating ``interpolate`` (lf_das.py:223-225) and XLA executes as
B shifted matmuls with B full HBM passes.

Design (v2, informed by round-4 chip measurements — PERF.md §5):

- **MXU banded matmul, not VPU shifted adds.**  For an SB-frame output
  sub-block the FIR is one dot ``Y = A @ X`` with
  ``A[k, k*R + j] = h[j]`` the (SB, (SB+HALO)*R) banded tap matrix and
  ``X`` the flat 2-D view of the input rows.  A is ~96% zeros, but the
  MXU has ~50x the VPU's throughput: the VPU formulation measured
  compute-bound at 174 GB/s while this one is bound by the DMA stream.
  A rides along as a grid-constant input (index map (0,0)): the
  pipeline fetches it once and skips the re-DMA on later steps.
- **P parallel input streams.**  A single auto-pipelined input block
  measured ~185 GB/s regardless of block geometry (one DMA in flight
  can't cover HBM latency).  Each grid step therefore reads P separate
  main blocks — P views of the same array at consecutive block
  indices, each with its own double buffer and in-flight DMA.
- **f32 accuracy via a 3-pass bf16 split** (hi/lo split of both
  operands, dropping lo*lo): Mosaic lowers only DEFAULT (1-pass bf16,
  ~3e-3 abs error on unit-scale data — too coarse) and HIGHEST
  (6-pass); 3 passes give ~1e-5 at half HIGHEST's MXU cost.  Interpret
  mode (the CPU test path) uses exact f32 dots instead, so CPU
  equality tests see the mathematically exact kernel.

Layout: the halo of main block j is the head of main block j+1 — for
j < P-1 that block is already resident in the same grid step, so only
the LAST sub-block needs a dedicated halo input (the head of the next
step's first main block, expressed as a second BlockSpec over the same
array; possible because HALO_F divides SB, so the halo offset is an
integer block index).

VMEM at (P, SB, CB) = (4, 128, 128), R=8: 4 mains x 512 KB x 2
(double-buffered) + A 557 KB + out 256 KB x 2 + halo 32 KB x 2 — about
6 MB of the ~16 MB budget.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "fir_decimate_pallas",
    "stage_input_rows",
    "fused_cascade_pallas",
    "fused_taps_fit",
    "kernel_quantum",
    "channel_block",
    "pallas_p",
]

_SB = 128  # output frames per sub-block (one MXU dot)


def _env_geom(name: str, default: int, multiple_of: int = 1) -> int:
    """Env-tunable geometry knob: empty/unset -> default; anything
    else must be a positive int (and a lane multiple where required)
    — fail at import with the variable named, not mid-run."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not an integer") from None
    if val < 1 or val % multiple_of:
        raise ValueError(
            f"{name} must be a positive multiple of {multiple_of}, "
            f"got {val}"
        )
    return val


# geometry is env-tunable so on-chip sweeps need no code edits; the
# engine's chain layout calls the same accessors, keeping the sizing
# math and the kernel grid in lockstep.  Read at CALL time (not
# import) so a retune (tools/retune_stage_ok.py) applies mid-process:
# every jit/layout cache that depends on these carries
# ``tpudas.ops.fir.knob_fingerprint()`` in its key, so a changed env
# value dispatches fresh instead of hitting a stale compile.


def pallas_p() -> int:
    """Parallel DMA streams per grid step (``TPUDAS_PALLAS_P``)."""
    return _env_geom("TPUDAS_PALLAS_P", 4)


def kernel_quantum() -> int:
    """Output frames per grid step (the grid quantum): ``_SB`` frames
    per parallel sub-block times :func:`pallas_p` sub-blocks."""
    return _SB * pallas_p()


def channel_block() -> int:
    """Channel (lane) block size (``TPUDAS_PALLAS_CB``)."""
    return _env_geom("TPUDAS_PALLAS_CB", 128, multiple_of=128)


def _mosaic_knobs():
    """Experimental Mosaic/pipeline knobs for on-chip sweeps (read at
    call time so one process can A/B them without reimport):

    - TPUDAS_PALLAS_DIMSEM: dimension_semantics for the (k, c) grid —
      "parallel", "arbitrary", or a comma pair like
      "arbitrary,parallel" (order follows the ACTIVE grid order).
    - TPUDAS_PALLAS_GRID: "kc" (default; channel block varies fastest)
      or "ck" (output-frame block varies fastest, so consecutive grid
      steps walk sequential rows of the input).
    - TPUDAS_PALLAS_VMEM_MB: vmem_limit_bytes override, in MiB —
      larger double-buffering headroom for big-block geometries.

    Defaults leave everything unset (Mosaic's own choices).
    """
    sems_env = os.environ.get("TPUDAS_PALLAS_DIMSEM", "").strip()
    grid_order = os.environ.get("TPUDAS_PALLAS_GRID", "kc").strip() or "kc"
    if grid_order not in ("kc", "ck"):
        raise ValueError(
            f"TPUDAS_PALLAS_GRID must be 'kc' or 'ck', got {grid_order!r}"
        )
    vmem_mb = _env_geom("TPUDAS_PALLAS_VMEM_MB", 0)  # 0 = unset
    cp_kwargs = {}
    if sems_env:
        sems = tuple(s.strip() for s in sems_env.split(","))
        if len(sems) == 1:
            sems = sems * 2
        if len(sems) != 2 or not all(
            s in ("parallel", "arbitrary") for s in sems
        ):
            raise ValueError(
                "TPUDAS_PALLAS_DIMSEM must be 'parallel', 'arbitrary' "
                f"or a comma pair of those, got {sems_env!r}"
            )
        cp_kwargs["dimension_semantics"] = sems
    if vmem_mb:
        cp_kwargs["vmem_limit_bytes"] = vmem_mb * 2**20
    call_kwargs = {}
    if cp_kwargs:
        call_kwargs["compiler_params"] = pltpu.CompilerParams(**cp_kwargs)
    return grid_order, call_kwargs


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _halo_frames(B: int, sb: int = _SB) -> int:
    """Halo frames: B rounded up to a sublane multiple that also
    divides the sub-block (so the halo offset is an integer block
    index). Single source for both the kernel and the sizing math."""
    halo_f = _round_up(B, 8)
    while halo_f <= sb and sb % halo_f != 0:
        halo_f += 8
    return halo_f


def stage_input_rows(B: int, R: int, n_out: int, kb: int | None = None) -> int:
    """Input rows this kernel consumes to emit ``n_out`` outputs with
    B tap-frames at stride R — the grid/halo-padded figure. Feeding
    exactly this many rows makes the kernel pad-free (the internal
    ``jnp.pad`` otherwise materializes a full copy of the input, which
    at engine scale is an extra HBM round-trip per stage)."""
    kb = kernel_quantum() if kb is None else int(kb)
    sb = min(int(kb), _SB)
    return (_round_up(int(n_out), kb) + _halo_frames(B, sb)) * R


def _split_bf16(v):
    hi = v.astype(jnp.bfloat16)
    lo = (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _dot_3x(a, x):
    """~f32-accurate matmul from 3 bf16 MXU passes (drops lo*lo)."""
    a_hi, a_lo = _split_bf16(a)
    x_hi, x_lo = _split_bf16(x)
    d = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    return d(a_hi, x_hi) + d(a_hi, x_lo) + d(a_lo, x_hi)


def _dot_f32(a, x):
    return jnp.dot(
        a,
        x,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _kernel_body(P, SB, CB, halo_rows, exact):
    dot = _dot_f32 if exact else _dot_3x

    def kernel(*refs):
        a_ref = refs[0]
        mains = refs[1 : 1 + P]
        halo_ref = refs[1 + P]
        out_ref = refs[2 + P]
        for j in range(P):
            head = (
                mains[j + 1][:halo_rows]
                if j < P - 1
                else halo_ref[:]
            )
            x = jnp.concatenate([mains[j][:], head], axis=0)
            # int16 ingest: bare cast in VMEM after the (half-width)
            # DMA — the quantization scale is the caller's (applied to
            # the decimated output; the FIR is linear).  Exact under
            # the 3x split too: a 16-bit integer is hi+lo bf16 exactly.
            x = x.astype(jnp.float32)
            out_ref[j * SB : (j + 1) * SB] = dot(a_ref[:], x)

    return kernel


@functools.lru_cache(maxsize=64)
def _band_matrix(taps: tuple, R: int, SB: int, rows: int) -> np.ndarray:
    h = np.asarray(taps, np.float32)
    A = np.zeros((SB, rows), np.float32)
    for k in range(SB):
        A[k, k * R : k * R + len(h)] = h
    return A


# ---------------------------------------------------------------------------
# v1 implementation (VPU shifted multiply-reduce), the round-4 kernel.
# Selectable via TPUDAS_PALLAS_IMPL=v1 for A/B runs on the chip; it is
# never chosen automatically (a failing kernel raises).


def _kernel_body_v1(B, KB, CB):
    def kernel(hb_ref, xm_ref, xh_ref, out_ref):
        full = jnp.concatenate(
            [xm_ref[:], xh_ref[:]], axis=0
        ).astype(jnp.float32)
        acc = jnp.zeros((KB, CB), jnp.float32)
        for b in range(B):
            acc = acc + jnp.sum(
                full[b : b + KB] * hb_ref[b][None, :, None], axis=1
            )
        out_ref[:] = acc

    return kernel


def _fir_decimate_pallas_v1(x, hb, R: int, n_out: int,
                            interpret: bool = False):
    """The round-4 session-1 kernel: 128-frame blocks, taps as a VMEM
    operand, B shifted VPU multiply-reduces.  Accepts int16 input
    (bare cast, like v2).  Tolerates inputs sized for the v2 grid
    quantum (it slices its own, smaller need)."""
    KB = CB = 128
    hb = np.asarray(hb)
    B = int(hb.shape[0])
    T, C = x.shape
    halo_f = _halo_frames(B, KB)
    if halo_f > KB:
        raise ValueError(
            f"tap frames ({B}) exceed the kernel block ({KB} frames); "
            "use the XLA polyphase path for very long stages"
        )
    nk = -(-int(n_out) // KB)
    nc = -(-int(C) // CB)
    Kpad = nk * KB
    need_rows = (Kpad + halo_f) * R
    pad_t = need_rows - T
    pad_c = nc * CB - C
    if pad_t > 0 or pad_c > 0:
        x = jnp.pad(x, ((0, max(pad_t, 0)), (0, pad_c)))
    xr = x[:need_rows].reshape(Kpad + halo_f, R, nc * CB)
    hb_pad = np.zeros((halo_f, R), np.float32)
    hb_pad[:B] = hb.astype(np.float32)
    step = KB // halo_f
    out = pl.pallas_call(
        _kernel_body_v1(B, KB, CB),
        grid=(nk, nc),
        in_specs=[
            pl.BlockSpec(
                (halo_f, R), lambda k, c: (0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (KB, R, CB),
                lambda k, c: (k, 0, c),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (halo_f, R, CB),
                lambda k, c, _s=step: (k * _s + _s, 0, c),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (KB, CB), lambda k, c: (k, c), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((Kpad, nc * CB), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(hb_pad), xr, xr)
    return out[:n_out, :C]


def fir_decimate_pallas(
    x, hb, R: int, n_out: int, interpret: bool = False, kb=None, cb=None
):
    """Strided FIR: x (T, C) f32 or int16, hb (B, R) f32 -> (n_out, C)
    f32.

    ``hb`` must be CONCRETE (host numpy or a settled device array, not
    a tracer): the banded tap matrix is built on the host.  ``x`` may
    be traced — callers jit the enclosing cascade.  ``n_out`` is
    static; the input is zero-padded on the right as needed (outputs
    whose receptive field crosses the pad carry edge artifacts,
    trimmed by the overlap-save caller), and channel counts that are
    not multiples of the lane tile get whole-block zero padding.
    ``kb`` is the grid quantum in output frames (P parallel sub-blocks
    of min(kb, 128) frames each); ``cb`` the channel block.

    int16 ``x`` (the tdas quantized-ingest payload) is cast to f32 in
    VMEM after the half-width DMA and filtered RAW — the caller owns
    the quantization scale and, the FIR being linear, applies it to
    this stage's (decimated, so R-times smaller) output.  Keeping the
    scale out of the kernel keeps it a traced value: one compiled
    executable serves every scale.

    ``TPUDAS_PALLAS_IMPL=v1`` selects the previous VPU formulation
    (see the v1 section below).
    """
    if os.environ.get("TPUDAS_PALLAS_IMPL", "v2") == "v1":
        return _fir_decimate_pallas_v1(x, hb, R, n_out, interpret)
    B = int(hb.shape[0])
    T, C = x.shape
    KB = kernel_quantum() if kb is None else int(kb)
    CB = channel_block() if cb is None else int(cb)
    SB = min(KB, _SB)
    P = KB // SB
    if KB % SB:
        raise ValueError(f"kb ({KB}) must be a multiple of {SB}")
    halo_f = _halo_frames(B, SB)
    if halo_f > SB:
        raise ValueError(
            f"tap frames ({B}) exceed the kernel sub-block ({SB} "
            "frames); use the XLA polyphase path for very long stages"
        )

    nk = -(-int(n_out) // KB)
    nc = -(-int(C) // CB)
    Kpad = nk * KB
    need_rows = stage_input_rows(B, R, n_out, KB)
    pad_t = need_rows - T
    pad_c = nc * CB - C
    if pad_t > 0 or pad_c > 0:
        x = jnp.pad(x, ((0, max(pad_t, 0)), (0, pad_c)))
    x2 = x[:need_rows]

    # frame-blocked taps (B, R) flatten back to the padded tap vector
    taps = tuple(np.asarray(jax.device_get(hb), np.float32).reshape(-1))
    band_rows = (SB + halo_f) * R
    A = jnp.asarray(_band_matrix(taps, R, SB, band_rows))

    halo_rows = halo_f * R
    step = SB * P // halo_f  # halo offset in halo-block units

    grid_order, call_kwargs = _mosaic_knobs()
    if grid_order == "ck":
        # grid (nc, nk): index-map args arrive as (c, k) — remap to
        # the (k, c) the block coordinates are written in
        grid = (nc, nk)

        def _km(f):
            return lambda c, k, _f=f: _f(k, c)

    else:
        grid = (nk, nc)

        def _km(f):
            return f

    main_specs = [
        pl.BlockSpec(
            (SB * R, CB),
            _km(lambda k, c, j=j: (k * P + j, c)),
            memory_space=pltpu.VMEM,
        )
        for j in range(P)
    ]
    halo_spec = pl.BlockSpec(
        (halo_rows, CB),
        _km(lambda k, c, _s=step: (k * _s + _s, c)),
        memory_space=pltpu.VMEM,
    )
    out = pl.pallas_call(
        _kernel_body(P, SB, CB, halo_rows, exact=interpret),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (SB, band_rows),
                _km(lambda k, c: (0, 0)),
                memory_space=pltpu.VMEM,
            ),
            *main_specs,
            halo_spec,
        ],
        out_specs=pl.BlockSpec(
            (KB, CB), _km(lambda k, c: (k, c)), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((Kpad, nc * CB), jnp.float32),
        interpret=interpret,
        **call_kwargs,
    )(A, *([x2] * P), x2)
    return out[:n_out, :C]


# ---------------------------------------------------------------------------
# v3: the FUSED cascade kernel (ISSUE 10).  One pallas_call runs the
# whole multistage decimator: the grid walks (channel block, time
# chunk); each grid step reads one full-rate input chunk, pushes it
# through EVERY stage back to back inside VMEM, and writes only the
# final decimated output chunk.  Each stage's trailing-sample state
# lives in a VMEM scratch buffer that persists across the time-chunk
# grid steps (initialized from the carry refs at t == 0, flushed to
# the carry outputs every step so the last step's write is the new
# carry) — zero per-stage full-rate intermediates ever reach HBM.
#
# Stage math is the v1 VPU formulation (exact f32 multiply-reduce, no
# bf16 split): the per-stage work is ~B multiply-adds per input sample
# and the fused kernel's DMA stream is ~R-times lighter than the
# per-stage kernels' (input read once, decimated output only), so the
# VPU-vs-MXU tradeoff of the v2 notes above tilts back — the v2 MXU banded
# matmul needed its arithmetic headroom to keep up with TWO full-rate
# HBM streams per stage, which the fused kernel has eliminated.
# The final output is written as (time chunk, chunk_out, C) blocks: a
# 2-D (chunk_out, CB) block would break Mosaic's 8x128 tiling rule for
# the chunk sizes fused_chunk_outputs picks (6 at a 60 s / 1 Hz
# block), while a block whose last two dims span the whole array's
# last two dims is always legal.  tests/test_tpu_compile.py compiles
# it for a described v5e.
#
# Tail alignment trick: stage i carries p_i trailing input rows
# (tpudas.ops.fir.stream_carry_sizes — p_i is NOT generally a
# multiple of R_i, and the carry layout is shared byte-for-byte with
# the unfused engines).  The scratch holds q_i = round_up(p_i, R_i)
# rows — off_i = q_i - p_i extra OLDER rows whose values multiply
# only against zero-padded taps — so the concatenated (q_i + chunk_i)
# working block frame-blocks exactly into (q_i/R_i + k_i) tap frames
# and the taps shift by off_i into hb'[b*R + r] = h[b*R + r - off_i].


def _round_up_div(a: int, b: int) -> int:
    return -(-int(a) // int(b))


def fused_taps_fit(stages, chunk_out: int) -> bool:
    """Whether :func:`fused_cascade_pallas` can run this plan at this
    chunk size: every stage's chunk must be a whole number of frames
    (guaranteed by construction) and the per-step VMEM footprint —
    input chunk + all stage scratch + taps — must fit the ~16 MiB
    budget with double-buffering headroom."""
    cb = channel_block()
    ratio = 1
    for R, _h in stages:
        ratio *= int(R)
    chunk_in = int(chunk_out) * ratio
    vmem = 2 * chunk_in * cb * 4  # double-buffered input block
    rows = chunk_in
    for R, h in stages:
        p = max(len(h) - int(R), 0)
        q = _round_up_div(p, int(R)) * int(R)
        vmem += (q + rows) * cb * 4  # working block + scratch
        rows //= int(R)
    vmem += 2 * int(chunk_out) * cb * 4  # double-buffered output
    return vmem <= 12 * 2**20


def _fused_stage_meta(stages, sizes, chunk_in: int):
    """Static per-stage geometry for the fused kernel: (R, k, p, q,
    off, L, hbp) with hbp the off-shifted frame-blocked taps and L
    the true tap length (the kernel SLICES the off/pad positions out
    of the partial frames rather than multiplying by zero — 0 * NaN
    would smear NaN outside the receptive field)."""
    meta = []
    rows = int(chunk_in)
    for (R, h), p in zip(stages, sizes):
        R = int(R)
        h = np.asarray(h, np.float32)
        p = int(p)
        q = _round_up_div(p, R) * R
        off = q - p
        k = rows // R
        bp = _round_up_div(off + len(h), R)
        hbp = np.zeros((bp, R), np.float32)
        hbp.reshape(-1)[off : off + len(h)] = h
        meta.append((R, k, p, q, off, int(len(h)), hbp))
        rows = k
    return meta


def _fused_kernel_body(meta, CB):
    n_stage = len(meta)
    n_state = sum(1 for _R, _k, p, _q, _off, _L, _h in meta if p)

    def kernel(*refs):
        taps = refs[:n_stage]
        x_ref = refs[n_stage]
        cin = refs[n_stage + 1 : n_stage + 1 + n_state]
        y_ref = refs[n_stage + 1 + n_state]
        cout = refs[n_stage + 2 + n_state : n_stage + 2 + 2 * n_state]
        scr = refs[n_stage + 2 + 2 * n_state :]
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _init():
            si = 0
            for _R, _k, p, q, off, _L, _h in meta:
                if not p:
                    continue
                if off:
                    scr[si][:off] = jnp.zeros((off, CB), jnp.float32)
                scr[si][off:] = cin[si][:]
                si += 1

        y = x_ref[:].astype(jnp.float32)
        si = 0
        for i, (R, k, p, q, off, L, hbp) in enumerate(meta):
            if p:
                z = jnp.concatenate([scr[si][:], y], axis=0)
                if off:
                    scr[si][:off] = jnp.zeros((off, CB), jnp.float32)
                scr[si][off:] = z[z.shape[0] - p :]
                cout[si][:] = z[z.shape[0] - p :]
                si += 1
            else:
                z = y
            zf = z.reshape(z.shape[0] // R, R, CB)
            acc = jnp.zeros((k, CB), jnp.float32)
            tv = taps[i][:]
            for b in range(hbp.shape[0]):
                # the partial first/last frames are SLICED to the true
                # tap support [off, off + L): multiplying the padded
                # positions by their zero taps instead would turn a
                # NaN-gap row into 0 * NaN = NaN and smear NaN outside
                # the receptive field (the per-stage polyphase path
                # pays that smear only FORWARD; slicing keeps this
                # kernel's NaN set a subset of the reference's)
                lo = max(0, off - b * R)
                hi = min(R, off + L - b * R)
                if hi <= lo:
                    continue
                acc = acc + jnp.sum(
                    zf[b : b + k, lo:hi] * tv[b, lo:hi][None, :, None],
                    axis=1,
                )
            y = acc
        y_ref[:] = y

    return kernel


def fused_cascade_pallas(
    x, bufs, stages, sizes, chunk_out: int, interpret: bool = False,
    cb=None,
):
    """One fused stateful cascade step: x (T, C) f32, ``bufs`` the
    per-stage carry tuple ((p_i, C) each, the same layout every other
    engine carries) -> (y (T/ratio, C), new_bufs).

    ``T`` must be a multiple of ``chunk_out * ratio`` (the caller
    picks ``chunk_out`` dividing the block's output count —
    :func:`tpudas.ops.fir.fused_chunk_outputs`).  ``stages`` are the
    plan's (R, taps) pairs with CONCRETE taps; ``x``/``bufs`` may be
    traced.  Channel counts that are not lane-block multiples get
    whole-block zero padding (carry columns included — zero columns
    stay zero through the linear stages, so the trim is exact)."""
    CB = channel_block() if cb is None else int(cb)
    T, C = x.shape
    ratio = 1
    for R, _h in stages:
        ratio *= int(R)
    chunk_in = int(chunk_out) * ratio
    if T % chunk_in:
        raise ValueError(
            f"fused kernel block ({T} rows) is not a multiple of the "
            f"chunk ({chunk_in} rows)"
        )
    nt = T // chunk_in
    nc = _round_up_div(C, CB)
    pad_c = nc * CB - C
    if pad_c:
        x = jnp.pad(x, ((0, 0), (0, pad_c)))
        bufs = tuple(jnp.pad(b, ((0, 0), (0, pad_c))) for b in bufs)
    meta = _fused_stage_meta(stages, sizes, chunk_in)
    state = [(i, q, p) for i, (_R, _k, p, q, _off, _L, _h) in
             enumerate(meta) if p]

    grid_spec = dict(
        grid=(nc, nt),
        in_specs=[
            *[
                pl.BlockSpec(
                    tuple(hbp.shape), lambda c, t: (0, 0),
                    memory_space=pltpu.VMEM,
                )
                for _R, _k, _p, _q, _off, _L, hbp in meta
            ],
            pl.BlockSpec(
                (chunk_in, CB), lambda c, t: (t, c),
                memory_space=pltpu.VMEM,
            ),
            *[
                pl.BlockSpec(
                    (p, CB), lambda c, t: (0, c),
                    memory_space=pltpu.VMEM,
                )
                for _i, _q, p in state
            ],
        ],
        out_specs=[
            pl.BlockSpec(
                (None, int(chunk_out), CB), lambda c, t: (t, 0, c),
                memory_space=pltpu.VMEM,
            ),
            *[
                pl.BlockSpec(
                    (p, CB), lambda c, t: (0, c),
                    memory_space=pltpu.VMEM,
                )
                for _i, _q, p in state
            ],
        ],
        out_shape=[
            jax.ShapeDtypeStruct(
                (nt, int(chunk_out), nc * CB), jnp.float32
            ),
            *[
                jax.ShapeDtypeStruct((p, nc * CB), jnp.float32)
                for _i, _q, p in state
            ],
        ],
        scratch_shapes=[
            pltpu.VMEM((q, CB), jnp.float32) for _i, q, _p in state
        ],
    )
    outs = pl.pallas_call(
        _fused_kernel_body(meta, CB),
        interpret=interpret,
        **grid_spec,
    )(
        *[jnp.asarray(hbp) for _R, _k, _p, _q, _off, _L, hbp in meta],
        x.astype(jnp.float32),
        *[bufs[i] for i, _q, _p in state],
    )
    y = outs[0].reshape(T // ratio, nc * CB)
    y = y[:, :C] if pad_c else y
    new_tails = iter(outs[1:])
    new_bufs = []
    for i, b in enumerate(bufs):
        if int(b.shape[0]):
            nb = next(new_tails)
            new_bufs.append(nb[:, :C] if pad_c else nb)
        else:
            new_bufs.append(b[:, :C] if pad_c else b)
    return y, tuple(new_bufs)
