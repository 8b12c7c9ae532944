"""Multistage polyphase FIR decimation — the fast path of the engine.

The reference's hot loop filters the FULL-rate stream with a zero-phase
IIR and then throws away ~99.9% of the samples at the interpolation
step (reference lf_das.py:223-225: ``pass_filter`` at corner
``0.45/dt`` followed by ``interpolate`` onto the decimated grid). The
FFT engine (tpudas.ops.filter) reproduces that shape faithfully but
pays O(T log T) and several full-rate HBM passes per window.

This module exploits the decimating structure instead: a cascade of
small linear-phase FIR stages, each decimating by an integer factor,
designed so the *composite* magnitude response matches the reference's
zero-phase Butterworth-squared response ``1/(1+(f/fc)^(2*order))`` on
the retained band. Compute per input sample drops from O(log T) FFT
passes to ~4-6 multiply-adds, all in one streaming pass — the shape
TPUs (and the Pallas kernel in tpudas.ops.pallas_fir) like.

Design scheme
-------------
- ``factor_ratio`` splits the decimation ratio into integer stages
  (large factors first, so the full-rate stage is the cheapest).
- every stage except the last is a plain anti-alias guard: a
  Kaiser-windowed low-pass whose stopband starts where energy would
  fold back into the final retained band. Its passband covers the
  final band with ~1e-4 ripple.
- the last stage is *response-matched*: a zero-phase frequency-sampled
  FIR of the desired composite response divided by the measured
  response of the guard stages, so the cascade's end-to-end magnitude
  equals the Butterworth-squared target within truncation ripple.
- all stages have odd length, so the composite group delay is an
  integer number of full-rate samples (``CascadePlan.delay``); the
  caller re-indexes outputs by that delay, which makes the cascade
  zero-phase exactly like the reference's forward-backward filter.

Correctness is tolerance-based against the FFT engine (the same way
the reference treats its own edges: the self-calibration probe at
lf_das.py:47-87 thresholds the impulse response at ``max*tol``);
``impulse_response``/``edge_support_samples`` provide that probe for
this engine analytically.
"""

from __future__ import annotations

import functools
import time as _time
from dataclasses import dataclass

import numpy as np

__all__ = [
    "CascadePlan",
    "factor_ratio",
    "design_cascade",
    "cascade_decimate",
    "cascade_decimate_stream",
    "cascade_stream_init",
    "stream_carry_sizes",
    "stream_warmup_outputs",
    "stream_stage_engines",
    "impulse_response",
    "edge_support_samples",
    "butter2_mag",
    "resolve_cascade_engine",
    "resolve_stream_engine",
    "stage_engines",
    "knob_fingerprint",
    "fused_chunk_outputs",
    "fused_intermediate_bytes",
    "STREAM_ENGINES",
    "BATCH_ENGINES",
    "STACKED_ENGINES",
    "cascade_decimate_stream_stacked",
]

# engine literals the STREAM dispatch (cascade_decimate_stream)
# accepts: the per-stage chain with its own pallas/xla routing, plus
# the fused single-kernel formulations (ISSUE 10).  "fused" resolves
# by backend + measured size threshold (resolve_stream_engine); the
# -xla/-pallas spellings force a variant.  tools/check_engines.py
# lints that every literal here appears in the test matrix.
STREAM_ENGINES = ("auto", "pallas", "xla", "fused", "fused-xla",
                  "fused-pallas")
# engine literals the BATCH entry points (cascade_decimate & the
# window/batched paths) accept — the fused formulation is
# streaming-only (it exists to kill per-stage intermediates ACROSS
# carried blocks; the batch path's windows are one-shot).
BATCH_ENGINES = ("auto", "pallas", "xla")
# engine literals the STACKED multi-stream entry point
# (cascade_decimate_stream_stacked) accepts: RESOLVED non-Pallas
# stream variants only.  The fleet's batch executor routes a block
# here only after the per-stream solo resolution already chose one of
# these, so stacking can never flip a stream across the
# fused_min_elems threshold (the stacked width is larger than any
# member's solo width) and never silently swaps a tolerance-based
# Pallas variant for the exact XLA one.  tools/check_engines.py lints
# that every literal here appears in the test matrix.
STACKED_ENGINES = ("xla", "fused-xla")

# every env knob that changes kernel geometry or engine selection.
# knob_fingerprint() reads them at CALL time and every jit/layout
# cache key below includes the fingerprint, so a retune
# (tools/retune_stage_ok.py) applies mid-process — no restart, no
# manual cache clear (the stale-knob footgun this replaces).
_KNOB_ENVS = (
    "TPUDAS_PALLAS_P",
    "TPUDAS_PALLAS_CB",
    "TPUDAS_PALLAS_IMPL",
    "TPUDAS_PALLAS_MIN_ELEMS",
    "TPUDAS_STREAM_PALLAS",
    "TPUDAS_PALLAS_DIMSEM",
    "TPUDAS_PALLAS_GRID",
    "TPUDAS_PALLAS_VMEM_MB",
    "TPUDAS_FUSED_CHUNK",
    "TPUDAS_FUSED_MIN_ELEMS",
)


def knob_fingerprint() -> tuple:
    """The current value of every geometry/selector env knob, as one
    hashable tuple.  Threaded into every compiled-fn and layout cache
    key so a mid-process knob change can never hit a stale entry."""
    import os

    return tuple(os.environ.get(n, "").strip() for n in _KNOB_ENVS)


def _plan_tag(plan) -> str:
    """Compact stable tag of a CascadePlan for devprof kernel keys —
    distinct plans must read as distinct shapes, but the key has to
    stay printable (the /devprof kernel log shows it)."""
    return (
        f"r{plan.ratio}s{len(plan.stages)}"
        f"h{hash(plan) & 0xFFFFFF:06x}"
    )


def butter2_mag(f, corner, order):
    """The reference's zero-phase magnitude: ``|H_butter|^2`` of an
    ``order``-pole Butterworth low-pass (sosfiltfilt applies the filter
    twice, squaring the magnitude — tpudas.ops.filter matches this)."""
    f = np.asarray(f, np.float64)
    return 1.0 / (1.0 + (f / float(corner)) ** (2 * int(order)))


def factor_ratio(ratio: int) -> list[int]:
    """Split an integer decimation ratio into stage factors in [2, 8],
    largest first. Raises if a prime factor > 8 remains."""
    ratio = int(ratio)
    if ratio < 1:
        raise ValueError(f"decimation ratio must be >= 1, got {ratio}")
    factors = []
    rem = ratio
    while rem > 1:
        for f in (8, 7, 6, 5, 4, 3, 2):
            if rem % f == 0:
                factors.append(f)
                rem //= f
                break
        else:
            raise ValueError(
                f"ratio {ratio} has a prime factor > 8; "
                "use the FFT engine for this ratio"
            )
    factors.sort(reverse=True)
    return factors


@dataclass(frozen=True, eq=False)
class CascadePlan:
    """A compiled multistage decimation filter.

    stages: tuple of (R, taps) — taps are float32, odd length.
    ratio:  product of all R.
    delay:  composite group delay in FULL-RATE samples (integer,
            because every stage is odd-length linear-phase);
            causal cascade output ``k`` is the zero-phase filtered
            input at full-rate index ``k*ratio + delay``.
    fs_in / corner / order: the design point.

    Hash/eq are by tap content so plans can key jit caches.
    """

    stages: tuple
    ratio: int
    delay: int
    fs_in: float
    corner: float
    order: int

    @property
    def receptive_field(self) -> int:
        """Total taps footprint in full-rate samples (= 2*delay + 1)."""
        return 2 * self.delay + 1

    def _fingerprint(self):
        return (
            self.ratio,
            self.delay,
            tuple(
                (int(R), np.asarray(h).tobytes()) for R, h in self.stages
            ),
        )

    def __hash__(self):
        return hash(self._fingerprint())

    def __eq__(self, other):
        return (
            isinstance(other, CascadePlan)
            and self._fingerprint() == other._fingerprint()
        )


def _guard_stage_taps(fs_in: float, R: int, f_keep: float) -> np.ndarray:
    """Anti-alias guard: keep [0, f_keep] intact, attenuate everything
    that decimation by R would fold back onto [0, f_keep]."""
    from scipy.signal import firwin, kaiserord

    fs_out = fs_in / R
    stop = fs_out - f_keep  # first fold-back edge
    pass_edge = f_keep
    width = max(stop - pass_edge, 0.05 * fs_in / R)
    numtaps, beta = kaiserord(80.0, width / (0.5 * fs_in))
    numtaps = max(numtaps, 9)
    if numtaps % 2 == 0:
        numtaps += 1
    cutoff = 0.5 * (pass_edge + stop)
    return firwin(
        numtaps, cutoff, window=("kaiser", beta), fs=fs_in
    ).astype(np.float32)


def _stage_response(taps: np.ndarray, fs: float, freqs: np.ndarray):
    """Real-valued magnitude response of a symmetric (linear-phase) FIR
    at ``freqs`` Hz (phase removed analytically)."""
    n = np.arange(len(taps), dtype=np.float64) - (len(taps) - 1) / 2.0
    ang = 2.0 * np.pi * np.asarray(freqs, np.float64)[:, None] * n[None, :] / fs
    return (np.cos(ang) @ np.asarray(taps, np.float64)).astype(np.float64)


def _matched_last_stage(
    fs_l: float,
    corner: float,
    order: int,
    guard_resp,
    taps: int | None,
) -> np.ndarray:
    """Frequency-sampled zero-phase FIR matching
    ``butter2_mag / guard_resp`` on [0, fs_l/2]."""
    nfft = 16384
    freqs = np.arange(nfft // 2 + 1, dtype=np.float64) * fs_l / nfft
    desired = butter2_mag(freqs, corner, order)
    g = np.clip(guard_resp(freqs), 1e-3, None)
    d = np.where(desired > 1e-8, desired / g, 0.0)
    h_full = np.fft.irfft(d, n=nfft)  # symmetric around index 0
    h_c = np.concatenate([h_full[nfft // 2 :], h_full[: nfft // 2]])
    center = nfft // 2
    if taps is None:
        mag = np.abs(h_c)
        thresh = mag.max() * 1e-6
        above = np.nonzero(mag > thresh)[0]
        half = int(
            max(center - above[0], above[-1] - center, 4)
        )
        taps = min(2 * half + 1, 4095)
    if taps % 2 == 0:
        taps += 1
    half = taps // 2
    h = h_c[center - half : center + half + 1].copy()
    # no taper: the target response is smooth, so the frequency-sampled
    # impulse response decays below 1e-6 before truncation and plain
    # truncation keeps the band error ~1e-6 (a Kaiser taper would bias
    # the passband by ~1e-2). Renormalize DC to the exact target gain.
    dc_target = d[0]
    s = h.sum()
    if s != 0:
        h *= dc_target / s
    return h.astype(np.float32)


@functools.lru_cache(maxsize=64)
def design_cascade(
    fs_in: float,
    ratio: int,
    corner: float,
    order: int = 4,
    last_taps: int | None = None,
) -> CascadePlan:
    """Design the multistage decimator for ``fs_in -> fs_in/ratio`` with
    composite response ``butter2_mag(f, corner, order)``.

    The retained band is [0, 0.5*fs_in/ratio] (the output Nyquist);
    guard stages protect it from aliasing at >= 80 dB, and the last
    stage shapes the composite response to the Butterworth-squared
    target of the reference engine (lf_das.py:223).
    """
    factors = factor_ratio(ratio)
    f_out = fs_in / ratio
    f_keep = 0.5 * f_out
    stages = []
    fs = fs_in
    guard_list = []
    if len(factors) > 1:
        for R in factors[:-1]:
            h = _guard_stage_taps(fs, R, f_keep)
            stages.append((R, h))
            guard_list.append((h, fs))
            fs /= R
    R_last = factors[-1] if factors else 1

    def guard_resp(freqs):
        resp = np.ones_like(np.asarray(freqs, np.float64))
        for taps, fs_i in guard_list:
            resp = resp * _stage_response(taps, fs_i, freqs)
        return resp

    h_last = _matched_last_stage(fs, corner, order, guard_resp, last_taps)
    stages.append((R_last, h_last))

    delay = 0
    prod = 1
    for R, h in stages:
        delay += (len(h) // 2) * prod
        prod *= R
    assert prod == ratio
    return CascadePlan(
        stages=tuple((int(R), h) for R, h in stages),
        ratio=int(ratio),
        delay=int(delay),
        fs_in=float(fs_in),
        corner=float(corner),
        order=int(order),
    )


# ---------------------------------------------------------------------------
# application


# the XLA stages' einsum precision on a TPU: "highest" is six bf16 MXU
# passes, "high" three.  On a v5e (PR 21 chip run,
# tools/xla_precision_probe.py, 80,000 x 2,048 window) the four-stage
# chain read 3.02e-7 vs float64 at "highest" and 5.21e-5 at "high" (half
# the 1e-4 design tolerance), at 10.00 vs 9.89 ms: the stages are
# HBM-bound, so the extra passes cost ~1 %
_XLA_STAGE_PRECISION = "highest"


def _polyphase_stage_xla(x, hb, R, n_out):
    """One causal decimating stage on (T, C) data:
    ``y[k, c] = sum_j h[j] x[k*R + j, c]`` for k in [0, n_out).

    hb is the (B, R) frame-blocked tap matrix (zero-padded taps).

    Phase-contracted formulation: one contraction over the tap phase
    ``r`` for ALL frames at once (``u[b, m] = <x frame m, hb[b]>``),
    then a B-term shifted sum over the small decimated frames.  The
    naive form (B shifted einsums over the full-rate input) re-reads
    the input B times; this reads it once plus ~B/R of it for ``u`` —
    the streaming stage is memory-bound at production widths, and the
    rewrite measures ~3x faster on stage 0 of the 1 kHz flagship plan
    at 10k channels on CPU (PERF.md §6, PR 7).  The b-loop accumulates
    in the same order as the naive form, and each b-term is the same
    dot over ``r``, so per-element float arithmetic is unchanged in
    structure (the stage remains deterministic, and channel columns are
    independent, which is what makes channel sharding bit-exact on the
    CPU backend; on a v5e 2x2 the sharded stream read 1.50 ulps from
    single device, PERF.md §7).

    The contraction runs at ``_XLA_STAGE_PRECISION``: at the default
    precision a TPU multiplies f32 operands in one bf16 pass, ~5e-3
    relative error on the chip (caught by LFProc's first-window
    cross-check in PR 21), far outside the cascade's 1e-4 design
    tolerance.  The CPU backend computes f32 either way.
    """
    import jax.numpy as jnp

    B = hb.shape[0]
    need = (n_out + B) * R
    T = x.shape[0]
    if need > T:
        x = jnp.pad(x, ((0, need - T), (0, 0)))
    xr = x[:need].reshape(n_out + B, R, x.shape[1])
    u = jnp.einsum("mrc,br->bmc", xr, hb, precision=_XLA_STAGE_PRECISION)
    y = jnp.zeros((n_out, x.shape[1]), x.dtype)
    for b in range(B):
        y = y + u[b, b : b + n_out]
    return y


def _block_taps(h: np.ndarray, R: int) -> np.ndarray:
    L = len(h)
    B = -(-L // R)
    hp = np.zeros(B * R, np.float32)
    hp[:L] = h
    return hp.reshape(B, R)


def _stage_counts(plan: CascadePlan, n_out: int) -> list[int]:
    """Required output count per stage: a stage producing n outputs
    with B tap-frames consumes (n + B) * R input samples."""
    counts = [n_out]
    for R, h in reversed(plan.stages[1:]):
        counts.append((counts[-1] + (-(-len(h) // R))) * R)
    counts.reverse()
    return counts


def cascade_input_need(plan: CascadePlan, n_out: int) -> int:
    """Input rows the cascade minimally consumes to emit ``n_out``
    outputs (after the delay pre-shift), with every stage on the XLA
    path: the first stage's ``(count + B) * R``. Pallas-aware sizing
    (grid rounding included) is :func:`chain_layout`'s ``rows``."""
    counts = _stage_counts(plan, int(n_out))
    R0, h0 = plan.stages[0]
    B0 = -(-len(h0) // int(R0))
    return (counts[0] + B0) * int(R0)


def _pallas_stage_ok(k: int, R: int, n_ch: int, n_frames: int) -> bool:
    """Pallas only for stages that are big enough to matter: small
    stages measure slower under the kernel (grid overheads dominate)
    AND their grid rounding — the kernel's quantum is ``_KB`` output
    frames (``_P`` parallel ``_SB``-frame sub-blocks per step) —
    inflates every upstream stage's output count through the chain
    layout. Thresholds from the round-4 v5e measurements (not
    re-measured on today's code): >= 2^24 elements touched and a full
    first grid step. Taps must
    also fit the kernel's sub-block; very long single-stage plans
    (possible via the public design API) take the XLA polyphase path
    instead of erroring.

    ``TPUDAS_PALLAS_MIN_ELEMS`` overrides the element threshold so a
    measured crossover (``tools/retune_stage_ok.py``) can be applied
    on a live chip without a code edit — and without a process
    restart: callers key their caches on :func:`knob_fingerprint`."""
    import os

    from tpudas.ops.pallas_fir import _SB, kernel_quantum

    raw = os.environ.get("TPUDAS_PALLAS_MIN_ELEMS", "").strip()
    min_elems = int(raw) if raw else (1 << 24)
    return (
        k * R * n_ch >= min_elems
        and k >= kernel_quantum()
        and n_frames <= _SB
    )


def resolve_cascade_engine(engine: str = "auto") -> str:
    """'auto' -> 'pallas' on the TPU backend, 'xla' elsewhere."""
    if engine == "auto":
        import jax

        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return engine


def chain_layout(
    plan: CascadePlan, n_out: int, n_ch: int, engine: str = "auto"
):
    """Per-stage execution layout: ``((engine_i, k_i), ...), rows``.

    ``k_i`` is the output count stage ``i`` emits and ``engine_i`` the
    kernel it runs under ('pallas'/'xla'); ``rows`` is the exact input
    length the first stage consumes. Sized back to front so every
    stage's input is exactly what its predecessor emits: an input of
    exactly ``rows`` flows through the whole cascade with ZERO internal
    padding (an internal ``jnp.pad`` materializes a full copy — a
    whole extra HBM round-trip at the full-rate stage). Shorter inputs
    still work (stages zero-pad, same numerics), they just pay the
    copy. This is also the single source of truth for which engine
    each stage actually runs (LFProc observability, the bench)."""
    engine = resolve_cascade_engine(engine)
    shapes = tuple(
        (int(R), -(-len(h) // int(R))) for R, h in plan.stages
    )
    return _layout_for(
        shapes, int(n_out), int(n_ch), engine, knob_fingerprint()
    )


def stage_engines(
    plan: CascadePlan, n_out: int, n_ch: int, engine: str = "auto"
) -> list[str]:
    """Which engine each stage will actually run under — the same
    decision :func:`_build_cascade_fn` makes at trace time, exposed so
    callers (LFProc observability, the bench) can report ground truth
    instead of the configured intent."""
    return [e for e, _ in chain_layout(plan, n_out, n_ch, engine)[0]]


def _check_quantized(x, qscale):
    """Shared guard for every quantized-ingest entry point: ``qscale``
    must accompany exactly an int16 payload."""
    import jax.numpy as jnp

    if qscale is not None and x.dtype != jnp.int16:
        raise ValueError(f"qscale given but data dtype is {x.dtype}")


def shift_to_phase(x, phase: int, delay: int, axis: int = 0):
    """Align a stream so causal cascade output ``k`` lands on
    zero-phase full-rate index ``phase + k*ratio``: drop
    ``phase - delay`` leading rows, or left-pad when the requested
    phase precedes the filter delay.  Single source for every cascade
    entry point (single-device, time-sharded, window-batched)."""
    import jax.numpy as jnp

    shift = int(phase) - int(delay)
    if shift >= 0:
        idx = (slice(None),) * axis + (slice(shift, None),)
        return x[idx]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (-shift, 0)
    return jnp.pad(x, pad)


def _apply_cascade_stages(x, blocked, n_out, use_pallas, interpret,
                          qscale=None):
    """Traceable cascade body shared by the jit path and the shard_map
    (mesh) paths: x (T_local, C_local) -> (n_out, C_local).

    Per-stage engine/size decisions come from :func:`chain_layout` on
    the traced shape, so emitted sizes line up stage to stage (pad-free
    when the input is pre-sized to the layout's ``rows``).

    ``qscale`` (a TRACED scalar — one compiled executable serves every
    scale) marks a quantized int16 ingest window: the first stage
    reads the raw int16 payload (half the HBM bytes) and dequantizes
    inside its kernel.  On the XLA path that is a fused cast*scale —
    bit-identical to decoding first.  On the Pallas path the kernel
    casts raw in VMEM and, the FIR being linear, the scale multiplies
    the stage's decimated (R-times smaller) output.
    """
    import jax.numpy as jnp

    engine = "pallas" if use_pallas else "xla"
    layout, _rows = _layout_for(
        tuple((int(R), int(hb.shape[0])) for R, hb in blocked),
        int(n_out),
        int(x.shape[1]),
        engine,
        knob_fingerprint(),
    )
    first_pallas = layout[0][0] == "pallas" if layout else False
    quantized = qscale is not None and x.dtype == jnp.int16
    scale0 = None
    if quantized:
        if first_pallas:
            scale0 = jnp.float32(qscale)  # applied to stage-0 output
        else:
            x = x.astype(jnp.float32) * jnp.float32(qscale)
    else:
        x = x.astype(jnp.float32)
    for i, ((R, hb), (eng, k)) in enumerate(zip(blocked, layout)):
        if eng == "pallas":
            from tpudas.ops.pallas_fir import fir_decimate_pallas

            x = fir_decimate_pallas(x, hb, R, n_out=k, interpret=interpret)
        else:
            x = _polyphase_stage_xla(x, hb, R, k)
        if i == 0 and scale0 is not None:
            x = x * scale0
    return x


@functools.lru_cache(maxsize=256)
def _layout_for(stage_shapes, n_out, n_ch, engine, knobs=()):
    """chain_layout core on hashable (R, B) pairs: returns
    ``(((engine_i, k_i), ...), rows)``.  ``knobs`` is the env
    fingerprint (:func:`knob_fingerprint`) — unused in the body (the
    threshold/quantum reads go to the live env) but REQUIRED in the
    cache key so a mid-process retune recomputes the layout."""
    from tpudas.ops.pallas_fir import stage_input_rows

    k = int(n_out)
    ks: list = [None] * len(stage_shapes)
    for i in range(len(stage_shapes) - 1, -1, -1):
        R, B = stage_shapes[i]
        use = engine == "pallas" and _pallas_stage_ok(k, R, n_ch, B)
        ks[i] = ("pallas" if use else "xla", k)
        k = stage_input_rows(B, R, k) if use else (k + B) * R
    return tuple(ks), k


def _blocked_taps(plan: CascadePlan):
    """Frame-blocked taps as HOST numpy arrays: the apply body may be
    traced inside an outer jit (e.g. a benchmark step), and a device
    constant created during one trace must not be cached into another
    (UnexpectedTracerError) — numpy constants are staged per-trace."""
    return [(R, _block_taps(np.asarray(h), R)) for R, h in plan.stages]


def _clear_cascade_caches():
    """Drop every compiled-cascade cache (single-device, streaming,
    fused, time-sharded, window-batched) so the next call retraces.
    Env knob changes no longer need this — every cache keys on
    :func:`knob_fingerprint` — but benches/tests that monkeypatch
    resolution functions themselves still do."""
    _build_cascade_fn.cache_clear()
    _build_stream_cascade_fn.cache_clear()
    _build_fused_stream_fn.cache_clear()
    _build_stacked_stream_fn.cache_clear()
    _layout_for.cache_clear()
    try:
        from tpudas.parallel.pipeline import _build_sharded_cascade_fn

        _build_sharded_cascade_fn.cache_clear()
    except Exception:
        pass
    try:
        from tpudas.parallel.batch import _build_batched_cascade_fn

        _build_batched_cascade_fn.cache_clear()
    except Exception:
        pass


def _pallas_interpret() -> bool:
    # interpret mode on the CPU backend only, so the same code path is
    # testable on the CPU mesh (SURVEY.md §4); on any other backend
    # the kernel is compiled by Mosaic
    import jax

    return jax.default_backend() == "cpu"


@functools.lru_cache(maxsize=64)
def _build_cascade_fn(plan: CascadePlan, n_out: int, engine: str, mesh=None,
                      ch_axis="ch", quantized=False, knobs=()):
    """jit-compiled causal cascade: x (T, C) -> (n_out, C); with
    ``quantized`` the signature is (x_int16, scale) and the scale is a
    TRACED operand (the compile caches on the bool, not the value —
    spools with differing quantization scales share one executable).

    With ``mesh``, the cascade runs under ``shard_map`` with channels
    split over the mesh's ``ch_axis`` — the zero-communication layout
    (SURVEY.md §2.4): every stage is channel-independent, so each
    device runs the full cascade (including the Pallas kernel, which
    GSPMD could not partition through a plain jit) on its local
    channel block.
    """
    import jax

    blocked = _blocked_taps(plan)
    use_pallas = engine == "pallas"
    interpret = _pallas_interpret() if use_pallas else False

    if quantized:
        def fn(x, scale):
            return _apply_cascade_stages(
                x, blocked, n_out, use_pallas, interpret, qscale=scale
            )
    else:
        def fn(x):
            return _apply_cascade_stages(
                x, blocked, n_out, use_pallas, interpret
            )

    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        spec = P(None, ch_axis)
        in_specs = (spec, P()) if quantized else (spec,)
        body = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=spec,
            check_vma=False,
        )
        return jax.jit(body)
    return jax.jit(fn)


def cascade_decimate(
    x, plan: CascadePlan, phase: int, n_out: int, engine="auto",
    mesh=None, ch_axis="ch", qscale=None,
):
    """Zero-phase filtered + decimated samples of ``x`` (T, C).

    Output ``k`` equals the composite zero-phase filter of ``x``
    evaluated at full-rate index ``phase + k*plan.ratio`` — exactly the
    samples the reference's ``pass_filter → interpolate`` pipeline
    (lf_das.py:223-225) lands on when the target grid is sample-aligned.
    ``phase`` may be any non-negative int; edge regions (within
    ``plan.delay`` of either end) carry the usual truncation artifacts,
    which the overlap-save scheduler trims (SURVEY.md §3.1).

    With ``mesh``, channels are split over the mesh's ``ch_axis``
    (zero-communication sharding; C is zero-padded to a multiple of the
    axis size and trimmed after).

    ``qscale`` accepts a raw int16 quantized window (tdas ingest fast
    path): the H2D transfer and the first stage's HBM read stay int16
    and dequantization happens inside the first kernel — equivalent to
    ``cascade_decimate(x.astype(f32) * qscale, ...)``.  The scale is a
    traced operand: windows with different scales share one compile.
    """
    import jax.numpy as jnp

    from tpudas.obs.trace import span

    engine = resolve_cascade_engine(engine)
    x = jnp.asarray(x)
    _check_quantized(x, qscale)
    quantized = qscale is not None
    x2 = shift_to_phase(x, phase, plan.delay)
    args = (x2, jnp.float32(qscale)) if quantized else (x2,)
    if mesh is None:
        fn = _build_cascade_fn(
            plan, int(n_out), engine, quantized=quantized,
            knobs=knob_fingerprint(),
        )
        # dispatch-side timing (async backends sync at the caller's
        # np.asarray; the synced wall lands in window device metrics)
        with span("op.cascade", rows=int(x.shape[0]), engine=engine):
            return fn(*args)
    nc = mesh.shape[ch_axis]
    C = x2.shape[1]
    pad_c = -C % nc
    if pad_c:
        x2 = jnp.pad(x2, ((0, 0), (0, pad_c)))
        args = (x2, *args[1:])
    fn = _build_cascade_fn(plan, int(n_out), engine, mesh, ch_axis,
                           quantized=quantized, knobs=knob_fingerprint())
    out = fn(*args)
    return out[:, :C] if pad_c else out


# ---------------------------------------------------------------------------
# stateful streaming: carry per-stage filter state across blocks
#
# The batch entry points above re-derive every output from a window
# that includes the filter's full edge support — a caller processing a
# live stream must therefore re-read ~2x the edge of FULL-RATE data
# per round just to rebuild transient state it already computed.  The
# streaming form below instead carries each stage's trailing input
# samples as an explicit O(1) pytree: every input sample flows through
# every stage exactly once.
#
# Semantics (the contract tests/test_stream_state.py pins): feed the
# stream in blocks whose length is a multiple of ``plan.ratio``.  With
# ``X`` the concatenation of everything fed so far, the concatenated
# outputs satisfy
#
#     y_stream[m] == cascade_decimate(X, plan, phase=plan.delay, .)[m - W]
#
# for m >= W := stream_warmup_outputs(plan) — i.e. after the warm-up
# (the first W outputs read the zero-initialized carry and are
# discarded by callers), streamed output m is the zero-phase filtered
# value of the stream at full-rate index (m - W) * ratio + delay, and
# every kept output reads only samples that have already arrived (the
# emission lag past an output's center is exactly the filter's causal
# support, delay full-rate samples).
#
# Per-stage carry: stage i keeps its last P_i input samples, with
# P_i >= len(taps_i) - R_i so each new block's outputs have their full
# look-back.  The composite full-rate lag D = sum_i P_i * prod_{j<i}
# R_j telescopes to receptive_field - ratio at the minimal sizes;
# stage 0's carry absorbs the padding that rounds D up to a multiple
# of ratio so the streamed grid stays on the decimated grid
# (W = D / ratio).


@functools.lru_cache(maxsize=256)
def stream_carry_sizes(plan: CascadePlan) -> tuple:
    """Per-stage carried trailing samples (at each stage's own input
    rate).  Stage 0 includes the alignment pad that makes the composite
    lag a whole number of output samples."""
    sizes = [max(len(h) - int(R), 0) for R, h in plan.stages]
    d = 0
    prod = 1
    for p, (R, _h) in zip(sizes, plan.stages):
        d += p * prod
        prod *= int(R)
    sizes[0] += (-d) % plan.ratio
    return tuple(sizes)


def stream_warmup_outputs(plan: CascadePlan) -> int:
    """Outputs to discard after a zero-initialized carry (the composite
    stream lag in output samples)."""
    d = 0
    prod = 1
    for p, (R, _h) in zip(stream_carry_sizes(plan), plan.stages):
        d += p * prod
        prod *= int(R)
    assert d % plan.ratio == 0
    return d // plan.ratio


def cascade_stream_init(plan: CascadePlan, n_ch: int) -> tuple:
    """Zero carry pytree for :func:`cascade_decimate_stream`."""
    return tuple(
        np.zeros((p, int(n_ch)), np.float32)
        for p in stream_carry_sizes(plan)
    )


def _stream_stage_pallas(plan: CascadePlan, T: int, n_ch: int,
                         engine: str) -> tuple:
    """Static per-stage engine decisions for a stream block of T
    full-rate rows (True = the Pallas kernel runs that stage).

    Gated on ``TPUDAS_STREAM_PALLAS=1`` (off by default, read at
    build time): a stream block's carry-extended input is never the
    kernel's exact ``stage_input_rows`` sizing, so every Pallas stage
    would pay the internal pad's full input copy per block — whether
    that still beats the XLA formulation at stream block sizes is a
    measure-on-silicon question, and until it is measured the stream
    step stays on the proven path.  The batch entry points are
    unaffected."""
    import os

    if os.environ.get("TPUDAS_STREAM_PALLAS", "0") != "1":
        return tuple(False for _ in plan.stages)
    use = []
    t = int(T)
    for R, h in plan.stages:
        k = t // int(R)
        b = -(-len(h) // int(R))
        use.append(
            engine == "pallas" and _pallas_stage_ok(k, int(R), n_ch, b)
        )
        t = k
    return tuple(use)


def stream_stage_engines(plan: CascadePlan, T: int, n_ch: int,
                         engine: str = "auto") -> list:
    """Ground truth of which engine each stage runs under for a stream
    block of ``T`` rows — the streaming analogue of
    :func:`stage_engines` (same observability contract).  Under a
    fused variant every stage runs inside the one fused kernel, so
    every entry is the variant name."""
    engine = resolve_stream_engine(engine, plan, T, n_ch)
    if engine.startswith("fused"):
        return [engine for _ in plan.stages]
    return [
        "pallas" if u else "xla"
        for u in _stream_stage_pallas(plan, T, n_ch, engine)
    ]


# ---------------------------------------------------------------------------
# fused streaming (ISSUE 10): the whole cascade as ONE kernel.
#
# The per-stage stream step above materializes every stage's output in
# HBM before the next stage consumes it — at 10k channels that is
# ~T/R0 * C * 4 bytes written AND re-read per block for stage 1 alone.
# The carry is an SSM-style O(1) autoregressive cache (PAPERS.md
# "Compiler-First State Space Duality"), and the fused formulation
# treats it as one: a single scan (XLA) or Pallas grid walk keeps
# EVERY stage's trailing-sample state live across chunk steps and
# emits only the final decimated output.  Per-stage intermediates
# exist only at chunk granularity — sized to stay cache/VMEM-resident
# — so the full-rate input is read once and nothing else at full rate
# touches HBM.
#
# The carry pytree layout is IDENTICAL to the per-stage engines
# (stream_carry_sizes), so a stream can cross between
# cascade <-> fused mid-run (tests/test_fused.py pins resume in both
# directions), and the fused-XLA scan replays the per-stage
# arithmetic chunk-by-chunk — byte-identical outputs AND carry.


def fused_min_elems() -> int:
    """Block elements (T*C) below which a ``fused`` request falls back
    to the per-stage chain: per-chunk scan/grid overheads dominate on
    small blocks.  Default from the measured CPU crossover
    (tools/retune_stage_ok.py --fused, PERF.md §6 PR 10);
    ``TPUDAS_FUSED_MIN_ELEMS`` applies a retune live (the dispatch
    caches key on :func:`knob_fingerprint`)."""
    import os

    raw = os.environ.get("TPUDAS_FUSED_MIN_ELEMS", "").strip()
    return int(raw) if raw else (1 << 23)


def fused_chunk_outputs(plan: CascadePlan, n_out: int) -> int:
    """Output samples per fused chunk step: the largest divisor of the
    block's output count not exceeding the target
    (``TPUDAS_FUSED_CHUNK``, default sized so one full-rate chunk is
    ~8192 rows — small enough that every stage's chunk intermediate
    stays cache/VMEM resident, large enough that per-chunk overhead
    amortizes).  A divisor (not a remainder split) keeps the scan a
    single static shape."""
    import os

    raw = os.environ.get("TPUDAS_FUSED_CHUNK", "").strip()
    target = int(raw) if raw else max(1, 8192 // plan.ratio)
    n_out = int(n_out)
    target = max(1, min(target, n_out))
    best = 1
    for d in range(1, n_out + 1):
        if d > target:
            break
        if n_out % d == 0:
            best = d
    return best


def fused_intermediate_bytes(plan: CascadePlan, T: int, n_ch: int) -> int:
    """HBM-traffic proxy: bytes of per-stage intermediates the
    per-stage chain materializes for a ``(T, n_ch)`` block that the
    fused formulation never writes (each is also re-READ by the next
    stage, so the eliminated traffic is ~2x this)."""
    rows = int(T)
    total = 0
    for R, _h in plan.stages[:-1]:
        rows //= int(R)
        total += rows * int(n_ch) * 4
    return total


def resolve_stream_engine(engine: str, plan: CascadePlan = None,
                          T: int = 0, n_ch: int = 0) -> str:
    """Resolve a stream-dispatch engine literal to what actually runs:
    ``auto`` -> the per-stage chain with backend routing; ``fused`` ->
    ``fused-pallas`` on TPU backends / ``fused-xla`` elsewhere when
    the block clears :func:`fused_min_elems` and the plan fits the
    kernel, else the per-stage chain (the measured-crossover
    threshold, same contract as ``_pallas_stage_ok``); explicit
    ``fused-xla``/``fused-pallas`` are forced."""
    if engine not in STREAM_ENGINES:
        raise ValueError(
            f"stream engine must be one of {STREAM_ENGINES}, got "
            f"{engine!r}"
        )
    if engine in ("auto", "pallas", "xla"):
        return resolve_cascade_engine(engine)
    if engine == "fused":
        if plan is not None and int(T) * int(n_ch) < fused_min_elems():
            return resolve_cascade_engine("auto")
        import jax

        engine = (
            "fused-pallas" if jax.default_backend() == "tpu" else "fused-xla"
        )
    if engine == "fused-pallas" and plan is not None:
        from tpudas.ops.pallas_fir import fused_taps_fit

        chunk = fused_chunk_outputs(
            plan, max(int(T) // plan.ratio, 1)
        )
        if not fused_taps_fit(plan.stages, chunk):
            return "fused-xla"
    return engine


@functools.lru_cache(maxsize=128)
def _build_fused_stream_fn(plan: CascadePlan, T: int, n_ch: int,
                           variant: str, mesh=None, ch_axis="ch",
                           knobs=(), quantized=False):
    """jit-compiled FUSED stateful step: (x (T, C), carry) ->
    (y (T/ratio, C), new_carry) with every stage state threaded
    through one program — no per-stage HBM intermediates.

    ``variant`` is ``fused-xla`` (a ``lax.scan`` over chunk steps
    whose body replays the per-stage polyphase arithmetic — chunk
    intermediates live in the scan body, and outputs/carry are
    byte-identical to the per-stage chain) or ``fused-pallas`` (the
    pallas_fir v3 kernel: stage tails in VMEM scratch across the
    block's grid steps).  Donation, mesh wrapping, and the sharded
    carry contract mirror :func:`_build_stream_cascade_fn`; ``knobs``
    keys the cache on the live env fingerprint.

    ``quantized`` compiles the raw-int16 ingest variant: the step
    takes a traced ``qscale`` scalar and the dequantizing
    ``cast * scale`` is the program's first op (the stream analogue
    of the batch path's in-kernel dequant) — the block crosses H2D
    and is read from HBM as int16, half the bytes, no host-side f32
    copy.  Carry leaves stay float32, so the quantized and float
    variants share one carry layout (resume/crossover-safe)."""
    import jax
    import jax.numpy as jnp

    blocked = _blocked_taps(plan)
    sizes = stream_carry_sizes(plan)
    n_out_total = T // plan.ratio
    chunk_out = fused_chunk_outputs(plan, n_out_total)
    chunk_in = chunk_out * plan.ratio
    n_steps = n_out_total // chunk_out

    if variant == "fused-pallas":
        from tpudas.ops.pallas_fir import fused_cascade_pallas

        stages_np = tuple(
            (int(R), np.asarray(h, np.float32)) for R, h in plan.stages
        )
        interpret = _pallas_interpret()

        def core(x, carry):
            return fused_cascade_pallas(
                x, tuple(carry), stages_np, sizes,
                chunk_out, interpret=interpret,
            )

    else:

        def step(bufs, xc):
            y = xc
            new = []
            for (R, hb), p, buf in zip(blocked, sizes, bufs):
                xi = jnp.concatenate([buf, y], axis=0) if p else y
                k = y.shape[0] // R
                new.append(xi[xi.shape[0] - p:])
                y = _polyphase_stage_xla(xi, hb, R, k)
            return tuple(new), y

        def core(x, carry):
            if n_steps <= 1:
                bufs, y = step(tuple(carry), x)
                return y, bufs
            xs = x.reshape(n_steps, chunk_in, x.shape[1])
            bufs, ys = jax.lax.scan(step, tuple(carry), xs)
            return ys.reshape(n_out_total, x.shape[1]), bufs

    if quantized:
        def fn(x, carry, qscale):
            return core(x.astype(jnp.float32) * qscale, carry)
    else:
        def fn(x, carry):
            return core(x.astype(jnp.float32), carry)

    body = fn
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        spec = P(None, ch_axis)
        carry_specs = tuple(spec for _ in sizes)
        in_specs = (
            (spec, carry_specs, P()) if quantized
            else (spec, carry_specs)
        )
        body = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(spec, carry_specs),
            check_vma=False,
        )
    # the carry is donated (each leaf aliases its updated leaf); the
    # input block has no same-shaped output to alias, so it is not
    donate = (1,) if jax.default_backend() != "cpu" else ()
    return jax.jit(body, donate_argnums=donate)


def _count_fused(plan: CascadePlan, T: int, n_ch: int,
                 variant: str) -> None:
    """Per-dispatch fused-path observability: rounds by variant and
    the HBM-traffic proxy (intermediate bytes the per-stage chain
    would have materialized) — tools/kernel_bench.py reads both."""
    from tpudas.obs.registry import get_registry

    reg = get_registry()
    reg.counter(
        "tpudas_fir_fused_rounds_total",
        "fused cascade stream steps dispatched",
        labelnames=("engine",),
    ).inc(engine=variant)
    reg.counter(
        "tpudas_fir_fused_intermediate_bytes_saved_total",
        "per-stage full-rate HBM intermediate bytes the fused kernel "
        "did not materialize",
    ).inc(fused_intermediate_bytes(plan, T, n_ch))


@functools.lru_cache(maxsize=128)
def _build_stream_cascade_fn(plan: CascadePlan, T: int, n_ch: int,
                             engine: str, mesh=None, ch_axis="ch",
                             knobs=(), quantized=False):
    """jit-compiled stateful step: (x (T, C), carry) -> (y (T/ratio, C),
    new_carry).  The carry is donated on accelerator backends, so
    steady-state streaming does not double-buffer the carry update.

    With ``mesh``, the step runs under ``shard_map`` with channels
    split over the mesh's ``ch_axis`` — the zero-communication layout:
    every stage (and its carry leaf) is channel-independent, so each
    device runs the identical per-stage loop on its local channel
    block and the sharded output/carry are byte-identical to the
    single-device step.  ``n_ch`` is then the PADDED global channel
    count (a multiple of the shard count; see
    tpudas.parallel.sharding's pad-and-mask layout)."""
    import jax
    import jax.numpy as jnp

    blocked = _blocked_taps(plan)
    sizes = stream_carry_sizes(plan)
    # Pallas thresholds see what one device actually traces: the
    # LOCAL channel count under a mesh
    n_ch_local = (
        n_ch // int(mesh.shape[ch_axis]) if mesh is not None else n_ch
    )
    use_pallas = _stream_stage_pallas(plan, T, n_ch_local, engine)
    interpret = _pallas_interpret() if any(use_pallas) else False

    def core(x, carry):
        new_carry = []
        for (R, hb), p, pall, buf in zip(blocked, sizes, use_pallas, carry):
            xc = jnp.concatenate([buf, x], axis=0) if p else x
            k = x.shape[0] // R
            if pall:
                from tpudas.ops.pallas_fir import fir_decimate_pallas

                y = fir_decimate_pallas(
                    xc, hb, R, n_out=k, interpret=interpret
                )
            else:
                y = _polyphase_stage_xla(xc, hb, R, k)
            new_carry.append(xc[xc.shape[0] - p:])
            x = y
        return x, tuple(new_carry)

    if quantized:
        # raw-int16 ingest variant: the dequantizing cast * scale is
        # the first traced op (in-kernel dequant — the batch path's
        # contract), with the scale a traced scalar so every window
        # scale shares one compile
        def fn(x, carry, qscale):
            return core(x.astype(jnp.float32) * qscale, carry)
    else:
        def fn(x, carry):
            return core(x.astype(jnp.float32), carry)

    body = fn
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        spec = P(None, ch_axis)
        carry_specs = tuple(spec for _ in sizes)
        in_specs = (
            (spec, carry_specs, P()) if quantized
            else (spec, carry_specs)
        )
        body = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(spec, carry_specs),
            check_vma=False,
        )
    # the carry is donated (each leaf aliases its updated leaf); the
    # input block has no same-shaped output to alias, so it is not
    donate = (1,) if jax.default_backend() != "cpu" else ()
    return jax.jit(body, donate_argnums=donate)


def cascade_decimate_stream(x, carry, plan: CascadePlan, engine="auto",
                            mesh=None, ch_axis="ch", qscale=None):
    """One stateful streaming step of the cascade.

    x: (T, C) float32 block, T a multiple of ``plan.ratio``; ``carry``
    from :func:`cascade_stream_init` or a previous step.  Returns
    ``(y (T/ratio, C), new_carry)`` — see the streamed-output contract
    in the section comment above.  The previous carry may not be
    reused after the call (it is donated on accelerators).

    With ``mesh``, channels are split over the mesh's ``ch_axis``
    (zero-communication shard_map; pad-and-mask for non-divisible
    counts) and the returned carry leaves are SHARDED device arrays —
    feed them back verbatim and they stay resident on the mesh with no
    host round-trip; ``y`` is trimmed to the logical channel count.
    The sharded step is byte-identical to the single-device step
    (channel columns are independent; tests/test_parallel.py pins it).

    ``engine`` accepts every :data:`STREAM_ENGINES` literal: the
    per-stage chain (``auto``/``pallas``/``xla``) or the fused
    single-kernel formulations (``fused`` resolves by backend and the
    measured size threshold; ``fused-xla``/``fused-pallas`` force a
    variant).  The carry layout is shared, so the engine may change
    between steps of one stream (cascade <-> fused crossover).

    ``qscale`` accepts a raw int16 quantized block (tdas ingest fast
    path): the H2D transfer and the first stage's HBM read stay int16
    and dequantization happens inside the step — bit-identical to
    feeding ``x.astype(f32) * qscale``.  The scale is a traced
    operand (one compile serves every scale); the carry stays float32
    either way.
    """
    import jax.numpy as jnp

    _check_quantized(x, qscale)
    quantized = qscale is not None
    T = int(np.shape(x)[0])
    n_ch = int(np.shape(x)[1])
    # size thresholds see what one device actually traces: the LOCAL
    # channel count under a mesh (same contract as _pallas_stage_ok)
    n_ch_res = (
        n_ch if mesh is None
        else -(-n_ch // int(mesh.shape[ch_axis]))
    )
    engine = resolve_stream_engine(engine, plan, T, n_ch_res)
    fused = engine.startswith("fused")
    x = jnp.asarray(x) if mesh is None else x
    if T % plan.ratio:
        raise ValueError(
            f"stream block length {T} is not a multiple of the "
            f"decimation ratio {plan.ratio}"
        )
    sizes = stream_carry_sizes(plan)
    if len(carry) != len(sizes) or any(
        int(np.shape(b)[0]) != p for b, p in zip(carry, sizes)
    ):
        raise ValueError(
            "carry does not match this plan's stream_carry_sizes "
            f"({[int(np.shape(b)[0]) for b in carry]} vs {list(sizes)})"
        )
    from tpudas.obs import devprof
    from tpudas.obs.trace import span

    knobs = knob_fingerprint()
    if mesh is None:
        if fused:
            fn = _build_fused_stream_fn(plan, T, n_ch, engine,
                                        knobs=knobs, quantized=quantized)
            sp = span("fir.fused", rows=T, engine=engine)
        else:
            fn = _build_stream_cascade_fn(plan, T, n_ch, engine,
                                          knobs=knobs, quantized=quantized)
            sp = span("op.cascade_stream", rows=T, engine=engine)
        shape_key = (T, n_ch, engine, int(quantized), _plan_tag(plan))
        devprof.note_kernel("cascade", shape_key, knobs)
        args = (jnp.float32(qscale),) if quantized else ()
        bufs = tuple(jnp.asarray(b, jnp.float32) for b in carry)
        cost = devprof.kernel_cost(
            "cascade", shape_key, fn, (x, bufs) + args
        )
        t0 = _time.perf_counter()
        with sp:
            out = fn(x, bufs, *args)
        devprof.note_launch(engine, t0, out, cost=cost)
        if fused:
            _count_fused(plan, T, n_ch, engine)
        return out
    from tpudas.parallel.sharding import (
        channel_pad,
        place_block,
        place_carry_leaves,
    )

    C = n_ch
    Cp = C + channel_pad(C, mesh, ch_axis)
    if any(int(np.shape(b)[1]) not in (C, Cp) for b in carry):
        raise ValueError(
            f"stream carry channel width {[np.shape(b) for b in carry]} "
            f"matches neither the block ({C}) nor the padded shard "
            f"layout ({Cp})"
        )
    xs = place_block(x, mesh, ch_axis, keep_dtype=quantized)
    if any(int(np.shape(b)[1]) != Cp for b in carry):
        # first call after open/resume: the leaves are host arrays at
        # the logical width — pad-and-place them once; every later
        # round feeds back the sharded leaves this step returns
        carry = place_carry_leaves(carry, mesh, ch_axis)
    if fused:
        fn = _build_fused_stream_fn(plan, T, Cp, engine, mesh, ch_axis,
                                    knobs=knobs, quantized=quantized)
        sp = span("fir.fused", rows=T, engine=engine,
                  shards=int(mesh.shape[ch_axis]))
    else:
        fn = _build_stream_cascade_fn(plan, T, Cp, engine, mesh, ch_axis,
                                      knobs=knobs, quantized=quantized)
        sp = span("op.cascade_stream", rows=T, engine=engine,
                  shards=int(mesh.shape[ch_axis]))
    shape_key = (
        T, Cp, engine, int(quantized), _plan_tag(plan),
        int(mesh.shape[ch_axis]),
    )
    devprof.note_kernel("cascade", shape_key, knobs)
    args = (jnp.float32(qscale),) if quantized else ()
    cost = devprof.kernel_cost(
        "cascade", shape_key, fn, (xs, tuple(carry)) + args
    )
    t0 = _time.perf_counter()
    with sp:
        y, bufs = fn(xs, tuple(carry), *args)
    devprof.note_launch(engine, t0, (y, bufs), cost=cost)
    if fused:
        _count_fused(plan, T, C, engine)
    return (y[:, :C] if Cp != C else y), bufs


# ---------------------------------------------------------------------------
# ragged-stacked streaming (ISSUE 16): N same-plan streams as ONE
# device program.
#
# Every stage of the cascade is channel-column independent (the
# property the PR 7 pad-and-mask layout already relies on), so N
# streams' (T, C_i) blocks concatenated along the channel axis run the
# SAME per-stage arithmetic in one launch and each stream's columns
# come out byte-identical to its solo step.  The ragged packing is the
# static (width, offset) row list: offsets are cumulative widths, the
# split slices are compiled into the program, and each stream's carry
# leaves are sliced back out as separate device arrays — a member
# leaving its batch group keeps a carry indistinguishable from solo
# execution.  With a mesh the stacked width is pad-and-masked to the
# shard multiple INSIDE the program (zeros are inert, exactly as in
# tpudas.parallel.sharding), so a 2-D stream x channel layout composes
# with the PR 7 mesh.


@functools.lru_cache(maxsize=128)
def _build_stacked_stream_fn(plan: CascadePlan, T: int, widths: tuple,
                             engine: str, mesh=None, ch_axis="ch",
                             knobs=(), quantized=False):
    """jit-compiled STACKED stateful step: (N blocks (T, C_i), N
    carries) -> (N outputs (T/ratio, C_i), N new carries), all inside
    one device program.  ``engine`` is a resolved
    :data:`STACKED_ENGINES` literal: ``xla`` replays the per-stage
    chain of :func:`_build_stream_cascade_fn`, ``fused-xla`` the
    chunked ``lax.scan`` of :func:`_build_fused_stream_fn` — both on
    the concatenated (T, sum C_i) block, so per-stream outputs AND
    carry leaves are byte-identical to the solo step (channel columns
    are independent).  ``quantized`` takes a traced ``qscale`` scalar
    shared by every member (the batch group former keys on it).
    The carries are donated on accelerator backends, mirroring the
    solo builders."""
    import jax
    import jax.numpy as jnp

    blocked = _blocked_taps(plan)
    sizes = stream_carry_sizes(plan)
    widths = tuple(int(w) for w in widths)
    C = sum(widths)
    offsets = tuple(int(o) for o in np.cumsum((0,) + widths[:-1]))

    if engine == "fused-xla":
        n_out_total = T // plan.ratio
        chunk_out = fused_chunk_outputs(plan, n_out_total)
        chunk_in = chunk_out * plan.ratio
        n_steps = n_out_total // chunk_out

        def step(bufs, xc):
            y = xc
            new = []
            for (R, hb), p, buf in zip(blocked, sizes, bufs):
                xi = jnp.concatenate([buf, y], axis=0) if p else y
                k = y.shape[0] // R
                new.append(xi[xi.shape[0] - p:])
                y = _polyphase_stage_xla(xi, hb, R, k)
            return tuple(new), y

        def core(x, carry):
            if n_steps <= 1:
                bufs, y = step(tuple(carry), x)
                return y, bufs
            xs = x.reshape(n_steps, chunk_in, x.shape[1])
            bufs, ys = jax.lax.scan(step, tuple(carry), xs)
            return ys.reshape(n_out_total, x.shape[1]), bufs

    else:

        def core(x, carry):
            new_carry = []
            for (R, hb), p, buf in zip(blocked, sizes, carry):
                xc = jnp.concatenate([buf, x], axis=0) if p else x
                k = x.shape[0] // R
                y = _polyphase_stage_xla(xc, hb, R, k)
                new_carry.append(xc[xc.shape[0] - p:])
                x = y
            return x, tuple(new_carry)

    body = core
    Cp = C
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        Cp = C + (-C % int(mesh.shape[ch_axis]))
        spec = P(None, ch_axis)
        carry_specs = tuple(spec for _ in sizes)
        body = jax.shard_map(
            core,
            mesh=mesh,
            in_specs=(spec, carry_specs),
            out_specs=(spec, carry_specs),
            check_vma=False,
        )
    pad = Cp - C

    def fn(xs, carries, *args):
        # ragged channel packing: concatenate member columns at the
        # static offsets, run one program, slice members back out
        x = jnp.concatenate(list(xs), axis=1).astype(jnp.float32)
        if quantized:
            x = x * args[0]
        cat = tuple(
            jnp.concatenate(
                [c[i].astype(jnp.float32) for c in carries], axis=1
            )
            for i in range(len(sizes))
        )
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
            cat = tuple(jnp.pad(b, ((0, 0), (0, pad))) for b in cat)
        y, new = body(x, cat)
        outs = tuple(
            y[:, o:o + w] for o, w in zip(offsets, widths)
        )
        new_carries = tuple(
            tuple(leaf[:, o:o + w] for leaf in new)
            for o, w in zip(offsets, widths)
        )
        return outs, new_carries

    donate = (1,) if jax.default_backend() != "cpu" else ()
    return jax.jit(fn, donate_argnums=donate)


def cascade_decimate_stream_stacked(blocks, carries, plan: CascadePlan,
                                    engine="xla", mesh=None,
                                    ch_axis="ch", qscale=None):
    """N same-plan streams' stateful steps as ONE stacked device
    program (the ragged-batched fleet path, ISSUE 16).

    ``blocks`` is a sequence of (T, C_i) blocks sharing T (mixed
    channel widths are the ragged case — each stream keeps its own
    width); ``carries`` the matching per-stream carry pytrees (from
    :func:`cascade_stream_init` or previous solo/stacked steps — the
    layouts are identical, so a stream moves freely between solo and
    stacked execution).  Returns ``[(y_i, new_carry_i), ...]`` in
    member order; every output and carry leaf is byte-identical to
    what ``cascade_decimate_stream`` returns for that member alone
    (channel-column independence — the same property that makes the
    PR 7 sharded step byte-identical).

    ``engine`` must be a RESOLVED :data:`STACKED_ENGINES` literal —
    callers resolve per member at the member's own solo width first
    (see tpudas.fleet.batch), so stacking never changes an engine
    decision.  ``qscale`` is a single traced scalar shared by every
    member: mixed-scale streams must not be stacked together (the
    group former keys on the scale value).  The previous carries may
    not be reused after the call (donated on accelerator backends)."""
    import jax.numpy as jnp

    if engine not in STACKED_ENGINES:
        raise ValueError(
            f"stacked engine must be one of {STACKED_ENGINES}, got "
            f"{engine!r}"
        )
    blocks = tuple(blocks)
    carries = tuple(tuple(c) for c in carries)
    if not blocks or len(blocks) != len(carries):
        raise ValueError(
            f"blocks/carries length mismatch: {len(blocks)} vs "
            f"{len(carries)}"
        )
    T = int(np.shape(blocks[0])[0])
    if T % plan.ratio:
        raise ValueError(
            f"stream block length {T} is not a multiple of the "
            f"decimation ratio {plan.ratio}"
        )
    widths = tuple(int(np.shape(b)[1]) for b in blocks)
    sizes = stream_carry_sizes(plan)
    for i, (b, c, w) in enumerate(zip(blocks, carries, widths)):
        if int(np.shape(b)[0]) != T:
            raise ValueError(
                f"member {i} block has {int(np.shape(b)[0])} rows; the "
                f"stacked step needs a shared T={T} (partition waves "
                "by block length)"
            )
        if len(c) != len(sizes) or any(
            int(np.shape(leaf)[0]) != p for leaf, p in zip(c, sizes)
        ):
            raise ValueError(
                f"member {i} carry does not match this plan's "
                "stream_carry_sizes "
                f"({[int(np.shape(leaf)[0]) for leaf in c]} vs "
                f"{list(sizes)})"
            )
        if any(int(np.shape(leaf)[1]) != w for leaf, _p in zip(c, sizes)):
            raise ValueError(
                f"member {i} carry width "
                f"{[np.shape(leaf) for leaf in c]} does not match its "
                f"block width {w}"
            )
        _check_quantized(b, qscale)
    quantized = qscale is not None
    knobs = knob_fingerprint()
    fn = _build_stacked_stream_fn(
        plan, T, widths, engine, mesh, ch_axis,
        knobs=knobs, quantized=quantized,
    )
    from tpudas.obs import devprof
    from tpudas.obs.trace import span

    shape_key = (T, widths, engine, int(quantized), _plan_tag(plan))
    devprof.note_kernel("cascade_stacked", shape_key, knobs)
    args = (jnp.float32(qscale),) if quantized else ()
    cost = devprof.kernel_cost(
        "cascade_stacked", shape_key, fn, (blocks, carries) + args
    )
    t0 = _time.perf_counter()
    with span("op.stacked", rows=T, streams=len(blocks), engine=engine):
        outs, news = fn(blocks, carries, *args)
    devprof.note_launch(engine, t0, (outs, news), cost=cost,
                        stacked=True)
    if engine == "fused-xla":
        for w in widths:
            _count_fused(plan, T, w, engine)
    return list(zip(outs, news))


# ---------------------------------------------------------------------------
# probing (host-side, analytic)


def impulse_response(plan: CascadePlan, n: int | None = None) -> np.ndarray:
    """Composite full-rate impulse response of the cascade (numpy).

    Equivalent to pushing a unit impulse through all stages WITHOUT
    decimation (valid because decimation commutes with the linear
    filters for response-support analysis) — the analytic counterpart of
    the reference's synthetic-impulse probe (lf_das.py:47-87).
    """
    h = np.ones(1, np.float64)
    prod = 1
    for R, taps in plan.stages:
        up = np.zeros(prod * (len(taps) - 1) + 1, np.float64)
        up[::prod] = np.asarray(taps, np.float64)
        h = np.convolve(h, up)
        prod *= R
    if n is not None and len(h) < n:
        h = np.pad(h, (0, n - len(h)))
    return h


@functools.lru_cache(maxsize=256)
def edge_support_samples(plan: CascadePlan, tol: float = 1e-3) -> int:
    """One-sided support (full-rate samples) of the composite impulse
    response thresholded at ``max*tol`` — the cascade's equivalent of
    ``get_edge_effect_time`` (reference lf_das.py:67-77)."""
    h = impulse_response(plan)
    mag = np.abs(h)
    above = np.nonzero(mag > mag.max() * tol)[0]
    center = plan.delay
    return int(max(center - above[0], above[-1] - center, 0))
