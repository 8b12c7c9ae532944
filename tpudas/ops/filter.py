"""Zero-phase band filtering on TPU.

The reference's ``pass_filter`` is scipy's forward-backward IIR
(``sosfiltfilt``) — inherently sequential, a poor fit for TPU. The
TPU-native equivalent used here exploits the fact that filtfilt's
magnitude response is exactly ``|H(f)|^2`` with zero phase: we apply the
squared Butterworth magnitude directly in the frequency domain —
``rfft → multiply → irfft`` along the time axis, batched over channels.
This is O(T log T) per channel (vs O(T·order) sequential), maps onto
XLA's fused FFT, and matches ``sosfiltfilt`` numerics away from chunk
edges; the self-calibrating edge probe (tpudas.proc.edge, reference
lf_das.py:47-87) measures the *actual* impulse-response support of this
filter, so the overlap-save scheduler trims exactly the right halo.

Reference call sites: lf_das.py:40 (probe pipeline, corner = 0.4/dt)
and lf_das.py:223 (engine, corner = 0.45/dt low-pass).
"""

from __future__ import annotations

import functools
import time as _time

import jax
import jax.numpy as jnp
import numpy as np

from tpudas.ops.fftlen import (
    fft_channel_blocks,
    map_channel_blocks,
    next_tpu_fft_len,
)

from tpudas.core import units as _units

__all__ = [
    "patch_pass_filter",
    "fft_lowpass_response",
    "fft_pass_filter",
    "fft_stream_init",
    "fft_pass_filter_stream",
    "fft_pass_filter_stream_stacked",
]


def _butter_mag2(freqs, low, high, order):
    """Squared Butterworth magnitude response (filtfilt-equivalent).

    ``low``/``high`` are the band edges in the same units as ``freqs``
    (low = high-pass corner, high = low-pass corner, as in
    ``pass_filter(time=(low, high))``).
    """
    resp = jnp.ones_like(freqs)
    if high is not None:
        resp = resp / (1.0 + (freqs / high) ** (2 * order))
    if low is not None:
        safe = jnp.maximum(freqs, jnp.finfo(freqs.dtype).tiny)
        resp = resp / (1.0 + (low / safe) ** (2 * order))
        resp = jnp.where(freqs <= 0.0, 0.0, resp)
    return resp


@functools.partial(
    jax.jit,
    static_argnames=("nfft", "order", "has_low", "has_high", "ch_blocks"),
)
def _fft_filter_kernel(data, d_sec, low, high, nfft, order, has_low, has_high,
                       ch_blocks=1):
    """data: (T, C) float32; filter along axis 0. Returns (T, C).
    ``ch_blocks``: channel blocks the transform runs in, one after
    another (``fftlen.fft_channel_blocks``)."""
    n = data.shape[0]

    def band(data):
        spec = jnp.fft.rfft(data, n=nfft, axis=0)
        freqs = jnp.arange(nfft // 2 + 1, dtype=jnp.float32) / (nfft * d_sec)
        resp = _butter_mag2(
            freqs,
            low if has_low else None,
            high if has_high else None,
            order,
        )
        out = jnp.fft.irfft(spec * resp[:, None], n=nfft, axis=0)
        return out[:n].astype(data.dtype)

    return map_channel_blocks(band, data, ch_blocks)


def fft_pass_filter(data, d_sec, low=None, high=None, order=4):
    """Apply the zero-phase band filter along axis 0 of a (T, C) array.

    Pure jittable entry point (also used by bench / graft entry).
    """
    data = jnp.asarray(data, jnp.float32)
    squeeze = data.ndim == 1
    if squeeze:
        data = data[:, None]
    nfft = next_tpu_fft_len(int(data.shape[0]))
    # the program holds the (T, C) f32 output too, and a copy of it
    # while the blocks' results are joined
    blocks = fft_channel_blocks(nfft, data, held_bytes=8 * data.size)
    out = _fft_filter_kernel(
        data,
        jnp.float32(d_sec),
        jnp.float32(0.0 if low is None else low),
        jnp.float32(0.0 if high is None else high),
        nfft,
        int(order),
        low is not None,
        high is not None,
        ch_blocks=blocks,
    )
    return out[:, 0] if squeeze else out


def fft_lowpass_response(nfft, d_sec, corner, order=4):
    """The rfft-domain response used by the kernel (for composition into
    fused pipelines, e.g. tpudas.parallel.pipeline)."""
    freqs = jnp.arange(nfft // 2 + 1, dtype=jnp.float32) / (nfft * d_sec)
    return _butter_mag2(freqs, None, jnp.float32(corner), order)


# ---------------------------------------------------------------------------
# streaming overlap-save: carry the filter's edge support across blocks
#
# The batch entry point above re-filters a window that includes the
# edge support on both sides; a streaming caller would have to re-read
# that halo every block.  The carry below is the overlap-save state —
# the last ``2 * edge`` RAW input samples — so each input sample enters
# the FFT engine exactly once and the emitted region of every block is
# clean (full ``edge`` support on both sides, circular-wrap artifacts
# confined to the discarded halo) as long as ``edge`` covers the
# filter's impulse-response support at the engine's tolerance (the
# same contract the batch overlap-save scheduler enforces through
# tpudas.proc.edge).


def fft_stream_init(edge: int, n_ch: int) -> np.ndarray:
    """Zero carry for :func:`fft_pass_filter_stream`: the stream's last
    ``2 * edge`` input samples (zeros = silence before the stream)."""
    return np.zeros((2 * int(edge), int(n_ch)), np.float32)


@functools.lru_cache(maxsize=128)
def _build_fft_stream_fn(T, rows_carry, n_ch, d_sec, low, high, order,
                         mesh, ch_axis, quantized=False):
    """jit-compiled FFT stream step: (block (T, C), carry (2*edge, C))
    -> (filtered (T, C), new_carry).  Both inputs are donated on
    accelerator backends (the caller never reuses either).

    With ``mesh``, the step runs under ``shard_map`` with channels
    split over ``ch_axis`` — the filter is column-independent (one
    rfft/irfft batch per channel), so each device runs the identical
    kernel on its local channel block and the sharded result is
    byte-identical to the single-device step.  ``n_ch`` is then the
    PADDED global channel count (tpudas.parallel.sharding's
    pad-and-mask layout).

    ``quantized`` compiles the raw-int16 ingest variant: the step
    takes a traced ``qscale`` scalar and the dequantizing
    ``cast * scale`` on the block is the program's first op (the
    overlap-save carry stays float32 — the layouts match the float
    variant's, so resume and mid-stream payload changes are safe)."""
    edge = rows_carry // 2

    def core(block, carry):
        xc = jnp.concatenate(
            [carry.astype(jnp.float32), block], axis=0,
        )
        filt = fft_pass_filter(xc, d_sec, low=low, high=high, order=order)
        return filt[edge : edge + T], xc[xc.shape[0] - 2 * edge :]

    if quantized:
        def fn(block, carry, qscale):
            return core(block.astype(jnp.float32) * qscale, carry)
    else:
        def fn(block, carry):
            return core(block.astype(jnp.float32), carry)

    body = fn
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        spec = P(None, ch_axis)
        in_specs = (spec, spec, P()) if quantized else (spec, spec)
        body = jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs,
            out_specs=(spec, spec), check_vma=False,
        )
    donate = (0, 1) if jax.default_backend() not in ("cpu",) else ()
    return jax.jit(body, donate_argnums=donate)


def fft_pass_filter_stream(block, carry, d_sec, low=None, high=None,
                           order=4, mesh=None, ch_axis="ch",
                           qscale=None):
    """One streaming step of the zero-phase FFT band filter.

    block: (T, C) new input samples; carry: (2*edge, C) from
    :func:`fft_stream_init` or a previous step.  Returns
    ``(filtered, new_carry)`` where ``filtered[i]`` is the zero-phase
    filtered value of the stream at the position ``edge`` samples
    BEHIND ``block[i]`` — the emission lags the input by ``edge``
    samples (an output needs its right-side support before it can be
    clean).  With a zero-initialized carry the first ``edge`` emitted
    samples read pre-stream silence; callers discard them exactly as
    the batch path discards its stream-start edge.

    Neither the block nor the previous carry may be reused after the
    call: both are DONATED on accelerator backends (the returned
    carry replaces the old one — feed it back verbatim and it stays
    device-resident with no host round-trip).

    With ``mesh``, channels are split over the mesh's ``ch_axis``
    (zero-communication shard_map; pad-and-mask for non-divisible
    counts) and the returned carry is a SHARDED device array — feed it
    back verbatim and it stays resident on the mesh with no host
    round-trip; ``filtered`` is trimmed to the logical channel count.
    Byte-identical to the single-device step (the filter is
    column-independent).

    ``qscale`` accepts a raw int16 quantized block (tdas ingest fast
    path): the H2D transfer stays int16 and dequantization happens
    inside the step — bit-identical to feeding
    ``block.astype(f32) * qscale``; the scale is a traced operand."""
    from tpudas.ops.fir import _check_quantized

    _check_quantized(block, qscale)
    quantized = qscale is not None
    rows_carry = int(np.shape(carry)[0])
    if len(np.shape(carry)) != 2 or rows_carry % 2:
        raise ValueError(
            f"carry must be (2*edge, C), got {tuple(np.shape(carry))}"
        )
    T = int(np.shape(block)[0])
    from tpudas.obs import devprof
    from tpudas.obs.trace import span

    edge = rows_carry // 2
    args = (jnp.float32(qscale),) if quantized else ()
    if mesh is None:
        carry = jnp.asarray(carry, jnp.float32)
        block = jnp.asarray(block)  # int16 stays int16 across H2D
        if not quantized:
            block = block.astype(jnp.float32)
        if block.ndim != 2 or block.shape[1] != carry.shape[1]:
            raise ValueError(
                f"block {tuple(block.shape)} does not match carry "
                f"{tuple(carry.shape)}"
            )
        fn = _build_fft_stream_fn(
            T, rows_carry, int(block.shape[1]),
            float(d_sec), low, high, int(order), None, ch_axis,
            quantized=quantized,
        )
        shape_key = (
            T, rows_carry, int(block.shape[1]), float(d_sec), low,
            high, int(order), int(quantized),
        )
        devprof.note_kernel("fft", shape_key, ())
        cost = devprof.kernel_cost(
            "fft", shape_key, fn, (block, carry) + args
        )
        t0 = _time.perf_counter()
        with span("op.fft_stream", rows=T, edge=edge):
            out = fn(block, carry, *args)
        devprof.note_launch("fft", t0, out, cost=cost)
        return out
    from tpudas.parallel.sharding import channel_pad, place_block

    C = int(np.shape(block)[1])
    C_carry = int(np.shape(carry)[1])
    Cp = C + channel_pad(C, mesh, ch_axis)
    if C_carry not in (C, Cp):
        raise ValueError(
            f"block {(T, C)} does not match carry "
            f"{tuple(np.shape(carry))}"
        )
    xs = place_block(block, mesh, ch_axis, keep_dtype=quantized)
    if C_carry != Cp:
        # first call after open/resume: the carry is a host array at
        # the logical width — pad-and-place it once; every later step
        # feeds back the sharded carry this step returns
        carry = place_block(np.asarray(carry, np.float32), mesh, ch_axis)
    fn = _build_fft_stream_fn(
        T, rows_carry, Cp, float(d_sec), low, high, int(order),
        mesh, ch_axis, quantized=quantized,
    )
    shape_key = (
        T, rows_carry, Cp, float(d_sec), low, high, int(order),
        int(quantized), int(mesh.shape[ch_axis]),
    )
    devprof.note_kernel("fft", shape_key, ())
    cost = devprof.kernel_cost("fft", shape_key, fn, (xs, carry) + args)
    t0 = _time.perf_counter()
    with span(
        "op.fft_stream", rows=T, edge=edge,
        shards=int(mesh.shape[ch_axis]),
    ):
        out, new_carry = fn(xs, carry, *args)
    devprof.note_launch("fft", t0, (out, new_carry), cost=cost)
    return (out[:, :C] if Cp != C else out), new_carry


@functools.lru_cache(maxsize=128)
def _build_stacked_fft_fn(T, rows_carry, widths, d_sec, low, high, order,
                          mesh, ch_axis, quantized=False):
    """jit-compiled STACKED FFT stream step (the ragged-batched fleet
    path, ISSUE 16): N same-parameter streams' overlap-save steps run
    as ONE device program on the channel-concatenated (T, sum C_i)
    block.  The filter is column-independent (one rfft/irfft batch per
    channel, nfft a function of T only), so each member's filtered
    block and new carry come out byte-identical to its solo
    :func:`fft_pass_filter_stream` step; members are sliced back out
    at the static ragged (width, offset) rows.  With ``mesh`` the
    stacked width is pad-and-masked to the shard multiple inside the
    program (zeros are inert).  Inputs are donated on accelerator
    backends, mirroring the solo builder."""
    edge = rows_carry // 2
    widths = tuple(int(w) for w in widths)
    C = sum(widths)
    offsets = tuple(int(o) for o in np.cumsum((0,) + widths[:-1]))

    def core(block, carry):
        xc = jnp.concatenate(
            [carry.astype(jnp.float32), block], axis=0,
        )
        filt = fft_pass_filter(xc, d_sec, low=low, high=high, order=order)
        return filt[edge : edge + T], xc[xc.shape[0] - 2 * edge :]

    body = core
    Cp = C
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        Cp = C + (-C % int(mesh.shape[ch_axis]))
        spec = P(None, ch_axis)
        body = jax.shard_map(
            core, mesh=mesh, in_specs=(spec, spec),
            out_specs=(spec, spec), check_vma=False,
        )
    pad = Cp - C

    def fn(blocks, carries, *args):
        x = jnp.concatenate(list(blocks), axis=1).astype(jnp.float32)
        if quantized:
            x = x * args[0]
        cat = jnp.concatenate(
            [c.astype(jnp.float32) for c in carries], axis=1
        )
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
            cat = jnp.pad(cat, ((0, 0), (0, pad)))
        filt, new = body(x, cat)
        outs = tuple(
            filt[:, o:o + w] for o, w in zip(offsets, widths)
        )
        news = tuple(
            new[:, o:o + w] for o, w in zip(offsets, widths)
        )
        return outs, news

    donate = (0, 1) if jax.default_backend() not in ("cpu",) else ()
    return jax.jit(fn, donate_argnums=donate)


def fft_pass_filter_stream_stacked(blocks, carries, d_sec, low=None,
                                   high=None, order=4, mesh=None,
                                   ch_axis="ch", qscale=None):
    """N streams' overlap-save FFT filter steps as ONE stacked device
    program.  ``blocks`` share T and the filter parameters; each keeps
    its own channel width (ragged packing).  Returns
    ``[(filtered_i, new_carry_i), ...]`` in member order,
    byte-identical per member to :func:`fft_pass_filter_stream` (the
    filter is column-independent).  ``qscale`` is one traced scalar
    shared by every member — the fleet group former keys on the value,
    so mixed-scale streams are never stacked.  Blocks and previous
    carries are donated on accelerator backends — do not reuse."""
    from tpudas.ops.fir import _check_quantized

    blocks = tuple(blocks)
    carries = tuple(carries)
    if not blocks or len(blocks) != len(carries):
        raise ValueError(
            f"blocks/carries length mismatch: {len(blocks)} vs "
            f"{len(carries)}"
        )
    T = int(np.shape(blocks[0])[0])
    rows_carry = int(np.shape(carries[0])[0])
    if rows_carry % 2:
        raise ValueError(
            f"carry must be (2*edge, C), got {tuple(np.shape(carries[0]))}"
        )
    for i, (b, c) in enumerate(zip(blocks, carries)):
        if int(np.shape(b)[0]) != T or int(np.shape(c)[0]) != rows_carry:
            raise ValueError(
                f"member {i} shapes {tuple(np.shape(b))}/"
                f"{tuple(np.shape(c))} do not match the wave's "
                f"T={T}, 2*edge={rows_carry}"
            )
        if int(np.shape(b)[1]) != int(np.shape(c)[1]):
            raise ValueError(
                f"member {i} block {tuple(np.shape(b))} does not match "
                f"carry {tuple(np.shape(c))}"
            )
        _check_quantized(b, qscale)
    quantized = qscale is not None
    widths = tuple(int(np.shape(b)[1]) for b in blocks)
    fn = _build_stacked_fft_fn(
        T, rows_carry, widths, float(d_sec), low, high, int(order),
        mesh, ch_axis, quantized=quantized,
    )
    from tpudas.obs import devprof
    from tpudas.obs.trace import span

    shape_key = (
        T, rows_carry, widths, float(d_sec), low, high, int(order),
        int(quantized),
    )
    devprof.note_kernel("fft_stacked", shape_key, ())
    args = (jnp.float32(qscale),) if quantized else ()
    cost = devprof.kernel_cost(
        "fft_stacked", shape_key, fn, (blocks, carries) + args
    )
    t0 = _time.perf_counter()
    with span(
        "op.stacked", rows=T, streams=len(blocks), edge=rows_carry // 2,
    ):
        outs, news = fn(blocks, carries, *args)
    devprof.note_launch("fft", t0, (outs, news), cost=cost,
                        stacked=True)
    return list(zip(outs, news))


def _host_sosfiltfilt(data, d_sec, low, high, order):
    """Host reference engine: scipy Butterworth + sosfiltfilt (the
    reference's exact numerics)."""
    from scipy.signal import butter, sosfiltfilt

    nyq = 0.5 / d_sec
    if low is not None and high is not None:
        sos = butter(order, [low / nyq, high / nyq], btype="bandpass", output="sos")
    elif high is not None:
        sos = butter(order, high / nyq, btype="lowpass", output="sos")
    elif low is not None:
        sos = butter(order, low / nyq, btype="highpass", output="sos")
    else:
        return np.asarray(data, np.float64)
    return sosfiltfilt(sos, np.asarray(data, np.float64), axis=0)


def patch_pass_filter(patch, order=4, engine=None, **kwargs):
    """Patch-level ``pass_filter(time=(low, high))``.

    Exactly one named dimension must be given; band edges are in Hz for
    time (cycles per meter for distance). ``None`` bounds are open.
    """
    if len(kwargs) != 1:
        raise ValueError("pass_filter requires exactly one dim, e.g. time=(None, 5)")
    (dim, band), = kwargs.items()
    low, high = band
    low = _units.get_seconds(low) if low is not None else None
    high = _units.get_seconds(high) if high is not None else None
    ax = patch.axis_of(dim)
    d = patch.get_sample_step(dim)
    if d is None or d <= 0:
        raise ValueError(f"cannot infer sample step for dim {dim!r}")
    nyq = 0.5 / d
    for edge in (low, high):
        if edge is not None and not (0 < edge <= nyq):
            raise ValueError(
                f"filter corner {edge} Hz outside (0, Nyquist={nyq}]"
            )

    from tpudas.obs.trace import span

    data = patch.data
    moved = ax != 0
    if engine in ("numpy", "scipy", "host"):
        with span("op.pass_filter", engine="host"):
            host = np.asarray(data)
            if moved:
                host = np.moveaxis(host, ax, 0)
            out = _host_sosfiltfilt(host, d, low, high, order)
            out = out.astype(np.asarray(data).dtype, copy=False)
            if moved:
                out = np.moveaxis(out, 0, ax)
    else:
        with span("op.pass_filter", engine="fft"):
            arr = jnp.asarray(data)
            if moved:
                arr = jnp.moveaxis(arr, ax, 0)
            out = fft_pass_filter(arr, d, low=low, high=high, order=order)
            if moved:
                out = jnp.moveaxis(out, 0, ax)
    return patch.new(data=out)
