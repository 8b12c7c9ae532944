"""Sharded end-to-end window pipeline: filter + decimate over a mesh.

The multi-device form of the engine's fused window kernel
(tpudas.proc.lfproc): a resident (T, C) super-block is laid out over a
(time, ch) mesh; each device filters its time shard plus exchanged
halos locally (FFT overlap-save — circular artifacts fall inside the
trimmed halo), then decimates its interior by strided subsampling.
Channel direction needs no communication at all; time direction costs
one neighbor ppermute of ``halo`` rows per step.

Alignment requirements (checked): T divisible by time-shards, local
block divisible by the decimation ratio, C divisible by channel shards.
The streaming host path (LFProc) has no such constraints; this path is
for resident super-batches on a slice (BASELINE.json configs 4-5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from tpudas.ops.fftlen import (
    fft_channel_blocks,
    map_channel_blocks,
    next_tpu_fft_len,
)
from tpudas.ops.filter import fft_lowpass_response
from tpudas.parallel.halo import exchange_halo_time

__all__ = [
    "sharded_lowpass_decimate",
    "sharded_cascade_decimate",
    "sharded_cascade_layout",
]


def _local_filter_decimate(padded, d_sec, corner, order, halo, t_local, ratio):
    """Filter a halo-padded local block, trim, stride-decimate."""
    nfft = next_tpu_fft_len(int(padded.shape[0]))

    def filter_decimate(padded):
        spec = jnp.fft.rfft(padded, n=nfft, axis=0)
        resp = fft_lowpass_response(nfft, d_sec, corner, order)
        filt = jnp.fft.irfft(spec * resp[:, None], n=nfft, axis=0)
        interior = jax.lax.slice_in_dim(filt, halo, halo + t_local, axis=0)
        return interior[::ratio].astype(padded.dtype)

    return map_channel_blocks(
        filter_decimate, padded, fft_channel_blocks(nfft, padded)
    )


def sharded_lowpass_decimate(
    mesh, data, d_sec, corner, ratio, halo, order=4,
    time_axis="time", ch_axis="ch",
):
    """Run the fused low-pass + decimate over a (time, ch) mesh.

    data: (T, C) float32 (host or device). Returns (T // ratio, C) with
    the same global result as the single-device kernel up to halo
    truncation (callers discard ``halo`` input samples at each stream
    end, as the engine's edge buffer already does).
    """
    T, C = data.shape
    nt = mesh.shape[time_axis]
    nc = mesh.shape[ch_axis]
    if T % nt != 0:
        raise ValueError(f"T={T} not divisible by time shards {nt}")
    t_local = T // nt
    if t_local % ratio != 0:
        raise ValueError(
            f"local block {t_local} not divisible by decimation ratio {ratio}"
        )
    if C % nc != 0:
        raise ValueError(f"C={C} not divisible by channel shards {nc}")
    if halo >= t_local:
        raise ValueError(f"halo {halo} must be < local block {t_local}")

    spec_2d = P(time_axis, ch_axis)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec_2d,),
        out_specs=spec_2d,
        check_vma=False,
    )
    def step(block):
        padded = exchange_halo_time(
            block, halo, axis_name=time_axis, n_shards=nt
        )
        return _local_filter_decimate(
            padded,
            jnp.float32(d_sec),
            jnp.float32(corner),
            order,
            halo,
            t_local,
            ratio,
        )

    arr = jax.device_put(
        jnp.asarray(data, jnp.float32), NamedSharding(mesh, spec_2d)
    )
    return jax.jit(step)(arr)


# ---------------------------------------------------------------------------
# time + channel sharded cascade (the product engine's mesh fast path)


@functools.lru_cache(maxsize=64)
def _build_sharded_cascade_fn(
    plan, n_loc, halo, engine, mesh, time_axis, ch_axis, quantized=False,
    knobs=(),
):
    """jit-compiled shard_map cascade: (nt*t_local, C) -> (nt*n_loc, C).

    Each time-shard receives its neighbors' halo rows over the ICI ring
    (``exchange_halo_time``), drops the unused left halo, and runs the
    causal cascade on its local block — valid because the cascade is
    shift-invariant under multiples of the composite ratio, and
    ``t_local = n_loc * ratio``. Channels split over ``ch_axis`` with
    no communication at all.
    """
    import jax

    from tpudas.ops.fir import (
        _apply_cascade_stages,
        _blocked_taps,
        _pallas_interpret,
    )

    nt = mesh.shape[time_axis]
    blocked = _blocked_taps(plan)
    use_pallas = engine == "pallas"
    interpret = _pallas_interpret() if use_pallas else False

    in_specs = (
        (P(time_axis, ch_axis), P())
        if quantized
        else (P(time_axis, ch_axis),)
    )

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(time_axis, ch_axis),
        check_vma=False,
    )
    def step(block, *maybe_scale):
        # causal consumer: only the RIGHT (look-ahead) halo is needed,
        # so the exchange is one-sided — half the ICI traffic (and a
        # quantized int16 window keeps its halved payload across the
        # ring too: dequantization happens inside the first stage)
        padded = exchange_halo_time(
            block, halo, axis_name=time_axis, n_shards=nt, left=False
        )
        return _apply_cascade_stages(
            padded, blocked, n_loc, use_pallas, interpret,
            qscale=maybe_scale[0] if quantized else None,
        )

    return jax.jit(step)


def sharded_cascade_layout(mesh, plan, phase, n_out, T,
                           time_axis="time", n_ch_local=1, engine="auto"):
    """(n_loc, t_local, halo) of the time-sharded cascade layout for a
    T-row input — or ``None`` when it does not fit (a shard's halo
    would exceed its local block: too many time shards for this
    window/filter combination). Shared by the executor below and by
    callers that need to predict per-device shapes (e.g. LFProc's
    engine observability, which must see the LOCAL output count the
    Pallas threshold sees).

    ``n_ch_local``/``engine`` size the halo from the same chain layout
    the shard body will trace (Pallas stages consume grid-rounded
    inputs): a halo sized that way keeps every stage pad-free inside
    the shard. The defaults give the plain ``(k+B)*R`` sizing.
    """
    from tpudas.ops.fir import chain_layout

    nt = mesh.shape[time_axis]
    ratio = int(plan.ratio)
    n_out = int(n_out)
    if n_out < 1 or nt < 1:
        return None
    # rows of the pre-shifted stream (phase < delay adds left padding)
    T_shift = int(T) - (int(phase) - plan.delay)
    # the shard grid must cover ALL real input rows, not just
    # n_out*ratio of them: the last shard has no right neighbor, so any
    # data past the grid would be replaced by boundary zeros inside the
    # tail outputs' filter support
    n_loc = max(-(-n_out // nt), -(-T_shift // (ratio * nt)))
    t_local = n_loc * ratio
    _, rows_local = chain_layout(plan, n_loc, int(n_ch_local), engine)
    halo = rows_local - t_local
    if halo < 0 or halo > t_local:
        return None
    return n_loc, t_local, halo


def sharded_cascade_decimate(
    mesh, x, plan, phase, n_out, engine="auto",
    time_axis="time", ch_axis="ch", qscale=None,
):
    """Mesh-parallel :func:`tpudas.ops.fir.cascade_decimate`: the time
    axis is sharded over ``time_axis`` (one-sided halo exchange over
    ICI neighbors, sized from the cascade's exact input need) and
    channels over ``ch_axis`` (zero-comm).

    Bit-equal to the single-device cascade for the same (plan, phase,
    n_out): out-of-data rows are zero in both layouts and each output's
    reduction reads the same rows in the same order. Returns ``None``
    when the layout does not fit (see :func:`sharded_cascade_layout`);
    the caller then falls back to channel-only sharding.
    """
    import jax.numpy as jnp

    from tpudas.ops.fir import (
        _check_quantized,
        resolve_cascade_engine,
        shift_to_phase,
    )

    nt = mesh.shape[time_axis]
    nc = mesh.shape[ch_axis]
    n_ch_local = -(-int(np.shape(x)[1]) // nc)
    layout = sharded_cascade_layout(
        mesh, plan, phase, int(n_out), int(np.shape(x)[0]), time_axis,
        n_ch_local=n_ch_local, engine=engine,
    )
    if layout is None:
        return None
    n_loc, t_local, halo = layout
    n_out = int(n_out)
    engine = resolve_cascade_engine(engine)
    if qscale is not None:
        x = jnp.asarray(x)  # raw int16: dequantized inside stage 0
        _check_quantized(x, qscale)
    else:
        x = jnp.asarray(x, jnp.float32)
    C = int(x.shape[1])
    x2 = shift_to_phase(x, phase, plan.delay)
    T_target = nt * t_local
    pad_t = T_target - int(x2.shape[0])
    if pad_t > 0:
        x2 = jnp.pad(x2, ((0, pad_t), (0, 0)))
    pad_c = -C % nc
    if pad_c:
        x2 = jnp.pad(x2, ((0, 0), (0, pad_c)))
    from tpudas.ops.fir import knob_fingerprint

    fn = _build_sharded_cascade_fn(
        plan, n_loc, halo, engine, mesh, time_axis, ch_axis,
        quantized=qscale is not None, knobs=knob_fingerprint(),
    )
    if qscale is not None:
        out = fn(x2, jnp.float32(qscale))
    else:
        out = fn(x2)
    return out[:n_out, :C]
