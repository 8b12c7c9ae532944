"""Data parallelism over independent patches.

The rolling-mean paths process spool patches independently
(rolling_mean_dascore.ipynb:147 is a serial for-loop; the *_edge
variant is per-new-file). TPU-native: stack patches into a leading
batch axis and shard it over the mesh — pure data parallelism, no
collectives."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from tpudas.ops.rolling import _reduce_window_kernel

__all__ = ["batched_rolling_mean", "batched_cascade_decimate"]


def batched_rolling_mean(mesh, batch, w: int, s: int, batch_axis="ch"):
    """Rolling mean over a (B, T, C) stack of windows/patches, batch
    axis sharded over the mesh's ``batch_axis``.

    Uses the same reduce_window kernel (and NaN warm-up semantics) as
    the single-patch path, vmapped over the batch.
    """
    arr = jnp.asarray(batch, jnp.float32)
    sharding = NamedSharding(mesh, P(batch_axis, None, None))
    arr = jax.device_put(arr, sharding)
    fn = jax.vmap(
        functools.partial(_reduce_window_kernel, w=int(w), s=int(s), op="mean")
    )
    return jax.jit(fn, out_shardings=sharding)(arr)


@functools.lru_cache(maxsize=64)
def _build_batched_cascade_fn(
    plan, n_out, engine, mesh, batch_axis, ch_axis, quantized, knobs=()
):
    from tpudas.ops.fir import (
        _apply_cascade_stages,
        _blocked_taps,
        _pallas_interpret,
    )

    blocked = _blocked_taps(plan)
    use_pallas = engine == "pallas"
    interpret = _pallas_interpret() if use_pallas else False
    spec = P(batch_axis, None, ch_axis)

    def one(x, scale=None):
        return _apply_cascade_stages(
            x, blocked, n_out, use_pallas, interpret, qscale=scale
        )

    if quantized:
        def body(stack, scale):
            return jax.vmap(lambda x: one(x, scale))(stack)

        in_specs = (spec, P())
    else:
        def body(stack):
            return jax.vmap(one)(stack)

        in_specs = (spec,)

    return jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=spec,
            check_vma=False,
        )
    )


def batched_cascade_decimate(
    mesh, stack, plan, phase, n_out, engine="auto",
    batch_axis="time", ch_axis="ch", qscale=None,
):
    """Window-level DATA parallelism for the LF pipeline: a (W, T, C)
    stack of same-shape overlap-save windows, batch axis sharded over
    ``batch_axis`` (channels optionally over ``ch_axis`` too) — the
    BASELINE "spool chunks pmapped across a TPU mesh" configuration.
    Windows are independent, so there are zero collectives; each
    device runs the full cascade (vmapped over its local windows).

    Every window is decimated with the SAME (plan, phase, n_out) —
    the steady-state overlap-save schedule, where all interior windows
    share one shape.  Result equals stacking per-window
    :func:`tpudas.ops.fir.cascade_decimate` outputs.  ``qscale``
    accepts a raw int16 stack (one shared quantization scale).
    """
    from tpudas.ops.fir import (
        _check_quantized,
        resolve_cascade_engine,
        shift_to_phase,
    )

    engine = resolve_cascade_engine(engine)
    stack = jnp.asarray(stack)
    if qscale is not None:
        _check_quantized(stack, qscale)
    elif stack.dtype != jnp.float32:
        stack = stack.astype(jnp.float32)
    W, T, C = stack.shape
    stack = shift_to_phase(stack, phase, plan.delay, axis=1)
    nb = mesh.shape[batch_axis]
    # a mesh without the channel axis (e.g. a custom 1-axis DP mesh)
    # simply leaves channels unsharded
    if ch_axis not in mesh.shape:
        ch_axis = None
    nc = mesh.shape[ch_axis] if ch_axis else 1
    pad_w = -W % nb
    pad_c = -C % nc
    if pad_w or pad_c:
        stack = jnp.pad(stack, ((0, pad_w), (0, 0), (0, pad_c)))
    from tpudas.ops.fir import knob_fingerprint

    fn = _build_batched_cascade_fn(
        plan, int(n_out), engine, mesh, batch_axis, ch_axis,
        qscale is not None, knobs=knob_fingerprint(),
    )
    if qscale is not None:
        out = fn(stack, jnp.float32(qscale))
    else:
        out = fn(stack)
    return out[:W, :, :C] if pad_w or pad_c else out
