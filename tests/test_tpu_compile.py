"""Compile rehearsals: the main-path kernels compiled for a described
v5e (on-chip-measurement guide, section 2).  Nothing runs; each test
lowers and compiles for the chip what the batch and edge paths hand
Mosaic at deployment widths, so a kernel the chip's compiler refuses
fails here at no chip time.  The FFT kernels are held to radix stages
and to the chip's HBM by the compiler's own counts.

``jax.default_backend()`` still says "cpu" here, so the tests call the
kernels and jitted builders directly, with Pallas interpret mode off.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

import tpudas.ops.fir as fir
from tpudas.proc.lfproc import output_corner

F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def compiled_for_chip(topo, monkeypatch):
    """Mosaic lowering (interpret off), no persistent cache around the
    compiles, and fresh builder caches before and after (their keys do
    not carry the interpret flag)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(fir, "_pallas_interpret", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    fir._clear_cascade_caches()
    yield
    fir._clear_cascade_caches()
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def one_chip(topo, compiled_for_chip):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mesh4(topo, compiled_for_chip):
    return Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("time", "ch"))


@pytest.fixture(scope="module")
def plan():
    """The flagship plan: 1 kHz to 1 Hz, stages R = 8/5/5/5."""
    return fir.design_cascade(1000.0, 1000, output_corner(1.0), 4)


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel is in there


def _on_tpu(monkeypatch):
    """Steer the backend-keyed selectors to the chip's branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("dtype", ["float32", "int16"])
@pytest.mark.parametrize("impl", ["v2", "v1"])
def test_stage_kernel(plan, one_chip, monkeypatch, impl, dtype):
    """Stage 0 of the batch cascade at 131072 x 2048."""
    from tpudas.ops.pallas_fir import fir_decimate_pallas

    monkeypatch.setenv("TPUDAS_PALLAS_IMPL", impl)
    R, hb = fir._blocked_taps(plan)[0]
    T, C = 131072, 2048
    x = jax.ShapeDtypeStruct((T, C), jnp.dtype(dtype), sharding=one_chip)
    _compile(lambda x: fir_decimate_pallas(x, hb, R, T // R - 64), x)


@pytest.mark.parametrize("quantized", [False, True])
def test_whole_cascade(plan, one_chip, quantized):
    """The jitted batch cascade (engine pallas) at the window the
    smoke's batch phase runs: 80 s x 2048 channels, 60 outputs."""
    fn = fir._build_cascade_fn(plan, 60, "pallas", quantized=quantized)
    x = jax.ShapeDtypeStruct(
        (80000, 2048), jnp.int16 if quantized else F32, sharding=one_chip
    )
    s = jax.ShapeDtypeStruct((), F32, sharding=one_chip)
    text = fn.lower(*((x, s) if quantized else (x,))).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_out", [1, 2, 4, 8, 16, 32, 60])
def test_fused_selector_blocks_compile(plan, one_chip, monkeypatch, n_out):
    """Every block size the stream hands the fused selector at 2048
    channels (the 60-output cap and its power-of-two remainders):
    where the selector picks fused-pallas, that step compiles; where
    the kernel would not fit, the selector has declined it up front."""
    from tpudas.ops.pallas_fir import fused_taps_fit

    _on_tpu(monkeypatch)
    T, C = n_out * plan.ratio, 2048
    eng = fir.resolve_stream_engine("fused", plan, T, C)
    chunk = fir.fused_chunk_outputs(plan, n_out)
    if eng != "fused-pallas":
        assert not (
            T * C >= fir.fused_min_elems()
            and fused_taps_fit(plan.stages, chunk)
        ), eng
        return
    fn = fir._build_fused_stream_fn(plan, T, C, eng, quantized=True)
    x = jax.ShapeDtypeStruct((T, C), jnp.int16, sharding=one_chip)
    bufs = tuple(
        jax.ShapeDtypeStruct((p, C), F32, sharding=one_chip)
        for p in fir.stream_carry_sizes(plan)
    )
    s = jax.ShapeDtypeStruct((), F32, sharding=one_chip)
    text = fn.lower(x, bufs, s).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("path", ["batch", "stream"])
def test_mesh_paths(plan, mesh4, monkeypatch, path):
    """The --chips 4 paths: 10,000 channels split over four chips, the
    meshed batch cascade and the channel-sharded fused stream step."""
    spec = NamedSharding(mesh4, P(None, "ch"))
    s = jax.ShapeDtypeStruct((), F32, sharding=NamedSharding(mesh4, P()))
    C = 10000
    if path == "batch":
        fn = fir._build_cascade_fn(plan, 60, "pallas", mesh4, quantized=True)
        x = jax.ShapeDtypeStruct((80000, C), jnp.int16, sharding=spec)
        args = (x, s)
    else:
        _on_tpu(monkeypatch)
        eng = fir.resolve_stream_engine("fused", plan, 60000, C // 4)
        assert eng == "fused-pallas"
        fn = fir._build_fused_stream_fn(
            plan, 60000, C, eng, mesh=mesh4, quantized=True
        )
        x = jax.ShapeDtypeStruct((60000, C), jnp.int16, sharding=spec)
        bufs = tuple(
            jax.ShapeDtypeStruct((p, C), F32, sharding=spec)
            for p in fir.stream_carry_sizes(plan)
        )
        args = (x, bufs, s)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# flop per transformed channel-sample above which the compiled FFT is a
# dense DFT matmul: ~740,000 at 60,750 points, ~6,800 at 65,536 in radix
# stages (a blocked transform counts its loop body once, so less)
DENSE_FLOP_PER_SAMPLE = 20_000


def _check_fft_program(lowered, rows, n_ch):
    """The compiled FFT runs in radix stages and fits the chip's HBM."""
    from tpudas.ops.fftlen import HBM_BYTES

    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    assert cost["flops"] / (rows * n_ch) < DENSE_FLOP_PER_SAMPLE
    mem = compiled.memory_analysis()
    held = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert held <= HBM_BYTES


@pytest.mark.parametrize("rows", [60001, 59001, 70001, 100001])
def test_fft_window_kernel(one_chip, rows):
    """The FFT engine's window kernel, int16 in, 40 outputs, at 10,000
    channels: the archive job's 60 s windows and its last one, and
    longer windows that pad to 131,072 points and run in channel
    blocks."""
    from tpudas.ops.fftlen import fft_channel_blocks, next_tpu_fft_len
    from tpudas.proc.lfproc import _lowpass_resample_kernel

    def shape(*s, dtype=F32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    data = shape(rows, 10000, dtype=jnp.int16)
    nfft = next_tpu_fft_len(rows)
    lowered = _lowpass_resample_kernel.lower(
        data, shape(), shape(), shape(40, dtype=jnp.int32), shape(40),
        nfft=nfft, order=4, scale=shape(),
        ch_blocks=fft_channel_blocks(nfft, data),
    )
    _check_fft_program(lowered, rows, 10000)


def test_fft_stream_step(one_chip):
    """The FFT stream step at 2,048 channels: a 32,768-row int16 block
    behind a 10 s edge carry on each side at 1 kHz."""
    from tpudas.ops.filter import _build_fft_stream_fn

    block, carry, n_ch = 32768, 20000, 2048
    fn = _build_fft_stream_fn(block, carry, n_ch, 1e-3, None, 0.45, 4,
                              None, "ch", quantized=True)
    lowered = fn.lower(
        jax.ShapeDtypeStruct((block, n_ch), jnp.int16, sharding=one_chip),
        jax.ShapeDtypeStruct((carry, n_ch), F32, sharding=one_chip),
        jax.ShapeDtypeStruct((), F32, sharding=one_chip),
    )
    _check_fft_program(lowered, block + carry, n_ch)
