"""The FFT length rule and the channel blocks that keep a padded
transform inside the chip's memory. The compile guards, which hold the
v5e's lowering of these transforms to radix stages and to its HBM, are
in ``tests/test_tpu_compile.py``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudas.ops.fftlen import (
    fft_channel_blocks,
    map_channel_blocks,
    next_tpu_fft_len,
)


@pytest.mark.parametrize("n, nfft", [
    (-5, 1), (1, 1), (2, 2), (3, 4), (4096, 4096), (4097, 8192),
    (59001, 65536), (60001, 65536), (70001, 131072),
])
def test_rule_is_the_next_power_of_two(n, nfft):
    """Among them the 60 s windows of a 1 kHz archive job (60,001
    rows) and its last window (59,001 rows)."""
    assert next_tpu_fft_len(n) == nfft


@pytest.mark.parametrize("rows, blocks", [
    (1000, 1), (60001, 1), (70001, 2), (100001, 3),
])
def test_channel_blocks_at_ten_thousand_channels(rows, blocks):
    """int16 windows of 10,000 channels: the archive job's 60,001 rows
    (65,536 points) run whole; longer windows pad to 131,072 and split."""
    data = jax.ShapeDtypeStruct((rows, 10000), jnp.int16)
    assert fft_channel_blocks(next_tpu_fft_len(rows), data) == blocks


def test_sharded_array_stays_one_block():
    """The mesh already divides the channels among its devices."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("ch",))
    data = jax.ShapeDtypeStruct(
        (100001, 10000), jnp.int16, sharding=NamedSharding(mesh, P(None, "ch"))
    )
    assert fft_channel_blocks(131072, data) == 1


@pytest.mark.parametrize("blocks", [1, 3, 4, 10])
def test_blocks_join_in_channel_order(blocks):
    """Ten channels in three or four blocks: the last block's start is
    clamped, so it overlaps the one before it."""
    data = jnp.arange(60.0).reshape(6, 10)
    out = map_channel_blocks(lambda x: x[::2] * 2, data, blocks)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(data[::2] * 2))


def _window(rows=3000, n_ch=10):
    rng = np.random.default_rng(5)
    return jnp.asarray(rng.integers(-3000, 3000, (rows, n_ch)), jnp.int16)


@pytest.mark.parametrize("blocks", [3, 4])
def test_blocked_window_kernel_matches_whole(blocks):
    from tpudas.proc.lfproc import _lowpass_resample_kernel

    data = _window()
    idx = jnp.asarray(np.arange(20) * 100 + 400, jnp.int32)
    w = jnp.full((20,), 0.25, jnp.float32)
    args = (data, jnp.float32(1e-2), jnp.float32(0.45), idx, w)

    def run(ch_blocks):
        return np.asarray(_lowpass_resample_kernel(
            *args, nfft=4096, order=4, scale=jnp.float32(1e-3),
            ch_blocks=ch_blocks,
        ))

    np.testing.assert_allclose(run(blocks), run(1), rtol=0, atol=1e-6)


@pytest.mark.parametrize("blocks", [3, 4])
def test_blocked_band_filter_matches_whole(blocks):
    from tpudas.ops.filter import _fft_filter_kernel

    data = _window().astype(jnp.float32) * 1e-3

    def run(ch_blocks):
        return np.asarray(_fft_filter_kernel(
            data, jnp.float32(1e-2), jnp.float32(1.0), jnp.float32(20.0),
            4096, 4, True, True, ch_blocks=ch_blocks,
        ))

    np.testing.assert_allclose(run(blocks), run(1), rtol=0, atol=1e-6)
