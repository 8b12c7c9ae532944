"""TPU-friendly FFT sizing: pad every transform to a power of two.

What XLA's TPU compiler makes of an FFT depends on the length's odd
part. Compiled for a v5e (``jax.experimental.topologies``, ``v5e:2x2``),
the window kernel ``lfproc._lowpass_resample_kernel`` at 60,001 x 10,000
int16 rows gives, by the compiled program's ``cost_analysis()`` and
``memory_analysis()``:

======  ==========  ===============================  ======  =====  ===========
length  factors     transform                        Tflop   GB     temporaries
======  ==========  ===============================  ======  =====  ===========
60750   2·3⁵·5³     6 dense ``conv f32[C,60750]``    442.98   65.7  9.74 GB
65536   2¹⁶         12 ``conv f32[C,256,256]``         4.06  187.2  10.57 GB
61440   2¹²·15      18 ``conv [C,128,32,15]`` etc.     1.33  249.1  13.0 GB
64000   2⁹·5³       radix stages                       4.84  194.5  15.58 GB
======  ==========  ===============================  ======  =====  ===========

A length with a large odd part, 5-smooth or not (59,049 = 3¹⁰ and
62,500 = 2²·5⁶ too), becomes a dense DFT matmul: O(n²) work at the
chip's f32 matmul rate, and at larger prime factors an [n, n] operand
that does not fit (a 182,952-point FFT asked for 134 GB). A length with
a large power-of-two factor splits into radix stages. Timed on one v5e
at that shape, one window takes 19.72 s at 60,750, 0.312 s at 65,536
and 0.401 s at 61,440. A power of two is the fastest there and the
simplest length that always splits, and the frequency-domain response
is sampled on the padded grid, so the filtered signal is unchanged.

The padding costs up to 2x the rows, and the compiler's temporaries
grow with the padded length: 10.58 GB at 65,536 x 10,000, ~16 bytes a
padded element. A 70,001-row window of 10,000 channels pads to 131,072
and would ask for 29.3 GB of the v5e's 15.75 GB (at the 5-smooth 72,000
it asks for 16.35 GB, which does not fit either). So a transform that
would not fit whole runs its channels in blocks, one after another
(``fft_channel_blocks``, ``map_channel_blocks``): two blocks of 5,000
channels there take 13.36 GB (~20.4 bytes a padded element, the
zero-padded block now held whole), and 65,536 x 10,000 stays one block.
"""

from __future__ import annotations

import math

import numpy as np

__all__ = [
    "fft_channel_blocks",
    "map_channel_blocks",
    "next_tpu_fft_len",
]

# HBM a v5e program may hold (the compiler's limit), and the compiler's
# temporaries per padded element of a transform, with room over the
# 20.4 measured for a blocked one
HBM_BYTES = 15.75e9
FFT_BYTES_PER_ELEM = 21


def next_tpu_fft_len(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1)."""
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def fft_channel_blocks(nfft: int, data, held_bytes: int = 0) -> int:
    """Channel blocks an ``nfft``-point transform of the (T, C) ``data``
    runs in: the fewest whose temporaries fit beside ``data`` and the
    ``held_bytes`` more the program holds (a full-size output). An
    array sharded over several devices stays whole, since its mesh
    divides the channels already; a traced array is sized by its shape
    (the local one under ``shard_map``)."""
    sharding = getattr(data, "sharding", None)
    if sharding is not None and len(sharding.device_set) > 1:
        return 1
    n_ch = int(data.shape[1])
    data_bytes = math.prod(data.shape) * np.dtype(data.dtype).itemsize
    room = max(HBM_BYTES - data_bytes - held_bytes, 1.0)
    need = FFT_BYTES_PER_ELEM * int(nfft) * n_ch
    return max(1, min(n_ch, math.ceil(need / room)))


def map_channel_blocks(fn, data, blocks: int):
    """``fn`` over ``blocks`` channel blocks of the (T, C) ``data``, one
    after another (``lax.map``), its (K, width) results joined back into
    (K, C). One block is ``fn(data)`` itself."""
    if blocks <= 1:
        return fn(data)
    import jax
    import jax.numpy as jnp

    n_ch = data.shape[1]
    width = -(-n_ch // blocks)
    blocks = -(-n_ch // width)
    # the last block's start is clamped to n_ch - width, so it overlaps
    # the block before it rather than run past the channels
    out = jax.lax.map(
        lambda j: fn(
            jax.lax.dynamic_slice_in_dim(data, j * width, width, axis=1)
        ),
        jnp.arange(blocks),
    )
    head = jnp.moveaxis(out[:-1], 0, 1).reshape(out.shape[1], -1)
    return jnp.concatenate([head, out[-1][:, blocks * width - n_ch:]], axis=1)
