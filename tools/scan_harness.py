"""Shared resident-scan measurement harness for the tools/ probes.

bench.py's kernel-mode methodology, standalone: NW distinct resident
windows, the whole timed loop one device dispatch (lax.scan), RNG
outside the timer, best-of-2.  Single source so every probe measures
the same way.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp


def measure(fn, T, C, iters=96, dtype="float32"):
    """Best-of-2 seconds per (T, C) window through ``fn``."""
    es = 2 if dtype == "int16" else 4
    nw = max(1, min(6, int(9e9 // (T * C * es))))
    rep = max(1, -(-iters // nw))
    if dtype == "int16":
        gen = jax.jit(
            lambda key: jax.random.randint(
                key, (nw, T, C), -3000, 3000, jnp.int16
            )
        )
    else:
        gen = jax.jit(
            lambda key: jax.random.normal(key, (nw, T, C), jnp.float32)
        )
    stack = gen(jax.random.PRNGKey(0))
    jax.block_until_ready(stack)

    @jax.jit
    def run(st):
        def body(tot, w):
            return tot + jnp.sum(jnp.abs(fn(w)).astype(jnp.float32)), None

        def outer(tot, _):
            t, _ = jax.lax.scan(body, tot, st)
            return t, None

        tot, _ = jax.lax.scan(
            outer, jnp.zeros((), jnp.float32), None, length=rep
        )
        return tot

    assert np.isfinite(float(run(stack)))
    best = 1e30
    for _ in range(2):
        t0 = time.perf_counter()
        assert np.isfinite(float(run(stack)))
        best = min(best, time.perf_counter() - t0)
    return best / (nw * rep)
