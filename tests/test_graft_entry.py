"""Graft entry contract: jittable single-chip step + multichip dryrun."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import __graft_entry__ as ge


def test_entry_jits_and_runs():
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    out = np.asarray(out)
    assert out.shape == (1024, 256)
    assert np.isfinite(out).all()


@pytest.mark.slow  # the round driver exercises this path on every run
@pytest.mark.parametrize("n", [4, 8])
def test_dryrun_multichip(n):
    # no device-count gate: the dryrun spawns its own CPU child
    # with n virtual CPU devices, independent of this process's backend
    ge.dryrun_multichip(n)
