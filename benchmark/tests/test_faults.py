"""A run with the timed path broken underneath must come out not
correct: the harness's look for a chip is skipped (``allow_cpu``) and
everything else is the run as the chip makes it, at a tiny width.

Faults, each where it is produced:

- ``altered``: one output row of one written file moved by 1 % of the
  scale (an answer altered where it is produced);
- ``half``: every written file keeps half of its channels, the rest
  zero (half of the batch left out);
- ``unchanged``: each edge increment hands back the cascade buffers it
  was given (a step that returns its state unchanged).  A persisted
  carry left stale is no fault: the program resumes from it and
  rewrites the same outputs, which a CPU run showed.

The channel mesh has no exchange between chips (channels are
independent), so there is no exchange to leave out: ``half`` on the
mesh cell stands for a shard that never arrives.
"""

import numpy as np
import pytest

import harness
from conftest import with_held_out

CELLS = ["batch-10k", "edge-2048-60s", "edge-2048-10s", "batch-10k-mesh4"]


def _wrap_writer(monkeypatch, mutate):
    import tpudas.io.registry as reg

    real = reg._resolve
    state = {"n": 0}

    def resolve(fmt):
        read, write, scan = real(fmt)
        if fmt != "dasdae":
            return read, write, scan

        def bad_write(patch, path, **kw):
            state["n"] += 1
            data = np.array(patch.host_data(), copy=True)
            return write(patch.new(data=mutate(data, patch, state["n"])),
                         path, **kw)

        return read, bad_write, scan

    monkeypatch.setattr(reg, "_resolve", resolve)


def _altered(data, patch, n):
    if n == 6:  # well inside the compared interior of every cell
        ax = patch.axis_of("time")
        sl = [slice(None)] * data.ndim
        sl[ax] = data.shape[ax] // 2
        data[tuple(sl)] += 0.01 * np.abs(data).max()
    return data


def _half(data, patch, n):
    ax = 1 - patch.axis_of("time")
    sl = [slice(None)] * data.ndim
    sl[ax] = slice(data.shape[ax] // 2, None)
    data[tuple(sl)] = 0.0
    return data


def _run(cell):
    result, checks = harness.run(cell, 1234, 1.0, False, allow_cpu=True,
                                 overrides={"channels": 16},
                                 bench=with_held_out())
    # a stream too short to have compared anything reads inf: no proof
    assert checks["output_gap"]["value"] < float("inf"), checks
    return result, checks


@pytest.mark.parametrize("cell", ["batch-10k", "edge-2048-10s"])
def test_sound_run_is_correct(cell):
    result, checks = _run(cell)
    assert result["correct"], checks


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["altered", "half"])
def test_broken_output_is_not_correct(monkeypatch, cell, fault):
    _wrap_writer(monkeypatch, {"altered": _altered, "half": _half}[fault])
    result, checks = _run(cell)
    assert not result["correct"], checks


@pytest.mark.parametrize("cell", ["edge-2048-60s", "edge-2048-10s"])
def test_unchanged_state_is_not_correct(monkeypatch, cell):
    """Each increment hands back the filter state it was given: the
    ingest position moves on, the cascade's buffers do not."""
    import tpudas.proc.stream as stream

    real = stream.process_increment
    calls = {"n": 0}

    def stuck(lfp, carry, edtime):
        bufs = carry.bufs
        out = real(lfp, carry, edtime)
        calls["n"] += 1
        if calls["n"] > 1 and bufs:
            carry.bufs = bufs
        return out

    monkeypatch.setattr(stream, "process_increment", stuck)
    result, checks = _run(cell)
    assert calls["n"] > 2
    assert not result["correct"], checks
