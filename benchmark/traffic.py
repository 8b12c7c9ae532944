"""Seeded DAS traffic: payloads, tdas files, and the stager process.

One generator serves every cell.  A traffic mix
(``benchmark/workloads/<traffic>.json``) says how long a file is, how
many files make one work unit (an archive job, or one edge round), how
many distinct payloads the pool holds, and how far ahead the stager
writes.  The configuration (``benchmark/configs/<config>.json``) gives
the interrogator: channels, sample rate, payload type and scale.

A payload is a low-rank int16 matrix ``U @ V``: ``U`` (rows x 8) holds
integer time series (three in-band and near-corner sines, one tone
anywhere in 20-100 Hz and 50 Hz mains hum, each a whole number of
cycles per file; three white-noise columns) and ``V`` (8 x channels)
small integer channel weights.  Every product and partial sum is an integer below
2**24, so float32 BLAS computes it exactly, and :func:`payload_columns`
gives any channel subset bit-identical to the file without reading it.

Delivered file ``i`` carries pool payload :func:`pool_index` ``(seed, i)``
under its own tdas header time, so the stream is continuous in time
whatever the pool size.  Nothing here imports JAX or tpudas: the stager
runs in a process of its own that must not touch the chip.
"""

from __future__ import annotations

import json
import os
import queue
import struct
import time

import numpy as np

# the tdas v1 header (tpudas/io/tdas.py): magic, version, t0_ns, dt_ns,
# n_time, n_ch, dtype code (1 = int16), float32 scale, d0, dx, reserved
TDAS_HEADER = struct.Struct("<4sIQQIIIfddQ")
T0_NS = int(np.datetime64("2023-03-22T00:00:00", "ns").astype(np.int64))
RANK = 8
# (amplitude in counts, lowest and highest frequency in Hz) of each sine
SINES = ((800, 0.02, 0.2), (300, 0.2, 0.4), (200, 0.5, 2.0), (400, 20, 100),
         (400, 50, 50))


def load_mix(bench_dir: str, traffic: str) -> dict:
    with open(os.path.join(bench_dir, "workloads", f"{traffic}.json")) as fh:
        return json.load(fh)


def file_rows(cfg: dict, mix: dict) -> int:
    return int(round(mix["file_seconds"] * cfg["sample_rate_hz"]))


def dt_ns(cfg: dict) -> int:
    return int(round(1e9 / cfg["sample_rate_hz"]))


def _rng(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), *key])


def channel_weights(seed: int, n_ch: int) -> np.ndarray:
    """V (RANK x n_ch): the LF column always positive, the rest signed."""
    rng = _rng(seed, 1)
    v = rng.integers(-3, 4, size=(RANK, n_ch))
    v[0] = rng.integers(1, 4, size=n_ch)
    return v.astype(np.float32)


def time_series(seed: int, k: int, rows: int, fs: float) -> np.ndarray:
    """U (rows x RANK) for pool payload ``k``.  Each sine makes a whole
    number of cycles per file, so any order of pool payloads joins into
    a stream without a step at the file seams; only the noise columns
    differ between payloads."""
    rng = _rng(seed, 2)
    file_s = rows / fs
    t = np.arange(rows, dtype=np.float64) / fs
    u = np.empty((rows, RANK), np.float32)
    for j, (amp, lo, hi) in enumerate(SINES):
        f = max(1, round(rng.uniform(lo, hi) * file_s)) / file_s
        u[:, j] = np.rint(amp * np.sin(2 * np.pi * f * t
                                       + rng.uniform(0, 2 * np.pi)))
    n = len(SINES)
    u[:, n:] = _rng(seed, 4, k).integers(-60, 61, size=(rows, RANK - n))
    return u


def pool_index(seed: int, i: int, pool: int) -> int:
    """Pool payload of delivered file ``i``: each block of ``pool``
    files is a seeded permutation of the pool."""
    return int(_rng(seed, 3, i // pool).permutation(pool)[i % pool])


def make_payload(seed: int, k: int, rows: int, n_ch: int, fs: float,
                 threads: int = 4):
    """Payload ``k`` (rows x n_ch int16), built in row blocks on a few
    threads (BLAS and the cast release the GIL)."""
    from concurrent.futures import ThreadPoolExecutor

    u, v = time_series(seed, k, rows, fs), channel_weights(seed, n_ch)
    out = np.empty((rows, n_ch), np.int16)
    step = -(-rows // (4 * threads))

    def block(lo):
        out[lo:lo + step] = u[lo:lo + step] @ v

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(block, range(0, rows, step)))
    return out


def payload_columns(seed: int, k: int, rows: int, n_ch: int, fs: float,
                    cols) -> np.ndarray:
    """The int16 values of payload ``k`` on channels ``cols`` (float64)."""
    v = channel_weights(seed, n_ch)[:, cols].astype(np.float64)
    return time_series(seed, k, rows, fs).astype(np.float64) @ v


def header(cfg: dict, i: int, rows: int) -> bytes:
    t0 = T0_NS + i * rows * dt_ns(cfg)
    return TDAS_HEADER.pack(
        b"TDAS", 1, t0, dt_ns(cfg), rows, cfg["channels"], 1,
        cfg["payload_scale"], 0.0, cfg["channel_spacing_m"], 0,
    )


def file_name(i: int) -> str:
    return f"das_{i:06d}.tdas"


def write_file(path: str, head: bytes, payload: np.ndarray) -> int:
    with open(path, "wb") as fh:
        fh.write(head)
        fh.write(payload.data)
    return len(head) + payload.nbytes


def stream_columns(cfg: dict, mix: dict, seed: int, n_files: int, cols):
    """The concatenated int16 values of files ``0..n_files-1`` on
    ``cols`` (float64, not scaled): the reference's input."""
    rows, fs = file_rows(cfg, mix), cfg["sample_rate_hz"]
    cache = {}
    parts = []
    for i in range(n_files):
        k = pool_index(seed, i, mix["pool"])
        if k not in cache:
            cache[k] = payload_columns(seed, k, rows, cfg["channels"], fs,
                                       cols)
        parts.append(cache[k])
    return np.concatenate(parts) if parts else np.zeros((0, len(cols)))


def stager_main(cfg, mix, seed, stage_dir, ready, ctl, limit):
    """The stager process: build the pool, then keep ``mix['lead']``
    files written ahead of the last one delivered, ``limit`` files in
    all (``None``: no end).  Each finished file
    is announced on ``ready`` as ``(i, bytes, seconds)``; ``ctl``
    carries the index of the newest delivered file, or ``None`` to
    stop."""
    rows, n_ch, fs = file_rows(cfg, mix), cfg["channels"], cfg["sample_rate_hz"]
    t0 = time.perf_counter()
    pool = [make_payload(seed, k, rows, n_ch, fs) for k in range(mix["pool"])]
    ready.put(("pool", 0, time.perf_counter() - t0))
    delivered, nxt = -1, 0
    while True:
        while nxt <= delivered + mix["lead"] and (limit is None
                                                   or nxt < limit):
            w0 = time.perf_counter()
            path = os.path.join(stage_dir, file_name(nxt))
            n = write_file(path + ".part", header(cfg, nxt, rows),
                           pool[pool_index(seed, nxt, mix["pool"])])
            os.rename(path + ".part", path)
            ready.put(("file", nxt, n, time.perf_counter() - w0))
            nxt += 1
        msg = ctl.get()
        if msg is None:
            return
        delivered = max(delivered, msg)


class Stager:
    """The harness's handle on the stager process: ``deliver(n)``
    renames the next ``n`` staged files into the live folder, waiting
    for the stager only if it has fallen behind (that wait is
    recorded, so a starved generator is never read as a slow system)."""

    def __init__(self, cfg, mix, seed, work_dir, limit=None):
        import multiprocessing as mp

        self.stage_dir = os.path.join(work_dir, "stage")
        self.live_dir = os.path.join(work_dir, "live")
        os.makedirs(self.stage_dir)
        os.makedirs(self.live_dir)
        ctx = mp.get_context("spawn")
        self.ready, self.ctl = ctx.Queue(), ctx.Queue()
        self.proc = ctx.Process(
            target=stager_main, daemon=True,
            args=(cfg, mix, seed, self.stage_dir, self.ready, self.ctl,
                  limit),
        )
        self.proc.start()
        self.staged = set()
        self.delivered = 0
        self.bytes_written = 0
        self.pool_s = None
        self.wait_s = 0.0  # window wait on the stager, reset by the harness
        self.min_lead = None  # fewest staged-ahead files at a delivery

    def _drain(self, block: bool) -> None:
        """Take the stager's announcements; with ``block``, wait for at
        least one (failing if the stager died or stalls for 600 s)."""
        deadline = time.monotonic() + 600
        while True:
            try:
                msg = self.ready.get(timeout=1.0) if block \
                    else self.ready.get_nowait()
            except queue.Empty:
                if not block:
                    return
                if not self.proc.is_alive():
                    raise RuntimeError("the stager process died")
                if time.monotonic() > deadline:
                    raise RuntimeError("the stager produced nothing in 600 s")
                continue
            if msg[0] == "pool":
                self.pool_s = msg[2]
            else:
                self.staged.add(msg[1])
                self.bytes_written += msg[2]
            block = False

    def deliver(self, n: int) -> list:
        self._drain(False)
        lead = sum(1 for i in self.staged if i >= self.delivered)
        self.min_lead = lead if self.min_lead is None else min(
            self.min_lead, lead)
        w0 = time.perf_counter()
        while any(i not in self.staged
                  for i in range(self.delivered, self.delivered + n)):
            self._drain(True)
        self.wait_s += time.perf_counter() - w0
        names = []
        for i in range(self.delivered, self.delivered + n):
            os.rename(os.path.join(self.stage_dir, file_name(i)),
                      os.path.join(self.live_dir, file_name(i)))
            names.append(file_name(i))
        self.delivered += n
        self.ctl.put(self.delivered - 1)
        return names

    def close(self) -> None:
        if self.proc.is_alive():
            self.ctl.put(None)
        # drain before join: a writer blocked on a full queue never exits
        deadline = time.monotonic() + 60
        while self.proc.is_alive() and time.monotonic() < deadline:
            self._drain(False)
            self.proc.join(0.2)
        if self.proc.is_alive():
            self.proc.terminate()
            self.proc.join(10)
