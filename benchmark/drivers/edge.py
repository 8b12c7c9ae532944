"""The ``edge`` driver: one ``LowpassStreamRunner`` (``build_runner``)
over a live folder; a round is the harness delivering the mix's files
(rename) followed by ``runner.step()``, which returns once the round's
outputs and carry are committed.  The interface is the batch driver's
(``benchmark/drivers/batch.py``).  ``check`` first resumes a fresh
runner from the persisted carry for one more round (the crash-resume
guarantee the carry exists for) and compares that round too.
"""

from __future__ import annotations

import os
import time

import numpy as np
from jax.profiler import TraceAnnotation

import harness
import reference as ref
import traffic as tr


def _compare(cfg, mix, seed, n_files, times, vals, cols, lo_ns, hi_ns):
    """Widest gap of outputs at times in [lo_ns, hi_ns] against the
    reference over delivered files ``0..n_files-1``."""
    keep = (times >= lo_ns) & (times <= hi_ns)
    if not keep.any():
        return None
    stream = tr.stream_columns(cfg, mix, seed, n_files, cols)
    idx = (times[keep] - tr.T0_NS) // tr.dt_ns(cfg)
    return ref.max_gap(vals[keep], ref.reference(cfg, stream, idx))


class Driver:
    def __init__(self, cfg, mix, seed, work):
        self.cfg, self.mix, self.seed, self.work = cfg, mix, seed, work
        self.stager = tr.Stager(cfg, mix, seed, work)
        self.rows = tr.file_rows(cfg, mix)
        self.out = os.path.join(work, "edge_out")
        self.round_ms = []

    def start(self) -> None:
        self.mesh = harness.device_mesh(self.cfg)
        self.runner = self._runner()

    def _runner(self):
        from tpudas.fleet.config import StreamConfig, StreamSpec
        from tpudas.fleet.engine import build_runner

        c = self.cfg
        sc = StreamConfig(
            kind="lowpass", start_time=np.datetime64(tr.T0_NS, "ns"),
            output_sample_interval=c["output_sample_interval_s"],
            edge_buffer=c["edge_buffer_s"],
            process_patch_size=c["process_patch_size"],
            filter_order=c["filter_order"], engine=c["engine"],
            stateful=c["stateful"], carry_save_every=c["carry_save_every"],
            poll_interval=c["poll_interval_s"], file_duration=0.0,
            mesh=self.mesh, pyramid=c["pyramid"], detect=c["detect"],
            live=c["live"], health=c["health"], flight=c["flight"],
            quarantine=c["quarantine"], poll_jitter=0.0,
        )
        spec = StreamSpec("edge", source=self.stager.live_dir, config=sc,
                          output_folder=self.out)
        return build_runner(spec, root=self.work)

    def samples_per_unit(self) -> int:
        return self.mix["files_per_unit"] * self.rows * self.cfg["channels"]

    def unit(self) -> None:
        t0 = time.perf_counter()
        with TraceAnnotation("bench.deliver"):
            self.stager.deliver(self.mix["files_per_unit"])
        with TraceAnnotation("bench.step"):
            res = self.runner.step()
        self.round_ms.append((time.perf_counter() - t0) * 1e3)
        if res.status != "processed":
            raise RuntimeError(f"edge round ended {res.status!r}")

    def check(self, cols) -> dict:
        """A fresh runner resumes from the persisted carry for one more
        round; then the folder must hold one contiguous output grid
        from the first post-buffer output to within two edge buffers of
        the head, and interior outputs must match the reference."""
        self.runner = self._runner()
        self.unit()
        self.round_ms.pop()
        cfg, g = self.cfg, ref.grid_ns(self.cfg)
        n_files = self.stager.delivered
        times, vals = ref.read_outputs(self.out, cols)
        head = tr.T0_NS + n_files * self.rows * tr.dt_ns(cfg)
        first = tr.T0_NS + int(cfg["edge_buffer_s"] * 1e9)
        want = np.arange(first, head - 2 * int(cfg["edge_buffer_s"] * 1e9), g)
        missing = np.setdiff1d(want, times).size + \
            int(np.sum(times < first)) + int(np.sum(times >= head))
        if times.size:
            missing += int(np.sum(np.diff(times) != g))
        m = int(ref.MARGIN_S * 1e9)
        gap = _compare(cfg, self.mix, self.seed, n_files, times, vals, cols,
                       tr.T0_NS + m, head - m)
        return {"output_gap": gap if gap is not None else float("inf"),
                "missing_outputs": missing, "units_checked": n_files}

    def mark(self) -> None:
        self.round_ms = []

    def readings(self) -> dict:
        return {"round_ms": list(self.round_ms)}
