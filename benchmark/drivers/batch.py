"""The ``batch`` driver: an archive job is
``LFProc.process_time_range`` over the whole staged spool into a fresh
output folder, built as a user builds it (spool, parameters, output
folder).  Jobs run back to back.

A driver is found by the configuration's ``"driver"`` name as
``benchmark/drivers/<driver>.py`` and gives a class ``Driver(cfg, mix,
seed, work)`` with ``stager``, ``start()``, ``unit()``,
``samples_per_unit()``, ``mark()``, ``readings()`` and ``check(cols)``.
It takes every processing parameter from the configuration file, so
the program's environment defaults decide nothing.  ``check`` reads
what the window wrote back from disk and compares it with
``benchmark/reference.py``.
"""

from __future__ import annotations

import os

import numpy as np
from jax.profiler import TraceAnnotation

import harness
import kernels
import reference as ref
import traffic as tr


class Driver:
    def __init__(self, cfg, mix, seed, work):
        self.cfg, self.mix, self.seed, self.work = cfg, mix, seed, work
        self.n_files = int(cfg["archive_seconds"] // mix["file_seconds"])
        self.rows = tr.file_rows(cfg, mix)
        self.stager = tr.Stager(cfg, mix, seed, work, limit=self.n_files)
        self.t0 = np.datetime64(tr.T0_NS, "ns")
        self.t1 = self.t0 + np.timedelta64(
            self.n_files * self.rows * tr.dt_ns(cfg), "ns")
        self.jobs = []
        self.timings = {"assemble_s": 0.0, "device_s": 0.0, "write_s": 0.0}
        self.windows = []  # input rows of each window the jobs ran

    def start(self) -> None:
        """Stage the archive: the spool every job reads."""
        self.stager.deliver(self.n_files)
        self.stager.close()
        self.spool = self.stager.live_dir
        self.mesh = harness.device_mesh(self.cfg)

    def samples_per_unit(self) -> int:
        return self.n_files * self.rows * self.cfg["channels"]

    def unit(self) -> None:
        from tpudas.io.spool import spool
        from tpudas.proc.lfproc import LFProc

        cfg = self.cfg
        out = os.path.join(self.work, f"job_{len(self.jobs):04d}")
        lfp = LFProc(spool(self.spool).sort("time").update(), mesh=self.mesh)
        lfp.update_processing_parameter(
            output_sample_interval=cfg["output_sample_interval_s"],
            process_patch_size=cfg["process_patch_size"],
            edge_buff_size=cfg["edge_buff_size"],
            engine=cfg["engine"],
            filter_order=cfg["filter_order"],
        )
        lfp.set_output_folder(out, delete_existing=True)
        with TraceAnnotation("bench.job"):
            lfp.process_time_range(self.t0, self.t1)
        self.jobs.append(out)
        self.windows += kernels.overlap_save_rows(self.n_files * self.rows,
                                                  cfg)
        for k, v in lfp.timings.items():
            self.timings[k] = self.timings.get(k, 0.0) + v

    def check(self, cols) -> dict:
        """Every job must emit the full output grid between the edge
        buffers; interior outputs are compared with the reference."""
        cfg, g = self.cfg, ref.grid_ns(self.cfg)
        buff = cfg["edge_buff_size"] * g
        t0 = int(self.t0.astype(np.int64))
        t1 = int(self.t1.astype(np.int64))
        # the program's grid ends at the last whole output step at or
        # before the last sample; each stream end loses one edge buffer
        last = t0 + (t1 - tr.dt_ns(cfg) - t0) // g * g
        want = np.arange(t0 + buff, last - buff, g)
        stream = tr.stream_columns(cfg, self.mix, self.seed, self.n_files, cols)
        m = int(ref.MARGIN_S * 1e9)
        inner = want[(want >= t0 + m) & (want <= t1 - m)]
        expect = ref.reference(cfg, stream, (inner - tr.T0_NS) // tr.dt_ns(cfg))
        missing, gap = 0, 0.0
        for out in self.jobs:
            times, vals = ref.read_outputs(out, cols)
            missing += np.setdiff1d(want, times).size + \
                np.setdiff1d(times, want).size
            sel = np.isin(times, inner)
            if sel.sum() != inner.size:
                continue  # counted in missing
            gap = max(gap, ref.max_gap(vals[sel], expect))
        return {"output_gap": gap, "missing_outputs": missing,
                "units_checked": len(self.jobs)}

    def mark(self) -> None:
        """The window starts: per-window readings start from zero."""
        self.timings = {k: 0.0 for k in self.timings}
        self.windows = []

    def readings(self) -> dict:
        return {"timings": dict(self.timings), "windows": list(self.windows)}
