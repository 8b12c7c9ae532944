"""Kernels as the trace names them, and the least HBM bytes the
low-pass needs, computed from shapes the benchmark knows.

The v3 fused stream kernel (``fused_cascade_pallas``) reaches the trace
as an XLA op whose text names ``custom_call_target="tpu_custom_call"``
and returns a tuple of the output and its carry leaves (recorded on a
v5e, PR 22): ``%fn.1 = (f32[10,6,2048]{...}, ...) custom-call(...,
f32[60000,2048] ...)``.  The v2 stage kernel returns one array.

The least bytes of a low-pass are its input read once, as the payload
is stored, and its decimated output written once, whatever engine,
kernel or padding implements it.
"""

from __future__ import annotations

import re

PALLAS = 'custom_call_target="tpu_custom_call"'
_ARRAY = re.compile(r"\b[a-z]+\d+\[(\d+),(\d+)\]")
PAYLOAD_BYTES = {"int16": 2, "float32": 4}


def is_fused(op: str) -> bool:
    return PALLAS in op and op.split("= ", 1)[-1].startswith("(")


def operand_rows(op: str) -> int:
    """Rows of the largest 2-D operand of a kernel op."""
    args = op.split("custom-call(", 1)[-1]
    shapes = [(int(a) * int(b), int(a)) for a, b in _ARRAY.findall(args)]
    return max(shapes)[1] if shapes else 0


def least_bytes(rows: int, channels: int, payload_bytes: int,
                decimation: int) -> int:
    return rows * channels * payload_bytes + (rows // decimation) * channels * 4


def decimation(cfg: dict) -> int:
    return int(round(cfg["sample_rate_hz"] * cfg["output_sample_interval_s"]))


def device_channels(cfg: dict) -> int:
    return -(-cfg["channels"] // max(1, cfg.get("mesh_devices") or 1))


def overlap_save_rows(n_rows: int, cfg: dict) -> list:
    """Input rows of each window of a batch job over ``n_rows`` samples:
    windows of ``process_patch_size`` output steps overlapping by two
    edge buffers, the last one cut at the final output step (the
    reference's overlap-save schedule, ``lf_das.py``)."""
    step = decimation(cfg)
    n_grid = (n_rows - 1) // step + 1
    patch, buff = cfg["process_patch_size"], cfg["edge_buff_size"]
    patch = min(patch, n_grid - 1)
    spans = [(0, patch)]
    end = patch
    while end + patch - 2 * buff < n_grid:
        spans.append((end - 2 * buff, end + patch - 2 * buff))
        end += patch - 2 * buff
    if n_grid - end > 1:
        spans.append((end - 2 * buff, n_grid - 1))
    return [(hi - lo) * step + 1 for lo, hi in spans]
