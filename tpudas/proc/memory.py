"""Memory-model chunk sizing (reference lf_das.py:90-107).

Sizes the overlap-save window so one in-flight chunk — raw window plus
the processing working set — fits a memory budget:
``bytes/sec = rate * n_ch * bytes_per_element * processing_factor *
safety``. On TPU the same closed form applies with the budget set to
usable HBM (about 14000 MB on a 16 GB v5e chip); the default
``processing_factor`` stays at the reference's 5 — the measured
peak-HBM-per-window question in PERF.md §7 (``tools/hbm_probe.py``)
validates that the cascade's working set stays under it in float32.

Distinct from this HBM model are LFProc's two HOST-side byte budgets,
which cap pipelining (not correctness): ``_STAGE_MAX_BYTES`` (2 GiB —
at most two prefetch-staged windows resident host-side) and
``_DP_MAX_BATCH_BYTES`` (8 GiB — a window-DP batch plus its stack
copy).  They bound extra host copies the pipeline keeps alive, so they
are deliberately smaller than the device budget this model sizes for.
"""

from __future__ import annotations

__all__ = ["get_patch_time"]


def get_patch_time(
    memory_size,
    sampling_rate,
    num_ch,
    bytes_per_element=8,
    processing_factor=5,
    memory_safety_factor=1.2,
):
    """Chunk length (seconds) that fits ``memory_size`` MB of memory."""
    mb_per_second = (
        sampling_rate
        * num_ch
        * bytes_per_element
        * processing_factor
        * memory_safety_factor
        / 1e6
    )
    return memory_size / mb_per_second
