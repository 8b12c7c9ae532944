"""Seconds the batch compute loop waited on the prefetch thread's
window read (sum of ``LFProc.timings['assemble_s']`` over the window's
jobs), as a share of the window."""


def read(ctx):
    t = ctx.get("timings")
    if not t or ctx["window_s"] <= 0:
        return None
    return 100.0 * t["assemble_s"] / ctx["window_s"]
