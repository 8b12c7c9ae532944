"""Share of the HBM roofline of the whole low-pass on the device: least
bytes of the window's batch windows (``kernels.least_bytes``: each
window's int16 rows read once, its 1 Hz output written once, on the
first device's channels) over the first device's busy time in the
window (every op, whatever engine or kernel runs the filter), over the
device's HBM bytes per second (``peaks.json``)."""

import kernels


def read(ctx):
    red, rows = ctx["trace"], ctx.get("windows")
    if red is None or not rows or ctx["peak"] is None:
        return None
    busy = red["busy_s"][red["devices"][0]]
    if busy <= 0:
        return None
    cfg = ctx["cfg"]
    need = sum(kernels.least_bytes(
        r, kernels.device_channels(cfg),
        kernels.PAYLOAD_BYTES[cfg["payload_dtype"]], kernels.decimation(cfg))
        for r in rows)
    return 100.0 * need / busy / ctx["peak"]["hbm_bytes_per_s"]
