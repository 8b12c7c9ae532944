"""Share of the HBM roofline of the v3 fused stream kernel: for each of
its ops on the first device, least bytes of the block it filtered (the
block's rows from the op's own input operand, as int16 payload read
once, its 1 Hz output written once), summed, over the ops' summed
device time, over the device's HBM bytes per second."""

import kernels


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["peak"] is None:
        return None
    ops = [e for e in red["op_events"] if kernels.is_fused(e["name"])]
    t = sum(e["dur"] for e in ops)
    if t <= 0:
        return None
    cfg = ctx["cfg"]
    need = sum(kernels.least_bytes(
        kernels.operand_rows(e["name"]), kernels.device_channels(cfg),
        kernels.PAYLOAD_BYTES[cfg["payload_dtype"]], kernels.decimation(cfg))
        for e in ops)
    return 100.0 * need / (t / 1e9) / ctx["peak"]["hbm_bytes_per_s"]
