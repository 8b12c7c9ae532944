"""Mean ``poll`` phase per round of the window, from the program's
``tpudas_stream_round_phase_seconds{phase="poll"}`` histogram."""


def read(ctx):
    h = ctx["registry"].get("tpudas_stream_round_phase_seconds")
    if h is None:
        return None
    snap = h.snapshot(phase="poll")
    return 1e3 * snap["sum"] / snap["count"] if snap["count"] else None
