"""Mean ``read_decode`` phase per round of the window, from the program's
``tpudas_stream_round_phase_seconds{phase="read_decode"}`` histogram."""


def read(ctx):
    h = ctx["registry"].get("tpudas_stream_round_phase_seconds")
    if h is None:
        return None
    snap = h.snapshot(phase="read_decode")
    return 1e3 * snap["sum"] / snap["count"] if snap["count"] else None
