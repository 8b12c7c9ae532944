"""Mean ``commit`` phase per round of the window, from the program's
``tpudas_stream_round_phase_seconds{phase="commit"}`` histogram."""


def read(ctx):
    h = ctx["registry"].get("tpudas_stream_round_phase_seconds")
    if h is None:
        return None
    snap = h.snapshot(phase="commit")
    return 1e3 * snap["sum"] / snap["count"] if snap["count"] else None
