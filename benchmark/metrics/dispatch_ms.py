"""Mean ``lfproc.dispatch`` span per window of the run's window (the
engine call until it hands back the device array, before any host
sync), from the program's ``tpudas_span_seconds`` histogram."""


def read(ctx):
    h = ctx["registry"].get("tpudas_span_seconds")
    if h is None:
        return None
    snap = h.snapshot(name="lfproc.dispatch")
    return 1e3 * snap["sum"] / snap["count"] if snap["count"] else None
