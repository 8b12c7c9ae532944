"""Window payload bytes handed to ``device_put`` over the seconds from
each call until its array is ready, in GB/s: the program's
``tpudas_window_h2d_bytes_total`` over its summed
``tpudas_span_seconds{name="lfproc.h2d"}``."""


def read(ctx):
    reg = ctx["registry"]
    h = reg.get("tpudas_span_seconds")
    if h is None:
        return None
    secs = h.snapshot(name="lfproc.h2d")["sum"]
    nbytes = reg.value("tpudas_window_h2d_bytes_total")
    return nbytes / secs / 1e9 if secs > 0 and nbytes > 0 else None
