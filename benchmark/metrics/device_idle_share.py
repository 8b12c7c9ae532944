"""1 - busy/window on the first device, from the profiler trace: busy
is the union of its op intervals inside the ``bench.window`` span."""


def read(ctx):
    red = ctx["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    busy = red["busy_s"][red["devices"][0]]
    return 100.0 * (1.0 - busy / red["window_s"])
