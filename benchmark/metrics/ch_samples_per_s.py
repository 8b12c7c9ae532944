"""Input channel-samples of every unit completed in the window, over
the time from the window's start to the last completion (M per s)."""


def read(ctx):
    if not ctx["units"] or ctx["window_s"] <= 0:
        return None
    return ctx["samples"] / ctx["window_s"] / 1e6
