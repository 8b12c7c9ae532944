"""Start of the run to the start of the window: traffic synthesis,
JAX start-up, warm-up units (compiles or cache loads included)."""


def read(ctx):
    return ctx["setup_s"]
