"""Client ops completed in the window over the window's wall time."""


def read(ctx):
    return ctx["ops"] / ctx["window_s"] if ctx["window_s"] > 0 else None
