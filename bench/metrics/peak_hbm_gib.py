"""Peak device memory in use on the fullest chip after the window, GiB."""


def read(ctx):
    b = ctx["peak_bytes"]
    return b / 2**30 if b else None
