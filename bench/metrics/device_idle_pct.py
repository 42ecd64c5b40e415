"""Share of the traced window with no operation on the device (%)."""


def read(ctx):
    tr = ctx["trace"]
    return tr.idle_pct if tr is not None and tr.window_s > 0 else None
