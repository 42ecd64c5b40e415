"""Host time inside the verb replay (``netsim.price_merged_phase``: merge
plus event loop) per client op of the traced window, in us."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    s = tr.span_s.get("bench.price_merged_phase", 0.0)
    return s / ctx["ops"] * 1e6 if s > 0 else None
