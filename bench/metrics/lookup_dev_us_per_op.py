"""Device time of the cached descent programs (``_jit_cached_lookup``,
``_jit_route``) per client op of the traced window, in us."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    s = tr.modules("_jit_cached_lookup", "_jit_route")
    return s / ctx["ops"] * 1e6 if s > 0 else None
