"""Device time of the write phase programs (``_jit_write_phase``,
``_jit_repair``) per client op of the traced window, in us."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["updates"]:
        return None
    s = tr.modules("_jit_write_phase", "_jit_repair")
    return s / ctx["ops"] * 1e6 if s > 0 else None
