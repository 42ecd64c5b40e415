"""B-link sibling hops past the descent per client lookup of the window,
from the program's ``blink_hops`` counter: the cached lookups' chases and
the write routes' hops (a program without the counter gives nothing)."""


def read(ctx):
    hops = ctx["counters"].get("blink_hops")
    if hops is None or not ctx["reads"]:
        return None
    return hops / ctx["reads"]
