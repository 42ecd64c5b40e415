"""Leaf and internal node splits per 1,000 client ops of the window, from
the program's ``leaf_splits`` and ``internal_splits`` counters (a write
wave's leaf splits, and the splits of its repair cascade and drain)."""


def read(ctx):
    c = ctx["counters"]
    if not ctx["ops"] or "leaf_splits" not in c:
        return None
    return (c["leaf_splits"] + c["internal_splits"]) / ctx["ops"] * 1e3
