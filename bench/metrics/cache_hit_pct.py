"""CS index-cache hits over lookups that consulted it (hits, misses and
stale hits) in the window, from the program's counters (%)."""


def read(ctx):
    c = ctx["counters"]
    n = c["cache_hits"] + c["cache_misses"] + c["cache_stale"]
    return 100.0 * c["cache_hits"] / n if n else None
