"""Blocking device-to-host reads of the served path per scheduler round of
the window, from the program's ``host_fetches`` and ``rounds`` counters."""


def read(ctx):
    c = ctx["counters"]
    if "host_fetches" not in c or not c.get("rounds"):
        return None
    return c["host_fetches"] / c["rounds"]
