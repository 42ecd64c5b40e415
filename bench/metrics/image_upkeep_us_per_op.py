"""Device idle time inside the program's CS-cache upkeep spans
(``sherman.cache.refill``, ``.sweep`` and ``.invalidate``) per client op
of the traced window, in us: image refills, version sweeps and lazy
invalidation."""
import hostspans

UPKEEP = {"sherman.cache.refill", "sherman.cache.sweep",
          "sherman.cache.invalidate"}


def read(ctx):
    red = hostspans.of_run(ctx)
    if red is None or not ctx["ops"]:
        return None
    idle = sum(s for p, s in red.idle_by_path.items() if UPKEEP & set(p))
    return idle / ctx["ops"] * 1e6
