"""Device idle time inside the program's ``sherman.lookup_wave`` spans and
outside its ``sherman.price`` (the replay), per client read of the traced
window, in us: the CS read path's host work and syncs."""
import hostspans


def read(ctx):
    red = hostspans.of_run(ctx)
    if red is None or not ctx["reads"]:
        return None
    idle = red.idle_within("sherman.lookup_wave", "sherman.price")
    return idle / ctx["reads"] * 1e6
