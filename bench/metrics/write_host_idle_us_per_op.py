"""Device idle time inside the program's ``sherman.write_wave`` spans and
outside its ``sherman.price`` (the replay), per client update of the
traced window, in us: routing, stats transfers, trace build, drain."""
import hostspans


def read(ctx):
    red = hostspans.of_run(ctx)
    if red is None or not ctx["updates"]:
        return None
    idle = red.idle_within("sherman.write_wave", "sherman.price")
    return idle / ctx["updates"] * 1e6
