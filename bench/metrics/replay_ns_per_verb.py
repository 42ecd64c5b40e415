"""Host time inside the program's ``sherman.price`` spans (merge and
replay of each priced wave) per verb replayed in the traced window, in
ns."""
import hostspans


def read(ctx):
    red = hostspans.of_run(ctx)
    verbs = ctx["counters"].get("verbs")
    if red is None or not verbs or "sherman.price" not in red.span_s:
        return None
    return red.span_s["sherman.price"] / verbs * 1e9
