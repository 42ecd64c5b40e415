"""Stacked write phases the scheduler ran per write wave (1 = no lane had
to retry), from the program's ``stacked_phases`` counter."""


def read(ctx):
    waves = ctx["write_waves"]
    return ctx["counters"]["stacked_phases"] / waves if waves else None
