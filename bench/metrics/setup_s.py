"""Seconds from process start to the window's start: start-up, bulk load,
warm-up (and compilation where the cache misses), traffic generation."""


def read(ctx):
    return ctx["setup_s"]
