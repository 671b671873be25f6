"""Peak bytes in use on the chip, from `memory_stats()`."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 2**30
