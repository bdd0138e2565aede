"""Seconds of the cell's warm-up call (compiling or loading, then running,
every program the cell's traffic uses), on the host clock."""


def read(ctx):
    return ctx["warmup_s"]
