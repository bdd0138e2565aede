"""Seconds from process start to the window's start: JAX's start, the
graphs and designs, and the warm-up (compiling or loading every program the
cell's traffic uses), on the host clock."""


def read(ctx):
    return ctx["setup_s"]
