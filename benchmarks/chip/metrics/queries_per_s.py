"""Replies completed ok over the whole window: from its start to the end of
the last call, which is let finish."""


def read(ctx):
    return len(ctx["done"]) / ctx["window_s"]
