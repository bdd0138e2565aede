"""Member-epochs of the frontier calls completed in the window, over the
window: each call's population times its epochs, summed, from the window's
start to the end of the last call, which is let finish."""


def read(ctx):
    work = sum(r.population * r.epochs for q, r, _ in ctx["done"] if q.kind == "frontier")
    return work / ctx["window_s"]
