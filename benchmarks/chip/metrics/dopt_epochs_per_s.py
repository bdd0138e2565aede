"""DOpt epochs of the optimize calls completed in the window, over the
window: from its start to the end of the last call, which is let finish."""


def read(ctx):
    epochs = sum(r.epochs for q, r, _ in ctx["done"] if q.kind == "optimize")
    return epochs / ctx["window_s"]
