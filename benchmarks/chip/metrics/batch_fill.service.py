"""Share of the service's request lanes that carried a query: queries over
(dispatches x max_batch), counted by ServiceStats over the window.  Every
dispatch pads its request axis to max_batch, so an empty lane is device work
that answers nothing."""


def read(ctx):
    s, mb = ctx["stats"], ctx["mix"]["service"]["max_batch"]
    dispatches = s["batches"] + (s["queries"] - s["batched_queries"])
    return 100.0 * s["queries"] / (dispatches * mb) if dispatches else None
