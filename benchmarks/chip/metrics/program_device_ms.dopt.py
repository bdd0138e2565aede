"""Device milliseconds per optimize call: the trace's time in the modules
``programs.json`` lists under "dopt" (the fused DOpt chunks and the two
report simulates), over the calls completed while the trace ran."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    secs = sum(v for k, v in t["modules"].items() if k in ctx["programs"]["dopt"])
    calls = ctx["traced_done"]
    return 1e3 * secs / calls if secs > 0 and calls else None
