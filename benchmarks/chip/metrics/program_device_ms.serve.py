"""Device milliseconds of the simulate/explain programs per completed query:
the trace's time in the modules ``programs.json`` lists under "serve", over
the replies completed while the trace ran."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    secs = sum(v for k, v in t["modules"].items() if k in ctx["programs"]["serve"])
    done = ctx["traced_done"]
    return 1e3 * secs / done if secs > 0 and done else None
