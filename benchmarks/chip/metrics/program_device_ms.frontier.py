"""Device milliseconds per frontier call: the trace's time in every module
(the frontier cell runs no other program: the population chunk, the final
evaluation, and the seeding's, front's and hypervolume's operations), per
chip, averaged over the cell's chips, over the calls completed while the
trace ran."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    secs = sum(t["modules"].values())
    calls = ctx["traced_done"]
    return 1e3 * secs / calls if secs > 0 and calls else None
