"""Host milliseconds per frontier call outside the device's work: the
``dragon.session.frontier`` spans less the device time of every module in
the trace (the frontier cell runs no other program; per chip, averaged over
the cell's chips), over the calls completed while the trace ran.  What is
left is the host's part of a call: seeding the population, dispatch, the
front and its hypervolume driven op by op, and the winners' ``.dhd`` text."""
import program_spans as S


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["traced_done"]:
        return None
    spans = S.named("dragon.session.frontier")
    if not spans:
        return None
    device = sum(t["modules"].values())
    return 1e3 * (S.seconds(spans) - device) / ctx["traced_done"]
