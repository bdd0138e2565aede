"""Host milliseconds per completed optimize call in DOpt's descent loop,
outside its host syncs: the ``dragon.dopt.descent`` spans less the
``dragon.dopt.sync`` spans inside them (dispatch, argument transfer,
history bookkeeping), over the calls completed while the trace ran."""
import program_spans as S


def read(ctx):
    if not ctx["trace"] or not ctx["traced_done"]:
        return None
    descent = S.named("dragon.dopt.descent")
    if not descent:
        return None
    return 1e3 * (S.seconds(descent) - S.seconds(S.named("dragon.dopt.sync"))) / ctx["traced_done"]
