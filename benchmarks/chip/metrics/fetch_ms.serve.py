"""Host milliseconds per completed query spent fetching program results to
the host: the ``dragon.session.fetch`` spans (their ``arrays`` arg counts
the device arrays each moved) over the replies completed while the trace
ran."""
import program_spans as S


def read(ctx):
    if not ctx["trace"] or not ctx["traced_done"]:
        return None
    spans = S.named("dragon.session.fetch")
    return 1e3 * S.seconds(spans) / ctx["traced_done"] if spans else None
