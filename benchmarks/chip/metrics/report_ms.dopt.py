"""Host milliseconds per completed optimize call spent building its
baseline and optimized reports from host arrays: the
``dragon.session.report`` and ``dragon.session.attribute`` spans
(collections inside them included) over the calls completed while the trace
ran."""
import program_spans as S


def read(ctx):
    if not ctx["trace"] or not ctx["traced_done"]:
        return None
    spans = S.named("dragon.session.report", "dragon.session.attribute")
    return 1e3 * S.seconds(spans) / ctx["traced_done"] if spans else None
