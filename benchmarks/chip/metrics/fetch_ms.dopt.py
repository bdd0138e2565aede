"""Host milliseconds per completed optimize call spent fetching its
baseline and optimized reports to the host: the ``dragon.session.fetch``
spans (their ``arrays`` arg counts the device arrays each moved) over the
calls completed while the trace ran.  DOpt's per-chunk sync is
``dragon.dopt.sync``, not counted here."""
import program_spans as S


def read(ctx):
    if not ctx["trace"] or not ctx["traced_done"]:
        return None
    spans = S.named("dragon.session.fetch")
    return 1e3 * S.seconds(spans) / ctx["traced_done"] if spans else None
