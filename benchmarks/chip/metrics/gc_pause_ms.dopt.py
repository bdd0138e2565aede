"""Milliseconds of garbage collection per second of the traced window: the
``dragon.gc`` spans, on every thread, over the window."""
import program_spans as S


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0:
        return None
    spans = S.named("dragon.gc")
    return 1e3 * S.seconds(spans) / t["window_s"] if spans else None
