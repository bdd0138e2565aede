"""Share of the pool threads' time spent on chunks: the summed duration of
the ``dragon.service.chunk`` spans over (workers x traced window)."""
import program_spans as S


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0:
        return None
    chunks = S.named("dragon.service.chunk")
    if not chunks:
        return None
    return 100.0 * S.seconds(chunks) / (ctx["mix"]["service"]["workers"] * t["window_s"])
