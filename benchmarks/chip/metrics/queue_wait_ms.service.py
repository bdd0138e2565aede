"""Mean wait of a served query from its enqueue to the start of its chunk,
in ms: each ``dragon.service.chunk`` span's ``wait_ms``, weighted by its
``n`` queries, over the traced window (the flush delay, then the wait for a
free pool thread)."""
import program_spans as S


def read(ctx):
    if not ctx["trace"]:
        return None
    chunks = S.named("dragon.service.chunk")
    n = sum(c["args"]["n"] for c in chunks)
    return sum(c["args"]["wait_ms"] * c["args"]["n"] for c in chunks) / n if n else None
