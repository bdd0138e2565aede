"""Share of the vertex axis, in %, that the optimize calls traced spend on
padding: 1 - (real vertices) / (padded vertices), summed over the
``dragon.session.optimize`` spans from their ``vertices`` arg and their
``bucket`` arg, ``(workloads, padded vertices)``.  A program whose spans
carry no ``vertices`` reads nothing."""
import re

import program_spans as S


def read(ctx):
    if not ctx["trace"]:
        return None
    spans = [s for s in S.named("dragon.session.optimize") if "vertices" in s["args"]]
    if not spans:
        return None
    real = sum(int(s["args"]["vertices"]) for s in spans)
    padded = 0
    for s in spans:
        w, v = (int(x) for x in re.findall(r"\d+", str(s["args"]["bucket"])))
        padded += w * v
    return 100.0 * (1.0 - real / padded)
