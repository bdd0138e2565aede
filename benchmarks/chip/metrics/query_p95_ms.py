"""95th percentile of every query's latency over the window, in ms: from
its due time (open loop) or its call (closed loop) to its reply in hand.  A
failed or missing reply misses every limit: where one falls in the tail, the
tail reads as the longest a reply was waited for."""
import numpy as np


def read(ctx):
    v = float(np.percentile(np.asarray(ctx["latency_s"], np.float64), 95))
    return 1e3 * (v if np.isfinite(v) else ctx["give_up_s"])
