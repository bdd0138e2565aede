"""The one traffic generator: a mix file of parameters + a configuration + a
seed -> the schedule of design queries a run sends.

Every schedule is a pure function of (mix, configuration, seed, seconds), so
one seed gives one schedule.  Open-loop mixes give the same multiset of work
and of arrival gaps to every seed: the number of arrivals, the split of kinds
and of (graph, architecture) pairs, and the exponential gaps of each block of
``arrival_block_s`` seconds are fixed by the mix; the seed only orders them.
So seeds reorder the work and the gaps instead of changing them.
Closed-loop mixes give an endless stream the client takes from until the
window ends.  A query's keyword arguments come from the mix's ``call`` entry
for its kind, so the kind names the ``Session`` call and nothing else does;
a mix may add arguments per graph (``per_graph``) and a pool of ``keys``:
each graph's calls then take every key of the pool once per round, in an
order drawn from the seed, so every seed sends the same calls.  A mix whose
window holds only a few calls fixes the graph it starts from
(``first_graph``), so that every window holds the same ones; a keyed mix's
seed still draws which members of each reply the check follows (``draw``).
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, field

import numpy as np


@dataclass(frozen=True)
class Design:
    """A design point: a library architecture of the configuration with at
    most one parameter set to another value (float32, as the program holds
    it).  ``param`` is ``field`` or ``field.memLevel``."""

    base: str
    param: str | None = None
    value: float | None = None


@dataclass
class Query:
    qid: int
    kind: str  # simulate | explain | optimize | frontier
    graph: str  # a graph name of the configuration
    design: Design
    due_s: float | None = None  # open loop: seconds after the window opens
    call: dict = field(default_factory=dict)  # extra keyword arguments of the call
    draw: int = 0  # drawn from the seed: which members of a frontier reply the check follows


BLOCK = 5  # closed-loop calls per graph in one shuffled block of work


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, stream])


def split_counts(n: int, shares: dict) -> dict:
    """``n`` items over ``shares`` by largest remainder (exact total)."""
    keys = list(shares)
    w = np.array([shares[k] for k in keys], np.float64)
    raw = n * w / w.sum()
    base = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - base), kind="stable")[: n - base.sum()]:
        base[i] += 1
    return dict(zip(keys, base.tolist()))


def base_value(config: dict, design_base: str, param: str) -> float:
    name, _, level = param.partition(".")
    v = config["architectures"][design_base]["arch"][name]
    if level:
        v = v[("localMem", "globalBuf", "mainMem").index(level)]
    return float(v)


def f32(x: float) -> float:
    return float(np.float32(x))


def sweep_values(config: dict, base: str, param: str, points: int, span) -> list[float]:
    lo, hi = span
    v0 = base_value(config, base, param)
    return [f32(v0 * lo * (hi / lo) ** (i / (points - 1))) for i in range(points)]


def call_of(mix: dict, kind: str) -> dict:
    """The keyword arguments the mix gives every call of ``kind``."""
    return dict(mix.get("call", {}).get(kind, {}))


def call_for(mix: dict, kind: str, graph: str, key: int | None = None) -> dict:
    """The keyword arguments of one call of ``kind`` on ``graph``: the
    mix's for every such call, its ``per_graph`` ones for that graph, and
    ``key`` where one is given."""
    call = call_of(mix, kind)
    call.update(mix.get("per_graph", {}).get(graph, {}))
    if key is not None:
        call["key"] = key
    return call


def calls_of(mix: dict, config: dict) -> list[tuple[str, str, dict]]:
    """Every distinct (kind, graph, call) a closed-loop mix with a key pool
    sends, whatever the seed: what its warm-up has to run."""
    return [(k, g["name"], call_for(mix, k, g["name"], key))
            for k in mix["kinds"] for g in config["graphs"] for key in mix["keys"]]


def arrival_times(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Poisson arrivals at ``rate_per_s`` whose every block of
    ``arrival_block_s`` seconds holds one fixed multiset of exponential gaps,
    drawn from a stream no seed changes and scaled to fill the block; the
    seed only permutes each block's gaps.  Every seed thus offers the same
    load in every block, and the arrival rate is exact."""
    block = mix["arrival_block_s"]
    per = int(round(mix["rate_per_s"] * block))
    gaps = rng_for(0, stream=2).exponential(1.0, per)
    gaps *= block / gaps.sum()
    rng = rng_for(seed, stream=3)
    times = []
    for b in range(int(math.ceil(seconds / block))):
        g = gaps[rng.permutation(per)]
        times.append(b * block + np.cumsum(g) - g)
    t = np.concatenate(times)
    return t[t < seconds]


def open_schedule(mix: dict, config: dict, seed: int, seconds: float) -> list[Query]:
    rng = rng_for(seed)
    graphs = [g["name"] for g in config["graphs"]]
    archs = list(config["architectures"])
    due = arrival_times(mix, seed, seconds)
    n = len(due)
    pairs = [(g, a) for g in graphs for a in archs]
    s = mix["pairs"]["zipf_s"]
    zipf = {i: 1.0 / (i + 1) ** s for i in range(len(pairs))}
    work = [
        (k, pairs[i])
        for i, c in split_counts(n, zipf).items()
        for k, ck in split_counts(c, mix["kinds"]).items()
        for _ in range(ck)
    ]
    work = [work[i] for i in rng.permutation(n)]
    items = [(float(t), k, g, Design(a)) for t, (k, (g, a)) in zip(due, work)]
    burst = mix.get("burst")
    if burst:
        sw = burst["sweep"]
        t = burst["offset_s"]
        while t < seconds:
            param = sw["params"][rng.integers(len(sw["params"]))]
            g = graphs[rng.integers(len(graphs))]
            for v in sweep_values(config, burst["architecture"], param, burst["size"], sw["span"]):
                items.append((float(t), burst["kind"], g, Design(burst["architecture"], param, v)))
            t += burst["every_s"]
    items.sort(key=lambda it: it[0])
    return [Query(i, k, g, d, due_s=t, call=call_of(mix, k)) for i, (t, k, g, d) in enumerate(items)]


def closed_stream(mix: dict, config: dict, seed: int):
    """Endless seeded stream of calls for one closed-loop client."""
    rng = rng_for(seed)
    graphs = [g["name"] for g in config["graphs"]]
    base = mix["architecture"]
    sweep = mix.get("sweep")
    if sweep:
        param = sweep["params"][rng.integers(len(sweep["params"]))]
        values = sweep_values(config, base, param, sweep["points"], sweep["span"])
        pos = sweep["points"] // 2
    perturb = mix.get("perturb")
    if perturb:
        designs = []
        for _ in range(perturb["designs"]):
            p = perturb["params"][rng.integers(len(perturb["params"]))]
            u = rng.uniform(-perturb["log_span"], perturb["log_span"])
            designs.append(Design(base, p, f32(base_value(config, base, p) * math.exp(u))))
    # a mix may fix the graph it starts from, so that every seed's window
    # holds the same calls when it holds only a few
    first = graphs.index(mix["first_graph"]) if "first_graph" in mix else int(rng.integers(len(graphs)))
    pool = mix.get("keys")
    draw = int(rng_for(seed, stream=5).integers(2**62)) if pool else 0
    key_order, rounds = rng_for(seed, stream=4), {g: [] for g in graphs}
    # the work comes in shuffled blocks that each hold every (kind, graph) in
    # the mix's shares, so every seed sends the same work per block
    per_graph = [k for k, c in split_counts(BLOCK, mix["kinds"]).items() for _ in range(c)]
    block = []
    for qid in itertools.count():
        if mix["workloads"] == "alternate":
            kind = per_graph[qid % len(per_graph)]
            g = graphs[(first + qid) % len(graphs)]
        else:
            if not block:
                block = [(k, g) for g in graphs for k in per_graph]
                block = [block[i] for i in rng.permutation(len(block))]
            kind, g = block.pop()
        if sweep:
            pos = min(max(pos + (1 if rng.random() < 0.5 else -1), 0), sweep["points"] - 1)
            design = Design(base, param, values[pos])
        elif perturb:
            design = designs[qid % len(designs)]
        else:
            design = Design(base)
        key = None
        if pool:
            if not rounds[g]:
                rounds[g] = [pool[i] for i in key_order.permutation(len(pool))]
            key = rounds[g].pop()
        yield Query(qid, kind, g, design, call=call_for(mix, kind, g, key), draw=draw)


def designs_of(mix: dict, config: dict, seed: int, seconds: float) -> set:
    """Every design point a run of this mix can send (built in set-up)."""
    if mix["loop"] == "open":
        return {q.design for q in open_schedule(mix, config, seed, seconds)}
    base = mix["architecture"]
    out = {Design(base)}
    if mix.get("sweep"):
        sw = mix["sweep"]
        p = next(closed_stream(mix, config, seed)).design.param
        out |= {Design(base, p, v) for v in sweep_values(config, base, p, sw["points"], sw["span"])}
    if mix.get("perturb"):
        n = mix["perturb"]["designs"]
        out |= {q.design for q in itertools.islice(closed_stream(mix, config, seed), n)}
    return out
