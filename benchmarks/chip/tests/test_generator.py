"""The traffic generator gives one schedule per seed, another for another
seed, the same work to every seed, and the configured arrival rate."""
import itertools
import json
from collections import Counter

import numpy as np
import pytest

import generator as G

CHIP = G.__file__.rsplit("/", 1)[0]
LM = json.load(open(f"{CHIP}/configs/lm_1024.json"))
ML = json.load(open(f"{CHIP}/configs/mlperf_small.json"))
MIXES = {n: json.load(open(f"{CHIP}/traffic/{n}.json"))
         for n in ("design_open", "notebook_sweep", "optimize", "frontier")}
CONFIG = {"design_open": LM, "notebook_sweep": ML, "optimize": LM, "frontier": LM}
SEEDS = (7, 2**31 + 5)


def schedule(name, seed, seconds=20.0, n=400):
    mix = MIXES[name]
    if mix["loop"] == "open":
        return G.open_schedule(mix, CONFIG[name], seed, seconds)
    return list(itertools.islice(G.closed_stream(mix, CONFIG[name], seed), n))


def key(q):
    return (q.kind, q.graph, q.design, q.due_s, json.dumps(q.call, sort_keys=True), q.draw)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_schedule(name):
    assert [key(q) for q in schedule(name, SEEDS[1])] == [key(q) for q in schedule(name, SEEDS[1])]


@pytest.mark.parametrize("name", sorted(MIXES))
def test_other_seed_other_schedule(name):
    assert [key(q) for q in schedule(name, SEEDS[0])] != [key(q) for q in schedule(name, SEEDS[1])]


def test_open_loop_same_work_every_seed():
    work = [Counter((q.kind, q.graph, q.design.base) for q in schedule("design_open", s)
                    if q.design.param is None) for s in SEEDS]
    assert work[0] == work[1]


def test_open_loop_arrival_rate():
    mix, seconds = MIXES["design_open"], 20.0
    q = [x for x in schedule("design_open", SEEDS[1], seconds) if x.design.param is None]
    rate = len(q) / seconds
    # the count is fixed by the mix; the times are uniform order statistics,
    # so the mean gap is 1/rate within its sampling error
    assert rate == pytest.approx(mix["rate_per_s"], abs=0.5 / seconds)
    gaps = [b.due_s - a.due_s for a, b in zip(q, q[1:])]
    mean = sum(gaps) / len(gaps)
    assert mean == pytest.approx(1 / mix["rate_per_s"], rel=4 / len(gaps) ** 0.5)
    assert all(0.0 <= x.due_s < seconds for x in q)


def test_open_loop_same_gaps_every_seed():
    """Every seed offers the same gaps in every block, in its own order."""
    mix = MIXES["design_open"]
    per = round(mix["rate_per_s"] * mix["arrival_block_s"])
    times = [G.arrival_times(mix, s, 4 * mix["arrival_block_s"]) for s in SEEDS]
    assert len(times[0]) == len(times[1]) == 4 * per
    for block in range(3):  # the gaps of block b end at block b + 1's first arrival
        gaps = [np.sort(np.diff(t[block * per:(block + 1) * per + 1])) for t in times]
        np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9, atol=1e-12)
    assert not np.array_equal(times[0], times[1])


def test_bursts_are_due_together():
    mix = MIXES["design_open"]
    burst = [q for q in schedule("design_open", SEEDS[0], 20.0) if q.design.param is not None]
    assert len(burst) == 2 * mix["burst"]["size"]
    assert len({q.due_s for q in burst}) == 2


@pytest.mark.parametrize("name", ["notebook_sweep", "optimize"])
def test_closed_loop_same_work_per_block(name):
    per = [Counter((q.kind, q.graph) for q in schedule(name, s, n=G.BLOCK * 6)) for s in SEEDS]
    assert per[0] == per[1]


def test_frontier_stream_same_work_every_seed():
    """Frontier calls alternate the graphs, from the mix's first graph,
    with each graph's budgets and box; each graph takes every key of the
    mix's pool once a round, so every seed sends the same calls (a window
    holds only a few), warm-up runs exactly the calls the window can send,
    and the seed draws which members the check follows."""
    mix = MIXES["frontier"]
    pool = mix["keys"]
    rounds = 5
    streams = [schedule("frontier", s, n=2 * len(pool) * rounds) for s in SEEDS]
    for qs in streams:
        graphs = [q.graph for q in qs]
        assert graphs[0] == mix["first_graph"]
        assert graphs[::2] == [graphs[0]] * (len(qs) // 2) and graphs[1::2] == [graphs[1]] * (len(qs) // 2)
        assert graphs[0] != graphs[1]
        for q in qs:
            assert q.kind == "frontier"
            want = dict(G.call_of(mix, "frontier"), **mix["per_graph"][q.graph])
            assert {k: v for k, v in q.call.items() if k != "key"} == want
            assert 0 <= q.call["key"] < 2**31
        for g in set(graphs):
            keys = [q.call["key"] for q in qs if q.graph == g]
            for r in range(rounds):
                assert sorted(keys[r * len(pool):(r + 1) * len(pool)]) == sorted(pool)
        warm = {(k, g, json.dumps(c, sort_keys=True)) for k, g, c in G.calls_of(mix, LM)}
        assert {(q.kind, q.graph, json.dumps(q.call, sort_keys=True)) for q in qs} == warm
    calls = [[(q.graph, json.dumps(q.call, sort_keys=True)) for q in qs] for qs in streams]
    assert calls[0] == calls[1]
    assert streams[0][0].draw != streams[1][0].draw


def test_frontier_budgets_are_the_seed_designs(x64):
    """Each graph's budgets and box are what ``seed_budgets.py`` derives
    from the seed designs with the plain reference."""
    import seed_budgets as S

    mix = MIXES["frontier"]
    assert mix["per_graph"] == S.per_graph(dict(LM, dir=CHIP), mix["call"]["frontier"])


def test_existing_streams_draw_no_key():
    """Mixes without ``draw_key`` and ``per_graph`` send the mix's call
    arguments and nothing else."""
    for name in ("notebook_sweep", "optimize"):
        for q in schedule(name, SEEDS[0], n=20):
            assert q.call == G.call_of(MIXES[name], q.kind)
