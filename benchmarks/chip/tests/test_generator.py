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
         for n in ("design_open", "notebook_sweep", "optimize")}
CONFIG = {"design_open": LM, "notebook_sweep": ML, "optimize": LM}
SEEDS = (7, 2**31 + 5)


def schedule(name, seed, seconds=20.0, n=400):
    mix = MIXES[name]
    if mix["loop"] == "open":
        return G.open_schedule(mix, CONFIG[name], seed, seconds)
    return list(itertools.islice(G.closed_stream(mix, CONFIG[name], seed), n))


def key(q):
    return (q.kind, q.graph, q.design, q.due_s, tuple(sorted(q.call.items())))


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
