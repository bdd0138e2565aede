"""``trace_reduce.py`` on a small trace recorded on the chip
(``data/trace.xplane.pb.gz``, one second of a traced run of
``mlperf_small.notebook_sweep``):
its busy time, idle share and per-program device time match the numbers
recorded with the trace (``data/trace_expected.json``) and a plain
recount of the trace's events."""
import gzip
import json
import shutil

import pytest

import trace_reduce as T
from conftest import HERE

DATA = HERE / "data"


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA / "trace.xplane.pb.gz", "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def expected():
    return json.loads((DATA / "trace_expected.json").read_text())


def test_matches_recorded_numbers(xplane, expected):
    # run_busy_s is what the traced run itself printed, on the chip
    assert expected["busy_s"] == pytest.approx(expected["run_busy_s"], rel=1e-9)
    got = T.reduce(xplane, expected["window_s"])
    assert got["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    idle = 1 - got["busy_s"] / got["window_s"]
    assert idle == pytest.approx(expected["idle_share"], rel=1e-9)
    for name, secs in expected["modules"].items():
        assert got["modules"][name] == pytest.approx(secs, rel=1e-9)


def test_matches_a_plain_recount(xplane, expected):
    devices, _ = T.load(xplane)
    assert devices, "the trace holds no device plane"
    busy = []
    for d in devices:
        # walk the op events in start order, extending the covered end
        covered, end = 0, None
        for _, s, e in sorted((x for x in (d["ops"] or d["modules"])), key=lambda x: x[1]):
            if end is None or s > end:
                covered += e - s
                end = e
            elif e > end:
                covered += e - end
                end = e
        busy.append(covered / 1e9)
    got = T.reduce(xplane, expected["window_s"])
    assert got["busy_s"] == pytest.approx(sum(busy) / len(busy), rel=1e-12)
    per = {}
    for d in devices:
        for name, s, e in d["modules"]:
            per[T.module_name(name)] = per.get(T.module_name(name), 0.0) + (e - s) / 1e9 / len(devices)
    assert got["modules"] == pytest.approx(per, rel=1e-12)
    assert 0 < got["busy_s"] <= got["window_s"]
