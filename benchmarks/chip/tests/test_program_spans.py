"""``program_spans.py`` and the seven readers of the program's spans, on a
CPU trace of a tiny pooled run and one optimize call: each reader equals
the sum worked out by hand from the trace's raw events, self time is the
duration less the nested spans, and each reader returns None with no trace
and on a trace of a program without spans (``data/trace.xplane.pb.gz``,
recorded on the chip before the program had any)."""
import gc
import gzip
import json
import shutil

import pytest

import program_spans as S
import run
from conftest import HERE, ROOT

READERS = ("queue_wait_ms.service", "pool_busy.service", "report_ms.serve", "report_ms.dopt",
           "descent_host_ms.dopt", "gc_pause_ms.serve", "gc_pause_ms.dopt")
WINDOW_S, DONE, WORKERS = 1.5, 7, 2


def _ctx(trace=True) -> dict:
    return dict(trace={"window_s": WINDOW_S} if trace else None, traced_done=DONE,
                mix={"service": {"workers": WORKERS}})


def _read(name: str, ctx: dict):
    return run.read_metric({"name": name}, ctx)


def _raw(trace_dir) -> list:
    """(name, start_ns, end_ns, args, line) of every dragon. host event."""
    from jax.profiler import ProfileData

    path = S.T.find_xplane(trace_dir)
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for li, line in enumerate(plane.lines):
            out += [(e.name, e.start_ns, e.end_ns, dict(e.stats), (plane.name, li))
                    for e in line.events if e.name.startswith("dragon.")]
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A CPU trace of a few pooled simulate/explain queries and one
    optimize(8 epochs, chunks of 4), with ``TRACE_DIR`` pointed at it."""
    import jax
    from repro.api import Session
    from repro.serving import DesignQuery, FlushPolicy, PooledDesignService

    qs = [DesignQuery(qid=i, kind=("simulate", "explain")[i % 2], workload="dlrm")
          for i in range(6)]
    sess = Session()
    trace_dir = tmp_path_factory.mktemp("trace")
    with PooledDesignService("base", workers=WORKERS,
                             policy=FlushPolicy(max_batch=4, max_delay_s=0.001)) as pool:
        pool.serve(qs)  # compile outside the trace
        sess.optimize("dlrm", steps=8, chunk=4)
        with jax.profiler.trace(str(trace_dir)):
            assert all(r.ok for r in pool.serve(qs))
            sess.optimize("dlrm", steps=8, chunk=4)
            gc.collect()
    saved, S.TRACE_DIR = S.TRACE_DIR, trace_dir
    yield _raw(trace_dir)
    S.TRACE_DIR = saved


def _total_s(raw, *names) -> float:
    return sum(e - s for n, s, e, _, _ in raw if n in names) / 1e9


def test_readers_equal_hand_sums(traced):
    chunks = [a for n, _, _, a, _ in traced if n == "dragon.service.chunk"]
    assert chunks and sum(a["n"] for a in chunks) == 6
    want = {
        "queue_wait_ms.service": sum(a["wait_ms"] * a["n"] for a in chunks) / 6,
        "pool_busy.service": 100 * _total_s(traced, "dragon.service.chunk") / (WORKERS * WINDOW_S),
        "report_ms.serve": 1e3 * _total_s(traced, "dragon.session.report",
                                         "dragon.session.attribute") / DONE,
        "descent_host_ms.dopt": 1e3 * (_total_s(traced, "dragon.dopt.descent")
                                       - _total_s(traced, "dragon.dopt.sync")) / DONE,
        "gc_pause_ms.serve": 1e3 * _total_s(traced, "dragon.gc") / WINDOW_S,
    }
    want["report_ms.dopt"] = want["report_ms.serve"]
    want["gc_pause_ms.dopt"] = want["gc_pause_ms.serve"]
    ctx = _ctx()
    for name in READERS:
        got = _read(name, ctx)
        assert got is not None and got > 0, name
        assert got == pytest.approx(want[name], rel=1e-12), name


def test_self_time_is_duration_less_direct_children(traced):
    spans = S.load()["spans"]
    assert len(spans) == len(traced)

    def inside(c, p):
        return (c is not p and c["thread"] == p["thread"]
                and p["start_ns"] <= c["start_ns"] and c["end_ns"] <= p["end_ns"])

    for p in spans:
        kids = [c for c in spans if inside(c, p)]
        direct = [c for c in kids if not any(inside(c, m) for m in kids)]
        assert p["self_ns"] == (p["end_ns"] - p["start_ns"]
                                - sum(c["end_ns"] - c["start_ns"] for c in direct)), p["name"]
    tot = S.totals()
    assert tot["dragon.dopt.descent"]["count"] == 1 and tot["dragon.dopt.chunk"]["count"] == 2
    assert tot["dragon.dopt.sync"]["count"] == 2


def test_idle_gaps_without_a_device_are_empty(traced):
    # the CPU trace has no device plane, so there is no gap to name
    assert S.idle_gaps() == []


def test_readers_none_without_a_trace(traced):
    for name in READERS:
        assert _read(name, _ctx(trace=False)) is None, name


def test_readers_none_on_a_trace_without_program_spans(tmp_path):
    with gzip.open(HERE / "data" / "trace.xplane.pb.gz", "rb") as src, \
            open(tmp_path / "t.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    saved, S.TRACE_DIR = S.TRACE_DIR, tmp_path
    try:
        for name in READERS:
            assert _read(name, _ctx()) is None, name
        # its device gaps are there, and no program span names them
        gaps = S.idle_gaps()
        assert len(gaps) == 10 and {g[0] for g in gaps} == {S.NO_SPAN}
    finally:
        S.TRACE_DIR = saved


def test_benchmark_lists_each_reader_as_a_program_span():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert per[name]["source"] == "program_span"
        assert (HERE.parent / "metrics" / f"{name}.py").exists()
