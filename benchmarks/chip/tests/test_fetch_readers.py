"""The two readers of ``dragon.session.fetch``, ``fetch_ms.serve`` and
``fetch_ms.dopt``, on a CPU trace of a tiny pooled run and one optimize
call: each equals the sum worked out by hand from the trace's raw events,
every fetch span carries the device arrays it moved, and each reader
returns None with no trace and on a trace of a program without spans
(``data/trace.xplane.pb.gz``)."""
import gzip
import json
import shutil

import pytest

import program_spans as S
import run
from conftest import HERE, ROOT

READERS = ("fetch_ms.serve", "fetch_ms.dopt")
DONE = 7


def _ctx(trace=True) -> dict:
    return dict(trace={"window_s": 1.5} if trace else None, traced_done=DONE)


def _read(name: str, ctx: dict):
    return run.read_metric({"name": name}, ctx)


@pytest.fixture(scope="module")
def fetches(tmp_path_factory):
    """The ``dragon.session.fetch`` events, as (duration ns, args), of a CPU
    trace of a few pooled simulate/explain queries and one optimize call
    with its two reports, with ``TRACE_DIR`` pointed at it."""
    import jax
    from jax.profiler import ProfileData
    from repro.api import Session
    from repro.serving import DesignQuery, FlushPolicy, PooledDesignService

    qs = [DesignQuery(qid=i, kind=("simulate", "explain")[i % 2], workload="dlrm")
          for i in range(6)]
    sess = Session()
    trace_dir = tmp_path_factory.mktemp("trace")
    with PooledDesignService("base", workers=2,
                             policy=FlushPolicy(max_batch=4, max_delay_s=0.001)) as pool:
        pool.serve(qs)  # compile outside the trace
        sess.optimize("dlrm", steps=8, chunk=4)
        with jax.profiler.trace(str(trace_dir)):
            assert all(r.ok for r in pool.serve(qs))
            sess.optimize("dlrm", steps=8, chunk=4)
    raw = [(e.end_ns - e.start_ns, dict(e.stats))
           for plane in ProfileData.from_file(str(S.T.find_xplane(trace_dir))).planes
           for line in plane.lines for e in line.events if e.name == "dragon.session.fetch"]
    saved, S.TRACE_DIR = S.TRACE_DIR, trace_dir
    yield raw
    S.TRACE_DIR = saved


def test_readers_equal_hand_sums(fetches):
    assert fetches
    want = 1e3 * sum(d for d, _ in fetches) / 1e9 / DONE
    for name in READERS:
        got = _read(name, _ctx())
        assert got is not None and got > 0, name
        assert got == pytest.approx(want, rel=1e-12), name


def test_each_fetch_names_the_arrays_it_moved(fetches):
    assert all(a["arrays"] == 1 for _, a in fetches)


def test_readers_none_without_a_trace(fetches):
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
    finally:
        S.TRACE_DIR = saved


def test_benchmark_lists_each_reader_as_a_program_span():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert per[name]["source"] == "program_span"
        assert per[name]["layer"] == "facade and program cache"
        assert (HERE.parent / "metrics" / f"{name}.py").exists()
