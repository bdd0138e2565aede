"""Tier-1: the profiler spans of ``core/instrument.py``.

With no profiler session a span computes nothing and the ``gc`` hook opens
nothing.  Under ``jax.profiler.trace`` a pooled service run records each
chunk with its arguments and its stage / launch / fetch / report children
on its own thread, carrying its chunk id; ``Session.optimize`` records one
descent holding its fused chunks and their host syncs; a full collection
records ``dragon.gc``; tracing an LM records ``dragon.trace.lm`` with its
vertices by role.  Replies and optimize results are bit-identical with
and without a session.  This is the only test file that starts a profiler
session.
"""
from __future__ import annotations

import gc
from pathlib import Path

import jax
import pytest

from repro.api import Session
from repro.core import instrument
from repro.serving import DesignQuery, FlushPolicy, PooledDesignService
from repro.workloads import lm_cell


def _events(trace_dir) -> list[dict]:
    """Every ``dragon.`` host event of the newest trace under ``trace_dir``:
    name, start/end (ns), args, and the (plane, line) pair of its thread."""
    from jax.profiler import ProfileData

    path = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)[-1]
    out = []
    for pi, plane in enumerate(ProfileData.from_file(str(path)).planes):
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("dragon."):
                    out.append(dict(name=e.name, start=e.start_ns, end=e.end_ns,
                                    args=dict(e.stats), thread=(pi, li)))
    return out


def _inside(child: dict, parent: dict) -> bool:
    return (child["thread"] == parent["thread"] and parent["start"] <= child["start"]
            and child["end"] <= parent["end"] and child is not parent)


def _queries() -> list[DesignQuery]:
    return [DesignQuery(qid=i, kind="simulate" if i % 2 == 0 else "explain", workload="dlrm")
            for i in range(6)]


def _replies_json(replies) -> list[str]:
    assert all(r.ok for r in replies)
    return [r.result.to_json() for r in replies]


class _Recorder:
    """Stands in for ``TraceAnnotation``: counts the annotations built."""

    made: list = []
    is_enabled = staticmethod(jax.profiler.TraceAnnotation.is_enabled)

    def __init__(self, name, **args):
        _Recorder.made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class TestWithoutSession:
    def test_lazy_args_are_never_computed(self):
        calls = []
        with instrument.span("dragon.test.lazy", lambda: calls.append(1) or {"n": 1}, n=2):
            pass
        assert calls == []

    def test_inert_span_is_one_shared_context(self):
        assert instrument.span("dragon.test.a") is instrument.span("dragon.test.b", n=1)

    def test_gc_hook_opens_no_annotation(self, monkeypatch):
        instrument.install_gc_spans()
        _Recorder.made = []
        monkeypatch.setattr(instrument, "_Annotation", _Recorder)
        gc.collect()
        with instrument.span("dragon.test.inert"):
            pass
        assert _Recorder.made == []

    def test_gc_hook_installed_once(self):
        Session()
        Session()
        assert gc.callbacks.count(instrument._gc_span) == 1


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """One pooled run and one optimize, each served without and then with a
    profiler session, plus spans nested by hand and a full collection."""
    policy = FlushPolicy(max_batch=4, max_delay_s=0.001)
    sess = Session()
    with PooledDesignService("base", workers=2, policy=policy) as pool:
        off = _replies_json(pool.serve(_queries()))
        opt_off = sess.optimize("dlrm", steps=8, chunk=4).to_json()
        trace_dir = tmp_path_factory.mktemp("trace")
        with jax.profiler.trace(str(trace_dir)):
            on = _replies_json(pool.serve(_queries()))
            opt_on = sess.optimize("dlrm", steps=8, chunk=4).to_json()
            with instrument.span("dragon.test.outer", chunk=77):
                with instrument.span("dragon.test.inner", lambda: {"lanes": 3}):
                    pass
            with instrument.span("dragon.test.after"):
                pass
            lm = lm_cell("kimi-k2-instruct", "decode_32k")
            sess.simulate_batch(["dlrm", "dlrm"])
            gc.collect()
    return dict(events=_events(trace_dir), off=off, on=on, opt_off=opt_off, opt_on=opt_on,
                lm_vertices=lm.n_vertices)


class TestRecorded:
    def test_replies_bit_identical_with_and_without_session(self, recorded):
        assert recorded["on"] == recorded["off"]
        assert recorded["opt_on"] == recorded["opt_off"]

    def test_service_chunk_args(self, recorded):
        chunks = [e for e in recorded["events"] if e["name"] == "dragon.service.chunk"]
        assert {c["args"]["kind"] for c in chunks} == {"simulate", "explain"}
        assert sum(c["args"]["n"] for c in chunks) == len(_queries())
        for c in chunks:
            a = c["args"]
            assert a["lanes"] == 4 and 1 <= a["n"] <= 4
            assert 0 <= a["flush_wait_ms"] <= a["wait_ms"]
        assert len({c["args"]["chunk"] for c in chunks}) == len(chunks)
        intake = [e for e in recorded["events"] if e["name"] == "dragon.service.intake"]
        assert sum(e["args"]["n"] for e in intake) == len(_queries())

    def test_chunk_children_nest_on_its_thread_with_its_id(self, recorded):
        events = recorded["events"]
        for c in (e for e in events if e["name"] == "dragon.service.chunk"):
            kids = [e for e in events if _inside(e, c)]
            names = {k["name"] for k in kids}
            assert {"dragon.service.stage", "dragon.session.launch",
                    "dragon.session.fetch", "dragon.session.report"} <= names
            if c["args"]["kind"] == "explain":
                assert "dragon.session.attribute" in names
            for k in kids:
                assert k["args"]["chunk"] == c["args"]["chunk"], k
            # no span of another chunk strays inside this one
            others = [e for e in events if e["name"].startswith("dragon.session.")
                      and e["args"].get("chunk") == c["args"]["chunk"] and not _inside(e, c)]
            assert others == []

    def test_optimize_descent_holds_two_chunks_each_with_a_sync(self, recorded):
        events = recorded["events"]
        descents = [e for e in events if e["name"] == "dragon.dopt.descent"]
        assert len(descents) == 1
        assert descents[0]["args"]["steps"] == 8
        chunks = [e for e in events if e["name"] == "dragon.dopt.chunk"]
        assert len(chunks) == 2 and all(_inside(c, descents[0]) for c in chunks)
        assert [c["args"]["epochs"] for c in chunks] == [4, 4]
        for c in chunks:
            syncs = [e for e in events if e["name"] == "dragon.dopt.sync" and _inside(e, c)]
            assert len(syncs) == 1
        opt = [e for e in events if e["name"] == "dragon.session.optimize"]
        assert len(opt) == 1 and _inside(descents[0], opt[0])
        # the baseline and optimized reports: two simulates inside the call
        sims = [e for e in events if e["name"] == "dragon.session.simulate" and _inside(e, opt[0])]
        assert len(sims) == 2

    def test_each_fetch_moves_one_packed_array(self, recorded):
        """Report and explain programs return one float32 buffer each: every
        fetch, batched or not, transfers one device array."""
        fetches = [e for e in recorded["events"] if e["name"] == "dragon.session.fetch"]
        assert fetches and all(e["args"]["arrays"] == 1 for e in fetches)

    def test_chunk_id_is_handed_to_nested_spans_only(self, recorded):
        by_name = {e["name"]: e for e in recorded["events"] if e["name"].startswith("dragon.test.")}
        assert by_name["dragon.test.inner"]["args"] == {"lanes": 3, "chunk": 77}
        assert "chunk" not in by_name["dragon.test.after"]["args"]

    def test_session_spans_carry_real_and_padded_vertices(self, recorded):
        """``vertices`` (dlrm's 9) beside the padded ``bucket`` (32)."""
        opt = [e for e in recorded["events"] if e["name"] == "dragon.session.optimize"]
        assert opt[0]["args"]["vertices"] == 9 and opt[0]["args"]["bucket"] == "(1, 32)"
        (batch,) = [e for e in recorded["events"] if e["name"] == "dragon.session.simulate_batch"]
        assert (batch["args"]["n"], batch["args"]["vertices"]) == (2, 18)

    def test_trace_lm_counts_vertices_by_role(self, recorded):
        (lm,) = [e for e in recorded["events"] if e["name"] == "dragon.trace.lm"]
        a = lm["args"]
        assert (a["arch"], a["shape"], a["vertices"]) == ("kimi-k2-instruct", "decode_32k",
                                                          recorded["lm_vertices"])
        # 61 latent-attention blocks of 13, 60 routed blocks of 6 and shared
        # experts of 3, one dense MLP of 4; embed, final norm and logits
        assert (a["mla"], a["gqa"], a["routed"], a["shared"], a["dense"]) == (793, 0, 360, 180, 4)
        assert a["vertices"] == 793 + 360 + 180 + 4 + 3

    def test_full_collection_recorded(self, recorded):
        gens = [e["args"]["generation"] for e in recorded["events"] if e["name"] == "dragon.gc"]
        assert 2 in gens

    def test_no_span_outside_the_dragon_names(self, recorded):
        layers = {e["name"].split(".")[1] for e in recorded["events"]}
        assert layers <= {"service", "session", "dopt", "gc", "trace", "test"}


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
