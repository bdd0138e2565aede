"""Worker pool & multi-process serving: frame protocol, staged-assembly
bit-identity, pooled dispatch, lossless stats aggregation, chaos determinism
under concurrency, worker crash/kill requeue, and AOT cache multi-writer
contention (docs/serving.md §worker pool)."""
import json
import os
import pickle
import socket
import subprocess
import sys
import tempfile
import textwrap

import jax
import pytest

from repro.serving import (
    BatchingDesignService,
    ChaosConfig,
    ChaosInjector,
    DesignQuery,
    DesignService,
    FlushPolicy,
    MultiProcessDesignService,
    PooledDesignService,
    ServiceStats,
    StagedBatchingService,
)
from repro.serving import protocol
from tests._per_leaf import per_leaf_replies

POLICY = FlushPolicy(max_batch=8, max_delay_s=0.001)

#: one compiled-program cache for every in-process service in this file —
#: parameter values are traced data, so sharing is exact and saves compiles
_SHARED: dict = {}


def _mk(cls=BatchingDesignService, **kw):
    kw.setdefault("programs", _SHARED)
    return cls("base", policy=POLICY, **kw)


def _queries(n, workloads=("lstm", "gcn")):
    archs = [None, "edge", "datacenter", "mobile"]
    return [
        DesignQuery(qid=i, kind="simulate" if i % 2 == 0 else "explain",
                    workload=workloads[(i // 2) % len(workloads)],
                    architecture=archs[(i // 2) % 4])
        for i in range(n)
    ]


def _fingerprints(replies):
    return [json.dumps(r.result.to_json(), sort_keys=True) for r in replies]


# --------------------------------------------------------------------------- #
# frame protocol
# --------------------------------------------------------------------------- #


class TestProtocol:
    def test_roundtrip_over_socketpair(self):
        a, b = socket.socketpair()
        try:
            protocol.send_frame(a, "chunk", (7, ["q0", "q1"]))
            protocol.send_frame(a, "hb", 3)
            assert protocol.recv_frame(b) == ("chunk", (7, ["q0", "q1"]))
            assert protocol.recv_frame(b) == ("hb", 3)
        finally:
            a.close(), b.close()

    def test_eof_mid_frame_raises(self):
        a, b = socket.socketpair()
        try:
            frame = protocol.encode_frame("chunk", list(range(100)))
            a.sendall(frame[: len(frame) // 2])
            a.close()
            with pytest.raises(protocol.ProtocolError, match="mid-frame"):
                protocol.recv_frame(b)
        finally:
            b.close()

    def test_clean_eof_between_frames_raises(self):
        a, b = socket.socketpair()
        a.close()
        try:
            with pytest.raises(protocol.ProtocolError):
                protocol.recv_frame(b)
        finally:
            b.close()

    def test_bad_magic_rejected(self):
        a, b = socket.socketpair()
        try:
            a.sendall(b"XXXX" + (0).to_bytes(4, "big"))
            with pytest.raises(protocol.ProtocolError, match="magic"):
                protocol.recv_frame(b)
        finally:
            a.close(), b.close()

    def test_absurd_length_rejected(self):
        a, b = socket.socketpair()
        try:
            a.sendall(protocol.MAGIC + (protocol.MAX_FRAME + 1).to_bytes(4, "big"))
            with pytest.raises(protocol.ProtocolError, match="exceeds"):
                protocol.recv_frame(b)
        finally:
            a.close(), b.close()

    def test_unpicklable_payload_fails_before_any_bytes_hit_the_wire(self):
        a, b = socket.socketpair()
        try:
            with pytest.raises(Exception):
                protocol.send_frame(a, "replies", lambda: None)
            b.settimeout(0.05)
            with pytest.raises(socket.timeout):
                b.recv(1)  # stream is still clean: nothing was written
        finally:
            a.close(), b.close()


# --------------------------------------------------------------------------- #
# staged assembly: bit-identity with the sequential tree-stack path
# --------------------------------------------------------------------------- #


class TestStagedAssembly:
    @pytest.fixture(scope="class")
    def baseline(self):
        svc = _mk()
        qs = _queries(16)
        return qs, _fingerprints(svc.serve(qs))

    def test_staged_replies_bit_identical_to_sequential(self, baseline):
        qs, want = baseline
        got = _fingerprints(_mk(StagedBatchingService).serve(qs))
        assert got == want

    def test_staged_replies_bit_identical_to_per_leaf_path(self, baseline):
        """The staged dispatcher hands the packed programs' outputs to the
        session unchanged; each reply equals the unpacked program's, fetched
        leaf by leaf, at the service's request bucket."""
        qs, _ = baseline
        svc = _mk(StagedBatchingService)
        got = _fingerprints(svc.serve(qs))
        want = []
        for q in qs:
            (rep,) = per_leaf_replies(svc.session, [q.workload], [q.architecture], kind=q.kind,
                                      objective=q.objective, request_bucket=svc.request_bucket)
            want.append(json.dumps(rep.to_json(), sort_keys=True))
        assert got == want

    def test_singleton_queries_route_through_staged_dispatch(self, baseline):
        qs, want = baseline
        svc = _mk(StagedBatchingService)
        got = _fingerprints([svc.submit(q) for q in qs])
        assert got == want
        # a size-1 staged dispatch is not a coalesce: stats must not claim one
        assert svc.stats.batches == 0 and svc.stats.batched_queries == 0

    def test_staging_buffers_are_reused_not_leaked(self, baseline):
        qs, _ = baseline
        svc = _mk(StagedBatchingService)
        svc.serve(qs)
        n_sets = len(svc._assembler._tls.bufs)
        assert n_sets >= 1
        svc.serve(qs)
        # one buffer set per (spec, bucket), not per call: repeats don't grow it
        assert len(svc._assembler._tls.bufs) == n_sets


# --------------------------------------------------------------------------- #
# pooled service: async dispatch, ordering, isolation
# --------------------------------------------------------------------------- #


class TestPooledService:
    @pytest.fixture(scope="class")
    def baseline(self):
        svc = _mk()
        qs = _queries(16)
        return qs, _fingerprints(svc.serve(qs))

    def test_pooled_replies_bit_identical_and_ordered(self, baseline):
        qs, want = baseline
        with _mk(PooledDesignService, workers=2) as pool:
            replies = pool.serve(qs)
        assert [r.qid for r in replies] == [q.qid for q in qs]
        assert all(r.ok for r in replies)
        assert _fingerprints(replies) == want

    def test_ticket_api(self, baseline):
        qs, want = baseline
        with _mk(PooledDesignService, workers=2) as pool:
            tickets = [pool.enqueue(q) for q in qs]
            assert pool.join(timeout=60)
            replies = [pool.take(t) for t in tickets]
            assert _fingerprints(replies) == want
            assert pool.take(tickets[0]) is None  # a reply pops exactly once

    def test_poison_query_is_isolated(self):
        qs = _queries(6)
        qs[2] = DesignQuery(qid=2, kind="simulate", workload="no_such_workload_xyz")
        with _mk(PooledDesignService, workers=2) as pool:
            replies = pool.serve(qs)
        assert [r.qid for r in replies] == [0, 1, 2, 3, 4, 5]
        assert not replies[2].ok and replies[2].error.code == "client-error"
        assert all(r.ok for i, r in enumerate(replies) if i != 2)
        st = pool.stats
        assert st.queries == 6 and st.ok == 5

    def test_enqueue_after_close_raises(self):
        pool = _mk(PooledDesignService, workers=1)
        pool.close()
        pool.close()  # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            pool.enqueue(_queries(1)[0])


# --------------------------------------------------------------------------- #
# satellite 1: ServiceStats.merge — lossless aggregation
# --------------------------------------------------------------------------- #


def _stats(**kw):
    base = dict(programs=1, hits=0, misses=0, traces=0, queries=0, ok=0,
                retries=0, deadline_misses=0, degraded=0, errors={},
                stragglers=(), breakers={})
    base.update(kw)
    return ServiceStats(**base)


class TestStatsMerge:
    def test_counters_sum_and_errors_merge_keywise(self):
        a = _stats(queries=5, ok=4, retries=2, errors={"transient": 1},
                   stragglers=((1, 0.5),))
        b = _stats(queries=3, ok=3, errors={"transient": 2, "numeric": 1},
                   stragglers=((7, 0.9),))
        m = a.merge(b)
        assert (m.queries, m.ok, m.retries) == (8, 7, 2)
        assert m.errors == {"transient": 3, "numeric": 1}
        assert m.stragglers == ((1, 0.5), (7, 0.9))
        assert m.availability == 7 / 8

    def test_add_operator_reduces_a_fleet(self):
        parts = [_stats(queries=i, ok=i) for i in (1, 2, 3)]
        total = sum(parts[1:], parts[0])
        assert total.queries == 6 and total.availability == 1.0

    def test_breaker_lanes_merge_keywise(self):
        a = _stats(breakers={("simulate", (1, 32)): dict(open=False, failures=1,
                                                         trips=0, rejected=0)})
        b = _stats(breakers={("simulate", (1, 32)): dict(open=True, failures=3,
                                                         trips=1, rejected=2),
                             ("explain", (1, 32)): dict(open=False, failures=0,
                                                        trips=0, rejected=0)})
        m = a.merge(b).breakers
        assert m[("simulate", (1, 32))] == dict(open=True, failures=4, trips=1,
                                                rejected=2)
        assert ("explain", (1, 32)) in m

    def test_partitioned_workers_sum_to_the_sequential_ledger(self):
        """The property the fleet view rests on: per-worker stats summed over
        any partition of a query stream equal the sequential run's ledger —
        chaos, retries and deadlines key on the query, never the worker."""
        chaos = ChaosConfig(seed=5, p_transient=0.3, p_nan=0.2,
                            p_latency=0.2, latency_s=0.0)
        n = 24
        seq_programs: dict = {}
        seq = DesignService("base", chaos=ChaosInjector(chaos),
                            request_bucket=POLICY.max_batch,
                            programs=seq_programs)
        seq.serve(_queries(n))
        want = seq.stats

        for k in (2, 3):
            part_programs: dict = {}
            workers = [
                DesignService("base", chaos=ChaosInjector(chaos),
                              request_bucket=POLICY.max_batch,
                              programs=part_programs)
                for _ in range(k)
            ]
            for i, q in enumerate(_queries(n)):
                workers[i % k].submit(q)
            merged = workers[0].stats
            for w in workers[1:]:
                merged = merged + w.stats
            for fld in ("queries", "ok", "retries", "deadline_misses",
                        "degraded", "errors", "hits", "misses", "traces",
                        "batches", "batched_queries"):
                assert getattr(merged, fld) == getattr(want, fld), (k, fld)
            assert merged.availability == want.availability


# --------------------------------------------------------------------------- #
# satellite 3: chaos determinism under concurrency
# --------------------------------------------------------------------------- #


class TestChaosDeterminismUnderConcurrency:
    CHAOS = ChaosConfig(seed=11, p_transient=0.3, p_nan=0.2, p_latency=0.3,
                        latency_s=0.001)

    def _outcomes(self, replies):
        return [
            (r.qid, r.ok, r.attempts, r.error.code if r.error else None)
            for r in sorted(replies, key=lambda r: r.qid)
        ]

    def test_same_seed_same_schedule_regardless_of_worker_count(self):
        """The chaos schedule is a pure function of (seed, qid): 1-worker
        and 3-worker pools must observe identical per-query faults, retry
        counts and (bit-identical) results — completion order is the only
        thing allowed to differ."""
        qs = _queries(16)
        runs = {}
        for workers in (1, 3):
            inj = ChaosInjector(self.CHAOS)
            with _mk(PooledDesignService, workers=workers, chaos=inj) as pool:
                replies = pool.serve([DesignQuery(**q.__dict__) for q in qs])
            runs[workers] = (self._outcomes(replies), _fingerprints(replies),
                            dict(inj.injected))
        assert runs[1] == runs[3]

    def test_pooled_chaos_outcomes_match_sequential(self):
        qs = _queries(16)
        seq = _mk(chaos=ChaosInjector(self.CHAOS))
        want = (self._outcomes(seq.serve(qs)), _fingerprints(seq.replies))
        inj = ChaosInjector(self.CHAOS)
        with _mk(PooledDesignService, workers=2, chaos=inj) as pool:
            replies = pool.serve([DesignQuery(**q.__dict__) for q in qs])
        assert (self._outcomes(replies), _fingerprints(replies)) == want

    def test_worker_kill_draw_appends_to_the_schedule(self):
        """Adding p_worker_kill must not reshuffle the historical fault
        schedule — new fault classes draw LAST."""
        base = ChaosInjector(ChaosConfig(seed=3, p_transient=0.4, p_nan=0.3))
        extended = ChaosInjector(ChaosConfig(seed=3, p_transient=0.4, p_nan=0.3,
                                             p_worker_kill=0.5))
        for qid in range(64):
            a, b = base.plan(qid), extended.plan(qid)
            assert (a.transient, a.compile_fail, a.nan, a.latency,
                    a.cache_corrupt) == (b.transient, b.compile_fail, b.nan,
                                         b.latency, b.cache_corrupt)
        assert any(extended.plan(q).worker_kill for q in range(64))
        assert not any(base.plan(q).worker_kill for q in range(64))


# --------------------------------------------------------------------------- #
# multi-process serving: shared AOT cache, crash containment
# --------------------------------------------------------------------------- #


class TestMultiProcess:
    @pytest.fixture(scope="class")
    def warmed(self, tmp_path_factory):
        """A preheated shared cache + the sequential baseline replies."""
        cache_dir = str(tmp_path_factory.mktemp("pool-aot"))
        seq = BatchingDesignService("base", policy=POLICY, cache_dir=cache_dir)
        seq.warmup(["lstm", "gcn"])
        qs = _queries(12)
        return cache_dir, qs, _fingerprints(seq.serve(qs))

    def test_two_workers_bit_identical_zero_compile(self, warmed):
        cache_dir, qs, want = warmed
        with MultiProcessDesignService("base", workers=2, cache_dir=cache_dir,
                                       policy=POLICY) as mp:
            replies = mp.serve(qs)
            st = mp.stats
        assert [r.qid for r in replies] == [q.qid for q in qs]
        assert all(r.ok for r in replies)
        assert _fingerprints(replies) == want
        # both workers rehydrated the parent's executables: nothing compiled
        assert st.traces == 0
        assert st.queries == len(qs) and st.ok == len(qs)

    def test_worker_kill_is_requeued_and_availability_holds(self, warmed):
        cache_dir, qs, want = warmed
        chaos = ChaosConfig(seed=7, p_worker_kill=0.15)
        with MultiProcessDesignService("base", workers=2, cache_dir=cache_dir,
                                       policy=POLICY, chaos=chaos,
                                       worker_timeout_s=6.0) as mp:
            replies = mp.serve(qs)
            info = mp.pool_info
        assert info["kills"] >= 1 and info["requeues"] >= 1
        assert all(r.ok for r in replies)  # availability == 1.0
        assert _fingerprints(replies) == want  # requeued answers are exact

    def test_heartbeat_silence_is_worker_death(self, warmed, tmp_path):
        """A hung worker (handshakes, then never beacons) must be detected
        by heartbeat timeout and its in-flight queries resolved — here to
        structured errors, since no live worker remains."""
        cache_dir, qs, _ = warmed
        stub = tmp_path / "stub_worker.py"
        stub.write_text(textwrap.dedent("""
            import argparse, os, socket, time
            from repro.serving import protocol

            ap = argparse.ArgumentParser()
            ap.add_argument("--socket"), ap.add_argument("--id", type=int)
            args = ap.parse_args()
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            conn.connect(args.socket)
            protocol.send_frame(conn, "hello", {"worker": args.id, "pid": os.getpid()})
            tag, cfg = protocol.recv_frame(conn)
            protocol.send_frame(conn, "ready", {"worker": args.id, "disk_loaded": 0})
            time.sleep(60)  # hang: no heartbeats, no replies
        """))
        mp = MultiProcessDesignService(
            "base", workers=2, cache_dir=cache_dir, policy=POLICY,
            heartbeat_s=0.1, worker_timeout_s=0.8,
            worker_cmd=[sys.executable, str(stub)],
        )
        with mp:
            replies = mp.serve(qs[:4])
        assert mp.pool_info["alive"] == 0
        assert len(replies) == 4  # serve() returned instead of hanging
        assert all(not r.ok for r in replies)
        assert all(r.error.code == "transient" for r in replies)

    def test_cache_dir_is_required(self):
        with pytest.raises(ValueError, match="cache_dir"):
            MultiProcessDesignService("base", workers=2)

    def test_architecture_must_cross_the_process_boundary(self, warmed):
        cache_dir, _, _ = warmed
        from repro.api import Architecture

        with pytest.raises(TypeError, match="process boundary"):
            MultiProcessDesignService(Architecture("edge"), cache_dir=cache_dir)

    def test_refuses_when_this_process_holds_the_tpu(self, monkeypatch, tmp_path):
        from repro.kernels import runtime

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(runtime, "backend_initialized", lambda: True)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        mp = MultiProcessDesignService("base", workers=1, cache_dir=tmp_path)
        with pytest.raises(RuntimeError, match="holds the TPU"):
            mp.start()
        assert not mp._workers  # refused before spawning anything

    def test_refuses_more_workers_than_chips(self, monkeypatch, tmp_path):
        from repro.kernels import runtime

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(runtime, "backend_initialized", lambda: False)
        monkeypatch.setattr(runtime, "local_tpu_chips", lambda: 1)
        mp = MultiProcessDesignService("base", workers=2, cache_dir=tmp_path)
        with pytest.raises(RuntimeError, match="2 workers but 1 local TPU chip"):
            mp.start()
        assert not mp._workers
        # a CPU-only platform list is not bound by the chip count
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        mp._check_chips()


# --------------------------------------------------------------------------- #
# satellite 2: AOT cache multi-writer contention
# --------------------------------------------------------------------------- #

_HAMMER = """
import pickle, sys
sys.path.insert(0, {src!r})
from repro.kernels import runtime
runtime.serialize_compiled = lambda fn: pickle.dumps(fn)
runtime.deserialize_compiled = pickle.loads
from repro.serving.aotcache import AotCache

cache = AotCache({path!r})
ok = 0
for r in range(4):
    for k in range(50):
        cache.put(("stress", k), {{"payload": k, "round": r}})
        ok += 1
print(ok)
"""


class TestAotCacheContention:
    def test_two_processes_racing_the_same_keys_never_tear(self, tmp_path):
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        path = str(tmp_path / "shared-aot")
        script = _HAMMER.format(src=os.path.abspath(src), path=path)
        procs = [
            subprocess.Popen([sys.executable, "-c", script],
                             stdout=subprocess.PIPE, stderr=subprocess.PIPE)
            for _ in range(2)
        ]
        for p in procs:
            out, err = p.communicate(timeout=120)
            assert p.returncode == 0, err.decode()
            assert out.strip() == b"200"

        import repro.kernels.runtime as runtime
        from repro.serving.aotcache import AotCache

        orig = (runtime.serialize_compiled, runtime.deserialize_compiled)
        runtime.serialize_compiled = lambda fn: pickle.dumps(fn)
        runtime.deserialize_compiled = pickle.loads
        try:
            cache = AotCache(path)
            entries = cache.load_all()
        finally:
            runtime.serialize_compiled, runtime.deserialize_compiled = orig
        # every key readable, no torn entries quarantined, no tmp litter
        assert len(entries) == 50
        assert sorted(k for _, k in entries) == list(range(50))
        assert cache.quarantined == 0
        leftovers = [n for n in os.listdir(path) if n.endswith(".tmp")]
        assert leftovers == []
        assert not any(n.endswith(".quarantined") for n in os.listdir(path))
