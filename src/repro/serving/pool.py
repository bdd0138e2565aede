"""Async worker-pool serving: overlapped dispatch + multi-process workers.

The single-thread :class:`~repro.serving.engine.BatchingDesignService`
serializes host-side batch assembly, device dispatch and report
construction on one thread — on the mixed design load that is ~95% host
assembly (tree-stacking 16 lanes costs ~25 ms against a ~0.6 ms program
dispatch).  This module is the serving tier above it, in two layers:

* :class:`StagedBatchingService` — the same coalescing service with a
  **staging-buffer** chunk dispatcher: per-lane parameter leaves are
  memoized as numpy views once per (workload, architecture) and copied
  into preallocated ``(request_bucket, ...)`` staging buffers (~0.1 ms for
  16 lanes, ~250x the stacked path), then fed to the *identical* batched
  program the sequential path runs.  Same program + same pad convention
  (repeat lane 0) = bit-identical replies, by construction.

* :class:`PooledDesignService` — async intake: callers ``enqueue`` and a
  dispatcher thread pulls flushed chunks from the :class:`IntakeQueue`,
  hands each to a bounded thread pool, and completions scatter back by
  ticket.  Host assembly of one chunk overlaps the device dispatch and
  report construction of another; the PR 7 guard stack still wraps every
  query individually (``_complete`` bookkeeping is mutex-guarded, the
  engine call runs outside the lock).

* :class:`MultiProcessDesignService` — N worker *processes*, each a
  :class:`StagedBatchingService` over ``Session(cache_dir=...)`` against
  one shared :class:`~repro.serving.aotcache.AotCache` directory (PR 9's
  persistent executables make worker spin-up zero-compile).  The
  coordinator owns a private Unix socket (:mod:`repro.serving.protocol`),
  shards flushed chunks to the least-loaded live worker, tracks worker
  heartbeats, detects crashes (process exit, EOF, heartbeat silence) and
  **re-enqueues in-flight queries** of a dead worker; per-worker
  :class:`ServiceStats` piggyback on reply frames and aggregate losslessly
  via :meth:`ServiceStats.merge`.  ``ChaosConfig.p_worker_kill`` marks
  queries whose assigned worker the coordinator SIGKILLs (once per qid) —
  the injectable crash fault the bench gates on.

Workers are spawned with ``subprocess`` (``python -m repro.serving.worker``),
never ``fork``: a forked JAX runtime deadlocks on its internal thread pools
(the ``fork-unsafe`` lint rule pins this repo-wide).

Determinism under concurrency: chaos schedules, retry jitter and deadline
classes are all pure functions of the query (qid, retry index, shape) —
never of thread identity, worker count or completion order — so the same
seed replays the same per-query faults on 1 worker or 8, and per-worker
stats summed over any partition equal the sequential ledger
(``tests/test_serving_pool.py`` pins both).
"""
from __future__ import annotations

import itertools
import os
import selectors
import socket
import subprocess
import sys
import tempfile
import threading
import time
import weakref
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np

from repro.core import instrument
from repro.kernels import runtime
from repro.serving import protocol
from repro.serving.batching import FlushPolicy, IntakeQueue, batch_key, make_chunk_handlers, plan_chunks
from repro.serving.chaos import ChaosConfig, ChaosInjector
from repro.serving.engine import (
    BatchingDesignService,
    DesignQuery,
    DesignReply,
    ServiceStats,
)
from repro.serving.resilience import FaultInfo, TransientFault

__all__ = [
    "StagedBatchingService",
    "PooledDesignService",
    "MultiProcessDesignService",
]


# --------------------------------------------------------------------------- #
# staging-buffer assembly
# --------------------------------------------------------------------------- #


class _StagedAssembler:
    """Fast host-side batch assembly for one session.

    ``Session._assemble_batch`` tree-stacks device arrays per call; this
    assembler instead memoizes each lane's flattened *numpy* leaves once
    per (architecture, workload) object and writes them into reusable
    ``(request_bucket, ...)`` staging buffers.  The output pytree has the
    exact structure and pad convention (lane 0 repeated) of the stacked
    path, and feeds the same compiled program — XLA converts host numpy
    identically to device stacking, so per-lane outputs are bit-identical
    (pinned by test).

    Buffers are thread-local: pool workers stage concurrently without
    copies racing.  Lane memos are weak-keyed so a transient Architecture
    (e.g. a one-off ``.dhd`` query) never pins memory or risks an id-reuse
    collision.
    """

    def __init__(self, request_bucket: int):
        self.nb = int(request_bucket)
        self._lock = threading.Lock()
        self._arch_np: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
        self._w_np: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
        self._tls = threading.local()

    def _arch_leaves(self, a) -> list:
        with self._lock:
            out = self._arch_np.get(a)
        if out is None:
            out = [np.asarray(x) for x in jax.tree.leaves((a.tech, a.arch))]
            with self._lock:
                self._arch_np[a] = out
        return out

    def _w_leaves(self, w) -> list:
        with self._lock:
            out = self._w_np.get(w)
        if out is None:
            out = [np.asarray(x) for x in jax.tree.leaves(w.stacked)]
            with self._lock:
                self._w_np[w] = out
        return out

    def stage(self, ws, archs):
        """``(techs, arch_ps, gstacks)`` staged to the request bucket —
        drop-in for the stacked pytrees ``Session._assemble_batch`` returns
        (callers validated same-spec / same-bucket already)."""
        lanes = [self._arch_leaves(a) + self._w_leaves(w) for w, a in zip(ws, archs)]
        key = (archs[0].spec, ws[0].bucket)
        cache = getattr(self._tls, "bufs", None)
        if cache is None:
            cache = self._tls.bufs = {}
        entry = cache.get(key)
        if entry is None:
            treedef = jax.tree.structure((archs[0].tech, archs[0].arch, ws[0].stacked))
            bufs = [np.empty((self.nb,) + lf.shape, lf.dtype) for lf in lanes[0]]
            entry = cache[key] = (treedef, bufs)
        treedef, bufs = entry
        n = len(lanes)
        for i in range(self.nb):
            lane = lanes[i] if i < n else lanes[0]  # pad = repeat lane 0
            for j, leaf in enumerate(lane):
                bufs[j][i] = leaf
        return jax.tree.unflatten(treedef, bufs)


class StagedBatchingService(BatchingDesignService):
    """:class:`BatchingDesignService` whose chunk dispatch assembles via
    :class:`_StagedAssembler` — bit-identical replies, ~10x the host
    throughput.  Also routes *singleton* batchable chunks through the
    staged dispatcher (``_coalesce_min = 1``): a lone simulate query costs
    one 0.1 ms staging pass instead of the sequential tree-stack.  This is
    the service a pool worker process runs."""

    _coalesce_min = 1

    def __init__(self, architecture="base", *, policy=None, **kw):
        super().__init__(architecture, policy=policy, **kw)
        self._assembler = _StagedAssembler(self.request_bucket)

    def _dispatch_chunk(self, adms: list) -> list:
        kind = adms[0].q.kind
        sess = self.session
        ws = [a.w for a in adms]
        archs = [a.arch for a in adms]
        bucket, spec = ws[0].bucket, archs[0].spec
        with instrument.span("dragon.service.stage"):
            staged = self._assembler.stage(ws, archs)
        prog = sess._batched_report_program(self.request_bucket, bucket, spec, sess.mcfg)
        with instrument.span("dragon.session.launch", program="report_batched"):
            out = prog(*staged)
        reports = sess._reports_from_batch(ws, archs, out)
        if kind == "simulate":
            return reports
        objective = adms[0].q.objective
        eprog = sess._batched_explain_program(
            self.request_bucket, bucket, spec, sess.mcfg, objective
        )
        with instrument.span("dragon.session.launch", program="explain_batched"):
            out = eprog(*staged)
        return sess._attribute_batch(reports, out, objective)


# --------------------------------------------------------------------------- #
# threaded pool: dispatcher thread + bounded worker pool
# --------------------------------------------------------------------------- #


class PooledDesignService(StagedBatchingService):
    """Async serving over one process: a dispatcher thread drains the
    intake queue per the flush policy and hands each planned chunk to a
    bounded thread pool, so one chunk's host assembly overlaps another's
    device dispatch and report construction.

    * :meth:`enqueue` is non-blocking and returns a **ticket**; replies
      scatter into an internal map as chunks complete.
    * :meth:`serve` keeps the synchronous contract — enqueue all, barrier
      on :meth:`join`, return replies in query order.
    * :meth:`join` forces a drain of sub-policy stragglers and blocks until
      every enqueued query has a reply.
    * Guard-stack semantics are unchanged: every query runs
      ``_complete`` individually (retry / deadline / chaos / breaker /
      non-finite checks), chunk-locally memoized exactly like the
      synchronous flush.  Bookkeeping races are closed by the service
      mutex; the engine call runs outside any lock.

    One caveat inherited from concurrency: ``DesignReply.compiled`` (and
    the straggler monitor's cold-reprime) keys on a service-wide trace
    counter, so with several chunks *compiling* simultaneously a query can
    be labelled compiled because its neighbor traced.  Preheated fleets —
    the deployment this tier exists for — compile nothing on the query
    path, where the label is exact.
    """

    def __init__(self, architecture="base", *, workers: int = 2, policy=None,
                 poll_s: Optional[float] = None, **kw):
        super().__init__(architecture, policy=policy, **kw)
        self.workers = max(1, int(workers))
        self._ticket = itertools.count()
        self._chunk_ids = itertools.count()  # the `chunk` arg of its spans
        self._cond = threading.Condition()
        self._pending = 0
        self._results: dict[int, DesignReply] = {}
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._drain_now = False
        self._poll_s = poll_s if poll_s is not None else max(self.policy.max_delay_s, 0.001)
        self._exec = ThreadPoolExecutor(
            max_workers=self.workers, thread_name_prefix="dragon-pool"
        )
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="dragon-dispatcher", daemon=True
        )
        self._dispatcher.start()

    # ------------------------------------------------------------- intake --
    def enqueue(self, q: DesignQuery) -> int:
        """Queue one query, non-blocking; returns a ticket for
        :meth:`take`.  (The synchronous parent returns flushed replies
        here — the async tier never blocks intake on a flush.)"""
        if self._stop.is_set():
            raise RuntimeError("PooledDesignService is closed")
        ticket = next(self._ticket)
        with self._cond:
            self._pending += 1
        self._queue.push((ticket, q))
        if self._queue.due(self.policy):
            self._wake.set()
        return ticket

    def pump(self) -> list:
        return []  # the dispatcher thread owns flushing

    def submit(self, q: DesignQuery) -> DesignReply:
        return self.serve([q])[0]

    def serve(self, queries: list[DesignQuery]) -> list[DesignReply]:
        tickets = [self.enqueue(q) for q in queries]
        self.join()
        return [self.take(t) for t in tickets]

    def flush(self) -> list:
        """Force-drain; returns [] (replies arrive via tickets)."""
        self.join()
        return []

    # ------------------------------------------------------------ results --
    def take(self, ticket: int) -> Optional[DesignReply]:
        """Pop the reply for a ticket (None if not finished yet)."""
        with self._cond:
            return self._results.pop(ticket, None)

    def join(self, timeout: Optional[float] = None) -> bool:
        """Force a drain and block until every enqueued query has a reply.
        Returns False on timeout."""
        self._drain_now = True
        self._wake.set()
        with self._cond:
            return self._cond.wait_for(lambda: self._pending == 0, timeout=timeout)

    def close(self) -> None:
        """Drain, then stop the dispatcher and the worker pool."""
        if self._stop.is_set():
            return
        self.join()
        self._stop.set()
        self._wake.set()
        self._dispatcher.join(timeout=10)
        self._exec.shutdown(wait=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # --------------------------------------------------------- dispatcher --
    def _dispatch_loop(self) -> None:
        while True:
            self._wake.wait(self._poll_s)
            self._wake.clear()
            drain = self._drain_now
            self._drain_now = False
            if drain or self._queue.due(self.policy):
                items = self._queue.drain()
                if items:
                    self._process(items)
            if self._stop.is_set() and not len(self._queue):
                return

    def _process(self, items: list) -> None:
        """Intake + plan one drained batch, then fan chunks out to the
        pool.  Mirrors the synchronous ``flush`` accounting exactly."""
        t_drain = self._clock()
        with instrument.span("dragon.service.intake", n=len(items)):
            admitted: list = []
            ticket_of: dict[int, int] = {}
            for i, (t_enq, (ticket, q)) in enumerate(items):
                ticket_of[i] = ticket
                try:
                    prep = self._prepare(q)
                except Exception as e:
                    prep = self._last_ditch(q, e)
                if isinstance(prep, DesignReply):
                    self._finish(ticket, prep)
                else:
                    prep.t0 = t_enq  # wall time includes the queue wait
                    admitted.append((i, prep))
            for chunk in plan_chunks(admitted, self.policy.max_batch):
                handler_of: dict = {}
                if len(chunk) >= self._coalesce_min and batch_key(chunk[0][1]) is not None:
                    handler_of = make_chunk_handlers(chunk, self._dispatch_chunk)
                    if len(chunk) > 1:
                        with self._mutex:
                            self._batches += 1
                            self._batched_queries += len(chunk)
                args = (chunk, handler_of, ticket_of, next(self._chunk_ids), t_drain)
                try:
                    self._exec.submit(self._run_chunk, *args)
                except RuntimeError:  # pool shut down mid-close: finish inline
                    self._run_chunk(*args)

    def _run_chunk(self, chunk: list, handler_of: dict, ticket_of: dict,
                   chunk_id: int, t_drain: float) -> None:
        n = len(chunk)
        t_start = self._clock()

        def span_args() -> dict:
            ms = 1e3 / n
            return dict(
                kind=chunk[0][1].q.kind, n=n,
                lanes=self.request_bucket if handler_of else 1,
                flush_wait_ms=ms * sum(t_drain - adm.t0 for _, adm in chunk),
                wait_ms=ms * sum(t_start - adm.t0 for _, adm in chunk),
                qids=[adm.q.qid for _, adm in chunk],
            )

        with instrument.span("dragon.service.chunk", span_args, chunk=chunk_id):
            for i, adm in chunk:
                try:
                    reply = self._complete(
                        adm, handler_of.get(i),
                        batched=n > 1 and i in handler_of,
                        batch_size=n if i in handler_of else 1,
                    )
                except Exception as e:
                    reply = self._last_ditch(adm.q, e)
                self._finish(ticket_of[i], reply)

    def _finish(self, ticket: int, reply: DesignReply) -> None:
        self._account(reply)
        with self._cond:
            self._results[ticket] = reply
            self.replies.append(reply)
            self._pending -= 1
            self._cond.notify_all()


# --------------------------------------------------------------------------- #
# multi-process coordinator
# --------------------------------------------------------------------------- #


@dataclass
class _Worker:
    """Coordinator-side state for one worker process."""

    wid: int
    proc: Optional[subprocess.Popen] = None
    conn: Optional[socket.socket] = None
    last_seen: float = 0.0
    ready: bool = False
    alive: bool = True
    inflight: dict = field(default_factory=dict)  # chunk id -> [(ticket, query)]
    stats: Optional[ServiceStats] = None


_EMPTY_STATS = ServiceStats(
    programs=0, hits=0, misses=0, traces=0, queries=0, ok=0, retries=0,
    deadline_misses=0, degraded=0, errors={}, stragglers=(), breakers={},
)


class MultiProcessDesignService:
    """N worker processes draining design queries from one coordinator.

    Each worker is a :class:`StagedBatchingService` over
    ``Session(cache_dir=...)`` against the **shared** AOT cache directory,
    so a preheated cache gives every worker zero-compile spin-up and
    bit-identical programs.  The coordinator is deliberately engine-free:
    it resolves queries only far enough to group them by batch key (a
    resolver ``Session`` that never dispatches), shards full chunks to the
    least-loaded live worker over the frame protocol, and scatters replies
    back by ticket.

    Fault containment extends the PR 7 stack across the process boundary:

    * **heartbeats** — workers beacon every ``heartbeat_s`` from a daemon
      thread; silence beyond ``worker_timeout_s`` marks the worker dead
      (hung processes count as dead, not just exited ones);
    * **crash detection** — process exit, socket EOF and framing errors
      all route to the same death path;
    * **requeue** — a dead worker's in-flight, unanswered queries re-enter
      the intake queue and are re-planned onto surviving workers; replies
      are deduplicated by ticket (first answer wins), so a worker killed
      *after* replying costs nothing;
    * **worker-kill chaos** — with ``chaos=ChaosConfig(p_worker_kill=...)``
      the coordinator SIGKILLs the assigned worker of each marked qid
      (once per qid, deterministically seeded like every other fault) and
      the requeue path must restore availability — the bench gate.

    ``stats`` merges the latest per-worker :class:`ServiceStats` (workers
    piggyback a snapshot on every reply frame, so even a crashed worker's
    ledger survives to its last answered chunk); ``pool_info`` carries the
    coordinator's own counters (kills, requeues, worker liveness).
    """

    def __init__(self, architecture: str = "base", *, workers: int = 2,
                 cache_dir=None, policy: Optional[FlushPolicy] = None,
                 retry=None, deadlines=None, chaos: Optional[ChaosConfig] = None,
                 request_bucket: Optional[int] = None,
                 heartbeat_s: float = 0.25, worker_timeout_s: float = 10.0,
                 ready_timeout_s: float = 600.0, max_inflight_chunks: int = 2,
                 warm: Optional[list] = None, objectives: tuple = ("edp",),
                 kinds: tuple = ("simulate", "explain"),
                 worker_cmd: Optional[list] = None):
        if cache_dir is None:
            raise ValueError(
                "multi-process serving requires cache_dir= (the shared AotCache "
                "directory workers rehydrate their programs from)"
            )
        if not isinstance(architecture, str):
            raise TypeError(
                "MultiProcessDesignService takes the architecture as a library "
                "name or .dhd text (it must cross a process boundary)"
            )
        self.architecture = architecture
        self.workers = max(1, int(workers))
        self.cache_dir = str(cache_dir)
        self.policy = policy or FlushPolicy()
        self.retry = retry
        self.deadlines = deadlines
        self.chaos_config = chaos
        self.request_bucket = int(request_bucket or self.policy.max_batch)
        self.heartbeat_s = float(heartbeat_s)
        self.worker_timeout_s = float(worker_timeout_s)
        self.ready_timeout_s = float(ready_timeout_s)
        self.max_inflight_chunks = max(1, int(max_inflight_chunks))
        self.warm = list(warm) if warm else None
        self.objectives = tuple(objectives)
        self.kinds = tuple(kinds)
        self.worker_cmd = list(worker_cmd) if worker_cmd else None
        # plan() only — the coordinator never injects attempt faults itself
        self._chaos_planner = ChaosInjector(chaos) if chaos is not None else None
        self.kills = 0
        self.requeues = 0
        self._killed: set[int] = set()
        self._queue = IntakeQueue()
        self._backlog: deque = deque()  # planned chunks awaiting a worker slot
        self._ticket = itertools.count()
        self._cid = itertools.count()
        self._cond = threading.Condition()
        self._pending = 0
        self._results: dict[int, DesignReply] = {}
        self._resolved: set[int] = set()
        self.replies: list[DesignReply] = []
        self._workers: dict[int, _Worker] = {}
        self._resolver = None  # lazy Session for batch-key grouping
        self._stop = threading.Event()
        self._drain_now = False
        self._started = False
        self._closed = False
        self._dir: Optional[str] = None
        self._loop_thread: Optional[threading.Thread] = None

    # -------------------------------------------------------------- start --
    def start(self) -> "MultiProcessDesignService":
        """Spawn workers, handshake, wait until all are warmed and taking
        traffic, then start the coordinator loop."""
        if self._started:
            return self
        import repro

        self._check_chips()
        self._dir = tempfile.mkdtemp(prefix="dragon-pool-")
        sock_path = os.path.join(self._dir, "pool.sock")
        self._listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._listener.bind(sock_path)
        self._listener.listen(self.workers)
        self._listener.settimeout(self.ready_timeout_s)
        # the child must import repro the same way we did, wherever the
        # parent was launched from
        env = dict(os.environ)
        src_root = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
        env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
        base_cmd = self.worker_cmd or [sys.executable, "-m", "repro.serving.worker"]
        for wid in range(self.workers):
            proc = subprocess.Popen(
                base_cmd + ["--socket", sock_path, "--id", str(wid)], env=env
            )
            self._workers[wid] = _Worker(wid=wid, proc=proc)
        cfg = dict(
            architecture=self.architecture, policy=self.policy,
            retry=self.retry, deadlines=self.deadlines,
            request_bucket=self.request_bucket, cache_dir=self.cache_dir,
            chaos=self.chaos_config, heartbeat_s=self.heartbeat_s,
            warm=self.warm, objectives=self.objectives, kinds=self.kinds,
        )
        for _ in range(self.workers):
            conn, _addr = self._listener.accept()
            conn.settimeout(self.ready_timeout_s)
            tag, payload = protocol.recv_frame(conn)
            if tag != "hello":
                raise protocol.ProtocolError(f"expected hello, got {tag!r}")
            w = self._workers[payload["worker"]]
            w.conn = conn
            w.last_seen = time.monotonic()
            protocol.send_frame(conn, "cfg", cfg)
        for w in self._workers.values():
            tag, payload = protocol.recv_frame(w.conn)
            while tag == "hb":  # beacons may precede readiness
                tag, payload = protocol.recv_frame(w.conn)
            if tag != "ready":
                raise protocol.ProtocolError(f"worker {w.wid}: expected ready, got {tag!r}")
            w.ready = True
            w.last_seen = time.monotonic()
            # liveness now rides on heartbeats; a blocking recv must not
            # stall the loop longer than one beacon interval
            w.conn.settimeout(self.worker_timeout_s)
        self._started = True
        self._loop_thread = threading.Thread(
            target=self._loop, name="dragon-coordinator", daemon=True
        )
        self._loop_thread.start()
        return self

    def _check_chips(self) -> None:
        """A TPU serves one process at a time: refuse a fleet whose workers
        could not each hold a chip, instead of letting it hang."""
        platforms = os.environ.get("JAX_PLATFORMS")
        if platforms and "tpu" not in platforms.split(","):
            return  # workers inherit a TPU-free platform list
        if runtime.backend_initialized():
            if jax.default_backend() == "tpu":
                raise RuntimeError(
                    "MultiProcessDesignService: this process has initialized JAX "
                    "and holds the TPU, so worker processes cannot reach it; start "
                    "the service before any JAX work, or serve in-process"
                )
            return
        chips = runtime.local_tpu_chips()
        if chips and self.workers > chips:
            raise RuntimeError(
                f"MultiProcessDesignService: {self.workers} workers but {chips} "
                "local TPU chip(s); each worker process needs a chip of its own"
            )

    # ------------------------------------------------------------- intake --
    def enqueue(self, q: DesignQuery) -> int:
        if not self._started:
            self.start()
        if self._stop.is_set():
            raise RuntimeError("MultiProcessDesignService is closed")
        ticket = next(self._ticket)
        with self._cond:
            self._pending += 1
        self._queue.push((ticket, q))
        return ticket

    def serve(self, queries: list[DesignQuery]) -> list[DesignReply]:
        tickets = [self.enqueue(q) for q in queries]
        self.join()
        with self._cond:
            return [self._results.pop(t) for t in tickets]

    def take(self, ticket: int) -> Optional[DesignReply]:
        with self._cond:
            return self._results.pop(ticket, None)

    def join(self, timeout: Optional[float] = None) -> bool:
        self._drain_now = True
        with self._cond:
            return self._cond.wait_for(lambda: self._pending == 0, timeout=timeout)

    # ------------------------------------------------------------ results --
    @property
    def stats(self) -> ServiceStats:
        """The merged fleet ledger (latest snapshot per worker)."""
        per = [w.stats for w in self._workers.values() if w.stats is not None]
        if not per:
            return _EMPTY_STATS
        out = per[0]
        for s in per[1:]:
            out = out.merge(s)
        return out

    @property
    def pool_info(self) -> dict:
        """Coordinator-side counters: worker liveness, chaos kills, requeues."""
        return dict(
            workers=self.workers,
            alive=sum(1 for w in self._workers.values() if w.alive),
            ready=sum(1 for w in self._workers.values() if w.ready),
            kills=self.kills,
            requeues=self.requeues,
        )

    # ------------------------------------------------------------ shutdown --
    def close(self, timeout: float = 30.0) -> None:
        """Drain, stop the loop, collect final worker stats, reap."""
        if self._closed:
            return
        self._closed = True
        if self._started:
            self.join(timeout=timeout)
            self._stop.set()
            if self._loop_thread is not None:
                self._loop_thread.join(timeout=timeout)
            for w in self._workers.values():
                if not (w.alive and w.conn):
                    continue
                try:
                    protocol.send_frame(w.conn, "shutdown", None)
                    w.conn.settimeout(5.0)
                    tag, payload = protocol.recv_frame(w.conn)
                    while tag != "bye":
                        tag, payload = protocol.recv_frame(w.conn)
                    w.stats = payload
                except (OSError, protocol.ProtocolError):
                    pass  # worker left early; last piggybacked snapshot stands
            for w in self._workers.values():
                if w.conn is not None:
                    try:
                        w.conn.close()
                    except OSError:
                        pass
                if w.proc is not None:
                    try:
                        w.proc.wait(timeout=5)
                    except subprocess.TimeoutExpired:
                        w.proc.kill()
                        w.proc.wait(timeout=5)
            try:
                self._listener.close()
            except OSError:
                pass
        if self._dir is not None:
            import shutil

            shutil.rmtree(self._dir, ignore_errors=True)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.close()
        return False

    # ----------------------------------------------------------- the loop --
    def _loop(self) -> None:
        sel = selectors.DefaultSelector()
        for w in self._workers.values():
            if w.alive and w.conn is not None:
                sel.register(w.conn, selectors.EVENT_READ, w)
        poll_s = max(self.policy.max_delay_s, 0.002)
        try:
            while not self._stop.is_set():
                self._maybe_dispatch(sel)
                for key, _ev in sel.select(timeout=poll_s):
                    self._read_worker(key.data, sel)
                self._check_liveness(sel)
        finally:
            sel.close()

    def _maybe_dispatch(self, sel) -> None:
        drain = self._drain_now
        self._drain_now = False
        if drain or self._queue.due(self.policy):
            for chunk in self._plan(self._queue.drain()):
                self._backlog.append(chunk)
        self._pump(sel)

    def _pump(self, sel) -> None:
        """Backpressured assignment: at most ``max_inflight_chunks`` chunks
        outstanding per worker.  Blasting the whole backlog down the pipes
        deadlocks at scale — the coordinator blocks in ``sendall`` while
        every worker blocks sending a reply frame nobody is reading, the
        worker's heartbeat thread starves behind its send lock, and
        ``worker_timeout_s`` later the whole fleet reads as hung.  Bounding
        in-flight chunks keeps both socket directions shallow and caps how
        much a crashed worker can strand."""
        while self._backlog:
            live = [w for w in self._workers.values() if w.alive and w.ready]
            if live and min(len(w.inflight) for w in live) >= self.max_inflight_chunks:
                return  # every live worker saturated: resume on next reply
            self._assign(self._backlog.popleft(), sel)

    # ------------------------------------------------------------- planning --
    def _resolve_key(self, q: DesignQuery):
        """The batch key, via a resolver Session that never dispatches.
        Unresolvable queries group as singletons — the worker owns the
        actual quarantine (and emits the structured client-error reply)."""
        if q.kind not in ("simulate", "explain"):
            return None
        if self._resolver is None:
            from repro.api import Session

            self._resolver = Session(self.architecture)
        try:
            w = self._resolver._workload(q.workload)
            a = self._resolver._arch(q.architecture)
        except Exception:
            return None
        return (q.kind, a.spec, w.bucket, q.objective if q.kind == "explain" else None)

    def _plan(self, items: list) -> list:
        """Group drained ``(t, (ticket, q))`` items into same-key chunks
        capped at the request bucket — ``plan_chunks`` over wire queries
        instead of admitted records."""
        chunks: list = []
        open_chunk: dict = {}
        for _t, (ticket, q) in items:
            key = self._resolve_key(q)
            if key is None:
                chunks.append([(ticket, q)])
                continue
            at = open_chunk.get(key)
            if at is None or len(chunks[at]) >= self.request_bucket:
                open_chunk[key] = len(chunks)
                chunks.append([(ticket, q)])
            else:
                chunks[at].append((ticket, q))
        return chunks

    # ----------------------------------------------------------- assignment --
    def _assign(self, chunk: list, sel) -> None:
        live = [w for w in self._workers.values() if w.alive and w.ready]
        if not live:
            for ticket, q in chunk:
                self._finish(ticket, self._no_worker_reply(q))
            return
        w = min(live, key=lambda h: len(h.inflight))
        cid = next(self._cid)
        w.inflight[cid] = chunk
        kill = False
        if self._chaos_planner is not None and len(live) >= 2:
            # enact a planned kill only while a survivor remains: the fault
            # models one process crashing out of a fleet, not the fleet
            # evaporating (a marked qid on the last live worker is skipped
            # permanently — the plan stays deterministic, enactment is
            # capacity-bounded)
            for _ticket, q in chunk:
                if q.qid not in self._killed and self._chaos_planner.plan(q.qid).worker_kill:
                    self._killed.add(q.qid)  # at most one kill per qid
                    kill = True
        try:
            protocol.send_frame(w.conn, "chunk", (cid, [q for _, q in chunk]))
        except (OSError, protocol.ProtocolError):
            self._dead(w, sel)  # requeues this chunk with the rest
            return
        if kill and w.proc is not None:
            # the seeded crash fault: SIGKILL the worker this chunk just
            # landed on, then take the death path immediately — the chunk
            # (and anything else unanswered) requeues onto survivors
            self.kills += 1
            self._chaos_planner._count("worker_kill")
            try:
                w.proc.kill()
            except OSError:
                pass
            self._dead(w, sel)

    def _no_worker_reply(self, q: DesignQuery) -> DesignReply:
        fault = TransientFault("no live workers (all worker processes died)")
        return DesignReply(
            qid=q.qid, kind=q.kind, wall_s=0.0, compiled=False, result=None,
            ok=False, error=FaultInfo(code=fault.code, message=str(fault),
                                      attempts=0, retryable=True),
            attempts=0, deadline_s=0.0,
        )

    # -------------------------------------------------------------- events --
    def _read_worker(self, w: _Worker, sel) -> None:
        try:
            tag, payload = protocol.recv_frame(w.conn)
        except (OSError, protocol.ProtocolError):
            self._dead(w, sel)
            return
        w.last_seen = time.monotonic()
        if tag == "hb":
            return
        if tag == "replies":
            cid, replies, stats = payload
            w.stats = stats
            chunk = w.inflight.pop(cid, None)
            if chunk is None:
                return  # chunk was already requeued (kill/reply race)
            if len(replies) == len(chunk):
                pairs = list(zip((t for t, _ in chunk), replies))
            else:  # defensive: match by qid if the worker reordered
                by_qid = {q.qid: t for t, q in chunk}
                pairs = [(by_qid.get(r.qid), r) for r in replies]
            for ticket, reply in pairs:
                if ticket is None:
                    continue
                self._finish(ticket, reply)
            self._pump(sel)  # a slot freed: hand this worker its next chunk
        elif tag == "bye":
            w.stats = payload

    def _finish(self, ticket: int, reply: DesignReply) -> None:
        with self._cond:
            if ticket in self._resolved:
                return  # duplicate answer after a requeue race: first wins
            self._resolved.add(ticket)
            self._results[ticket] = reply
            self.replies.append(reply)
            self._pending -= 1
            self._cond.notify_all()

    def _dead(self, w: _Worker, sel) -> None:
        """One death path for every detection mode: unregister, reap, and
        re-enqueue whatever the worker never answered."""
        if not w.alive:
            return
        w.alive = False
        w.ready = False
        try:
            sel.unregister(w.conn)
        except (KeyError, ValueError, OSError):
            pass
        try:
            w.conn.close()
        except OSError:
            pass
        if w.proc is not None and w.proc.poll() is None:
            try:
                w.proc.kill()
            except OSError:
                pass
        for _cid, chunk in w.inflight.items():
            for ticket, q in chunk:
                with self._cond:
                    done = ticket in self._resolved
                if done:
                    continue
                self.requeues += 1
                self._queue.push((ticket, q))
        w.inflight.clear()
        self._drain_now = True

    def _check_liveness(self, sel) -> None:
        now = time.monotonic()
        for w in list(self._workers.values()):
            if not w.alive:
                continue
            if w.proc is not None and w.proc.poll() is not None:
                self._dead(w, sel)
            elif now - w.last_seen > self.worker_timeout_s:
                self._dead(w, sel)  # hung counts as dead
