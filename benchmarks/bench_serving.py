"""Serving benchmark: token engine + design-service batching + chaos gates.

Three sections, all written to ``results/bench/serving.json``:

* **token** — the continuous-batching engine on a reduced qwen config:
  warm throughput, per-token latency and TTFT with mixed request sizes.
  Compile time is excluded by a warmup pass (the regression this bench
  once recorded — slots4 3x *slower* than slots1 — was per-prompt-length
  prefill retraces plus a full-cache copy per admit, both fixed; the gain
  is now HARD-GATED: >= 1.2 in ``--quick``, > 1.0 in the full run).

* **design** — the cross-request batching load generator: the same mixed
  simulate/explain query stream served sequentially
  (:class:`repro.serving.DesignService`) and through the coalescing
  :class:`repro.serving.BatchingDesignService`, reporting QPS and
  p50/p99 reply latency.  Both paths dispatch the same request-axis
  program at one pinned request bucket, so replies are bit-identical
  (asserted).  The QPS gain is HARD-GATED at > 1.5x.

* **design.pooled / design.multiprocess** — the serving-pool tier on the
  same mixed stream: the staged-dispatch :class:`PooledDesignService`
  (dispatcher thread + bounded worker pool, staging-buffer assembly) and
  the 2-worker :class:`MultiProcessDesignService` (worker processes over
  one shared preheated AOT cache).  Replies are bit-identical to the
  sequential baseline (asserted).  HARD-GATED: full run, pooled and
  multi-process QPS each > 1.5x the batched service; quick (the CI
  probe), availability == 1.0 and pooled QPS >= the batched QPS.  The
  full run also injects seeded **worker kills** (``p_worker_kill``) and
  gates kills >= 1, requeues >= 1, availability == 1.0 — a crashed
  worker's in-flight queries must be re-enqueued and answered exactly.
  On a TPU backend the multi-process sections are skipped, and the output
  says so: this process holds the chip, and a chip serves one process.

* **chaos** — the PR 7 resilience gates, now run against the BATCHED
  path (availability (fraction of queries answered ok within deadline),
  p50/p99 reply latency, retry and injection counts), with four hard
  gates —

    1. *isolation*: every batch completes, one reply per query, zero
       uncaught exceptions;
    2. *transient-only availability == 1.0*: every fault class that clears
       on retry MUST clear under the default policy (the CI probe's gate);
    3. *bit-identity*: replies for queries the chaos schedule left clean
       are bit-identical (``to_json`` string equality) to a no-chaos run,
       and the seeded schedule itself replays identically;
    4. *replay*: a fresh injector with the same seed reproduces schedule,
       outcomes and results exactly.

``--quick --chaos`` is the CI probe: design-service sections only, writing
``serving_quick.json`` (the canonical ``serving.json`` comes from a full
run on an idle machine).
"""
from __future__ import annotations

import argparse
import tempfile
import time

import jax
import numpy as np

from benchmarks.common import emit, save_json
from repro.configs import get_config
from repro.models.model import build_model
from repro.serving import (
    BatchingDesignService,
    ChaosConfig,
    ChaosInjector,
    DesignQuery,
    DesignService,
    Engine,
    FlushPolicy,
    MultiProcessDesignService,
    PooledDesignService,
    Request,
    RetryPolicy,
)

_SEED = 20260808
_REQUEST_BUCKET = 16  # pinned request axis: sequential + batched share it


# --------------------------------------------------------------------------- #
# token engine
# --------------------------------------------------------------------------- #


def _token_requests(n: int, rng, vocab: int, max_tokens: int) -> list[Request]:
    return [
        Request(
            rid=i,
            prompt=rng.integers(0, vocab, (int(rng.integers(4, 20)),)).astype(np.int32),
            max_tokens=max_tokens, temperature=0.0, seed=i,
        )
        for i in range(n)
    ]


def token_bench(quick: bool = False) -> dict:
    cfg = get_config("qwen2.5-32b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    n_req = 12 if quick else 16
    max_tokens = 32  # decode-heavy: the regime slot batching exists for
    out = {}
    for slots in (1, 4):
        eng = Engine(model, params, slots=slots, max_len=128)
        # warmup: one measurement-shaped pass (same prompt-length mix, same
        # max_tokens) compiles the prefill buckets, the admit write and the
        # decode step — measured numbers are the warm engine
        for r in _token_requests(n_req, np.random.default_rng(1), cfg.vocab_size, max_tokens):
            eng.submit(r)
        eng.run()
        eng.finished.clear()
        t0 = time.perf_counter()
        for r in _token_requests(n_req, np.random.default_rng(0), cfg.vocab_size, max_tokens):
            eng.submit(r)
        done = eng.run()
        wall = time.perf_counter() - t0
        toks = sum(len(r.generated) for r in done)
        ttft = float(np.mean([r.t_first - r.t_submit for r in done]))
        row = dict(slots=slots, requests=len(done), tok_per_s=round(toks / wall, 1),
                   mean_ttft_ms=round(ttft * 1e3, 1), wall_s=round(wall, 2))
        out[f"slots{slots}"] = row
        emit("serving", row)
    gain = out["slots4"]["tok_per_s"] / max(out["slots1"]["tok_per_s"], 1e-9)
    emit("serving", dict(batching_throughput_gain=round(gain, 2)))
    out["batching_gain"] = gain
    floor = 1.2 if quick else 1.0
    if gain < floor:
        raise SystemExit(
            f"GATE FAILED: token batching_gain {gain:.2f} < {floor} — slots must "
            "buy throughput, not lose it (prefill retraces / cache-copy regression?)"
        )
    return out


# --------------------------------------------------------------------------- #
# design-service cross-request batching load generator
# --------------------------------------------------------------------------- #


def _design_queries(n: int) -> list[DesignQuery]:
    """A deterministic mixed stream over one shape bucket — lstm, merge_sort,
    gcn and stencil2d all stack to (1, 32) — across four library
    architectures, so coalescing has real cross-request variety (different
    design points share one compiled program: parameters are traced data)."""
    kinds = ("simulate", "explain")
    loads = ("lstm", "merge_sort", "gcn", "stencil2d")
    archs = (None, "edge", "datacenter", "mobile")
    return [
        DesignQuery(i, kinds[i % 2], loads[(i // 2) % 4],
                    architecture=archs[(i // 8) % 4])
        for i in range(n)
    ]


def _fingerprints(replies) -> dict:
    """qid -> canonical result text for ok replies (bit-identity oracle:
    report objects serialize every float, so string equality is value
    equality down to the last bit)."""
    return {r.qid: r.result.to_json() for r in replies if r.ok}


def _lat_ms(replies) -> dict:
    walls = np.asarray([r.wall_s for r in replies if r.ok], np.float64)
    if not walls.size:
        return dict(p50_ms=None, p99_ms=None)
    return dict(p50_ms=round(float(np.percentile(walls, 50)) * 1e3, 2),
                p99_ms=round(float(np.percentile(walls, 99)) * 1e3, 2))


def design_bench(quick: bool = False) -> dict:
    n = 200 if quick else 1200
    queries = _design_queries(n)
    out: dict = {"queries": n, "request_bucket": _REQUEST_BUCKET}

    # sequential baseline: one query at a time, same pinned request bucket
    seq = DesignService("base", request_bucket=_REQUEST_BUCKET,
                        retry=RetryPolicy(max_attempts=4, base_s=0.005))
    t0 = time.perf_counter()
    seq_replies = seq.serve(queries)
    seq_wall = time.perf_counter() - t0
    out["sequential"] = dict(qps=round(n / seq_wall, 1), wall_s=round(seq_wall, 2),
                             ok=int(sum(r.ok for r in seq_replies)),
                             **_lat_ms(seq_replies))
    emit("serving.design", dict(mode="sequential", **out["sequential"]))

    # batched: load-generator arrival, size/age flush, coalesced dispatch
    policy = FlushPolicy(max_batch=_REQUEST_BUCKET, max_delay_s=0.005)
    bat = BatchingDesignService("base", policy=policy,
                                retry=RetryPolicy(max_attempts=4, base_s=0.005))
    bat_replies: list = []
    t0 = time.perf_counter()
    for q in queries:
        bat_replies.extend(bat.enqueue(q))
    bat_replies.extend(bat.flush())
    bat_wall = time.perf_counter() - t0
    st = bat.stats
    out["batched"] = dict(
        qps=round(n / bat_wall, 1), wall_s=round(bat_wall, 2),
        ok=int(sum(r.ok for r in bat_replies)),
        batches=st.batches, batched_queries=st.batched_queries,
        mean_batch=round(st.batched_queries / max(st.batches, 1), 2),
        **_lat_ms(bat_replies),
    )
    emit("serving.design", dict(mode="batched", **out["batched"]))

    assert len(seq_replies) == len(bat_replies) == n, "isolation: every query answers"
    fp_seq, fp_bat = _fingerprints(seq_replies), _fingerprints(bat_replies)
    mismatch = [q for q in fp_seq if fp_seq[q] != fp_bat.get(q)]
    out["bit_identical"] = not mismatch
    if mismatch:
        raise SystemExit(
            f"GATE FAILED: {len(mismatch)} batched replies differ from sequential "
            f"(qids {sorted(mismatch)[:8]}) — coalescing must not change answers"
        )

    gain = out["batched"]["qps"] / max(out["sequential"]["qps"], 1e-9)
    out["qps_gain"] = round(gain, 2)
    emit("serving.design", dict(qps_gain=out["qps_gain"]))
    if gain <= 1.5:
        raise SystemExit(
            f"GATE FAILED: batched design-query QPS gain {gain:.2f}x <= 1.5x — "
            "cross-request coalescing must buy real throughput"
        )

    out.update(_pool_bench(queries, fp_seq, out["batched"]["qps"], quick=quick))
    return out


def _pool_bench(queries, fp_seq: dict, batched_qps: float, *, quick: bool) -> dict:
    """The serving-pool tier on the same stream: threaded staged pool +
    2-worker multi-process fleet over a shared preheated AOT cache, each
    hard-gated on throughput, bit-identity and (mp) crash containment."""
    n = len(queries)
    policy = FlushPolicy(max_batch=_REQUEST_BUCKET, max_delay_s=0.005)
    retry = RetryPolicy(max_attempts=4, base_s=0.005)
    out: dict = {}

    # threaded pool: dispatcher thread + staged assembly, 2 workers
    with PooledDesignService("base", workers=2, policy=policy, retry=retry) as pool:
        t0 = time.perf_counter()
        replies = pool.serve(queries)
        wall = time.perf_counter() - t0
    out["pooled"] = dict(workers=2, qps=round(n / wall, 1), wall_s=round(wall, 2),
                         ok=int(sum(r.ok for r in replies)), **_lat_ms(replies))
    emit("serving.design", dict(mode="pooled", **out["pooled"]))
    _gate_identical(fp_seq, _fingerprints(replies), "pooled")

    if jax.default_backend() == "tpu":
        # a chip belongs to one process: this process holds it, so worker
        # processes could not reach it (MultiProcessDesignService refuses)
        reason = "multi-process sections skipped: backend is tpu (one process per chip)"
        print(reason, flush=True)
        out["multiprocess"] = out["worker_kill"] = dict(skipped=reason)
        _gate_qps(out, ("pooled",), batched_qps, n, quick)
        return out

    # multi-process: the parent preheats ONE shared cache dir, workers
    # rehydrate from it (zero compiles) — QPS measured after ready
    cache_dir = tempfile.mkdtemp(prefix="dragon-bench-aot-")
    parent = BatchingDesignService("base", policy=policy, cache_dir=cache_dir)
    parent.warmup(["lstm", "merge_sort", "gcn", "stencil2d"])
    with MultiProcessDesignService("base", workers=2, cache_dir=cache_dir,
                                   policy=policy, retry=retry) as mp:
        t0 = time.perf_counter()
        replies = mp.serve(queries)
        wall = time.perf_counter() - t0
        traces = mp.stats.traces
    out["multiprocess"] = dict(workers=2, qps=round(n / wall, 1),
                               wall_s=round(wall, 2),
                               ok=int(sum(r.ok for r in replies)),
                               worker_traces=traces, **_lat_ms(replies))
    emit("serving.design", dict(mode="multiprocess", **out["multiprocess"]))
    _gate_identical(fp_seq, _fingerprints(replies), "multiprocess")

    _gate_qps(out, ("pooled", "multiprocess"), batched_qps, n, quick)
    emit("serving.design", dict(pooled_gain=out["pooled"]["qps_vs_batched"],
                                multiprocess_gain=out["multiprocess"]["qps_vs_batched"]))

    # seeded worker-kill chaos: a crashed worker's in-flight queries must be
    # requeued onto the survivor and answered bit-identically
    chaos = ChaosConfig(seed=_SEED, p_worker_kill=0.1)
    with MultiProcessDesignService("base", workers=2, cache_dir=cache_dir,
                                   policy=policy, retry=retry, chaos=chaos) as mpk:
        replies = mpk.serve(queries)
        info = mpk.pool_info
    out["worker_kill"] = dict(kills=info["kills"], requeues=info["requeues"],
                              ok=int(sum(r.ok for r in replies)),
                              alive=info["alive"])
    emit("serving.design", dict(mode="worker_kill", **out["worker_kill"]))
    if info["kills"] < 1 or info["requeues"] < 1:
        raise SystemExit(
            f"GATE FAILED: worker-kill chaos injected kills={info['kills']} "
            f"requeues={info['requeues']} — the crash fault must actually fire"
        )
    if out["worker_kill"]["ok"] != n:
        raise SystemExit(
            f"GATE FAILED: availability {out['worker_kill']['ok']}/{n} != 1.0 "
            "under worker-kill chaos — requeue must restore every in-flight query"
        )
    _gate_identical(fp_seq, _fingerprints(replies), "worker_kill")
    return out


def _gate_qps(out: dict, modes, batched_qps: float, n: int, quick: bool) -> None:
    for mode in modes:
        row = out[mode]
        row["qps_vs_batched"] = round(row["qps"] / max(batched_qps, 1e-9), 2)
        if row["ok"] != n:
            raise SystemExit(
                f"GATE FAILED: {mode} availability {row['ok']}/{n} != 1.0"
            )
        floor = 1.0 if quick else 1.5
        hard = row["qps_vs_batched"] >= floor if quick else row["qps_vs_batched"] > floor
        if not hard:
            raise SystemExit(
                f"GATE FAILED: {mode} QPS {row['qps']} is {row['qps_vs_batched']}x "
                f"the batched service (floor {floor}x) — the pool must buy real "
                "throughput, not just concurrency"
            )


def _gate_identical(fp_seq: dict, fp_got: dict, mode: str) -> None:
    mismatch = [q for q in fp_seq if fp_seq[q] != fp_got.get(q)]
    if mismatch:
        raise SystemExit(
            f"GATE FAILED: {len(mismatch)} {mode} replies differ from sequential "
            f"(qids {sorted(mismatch)[:8]}) — the pool must not change answers"
        )


# --------------------------------------------------------------------------- #
# design-service chaos probe (against the BATCHED path)
# --------------------------------------------------------------------------- #


def _queries(n: int, optimize_every: int = 0) -> list[DesignQuery]:
    """A deterministic mixed stream over one shape bucket (lstm/merge_sort
    share (1, 32)), so after the first cold queries everything is warm —
    the regime availability and p99 are defined on."""
    kinds = ("simulate", "explain")
    loads = ("lstm", "merge_sort")
    qs = []
    for i in range(n):
        if optimize_every and i and i % optimize_every == 0:
            qs.append(DesignQuery(i, "optimize", loads[i % 2],
                                  params=dict(steps=6, report=False)))
        else:
            qs.append(DesignQuery(i, kinds[i % 2], loads[(i // 2) % 2]))
    return qs


def _serve(queries, chaos=None, retry=None) -> tuple:
    """The chaos harness drives the BATCHED service: every gate below holds
    with coalescing on, which is the point — batching must not weaken any
    PR 7 guarantee."""
    svc = BatchingDesignService(
        "base", policy=FlushPolicy(max_batch=_REQUEST_BUCKET, max_delay_s=0.005),
        chaos=chaos, retry=retry or RetryPolicy(max_attempts=4, base_s=0.005))
    t0 = time.perf_counter()
    replies = svc.serve(queries)
    wall = time.perf_counter() - t0
    return svc, replies, wall


def _latency(replies, st) -> dict:
    return dict(
        queries=len(replies),
        ok=int(sum(r.ok for r in replies)),
        availability=round(st.availability, 6),
        retries=st.retries,
        deadline_misses=st.deadline_misses,
        degraded=st.degraded,
        errors=dict(st.errors),
        stragglers=len(st.stragglers),
        batches=st.batches,
        batched_queries=st.batched_queries,
        **_lat_ms(replies),
    )


def chaos_bench(quick: bool = False) -> dict:
    n = 24 if quick else 96
    queries = _queries(n, optimize_every=0 if quick else 24)
    out: dict = {"seed": _SEED, "queries": n}

    # 1) clean baseline: no chaos — also the bit-identity oracle
    svc0, replies0, wall0 = _serve(queries)
    base = _fingerprints(replies0)
    out["clean"] = {**_latency(replies0, svc0.stats), "wall_s": round(wall0, 2)}
    assert len(replies0) == len(queries), "isolation: batch must always complete"
    emit("serving.chaos", dict(mode="clean", **{k: out["clean"][k] for k in ("availability", "p50_ms", "p99_ms")}))

    # 2) transient-only chaos: every fault clears on retry -> the hard gate.
    # cache_corrupt (a torn persistent AOT entry, PR 9) is transient-class:
    # the reader quarantines + recompiles, so retry must clear it too.
    # Worst case transient+compile_fail+cache_corrupt costs 3 attempts, +1
    # clean = 4 == RetryPolicy.max_attempts, so availability stays 1.0.
    inj_t = ChaosInjector(ChaosConfig(seed=_SEED, p_transient=0.35, p_compile_fail=0.2,
                                      p_cache_corrupt=0.2))
    svc_t, replies_t, wall_t = _serve(queries, chaos=inj_t)
    out["transient_only"] = {**_latency(replies_t, svc_t.stats),
                             "injected": inj_t.summary(), "wall_s": round(wall_t, 2)}
    emit("serving.chaos", dict(mode="transient_only",
                               availability=out["transient_only"]["availability"],
                               injected=sum(inj_t.summary().values())))
    if out["transient_only"]["availability"] != 1.0:
        raise SystemExit(
            f"GATE FAILED: transient-only chaos availability "
            f"{out['transient_only']['availability']} != 1.0 — retryable faults "
            "must always clear under the default RetryPolicy"
        )

    # 3) full chaos: transients + NaN poisoning + latency spikes
    cfg = ChaosConfig(seed=_SEED, p_transient=0.3, p_compile_fail=0.1,
                      p_nan=0.25, p_latency=0.2, latency_s=0.02)
    inj_f = ChaosInjector(cfg)
    svc_f, replies_f, wall_f = _serve(queries, chaos=inj_f)
    stats_f = svc_f.stats
    plans = inj_f.schedule([q.qid for q in queries])
    clean_qids = {p.qid for p in plans if p.clean}
    fp_f = _fingerprints(replies_f)
    mismatch = [q for q in clean_qids if q in base and q in fp_f and base[q] != fp_f[q]]
    out["full"] = {
        **_latency(replies_f, stats_f),
        "injected": inj_f.summary(),
        "wall_s": round(wall_f, 2),
        "clean_queries": len(clean_qids),
        "bit_identical_clean": len(clean_qids) - len(mismatch),
        "schedule": [p.to_json() for p in plans if not p.clean],
    }
    emit("serving.chaos", dict(mode="full", availability=out["full"]["availability"],
                               p99_ms=out["full"]["p99_ms"],
                               injected=sum(inj_f.summary().values())))
    assert len(replies_f) == len(queries), "isolation: batch must always complete"
    if mismatch:
        raise SystemExit(
            f"GATE FAILED: {len(mismatch)} fault-free replies differ from the "
            f"no-chaos run (qids {sorted(mismatch)[:8]}) — chaos must not perturb "
            "untouched queries"
        )
    if out["full"]["availability"] < 0.99:
        raise SystemExit(
            f"GATE FAILED: full-chaos availability {out['full']['availability']} < 0.99"
        )

    # 4) determinism: same seed -> identical schedule and identical outcomes
    inj_r = ChaosInjector(cfg)
    svc_r, replies_r, _ = _serve(queries, chaos=inj_r)
    same_sched = [p.to_json() for p in inj_r.schedule([q.qid for q in queries])] == \
        [p.to_json() for p in inj_f.schedule([q.qid for q in queries])]
    same_outcome = [(r.qid, r.ok, r.error.code if r.error else None) for r in replies_r] == \
        [(r.qid, r.ok, r.error.code if r.error else None) for r in replies_f]
    same_results = _fingerprints(replies_r) == fp_f
    out["replay"] = dict(same_schedule=same_sched, same_outcomes=same_outcome,
                         same_results=same_results,
                         availability=round(svc_r.stats.availability, 6))
    if not (same_sched and same_outcome and same_results):
        raise SystemExit("GATE FAILED: seeded chaos replay diverged (schedule/outcomes/results)")
    emit("serving.chaos", dict(mode="replay", deterministic=True))
    return out


def run(quick: bool = False, chaos_only: bool = False) -> dict:
    out: dict = {}
    if not chaos_only:
        out.update(token_bench(quick))
    out["design"] = design_bench(quick)
    out["chaos"] = chaos_bench(quick)
    save_json("serving", out, quick=quick)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="CI probe sizes; writes serving_quick.json")
    ap.add_argument("--chaos", action="store_true",
                    help="design-service sections only (skip the token-engine bench)")
    args = ap.parse_args()
    run(quick=args.quick, chaos_only=args.chaos)
