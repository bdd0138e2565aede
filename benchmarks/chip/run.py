#!/usr/bin/env python3
"""DRAGON's chip benchmark: run one cell of ``BENCHMARK.json`` once.

    python3 benchmarks/chip/run.py --workload lm_1024.design_open \\
        --seed 7 --seconds 10 --trace 0

A cell names a configuration (``configs/<config>.json``: graphs, library
architectures), a traffic mix (``traffic/<traffic>.json``, read by
``generator.py``) and, through ``BENCHMARK.json``, its metrics; per-layer
metrics are readers in ``metrics/<name>.py``.  A run checks for a TPU
(there is no CPU fallback), builds the cell's graphs and designs, warms only
the cell's own programs, measures for ``--seconds``, checks the replies
against the plain reference (``check.py``), and prints one JSON object as
its last line.  ``--trace 1`` records a profiler trace of the window and
reports the per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

T_PROCESS = __import__("time").perf_counter()

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = HERE / ".trace"
# a --trace 1 run records the first seconds of its window: writing the trace
# out takes many times the traced span (about 125 s after 5 s of the optimize
# cell on a TPU v5e)
TRACE_S = 2.0
WAIT_AFTER_S = 60.0  # how long past the window a reply may still come

sys.path.insert(0, str(HERE))
import generator as G  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_tpu(chips: int) -> dict:
    """The device the run measures, or exit 3 when JAX finds no TPU or fewer
    chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"run: needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform!r} device(s)")
        raise SystemExit(3)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def load_cell(name: str, bench_path: Path) -> tuple[dict, dict, dict, dict]:
    """The benchmark, the cell's entry, its configuration (with ``dir``, the
    directory its graph files are relative to) and its traffic mix."""
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run: no workload {name!r} in {bench_path}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((bench_path.parent / configs[cell["config"]]["file"]).read_text())
    config["dir"] = str(bench_path.parent / "benchmarks" / "chip")
    mix = json.loads((Path(config["dir"]) / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, mix


def metrics_for(bench: dict, cell: str, section: str) -> list[dict]:
    return [m for m in bench[section] if cell in m.get("workloads", [cell])]


class Tracer:
    """The profiler over the first ``TRACE_S`` seconds of the window: the
    loops call ``tick()`` between queries; it stops the trace once due."""

    def __init__(self, on: bool):
        self.on, self.t0, self.window_s = on, None, 0.0
        self.done = 0  # replies completed while tracing

    def count(self, ok: bool) -> None:
        if self.on and ok:
            self.done += 1

    def start(self) -> None:
        self.t0 = time.perf_counter()
        if self.on:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the harness's own spans suffice
            opts.host_tracer_level = 1
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    def tick(self) -> None:
        if self.on and time.perf_counter() - self.t0 >= TRACE_S:
            self.stop()

    def stop(self) -> None:
        if self.on:
            import jax

            # the traced window ends here; writing the trace out takes longer
            self.window_s = time.perf_counter() - self.t0
            self.on = False
            jax.profiler.stop_trace()


@contextlib.contextmanager
def span(name: str, on: bool):
    if not on:
        yield
        return
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


class Builds:
    """The XLA programs this process compiles, and those it loads from the
    persistent cache instead, from JAX's monitoring events (a load fires no
    compile event): two counts, and the seconds spent compiling."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.compiled, self.loaded, self.seconds = 0, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event: str, secs: float, **_) -> None:
        if event == self.COMPILE:
            self.compiled += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == self.CACHE_HIT:
            self.loaded += 1

    def snapshot(self) -> tuple:
        return self.compiled, self.loaded, self.seconds


# --------------------------------------------------------------------------- #
# set-up
# --------------------------------------------------------------------------- #


class Cell:
    """The cell's inputs as the program takes them: Workloads by graph name
    (from the configuration's graph files), Architectures by design point;
    and the chips the cell runs on."""

    def __init__(self, config: dict, mix: dict, seed: int, seconds: float, chips: int = 1):
        import jax.numpy as jnp
        from repro.api import Workload
        from repro.core.graph import Graph

        import check as C

        self.config, self.mix, self.seed, self.seconds = config, mix, seed, seconds
        self.chips = chips
        self.workloads = {}
        for name, arrays in C.load_graphs(config).items():
            names = tuple(str(n) for n in arrays["names"])
            graph = Graph(**{k: jnp.asarray(v) for k, v in arrays.items() if k != "names"}, names=names)
            self.workloads[name] = Workload(graph, labels=(name,))
        self.designs = {}
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Build every design point this seed's traffic sends (set-up work)."""
        self.seed = seed
        for d in G.designs_of(self.mix, self.config, seed, self.seconds):
            if d not in self.designs:
                self.designs[d] = self._architecture(d)

    def _architecture(self, d: G.Design):
        import dataclasses

        import jax.numpy as jnp
        from repro.api import Architecture

        base = Architecture(d.base)
        if d.param is None:
            return base
        name, _, level = d.param.partition(".")
        old = getattr(base.arch, name)
        if level:
            new = old.at[("localMem", "globalBuf", "mainMem").index(level)].set(d.value)
        else:
            new = jnp.asarray(d.value, jnp.float32)
        return Architecture(base, arch=dataclasses.replace(base.arch, **{name: new}),
                            name=f"{d.base}_{name}_{level}")


class OpenLoop:
    """Independent users of one shared design service: queries sent on the
    schedule's due times, whatever the service's backlog."""

    def __init__(self, cell: Cell, tracing: bool):
        from repro.serving import FlushPolicy, PooledDesignService

        svc = cell.mix["service"]
        self.cell, self.tracing = cell, tracing
        self.max_batch = svc["max_batch"]
        self.service = PooledDesignService(
            cell.mix["burst"]["architecture"] if cell.mix.get("burst") else "base",
            workers=svc["workers"],
            policy=FlushPolicy(max_batch=svc["max_batch"], max_delay_s=svc["max_delay_s"]),
            request_bucket=svc["max_batch"],
        )

    def _query(self, q: G.Query):
        from repro.serving import DesignQuery

        params = dict(q.call)
        objective = params.pop("objective", "edp")
        return DesignQuery(q.qid, q.kind, self.cell.workloads[q.graph],
                           architecture=self.cell.designs[q.design],
                           objective=objective, params=params)

    def warm(self) -> None:
        """Two full chunks per (kind, bucket) the schedule uses, so every
        batched program is compiled and every pool thread has run."""
        sched = G.open_schedule(self.cell.mix, self.cell.config, self.cell.seed, self.cell.seconds)
        seen = {}
        for q in sched:
            key = (q.kind, self.cell.workloads[q.graph].bucket)
            seen.setdefault(key, q)
        for _ in range(2):
            batch = [q for q in seen.values() for _ in range(self.max_batch)]
            replies = self.service.serve([self._query(q) for q in batch])
            bad = [r for r in replies if not r.ok]
            if bad:
                raise RuntimeError(f"warm-up query failed: {bad[0].error}")

    def stats(self) -> dict:
        s = self.service.stats
        return {"queries": s.queries, "ok": s.ok, "batches": s.batches,
                "batched_queries": s.batched_queries}

    def run(self, tracer: Tracer) -> dict:
        sched = G.open_schedule(self.cell.mix, self.cell.config, self.cell.seed, self.cell.seconds)
        clock = time.perf_counter
        pending, records = {}, []
        late_max = 0.0
        i = 0
        tracer.start()
        t_open = clock()
        t_give_up = t_open + self.cell.seconds + WAIT_AFTER_S
        while i < len(sched) or pending:
            now = clock()
            while i < len(sched) and t_open + sched[i].due_s <= now:
                q = sched[i]
                due = t_open + q.due_s
                late_max = max(late_max, now - due)
                with span("bench.enqueue", self.tracing):
                    ticket = self.service.enqueue(self._query(q))
                pending[ticket] = (q, due)
                i += 1
            with span("bench.take", self.tracing):
                for ticket in list(pending):
                    r = self.service.take(ticket)
                    if r is not None:
                        q, due = pending.pop(ticket)
                        records.append((q, r, clock() - due))
                        tracer.count(r.ok)
            tracer.tick()
            if now > t_give_up:
                break
            nxt = t_open + sched[i].due_s if i < len(sched) else now + 0.002
            time.sleep(max(0.0, min(nxt - clock(), 0.002)))
        t_end = clock()
        missing = [(q, None, t_end - due) for q, due in pending.values()]
        return {"records": records + missing, "t_open": t_open, "t_end": t_end,
                "late_max_s": late_max, "attempted": len(sched)}

    def close(self) -> None:
        self.service.close()


class ClosedLoop:
    """One client calling ``Session`` back to back, with no think time."""

    def __init__(self, cell: Cell, tracing: bool):
        from repro.api import Session

        self.cell, self.tracing = cell, tracing
        self.session = Session(cell.mix["architecture"])

    def call(self, q: G.Query):
        """The ``Session`` call the query's kind names."""
        return getattr(self.session, q.kind)(
            self.cell.workloads[q.graph], architecture=self.cell.designs[q.design], **q.call)

    def warm(self) -> None:
        """Each (kind, graph) the stream sends, twice, at the base design."""
        mix = self.cell.mix
        base = G.Design(mix["architecture"])
        for _ in range(2):
            for kind in mix["kinds"]:
                for g in self.cell.workloads:
                    self.call(G.Query(-1, kind, g, base, call=G.call_of(mix, kind)))

    def stats(self) -> dict:
        return {}

    def run(self, tracer: Tracer) -> dict:
        stream = G.closed_stream(self.cell.mix, self.cell.config, self.cell.seed)
        clock = time.perf_counter
        records = []
        tracer.start()
        t_open = clock()
        while clock() - t_open < self.cell.seconds:
            tracer.tick()
            q = next(stream)
            t0 = clock()
            with span(f"bench.{q.kind}", self.tracing):
                result = self.call(q)
            records.append((q, result, clock() - t0))
            tracer.count(True)
        t_end = clock()
        return {"records": records, "t_open": t_open, "t_end": t_end, "late_max_s": 0.0,
                "attempted": len(records)}

    def close(self) -> None:
        self.session = None


class FrontierLoop(ClosedLoop):
    """One client calling ``Session.frontier`` back to back: the population
    DSE, its members sharded over a ``pop`` mesh of the cell's chips."""

    def __init__(self, cell: Cell, tracing: bool):
        super().__init__(cell, tracing)
        self.mesh = pop_mesh(cell.chips)

    def call(self, q: G.Query):
        """``Session.frontier`` on the query's graph; the population seeds
        from the library designs the call names, so no architecture."""
        call = {k: tuple(v) if k in ("seeds", "metrics") else v for k, v in q.call.items()}
        return self.session.frontier(self.cell.workloads[q.graph], mesh=self.mesh, **call)

    def warm(self) -> None:
        """Every call the window can send, once: each graph with each key of
        the mix's pool.  The front's size follows the key, and the program
        builds the front's and the hypervolume's operations at that size,
        so only these calls leave the window nothing to build."""
        base = G.Design(self.cell.mix["architecture"])
        for kind, g, call in G.calls_of(self.cell.mix, self.cell.config):
            self.call(G.Query(-1, kind, g, base, call=call))


def pop_mesh(chips: int):
    """A one-axis ``pop`` mesh over the first ``chips`` devices; None for
    one chip (the population then runs unsharded)."""
    if chips == 1:
        return None
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:chips]), ("pop",))


LOOPS = {"open": OpenLoop, "closed": ClosedLoop, "frontier": FrontierLoop}


# --------------------------------------------------------------------------- #
# metrics
# --------------------------------------------------------------------------- #


def window_context(win: dict, setup_s: float) -> dict:
    """What an end-to-end metric's reader reads: the window's span, every
    query's latency (a failed or missing reply as infinite), the completed
    (query, result, latency) triples, and the set-up seconds."""
    span_s = win["t_end"] - win["t_open"]
    return dict(
        window_s=span_s, give_up_s=span_s + WAIT_AFTER_S, setup_s=setup_s,
        latency_s=[lat if _ok(r) else float("inf") for _, r, lat in win["records"]],
        done=[(q, _result(r), lat) for q, r, lat in win["records"] if _ok(r)],
    )


def _ok(r) -> bool:
    if r is None:
        return False
    return getattr(r, "ok", True)


def _result(r):
    return r.result if hasattr(r, "result") and hasattr(r, "ok") else r


def read_metric(metric: dict, ctx: dict):
    """The metric's reader, ``metrics/<name>.py``, over ``ctx``; None where
    it finds nothing to read."""
    path = HERE / "metrics" / f"{metric['name']}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{metric['name']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


# --------------------------------------------------------------------------- #
# main
# --------------------------------------------------------------------------- #


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def setup(argv, device_check, bench_path: Path):
    """Everything before the window: returns the run's state."""
    args = parse(argv)
    bench, cell_entry, config, mix = load_cell(args.workload, bench_path)
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"  # the reference runs on the host
    device = device_check(cell_entry["chips"])
    import jax

    if device["platform"] == "tpu":
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    sys.path.insert(0, str(ROOT / "src"))
    builds = Builds()
    cell = Cell(config, mix, args.seed, args.seconds, cell_entry["chips"])
    loop = LOOPS[mix["loop"]](cell, tracing=bool(args.trace))
    t0 = time.perf_counter()
    loop.warm()
    warmup_s = time.perf_counter() - t0
    return dict(args=args, bench=bench, cell_entry=cell_entry, config=config, mix=mix,
                device=device, cell=cell, loop=loop, warmup_s=warmup_s, builds=builds)


def window(st: dict) -> dict:
    """The measured window (traced with ``--trace 1``)."""
    import jax
    from repro.core import instrument

    args, loop = st["args"], st["loop"]
    traces0 = instrument.trace_count()
    builds0 = st["builds"].snapshot()
    stats0 = loop.stats()
    tracer = Tracer(bool(args.trace))
    win = loop.run(tracer)
    tracer.stop()
    win["trace_window_s"] = tracer.window_s
    win["traced_done"] = tracer.done
    win["retraces"] = instrument.trace_count() - traces0
    win["builds"] = [b - a for a, b in zip(builds0, st["builds"].snapshot())]
    stats1 = loop.stats()
    win["stats"] = {k: stats1[k] - stats0[k] for k in stats1}
    # the peak on the fullest of the cell's chips
    mems = [d.memory_stats() or {} for d in jax.devices()[: st["cell"].chips]]
    win["memory_peak_bytes"] = max(int(m.get("peak_bytes_in_use", 0)) for m in mems)
    return win


def sample(records: list, n: int, seed: int) -> list:
    """Up to ``n`` completed replies drawn from the seed: first one of each
    graph the window served (the largest among them), then any."""
    done = [(q, _result(r)) for q, r, _ in records if _ok(r)]
    order = G.rng_for(seed, stream=1).permutation(len(done)).tolist()
    picks, graphs = [], set()
    for i in order:
        if done[i][0].graph not in graphs:
            graphs.add(done[i][0].graph)
            picks.append(i)
    picks += [i for i in order if i not in picks][: max(0, n - len(picks))]
    return [done[i] for i in sorted(picks)]


def free_program(st: dict):
    """Close the system under test and switch JAX to float64 on the host for
    the reference.  Returns the reference's inputs."""
    import jax

    import check as C

    inputs = C.Inputs(st["config"])
    st["loop"].close()
    jax.clear_caches()
    jax.config.update("jax_enable_x64", True)
    return inputs


def on_host():
    import jax

    return jax.default_device(jax.devices("cpu")[0])


def verify(st: dict, win: dict) -> tuple[bool, dict]:
    """Compare a seeded sample of the window's replies with the reference,
    on the host in float64, once the program's state is freed."""
    import check as C

    mix = st["mix"]
    pairs = [(q, C.host_result(q, r)) for q, r in sample(win["records"], mix["check"]["sample"],
                                                        st["args"].seed)]
    inputs = free_program(st)
    with on_host():
        readings = C.program_readings(inputs, pairs)
    ok, checks = C.judge(readings, mix["check"]["limits"])
    missing = sum(1 for _, r, _ in win["records"] if r is None)
    if missing or not pairs:
        ok = False
    checks["missing_replies"] = {"value": missing, "limit": mix["check"]["limits"]["missing_replies"]}
    return ok, checks


def main(argv=None, device_check=require_tpu, bench_path: Path = ROOT / "BENCHMARK.json") -> int:
    st = setup(argv, device_check, bench_path)
    args, name = st["args"], st["args"].workload
    setup_s = time.perf_counter() - T_PROCESS
    win = window(st)
    log(f"device: platform={st['device']['platform']} device_kind={st['device']['kind']} "
        f"count={st['device']['count']}")
    log(f"generator: attempted={win['attempted']} late_max_s={win['late_max_s']!r}")
    log(f"set-up: setup_s={setup_s!r} warmup_s={st['warmup_s']!r}; window: "
        f"{len(win['records'])} calls or queries in {win['t_end'] - win['t_open']!r} s")
    log(f"retraces inside the window: {win['retraces']}")
    compiled, loaded, secs = win["builds"]
    log(f"programs compiled inside the window: {compiled} ({secs!r} s); "
        f"loaded from the persistent cache inside the window: {loaded}")

    metrics = {}
    device = dict(st["device"], memory_peak_bytes=win["memory_peak_bytes"])
    breakdown = None
    if args.trace:
        import trace_reduce as T

        xplane = T.find_xplane(TRACE_DIR)
        reduced = T.reduce(xplane, win["trace_window_s"]) if xplane else None
        if reduced:
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
        ctx = dict(trace=reduced, stats=win["stats"], mix=st["mix"], warmup_s=st["warmup_s"],
                   traced_done=win["traced_done"],
                   programs=json.loads((HERE / "programs.json").read_text()))
        section = "per_layer"
    else:
        ctx, section = window_context(win, setup_s), "end_to_end"
    for m in metrics_for(st["bench"], name, section):
        v = read_metric(m, ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    t_check = time.perf_counter()
    correct, checks = verify(st, win)
    log(f"reference and check: {time.perf_counter() - t_check!r} s")
    failed = sum(1 for _, r, _ in win["records"] if not _ok(r))
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    out = {"correct": correct, "attempted": win["attempted"], "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
