#!/usr/bin/env python3
"""Readings that set the limits of ``check.py``, and the rate sweep of an
open-loop cell: many short windows of one cell in one process.

    python3 benchmarks/chip/calibrate.py --workload lm_1024.optimize \\
        --seeds 11,12,13 --seconds 4 --control 3

For each seed it runs the cell's timed path for ``--seconds`` (set-up is
paid once), then, with the program's state freed, prints per seed the
numbers ``check.py`` compares for the program's replies and, for the first
``--control N`` seeds, for the reference in bfloat16 put in the program's
place.  ``--rates`` runs an open-loop cell at each offered rate instead (one
seed each) and prints completed rate, latency quantiles and whether later
queries waited longer than earlier ones (a growing backlog).
``--exhaustive 1`` also sends, once, every (kind, graph, design) the mix can
send and prints the widest readings.  The benchmark itself never runs this;
it needs a TPU like ``run.py``.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
import run  # noqa: E402


def every_query(mix: dict, config: dict) -> list:
    """Every (kind, graph, design) a mix of the sweep kind can send."""
    import generator as G

    graphs = [g["name"] for g in config["graphs"]]
    out = []
    if mix["loop"] == "open":
        kinds = list(mix["kinds"])
        out += [(k, g, G.Design(a)) for g in graphs for a in config["architectures"] for k in kinds]
        b = mix.get("burst")
        if b:
            for p in b["sweep"]["params"]:
                for v in G.sweep_values(config, b["architecture"], p, b["size"], b["sweep"]["span"]):
                    out += [(b["kind"], g, G.Design(b["architecture"], p, v)) for g in graphs]
    elif mix.get("sweep"):
        sw, base = mix["sweep"], mix["architecture"]
        for p in sw["params"]:
            for v in G.sweep_values(config, base, p, sw["points"], sw["span"]):
                out += [(k, g, G.Design(base, p, v)) for g in graphs for k in mix["kinds"]]
    return [G.Query(i, k, g, d, call=G.call_of(mix, k)) for i, (k, g, d) in enumerate(out)]


def run_every(st: dict) -> list:
    """Send every query of ``every_query`` through the cell's timed path."""
    cell, loop = st["cell"], st["loop"]
    qs = every_query(cell.mix, cell.config)
    for q in qs:
        if q.design not in cell.designs:
            cell.designs[q.design] = cell._architecture(q.design)
    if cell.mix["loop"] == "open":
        replies = loop.service.serve([loop._query(q) for q in qs])
        return [(q, r.result) for q, r in zip(qs, replies) if r.ok]
    return [(q, loop.call(q)) for q in qs]


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--control", type=int, default=0, help="seeds (the first N) that get the control")
    p.add_argument("--rates", default="")
    p.add_argument("--exhaustive", type=int, default=0,
                   help="also send every (kind, graph, design) the mix can send, once")
    p.add_argument("--sample", type=int, default=0, help="replies compared per seed (default: the mix's)")
    p.add_argument("--out", default=str(HERE / ".calibration"))
    a = p.parse_args(argv)
    rates = [float(r) for r in a.rates.split(",") if r]
    seeds = [int(s) for s in a.seeds.split(",") if s] or list(range(1000, 1000 + len(rates)))
    st = run.setup(["--workload", a.workload, "--seed", str(seeds[0]), "--seconds", str(a.seconds),
                    "--trace", "0"], run.require_tpu, run.ROOT / "BENCHMARK.json")
    print(f"setup: warmup_s={st['warmup_s']!r} total_s={time.perf_counter() - run.T_PROCESS!r}", flush=True)
    cell, mix = st["cell"], st["mix"]
    out = Path(a.out)
    out.mkdir(parents=True, exist_ok=True)
    import check as C

    rows = []
    for i, seed in enumerate(seeds):
        if rates:
            cell.mix = mix = dict(copy.deepcopy(st["mix"]), rate_per_s=rates[i])
        cell.reseed(seed)
        st["args"].seed = seed
        win = run.window(st)
        lat = np.asarray([l for q, r, l in win["records"] if run._ok(r)], np.float64)
        ok = len(lat)
        row = {"seed": seed, "rate": mix.get("rate_per_s"), "attempted": win["attempted"], "ok": ok,
               "span_s": win["t_end"] - win["t_open"], "retraces": win["retraces"],
               "late_max_s": win["late_max_s"], "stats": win["stats"],
               "p50_ms": float(np.percentile(lat, 50) * 1e3) if ok else None,
               "p95_ms": float(np.percentile(lat, 95) * 1e3) if ok else None,
               "memory_peak_bytes": win["memory_peak_bytes"]}
        if rates:
            # a growing backlog shows as later queries waiting longer
            thirds = sorted((q.due_s, l) for q, r, l in win["records"] if run._ok(r))
            k = max(1, len(thirds) // 3)
            row["first_third_ms"] = float(np.median([l for _, l in thirds[:k]]) * 1e3)
            row["last_third_ms"] = float(np.median([l for _, l in thirds[-k:]]) * 1e3)
        ctx = run.window_context(win, 0.0)
        for m in run.metrics_for(st["bench"], a.workload, "end_to_end"):
            if m["name"] != "setup_s":
                row[m["name"]] = run.read_metric(m, ctx)
        row["pairs"] = [(q, C.host_result(q, r))
                        for q, r in run.sample(win["records"], a.sample or mix["check"]["sample"], seed)]
        rows.append(row)
        print("window", json.dumps({k: v for k, v in row.items() if k != "pairs"}), flush=True)
    every = []
    if a.exhaustive:
        every = run_every(st)
        print(f"exhaustive: {len(every)} queries", flush=True)
    inputs = run.free_program(st)
    with run.on_host():
        for row in rows:
            t0 = time.perf_counter()
            row["program"] = C.program_readings(inputs, row["pairs"])
            row["reference_s"] = time.perf_counter() - t0
            if row["seed"] in seeds[: a.control]:
                row["control"] = C.control_readings(inputs, [q for q, _ in row.pop("pairs")])
            row.pop("pairs", None)
            print("readings", json.dumps(row), flush=True)
        if every:
            worst = []
            for q, res in every:
                r = C.program_readings(inputs, [(q, res)])
                worst.append((max(v for k, v in r.items() if k != "tie_vertices"), q, r))
            worst.sort(key=lambda w: -w[0])
            readings = C.program_readings(inputs, every)
            print("exhaustive program", json.dumps(readings), flush=True)
            for _, q, r in worst[:8]:
                print("  worst", q.kind, q.graph, q.design, json.dumps(r), flush=True)
            qs = [q for _, q, _ in worst[: a.control * 4]]
            print("exhaustive control", json.dumps(C.control_readings(inputs, qs)), flush=True)
    name = a.workload + ("_rates" if rates else "")
    (out / f"calib_{name}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
