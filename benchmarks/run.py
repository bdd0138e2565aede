"""Benchmark harness — one benchmark per paper table/figure.

  python -m benchmarks.run [--quick] [--only sim_speed,dse,...]

| benchmark     | paper artifact                 |
|---------------|--------------------------------|
| sim_speed     | §8.1 Fig.4/Table 1 (accuracy + ~1000x speed) |
| dse           | §8.2 Table 4/Fig.7 (derived accelerators)    |
| tech_targets  | §8.3 Table 3/Fig.3 (importance + 100x EDP)   |
| edp_gain      | abstract (5x vs published baselines)          |
| roofline      | EXPERIMENTS.md §Roofline (from the dry-run)   |
| pareto        | constrained latency/energy/area frontier (population DSE) |
| api           | Session compiled-program cache (cold/warm, zero-retrace gates) |
"""
from __future__ import annotations

import argparse
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    # process set-up, not an engine call -- dragonlint: disable=api-surface
    from repro.kernels import runtime

    runtime.enable_compile_cache()

    from benchmarks import (
        bench_api,
        bench_dse,
        bench_edp_gain,
        bench_pareto,
        bench_roofline,
        bench_serving,
        bench_sim_speed,
        bench_tech_targets,
    )

    table = {
        "sim_speed": bench_sim_speed.run,
        "dse": bench_dse.run,
        "tech_targets": bench_tech_targets.run,
        "edp_gain": bench_edp_gain.run,
        "roofline": bench_roofline.run,
        "serving": bench_serving.run,
        "pareto": bench_pareto.run,
        "api": bench_api.run,
    }
    names = args.only.split(",") if args.only else list(table)
    failures = []
    for name in names:
        t0 = time.time()
        print(f"=== bench {name} ===", flush=True)
        try:
            table[name](quick=args.quick)
            print(f"=== bench {name} done in {time.time()-t0:.1f}s ===", flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")
    print("ALL BENCHMARKS PASSED")


if __name__ == "__main__":
    main()
