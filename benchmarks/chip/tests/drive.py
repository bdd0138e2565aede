"""Drive one run of ``run.py`` on the CPU, skipping its look for a TPU, with
one fault planted in the timed path:

    python drive.py <fault> <BENCHMARK.json> --workload ... --seed ... --seconds ... --trace 0

Faults: ``none``; ``answer`` (every report's cycles altered by 1% where the
report is built); ``half_batch`` (the second half of each coalesced chunk
answered with its first lane's result); ``state_unchanged`` (DOpt's Adam
step returns no update, so the design never moves); ``design_stale`` (an
optimize reply states its start design, and that design's report, as the
optimized one)."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))

import run  # noqa: E402


def cpu(chips: int) -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}


def plant(fault: str) -> None:
    if fault == "answer":
        from repro import api

        build = api.Session._build_report

        def altered(self, *a, **kw):
            rep = build(self, *a, **kw)
            w = rep.workloads[0]
            w = dataclasses.replace(w, cycles=w.cycles * 1.01)
            return dataclasses.replace(rep, workloads=(w,) + rep.workloads[1:])

        api.Session._build_report = altered
    elif fault == "half_batch":
        from repro.serving import pool

        dispatch = pool.StagedBatchingService._dispatch_chunk

        def half(self, adms):
            out = dispatch(self, adms)
            n = len(out)
            return out[: (n + 1) // 2] + [out[0]] * (n // 2)

        pool.StagedBatchingService._dispatch_chunk = half
    elif fault == "state_unchanged":
        import jax
        from repro import api

        update = api._dopt.adam_update  # the optimizer module the façade drives

        def frozen(grads, state, lr, *a, **kw):
            upd, st = update(grads, state, lr, *a, **kw)
            return jax.tree.map(lambda u: u * 0.0, upd), st

        api._dopt.adam_update = frozen
    elif fault == "design_stale":
        from repro import api

        optimize = api.Session.optimize

        def stale(self, workload, *, architecture=None, **kw):
            res = optimize(self, workload, architecture=architecture, **kw)
            start = self._arch(architecture)
            return dataclasses.replace(res, dhd=start.to_dhd(), optimized=res.baseline)

        api.Session.optimize = stale
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(sys.argv[1])
    sys.exit(run.main(sys.argv[3:], device_check=cpu, bench_path=Path(sys.argv[2])))
