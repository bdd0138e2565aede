"""Drive one run of ``run.py`` on the CPU, skipping its look for a TPU, with
one fault planted in the timed path:

    python drive.py <fault> <BENCHMARK.json> --workload ... --seed ... --seconds ... --trace 0

Faults: ``none``; ``answer`` (every report's cycles altered by 1% where the
report is built); ``half_batch`` (the second half of each coalesced chunk
answered with its first lane's result); ``state_unchanged`` (DOpt's Adam
step returns no update, so the design never moves); ``design_stale`` (an
optimize reply states its start design, and that design's report, as the
optimized one); and for frontier replies ``front_dropped`` (the last member
of the reply's front left out), ``member_frozen`` (the first member of each
shard of the population keeps its state through every epoch),
``penalty_skipped`` (the budget penalty left out of the members'
objective), ``hv_box_shifted`` (the hypervolume taken in a box moved by 0.5
on each axis) and ``shard_lost`` (the second shard of a sharded population
answered with the first shard's members)."""
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
    elif fault in FRONTIER_FAULTS:
        FRONTIER_FAULTS[fault]()
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


def front_dropped() -> None:
    from repro import api

    frontier = api.Session.frontier

    def dropped(self, *a, **kw):
        res = frontier(self, *a, **kw)
        return dataclasses.replace(res, front=res.front[:-1])

    api.Session.frontier = dropped


def member_frozen() -> None:
    from functools import partial

    import jax
    from repro import api

    popsim = api._popsim  # the population engine the façade drives

    def frozen(state, mixes, gstack, lr, pw_schedule, spec, mcfg, opt_over):
        step = partial(popsim._member_step, spec=spec, mcfg=mcfg, opt_over=opt_over)

        def epoch(st, pw):
            new, m = jax.vmap(lambda t, a, ts, as_, w, ab, pb: step(t, a, ts, as_, w, ab, pb, gstack, lr, pw)
                              )(*st, *mixes)
            return jax.tree.map(lambda n, o: n.at[0].set(o[0]), new, st), m

        return jax.lax.scan(epoch, state, pw_schedule)

    popsim._population_scan = frozen


def penalty_skipped() -> None:
    from repro import api

    popsim = api._popsim
    mixed = popsim.mixed_log_objective

    def unpenalized(tech, arch, gs, weights, area_budget, power_budget, penalty_w, spec, mcfg):
        return mixed(tech, arch, gs, weights, area_budget, power_budget, 0.0 * penalty_w, spec, mcfg)

    popsim.mixed_log_objective = unpenalized


def hv_box_shifted() -> None:
    import numpy as np
    from repro import api

    popsim = api._popsim
    dse = popsim.pareto_dse

    def shifted(*a, hv_box=None, **kw):
        return dse(*a, hv_box=tuple(np.asarray(b, np.float32) + 0.5 for b in hv_box), **kw)

    popsim.pareto_dse = shifted


def shard_lost() -> None:
    import jax
    from repro import api

    popsim = api._popsim
    chunk = popsim.population_chunk

    def lost(state, *a, mesh=None, **kw):
        state, m = chunk(state, *a, mesh=mesh, **kw)
        if mesh is None or mesh.size == 1:
            return state, m
        q = m.shape[1] // mesh.size
        return (jax.tree.map(lambda x: x.at[q:2 * q].set(x[:q]), state),
                m.at[:, q:2 * q].set(m[:, :q]))

    popsim.population_chunk = lost


FRONTIER_FAULTS = {f.__name__: f for f in (front_dropped, member_frozen, penalty_skipped,
                                           hv_box_shifted, shard_lost)}


if __name__ == "__main__":
    plant(sys.argv[1])
    sys.exit(run.main(sys.argv[3:], device_check=cpu, bench_path=Path(sys.argv[2])))
