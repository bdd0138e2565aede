#!/usr/bin/env python3
"""Drive DRAGON's served path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: phases 0-3 below
    python chip_smoke.py --four-chips  # four chips: the sharded frontier only

Phases, all in this one process (a chip serves one process at a time):

0. device check: the platform must be ``tpu``; there is no CPU fallback.
1. façade at full width: ``Session("datacenter")`` on the 707-vertex
   ``qwen2.5-32b:prefill_32k`` cell (1024 bucket) runs simulate, explain,
   optimize(200 epochs) and frontier(P=512, 24 epochs).  Checks: finite
   headline fields, simulate == simulate_batch at request bucket 1, DSim
   vs the reference walker (``core/refsim.py``) on bert_base/base, and the
   same jitted simulate program on the chip vs on the host CPU.
2. service: ``BatchingDesignService`` (max_batch 16) serves 64 mixed
   simulate/explain queries over three 1024-bucket LM cells x four library
   architectures.  Checks: availability 1.0, replies bit-identical to the
   sequential ``DesignService`` at the same request bucket.
3. restart: ``Session(cache_dir=d).preheat(...)``, then a fresh
   ``Session(cache_dir=d)`` serves its first simulate/explain.  Checks:
   every built program persisted, zero traces, identical replies.

``--four-chips`` runs the façade frontier at P=1024 on a 1-D ``pop`` mesh of
four chips and on one chip, and checks that they agree.

Seconds printed per phase are set-up figures (compile + first run, then a
warm repeat), not benchmark metrics.  Any failed check exits non-zero
without the result line; on success the last line is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
LM_CELLS = (
    ("qwen2.5-32b", "prefill_32k"),
    ("kimi-k2-1t-a32b", "decode_32k"),
    ("llama4-scout-17b-a16e", "train_4k"),
)
ARCHS = ("base", "edge", "datacenter", "hbm_class")
REFSIM_TOL = 0.03  # bert_base's tolerance in tests/test_refsim_accuracy.py
CHIP_VS_CPU_RTOL = 1e-4
FOUR_CHIP_RTOL = 1e-5


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def timed(label: str, fn, repeat: bool = True):
    """Call ``fn`` (compile + run), then once more warm; print both walls
    and require the warm repeat to give the identical answer."""
    t0 = time.perf_counter()
    first = fn()
    t1 = time.perf_counter()
    line = f"{label}: first_call_s={t1 - t0:.3f}"
    if repeat:
        again = fn()
        line += f" warm_call_s={time.perf_counter() - t1:.3f}"
        check(again.to_json() == first.to_json(), f"{label}: warm repeat differs from first call")
    print(line, flush=True)
    return first


def _finite(result, what: str) -> None:
    from repro.serving.resilience import nonfinite_in

    bad = nonfinite_in(result)
    check(bad is None, f"{what}: non-finite {bad}")


def lm_workload(arch: str, shape: str):
    from repro.api import Workload
    from repro.workloads import lm_cell

    return Workload(lm_cell(arch, shape), labels=(f"{arch}:{shape}",))


# --------------------------------------------------------------------------- #
# phase 0
# --------------------------------------------------------------------------- #


def device_check():
    # the chip-vs-host comparison needs the CPU backend beside the TPU
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax
    import jaxlib

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's default platform is {platform!r} "
              f"({len(devices)} device(s)); this check runs on a TPU only",
              file=sys.stderr)
        raise SystemExit(3)
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform={platform} kind={devices[0].device_kind} "
          f"count={len(devices)} jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu}", flush=True)
    return devices


# --------------------------------------------------------------------------- #
# phase 1: the façade at full width
# --------------------------------------------------------------------------- #


def phase_facade(*, steps: int = 200, population: int = 512, epochs: int = 24) -> None:
    import jax
    import numpy as np

    from repro.api import Session, Workload
    from repro.core.refsim import reference_simulate  # engine-oracle: the reference walker

    sess = Session("datacenter")
    w = lm_workload(*LM_CELLS[0])
    check(w.bucket == (1, 1024), f"{w.labels[0]} bucket {w.bucket} != (1, 1024)")

    rep = timed("phase1 simulate", lambda: sess.simulate(w))
    _finite(rep, "simulate")
    expl = timed("phase1 explain", lambda: sess.explain(w, objective="edp"))
    _finite(expl, "explain")
    check(all(math.isfinite(a.elasticity) for a in expl.attribution),
          "explain: non-finite elasticity")
    opt = timed("phase1 optimize", lambda: sess.optimize(w, steps=steps))
    _finite(opt, "optimize")
    check(opt.epochs == steps, f"optimize ran {opt.epochs} epochs, not {steps}")
    front = timed("phase1 frontier",
                  lambda: sess.frontier(w, population=population, steps=epochs))
    _finite(front, "frontier")
    check(len(front.front) > 0, "frontier: empty front")
    print(f"phase1 results: runtime_s={rep.workloads[0].runtime_s!r} "
          f"energy_j={rep.workloads[0].energy_j!r} area_mm2={rep.area_mm2!r} "
          f"optimize_improvement={opt.improvement!r} front={len(front.front)}/"
          f"{population} hypervolume={front.hypervolume!r}", flush=True)

    batched = sess.simulate_batch([w], request_bucket=1)[0]
    check(batched.to_json() == rep.to_json(),
          "simulate != simulate_batch at request bucket 1")

    base = Session("base")
    b_rep = base.simulate("bert_base")
    chw = base.architecture.compiled.specialize()
    ref = reference_simulate(chw, Workload("bert_base").graphs[0])["cycles"]
    rel = abs(b_rep.workloads[0].cycles - ref) / max(ref, 1.0)
    print(f"phase1 refsim bert_base/base: dsim_cycles={b_rep.workloads[0].cycles!r} "
          f"ref_cycles={ref!r} rel={rel!r} tol={REFSIM_TOL}", flush=True)
    check(rel <= REFSIM_TOL, f"DSim vs refsim rel err {rel} > {REFSIM_TOL}")

    # the jitted program Session.perf dispatches, on the chip and on the host
    cpu = jax.devices("cpu")[0]
    a = sess.architecture
    worst = 0.0
    for cell in LM_CELLS:
        wl = lm_workload(*cell)
        prog = sess._perf_program(wl.bucket, a.spec, sess.mcfg)
        args = (a.tech, a.arch, wl.stacked)
        on_chip = prog(*args)
        with jax.default_device(cpu):
            on_cpu = prog(*jax.device_put(args, cpu))
        check(on_cpu.cycles.devices() == {cpu}, "host reference did not run on the CPU")
        check(next(iter(on_chip.cycles.devices())).platform == "tpu",
              "chip result did not run on the TPU")
        for field in ("cycles", "energy", "area", "power"):
            x = np.asarray(getattr(on_chip, field), np.float64)
            y = np.asarray(getattr(on_cpu, field), np.float64)
            rel = float(np.max(np.abs(x - y) / np.maximum(np.abs(y), 1e-300)))
            print(f"phase1 chip_vs_cpu {cell[0]}:{cell[1]} {field}: rel={rel!r}", flush=True)
            worst = max(worst, rel)
    print(f"phase1 chip_vs_cpu max_rel={worst!r} bound={CHIP_VS_CPU_RTOL}", flush=True)
    check(worst <= CHIP_VS_CPU_RTOL,
          f"chip vs CPU relative difference {worst} > {CHIP_VS_CPU_RTOL}")


# --------------------------------------------------------------------------- #
# phase 2: the batching service
# --------------------------------------------------------------------------- #


def phase_service(*, n_queries: int = 64, max_batch: int = 16) -> None:
    from repro.serving import BatchingDesignService, DesignQuery, DesignService, FlushPolicy

    wls = [lm_workload(*cell) for cell in LM_CELLS]
    check(all(w.bucket == (1, 1024) for w in wls), "service cells must share the 1024 bucket")
    queries = [
        DesignQuery(qid=i, kind=("simulate", "explain")[i % 2], workload=wls[i % 3],
                    architecture=ARCHS[(i // 3) % len(ARCHS)])
        for i in range(n_queries)
    ]
    svc = BatchingDesignService("datacenter", policy=FlushPolicy(max_batch=max_batch))
    t0 = time.perf_counter()
    info = svc.warmup(wls)
    print(f"phase2 warmup: programs={info['programs']} built={info['built']} "
          f"seconds={time.perf_counter() - t0:.3f}", flush=True)
    t0 = time.perf_counter()
    replies = svc.serve(queries)
    print(f"phase2 batched serve: queries={len(replies)} seconds="
          f"{time.perf_counter() - t0:.3f} batches={svc.stats.batches}", flush=True)
    seq = DesignService("datacenter", request_bucket=max_batch,
                        programs=svc.session.programs)
    t0 = time.perf_counter()
    seq_replies = seq.serve(queries)
    print(f"phase2 sequential serve: queries={len(seq_replies)} seconds="
          f"{time.perf_counter() - t0:.3f}", flush=True)

    for name, s, rs in (("batched", svc, replies), ("sequential", seq, seq_replies)):
        bad = [(r.qid, r.error) for r in rs if not r.ok]
        check(not bad, f"{name}: failed replies {bad[:3]}")
        check(s.stats.availability == 1.0,
              f"{name}: availability {s.stats.availability} != 1.0")
        for r in rs:
            _finite(r.result, f"{name} qid {r.qid}")
    diff = [r.qid for r, q in zip(replies, seq_replies)
            if r.qid != q.qid or r.result.to_json() != q.result.to_json()]
    check(not diff, f"batched replies differ from sequential: qids {diff[:8]}")
    print(f"phase2 availability=1.0 bit_identical=True "
          f"batched_queries={svc.stats.batched_queries}", flush=True)


# --------------------------------------------------------------------------- #
# phase 3: restart from the persistent AOT cache
# --------------------------------------------------------------------------- #


def phase_restart(cache_dir: Path) -> None:
    from repro.api import Session

    shutil.rmtree(cache_dir, ignore_errors=True)
    try:
        w = lm_workload(*LM_CELLS[0])
        first = Session("datacenter", cache_dir=cache_dir)
        info = first.preheat(w, kinds=("simulate", "explain"))
        print(f"phase3 preheat: built={info['built']} persisted={info['persisted']} "
              f"seconds={info['seconds']}", flush=True)
        check(info["built"] > 0, "preheat built nothing")
        check(info["persisted"] == info["built"],
              f"preheat persisted {info['persisted']} of {info['built']} programs")
        want = (first.simulate(w).to_json(), first.explain(w).to_json())

        t0 = time.perf_counter()
        fresh = Session("datacenter", cache_dir=cache_dir)
        got = (fresh.simulate(w).to_json(), fresh.explain(w).to_json())
        print(f"phase3 restart: disk_loaded={fresh.disk_loaded} traces="
              f"{fresh.stats.traces} seconds={time.perf_counter() - t0:.3f}", flush=True)
        check(fresh.disk_loaded == info["persisted"],
              f"restart loaded {fresh.disk_loaded} of {info['persisted']} programs")
        check(fresh.stats.traces == 0, f"restart traced {fresh.stats.traces} programs")
        check(got == want, "restarted replies differ from the preheating session's")
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)


# --------------------------------------------------------------------------- #
# --four-chips: the population sharded over a pop mesh
# --------------------------------------------------------------------------- #


def phase_four_chips(devices, *, population: int = 1024, epochs: int = 24) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.api import Session

    check(len(devices) >= 4, f"--four-chips needs 4 devices, found {len(devices)}")
    mesh = Mesh(np.array(devices[:4]), ("pop",))
    sess = Session("datacenter")
    w = lm_workload(*LM_CELLS[0])
    sharded = timed("four_chips frontier sharded",
                    lambda: sess.frontier(w, population=population, steps=epochs, mesh=mesh),
                    repeat=False)
    single = timed("four_chips frontier one_chip",
                   lambda: sess.frontier(w, population=population, steps=epochs),
                   repeat=False)
    for name, res in (("sharded", sharded), ("one_chip", single)):
        _finite(res, f"frontier {name}")
    held = {len(leaf.sharding.device_set) for leaf in jax.tree.leaves(sharded.raw.tech)}
    print(f"four_chips population state devices per leaf: {sorted(held)}", flush=True)
    check(held == {4}, f"sharded population state spans {sorted(held)} devices, not 4")
    h4, h1 = sharded.raw.history, single.raw.history
    worst = float(np.max(np.abs(h4 - h1) / np.maximum(np.abs(h1), 1e-30)))
    print(f"four_chips history max_rel={worst!r} rtol={FOUR_CHIP_RTOL} "
          f"front_sharded={sharded.raw.front.tolist()} "
          f"front_one_chip={single.raw.front.tolist()}", flush=True)
    check(np.allclose(h4, h1, rtol=FOUR_CHIP_RTOL, atol=0.0),
          f"sharded history differs from one chip (max rel {worst})")
    check(np.array_equal(sharded.raw.front, single.raw.front),
          "sharded front indices differ from one chip")


# --------------------------------------------------------------------------- #
# main
# --------------------------------------------------------------------------- #


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pop-sharded frontier on 4 chips vs 1 chip")
    args = ap.parse_args(argv)

    devices = device_check()
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.kernels import runtime
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script ({e})",
              file=sys.stderr)
        return 4
    print(f"compile cache: {runtime.enable_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    try:
        if args.four_chips:
            phase_four_chips(devices)
        else:
            for name, phase in (
                ("phase1", phase_facade),
                ("phase2", phase_service),
                ("phase3", lambda: phase_restart(ROOT / ".smoke_aot")),
            ):
                t = time.perf_counter()
                phase()
                print(f"{name} done: seconds={time.perf_counter() - t:.3f}", flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total seconds={time.perf_counter() - t0:.3f}", flush=True)
    count = 4 if args.four_chips else len(devices)
    print(json.dumps({"ok": True, "device": {"platform": devices[0].platform,
                                             "kind": devices[0].device_kind,
                                             "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
