"""Pass B: the jaxpr hazard pass over every served program kind.

Pass A reasons about source text; this pass reasons about the *programs*.
``Session.trace_programs`` abstractly lowers (``jax.make_jaxpr`` — no
compile, no execution) the four served program kinds — simulate / explain /
optimize / frontier — and this module walks the closed jaxprs (recursing
into scan/cond/pjit sub-jaxprs) looking for hazards no AST rule can see:

* ``jaxpr-callback``  — host-callback primitives (``jax.debug``/
  ``pure_callback``/``io_callback``) embedded in a served program: every
  dispatch round-trips to Python.
* ``jaxpr-transfer``  — explicit ``device_put`` inside the program: a
  value that should have entered as a traced argument is being shipped
  mid-program.
* ``jaxpr-float64``   — a float64 intermediate: the suite's serving
  contract is float32 end-to-end; a single promoted op doubles traffic
  downstream of it.
* ``jaxpr-const``     — a large array folded into the program as a
  constant.  Constants are baked into the executable; a big one is almost
  always a traced-argument candidate that leaked into the trace (and it
  bloats the AOT cache ROADMAP item 2 wants to ship).
* ``jaxpr-seam``      — primitives that cannot lower through the
  ``kernels/runtime.py`` seam (decompositions backed by per-backend custom
  calls, e.g. linear-algebra factorizations).

The sweep covers the full 7-architecture ``.dhd`` library x all 4 kinds
over one representative workload bucket; ``run_pass_b`` returns the
machine-readable dict embedded in ``results/analysis/dragonlint.json``.
"""
from __future__ import annotations

from pathlib import Path

from tools.dragonlint.engine import REPO_ROOT, Finding

KINDS = ("simulate", "explain", "optimize", "frontier")
DEFAULT_WORKLOAD = "bert_base"

# host-callback primitive names; jax.debug.print lowers to debug_print
CALLBACK_PRIMS = {
    "debug_callback", "debug_print", "pure_callback", "io_callback", "callback",
    "outside_call",
}
# mid-program host<->device / placement transfers.  jnp.asarray over tiny
# static config (spec masks) lowers to an ALIAS-semantics device_put of a
# constant — free at dispatch, constant-folded by XLA — so the rule only
# fires on placements bigger than this.
TRANSFER_PRIMS = {"device_put", "copy"}
TRANSFER_ELEMS_LIMIT = 1024
# backed by per-backend custom calls the kernels/runtime.py seam can't carry
SEAM_UNSAFE_PRIMS = {
    "eig", "eigh", "svd", "lu", "qr", "cholesky", "triangular_solve",
    "custom_linear_solve", "tridiagonal", "tridiagonal_solve", "schur",
    "approx_top_k", "fft",
}
# a constant this large folded into the executable is a traced-arg leak
CONST_ELEMS_LIMIT = 4096


def iter_eqns(jaxpr):
    """Depth-first over every equation, recursing into sub-jaxprs carried in
    eqn params (scan/while/cond bodies, pjit/custom_vjp calls, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def _sub_jaxprs(value):
    from jax.extend import core as jex_core

    vals = value if isinstance(value, (list, tuple)) else [value]
    for v in vals:
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v


def _is_float64(aval) -> bool:
    import numpy as np

    dt = getattr(aval, "dtype", None)
    return dt is not None and dt == np.dtype("float64")


def hazards_in(closed, label: str) -> list[Finding]:
    """All jaxpr hazards in one ClosedJaxpr; ``label`` becomes the finding's
    pseudo-path ``<jaxpr:arch/kind>``."""
    import numpy as np

    findings: list[Finding] = []
    path = f"<jaxpr:{label}>"

    for const in closed.consts:
        a = np.asarray(const)
        if a.size > CONST_ELEMS_LIMIT:
            findings.append(Finding(
                "jaxpr-const", path, 0,
                f"array of shape {a.shape} ({a.size} elems, {a.dtype}) folded into "
                "the program as a constant — pass it as a traced argument",
            ))
        if _is_float64(a):
            findings.append(Finding(
                "jaxpr-float64", path, 0,
                f"float64 constant of shape {a.shape} baked into the program",
            ))

    seen: set[tuple[str, str]] = set()
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        hit = None
        if name in CALLBACK_PRIMS:
            hit = ("jaxpr-callback",
                   f"host-callback primitive {name!r} in a served program — every "
                   "dispatch round-trips to Python")
        elif name in TRANSFER_PRIMS:
            sizes = [getattr(getattr(v, "aval", None), "size", 0) for v in eqn.invars]
            if max(sizes, default=0) > TRANSFER_ELEMS_LIMIT:
                hit = ("jaxpr-transfer",
                       f"mid-program transfer primitive {name!r} over "
                       f"{max(sizes)} elements — the value belongs in the "
                       "program's traced arguments")
        elif name in SEAM_UNSAFE_PRIMS:
            hit = ("jaxpr-seam",
                   f"primitive {name!r} lowers via per-backend custom calls and "
                   "cannot pass the kernels/runtime.py seam")
        if hit and (hit[0], name) not in seen:
            seen.add((hit[0], name))
            findings.append(Finding(hit[0], path, 0, hit[1]))
        for var in eqn.outvars:
            if _is_float64(getattr(var, "aval", None)) and ("jaxpr-float64", name) not in seen:
                seen.add(("jaxpr-float64", name))
                findings.append(Finding(
                    "jaxpr-float64", path, 0,
                    f"primitive {name!r} produces a float64 intermediate — the "
                    "serving contract is float32 end-to-end",
                ))
    return findings


def run_pass_b(root: Path = REPO_ROOT, workload: str = DEFAULT_WORKLOAD,
               objective: str = "edp") -> dict:
    """Lower simulate/explain/optimize/frontier for every library
    architecture and inspect the jaxprs.  Returns the Pass B report dict
    (``findings`` non-empty => fail)."""
    from repro.api import Architecture, Session, Workload
    from repro.core.dhdl import load_library

    archs = sorted(load_library(refresh=True))
    w = Workload(workload)
    findings: list[Finding] = []
    coverage: list[list[str]] = []
    for arch_name in archs:
        sess = Session(Architecture(arch_name))
        progs = sess.trace_programs(w, objective=objective)
        missing = [k for k in KINDS if k not in progs]
        if missing:
            findings.append(Finding(
                "jaxpr-coverage", f"<jaxpr:{arch_name}>", 0,
                f"trace_programs returned no program for kinds {missing}",
            ))
        for kind in KINDS:
            if kind not in progs:
                continue
            findings.extend(hazards_in(progs[kind], f"{arch_name}/{kind}"))
            coverage.append([arch_name, kind])
    return {
        "workload": workload,
        "bucket": list(w.bucket),
        "objective": objective,
        "architectures": archs,
        "kinds": list(KINDS),
        "coverage": coverage,
        "programs_lowered": len(coverage),
        "findings": [f.to_json() for f in findings],
    }
