"""Pass A rules: the serving contract, checked from the AST.

Two families:

* the three absorbed legacy checkers — ``kernel-seam`` (version-fragile JAX
  spellings stay inside ``kernels/runtime.py``), ``api-surface``
  (examples/benchmarks/tools consume the façade) and the repo-scope
  ``dhdl-corpus`` (:mod:`tools.dragonlint.corpus`);

* the serving-contract rules — hazards that silently destroy the zero-
  retrace / no-host-sync guarantees ``bench_api`` gates dynamically:
  ``host-sync``, ``scan-donate``, ``retrace-hazard``, ``stray-debug``,
  ``float64-promotion``, ``stale-oracle-tag``.

The contract rules need to know what code runs *under trace*: a host sync in
a benchmark driver is normal, the same call inside a jitted body blocks the
dispatch pipeline on every step.  :func:`traced_functions` computes a static
approximation — a function is traced if it is decorated with / passed to a
JAX tracing entry point (``jax.jit``, ``vmap``, ``grad``, ``lax.scan``,
``runtime.spmd_map``, ``dragon_pallas_call``, ...), calls the repo's own
trace probe (``instrument.count_trace``), is defined inside a traced
function, or is called by name from one (module-local fixpoint).  Cross-
module tracing is intentionally out of scope for Pass A — Pass B covers it
by lowering the real served programs to jaxprs.
"""
from __future__ import annotations

import ast
import re
from typing import Iterator

from tools.dragonlint.engine import Finding, rule

# --------------------------------------------------------------------------- #
# shared AST helpers
# --------------------------------------------------------------------------- #


def _dotted(node: ast.AST) -> str | None:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _parents(tree: ast.AST) -> dict[ast.AST, ast.AST]:
    par: dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            par[child] = node
    return par


_FUNCS = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def _enclosing_func(par: dict, node: ast.AST):
    n = par.get(node)
    while n is not None and not isinstance(n, _FUNCS):
        n = par.get(n)
    return n


def _scope_chain(par: dict, node: ast.AST) -> list:
    chain, n = [], _enclosing_func(par, node)
    while n is not None:
        chain.append(n)
        n = _enclosing_func(par, n)
    return chain


# entry points whose function-valued arguments (or decorated functions) run
# under trace
TRACING_CALLS = {
    "jax.jit", "jit",
    "jax.vmap", "vmap", "jax.pmap", "pmap",
    "jax.grad", "grad", "jax.value_and_grad", "value_and_grad",
    "jax.jacfwd", "jax.jacrev", "jax.hessian",
    "jax.vjp", "jax.jvp", "jax.linearize",
    "jax.lax.scan", "lax.scan",
    "jax.lax.associative_scan", "lax.associative_scan",
    "jax.lax.cond", "lax.cond",
    "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop",
    "jax.lax.map", "lax.map",
    "jax.checkpoint", "jax.remat",
    "jax.eval_shape", "jax.make_jaxpr",
    "jax.custom_vjp", "jax.custom_jvp",
    "runtime.spmd_map", "spmd_map",
    "runtime.dragon_pallas_call", "dragon_pallas_call", "pl.pallas_call",
}
_PARTIAL = {"partial", "functools.partial"}
_TRACE_MARKER = {"instrument.count_trace", "count_trace"}


def _tracing_name(node: ast.AST) -> bool:
    """Is this expression a tracing entry point — either the name itself or
    ``partial(<tracing entry>, ...)``?"""
    d = _dotted(node)
    if d in TRACING_CALLS:
        return True
    if isinstance(node, ast.Call) and _dotted(node.func) in _PARTIAL and node.args:
        return _dotted(node.args[0]) in TRACING_CALLS
    return False


def _local_defs(tree: ast.AST) -> dict[str, list[ast.AST]]:
    defs: dict[str, list[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)
    return defs


def _resolve(name: str, site: ast.AST, par: dict, defs: dict) -> ast.AST | None:
    """Module-local name resolution: nearest definition whose scope encloses
    (or equals module scope for) the use site."""
    candidates = defs.get(name, [])
    if not candidates:
        return None
    site_chain = _scope_chain(par, site)
    best, best_depth = None, -1
    for cand in candidates:
        cand_scope = _enclosing_func(par, cand)
        if cand_scope is None:
            depth = 0
        elif cand_scope in site_chain:
            depth = 1 + site_chain.index(cand_scope)
        else:
            continue
        if depth > best_depth:
            best, best_depth = cand, depth
    return best


def traced_functions(tree: ast.AST, par: dict) -> set:
    """The set of function nodes whose bodies run under a JAX trace (static
    approximation; see module docstring)."""
    defs = _local_defs(tree)
    traced: set = set()

    def mark(fn):
        if fn is not None and isinstance(fn, _FUNCS) and fn not in traced:
            traced.add(fn)

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_tracing_name(d) or (isinstance(d, ast.Call) and _tracing_name(d.func))
                   for d in node.decorator_list):
                mark(node)
        if isinstance(node, ast.Call):
            if _dotted(node.func) in _TRACE_MARKER:
                mark(_enclosing_func(par, node))
            if _tracing_name(node.func):
                for arg in list(node.args) + [kw.value for kw in node.keywords]:
                    if isinstance(arg, ast.Lambda):
                        mark(arg)
                    elif isinstance(arg, ast.Name):
                        mark(_resolve(arg.id, node, par, defs))

    # fixpoint: nesting + module-local calls from traced regions
    changed = True
    while changed:
        changed = False
        for node in ast.walk(tree):
            enc = _enclosing_func(par, node)
            in_traced = enc in traced or any(s in traced for s in _scope_chain(par, node))
            if not in_traced:
                continue
            new = None
            if isinstance(node, _FUNCS) and node not in traced:
                new = node
            elif isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                cand = _resolve(node.func.id, node, par, defs)
                if cand is not None and cand not in traced:
                    new = cand
            if new is not None:
                traced.add(new)
                changed = True
    return traced


def _in_traced(node: ast.AST, par: dict, traced: set) -> bool:
    return any(s in traced for s in _scope_chain(par, node))


def _line(text: str, lineno: int) -> str:
    lines = text.splitlines()
    return lines[lineno - 1].strip() if 0 < lineno <= len(lines) else ""


# --------------------------------------------------------------------------- #
# absorbed rule: kernel-seam
# --------------------------------------------------------------------------- #

KERNEL_SEAM_PATTERN = re.compile(
    r"CompilerParams|shard_map|\bpltpu\b|pallas\s+import\s+tpu|pl\.pallas_call"
    r"|serialize_executable|deserialize_and_load"
)
KERNEL_SEAM_ALLOWED = ("kernels/runtime.py",)


@rule(
    "kernel-seam",
    doc="version-fragile JAX spellings (pallas_call / shard_map / TPU compiler "
        "params / executable serialization) must stay inside kernels/runtime.py",
    scan=("src/",),
)
def kernel_seam(rel: str, text: str, tree: ast.AST) -> Iterator[Finding]:
    if rel.endswith(KERNEL_SEAM_ALLOWED):
        return
    for lineno, line in enumerate(text.splitlines(), 1):
        if KERNEL_SEAM_PATTERN.search(line):
            yield Finding("kernel-seam", rel, lineno,
                          "version-fragile spelling outside the runtime seam — route "
                          "through repro.kernels.runtime", line.strip())


# --------------------------------------------------------------------------- #
# absorbed rule: api-surface (+ the stale-oracle-tag companion)
# --------------------------------------------------------------------------- #

ENGINE_MODULES = re.compile(
    r"repro\.core\.(dsim|dopt|popsim|mapper|dgen|refsim)\b|repro\.kernels\b"
)
ENGINE_NAMES = (
    "dsim", "dopt", "popsim", "mapper", "dgen", "refsim", "kernels",
    "simulate", "simulate_chw", "simulate_stacked", "simulate_jit",
    "simulate_breakdown", "stacked_log_objective", "stacked_log_metrics",
    "mixed_log_objective", "optimize", "derive_tech_targets", "pareto_dse",
    "population_chunk", "seed_population", "sample_objective_mixes",
    "init_population_state", "specialize", "map_workload", "map_workload_scan",
)
FROM_CORE = re.compile(r"^\s*from\s+repro\.core\s+import\s+(.+)$")
ORACLE_TAG = "# engine-oracle"

_SURFACE_SCAN = ("examples/", "benchmarks/", "tools/")
# these files spell the forbidden patterns in their own docs/rule bodies
_SURFACE_EXCLUDE = (
    "tools/check_api_surface.py",
    "tools/dragonlint/rules_ast.py",
)


def _logical_stmts(text: str) -> Iterator[tuple[int, str, str]]:
    """(lineno, first_line, folded_stmt): parenthesized ``from X import
    (...)`` statements folded into one logical line so wrapped imports can't
    slip through."""
    lines = text.splitlines()
    i = 0
    while i < len(lines):
        lineno, line = i + 1, lines[i]
        i += 1
        stmt = line
        if re.match(r"^\s*from\s+\S+\s+import\s*\(", line) and ")" not in line:
            while i < len(lines) and ")" not in lines[i]:
                stmt += " " + lines[i]
                i += 1
            if i < len(lines):
                stmt += " " + lines[i]
                i += 1
        yield lineno, line, stmt


def _engine_import_hit(stmt: str) -> str | None:
    if ENGINE_MODULES.search(stmt) and ("import" in stmt or "from" in stmt):
        return "engine module"
    m = FROM_CORE.match(stmt)
    if m:
        names = {
            n.strip().split(" as ")[0]
            for n in m.group(1).replace("(", " ").replace(")", " ").split(",")
        }
        bad = names & set(ENGINE_NAMES)
        if bad:
            return f"engine entry point {sorted(bad)}"
    return None


@rule(
    "api-surface",
    doc="examples/benchmarks/tools must consume the repro.api façade; deliberate "
        "engine baselines carry an '# engine-oracle' tag",
    scan=_SURFACE_SCAN,
    exclude=_SURFACE_EXCLUDE,
)
def api_surface(rel: str, text: str, tree: ast.AST) -> Iterator[Finding]:
    for lineno, line, stmt in _logical_stmts(text):
        hit = _engine_import_hit(stmt)
        if hit and ORACLE_TAG not in stmt:
            yield Finding("api-surface", rel, lineno,
                          f"[{hit}] use repro.api / repro instead, or tag a deliberate "
                          f"oracle comparison with {ORACLE_TAG!r}", line.strip())


@rule(
    "stale-oracle-tag",
    doc="an '# engine-oracle' tag on a line that no longer imports an engine "
        "module is a stale escape hatch — remove it",
    scan=_SURFACE_SCAN,
    exclude=_SURFACE_EXCLUDE,
)
def stale_oracle_tag(rel: str, text: str, tree: ast.AST) -> Iterator[Finding]:
    for lineno, line, stmt in _logical_stmts(text):
        if not re.match(r"^\s*(from|import)\s", stmt):
            continue  # prose mentions of the tag (docstrings) are not tags
        if ORACLE_TAG in stmt and _engine_import_hit(stmt) is None:
            yield Finding("stale-oracle-tag", rel, lineno,
                          "stale '# engine-oracle' tag: the line imports no engine "
                          "module/entry point — drop the tag", line.strip())


# --------------------------------------------------------------------------- #
# serving-contract rule: host-sync
# --------------------------------------------------------------------------- #

_HOST_SYNC_CALLS = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get", "float", "int", "bool",
}
_HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_HOST_CONTAINERS = (ast.List, ast.Tuple, ast.ListComp, ast.GeneratorExp,
                    ast.SetComp, ast.DictComp, ast.Constant)
_HOST_SCALAR_ANNOS = {"float", "int", "bool", "str"}


def _host_scalar_param(node: ast.AST, arg: ast.AST, par: dict) -> bool:
    """Is ``arg`` a Name bound to an enclosing parameter annotated with a
    host scalar type (``decay: float``)?  Casting those is host arithmetic
    on static config, not a device sync."""
    if not isinstance(arg, ast.Name):
        return False
    for fn in _scope_chain(par, node):
        if isinstance(fn, ast.Lambda):
            continue
        a = fn.args
        for p in a.posonlyargs + a.args + a.kwonlyargs:
            if p.arg == arg.id:
                return (isinstance(p.annotation, ast.Name)
                        and p.annotation.id in _HOST_SCALAR_ANNOS)
    return False


@rule(
    "host-sync",
    doc="host-synchronizing calls (float()/.item()/np.asarray/jax.device_get) on "
        "traced values inside jit regions stall the dispatch pipeline every step",
    scan=("src/repro/",),
)
def host_sync(rel: str, text: str, tree: ast.AST) -> Iterator[Finding]:
    par = _parents(tree)
    traced = traced_functions(tree, par)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not _in_traced(node, par, traced):
            continue
        d = _dotted(node.func)
        hit = None
        if d in _HOST_SYNC_CALLS:
            arg0 = node.args[0] if node.args else None
            # casting a literal or a host-scalar-annotated parameter is host
            # arithmetic on static config, not a device sync
            if d in ("float", "int", "bool") and (
                arg0 is None or isinstance(arg0, ast.Constant)
                or _host_scalar_param(node, arg0, par)
            ):
                continue
            # np.array over a host container (list/tuple/comprehension) is
            # trace-time table building, not a device readback
            if isinstance(arg0, _HOST_CONTAINERS):
                continue
            hit = f"{d}()"
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr in _HOST_SYNC_METHODS and not node.args):
            hit = f".{node.func.attr}()"
        if hit:
            yield Finding("host-sync", rel, node.lineno,
                          f"{hit} inside a traced region forces a device->host sync "
                          "(or fails under jit) — keep values on device or hoist to "
                          "the driver", _line(text, node.lineno))


# --------------------------------------------------------------------------- #
# serving-contract rule: scan-donate
# --------------------------------------------------------------------------- #


def _contains_scan(fn: ast.AST, par: dict, defs: dict) -> bool:
    """Does this function (or a module-local callee) run a lax.scan?"""
    seen: set = set()
    stack = [fn]
    while stack:
        cur = stack.pop()
        if cur in seen or cur is None:
            continue
        seen.add(cur)
        for node in ast.walk(cur):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d in ("jax.lax.scan", "lax.scan"):
                    return True
                if isinstance(node.func, ast.Name):
                    stack.append(_resolve(node.func.id, node, par, defs))
    return False


def _jit_sites(tree: ast.AST, par: dict, defs: dict):
    """Yield ``(report_node, wrapped_fn_node_or_None, jit_kwargs)`` for every
    ``jax.jit`` application: decorator (bare, call, or partial) and direct
    ``jax.jit(fn, ...)`` calls."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _dotted(dec) in ("jax.jit", "jit"):
                    yield dec, node, {}
                elif isinstance(dec, ast.Call):
                    f = _dotted(dec.func)
                    if f in ("jax.jit", "jit"):
                        yield dec, node, {kw.arg: kw.value for kw in dec.keywords}
                    elif f in _PARTIAL and dec.args and _dotted(dec.args[0]) in ("jax.jit", "jit"):
                        yield dec, node, {kw.arg: kw.value for kw in dec.keywords}
        elif isinstance(node, ast.Call) and _dotted(node.func) in ("jax.jit", "jit"):
            wrapped = None
            if node.args:
                a0 = node.args[0]
                if isinstance(a0, ast.Lambda):
                    wrapped = a0
                elif isinstance(a0, ast.Name):
                    wrapped = _resolve(a0.id, node, par, defs)
            yield node, wrapped, {kw.arg: kw.value for kw in node.keywords}


@rule(
    "scan-donate",
    doc="a jitted program that advances carried state through lax.scan must "
        "donate that state (donate_argnums/donate_argnames) or every dispatch "
        "copies it",
    scan=("src/repro/",),
)
def scan_donate(rel: str, text: str, tree: ast.AST) -> Iterator[Finding]:
    par = _parents(tree)
    defs = _local_defs(tree)
    for site, wrapped, kw in _jit_sites(tree, par, defs):
        if wrapped is None or not _contains_scan(wrapped, par, defs):
            continue
        if "donate_argnums" not in kw and "donate_argnames" not in kw:
            name = getattr(wrapped, "name", "<lambda>")
            yield Finding("scan-donate", rel, site.lineno,
                          f"jit of {name!r} runs a lax.scan over carried state but "
                          "donates nothing — pass donate_argnums/donate_argnames so "
                          "the state buffers are reused in place",
                          _line(text, site.lineno))


# --------------------------------------------------------------------------- #
# serving-contract rule: retrace-hazard
# --------------------------------------------------------------------------- #


def _static_names(kw: dict) -> set[str]:
    names: set[str] = set()
    v = kw.get("static_argnames")
    if isinstance(v, ast.Constant) and isinstance(v.value, str):
        names.add(v.value)
    elif isinstance(v, (ast.Tuple, ast.List)):
        names.update(e.value for e in v.elts
                     if isinstance(e, ast.Constant) and isinstance(e.value, str))
    return names


def _float_param(fn: ast.AST, name: str) -> bool:
    """Does parameter ``name`` default to a float literal or carry a bare
    ``float`` annotation?  (Both make the value part of the jit cache key —
    every distinct float compiles a fresh program.)"""
    if isinstance(fn, ast.Lambda):
        return False
    args = fn.args
    pos = args.posonlyargs + args.args
    pairs = list(zip(pos[len(pos) - len(args.defaults):], args.defaults))
    pairs += [(a, d) for a, d in zip(args.kwonlyargs, args.kw_defaults) if d is not None]
    for a, d in pairs:
        if a.arg == name and isinstance(d, ast.Constant) and isinstance(d.value, float):
            return True
    for a in pos + args.kwonlyargs:
        if a.arg == name and isinstance(a.annotation, ast.Name) and a.annotation.id == "float":
            return True
    return False


@rule(
    "retrace-hazard",
    doc="a float-valued static jit argument retraces on every distinct value — "
        "make it a traced argument (or part of the Session cache key if it is "
        "genuinely structural)",
    scan=("src/repro/",),
)
def retrace_hazard(rel: str, text: str, tree: ast.AST) -> Iterator[Finding]:
    par = _parents(tree)
    defs = _local_defs(tree)
    for site, wrapped, kw in _jit_sites(tree, par, defs):
        if wrapped is None:
            continue
        for name in sorted(_static_names(kw)):
            if _float_param(wrapped, name):
                yield Finding("retrace-hazard", rel, site.lineno,
                              f"static jit argument {name!r} of "
                              f"{getattr(wrapped, 'name', '<lambda>')!r} is float-"
                              "valued — every distinct value compiles a new program; "
                              "pass it traced instead", _line(text, site.lineno))


# --------------------------------------------------------------------------- #
# serving-contract rule: stray-debug
# --------------------------------------------------------------------------- #


@rule(
    "stray-debug",
    doc="jax.debug.* / breakpoint() in engine modules (and print() or a profiler "
        "span under trace) insert host callbacks into served programs or run once",
    scan=("src/repro/",),
)
def stray_debug(rel: str, text: str, tree: ast.AST) -> Iterator[Finding]:
    par = _parents(tree)
    traced = traced_functions(tree, par)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if d and d.startswith(("jax.debug.", "debug.print", "debug.breakpoint")):
            yield Finding("stray-debug", rel, node.lineno,
                          f"{d} in library code lowers to a host callback — remove "
                          "before serving", _line(text, node.lineno))
        elif d == "breakpoint":
            yield Finding("stray-debug", rel, node.lineno,
                          "breakpoint() left in library code", _line(text, node.lineno))
        elif d == "print" and _in_traced(node, par, traced):
            yield Finding("stray-debug", rel, node.lineno,
                          "print() inside a traced region runs at trace time only "
                          "(or becomes a host callback) — use the driver loop or "
                          "jax.debug deliberately", _line(text, node.lineno))
        elif d and d.endswith(("instrument.span", "TraceAnnotation")) and _in_traced(node, par, traced):
            yield Finding("stray-debug", rel, node.lineno,
                          f"{d}() inside a traced region records one span at trace "
                          "time and nothing per call — open it in the host code "
                          "around the dispatch", _line(text, node.lineno))


# --------------------------------------------------------------------------- #
# serving-contract rule: swallowed-fault
# --------------------------------------------------------------------------- #

_BROAD_EXC = {"Exception", "BaseException", "builtins.Exception", "builtins.BaseException"}


def _only_pass(body: list) -> bool:
    """True when a handler body does nothing: ``pass`` / ``...`` / a bare
    docstring — no logging, no typed re-packaging, no re-raise."""
    for stmt in body:
        if isinstance(stmt, ast.Pass):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant):
            continue
        return False
    return True


@rule(
    "swallowed-fault",
    doc="bare 'except:' and 'except Exception: pass' silently swallow faults — "
        "the resilience layer needs every failure typed, logged, or re-raised",
    scan=("src/",),
)
def swallowed_fault(rel: str, text: str, tree: ast.AST) -> Iterator[Finding]:
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            yield Finding("swallowed-fault", rel, node.lineno,
                          "bare 'except:' catches everything (KeyboardInterrupt "
                          "included) and hides the fault class — catch a typed "
                          "exception or classify via repro.serving.resilience",
                          _line(text, node.lineno))
            continue
        types = [node.type] if not isinstance(node.type, ast.Tuple) else list(node.type.elts)
        broad = any(_dotted(t) in _BROAD_EXC for t in types)
        if broad and _only_pass(node.body):
            yield Finding("swallowed-fault", rel, node.lineno,
                          "'except Exception: pass' swallows the fault with no "
                          "trace — type it, log it, re-raise, or degrade to a "
                          "structured error reply", _line(text, node.lineno))


# --------------------------------------------------------------------------- #
# serving-contract rule: float64-promotion
# --------------------------------------------------------------------------- #

_F64 = {"np.float64", "numpy.float64", "jnp.float64", "jax.numpy.float64"}


@rule(
    "float64-promotion",
    doc="float64 spellings inside traced regions double memory traffic and fall "
        "off the fast path (the suite is float32 end-to-end)",
    scan=("src/repro/",),
)
def float64_promotion(rel: str, text: str, tree: ast.AST) -> Iterator[Finding]:
    par = _parents(tree)
    traced = traced_functions(tree, par)
    for node in ast.walk(tree):
        if not _in_traced(node, par, traced):
            continue
        if isinstance(node, (ast.Attribute, ast.Name)) and _dotted(node) in _F64:
            yield Finding("float64-promotion", rel, node.lineno,
                          "float64 dtype inside a traced region — the serving "
                          "contract is float32 end-to-end", _line(text, node.lineno))
        elif isinstance(node, ast.Call):
            # x.astype(float) / jnp.asarray(x, dtype=float): weak float64
            args = list(node.args) + [kw.value for kw in node.keywords
                                      if kw.arg in ("dtype", None)]
            if (isinstance(node.func, ast.Attribute) and node.func.attr == "astype") or (
                _dotted(node.func) in ("jnp.asarray", "jnp.array")
            ):
                for a in args:
                    if isinstance(a, ast.Name) and a.id == "float":
                        yield Finding("float64-promotion", rel, node.lineno,
                                      "bare `float` dtype promotes to float64 under "
                                      "x64 — spell jnp.float32",
                                      _line(text, node.lineno))


# --------------------------------------------------------------------------- #
# serving-contract rule: fork-unsafe
# --------------------------------------------------------------------------- #

_FORK_CALLS = {"os.fork", "os.forkpty"}
_MP_FACTORIES = {"multiprocessing.Process", "multiprocessing.Pool",
                 "mp.Process", "mp.Pool"}
_CTX_CALLS = {"get_context", "set_start_method"}


@rule(
    "fork-unsafe",
    doc="os.fork / fork-start multiprocessing deadlock an imported JAX runtime "
        "(its internal thread pools don't survive fork) — spawn worker "
        "processes via subprocess or an explicit 'spawn' context",
    scan=("src/",),
)
def fork_unsafe(rel: str, text: str, tree: ast.AST) -> Iterator[Finding]:
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if name in _FORK_CALLS:
            yield Finding("fork-unsafe", rel, node.lineno,
                          f"{name}() forks the process — a forked JAX runtime "
                          "deadlocks on its thread pools; spawn a fresh "
                          "interpreter (subprocess / 'spawn' context) instead",
                          _line(text, node.lineno))
        elif name in _MP_FACTORIES:
            # bare Process()/Pool() default to fork on Linux; a spawn-context
            # handle (ctx.Process where ctx = get_context("spawn")) resolves
            # to a different dotted name and passes
            yield Finding("fork-unsafe", rel, node.lineno,
                          f"{name}(...) uses the platform default start method "
                          "(fork on Linux) — JAX is already initialized here; "
                          "use subprocess or get_context('spawn')",
                          _line(text, node.lineno))
        elif (
            name is not None
            and name.split(".")[-1] in _CTX_CALLS
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and node.args[0].value == "fork"
        ):
            yield Finding("fork-unsafe", rel, node.lineno,
                          "explicit 'fork' start method — a forked JAX runtime "
                          "deadlocks; request 'spawn'",
                          _line(text, node.lineno))
