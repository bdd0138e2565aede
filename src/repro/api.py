"""The DRAGON front door: one typed façade over DGen, DSim and DOpt.

The suite's engines are free functions over raw pytrees — right for
composing JAX programs, wrong as a public surface: every caller re-implements
the same specialize → stack → simulate → optimize plumbing and pays compile
time on every query.  This module is the served API instead:

    from repro import Session, Architecture, Workload

    sess = Session(Architecture("edge"))            # .dhd text, library name,
    rep = sess.simulate(Workload("bert_base"))      #   or raw pytrees
    print(rep)                                      # explainable SimReport
    opt = sess.optimize("bert_base", objective="edp", steps=40)
    front = sess.frontier(["lstm", "bert_base"], population=12)

Three types:

  * :class:`Workload` — a validated workload set.  Wraps one Graph, a list,
    or workload names; stacks them (``Graph.stack``) with the vertex axis
    padded to a shape *bucket* (next power of two, min 32) so different
    workload sets of similar size land on the same compiled program.
    Padding is exact — the mapper prices no-op vertices at zero.
  * :class:`Architecture` — a validated design point: ``.dhd`` text, a
    library name, a ``CompiledArch``, or raw ``(tech, arch, spec)`` pytrees
    — one constructor, ``CompiledArch`` underneath, ``to_dhd()`` back out.
  * :class:`Session` — owns the compiled-program cache and routes
    ``simulate()`` / ``optimize()`` / ``frontier()`` / ``explain()`` to the
    dsim / dopt / popsim / pareto engines, returning the frozen result
    objects from :mod:`repro.core.report`.

Cache-key semantics (the serving contract)
------------------------------------------

Programs are keyed by ``(kind, ArchSpec, MapperCfg, shape bucket,
objective signature)``:

  * **ArchSpec / MapperCfg** are static configuration — they change the
    traced program, so they key it;
  * **shape bucket** is ``(n_workloads, padded_vertex_count)`` from
    :attr:`Workload.bucket` — any workload set in the same bucket replays
    the same executable;
  * **objective signature** is the objective *name* only.  Objective
    weights, budgets and penalty weights are *traced* arguments (PR 4), so
    a changed mix reuses the program; technology/architecture parameter
    values are traced too, so a changed design point never retraces.

Repeated calls — the serving pattern — therefore never retrace and never
recompile; :attr:`Session.stats` reports programs/hits/misses/traces, and
the trace counts are asserted (not assumed) via
:mod:`repro.core.instrument`.

The same keys address the *persistent* executable cache:
``Session(cache_dir=...)`` loads serialized executables written by
:meth:`Session.preheat` (AOT ``jax.jit(...).lower().compile()``), so a
restarted process answers its first query with zero traces and replies
bit-identical to a fresh compile — see :mod:`repro.serving.aotcache` for
the digest/versioning/quarantine story and ``docs/api.md`` for the
operator view.

The engine layer (``repro.core.simulate`` / ``optimize`` / ``pareto_dse``
...) keeps working as-is for one more release: it is the numerical oracle
the façade is tested identical against.  New code — and everything under
``examples/``, ``benchmarks/``, ``tools/`` (lint-enforced by
``tools/check_api_surface.py``) — should use the façade.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dgen as _dgen
from repro.core import dopt as _dopt
from repro.core import instrument
from repro.core import popsim as _popsim
from repro.core.dhdl import CompiledArch, load_arch, parse_arch, serialize_arch
from repro.core.dopt import from_log, tech_param_names, to_log
from repro.core.dsim import (
    PARETO_METRICS,
    PerfEstimate,
    simulate_breakdown,
    simulate_stacked,
    stacked_log_objective,
)
from repro.core.graph import Graph
from repro.core.mapper import MapperCfg
from repro.core.params import COMP_CLS, MEM_CLS, ArchParams, ArchSpec, TechParams
from repro.core.report import (
    Attribution,
    ComputeClassReport,
    FrontierPoint,
    FrontierResult,
    MemoryLevelReport,
    OptResult,
    SimReport,
    VertexReport,
    WorkloadReport,
)
from repro.workloads import get_workload

__all__ = [
    "Workload",
    "Architecture",
    "Session",
    "CacheStats",
    # result objects (re-exported from core.report)
    "SimReport",
    "OptResult",
    "FrontierResult",
    "Attribution",
    # engine types call sites legitimately need alongside the façade
    "Graph",
    "MapperCfg",
    "ArchParams",
    "ArchSpec",
    "TechParams",
    "PerfEstimate",
    "PARETO_METRICS",
    "get_workload",
]

_MIN_BUCKET = 32  # below this the mapper's auto dispatch flips impls; also
# keeps tiny-workload buckets from fragmenting the program cache

_MIN_REQUEST_BUCKET = 2  # batched dispatches pad the request axis to pow2;
# below 2 the sequential program is already the right shape


def _bucket_vertices(v: int) -> int:
    """Vertex-axis bucket: next power of two, at least ``_MIN_BUCKET``."""
    return max(_MIN_BUCKET, 1 << (max(v, 1) - 1).bit_length())


def _bucket_requests(n: int) -> int:
    """Request-axis bucket for batched dispatches: next power of two, at
    least ``_MIN_REQUEST_BUCKET`` — same convention as the vertex axis, so
    warm batches of similar size replay one compiled program."""
    return max(_MIN_REQUEST_BUCKET, 1 << (max(n, 1) - 1).bit_length())


def _dhd_ident(name: str) -> str:
    """Sanitize a display name into a ``.dhd`` identifier, so every
    Architecture serializes to parseable text."""
    import re

    ident = re.sub(r"[^A-Za-z0-9_]", "_", name) or "anonymous"
    return ident if ident[0].isalpha() or ident[0] == "_" else f"_{ident}"


def _check_finite_positive(tree, what: str) -> None:
    for leaf in jax.tree.leaves(tree):
        a = np.asarray(leaf)
        if not np.all(np.isfinite(a)):
            raise ValueError(f"{what} contains non-finite values")
        if np.any(a <= 0):
            raise ValueError(f"{what} contains non-positive values (parameters are positive)")


# --------------------------------------------------------------------------- #
# Workload
# --------------------------------------------------------------------------- #


class Workload:
    """A validated, shape-bucketed workload set.

    ``source`` may be a workload name (resolved via
    ``repro.workloads.get_workload``), a :class:`Graph`, another
    ``Workload``, or a list mixing names and Graphs.  The set stacks into
    one ``[W, V_bucket, ...]`` Graph (:attr:`stacked`) with vertex padding
    to the shape bucket and the static per-vertex names stripped, so any
    same-bucket set is *structurally identical* to jit — that is what lets
    a :class:`Session` serve different workloads from one compiled program.

    Construct once and reuse in hot loops: stacking is host work.
    """

    def __init__(self, source, *, labels: tuple[str, ...] | None = None):
        graphs, auto_labels = self._resolve(source)
        if not graphs:
            raise ValueError("Workload needs at least one graph")
        for lbl, g in zip(auto_labels, graphs):
            if not isinstance(g, Graph):
                raise TypeError(f"workload {lbl!r} is not a Graph (got {type(g).__name__})")
            if g.n_vertices < 1:
                raise ValueError(f"workload {lbl!r} has no vertices")
            if g.n_comp.ndim != 2:
                raise ValueError(
                    f"workload {lbl!r} is already stacked ([W,V,...]); pass its member graphs"
                )
            for field in ("n_comp", "n_read", "n_write", "n_alloc"):
                a = np.asarray(getattr(g, field))
                if not np.all(np.isfinite(a)) or np.any(a < 0):
                    raise ValueError(f"workload {lbl!r}.{field} must be finite and >= 0")
        self.graphs: tuple[Graph, ...] = tuple(graphs)
        self.labels: tuple[str, ...] = tuple(labels) if labels is not None else tuple(auto_labels)
        if len(self.labels) != len(self.graphs):
            raise ValueError(f"{len(self.labels)} labels for {len(self.graphs)} graphs")
        vmax = max(g.n_vertices for g in self.graphs)
        self._bucket = (len(self.graphs), _bucket_vertices(vmax))
        self._vertices = sum(g.n_vertices for g in self.graphs)
        self._stacked: Graph | None = None

    @staticmethod
    def _resolve(source) -> tuple[list[Graph], list[str]]:
        if isinstance(source, Workload):
            return list(source.graphs), list(source.labels)
        if isinstance(source, (str, Graph)):
            source = [source]
        graphs, labels = [], []
        for i, item in enumerate(source):
            if isinstance(item, str):
                graphs.append(get_workload(item))
                labels.append(item)
            elif isinstance(item, Graph):
                graphs.append(item)
                labels.append(f"workload{i}")
            else:
                raise TypeError(f"cannot build a Workload from {type(item).__name__}")
        return graphs, labels

    @property
    def bucket(self) -> tuple[int, int]:
        """``(n_workloads, padded_vertex_count)`` — the cache-key shape."""
        return self._bucket

    @property
    def n_workloads(self) -> int:
        return len(self.graphs)

    @property
    def vertices(self) -> int:
        """Real vertices over the set's graphs, before bucket padding."""
        return self._vertices

    @property
    def stacked(self) -> Graph:
        """The bucket-padded ``[W, V_bucket, ...]`` stack, names stripped."""
        if self._stacked is None:
            _, vb = self._bucket
            gs = Graph.stack([g.pad_to(vb) for g in self.graphs])
            self._stacked = dataclasses.replace(gs, names=())
        return self._stacked

    def __repr__(self) -> str:
        w, v = self._bucket
        return f"Workload({list(self.labels)!r}, bucket=[{w}, {v}])"


# --------------------------------------------------------------------------- #
# Architecture
# --------------------------------------------------------------------------- #


class Architecture:
    """A validated design point — one constructor for every spelling.

    ``Architecture("edge")`` loads the named ``.dhd`` library design;
    ``Architecture("arch mine inherits edge { ... }")`` parses text (any
    source containing ``{`` is treated as text); ``Architecture(ca)`` wraps
    an existing :class:`CompiledArch`; ``Architecture(tech=..., arch=...,
    spec=...)`` builds one from raw pytrees (defaults fill the gaps).
    ``to_dhd()`` serializes back to canonical text — the suite's
    interchange format (parse → serialize → parse is the identity).  Names
    are sanitized to ``.dhd`` identifiers (``[A-Za-z_][A-Za-z0-9_]*``) so
    every Architecture's text form is guaranteed parseable.
    """

    def __init__(
        self,
        source: "str | CompiledArch | Architecture | None" = None,
        *,
        tech: TechParams | None = None,
        arch: ArchParams | None = None,
        spec: ArchSpec | None = None,
        name: str | None = None,
    ):
        if isinstance(source, Architecture):
            ca = source._ca
        elif isinstance(source, CompiledArch):
            ca = source
        elif isinstance(source, str):
            ca = parse_arch(source) if "{" in source else load_arch(source)
        elif source is None:
            ca = CompiledArch(
                name=name or "custom",
                spec=spec if spec is not None else ArchSpec(),
                arch=arch if arch is not None else ArchParams.default(),
                tech=tech if tech is not None else TechParams.default(),
            )
        else:
            raise TypeError(f"cannot build an Architecture from {type(source).__name__}")
        if source is not None and (tech is not None or arch is not None or spec is not None):
            ca = CompiledArch(
                name=name or ca.name,
                spec=spec if spec is not None else ca.spec,
                arch=arch if arch is not None else ca.arch,
                tech=tech if tech is not None else ca.tech,
            )
        elif name is not None and name != ca.name:
            ca = CompiledArch(name=name, spec=ca.spec, arch=ca.arch, tech=ca.tech)
        ident = _dhd_ident(ca.name)
        if ident != ca.name:
            ca = CompiledArch(name=ident, spec=ca.spec, arch=ca.arch, tech=ca.tech)
        _check_finite_positive(ca.tech, f"Architecture {ca.name!r} tech params")
        _check_finite_positive(ca.arch, f"Architecture {ca.name!r} arch params")
        self._ca = ca

    @property
    def name(self) -> str:
        return self._ca.name

    @property
    def spec(self) -> ArchSpec:
        return self._ca.spec

    @property
    def arch(self) -> ArchParams:
        return self._ca.arch

    @property
    def tech(self) -> TechParams:
        return self._ca.tech

    @property
    def compiled(self) -> CompiledArch:
        return self._ca

    def to_dhd(self) -> str:
        """Canonical ``.dhd`` text of this design (round-trips bit-exactly)."""
        return serialize_arch(name=self.name, spec=self.spec, arch=self.arch, tech=self.tech)

    def peaks(self) -> dict:
        """Machine peaks of this design point — the roofline axes.

        Evaluates the hardware model (DGen ``specialize``) and returns
        ``peak_flops`` (FLOP/s summed over enabled compute classes at the
        timing-feasible clock), ``mem_bw`` (bytes/s per memory level, keyed
        by :data:`MEM_CLS` name) and ``frequency`` (Hz).  Host floats — this
        is reporting surface, not a traced program.
        """
        chw = _dgen.specialize(self.tech, self.arch, self.spec)
        freq = float(np.asarray(chw.frequency))
        bw = np.asarray(chw.mem_bw)
        return {
            "peak_flops": float(np.sum(np.asarray(chw.flops_per_cycle))) * freq,
            "mem_bw": {lvl: float(bw[i]) for i, lvl in enumerate(MEM_CLS)},
            "frequency": freq,
        }

    def __repr__(self) -> str:
        return f"Architecture({self.name!r})"


# --------------------------------------------------------------------------- #
# Session
# --------------------------------------------------------------------------- #


@dataclass(frozen=True)
class CacheStats:
    """Program-cache bookkeeping: ``traces`` counts actual compilations of
    this session's programs (via the trace-side-effect probe); ``hits`` /
    ``misses`` count cache-key lookups."""

    programs: int
    hits: int
    misses: int
    traces: int


_ARCH_PARAM_NAMES: list[str] | None = None


def _arch_param_names() -> list[str]:
    # memoized: building ArchParams.default() materializes device arrays,
    # ~15 of them — at ~1 ms a pop that was most of a warm explain() call
    global _ARCH_PARAM_NAMES
    if _ARCH_PARAM_NAMES is None:
        default = ArchParams.default()
        names = []
        for f in dataclasses.fields(ArchParams):
            n = np.asarray(getattr(default, f.name)).size
            if n == 1:
                names.append(f.name)
            else:
                names.extend(f"{cls}.{f.name}" for cls in MEM_CLS[:n])
        _ARCH_PARAM_NAMES = names
    return _ARCH_PARAM_NAMES


_ELASTICITY_NAMES: tuple | None = None


def _attributed(rep: SimReport, objective: str, elast: np.ndarray) -> SimReport:
    """``rep`` with every technology and architecture parameter ranked by
    |elasticity| (``elast`` in tech-then-arch order)."""
    global _ELASTICITY_NAMES
    if _ELASTICITY_NAMES is None:
        _ELASTICITY_NAMES = tuple(f"tech.{n}" for n in tech_param_names()) + tuple(
            f"arch.{n}" for n in _arch_param_names()
        )
    ranked = sorted(zip(_ELASTICITY_NAMES, elast.tolist()), key=lambda kv: -abs(kv[1]))
    attribution = tuple(Attribution(parameter=n, elasticity=float(v)) for n, v in ranked)
    return dataclasses.replace(rep, objective=objective, attribution=attribution)


# --------------------------------------------------------------------------- #
# packed program outputs
# --------------------------------------------------------------------------- #
# Every served report and explain program returns its outputs packed: each
# leaf raveled and concatenated into one flat buffer per dtype, per request
# lane (batched programs return ``[nb, L]`` buffers, a lane per row).  The
# host then fetches a dispatch's results in one device-to-host transfer per
# dtype instead of one per leaf, and unpacks them as numpy views.
#
# Report programs also keep each leaf as an output of its own, never
# fetched: where the concatenate is a leaf's only consumer, XLA fuses the
# leaf's producers differently and a report moves by an ulp (seen on the
# CPU); as outputs the leaves keep the unpacked program's bits, and the
# packed copy holds them.  Explain programs pack with no such effect.


@dataclass(frozen=True)
class _Packing:
    """Where each leaf of one lane's output tree lies in its packed
    buffers: ``slots[i]`` is leaf ``i``'s ``(buffer, start, stop, shape)``,
    buffers numbered by each dtype's first appearance in leaf order."""

    treedef: object
    slots: tuple

    @staticmethod
    def of(tree) -> "_Packing":
        """The layout of ``tree``, whose leaves may be arrays, tracers or
        ``jax.ShapeDtypeStruct``\\ s (``jax.eval_shape`` output)."""
        leaves, treedef = jax.tree.flatten(tree)
        buffer_of: dict = {}
        ends: list[int] = []
        slots = []
        for leaf in leaves:
            b = buffer_of.setdefault(np.dtype(leaf.dtype), len(buffer_of))
            if b == len(ends):
                ends.append(0)
            start = ends[b]
            ends[b] += math.prod(leaf.shape)
            slots.append((b, start, ends[b], tuple(leaf.shape)))
        return _Packing(treedef, tuple(slots))

    def unpack(self, bufs):
        """The output tree of one lane, as numpy views into its host
        buffers (no copy)."""
        return jax.tree.unflatten(
            self.treedef, [bufs[b][s:e].reshape(shape) for b, s, e, shape in self.slots]
        )


def _pack(tree) -> tuple:
    """``tree``'s leaves raveled and concatenated in leaf order, one buffer
    per dtype, never cast — traced inside a served program, per lane."""
    leaves = jax.tree.leaves(tree)
    slots = _Packing.of(tree).slots
    n = 1 + max(b for b, *_ in slots)
    return tuple(
        jnp.concatenate([jnp.ravel(x) for x, (b, *_) in zip(leaves, slots) if b == i])
        for i in range(n)
    )


def _fetch(out) -> list:
    """A packed program's buffers on the host: one device-to-host transfer
    per buffer, recorded as the span's ``arrays``."""
    with instrument.span("dragon.session.fetch", arrays=len(out)):
        return [np.asarray(b) for b in out]


def _report_lane(tech, arch, gstack, spec: ArchSpec, mcfg: MapperCfg):
    """One request's report outputs, ``(PerfEstimate, extras)`` with a
    leading ``[W]`` axis: simulate_breakdown computes both in one pass."""
    return jax.vmap(lambda g: simulate_breakdown(tech, arch, g, spec, mcfg))(gstack)


def _report_packed(tech, arch, gstack, spec: ArchSpec, mcfg: MapperCfg):
    """A report program's lane: the packed buffers, then the leaves they
    copy (outputs only to keep their bits; the host fetches the buffers)."""
    out = _report_lane(tech, arch, gstack, spec, mcfg)
    return _pack(out), out


def _explain_lane(tech, arch, gstack, spec: ArchSpec, mcfg: MapperCfg, objective: str):
    """One request's ``(g_tech, g_arch)``: d log(objective) / d log(param)."""

    def loss(tz, az):
        val, _ = stacked_log_objective(
            from_log(tz), from_log(az), gstack, objective, spec=spec, mcfg=mcfg
        )
        return val

    return jax.grad(loss, argnums=(0, 1))(to_log(tech), to_log(arch))


_REPORT_PACKINGS: dict = {}  # report program key -> its lane's _Packing


def _report_arrays(packing: _Packing, bufs) -> dict:
    """The fields a :class:`SimReport` reads: one lane of a packed report
    program's host buffers, unpacked by ``packing`` into numpy views."""
    perfs, extras = packing.unpack(bufs)
    state = perfs.state
    return dict(
        reads=state.reads,
        writes=state.writes,
        comp_ops=state.comp_ops,
        bw_util=state.bw_util,
        extras=extras,
        runtime=perfs.runtime,
        energy=perfs.energy,
        power=perfs.power,
        edp=perfs.edp,
        cycles=perfs.cycles,
        energy_mem=perfs.energy_mem,
        energy_comp=perfs.energy_comp,
        energy_leak=perfs.energy_leak,
        area=perfs.area,
    )


class Session:
    """The suite front door: simulate / optimize / frontier / explain
    against one architecture, with compiled programs cached across calls.

    ``architecture`` accepts anything :class:`Architecture` accepts (and
    defaults to the library ``base`` design); per-call ``architecture=``
    overrides never invalidate the cache — parameter values are traced
    arguments, only a changed :class:`ArchSpec` keys a new program.

    ``programs`` shares a compiled-program cache between sessions: pass
    another session's :attr:`programs` (or a plain dict) and every program
    one session compiles is warm for the others — the multi-tenant serving
    arrangement, where N tenants must not mean N copies of every
    executable.  Hit/miss/trace *stats* stay per-session (a shared program
    counts as a hit for the session that finds it and traces only under
    the session that built it).

    ``cache_dir`` makes the cache *persistent*: executables built by
    :meth:`preheat` are serialized to disk
    (:class:`repro.serving.aotcache.AotCache`), and construction loads
    every entry matching this runtime back into :attr:`programs` — a
    restarted process serves its first query with zero traces
    (:attr:`disk_loaded` reports how many programs arrived that way).
    """

    _ids = itertools.count()

    def __init__(self, architecture="base", *, mcfg: MapperCfg = MapperCfg(),
                 programs: dict | None = None, cache_dir=None):
        self.architecture = Architecture(architecture)
        self.mcfg = mcfg
        self._tag = f"api.session{next(Session._ids)}"
        # key -> compiled callable; shared across sessions when passed in
        self._programs: dict = programs if programs is not None else {}
        self._engine_keys: set = set()  # engine-routed configs seen (bookkeeping)
        self._hits = 0
        self._misses = 0
        self._workload_memo: dict[str, Workload] = {}
        self._arch_memo: dict[str, Architecture] = {}
        # the pooled serving tier dispatches chunks from worker threads that
        # share one session; cache lookups and build bookkeeping stay atomic
        self._plock = threading.RLock()
        self._aot = None
        self.disk_loaded = 0  # programs rehydrated from cache_dir at construction
        instrument.install_gc_spans()
        if cache_dir is not None:
            # deferred: the serving package (and its fault taxonomy) only
            # loads for sessions that opt into persistence
            from repro.serving.aotcache import AotCache

            self._aot = AotCache(cache_dir)
            for key, fn in self._aot.load_all().items():
                if key not in self._programs:
                    self._programs[key] = fn
                    self.disk_loaded += 1

    @property
    def programs(self) -> dict:
        """The compiled-program cache — pass to another ``Session`` to share."""
        return self._programs

    # ------------------------------------------------------------- helpers --
    def _arch(self, architecture) -> Architecture:
        if architecture is None:
            return self.architecture
        if isinstance(architecture, Architecture):
            return architecture
        if isinstance(architecture, str):
            # memoized like workloads: re-parsing a .dhd and materializing
            # its params costs ~ms — far more than a warm dispatch
            a = self._arch_memo.get(architecture)
            if a is None:
                a = self._arch_memo[architecture] = Architecture(architecture)
            return a
        return Architecture(architecture)

    def _workload(self, workload) -> Workload:
        if isinstance(workload, Workload):
            return workload
        if isinstance(workload, str):
            if workload not in self._workload_memo:
                self._workload_memo[workload] = Workload(workload)
            return self._workload_memo[workload]
        return Workload(workload)

    def _program(self, key: tuple, build):
        """The compiled-program cache: ``key`` -> jitted callable.

        Misses consult the persistent cache first (an entry another worker
        preheated after this session started is still a disk hit); only a
        full miss pays ``build()`` — a jit wrapper that traces on first
        call.  Thread-safe: concurrent pool workers racing the same key get
        one build and consistent hit/miss counts.
        """
        with self._plock:
            fn = self._programs.get(key)
            if fn is None and self._aot is not None:
                fn = self._aot.get(key)
                if fn is not None:
                    self._programs[key] = fn
            if fn is None:
                self._misses += 1
                fn = self._programs[key] = build()
            else:
                self._hits += 1
            return fn

    def _engine_call(self, key: tuple) -> None:
        """Bookkeeping for calls whose program lives in the *engine's* jit
        cache (optimize/frontier): hit/miss counts key recurrence; their
        retraces show up in the engine's global probe tags
        (``dopt._dopt_step`` / ``popsim._member_step``), not in
        ``stats.traces``."""
        if key in self._engine_keys:
            self._hits += 1
        else:
            self._misses += 1
            self._engine_keys.add(key)

    @property
    def stats(self) -> CacheStats:
        # trailing "." so session1 never sums session10's counters
        return CacheStats(
            programs=len(self._programs),
            hits=self._hits,
            misses=self._misses,
            traces=instrument.trace_count(prefix=f"{self._tag}."),
        )

    # ------------------------------------------------------------ programs --
    # Each served program kind is declared as a *spec* — ``(cache key,
    # build)`` where ``build()`` returns the jit wrapper — so the lazy
    # first-call path (``_program``) and the AOT path (``preheat``, which
    # wants ``build().lower(...).compile()`` instead) share one definition.

    def _perf_spec(self, bucket, spec: ArchSpec, mcfg: MapperCfg):
        """jit(simulate_stacked) — byte-identical to the engine call it wraps."""
        tag = f"{self._tag}.simulate"

        def build():
            def fn(tech, arch, gstack):
                instrument.count_trace(tag)
                return simulate_stacked(tech, arch, gstack, spec, mcfg)

            return jax.jit(fn)

        return ("simulate", spec, mcfg, bucket), build

    def _perf_program(self, bucket, spec: ArchSpec, mcfg: MapperCfg):
        return self._program(*self._perf_spec(bucket, spec, mcfg))

    def _report_spec(self, bucket, spec: ArchSpec, mcfg: MapperCfg):
        """One program for the whole report: batched PerfEstimate + the
        per-vertex / per-level breakdown extras (simulate_breakdown computes
        both in one pass, so reports cost one compile and one dispatch),
        packed so the host fetches them in one transfer per dtype."""
        tag = f"{self._tag}.report"

        def build():
            def fn(tech, arch, gstack):
                instrument.count_trace(tag)
                return _report_packed(tech, arch, gstack, spec, mcfg)

            return jax.jit(fn)

        return ("report", spec, mcfg, bucket), build

    def _report_program(self, bucket, spec: ArchSpec, mcfg: MapperCfg):
        return self._program(*self._report_spec(bucket, spec, mcfg))

    def _report_packing(self, w: Workload, a: Architecture, out, lead: int) -> _Packing:
        """The lane layout of the report programs for ``w``'s bucket and
        ``a``'s spec, read once per report program key from the shapes of
        the leaves a report program ``out`` returns beside its buffers, less
        ``lead`` request axes: no transfer and no second trace, and the
        batched variants share it (each of their lanes is that report)."""
        key = ("report", a.spec, self.mcfg, w.bucket)
        packing = _REPORT_PACKINGS.get(key)
        if packing is None:
            lane = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[lead:], x.dtype), out[1])
            packing = _REPORT_PACKINGS.setdefault(key, _Packing.of(lane))
        return packing

    def _explain_spec(self, bucket, spec: ArchSpec, mcfg: MapperCfg, objective: str):
        """Elasticities d log(objective) / d log(param) for tech AND arch,
        packed: one vector, ``g_tech`` then ``g_arch`` in leaf order."""
        tag = f"{self._tag}.explain"

        def build():
            def fn(tech, arch, gstack):
                instrument.count_trace(tag)
                return _pack(_explain_lane(tech, arch, gstack, spec, mcfg, objective))

            return jax.jit(fn)

        return ("explain", spec, mcfg, bucket, objective), build

    def _explain_program(self, bucket, spec: ArchSpec, mcfg: MapperCfg, objective: str):
        return self._program(*self._explain_spec(bucket, spec, mcfg, objective))

    # ----------------------------------------------------- batched programs --
    def _batched_report_spec(self, nb: int, bucket, spec: ArchSpec, mcfg: MapperCfg):
        """The report program with a leading *request* axis: one dispatch
        answers ``nb`` same-bucket queries, each with its own (tech, arch,
        gstack), its packed buffers ``[nb, L]``, a lane per row.  Keyed by
        the request bucket too, so warm batches of similar size never
        retrace."""
        tag = f"{self._tag}.report_batched"

        def build():
            def fn(techs, archs, gstacks):
                instrument.count_trace(tag)
                return jax.vmap(lambda t, a, g: _report_packed(t, a, g, spec, mcfg))(
                    techs, archs, gstacks
                )

            return jax.jit(fn)

        return ("report_batched", spec, mcfg, bucket, nb), build

    def _batched_report_program(self, nb: int, bucket, spec: ArchSpec, mcfg: MapperCfg):
        return self._program(*self._batched_report_spec(nb, bucket, spec, mcfg))

    def _batched_explain_spec(
        self, nb: int, bucket, spec: ArchSpec, mcfg: MapperCfg, objective: str
    ):
        """Elasticities with a leading request axis (vmapped grad): one
        ``[nb, L]`` buffer, a lane's vector per row."""
        tag = f"{self._tag}.explain_batched"

        def build():
            def fn(techs, archs, gstacks):
                instrument.count_trace(tag)
                return jax.vmap(
                    lambda t, a, g: _pack(_explain_lane(t, a, g, spec, mcfg, objective))
                )(techs, archs, gstacks)

            return jax.jit(fn)

        return ("explain_batched", spec, mcfg, bucket, objective, nb), build

    def _batched_explain_program(
        self, nb: int, bucket, spec: ArchSpec, mcfg: MapperCfg, objective: str
    ):
        return self._program(
            *self._batched_explain_spec(nb, bucket, spec, mcfg, objective)
        )

    # ------------------------------------------------------------- preheat --
    def _bucket_stack(self, item) -> tuple[tuple[int, int], Graph]:
        """Resolve a preheat target into ``(bucket, example stack)``.

        Accepts anything :class:`Workload` accepts *or* a bare
        ``(n_workloads, vertex_count)`` bucket tuple, for which a zero-filled
        stack of that shape is synthesized — compilation depends on array
        shapes/dtypes only, so the dummy program serves real same-bucket
        workloads bit-identically.
        """
        if (
            isinstance(item, tuple)
            and len(item) == 2
            and all(isinstance(x, (int, np.integer)) for x in item)
        ):
            w, vb = int(item[0]), _bucket_vertices(int(item[1]))
            stack = Graph(
                n_comp=jnp.zeros((w, vb, len(COMP_CLS)), jnp.float32),
                n_read=jnp.zeros((w, vb, len(MEM_CLS)), jnp.float32),
                n_write=jnp.zeros((w, vb, len(MEM_CLS)), jnp.float32),
                n_alloc=jnp.zeros((w, vb, len(MEM_CLS)), jnp.float32),
                dims=jnp.zeros((w, vb, 3), jnp.float32),
                op_kind=jnp.zeros((w, vb), jnp.int32),
                edges=jnp.zeros((w, 0, 2), jnp.int32),
                names=(),
            )
            return (w, vb), stack
        wl = self._workload(item)
        return wl.bucket, wl.stacked

    def _preheat_one(self, key, build, args) -> tuple[bool, bool]:
        """Ensure one program is compiled (AOT) and persisted.

        Returns ``(built, persisted)``.  An existing in-memory or on-disk
        program is reused; otherwise the program is built ahead of time via
        ``build().lower(*args).compile()`` — the same trace a first call
        would pay, paid now, yielding a serializable executable.
        """
        fn = self._programs.get(key)
        if fn is None and self._aot is not None:
            fn = self._aot.get(key)
            if fn is not None:
                self._programs[key] = fn
        built = False
        if fn is None:
            self._misses += 1
            fn = self._programs[key] = build().lower(*args).compile()
            built = True
        else:
            self._hits += 1
        persisted = False
        if self._aot is not None and not self._aot.has(key):
            target = fn
            if not isinstance(fn, jax.stages.Compiled):
                # snapshot path: the program was first compiled lazily (a
                # jit wrapper, not serializable) — AOT-compile an equivalent
                # executable for the disk entry; the in-memory one stays
                target = build().lower(*args).compile()
            persisted = self._aot.put(key, target)
        return built, persisted

    def preheat(
        self,
        workloads,
        *,
        objectives: tuple[str, ...] = ("edp",),
        kinds: tuple[str, ...] = ("simulate", "explain"),
        request_buckets: tuple[int, ...] = (),
        architecture=None,
    ) -> dict:
        """Compile the declared working set ahead of time — no first-call
        trace latency, and (with ``cache_dir``) no recompiles after restart.

        ``workloads`` is one item or a list: anything :meth:`simulate`
        accepts, or bare ``(n_workloads, vertex_count)`` bucket tuples when
        the real graphs don't exist yet (shapes are all compilation needs).
        ``kinds`` selects program families — ``"simulate"`` (the report
        program behind :meth:`simulate`), ``"explain"`` (adds the gradient
        program per objective), ``"perf"`` (the raw :meth:`perf` program).
        ``request_buckets`` additionally builds the batched-dispatch
        variants at those pinned request axes (pass the serving layer's
        ``request_bucket`` — ``DesignService.warmup`` does).

        Programs land in :attr:`programs` as AOT executables and, when the
        session has a ``cache_dir``, are serialized to disk.  Returns a
        summary dict: ``programs`` touched, ``built`` (compiled now),
        ``reused`` (already warm), ``persisted`` (new disk entries),
        ``seconds``.
        """
        a = self._arch(architecture)
        spec, mcfg = a.spec, self.mcfg
        if isinstance(workloads, (str, Graph, Workload)) or (
            isinstance(workloads, tuple)
            and len(workloads) == 2
            and all(isinstance(x, (int, np.integer)) for x in workloads)
        ):
            workloads = [workloads]
        kinds = tuple(kinds)
        unknown = set(kinds) - {"perf", "simulate", "explain"}
        if unknown:
            raise ValueError(
                f"preheat kinds {sorted(unknown)} not in ('perf', 'simulate', 'explain')"
            )
        t0 = time.perf_counter()
        built = reused = persisted = 0
        seen: set = set()
        for item in workloads:
            bucket, gstack = self._bucket_stack(item)
            if bucket in seen:
                continue
            seen.add(bucket)
            args = (a.tech, a.arch, gstack)
            jobs = []
            if "perf" in kinds:
                jobs.append((self._perf_spec(bucket, spec, mcfg), args))
            if "simulate" in kinds or "explain" in kinds:
                jobs.append((self._report_spec(bucket, spec, mcfg), args))
            if "explain" in kinds:
                for obj in objectives:
                    jobs.append((self._explain_spec(bucket, spec, mcfg, obj), args))
            for nb in request_buckets:
                nb = int(nb)
                bargs = jax.tree.map(lambda x: jnp.stack([x] * nb), args)
                if "simulate" in kinds or "explain" in kinds:
                    jobs.append((self._batched_report_spec(nb, bucket, spec, mcfg), bargs))
                if "explain" in kinds:
                    for obj in objectives:
                        jobs.append(
                            (self._batched_explain_spec(nb, bucket, spec, mcfg, obj), bargs)
                        )
            for (key, build), eargs in jobs:
                was_built, was_persisted = self._preheat_one(key, build, eargs)
                built += was_built
                reused += not was_built
                persisted += was_persisted
        return dict(
            programs=built + reused,
            built=built,
            reused=reused,
            persisted=persisted,
            seconds=round(time.perf_counter() - t0, 3),
        )

    def _batch_span_args(self, workloads) -> dict:
        """``n``, ``bucket`` and real ``vertices`` of a batched call, for its span."""
        if not workloads:
            return dict(n=0)
        ws = [self._workload(w) for w in workloads]
        return dict(n=len(ws), bucket=ws[0].bucket, vertices=sum(w.vertices for w in ws))

    def _assemble_batch(self, workloads, architectures, request_bucket=None):
        """Validate + stack a request batch: every item must share the
        session's spec and one shape bucket (that is what makes the stacks
        structurally identical under one program).  Returns
        ``(ws, archs, nb, stacked-pytrees)`` with the request axis padded to
        the pow2 bucket by repeating lane 0 (padding lanes are computed and
        discarded — same convention as vertex padding, minus the zero
        pricing, because discarding is exact).

        ``request_bucket`` pins the padded request axis instead of the
        auto pow2 bucket.  XLA specializes reduction order to array shape,
        so two *different* request buckets can differ in the last ulp;
        serving pins one bucket across sequential and coalesced dispatches
        precisely so replies are bit-identical however queries were
        batched."""
        ws = [self._workload(w) for w in workloads]
        if not ws:
            raise ValueError("batched call needs at least one workload")
        if architectures is None:
            archs = [self.architecture] * len(ws)
        else:
            archs = [self._arch(a) for a in architectures]
        if len(archs) != len(ws):
            raise ValueError(f"{len(archs)} architectures for {len(ws)} workloads")
        bucket, spec = ws[0].bucket, archs[0].spec
        for w in ws[1:]:
            if w.bucket != bucket:
                raise ValueError(
                    f"batched call mixes shape buckets {bucket} and {w.bucket}; "
                    "coalesce same-bucket queries only"
                )
        for a in archs[1:]:
            if a.spec != spec:
                raise ValueError("batched call mixes ArchSpecs; split by spec")
        if request_bucket is None:
            nb = _bucket_requests(len(ws))
        else:
            nb = int(request_bucket)
            if nb < len(ws):
                raise ValueError(
                    f"request_bucket={nb} smaller than the batch ({len(ws)} queries)"
                )
        pad = nb - len(ws)
        techs = jax.tree.map(
            lambda *xs: jnp.stack(xs + (xs[0],) * pad), *[a.tech for a in archs]
        )
        arch_ps = jax.tree.map(
            lambda *xs: jnp.stack(xs + (xs[0],) * pad), *[a.arch for a in archs]
        )
        gstacks = jax.tree.map(
            lambda *xs: jnp.stack(xs + (xs[0],) * pad), *[w.stacked for w in ws]
        )
        return ws, archs, nb, (techs, arch_ps, gstacks)

    def simulate_batch(
        self, workloads, *, architectures=None, request_bucket=None
    ) -> list[SimReport]:
        """Answer N same-bucket simulate queries in ONE vmapped dispatch.

        ``workloads`` is a list of anything :meth:`simulate` accepts;
        ``architectures`` (optional, same length) gives each request its own
        design point.  Every workload must share one shape bucket and every
        architecture the session's ``ArchSpec``.  Reports are bit-identical
        across batch compositions at one ``request_bucket`` — pinned by
        test — the batch only amortizes dispatch overhead across requests.
        """
        with instrument.span("dragon.session.simulate_batch", lambda: self._batch_span_args(workloads)):
            ws, archs, nb, stacked = self._assemble_batch(
                workloads, architectures, request_bucket
            )
            return self._simulate_batch_assembled(ws, archs, nb, stacked)

    def _simulate_batch_assembled(self, ws, archs, nb, stacked) -> list[SimReport]:
        prog = self._batched_report_program(nb, ws[0].bucket, archs[0].spec, self.mcfg)
        with instrument.span("dragon.session.launch", program="report_batched"):
            out = prog(*stacked)
        return self._reports_from_batch(ws, archs, out)

    def _reports_from_batch(self, ws, archs, out) -> list[SimReport]:
        """Finish a batched report dispatch: fetch the packed ``[nb, L]``
        buffers and build each lane's :class:`SimReport` from views of its
        row.  Shared by :meth:`simulate_batch` and the serving pool's
        staging-buffer dispatcher, so both paths build reports from
        identical bits."""
        packing = self._report_packing(ws[0], archs[0], out, lead=1)
        host = _fetch(out[0])
        with instrument.span("dragon.session.report", lanes=len(ws)):
            return [
                self._build_report(archs[i], ws[i], _report_arrays(packing, [h[i] for h in host]))
                for i in range(len(ws))
            ]

    def explain_batch(
        self, workloads, *, objective: str = "edp", architectures=None,
        request_bucket=None,
    ) -> list[SimReport]:
        """Batched :meth:`explain`: one vmapped report dispatch + one
        vmapped gradient dispatch answer N same-bucket explain queries.
        Reports (attribution included) are bit-identical across batch
        compositions at one ``request_bucket``."""
        with instrument.span("dragon.session.explain_batch", lambda: self._batch_span_args(workloads)):
            ws, archs, nb, stacked = self._assemble_batch(
                workloads, architectures, request_bucket
            )
            reports = self._simulate_batch_assembled(ws, archs, nb, stacked)
            prog = self._batched_explain_program(
                nb, ws[0].bucket, archs[0].spec, self.mcfg, objective
            )
            with instrument.span("dragon.session.launch", program="explain_batched"):
                out = prog(*stacked)
            return self._attribute_batch(reports, out, objective)

    def _attribute_batch(self, reports, out, objective) -> list[SimReport]:
        """Finish a batched explain dispatch: fetch the ``[nb, L]``
        elasticity buffer and rank each lane's row into its attribution.
        Shared by :meth:`explain_batch` and the serving pool's
        staging-buffer dispatcher."""
        (elast,) = _fetch(out)  # float32 parameters: one buffer
        with instrument.span("dragon.session.attribute", lanes=len(reports)):
            return [_attributed(rep, objective, elast[i]) for i, rep in enumerate(reports)]

    # ------------------------------------------------------------ simulate --
    def perf(self, workload, *, architecture=None) -> PerfEstimate:
        """Raw batched :class:`PerfEstimate` (device arrays, leading [W]
        axis) from the cached program — the zero-overhead serving path; use
        :meth:`simulate` for the explainable report."""
        w, a = self._workload(workload), self._arch(architecture)
        prog = self._perf_program(w.bucket, a.spec, self.mcfg)
        return prog(a.tech, a.arch, w.stacked)

    def simulate(self, workload, *, architecture=None) -> SimReport:
        """Simulate the workload set; returns a :class:`SimReport` with
        per-workload totals and per-memory-level / per-vertex breakdowns."""
        w, a = self._workload(workload), self._arch(architecture)
        with instrument.span("dragon.session.simulate", bucket=w.bucket, vertices=w.vertices, n=1):
            prog = self._report_program(w.bucket, a.spec, self.mcfg)
            with instrument.span("dragon.session.launch", program="report"):
                out = prog(a.tech, a.arch, w.stacked)
            host = _fetch(out[0])
            with instrument.span("dragon.session.report", lanes=1):
                packing = self._report_packing(w, a, out, lead=0)
                return self._build_report(a, w, _report_arrays(packing, host))

    def explain(self, workload, *, objective: str = "edp", architecture=None) -> SimReport:
        """:meth:`simulate` + gradient-based bottleneck attribution: every
        technology and architecture parameter ranked by its elasticity
        d log(objective) / d log(parameter) — DOpt's Table-3 signal, served
        as an explanation instead of a descent direction."""
        w, a = self._workload(workload), self._arch(architecture)
        with instrument.span("dragon.session.explain", bucket=w.bucket, vertices=w.vertices, n=1):
            rep = self.simulate(w, architecture=a)
            prog = self._explain_program(w.bucket, a.spec, self.mcfg, objective)
            with instrument.span("dragon.session.launch", program="explain"):
                out = prog(a.tech, a.arch, w.stacked)
            (elast,) = _fetch(out)  # float32 parameters: one buffer
            with instrument.span("dragon.session.attribute", lanes=1):
                return _attributed(rep, objective, elast)

    # ------------------------------------------------------------ optimize --
    def optimize(
        self,
        workload,
        *,
        objective: str = "edp",
        steps: int = 200,
        lr: float = 0.05,
        opt_over: str = "both",
        architecture=None,
        report: bool = True,
        **engine_kw,
    ) -> OptResult:
        """Gradient-descend the design for this workload set (DOpt).

        Routes to ``repro.core.optimize`` with the session's bucketed stack,
        so repeated calls with same-bucket workloads reuse the engine's
        fused-chunk program (the mix/budget arguments are traced — see
        module docstring).  ``engine_kw`` forwards the engine's knobs
        (``fused``, ``chunk``, ``target_factor``, ``objective_weights``,
        ``area_budget``, ``power_budget``, ``penalty_weight``, ...).

        ``report=False`` skips the baseline/optimized :class:`SimReport`
        pair (those fields come back ``None``) — the lean serving/benchmark
        mode where only the descent itself should be on the clock.
        """
        w, a = self._workload(workload), self._arch(architecture)
        with instrument.span("dragon.session.optimize", bucket=w.bucket, vertices=w.vertices, n=1):
            mcfg = engine_kw.pop("mcfg", self.mcfg)
            # everything static to the engine's fused-chunk program belongs in
            # the key: steps/target_factor/chunk set the scan length, and
            # fused/area_constraint are static argnames of _fused_chunk
            self._engine_call(
                ("optimize", a.spec, mcfg, w.bucket, objective, opt_over, steps,
                 engine_kw.get("fused", True), engine_kw.get("chunk"),
                 engine_kw.get("target_factor"), engine_kw.get("area_constraint"))
            )
            res = _dopt.optimize(
                w.stacked,
                tech=a.tech,
                arch=a.arch,
                spec=a.spec,
                objective=objective,
                opt_over=opt_over,
                steps=steps,
                lr=lr,
                mcfg=mcfg,
                **engine_kw,
            )
            opt_arch = Architecture(
                None, name=f"{a.name}_opt", tech=res.tech, arch=res.arch, spec=a.spec
            )
            hist = tuple(float(math.exp(v)) for v in res.history["objective"])
            improvement = hist[0] / max(hist[-1], 1e-300) if hist else 1.0
            return OptResult(
                objective=objective,
                opt_over=opt_over,
                epochs=len(hist),
                improvement=improvement,
                objective_history=hist,
                importance=tuple(
                    Attribution(parameter=f"tech.{n}", elasticity=v) for n, v in res.importance
                ),
                baseline=self.simulate(w, architecture=a) if report else None,
                optimized=self.simulate(w, architecture=opt_arch) if report else None,
                dhd=opt_arch.to_dhd(),
            )

    def tech_targets(self, workload, *, goal_factor: float = 100.0, **engine_kw) -> dict:
        """Technology targets for a ``goal_factor``x objective improvement
        (paper §8.3) — thin passthrough to ``repro.core.dopt.derive_tech_targets``
        on the session's bucketed stack."""
        w = self._workload(workload)
        return _dopt.derive_tech_targets(w.stacked, goal_factor=goal_factor, **engine_kw)

    # ------------------------------------------------------------ frontier --
    def frontier(
        self,
        workload,
        *,
        seeds: tuple[str, ...] = ("base", "edge", "datacenter"),
        population: int = 24,
        steps: int = 24,
        lr: float = 0.1,
        metrics: tuple[str, ...] = ("time", "energy", "area"),
        area_budget: float | None = None,
        power_budget: float | None = None,
        **engine_kw,
    ) -> FrontierResult:
        """Population-scale constrained multi-objective DSE: the feasible
        latency/energy/area Pareto front for this workload set (popsim).

        Seeds descend from the named ``.dhd`` library designs (the session
        architecture does not constrain the population).  ``engine_kw``
        forwards ``repro.core.pareto_dse``'s knobs (``penalty_weight``,
        ``sigma``, ``mesh``, ``key``, ``hv_box``, ...).
        """
        w = self._workload(workload)
        with instrument.span("dragon.session.frontier", bucket=w.bucket, vertices=w.vertices, n=1):
            mcfg = engine_kw.pop("mcfg", self.mcfg)
            self._engine_call(
                ("frontier", mcfg, w.bucket, tuple(metrics), tuple(seeds),
                 population, steps, engine_kw.get("chunk"), engine_kw.get("opt_over", "both"))
            )
            res = _popsim.pareto_dse(
                w.stacked,
                seeds=seeds,
                population=population,
                steps=steps,
                lr=lr,
                metrics=metrics,
                area_budget=area_budget,
                power_budget=power_budget,
                mcfg=mcfg,
                **engine_kw,
            )
            front = tuple(
                FrontierPoint(
                    index=int(win["index"]),
                    seed=win["seed"],
                    weights=tuple(win["weights"][m] for m in PARETO_METRICS),
                    time_s=win["time_s"],
                    energy_j=win["energy_j"],
                    area_mm2=win["area_mm2"],
                    power_w=win["power_w"],
                    edp=win["edp"],
                    dhd=win["dhd"],
                )
                for win in res.winners
            )
            return FrontierResult(
                metrics=tuple(metrics),
                population=population,
                epochs=steps,
                feasible=int(res.feasible.sum()),
                hypervolume=float(res.hypervolume),
                area_budget=float("inf") if area_budget is None else float(area_budget),
                power_budget=float("inf") if power_budget is None else float(power_budget),
                front=front,
                raw=res,
            )

    # --------------------------------------------------------- introspection --
    def trace_programs(self, workload, *, objective: str = "edp", architecture=None) -> dict:
        """Abstractly lower the four served program kinds to jaxprs.

        Returns ``{"simulate": ..., "explain": ..., "optimize": ...,
        "frontier": ...}`` — each a ``ClosedJaxpr`` from ``jax.make_jaxpr``
        over *the same engine functions the session compiles and serves*
        (``simulate_stacked``; the explain gradient; one DOpt epoch, i.e.
        the body the fused chunk scans; the vmapped popsim member step over
        a 2-member population).  Nothing is compiled or executed — this is
        the static program view ``tools/dragonlint`` Pass B inspects for
        transfers, dtype promotions, folded constants and seam-unsafe
        primitives.

        Tracing is a real trace: the engines' retrace probes
        (``dopt._dopt_step`` / ``popsim._member_step``) each bump once per
        call.  Benchmarks gate on *deltas* of those counters, so calling
        this between measurements is safe; don't call it inside one.
        """
        w, a = self._workload(workload), self._arch(architecture)
        spec, mcfg = a.spec, self.mcfg
        gstack = w.stacked
        out: dict = {}

        def sim(tech, arch, g):
            return simulate_stacked(tech, arch, g, spec, mcfg)

        out["simulate"] = jax.make_jaxpr(sim)(a.tech, a.arch, gstack)

        def expl(tech, arch, g):
            return _explain_lane(tech, arch, g, spec, mcfg, objective)

        out["explain"] = jax.make_jaxpr(expl)(a.tech, a.arch, gstack)

        # one DOpt epoch with the exact state/mix layout optimize() scans
        # (opt_over="both": no type logits, placeholder ystate)
        tech_z, arch_z = to_log(a.tech), to_log(a.arch)
        state = (
            tech_z, arch_z, None,
            _dopt.adam_init(tech_z), _dopt.adam_init(arch_z),
            _dopt.adam_init(jnp.zeros(1)),
            _dopt.guard_init(),
        )
        mix = (
            jnp.zeros(len(PARETO_METRICS)), jnp.float32(jnp.inf),
            jnp.float32(jnp.inf), jnp.float32(1.0),
        )

        def opt(st, g, lr, mx, flt):
            return _dopt._dopt_step(st, g, lr, mx, flt, spec, objective, None, "both", mcfg)

        out["optimize"] = jax.make_jaxpr(opt)(state, gstack, jnp.float32(0.05), mix, jnp.float32(0.0))

        # the population chunk's member axis, minimally populated (P=2)
        pop = 2
        ptz = jax.tree.map(lambda x: jnp.stack([x] * pop), tech_z)
        paz = jax.tree.map(lambda x: jnp.stack([x] * pop), arch_z)
        tstate = jax.vmap(_dopt.adam_init)(ptz)
        astate = jax.vmap(_dopt.adam_init)(paz)
        weights = jnp.zeros((pop, len(PARETO_METRICS)))
        budgets = jnp.full((pop,), jnp.inf)

        def front(tz, az, ts, as_, wts, ab, pb, g, lr, pw):
            def member(tz1, az1, ts1, as1, w1, ab1, pb1):
                return _popsim._member_step(
                    tz1, az1, ts1, as1, w1, ab1, pb1, g, lr, pw, spec, mcfg, "both"
                )

            return jax.vmap(member)(tz, az, ts, as_, wts, ab, pb)

        out["frontier"] = jax.make_jaxpr(front)(
            ptz, paz, tstate, astate, weights, budgets, budgets,
            gstack, jnp.float32(0.1), jnp.float32(1.0),
        )
        return out

    # -------------------------------------------------------------- report --
    def _build_report(self, a: Architecture, w: Workload, arrays: dict) -> SimReport:
        """A :class:`SimReport` from the host arrays :func:`_report_arrays`
        unpacked."""
        reads, writes = arrays["reads"], arrays["writes"]
        comp_ops, bw_util = arrays["comp_ops"], arrays["bw_util"]
        runtime, energy, power = arrays["runtime"], arrays["energy"], arrays["power"]
        edp, cycles, area = arrays["edp"], arrays["cycles"], arrays["area"]
        energy_mem, energy_comp = arrays["energy_mem"], arrays["energy_comp"]
        energy_leak, ex = arrays["energy_leak"], arrays["extras"]
        workloads = []
        for i, (lbl, g) in enumerate(zip(w.labels, w.graphs)):
            v = g.n_vertices
            time_v = ex["time_v"][i, :v]
            energy_v = ex["energy_v"][i, :v]
            rt = float(runtime[i])
            levels = tuple(
                MemoryLevelReport(
                    level=lvl,
                    reads_bytes=float(reads[i, li]),
                    writes_bytes=float(writes[i, li]),
                    transfer_time_s=float(ex["t_level"][i, li]),
                    dynamic_energy_j=float(ex["e_level_dyn"][i, li]),
                    leakage_energy_j=float(ex["e_level_leak"][i, li]),
                    bw_utilization=float(bw_util[i, li]),
                )
                for li, lvl in enumerate(MEM_CLS)
            )
            compute = tuple(
                ComputeClassReport(
                    unit=unit,
                    flops=float(comp_ops[i, ci]),
                    dynamic_energy_j=float(ex["e_comp_dyn"][i, ci]),
                    leakage_energy_j=float(ex["e_comp_leak"][i, ci]),
                )
                for ci, unit in enumerate(COMP_CLS)
            )
            vertices = tuple(
                VertexReport(
                    name=str(g.names[vi]) if vi < len(g.names) else f"v{vi}",
                    time_s=float(time_v[vi]),
                    energy_j=float(energy_v[vi]),
                    time_share=float(time_v[vi] / max(rt, 1e-300)),
                )
                for vi in range(v)
            )
            workloads.append(
                WorkloadReport(
                    label=lbl,
                    runtime_s=rt,
                    energy_j=float(energy[i]),
                    power_w=float(power[i]),
                    edp=float(edp[i]),
                    cycles=float(cycles[i]),
                    energy_mem_j=float(energy_mem[i]),
                    energy_comp_j=float(energy_comp[i]),
                    energy_leak_j=float(energy_leak[i]),
                    levels=levels,
                    compute=compute,
                    vertices=vertices,
                )
            )
        return SimReport(
            architecture=a.name,
            objective="",
            area_mm2=float(area[0]),
            workloads=tuple(workloads),
        )
