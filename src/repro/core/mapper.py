"""The differentiable mapper (paper §5.2, Algorithms 1/2/7).

Maps a workload DFG onto a concrete hardware model CH and produces cycle
counts plus the memory/compute state the energy model consumes.

JAX adaptation of the paper's control flow (see DESIGN.md §3):

  * MAPVERTEX's vertex *splitting* when the working set exceeds memory
    capacity (Alg. 1 lines 20-23) becomes *continuous tiling*:
    ``n_tiles = ceil(alloc / 0.9*capacity)`` with a straight-through ceil —
    the forward value matches the discrete split count exactly, while the
    backward pass sees a smooth surrogate so capacity gradients exist.

  * PREFETCHVERTEX / Alg. 7's prefetch & streaming decisions
    (bw_util < 0.9 * bw_limit, size_util < 0.9 * size_limit) become hard
    gates forward + sigmoid surrogate gradients.

  * Appendix C stall-time gradients: ``t = max(t_mem, t_comp)`` — the
    subgradient of max flows only through the critical (non-hidden) term,
    exactly the paper's 'gradient is zero if latency is entirely hidden'.

Scan structure
--------------

Everything the mapper computes per vertex is elementwise except the two
inter-vertex carries Alg. 7 threads through the topological order:

  * decaying buffer occupancy   ``o' = min(0.5*o + alloc, capacity)``
  * bandwidth-utilization EMA   ``b' = 0.8*b + 0.2*x``

Both are first-order (min-)affine recurrences in the carry, with inputs
``alloc``/``x`` that depend only on the vertex (the EMA input is the
*demanded* bandwidth utilization — the no-overlap transfer time Alg. 7
inspects *before* granting prefetch — so it is independent of the gate it
feeds).  That makes the whole mapper parallel-depth:

  1. compute all per-vertex intrinsics elementwise ([V]-vectorized);
  2. run the two carries as ``jax.lax.associative_scan`` — O(log V) depth
     instead of O(V) for the 700+-vertex LM graphs, and it vmaps across
     populations for DSE;
  3. compute gates / exposed-time / cycles elementwise from the scanned
     prefix states and reduce.

``MapperCfg.scan_impl`` selects the implementation:

  * ``"auto"``   (default) — associative for graphs with >= 32 vertices;
    tiny graphs take the fully-fused sequential scan, whose single-loop
    dispatch is cheaper than the associative tree's op fan-out when V is
    small (the two are numerically equivalent, so this is pure dispatch);
  * ``"assoc"``  — always the associative-scan formulation above;
  * ``"ref"``    — the sequential ``lax.scan`` over vertices with the whole
    vertex computation inlined in the body (the pre-parallel structure),
    kept as the independent semantic oracle — tests/test_mapper_equiv.py
    asserts values and gradients match;
  * ``"pallas"`` — opt-in: the bw-EMA prefix dispatches through the
    ``kernels.sscan.affine_scan`` Pallas kernel
    (``runtime.dragon_pallas_call`` seam); occupancy stays associative.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.dgen import ConcreteHW
from repro.core.graph import Graph
from repro.core.params import COMP_IDX, MEM_IDX, N_COMP, N_MEM

_GBUF = MEM_IDX["globalBuf"]
_MAIN = MEM_IDX["mainMem"]
_LOCAL = MEM_IDX["localMem"]
_SYS = COMP_IDX["systolicArray"]
_VEC = COMP_IDX["vector"]

_OCC_DECAY = 0.5  # buffer-residency decay per vertex (Alg. 7 carry)
_BW_DECAY = 0.8  # bandwidth-EMA decay per vertex
_ASSOC_MIN_V = 32  # "auto": below this the fused sequential scan dispatches faster


# --------------------------------------------------------------------------- #
# straight-through helpers
# --------------------------------------------------------------------------- #


def ste(hard: jax.Array, soft: jax.Array) -> jax.Array:
    """Forward = hard (exact discrete semantics); backward = d soft."""
    return soft + jax.lax.stop_gradient(hard - soft)


def ceil_ste(x: jax.Array) -> jax.Array:
    return ste(jnp.ceil(x), x)


def gate_below_ste(x: jax.Array, thresh: jax.Array, tau: float = 0.1) -> jax.Array:
    """1.0 when x < thresh (hard forward), sigmoid surrogate backward."""
    hard = (x < thresh).astype(jnp.float32)
    soft = jax.nn.sigmoid((thresh - x) / (tau * jnp.abs(thresh) + 1e-30))
    return ste(hard, soft)


# --------------------------------------------------------------------------- #
# Mapper config + state
# --------------------------------------------------------------------------- #


@dataclass(frozen=True)
class MapperCfg:
    headroom: float = 0.9  # paper Alg. 7 thresholds
    prefetch: bool = True
    streaming: bool = True
    merge_threshold: float = 0.0  # compute-merge pass threshold (FLOPs)
    scan_impl: str = "auto"  # auto | assoc | ref | pallas (see module docstring)


@jax.tree_util.register_dataclass
@dataclass
class MapState:
    """paper ⟨z, ms, cs⟩: cycle count + memory state + compute state."""

    cycles: jax.Array
    reads: jax.Array  # [N_MEM] total bytes read
    writes: jax.Array  # [N_MEM] total bytes written
    comp_ops: jax.Array  # [N_COMP] total FLOPs issued
    peak_alloc: jax.Array  # [N_MEM] peak working set
    t_comp: jax.Array  # total compute-critical seconds (diagnostic)
    t_mem: jax.Array  # total memory-critical seconds (diagnostic)
    t_exposed_main: jax.Array  # main-memory time not hidden by prefetch
    bw_util: jax.Array  # [N_MEM] average bandwidth utilization
    n_tiles: jax.Array  # total vertex splits (diagnostic)


# --------------------------------------------------------------------------- #
# per-vertex intrinsics (carry-independent, [V]-vectorized)
# --------------------------------------------------------------------------- #


def _vertex_intrinsics(chw: ConcreteHW, g: Graph, cfg: MapperCfg) -> dict:
    """Everything MAPVERTEX computes that does not depend on the carry."""
    freq = chw.frequency
    cap_gbuf = chw.capacity[_GBUF] * cfg.headroom
    bw = chw.mem_bw  # [N_MEM] bytes/s

    alloc_gbuf = g.n_alloc[:, _GBUF]
    # ---------------- tiling (MAPVERTEX split, lines 20-23) -----------------
    tiles = jnp.maximum(ceil_ste(alloc_gbuf / cap_gbuf), 1.0)

    # ---------------- compute time per class --------------------------------
    # systolic array: discrete wave model (matches the cycle-walker's
    # semantics, differentiable through STE-ceil): each (sys_x x sys_y)
    # output tile streams K MACs + a fill/drain bubble of sx+sy cycles
    M, N, K = g.dims[:, 0], g.dims[:, 1], g.dims[:, 2]
    m_t = jnp.maximum(M / tiles, 1.0)
    waves_m = ceil_ste(m_t / chw.sys_x)
    waves_n = ceil_ste(jnp.maximum(N, 1.0) / chw.sys_y)
    k_cycles = ceil_ste(jnp.maximum(K, 1.0))
    fill = chw.sys_x + chw.sys_y
    cyc_sys_tile = waves_m * waves_n * (k_cycles + fill)
    ops_sys_tile = g.n_comp[:, _SYS] / tiles
    cyc_sys_tile = jnp.maximum(
        cyc_sys_tile, ops_sys_tile / jnp.maximum(chw.flops_per_cycle[_SYS], 1e-9)
    )
    t_sys = jnp.where(ops_sys_tile > 0, tiles * cyc_sys_tile / freq, 0.0)
    # other classes: rate model
    eff_rate = jnp.maximum(chw.flops_per_cycle, 1e-9) * freq  # [N_COMP] FLOP/s
    t_comp_cls = g.n_comp / eff_rate[None, :]
    t_comp = jnp.maximum(jnp.max(t_comp_cls.at[:, _SYS].set(0.0), axis=-1), t_sys)

    # ---------------- memory time per level ---------------------------------
    # burst-quantized transfers with the average bank-conflict factor of
    # the reference walker (mean of its 1.00-1.08 hash-spread) + per-tile
    # access latency
    conflict = 1.04
    t_lvl = (g.n_read + g.n_write) / bw[None, :] * conflict  # [V, N_MEM]
    t_tile_lat = tiles[:, None] * (chw.read_latency + chw.write_latency)[None, :]
    t_onchip = jnp.maximum(t_lvl[:, _GBUF] + t_tile_lat[:, _GBUF], t_lvl[:, _LOCAL])
    t_main = t_lvl[:, _MAIN] + t_tile_lat[:, _MAIN] * (g.n_alloc[:, _MAIN] > 0)
    t_core = jnp.maximum(t_comp, t_onchip)

    # ---------------- demanded bandwidth utilization (EMA input) ------------
    # the no-overlap (fully exposed) vertex time: what Alg. 7 inspects when
    # deciding whether bandwidth headroom exists — independent of the
    # prefetch/streaming decision it gates, so the EMA is a pure affine
    # recurrence
    t_full = tiles * ceil_ste((t_core + t_main) * freq / jnp.maximum(tiles, 1.0)) / freq
    bytes_gbuf = g.n_read[:, _GBUF] + g.n_write[:, _GBUF]
    used_bw = jnp.where(
        t_full > 0, bytes_gbuf / jnp.maximum(t_full, 1e-30) / bw[_GBUF], 0.0
    )
    bw_x = jnp.clip(used_bw, 0.0, 2.0)

    # no-op (padding) vertices cost nothing — this is what makes
    # Graph.stack()'s pad_to exactly free in the batched-workload path
    active = (
        jnp.sum(g.n_comp, -1)
        + jnp.sum(g.n_read, -1)
        + jnp.sum(g.n_write, -1)
        + jnp.sum(g.n_alloc, -1)
    ) > 0

    return dict(
        tiles=tiles,
        alloc_gbuf=alloc_gbuf,
        t_comp=t_comp,
        t_onchip=t_onchip,
        t_main=t_main,
        t_core=t_core,
        t_lvl=t_lvl,
        used_bw=used_bw,
        bw_x=bw_x,
        active=active.astype(jnp.float32),
    )


def _vertex_exec(chw: ConcreteHW, g: Graph, cfg: MapperCfg, iv: dict,
                 occ_prev: jax.Array, bw_prev: jax.Array) -> dict:
    """Per-vertex gates, exposed time and cycles — elementwise from the
    prefix carries.  Shared by the MapState reduction (:func:`_vertex_finish`)
    and the per-vertex diagnostics (:func:`map_workload_breakdown`)."""
    freq = chw.frequency

    # ---------------- prefetch / streaming gates (Alg. 7) -------------------
    can_prefetch = (
        gate_below_ste(occ_prev + iv["alloc_gbuf"] / iv["tiles"],
                       chw.capacity[_GBUF] * cfg.headroom)
        * gate_below_ste(bw_prev, cfg.headroom)
        * (1.0 if cfg.prefetch else 0.0)
    )
    # streaming: if over capacity but bw available, overlap main-mem
    # traffic with compute (set_execution = streaming)
    can_stream = gate_below_ste(bw_prev, cfg.headroom) * (1.0 if cfg.streaming else 0.0)
    hide = jnp.maximum(can_prefetch, can_stream)

    # exposed main-memory time: hidden behind compute when gated on
    t_main_exposed = jnp.maximum(iv["t_main"] - hide * iv["t_core"], 0.0)
    # integer-cycle quantization per tile (cycle-walker semantics, exact
    # forward via STE): decode-scale vertices cost whole cycles
    per_tile_cyc = (iv["t_core"] + t_main_exposed) * freq / iv["tiles"]
    t_vertex = iv["tiles"] * ceil_ste(per_tile_cyc) / freq * iv["active"]
    return dict(t_vertex=t_vertex, cycles_v=t_vertex * freq, t_main_exposed=t_main_exposed)


def _vertex_finish(chw: ConcreteHW, g: Graph, cfg: MapperCfg, iv: dict,
                   occ_prev: jax.Array, bw_prev: jax.Array) -> MapState:
    """The reductions into MapState, from the shared per-vertex execution."""
    ex = _vertex_exec(chw, g, cfg, iv, occ_prev, bw_prev)
    t_main_exposed = ex["t_main_exposed"]
    cycles_v = ex["cycles_v"]
    total_cyc = jnp.sum(cycles_v)
    return MapState(
        cycles=total_cyc,
        reads=jnp.sum(g.n_read, 0),
        writes=jnp.sum(g.n_write, 0),
        comp_ops=jnp.sum(g.n_comp, 0),
        peak_alloc=jnp.max(g.n_alloc, 0),
        t_comp=jnp.sum(iv["t_comp"]),
        t_mem=jnp.sum(iv["t_onchip"] * iv["active"]),
        t_exposed_main=jnp.sum(t_main_exposed),
        bw_util=jnp.stack(
            [
                jnp.float32(0.0),
                jnp.sum(iv["used_bw"] * cycles_v) / jnp.maximum(total_cyc, 1e-30),
                jnp.float32(0.0),
            ]
        ),
        # diagnostics also exclude no-op (padding) vertices, so Graph.stack's
        # pad_to is exact for the whole MapState, not just cycles
        n_tiles=jnp.sum(iv["tiles"] * iv["active"]),
    )


# --------------------------------------------------------------------------- #
# carry prefixes: associative (O(log V) depth) and sequential reference
# --------------------------------------------------------------------------- #


def _exclusive(after: jax.Array) -> jax.Array:
    """Shift an inclusive prefix to the state *before* each vertex (x0 = 0)."""
    return jnp.concatenate([jnp.zeros((1,), after.dtype), after[:-1]])


def affine_prefix_assoc(decay: float, add: jax.Array) -> jax.Array:
    """Inclusive prefix of ``s' = decay*s + add_i`` (s0 = 0), O(log V) depth.

    Elements are affine maps (a, b): s -> a*s + b; composition
    (later ∘ earlier) is (a1*a2, a2*b1 + b2), which is associative.
    """
    a = jnp.full_like(add, decay)

    def combine(l, r):
        a1, b1 = l
        a2, b2 = r
        return a1 * a2, a2 * b1 + b2

    _, after = jax.lax.associative_scan(combine, (a, add))
    return after


def minaffine_prefix_assoc(decay: float, add: jax.Array, cap: jax.Array) -> jax.Array:
    """Inclusive prefix of ``s' = min(decay*s + add_i, cap)`` (s0 = 0).

    Maps s -> min(a*s + b, c) are closed under composition
    (later (a2,b2,c2) ∘ earlier (a1,b1,c1) =
     (a1*a2, a2*b1 + b2, min(a2*c1 + b2, c2)) for a2 >= 0), so the clamped
    occupancy recurrence is still an associative scan.
    """
    a = jnp.full_like(add, decay)
    c = jnp.broadcast_to(cap, add.shape).astype(add.dtype)

    def combine(l, r):
        a1, b1, c1 = l
        a2, b2, c2 = r
        return a1 * a2, a2 * b1 + b2, jnp.minimum(a2 * c1 + b2, c2)

    _, b, c = jax.lax.associative_scan(combine, (a, add, c))
    return jnp.minimum(b, c)  # applied to s0 = 0


def _carry_prefixes(chw: ConcreteHW, cfg: MapperCfg, iv: dict) -> tuple[jax.Array, jax.Array]:
    """The two Alg.-7 carries as exclusive prefixes (pre-vertex states),
    honoring the pallas opt-in for the bw-EMA."""
    occ_after = minaffine_prefix_assoc(_OCC_DECAY, iv["alloc_gbuf"], chw.capacity[_GBUF])
    if cfg.scan_impl == "pallas":
        from repro.kernels.sscan import affine_scan

        bw_after = affine_scan(_BW_DECAY, 0.2 * iv["bw_x"])
    else:
        bw_after = affine_prefix_assoc(_BW_DECAY, 0.2 * iv["bw_x"])
    return _exclusive(occ_after), _exclusive(bw_after)


def _map_workload_assoc(chw: ConcreteHW, g: Graph, cfg: MapperCfg) -> MapState:
    iv = _vertex_intrinsics(chw, g, cfg)
    occ_prev, bw_prev = _carry_prefixes(chw, cfg, iv)
    return _vertex_finish(chw, g, cfg, iv, occ_prev, bw_prev)


def map_workload_breakdown(chw: ConcreteHW, g: Graph, cfg: MapperCfg = MapperCfg()) -> dict:
    """Per-vertex / per-level mapping diagnostics (the ``explain`` path).

    Runs the associative formulation's per-vertex pipeline but returns the
    arrays *before* the MapState reductions:

      * ``time_v`` / ``cycles_v`` [V] — each vertex's wall time and cycles
        (padding vertices are exactly zero);
      * ``t_comp_v`` [V] — compute-critical seconds per vertex;
      * ``t_main_exposed_v`` [V] — main-memory time not hidden by prefetch;
      * ``tiles_v`` [V] — MAPVERTEX split counts;
      * ``t_level`` [N_MEM] — total demanded (no-overlap) transfer time per
        memory level;
      * ``active`` [V] — 1.0 for real vertices, 0.0 for padding.

    Consistency with :func:`map_workload`: for ``scan_impl`` "auto" (V >=
    32, the façade's bucketed case), "assoc" and "pallas" the prefixes are
    the *same computation*, so the per-vertex cycles sum to
    ``MapState.cycles`` exactly.  Under the sequential reference
    (``"ref"``) the arrays come from the associative formulation and match
    to the formulations' tested equivalence (tests/test_mapper_equiv.py),
    not bit-exactly.  Differentiable like everything else in the mapper.
    """
    iv = _vertex_intrinsics(chw, g, cfg)
    occ_prev, bw_prev = _carry_prefixes(chw, cfg, iv)
    ex = _vertex_exec(chw, g, cfg, iv, occ_prev, bw_prev)
    return dict(
        time_v=ex["t_vertex"],
        cycles_v=ex["cycles_v"],
        t_comp_v=iv["t_comp"] * iv["active"],
        t_main_exposed_v=ex["t_main_exposed"] * iv["active"],
        tiles_v=iv["tiles"] * iv["active"],
        t_level=jnp.sum(iv["t_lvl"] * iv["active"][:, None], axis=0),
        active=iv["active"],
    )


def map_workload_scan(chw: ConcreteHW, g: Graph, cfg: MapperCfg = MapperCfg()) -> MapState:
    """Sequential-reference MAPWORKLOAD: one ``lax.scan`` over the
    (topologically ordered) vertex list with the whole per-vertex
    computation inlined in the body, O(V) depth.

    This is deliberately *not* written in terms of ``_vertex_intrinsics`` —
    it is the independent oracle the associative formulation is tested
    against, and its single fused loop body is also the cheapest dispatch
    for tiny graphs (the "auto" small-V path).
    """
    freq = chw.frequency
    cap_gbuf = chw.capacity[_GBUF] * cfg.headroom
    bw = chw.mem_bw  # [N_MEM] bytes/s

    def vertex_step(carry, v):
        n_comp, n_read, n_write, n_alloc, dims = v
        # ---------------- tiling (MAPVERTEX split, lines 20-23) -------------
        alloc_gbuf = n_alloc[_GBUF]
        tiles = jnp.maximum(ceil_ste(alloc_gbuf / cap_gbuf), 1.0)

        # ---------------- compute time per class ---------------------------
        M, N, K = dims[0], dims[1], dims[2]
        m_t = jnp.maximum(M / tiles, 1.0)
        waves_m = ceil_ste(m_t / chw.sys_x)
        waves_n = ceil_ste(jnp.maximum(N, 1.0) / chw.sys_y)
        k_cycles = ceil_ste(jnp.maximum(K, 1.0))
        fill = chw.sys_x + chw.sys_y
        cyc_sys_tile = waves_m * waves_n * (k_cycles + fill)
        ops_sys_tile = n_comp[_SYS] / tiles
        cyc_sys_tile = jnp.maximum(
            cyc_sys_tile, ops_sys_tile / jnp.maximum(chw.flops_per_cycle[_SYS], 1e-9)
        )
        t_sys = jnp.where(ops_sys_tile > 0, tiles * cyc_sys_tile / freq, 0.0)
        eff_rate = jnp.maximum(chw.flops_per_cycle, 1e-9) * freq  # FLOP/s
        t_comp_cls = n_comp / eff_rate
        t_comp = jnp.maximum(jnp.max(t_comp_cls.at[_SYS].set(0.0)), t_sys)

        # ---------------- memory time per level ----------------------------
        conflict = 1.04
        t_lvl = (n_read + n_write) / bw * conflict
        t_tile_lat = tiles * (chw.read_latency + chw.write_latency)
        t_onchip = jnp.maximum(t_lvl[_GBUF] + t_tile_lat[_GBUF], t_lvl[_LOCAL])
        t_main = t_lvl[_MAIN] + t_tile_lat[_MAIN] * (n_alloc[_MAIN] > 0)
        t_core = jnp.maximum(t_comp, t_onchip)

        # ---------------- prefetch / streaming gates (Alg. 7) --------------
        occupancy, bw_ema = carry["occupancy"], carry["bw_ema"]
        can_prefetch = (
            gate_below_ste(occupancy + alloc_gbuf / tiles, chw.capacity[_GBUF] * cfg.headroom)
            * gate_below_ste(bw_ema, cfg.headroom)
            * (1.0 if cfg.prefetch else 0.0)
        )
        can_stream = gate_below_ste(bw_ema, cfg.headroom) * (1.0 if cfg.streaming else 0.0)
        hide = jnp.maximum(can_prefetch, can_stream)

        t_main_exposed = jnp.maximum(t_main - hide * t_core, 0.0)
        per_tile_cyc = (t_core + t_main_exposed) * freq / tiles
        active = (jnp.sum(n_comp) + jnp.sum(n_read) + jnp.sum(n_write) + jnp.sum(n_alloc)) > 0
        t_vertex = tiles * ceil_ste(per_tile_cyc) / freq * active

        # ---------------- state updates -------------------------------------
        # the EMA input is the *demanded* (no-overlap) utilization — see
        # _vertex_intrinsics; this is what keeps the carry a pure affine
        # recurrence in the parallel formulation
        t_full = tiles * ceil_ste((t_core + t_main) * freq / jnp.maximum(tiles, 1.0)) / freq
        used_bw = jnp.where(
            t_full > 0, (n_read[_GBUF] + n_write[_GBUF]) / jnp.maximum(t_full, 1e-30) / bw[_GBUF], 0.0
        )
        new_bw = _BW_DECAY * bw_ema + 0.2 * jnp.clip(used_bw, 0.0, 2.0)
        new_occ = _OCC_DECAY * occupancy + alloc_gbuf  # decaying residency
        new_occ = jnp.minimum(new_occ, chw.capacity[_GBUF])

        out = dict(
            cycles=t_vertex * freq,
            t_comp=t_comp,
            t_mem=t_onchip * active,
            t_main_exposed=t_main_exposed,
            tiles=tiles * active,
            bw_now=used_bw,
        )
        return dict(occupancy=new_occ, bw_ema=new_bw), out

    # zeros derived from the hardware point, not literals: when a population
    # is sharded over a mesh axis the hardware varies along it, and so must
    # the carry (runtime.spmd_map checks that the two agree)
    zero = jnp.zeros_like(freq)
    carry0 = dict(occupancy=zero, bw_ema=zero)
    xs = (g.n_comp, g.n_read, g.n_write, g.n_alloc, g.dims)
    _, outs = jax.lax.scan(vertex_step, carry0, xs)

    total_cyc = jnp.sum(outs["cycles"])
    return MapState(
        cycles=total_cyc,
        reads=jnp.sum(g.n_read, 0),
        writes=jnp.sum(g.n_write, 0),
        comp_ops=jnp.sum(g.n_comp, 0),
        peak_alloc=jnp.max(g.n_alloc, 0),
        t_comp=jnp.sum(outs["t_comp"]),
        t_mem=jnp.sum(outs["t_mem"]),
        t_exposed_main=jnp.sum(outs["t_main_exposed"]),
        bw_util=jnp.stack(
            [
                jnp.float32(0.0),
                jnp.sum(outs["bw_now"] * outs["cycles"]) / jnp.maximum(total_cyc, 1e-30),
                jnp.float32(0.0),
            ]
        ),
        n_tiles=jnp.sum(outs["tiles"]),
    )


def map_workload(chw: ConcreteHW, g: Graph, cfg: MapperCfg = MapperCfg()) -> MapState:
    """MAPWORKLOAD (paper Alg. 1): map the vertex list onto CH, tiling /
    streaming / prefetching per vertex.  Dispatches on ``cfg.scan_impl``."""
    impl = cfg.scan_impl
    if impl == "auto":
        impl = "ref" if g.n_comp.shape[0] < _ASSOC_MIN_V else "assoc"
    if impl == "ref":
        return map_workload_scan(chw, g, cfg)
    if impl in ("assoc", "pallas"):
        return _map_workload_assoc(chw, g, cfg)
    raise ValueError(f"unknown MapperCfg.scan_impl {cfg.scan_impl!r}")
