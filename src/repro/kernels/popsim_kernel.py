"""Pallas TPU population-simulation kernel — DSim's hot loop (the paper's
~1000x speed claim) batched across DSE candidate populations.

One grid step evaluates a block of BP candidate designs against the whole
workload DFG: the graph's per-vertex stats stay resident in VMEM (one HBM
read per population block) and a fori_loop walks the vertices, accumulating
cycles + dynamic energy per candidate with the mapper's forward semantics
(tiling, max(t_comp, t_mem) critical path, prefetch/stream gating on the
bandwidth EMA).  Lanes = candidates, so all per-vertex arithmetic is
(BP,)-vectorized on the VPU.

Packed layouts (see ops.pack_chw / ops.pack_graph):
  chw   [P, 27]: freq, cap_gbuf, bw[3], rlat[3], wlat[3], re_pb[3], we_pb[3],
                 e_flop[4], rate[4] (FLOP/cycle), sys_x, sys_y
                 (= CHW_COLS = 27; column slices below are the ground truth)
  graph [V, 16]: n_comp[4], n_read[3], n_write[3], n_alloc_gbuf, main_alloc,
                 dims[3], pad  (= GRAPH_COLS = 16)
Output [P, 8]: cycles, e_dyn, t_comp, t_mem, t_exposed, tiles, pad, pad.

The pure-jnp oracle is ref.popsim_reference — identical math via lax.scan —
and tests sweep population/graph sizes in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import runtime

# chw packed column indices
FREQ, CAP_GBUF = 0, 1
BW = slice(2, 5)
RLAT = slice(5, 8)
WLAT = slice(8, 11)
RE_PB = slice(11, 14)
WE_PB = slice(14, 17)
E_FLOP = slice(17, 21)
RATE = slice(21, 25)
SYS_X, SYS_Y = 25, 26
CHW_COLS = 27

# graph packed column indices
G_COMP = slice(0, 4)
G_READ = slice(4, 7)
G_WRITE = slice(7, 10)
G_ALLOC_GBUF = 10
G_MAIN_PRESENT = 11
G_DIMS = slice(12, 15)
GRAPH_COLS = 16

# layout consistency: the column map must tile the declared widths exactly
assert RATE.stop == SYS_X and SYS_Y == CHW_COLS - 1, "chw column map out of sync"
assert G_DIMS.stop < GRAPH_COLS, "graph column map out of sync"

OUT_COLS = 8
_LOCAL, _GBUF, _MAIN = 0, 1, 2
_SYS = 0
HEADROOM = 0.9


def _popsim_kernel(graph_ref, chw_ref, out_ref, *, n_vertices: int):
    chw = chw_ref[...].astype(jnp.float32)  # [BP, CHW_COLS]
    freq = chw[:, FREQ]
    cap_gbuf = chw[:, CAP_GBUF] * HEADROOM
    bw = chw[:, BW]  # [BP, 3]
    rlat, wlat = chw[:, RLAT], chw[:, WLAT]
    re_pb, we_pb = chw[:, RE_PB], chw[:, WE_PB]
    e_flop, rate = chw[:, E_FLOP], chw[:, RATE]
    sys_x, sys_y = chw[:, SYS_X], chw[:, SYS_Y]

    bp = chw.shape[0]
    zeros = jnp.zeros((bp,), jnp.float32)

    def body(v, carry):
        cycles, e_dyn, t_comp_acc, t_mem_acc, t_exp_acc, tiles_acc, occupancy, bw_ema = carry
        g = graph_ref[v]  # [GRAPH_COLS]
        n_comp = g[G_COMP]  # [4]
        n_read, n_write = g[G_READ], g[G_WRITE]
        alloc_gbuf = g[G_ALLOC_GBUF]
        has_main = g[G_MAIN_PRESENT]
        M, N, K = g[G_DIMS][0], g[G_DIMS][1], g[G_DIMS][2]

        tiles = jnp.maximum(jnp.ceil(alloc_gbuf / cap_gbuf), 1.0)  # [BP]

        # systolic wave model (same calibrated form as mapper.py)
        m_t = jnp.maximum(M / tiles, 1.0)
        waves = jnp.ceil(m_t / sys_x) * jnp.ceil(jnp.maximum(N, 1.0) / sys_y)
        cyc_sys_tile = waves * (jnp.ceil(jnp.maximum(K, 1.0)) + sys_x + sys_y)
        ops_sys_tile = n_comp[_SYS] / tiles
        cyc_sys_tile = jnp.maximum(
            cyc_sys_tile, ops_sys_tile / jnp.maximum(rate[:, _SYS], 1e-9)
        )
        t_sys = jnp.where(ops_sys_tile > 0, tiles * cyc_sys_tile / freq, 0.0)
        eff = jnp.maximum(rate, 1e-9) * freq[:, None]  # FLOP/s
        t_cls = n_comp[None, :] / eff  # [BP, 4]
        # the systolic class is priced by the wave model above; an iota mask
        # (not a scatter, which Mosaic cannot lower) drops its column
        not_sys = jax.lax.broadcasted_iota(jnp.int32, t_cls.shape, 1) != _SYS
        t_other = jnp.max(jnp.where(not_sys, t_cls, 0.0), axis=-1)
        t_comp = jnp.maximum(t_other, t_sys)  # [BP]

        t_lvl = (n_read + n_write)[None, :] / bw * 1.04  # bank-conflict mean
        t_tile_lat = tiles[:, None] * (rlat + wlat)
        t_onchip = jnp.maximum(t_lvl[:, _GBUF] + t_tile_lat[:, _GBUF], t_lvl[:, _LOCAL])
        t_main = t_lvl[:, _MAIN] + t_tile_lat[:, _MAIN] * has_main

        can_prefetch = ((occupancy + alloc_gbuf / tiles) < cap_gbuf).astype(jnp.float32) * (
            bw_ema < HEADROOM
        ).astype(jnp.float32)
        can_stream = (bw_ema < HEADROOM).astype(jnp.float32)
        hide = jnp.maximum(can_prefetch, can_stream)

        t_core = jnp.maximum(t_comp, t_onchip)
        t_exposed = jnp.maximum(t_main - hide * t_core, 0.0)
        # integer-cycle quantization per tile; no-op (padding) vertices are
        # free and excluded from diagnostics (matches mapper.py)
        active = (
            jnp.sum(n_comp) + jnp.sum(n_read) + jnp.sum(n_write) + alloc_gbuf + has_main
        ) > 0
        t_vertex = tiles * jnp.ceil((t_core + t_exposed) * freq / tiles) / freq * active

        # EMA of the *demanded* (no-overlap) utilization — matches mapper.py's
        # carry-free recurrence, not the post-gating realized time
        t_full = tiles * jnp.ceil((t_core + t_main) * freq / tiles) / freq
        used_bw = jnp.where(
            t_full > 0,
            (n_read[_GBUF] + n_write[_GBUF]) / jnp.maximum(t_full, 1e-30) / bw[:, _GBUF],
            0.0,
        )
        bw_ema = 0.8 * bw_ema + 0.2 * jnp.clip(used_bw, 0.0, 2.0)
        occupancy = jnp.minimum(0.5 * occupancy + alloc_gbuf, cap_gbuf / HEADROOM)

        e_v = jnp.sum(n_read[None, :] * re_pb + n_write[None, :] * we_pb, -1) + jnp.sum(
            n_comp[None, :] * e_flop, -1
        )
        return (
            cycles + t_vertex * freq,
            e_dyn + e_v,
            t_comp_acc + t_comp,
            t_mem_acc + t_onchip * active,
            t_exp_acc + t_exposed,
            tiles_acc + tiles * active,
            occupancy,
            bw_ema,
        )

    init = (zeros,) * 8
    cycles, e_dyn, t_c, t_m, t_e, tiles, _, _ = jax.lax.fori_loop(0, n_vertices, body, init)
    out = jnp.stack([cycles, e_dyn, t_c, t_m, t_e, tiles, zeros, zeros], axis=-1)
    out_ref[...] = out.astype(out_ref.dtype)


def popsim(
    graph_packed: jax.Array,  # [V, GRAPH_COLS] fp32
    chw_packed: jax.Array,  # [P, CHW_COLS] fp32
    *,
    block_pop: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Evaluate P candidate designs against one DFG.  Returns [P, OUT_COLS]."""
    V = graph_packed.shape[0]
    P = chw_packed.shape[0]
    block_pop = runtime.clamp_block(block_pop, P, name="block_pop")

    kernel = functools.partial(_popsim_kernel, n_vertices=V)
    return runtime.dragon_pallas_call(
        kernel,
        grid=(P // block_pop,),
        in_specs=[
            pl.BlockSpec((V, GRAPH_COLS), lambda p: (0, 0)),  # graph resident
            pl.BlockSpec((block_pop, CHW_COLS), lambda p: (p, 0)),
        ],
        out_specs=pl.BlockSpec((block_pop, OUT_COLS), lambda p: (p, 0)),
        out_shape=jax.ShapeDtypeStruct((P, OUT_COLS), jnp.float32),
        interpret=interpret,
        dimension_semantics=("parallel",),
    )(graph_packed, chw_packed)
