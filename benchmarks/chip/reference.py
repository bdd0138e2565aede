"""Plain reference of DRAGON's simulator semantics, for the benchmark's check.

Written from the model's equations (DGen's device and template models, the
mapper's per-vertex tiling / prefetch / streaming rules, DSim's energy sum,
DOpt's log-space Adam with bounds clamping), in straightforward
``jax.numpy`` with one sequential ``lax.scan`` over the vertices.  It imports
nothing of the program under test: the device-library constants and the
parameter bounds are copied here, and the architectures' numbers come from
the benchmark's configuration files.  The workload graphs are inputs (the
query's data), handed over as plain arrays.

Every function takes a ``dtype``: ``float64`` is the reference, ``bfloat16``
is the lower-precision control that the check must refuse.  Discrete steps
(ceilings, prefetch gates) are straight-through, as the paper's JAX
adaptation defines them: the forward value is the discrete one, the
gradient is that of the smooth surrogate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MEM = ("localMem", "globalBuf", "mainMem")
COMP = ("systolicArray", "vector", "macTree", "fpu")
MEM_TYPES = ("sram", "rram", "dram")
LOCAL, GBUF, MAIN = 0, 1, 2
SYS = 0

TECH_FIELDS = (
    "mem_wire_cap", "mem_wire_resist", "cell_read_latency", "cell_access_device",
    "cell_read_power", "cell_leakage_power", "cell_area", "peripheral_node",
    "comp_wire_cap", "comp_wire_resist", "node",
)
ARCH_FIELDS = (
    "sys_arr_x", "sys_arr_y", "sys_arr_n", "vect_width", "vect_n", "mtree_x",
    "mtree_y", "mtree_tile_x", "mtree_tile_y", "fpu_n", "frequency", "capacity",
    "bank_size", "n_read_ports", "bw_scale",
)

# device library @ 40 nm, per memory technology (sram, rram, dram)
WRITE_LAT_MULT = (1.0, 3.0, 1.2)
WRITE_EN_MULT = (1.0, 8.0, 1.1)
PERIPH_DELAY_REF = (0.25e-9, 0.35e-9, 2.0e-9)
PERIPH_OVERHEAD = (0.35, 0.25, 0.15)
LEAK_PERIPH_REF = (2.0e-3, 1.5e-3, 0.5e-3)
VDD = 0.9
# logic primitives @ 40 nm: adder, multiplier, flip-flop
PRIM_DELAY = (0.15e-9, 0.60e-9, 0.05e-9)
PRIM_ENERGY = (0.03e-12, 0.80e-12, 0.01e-12)
PRIM_AREA = (60.0, 800.0, 10.0)
LEAK_LOGIC_REF = 4.0e-3

HEADROOM = 0.9
# a ceiling whose argument lies within this share of a whole number is a tie:
# float32 may round it to either side (about eight float32 ulps; the chip's
# float32 division is not correctly rounded, so an exact quotient such as
# 3584 / 64 can come out just above 56)
TIE_REL = 1e-6
# the ceilings that can tie, in the order of a vertex's ``tie_up`` row
TIE_CEILINGS = ("tiles", "waves_m", "waves_n")
CONFLICT = 1.04
OCC_DECAY = 0.5
BW_DECAY = 0.8

# realistic bounds DOpt clamps to
TECH_LO = dict(
    mem_wire_cap=[0.02] * 3, mem_wire_resist=[0.1] * 3,
    cell_read_latency=[0.01e-9, 0.05e-9, 1e-9], cell_access_device=[0.25] * 3,
    cell_read_power=[2e-4, 5e-4, 0.05], cell_leakage_power=[1e-6] * 3,
    cell_area=[0.01, 0.005, 1e-4], peripheral_node=[3.0] * 3,
    comp_wire_cap=[0.02] * 4, comp_wire_resist=[0.1] * 4, node=[3.0] * 4,
)
TECH_HI = dict(
    mem_wire_cap=[1.0] * 3, mem_wire_resist=[10.0] * 3,
    cell_read_latency=[5e-9, 5e-9, 100e-9], cell_access_device=[4.0] * 3,
    cell_read_power=[0.05, 0.2, 20.0], cell_leakage_power=[0.05] * 3,
    cell_area=[2.0, 1.0, 0.05], peripheral_node=[90.0] * 3,
    comp_wire_cap=[1.0] * 4, comp_wire_resist=[10.0] * 4, node=[90.0] * 4,
)
ARCH_LO = dict(
    sys_arr_x=4.0, sys_arr_y=4.0, sys_arr_n=1.0, vect_width=8.0, vect_n=1.0,
    mtree_x=4.0, mtree_y=1.0, mtree_tile_x=1.0, mtree_tile_y=1.0, fpu_n=1.0,
    frequency=0.2e9, capacity=[2.0**16, 2.0**20, 2.0**30],
    bank_size=[2.0**12, 2.0**14, 2.0**19], n_read_ports=[1.0] * 3, bw_scale=[0.25] * 3,
)
ARCH_HI = dict(
    sys_arr_x=1024.0, sys_arr_y=1024.0, sys_arr_n=64.0, vect_width=4096.0, vect_n=128.0,
    mtree_x=1024.0, mtree_y=256.0, mtree_tile_x=64.0, mtree_tile_y=64.0, fpu_n=512.0,
    frequency=3e9, capacity=[64 * 2.0**20, 512 * 2.0**20, 256 * 2.0**30],
    bank_size=[2.0**20, 2.0**23, 2.0**26], n_read_ports=[64.0] * 3, bw_scale=[16.0] * 3,
)


def param_names() -> list[str]:
    """Names of the flattened (tech, arch) parameter vector, in the order the
    program's attribution names them."""
    names = []
    for f in TECH_FIELDS:
        cls = COMP if f in ("comp_wire_cap", "comp_wire_resist", "node") else MEM
        names += [f"tech.{c}.{f}" for c in cls]
    for f in ARCH_FIELDS:
        if f in ("capacity", "bank_size", "n_read_ports", "bw_scale"):
            names += [f"arch.{c}.{f}" for c in MEM]
        else:
            names.append(f"arch.{f}")
    return names


def cast(tree: dict, dtype) -> dict:
    return {
        k: jnp.asarray(v if isinstance(v, jax.Array) else np.asarray(v, np.float64), dtype)
        for k, v in tree.items()
    }


def ste(hard, soft):
    return soft + jax.lax.stop_gradient(hard - soft)


def ceil_ste(x):
    return ste(jnp.ceil(x), x)


def gate_below(x, thresh, tau=0.1):
    hard = (x < thresh).astype(x.dtype)
    soft = jax.nn.sigmoid((thresh - x) / (tau * jnp.abs(thresh) + 1e-30))
    return ste(hard, soft)


def hardware(tech: dict, arch: dict, mem_type, dtype) -> dict:
    """The concrete hardware metrics of one design point (DGen)."""
    c = lambda x: jnp.asarray(np.asarray(x, np.float64), dtype)
    tw = c(np.eye(3)[[MEM_TYPES.index(t) for t in mem_type]])  # [3 mem, 3 types]
    pick = lambda table: jnp.sum(tw * c(table)[None, :], axis=1)

    # compute templates
    s = tech["node"] / 40.0
    add_d, mul_d, ff_d = (c(PRIM_DELAY[i]) * s for i in range(3))
    add_e, mul_e, ff_e = (c(PRIM_ENERGY[i]) * s * s for i in range(3))
    add_a, mul_a, ff_a = (c(PRIM_AREA[i]) * s * s for i in range(3))
    pe_side = jnp.sqrt(mul_a + add_a + 3 * ff_a)
    wire_d = 0.5 * tech["comp_wire_resist"] * tech["comp_wire_cap"] * 1e-15 * pe_side**2
    wire_e = tech["comp_wire_cap"] * pe_side * 1e-15 * VDD**2
    macs = jnp.stack([
        arch["sys_arr_x"] * arch["sys_arr_y"] * arch["sys_arr_n"],
        arch["vect_width"] * arch["vect_n"],
        arch["mtree_x"] * arch["mtree_y"] * arch["mtree_tile_x"] * arch["mtree_tile_y"],
        arch["fpu_n"],
    ])
    flops_per_cycle = 2.0 * macs
    depth = jnp.log2(jnp.maximum(arch["mtree_x"], 2.0))
    crit = jnp.stack([
        mul_d[0] + ff_d[0] + wire_d[0],
        mul_d[1] + add_d[1] + wire_d[1],
        mul_d[2] + add_d[2] + wire_d[2] * depth,
        2.0 * (mul_d[3] + add_d[3]),
    ])
    e_mac = jnp.stack([
        mul_e[0] + add_e[0] + 3 * ff_e[0] + wire_e[0],
        mul_e[1] + add_e[1] + 2 * ff_e[1] + wire_e[1],
        mul_e[2] + add_e[2] + ff_e[2] + wire_e[2],
        2.0 * (mul_e[3] + add_e[3]) + 4 * ff_e[3],
    ])
    a_mac = jnp.stack([
        mul_a[0] + add_a[0] + 3 * ff_a[0],
        mul_a[1] + add_a[1] + 2 * ff_a[1],
        mul_a[2] + add_a[2] + ff_a[2],
        4.0 * (mul_a[3] + add_a[3]),
    ])
    comp_area = macs * a_mac * 1e-6 * 1.2
    comp_leakage = LEAK_LOGIC_REF * comp_area * jnp.sqrt(40.0 / tech["node"])

    # memories
    bits = arch["capacity"] * 8.0
    bank_bits = arch["bank_size"] * 8.0
    n_banks = jnp.maximum(bits / bank_bits, 1.0)
    side = jnp.sqrt(bank_bits * tech["cell_area"])
    global_wire = jnp.sqrt(n_banks) * side
    rc = 0.5 * tech["mem_wire_resist"] * tech["mem_wire_cap"] * 1e-15
    periph_node = tech["peripheral_node"] / 40.0
    read_latency = (
        tech["cell_read_latency"] / jnp.maximum(tech["cell_access_device"], 1e-3)
        + rc * side**2 + rc * global_wire**2 + pick(PERIPH_DELAY_REF) * periph_node
    )
    write_latency = read_latency * pick(WRITE_LAT_MULT)
    bw_scale = jnp.maximum(arch["bw_scale"], 1e-3)
    wire_e_bit = tech["mem_wire_cap"] * (side + global_wire) * 1e-15 * VDD**2 * jnp.sqrt(bw_scale)
    read_energy_pb = 8.0 * (tech["cell_read_power"] * 1e-12 + wire_e_bit)
    write_energy_pb = read_energy_pb * pick(WRITE_EN_MULT)
    overhead = pick(PERIPH_OVERHEAD) * periph_node
    mem_area = bits * tech["cell_area"] * 1e-6 * (1.0 + overhead) * (1.0 + 0.10 * (bw_scale - 1.0))
    mem_leakage = (
        tech["cell_leakage_power"] * 1e-9 * bits
        + pick(LEAK_PERIPH_REF) * mem_area * overhead * jnp.sqrt(40.0 / tech["peripheral_node"])
    )
    # localMem's ports replicate with the PE fabric: one per 8 MACs
    local_ports = jnp.maximum(jnp.sum(macs) / 8.0, 1.0)
    port_scale = jnp.stack([local_ports, c(1.0), c(1.0)])
    row_bytes = jnp.sqrt(bank_bits) / 8.0
    mem_bw = arch["n_read_ports"] * port_scale * row_bytes / read_latency * bw_scale

    frequency = jnp.minimum(arch["frequency"], 1.0 / jnp.max(crit))
    return dict(
        read_latency=read_latency, write_latency=write_latency,
        read_energy_pb=read_energy_pb, write_energy_pb=write_energy_pb,
        mem_leakage=mem_leakage, mem_area=mem_area, mem_bw=mem_bw,
        capacity=arch["capacity"], flops_per_cycle=flops_per_cycle,
        energy_per_flop=e_mac / 2.0, comp_leakage=comp_leakage, comp_area=comp_area,
        sys_x=arch["sys_arr_x"], sys_y=arch["sys_arr_y"], frequency=frequency,
    )


def ceil_tie(x, up):
    """Straight-through ceiling of ``x``, and whether ``x`` is a tie (within
    ``TIE_REL`` of a whole number r >= 1).  At a tie ``up`` may fix the
    result: 0 takes r, 1 takes r + 1; -1 takes the ceiling."""
    r = jnp.round(x)
    tie = (jnp.abs(x - r) <= TIE_REL * r) & (r >= 1)
    return ste(jnp.where(tie & (up >= 0), r + up, jnp.ceil(x)), x), tie


def _walk(hw: dict, graph: dict):
    """Map the vertices in topological order, one at a time, threading the
    globalBuf occupancy and the bandwidth-utilization average.  A vertex's
    row of ``graph["tie_up"]`` may fix each of its ``TIE_CEILINGS`` at a tie
    (see ``ceil_tie``; the default -1 takes the ceilings).  Also returns,
    per vertex, which of those ceilings tie."""
    freq = hw["frequency"]
    bw = hw["mem_bw"]
    cap = hw["capacity"][GBUF]
    rate = jnp.maximum(hw["flops_per_cycle"], 1e-9)
    lat = hw["read_latency"] + hw["write_latency"]

    def vertex(carry, v):
        occ, bw_avg = carry
        n_comp, n_read, n_write, n_alloc, dims, up = v
        tiles, tie_t = ceil_tie(n_alloc[GBUF] / (cap * HEADROOM), up[0])
        tiles = jnp.maximum(tiles, 1.0)
        m, n, k = dims[0], dims[1], dims[2]
        waves_m, tie_m = ceil_tie(jnp.maximum(m / tiles, 1.0) / hw["sys_x"], up[1])
        waves_n, tie_n = ceil_tie(jnp.maximum(n, 1.0) / hw["sys_y"], up[2])
        waves = waves_m * waves_n
        cyc_tile = waves * (ceil_ste(jnp.maximum(k, 1.0)) + hw["sys_x"] + hw["sys_y"])
        ops_tile = n_comp[SYS] / tiles
        cyc_tile = jnp.maximum(cyc_tile, ops_tile / rate[SYS])
        t_sys = jnp.where(ops_tile > 0, tiles * cyc_tile / freq, 0.0)
        t_other = jnp.max((n_comp / (rate * freq))[1:])
        t_comp = jnp.maximum(t_other, t_sys)

        t_lvl = (n_read + n_write) / bw * CONFLICT
        t_onchip = jnp.maximum(t_lvl[GBUF] + tiles * lat[GBUF], t_lvl[LOCAL])
        t_main = t_lvl[MAIN] + tiles * lat[MAIN] * (n_alloc[MAIN] > 0)
        t_core = jnp.maximum(t_comp, t_onchip)

        bw_ok = gate_below(bw_avg, HEADROOM)
        prefetch = gate_below(occ + n_alloc[GBUF] / tiles, cap * HEADROOM) * bw_ok
        hide = jnp.maximum(prefetch, bw_ok)
        t_exposed = jnp.maximum(t_main - hide * t_core, 0.0)
        active = (jnp.sum(n_comp) + jnp.sum(n_read) + jnp.sum(n_write) + jnp.sum(n_alloc)) > 0
        t_vertex = tiles * ceil_ste((t_core + t_exposed) * freq / tiles) / freq * active

        # the demanded time divides by max(tiles, 1), as the model states it:
        # the same value, but at tiles == 1 it halves the tile count's gradient
        t_full = tiles * ceil_ste((t_core + t_main) * freq / jnp.maximum(tiles, 1.0)) / freq
        used_bw = jnp.where(
            t_full > 0,
            (n_read[GBUF] + n_write[GBUF]) / jnp.maximum(t_full, 1e-30) / bw[GBUF], 0.0,
        )
        carry = (
            jnp.minimum(OCC_DECAY * occ + n_alloc[GBUF], cap),
            BW_DECAY * bw_avg + 0.2 * jnp.clip(used_bw, 0.0, 2.0),
        )
        ties = jnp.stack([tie_t, tie_m, tie_n]) & active
        return carry, (t_vertex, used_bw, t_lvl * active, ties)

    zero = jnp.zeros_like(freq)
    up = graph.get("tie_up", -jnp.ones_like(graph["dims"]))
    xs = (graph["n_comp"], graph["n_read"], graph["n_write"], graph["n_alloc"], graph["dims"], up)
    _, out = jax.lax.scan(vertex, (zero, zero), xs)
    return out


def simulate(tech: dict, arch: dict, mem_type, graph: dict, dtype) -> dict:
    """Everything a simulate report states, for one workload on one design."""
    hw = hardware(tech, arch, mem_type, dtype)
    time_v, used_bw, t_lvl, ties = _walk(hw, graph)
    freq = hw["frequency"]
    cycles_v = time_v * freq
    cycles = jnp.sum(cycles_v)
    runtime = cycles / freq
    reads = jnp.sum(graph["n_read"], 0)
    writes = jnp.sum(graph["n_write"], 0)
    flops = jnp.sum(graph["n_comp"], 0)
    e_level_dyn = reads * hw["read_energy_pb"] + writes * hw["write_energy_pb"]
    e_comp_dyn = flops * hw["energy_per_flop"]
    leak_w = jnp.sum(hw["mem_leakage"]) + jnp.sum(hw["comp_leakage"])
    energy_mem = jnp.sum(e_level_dyn)
    energy_comp = jnp.sum(e_comp_dyn)
    energy_leak = leak_w * runtime
    energy = energy_mem + energy_comp + energy_leak
    active = (
        jnp.sum(graph["n_comp"], -1) + jnp.sum(graph["n_read"], -1)
        + jnp.sum(graph["n_write"], -1) + jnp.sum(graph["n_alloc"], -1)
    ) > 0
    e_v = (
        graph["n_read"] @ hw["read_energy_pb"] + graph["n_write"] @ hw["write_energy_pb"]
        + graph["n_comp"] @ hw["energy_per_flop"]
    ) * active
    gbuf_util = jnp.sum(used_bw * cycles_v) / jnp.maximum(cycles, 1e-30)
    return dict(
        runtime_s=runtime, energy_j=energy,
        power_w=energy / jnp.maximum(runtime, 1e-30), edp=energy * runtime,
        cycles=cycles, energy_mem_j=energy_mem, energy_comp_j=energy_comp,
        energy_leak_j=energy_leak,
        area_mm2=jnp.sum(hw["mem_area"]) + jnp.sum(hw["comp_area"]),
        reads_bytes=reads, writes_bytes=writes,
        transfer_time_s=jnp.sum(t_lvl, 0),
        level_dynamic_energy_j=e_level_dyn,
        level_leakage_energy_j=hw["mem_leakage"] * runtime,
        bw_utilization=jnp.stack([0.0 * gbuf_util, gbuf_util, 0.0 * gbuf_util]),
        flops=flops, comp_dynamic_energy_j=e_comp_dyn,
        comp_leakage_energy_j=hw["comp_leakage"] * runtime,
        time_v=time_v, energy_v=e_v + leak_w * time_v, ties_v=ties,
    )


def from_log(z: dict, anchor: dict) -> dict:
    """``exp(z)``, except that a parameter still at its starting log value
    keeps its starting value exactly: ``exp(log(x))`` is not always ``x``,
    and the model has steps (ceilings, gates) that a last-bit change can
    cross.  The gradient is that of ``exp`` everywhere."""
    out = {}
    for k, v in z.items():
        x0, z0 = anchor[k]
        e = jnp.exp(v)
        out[k] = jnp.where(v == z0, x0, jax.lax.stop_gradient(e)) + (e - jax.lax.stop_gradient(e))
    return out


def anchor(p: dict) -> dict:
    return {k: (v, jnp.log(jnp.maximum(v, 1e-30))) for k, v in p.items()}


def log_objective(tz: dict, az: dict, mem_type, graphs: list, objective: str, dtype,
                  t_anchor: dict, a_anchor: dict):
    """Mean over workloads of log(objective), as a function of the log-space
    parameters (what explain differentiates and DOpt descends)."""
    tech = from_log(tz, t_anchor)
    arch = from_log(az, a_anchor)
    vals = []
    for g in graphs:
        r = simulate(tech, arch, mem_type, g, dtype)
        key = {"time": "runtime_s", "energy": "energy_j", "edp": "edp",
               "power": "power_w", "area": "area_mm2"}[objective]
        vals.append(jnp.log(r[key]))
    return sum(vals) / len(vals), r


def to_log(p: dict) -> dict:
    return {k: jnp.log(jnp.maximum(v, 1e-30)) for k, v in p.items()}


def elasticities(tech: dict, arch: dict, mem_type, graph: dict, objective: str, dtype):
    """d log(objective) / d log(parameter), flattened in ``param_names`` order."""
    tech, arch = cast(tech, dtype), cast(arch, dtype)
    ta, aa = anchor(tech), anchor(arch)
    graph = {k: jnp.asarray(v, dtype) for k, v in graph.items()}
    fn = lambda tz, az: log_objective(tz, az, mem_type, [graph], objective, dtype, ta, aa)[0]
    gt, ga = jax.grad(fn, argnums=(0, 1))(to_log(tech), to_log(arch))
    flat = [jnp.atleast_1d(gt[f]) for f in TECH_FIELDS] + [jnp.atleast_1d(ga[f]) for f in ARCH_FIELDS]
    return jnp.concatenate(flat)


def _adam(g, m, v, step, lr, b1=0.9, b2=0.999, eps=1e-8):
    """One Adam update.  The step count and the bias corrections are float32
    scalars whatever the parameters' type, as in the program."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - jnp.power(jnp.float32(b1), step))
    vh = v / (1 - jnp.power(jnp.float32(b2), step))
    return (-lr * mh / (jnp.sqrt(vh) + eps)).astype(g.dtype), m, v


def descend(tech: dict, arch: dict, mem_type, graph: dict, objective: str, steps: int,
            lr: float, dtype) -> dict:
    """DOpt over tech and arch jointly: ``steps`` epochs of log-space Adam,
    each clamped to the bounds.  Returns the per-epoch log objective
    (evaluated before that epoch's update), the final design's parameters
    and the first epoch's gradient."""
    tech, arch = cast(tech, dtype), cast(arch, dtype)
    ta, aa = anchor(tech), anchor(arch)
    tz, az = to_log(tech), to_log(arch)
    lo_t, hi_t = to_log(cast(TECH_LO, dtype)), to_log(cast(TECH_HI, dtype))
    lo_a, hi_a = to_log(cast(ARCH_LO, dtype)), to_log(cast(ARCH_HI, dtype))
    zeros = lambda d: {k: jnp.zeros_like(v) for k, v in d.items()}
    graph = {k: jnp.asarray(v, dtype) for k, v in graph.items()}
    fn = lambda tz, az: log_objective(tz, az, mem_type, [graph], objective, dtype, ta, aa)[0]
    vg = jax.value_and_grad(fn, argnums=(0, 1))

    def epoch(state, i):
        tz, az, mt, vt, ma, va = state
        val, (gt, ga) = vg(tz, az)
        step = (i + 1).astype(jnp.float32)
        new = []
        for z, g, m, v, lo, hi in ((tz, gt, mt, vt, lo_t, hi_t), (az, ga, ma, va, lo_a, hi_a)):
            z2, m2, v2 = {}, {}, {}
            for k in z:
                u, m2[k], v2[k] = _adam(g[k], m[k], v[k], step, jnp.asarray(lr, dtype))
                z2[k] = jnp.clip(z[k] + u, lo[k], hi[k])
            new.append((z2, m2, v2))
        (tz, mt, vt), (az, ma, va) = new
        return (tz, az, mt, vt, ma, va), val

    _, (gt, ga) = vg(tz, az)
    state = (tz, az, zeros(tz), zeros(tz), zeros(az), zeros(az))
    state, vals = jax.lax.scan(epoch, state, jnp.arange(steps))
    return dict(objective=vals, tech=from_log(state[0], ta), arch=from_log(state[1], aa),
                grad=dict(tech=gt, arch=ga))


# --------------------------------------------------------------------------- #
# population DSE (Session.frontier): seeding, mixes, member epochs, front, HV
# --------------------------------------------------------------------------- #

PARETO_METRICS = ("time", "energy", "area", "edp")
PENALTY_SHARPNESS = 8.0  # the budget penalty's softplus sharpness
HV_SAMPLES = 16384  # the hypervolume's quasi-Monte-Carlo sample (3+ objectives)


def seed_population(designs: list, n: int, key, sigma: float, dtype) -> tuple[dict, dict]:
    """``n`` start points, stacked on a leading member axis.  Member ``i``
    starts from ``designs[i % k]`` (``(tech, arch)`` dicts, the library's
    numbers); from the second pass over the ``k`` designs on, each parameter
    moves by log-space jitter ``sigma * N(0, 1)``, clamped into the bounds.
    The draws come from ``key`` as the population's definition makes them:
    the key splits into one for tech and one for arch, each into one key per
    parameter field (fields in their declared order), and each field draws
    one float32 normal array of shape ``[n, ...]``."""
    k = len(designs)
    pick = np.arange(n) % k
    jittered = np.arange(n) >= k
    kt, ka = jax.random.split(key)
    out = []
    for side, fields, lo, hi, kk in ((0, TECH_FIELDS, TECH_LO, TECH_HI, kt),
                                     (1, ARCH_FIELDS, ARCH_LO, ARCH_HI, ka)):
        tree = {}
        for f, kf in zip(fields, jax.random.split(kk, len(fields))):
            x = jnp.asarray(np.stack([np.asarray(d[side][f], np.float64) for d in designs])[pick], dtype)
            noise = sigma * jax.random.normal(kf, x.shape, jnp.float32).astype(dtype)
            llo = jnp.log(jnp.asarray(np.asarray(lo[f], np.float64), dtype))
            lhi = jnp.log(jnp.asarray(np.asarray(hi[f], np.float64), dtype))
            moved = jnp.exp(jnp.clip(jnp.log(x) + noise, llo, lhi))
            mask = jittered.reshape((n,) + (1,) * (x.ndim - 1))
            tree[f] = jnp.where(mask, moved, x)
        out.append(tree)
    return out[0], out[1]


def sample_objective_mixes(n: int, metrics, key, concentration: float) -> np.ndarray:
    """``[n, 4]`` weights over ``PARETO_METRICS``, one objective mix per
    member: the first ``len(metrics)`` members take the one-hot corners of
    the chosen metrics, the rest a float32 Dirichlet(``concentration``) draw
    from ``key`` over them."""
    idx = [PARETO_METRICS.index(m) for m in metrics]
    alpha = jnp.full((len(idx),), jnp.float32(concentration))
    draws = np.array(jax.random.dirichlet(key, alpha, (n,), jnp.float32), np.float64)
    k = min(n, len(idx))
    draws[:k] = np.eye(len(idx))[:k]
    w = np.zeros((n, len(PARETO_METRICS)))
    w[:, idx] = draws
    return w


def member_metrics(tz: dict, az: dict, mem_type, graph: dict, dtype):
    """One member's log metrics (``PARETO_METRICS`` order), area and power,
    at the log-space parameters ``tz``, ``az`` (one workload)."""
    tech = {k: jnp.exp(v) for k, v in tz.items()}
    arch = {k: jnp.exp(v) for k, v in az.items()}
    r = simulate(tech, arch, mem_type, graph, dtype)
    logm = jnp.stack([jnp.log(r["runtime_s"]), jnp.log(r["energy_j"]), jnp.log(r["area_mm2"]),
                      jnp.log(r["edp"])])
    return logm, r["area_mm2"], r["power_w"]


def member_loss(tz, az, mem_type, graph, weights, area_budget, power_budget, penalty_w, dtype):
    """The mixed objective: the weights' mix of the log metrics, plus
    ``penalty_w`` times the budget penalty, a softplus hinge on each
    budget's log violation (``softplus(s v) / s``)."""
    logm, area, power = member_metrics(tz, az, mem_type, graph, dtype)
    hinge = lambda v: jax.nn.softplus(PENALTY_SHARPNESS * v) / PENALTY_SHARPNESS
    penalty = hinge(jnp.log(area) - jnp.log(area_budget)) + hinge(jnp.log(power) - jnp.log(power_budget))
    return jnp.dot(weights, logm) + penalty_w * penalty, logm


def member_descend(tech: dict, arch: dict, mem_type, graph: dict, weights, area_budget, power_budget,
                   penalty_schedule, lr: float, dtype) -> dict:
    """One member's descent: per epoch, the mixed objective's value and
    gradient in log space, one Adam step on tech and arch, the clamp to the
    bounds, and, where the value or a gradient is not finite, the epoch
    undone (parameters and Adam state keep their last finite values).
    ``penalty_schedule`` gives each epoch's penalty weight.  Returns the
    per-epoch ``[value, log metrics]`` rows (before each epoch's update),
    the final log-space parameters, the final log metrics, area and power,
    and the first epoch's gradient."""
    c = lambda v: jnp.asarray(v, dtype)
    tz = {k: jnp.log(jnp.maximum(c(v), 1e-30)) for k, v in tech.items()}
    az = {k: jnp.log(jnp.maximum(c(v), 1e-30)) for k, v in arch.items()}
    lo_t, hi_t = to_log(cast(TECH_LO, dtype)), to_log(cast(TECH_HI, dtype))
    lo_a, hi_a = to_log(cast(ARCH_LO, dtype)), to_log(cast(ARCH_HI, dtype))
    graph = {k: c(v) for k, v in graph.items()}
    budgets = (c(weights), c(area_budget), c(power_budget))
    vg = jax.value_and_grad(
        lambda tz, az, pw: member_loss(tz, az, mem_type, graph, *budgets, pw, dtype),
        argnums=(0, 1), has_aux=True)
    zeros = lambda d: {k: jnp.zeros_like(v) for k, v in d.items()}

    def epoch(state, pw):
        tz, az, mt, vt, ma, va, step = state
        (val, logm), (gt, ga) = vg(tz, az, pw)
        ok = jnp.isfinite(val)
        for g in list(gt.values()) + list(ga.values()):
            ok = ok & jnp.all(jnp.isfinite(g))
        nstep = step + 1.0
        new = []
        for z, g, m, v, lo, hi in ((tz, gt, mt, vt, lo_t, hi_t), (az, ga, ma, va, lo_a, hi_a)):
            z2, m2, v2 = {}, {}, {}
            for k in z:
                u, m2[k], v2[k] = _adam(g[k], m[k], v[k], nstep, c(lr))
                z2[k] = jnp.clip(z[k] + u, lo[k], hi[k])
            new.append((z2, m2, v2))
        (tz2, mt2, vt2), (az2, ma2, va2) = new
        cand = (tz2, az2, mt2, vt2, ma2, va2, nstep)
        state = jax.tree.map(lambda n_, o_: jnp.where(ok, n_, o_), cand, state)
        return state, jnp.concatenate([val[None], logm])

    _, (gt, ga) = vg(tz, az, c(penalty_schedule[0]))
    state = (tz, az, zeros(tz), zeros(tz), zeros(az), zeros(az), jnp.float32(0.0))
    state, rows = jax.lax.scan(epoch, state, c(penalty_schedule))
    logm, area, power = member_metrics(state[0], state[1], mem_type, graph, dtype)
    return dict(history=rows, tech=state[0], arch=state[1], log_metrics=logm, area=area, power=power,
                grad=dict(tech=gt, arch=ga))


def non_dominated(points: np.ndarray, feasible: np.ndarray, block: int = 512) -> np.ndarray:
    """Which points lie on the front, all coordinates costs: a feasible
    point that no feasible point dominates (``a`` dominates ``b`` when it is
    nowhere worse and somewhere better).  Infeasible points neither join
    the front nor shadow a point.  O(n^2), in blocks of dominating rows."""
    pts = np.asarray(points, np.float64)
    feas = np.asarray(feasible, bool)
    cand = pts[feas]
    dominated = np.zeros(len(pts), bool)
    for s in range(0, len(cand), block):
        a = cand[s:s + block, None, :]
        b = pts[None, :, :]
        dominated |= np.any(np.all(a <= b, axis=-1) & np.any(a < b, axis=-1), axis=0)
    return feas & ~dominated


def hypervolume(points: np.ndarray, lo: np.ndarray, ref: np.ndarray, tie: float = 0.0) -> tuple:
    """The volume of the box ``[lo, ref]`` that ``points`` dominate (costs),
    as the range ``(least, most)`` that comparisons within ``tie`` (relative)
    of equal allow.  Two objectives: exact, as the area of the staircase.
    Three or more: the share of a fixed sample of the box that some point
    dominates, times the box's volume; the sample is ``HV_SAMPLES`` float32
    uniforms drawn from ``PRNGKey(0)``, mapped onto the box (a float32
    mapping may put a sample on either side of a point's coordinate)."""
    pts = np.atleast_2d(np.asarray(points, np.float64))
    lo, ref = np.asarray(lo, np.float64), np.asarray(ref, np.float64)
    if pts.shape[1] == 2:
        p = np.minimum(pts, ref)
        p = p[np.lexsort((p[:, 1], p[:, 0]))]
        best = np.minimum.accumulate(p[:, 1])
        prev = np.concatenate([[ref[1]], best[:-1]])
        hv = float(np.sum((ref[0] - p[:, 0]) * np.maximum(prev - best, 0.0)))
        return hv, hv
    u01 = np.asarray(jax.random.uniform(jax.random.PRNGKey(0), (HV_SAMPLES, pts.shape[1]), jnp.float32),
                     np.float64)
    u = np.maximum(lo, u01 * (ref - lo) + lo)
    slack = tie * np.abs(u)
    least, most = np.zeros(HV_SAMPLES, bool), np.zeros(HV_SAMPLES, bool)
    for s in range(0, len(pts), 256):
        p = pts[s:s + 256, None, :]
        least |= np.any(np.all(p <= u[None] - slack, axis=-1), axis=0)
        most |= np.any(np.all(p <= u[None] + slack, axis=-1), axis=0)
    box = float(np.prod(np.maximum(ref - lo, 0.0)))
    return box * least.mean(), box * most.mean()
