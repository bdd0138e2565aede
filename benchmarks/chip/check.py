"""The comparison that decides ``correct``: replies of the timed path against
the plain reference (``reference.py``), computed once the window has closed.

Numbers compared, each the widest gap over the sampled replies of a run:

* ``report_rel`` -- every scalar a report states (runtime, energy, power,
  EDP, cycles, the energy split, area; per memory level the bytes, demanded
  transfer time, dynamic and leakage energy and bandwidth utilization; per
  compute class the FLOPs and energies), as |program - reference| / |reference|;
* ``vertex_gap`` -- the per-vertex time and energy breakdown, as the largest
  |program - reference| over the workload's total runtime or energy;
* ``elasticity_gap`` -- explain replies: the largest |program - reference|
  elasticity d log(objective) / d log(parameter), over the largest
  reference elasticity of that reply;
* ``attribution_order`` -- explain replies: attributions that are missing,
  duplicated, or out of the |elasticity| order (exact: limit 0);
* ``history_gap`` -- optimize replies: the widest |log objective| gap over
  every epoch of the DOpt history;
* ``design_gap`` -- optimize replies: the final design (the reply's ``.dhd``
  text) against the reference's own descent, as the widest
  |log program - log reference| over the parameters that the reference
  moves (see ``moved``).

An optimize reply's two reports count in ``report_rel`` and ``vertex_gap``:
the baseline at the start design, and the optimized report at the final
design the reply states.  Where the argument of one of a vertex's ceilings
(its tile count, its two wave counts) lies within float32 rounding of a
whole number (``reference.ceil_tie``), either result is correct: the
reference takes, at each such vertex, the results that put its time nearest
the program's.

``program_readings`` computes them for the program's replies (with
``tie_vertices``, the count of tie vertices met, which no limit names), and
``control_readings`` for the control: the reference in bfloat16 in the
program's place.  Each traffic mix states its own limits
(``check.limits`` in its file), set from readings of the program and of the
control on the chip (``PERF.md`` gives them).
"""
from __future__ import annotations

import itertools
from functools import lru_cache
from pathlib import Path

import numpy as np

import reference as R

SCALARS = ("runtime_s", "energy_j", "power_w", "edp", "cycles", "energy_mem_j",
           "energy_comp_j", "energy_leak_j")
LEVEL = (("reads_bytes", "reads_bytes"), ("writes_bytes", "writes_bytes"),
         ("transfer_time_s", "transfer_time_s"), ("dynamic_energy_j", "level_dynamic_energy_j"),
         ("leakage_energy_j", "level_leakage_energy_j"), ("bw_utilization", "bw_utilization"))
COMPUTE = (("flops", "flops"), ("dynamic_energy_j", "comp_dynamic_energy_j"),
           ("leakage_energy_j", "comp_leakage_energy_j"))


def rel(p: float, r: float) -> float:
    if p == r:
        return 0.0
    return abs(p - r) / max(abs(r), 1e-300)


def report_view(rep) -> dict:
    """A SimReport's first workload as plain arrays, keyed like the reference."""
    w = rep.workloads[0]
    out = {k: float(getattr(w, k)) for k in SCALARS}
    out["area_mm2"] = float(rep.area_mm2)
    for a, b in LEVEL:
        out[b] = np.array([getattr(lv, a) for lv in w.levels], np.float64)
    for a, b in COMPUTE:
        out[b] = np.array([getattr(c, a) for c in w.compute], np.float64)
    out["time_v"] = np.array([v.time_s for v in w.vertices], np.float64)
    out["energy_v"] = np.array([v.energy_j for v in w.vertices], np.float64)
    return out


def report_gaps(view: dict, ref: dict) -> tuple[float, float]:
    """(report_rel, vertex_gap) of one report against the reference."""
    worst = 0.0
    for k in SCALARS + ("area_mm2",):
        worst = max(worst, rel(view[k], float(ref[k])))
    for _, k in LEVEL + COMPUTE:
        for p, r in zip(view[k], np.asarray(ref[k], np.float64)):
            worst = max(worst, rel(float(p), float(r)))
    tv = np.asarray(ref["time_v"], np.float64)
    ev = np.asarray(ref["energy_v"], np.float64)
    if view["time_v"].shape != tv.shape:
        return worst, float("inf")
    vgap = max(
        float(np.max(np.abs(view["time_v"] - tv))) / max(float(ref["runtime_s"]), 1e-300),
        float(np.max(np.abs(view["energy_v"] - ev))) / max(float(ref["energy_j"]), 1e-300),
    )
    return worst, vgap


DHD_ARCH = {"frequency": "frequency", "capacity": "capacity", "bank_size": "bank_size",
            "read_ports": "n_read_ports", "bw_scale": "bw_scale",
            ("systolicArray", "x"): "sys_arr_x", ("systolicArray", "y"): "sys_arr_y",
            ("systolicArray", "count"): "sys_arr_n", ("vector", "width"): "vect_width",
            ("vector", "count"): "vect_n", ("macTree", "x"): "mtree_x", ("macTree", "y"): "mtree_y",
            ("macTree", "tile_x"): "mtree_tile_x", ("macTree", "tile_y"): "mtree_tile_y",
            ("fpu", "count"): "fpu_n"}
DHD_TECH = {"wire_cap": "mem_wire_cap", "wire_resist": "mem_wire_resist",
            "cell_read_latency": "cell_read_latency", "cell_access_device": "cell_access_device",
            "cell_read_power": "cell_read_power", "cell_leakage_power": "cell_leakage_power",
            "cell_area": "cell_area", "peripheral_node": "peripheral_node"}
DHD_TECH_COMP = {"node": "node", "wire_cap": "comp_wire_cap", "wire_resist": "comp_wire_resist"}


def parse_dhd(text: str) -> tuple[dict, dict]:
    """(tech, arch) of a canonical ``.dhd`` text (every field explicit, base
    units), as float64 arrays keyed like the reference's parameters."""
    tech, arch = {}, {}
    path = []
    for line in text.splitlines():
        words = line.split()
        if not words:
            continue
        if words[-1] == "{":
            path.append(words[:-1])
            continue
        if words == ["}"]:
            path.pop()
            continue
        key, value = words[0], words[2]
        if key in ("enabled", "type"):
            continue
        block = path[-1]
        in_tech = any(p == ["tech"] for p in path)
        if block[0] == "arch":
            arch[DHD_ARCH[key]] = float(value)
        elif block[0] == "memory":
            i = R.MEM.index(block[1])
            field = DHD_TECH[key] if in_tech else DHD_ARCH[key]
            (tech if in_tech else arch).setdefault(field, [0.0] * 3)[i] = float(value)
        elif block[0] == "compute" and in_tech:
            tech.setdefault(DHD_TECH_COMP[key], [0.0] * 4)[R.COMP.index(block[1])] = float(value)
        elif block[0] == "compute":
            arch[DHD_ARCH[(block[1], key)]] = float(value)
    f64 = lambda d: {k: np.asarray(v, np.float64) for k, v in d.items()}
    return f64(tech), f64(arch)


def attribution_faults(rep) -> int:
    names = [a.parameter for a in rep.attribution]
    want = R.param_names()
    faults = len(set(want) ^ set(names)) + (len(names) - len(set(names)))
    mags = [abs(a.elasticity) for a in rep.attribution]
    faults += sum(1 for a, b in zip(mags, mags[1:]) if b > a)
    return faults


def load_graphs(config: dict) -> dict:
    """Each graph of the configuration, by name: the arrays of its file
    (``freeze_graphs.py`` wrote them), relative to ``config["dir"]``."""
    out = {}
    for g in config["graphs"]:
        with np.load(Path(config["dir"]) / g["file"]) as z:
            out[g["name"]] = {k: z[k] for k in z.files}
    return out


class Inputs:
    """The reference's inputs for a query: the graph's arrays, from the
    configuration's graph files, and the design's parameters,
    float32-rounded as the program holds them."""

    def __init__(self, config: dict):
        self.config = config
        keys = ("n_comp", "n_read", "n_write", "n_alloc", "dims")
        self.graphs = {
            name: dict({k: np.asarray(g[k], np.float64) for k in keys},
                       tie_up=-np.ones((len(g["n_alloc"]), len(R.TIE_CEILINGS)), np.float64))
            for name, g in load_graphs(config).items()
        }

    def design(self, d) -> tuple[dict, dict, tuple]:
        a = self.config["architectures"][d.base]
        f32 = lambda v: np.asarray(np.float32(v), np.float64)
        tech = {k: f32(v) for k, v in a["tech"].items()}
        arch = {k: f32(v) for k, v in a["arch"].items()}
        if d.param is not None:
            name, _, level = d.param.partition(".")
            if level:
                arch[name] = arch[name].copy()
                arch[name][R.MEM.index(level)] = d.value
            else:
                arch[name] = f32(d.value)
        return tech, arch, tuple(a["mem_type"])


@lru_cache(maxsize=None)
def _sim(mem_type: tuple, dtype_name: str):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype_name)
    return jax.jit(lambda t, a, g: R.simulate(t, a, mem_type, g, dt))


@lru_cache(maxsize=None)
def _descend(mem_type: tuple, dtype_name: str, steps: int, lr: float, objective: str):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype_name)
    return jax.jit(lambda t, a, g: R.descend(t, a, mem_type, g, objective, steps, lr, dt))


@lru_cache(maxsize=None)
def _grad(mem_type: tuple, dtype_name: str, objective: str):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype_name)
    return jax.jit(lambda t, a, g: R.elasticities(t, a, mem_type, g, objective, dt))


def _cast(tree: dict, dtype_name: str) -> dict:
    import jax.numpy as jnp

    return {k: jnp.asarray(np.asarray(v, np.float64), jnp.dtype(dtype_name)) for k, v in tree.items()}


def reference_report(inputs: Inputs, q, dtype_name: str = "float64", graph=None, design=None) -> dict:
    tech, arch, mem_type = inputs.design(q.design)
    if design is not None:
        tech, arch = design
    g = _cast(graph or inputs.graphs[q.graph], dtype_name)
    out = _sim(mem_type, dtype_name)(_cast(tech, dtype_name), _cast(arch, dtype_name), g)
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def tie_aware_report(inputs: Inputs, q, view: dict, design=None) -> tuple[dict, dict]:
    """The float64 reference report, with each tied ceiling
    (``reference.TIE_CEILINGS``) of each vertex taken the way that puts the
    vertex's time nearest the program's (``view``); and the graph with those
    choices."""
    graph = inputs.graphs[q.graph]
    base = reference_report(inputs, q, graph=graph, design=design)
    ties = base["ties_v"] > 0
    if not ties.any() or view["time_v"].shape != base["time_v"].shape:
        return base, graph
    kinds = [j for j in range(ties.shape[1]) if ties[:, j].any()]
    ups, times = [], []
    for bits in itertools.product((0.0, 1.0), repeat=len(kinds)):
        up = -np.ones(ties.shape)
        for j, bit in zip(kinds, bits):
            up[:, j] = np.where(ties[:, j], bit, -1.0)
        ups.append(up)
        times.append(reference_report(inputs, q, graph=dict(graph, tie_up=up), design=design)["time_v"])
    nearest = np.argmin(np.abs(np.stack(times) - view["time_v"]), axis=0)
    graph = dict(graph, tie_up=np.stack(ups)[nearest, np.arange(len(nearest))])
    return reference_report(inputs, q, graph=graph, design=design), graph


def reference_elasticities(inputs: Inputs, q, dtype_name: str = "float64", graph=None) -> np.ndarray:
    tech, arch, mem_type = inputs.design(q.design)
    g = _cast(graph or inputs.graphs[q.graph], dtype_name)
    fn = _grad(mem_type, dtype_name, q.call.get("objective", "edp"))
    return np.asarray(fn(_cast(tech, dtype_name), _cast(arch, dtype_name), g), np.float64)


def elasticity_gap(program: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(np.abs(program - ref)) / max(float(np.max(np.abs(ref))), 1e-300))


def reference_descent(inputs: Inputs, q, dtype_name: str = "float64") -> dict:
    """The reference's DOpt over the call's epochs: its per-epoch log
    objective, the final design's parameters and the first epoch's gradient."""
    tech, arch, mem_type = inputs.design(q.design)
    g = _cast(inputs.graphs[q.graph], dtype_name)
    fn = _descend(mem_type, dtype_name, int(q.call["steps"]), float(q.call["lr"]),
                  q.call.get("objective", "edp"))
    out = fn(_cast(tech, dtype_name), _cast(arch, dtype_name), g)
    return jax_tree_f64(out)


def jax_tree_f64(tree):
    if isinstance(tree, dict):
        return {k: jax_tree_f64(v) for k, v in tree.items()}
    return np.asarray(tree, np.float64)


def moved(descent: dict) -> dict:
    """Per parameter (``tech.<field>`` / ``arch.<field>``), a mask of the
    elements the reference's descent moves: those whose first-epoch gradient
    is at least a thousandth of the median element's.  An element with a
    gradient nought to rounding moves under Adam by round-off alone."""
    g = {f"{side}.{k}": np.abs(np.atleast_1d(v)) for side in ("tech", "arch")
         for k, v in descent["grad"][side].items()}
    med = float(np.median(np.concatenate(list(g.values()))))
    return {k: v >= 1e-3 * med for k, v in g.items()}


def design_gap(program: tuple, descent: dict, mask: dict) -> float:
    """Widest |log program - log reference| over the final design's moved
    parameters."""
    worst = 0.0
    for side, prog in zip(("tech", "arch"), program):
        for k, v in prog.items():
            m = mask[f"{side}.{k}"]
            d = np.abs(np.log(np.atleast_1d(v)) - np.log(np.atleast_1d(descent[side][k])))
            if m.any():
                worst = max(worst, float(np.max(d[m])))
    return worst


def _widest(out: dict, key: str, value: float) -> None:
    out[key] = max(out.get(key, 0.0), value if np.isfinite(value) else float("inf"))


def program_readings(inputs: Inputs, pairs: list) -> dict:
    """The numbers compared, over ``pairs`` of (query, program result)."""
    out = {}
    for q, res in pairs:
        rep = res.baseline if q.kind == "optimize" else res
        view = report_view(rep)
        ref, graph = tie_aware_report(inputs, q, view)
        out["tie_vertices"] = out.get("tie_vertices", 0) + int(np.sum(np.any(graph["tie_up"] >= 0, axis=1)))
        r, v = report_gaps(view, ref)
        _widest(out, "report_rel", r)
        _widest(out, "vertex_gap", v)
        if q.kind == "explain":
            out["attribution_order"] = out.get("attribution_order", 0) + attribution_faults(rep)
            got = {a.parameter: a.elasticity for a in rep.attribution}
            prog = np.array([got.get(n, np.nan) for n in R.param_names()], np.float64)
            _widest(out, "elasticity_gap", elasticity_gap(prog, reference_elasticities(inputs, q, graph=graph)))
        if q.kind == "optimize":
            descent = reference_descent(inputs, q)
            hist = np.log(np.asarray(res.objective_history, np.float64))
            same = hist.shape == descent["objective"].shape
            gap = float(np.max(np.abs(hist - descent["objective"]))) if same else float("inf")
            _widest(out, "history_gap", gap)
            final = parse_dhd(res.dhd)
            _widest(out, "design_gap", design_gap(final, descent, moved(descent)))
            opt_view = report_view(res.optimized)
            ref, _ = tie_aware_report(inputs, q, opt_view, design=final)
            r, v = report_gaps(opt_view, ref)
            _widest(out, "report_rel", r)
            _widest(out, "vertex_gap", v)
    return out


def control_readings(inputs: Inputs, queries: list, dtype_name: str = "bfloat16") -> dict:
    """The same numbers with the reference in ``dtype_name`` in the program's
    place (the control that the limits must refuse)."""
    out = {}
    for q in queries:
        ref = reference_report(inputs, q)
        low = reference_report(inputs, q, dtype_name)
        view = {k: (float(v) if np.ndim(v) == 0 else np.asarray(v, np.float64)) for k, v in low.items()}
        r, v = report_gaps(view, ref)
        out["report_rel"] = max(out.get("report_rel", 0.0), r)
        out["vertex_gap"] = max(out.get("vertex_gap", 0.0), v)
        if q.kind == "explain":
            _widest(out, "elasticity_gap", elasticity_gap(reference_elasticities(inputs, q, dtype_name),
                                                          reference_elasticities(inputs, q)))
        if q.kind == "optimize":
            lo, hi = reference_descent(inputs, q, dtype_name), reference_descent(inputs, q)
            _widest(out, "history_gap", float(np.max(np.abs(lo["objective"] - hi["objective"]))))
            final = (lo["tech"], lo["arch"])
            _widest(out, "design_gap", design_gap(final, hi, moved(hi)))
            low = reference_report(inputs, q, dtype_name, design=final)
            view = {k: (float(v) if np.ndim(v) == 0 else np.asarray(v, np.float64)) for k, v in low.items()}
            r, v = report_gaps(view, reference_report(inputs, q, design=final))
            _widest(out, "report_rel", r)
            _widest(out, "vertex_gap", v)
    return out


def judge(readings: dict, lim: dict) -> tuple[bool, dict]:
    """Each reading the cell's limits name, beside its limit; a cell leaves
    out a number that no limit separates on it (``PERF.md`` says which)."""
    checks = {k: {"value": v, "limit": lim[k]} for k, v in readings.items() if k in lim}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
