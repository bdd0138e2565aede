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

A frontier reply (``Session.frontier``, the population DSE) is judged on:

* ``history_gap`` -- a seeded sample of members (``frontier_members``): each
  member's median, over the epochs, of the widest gap in its per-epoch
  ``[value, log metrics]`` row against the reference's own descent; the
  widest over the members.  A median, because the metrics step at the
  model's ceilings: where a member's path runs along a step, float32 and
  float64 put it on either side for an epoch, and its row then jumps by
  the step (0.37 in log time, once in 96 sampled calls; ``PERF.md``);
* ``member_gap`` -- those members' final log-space parameters against the
  reference's, over the parameters the reference moves (``moved``);
* ``final_gap`` -- those members' final log metrics, as the reply states
  them, against the reference's evaluation at the member's final design in
  the reply, as the widest |log| gap (a ceiling that ties there takes the
  program's side, as at the start design);
* ``front_mismatch`` -- the members whose place on the front (the reply's
  front and its feasible count) differs from the plain filter's over the
  program's own final metrics; a member whose area or power lies within
  ``TIE_REL`` of its budget limit keeps the program's feasibility;
* ``hv_rel`` -- the reply's hypervolume against the plain one of that
  front, in the box the call asked for, as a relative gap;
* ``winner_gap`` -- each winner's ``.dhd`` text against that member's final
  design in the reply, as the widest |log| gap (float32 as the program
  holds it: exact).

The last three hold the program to its own final metrics and designs, so
the control (below) gives them no reading: its precision cannot move them.

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

import dataclasses
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


FRONTIER_MEMBERS = 16  # members of a frontier reply the reference follows


def host_result(q, res):
    """The reply as the check reads it, all on the host: a frontier reply's
    final population (device arrays) is copied to numpy, by field name, so
    the reference can run once the program's state is freed.  Other
    replies are returned as they are."""
    if q.kind != "frontier":
        return res
    fields = lambda tree: {f.name: np.asarray(getattr(tree, f.name), np.float64)
                           for f in dataclasses.fields(tree)}
    raw = dataclasses.replace(res.raw, tech=fields(res.raw.tech), arch=fields(res.raw.arch))
    return dataclasses.replace(res, raw=raw)


def frontier_members(q, n: int = FRONTIER_MEMBERS) -> np.ndarray:
    """The members of a frontier call that the reference follows: the first
    member of each seed design (the library design itself), then members
    drawn from the call's key, its place in the window and the run's seed
    (``Query.draw``), so that runs of one call follow different members."""
    k, p = len(q.call["seeds"]), int(q.call["population"])
    rest = np.random.default_rng([int(q.call["key"]), q.draw, q.qid, 1]).choice(
        np.arange(k, p), size=min(n - k, p - k), replace=False)
    return np.sort(np.concatenate([np.arange(k), rest]))


class Library:
    """A seed design by its library name, as ``Inputs.design`` takes it."""

    def __init__(self, name: str):
        self.base, self.param, self.value = name, None, None


def reference_frontier(inputs: Inputs, q, members: np.ndarray, dtype_name: str = "float64",
                       start_rows: np.ndarray | None = None) -> dict:
    """The reference's descent of ``members`` of a frontier call: the
    population seeded from the configuration's library designs and the
    call's key, each member's objective mix, and the budget penalty's
    geometric ramp, as the call states them.  With ``start_rows`` (the
    program's first-epoch ``[value, log metrics]`` row of each member), a
    ceiling that ties at a member's start design (``start_ties``) takes the
    side nearer the program's row."""
    import jax

    c = q.call
    designs = [inputs.design(Library(s)) for s in c["seeds"]]
    mem_type = designs[0][2]
    pop = int(c["population"])
    k_seed, k_mix = jax.random.split(jax.random.PRNGKey(int(c["key"])))
    tech, arch = R.seed_population([d[:2] for d in designs], pop, k_seed, float(c["sigma"]), np.float64)
    tech, arch = ({k: np.asarray(v, np.float64)[members] for k, v in t.items()} for t in (tech, arch))
    weights = R.sample_objective_mixes(pop, c["metrics"], k_mix, float(c["concentration"]))[members]
    w0, w1 = c["penalty_weight"]
    ramp = np.geomspace(max(w0, 1e-6), max(w1, 1e-6), int(c["steps"]))
    graph = inputs.graphs[q.graph]
    ups = np.stack([graph["tie_up"]] * len(members))
    if start_rows is not None:
        for j in range(len(members)):
            ups[j], _ = start_ties(_member(tech, j), _member(arch, j), mem_type, graph, start_rows[j, 1:])
    f32 = lambda x: float(np.float32(x))
    fn = _frontier_descend(mem_type, dtype_name, float(c["lr"]))
    out = fn(_cast(tech, "float64"), _cast(arch, "float64"), _cast(dict(graph, tie_up=ups), "float64"),
             weights, f32(c["area_budget"]), f32(c["power_budget"]), ramp)
    return jax_tree_f64(out)


def start_ties(tech: dict, arch: dict, mem_type: tuple, graph: dict, logm: np.ndarray) -> tuple:
    """A member's ``tie_up`` rows: where a ceiling ties at the design
    (``reference.ceil_tie``), the choice, made jointly for each kind of
    ceiling, whose log metrics lie nearest the program's ``logm``; -1
    elsewhere.  And the widest |log| gap of those metrics to ``logm``.  The
    program starts from ``exp(log(x))`` in float32, which lands on either
    side of a design's whole-number quotients."""
    ties = np.asarray(_sim(mem_type, "float64")(_cast(tech, "float64"), _cast(arch, "float64"),
                                                _cast(graph, "float64"))["ties_v"]) > 0
    kinds = [j for j in range(ties.shape[1]) if ties[:, j].any()]
    tz = _cast({k: np.log(v) for k, v in tech.items()}, "float64")
    az = _cast({k: np.log(v) for k, v in arch.items()}, "float64")
    best, best_gap = -np.ones(ties.shape), float("inf")
    for bits in itertools.product((0.0, 1.0), repeat=len(kinds)):
        up = -np.ones(ties.shape)
        for j, bit in zip(kinds, bits):
            up[:, j] = np.where(ties[:, j], bit, -1.0)
        got = _start_metrics(mem_type)(tz, az, _cast(dict(graph, tie_up=up), "float64"))
        gap = float(np.max(np.abs(np.asarray(got, np.float64) - logm)))
        if gap < best_gap:
            best, best_gap = up, gap
    return best, best_gap


@lru_cache(maxsize=None)
def _start_metrics(mem_type: tuple):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda tz, az, g: R.member_metrics(tz, az, mem_type, g, jnp.float64)[0])


@lru_cache(maxsize=None)
def _frontier_descend(mem_type: tuple, dtype_name: str, lr: float):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype_name)
    one = lambda t, a, g, w, ab, pb, s: R.member_descend(t, a, mem_type, g, w, ab, pb, s, lr, dt)
    graph_axes = {k: 0 if k == "tie_up" else None for k in ("n_comp", "n_read", "n_write", "n_alloc",
                                                            "dims", "tie_up")}
    return jax.jit(jax.vmap(one, in_axes=(0, 0, graph_axes, 0, None, None, None)))


def _member(tree: dict, j: int) -> dict:
    return {k: v[j] for k, v in tree.items()}


def member_gaps(prog_hist: np.ndarray, prog_final: tuple, prog_logm: np.ndarray, ref: dict,
                mem_type: tuple, graph: dict) -> dict:
    """``history_gap``, ``member_gap`` and ``final_gap`` of followed members:
    the program's ``[epoch, member, 5]`` rows, final ``(tech, arch)`` values
    and final log metrics against the reference's descent
    (``reference_frontier``) and its evaluation at those final designs."""
    hist = np.swapaxes(np.asarray(ref["history"], np.float64), 0, 1)  # [epoch, member, 5]
    if prog_hist.shape == hist.shape:
        hgap = float(np.max(np.median(np.max(np.abs(prog_hist - hist), axis=2), axis=0)))
    else:
        hgap = float("inf")
    mgap = fgap = 0.0
    for j in range(hist.shape[1]):
        descent = {"grad": {s: _member(ref["grad"][s], j) for s in ("tech", "arch")},
                   "tech": {k: np.exp(v) for k, v in _member(ref["tech"], j).items()},
                   "arch": {k: np.exp(v) for k, v in _member(ref["arch"], j).items()}}
        final = tuple(_member(t, j) for t in prog_final)
        mgap = max(mgap, design_gap(final, descent, moved(descent)))
        fgap = max(fgap, start_ties(*final, mem_type, graph, np.asarray(prog_logm[j], np.float64))[1])
    return {"history_gap": hgap if np.isfinite(hgap) else float("inf"), "member_gap": mgap,
            "final_gap": fgap if np.isfinite(fgap) else float("inf")}


def frontier_readings(inputs: Inputs, q, res) -> dict:
    """The numbers compared for one frontier reply (``host_result``)."""
    c, raw = q.call, res.raw
    members = frontier_members(q)
    hist = np.asarray(raw.history, np.float64)[:, members]
    ref = reference_frontier(inputs, q, members, start_rows=hist[0])
    final = tuple({k: v[members] for k, v in tree.items()} for tree in (raw.tech, raw.arch))
    out = member_gaps(hist, final, np.asarray(raw.log_metrics, np.float64)[members], ref,
                      inputs.design(Library(c["seeds"][0]))[2], inputs.graphs[q.graph])

    # the front: the plain filter over the program's own final metrics
    cols, (lim_a, lim_p), (lo, hi) = _frontier_terms(c)
    logm = np.asarray(raw.log_metrics, np.float64)
    area, power = np.asarray(raw.area, np.float64), np.asarray(raw.power, np.float64)
    feasible = np.isfinite(logm).all(axis=1) & (area <= lim_a) & (power <= lim_p)
    tie = (np.abs(area - lim_a) <= R.TIE_REL * lim_a) | (np.abs(power - lim_p) <= R.TIE_REL * lim_p)
    feasible = np.where(tie, np.asarray(raw.feasible, bool), feasible)
    front = R.non_dominated(logm[:, cols], feasible)
    shown = {int(p.index) for p in res.front}
    out["front_mismatch"] = (len(shown ^ set(np.nonzero(front)[0].tolist()))
                             + abs(int(res.feasible) - int(feasible.sum())))

    # the hypervolume of that front, in the box the call asked for; a sample
    # within TIE_REL of a point's coordinate may count either way
    least, most = R.hypervolume(logm[front][:, cols], lo, hi, R.TIE_REL) if front.any() else (0.0, 0.0)
    hv = float(res.hypervolume)
    out["hv_rel"] = float(rel(hv, min(max(hv, least), most)))

    # each winner as the reply states it: its .dhd and its headline numbers
    worst = 0.0
    for p in res.front:
        i = int(p.index)
        tech, arch = parse_dhd(p.dhd)
        for got, want in ((tech, raw.tech), (arch, raw.arch)):
            if set(got) != set(want):
                return dict(out, winner_gap=float("inf"))
            for k, v in got.items():
                g = np.asarray(np.float32(v), np.float64)
                worst = max(worst, float(np.max(np.abs(np.log(g) - np.log(want[k][i])))))
        shown_m = (p.time_s, p.energy_j, p.edp, p.area_mm2, p.power_w)
        want_m = (np.exp(raw.log_metrics[i, 0]), np.exp(raw.log_metrics[i, 1]),
                  np.exp(raw.log_metrics[i, 3]), raw.area[i], raw.power[i])
        for a, b in zip(shown_m, want_m):
            worst = max(worst, abs(float(np.log(a)) - float(np.log(np.float64(b)))))
    out["winner_gap"] = worst
    return out


def _frontier_terms(c: dict) -> tuple:
    """A frontier call's metric columns (``PARETO_METRICS`` indices), its
    area and power limits (the budgets, float32 as the program holds them,
    times 1 + ``budget_tol``) and its hypervolume box (float32)."""
    tol = 1.0 + float(c["budget_tol"])
    return ([R.PARETO_METRICS.index(m) for m in c["metrics"]],
            (np.float32(c["area_budget"]) * tol, np.float32(c["power_budget"]) * tol),
            tuple(np.asarray(np.asarray(b, np.float32), np.float64) for b in c["hv_box"]))


def _widest(out: dict, key: str, value: float) -> None:
    out[key] = max(out.get(key, 0.0), value if np.isfinite(value) else float("inf"))


def program_readings(inputs: Inputs, pairs: list) -> dict:
    """The numbers compared, over ``pairs`` of (query, program result)."""
    out = {}
    for q, res in pairs:
        if q.kind == "frontier":
            for k, v in frontier_readings(inputs, q, res).items():
                if k == "front_mismatch":
                    out[k] = out.get(k, 0) + v
                else:
                    _widest(out, k, v)
            continue
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
        if q.kind == "frontier":
            members = frontier_members(q)
            lo, hi = reference_frontier(inputs, q, members, dtype_name), reference_frontier(inputs, q, members)
            final = tuple({k: np.exp(v) for k, v in lo[s].items()} for s in ("tech", "arch"))
            gaps = member_gaps(np.swapaxes(lo["history"], 0, 1), final, lo["log_metrics"], hi,
                               inputs.design(Library(q.call["seeds"][0]))[2], inputs.graphs[q.graph])
            for k, v in gaps.items():
                _widest(out, k, v)
            # hv_rel, front_mismatch and winner_gap hold the program to its
            # own final metrics; the control's precision cannot move them
            continue
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
