"""DOpt — the hardware optimizer (paper §7, Appendix A/B).

Gradient descent on the *joint* space of technology and architectural
parameters, through the differentiable mapper.  One forward (simulate) +
backward (grad) = one epoch (paper §7).  Features:

  * objectives: time / energy / edp / power, optional area constraint
    F = obj * e^(a-A) (paper §11.3 / Appendix C);
  * optimization over tech params, arch params, or both;
  * log-space Adam (positive parameters, multiplicative updates) with
    realistic bounds clamping (paper Alg. 6 step 5);
  * technology-target derivation (paper §8.3): run until a target
    improvement factor is met, return the ranked order of technology
    parameters by accumulated |elasticity| — the paper's Table 3;
  * DOpt2: differentiable memory-technology selection via Gumbel-softmax
    over {sram, rram, dram} per memory unit, annealed.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import instrument
from repro.core.dsim import PARETO_METRICS, mixed_log_objective, stacked_log_objective
from repro.core.graph import Graph
from repro.core.mapper import MapperCfg
from repro.core.params import (
    COMP_CLS,
    MEM_CLS,
    MEM_TYPES,
    ArchParams,
    ArchSpec,
    TechParams,
    clamp_params,
)

# --------------------------------------------------------------------------- #
# log-space Adam over pytrees
# --------------------------------------------------------------------------- #


@jax.tree_util.register_dataclass
@dataclass
class AdamState:
    m: object
    v: object
    step: jax.Array  # dynamic! a static step would retrace every epoch


def adam_init(params) -> AdamState:
    z = jax.tree.map(jnp.zeros_like, params)
    return AdamState(m=z, v=jax.tree.map(jnp.zeros_like, params), step=jnp.zeros((), jnp.int32))


def adam_update(grads, state: AdamState, lr: float, b1=0.9, b2=0.999, eps=1e-8):
    step = state.step + 1
    stepf = step.astype(jnp.float32)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state.v, grads)
    mh = jax.tree.map(lambda m: m / (1 - jnp.power(b1, stepf)), m)
    vh = jax.tree.map(lambda v: v / (1 - jnp.power(b2, stepf)), v)
    upd = jax.tree.map(lambda m, v: -lr * m / (jnp.sqrt(v) + eps), mh, vh)
    return upd, AdamState(m=m, v=v, step=step)


def to_log(p):
    return jax.tree.map(lambda x: jnp.log(jnp.maximum(x, 1e-30)), p)


def from_log(z):
    return jax.tree.map(jnp.exp, z)


# --------------------------------------------------------------------------- #
# parameter naming (for importance ranking / Table 3)
# --------------------------------------------------------------------------- #

_TECH_FIELD_CLASSES = {
    "mem_wire_cap": MEM_CLS,
    "mem_wire_resist": MEM_CLS,
    "cell_read_latency": MEM_CLS,
    "cell_access_device": MEM_CLS,
    "cell_read_power": MEM_CLS,
    "cell_leakage_power": MEM_CLS,
    "cell_area": MEM_CLS,
    "peripheral_node": MEM_CLS,
    "comp_wire_cap": COMP_CLS,
    "comp_wire_resist": COMP_CLS,
    "node": COMP_CLS,
}


def tech_param_names() -> list[str]:
    names = []
    for f in dataclasses.fields(TechParams):
        for cls in _TECH_FIELD_CLASSES[f.name]:
            names.append(f"{cls}.{f.name}")
    return names


def _flatten_tech(t: TechParams) -> jax.Array:
    return jnp.concatenate([jnp.atleast_1d(getattr(t, f.name)) for f in dataclasses.fields(TechParams)])


# --------------------------------------------------------------------------- #
# DOpt driver
# --------------------------------------------------------------------------- #


@dataclass
class OptResult:
    tech: TechParams
    arch: ArchParams
    type_weights: jax.Array | None
    history: dict  # lists per metric
    importance: list[tuple[str, float]]  # ranked tech-parameter elasticities


def _default_chunk(steps: int, target_factor) -> int:
    """Epochs fused per device dispatch.

    Equal-size chunks (ceil-divided against a cap) so one optimize() call
    compiles at most two scan-program lengths, usually one — e.g. 200 steps
    -> 4x50, 60 steps -> 2x30.  The cap bounds compile time per program;
    with ``target_factor`` a smaller cap bounds how far past the target the
    fused scan can overshoot before the boundary check."""
    if steps <= 0:  # steps=0 is a valid no-op run (baseline read)
        return 1
    cap = 25 if target_factor is not None else 50
    n_chunks = -(-steps // cap)
    return -(-steps // n_chunks)


def guard_init() -> tuple:
    """Initial non-finite-containment guard carried through the scan:
    ``(lr_scale, last_metrics)``.  ``lr_scale`` multiplies the learning rate
    (1.0 until a fault halves it); ``last_metrics`` is the most recent
    *accepted* history row (NaN until the first finite epoch), emitted in
    place of a faulted epoch's metrics so history never carries the
    corruption."""
    return (jnp.float32(1.0), jnp.full((5,), jnp.nan, jnp.float32))


def _dopt_step(state, gstack: Graph, lr, mix, fault, spec, objective, area_constraint, opt_over, mcfg):
    """One DOpt epoch (forward + backward + Adam + in-jit log-space clamp),
    with in-jit non-finite containment.

    Top-level (not a closure) so the jitted chunk runner below caches across
    ``optimize()`` calls: the workload stack, lr and the objective mix are
    traced *arguments*, not baked-in constants, so any optimize() with
    matching shapes and static config reuses the compiled program.

    ``mix`` is the traced ``(weights, area_budget, power_budget,
    penalty_weight)`` tuple consumed when ``objective == "mixed"`` (the
    multi-objective scalarization); for string objectives it is carried but
    unused.

    ``fault`` is the traced chaos seam: a positive scalar poisons this
    epoch's loss and gradients with NaN *before* the containment check, so
    the rollback path is exercised by the exact machinery a real divergence
    would hit.  Containment: when the loss or any gradient leaf is
    non-finite, the epoch's parameter/Adam/type updates are rolled back
    (the previous state is re-emitted bit-for-bit), the guard's ``lr_scale``
    halves (recovering 2x per clean epoch, capped at 1.0), the elasticity
    contribution is zeroed, and the history row re-emits the last accepted
    metrics with the trailing fault flag set.  A fault-free epoch is
    bit-identical to the unguarded computation: the selects take the
    all-true branch and ``lr * 1.0`` is exact.
    """
    instrument.count_trace("dopt._dopt_step")  # retrace probe (trace-time only)
    tech_z, arch_z, type_logits, tstate, astate, ystate, guard = state
    lr_scale, last_metrics = guard
    dopt2 = opt_over == "both+types"

    def loss_fn(tz, az, tl):
        # batched multi-workload loss: one vmapped simulate over the stacked
        # workload axis; log-objective keeps gradients scale-free
        tw = None if tl is None else jax.nn.softmax(tl, -1)
        if objective == "mixed":
            w, ab, pb, pw = mix
            return mixed_log_objective(
                from_log(tz), from_log(az), gstack, w, ab, pb, pw, spec, mcfg, tw
            )
        return stacked_log_objective(
            from_log(tz), from_log(az), gstack, objective, area_constraint, spec, mcfg, tw
        )

    (val, perfs), grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2) if dopt2 else (0, 1), has_aux=True)(
        tech_z, arch_z, type_logits
    )
    # chaos seam: an injected fault corrupts loss+grads exactly like a real
    # numeric escape would, upstream of the containment logic
    poison = fault > 0
    val = jnp.where(poison, jnp.full_like(val, jnp.nan), val)
    grads = jax.tree.map(lambda g: jnp.where(poison, jnp.full_like(g, jnp.nan), g), grads)
    ok = jnp.isfinite(val)
    for leaf in jax.tree.leaves(grads):
        ok = ok & jnp.all(jnp.isfinite(leaf))
    g_tech, g_arch = grads[0], grads[1]
    prev = (tech_z, arch_z, type_logits, tstate, astate, ystate)
    lr_eff = lr * lr_scale
    if opt_over in ("tech", "both", "both+types"):
        upd, tstate = adam_update(g_tech, tstate, lr_eff)
        tech_z = jax.tree.map(lambda p, u: p + u, tech_z, upd)
    if opt_over in ("arch", "both", "both+types"):
        upd, astate = adam_update(g_arch, astate, lr_eff)
        arch_z = jax.tree.map(lambda p, u: p + u, arch_z, upd)
    if dopt2:
        upd, ystate = adam_update(grads[2], ystate, lr_eff * 4.0)
        type_logits = type_logits + upd
    # clamp to realistic bounds (paper Alg. 6) — log is monotone, so
    # clamping z against log(bounds) inside the jitted body replaces the
    # old out-of-jit exp/clip/log host round-trip
    tech_z = clamp_params(tech_z, *(to_log(b) for b in TechParams.bounds()))
    arch_z = clamp_params(arch_z, *(to_log(b) for b in ArchParams.bounds()))
    # containment: roll back to the last finite state when anything escaped
    cand = (tech_z, arch_z, type_logits, tstate, astate, ystate)
    tech_z, arch_z, type_logits, tstate, astate, ystate = jax.tree.map(
        lambda n_, o_: jnp.where(ok, n_, o_), cand, prev
    )
    lr_scale = jnp.where(ok, jnp.minimum(lr_scale * 2.0, 1.0), lr_scale * 0.5)
    # elasticity d log obj / d log param = gradient in log space (zeroed on
    # a faulted epoch so the importance accumulator never sees NaN)
    elast = jnp.where(ok, _flatten_tech(g_tech), jnp.zeros(len(tech_param_names()), jnp.float32))
    # history row: [objective, runtime, energy, area, edp] of workload 0,
    # re-emitting the last accepted row on a faulted epoch, + fault flag
    rt, en, ar = perfs.runtime[0], perfs.energy[0], perfs.area[0]
    row = jnp.where(ok, jnp.stack([val, rt, en, ar, rt * en]), last_metrics)
    metrics = jnp.concatenate([row, 1.0 - ok.astype(jnp.float32)[None]])
    guard = (lr_scale, row)
    return (tech_z, arch_z, type_logits, tstate, astate, ystate, guard), elast, metrics


@partial(
    jax.jit,
    static_argnames=("spec", "objective", "area_constraint", "opt_over", "mcfg", "n"),
    donate_argnums=(0, 1),
)
def _fused_chunk(state, elast_acc, gstack: Graph, lr, mix, faults, *, spec, objective, area_constraint, opt_over, mcfg, n: int):
    """``n`` device-resident epochs as one ``lax.scan`` dispatch.

    Param/Adam state is donated between chunks; elasticity accumulates
    on-device; the per-epoch metric history comes back as one stacked
    [n, 6] array (a single host transfer per chunk).  ``faults`` is the
    [n] chaos schedule scanned alongside (all-zero outside chaos tests)."""

    def body(c, fault):
        st, eacc = c
        st, elast, metrics = _dopt_step(st, gstack, lr, mix, fault, spec, objective, area_constraint, opt_over, mcfg)
        return (st, eacc + jnp.abs(elast)), metrics

    return jax.lax.scan(body, (state, elast_acc), faults, length=n)


def optimize(
    graphs: list[Graph] | Graph,
    tech: TechParams | None = None,
    arch: ArchParams | None = None,
    spec: ArchSpec = ArchSpec(),
    objective: str = "edp",
    area_constraint: float | None = None,
    opt_over: str = "both",  # tech | arch | both | both+types (DOpt2)
    steps: int = 200,
    lr: float = 0.05,
    mcfg: MapperCfg = MapperCfg(),
    target_factor: float | None = None,  # stop when obj improves by this factor
    fused: bool = True,  # device-resident chunked-scan epochs (False: per-step loop)
    chunk: int | None = None,  # epochs per device dispatch when fused
    objective_weights=None,  # [4] PARETO_METRICS mix, for objective="mixed"
    area_budget: float | None = None,  # worst-case area ceiling (mm^2), mixed only
    power_budget: float | None = None,  # worst-case power ceiling (W), mixed only
    penalty_weight: float = 1.0,  # budget-penalty scale, mixed only
    nan_epochs: tuple = (),  # chaos seam: epochs whose loss/grads are NaN-poisoned
) -> OptResult:
    """DOpt driver.

    ``objective="mixed"`` descends the constrained scalarization of the
    (time, energy, area, edp) log-metric vector (dsim.mixed_log_objective):
    ``objective_weights`` mixes the metrics, ``area_budget``/``power_budget``
    apply smooth log-space penalties scaled by ``penalty_weight``.  The mix
    is a *traced* argument, so sequential calls with different mixes reuse
    one compiled program — this is the per-trajectory form of what
    popsim.pareto_dse runs as a vmapped population.

    ``fused=True`` (default) runs epochs device-resident: chunks of
    ``jax.lax.scan`` over the jitted step with the Adam/param state donated
    between dispatches, bounds clamping in log-space inside the jitted body,
    elasticity accumulated on-device, and the per-epoch metric history
    coming back as one stacked [chunk, 5] device array — a single host sync
    per chunk instead of five scalar transfers per epoch.  The
    ``target_factor`` early exit is evaluated at chunk boundaries, so the
    fused loop may run up to one chunk past the meeting epoch; history,
    elasticities and the returned params consistently cover every executed
    epoch.

    ``fused=False`` keeps a per-step Python loop: one jitted dispatch and
    one host sync per epoch, retraced per optimize() call — a conservative
    stand-in for the pre-fusion driver (the original additionally clamped
    out-of-jit and made five scalar transfers per epoch), retained for
    equivalence tests and before/after throughput benchmarks.

    ``graphs`` may be a single Graph, a list of Graphs, or an already
    ``Graph.stack()``-ed workload set (leading [W] axis) — the façade passes
    pre-bucketed stacks so same-shape calls share one compiled program.
    """
    if isinstance(graphs, Graph):
        gstack = graphs if graphs.n_comp.ndim == 3 else Graph.stack([graphs])
    else:
        gstack = Graph.stack(list(graphs))
    tech = tech or TechParams.default()
    arch = arch or ArchParams.default()

    tech_z, arch_z = to_log(tech), to_log(arch)
    dopt2 = opt_over == "both+types"
    type_logits = jnp.zeros((len(MEM_CLS), len(MEM_TYPES))) if dopt2 else None
    lr_arr = jnp.float32(lr)
    if objective == "mixed" and objective_weights is None:
        raise ValueError('objective="mixed" needs objective_weights (len-4 PARETO_METRICS mix)')
    if objective == "mixed" and area_constraint is not None:
        raise ValueError('objective="mixed" takes area_budget (log-space penalty), not area_constraint')
    if objective != "mixed" and not (
        objective_weights is None and area_budget is None and power_budget is None and penalty_weight == 1.0
    ):
        raise ValueError(
            "objective_weights/area_budget/power_budget/penalty_weight only apply to "
            f'objective="mixed" (got objective={objective!r}) — they would be silently ignored'
        )
    w = jnp.zeros(len(PARETO_METRICS)) if objective_weights is None else jnp.asarray(objective_weights, jnp.float32)
    if w.shape != (len(PARETO_METRICS),):
        raise ValueError(f"objective_weights must be shape {(len(PARETO_METRICS),)}, got {w.shape}")
    mix = (
        w,
        jnp.float32(jnp.inf if area_budget is None else area_budget),
        jnp.float32(jnp.inf if power_budget is None else power_budget),
        jnp.float32(penalty_weight),
    )
    static = dict(spec=spec, objective=objective, area_constraint=area_constraint, opt_over=opt_over, mcfg=mcfg)

    # chaos schedule: which epochs get their loss/grads NaN-poisoned inside
    # the jitted step (tests the rollback path with the real machinery)
    fault_np = np.zeros(steps, np.float32)
    for i in nan_epochs:
        if 0 <= int(i) < steps:
            fault_np[int(i)] = 1.0

    # the pre-fusion baseline: a per-call jitted step closure, exactly the
    # old driver's cost model (retraces every optimize() invocation, one
    # dispatch + host sync per epoch)
    step_jit = jax.jit(lambda st, flt: _dopt_step(st, gstack, lr_arr, mix, flt, **static))

    tstate, astate = adam_init(tech_z), adam_init(arch_z)
    ystate = adam_init(type_logits) if dopt2 else adam_init(jnp.zeros(1))
    state = (tech_z, arch_z, type_logits, tstate, astate, ystate, guard_init())
    elast_acc = jnp.zeros(len(tech_param_names()), jnp.float32)

    hist = dict(objective=[], runtime=[], energy=[], area=[], edp=[], fault=[])

    def _append(m: np.ndarray):
        hist["objective"] += m[:, 0].tolist()
        hist["runtime"] += m[:, 1].tolist()
        hist["energy"] += m[:, 2].tolist()
        hist["area"] += m[:, 3].tolist()
        hist["edp"] += m[:, 4].tolist()
        hist["fault"] += m[:, 5].tolist()

    def _target_met() -> bool:
        """True once the objective has improved by target_factor.  The fused
        path evaluates this at chunk boundaries, so it may run up to one
        chunk past the meeting epoch — history, elasticities and the
        returned params all consistently cover every executed epoch."""
        if target_factor is None or len(hist["edp"]) < 2:
            return False
        cur = np.asarray(hist["edp"] if objective == "edp" else np.exp(np.asarray(hist["objective"])))
        return bool(np.any(cur[0] / np.maximum(cur[1:], 1e-300) >= target_factor))

    executed = 0
    if fused:
        chunk = _default_chunk(steps, target_factor) if chunk is None else max(1, chunk)
    else:
        chunk = 1  # one jitted dispatch and one host sync per epoch
    with instrument.span("dragon.dopt.descent", steps=steps, chunk_epochs=chunk):
        while executed < steps:
            n = min(chunk, steps - executed)
            with instrument.span("dragon.dopt.chunk", epochs=n):
                if fused:
                    faults = jnp.asarray(fault_np[executed:executed + n])
                    (state, elast_acc), metrics = _fused_chunk(
                        state, elast_acc, gstack, lr_arr, mix, faults, n=n, **static
                    )
                else:
                    state, elast, metrics = step_jit(state, jnp.float32(fault_np[executed]))
                    elast_acc = elast_acc + jnp.abs(elast)
                with instrument.span("dragon.dopt.sync"):
                    m = np.asarray(metrics)  # the one host sync per chunk
            executed += n
            _append(m if fused else m[None])
            if _target_met():
                break

    tech_z, arch_z, type_logits = state[0], state[1], state[2]
    elast_mean = np.asarray(elast_acc, np.float64) / max(executed, 1)
    ranked = sorted(zip(tech_param_names(), elast_mean), key=lambda kv: -kv[1])
    return OptResult(
        tech=from_log(tech_z),
        arch=from_log(arch_z),
        type_weights=None if not dopt2 else jax.nn.softmax(type_logits, -1),
        history=hist,
        importance=[(n, float(v)) for n, v in ranked],
    )


def derive_tech_targets(
    graphs,
    goal_factor: float = 100.0,
    objective: str = "edp",
    spec: ArchSpec = ArchSpec(),
    steps: int = 400,
    lr: float = 0.05,
) -> dict:
    """paper §8.3: derive technology targets for a goal_factor x improvement.

    Returns the targets (start -> end values per tech parameter), the ranked
    importance order, and the achieved factor — a single gradient-descent
    pass instead of a >1e5-point technology sweep.
    """
    # baseline objective at the default design point: a direct simulate —
    # not a throwaway optimize(steps=1, lr=0) that jit-compiles a full
    # gradient step just to read one forward value
    if isinstance(graphs, Graph) and graphs.n_comp.ndim == 3:
        gstack = graphs
    else:
        gstack = Graph.stack([graphs] if isinstance(graphs, Graph) else list(graphs))
    base_val, _ = stacked_log_objective(
        TechParams.default(), ArchParams.default(), gstack, objective, spec=spec
    )
    start = TechParams.default()
    res = optimize(
        graphs, tech=start, opt_over="tech", objective=objective, steps=steps, lr=lr, spec=spec, target_factor=goal_factor
    )
    start_f = np.asarray(_flatten_tech(start))
    end_f = np.asarray(_flatten_tech(res.tech))
    names = tech_param_names()
    targets = {
        n: dict(start=float(s), target=float(e), factor=float(s / max(e, 1e-300)))
        for n, s, e in zip(names, start_f, end_f)
    }
    edp0 = res.history["edp"][0]
    edp1 = res.history["edp"][-1]
    return dict(
        targets=targets,
        importance=res.importance,
        achieved_factor=edp0 / max(edp1, 1e-300),
        epochs=len(res.history["edp"]),
        history=res.history,
        baseline_objective=float(base_val),
    )
