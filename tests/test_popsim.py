"""Population DSE: shared batched-workload path, mesh-robust shardings, and
the population-scale multi-objective engine (vmapped chunks, spmd sharding,
budget constraints, .dhd round-trips)."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import ArchParams, TechParams, optimize, simulate
from repro.core.dhdl import load_arch, parse_arch, serialize_arch
from repro.core.dopt import from_log, to_log
from repro.core.dsim import (
    PARETO_METRICS,
    mixed_log_objective,
    stacked_log_objective,
)
from repro.core.graph import Graph
from repro.core.params import ArchSpec
from repro.core.popsim import (
    dse_in_shardings,
    init_population_state,
    pareto_dse,
    population_chunk,
    population_log_metrics,
    population_objective,
    sample_objective_mixes,
    seed_population,
)
from repro.workloads import get_workload


def _stack(names):
    return Graph.stack([get_workload(n) for n in names])


def _mesh(axis_names):
    devs = np.array(jax.devices()[:1]).reshape((1,) * len(axis_names))
    return Mesh(devs, axis_names)


class TestPopulationObjective:
    def test_matches_single_candidate_path(self):
        """The population path is literally DOpt's batched loss, vmapped."""
        gs = _stack(["lstm", "merge_sort"])
        tech, arch = TechParams.default(), ArchParams.default()
        pop = jax.tree.map(lambda x: x[None], (tech, arch))
        got = population_objective(pop, gs)
        want, _ = stacked_log_objective(tech, arch, gs)
        assert got.shape == (1,)
        np.testing.assert_allclose(float(got[0]), float(want), rtol=1e-5)

    def test_population_axis_shape(self):
        gs = _stack(["lstm"])
        tech, arch = TechParams.default(), ArchParams.default()
        pop = jax.tree.map(lambda x: jnp.stack([x, x * 1.1]), (tech, arch))
        out = population_objective(pop, gs)
        assert out.shape == (2,)
        assert np.all(np.isfinite(np.asarray(out)))


class TestPopsimKernelPadding:
    def test_pad_vertices_free_in_popsim_kernel(self):
        """The Pallas population kernel and its oracle price Graph.pad_to's
        no-op vertices at zero, matching the mapper (Graph.stack convention)."""
        from repro.kernels import pack_chw, pack_graph, popsim, ref
        from repro.core import specialize

        g = get_workload("lstm")
        chw = jax.tree.map(lambda x: x[None], specialize(TechParams.default(), ArchParams.default()))
        cp = pack_chw(chw)
        out0 = np.asarray(popsim(pack_graph(g), cp))
        out1 = np.asarray(popsim(pack_graph(g.pad_to(g.n_vertices + 17)), cp))
        np.testing.assert_allclose(out1, out0, rtol=1e-6)
        ref1 = np.asarray(ref.popsim_reference(pack_graph(g.pad_to(g.n_vertices + 17)), cp))
        np.testing.assert_allclose(ref1, out0, rtol=1e-5)


def _jittered_starts(n, key, sigma=0.2):
    """n log-normal-jittered copies of the default design point."""
    leaves, td = jax.tree.flatten((TechParams.default(), ArchParams.default()))
    keys = jax.random.split(key, len(leaves))
    stacked = [
        jnp.exp(jnp.log(l)[None] + sigma * jax.random.normal(k, (n,) + l.shape))
        for l, k in zip(leaves, keys)
    ]
    return jax.tree.unflatten(td, stacked)


def _onehot(metric, n):
    i = PARETO_METRICS.index(metric)
    return jnp.zeros((n, len(PARETO_METRICS))).at[:, i].set(1.0)


class TestMixedObjective:
    def test_onehot_mix_equals_string_objective(self):
        """A one-hot weight reproduces the single-objective loss exactly —
        the off-metric terms are exact float zeros."""
        gs = _stack(["lstm", "merge_sort"])
        tech, arch = TechParams.default(), ArchParams.default()
        for metric in PARETO_METRICS:
            w = _onehot(metric, 1)[0]
            got, _ = mixed_log_objective(tech, arch, gs, w)
            want, _ = stacked_log_objective(tech, arch, gs, metric)
            assert float(got) == float(want), metric

    def test_onehot_mix_grads_equal_string_objective_grads(self):
        gs = _stack(["lstm"])
        tz, az = to_log(TechParams.default()), to_log(ArchParams.default())

        def mixed(tz, az):
            return mixed_log_objective(from_log(tz), from_log(az), gs, _onehot("edp", 1)[0])[0]

        def plain(tz, az):
            return stacked_log_objective(from_log(tz), from_log(az), gs, "edp")[0]

        gm = jax.grad(mixed, argnums=(0, 1))(tz, az)
        gp = jax.grad(plain, argnums=(0, 1))(tz, az)
        for a, b in zip(jax.tree.leaves(gm), jax.tree.leaves(gp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    def test_inf_budgets_are_exact_noops(self):
        gs = _stack(["lstm"])
        tech, arch = TechParams.default(), ArchParams.default()
        w = jnp.asarray([0.25, 0.25, 0.25, 0.25])
        free, _ = mixed_log_objective(tech, arch, gs, w)
        gated, _ = mixed_log_objective(
            tech, arch, gs, w, jnp.float32(jnp.inf), jnp.float32(jnp.inf), 3.0
        )
        assert float(free) == float(gated)

    def test_optimize_rejects_mismatched_constraint_args(self):
        """Constraint/mix arguments that the chosen objective would silently
        ignore are rejected loudly instead."""
        g = get_workload("lstm")
        with pytest.raises(ValueError, match="only apply"):
            optimize(g, objective="edp", area_budget=500.0, steps=1)
        with pytest.raises(ValueError, match="objective_weights"):
            optimize(g, objective="mixed", steps=1)
        with pytest.raises(ValueError, match="area_constraint"):
            optimize(g, objective="mixed", objective_weights=[0, 0, 0, 1.0],
                     area_constraint=500.0, steps=1)

    def test_binding_budget_raises_objective(self):
        gs = _stack(["lstm"])
        tech, arch = TechParams.default(), ArchParams.default()
        perf = simulate(tech, arch, get_workload("lstm"))
        w = jnp.asarray([0.0, 0.0, 0.0, 1.0])
        free, _ = mixed_log_objective(tech, arch, gs, w)
        tight, _ = mixed_log_objective(
            tech, arch, gs, w, jnp.float32(float(perf.area) * 0.5), None, 1.0
        )
        assert float(tight) > float(free)


class TestPopulationEquivalence:
    """The vmapped P-member chunk IS P sequential optimize(fused=True) runs."""

    def test_chunk_matches_sequential_optimize_trajectories(self):
        gl = [get_workload("lstm"), get_workload("merge_sort")]
        gstack = Graph.stack(list(gl))
        n_pop, steps = 2, 4
        techP, archP = _jittered_starts(n_pop, jax.random.PRNGKey(7))
        mixes = (_onehot("edp", n_pop), jnp.full((n_pop,), jnp.inf), jnp.full((n_pop,), jnp.inf))
        state = init_population_state(techP, archP)
        state, m = population_chunk(state, mixes, gstack, 0.05, jnp.ones(steps))
        popt, popa = from_log(state[0]), from_log(state[1])

        for i in range(n_pop):
            t_i = jax.tree.map(lambda x: x[i], techP)
            a_i = jax.tree.map(lambda x: x[i], archP)
            res = optimize(gl, tech=t_i, arch=a_i, objective="edp", steps=steps, lr=0.05, fused=True)
            np.testing.assert_allclose(
                np.asarray(res.history["objective"]), np.asarray(m[:, i, 0]), rtol=1e-5
            )
            for got, want in zip(
                jax.tree.leaves((jax.tree.map(lambda x: x[i], popt), jax.tree.map(lambda x: x[i], popa))),
                jax.tree.leaves((res.tech, res.arch)),
            ):
                np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)

    def test_chunk_matches_sequential_mixed_optimize(self):
        """objective="mixed" optimize() is the sequential form of one member —
        including a non-trivial weight mix and a binding budget."""
        gl = [get_workload("lstm")]
        gstack = Graph.stack(list(gl))
        steps = 3
        w = jnp.asarray([[0.5, 0.3, 0.2, 0.0]])
        ab = jnp.asarray([300.0])
        state = init_population_state(*jax.tree.map(lambda x: x[None], (TechParams.default(), ArchParams.default())))
        state, m = population_chunk(
            state, (w, ab, jnp.full((1,), jnp.inf)), gstack, 0.08, jnp.full(steps, 2.0)
        )
        res = optimize(
            gl, objective="mixed", objective_weights=w[0], area_budget=300.0,
            penalty_weight=2.0, steps=steps, lr=0.08, fused=True,
        )
        np.testing.assert_allclose(
            np.asarray(res.history["objective"]), np.asarray(m[:, 0, 0]), rtol=1e-5
        )

    def test_population_grads_match_per_member_grads(self):
        """vmapped value_and_grad == per-member value_and_grad, member by member."""
        gstack = _stack(["lstm"])
        n_pop = 3
        techP, archP = _jittered_starts(n_pop, jax.random.PRNGKey(3))
        w = sample_objective_mixes(n_pop)
        tzP, azP = to_log(techP), to_log(archP)

        def loss(tz, az, wi):
            return mixed_log_objective(from_log(tz), from_log(az), gstack, wi)[0]

        vals, grads = jax.vmap(jax.value_and_grad(loss, argnums=(0, 1)), in_axes=(0, 0, 0))(tzP, azP, w)
        for i in range(n_pop):
            vi, gi = jax.value_and_grad(loss, argnums=(0, 1))(
                jax.tree.map(lambda x: x[i], tzP), jax.tree.map(lambda x: x[i], azP), w[i]
            )
            np.testing.assert_allclose(float(vals[i]), float(vi), rtol=1e-6)
            for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(gi)):
                np.testing.assert_allclose(np.asarray(a[i]), np.asarray(b), rtol=2e-5, atol=1e-7)


class TestShardedPopulation:
    def test_sharded_matches_single_device(self):
        """spmd_map-sharded chunk == single-device chunk (float32 tolerance).
        Skips cleanly when only one device is present."""
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices for a sharded mesh")
        n_dev = 2
        gstack = _stack(["lstm"])
        n_pop, steps = 2 * n_dev, 2
        (tech, arch), spec, _ = seed_population(n_pop, ("base", "edge"), jax.random.PRNGKey(0))
        mixes = (sample_objective_mixes(n_pop), jnp.full((n_pop,), 300.0), jnp.full((n_pop,), jnp.inf))
        sched = jnp.linspace(0.5, 2.0, steps)
        s1, m1 = population_chunk(init_population_state(tech, arch), mixes, gstack, 0.1, sched, spec=spec)
        mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(n_dev), ("pop",))
        s2, m2 = population_chunk(
            init_population_state(tech, arch), mixes, gstack, 0.1, sched, spec=spec, mesh=mesh
        )
        np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-5, atol=1e-6)
        for l1, l2 in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
            np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-5, atol=1e-6)

    @pytest.mark.slow
    def test_sharded_matches_single_device_subprocess(self):
        """The same check on a forced 4-device CPU platform, in a subprocess
        (the in-process platform is pinned to 1 device by conftest): the
        engine-level chunk on an unpadded graph (V < 32, the sequential
        mapper scan, whose carry must vary over ``pop`` under shard_map's
        check_vma), and the façade frontier (padded to 32+, the associative
        scan), each sharded over a 4-device ``pop`` mesh vs one device."""
        script = textwrap.dedent(
            """
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import Mesh
            from repro.core.graph import Graph
            from repro.core.popsim import (
                init_population_state, population_chunk, sample_objective_mixes, seed_population,
            )
            from repro.workloads import get_workload

            assert len(jax.devices()) == 4, jax.devices()
            gstack = Graph.stack([get_workload("lstm")])
            assert gstack.n_comp.shape[1] < 32  # the sequential-scan path
            n_pop, steps = 8, 2
            (tech, arch), spec, _ = seed_population(n_pop, ("base", "edge"), jax.random.PRNGKey(0))
            mixes = (sample_objective_mixes(n_pop), jnp.full((n_pop,), 300.0), jnp.full((n_pop,), jnp.inf))
            sched = jnp.linspace(0.5, 2.0, steps)
            s1, m1 = population_chunk(init_population_state(tech, arch), mixes, gstack, 0.1, sched, spec=spec)
            mesh = Mesh(np.array(jax.devices()).reshape(4), ("pop",))
            s2, m2 = population_chunk(
                init_population_state(tech, arch), mixes, gstack, 0.1, sched, spec=spec, mesh=mesh
            )
            np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-5, atol=1e-6)
            for l1, l2 in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
                np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-5, atol=1e-6)

            from repro.api import Session
            sess = Session("base")
            r4 = sess.frontier("lstm", population=n_pop, steps=steps, mesh=mesh)
            r1 = sess.frontier("lstm", population=n_pop, steps=steps)
            np.testing.assert_allclose(r4.raw.history, r1.raw.history, rtol=1e-5, atol=1e-6)
            assert np.array_equal(r4.raw.front, r1.raw.front)
            held = {len(x.sharding.device_set) for x in jax.tree.leaves(r4.raw.tech)}
            assert held == {4}, held
            print("SHARDED_EQUIV_OK")
            """
        )
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4").strip()
        env["JAX_PLATFORMS"] = "cpu"
        src_dir = os.path.join(os.path.dirname(__file__), "..", "src")
        env["PYTHONPATH"] = os.path.abspath(src_dir) + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run(
            [sys.executable, "-c", script], env=env, capture_output=True, text=True, timeout=600
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "SHARDED_EQUIV_OK" in out.stdout


class TestSeedingAndMixes:
    def test_pristine_seeds_bit_exact(self):
        (tech, arch), spec, names = seed_population(5, ("base", "edge"), jax.random.PRNGKey(0))
        assert names == ("base", "edge", "base", "edge", "base")
        for nm, i in (("base", 0), ("edge", 1)):
            ca = load_arch(nm)
            for got, want in zip(
                jax.tree.leaves(jax.tree.map(lambda x: x[i], (tech, arch))),
                jax.tree.leaves((ca.tech, ca.arch)),
            ):
                assert np.array_equal(np.asarray(got), np.asarray(want)), nm

    def test_jittered_members_within_bounds(self):
        (tech, arch), _, _ = seed_population(16, ("base",), jax.random.PRNGKey(1), sigma=3.0)
        for tree, bounds in ((tech, TechParams.bounds()), (arch, ArchParams.bounds())):
            for leaf, lo, hi in zip(
                jax.tree.leaves(tree), jax.tree.leaves(bounds[0]), jax.tree.leaves(bounds[1])
            ):
                assert np.all(np.asarray(leaf) >= np.asarray(lo) * (1 - 1e-6))
                assert np.all(np.asarray(leaf) <= np.asarray(hi) * (1 + 1e-6))

    def test_spec_mismatch_raises(self):
        with pytest.raises(ValueError, match="ArchSpec"):
            seed_population(4, ("base", "rram_cim"), jax.random.PRNGKey(0))

    def test_mixes_are_simplex_weights_with_corners(self):
        w = np.asarray(sample_objective_mixes(10, ("time", "energy", "area")))
        assert w.shape == (10, 4)
        np.testing.assert_allclose(w.sum(axis=1), 1.0, rtol=1e-5)
        assert np.all(w[:, PARETO_METRICS.index("edp")] == 0.0)  # unused metric untouched
        np.testing.assert_allclose(w[0], [1, 0, 0, 0], atol=1e-6)  # pure latency corner
        np.testing.assert_allclose(w[1], [0, 1, 0, 0], atol=1e-6)


class TestConstraintCorrectness:
    def test_optimized_design_meets_budgets(self):
        """Binding area+power budgets are met within tolerance after descent."""
        g = get_workload("lstm")
        perf0 = simulate(TechParams.default(), ArchParams.default(), g)
        area_b = float(perf0.area) * 0.7
        power_b = float(perf0.power) * 0.8
        res = optimize(
            g, objective="mixed", objective_weights=[0.0, 0.0, 0.0, 1.0],
            area_budget=area_b, power_budget=power_b, penalty_weight=4.0,
            opt_over="both", steps=40, lr=0.1,
        )
        perf = simulate(res.tech, res.arch, g)
        assert float(perf.area) <= area_b * 1.05, (float(perf.area), area_b)
        assert float(perf.power) <= power_b * 1.05, (float(perf.power), power_b)

    def test_penalty_gradient_finite_difference(self):
        """AD == central finite differences through the *binding* budget
        penalty, on smooth coordinates (the test_dhdl FD pattern)."""
        ca = load_arch("edge")
        gs = _stack(["lstm", "merge_sort"])
        perf = simulate(ca.tech, ca.arch, get_workload("lstm"), ca.spec)
        area_b = jnp.float32(float(perf.area) * 0.6)  # binding
        power_b = jnp.float32(float(perf.power) * 0.7)  # binding
        w = jnp.asarray([0.3, 0.3, 0.2, 0.2])
        coords = [
            ("tech", "cell_read_power", 2),
            ("tech", "cell_area", 1),
            ("arch", "bw_scale", 2),
            ("arch", "frequency", None),
        ]
        for tree, fname, idx in coords:
            def f(s):
                t, a = ca.tech, ca.arch
                obj = t if tree == "tech" else a
                v = getattr(obj, fname)
                v2 = v * s if idx is None else v.at[idx].mul(s)
                obj2 = dataclasses.replace(obj, **{fname: v2})
                return mixed_log_objective(
                    obj2 if tree == "tech" else t,
                    a if tree == "tech" else obj2,
                    gs, w, area_b, power_b, 2.0, ca.spec,
                )[0]

            val, grad = jax.value_and_grad(f)(jnp.float32(1.0))
            assert np.isfinite(float(val))
            eps = 0.05
            fd = (float(f(jnp.float32(1 + eps))) - float(f(jnp.float32(1 - eps)))) / (2 * eps)
            assert float(grad) == pytest.approx(fd, rel=5e-2, abs=1e-5), (
                f"{tree}.{fname}[{idx}]: AD {float(grad)} vs FD {fd}"
            )


class TestParetoDse:
    @pytest.fixture(scope="class")
    def result(self):
        return pareto_dse(
            [get_workload("lstm")], seeds=("base", "edge"), population=8, steps=6,
            lr=0.1, area_budget=400.0, power_budget=80.0, key=0,
        )

    def test_front_is_feasible_and_non_dominated(self, result):
        assert result.front.size >= 1
        assert result.feasible[result.front].all()
        from repro.core.pareto import dominates

        sub = jnp.asarray(result.front_log_metrics)
        dom = np.asarray(dominates(sub[:, None], sub[None, :]))
        assert not dom.any()
        assert result.hypervolume > 0.0

    def test_history_covers_every_epoch(self, result):
        assert result.history.shape == (6, 8, 5)
        assert np.isfinite(result.history).all()

    def test_winners_round_trip_bit_exact(self, result):
        """Every Pareto winner serializes to .dhd text that parses back to
        the identical pytrees — serialize -> parse -> serialize is the
        identity, bit for bit."""
        assert result.winners
        for w in result.winners:
            i = w["index"]
            ca = parse_arch(w["dhd"])
            want_t = jax.tree.map(lambda x: x[i], result.tech)
            want_a = jax.tree.map(lambda x: x[i], result.arch)
            assert ca.spec == result.spec
            for got, want in zip(
                jax.tree.leaves((ca.tech, ca.arch)), jax.tree.leaves((want_t, want_a))
            ):
                assert np.array_equal(np.asarray(got), np.asarray(want))
            again = serialize_arch(ca)
            assert again == w["dhd"]

    def test_unsupported_opt_over_raises(self):
        """An opt_over the member step would silently no-op on is rejected."""
        gstack = _stack(["lstm"])
        state = init_population_state(
            *jax.tree.map(lambda x: x[None], (TechParams.default(), ArchParams.default()))
        )
        mixes = (_onehot("edp", 1), jnp.full((1,), jnp.inf), jnp.full((1,), jnp.inf))
        with pytest.raises(ValueError, match="opt_over"):
            population_chunk(state, mixes, gstack, 0.1, jnp.ones(1), opt_over="both+types")

    def test_chunked_run_matches_single_dispatch(self):
        kw = dict(
            seeds=("base",), population=4, steps=4, lr=0.1, area_budget=400.0, key=3,
        )
        a = pareto_dse([get_workload("lstm")], chunk=None, **kw)
        b = pareto_dse([get_workload("lstm")], chunk=2, **kw)
        np.testing.assert_allclose(a.history, b.history, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(a.log_metrics, b.log_metrics, rtol=1e-5)


class TestDseInShardings:
    def test_no_model_axis_does_not_raise(self):
        """Regression: mesh.shape["model"] used to KeyError on meshes
        without a model axis; now workloads are replicated instead."""
        mesh = _mesh(("pod", "data"))
        gs = _stack(["lstm", "merge_sort"])
        pop = jax.tree.map(lambda x: x[None], (TechParams.default(), ArchParams.default()))
        pop_s, g_s = dse_in_shardings(mesh, pop, gs)
        for s in jax.tree.leaves(g_s):
            assert s.spec == P()
        for s in jax.tree.leaves(pop_s):
            assert s.spec == P(("pod", "data"))

    def test_model_axis_shards_dividing_leading_dims(self):
        mesh = _mesh(("data", "model"))
        gs = _stack(["lstm", "merge_sort"])  # leading dim 2 % 1 == 0
        pop = jax.tree.map(lambda x: x[None], (TechParams.default(), ArchParams.default()))
        _, g_s = dse_in_shardings(mesh, pop, gs)
        specs = {s.spec for s in jax.tree.leaves(g_s)}
        assert P("model") in specs
