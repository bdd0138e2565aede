"""Façade behaviour: Session parity with the engines + the compiled-program
cache contract (tier-1).

Parity: every Session method must be numerically identical to the direct
engine call it wraps, evaluated on the same bucketed workload stack — the
engine layer is the oracle.  Cache: warm same-bucket calls must trigger
zero new traces (counted via the trace-side-effect probe in
repro.core.instrument, not inferred from wall time), and a changed
objective mix / design point must reuse the compiled program (weights and
parameters are traced arguments).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import Architecture, Session, Workload
from repro.core import instrument
from repro.core.dhdl import load_arch, parse_arch
from repro.core.dopt import optimize
from repro.core.dsim import simulate, simulate_stacked
from repro.core.graph import Graph
from repro.core.mapper import MapperCfg
from repro.core.params import ArchParams, ArchSpec, TechParams
from repro.core.popsim import pareto_dse
from repro.workloads import get_workload
from tests._per_leaf import per_leaf_replies


# --------------------------------------------------------------------------- #
# Workload / Architecture construction + validation
# --------------------------------------------------------------------------- #


class TestWorkload:
    def test_bucketing_pow2_min32(self):
        assert Workload("lstm").bucket == (1, 32)  # 9 vertices -> 32
        assert Workload("bert_base").bucket == (1, 128)  # 109 -> 128
        assert Workload(["lstm", "merge_sort"]).bucket == (2, 32)

    def test_same_bucket_same_structure(self):
        a, b = Workload("lstm").stacked, Workload("merge_sort").stacked
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert [x.shape for x in la] == [x.shape for x in lb]
        assert jax.tree.structure(a) == jax.tree.structure(b)  # names stripped

    def test_sources(self):
        g = get_workload("lstm")
        assert Workload(g).n_workloads == 1
        assert Workload([g, "dlrm"]).labels == ("workload0", "dlrm")
        w = Workload(["lstm"])
        assert Workload(w).labels == w.labels

    def test_validation(self):
        with pytest.raises(ValueError):
            Workload([])
        with pytest.raises((KeyError, TypeError)):
            Workload("no_such_workload")
        g = get_workload("lstm")
        import dataclasses

        bad = dataclasses.replace(g, n_read=g.n_read.at[0, 0].set(-1.0))
        with pytest.raises(ValueError, match="finite and >= 0"):
            Workload(bad)
        stacked = Graph.stack([g, g])
        with pytest.raises(ValueError, match="already stacked"):
            Workload(stacked)

    def test_padding_is_exact(self):
        g = get_workload("lstm")
        w = Workload(g)
        tech, arch = TechParams.default(), ArchParams.default()
        padded = simulate_stacked(tech, arch, w.stacked)
        raw = simulate(tech, arch, g, mcfg=MapperCfg(scan_impl="assoc"))
        np.testing.assert_allclose(
            np.asarray(padded.cycles)[0], np.asarray(raw.cycles), rtol=1e-6
        )


class TestArchitecture:
    def test_one_constructor_all_spellings(self):
        lib = Architecture("edge")
        ca = load_arch("edge")
        txt = Architecture(lib.to_dhd())
        raw = Architecture(tech=ca.tech, arch=ca.arch, spec=ca.spec, name="edge")
        for other in (Architecture(ca), txt, raw):
            for a, b in zip(jax.tree.leaves((lib.tech, lib.arch)), jax.tree.leaves((other.tech, other.arch))):
                assert np.array_equal(np.asarray(a), np.asarray(b))
        assert lib.spec == txt.spec == raw.spec

    def test_to_dhd_roundtrip(self):
        a = Architecture("datacenter")
        again = Architecture(a.to_dhd())
        for x, y in zip(jax.tree.leaves((a.tech, a.arch)), jax.tree.leaves((again.tech, again.arch))):
            assert np.array_equal(np.asarray(x), np.asarray(y))

    def test_validation(self):
        import dataclasses

        bad = dataclasses.replace(ArchParams.default(), frequency=jnp.float32(-1.0))
        with pytest.raises(ValueError, match="non-positive"):
            Architecture(arch=bad)
        with pytest.raises(TypeError):
            Architecture(123)

    def test_names_sanitized_to_dhd_identifiers(self):
        # every Architecture must serialize to parseable text, whatever the
        # display name — "scale-sim 32x32" would break the .dhd grammar
        a = Architecture("base", name="scale-sim 32x32")
        assert a.name == "scale_sim_32x32"
        assert Architecture(a.to_dhd()).name == a.name  # text round-trips
        assert Architecture("base", name="4chip").name == "_4chip"


# --------------------------------------------------------------------------- #
# parity with the engine oracle
# --------------------------------------------------------------------------- #


class TestParity:
    def test_simulate_identical_to_engine(self):
        w = Workload(["lstm", "bert_base"])
        a = Architecture("edge")
        sess = Session(a)
        perfs = sess.perf(w)
        # oracle: the jitted engine call on the identical bucketed stack
        oracle = jax.jit(
            lambda t, ar, g: simulate_stacked(t, ar, g, a.spec, MapperCfg())
        )(a.tech, a.arch, w.stacked)
        for got, want in zip(jax.tree.leaves(perfs), jax.tree.leaves(oracle)):
            assert np.array_equal(np.asarray(got), np.asarray(want))
        # and the report repeats the same numbers
        rep = sess.simulate(w)
        np.testing.assert_allclose(
            [wr.runtime_s for wr in rep.workloads], np.asarray(oracle.runtime), rtol=1e-6
        )
        # unpadded per-workload engine calls agree to float tolerance
        for wr, g in zip(rep.workloads, w.graphs):
            direct = simulate(a.tech, a.arch, g, a.spec, MapperCfg(scan_impl="assoc"))
            np.testing.assert_allclose(wr.runtime_s, float(direct.runtime), rtol=1e-5)
            np.testing.assert_allclose(wr.energy_j, float(direct.energy), rtol=1e-5)

    def test_optimize_identical_to_engine(self):
        w = Workload(["lstm", "dlrm"])
        sess = Session("base")
        res = sess.optimize(w, objective="edp", steps=8, lr=0.05)
        oracle = optimize(w.stacked, objective="edp", steps=8, lr=0.05)
        import math

        np.testing.assert_array_equal(
            [math.exp(v) for v in oracle.history["objective"]], np.asarray(res.objective_history)
        )
        assert [n for n, _ in oracle.importance] == [
            a.parameter.removeprefix("tech.") for a in res.importance
        ]
        # the serialized design is the oracle's design, bit for bit
        ca = parse_arch(res.to_dhd())
        for got, want in zip(
            jax.tree.leaves((ca.tech, ca.arch)), jax.tree.leaves((oracle.tech, oracle.arch))
        ):
            assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_frontier_identical_to_engine(self):
        w = Workload("lstm")
        sess = Session()
        fr = sess.frontier(w, population=6, steps=3, key=0)
        oracle = pareto_dse(w.stacked, population=6, steps=3, key=0)
        assert len(fr.front) == int(oracle.front.size)
        assert fr.hypervolume == pytest.approx(oracle.hypervolume)
        for p, win in zip(fr.front, oracle.winners):
            assert p.dhd == win["dhd"]
            assert p.time_s == win["time_s"]

    def test_explain_matches_direct_gradient(self):
        w = Workload("lstm")
        a = Architecture("base")
        rep = Session(a).explain(w, objective="edp")
        assert rep.objective == "edp"
        # oracle elasticity for the named tech parameters
        from repro.core.dopt import _flatten_tech, from_log, tech_param_names, to_log

        tz = to_log(a.tech)
        g = jax.grad(
            lambda tz: jnp.mean(
                jnp.log(
                    simulate_stacked(from_log(tz), a.arch, w.stacked, a.spec).edp
                )
            )
        )(tz)
        want = {f"tech.{n}": float(v) for n, v in zip(tech_param_names(), np.asarray(_flatten_tech(g)))}
        got = {at.parameter: at.elasticity for at in rep.attribution if at.parameter.startswith("tech.")}
        for k, v in want.items():
            np.testing.assert_allclose(got[k], v, rtol=1e-4, atol=1e-7)

    def test_report_breakdowns_consistent(self):
        rep = Session("edge").simulate(["lstm", "bert_base"])
        for wr in rep.workloads:
            # per-vertex times sum to the runtime; energies to the total
            np.testing.assert_allclose(
                sum(v.time_s for v in wr.vertices), wr.runtime_s, rtol=1e-4
            )
            np.testing.assert_allclose(
                sum(v.energy_j for v in wr.vertices), wr.energy_j, rtol=1e-4
            )
            # per-level + per-class energies cover the total exactly
            total = sum(l.dynamic_energy_j + l.leakage_energy_j for l in wr.levels) + sum(
                c.dynamic_energy_j + c.leakage_energy_j for c in wr.compute
            )
            np.testing.assert_allclose(total, wr.energy_j, rtol=1e-4)
        import json

        parsed = json.loads(rep.to_json())
        assert parsed["architecture"] == "edge"
        assert len(parsed["workloads"]) == 2


# --------------------------------------------------------------------------- #
# packed program outputs against the per-leaf path
# --------------------------------------------------------------------------- #


_MIXED = (["lstm", "merge_sort", "dlrm", "gcn"], ["base", "edge", "datacenter", "base"])


class TestPackedOutputs:
    @pytest.mark.parametrize("kind", ["report", "explain", "report_batched", "explain_batched"])
    def test_every_program_returns_one_array_per_dtype(self, kind):
        sess = Session("edge")
        w = Workload(["lstm", "bert_base"])
        a = sess.architecture
        bucket, spec, mcfg = w.bucket, a.spec, sess.mcfg
        args = (a.tech, a.arch, w.stacked)
        if kind.endswith("_batched"):
            args = jax.tree.map(lambda x: jnp.stack([x] * 4), args)
        key, build = {
            "report": lambda: sess._report_spec(bucket, spec, mcfg),
            "explain": lambda: sess._explain_spec(bucket, spec, mcfg, "edp"),
            "report_batched": lambda: sess._batched_report_spec(4, bucket, spec, mcfg),
            "explain_batched": lambda: sess._batched_explain_spec(4, bucket, spec, mcfg, "edp"),
        }[kind]()
        assert key[0] == kind
        out = jax.eval_shape(build(), *args)
        lead = (4,) if kind.endswith("_batched") else ()
        if kind.startswith("report"):
            out, leaves = out  # the packed buffers, then the leaves they copy
            width = sum(math.prod(x.shape[len(lead):]) for x in jax.tree.leaves(leaves))
        else:
            width = len(sess.explain("lstm").attribution)
        assert isinstance(out, tuple) and len(out) == 1  # every output is float32
        (buf,) = out
        assert buf.dtype == jnp.float32 and buf.shape == lead + (width,)

    def test_packing_keeps_each_dtype_and_shape(self):
        tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                "b": jnp.array([7, -8], jnp.int32),
                "c": jnp.float32(0.5),
                "d": jnp.array([2**24 + 1], jnp.int32)}
        bufs = jax.jit(api._pack)(tree)
        assert [b.dtype for b in bufs] == [jnp.float32, jnp.int32]
        got = api._Packing.of(tree).unpack([np.asarray(b) for b in bufs])
        for k in tree:
            assert got[k].dtype == tree[k].dtype and got[k].shape == tree[k].shape
            assert np.array_equal(got[k], np.asarray(tree[k])), k

    def test_simulate_and_explain_bit_identical_to_per_leaf_path(self):
        sess = Session("base")
        ws, archs = _MIXED
        for kind in ("simulate", "explain"):
            want = per_leaf_replies(sess, ws, archs, kind=kind)
            got = [getattr(sess, kind)(w, architecture=a) for w, a in zip(ws, archs)]
            assert [r.to_json() for r in got] == [r.to_json() for r in want], kind

    @pytest.mark.parametrize("kind", ["simulate", "explain"])
    def test_batches_bit_identical_to_per_leaf_path(self, kind):
        sess = Session("base")
        ws, archs = _MIXED
        call = sess.simulate_batch if kind == "simulate" else sess.explain_batch
        for nb in (4, 8):
            want = per_leaf_replies(sess, ws, archs, kind=kind, request_bucket=nb)
            got = call(ws, architectures=archs, request_bucket=nb)
            assert [r.to_json() for r in got] == [r.to_json() for r in want], nb
            # a lane's reply does not depend on the batch around it
            lone = call(ws[1:2], architectures=archs[1:2], request_bucket=nb)
            assert lone[0].to_json() == want[1].to_json()


# --------------------------------------------------------------------------- #
# the compiled-program cache contract
# --------------------------------------------------------------------------- #


class TestCache:
    def test_warm_same_bucket_zero_retrace(self):
        """The serving pattern: after the first call, same-bucket queries —
        same workload, different workload, different design point — replay
        the compiled programs with zero new traces."""
        sess = Session("base")
        sess.simulate("lstm")  # cold: compiles
        t0 = sess.stats.traces
        assert t0 >= 1
        sess.simulate("lstm")  # warm, identical
        sess.simulate("merge_sort")  # warm: same (1, 32) bucket, new workload
        sess.simulate("dlrm", architecture=Architecture("edge"))  # new design point
        assert sess.stats.traces == t0, "warm same-bucket simulate retraced"
        assert sess.stats.hits >= 3  # one report program, three warm calls
        # a new bucket is a genuine miss and compiles once more
        sess.simulate("bert_base")  # (1, 128)
        t1 = sess.stats.traces
        assert t1 > t0
        sess.simulate("bert_base")
        assert sess.stats.traces == t1

    def test_changed_objective_mix_reuses_program(self):
        """Weights/budgets are traced args (PR 4): switching the mix — or the
        budgets — must not retrace the DOpt step."""
        sess = Session("base")
        w = Workload(["lstm", "dlrm"])
        sess.optimize(w, objective="mixed", objective_weights=[1.0, 0.0, 0.0, 0.0], steps=4)
        before = instrument.trace_count("dopt._dopt_step")
        r2 = sess.optimize(
            w,
            objective="mixed",
            objective_weights=[0.0, 1.0, 0.0, 0.0],
            area_budget=900.0,
            penalty_weight=2.0,
            steps=4,
        )
        assert instrument.trace_count("dopt._dopt_step") == before, (
            "changed objective mix retraced the DOpt step"
        )
        assert r2.epochs == 4

    def test_warm_optimize_zero_retrace_across_workloads(self):
        sess = Session("base")
        sess.optimize("lstm", steps=4)
        before = instrument.trace_count("dopt._dopt_step")
        sess.optimize("merge_sort", steps=4)  # same bucket (1->32)
        assert instrument.trace_count("dopt._dopt_step") == before
        assert sess.stats.hits >= 1

    def test_explain_program_cached(self):
        sess = Session("base")
        sess.explain("lstm")
        t0 = sess.stats.traces
        sess.explain("merge_sort")  # same bucket
        assert sess.stats.traces == t0
        sess.explain("lstm", objective="time")  # new objective signature
        assert sess.stats.traces > t0

    def test_sessions_do_not_share_stats(self):
        s1, s2 = Session("base"), Session("base")
        s1.simulate("lstm")
        assert s2.stats.traces == 0 and s2.stats.programs == 0
