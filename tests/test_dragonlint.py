"""Tier-1: dragonlint — registry pins, per-rule bad/good fixtures, Pass B.

Every registered rule gets a minimal bad fixture it must fire on and a good
twin it must stay silent on (the acceptance contract for the lint suite);
the registry itself is pinned so a rule can't vanish without this file
noticing.  Pass B is exercised through ``Session.trace_programs`` (all four
program kinds) and through crafted jaxprs for each hazard class.
"""
from __future__ import annotations

import os
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from tools.dragonlint import RULES, lint_source, run_pass_a  # noqa: E402
from tools.dragonlint.engine import Finding, suppressions, write_report  # noqa: E402


def lint(rel: str, src: str) -> list[Finding]:
    return lint_source(rel, textwrap.dedent(src))


def names(findings) -> set[str]:
    return {f.rule for f in findings}


# --------------------------------------------------------------------------- #
# registry pins
# --------------------------------------------------------------------------- #

EXPECTED_RULES = (
    "api-surface",
    "dhdl-corpus",
    "float64-promotion",
    "fork-unsafe",
    "host-sync",
    "kernel-seam",
    "retrace-hazard",
    "scan-donate",
    "stale-oracle-tag",
    "stray-debug",
    "swallowed-fault",
)


class TestRegistry:
    def test_registry_pinned(self):
        assert tuple(sorted(RULES)) == EXPECTED_RULES

    def test_every_rule_documented(self):
        for r in RULES.values():
            assert r.doc, f"rule {r.name} has no doc line"
            assert r.scope in ("file", "repo")
            if r.scope == "file":
                assert r.scan, f"file rule {r.name} scans nothing"

    def test_rule_catalog_in_docs(self):
        catalog = open(os.path.join(os.path.dirname(__file__), "..", "docs", "lint.md")).read()
        for name in EXPECTED_RULES:
            assert f"`{name}`" in catalog, f"docs/lint.md missing rule {name}"

    def test_duplicate_rule_rejected(self):
        from tools.dragonlint.engine import rule

        with pytest.raises(ValueError, match="duplicate"):
            rule("kernel-seam", doc="dup", scan=("src/",))(lambda *a: [])


# --------------------------------------------------------------------------- #
# absorbed rules
# --------------------------------------------------------------------------- #


class TestKernelSeam:
    BAD = """
        import jax.experimental.pallas as pl
        out = pl.pallas_call(kernel, grid=(1,))
        """
    GOOD = """
        from repro.kernels import runtime
        out = runtime.dragon_pallas_call(kernel, grid=(1,))
        """

    def test_fires_on_fragile_spelling(self):
        assert "kernel-seam" in names(lint("src/repro/kernels/sscan.py", self.BAD))

    def test_silent_on_runtime_wrapper(self):
        assert not lint("src/repro/kernels/sscan.py", self.GOOD)

    def test_runtime_seam_itself_is_allowed(self):
        assert not lint("src/repro/kernels/runtime.py", self.BAD)

    def test_out_of_scope_path_ignored(self):
        assert not lint("examples/demo.py", self.BAD)

    # the executable-serialization spellings joined the seam with the AOT
    # cache: only kernels/runtime.py may touch jax.experimental.serialize_executable
    BAD_SERIALIZE = """
        from jax.experimental import serialize_executable as se
        blob = se.serialize(compiled)
        fn = se.deserialize_and_load(*blob)
        """
    GOOD_SERIALIZE = """
        from repro.kernels import runtime
        blob = runtime.serialize_compiled(compiled)
        fn = runtime.deserialize_compiled(blob)
        """

    def test_fires_on_executable_serialization_spelling(self):
        found = names(lint("src/repro/serving/aotcache.py", self.BAD_SERIALIZE))
        assert "kernel-seam" in found

    def test_silent_on_runtime_serialization_wrapper(self):
        assert not lint("src/repro/serving/aotcache.py", self.GOOD_SERIALIZE)

    def test_serialization_allowed_in_runtime_seam(self):
        assert not lint("src/repro/kernels/runtime.py", self.BAD_SERIALIZE)


class TestApiSurface:
    def test_fires_on_engine_module_import(self):
        bad = "from repro.core.dsim import simulate\n"
        assert "api-surface" in names(lint("benchmarks/bench_x.py", bad))

    def test_fires_on_engine_entry_via_aggregate(self):
        bad = "from repro.core import optimize\n"
        assert "api-surface" in names(lint("examples/demo.py", bad))

    def test_fires_on_wrapped_parenthesized_import(self):
        bad = "from repro.core import (\n    clamp_params,\n    pareto_dse,\n)\n"
        assert "api-surface" in names(lint("tools/sweep.py", bad))

    def test_silent_on_facade(self):
        good = "from repro.api import Session, Architecture, Workload\n"
        assert not lint("benchmarks/bench_x.py", good)

    def test_oracle_tag_is_the_escape_hatch(self):
        tagged = "from repro.core.refsim import simulate_ref  # engine-oracle\n"
        assert not lint("benchmarks/bench_x.py", tagged)

    def test_src_is_out_of_scope(self):
        assert not lint("src/repro/serving/engine.py", "from repro.core.dsim import simulate\n")


class TestStaleOracleTag:
    def test_fires_on_tag_without_engine_import(self):
        bad = "import numpy as np  # engine-oracle\n"
        assert "stale-oracle-tag" in names(lint("benchmarks/bench_x.py", bad))

    def test_silent_on_live_tag(self):
        good = "from repro.core.dsim import simulate  # engine-oracle\n"
        assert not lint("benchmarks/bench_x.py", good)

    def test_silent_on_docstring_mention(self):
        good = '"""tagged ``# engine-oracle`` for the API-surface lint."""\n'
        assert not lint("benchmarks/bench_x.py", good)


# --------------------------------------------------------------------------- #
# serving-contract rules
# --------------------------------------------------------------------------- #


class TestHostSync:
    def test_fires_on_float_of_traced_value(self):
        bad = """
            import jax

            @jax.jit
            def f(x):
                return float(x) * 2.0
            """
        assert "host-sync" in names(lint("src/repro/core/x.py", bad))

    def test_fires_on_item_and_device_get(self):
        bad = """
            import jax

            @jax.jit
            def f(x):
                y = jax.device_get(x)
                return y.item()
            """
        assert names(lint("src/repro/core/x.py", bad)) == {"host-sync"}

    def test_fires_in_locally_called_helper(self):
        bad = """
            import jax
            import numpy as np

            def helper(x):
                return np.asarray(x)

            @jax.jit
            def f(x):
                return helper(x)
            """
        assert "host-sync" in names(lint("src/repro/core/x.py", bad))

    def test_silent_outside_traced_region(self):
        good = """
            import numpy as np

            def driver(x):
                return float(np.asarray(x).sum())
            """
        assert not lint("src/repro/core/x.py", good)

    def test_silent_on_host_scalar_param_cast(self):
        good = """
            import jax

            @jax.jit
            def f(x, decay: float):
                return x * float(decay)
            """
        assert not lint("src/repro/core/x.py", good)

    def test_silent_on_host_container_table(self):
        good = """
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                idx = np.array([i for i in range(4)], np.int32)
                return x[idx]
            """
        assert not lint("src/repro/core/x.py", good)


class TestScanDonate:
    BAD = """
        import jax

        def step(c, _):
            return c + 1, None

        @jax.jit
        def chunk(state):
            return jax.lax.scan(step, state, None, length=8)
        """
    GOOD = """
        import functools
        import jax

        def step(c, _):
            return c + 1, None

        @functools.partial(jax.jit, donate_argnums=(0,))
        def chunk(state):
            return jax.lax.scan(step, state, None, length=8)
        """

    def test_fires_on_undonated_scan_carry(self):
        assert "scan-donate" in names(lint("src/repro/core/x.py", self.BAD))

    def test_silent_when_donated(self):
        assert not lint("src/repro/core/x.py", self.GOOD)

    def test_silent_on_jit_without_scan(self):
        good = """
            import jax

            @jax.jit
            def f(x):
                return x + 1
            """
        assert not lint("src/repro/core/x.py", good)


class TestRetraceHazard:
    def test_fires_on_float_static_argname(self):
        bad = """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("lr",))
            def f(x, lr: float):
                return x * lr
            """
        assert "retrace-hazard" in names(lint("src/repro/core/x.py", bad))

    def test_fires_on_float_default(self):
        bad = """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("lr",))
            def f(x, lr=0.05):
                return x * lr
            """
        assert "retrace-hazard" in names(lint("src/repro/core/x.py", bad))

    def test_silent_when_float_is_traced(self):
        good = """
            import jax

            @jax.jit
            def f(x, lr: float):
                return x * lr
            """
        assert not lint("src/repro/core/x.py", good)

    def test_silent_on_structural_statics(self):
        good = """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("spec", "n"))
            def f(x, spec, n: int):
                return x[:n]
            """
        assert not lint("src/repro/core/x.py", good)


class TestStrayDebug:
    def test_fires_on_jax_debug_print(self):
        bad = """
            import jax

            def f(x):
                jax.debug.print("x={}", x)
                return x
            """
        assert "stray-debug" in names(lint("src/repro/core/x.py", bad))

    def test_fires_on_breakpoint(self):
        bad = """
            def f(x):
                breakpoint()
                return x
            """
        assert "stray-debug" in names(lint("src/repro/core/x.py", bad))

    def test_fires_on_print_under_trace(self):
        bad = """
            import jax

            @jax.jit
            def f(x):
                print("tracing", x)
                return x
            """
        assert "stray-debug" in names(lint("src/repro/core/x.py", bad))

    def test_silent_on_driver_print(self):
        good = """
            def report(rows):
                print(len(rows), "rows")
            """
        assert not lint("src/repro/core/x.py", good)

    def test_fires_on_span_under_trace(self):
        bad = """
            import jax
            from repro.core import instrument

            @jax.jit
            def f(x):
                with instrument.span("dragon.x"):
                    return x + 1
            """
        assert "stray-debug" in names(lint("src/repro/core/x.py", bad))

    def test_silent_on_span_around_dispatch(self):
        good = """
            import jax
            from repro.core import instrument

            def run(prog, x):
                with instrument.span("dragon.session.launch", program="report"):
                    return prog(x)
            """
        assert not lint("src/repro/core/x.py", good)


class TestSwallowedFault:
    def test_fires_on_bare_except(self):
        bad = """
            def f(x):
                try:
                    return 1 / x
                except:
                    return 0
            """
        assert "swallowed-fault" in names(lint("src/repro/core/x.py", bad))

    def test_fires_on_except_exception_pass(self):
        bad = """
            def f(x):
                try:
                    return 1 / x
                except Exception:
                    pass
                return 0
            """
        assert "swallowed-fault" in names(lint("src/repro/core/x.py", bad))

    def test_silent_on_typed_handler(self):
        good = """
            def f(x):
                try:
                    return 1 / x
                except ZeroDivisionError:
                    pass
                return 0
            """
        assert "swallowed-fault" not in names(lint("src/repro/core/x.py", good))

    def test_silent_on_handled_broad_exception(self):
        good = """
            def f(x):
                try:
                    return 1 / x
                except Exception as e:
                    raise ValueError(f"bad input: {e}")
            """
        assert "swallowed-fault" not in names(lint("src/repro/core/x.py", good))


class TestFloat64Promotion:
    def test_fires_on_float64_dtype_in_trace(self):
        bad = """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return x.astype(jnp.float64)
            """
        assert "float64-promotion" in names(lint("src/repro/core/x.py", bad))

    def test_fires_on_bare_float_dtype(self):
        bad = """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return jnp.asarray(x, dtype=float)
            """
        assert "float64-promotion" in names(lint("src/repro/core/x.py", bad))

    def test_silent_on_float32(self):
        good = """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return x.astype(jnp.float32)
            """
        assert not lint("src/repro/core/x.py", good)

    def test_silent_on_host_side_float64(self):
        good = """
            import numpy as np

            def summarize(xs):
                return np.asarray(xs, np.float64).mean()
            """
        assert not lint("src/repro/core/x.py", good)


class TestForkUnsafe:
    def test_fires_on_os_fork(self):
        bad = """
            import os

            def spawn_worker():
                pid = os.fork()
            """
        assert "fork-unsafe" in names(lint("src/repro/serving/pool.py", bad))

    def test_fires_on_default_multiprocessing_process(self):
        bad = """
            import multiprocessing

            def spawn_worker(fn):
                p = multiprocessing.Process(target=fn)
                p.start()
            """
        assert "fork-unsafe" in names(lint("src/repro/serving/pool.py", bad))

    def test_fires_on_explicit_fork_context(self):
        bad = """
            import multiprocessing

            ctx = multiprocessing.get_context("fork")
            """
        assert "fork-unsafe" in names(lint("src/repro/serving/pool.py", bad))

    def test_silent_on_subprocess_spawn(self):
        good = """
            import subprocess
            import sys

            def spawn_worker(argv):
                return subprocess.Popen([sys.executable, "-m", "repro.serving.worker"] + argv)
            """
        assert not lint("src/repro/serving/pool.py", good)

    def test_silent_on_spawn_context(self):
        good = """
            import multiprocessing

            ctx = multiprocessing.get_context("spawn")
            """
        assert not lint("src/repro/serving/pool.py", good)

    def test_out_of_scope_path_ignored(self):
        assert not lint("benchmarks/bench_x.py", "import os\npid = os.fork()\n")


# --------------------------------------------------------------------------- #
# engine mechanics: suppressions, parse errors, file mode
# --------------------------------------------------------------------------- #


class TestEngine:
    BAD_LINE = "import jax\n\n@jax.jit\ndef f(x):\n    return float(x)\n"

    def test_suppression_same_line(self):
        src = self.BAD_LINE.replace("return float(x)",
                                    "return float(x)  # dragonlint: disable=host-sync")
        assert not lint("src/repro/core/x.py", src)

    def test_suppression_comment_above(self):
        src = self.BAD_LINE.replace(
            "    return float(x)",
            "    # host scalar by contract -- dragonlint: disable=host-sync\n    return float(x)",
        )
        assert not lint("src/repro/core/x.py", src)

    def test_suppression_all(self):
        src = self.BAD_LINE.replace("return float(x)",
                                    "return float(x)  # dragonlint: disable=all")
        assert not lint("src/repro/core/x.py", src)

    def test_suppression_wrong_rule_does_not_mask(self):
        src = self.BAD_LINE.replace("return float(x)",
                                    "return float(x)  # dragonlint: disable=kernel-seam")
        assert "host-sync" in names(lint("src/repro/core/x.py", src))

    def test_suppressions_parser(self):
        sup = suppressions("x = 1  # dragonlint: disable=a,b\n# dragonlint: disable=c\ny = 2\n")
        assert sup[1] == {"a", "b"}
        assert sup[3] == {"c"}

    def test_parse_error_is_a_finding(self):
        out = lint("src/repro/core/x.py", "def f(:\n")
        assert names(out) == {"parse-error"}

    def test_repo_pass_a_is_clean(self):
        # the acceptance gate: the repo's own tree has no Pass A findings
        findings = run_pass_a(rules=[n for n in RULES if RULES[n].scope == "file"])
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_files_mode_scopes_to_given_files(self, tmp_path):
        findings = run_pass_a(files=["benchmarks/bench_roofline.py"])
        assert findings == []

    def test_write_report_shape(self, tmp_path):
        f = Finding("host-sync", "src/x.py", 3, "msg", "snippet")
        out = write_report(tmp_path, [f], {"findings": [], "coverage": []},
                           path="out/report.json")
        import json

        payload = json.loads(out.read_text())
        assert payload["ok"] is False
        assert payload["pass_a"]["findings"][0]["rule"] == "host-sync"
        assert set(payload["rules"]) == set(RULES)


# --------------------------------------------------------------------------- #
# Pass B: jaxpr hazards + Session.trace_programs coverage
# --------------------------------------------------------------------------- #


class TestJaxprHazards:
    def test_callback_detected(self):
        import jax

        def f(x):
            jax.debug.print("x={}", x)
            return x + 1

        import jax.numpy as jnp

        closed = jax.make_jaxpr(f)(jnp.zeros(4))
        from tools.dragonlint.rules_jaxpr import hazards_in

        assert "jaxpr-callback" in names(hazards_in(closed, "t/cb"))

    def test_large_folded_const_detected(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        table = jnp.asarray(np.ones(8192, np.float32))

        def f(x):
            return x + table

        closed = jax.make_jaxpr(f)(jnp.zeros(8192))
        from tools.dragonlint.rules_jaxpr import hazards_in

        assert "jaxpr-const" in names(hazards_in(closed, "t/const"))

    def test_seam_unsafe_primitive_detected(self):
        import jax
        import jax.numpy as jnp

        def f(x):
            return jnp.fft.fft(x)

        closed = jax.make_jaxpr(f)(jnp.zeros(8, jnp.complex64))
        from tools.dragonlint.rules_jaxpr import hazards_in

        assert "jaxpr-seam" in names(hazards_in(closed, "t/seam"))

    def test_clean_program_is_clean(self):
        import jax
        import jax.numpy as jnp

        def f(x, y):
            return jnp.sum(x * y)

        closed = jax.make_jaxpr(f)(jnp.zeros(16), jnp.ones(16))
        from tools.dragonlint.rules_jaxpr import hazards_in

        assert hazards_in(closed, "t/clean") == []

    def test_recurses_into_scan_bodies(self):
        import jax
        import jax.numpy as jnp

        def step(c, _):
            jax.debug.print("c={}", c)
            return c + 1, None

        def f(c):
            return jax.lax.scan(step, c, None, length=3)

        closed = jax.make_jaxpr(f)(jnp.float32(0.0))
        from tools.dragonlint.rules_jaxpr import hazards_in

        assert "jaxpr-callback" in names(hazards_in(closed, "t/scan"))


class TestTraceProgramsCoverage:
    def test_all_four_kinds_lower_and_are_hazard_free(self):
        from repro.api import Architecture, Session

        from tools.dragonlint.rules_jaxpr import KINDS, hazards_in

        sess = Session(Architecture("edge"))
        progs = sess.trace_programs("bfs_graph")
        assert tuple(sorted(progs)) == tuple(sorted(KINDS))
        for kind, closed in progs.items():
            assert hazards_in(closed, f"edge/{kind}") == []

    def test_kinds_match_session_surface(self):
        from tools.dragonlint.rules_jaxpr import KINDS

        assert set(KINDS) == {"simulate", "explain", "optimize", "frontier"}

    def test_trace_programs_does_not_pollute_session_stats(self):
        from repro.api import Architecture, Session

        sess = Session(Architecture("base"))
        sess.trace_programs("bfs_graph")
        assert sess.stats.traces == 0  # probes hit engine tags, not session tags
        assert sess.stats.programs == 0  # nothing entered the program cache


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
