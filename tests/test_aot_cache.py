"""The persistent AOT executable cache, proven across process boundaries.

Three suites (ISSUE 9 satellites):

* **Cross-process restart** — one subprocess preheats a tmp ``cache_dir``;
  a second subprocess constructs ``Session(cache_dir=...)`` and must serve
  simulate/explain with ZERO traces (instrument probe) and replies
  bit-identical (``to_json`` string-equal) to the preheating process's
  fresh-compiled session — the persistent-cache analogue of PR 8's
  pinned-bucket identity gate.

* **Cache-key properties** (hypothesis via the shim) — equal
  ``(kind, ArchSpec, MapperCfg, bucket[, objective][, request bucket])``
  tuples digest equal across processes; any single-field perturbation
  changes the digest; the digest covers the schema version and the
  jax/jaxlib/backend fingerprint so upgrades miss cleanly.

* **Corruption robustness** — truncated / bit-flipped / zero-length /
  garbage entries classify as transient, fall back to a fresh compile,
  quarantine (rename) the bad file, and never poison the in-memory
  program cache; the chaos harness injects the same fault class
  (``ChaosConfig.p_cache_corrupt``) and retry must clear it.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.api import Session, Workload
from repro.core.mapper import MapperCfg
from repro.core.params import ArchSpec
from repro.kernels import runtime
from repro.serving import aotcache
from repro.serving.aotcache import (
    AotCache,
    CacheCorruption,
    cache_key_digest,
    canonical_key_text,
)
from repro.serving.resilience import classify_exception
from tests._hypothesis_compat import given, settings, st

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run_child(code: str, *argv: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, "-c", code, *argv],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"child failed:\n{out.stderr}"
    return json.loads(out.stdout.strip().splitlines()[-1])


# --------------------------------------------------------------------------- #
# cross-process restart
# --------------------------------------------------------------------------- #

# Preheats AND serves: preheat AOT-compiled the programs in this process, so
# its replies are by construction those of a freshly-compiled session.
_PREHEAT_CHILD = r"""
import json, sys
from repro.api import Session
sess = Session("base", cache_dir=sys.argv[1])
info = sess.preheat(["lstm"], objectives=("edp",), kinds=("simulate", "explain"))
sim = sess.simulate("lstm").to_json()
expl = sess.explain("lstm", objective="edp").to_json()
print(json.dumps(dict(info=info, sim=sim, expl=expl)))
"""

_RESTART_CHILD = r"""
import json, sys
from repro.api import Session
from repro.core import instrument
sess = Session("base", cache_dir=sys.argv[1])
rep = sess.simulate("lstm")
expl = sess.explain("lstm", objective="edp")
print(json.dumps(dict(traces=sess.stats.traces,
                      global_traces=instrument.trace_count(),
                      disk_loaded=sess.disk_loaded,
                      hits=sess.stats.hits, misses=sess.stats.misses,
                      sim=rep.to_json(), expl=expl.to_json())))
"""


@pytest.fixture(scope="module")
def restart_run(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("aot-restart"))
    pre = _run_child(_PREHEAT_CHILD, d)
    post = _run_child(_RESTART_CHILD, d)
    return pre, post


class TestCrossProcessRestart:
    def test_preheat_builds_and_persists(self, restart_run):
        pre, _ = restart_run
        assert pre["info"]["built"] == 2  # report + explain(edp)
        assert pre["info"]["persisted"] == 2

    def test_restarted_process_serves_with_zero_traces(self, restart_run):
        _, post = restart_run
        assert post["disk_loaded"] == 2
        assert post["traces"] == 0
        assert post["global_traces"] == 0  # nothing else traced either

    def test_restarted_replies_bit_identical(self, restart_run):
        pre, post = restart_run
        assert post["sim"] == pre["sim"]
        assert post["expl"] == pre["expl"]

    def test_restarted_cache_lookups_are_hits(self, restart_run):
        _, post = restart_run
        assert post["misses"] == 0
        assert post["hits"] >= 2


# --------------------------------------------------------------------------- #
# cache-key properties
# --------------------------------------------------------------------------- #

_BASE_KEY = ("report", ArchSpec(), MapperCfg(), (1, 32))

# every entry perturbs exactly one component of _BASE_KEY (or its length)
_PERTURBATIONS = (
    ("kind", lambda k: ("explain",) + k[1:]),
    ("spec.mem_type", lambda k: (k[0], dataclasses.replace(k[1], mem_type=("sram", "rram", "dram")), k[2], k[3])),
    ("spec.mem_units", lambda k: (k[0], dataclasses.replace(k[1], mem_units=("l0", "l1", "l2")), k[2], k[3])),
    ("mcfg.headroom", lambda k: (k[0], k[1], dataclasses.replace(k[2], headroom=0.8), k[3])),
    ("mcfg.prefetch", lambda k: (k[0], k[1], dataclasses.replace(k[2], prefetch=False), k[3])),
    ("mcfg.scan_impl", lambda k: (k[0], k[1], dataclasses.replace(k[2], scan_impl="ref"), k[3])),
    ("bucket.w", lambda k: (k[0], k[1], k[2], (2, 32))),
    ("bucket.v", lambda k: (k[0], k[1], k[2], (1, 64))),
    ("objective appended", lambda k: k + ("edp",)),
    ("request bucket appended", lambda k: k + ("edp", 8)),
)

_DIGEST_CHILD = r"""
import json
from repro.core.mapper import MapperCfg
from repro.core.params import ArchSpec
from repro.serving.aotcache import cache_key_digest
keys = [
    ("report", ArchSpec(), MapperCfg(), (1, 32)),
    ("explain", ArchSpec(), MapperCfg(), (1, 32), "edp"),
    ("report_batched", ArchSpec(), MapperCfg(), (4, 64), 8),
    ("explain_batched", ArchSpec(), MapperCfg(), (1, 32), "mixed", 16),
]
print(json.dumps(dict(digests=[cache_key_digest(k) for k in keys])))
"""


class TestCacheKeyDigest:
    def test_equal_tuples_equal_digest(self):
        # fresh, structurally-equal dataclasses — not the same objects
        k2 = ("report", ArchSpec(), MapperCfg(), (1, 32))
        assert cache_key_digest(_BASE_KEY) == cache_key_digest(k2)

    def test_digest_stable_across_processes(self):
        local = [
            cache_key_digest(("report", ArchSpec(), MapperCfg(), (1, 32))),
            cache_key_digest(("explain", ArchSpec(), MapperCfg(), (1, 32), "edp")),
            cache_key_digest(("report_batched", ArchSpec(), MapperCfg(), (4, 64), 8)),
            cache_key_digest(("explain_batched", ArchSpec(), MapperCfg(), (1, 32), "mixed", 16)),
        ]
        assert _run_child(_DIGEST_CHILD)["digests"] == local

    @pytest.mark.parametrize("label,perturb", _PERTURBATIONS, ids=[p[0] for p in _PERTURBATIONS])
    def test_any_single_field_perturbation_changes_digest(self, label, perturb):
        assert cache_key_digest(perturb(_BASE_KEY)) != cache_key_digest(_BASE_KEY), label

    def test_perturbations_pairwise_distinct(self):
        digests = {cache_key_digest(_BASE_KEY)}
        for label, perturb in _PERTURBATIONS:
            d = cache_key_digest(perturb(_BASE_KEY))
            assert d not in digests, f"collision via {label}"
            digests.add(d)

    def test_digest_covers_schema_version(self, monkeypatch):
        d0 = cache_key_digest(_BASE_KEY)
        monkeypatch.setattr(aotcache, "SCHEMA_VERSION", aotcache.SCHEMA_VERSION + 1)
        assert cache_key_digest(_BASE_KEY) != d0

    def test_digest_covers_runtime_fingerprint(self, monkeypatch):
        fp = runtime.executable_fingerprint()
        devices = jax.devices()
        kind, count = f"kind={devices[0].device_kind}", f"count={len(devices)}"
        assert f"|{kind}|" in fp and fp.endswith(f"|{count}")
        d0 = cache_key_digest(_BASE_KEY)
        for other in (
            "jax=9.9.9|jaxlib=9.9.9|backend=tpu",
            fp.replace(kind, "kind=TPU v4"),  # another chip generation
            fp.replace(count, f"count={len(devices) + 3}"),
        ):
            monkeypatch.setattr(runtime, "executable_fingerprint", lambda o=other: o)
            assert cache_key_digest(_BASE_KEY) != d0, other

    def test_unsupported_component_rejected(self):
        with pytest.raises(TypeError, match="unsupported"):
            canonical_key_text(("report", object()))

    @given(
        kind=st.sampled_from(["simulate", "report", "explain", "report_batched"]),
        w=st.integers(1, 64),
        v=st.sampled_from([32, 64, 128, 256]),
        headroom=st.floats(0.05, 0.99, allow_nan=False),
        prefetch=st.booleans(),
        objective=st.sampled_from(["edp", "energy", "time", "mixed"]),
    )
    @settings(max_examples=40, deadline=None)
    def test_digest_equality_iff_canonical_equality(
        self, kind, w, v, headroom, prefetch, objective
    ):
        base = ("report", ArchSpec(), MapperCfg(), (1, 32), "edp")
        drawn = (
            kind, ArchSpec(), MapperCfg(headroom=headroom, prefetch=prefetch),
            (w, v), objective,
        )
        same_text = canonical_key_text(drawn) == canonical_key_text(base)
        same_digest = cache_key_digest(drawn) == cache_key_digest(base)
        assert same_text == same_digest

    @given(h1=st.floats(0.05, 0.99, allow_nan=False), h2=st.floats(0.05, 0.99, allow_nan=False))
    @settings(max_examples=40, deadline=None)
    def test_float_fields_injective(self, h1, h2):
        k1 = ("report", ArchSpec(), MapperCfg(headroom=h1), (1, 32))
        k2 = ("report", ArchSpec(), MapperCfg(headroom=h2), (1, 32))
        assert (cache_key_digest(k1) == cache_key_digest(k2)) == (h1 == h2)


# --------------------------------------------------------------------------- #
# corruption robustness
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def preheated(tmp_path_factory):
    """One in-process preheated cache dir (a single report program) plus the
    fresh-compile reference reply — copied per corruption test."""
    d = str(tmp_path_factory.mktemp("aot-pristine"))
    sess = Session("base", cache_dir=d)
    info = sess.preheat(["lstm"], kinds=("simulate",))
    assert info["persisted"] == 1
    return dict(dir=d, ref=sess.simulate("lstm").to_json())


def _copy_cache(preheated, tmp_path) -> str:
    dst = str(tmp_path / "cache")
    shutil.copytree(preheated["dir"], dst)
    return dst


def _entry_path(d: str) -> str:
    entries = [n for n in os.listdir(d) if n.endswith(".aotx")]
    assert len(entries) == 1
    return os.path.join(d, entries[0])


def _corrupt(path: str, mode: str) -> None:
    data = open(path, "rb").read()
    if mode == "truncate":
        data = data[: len(data) // 2]
    elif mode == "zero_length":
        data = b""
    elif mode == "bit_flip":
        body = bytearray(data)
        body[len(body) // 2] ^= 0xFF
        data = bytes(body)
    elif mode == "garbage":
        data = b"not a cache entry at all"
    else:  # pragma: no cover
        raise AssertionError(mode)
    with open(path, "wb") as f:
        f.write(data)


class TestCorruptionRobustness:
    MODES = ("truncate", "zero_length", "bit_flip", "garbage")

    def test_entry_of_another_schema_is_a_clean_miss(self, tmp_path, preheated, monkeypatch):
        """An entry written under an older schema (programs whose outputs
        had another tree) is never deserialized: construction rejects it,
        leaves it in place, and the session compiles afresh."""
        d = _copy_cache(preheated, tmp_path)
        entry = _entry_path(d)
        monkeypatch.setattr(aotcache, "SCHEMA_VERSION", aotcache.SCHEMA_VERSION + 1)

        def refuse(blob):
            raise AssertionError("deserialized an entry of another schema")

        monkeypatch.setattr(runtime, "deserialize_compiled", refuse)
        sess = Session("base", cache_dir=d)
        assert sess.disk_loaded == 0 and sess.programs == {}
        assert sess._aot.stats()["rejected"] == 1 and sess._aot.stats()["quarantined"] == 0
        key, _ = sess._report_spec((1, 32), sess.architecture.spec, sess.mcfg)
        assert sess._aot.get(key) is None
        assert os.path.exists(entry)
        assert sess.simulate("lstm").to_json() == preheated["ref"]

    @pytest.mark.parametrize("mode", MODES)
    def test_corrupt_entry_quarantined_and_recompiled(self, mode, tmp_path, preheated):
        d = _copy_cache(preheated, tmp_path)
        _corrupt(_entry_path(d), mode)
        sess = Session("base", cache_dir=d)
        # nothing loaded, in-memory cache not poisoned
        assert sess.disk_loaded == 0
        assert sess.programs == {}
        # the bad file left the cache namespace, bytes kept for post-mortem
        names = os.listdir(d)
        assert not any(n.endswith(".aotx") for n in names)
        assert any(".quarantined" in n for n in names)
        # serving falls back to a fresh compile with the identical reply
        rep = sess.simulate("lstm")
        assert sess.stats.traces == 1
        assert rep.to_json() == preheated["ref"]
        # and the recompiled program is warm — the corruption cost one compile
        assert sess.simulate("lstm").to_json() == preheated["ref"]
        assert sess.stats.traces == 1

    @pytest.mark.parametrize("mode", MODES)
    def test_lazy_get_never_raises(self, mode, tmp_path, preheated):
        d = _copy_cache(preheated, tmp_path)
        path = _entry_path(d)
        _corrupt(path, mode)
        cache = AotCache(d)
        key = ("report", ArchSpec(), MapperCfg(), (1, 32))
        assert cache.get(key) is None
        assert cache.load_all() == {}
        assert cache.quarantined >= 1

    def test_quarantine_survives_repeat_corruption(self, tmp_path, preheated):
        d = _copy_cache(preheated, tmp_path)
        path = _entry_path(d)
        _corrupt(path, "bit_flip")
        cache = AotCache(d)
        assert cache.load_all() == {}
        # a second bad file with the same name quarantines alongside, not over
        shutil.copy(os.path.join(preheated["dir"], os.path.basename(path)), path)
        _corrupt(path, "truncate")
        assert cache.load_all() == {}
        assert sum(".quarantined" in n for n in os.listdir(d)) == 2

    def test_foreign_fingerprint_is_clean_miss_not_quarantine(
        self, tmp_path, preheated, monkeypatch
    ):
        d = _copy_cache(preheated, tmp_path)
        monkeypatch.setattr(
            runtime, "executable_fingerprint",
            lambda: "jax=9.9.9|jaxlib=9.9.9|backend=tpu",
        )
        cache = AotCache(d)
        assert cache.load_all() == {}
        assert cache.rejected == 1
        assert cache.quarantined == 0
        # the entry stays on disk: it belongs to another runtime, not the bin
        assert any(n.endswith(".aotx") for n in os.listdir(d))

    def test_pristine_copy_still_loads(self, tmp_path, preheated):
        d = _copy_cache(preheated, tmp_path)
        sess = Session("base", cache_dir=d)
        assert sess.disk_loaded == 1
        assert sess.simulate("lstm").to_json() == preheated["ref"]
        assert sess.stats.traces == 0

    def test_cache_corruption_classifies_transient(self):
        fault = classify_exception(CacheCorruption("torn entry"))
        assert fault.code == "transient"
        assert fault.retryable

    def test_chaos_injected_corruption_clears_on_retry(self):
        from repro.serving import (
            ChaosConfig,
            ChaosInjector,
            DesignQuery,
            DesignService,
            RetryPolicy,
        )

        inj = ChaosInjector(ChaosConfig(seed=11, p_cache_corrupt=1.0), sleep=lambda s: None)
        svc = DesignService(
            "base", chaos=inj, retry=RetryPolicy(max_attempts=3, base_s=0.001)
        )
        r = svc.submit(DesignQuery(0, "simulate", "lstm"))
        assert r.ok and r.attempts == 2
        assert inj.summary() == {"cache_corrupt": 1}
        assert svc.stats.availability == 1.0


# --------------------------------------------------------------------------- #
# preheat semantics (in-process)
# --------------------------------------------------------------------------- #


class TestPreheat:
    def test_preheat_idempotent_and_disk_warm(self, preheated):
        sess = Session("base", cache_dir=preheated["dir"])
        assert sess.disk_loaded == 1
        info = sess.preheat(["lstm"], kinds=("simulate",))
        assert info == dict(
            programs=1, built=0, reused=1, persisted=0, seconds=info["seconds"]
        )
        assert sess.stats.traces == 0
        assert sess.simulate("lstm").to_json() == preheated["ref"]
        assert sess.stats.traces == 0

    def test_preheat_by_bare_bucket_tuple(self, tmp_path, preheated):
        # shapes are all compilation needs: a zero-filled synthetic stack
        # preheats the very program that serves the real workload
        sess = Session("base", cache_dir=str(tmp_path))
        info = sess.preheat([(1, 32)], kinds=("simulate",))
        assert info["built"] == 1 and info["persisted"] == 1
        assert sess.stats.traces == 1  # the preheat compile itself
        rep = sess.simulate("lstm")  # lstm stacks into bucket (1, 32)
        assert sess.stats.traces == 1  # the serve added none
        assert rep.to_json() == preheated["ref"]

    def test_preheat_rejects_unknown_kind(self):
        with pytest.raises(ValueError, match="preheat kinds"):
            Session("base").preheat(["lstm"], kinds=("simulate", "frontier"))

    def test_preheat_without_cache_dir_is_in_memory_only(self):
        sess = Session("base")
        info = sess.preheat([(1, 32)], kinds=("simulate",))
        assert info["built"] == 1 and info["persisted"] == 0
        assert sess.stats.traces == 1
        sess.simulate("lstm")
        assert sess.stats.traces == 1  # AOT program serves, no retrace

    def test_bucket_dedupe_one_build_per_bucket(self, tmp_path):
        sess = Session("base", cache_dir=str(tmp_path))
        # lstm and merge_sort share bucket (1, 32): one program, not two
        info = sess.preheat(["lstm", "merge_sort"], kinds=("simulate",))
        assert info == dict(
            programs=1, built=1, reused=0, persisted=1, seconds=info["seconds"]
        )
