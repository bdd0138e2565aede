"""The runtime seam: compiler-params construction under monkeypatch
(CompilerParams present or absent), jax.shard_map resolution and its
check_vma keyword, interpret mode on CPU only, block clamping, and the
persistent compile-cache helper."""
import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import runtime


class NewStyleParams:
    def __init__(self, dimension_semantics=None):
        self.dimension_semantics = dimension_semantics


class TestCompilerParams:
    def test_prefers_new_spelling(self, monkeypatch):
        fake = SimpleNamespace(CompilerParams=NewStyleParams)
        monkeypatch.setattr(runtime, "pltpu", fake)
        p = runtime.tpu_compiler_params(dimension_semantics=("parallel",))
        assert isinstance(p, NewStyleParams)
        assert p.dimension_semantics == ("parallel",)

    def test_neither_spelling_returns_none(self, monkeypatch):
        monkeypatch.setattr(runtime, "pltpu", SimpleNamespace())
        assert runtime.tpu_compiler_params(dimension_semantics=("parallel",)) is None

    def test_no_tpu_module_returns_none(self, monkeypatch):
        monkeypatch.setattr(runtime, "pltpu", None)
        assert runtime.tpu_compiler_params(dimension_semantics=("parallel",)) is None

    def test_unknown_kwargs_dropped(self, monkeypatch):
        fake = SimpleNamespace(CompilerParams=NewStyleParams)
        monkeypatch.setattr(runtime, "pltpu", fake)
        p = runtime.tpu_compiler_params(
            dimension_semantics=("parallel",), serial_iteration_hints=123
        )
        assert isinstance(p, NewStyleParams)

    def test_real_install_resolves(self):
        # the installed JAX ships pltpu.CompilerParams
        p = runtime.tpu_compiler_params(dimension_semantics=("parallel",))
        assert p is not None


class TestShardMapResolution:
    def test_prefers_stable_spelling(self, monkeypatch):
        sentinel = lambda *a, **k: "stable"  # noqa: E731
        monkeypatch.setattr(jax, "shard_map", sentinel, raising=False)
        assert runtime.resolve_shard_map() is sentinel

    def test_spmd_map_adapts_check_vma_keyword(self, monkeypatch):
        seen = {}

        def fake_sm(f, *, mesh, in_specs, out_specs, check_vma=True):
            seen.update(check_vma=check_vma)
            return f

        monkeypatch.setattr(jax, "shard_map", fake_sm, raising=False)
        runtime.spmd_map(lambda x: x, mesh="M", in_specs=(), out_specs=(), check=True)
        assert seen == {"check_vma": True}

    def test_spmd_map_raises_without_shard_map(self, monkeypatch):
        monkeypatch.delattr(jax, "shard_map", raising=False)
        assert runtime.resolve_shard_map() is None
        with pytest.raises(RuntimeError, match="jax.shard_map"):
            runtime.spmd_map(lambda x: x, mesh=None, in_specs=(), out_specs=())


class TestDispatch:
    def test_auto_interpret_tracks_backend(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert runtime.auto_interpret() is True
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert runtime.auto_interpret() is False

    @pytest.mark.parametrize("backend", ["gpu", "METAL"])
    def test_other_backends_refuse_to_interpret(self, monkeypatch, backend):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        with pytest.raises(RuntimeError, match=repr(backend)):
            runtime.resolve_interpret(None)
        assert runtime.resolve_interpret(True) is True  # explicit stays explicit

    @pytest.mark.parametrize("backend,expect_interpret", [("cpu", True), ("tpu", False)])
    def test_dragon_pallas_call_mode_selection(self, monkeypatch, backend, expect_interpret):
        captured = {}

        def fake_pallas_call(kernel, **kwargs):
            captured.update(kwargs)
            return lambda *operands: None

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(runtime.pl, "pallas_call", fake_pallas_call)
        runtime.dragon_pallas_call(
            lambda x_ref, o_ref: None,
            grid=(1,),
            in_specs=[],
            out_specs=None,
            out_shape=jax.ShapeDtypeStruct((4,), jnp.float32),
            dimension_semantics=("parallel",),
        )()
        assert captured["interpret"] is expect_interpret
        assert captured["compiler_params"] is not None
        assert captured["compiler_params"].dimension_semantics == ("parallel",)

    def test_dragon_pallas_call_omits_params_when_unresolvable(self, monkeypatch):
        captured = {}

        def fake_pallas_call(kernel, **kwargs):
            captured.update(kwargs)
            return lambda *operands: None

        monkeypatch.setattr(runtime.pl, "pallas_call", fake_pallas_call)
        monkeypatch.setattr(runtime, "pltpu", None)
        runtime.dragon_pallas_call(
            lambda x_ref, o_ref: None,
            grid=(1,),
            in_specs=[],
            out_specs=None,
            out_shape=jax.ShapeDtypeStruct((4,), jnp.float32),
            dimension_semantics=("parallel",),
            interpret=True,
        )()
        assert "compiler_params" not in captured

    def test_vmem_scratch_without_tpu_module_raises_descriptively(self, monkeypatch):
        monkeypatch.setattr(runtime, "pltpu", None)
        with pytest.raises(RuntimeError, match="no portable scratch spelling"):
            runtime.vmem_scratch((4, 4), jnp.float32)

    def test_end_to_end_interpret_kernel(self):
        """A real (tiny) kernel through the seam in interpret mode."""
        from jax.experimental import pallas as pl

        def double(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        x = jnp.arange(8, dtype=jnp.float32).reshape(2, 4)
        y = runtime.dragon_pallas_call(
            double,
            grid=(2,),
            in_specs=[pl.BlockSpec((1, 4), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 4), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            dimension_semantics=("parallel",),
            interpret=None,  # auto: CPU backend -> interpret
        )(x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x) * 2.0)

    def test_spmd_map_end_to_end(self):
        """Real shard-map through the seam on the 1-device CPU mesh."""
        from jax.sharding import PartitionSpec as P

        mesh = jax.make_mesh((1,), ("data",))
        fn = runtime.spmd_map(
            functools.partial(jax.lax.psum, axis_name="data"),
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P(),
            check=False,
        )
        x = jnp.ones((4,), jnp.float32)
        np.testing.assert_allclose(np.asarray(fn(x)), np.ones(4))


class TestBlockClamping:
    def test_clamp_block(self):
        assert runtime.clamp_block(512, 128) == 128
        assert runtime.clamp_block(64, 128) == 64

    def test_clamp_block_rejects_non_tiling(self):
        with pytest.raises(ValueError, match="block_q"):
            runtime.clamp_block(128, 300, name="block_q")

    def test_gcd_block_always_tiles(self):
        for block, size in [(128, 300), (128, 128), (7, 13), (1000, 4)]:
            b = runtime.gcd_block(block, size)
            assert b >= 1 and size % b == 0


class TestCompileCache:
    def _updates(self, monkeypatch, chips=1):
        seen = {}
        monkeypatch.setattr(runtime, "local_tpu_chips", lambda: chips)
        monkeypatch.setattr(jax.config, "update", lambda k, v: seen.__setitem__(k, v))
        return seen

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        seen = self._updates(monkeypatch)
        assert runtime.enable_compile_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in seen
        assert seen["jax_persistent_cache_min_compile_time_secs"] == 0.0

    def test_default_is_fixed_path_in_checkout(self, monkeypatch):
        import tempfile
        from pathlib import Path

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        seen = self._updates(monkeypatch)
        got = runtime.enable_compile_cache()
        assert got == seen["jax_compilation_cache_dir"] == runtime.enable_compile_cache()
        repo = Path(__file__).resolve().parents[1]
        assert Path(got) == repo / ".jax_cache"
        assert not Path(got).is_relative_to(tempfile.gettempdir())
        assert seen["jax_persistent_cache_min_compile_time_secs"] == 0.0

    def test_no_tpu_attached_sets_nothing(self, monkeypatch, tmp_path):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        seen = self._updates(monkeypatch, chips=0)
        assert runtime.enable_compile_cache() is None
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert runtime.enable_compile_cache() == str(tmp_path)  # JAX's own reading
        assert seen == {}
