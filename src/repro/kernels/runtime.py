"""The JAX/Pallas runtime seam — the ONE place that spells Pallas compiler
params, shard-map, executable (de)serialization and the compile cache.

Every kernel and every explicit-SPMD call site routes through this module so
the rest of the codebase never spells these APIs itself:

  * :func:`tpu_compiler_params` — construct ``pltpu.CompilerParams``
    (``None`` when no TPU Pallas module is available).
  * :func:`resolve_shard_map` / :func:`spmd_map` — ``jax.shard_map`` with
    its ``check_vma`` replication check.
  * :func:`dragon_pallas_call` — the single ``pl.pallas_call`` wrapper:
    interpret mode on the CPU backend only, Mosaic on TPU, an error
    anywhere else; compiler-params construction and scratch plumbing.
  * :func:`clamp_block` / :func:`gcd_block` — centralized block-size clamping.
  * :func:`vmem_scratch` — VMEM scratch allocation without importing pltpu.
  * :func:`serialize_compiled` / :func:`deserialize_compiled` /
    :func:`executable_fingerprint` — the executable (de)serialization seam
    (``jax.experimental.serialize_executable``) behind the persistent AOT
    cache; the fingerprint names the runtime and device an artifact is
    valid under.
  * :func:`enable_compile_cache` — JAX's persistent compilation cache at a
    fixed path (or wherever ``JAX_COMPILATION_CACHE_DIR`` points).

Resolution is performed at call time (never cached) so tests can monkeypatch
the backend and module attributes.
"""
from __future__ import annotations

import inspect
import math
import os
import warnings
from pathlib import Path
from typing import Any, Callable, Sequence

import jax
from jax.experimental import pallas as pl

try:  # pltpu imports cleanly on CPU-only installs; gate it anyway.
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover - exercised via monkeypatch in tests
    pltpu = None


# --------------------------------------------------------------------------- #
# backend detection
# --------------------------------------------------------------------------- #


def auto_interpret() -> bool:
    """True when Pallas kernels must run in interpret mode.

    Kernels compile through Mosaic on the TPU backend and run in the Pallas
    interpreter on the CPU backend (the test path).  Any other backend is an
    error: an interpreted kernel there would run, slowly, while the program
    reported a device run.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"DSim Pallas kernels compile for 'tpu' and interpret on 'cpu'; "
        f"backend {backend!r} is neither"
    )


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve the tri-state ``interpret`` convention: None means auto."""
    return auto_interpret() if interpret is None else bool(interpret)


# --------------------------------------------------------------------------- #
# compiler params
# --------------------------------------------------------------------------- #


def tpu_compiler_params(**kw) -> Any | None:
    """Build ``pltpu.CompilerParams``; ``None`` (caller omits the argument)
    when the install has no TPU Pallas module.  Keywords the class does not
    accept are dropped."""
    cls = getattr(pltpu, "CompilerParams", None) if pltpu is not None else None
    if cls is None:
        return None
    try:
        accepted = inspect.signature(cls).parameters
    except (TypeError, ValueError):  # pragma: no cover - exotic builds
        return cls(**kw)
    if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in accepted.values()):
        return cls(**kw)
    return cls(**{k: v for k, v in kw.items() if k in accepted})


# --------------------------------------------------------------------------- #
# shard-map
# --------------------------------------------------------------------------- #


def resolve_shard_map() -> Callable | None:
    """``jax.shard_map``, or ``None`` if the installed JAX lacks it."""
    return getattr(jax, "shard_map", None)


def spmd_map(fn: Callable, *, mesh, in_specs, out_specs, check: bool = True) -> Callable:
    """Shard-map wrapper — the only sanctioned call site API.  ``check`` is
    ``jax.shard_map``'s ``check_vma`` (varying-manual-axes type check)."""
    sm = resolve_shard_map()
    if sm is None:
        raise RuntimeError("the installed JAX has no jax.shard_map")
    return sm(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check)


# --------------------------------------------------------------------------- #
# block-size clamping
# --------------------------------------------------------------------------- #


def clamp_block(block: int, size: int, *, name: str = "block") -> int:
    """Clamp a block size to the dimension extent; the result must tile it."""
    b = min(int(block), int(size))
    if b <= 0 or size % b != 0:
        raise ValueError(f"{name}={block} cannot tile extent {size} (clamped to {b})")
    return b


def gcd_block(block: int, size: int) -> int:
    """Largest divisor of ``size`` that is <= gcd(block, size) — always tiles."""
    return max(int(math.gcd(int(block), int(size))), 1)


# --------------------------------------------------------------------------- #
# scratch + the pallas_call seam
# --------------------------------------------------------------------------- #


def vmem_scratch(shape: Sequence[int], dtype) -> Any:
    """A VMEM scratch allocation, without the caller importing pltpu.

    Unlike compiler params (which degrade to "omit the argument"), scratch
    has no pltpu-free spelling — even interpret mode rejects a plain
    ShapeDtypeStruct — so an install without the TPU Pallas module gets a
    hard, descriptive error rather than silent misbehavior.
    """
    if pltpu is None:
        raise RuntimeError(
            "jax.experimental.pallas.tpu is unavailable in this install; "
            "scratch-using kernels need it even in interpret mode (there is "
            "no portable scratch spelling)."
        )
    return pltpu.VMEM(tuple(shape), dtype)


def backend_initialized() -> bool:
    """Whether this process has initialized a JAX backend — on a TPU host,
    whether it already holds the chips (a chip serves one process)."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def local_tpu_chips() -> int:
    """TPU chips attached to this host, counted on the PCI bus without
    initializing JAX (so without taking them)."""
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def executable_fingerprint() -> str:
    """The runtime identity a serialized executable is only valid under.

    Compiled artifacts are specific to the jax/jaxlib pair that lowered
    them and to the device they were compiled for (one TPU generation's
    executable does not run on another); the persistent AOT cache
    (:mod:`repro.serving.aotcache`) folds this string into every cache-key
    digest so a changed runtime or device misses cleanly instead of
    deserializing a stale executable.
    """
    import jaxlib

    devices = jax.devices()
    return (
        f"jax={jax.__version__}|jaxlib={jaxlib.__version__}"
        f"|backend={jax.default_backend()}|kind={devices[0].device_kind}"
        f"|count={len(devices)}"
    )


def serialize_compiled(compiled) -> bytes | None:
    """Serialize a ``jax.stages.Compiled`` into one portable byte string.

    Returns ``None`` when ``compiled`` is not an AOT-compiled stage (plain
    ``jax.jit`` wrappers cannot be snapshotted) or when the backend refuses;
    callers treat ``None`` as "this program cannot be persisted".  Every
    ``None`` comes with a ``RuntimeWarning`` that says why, so a cache that
    persists nothing is visible.
    """
    import pickle

    from jax.experimental import serialize_executable as se

    try:
        payload, in_tree, out_tree = se.serialize(compiled)
    except Exception as e:  # reported, then treated as "cannot persist"
        warnings.warn(
            f"serialize_compiled: {type(e).__name__}: {e}", RuntimeWarning, stacklevel=2
        )
        return None
    return pickle.dumps((payload, in_tree, out_tree), protocol=pickle.HIGHEST_PROTOCOL)


def deserialize_compiled(data: bytes):
    """Rehydrate :func:`serialize_compiled` output into a loaded executable.

    Raises on malformed bytes — the cache layer catches, quarantines the
    source file, and falls back to a fresh compile.
    """
    import pickle

    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree = pickle.loads(data)
    return se.deserialize_and_load(payload, in_tree, out_tree)


def dragon_pallas_call(
    kernel: Callable,
    *,
    grid,
    in_specs,
    out_specs,
    out_shape,
    scratch_shapes: Sequence[Any] | None = None,
    dimension_semantics: Sequence[str] | None = None,
    interpret: bool | None = None,
    **compiler_kw,
) -> Callable:
    """The single ``pl.pallas_call`` wrapper all DSim kernels go through.

    * ``interpret=None`` means Mosaic on TPU and the interpreter on the CPU
      backend (the test path); any other backend raises
      (:func:`auto_interpret`).
    * ``dimension_semantics`` (plus any extra ``compiler_kw``) is turned into
      compiler params via :func:`tpu_compiler_params`; when the install has
      no TPU Pallas module the argument is omitted entirely.
    """
    interpret = resolve_interpret(interpret)
    kwargs: dict[str, Any] = dict(
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )
    if scratch_shapes:
        kwargs["scratch_shapes"] = list(scratch_shapes)
    if dimension_semantics is not None:
        compiler_kw = dict(compiler_kw, dimension_semantics=tuple(dimension_semantics))
    if compiler_kw:
        params = tpu_compiler_params(**compiler_kw)
        if params is not None:
            kwargs["compiler_params"] = params
    return pl.pallas_call(kernel, **kwargs)


# --------------------------------------------------------------------------- #
# persistent compilation cache
# --------------------------------------------------------------------------- #

# <checkout>/.jax_cache: fixed, so a later run finds what an earlier one wrote
# (the path is part of each entry's key); gitignored
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache on a TPU host; returns the
    directory JAX caches in (``None``: no cache).

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and no
    directory is set here; otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR`.  The minimum compile time is lowered to zero
    so the ~1 s DSim programs are cached too (JAX's default skips them).

    On a host with no TPU attached this sets nothing: an XLA:CPU executable
    that JAX's cache hands back cannot be serialized again for the AOT cache
    (:mod:`repro.serving.aotcache`) — the copy fails when it runs — and CPU
    compiles are cheap.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
    if local_tpu_chips() == 0:
        return env_dir
    if env_dir is None:
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return env_dir or str(COMPILE_CACHE_DIR)
