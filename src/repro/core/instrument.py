"""DRAGON's instrumentation: retrace counters and profiler spans.

**Retrace counters.** JAX re-executes a function's Python body only when it
*traces* (compiles) a new program; steady-state dispatches replay the cached
executable without touching Python.  A counter bumped at the top of a jitted
body is therefore an exact retrace probe: it increments once per compilation
and never on a cache hit.

The engine entry points (``dopt._dopt_step``, ``popsim._member_step``) and
every :class:`repro.api.Session` program call :func:`count_trace` with a tag;
``Session.stats`` and the cache tests read the counters back.  This is the
mechanism behind the façade's serving guarantee — "warm same-bucket calls
never retrace" is asserted, not assumed.

**Spans.** :func:`span` marks one host-side phase — a service chunk, a
program launch, a device-to-host fetch, report building, a DOpt chunk — as
a ``jax.profiler.TraceAnnotation`` named ``dragon.<layer>.<what>``.  Spans
land in the profiler's own trace, on the same clock as the device's
``XLA Ops`` and ``XLA Modules`` lines, with their arguments as event stats.
Starting a profiler session (``jax.profiler.trace(dir)``) is the only
switch: with none active a span costs one check and computes no arguments.
A span's ``chunk`` argument is handed to every span opened inside it on the
same thread, so the spans of one request share an identifier.
:func:`install_gc_spans` adds a ``dragon.gc`` span around every garbage
collection.

Spans belong in host code, once per call or per chunk: inside a jitted body
a span runs at trace time only (``dragonlint``'s ``stray-debug`` rule flags
it), and one per lane or per vertex would cost more than it tells.
"""
from __future__ import annotations

import contextlib
import gc
import threading
from collections import Counter

import jax

_counts: Counter = Counter()


def count_trace(tag: str) -> None:
    """Record one trace of the program ``tag``.  Call this at the top of a
    jit-compiled function body: it runs at trace time only."""
    _counts[tag] += 1


def trace_count(tag: str | None = None, prefix: str | None = None) -> int:
    """Total traces recorded for ``tag``, for all tags starting with
    ``prefix``, or for everything."""
    if tag is not None:
        return _counts[tag]
    if prefix is not None:
        return sum(v for k, v in _counts.items() if k.startswith(prefix))
    return sum(_counts.values())


def snapshot() -> dict:
    """Immutable copy of all counters (for before/after deltas in tests)."""
    return dict(_counts)


def reset(prefix: str | None = None) -> None:
    """Clear counters (optionally only those under ``prefix``).  Test-only:
    resetting does not un-compile anything."""
    if prefix is None:
        _counts.clear()
    else:
        for k in [k for k in _counts if k.startswith(prefix)]:
            del _counts[k]


# --------------------------------------------------------------------------- #
# profiler spans
# --------------------------------------------------------------------------- #

_Annotation = jax.profiler.TraceAnnotation
_tls = threading.local()  # .chunk: the innermost open span's chunk id
_gc_lock = threading.Lock()


class _Inert(contextlib.nullcontext):
    """The span handed out with no profiler session: enters as itself and
    records nothing."""

    def __init__(self):
        super().__init__(self)

    def set(self, **args) -> None:
        pass


_INERT = _Inert()


class _Span:
    """An active span: the annotation plus the thread's inherited chunk id."""

    __slots__ = ("_name", "_args", "_ann", "_outer")

    def __init__(self, name: str, args: dict):
        self._name, self._args = name, args

    def __enter__(self):
        self._outer = getattr(_tls, "chunk", None)
        if "chunk" not in self._args and self._outer is not None:
            self._args["chunk"] = self._outer
        self._ann = _Annotation(self._name, **self._args)
        self._ann.__enter__()
        _tls.chunk = self._args.get("chunk")
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        _tls.chunk = self._outer
        return False

    def set(self, **args) -> None:
        """Add args known only once the spanned work is done."""
        self._ann.set_metadata(**args)


def span(name: str, lazy=None, /, **args):
    """A context manager that records ``name`` as a profiler span.

    ``args`` become the event's stats; ``lazy``, when given, is a callable
    returning a dict of further args, called only when a profiler session is
    active; the entered span's ``set(**args)`` adds args at its end.  With
    no session the call returns a shared inert context and computes
    nothing."""
    if not _Annotation.is_enabled():
        return _INERT
    if lazy is not None:
        args.update(lazy())
    return _Span(name, args)


def _gc_span(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: open ``dragon.gc`` at a collection's start and
    close it at its stop, on the collecting thread."""
    if phase == "start":
        if _Annotation.is_enabled():
            s = _Span("dragon.gc", {"generation": info["generation"]})
            s.__enter__()
            _tls.gc = s
    else:
        s = getattr(_tls, "gc", None)
        if s is not None:
            _tls.gc = None
            s.__exit__(None, None, None)


def install_gc_spans() -> None:
    """Add the ``dragon.gc`` hook to ``gc.callbacks``, once per process."""
    with _gc_lock:
        if _gc_span not in gc.callbacks:
            gc.callbacks.append(_gc_span)
