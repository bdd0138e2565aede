"""Persistent AOT executable cache — compiled programs that survive restart.

Warm façade calls run at ~0.4 ms but every cold ``(kind, spec, bucket,
objective)`` pays ~0.7–1.1 s of trace+compile; a fleet worker restarting
under traffic eats that per program (ROADMAP open item 2).  This module is
the on-disk half of the fix: :class:`AotCache` persists executables that
``Session.preheat`` built via ``jax.jit(...).lower().compile()``, and a
restarted ``Session(cache_dir=...)`` loads them back so its first query
dispatches a deserialized executable — zero traces, bit-identical replies
(the artifact *is* the bytes the fresh compile produced).

Keying
------

Entries are addressed by :func:`cache_key_digest`: a SHA-256 over

  * a cache **schema version** (bump it to invalidate every entry on a
    format change),
  * the **runtime fingerprint** (jax + jaxlib versions, the backend, the
    device kind and count, from
    ``repro.kernels.runtime.executable_fingerprint`` — an upgraded runtime
    or another chip misses cleanly instead of deserializing a stale
    executable),
  * a **canonical text encoding** of the existing Session program-cache
    key — ``(kind, ArchSpec, MapperCfg, bucket[, objective][, request
    bucket])`` — encoded field-by-field (:func:`canonical_key_text`), never
    via Python ``hash()`` (which is salted per process).

Robustness
----------

Reads never raise.  A truncated / bit-flipped / zero-length entry fails
the checksum (or unpickling) and is **quarantined** — renamed to
``*.quarantined`` so it can never be read as a cache entry again, while
the bytes stay on disk for post-mortem — and the caller falls back to a
fresh compile.  A schema or fingerprint mismatch is a *clean miss*: the
entry is left in place (it belongs to another runtime).  Writes are
atomic (temp file + rename) so a crashed writer can never publish a torn
entry.  :class:`CacheCorruption` subclasses ``TransientFault`` — the
chaos harness injects it (``ChaosConfig.p_cache_corrupt``) to prove the
retry loop clears it.

Entries carry pickled executables; a cache directory is trusted local
state (like ``__pycache__``), not an interchange format — don't load
cache directories from untrusted sources.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
import tempfile

from repro.kernels import runtime
from repro.serving.resilience import TransientFault

__all__ = [
    "AotCache",
    "CacheCorruption",
    "SCHEMA_VERSION",
    "cache_key_digest",
    "canonical_key_text",
]

# 2: report and explain programs return packed buffers, so an executable
# keyed as before returns another output tree
SCHEMA_VERSION = 2

_MAGIC = b"DRGNAOT\x01"
_SUFFIX = ".aotx"
_QUARANTINE = ".quarantined"
_CHECKSUM_BYTES = 32  # sha256 of the body, stored right after the magic


class CacheCorruption(TransientFault):
    """A persisted executable failed its checksum or deserialization.

    Transient by construction: the reader quarantines the bad file and
    falls back to a fresh compile, so a retry serves from a clean slate.
    The wire code stays ``"transient"`` — no new alert class for fleets.
    """


# --------------------------------------------------------------------------- #
# key canonicalization + digest
# --------------------------------------------------------------------------- #


def canonical_key_text(key) -> str:
    """Deterministic text encoding of a Session program-cache key.

    Frozen dataclasses (``ArchSpec``, ``MapperCfg``) encode as
    ``ClassName(field=value, ...)`` over their declared fields, scalars by
    ``repr`` — every component lands in the text, so any single-field
    perturbation changes the digest, and equal keys encode equally in any
    process (property-tested in ``tests/test_aot_cache.py``).
    """
    if dataclasses.is_dataclass(key) and not isinstance(key, type):
        inner = ",".join(
            f"{f.name}={canonical_key_text(getattr(key, f.name))}"
            for f in dataclasses.fields(key)
        )
        return f"{type(key).__qualname__}({inner})"
    if isinstance(key, (tuple, list)):
        return "(" + ",".join(canonical_key_text(x) for x in key) + ")"
    if key is None or isinstance(key, (bool, int, float, str)):
        return repr(key)
    raise TypeError(
        f"cache key contains an unsupported component {type(key).__name__}: {key!r}"
    )


def cache_key_digest(key, *, schema: int | None = None, fingerprint: str | None = None) -> str:
    """SHA-256 hex digest addressing one persisted executable.

    Covers the schema version and the runtime fingerprint in addition to
    the key itself, so format changes and jax/jaxlib/backend upgrades both
    invalidate by *missing*, never by deserializing the wrong artifact.
    """
    if schema is None:
        schema = SCHEMA_VERSION
    if fingerprint is None:
        fingerprint = runtime.executable_fingerprint()
    text = f"dragon-aot|v{schema}|{fingerprint}|{canonical_key_text(key)}"
    return hashlib.sha256(text.encode()).hexdigest()


# --------------------------------------------------------------------------- #
# the cache
# --------------------------------------------------------------------------- #


class AotCache:
    """One directory of serialized executables, one file per program key.

    File layout: ``dragon-<digest32>.aotx`` = magic + sha256(body) + body,
    where body pickles ``{schema, fingerprint, key, blob}`` and ``blob`` is
    ``runtime.serialize_compiled`` output.  All read paths return misses
    instead of raising; corrupt files are quarantined via :meth:`_quarantine`.
    """

    def __init__(self, path):
        self.path = str(path)
        os.makedirs(self.path, exist_ok=True)
        self.loaded = 0  # entries successfully deserialized
        self.written = 0  # entries persisted by this process
        self.rejected = 0  # clean misses: schema/fingerprint from another runtime
        self.quarantined = 0  # corrupt files renamed out of the namespace

    # -------------------------------------------------------------- naming --
    def _file(self, key) -> str:
        return os.path.join(self.path, f"dragon-{cache_key_digest(key)[:32]}{_SUFFIX}")

    def entries(self) -> list[str]:
        """Cache-entry file names currently in the directory (sorted)."""
        return sorted(n for n in os.listdir(self.path) if n.endswith(_SUFFIX))

    def has(self, key) -> bool:
        return os.path.exists(self._file(key))

    def stats(self) -> dict:
        return dict(
            entries=len(self.entries()),
            loaded=self.loaded,
            written=self.written,
            rejected=self.rejected,
            quarantined=self.quarantined,
        )

    # ------------------------------------------------------------- writing --
    def put(self, key, compiled) -> bool:
        """Persist one executable; returns True iff a new entry was written.

        Skips keys already on disk and programs that cannot be serialized
        (plain jit wrappers; ``serialize_compiled`` warns why) — persisting
        is best-effort, serving never depends on it.
        """
        path = self._file(key)
        if os.path.exists(path):
            return False
        blob = runtime.serialize_compiled(compiled)
        if blob is None:
            return False
        body = pickle.dumps(
            dict(
                schema=SCHEMA_VERSION,
                fingerprint=runtime.executable_fingerprint(),
                key=key,
                blob=blob,
            ),
            protocol=pickle.HIGHEST_PROTOCOL,
        )
        # multi-writer safe: N worker processes racing the same digest each
        # write a private tmp (mkstemp randomizes the name; the pid suffix
        # additionally namespaces writers, and makes a stray tmp attributable
        # post-mortem) and publish via atomic rename — last rename wins with
        # byte-identical content, readers never observe a torn file
        fd, tmp = tempfile.mkstemp(dir=self.path, suffix=f".{os.getpid()}.tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(_MAGIC + hashlib.sha256(body).digest() + body)
            os.replace(tmp, path)  # atomic publish: readers see whole files only
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        self.written += 1
        return True

    # ------------------------------------------------------------- reading --
    def get(self, key):
        """The loaded executable for ``key``, or None (miss / rejected /
        quarantined).  Never raises."""
        path = self._file(key)
        if not os.path.exists(path):
            return None
        record = self._read_record(path)
        if record is None:
            return None
        if record["key"] != key:
            # digest collision or a tampered record: impossible by
            # construction, so treat as corruption
            self._quarantine(path)
            return None
        return self._load(record, path)

    def load_all(self) -> dict:
        """Every valid entry, as ``{session cache key: loaded executable}`` —
        the restart path: feed straight into ``Session(programs=...)``."""
        out: dict = {}
        for name in self.entries():
            path = os.path.join(self.path, name)
            record = self._read_record(path)
            if record is None:
                continue
            fn = self._load(record, path)
            if fn is not None:
                out[record["key"]] = fn
        return out

    def _read_record(self, path: str) -> dict | None:
        """Read + verify one entry file.  None on any failure: corruption is
        quarantined, foreign schema/fingerprint is a clean miss."""
        try:
            with open(path, "rb") as f:
                payload = f.read()
            header = len(_MAGIC) + _CHECKSUM_BYTES
            if len(payload) < header or not payload.startswith(_MAGIC):
                raise CacheCorruption(f"bad header: {os.path.basename(path)}")
            body = payload[header:]
            if hashlib.sha256(body).digest() != payload[len(_MAGIC):header]:
                raise CacheCorruption(f"checksum mismatch: {os.path.basename(path)}")
            record = pickle.loads(body)
            if not isinstance(record, dict) or "key" not in record or "blob" not in record:
                raise CacheCorruption(f"malformed record: {os.path.basename(path)}")
        except Exception:
            self._quarantine(path)
            return None
        if (
            record.get("schema") != SCHEMA_VERSION
            or record.get("fingerprint") != runtime.executable_fingerprint()
        ):
            self.rejected += 1
            return None
        return record

    def _load(self, record: dict, path: str):
        """Deserialize a verified record; quarantine on executable rejection
        (checksum passed but the runtime refused the artifact)."""
        try:
            fn = runtime.deserialize_compiled(record["blob"])
        except Exception:
            self._quarantine(path)
            return None
        self.loaded += 1
        return fn

    def _quarantine(self, path: str) -> None:
        """Rename, never delete: the bytes stay for post-mortem and can
        never be read as a cache entry again."""
        dst = path + _QUARANTINE
        n = 0
        while os.path.exists(dst):
            n += 1
            dst = f"{path}{_QUARANTINE}.{n}"
        try:
            os.replace(path, dst)
        except OSError:
            return  # already quarantined/removed by a concurrent reader
        self.quarantined += 1
