"""Persistent compilation caches.

Two layers, both rooted in ``$JAX_COMPILATION_CACHE_DIR`` when that is set
(the store in its ``textblast-aot/`` subdirectory) and otherwise in the
repo-local (gitignored) ``.cache/``:

1. **XLA's built-in compilation cache** (:func:`enable_compilation_cache`)
   — skips the XLA *compile*, but every process still pays trace + lower
   per program (~seconds each for the fused filter graphs).
2. **Serialized AOT executable store** (:class:`AOTExecutableCache`) —
   pickles ``jax.experimental.serialize_executable.serialize()`` payloads
   per program, keyed by everything that shapes the traced computation
   (geometry + filter-config fingerprints, jax/jaxlib versions, backend,
   device topology, program shape, trace-shaping env knobs, and a
   content hash of this package's sources).  A warm start loads finished
   executables and skips trace, lower, *and* compile —
   ``CompiledPipeline.warmup_parallel`` consults it first.

``TEXTBLAST_NO_COMPILE_CACHE=1`` bypasses both layers (measurement escape
hatch: cache-loaded XLA:CPU executables can differ in performance from the
in-memory JIT result of a fresh compile).

Entries that fail to unpickle or to deserialize (corrupt, truncated, or
written by an incompatible runtime that slipped past the key) are evicted
and silently recompiled — a cache problem must never take down a run.
The store is size-capped (``TEXTBLAST_AOT_CACHE_MB``, default 512) with
least-recently-*used* eviction: loads touch the entry's mtime.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import logging
import os
import pickle
import tempfile
from typing import Any, Dict, Optional

from jax.experimental.serialize_executable import deserialize_and_load, serialize

logger = logging.getLogger(__name__)

__all__ = [
    "enable_compilation_cache",
    "DEFAULT_CACHE_DIR",
    "DEFAULT_AOT_DIR",
    "AOTExecutableCache",
    "aot_cache_enabled",
    "config_fingerprint",
    "program_cache_key",
]

_CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".cache",
)

#: XLA compilation-cache directory (gitignored).
DEFAULT_CACHE_DIR = os.path.join(_CACHE_ROOT, "jax")

#: Serialized-executable store directory (gitignored).
DEFAULT_AOT_DIR = os.path.join(_CACHE_ROOT, "aot")

_SUFFIX = ".aotx"

#: Static cost-model sidecar written next to each executable entry
#: (``<key>.cost.json``): the ``cost_analysis``/``memory_analysis``
#: numbers captured at compile time, so an AOT cache hit keeps the exact
#: cost model of the compile that produced it (re-running the analyses on
#: a deserialized executable is backend-dependent).  Sidecars ride their
#: entry's lifecycle — evicted together, never counted against the size
#: cap (a few hundred bytes each).
_COST_SUFFIX = ".cost.json"


def _placed_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when the caller placed the cache from
    outside, else ""."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()


def enable_compilation_cache(cache_dir: str | None = None) -> str:
    """Turn on JAX's persistent compilation cache and return its directory
    (created if missing).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the cache
    from there and ``jax_compilation_cache_dir`` is left alone; otherwise
    the cache goes to ``cache_dir`` or the fixed in-checkout
    :data:`DEFAULT_CACHE_DIR` (the path is part of the cache's key, so it
    must not move between runs).

    ``TEXTBLAST_NO_COMPILE_CACHE=1`` turns this into a no-op (measurement
    escape hatch: cache-loaded XLA:CPU executables can differ in performance
    from the in-memory JIT result of a fresh compile)."""
    import jax

    if os.environ.get("TEXTBLAST_NO_COMPILE_CACHE") == "1":
        return ""
    placed = _placed_cache_dir()
    if cache_dir is None and placed:
        cache_dir = placed
    else:
        cache_dir = cache_dir or DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return cache_dir


def aot_cache_enabled() -> bool:
    """The executable store honors the same bypass as the XLA cache."""
    return os.environ.get("TEXTBLAST_NO_COMPILE_CACHE") != "1"


def default_aot_dir() -> str:
    """Where the executable store lives unless a caller names a directory:
    ``TEXTBLAST_AOT_CACHE_DIR``, else beside JAX's cache when that was placed
    from outside (so every compiled artifact lands in one directory), else
    :data:`DEFAULT_AOT_DIR`."""
    explicit = os.environ.get("TEXTBLAST_AOT_CACHE_DIR")
    if explicit:
        return explicit
    placed = _placed_cache_dir()
    return os.path.join(placed, "textblast-aot") if placed else DEFAULT_AOT_DIR


# --- cache keys -------------------------------------------------------------


def config_fingerprint(config: Any) -> str:
    """Filter-config fingerprint: step types + params as stable JSON (the
    same recipe the checkpoint manifest uses, re-implemented here so the
    cache layer stays import-light)."""
    steps = getattr(config, "pipeline", config)
    blob = json.dumps(
        [{"type": s.type, "params": dataclasses.asdict(s.params)} for s in steps],
        sort_keys=True,
        default=str,
    )
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]


#: Env knobs that change the *traced program* (wire dtype, phase layout,
#: Pallas kernels on or off, interpret mode).  Two processes whose knobs
#: differ must never share an executable.
_TRACE_ENV_KNOBS = (
    "TEXTBLAST_WIRE",
    "TEXTBLAST_PHASES",
    "TEXTBLAST_PALLAS",
    "TEXTBLAST_PALLAS_INTERPRET",
)


@functools.lru_cache(maxsize=1)
def _code_fingerprint() -> str:
    """Content hash of this package's sources.  The traced program changes
    whenever the kernels change; jax/config versioning alone would happily
    serve an executable compiled from last week's code."""
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for root, dirs, files in sorted(os.walk(pkg_dir)):
        dirs.sort()
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            h.update(os.path.relpath(path, pkg_dir).encode("utf-8"))
            try:
                with open(path, "rb") as f:
                    h.update(f.read())
            except OSError:  # pragma: no cover - racing an editor
                continue
    return h.hexdigest()[:16]


def program_cache_key(
    *,
    config_fp: str,
    geometry_fp: str,
    backend: str,
    length: int,
    phase: int,
    rows: int,
    wire: str,
    n_devices: int = 1,
    mesh: bool = False,
    extra: Optional[Dict[str, Any]] = None,
) -> str:
    """Stable key for one compiled program.  Everything that shapes the
    trace or the executable's validity participates; any mismatch is a
    cache miss, never a wrong program."""
    import jax

    try:
        import jaxlib

        jaxlib_version = getattr(jaxlib, "__version__", "?")
    except Exception:  # pragma: no cover
        jaxlib_version = "?"
    parts = {
        "code": _code_fingerprint(),
        "config": config_fp,
        "geometry": geometry_fp,
        "jax": jax.__version__,
        "jaxlib": jaxlib_version,
        "backend": backend,
        "n_devices": n_devices,
        "mesh": bool(mesh),
        "processes": jax.process_count(),
        "length": length,
        "phase": phase,
        "rows": rows,
        "wire": wire,
        "x64": bool(jax.config.jax_enable_x64),
        "env": {k: os.environ.get(k, "") for k in _TRACE_ENV_KNOBS},
    }
    if extra:
        parts["extra"] = extra
    blob = json.dumps(parts, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:32]


# --- the store --------------------------------------------------------------


def _unlink_quiet(path: str) -> None:
    try:
        os.unlink(path)
    except OSError:
        pass


class AOTExecutableCache:
    """On-disk store of serialized compiled executables.

    ``load``/``store`` never raise for cache-side problems: a missing,
    corrupt, or incompatible entry is a miss (and is evicted), a failed
    write is a warning.  Writes are atomic (tmp + rename) so concurrent
    warmup threads and sibling processes can share the directory."""

    def __init__(
        self, cache_dir: Optional[str] = None, max_bytes: Optional[int] = None
    ) -> None:
        self.cache_dir = cache_dir or default_aot_dir()
        if max_bytes is None:
            max_bytes = int(
                float(os.environ.get("TEXTBLAST_AOT_CACHE_MB", "512")) * 1_000_000
            )
        self.max_bytes = max_bytes

    def _path(self, key: str) -> str:
        return os.path.join(self.cache_dir, key + _SUFFIX)

    def _cost_path(self, key: str) -> str:
        return os.path.join(self.cache_dir, key + _COST_SUFFIX)

    def load_cost(self, key: str):
        """The cost-model sidecar for ``key`` as a dict, or None (absent,
        bypassed, corrupt — the latter evicted, like executables)."""
        if not aot_cache_enabled():
            return None
        path = self._cost_path(key)
        try:
            with open(path, "r", encoding="utf-8") as f:
                cost = json.load(f)
        except FileNotFoundError:
            return None
        except Exception as e:  # corrupt / truncated
            logger.warning("evicting corrupt cost sidecar %s: %s", key, e)
            _unlink_quiet(path)
            return None
        if not isinstance(cost, dict):
            _unlink_quiet(path)
            return None
        return cost

    def store_cost(self, key: str, cost) -> bool:
        """Write the cost-model sidecar for ``key`` (atomic tmp + rename);
        returns True on success.  Failures are warnings, never fatal."""
        if not aot_cache_enabled() or not isinstance(cost, dict):
            return False
        try:
            os.makedirs(self.cache_dir, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.cache_dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "w", encoding="utf-8") as f:
                    json.dump(cost, f, sort_keys=True)
                os.replace(tmp, self._cost_path(key))
            finally:
                if os.path.exists(tmp):  # replace failed
                    _unlink_quiet(tmp)
        except OSError as e:  # pragma: no cover - disk full etc.
            logger.warning("cost sidecar write failed for %s: %s", key, e)
            return False
        return True

    def load(self, key: str):
        """Return the deserialized executable for ``key``, or None on any
        miss (absent, bypassed, unsupported, corrupt — the latter evicted)."""
        if not aot_cache_enabled():
            return None
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                payload, in_tree, out_tree, device_ids = pickle.load(f)
        except FileNotFoundError:
            return None
        except Exception as e:  # corrupt / truncated / wrong pickle
            logger.warning("evicting corrupt AOT cache entry %s: %s", key, e)
            _unlink_quiet(path)
            _unlink_quiet(self._cost_path(key))
            return None
        try:
            import jax

            by_id = {d.id: d for d in jax.devices()}
            compiled = deserialize_and_load(
                payload,
                in_tree,
                out_tree,
                execution_devices=[by_id[i] for i in device_ids],
            )
        except Exception as e:  # runtime/topology mismatch that beat the key
            logger.warning("evicting unloadable AOT cache entry %s: %s", key, e)
            _unlink_quiet(path)
            _unlink_quiet(self._cost_path(key))
            return None
        try:
            os.utime(path, None)  # LRU recency
        except OSError:  # pragma: no cover
            pass
        return compiled

    def store(self, key: str, compiled) -> bool:
        """Serialize ``compiled`` under ``key``; returns True on success.
        Backends whose executables do not serialize simply decline."""
        if not aot_cache_enabled():
            return False
        try:
            payload, in_tree, out_tree = serialize(compiled)
            # The executable's own devices: without them a one-device
            # program would be loaded onto every local device and fail at
            # its first call.
            devices = compiled.runtime_executable().local_devices()
            # Validate before writing: executables XLA served from its own
            # persistent compilation cache serialize without their kernel
            # object code ("Symbols not found" on load, XLA:CPU) — a store
            # that every future process would evict is worse than no store.
            deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=devices
            )
            blob = pickle.dumps(
                (payload, in_tree, out_tree, [d.id for d in devices])
            )
        except Exception as e:
            logger.debug("AOT serialize declined for %s: %s", key, e)
            return False
        try:
            os.makedirs(self.cache_dir, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.cache_dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, self._path(key))
            finally:
                if os.path.exists(tmp):  # replace failed
                    _unlink_quiet(tmp)
        except OSError as e:  # pragma: no cover - disk full etc.
            logger.warning("AOT cache write failed for %s: %s", key, e)
            return False
        self._evict_lru()
        return True

    def _entries(self):
        try:
            names = os.listdir(self.cache_dir)
        except OSError:
            return []
        out = []
        for name in names:
            if not name.endswith(_SUFFIX):
                continue
            path = os.path.join(self.cache_dir, name)
            try:
                st = os.stat(path)
            except OSError:  # racing another evictor
                continue
            out.append((st.st_mtime, st.st_size, path))
        return out

    def _evict_lru(self) -> int:
        """Drop least-recently-used entries until under the size cap.
        Returns the number evicted."""
        entries = self._entries()
        total = sum(size for _, size, _ in entries)
        evicted = 0
        for _, size, path in sorted(entries):
            if total <= self.max_bytes:
                break
            _unlink_quiet(path)
            _unlink_quiet(path[: -len(_SUFFIX)] + _COST_SUFFIX)
            total -= size
            evicted += 1
        if evicted:
            logger.info("AOT cache evicted %d entr%s (size cap %d bytes)",
                        evicted, "y" if evicted == 1 else "ies", self.max_bytes)
        return evicted

    def size_bytes(self) -> int:
        return sum(size for _, size, _ in self._entries())
