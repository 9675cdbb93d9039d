"""Persistent on-disk cache of serialized compiled executables.

The TVM serving model (PAPERS.md, arXiv 1802.04799): the *compiled
artifact* is the persisted, shippable unit.  Every new process — a fresh
serving replica scaling out, a preempted FaultTolerantTrainer restarting,
a bench run — otherwise re-traces and re-compiles every executable from
scratch; with this cache the second process deserializes the bytes the
first one paid XLA to produce, so warm-pool scale-out and auto-resume
skip the multi-second compile stall entirely.

Entry format (one file per executable, `<sha256-key>.jexe`):

    DL4JXC2\n                       magic + format version
    {json header}\n                 crc32 of payload, byte count, the full
                                    key parts (env fingerprint included)
    <pickle payload>                (serialized bytes, in_tree, out_tree)
                                    from jax.experimental.serialize_executable
                                    + the ids of the devices it runs on

Writes are atomic in the style of `parallel/checkpoint.py`: tmp file +
`os.replace`, so a torn write never commits; loads verify the crc32 and
that the header's key parts match the request (a renamed/garbled entry is
treated as a miss and overwritten, never served).  Version/topology
invalidation is structural: the jax/jaxlib version, backend platform,
device population and mesh topology are hashed *into the key*, so a stale
executable is unreachable rather than detected late.

When a backend cannot serialize executables (`serialize` raises), nothing
is stored here; the process-wide JAX compilation cache
(`place_compilation_cache`) still spares the next process XLA's
optimization passes, though tracing re-runs.
"""
from __future__ import annotations

import json
import os
import pickle
import tempfile
import threading
import time
import zlib
from typing import Any, Callable, Dict, Optional, Tuple

from deeplearning4j_tpu.compile.fingerprint import (canonical_json, digest,
                                                    environment_fingerprint)

MAGIC = b"DL4JXC2\n"
ENTRY_SUFFIX = ".jexe"

_ENV_DIR_VAR = "DL4J_TPU_EXEC_CACHE"


def _summarize(parts: Any, limit: int = 2000) -> Any:
    """Header-embedded copy of the key parts, with long string components
    truncated to their sha256 so the header stays a few KB even for huge
    config JSONs (the sha256 key is the authoritative identity; the header
    copy is for verification and debuggability)."""
    if isinstance(parts, dict):
        return {k: _summarize(v, limit) for k, v in parts.items()}
    if isinstance(parts, (list, tuple)):
        return [_summarize(v, limit) for v in parts]
    if isinstance(parts, str) and len(parts) > limit:
        return {"sha256": digest(parts), "len": len(parts)}
    return parts


class PersistentExecutableCache:
    """On-disk store of serialized compiled executables.

    `get_or_compile(parts, compile_fn)` is the whole API surface hot paths
    need: look the key up on disk, deserialize on a hit, otherwise call
    `compile_fn()` (which must return a `jax.stages.Compiled`) and persist
    the result.  All failure modes — corrupt bytes, version mismatch,
    unserializable backend — degrade to compiling, never to serving a
    wrong executable.
    """

    def __init__(self, directory: str,
                 env: Optional[Dict[str, Any]] = None):
        self.directory = os.path.expanduser(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._env = env
        self._serialize_ok: Optional[bool] = None   # None = not yet probed
        self._lock = threading.Lock()
        from deeplearning4j_tpu.monitor.instrument import aot_instruments
        self._instr = aot_instruments()
        # per-instance tallies (registry counters are process-global; tests
        # read these to assert on ONE cache's behaviour)
        self.stats: Dict[str, int] = {
            "disk_hits": 0, "disk_misses": 0, "compiles": 0, "stores": 0,
            "errors": 0, "bytes_read": 0, "bytes_written": 0}

    # ---- keying ----
    def environment(self) -> Dict[str, Any]:
        return self._env if self._env is not None \
            else environment_fingerprint()

    def _key_parts(self, parts: Dict[str, Any]) -> Dict[str, Any]:
        return {"env": self.environment(), "parts": parts}

    def key_for(self, parts: Dict[str, Any]) -> str:
        return digest(self._key_parts(parts))

    def _path(self, key: str) -> str:
        return os.path.join(self.directory, key + ENTRY_SUFFIX)

    # ---- load ----
    def load(self, parts: Dict[str, Any]):
        """The deserialized executable for `parts`, or None (miss).  Any
        defect — missing file, torn/corrupt bytes, header/key mismatch,
        deserialization failure — is a miss."""
        keyed = self._key_parts(parts)
        key = digest(keyed)
        path = self._path(key)
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            self._record("disk_misses")
            self._instr.misses.inc()
            return None
        try:
            if not blob.startswith(MAGIC):
                raise ValueError("bad magic (not a cache entry / truncated)")
            head_end = blob.index(b"\n", len(MAGIC)) + 1
            header = json.loads(blob[len(MAGIC):head_end])
            payload = blob[head_end:]
            if len(payload) != int(header["payload_bytes"]):
                raise ValueError("payload length mismatch (torn write)")
            crc = zlib.crc32(payload) & 0xFFFFFFFF
            if crc != int(header["crc32"]):
                raise ValueError(
                    f"crc mismatch: header {int(header['crc32']):#010x} vs "
                    f"payload {crc:#010x} (bytes corrupted after commit)")
            # header carries the (summarized) key parts: a collision or a
            # renamed entry must never deserialize as the wrong program
            if header.get("key") != key or \
                    header.get("parts") != _summarize(keyed):
                raise ValueError("header key/parts mismatch — entry does "
                                 "not belong to this request")
            serialized, in_tree, out_tree, device_ids = pickle.loads(payload)
            import jax
            from jax.experimental import serialize_executable as se
            # jax 0.9.0 loads onto EVERY device of the backend unless told
            # which ones the executable was compiled for
            by_id = {d.id: d for d in jax.devices()}
            fn = se.deserialize_and_load(
                serialized, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        except Exception as e:
            self._record("errors")
            self._record("disk_misses")
            self._instr.errors.inc()
            self._instr.misses.inc()
            self._instr.note_error(path, e)
            return None
        self._record("disk_hits")
        self._record("bytes_read", len(blob))
        self._instr.hits.inc()
        self._instr.bytes_read.inc(len(blob))
        self._instr.load_ms.observe((time.perf_counter() - t0) * 1000.0)
        return fn

    # ---- store ----
    def store(self, parts: Dict[str, Any], compiled) -> bool:
        """Serialize `compiled` and commit it atomically under the key for
        `parts`.  Returns False when the backend cannot serialize."""
        if self._serialize_ok is False:
            return False
        t0 = time.perf_counter()
        try:
            from jax.experimental import serialize_executable as se
            serialized, in_tree, out_tree = se.serialize(compiled)
            device_ids = [
                d.id for d in compiled.runtime_executable().local_devices()]
            payload = pickle.dumps(
                (serialized, in_tree, out_tree, device_ids),
                protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as e:
            # backend can't serialize executables: recorded once, then
            # every later store() is a no-op
            self._serialize_ok = False
            self._record("errors")
            self._instr.errors.inc()
            self._instr.note_error("serialize", e)
            return False
        self._serialize_ok = True
        keyed = self._key_parts(parts)
        key = digest(keyed)
        header = canonical_json({
            "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
            "payload_bytes": len(payload),
            "key": key,
            "parts": _summarize(keyed),
            "written_at": time.time(),
        }).encode()
        blob = MAGIC + header + b"\n" + payload
        path = self._path(key)
        try:
            fd, tmp = tempfile.mkstemp(dir=self.directory,
                                       prefix=".tmp-" + key[:8])
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)       # atomic commit
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except OSError as e:
            self._record("errors")
            self._instr.errors.inc()
            self._instr.note_error(path, e)
            return False
        self._record("stores")
        self._record("bytes_written", len(blob))
        self._instr.stores.inc()
        self._instr.bytes_written.inc(len(blob))
        self._instr.store_ms.observe((time.perf_counter() - t0) * 1000.0)
        return True

    # ---- the one-call surface ----
    def get_or_compile(self, parts: Dict[str, Any],
                       compile_fn: Callable[[], Any]
                       ) -> Tuple[Any, str]:
        """(executable, source): source is "disk" for a deserialized hit,
        "compiled" for a fresh compile (persisted when possible)."""
        fn = self.load(parts)
        if fn is not None:
            return fn, "disk"
        compiled = compile_fn()
        self._record("compiles")
        self._instr.compiles.inc()
        self.store(parts, compiled)
        return compiled, "compiled"

    # ---- maintenance ----
    def entries(self) -> Dict[str, Dict[str, Any]]:
        """key -> header for every committed entry (debug/tooling)."""
        out = {}
        for name in sorted(os.listdir(self.directory)):
            if not name.endswith(ENTRY_SUFFIX):
                continue
            try:
                with open(os.path.join(self.directory, name), "rb") as f:
                    blob = f.read(65536)
                head_end = blob.index(b"\n", len(MAGIC)) + 1
                out[name[:-len(ENTRY_SUFFIX)]] = json.loads(
                    blob[len(MAGIC):head_end])
            except Exception:
                out[name[:-len(ENTRY_SUFFIX)]] = {"error": "unreadable"}
        return out

    def clear(self) -> int:
        """Remove every committed entry; returns the count removed."""
        n = 0
        for name in os.listdir(self.directory):
            if name.endswith(ENTRY_SUFFIX) or name.startswith(".tmp-"):
                try:
                    os.remove(os.path.join(self.directory, name))
                    n += 1
                except OSError:
                    pass
        return n

    def _record(self, stat: str, n: int = 1) -> None:
        with self._lock:
            self.stats[stat] += n


def place_compilation_cache() -> str:
    """Decide where jax's persistent compilation cache lives; entry points
    call this once, before the first compile.  With
    `$JAX_COMPILATION_CACHE_DIR` set jax already uses that directory and
    nothing is set here.  Otherwise the cache goes to one fixed directory
    inside the checkout (git-ignored): a directory named after a pid, a
    time or `mkdtemp` is never found again by the next process.  Returns
    the directory in effect."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if d:
        return d
    import jax
    d = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compilation_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    return d


# ---------------------------------------------------------------------------
# Process default (opt-in via env var or set_default_cache)
# ---------------------------------------------------------------------------

_default_cache: Optional[PersistentExecutableCache] = None
_default_resolved = False


def default_cache_dir() -> Optional[str]:
    """The opt-in default directory: $DL4J_TPU_EXEC_CACHE, or None (the
    persistent layer is explicit-opt-in so tests/benches that count
    compiles see pristine behaviour unless they ask for the cache)."""
    d = os.environ.get(_ENV_DIR_VAR)
    return os.path.expanduser(d) if d else None


def default_cache() -> Optional[PersistentExecutableCache]:
    """Process-wide cache instance, created lazily from
    $DL4J_TPU_EXEC_CACHE (None when unset and never `set_default_cache`d)."""
    global _default_cache, _default_resolved
    if not _default_resolved:
        d = default_cache_dir()
        _default_cache = PersistentExecutableCache(d) if d else None
        _default_resolved = True
    return _default_cache


def set_default_cache(cache) -> Optional[PersistentExecutableCache]:
    """Install a process-wide default (a PersistentExecutableCache, a
    directory path, or None to disable).  Returns the installed cache."""
    global _default_cache, _default_resolved
    if isinstance(cache, str):
        cache = PersistentExecutableCache(cache)
    _default_cache = cache
    _default_resolved = True
    return _default_cache


def as_cache(cache) -> Optional[PersistentExecutableCache]:
    """Coerce a user-supplied `cache=` argument: a directory string becomes
    a PersistentExecutableCache, None falls through to the process default."""
    if cache is None:
        return default_cache()
    if isinstance(cache, str):
        return PersistentExecutableCache(cache)
    return cache
