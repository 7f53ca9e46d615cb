"""Persistent-compile-cache manager: knobs, identity key, hit stats.

JAX's persistent compilation cache keys entries by the full XLA
computation + backend fingerprint, so a stale or foreign entry can
never produce a wrong executable — it just misses. The manager adds
the operational layer the cache itself doesn't have:

  * ``enable`` decides where the cache lives and tracks it. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, the cache was placed from
    outside: JAX already reads that directory, the manager tracks it
    as it is and never touches ``jax_compilation_cache_dir``. Where
    it is unset, the cache is an identity-namespaced subdir of a
    root — the node agent's ``$SHIPYARD_COMPILE_CACHE_DIR`` on pools,
    else one fixed git-ignored directory in the checkout
    (``DEFAULT_CACHE_ROOT``) — stamped with a sidecar
    ``identity.json``. Either way the min-entry-size /
    min-compile-time thresholds drop to zero so every compile lands,
    fast CPU-test ones included.
  * ``identity_key`` is the *transport* key for pool-wide seeding
    (compilecache/seeding.py): jax/jaxlib versions, device kind,
    topology, and an optional model-config digest. Shipping a cache
    tar whose identity mismatches the node would waste bytes on
    entries that can only miss, so seeding refuses them.
  * ``track`` measures one compile region by diffing cache-dir
    contents around it: new entries mean a cold compile (its wall time
    is remembered in a ``cache_meta.json`` sidecar, which travels with
    the seeded tar); no new entries over a non-empty cache means a
    warm hit, and ``saved_seconds`` is the remembered cold time minus
    the measured warm time. These land in the goodput compile events'
    attrs (``cache_hit`` / ``saved_seconds``) so accounting can report
    ``compile_saved_seconds`` next to compile badput.

No module-level jax import: the node agent and the CLI import this for
env names and seeding validation without paying (or requiring) a JAX
backend.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import pathlib
import re
import time
from typing import Any, Iterator, Optional

from batch_shipyard_tpu.utils import util

logger = util.get_logger(__name__)

# Env var the node agent exports into every task: the node-local
# persistent cache directory (seeded from / exported to the pool's
# state store around tasks).
CACHE_DIR_ENV = "SHIPYARD_COMPILE_CACHE_DIR"

# JAX's own variable. Set, it places the cache from outside the
# program and nothing here may move it (the path is part of what makes
# a later run hit).
JAX_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

# The cache root when nobody chose one: fixed, inside the checkout,
# git-ignored. Never a temporary name — a cache that moves never hits.
DEFAULT_CACHE_ROOT = str(pathlib.Path(__file__).resolve().parents[2]
                         / ".jax_compile_cache")

# Sidecar files the manager owns inside the cache dir. They are not
# cache entries (snapshot() excludes them) but they DO travel with the
# seeded tar: identity gates transport, meta carries cold times so a
# seeded node can price its warm hits.
IDENTITY_FILE = "identity.json"
META_FILE = "cache_meta.json"
_SIDECARS = (IDENTITY_FILE, META_FILE)

# Object repr memory addresses (``<function f at 0x7f...>``) must
# never leak into a digest: they vary per process, and the whole point
# of the identity key is cross-process stability.
_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


def _stable(obj: Any) -> Any:
    """Reduce an arbitrary config value to a deterministic,
    process-independent structure for digesting."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: _stable(getattr(obj, f.name))
                for f in dataclasses.fields(obj)}
    if isinstance(obj, dict):
        return {str(k): _stable(v) for k, v in sorted(obj.items())}
    if isinstance(obj, (list, tuple)):
        return [_stable(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    if callable(obj):
        return getattr(obj, "__qualname__", type(obj).__name__)
    return _ADDR_RE.sub("0x", str(obj))


def config_digest(obj: Any) -> str:
    """Stable short digest of a model/config object (dataclass, dict,
    anything): identical configs digest identically across processes;
    any field change changes it."""
    payload = json.dumps(_stable(obj), sort_keys=True)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def identity_key(*, jax_version: Optional[str] = None,
                 jaxlib_version: Optional[str] = None,
                 backend: Optional[str] = None,
                 device_kind: Optional[str] = None,
                 device_count: Optional[int] = None,
                 process_count: Optional[int] = None,
                 mesh_shape: Optional[dict] = None,
                 model_digest: Optional[str] = None) -> str:
    """The cache transport identity: pure over its inputs (tests pass
    them explicitly); unspecified fields resolve from the live JAX
    runtime. Two nodes share cache artifacts iff their keys match."""
    if (jax_version is None or jaxlib_version is None or
            backend is None or device_kind is None or
            device_count is None or process_count is None):
        import jax
        import jaxlib
        jax_version = jax_version or jax.__version__
        jaxlib_version = jaxlib_version or jaxlib.__version__
        backend = backend or jax.default_backend()
        devices = jax.devices()
        device_kind = device_kind or devices[0].device_kind
        device_count = (len(devices) if device_count is None
                        else device_count)
        process_count = (jax.process_count() if process_count is None
                         else process_count)
    payload = json.dumps({
        "jax": jax_version, "jaxlib": jaxlib_version,
        "backend": backend, "device_kind": device_kind,
        "device_count": int(device_count),
        "process_count": int(process_count),
        "mesh_shape": _stable(mesh_shape or {}),
        "model_digest": model_digest or "",
    }, sort_keys=True)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def snapshot(cache_dir: str) -> dict[str, int]:
    """Cache ENTRIES (name -> size): everything in the dir except the
    manager sidecars and XLA's ``-atime`` access markers."""
    entries: dict[str, int] = {}
    try:
        for name in os.listdir(cache_dir):
            # Excluded: manager sidecars, XLA access-time markers,
            # and in-flight atomic-write temporaries (seeding).
            if name in _SIDECARS or name.endswith(
                    ("-atime", ".tmp", ".seedtmp")):
                continue
            path = os.path.join(cache_dir, name)
            if os.path.isfile(path):
                entries[name] = os.path.getsize(path)
    except OSError:
        pass
    return entries


class CompileCacheManager:
    """One process's handle on an enabled persistent cache dir."""

    def __init__(self, cache_dir: str, identity: str) -> None:
        self.cache_dir = os.path.abspath(cache_dir)
        self.identity = identity
        self.hits = 0
        self.misses = 0
        self.saved_seconds = 0.0
        # Labels already measured IN THIS PROCESS: a repeat (e.g.
        # replica engines 2..N sharing replica 1's module-level jits)
        # reuses the in-process dispatch cache, not the persistent
        # cache — crediting it as a warm hit would multiply
        # compile_saved_seconds by the replica count.
        self._seen_labels: set = set()

    # ------------------------------ stats ------------------------------

    def entries(self) -> dict[str, int]:
        return snapshot(self.cache_dir)

    def stats(self) -> dict:
        entries = self.entries()
        return {
            "cache_dir": self.cache_dir, "identity": self.identity,
            "entries": len(entries),
            "bytes": sum(entries.values()),
            "hits": self.hits, "misses": self.misses,
            "saved_seconds": round(self.saved_seconds, 6),
        }

    def _load_meta(self) -> dict:
        try:
            with open(os.path.join(self.cache_dir, META_FILE),
                      encoding="utf-8") as fh:
                meta = json.load(fh)
            return meta if isinstance(meta, dict) else {}
        except (OSError, ValueError):
            return {}

    def _save_meta(self, meta: dict) -> None:
        path = os.path.join(self.cache_dir, META_FILE)
        try:
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(meta, fh)
            os.replace(tmp, path)
        except OSError:
            logger.debug("cache meta write failed", exc_info=True)

    @contextlib.contextmanager
    def track(self, label: str) -> Iterator[dict]:
        """Measure one compile region against the cache dir. Yields a
        result dict filled in on exit: ``cache_hit``, ``new_entries``,
        ``elapsed_seconds``, ``saved_seconds``. A cold compile records
        its wall time per label in the meta sidecar; a later warm run
        of the same label in a FRESH process (this node or a seeded
        one) prices its saving against that. A repeat of a label
        within one process is in-process jit reuse, not a persistent
        cache hit — it is reported (``in_process_reuse``) but neither
        counted nor priced."""
        first_of_label = label not in self._seen_labels
        self._seen_labels.add(label)
        before = snapshot(self.cache_dir)
        start = time.perf_counter()
        result: dict = {"label": label}
        try:
            yield result
        finally:
            elapsed = time.perf_counter() - start
            after = snapshot(self.cache_dir)
            new = [name for name in after if name not in before]
            hit = not new and bool(before) and first_of_label
            result["elapsed_seconds"] = elapsed
            result["new_entries"] = len(new)
            result["cache_hit"] = bool(hit)
            result["in_process_reuse"] = not first_of_label and \
                not new
            saved = 0.0
            if not result["in_process_reuse"]:
                meta = self._load_meta()
                if new:
                    # Cold: remember this label's full compile cost
                    # so a warm replay (here or on a seeded node) can
                    # price the time it did NOT spend. First cold
                    # measurement wins: a PARTIALLY warm rerun (one
                    # changed function over a seeded cache) also
                    # lands here, and letting its mostly-warm elapsed
                    # overwrite the true cold time would corrupt
                    # every later node's saved_seconds (the meta
                    # travels with the seed tar).
                    meta.setdefault("cold_seconds",
                                    {}).setdefault(label, elapsed)
                    self._save_meta(meta)
                    self.misses += 1
                elif hit:
                    cold = meta.get("cold_seconds", {}).get(label)
                    try:
                        saved = max(0.0, float(cold) - elapsed)
                    except (TypeError, ValueError):
                        saved = 0.0
                    self.hits += 1
                else:
                    self.misses += 1
            result["saved_seconds"] = saved
            self.saved_seconds += saved


_current: Optional[CompileCacheManager] = None


def current() -> Optional[CompileCacheManager]:
    """The process's enabled manager, or None (cache disabled)."""
    return _current


def identity_subdir(cache_root: str, identity: str) -> str:
    """The identity-namespaced cache dir under a shared root."""
    return os.path.join(os.path.abspath(cache_root),
                        f"ident-{identity}")


def list_identity_dirs(cache_root: str) -> dict[str, str]:
    """identity -> subdir for every namespaced cache under a root."""
    out: dict[str, str] = {}
    try:
        for name in os.listdir(cache_root):
            if not name.startswith("ident-"):
                continue
            path = os.path.join(cache_root, name)
            if os.path.isdir(path):
                out[name[len("ident-"):]] = path
    except OSError:
        pass
    return out


def resolve_root(cache_root: Optional[str] = None
                 ) -> tuple[str, bool]:
    """(directory, placed_from_outside): ``JAX_COMPILATION_CACHE_DIR``
    when set — the cache itself, used flat — else ``cache_root`` or
    ``DEFAULT_CACHE_ROOT``, under which caches are identity
    subdirs."""
    placed = os.environ.get(JAX_CACHE_DIR_ENV)
    if placed:
        return os.path.abspath(placed), True
    return os.path.abspath(cache_root or DEFAULT_CACHE_ROOT), False


def enable(cache_root: Optional[str] = None, *,
           min_entry_size_bytes: int = 0,
           min_compile_time_secs: float = 0.0,
           identity: Optional[str] = None,
           mesh_shape: Optional[dict] = None,
           model_digest: Optional[str] = None,
           configure_jax: bool = True) -> CompileCacheManager:
    """Install the process-global manager on the persistent XLA
    compilation cache. Idempotent.

    ``JAX_COMPILATION_CACHE_DIR`` set: the cache stays exactly there
    (``cache_root`` is ignored) and ``jax_compilation_cache_dir`` is
    not written. Unset: the cache is ``cache_root``'s
    identity-namespaced subdir (``DEFAULT_CACHE_ROOT`` when no root is
    given). Namespacing is what lets MIXED pools share one node dir: a
    transformer task and a resnet task (different identities) each
    warm their own subdir instead of clobbering each other's — XLA
    entries are self-keying, but cold-time metas and export artifacts
    are not. ``configure_jax=False`` is for tests and agent-side
    tooling that never compile: no jax.config writes, and the root is
    always namespaced."""
    global _current
    if identity is None:
        identity = identity_key(mesh_shape=mesh_shape,
                                model_digest=model_digest)
    root, placed_outside = (
        resolve_root(cache_root) if configure_jax
        else (cache_root or DEFAULT_CACHE_ROOT, False))
    cache_dir = (root if placed_outside
                 else identity_subdir(root, identity))
    os.makedirs(cache_dir, exist_ok=True)
    if not placed_outside and read_identity(cache_dir) != identity:
        with open(os.path.join(cache_dir, IDENTITY_FILE), "w",
                  encoding="utf-8") as fh:
            json.dump({"identity": identity,
                       "written_at": util.datetime_utcnow_iso()}, fh)
    if configure_jax:
        import jax
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          int(min_entry_size_bytes))
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_time_secs))
        if not placed_outside:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
            # Any compile that ran BEFORE enable latches the cache
            # module to its initialized-disabled state for the process
            # (config updates alone don't un-latch it); reset so the
            # new dir takes effect even mid-process.
            from jax.experimental.compilation_cache import (
                compilation_cache as jax_cc)
            jax_cc.reset_cache()
    _current = CompileCacheManager(cache_dir, identity)
    return _current


def read_identity(cache_dir: str) -> Optional[str]:
    """The identity a cache dir was stamped with, or None."""
    try:
        with open(os.path.join(cache_dir, IDENTITY_FILE),
                  encoding="utf-8") as fh:
            value = json.load(fh).get("identity")
        return value if isinstance(value, str) else None
    except (OSError, ValueError):
        return None


@contextlib.contextmanager
def tracked(attrs: dict, label: str) -> Iterator[None]:
    """Nest inside a goodput compile/warm-up phase to stamp the
    event's attrs with ``cache_hit`` / ``saved_seconds``::

        with goodput_events.phase(PROGRAM_COMPILE, what="x") as attrs,\\
                compilecache.tracked(attrs, "x"):
            ...  # the compile

    No-op when no manager is enabled."""
    mgr = current()
    if mgr is None:
        yield
        return
    with mgr.track(label) as result:
        yield
    if result.get("in_process_reuse"):
        # Replica N reusing replica 1's in-process jits is neither a
        # persistent-cache hit nor a miss — stamping either would
        # skew the pool's hit/saved accounting.
        return
    attrs["cache_hit"] = result["cache_hit"]
    attrs["saved_seconds"] = round(result["saved_seconds"], 6)


def add_compile_cache_args(parser) -> None:
    """The shared warm-start flag surface of every train/serve
    workload (the checkpoint.add_checkpoint_args pattern)."""
    group = parser.add_argument_group("compile cache")
    group.add_argument(
        "--compile-cache-dir",
        default=os.environ.get(CACHE_DIR_ENV) or None,
        help="persistent XLA compilation cache ROOT (default: "
             f"${CACHE_DIR_ENV}, which the node agent exports on "
             f"pools, else {DEFAULT_CACHE_ROOT}); ignored where "
             f"${JAX_CACHE_DIR_ENV} is set — the cache then stays "
             "in that directory")
    group.add_argument(
        "--no-compile-cache", action="store_true",
        help="opt out of the persistent compile cache")
    group.add_argument(
        "--aot-precompile", action="store_true",
        help="AOT lower+compile the hot functions against abstract "
             "shapes so compilation overlaps data/loader startup "
             "instead of blocking the first step")


def enable_from_args(args, *, mesh_shape: Optional[dict] = None,
                     model_digest: Optional[str] = None
                     ) -> Optional[CompileCacheManager]:
    """The workload-side enable hook (the AST check in
    tests/test_names_consistency.py requires every parallel.train
    workload to call this): enables the persistent cache where
    ``enable`` resolves it, or returns None under
    ``--no-compile-cache``. A cache directory that cannot be used
    raises: a run that silently compiles cold every time is not the
    run that was asked for."""
    if getattr(args, "no_compile_cache", False):
        return None
    return enable(getattr(args, "compile_cache_dir", None),
                  mesh_shape=mesh_shape, model_digest=model_digest)
