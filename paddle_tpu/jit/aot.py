"""jit.aot — shape-bucketed AOT executables + persistent compile cache.

Serving pays XLA compilation twice today: once per process for the
exported module's batch=1 path, and again for every *new* batch shape a
batching layer wants to run. Both costs are removable:

* **Bucketed AOT lowering** (`compile_batched`) builds, for one exported
  module and one bucket size B, a single XLA executable mapping
  `(params, stacked_inputs[B, ...]) -> stacked_outputs[B, ...]`. The body
  is `lax.map` over the module's `call` — the exported program is traced
  ONCE regardless of B (no graph duplication at large buckets), weights
  stay runtime arguments (never baked in as constants, so the serialized
  executable holds no model weights), and each example runs exactly the
  program the standalone module would run, so per-example outputs are
  bit-identical to unbatched execution. One dispatch then serves B
  requests — the serving analog of the training engine's multi-step scan.

* **Persistent compile cache** (`CompileCache`): compiled executables are
  serialized (`jax.experimental.serialize_executable`) to an on-disk
  cache keyed by model fingerprint x bucket shape x jax/jaxlib version x
  backend, so a fresh process (or a re-cloned pool member on another
  host with the same platform) loads the executable instead of
  recompiling. Writes are crash-atomic (shared `_atomic_io` protocol)
  and the directory is size-bounded (keep-last-K by LRU mtime).

Cache location: ONE root for every compiled program — jax's own
persistent compilation cache and these serialized executables (in its
`paddle_tpu_aot/` subdirectory). The root is `$JAX_COMPILATION_CACHE_DIR`
when set (jax reads that variable itself; nothing here sets another
directory), else the fixed `<checkout>/.jax_cache` — see
`compile_cache_root` / `enable_compile_cache`. Capacity:
`$PADDLE_TPU_COMPILE_CACHE_KEEP` entries (default 64). A corrupt or
version-skewed entry is never fatal — deserialization failure falls back
to a fresh compile and overwrites it.
"""
from __future__ import annotations

import contextlib
import hashlib
import os
import pickle
import tempfile
import warnings

from ..analysis import commcheck as _cc
from ..analysis import graphcheck as _gc
from ..analysis import locks as _locks
from ..analysis import runtime_san as _san

__all__ = ["CompileCache", "compile_batched", "compile_jit", "default_cache",
           "cache_dir", "compile_cache_root", "enable_compile_cache",
           "hermetic_cache"]

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
_ENV_KEEP = "PADDLE_TPU_COMPILE_CACHE_KEEP"
_SUFFIX = ".aotexec"
# Fixed, inside the checkout, never $HOME / a temp name / a pid / a time:
# a directory that moves between runs never hits.
_CHECKOUT_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_root():
    """The one compile-cache root: `$JAX_COMPILATION_CACHE_DIR`, else
    `<checkout>/.jax_cache`."""
    return os.environ.get(_ENV_DIR) or _CHECKOUT_ROOT


def cache_dir():
    """Where serialized AOT executables live: a subdirectory of the root
    (read per call, so tests repointing the variable get a fresh dir)."""
    return os.path.join(compile_cache_root(), "paddle_tpu_aot")


def enable_compile_cache():
    """Turn on jax's persistent compilation cache at `compile_cache_root()`
    and return the root. Entry points (benchmarks/run.py, chip_smoke.py) call this
    once before their first compile. With `$JAX_COMPILATION_CACHE_DIR` set
    jax has already pointed itself there and nothing is set in code."""
    root = compile_cache_root()
    if not os.environ.get(_ENV_DIR):
        import jax

        jax.config.update("jax_compilation_cache_dir", root)
    return root


@contextlib.contextmanager
def hermetic_cache(prefix="aot-"):
    """Point the AOT executable cache at a fresh temporary root for the
    duration, whatever `$JAX_COMPILATION_CACHE_DIR` says, and put the
    caller's back afterwards; yields the temporary directory. For the
    audit CLIs and fault harnesses, which measure compiles: every one
    must be real (a disk hit skips the audit hooks) and every run must
    start as cold as the last. Not for the main path, where a cache
    that moves never hits."""
    outer = os.environ.get(_ENV_DIR)
    with tempfile.TemporaryDirectory(prefix=prefix) as tmp:
        os.environ[_ENV_DIR] = os.path.join(tmp, "compile-cache")
        try:
            yield tmp
        finally:
            if outer is None:
                os.environ.pop(_ENV_DIR, None)
            else:
                os.environ[_ENV_DIR] = outer


class CompileCache:
    """Size-bounded on-disk blob cache for serialized XLA executables.

    Filesystem layout is one file per key (`<sha256>.aotexec`); writes go
    through the crash-atomic write-tmp/fsync/rename protocol so a killed
    process can never leave a torn entry, and concurrent writers (two
    pools warming the same bucket) simply last-write-win the same bytes.
    Reads bump the entry's mtime, making the keep-last-K prune an LRU.
    """

    def __init__(self, root=None, keep=None):
        self.root = root or cache_dir()
        if keep is None:
            keep = int(os.environ.get(_ENV_KEEP, "64"))
        if keep < 1:
            raise ValueError("compile cache must keep at least 1 entry")
        self.keep = keep
        self._lock = _locks.new_lock("aot.compile_cache")
        self.hits = 0
        self.misses = 0
        self.puts = 0
        self.evictions = 0

    # -- keys -------------------------------------------------------------
    @staticmethod
    def key(*parts):
        """Stable cache key over the identity parts (model fingerprint,
        bucket shapes, software versions, backend)."""
        h = hashlib.sha256()
        for p in parts:
            h.update(str(p).encode())
            h.update(b"\x00")
        return h.hexdigest()

    def _path(self, key):
        return os.path.join(self.root, key + _SUFFIX)

    # -- IO ---------------------------------------------------------------
    def get(self, key):
        """Blob bytes for `key`, or None. A hit refreshes the entry's
        LRU position."""
        p = self._path(key)
        try:
            with _locks.blocking_region("aot.cache_read"), \
                    open(p, "rb") as f:
                blob = f.read()
        except OSError:
            with self._lock:
                self.misses += 1
            return None
        try:
            os.utime(p, None)
        except OSError:
            pass
        with self._lock:
            self.hits += 1
        return blob

    def put(self, key, blob):
        from .._atomic_io import atomic_write

        os.makedirs(self.root, exist_ok=True)
        # atomic_write enters blocking_region("io.atomic_write") itself
        atomic_write(self._path(key), lambda f: f.write(blob))
        with self._lock:
            self.puts += 1
        self._prune()

    def _prune(self):
        """Drop the oldest entries beyond `keep` (LRU by mtime)."""
        try:
            names = [n for n in os.listdir(self.root)
                     if n.endswith(_SUFFIX)]
        except OSError:
            return
        if len(names) <= self.keep:
            return
        aged = []
        for n in names:
            try:
                aged.append((os.path.getmtime(os.path.join(self.root, n)), n))
            except OSError:
                continue
        aged.sort()
        for _, n in aged[: max(0, len(aged) - self.keep)]:
            try:
                os.remove(os.path.join(self.root, n))
                with self._lock:
                    self.evictions += 1
            except OSError:
                pass  # concurrent prune; the bound still holds eventually

    def entries(self):
        try:
            return sorted(n[: -len(_SUFFIX)] for n in os.listdir(self.root)
                          if n.endswith(_SUFFIX))
        except OSError:
            return []

    def stats(self):
        with self._lock:
            return {"root": self.root, "keep": self.keep,
                    "entries": len(self.entries()), "hits": self.hits,
                    "misses": self.misses, "puts": self.puts,
                    "evictions": self.evictions}


_default_cache = None
_default_lock = _locks.new_lock("aot.default_cache")
# Tracing a functionalized layer swaps the traced values into the LIVE
# layer objects (distributed/functional.py), so two traces of one model may
# not overlap. What follows a trace (XLA compilation, the load of a cached
# executable) touches no layer and runs side by side: the decode engine's
# `warmup()` builds what its cache lacks on a thread pool.
_trace_lock = _locks.new_lock("aot.trace")


def default_cache():
    """Process-wide CompileCache over the resolved cache dir. Rebuilt if
    the resolved directory changed (tests repoint it per tmpdir)."""
    global _default_cache
    with _default_lock:
        if _default_cache is None or _default_cache.root != cache_dir():
            _default_cache = CompileCache()
        return _default_cache


# ---------------------------------------------------------------------------
# batched AOT lowering
# ---------------------------------------------------------------------------

def _versions():
    import jax
    import jaxlib

    dev = jax.devices()[0]
    return (jax.__version__, getattr(jaxlib, "__version__", "?"),
            dev.platform, str(dev.device_kind))


def _sharding_sig(in_shardings):
    """Deterministic signature of an in_shardings pytree: mesh topology +
    per-leaf PartitionSpec. A tensor-parallel executable and a
    single-device one must never share a persistent-cache key (and two
    processes with the SAME mesh shape may share one)."""
    if in_shardings is None:
        return None
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(
        in_shardings, is_leaf=lambda x: hasattr(x, "spec"))
    m = _first_mesh(leaves)
    mesh_sig = None if m is None else tuple(
        (str(a), int(s)) for a, s in dict(m.shape).items())
    return (str(treedef), mesh_sig,
            [str(getattr(sh, "spec", sh)) for sh in leaves])


def _first_mesh(shardings):
    """The mesh the first mesh-carrying sharding names, else None."""
    return next((sh.mesh for sh in shardings
                 if getattr(sh, "mesh", None) is not None), None)


def executable_key(fingerprint, bucket, input_spec, holder_shapes,
                   sharding_sig=None):
    """Cache key for one bucket executable: model identity x batch shape x
    software/backend identity (a jax upgrade or platform change must never
    resurrect a stale executable) x sharding signature (a TP executable is
    a different program)."""
    return CompileCache.key(
        "batched-v1", fingerprint, bucket,
        [(list(s["shape"]), str(s["dtype"])) for s in input_spec],
        holder_shapes, *_versions(),
        *(("shardings", sharding_sig) if sharding_sig else ()))


def _aval_signature(avals):
    """Deterministic shape/dtype signature of an aval pytree (cache-key
    material; the tree structure itself is part of the signature so two
    functions over differently-nested identical leaves never collide)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(avals)
    return (str(treedef),
            [(list(a.shape), str(a.dtype)) for a in leaves])


def _load_executable(cache, key, in_shardings):
    """The executable persisted under `key`, or None (absent, or a stale /
    corrupt entry — warned about, then recompiled and overwritten). It is
    loaded onto the devices it was compiled for — the mesh `in_shardings`
    names, else the default device: left to itself jax 0.9 spreads it
    over EVERY local device, and a one-device program then refuses its
    arguments at call time on a multi-device host."""
    import jax
    from jax.experimental import serialize_executable as _se

    blob = cache.get(key)
    if blob is None:
        return None
    mesh = _first_mesh(jax.tree_util.tree_leaves(
        in_shardings, is_leaf=lambda x: hasattr(x, "spec")))
    devices = jax.devices()[:1] if mesh is None \
        else list(mesh.devices.flat)
    try:
        payload, in_tree, out_tree = pickle.loads(blob)
        return _se.deserialize_and_load(payload, in_tree, out_tree,
                                        execution_devices=devices)
    except Exception as e:  # tpu-lint: disable=TL007 — never fatal
        warnings.warn(f"aot: cached executable {key[:12]} did not load "
                      f"({type(e).__name__}: {e}); recompiling")
        return None


def _store_executable(cache, key, compiled):
    """Persist `compiled` under `key`; a backend that cannot serialize
    still serves from memory, but says so."""
    from jax.experimental import serialize_executable as _se

    try:
        cache.put(key, pickle.dumps(_se.serialize(compiled), protocol=4))
    except Exception as e:  # tpu-lint: disable=TL007 — never fatal
        warnings.warn(f"aot: executable {key[:12]} was not persisted "
                      f"({type(e).__name__}: {e}); every process will "
                      f"recompile it")


def compile_jit(fn, avals, *, fingerprint=None, cache=None, tag="jit-v1",
                in_shardings=None, out_shardings=None, audit_ctx=None,
                donate_argnums=None, extra_key=None, cached_only=False):
    """AOT-compile (or cache-load) `fn` over an aval pytree, persisting the
    executable like `compile_batched` does for bucket executables.

    `avals` is the positional-argument pytree of `jax.ShapeDtypeStruct`s
    (weights must ride as runtime arguments — never closed over — so the
    serialized executable holds no model state). `in_shardings` (a pytree
    of NamedShardings matching `avals`) compiles the program partitioned
    over those placements — the decode engine's tensor-parallel path; it
    joins the cache key, so a TP executable never collides with the
    single-device one. `donate_argnums` joins it too (and the retrace
    sentinel's signature): a program that consumes an argument and one that
    copies it are different binaries under one tag, and neither may be
    served the other's. `extra_key` (any str()-able value) joins both the
    persistent-cache key and the retrace-sentinel signature: callers whose
    traced program depends on configuration `fn` CLOSES OVER — the decode
    engine's speculative propose/verify steps close over `speculate_k`,
    and two K values can share identical input avals — must pass it, or a
    stale executable for a different configuration could be resurrected
    from disk. `cached_only` brings up what the persistent cache holds
    and builds nothing: `(None, None)` where it holds no such executable
    (the decode engine loads its warm set on one thread and builds the
    rest on several). Returns `(compiled, source)` where
    `compiled(*args)` runs the executable and `source` is "compiled"
    (built here, persisted when a fingerprint was given) or "disk"
    (loaded from the persistent cache, zero XLA compilation).

    This is the decode-engine analog of `compile_batched`: the continuous-
    batching step function is compiled once per batch bucket and a warm
    process start loads every bucket from disk instead of recompiling.
    """
    import jax

    key = None
    if fingerprint is not None:
        cache = cache or default_cache()
        sig = (_sharding_sig(in_shardings), _sharding_sig(out_shardings))
        key = CompileCache.key(tag, fingerprint, _aval_signature(avals),
                               *_versions(),
                               "donate", tuple(donate_argnums or ()),
                               *(("shardings", sig) if sig != (None, None)
                                 else ()),
                               *(("extra", extra_key)
                                 if extra_key is not None else ()))
        loaded = _load_executable(cache, key, in_shardings)
        if loaded is not None:
            return loaded, "disk"
    if cached_only:
        return None, None

    if _san.enabled():
        # retrace sentinel (tpu-san): this is a REAL XLA compile — a
        # duplicate (fingerprint, aval) signature here means the
        # persistent cache failed; any compile after mark_warm() is a
        # retrace finding
        _san.note_trace(
            f"aot.{tag}",
            # no fingerprint = no persistent cache: a fresh token per
            # call (an id() could be recycled into a warm entry)
            fingerprint if fingerprint is not None else object(),
            # the "sharding:" tag routes a placement-only delta into the
            # retrace blame as a sharding-signature change
            (_san.aval_signature(avals),
             "sharding:" + str(_sharding_sig(in_shardings)),
             "donate:" + str(tuple(donate_argnums or ())),
             # closed-over configuration (e.g. speculate_k): two programs
             # with identical avals must not look like a duplicate compile
             "extra:" + str(extra_key)))
    with _locks.blocking_region("aot.compile"):
        kw = {}
        if donate_argnums is not None:
            kw["donate_argnums"] = donate_argnums
        if in_shardings is not None:
            kw["in_shardings"] = in_shardings
        if out_shardings is not None:
            # pinning outputs keeps carried state (e.g. the decode
            # engine's KV pool) on the placement the NEXT dispatch's
            # in_shardings demand — AOT executables accept exact matches
            kw["out_shardings"] = out_shardings
        with _trace_lock:
            lowered = jax.jit(fn, **kw).lower(*avals)
        compiled = lowered.compile()
    if _gc.enabled():
        # graph auditor: every REAL compile is audited (disk loads were
        # audited when first built); `audit_ctx` carries the caller's
        # placement context (decode engine, sharded layers). It traces
        # `fn` again
        with _trace_lock:
            _gc.audit_executable(f"aot.{tag}", fn=fn, args=avals,
                                 lowered=lowered, compiled=compiled,
                                 in_shardings=in_shardings,
                                 **(audit_ctx or {}))
    if _cc.enabled():
        # collective-schedule auditor: the lowered/compiled objects are
        # already in hand, so recording+verifying here is (extra
        # compile)-free — decode bucket executables verify cross-host
        # BEFORE their first dispatch
        _cc.check_entrypoint(f"aot.{tag}", fn=fn, args=avals,
                             lowered=lowered, compiled=compiled)
    if key is not None:
        _store_executable(cache, key, compiled)
    return compiled, "compiled"


def compile_batched(exported, holder_avals, input_spec, bucket, *,
                    fingerprint=None, cache=None, holder_shardings=None,
                    mesh=None, audit_ctx=None):
    """AOT-compile (or cache-load) the bucket-B executable for a
    deserialized `jax.export` module.

    With `holder_shardings` (one NamedSharding per holder, from
    `TranslatedLayer.shard_`) the executable is compiled tensor-parallel:
    weights stay sharded over `mesh`, stacked inputs/outputs replicate,
    and GSPMD inserts the tp collectives inside the lax.map body. The
    sharding signature joins the persistent-cache key.

    Returns `(fn, source)` where `fn(holder_vals, *stacked_inputs)` runs
    the module over `bucket` stacked examples in one dispatch and returns
    a tuple of stacked outputs, and `source` is "compiled" (cold: built
    here, persisted if a fingerprint was given) or "disk" (warm: loaded
    from the persistent cache, zero XLA compilation).
    """
    import jax
    import jax.numpy as jnp

    if bucket < 1:
        raise ValueError(f"bucket size must be >= 1, got {bucket}")
    in_shardings = None
    if holder_shardings is not None:
        from .. import sharding as _shardlib

        repl = _shardlib.replicated(mesh)
        in_shardings = (list(holder_shardings),
                        *([repl] * len(input_spec)))
    holder_shapes = [(list(a.shape), str(a.dtype)) for a in holder_avals]
    key = None
    if fingerprint is not None:
        cache = cache or default_cache()
        key = executable_key(fingerprint, bucket, input_spec, holder_shapes,
                             sharding_sig=_sharding_sig(in_shardings))
        loaded = _load_executable(cache, key, in_shardings)
        if loaded is not None:
            return (lambda holders, *stacked:
                    loaded(list(holders), *stacked)), "disk"

    if _san.enabled():
        _san.note_trace(
            "aot.batched",
            fingerprint if fingerprint is not None else object(),
            (bucket, _san.aval_signature(list(holder_avals)),
             str([(list(s["shape"]), str(s["dtype"])) for s in input_spec]),
             "sharding:" + str(_sharding_sig(in_shardings))))

    def batched(holder_vals, *stacked):
        def body(xs):
            out = exported.call(holder_vals, *xs)
            return out if isinstance(out, tuple) else (out,)
        # lax.map traces the exported program once (single copy of the
        # graph at any bucket size) and runs it per example inside ONE
        # XLA program — identical per-example numerics, one dispatch.
        return jax.lax.map(body, tuple(stacked))

    stacked_avals = [
        jax.ShapeDtypeStruct((bucket, *s["shape"]), jnp.dtype(s["dtype"]))
        for s in input_spec]
    jitted = jax.jit(batched) if in_shardings is None else \
        jax.jit(batched, in_shardings=in_shardings)
    lowered = jitted.lower(list(holder_avals), *stacked_avals)
    compiled = lowered.compile()
    if _gc.enabled():
        ctx = dict(audit_ctx or {})
        ctx.setdefault("mesh", mesh)
        _gc.audit_executable("aot.batched", fn=batched,
                             args=(list(holder_avals), *stacked_avals),
                             lowered=lowered, compiled=compiled,
                             in_shardings=in_shardings, **ctx)
    if _cc.enabled():
        _cc.check_entrypoint("aot.batched", fn=batched,
                             args=(list(holder_avals), *stacked_avals),
                             lowered=lowered, compiled=compiled)
    if key is not None:
        _store_executable(cache, key, compiled)
    return (lambda holders, *stacked:
            compiled(list(holders), *stacked)), "compiled"
