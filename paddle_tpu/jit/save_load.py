"""jit.save / jit.load — inference-model export.

Reference: paddle.jit.save (jit/api.py) writes pdmodel+pdiparams; here the
exported artifact is a StableHLO text module + a parameter archive, the
XLA-native deployment format (consumed by PJRT AOT / IFRT serving, replacing
the reference's AnalysisPredictor path).
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading

from ..analysis import commcheck as _cc
from ..analysis import graphcheck as _gc
from ..analysis import locks as _locks
from ..analysis import runtime_san as _san

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor


def save(layer, path, input_spec=None, **configs):
    """Exports layer.forward traced over `input_spec` (list of example
    Tensors or InputSpec-like (shape, dtype) tuples)."""
    from ..nn.layer.layers import Layer

    if input_spec is None:
        raise ValueError("jit.save requires input_spec on the TPU build")

    examples = []
    for spec in input_spec:
        if isinstance(spec, Tensor):
            examples.append(spec._value)
        elif hasattr(spec, "shape"):
            shape = [1 if (s is None or s < 0) else int(s) for s in spec.shape]
            dt = getattr(spec, "dtype", jnp.float32)
            examples.append(jnp.zeros(shape, dt))
        else:
            shape, dt = spec
            examples.append(jnp.zeros([int(s) for s in shape], dt))

    params = dict(layer.named_parameters()) if isinstance(layer, Layer) else {}
    buffers = {k: v for k, v in layer.named_buffers()} if isinstance(layer, Layer) else {}

    names = list(params) + list(buffers)
    holders = [params[n] for n in params] + [buffers[n] for n in buffers]

    was_training = getattr(layer, "training", False)
    if isinstance(layer, Layer):
        layer.eval()

    def pure(holder_vals, *input_vals):
        saved = [h._value for h in holders]
        try:
            for h, v in zip(holders, holder_vals):
                h._value = v
            from ..core.dispatch import no_grad
            with no_grad():
                out = layer(*[Tensor(v) for v in input_vals])
            if isinstance(out, (list, tuple)):
                return tuple(o._value for o in out)
            return out._value
        finally:
            for h, v in zip(holders, saved):
                h._value = v

    # one trace: the jax.export module is both the runnable .pdmodel blob
    # and the source of the inspectable StableHLO text
    exported = jax.export.export(jax.jit(pure))(
        [jax.ShapeDtypeStruct(h.shape, h._value.dtype) for h in holders],
        *[jax.ShapeDtypeStruct(e.shape, e.dtype) for e in examples])
    blob = exported.serialize()
    stablehlo = exported.mlir_module()

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path + ".stablehlo.mlir", "w") as f:
        f.write(stablehlo)
    with open(path + ".pdmodel", "wb") as f:
        f.write(blob)
    with open(path + ".pdiparams", "wb") as f:
        pickle.dump({n: np.asarray(h._value) for n, h in zip(names, holders)},
                    f, protocol=4)
    # per-parameter sharding annotations ride along in the meta JSON so a
    # loaded artifact can re-shard onto a serving mesh (TranslatedLayer
    # .shard_): logical axis names resolve through the rule table at LOAD
    # time (the serving mesh's vocabulary, not the trainer's); physical
    # dist_spec entries are recorded as-is for legacy layers
    shardings = {}
    for n, h in zip(names, holders):
        axes = getattr(h, "logical_axes", None)
        if axes is not None:
            shardings[n] = {"logical": list(axes)}
            continue
        phys = getattr(h, "dist_spec", None)
        if phys:
            shardings[n] = {"physical": [
                list(e) if isinstance(e, (tuple, list)) else e
                for e in phys]}
    meta = {
        "inputs": [{"shape": list(e.shape), "dtype": str(e.dtype)} for e in examples],
        "param_names": names,
        "shardings": shardings,
    }
    with open(path + ".pdmodel.json", "w") as f:
        json.dump(meta, f)

    if was_training and isinstance(layer, Layer):
        layer.train()


class TranslatedLayer:
    """Loaded inference program (reference: TranslatedLayer, jit/
    translated_layer.py). Executes the deserialized jax.export module —
    no Python body needed; the program IS the artifact."""

    def __init__(self, params, meta, stablehlo_text, exported=None,
                 fingerprint=None):
        self._param_names = list(params)
        self._params = {k: Tensor(jnp.asarray(v)) for k, v in params.items()}
        self._meta = meta
        self._stablehlo = stablehlo_text
        self._exported = exported
        self._call = jax.jit(exported.call) if exported is not None else None
        self._fingerprint = fingerprint
        # shape-bucketed AOT executables (jit.aot): keyed by batch bucket,
        # shared by every Predictor clone over this layer — a re-cloned
        # (quarantined) serving member never re-pays compilation
        self._aot_lock = _locks.new_lock("aot.layer")
        # tpu-san entrypoint identity: a fresh object per layer instance
        # (id() could be recycled into a warm entry after GC)
        self._san_token = object()
        # graph auditor: signatures already audited (one audit per input
        # signature per layer — the audit pays its own lower+compile)
        self._gc_sigs = set()
        self._aot_execs: dict = {}
        self._aot_building: dict = {}   # bucket -> Event (build in flight)
        self._aot_counts = {"compiles": 0, "disk_hits": 0, "mem_hits": 0}
        # tensor-parallel placement (shard_): mesh + resolved per-param
        # specs; None until shard_ is called (single-device execution)
        self._mesh = None
        self._param_specs = None
        self._sharding_obs_key = None

    def __call__(self, *inputs):
        if self._call is None:
            raise RuntimeError("artifact has no executable module "
                               "(.pdmodel missing)")
        vals = [i._value if isinstance(i, Tensor) else jnp.asarray(i)
                for i in inputs]
        if _san.enabled():
            # per-call retrace sentinel on the layer's caching jit: a
            # NEW input signature means jax retraces right here — after
            # mark_warm that's a serving-hot-path recompile finding; the
            # sharding signature rides along so a shard_() recompile is
            # blamed as a placement change, not a shape delta
            _san.note_trace(
                "aot.layer_call", self._san_token,
                (_san.aval_signature(vals),
                 _san.sharding_signature(self._mesh, self._param_specs)),
                per_call=True)
        holder_vals = [self._params[n]._value for n in self._param_names]
        if _gc.enabled() or _cc.enabled():
            sig = _san.aval_signature(vals)
            with self._aot_lock:      # check-then-act under the lock:
                fresh = sig not in self._gc_sigs    # concurrent workers
                if fresh:                           # must not double-pay
                    self._gc_sigs.add(sig)          # the audit compile
            if fresh:
                if _gc.enabled():
                    _gc.audit_executable("aot.layer_call",
                                         jit_obj=self._call,
                                         args=(holder_vals, *vals),
                                         **self._gc_ctx())
                if _cc.enabled():
                    _cc.check_entrypoint("aot.layer_call",
                                         jit_obj=self._call,
                                         args=(holder_vals, *vals))
        out = self._call(holder_vals, *vals)
        if isinstance(out, (list, tuple)):
            return tuple(Tensor(o) for o in out)
        return Tensor(out)

    forward = __call__

    def state_dict(self):
        return dict(self._params)

    def set_state_dict(self, state):
        for k, v in state.items():
            if k in self._params:
                t = v if isinstance(v, Tensor) else \
                    Tensor(jnp.asarray(np.asarray(v)))
                if self._mesh is not None:
                    # a sharded layer stays sharded across weight swaps:
                    # the TP AOT executables demand exactly this placement
                    from .. import sharding as _shardlib

                    t = Tensor(jax.device_put(
                        t._value, _shardlib.named_sharding(
                            self._mesh, self._param_specs[k])))
                self._params[k] = t

    # -- tensor-parallel placement (paddle_tpu.sharding) -------------------
    def shard_(self, mesh, rules=None, registry=None):
        """Re-place every parameter across `mesh` per the sharding
        annotations recorded at export (logical axes resolved through the
        active rule table, or `rules`); unannotated params replicate.
        Subsequent `__call__`/`batched_call` executables partition over
        the mesh (GSPMD inserts the tp collectives), so a ServingPool or
        DecodeEngine over this layer serves tensor-parallel. Cached AOT
        executables are dropped (they were compiled for the previous
        placement). Returns self."""
        import jax as _jax

        from .. import sharding as _shardlib

        ax_map = self._meta.get("shardings") or {}
        specs = {}
        for n in self._param_names:
            t = self._params[n]
            v = t._value
            entry = ax_map.get(n) or {}
            if "logical" in entry:
                sh = _shardlib.logical_to_sharding(
                    entry["logical"], mesh, rules=rules,
                    shape=tuple(v.shape))
            else:
                phys = [tuple(e) if isinstance(e, list) else e
                        for e in entry.get("physical", ())]
                sizes = dict(mesh.shape)
                entries = [e if e is None or all(
                    a in sizes for a in ((e,) if isinstance(e, str) else e))
                    else None for e in phys]
                entries += [None] * (v.ndim - len(entries))
                from ..sharding.rules import _divisible_spec

                sh = _shardlib.named_sharding(mesh, _divisible_spec(
                    _shardlib.spec(*entries[: v.ndim]), tuple(v.shape),
                    mesh))
            t._value = _jax.device_put(v, sh)
            specs[n] = sh.spec
        self._mesh = mesh
        self._param_specs = specs
        with self._aot_lock:
            self._aot_execs.clear()
            self._gc_sigs.clear()  # new placement -> new programs: re-audit
        # `sharding.artifact.<fp8>` collector: mesh shape + per-param
        # shard fractions; bound method, so the registry holds it weakly
        from ..obs.metrics import registry as _registry

        reg = registry if registry is not None else _registry()
        fp = (self.fingerprint or "unfingerprinted")[:8]
        self._sharding_obs_key = f"sharding.artifact.{fp}"
        reg.register_collector(self._sharding_obs_key,
                               self._sharding_obs_collect)
        return self

    def _sharding_obs_collect(self):
        from .. import sharding as _shardlib

        if self._mesh is None:
            return {}
        return _shardlib.mesh_stats(self._mesh, self._param_specs)

    def _gc_ctx(self):
        """Graph-auditor context: after shard_() the parameters must
        STAY sharded through every executable (GC001 full-gather check);
        single-device layers audit the structural rules only."""
        param_avals = {
            n: jax.ShapeDtypeStruct(self._params[n]._value.shape,
                                    self._params[n]._value.dtype)
            for n in self._param_names}
        return {"mesh": self._mesh, "param_avals": param_avals,
                "param_specs": dict(self._param_specs or {}),
                "axes_specs": list((self._param_specs or {}).values()),
                "expect_sharded_params": self._mesh is not None}

    @property
    def mesh(self):
        return self._mesh

    def param_shardings(self):
        """{name: PartitionSpec} after shard_(); None before."""
        return dict(self._param_specs) if self._param_specs else None

    @property
    def input_spec(self):
        return self._meta["inputs"]

    @property
    def num_outputs(self):
        if self._exported is None:
            return None
        return len(self._exported.out_avals)

    @property
    def program_text(self):
        return self._stablehlo

    # -- shape-bucketed AOT executables (serving hot path) -----------------
    @property
    def fingerprint(self):
        """Stable identity of the executable module (sha256 of the
        serialized jax.export blob) — the model part of the persistent
        compile-cache key. None when the artifact has no module."""
        if self._fingerprint is None and self._exported is not None:
            self._fingerprint = hashlib.sha256(
                bytes(self._exported.serialize())).hexdigest()
        return self._fingerprint

    def _holder_avals(self):
        return [jax.ShapeDtypeStruct(self._params[n]._value.shape,
                                     self._params[n]._value.dtype)
                for n in self._param_names]

    def batched_call(self, bucket, cache=None):
        """`fn(stacked_inputs) -> tuple of stacked outputs` running this
        module over `bucket` stacked examples (leading batch axis) in ONE
        XLA dispatch. Compiled at most once per bucket per process
        (in-memory cache on the layer, shared by all clones) and at most
        once per bucket per *machine* (persistent on-disk cache — see
        jit.aot). Per-example outputs are bit-identical to `__call__`."""
        if self._exported is None:
            raise RuntimeError("artifact has no executable module "
                               "(.pdmodel missing)")
        with self._aot_lock:
            fn = self._aot_execs.get(bucket)
            if fn is not None:
                self._aot_counts["mem_hits"] += 1
                return fn
            ev = self._aot_building.get(bucket)
            builder = ev is None
            if builder:
                ev = self._aot_building[bucket] = threading.Event()
        if not builder:
            # another worker is already building this bucket: wait for it
            # instead of paying a duplicate multi-second compile
            ev.wait()
            with self._aot_lock:
                fn = self._aot_execs.get(bucket)
                if fn is not None:
                    self._aot_counts["mem_hits"] += 1
                    return fn
            # the builder failed — retry (one waiter becomes the builder)
            return self.batched_call(bucket, cache=cache)
        from .aot import compile_batched

        try:
            holder_sh = None
            if self._mesh is not None:
                from .. import sharding as _shardlib

                holder_sh = [
                    _shardlib.named_sharding(self._mesh,
                                             self._param_specs[n])
                    for n in self._param_names]
            with _locks.blocking_region("aot.compile"):
                raw, source = compile_batched(
                    self._exported, self._holder_avals(), self.input_spec,
                    bucket, fingerprint=self.fingerprint, cache=cache,
                    holder_shardings=holder_sh, mesh=self._mesh,
                    audit_ctx=self._gc_ctx() if _gc.enabled() else None)

            def fn(*stacked_inputs, _raw=raw):
                holders = [self._params[n]._value
                           for n in self._param_names]
                return _raw(holders, *stacked_inputs)

            with self._aot_lock:
                self._aot_execs[bucket] = fn
                self._aot_counts["compiles" if source == "compiled"
                                 else "disk_hits"] += 1
            return fn
        finally:
            with self._aot_lock:
                self._aot_building.pop(bucket, None)
            ev.set()

    def warmup_buckets(self, buckets, cache=None):
        """Precompile (or cache-load) the executables for every bucket so
        a pool takes traffic with zero compile stalls."""
        for b in sorted(set(int(b) for b in buckets)):
            self.batched_call(b, cache=cache)

    def aot_stats(self):
        with self._aot_lock:
            return {"buckets": sorted(self._aot_execs),
                    **dict(self._aot_counts)}


def load(path, **configs):
    with open(path + ".pdiparams", "rb") as f:
        params = pickle.load(f)
    with open(path + ".pdmodel.json") as f:
        meta = json.load(f)
    with open(path + ".stablehlo.mlir") as f:
        text = f.read()
    exported = None
    fingerprint = None
    if os.path.exists(path + ".pdmodel"):
        with open(path + ".pdmodel", "rb") as f:
            blob = f.read()
        # fingerprint from the artifact bytes: deterministic across
        # processes, so the persistent compile cache keys stay stable
        fingerprint = hashlib.sha256(blob).hexdigest()
        exported = jax.export.deserialize(bytearray(blob))
    ordered = {n: params[n] for n in meta.get("param_names", params)}
    return TranslatedLayer(ordered, meta, text, exported,
                           fingerprint=fingerprint)
