"""Sharded, fully-jitted hybrid-parallel train step.

Reference analogs, collapsed into one component:
- `fleet.distributed_model` wrapper selection (fleet/model.py:32)
- EagerReducer fused grad allreduce (collective/reducer.cc:1067)
- DygraphShardingOptimizer / GroupShardedStage2/3 (ZeRO 1/2/3)
- HybridParallelOptimizer grad-clip-across-groups
  (hybrid_parallel_optimizer.py:254)
- static-graph Engine._parallel (auto_parallel/static/engine.py:764)
- multi-step `Executor.run` amortization (the pipelined hot path below)

TPU-native design: ONE jitted program per training step. Parameters,
optimizer slots and the batch carry NamedShardings over the hybrid mesh
(dp/pp/sharding/sep/mp); XLA/GSPMD then *derives* every collective the
reference implements imperatively: grad all-reduce over dp (reducer),
all-gather of ZeRO-sharded params before use + reduce-scatter of grads
(stages 1-3), mp all-reduces inside TP blocks. Buffers are donated so
parameter memory updates in place in HBM.

Pipelined hot path (PR 3): the per-step host work is driven to ~zero —
batch placement uses cached per-ndim NamedShardings, the learning rate
and step counter live on device (the step counter and RNG key are donated
carry state incremented/split in-graph), and live Parameter objects
resolve lazily against engine state (core.lazy.EngineRef) instead of
being reassigned every step. `train_batches` runs N optimizer steps per
dispatch via `lax.scan` (with a fused variant for a static repeated
batch), so nothing host-side executes between micro-steps.
"""
from __future__ import annotations

import itertools
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..analysis import commcheck as _cc
from ..analysis import graphcheck as _gc
from ..analysis import runtime_san as _san
from ..obs import trace as _otrace
from ..core import lazy as _lazy
from ..core.tensor import Tensor
from ..nn.clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue
from ..optimizer.lr import LRScheduler
from ..ops import random as rng_mod
from .functional import functionalize
from .sharding_spec import (
    DEFAULT_TP_RULES, spec_for_param, opt_state_spec,
)
from . import topology as topo_mod
# placement is resolved by the ONE sharding authority (paddle_tpu.sharding);
# batch-spec helpers are re-exported under their historic names
from ..sharding import (
    batch_spec_for_ndim, default_batch_spec,  # noqa: F401 (re-export)
    named_sharding as _named_sharding,
    replicated as _replicated,
    stacked_batch_spec as _stacked_batch_spec,
)


def _is_float(x):
    return jnp.issubdtype(x.dtype, jnp.floating)


_prof_mod = None

#: registry collector keys need a distinct name per engine instance
_ENGINE_OBS_SEQ = itertools.count()


def _span(name, histogram=None):
    """`profiler.profiled_span` indirection (imported on first use): a
    child of the call's `engine.dispatch` root span in the flight
    recorder, the `histogram=` observation, and a RecordEvent while a
    host profiler records."""
    global _prof_mod
    if _prof_mod is None:
        from .. import profiler as _p
        _prof_mod = _p
    return _prof_mod.profiled_span(name, histogram=histogram)


def _clip_grads(grads, clip):
    """Functional grad clip (reference: ClipGradByGlobalNorm nn/clip.py,
    applied across all hybrid groups by HybridParallelOptimizer — here grads
    are already global values, so one global norm is THE cross-group norm)."""
    if clip is None:
        return grads
    if isinstance(clip, ClipGradByGlobalNorm):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in grads.values()))
        scale = jnp.minimum(1.0, clip.clip_norm / jnp.maximum(norm, 1e-12))
        return dict((k, (g.astype(jnp.float32) * scale).astype(g.dtype))
                           for k, g in grads.items())
    if isinstance(clip, ClipGradByNorm):
        out = {}
        for k, g in grads.items():
            n = jnp.linalg.norm(g.astype(jnp.float32).reshape(-1))
            s = jnp.minimum(1.0, clip.clip_norm / jnp.maximum(n, 1e-12))
            out[k] = (g.astype(jnp.float32) * s).astype(g.dtype)
        return out
    if isinstance(clip, ClipGradByValue):
        return dict(
            (k, jnp.clip(g, clip.min, clip.max)) for k, g in grads.items())
    return grads


class ShardedTrainStep:
    """Compile `loss_fn(model, *batch)` + optimizer update into one sharded
    XLA program over the hybrid mesh."""

    def __init__(self, model, optimizer, loss_fn=None, hcg=None,
                 sharding_stage=0, rules=None, compute_dtype=None,
                 batch_spec=None, donate=True, context_parallel="ring"):
        self.model = model
        self.optimizer = optimizer
        self.hcg = hcg or topo_mod.get_hybrid_communicate_group()
        if self.hcg is None:
            self.hcg = topo_mod.HybridCommunicateGroup(
                mesh=topo_mod.build_mesh(dp=-1))
            topo_mod.set_hybrid_communicate_group(self.hcg)
        self.mesh = self.hcg.mesh
        self.sharding_stage = sharding_stage
        self.rules = DEFAULT_TP_RULES if rules is None else rules
        self.compute_dtype = (jnp.dtype(compute_dtype)
                              if compute_dtype is not None else None)
        self.donate = donate
        # context-parallel attention over the sep axis ("ring" | "ulysses" |
        # None); model-level sdpa calls reroute inside the traced step.
        self.context_parallel = context_parallel

        if loss_fn is None:
            if not hasattr(model, "loss"):
                raise ValueError("pass loss_fn or give the model a .loss")
            loss_fn = lambda m, *batch: m.loss(*batch)  # noqa: E731
        self._apply, self._params, self._buffers = functionalize(
            model, method=lambda *b: loss_fn(model, *b))

        # ---- shardings (built ONCE; the hot path only does dict reads) --
        mesh = self.mesh
        self.param_specs = dict(
            (n, spec_for_param(n, p, self.rules,
                               sharding_stage=sharding_stage, mesh=mesh))
            for n, p in self._params.items())
        self.state_specs = dict(
            (n, opt_state_spec(self.param_specs[n], p.shape, mesh,
                               sharding_stage=sharding_stage))
            for n, p in self._params.items())
        if batch_spec is None:
            batch_spec = default_batch_spec(mesh)
        self.batch_spec = batch_spec
        self._param_sh = {n: _named_sharding(mesh, s)
                          for n, s in self.param_specs.items()}
        self._state_sh = {n: _named_sharding(mesh, s)
                          for n, s in self.state_specs.items()}
        self._scalar_sh = _replicated(mesh)
        self._batch_sh_cache = {}   # ndim -> NamedSharding

        # ---- place values ---------------------------------------------
        self.param_vals = {}
        for n, p in self._params.items():
            p._value = jax.device_put(p._value, self._param_sh[n])
            self.param_vals[n] = p._value
        self.buffer_vals = {}
        self._buf_sh = {}
        for n, b in self._buffers.items():
            sh = _replicated(mesh, b.ndim)
            self._buf_sh[n] = sh
            b._value = jax.device_put(b._value, sh)
            self.buffer_vals[n] = b._value

        # optimizer slots, sharded per state_specs (None optimizer = eval-only
        # engine; train_batch will refuse)
        self.opt_state = {}
        if self.optimizer is not None:
            for n, p in self._params.items():
                names = self.optimizer._state_names
                sh = self._state_sh[n]
                self.opt_state[n] = {
                    s: jax.device_put(jnp.zeros(p.shape, p.dtype), sh)
                    for s in names}

        # ---- lazy parameter write-back ---------------------------------
        # Live Parameters resolve against engine state on read (EngineRef)
        # instead of being reassigned every step. External writes replace
        # the ref; _adopt_external_writes() picks them up (identity check,
        # no per-step property work).
        self._param_refs = []
        for n, p in self._params.items():
            v = self.param_vals[n]
            ref = _lazy.EngineRef(
                (lambda eng=self, k=n: eng.param_vals[k]), v.shape, v.dtype)
            p._value = ref
            self._param_refs.append((n, p, ref))

        self._step_fn = None
        self._eval_fns = {}
        self._multi_fns = {}
        self._step_count = 0
        self.last_grad_norm = None
        self.last_grad_norms = None
        # device-resident per-step scalars: lr re-put only when the host
        # value changes; step counter and RNG key are donated carry state
        self._lr_host = None
        self._lr_dev = None
        self._step_dev = None
        self._key_dev = None
        self._key_epoch = None
        # most-recent (n, lr) -> device (n,) array for constant lr; a
        # single entry so host-driven lr decay can't grow it unboundedly
        self._lrs_key = None
        self._lrs_dev = None
        # dispatch-count hook: host dispatches of compiled step programs and
        # explicit host->device transfers, for perf smoke tests that must
        # not depend on wall-clock
        self.stats = {"dispatches": 0, "device_puts": 0, "steps": 0}
        # in-flight dispatch marker (site, monotonic start), set around
        # every compiled-step dispatch: the training watchdog
        # (train_guard.TrainWatchdog) flags a dispatch that exceeds its
        # timeout as a wedged collective/device hang
        self._inflight = None
        # telemetry (paddle_tpu.obs): the SAME stats dict registered as a
        # weakly-held collector (the registry prunes it when the engine is
        # garbage-collected), plus a dispatch-latency histogram fed by the
        # engine::dispatch spans below whether or not a profiler records
        from ..obs.metrics import registry as _obs_registry

        self._obs_key = f"train.engine{next(_ENGINE_OBS_SEQ)}"
        # the engine's mesh never changes, so its tpu-san sharding
        # signature is computed once (the per-call probes below ride it
        # on the dispatch hot path)
        self._san_mesh_sig = _san.sharding_signature(mesh)
        self._h_dispatch = _obs_registry().histogram(
            "engine.dispatch_seconds",
            help="host-side latency of one compiled train/eval step "
                 "dispatch (enqueue, not device completion)")
        _obs_registry().register_collector(self._obs_key,
                                           self._obs_collect)
        # sharding telemetry: mesh shape + per-param shard fractions under
        # `sharding.train.engineN` (docs/sharding.md); a bound method, so
        # the registry holds it weakly and prunes with the engine
        self._sharding_obs_key = f"sharding.{self._obs_key}"
        _obs_registry().register_collector(self._sharding_obs_key,
                                           self._sharding_obs_collect)

    # ------------------------------------------------------------------
    def _obs_collect(self):
        """Registry collector: the engine's dispatch counters, weakly
        held (see __init__) so a dropped engine un-registers itself."""
        return dict(self.stats)

    def _sharding_obs_collect(self):
        """`sharding.<name>` collector: mesh shape + per-param shard
        fractions (weakly held, like _obs_collect)."""
        from ..sharding import mesh_stats

        return mesh_stats(self.mesh, self.param_specs)

    # ------------------------------------------------------------------
    def _cp_guard(self):
        """Context manager publishing the mesh to attention during trace
        (no-op on a one-device mesh). Model-level sdpa calls then become
        context-parallel when the mesh has a sequence axis > 1 (unless
        context_parallel=None) — resolved through the AxisRules "seq"
        entries, so "sep" (hybrid topology) and "cp" (MeshConfig) meshes
        both route without engine-side special cases — and on every
        multi-device mesh the Pallas flash kernel runs per shard, which
        is the only way a Mosaic kernel runs under a partitioned step."""
        import contextlib

        from ..sharding import resolve_axis
        if self.mesh.devices.size == 1:
            return contextlib.nullcontext()
        seq_axis = resolve_axis("seq", mesh=self.mesh) \
            if self.context_parallel else None
        from .context_parallel import context_parallel_guard
        return context_parallel_guard(
            self.mesh, mode=self.context_parallel or "ring",
            seq_axis=seq_axis if isinstance(seq_axis, str) else None)

    # ---- cached placement helpers (shared by train/eval/prefetch) -----
    def _batch_sharding(self, ndim):
        sh = self._batch_sh_cache.get(ndim)
        if sh is None:
            sh = _named_sharding(self.mesh, self._batch_spec_for(ndim))
            self._batch_sh_cache[ndim] = sh
        return sh

    def _place_batch(self, batch):
        """Tensors/arrays -> sharded device arrays via the per-ndim cached
        NamedShardings. Values already carrying the target sharding (e.g.
        from prefetch_to_device) are passed through untouched."""
        placed = []
        nputs = 0
        san = _san.enabled()
        for b in batch:
            v = b._value if isinstance(b, Tensor) else jnp.asarray(b)
            if san:
                # donation guard: a batch built from a buffer the engine
                # donated last step fails HERE with the donation site,
                # not inside XLA with "Array has been deleted"
                _san.check_use(v, "engine.place_batch")
            sh = self._batch_sharding(v.ndim)
            if getattr(v, "sharding", None) != sh:
                v = jax.device_put(v, sh)
                nputs += 1
            placed.append(v)
        self.stats["device_puts"] += nputs
        return tuple(placed)

    def _lr_scalar(self):
        lr = self.optimizer.get_lr()
        if self._lr_dev is None or lr != self._lr_host:
            self._lr_host = lr
            self._lr_dev = jax.device_put(jnp.asarray(lr, jnp.float32),
                                          self._scalar_sh)
            self.stats["device_puts"] += 1
        return self._lr_dev

    def _step_scalar(self):
        if self._step_dev is None:
            self._step_dev = jax.device_put(
                jnp.asarray(self._step_count + 1, jnp.int32), self._scalar_sh)
            self.stats["device_puts"] += 1
        return self._step_dev

    def _key_scalar(self):
        # the RNG key is donated carry state split in-graph; a mid-run
        # paddle.seed()/set_state() bumps the seed epoch and must refresh
        # the cached key or the reseed would be silently ignored
        epoch = rng_mod.seed_epoch()
        if self._key_dev is None or self._key_epoch != epoch:
            self._key_epoch = epoch
            self._key_dev = jax.device_put(rng_mod.next_key(),
                                           self._scalar_sh)
            self.stats["device_puts"] += 1
        return self._key_dev

    def _adopt_external_writes(self):
        """A write to an engine-managed Parameter (load_state_dict, manual
        surgery) replaces its EngineRef; fold the new value into engine
        state and re-install the ref. Identity checks only on the common
        path — no property-setter work per step."""
        for n, p, ref in self._param_refs:
            if p._v_ is not ref:
                if _san.enabled():
                    _san.check_use(p._value,
                                   f"engine.adopt_external_write[{n}]")
                self.param_vals[n] = jax.device_put(p._value,
                                                    self._param_sh[n])
                self.stats["device_puts"] += 1
                p._v_ = ref

    # ---- step program --------------------------------------------------
    def _make_step(self):
        """The pure single-step function shared by the one-step jit and the
        lax.scan multi-step variants: carries (params, opt_state, buffers,
        key, step_no) with the RNG split and step increment in-graph."""
        apply_fn = self._apply
        opt = self.optimizer
        clip = getattr(opt, "_grad_clip", None)
        compute_dtype = self.compute_dtype
        cp_guard = self._cp_guard

        def loss_of(params, buffers, batch, key):
            if compute_dtype is not None:
                params = {n: (v.astype(compute_dtype) if _is_float(v) else v)
                          for n, v in params.items()}
                # float batch inputs (images, features) join the compute
                # dtype too — conv/matmul require matching operand dtypes
                batch = tuple(b.astype(compute_dtype) if _is_float(b) else b
                              for b in batch)
            rng_mod.push_trace_key(key)
            try:
                with cp_guard():
                    loss, new_buf = apply_fn(params, buffers, *[
                        Tensor(b) for b in batch])
            finally:
                rng_mod.pop_trace_key()
            return loss, new_buf

        def step(params, opt_state, buffers, batch, lr, key, step_no):
            key, sub = jax.random.split(key)
            (loss, new_buf), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, buffers, batch, sub)
            grads = dict(
                (n, g.astype(params[n].dtype)) for n, g in grads.items())
            # pre-clip global grad norm, exposed for parity/diagnostics
            # (sharding bugs show up in the grad-norm trajectory steps before
            # they move the loss); XLA CSEs this with the clip's own norm
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in grads.values()))
            grads = _clip_grads(grads, clip)
            new_params = {}
            new_state = {}
            for n, p in params.items():
                np_, ns = opt._update_one(p, grads[n], opt_state[n], lr,
                                          step_no)
                new_params[n] = np_
                new_state[n] = ns
            return (loss, gnorm, new_params, new_state, new_buf, key,
                    step_no + 1)

        return step

    def _opt_state_sh(self):
        return {n: {s: self._state_sh[n] for s in self.opt_state[n]}
                for n in self.opt_state}

    def _build_step(self, batch_avals):
        step = self._make_step()
        param_sh = self._param_sh
        state_sh = self._opt_state_sh()
        buf_sh = self._buf_sh
        batch_sh = tuple(self._batch_sharding(a.ndim) for a in batch_avals)
        scalar_sh = self._scalar_sh

        return jax.jit(
            step,
            in_shardings=(param_sh, state_sh, buf_sh, batch_sh, scalar_sh,
                          scalar_sh, scalar_sh),
            out_shardings=(scalar_sh, scalar_sh, param_sh, state_sh, buf_sh,
                           scalar_sh, scalar_sh),
            # donate the whole carried state: params, slots, buffers, RNG
            # key and step counter update in place in HBM (lr is reused
            # across steps and stays un-donated)
            donate_argnums=(0, 1, 2, 5, 6) if self.donate else (),
        )

    def _build_multi(self, batch_avals, static):
        # scan length comes from the (n,) lrs xs; the _multi_fns cache key
        # carries n so each micro-step count compiles its own program
        step = self._make_step()
        param_sh = self._param_sh
        state_sh = self._opt_state_sh()
        buf_sh = self._buf_sh
        scalar_sh = self._scalar_sh

        def body(carry, x):
            params, opt_state, buffers, key, step_no = carry
            batch, lr = x
            loss, gnorm, params, opt_state, buffers, key, step_no = step(
                params, opt_state, buffers, batch, lr, key, step_no)
            return (params, opt_state, buffers, key, step_no), (loss, gnorm)

        if static:
            # fused variant for a static batch: the batch rides along as a
            # scan-invariant operand — no stacking, no duplicated HBM
            def multi(params, opt_state, buffers, batch, lrs, key, step0):
                carry = (params, opt_state, buffers, key, step0)
                carry, (losses, gnorms) = jax.lax.scan(
                    lambda c, lr: body(c, (batch, lr)), carry, lrs)
                params, opt_state, buffers, key, step_no = carry
                return (losses, gnorms, params, opt_state, buffers, key,
                        step_no)

            batch_sh = tuple(self._batch_sharding(a.ndim)
                             for a in batch_avals)
        else:
            # per-step batches stacked on a leading scan axis
            def multi(params, opt_state, buffers, batches, lrs, key, step0):
                carry = (params, opt_state, buffers, key, step0)
                carry, (losses, gnorms) = jax.lax.scan(
                    lambda c, x: body(c, x), carry, (batches, lrs))
                params, opt_state, buffers, key, step_no = carry
                return (losses, gnorms, params, opt_state, buffers, key,
                        step_no)

            batch_sh = tuple(
                _named_sharding(self.mesh,
                                _stacked_batch_spec(self.batch_spec, a.ndim))
                for a in batch_avals)

        return jax.jit(
            multi,
            in_shardings=(param_sh, state_sh, buf_sh, batch_sh, scalar_sh,
                          scalar_sh, scalar_sh),
            out_shardings=(scalar_sh, scalar_sh, param_sh, state_sh, buf_sh,
                           scalar_sh, scalar_sh),
            donate_argnums=(0, 1, 2, 5, 6) if self.donate else (),
        )

    def _batch_spec_for(self, ndim):
        return batch_spec_for_ndim(self.batch_spec, ndim)

    def declared_state(self):
        """(avals, specs) of the engine's full declared state — params
        plus optimizer slots (keyed ``opt/<param>/<slot>``, sharded like
        their param). The one enumeration behind both the graphcheck
        ``<site>::params`` per-chip watermark and the fsdp state-shrink
        test (`graphcheck.params_bytes_per_chip`)."""
        avals = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for n, v in self.param_vals.items()}
        specs = dict(self.param_specs)
        for n, slots in self.opt_state.items():
            for s, v in slots.items():
                avals[f"opt/{n}/{s}"] = jax.ShapeDtypeStruct(v.shape,
                                                             v.dtype)
                specs[f"opt/{n}/{s}"] = self.state_specs[n]
        return avals, specs

    def _audit_graph(self, site, fn, args):
        """Graph auditor (PADDLE_TPU_GRAPHCHECK=1): statically audit the
        freshly built step program — collectives vs the declared specs,
        conv-region layout changes, host transfers, donation actually
        aliased, live-memory watermark. Costs one extra AOT
        lower+compile per cold entrypoint; free when off.
        `expect_sharded_params` stays False: fsdp-style training gathers
        params in-graph by design (serving entrypoints pass True).
        Optimizer slots join the declared set (`declared_state`) so the
        `<site>::params` per-chip watermark covers param + opt-state
        residency — the number the fsdp memory ratchet gates
        (docs/sharding.md)."""
        param_avals, param_specs = self.declared_state()
        _gc.audit_executable(
            site, jit_obj=fn, args=args, mesh=self.mesh,
            axes_specs=[*self.param_specs.values(), self.batch_spec],
            param_avals=param_avals, param_specs=param_specs,
            expect_sharded_params=False)

    def _check_comm(self, site, fn, args):
        """Collective-schedule auditor (PADDLE_TPU_COMMCHECK=1): record
        the freshly built program's ordered collective schedule and —
        when a cross-host verifier is attached (init_parallel_env) —
        verify it against the cohort BEFORE the first dispatch, so a
        divergent host dies typed (CollectiveScheduleMismatchError)
        instead of hanging every peer in a collective. Costs one extra
        AOT lower+compile per cold entrypoint; free when off."""
        _cc.check_entrypoint(site, jit_obj=fn, args=args)

    # ---- public step APIs ----------------------------------------------
    def lower_step(self, *batch):
        """The single-step program `train_batch(*batch)` dispatches, as a
        `jax.stages.Lowered` over this engine's mesh. Its `.as_text()` and
        `.compile().as_text()` say what the step really contains — Pallas
        custom calls, the partitioner's collectives — where a platform
        test can only say what was asked for. Nothing is dispatched."""
        placed = self._place_batch(batch)
        fn = self._step_fn or self._build_step(placed)
        return fn.lower(self.param_vals, self.opt_state, self.buffer_vals,
                        placed, self._lr_scalar(), self._key_scalar(),
                        self._step_scalar())

    def _dispatch_root(self, steps):
        """The root span of one `train_batch` / `train_batches` call
        (`engine.dispatch`, attrs `steps` and `cold`): its children are
        the `engine::device_put` / `engine::dispatch` /
        `engine::write_back` spans, its own time the host code between
        them. The calling thread keeps a window of them (`flight`)."""
        _otrace.reserve_ring()
        return _otrace.root_span("engine.dispatch",
                                 attrs={"steps": steps}, profile=True)

    def train_batch(self, *batch):
        """Run one optimizer step; returns the (device) loss Tensor."""
        if self.optimizer is None:
            raise RuntimeError(
                "this engine was built without an optimizer; use eval_batch")
        with self._dispatch_root(1) as root:
            return self._train_batch(root, batch)

    def _train_batch(self, root, batch):
        self._adopt_external_writes()
        with _span("engine::device_put"):
            placed = self._place_batch(batch)
        san = _san.enabled()
        cold = self._step_fn is None
        root.set_attr("cold", cold)
        if san:
            # per-call sentinel: the step jit retraces INTERNALLY on any
            # new batch signature — a cache-keyed build hook would miss
            # exactly the silent steady-state recompile this flags
            _san.note_trace(
                "engine.step", self._obs_key,
                (_san.aval_signature(placed), self._san_mesh_sig),
                per_call=True)
        if cold:
            self._step_fn = self._build_step(placed)
        lr = self._lr_scalar()
        key = self._key_scalar()
        step_no = self._step_scalar()
        if cold and _gc.enabled():
            self._audit_graph("engine.step", self._step_fn,
                              (self.param_vals, self.opt_state,
                               self.buffer_vals, placed, lr, key, step_no))
        if cold and _cc.enabled():
            self._check_comm("engine.step", self._step_fn,
                             (self.param_vals, self.opt_state,
                              self.buffer_vals, placed, lr, key, step_no))
        self._step_count += 1
        donated = (self.param_vals, self.opt_state, self.buffer_vals,
                   key, step_no) if san and self.donate else None
        # the hot-sync probe arms only on WARM dispatches: the cold call
        # traces user loss code (compile time, not the hot path)
        self._inflight = ("engine.dispatch", time.monotonic())
        try:
            with _span("engine::dispatch", histogram=self._h_dispatch), \
                    (_san.allow_host_sync("engine.compile") if cold
                     else _san.hot_region("engine.dispatch")):
                (loss, gnorm, self.param_vals, self.opt_state,
                 self.buffer_vals, self._key_dev, self._step_dev) = \
                    self._step_fn(
                        self.param_vals, self.opt_state, self.buffer_vals,
                        placed, lr, key, step_no)
        finally:
            self._inflight = None
        if donated is not None:
            _san.note_donation("engine.dispatch", donated,
                               tag=f"step {self._step_count}")
        self.stats["dispatches"] += 1
        self.stats["steps"] += 1
        self.last_grad_norm = gnorm  # device scalar; float() to read
        self.last_grad_norms = None  # per-step vector: train_batches only
        with _span("engine::write_back"):
            self._write_back_buffers()
        if san:
            # AFTER write-back: a NonFiniteError here is meant to be
            # caught, and the model's buffer Tensors must already point
            # at the post-dispatch values (their old buffers were just
            # donated)
            _san.check_finite("engine.step", self._finite_leaves(
                loss=loss, grad_norm=gnorm))
        # Parameters resolve lazily via their EngineRef — no per-param
        # write-back loop. LR schedulers follow the eager convention: the
        # USER calls scheduler.step(); get_lr() is re-read every batch (the
        # device scalar is refreshed only when the host value changes).
        return Tensor(loss)

    def train_batches(self, batches, n=None):
        """Run up to `n` optimizer micro-steps in ONE XLA dispatch.

        `batches` is an iterable of batch-arg tuples (or single args). All
        micro-steps run inside a `lax.scan`: the step counter, RNG key and
        learning-rate schedule advance on-device, so no host code executes
        between micro-steps. When every element is the *same* batch object
        (e.g. ``[batch] * n``) the fused static variant is used — the batch
        is passed once as a scan-invariant operand instead of stacked.

        If the optimizer's learning rate is an LRScheduler the engine
        advances it once per consumed micro-batch (do NOT also call
        ``scheduler.step()`` for these steps). Returns a device Tensor of
        shape ``(n,)`` with the per-micro-step losses.
        """
        if self.optimizer is None:
            raise RuntimeError(
                "this engine was built without an optimizer; use eval_batch")
        batches = list(batches)
        if n is not None:
            batches = batches[:n]
        if not batches:
            return Tensor(jnp.zeros((0,), jnp.float32))
        with self._dispatch_root(len(batches)) as root:
            return self._train_batches(root, batches)

    def _train_batches(self, root, batches):
        n = len(batches)
        static = all(b is batches[0] for b in batches[1:])
        norm = [tuple(b) if isinstance(b, (list, tuple)) else (b,)
                for b in batches]

        self._adopt_external_writes()
        with _span("engine::device_put"):
            if static:
                placed = self._place_batch(norm[0])
            else:
                vals = [tuple(b._value if isinstance(b, Tensor)
                              else jnp.asarray(b) for b in bt)
                        for bt in norm]
                arity = len(vals[0])
                ragged = any(len(bt) != arity for bt in vals) or any(
                    len(set((tuple(bt[j].shape), str(bt[j].dtype))
                            for bt in vals)) > 1
                    for j in range(arity))
                if ragged:
                    # ragged batches can't stack onto a scan axis — fall
                    # back to sequential single-step dispatches, keeping
                    # the train_batches contract: the engine (not the
                    # user) advances an LRScheduler per consumed batch
                    sched = self.optimizer._learning_rate
                    losses, gnorms = [], []
                    for bt in norm:
                        losses.append(self.train_batch(*bt)._value)
                        gnorms.append(self.last_grad_norm)
                        if isinstance(sched, LRScheduler):
                            sched.step()
                    self.last_grad_norms = jnp.stack(gnorms)
                    return Tensor(jnp.stack(losses))
                placed = []
                nputs = 0
                for j in range(len(vals[0])):
                    stacked = jnp.stack([bt[j] for bt in vals])
                    sh = _named_sharding(
                        self.mesh,
                        _stacked_batch_spec(self.batch_spec, stacked.ndim))
                    placed.append(jax.device_put(stacked, sh))
                    nputs += 1
                placed = tuple(placed)
                self.stats["device_puts"] += nputs

        sig = (n, static, tuple((tuple(a.shape), str(a.dtype))
                                for a in placed))
        san = _san.enabled()
        if san:
            _san.note_trace("engine.multi", self._obs_key,
                            (sig, self._san_mesh_sig), per_call=True)
        fn = self._multi_fns.get(sig)
        cold = fn is None
        root.set_attr("cold", cold)
        if cold:
            fn = self._build_multi(placed, static)
            self._multi_fns[sig] = fn

        lrs = self._lr_schedule_array(n)
        key = self._key_scalar()
        step0 = self._step_scalar()
        if cold and _gc.enabled():
            self._audit_graph("engine.multi", fn,
                              (self.param_vals, self.opt_state,
                               self.buffer_vals, placed, lrs, key, step0))
        if cold and _cc.enabled():
            self._check_comm("engine.multi", fn,
                             (self.param_vals, self.opt_state,
                              self.buffer_vals, placed, lrs, key, step0))
        donated = (self.param_vals, self.opt_state, self.buffer_vals,
                   key, step0) if san and self.donate else None
        self._inflight = ("engine.dispatch", time.monotonic())
        try:
            with _span("engine::dispatch", histogram=self._h_dispatch), \
                    (_san.allow_host_sync("engine.compile") if cold
                     else _san.hot_region("engine.dispatch")):
                (losses, gnorms, self.param_vals, self.opt_state,
                 self.buffer_vals, self._key_dev, self._step_dev) = fn(
                    self.param_vals, self.opt_state, self.buffer_vals,
                    placed, lrs, key, step0)
        finally:
            self._inflight = None
        if donated is not None:
            _san.note_donation("engine.dispatch", donated,
                               tag=f"steps {self._step_count + 1}.."
                                   f"{self._step_count + n}")
        self.stats["dispatches"] += 1
        self.stats["steps"] += n
        self._step_count += n
        self.last_grad_norms = gnorms  # (n,) device vector, one per step
        self.last_grad_norm = gnorms[-1]
        with _span("engine::write_back"):
            self._write_back_buffers()
        if san:
            # AFTER write-back — see train_batch
            _san.check_finite("engine.step", self._finite_leaves(
                loss=losses, grad_norm=gnorms))
        return Tensor(losses)

    def _lr_schedule_array(self, n):
        """(n,) device lr values for the next n micro-steps. Plain-float
        learning rates are cached per (n, value); an LRScheduler is
        evaluated AND advanced host-side once per micro-step (the schedule
        values then ride into the compiled scan as xs)."""
        sched = self.optimizer._learning_rate
        if not isinstance(sched, LRScheduler):
            lr = float(sched)
            if self._lrs_key != (n, lr):
                self._lrs_key = (n, lr)
                self._lrs_dev = jax.device_put(
                    jnp.full((n,), lr, jnp.float32), self._scalar_sh)
                self.stats["device_puts"] += 1
            return self._lrs_dev
        vals = np.empty((n,), np.float32)
        for i in range(n):
            vals[i] = float(sched())
            sched.step()
        arr = jax.device_put(jnp.asarray(vals), self._scalar_sh)
        self.stats["device_puts"] += 1
        return arr

    def _finite_leaves(self, **scalars):
        """(path, value) sweep order for the tpu-san non-finite guard:
        loss and grad norm first (cheapest, most diagnostic), then every
        parameter — so the blame names the first poisoned param path."""
        leaves = list(scalars.items())
        leaves.extend(("param/" + n, v) for n, v in self.param_vals.items())
        return leaves

    def _write_back_buffers(self):
        for n, b in self._buffers.items():
            b._value = self.buffer_vals[n]

    def eval_batch(self, *batch):
        """Jitted loss evaluation (no grads, no update). Shares the cached
        batch-placement helper and shardings with the train path."""
        self._adopt_external_writes()
        with _span("engine::device_put"):
            placed = self._place_batch(batch)
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in placed)
        if _san.enabled():
            _san.note_trace("engine.eval", self._obs_key,
                            (sig, self._san_mesh_sig), per_call=True)
        fn = self._eval_fns.get(sig)
        cold = fn is None
        if cold:
            fn = self._build_eval(placed)
            self._eval_fns[sig] = fn
        key = rng_mod.next_key()
        if cold and _gc.enabled():
            self._audit_graph("engine.eval", fn,
                              (self.param_vals, self.buffer_vals, placed,
                               key))
        if cold and _cc.enabled():
            self._check_comm("engine.eval", fn,
                             (self.param_vals, self.buffer_vals, placed,
                              key))
        with _span("engine::dispatch", histogram=self._h_dispatch), \
                (_san.allow_host_sync("engine.compile") if cold
                 else _san.hot_region("engine.dispatch")):
            loss = fn(self.param_vals, self.buffer_vals, placed, key)
        self.stats["dispatches"] += 1
        if _san.enabled():
            _san.check_finite("engine.eval", [("loss", loss)])
        return Tensor(loss)

    def _build_eval(self, batch_avals):
        apply_fn = self._apply
        compute_dtype = self.compute_dtype
        cp_guard = self._cp_guard

        def ev(params, buffers, batch, key):
            if compute_dtype is not None:
                params = {n: (v.astype(compute_dtype) if _is_float(v)
                              else v) for n, v in params.items()}
            rng_mod.push_trace_key(key)
            try:
                with cp_guard():
                    loss, _ = apply_fn(params, buffers,
                                       *[Tensor(b) for b in batch])
            finally:
                rng_mod.pop_trace_key()
            return loss

        batch_sh = tuple(self._batch_sharding(a.ndim) for a in batch_avals)
        return jax.jit(
            ev,
            in_shardings=(self._param_sh, self._buf_sh, batch_sh,
                          self._scalar_sh),
            out_shardings=self._scalar_sh,
        )

    def sync_optimizer_state(self):
        """Write engine opt slots back into the eager Optimizer (for
        state_dict parity)."""
        for n, p in self._params.items():
            self.optimizer._accumulators[id(p)] = dict(self.opt_state[n])
        self.optimizer._step_count = self._step_count

    # ---- fault tolerance: snapshots + checkpoint state -----------------
    def _copy_tree(self, d):
        # jnp.copy dispatches a device-side copy that preserves sharding;
        # plain references would be invalidated by the NEXT dispatch (the
        # engine donates params/slots/buffers/key/step to XLA every step)
        return {k: jnp.copy(v) for k, v in d.items()}

    def snapshot(self):
        """Donation-safe deep copy of the engine's carried train state
        (params, optimizer slots, buffers, step count, RNG key) — the unit
        of `train_guard.TrainGuard`'s rollback ring. The RNG key is
        materialized first so a restore replays the EXACT key sequence
        (bit-identical skip-and-continue) instead of redrawing."""
        if self.optimizer is not None:
            self._key_scalar()
        return {
            "step_count": self._step_count,
            "params": self._copy_tree(self.param_vals),
            "opt": {n: self._copy_tree(s)
                    for n, s in self.opt_state.items()},
            "buffers": self._copy_tree(self.buffer_vals),
            "key": None if self._key_dev is None else jnp.copy(
                self._key_dev),
            "key_epoch": self._key_epoch,
        }

    def restore(self, snap):
        """Rewind the engine to `snap` (from `snapshot()`). The snapshot
        itself is copied on the way in, so the SAME snapshot can absorb a
        second rollback. External Parameter writes since the snapshot are
        dropped (the refs are re-armed) — a rollback rewinds everything."""
        self.param_vals = self._copy_tree(snap["params"])
        self.opt_state = {n: self._copy_tree(s)
                          for n, s in snap["opt"].items()}
        self.buffer_vals = self._copy_tree(snap["buffers"])
        self._step_count = int(snap["step_count"])
        self._step_dev = None     # rebuilt from _step_count on next step
        self._key_epoch = snap["key_epoch"]
        self._key_dev = None if snap["key"] is None else jnp.copy(
            snap["key"])
        self._write_back_buffers()
        for _n, p, ref in self._param_refs:
            p._v_ = ref

    def state_dict(self):
        """Checkpointable state tree (Tensor leaves + the step scalar) for
        `CheckpointManager` round-trips: restore_latest() into this tree,
        then `load_state_dict` it back — the engine-level resume path the
        fault-tolerance layer (preemption saves, elastic relaunch) uses."""
        tree = {
            "model": {n: Tensor(v) for n, v in self.param_vals.items()},
            "buffers": {n: Tensor(v) for n, v in self.buffer_vals.items()},
            "step": self._step_count,
        }
        if self.opt_state:
            tree["opt"] = {n: {s: Tensor(v) for s, v in slots.items()}
                           for n, slots in self.opt_state.items()}
        return tree

    def load_state_dict(self, tree):
        """Adopt a `state_dict()`-shaped tree (fresh from a checkpoint
        restore) as the engine's carried state, re-placed per the CURRENT
        mesh shardings."""
        for n in self.param_vals:
            self.param_vals[n] = jax.device_put(
                tree["model"][n]._value, self._param_sh[n])
        for n in self.buffer_vals:
            if n in tree.get("buffers", {}):
                self.buffer_vals[n] = jax.device_put(
                    tree["buffers"][n]._value, self._buf_sh[n])
        for n, slots in (tree.get("opt") or {}).items():
            sh = self._state_sh[n]
            for s, v in slots.items():
                self.opt_state[n][s] = jax.device_put(v._value, sh)
        self._step_count = int(tree.get("step", 0))
        self._step_dev = None
        self._write_back_buffers()
        for _n, p, ref in self._param_refs:
            p._v_ = ref


def parallelize(model, optimizer=None, loss_fn=None, *, mesh=None,
                sharding_stage=0, rules=None, compute_dtype=None,
                context_parallel="ring"):
    """High-level entry (≈ dist.parallelize / fleet.distributed_model +
    distributed_optimizer in one): returns a ShardedTrainStep.

    `mesh` may be a built `jax.sharding.Mesh` OR a declarative
    `sharding.MeshConfig` — `MeshConfig(fsdp=N)` is the one-config pod
    training story (docs/sharding.md): params and optimizer state shard
    along the fsdp axis, gathered in-graph at use sites, with zero
    per-model spec tables."""
    from ..sharding import MeshConfig

    if isinstance(mesh, MeshConfig):
        mesh = mesh.build()
    hcg = None
    if mesh is not None:
        hcg = topo_mod.HybridCommunicateGroup(mesh=mesh)
        topo_mod.set_hybrid_communicate_group(hcg)
    return ShardedTrainStep(model, optimizer, loss_fn=loss_fn, hcg=hcg,
                            sharding_stage=sharding_stage, rules=rules,
                            compute_dtype=compute_dtype,
                            context_parallel=context_parallel)
