"""Eager op dispatch with tape-based autograd over jitted JAX primitives.

Design (TPU-native replacement for the reference's eager stack):

The reference dispatches each eager op through a generated `*_ad_func` that
records a GradNode on the tape and calls a phi kernel
(paddle/fluid/eager/auto_code_generator/generator/eager_gen.py:251,
paddle/fluid/eager/grad_node_info.h:197). Here every op is a *pure JAX
function*; eager execution runs it under a cached `jax.jit` (one compilation
per (op, static-args, shapes) — XLA is the kernel library). Autograd records a
lightweight tape node holding the op's input arrays; the backward pass calls a
cached jitted VJP (`jax.vjp` inside jit) so gradients are also compiled. The
residual policy is "store inputs, recompute forward inside the VJP" — per-op
rematerialization, which on TPU trades cheap FLOPs for HBM.

The fully-jitted training path (paddle_tpu.jit) bypasses this tape entirely by
tracing the whole step; this module is the define-by-run compatibility layer.
"""
from __future__ import annotations

import contextlib
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import monitor

from .lazy import _state as _lazy_state

__all__ = [
    "apply",
    "no_grad",
    "is_grad_enabled",
    "set_grad_enabled",
    "GradNode",
]


class _State(threading.local):
    def __init__(self):
        self.grad_enabled = True


_state = _State()


def is_grad_enabled() -> bool:
    return _state.grad_enabled


def set_grad_enabled(mode: bool):
    _state.grad_enabled = bool(mode)


_saved_tensors_hooks: list = []


@contextlib.contextmanager
def saved_tensors_hooks(pack_hook, unpack_hook):
    """Intercept tensors the tape saves for backward (reference:
    paddle.autograd.saved_tensors_hooks — e.g. offload-to-host packs).
    pack_hook(array) runs when an op records its inputs; unpack_hook runs
    once when the node's VJP first needs them."""
    _saved_tensors_hooks.append((pack_hook, unpack_hook))
    try:
        yield
    finally:
        _saved_tensors_hooks.pop()


@contextlib.contextmanager
def no_grad():
    """Context manager disabling tape recording (reference: paddle.no_grad)."""
    prev = _state.grad_enabled
    _state.grad_enabled = False
    try:
        yield
    finally:
        _state.grad_enabled = prev


# --------------------------------------------------------------------------
# Cached jitted forward / vjp per (impl, static-args) pair.
# --------------------------------------------------------------------------

_jit_cache: dict = {}


def _hashable(v):
    if isinstance(v, (list,)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    if isinstance(v, np.ndarray):
        return (v.shape, str(v.dtype), v.tobytes())
    return v


def _get_fwd(impl, statics_key, statics):
    key = ("fwd", impl, statics_key)
    fn = _jit_cache.get(key)
    if fn is None:
        fn = jax.jit(partial(impl, **statics))
        _jit_cache[key] = fn
        monitor.increment("op_jit_program_total")
    return fn


def _get_fwd_vjp(impl, statics_key, n_primals, statics):
    """Jitted function: primals -> (out, residual-free). We don't keep the
    closure; backward re-runs the forward inside the jitted VJP below."""
    return _get_fwd(impl, statics_key, statics)


def _vjp_callable(impl, statics, n_primals):
    def run(primals, cotangent):
        f = partial(impl, **statics)
        out, vjp_fn = jax.vjp(f, *primals)
        # Cotangents may arrive in a different float dtype than the output
        # (mixed-precision tapes: an fp32 loss feeding a bf16 matmul). Cast to
        # the output aval's dtype — XLA fuses the convert into the vjp.
        cotangent = jax.tree_util.tree_map(
            lambda c, o: jnp.asarray(c, o.dtype) if c.dtype != o.dtype else c,
            cotangent, out)
        return vjp_fn(cotangent)

    return run


def _get_vjp(impl, statics_key, n_primals, statics):
    key = ("vjp", impl, statics_key, n_primals)
    fn = _jit_cache.get(key)
    if fn is None:
        fn = jax.jit(_vjp_callable(impl, statics, n_primals))
        _jit_cache[key] = fn
    return fn


# --------------------------------------------------------------------------
# create_graph=True path: the VJP itself dispatched as a taped op.
#
# Reference analog: egr::RunBackward with create_graph — grad-node execution
# runs through the normal eager dispatch so new GradNodes are recorded for the
# cotangent computation (paddle/fluid/eager/backward.cc:428). Here the VJP of
# op `impl` becomes an op in its own right: a pure function of
# (primals..., cotangents...) returning one grad per primal. Dispatching it via
# `apply` makes the produced gradients differentiable (grad-of-grad), and
# higher orders nest for free — the taped VJP of a taped VJP is just another
# cached impl.
# --------------------------------------------------------------------------

_taped_vjp_cache: dict = {}


def taped_vjp_impl(impl, n_primals, out_is_seq):
    key = (impl, n_primals, out_is_seq)
    fn = _taped_vjp_cache.get(key)
    if fn is None:
        def run(*args, **statics):
            primals, cts = args[:n_primals], args[n_primals:]
            f = partial(impl, **statics)
            out, vjp_fn = jax.vjp(f, *primals)
            outs = out if isinstance(out, (tuple, list)) else (out,)
            cts = tuple(
                jnp.asarray(c, o.dtype)
                if hasattr(c, "dtype") and c.dtype != o.dtype else c
                for c, o in zip(cts, outs))
            grads = vjp_fn(tuple(cts) if out_is_seq else cts[0])
            # float0 cotangents (integer primals) can't cross a jit boundary
            # as Tensor payloads; substitute dead float zeros (their metas
            # carry needs_grad=False so the engine never uses them).
            return tuple(
                jnp.zeros(p.shape, jnp.float32)
                if hasattr(g, "dtype") and g.dtype == jax.dtypes.float0 else g
                for g, p in zip(grads, primals))

        run.__name__ = f"{getattr(impl, '__name__', 'op')}_taped_vjp"
        _taped_vjp_cache[key] = fn = run
    return fn


# --------------------------------------------------------------------------
# Tape
# --------------------------------------------------------------------------


class GradNode:
    """A recorded op on the eager tape.

    Reference analog: egr::GradNodeBase (grad_node_info.h:197). Holds the pure
    impl + static args + input arrays; `run_vjp` computes input cotangents via
    a cached jitted VJP.
    """

    __slots__ = (
        "name",
        "impl",
        "statics",
        "statics_key",
        "input_arrays",
        "input_metas",
        "input_versions",
        "n_outputs",
        "out_is_seq",
        "_id",
        "_unpack_hook",
    )

    _counter = [0]

    def __init__(self, name, impl, statics, statics_key, input_arrays, input_metas, n_outputs, out_is_seq):
        self.name = name
        self.impl = impl
        self.statics = statics
        self.statics_key = statics_key
        self.input_arrays = input_arrays
        self._unpack_hook = None
        self.input_metas = input_metas  # list of (producer GradNode|None, out_idx, leaf Tensor|None, needs_grad)
        # Tensor versions at record time — the taped (create_graph) path
        # recomputes from live tensors and must refuse in-place-mutated ones
        # (reference analog: the eager tensor inplace_version check,
        # paddle/fluid/eager/tensor_wrapper.h).
        self.input_versions = [
            getattr(m[2], "_version", 0) if m[2] is not None else 0
            for m in input_metas]
        self.n_outputs = n_outputs
        self.out_is_seq = out_is_seq
        GradNode._counter[0] += 1
        self._id = GradNode._counter[0]

    def run_vjp(self, cotangents):
        """cotangents: list aligned with outputs (None entries filled with zeros)."""
        unpack = getattr(self, "_unpack_hook", None)
        if unpack is not None and self.input_arrays is not None:
            self.input_arrays = [unpack(a) for a in self.input_arrays]
            self._unpack_hook = None
        if self.input_arrays is None:
            raise RuntimeError(
                f"Trying to backward through op '{self.name}' a second time; "
                "the saved tensors were already released. Call backward with "
                "retain_graph=True to backward multiple times.")
        if self.out_is_seq:
            ct = tuple(cotangents)
        else:
            ct = cotangents[0]
        vjp = _get_vjp(self.impl, self.statics_key, len(self.input_arrays), self.statics)
        return vjp(tuple(self.input_arrays), ct)

    def run_vjp_taped(self, cotangents):
        """create_graph=True: dispatch the VJP through `apply` so the
        cotangent computation is itself recorded on the tape. `cotangents`
        entries are Tensors (tracked) or raw arrays (constants); returns a
        list of Tensors, one per input slot.

        Uses the live input Tensors from the metas — that is what links the
        new grad nodes back to the original graph for second order — guarded
        by a version check so an in-place mutation between forward and
        backward raises instead of silently changing the gradient. (Under
        AMP the live values are the pre-cast fp32 ones, so taped gradients
        are computed at full precision — an intentional, finer deviation
        from the snapshot path.) Saved-tensor unpack hooks only fire for
        slots with no live Tensor, and nothing is unpacked in place, so
        offloaded residuals stay offloaded."""
        if self.input_arrays is None:
            raise RuntimeError(
                f"Trying to backward through op '{self.name}' a second time; "
                "the saved tensors were already released. Call backward with "
                "retain_graph=True to backward multiple times.")
        unpack = getattr(self, "_unpack_hook", None)
        ins = []
        for meta, a, ver in zip(self.input_metas, self.input_arrays,
                                self.input_versions):
            t = meta[2]
            if t is not None:
                if getattr(t, "_version", 0) != ver:
                    raise RuntimeError(
                        f"Input of op '{self.name}' was modified by an "
                        "in-place operation after being used in the forward; "
                        "double-grad (create_graph=True) cannot recompute "
                        "through it. Clone the tensor before mutating it.")
                ins.append(t)
            else:
                ins.append(unpack(a) if unpack is not None else a)
        impl = taped_vjp_impl(self.impl, len(ins), self.out_is_seq)
        outs = apply(self.name + "_grad", impl, [*ins, *cotangents],
                     statics=self.statics)
        return list(outs) if isinstance(outs, (tuple, list)) else [outs]

    def release(self):
        self.input_arrays = None


# AMP hook: set by paddle_tpu.amp at import; returns target dtype for an op
# under the active autocast policy, or None (reference analog: the AMP cast
# logic generated into every ad_func, eager_amp_auto_cast.h:64).
_amp_cast_hook = None


def set_amp_cast_hook(fn):
    global _amp_cast_hook
    _amp_cast_hook = fn


# Profiler hook: set by paddle_tpu.profiler while recording; maps op name ->
# a span object with begin()/end() (reference analog: the RecordEvent
# emitted inside every generated ad_func).
_profile_hook = None


def set_profile_hook(fn):
    global _profile_hook
    _profile_hook = fn


# Static-capture hook: set by paddle_tpu.static while static mode is on;
# appends every dispatched op to the default Program (the reference appends
# OpDescs to the Program block instead, python/paddle/base/framework.py).
_static_capture_hook = None


def set_static_capture_hook(fn):
    global _static_capture_hook
    _static_capture_hook = fn


def apply(name, impl, tensor_args, statics=None, out_wrapper=None):
    hook = _profile_hook  # single read: may be unset concurrently by stop()
    if hook is None:
        return _apply(name, impl, tensor_args, statics, out_wrapper)
    ev = hook(name)
    ev.begin()
    try:
        return _apply(name, impl, tensor_args, statics, out_wrapper)
    finally:
        ev.end()


def _apply(name, impl, tensor_args, statics=None, out_wrapper=None):
    """Dispatch one eager op.

    Args:
      name: op name (for debugging / profiling).
      impl: pure function (array_args..., **statics) -> array | tuple of arrays.
      tensor_args: sequence of Tensor (or raw array) positional operands.
      statics: dict of non-traced keyword args (must be hashable-ish).
      out_wrapper: optional callable mapping each output array -> Tensor
        (defaults to Tensor construction).

    Returns a Tensor or tuple of Tensors mirroring impl's output structure.
    """
    from .tensor import Tensor  # circular-safe

    rec = _lazy_state.stack[-1] if _lazy_state.stack else None
    if rec is not None and out_wrapper is not None:
        rec = None
    if rec is not None and _amp_cast_hook is not None:
        from ..amp import amp_state
        if amp_state().enabled:
            rec = None       # per-op autocast needs per-op names: no defer
    if rec is not None and not rec.flushing:
        from .. import flags as _flags
        if _flags.flag("check_nan_inf"):
            rec = None                     # per-op NaN isolation
    if rec is not None and not rec.flushing:
        res = rec.maybe_record(name, impl, tensor_args, statics)
        if res is not NotImplemented:
            return res
        # op declined deferral (shape/value-dependent impl): it is a break
        # point — materialize the segment, then run this op eagerly
        rec.flush()
        monitor.increment("lazy_segment_fallback_ops")

    monitor.increment("op_dispatch_total")
    statics = statics or {}
    statics_key = _hashable(statics)

    cast_to = _amp_cast_hook(name) if _amp_cast_hook is not None else None

    arrays = []
    metas = []
    any_grad = False
    for t in tensor_args:
        if isinstance(t, Tensor):
            v = t._value
            # host-offloaded operands (pinned_host params from
            # group_sharded_parallel(offload=True) etc.) stream to device
            # memory on use — XLA cannot mix memory spaces in one op
            mk = getattr(getattr(v, "sharding", None), "memory_kind", None)
            if mk in ("pinned_host", "unpinned_host"):
                v = jax.device_put(
                    v, v.sharding.with_memory_kind("device"))
            if cast_to is not None and v.dtype != cast_to and jnp.issubdtype(v.dtype, jnp.floating):
                v = v.astype(cast_to)
            arrays.append(v)
            needs = (not t.stop_gradient) and _state.grad_enabled
            metas.append((t._grad_node, t._out_idx, t, needs))
            any_grad = any_grad or needs
        else:
            arrays.append(t)
            metas.append((None, 0, None, False))

    fwd = _get_fwd(impl, statics_key, statics)
    out = fwd(*arrays)

    out_is_seq = isinstance(out, (tuple, list))
    outs = list(out) if out_is_seq else [out]

    # numerical sanitizer (reference: FLAGS_check_nan_inf ->
    # eager/nan_inf_utils.cc per-op scan); debugging mode — forces a sync
    from .. import flags as _flags

    if _flags.flag("check_nan_inf"):
        for i, o in enumerate(outs):
            if isinstance(o, jax.core.Tracer):
                continue  # traced value: nothing concrete to scan
            if hasattr(o, "dtype") and jnp.issubdtype(o.dtype, jnp.inexact) \
                    and not bool(jnp.isfinite(o).all()):
                msg = (f"NaN/Inf detected in output {i} of op '{name}' "
                       f"(shape {getattr(o, 'shape', ())})")
                if _flags.flag("check_nan_inf_level") >= 1:
                    import warnings

                    warnings.warn(msg)
                else:
                    raise RuntimeError(msg)

    node = None
    if any_grad:
        saved = arrays
        if _saved_tensors_hooks:
            pack, _ = _saved_tensors_hooks[-1]
            saved = [pack(a) for a in arrays]
        node = GradNode(name, impl, statics, statics_key, saved, metas, len(outs), out_is_seq)
        if _saved_tensors_hooks:
            node._unpack_hook = _saved_tensors_hooks[-1][1]

    wrapped = []
    for i, o in enumerate(outs):
        t = Tensor(o, stop_gradient=not any_grad)
        if node is not None:
            t._grad_node = node
            t._out_idx = i
        wrapped.append(t)

    if _static_capture_hook is not None:
        _static_capture_hook(name, impl, statics, tensor_args, wrapped)

    if out_is_seq:
        return tuple(wrapped)
    return wrapped[0]
