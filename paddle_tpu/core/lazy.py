"""Lazy op segments: compiled subgraphs between graph breaks.

Reference analog: SOT's partial-graph compilation — the reference's
opcode translator executes *compiled subgraphs between graph breaks* and
resumes tracing after them
(python/paddle/jit/sot/opcode_translator/executor/opcode_executor.py:1473,
break classes jit/sot/utils/exceptions.py:38). Our to_static traces
whole functions; when a function contains an unconvertible construct the
round-3 contract dropped the WHOLE call to per-op eager execution.

TPU-native v2 (this module): in fallback mode, `dispatch.apply` defers
ops into a *segment* instead of executing them. The segment flushes — as
ONE composite op through the normal `apply` path (so it gets the per-op
jit cache, the tape GradNode, and a compiled VJP for free) — exactly when
a real value is demanded: `float(x)`, `.numpy()`, tensor-dependent python
control flow, or any library code touching `._value`. Everything between
two such break points therefore runs as one XLA-compiled subgraph, and
the breaking construct itself runs on real values, after which recording
resumes. This is the define-by-run equivalent of the reference's
"compile the pieces around the break" contract, with the break points
discovered dynamically instead of from bytecode.

Monitor counters (utils/monitor): `lazy_segment_ops` (ops that were
deferred), `lazy_segment_flushes` (compiled-subgraph executions),
`lazy_segment_fallback_ops` (ops a segment could not defer — executed
eagerly after a flush).
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from . import monitor

__all__ = ["lazy_segments", "lazy_recorder", "PendingValue", "EngineRef"]


class EngineRef:
    """Lazy binding of a Tensor to externally-managed device state.

    The distributed engine donates its parameter buffers every step, so a
    live Parameter's current value is whatever the engine's state dict
    holds *now*. Instead of rewriting every Parameter's `_value` after
    each step (a Python loop of property-setter work on the hot path),
    the engine installs one EngineRef per Parameter at construction:
    `_value` reads resolve through `fetch()` against the live engine
    state, and shape/dtype queries stay host-only. Writes through the
    `_value` setter simply replace the ref; the engine detects that
    (identity check) and adopts the external value on its next step.
    """

    __slots__ = ("fetch", "shape", "dtype")

    def __init__(self, fetch, shape, dtype):
        self.fetch = fetch
        self.shape = tuple(shape)
        self.dtype = dtype

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def size(self):
        n = 1
        for d in self.shape:
            n *= int(d)
        return n


class PendingValue:
    """Placeholder stored in Tensor._v_ while the producing segment has
    not flushed. Carries the aval so shape/dtype queries stay lazy."""

    __slots__ = ("aval", "recorder", "slot")

    def __init__(self, aval, recorder, slot):
        self.aval = aval
        self.recorder = recorder
        self.slot = slot

    @property
    def shape(self):
        return self.aval.shape

    @property
    def dtype(self):
        return self.aval.dtype

    @property
    def ndim(self):
        return len(self.aval.shape)

    @property
    def size(self):
        import numpy as np

        return int(np.prod(self.aval.shape)) if self.aval.shape else 1


# (impl, statics_items, input aval signature) -> output avals. eval_shape
# re-traces the impl through abstract interpretation every call (~100us+);
# recorded programs repeat identically every step, so memoize.
_EVAL_CACHE: dict = {}


def _segment_impl(*arrays, prog=()):
    """Replay a recorded program. arrays = the segment's external inputs;
    prog rows are (impl, statics_items, in_slots, n_outs) with slots
    ('x', i) = external input i, ('p', j) = pending value j. Returns the
    tuple of ALL pending values (any of them may be consumed later)."""
    pend = []
    for impl, st_items, in_slots, n_outs in prog:
        args = [arrays[i] if kind == "x" else pend[i]
                for kind, i in in_slots]
        out = impl(*args, **dict(st_items))
        if isinstance(out, (tuple, list)):
            pend.extend(out)
        else:
            pend.append(out)
    return tuple(pend)


class SegmentRecorder:
    def __init__(self):
        self.records = []       # (impl, statics_items, in_slots, n_outs)
        self.inputs = []        # external operands (Tensor or raw)
        self._input_ids = {}    # id(obj) -> input slot
        self.pending = []       # Tensor objects awaiting values
        self.flushing = False
        self.had_grad = False   # any recorded op needed gradients

    # -- recording ---------------------------------------------------------

    def maybe_record(self, name, impl, tensor_args, statics):
        """Try to defer this op. Returns the pending output Tensor(s), or
        NotImplemented if the op must run eagerly (after a flush)."""
        from .tensor import Tensor

        statics = statics or {}
        in_slots = []
        metas = []        # (shape, dtype) | raw scalar — for sig + avals
        for t in tensor_args:
            if isinstance(t, Tensor):
                v = t._v_
                if type(v) is PendingValue:
                    if v.recorder is not self:
                        return NotImplemented  # foreign segment: bail
                    in_slots.append(("p", v.slot))
                    metas.append((v.aval.shape, v.aval.dtype))
                    continue
                in_slots.append(("x", self._ext_slot(t)))
                metas.append((v.shape, v.dtype))
            else:
                in_slots.append(("x", self._ext_slot(t)))
                metas.append(t)
        try:
            st_items = tuple(sorted(statics.items())) if statics else ()
            ck = (impl, st_items, tuple(
                m if type(m) is tuple else (type(m), m) for m in metas))
            out_aval = _EVAL_CACHE.get(ck)
            if out_aval is None:
                aval_args = [
                    jax.ShapeDtypeStruct(*m) if type(m) is tuple else m
                    for m in metas]
                out_aval = jax.eval_shape(
                    lambda *a: impl(*a, **statics), *aval_args)
                _EVAL_CACHE[ck] = out_aval
        except Exception:  # tpu-lint: disable=TL007 — deliberate probe:
            # ANY trace failure (shape-/value-dependent impl, unhashable
            # statics, non-hashable scalar arg) just means this op is a
            # break point — the caller flushes and runs it eagerly
            return NotImplemented

        out_is_seq = isinstance(out_aval, (tuple, list))
        out_avals = list(out_aval) if out_is_seq else [out_aval]
        base = len(self.pending)
        self.records.append((impl, st_items, tuple(in_slots),
                             len(out_avals)))
        from .dispatch import is_grad_enabled

        any_grad = is_grad_enabled() and any(
            isinstance(t, Tensor) and not t.stop_gradient
            for t in tensor_args)
        if any_grad:
            self.had_grad = True
        outs = []
        for i, av in enumerate(out_avals):
            t = Tensor.__new__(Tensor)
            t._v_ = PendingValue(av, self, base + i)
            t.stop_gradient = not any_grad
            t.grad = None
            t._grad_node = None
            t._out_idx = 0
            t.name = None
            t.persistable = False
            t._hooks = None
            t.trainable = True
            self.pending.append(t)
            outs.append(t)
        monitor.increment("lazy_segment_ops")
        return tuple(outs) if out_is_seq else outs[0]

    def _ext_slot(self, obj):
        slot = self._input_ids.get(id(obj))
        if slot is None:
            slot = len(self.inputs)
            self._input_ids[id(obj)] = slot
            self.inputs.append(obj)
        return slot

    # -- flushing ----------------------------------------------------------

    def flush(self):
        """Execute all recorded ops as one compiled composite op and fill
        the pending tensors (tape-wired through the normal apply path)."""
        if not self.records or self.flushing:
            return
        from .dispatch import apply

        records = self.records
        inputs = self.inputs
        pending = self.pending
        self.records, self.inputs, self.pending = [], [], []
        self._input_ids = {}
        prog = tuple(records)
        had_grad = self.had_grad
        self.had_grad = False
        self.flushing = True
        from .dispatch import set_grad_enabled, is_grad_enabled

        prev_grad = is_grad_enabled()
        try:
            if had_grad and not prev_grad:
                # a value read under no_grad() (logging, metrics) must not
                # silently drop the gradients of ops recorded WITH grad
                set_grad_enabled(True)
            outs = apply("lazy_segment", _segment_impl, inputs,
                         {"prog": prog})
        finally:
            set_grad_enabled(prev_grad)
            self.flushing = False
        outs = outs if isinstance(outs, tuple) else (outs,)
        for t, o in zip(pending, outs):
            v = t._v_
            if not (type(v) is PendingValue and v.recorder is self):
                continue  # rebound by the user since recording: keep theirs
            t._v_ = o._v_
            t._grad_node = o._grad_node
            t._out_idx = o._out_idx
            t.stop_gradient = o.stop_gradient
        monitor.increment("lazy_segment_flushes")


class _State(threading.local):
    def __init__(self):
        self.stack = []


_state = _State()


def lazy_recorder():
    """The active recorder for this thread, or None."""
    return _state.stack[-1] if _state.stack else None


class lazy_segments:
    """Context manager enabling segment recording on this thread."""

    def __enter__(self):
        self._rec = SegmentRecorder()
        _state.stack.append(self._rec)
        return self._rec

    def __exit__(self, exc_type, exc, tb):
        rec = _state.stack.pop()
        if exc_type is None:
            rec.flush()
        return False
