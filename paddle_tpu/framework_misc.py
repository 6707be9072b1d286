"""Top-level misc parity surface (reference: the odds and ends exported
from python/paddle/__init__.py — dtype info, grad-mode contexts, reader
batching, RNG-state shims, places)."""
from __future__ import annotations

import contextlib

import numpy as np
import jax
import jax.numpy as jnp

import ml_dtypes

from .core import dispatch as _dispatch
from .core import dtype as _dtype_mod

__all__ = ["enable_grad", "finfo", "iinfo", "batch", "reverse",
           "disable_signal_handler", "get_cuda_rng_state",
           "set_cuda_rng_state", "check_shape", "LazyGuard",
           "CUDAPinnedPlace", "dtype"]

dtype = _dtype_mod.DType if hasattr(_dtype_mod, "DType") else str


@contextlib.contextmanager
def enable_grad():
    """Re-enable the tape inside a no_grad region (reference:
    paddle.enable_grad)."""
    prev = _dispatch.is_grad_enabled()
    _dispatch.set_grad_enabled(True)
    try:
        yield
    finally:
        _dispatch.set_grad_enabled(prev)


class _FInfo:
    def __init__(self, np_info, dt):
        self.dtype = str(dt)
        self.bits = np_info.bits
        self.eps = float(np_info.eps)
        self.min = float(np_info.min)
        self.max = float(np_info.max)
        self.tiny = float(getattr(np_info, "tiny",
                                  getattr(np_info, "smallest_normal", 0)))
        self.smallest_normal = self.tiny
        self.resolution = float(getattr(np_info, "resolution", self.eps))


class _IInfo:
    def __init__(self, np_info, dt):
        self.dtype = str(dt)
        self.bits = np_info.bits
        self.min = int(np_info.min)
        self.max = int(np_info.max)


def finfo(dt):
    """Float dtype limits (reference: paddle.finfo) incl. bfloat16 via
    ml_dtypes."""
    d = _dtype_mod.convert_dtype(dt)
    return _FInfo(ml_dtypes.finfo(str(d)) if "bfloat" in str(d)
                  else np.finfo(str(d)), d)


def iinfo(dt):
    d = _dtype_mod.convert_dtype(dt)
    return _IInfo(np.iinfo(str(d)), d)


def batch(reader, batch_size, drop_last=False):
    """Wrap an item-reader into a batch-reader (reference: paddle.batch,
    the classic fluid reader decorator)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched


def reverse(x, axis, name=None):
    """Alias of flip (reference: paddle.reverse -> flip)."""
    from .ops.manipulation import flip
    return flip(x, axis)


def disable_signal_handler():
    """Reference: paddle.disable_signal_handler — the C++ runtime installs
    crash handlers there; this runtime installs none, so this is the
    documented no-op equivalent."""


def get_cuda_rng_state():
    """CUDA generator state surface (reference: paddle.get_cuda_rng_state).
    The TPU/jax runtime keys RNG from paddle.seed's threaded PRNG keys;
    returns that key list so set_cuda_rng_state can restore it."""
    from .ops import random as rnd
    return [np.asarray(rnd.get_state())] \
        if hasattr(rnd, "get_state") else []


def set_cuda_rng_state(state):
    from .ops import random as rnd
    if state and hasattr(rnd, "set_state"):
        rnd.set_state(jnp.asarray(state[0]))


def check_shape(shape):
    """Validate a shape argument (reference: utils layer_utils
    check_shape surfaced at top level)."""
    if isinstance(shape, (list, tuple)):
        for s in shape:
            if not isinstance(s, int) and s is not None:
                raise TypeError(f"shape entries must be int, got {s!r}")
    return shape


class LazyGuard:
    """Reference: paddle.LazyGuard — delays parameter materialization for
    giant models. A parameter that `Layer.create_parameter` makes inside
    the guard knows its shape and dtype and holds no array: its
    initializer runs at the first read of its value, and a value assigned
    before that (a checkpoint's, a benchmark's seeded weights) takes its
    place without the initial one ever being built. A model whose float32
    initial values would not fit the device beside its real weights is
    constructed this way."""

    _active = False

    def __enter__(self):
        LazyGuard._active = True
        return self

    def __exit__(self, *exc):
        LazyGuard._active = False
        return False


class CUDAPinnedPlace:
    """Reference: paddle.CUDAPinnedPlace. The jax analog of pinned host
    staging memory is the pinned_host memory kind (used by the PS host
    tier and offloaded sharding)."""

    def __repr__(self):
        return "Place(cuda_pinned) [pinned_host memory kind]"
