"""C++ custom-op extension over the XLA FFI.

Reference analog: paddle.utils.cpp_extension (cpp_extension/extension_utils
+ PD_BUILD_OP, phi/api/ext/op_meta_info.h) — user C++/CUDA ops compiled
in-process and dispatched like built-ins.

TPU-native split: device kernels belong to Pallas (python-defined, Mosaic-
compiled — see paddle_tpu.ops.pallas); this module covers the NATIVE HOST
op path: C++ handlers written against jaxlib's bundled XLA FFI headers
(xla/ffi/api/ffi.h), compiled with the system toolchain, registered as FFI
targets, and exposed as framework ops that work under jit and on the eager
tape. On TPU programs these run as host callbacks; on the CPU platform
they are first-class custom calls.
"""
from __future__ import annotations

import ctypes
import os

import jax
import numpy as np

from ..core.dispatch import apply
from ..native import build_sources

__all__ = ["include_paths", "load", "CppExtensionModule"]


def include_paths():
    """Include dirs for building FFI handlers (reference:
    cpp_extension.include_paths)."""
    import jaxlib

    return [os.path.join(os.path.dirname(jaxlib.__file__), "include")]


def _ffi_flags():
    return [f"-I{p}" for p in include_paths()]


class CppExtensionModule:
    """Loaded extension: `get_op` builds python wrappers per exported
    FFI handler symbol."""

    def __init__(self, name, lib):
        self.name = name
        self._lib = lib
        self._ops = {}
        self._registered = set()

    def get_op(self, symbol, out_like=0, out_shape_fn=None, platform="cpu",
               vjp=None):
        """Wrap exported handler `symbol` as a framework op.

        out_like: input index whose shape/dtype the output mirrors, or use
        out_shape_fn(*avals) -> jax.ShapeDtypeStruct. vjp: optional
        (saved_inputs, cotangent) -> input cotangents for custom gradients.
        """
        key = (symbol, out_like, out_shape_fn, platform, vjp)
        if key in self._ops:
            return self._ops[key]
        target = f"{self.name}.{symbol}"
        if target not in self._registered:
            fn_ptr = getattr(self._lib, symbol)
            jax.ffi.register_ffi_target(
                target, jax.ffi.pycapsule(fn_ptr), platform=platform)
            self._registered.add(target)

        def impl(*arrays, **attrs):
            if out_shape_fn is not None:
                out = out_shape_fn(*arrays)
            else:
                ref = arrays[out_like]
                out = jax.ShapeDtypeStruct(ref.shape, ref.dtype)
            return jax.ffi.ffi_call(target, out)(*arrays, **attrs)

        if vjp is not None:
            from .custom_op import wrap_custom_vjp

            impl = wrap_custom_vjp(impl, vjp)

        def op(*tensors, **attrs):
            return apply(f"{self.name}.{symbol}", impl, tensors,
                         attrs or None)

        op.__name__ = symbol
        self._ops[key] = op
        return op


def load(name, sources, extra_cflags=(), build_directory=None,
         verbose=False):
    """Compile `sources` (C++ using xla/ffi/api/ffi.h) into a shared lib
    and return a CppExtensionModule (reference: cpp_extension.load JIT
    path)."""
    lib = build_sources(name, [os.fspath(s) for s in sources],
                        tuple(extra_cflags) + tuple(_ffi_flags()),
                        build_dir=build_directory)
    return CppExtensionModule(name, lib)
