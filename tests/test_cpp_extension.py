"""Custom-op extension tests (reference: test/custom_op/ — compile user
ops in-test and check output + gradient parity)."""
import os
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.utils import cpp_extension, register_op

_SRC = """
#include <cmath>

#include "xla/ffi/api/ffi.h"

namespace ffi = xla::ffi;

// y = alpha * x + z  (the classic custom-op demo)
static ffi::Error ScaledAddImpl(ffi::Buffer<ffi::F32> x,
                                ffi::Buffer<ffi::F32> z, float alpha,
                                ffi::ResultBuffer<ffi::F32> y) {
  size_t n = x.element_count();
  for (size_t i = 0; i < n; ++i)
    y->typed_data()[i] = alpha * x.typed_data()[i] + z.typed_data()[i];
  return ffi::Error::Success();
}

XLA_FFI_DEFINE_HANDLER_SYMBOL(
    ScaledAdd, ScaledAddImpl,
    ffi::Ffi::Bind()
        .Arg<ffi::Buffer<ffi::F32>>()
        .Arg<ffi::Buffer<ffi::F32>>()
        .Attr<float>("alpha")
        .Ret<ffi::Buffer<ffi::F32>>());

static ffi::Error MySoftShrinkImpl(ffi::Buffer<ffi::F32> x,
                                   ffi::ResultBuffer<ffi::F32> y) {
  size_t n = x.element_count();
  for (size_t i = 0; i < n; ++i) {
    float v = x.typed_data()[i];
    y->typed_data()[i] = v > 0.5f ? v - 0.5f : (v < -0.5f ? v + 0.5f : 0.f);
  }
  return ffi::Error::Success();
}

XLA_FFI_DEFINE_HANDLER_SYMBOL(
    MySoftShrink, MySoftShrinkImpl,
    ffi::Ffi::Bind().Arg<ffi::Buffer<ffi::F32>>()
        .Ret<ffi::Buffer<ffi::F32>>());
"""


@pytest.fixture(scope="module")
def ext(tmp_path_factory):
    d = tmp_path_factory.mktemp("ops")
    src = d / "my_ops.cc"
    src.write_text(_SRC)
    return cpp_extension.load("my_ops", [src])


def test_cpp_op_executes(ext):
    op = ext.get_op("ScaledAdd")
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    z = np.ones((2, 3), np.float32)
    out = op(paddle.to_tensor(x), paddle.to_tensor(z),
             alpha=np.float32(2.0))
    np.testing.assert_allclose(out.numpy(), 2 * x + 1, rtol=1e-6)


def test_cpp_op_under_jit(ext):
    import jax

    op_raw = ext.get_op("MySoftShrink")
    x = np.linspace(-1, 1, 9).astype(np.float32)

    # the ffi target also composes into larger jitted programs
    def f(v):
        return jax.numpy.sum(
            jax.ffi.ffi_call("my_ops.MySoftShrink",
                           jax.ShapeDtypeStruct(v.shape, v.dtype))(v) ** 2)

    got = jax.jit(f)(x)
    want = np.sum(np.where(np.abs(x) > 0.5,
                           x - np.sign(x) * 0.5, 0.0) ** 2)
    np.testing.assert_allclose(float(got), want, rtol=1e-5)
    out = op_raw(paddle.to_tensor(x))
    assert out.shape == [9]


def test_cpp_op_custom_vjp(ext):
    # gradient of scaled-add supplied as a python vjp over the C op
    def vjp(saved, ct):
        x, z = saved
        return 2.0 * ct, ct  # d/dx (2x+z), d/dz

    op = ext.get_op("ScaledAdd", vjp=vjp)
    x = paddle.to_tensor(np.ones(4, np.float32))
    z = paddle.to_tensor(np.zeros(4, np.float32))
    x.stop_gradient = False
    z.stop_gradient = False
    out = op(x, z, alpha=np.float32(2.0)).sum()
    out.backward()
    np.testing.assert_allclose(x.grad.numpy(), np.full(4, 2.0))
    np.testing.assert_allclose(z.grad.numpy(), np.ones(4))


def test_python_register_op_with_custom_grad():
    import jax.numpy as jnp

    def forward(x, *, beta):
        return jnp.where(x > 0, x * beta, 0.0)

    def backward(saved, ct):
        (x,) = saved
        return (jnp.where(x > 0, ct * 3.0, 0.0),)  # deliberately not beta

    op = register_op("my_relu_scaled", forward, backward)
    x = paddle.to_tensor(np.array([-1.0, 2.0], np.float32))
    x.stop_gradient = False
    y = op(x, beta=2.0)
    np.testing.assert_allclose(y.numpy(), [0.0, 4.0])
    y.sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [0.0, 3.0])
