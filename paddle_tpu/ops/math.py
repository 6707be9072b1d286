"""Elementwise & scalar math ops (reference surface: python/paddle/tensor/math.py,
ops.yaml entries; kernels paddle/phi/kernels/cpu|gpu/activation_*, elementwise_*).
All ops lower to single XLA HLO ops and fuse freely."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ._helpers import apply, wrap, unary_op, binary_op, norm_axis, Tensor

# ---- unary -----------------------------------------------------------------
exp, exp_ = unary_op("exp", jnp.exp)
expm1, expm1_ = unary_op("expm1", jnp.expm1)
log, log_ = unary_op("log", jnp.log)
log2, log2_ = unary_op("log2", jnp.log2)
log10, log10_ = unary_op("log10", jnp.log10)
log1p, log1p_ = unary_op("log1p", jnp.log1p)
sqrt, sqrt_ = unary_op("sqrt", jnp.sqrt)
rsqrt, rsqrt_ = unary_op("rsqrt", jax.lax.rsqrt)
abs, abs_ = unary_op("abs", jnp.abs)
sign, _ = unary_op("sign", jnp.sign)
neg, neg_ = unary_op("neg", jnp.negative)
floor, floor_ = unary_op("floor", jnp.floor)
ceil, ceil_ = unary_op("ceil", jnp.ceil)
round, round_ = unary_op("round", jnp.round)
trunc, trunc_ = unary_op("trunc", jnp.trunc)
frac, frac_ = unary_op("frac", lambda x: x - jnp.trunc(x))
reciprocal, reciprocal_ = unary_op("reciprocal", jnp.reciprocal)
square, square_ = unary_op("square", jnp.square)
sin, sin_ = unary_op("sin", jnp.sin)
cos, cos_ = unary_op("cos", jnp.cos)
tan, tan_ = unary_op("tan", jnp.tan)
asin, asin_ = unary_op("asin", jnp.arcsin)
acos, acos_ = unary_op("acos", jnp.arccos)
atan, atan_ = unary_op("atan", jnp.arctan)
sinh, sinh_ = unary_op("sinh", jnp.sinh)
cosh, cosh_ = unary_op("cosh", jnp.cosh)
tanh, tanh_ = unary_op("tanh", jnp.tanh)
asinh, asinh_ = unary_op("asinh", jnp.arcsinh)
acosh, acosh_ = unary_op("acosh", jnp.arccosh)
atanh, atanh_ = unary_op("atanh", jnp.arctanh)
erf, erf_ = unary_op("erf", jax.lax.erf)
erfinv, erfinv_ = unary_op("erfinv", jax.lax.erf_inv)
sigmoid, sigmoid_ = unary_op("sigmoid", jax.nn.sigmoid)
logit_raw, _ = unary_op("logit", jax.scipy.special.logit)
digamma, digamma_ = unary_op("digamma", jax.scipy.special.digamma)
lgamma, lgamma_ = unary_op("lgamma", jax.scipy.special.gammaln)
gammaln = lgamma
i0, i0_ = unary_op("i0", jax.scipy.special.i0)
i0e, _ = unary_op("i0e", jax.scipy.special.i0e)
i1, _ = unary_op("i1", jax.scipy.special.i1)
i1e, _ = unary_op("i1e", jax.scipy.special.i1e)
deg2rad, _ = unary_op("deg2rad", jnp.deg2rad)
rad2deg, _ = unary_op("rad2deg", jnp.rad2deg)
angle, _ = unary_op("angle", jnp.angle)
conj, _ = unary_op("conj", jnp.conj)
real, _ = unary_op("real", jnp.real)
imag, _ = unary_op("imag", jnp.imag)
nan_to_num_raw, _ = unary_op("nan_to_num", jnp.nan_to_num)

# ---- binary ----------------------------------------------------------------
add = binary_op("add", jnp.add)
subtract = binary_op("subtract", jnp.subtract)
multiply = binary_op("multiply", jnp.multiply)
divide = binary_op("divide", jnp.divide)
floor_divide = binary_op("floor_divide", jnp.floor_divide)
mod = binary_op("mod", jnp.mod)
remainder = mod
floor_mod = mod
fmod = binary_op("fmod", jnp.fmod)
pow_op = binary_op("pow", jnp.power)
maximum = binary_op("maximum", jnp.maximum)
minimum = binary_op("minimum", jnp.minimum)
fmax = binary_op("fmax", jnp.fmax)
fmin = binary_op("fmin", jnp.fmin)
atan2 = binary_op("atan2", jnp.arctan2)
hypot = binary_op("hypot", jnp.hypot)
logaddexp = binary_op("logaddexp", jnp.logaddexp)
heaviside = binary_op("heaviside", jnp.heaviside)
copysign = binary_op("copysign", jnp.copysign)
nextafter = binary_op("nextafter", jnp.nextafter)
ldexp = binary_op("ldexp", lambda x, y: x * (2.0 ** y))
gcd = binary_op("gcd", jnp.gcd)
lcm = binary_op("lcm", jnp.lcm)
inner = binary_op("inner", jnp.inner)
outer = binary_op("outer", lambda x, y: jnp.outer(x, y))
kron = binary_op("kron", jnp.kron)
polygamma_n = binary_op("polygamma", lambda x, n: jax.scipy.special.polygamma(n, x))

scale_alias = None


def pow(x, y, name=None):
    return pow_op(x, y)


def _scale_impl(x, *, scale, bias, bias_after_scale):
    if bias_after_scale:
        return x * scale + bias
    return (x + bias) * scale


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    """Reference: paddle.scale (ops.yaml scale op)."""
    out = apply("scale", _scale_impl, (wrap(x),),
                {"scale": float(scale), "bias": float(bias),
                 "bias_after_scale": bool(bias_after_scale)})
    return out


def _clip_impl(x, *, min, max):
    return jnp.clip(x, min, max)


def clip(x, min=None, max=None, name=None):
    mn = float(min) if min is not None and not isinstance(min, Tensor) else (min._value if isinstance(min, Tensor) else None)
    mx = float(max) if max is not None and not isinstance(max, Tensor) else (max._value if isinstance(max, Tensor) else None)
    if isinstance(mn, (int, float)) or mn is None:
        if isinstance(mx, (int, float)) or mx is None:
            return apply("clip", _clip_impl, (wrap(x),), {"min": mn, "max": mx})
    # tensor bounds path
    return minimum(maximum(x, min if min is not None else -jnp.inf), max if max is not None else jnp.inf)


def clip_(x, min=None, max=None, name=None):
    out = clip(x, min, max)
    x._value, x._grad_node, x._out_idx, x.stop_gradient = out._value, out._grad_node, out._out_idx, out.stop_gradient
    return x


def _lerp_impl(x, y, w):
    return x + w * (y - x)


def lerp(x, y, weight, name=None):
    return apply("lerp", _lerp_impl, (wrap(x), wrap(y), weight))


def _stanh_impl(x, *, scale_a, scale_b):
    return scale_b * jnp.tanh(scale_a * x)


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    return apply("stanh", _stanh_impl, (wrap(x),), {"scale_a": scale_a, "scale_b": scale_b})


def multiplex(inputs, index, name=None):
    stacked = [wrap(t) for t in inputs]
    return apply("multiplex", _multiplex_impl, tuple([wrap(index)] + stacked))


def _multiplex_impl(idx, *xs):
    s = jnp.stack(xs, axis=0)
    idx = idx.reshape(-1)
    return s[idx, jnp.arange(s.shape[1])]


def _logit_impl(x, *, eps):
    if eps is not None:
        x = jnp.clip(x, eps, 1.0 - eps)
    return jax.scipy.special.logit(x)


def logit(x, eps=None, name=None):
    return apply("logit", _logit_impl, (wrap(x),), {"eps": eps})


def _nan_to_num_impl(x, *, nan, posinf, neginf):
    return jnp.nan_to_num(x, nan=nan, posinf=posinf, neginf=neginf)


def nan_to_num(x, nan=0.0, posinf=None, neginf=None, name=None):
    return apply("nan_to_num", _nan_to_num_impl, (wrap(x),),
                 {"nan": nan, "posinf": posinf, "neginf": neginf})


def _addmm_impl(input, x, y, *, beta, alpha):
    return beta * input + alpha * (x @ y)


def addmm(input, x, y, beta=1.0, alpha=1.0, name=None):
    return apply("addmm", _addmm_impl, (wrap(input), wrap(x), wrap(y)),
                 {"beta": float(beta), "alpha": float(alpha)})


def _trace_impl(x, *, offset, axis1, axis2):
    return jnp.trace(x, offset=offset, axis1=axis1, axis2=axis2)


def trace(x, offset=0, axis1=0, axis2=1, name=None):
    return apply("trace", _trace_impl, (wrap(x),),
                 {"offset": offset, "axis1": axis1, "axis2": axis2})


def _diff_impl(x, *, n, axis):
    return jnp.diff(x, n=n, axis=axis)


def diff(x, n=1, axis=-1, prepend=None, append=None, name=None):
    if prepend is not None or append is not None:
        parts = []
        if prepend is not None:
            parts.append(wrap(prepend))
        parts.append(wrap(x))
        if append is not None:
            parts.append(wrap(append))
        from .manipulation import concat
        x = concat(parts, axis=axis)
    return apply("diff", _diff_impl, (wrap(x),), {"n": n, "axis": axis})


def _cumsum_impl(x, *, axis, dtype):
    return jnp.cumsum(x, axis=axis, dtype=dtype)


def cumsum(x, axis=None, dtype=None, name=None):
    from ._helpers import static_dtype
    return apply("cumsum", _cumsum_impl, (wrap(x),),
                 {"axis": axis, "dtype": static_dtype(dtype)})


def _cumprod_impl(x, *, dim, dtype):
    return jnp.cumprod(x, axis=dim, dtype=dtype)


def cumprod(x, dim=None, dtype=None, name=None):
    from ._helpers import static_dtype
    return apply("cumprod", _cumprod_impl, (wrap(x),),
                 {"dim": dim, "dtype": static_dtype(dtype)})


def _cummax_impl(x, *, axis):
    return jax.lax.associative_scan(jnp.maximum, x, axis=axis)


def cummax(x, axis=None, dtype="int64", name=None):
    xx = wrap(x)
    ax = axis if axis is not None else 0
    if axis is None:
        from .manipulation import reshape
        xx = reshape(xx, [-1])
    values = apply("cummax", _cummax_impl, (xx,), {"axis": ax})
    return values, _cummax_indices(xx, ax, jnp.maximum)


def _cummin_impl(x, *, axis):
    return jax.lax.associative_scan(jnp.minimum, x, axis=axis)


def cummin(x, axis=None, dtype="int64", name=None):
    xx = wrap(x)
    ax = axis if axis is not None else 0
    if axis is None:
        from .manipulation import reshape
        xx = reshape(xx, [-1])
    values = apply("cummin", _cummin_impl, (xx,), {"axis": ax})
    return values, _cummax_indices(xx, ax, jnp.minimum)


def _cummax_idx_impl(x, *, axis, is_max):
    op = jnp.maximum if is_max else jnp.minimum
    run = jax.lax.associative_scan(op, x, axis=axis)
    eq = x == run
    idx = jnp.arange(x.shape[axis]).reshape(
        [-1 if i == (axis % x.ndim) else 1 for i in range(x.ndim)]
    )
    idx = jnp.broadcast_to(idx, x.shape)
    masked = jnp.where(eq, idx, -1)
    return jax.lax.associative_scan(jnp.maximum, masked, axis=axis).astype(jnp.int64)


def _cummax_indices(xx, ax, op):
    return apply("cummax_idx", _cummax_idx_impl, (xx,),
                 {"axis": ax, "is_max": op is jnp.maximum})


def _logcumsumexp_impl(x, *, axis):
    return jax.lax.cumlogsumexp(x, axis=axis)


def logcumsumexp(x, axis=None, dtype=None, name=None):
    xx = wrap(x)
    if axis is None:
        from .manipulation import reshape
        xx = reshape(xx, [-1])
        axis = 0
    return apply("logcumsumexp", _logcumsumexp_impl, (xx,), {"axis": axis})


def isfinite(x, name=None):
    return apply("isfinite", jnp.isfinite, (wrap(x),))


def isinf(x, name=None):
    return apply("isinf", jnp.isinf, (wrap(x),))


def isnan(x, name=None):
    return apply("isnan", jnp.isnan, (wrap(x),))


def isneginf(x, name=None):
    return apply("isneginf", jnp.isneginf, (wrap(x),))


def isposinf(x, name=None):
    return apply("isposinf", jnp.isposinf, (wrap(x),))


def isreal(x, name=None):
    return apply("isreal", jnp.isreal, (wrap(x),))


def _increment_impl(x, *, value):
    return x + value


def increment(x, value=1.0, name=None):
    out = apply("increment", _increment_impl, (wrap(x),), {"value": float(value)})
    x._value = out._value
    return x
