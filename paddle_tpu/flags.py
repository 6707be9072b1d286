"""Global flags registry (reference: ~125 PHI_DEFINE_EXPORTED_* flags in
phi/core/flags.cc surfaced as env FLAGS_* + paddle.set_flags/get_flags,
backed by the gflags clone utils/flags_native.cc).

TPU-native: a typed python registry with FLAGS_<name> env overrides at
first read; XLA's own tuning knobs remain XLA_FLAGS. The reference's
per-flag C++ consumers map to the subsystems reading these at run time.
"""
from __future__ import annotations

import os

from .analysis import locks as _locks

__all__ = ["set_flags", "get_flags", "define_flag", "flag"]

_lock = _locks.new_lock("flags.registry")
_defs: dict = {}     # name -> (type, default, help)
_values: dict = {}   # name -> current value (resolved); read lock-free on
                     # the hot path (CPython dict reads are atomic)


def define_flag(name, default, help="", type=None):
    ftype = type if type is not None else default.__class__
    with _lock:
        _defs[name] = (ftype, default, help)
    return name


def _coerce(ftype, raw):
    if ftype is bool:
        if isinstance(raw, str):
            return raw.lower() in ("1", "true", "yes", "on")
        return bool(raw)
    return ftype(raw)


def flag(name):
    """Current value (env FLAGS_<name> overrides the default once).
    Lock-free after first resolution — safe for per-op dispatch reads."""
    v = _values.get(name, _MISSING)
    if v is not _MISSING:
        return v
    with _lock:
        if name not in _defs:
            raise KeyError(f"unknown flag {name!r}")
        if name in _values:
            return _values[name]
        ftype, default, _ = _defs[name]
        env = os.environ.get(f"FLAGS_{name}")
        val = _coerce(ftype, env) if env is not None else default
        _values[name] = val
        return val


_MISSING = object()


def set_flags(flags_dict):
    """Reference: paddle.set_flags({'FLAGS_x': v} or {'x': v})."""
    with _lock:
        for k, v in flags_dict.items():
            name = k[6:] if k.startswith("FLAGS_") else k
            if name not in _defs:
                raise KeyError(f"unknown flag {name!r}")
            ftype, _, _ = _defs[name]
            _values[name] = _coerce(ftype, v)


def get_flags(names=None):
    """Reference: paddle.get_flags(['FLAGS_x']) -> {'FLAGS_x': v}."""
    if names is None:
        names = list(_defs)
    if isinstance(names, str):
        names = [names]
    out = {}
    for k in names:
        name = k[6:] if k.startswith("FLAGS_") else k
        out[f"FLAGS_{name}"] = flag(name)
    return out


# ---- core flag set (the reference names users actually touch) -------------
define_flag("check_nan_inf", False,
            "scan op outputs for NaN/Inf in eager dispatch")
define_flag("check_nan_inf_level", 0, "0 raise, 1 warn")
define_flag("eager_delete_tensor_gb", 0.0, "kept for parity; XLA owns GC")
define_flag("benchmark", False, "per-step timing logs")
define_flag("allocator_strategy", "auto_growth", "parity; XLA allocates")
define_flag("cudnn_deterministic", False, "parity alias: deterministic ops")
define_flag("embedding_deterministic", 0, "parity")
define_flag("max_inplace_grad_add", 0, "parity")
define_flag("conv_workspace_size_limit", 512, "parity")
define_flag("use_autotune", True,
            "kernel autotune (XLA's backend autotuner; parity switch read "
            "by incubate.autotune.get_config)")
define_flag("layout_autotune", False,
            "run NCHW convs in the TPU-preferred NHWC layout inside jit "
            "(reference: eager_layout_auto_tune.h)")
