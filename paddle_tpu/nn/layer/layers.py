"""nn.Layer — module base class.

Reference: python/paddle/nn/layer/layers.py (`Layer`): parameter/sublayer
registries, forward hooks, train/eval mode, state_dict round-trip, apply,
to(). TPU note: parameters are eager Tensors; the jit path
(paddle_tpu.jit.to_static) lifts them into a pytree and traces forward as a
pure function over them.
"""
from __future__ import annotations

import collections
from typing import Iterator

import numpy as np
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...core import dtype as dtypes


class Parameter(Tensor):
    """Trainable tensor (reference: EagerParamBase, base/framework.py)."""

    __slots__ = ("optimize_attr", "regularizer", "do_model_average", "need_clip", "is_distributed", "dist_spec", "logical_axes", "sequence_parallel")

    def __init__(self, value, trainable=True, name=None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.do_model_average = None
        self.need_clip = True
        self.is_distributed = False
        self.dist_spec = None  # PartitionSpec set by legacy/auto_parallel
        self.logical_axes = None  # logical axis names set by mp_layers,
        #                           resolved via paddle_tpu.sharding rules
        self.persistable = True

    def __deepcopy__(self, memo):
        p = Parameter(self._value, trainable=self.trainable, name=self.name)
        p.dist_spec = self.dist_spec
        p.logical_axes = self.logical_axes
        p.is_distributed = self.is_distributed
        p.need_clip = self.need_clip
        p.optimize_attr = dict(self.optimize_attr)
        memo[id(self)] = p
        return p


class HookRemoveHelper:
    def __init__(self, hooks, key):
        self._hooks = hooks
        self._key = key

    def remove(self):
        self._hooks.pop(self._key, None)


_hook_id = [0]


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self._parameters = collections.OrderedDict()
        self._sub_layers = collections.OrderedDict()
        self._buffers = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self.training = True
        self._dtype = dtype
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # -- parameter/buffer creation --------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        from ..initializer import Constant, XavierUniform
        from .common import ParamAttr

        dtype = dtype or self._dtype or dtypes.get_default_dtype()
        init = default_initializer
        name = None
        trainable = True
        if isinstance(attr, ParamAttr):
            if attr.initializer is not None:
                init = attr.initializer
            name = attr.name
            trainable = attr.trainable
        elif attr is False:
            return None
        if init is None:
            init = Constant(0.0) if is_bias else XavierUniform()
        shape = [int(s) for s in shape]
        dtype = dtypes.convert_dtype(dtype)
        from ...framework_misc import LazyGuard

        if LazyGuard._active:
            # a lazy binding (core/lazy.py): shape and dtype are there at
            # once, the initializer runs at the first read of the value,
            # and a value written before that takes its place unbuilt
            from ...core.lazy import EngineRef

            p = Parameter(jnp.zeros((), dtype), trainable=trainable,
                          name=name)

            def materialize():
                p._v_ = init._init(shape, dtype)
                return p._v_

            p._v_ = EngineRef(materialize, shape, dtype)
            return p
        value = init._init(shape, dtype)
        p = Parameter(value, trainable=trainable, name=name)
        return p

    def add_parameter(self, name, parameter):
        if parameter is None:
            self._parameters[name] = None
        else:
            self._parameters[name] = parameter
        object.__getattribute__  # keep linters quiet
        return parameter

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    # -- attribute magic -------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call super().__init__() first")
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call super().__init__() first")
            layers[name] = value
            self.__dict__.pop(name, None)
        else:
            if params is not None and name in params:
                if value is None or isinstance(value, Tensor):
                    params[name] = value if value is None else (
                        value if isinstance(value, Parameter) else Parameter(value))
                    return
            if buffers is not None and name in buffers:
                buffers[name] = value
                return
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        # called only when normal lookup fails
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + list(self._sub_layers) + list(self._buffers)

    # -- traversal -------------------------------------------------------
    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        if layers_set is None:
            layers_set = set()
        if include_self and id(self) not in layers_set:
            layers_set.add(id(self))
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None or id(layer) in layers_set:
                continue
            layers_set.add(id(layer))
            p = prefix + ("." if prefix else "") + name
            yield p, layer
            yield from layer.named_sublayers(prefix=p, include_self=False,
                                             layers_set=layers_set)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return iter(self._sub_layers.values())

    def named_children(self):
        return iter(self._sub_layers.items())

    def named_parameters(self, prefix="", include_sublayers=True) -> Iterator:
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield prefix + ("." if prefix else "") + name, p
        if include_sublayers:
            for lname, layer in self.named_sublayers(prefix=prefix):
                for name, p in layer._parameters.items():
                    if p is not None and id(p) not in seen:
                        seen.add(id(p))
                        yield lname + "." + name, p

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for name, b in self._buffers.items():
            if b is not None and id(b) not in seen:
                seen.add(id(b))
                yield prefix + ("." if prefix else "") + name, b
        if include_sublayers:
            for lname, layer in self.named_sublayers(prefix=prefix):
                for name, b in layer._buffers.items():
                    if b is not None and id(b) not in seen:
                        seen.add(id(b))
                        yield lname + "." + name, b

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    # -- mode ------------------------------------------------------------
    def train(self):
        self.training = True
        for layer in self.sublayers():
            layer.training = True
        return self

    def eval(self):
        self.training = False
        for layer in self.sublayers():
            layer.training = False
        return self

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            d = dtypes.convert_dtype(dtype)
            for p in self.parameters():
                if jnp.issubdtype(p._value.dtype, jnp.floating):
                    p._value = p._value.astype(d)
            for b in self.buffers():
                if isinstance(b, Tensor) and jnp.issubdtype(b._value.dtype, jnp.floating):
                    b._value = b._value.astype(d)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def half(self):
        return self.to(dtype="float16")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- hooks -----------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        _hook_id[0] += 1
        self._forward_pre_hooks[_hook_id[0]] = hook
        return HookRemoveHelper(self._forward_pre_hooks, _hook_id[0])

    def register_forward_post_hook(self, hook):
        _hook_id[0] += 1
        self._forward_post_hooks[_hook_id[0]] = hook
        return HookRemoveHelper(self._forward_post_hooks, _hook_id[0])

    # -- call ------------------------------------------------------------
    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            res = hook(self, inputs, outputs)
            if res is not None:
                outputs = res
        return outputs

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    # -- state dict ------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix.rstrip(".")):
            dest[name] = p
        for name, b in self.named_buffers(prefix=structured_name_prefix.rstrip(".")):
            short = name.rsplit(".", 1)[-1]
            # find owning layer to check persistability
            dest[name] = b
        # remove non-persistable buffers
        for lname, layer in list(self.named_sublayers(include_self=True)):
            for bname in layer._non_persistable_buffer_names:
                full = (lname + "." if lname else "") + bname
                dest.pop(full, None)
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        """Load values by structured name; shape-checked (reference:
        Layer.set_state_dict layers.py)."""
        own = self.state_dict()
        missing, unexpected = [], []
        for name, value in state_dict.items():
            if name not in own:
                unexpected.append(name)
                continue
            target = own[name]
            v = value._value if isinstance(value, Tensor) else jnp.asarray(
                np.asarray(value))
            if tuple(target.shape) != tuple(v.shape):
                raise ValueError(
                    f"shape mismatch for {name}: {tuple(target.shape)} vs {tuple(v.shape)}")
            target._value = v.astype(target._value.dtype)
        for name in own:
            if name not in state_dict:
                missing.append(name)
        return missing, unexpected

    load_dict = set_state_dict

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def full_name(self):
        return self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            mod_str = repr(layer)
            mod_str = "\n  ".join(mod_str.split("\n"))
            lines.append(f"({name}): {mod_str}")
        main = self.__class__.__name__ + "("
        if extra:
            main += extra
        if lines:
            main += "\n  " + "\n  ".join(lines) + "\n"
        return main + ")"
