"""nn.Layer — the module base class.

Ref parity: python/paddle/fluid/dygraph/layers.py (Layer, __call__ at :880,
state_dict assembly, hook registry). Parameters are Tensors backed by
jax.Array; the functional engine can temporarily swap their values with
tracers to build one compiled XLA program from the same forward code.
"""

from __future__ import annotations

import typing as _t
from collections import OrderedDict

import numpy as np

from ...core import config
from ...core.tensor import Parameter, Tensor
from ...param_attr import ParamAttr


class HookRemoveHelper:
    _next_id = 0

    def __init__(self, hooks):
        self._hooks = hooks
        self._hook_id = HookRemoveHelper._next_id
        HookRemoveHelper._next_id += 1

    def remove(self):
        self._hooks.pop(self._hook_id, None)


_auto_name_counters: dict = {}


def _auto_prefix(layer):
    """Stable per-instance prefix like 'linear_0' (ref fluid unique_name
    generator). Cached on the instance itself (no global id map)."""
    cached = layer.__dict__.get("_auto_prefix_name")
    if cached is None:
        cls = type(layer).__name__.lower()
        n = _auto_name_counters.get(cls, 0)
        _auto_name_counters[cls] = n + 1
        cached = f"{cls}_{n}"
        layer.__dict__["_auto_prefix_name"] = cached
    return cached


class Layer:
    def __init__(self, name_scope=None, dtype=None):
        from ...core.config import get_default_dtype

        self.training = True
        # parameters follow `paddle.set_default_dtype` (float32 unless
        # set), as the reference's LayerHelper does: a model built
        # under bfloat16 draws its weights in bfloat16, once
        self._dtype = dtype or get_default_dtype()
        self._full_name = name_scope or self.__class__.__name__.lower()
        self._parameters: "OrderedDict[str, Parameter]" = OrderedDict()
        self._sub_layers: "OrderedDict[str, Layer]" = OrderedDict()
        self._buffers: "OrderedDict[str, Tensor]" = OrderedDict()
        self._non_persistable_buffer_names: set[str] = set()
        self._forward_pre_hooks: "OrderedDict[int, _t.Callable]" = OrderedDict()
        self._forward_post_hooks: "OrderedDict[int, _t.Callable]" = OrderedDict()

    # -- construction helpers ----------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        from ... import nn

        dtype = dtype or self._dtype
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        init = None
        if attr is not None and attr.initializer is not None:
            init = attr.initializer
        elif default_initializer is not None:
            init = default_initializer
        elif is_bias:
            init = nn.initializer.Constant(0.0)
        else:
            init = nn.initializer.XavierNormal()
        data = init(shape, dtype)
        p = Parameter(data, name=attr.name if attr else None)
        if attr is not None:
            p.optimize_attr["learning_rate"] = attr.learning_rate
            p.regularizer = attr.regularizer
            p.trainable = attr.trainable
            p.stop_gradient = not attr.trainable
            p.need_clip = attr.need_clip
        return p

    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # -- attribute protocol -------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError(
                    "call super().__init__() before assigning parameters")
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            if value.name is None:
                # auto name (ref fluid unique_name): '<class>_<n>.<attr>'
                # — name-based matching (e.g. LARS exclude lists) works
                # without explicit ParamAttr names
                value.name = f"{_auto_prefix(self)}.{name}"
            params[name] = value
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError(
                    "call super().__init__() before assigning sublayers")
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            layers[name] = value
        elif buffers is not None and name in buffers:
            if value is None or isinstance(value, Tensor):
                buffers[name] = value
            else:
                object.__setattr__(self, name, value)
        else:
            if params is not None and name in params:
                if value is None:
                    params[name] = None
                    return
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        extra = []
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d:
                extra.extend(d.keys())
        return list(super().__dir__()) + extra

    # -- traversal ----------------------------------------------------------
    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer in self.named_sublayers(
                prefix=prefix, include_self=True):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (f"{name}.{pname}" if name else pname, p)
            if not include_sublayers:
                break

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer in self.named_sublayers(
                prefix=prefix, include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (f"{name}.{bname}" if name else bname, b)
            if not include_sublayers:
                break

    def children(self):
        return [l for _, l in self.named_children()]

    def named_children(self):
        seen = set()
        for name, layer in self._sub_layers.items():
            if layer is None or id(layer) in seen:
                continue
            seen.add(id(layer))
            yield name, layer

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, layer in self.named_children():
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield from layer.named_sublayers(prefix=sub_prefix,
                                             include_self=True)

    def apply(self, fn):
        for layer in self.children():
            layer.apply(fn)
        fn(self)
        return self

    def full_name(self):
        return self._full_name

    # -- state dict ----------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        out = OrderedDict() if destination is None else destination
        for name, p in self.named_parameters(prefix=structured_name_prefix):
            out[name] = p
        for name, layer in self.named_sublayers(
                prefix=structured_name_prefix, include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or bname in layer._non_persistable_buffer_names:
                    continue
                out[f"{name}.{bname}" if name else bname] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, target in own.items():
            if name not in state_dict:
                missing.append(name)
                continue
            src = state_dict[name]
            arr = src.numpy() if isinstance(src, Tensor) else np.asarray(src)
            if tuple(arr.shape) != tuple(target.shape):
                raise ValueError(
                    f"shape mismatch for {name}: {list(arr.shape)} vs "
                    f"{target.shape}")
            target.set_value(arr)
        for name in state_dict:
            if name not in own:
                unexpected.append(name)
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # -- modes ----------------------------------------------------------------
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

    # -- dtype/device ---------------------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            from ...core.dtype import to_jax_dtype

            jdt = to_jax_dtype(dtype)
            for p in self.parameters():
                p._value = p._value.astype(jdt)
            for b in self.buffers():
                if b is not None and b._value.dtype.kind == "f":
                    b._value = b._value.astype(jdt)
            self._dtype = dtype
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    float = lambda self: self.to(dtype="float32")  # noqa: E731

    # -- hooks ----------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        helper = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[helper._hook_id] = hook
        return helper

    def register_forward_post_hook(self, hook):
        helper = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[helper._hook_id] = hook
        return helper

    # -- call -----------------------------------------------------------------
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

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self.named_children():
            mod_str = repr(layer)
            mod_str = "\n".join(
                "  " + l for l in mod_str.splitlines())
            lines.append(f"  ({name}): {mod_str.strip()}" if "\n" not in
                         mod_str else f"  ({name}): {mod_str.lstrip()}")
        main = f"{type(self).__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"
