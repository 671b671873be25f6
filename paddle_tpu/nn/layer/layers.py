"""Layer: the module base class.

Reference: python/paddle/nn/layer/layers.py:351 `class Layer` — parameter /
buffer / sublayer registries, hooks, state_dict, train/eval. The TPU-native
Layer keeps the exact user contract; parameters hold `jax.Array`s and the
whole tree can be flattened to a pytree for jit/pjit (`raw_state` /
`load_raw_state`), which is the functional bridge the distributed trainer
uses.
"""
from __future__ import annotations

import collections
import functools
import math
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core.tensor import Parameter, Tensor, unwrap
from ...framework import dtype as dtypes
from ...framework import random as _random


_param_auto_counter = 0


class HookRemoveHelper:
    def __init__(self, hooks, hook_id):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


# parameters at or above this many elements are initialised by ONE jitted
# program (see _initialize)
_JIT_INIT_ELEMS = 1 << 24


@functools.lru_cache(maxsize=64)
def _jitted_init(init_type, init_attrs, shape, dtype):
    init = init_type.__new__(init_type)
    init.__dict__.update(init_attrs)

    def run(key):
        with _random.rng_scope(key):
            return init(shape, dtype)

    return jax.jit(run)


def _initialize(init, shape, dtype):
    """Run an initializer. Small parameters run it eagerly, as ever. A
    LARGE one runs it as one jitted program fed a fresh key: eagerly,
    `std * normal(f32)` then `.astype(bf16)` holds two f32 copies of the
    weight next to the result — 5 GB for a 128k-vocab head, on top of
    whatever is already resident (PR 22: llama3-8B widths on one 16 GB
    v5e). Fused, only the result is ever materialised."""
    if math.prod(shape) < _JIT_INIT_ELEMS:
        return init(shape, dtype)
    try:
        fn = _jitted_init(type(init), tuple(sorted(vars(init).items())),
                          shape, dtype)
    except TypeError:       # an attribute that does not hash (an array)
        return init(shape, dtype)
    return fn(_random.next_key())


class Layer:
    """Base class for all neural network layers (paddle.nn.Layer)."""

    def __init__(self, name_scope=None, dtype=None):
        object.__setattr__(self, "_parameters", collections.OrderedDict())
        object.__setattr__(self, "_sub_layers", collections.OrderedDict())
        object.__setattr__(self, "_buffers", collections.OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names_set", set())
        self.training = True
        # None = the default dtype in force (float32 unless
        # set_default_dtype / dtypes.default_dtype says otherwise)
        self._dtype = dtypes.convert_dtype(dtype) \
            or dtypes.get_default_dtype()
        self._name_scope = name_scope or type(self).__name__.lower()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._hook_id = 0
        self._casted_by_pure_fp16 = False

    # ------------------------------------------------------------------
    # attribute magic (reference Layer.__setattr__)
    # ------------------------------------------------------------------
    def __setattr__(self, name: str, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            layers[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Tensor) and buffers is not None and name in buffers:
            buffers[name] = value
        else:
            if params is not None:
                params.pop(name, None)
            if layers is not None:
                layers.pop(name, None)
            object.__setattr__(self, name, value)

    def __getattr__(self, name: str):
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

    # ------------------------------------------------------------------
    # forward plumbing
    # ------------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            o = hook(self, inputs, outputs)
            if o is not None:
                outputs = o
        return outputs

    def register_forward_pre_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # ------------------------------------------------------------------
    # parameter / buffer management
    # ------------------------------------------------------------------
    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias=False,
        default_initializer=None,
    ) -> Parameter:
        """Reference: Layer.create_parameter (layers.py) — honours ParamAttr
        (initializer, trainable, name) or a default initializer."""
        from ..initializer import Constant, XavierNormal, _resolve_param_attr

        dtype = dtypes.convert_dtype(dtype) or self._dtype
        attr = _resolve_param_attr(attr)
        init = None
        trainable = True
        name = None
        lr = 1.0
        if attr is not None:
            init = attr.initializer
            trainable = attr.trainable
            name = attr.name
            lr = attr.learning_rate
        if init is None:
            init = default_initializer or (Constant(0.0) if is_bias else XavierNormal())
        arr = _initialize(init, tuple(int(s) for s in shape), dtype)
        if name is None:
            # reference Parameters always carry an auto-generated unique
            # name ("linear_0.w_0", LayerHelper naming) assigned at
            # CREATION — caller-independent, so name-keyed configs
            # (apply_decay_param_fun, no-clip lists) bind identically in
            # the eager and fused optimizer paths
            global _param_auto_counter
            name = (f"{type(self).__name__.lower()}_{_param_auto_counter}"
                    f".{'b' if is_bias else 'w'}_0")
            _param_auto_counter += 1
        p = Parameter(arr, dtype=dtype, name=name, trainable=trainable)
        p.optimize_attr["learning_rate"] = lr
        return p

    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is None:
            self._parameters[name] = None
        else:
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable: bool = True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names_set.add(name)
        return tensor

    # ------------------------------------------------------------------
    # traversal
    # ------------------------------------------------------------------
    def named_sublayers(self, prefix="", include_self=False, layers_set=None) -> Iterator[Tuple[str, "Layer"]]:
        if layers_set is None:
            layers_set = set()
        if include_self and id(self) not in layers_set:
            layers_set.add(id(self))
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = prefix + ("." if prefix else "") + name
            yield from layer.named_sublayers(prefix=sub_prefix, include_self=True, layers_set=layers_set)

    def sublayers(self, include_self=False) -> List["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self) -> Iterator[Tuple[str, "Layer"]]:
        seen = set()
        for name, l in self._sub_layers.items():
            if l is not None and id(l) not in seen:
                seen.add(id(l))
                yield name, l

    def named_parameters(self, prefix="", include_sublayers=True) -> Iterator[Tuple[str, Parameter]]:
        params_set = set()
        layers = (
            self.named_sublayers(prefix=prefix, include_self=True)
            if include_sublayers
            else [(prefix, self)]
        )
        for layer_prefix, layer in layers:
            for name, p in layer._parameters.items():
                if p is None or id(p) in params_set:
                    continue
                params_set.add(id(p))
                yield layer_prefix + ("." if layer_prefix else "") + name, p

    def parameters(self, include_sublayers=True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True) -> Iterator[Tuple[str, Tensor]]:
        buf_set = set()
        layers = (
            self.named_sublayers(prefix=prefix, include_self=True)
            if include_sublayers
            else [(prefix, self)]
        )
        for layer_prefix, layer in layers:
            for name, b in layer._buffers.items():
                if b is None or id(b) in buf_set:
                    continue
                buf_set.add(id(b))
                yield layer_prefix + ("." if layer_prefix else "") + name, b

    def buffers(self, include_sublayers=True) -> List[Tensor]:
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def apply(self, fn: Callable[["Layer"], None]) -> "Layer":
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    # ------------------------------------------------------------------
    # modes
    # ------------------------------------------------------------------
    def train(self):
        self.training = True
        for l in self.sublayers():
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers():
            l.training = False
        return self

    # ------------------------------------------------------------------
    # state dict
    # ------------------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True, structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(include_sublayers=include_sublayers):
            dest[structured_name_prefix + name] = p
        for name, b in self.named_buffers(include_sublayers=include_sublayers):
            leaf = name.rsplit(".", 1)[-1]
            # skip non-persistable buffers of any sublayer
            owner = self
            if "." in name:
                for part in name.split(".")[:-1]:
                    owner = owner._sub_layers[part]
            if leaf in owner._non_persistable_buffer_names_set:
                continue
            dest[structured_name_prefix + name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        """Reference: Layer.set_state_dict (layers.py). Matches by structured
        name; shape-checks each entry."""
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k not in own:
                unexpected.append(k)
                continue
            tgt = own[k]
            arr = unwrap(v) if isinstance(v, Tensor) else jnp.asarray(v)
            if tuple(arr.shape) != tuple(tgt.shape):
                raise ValueError(
                    f"shape mismatch for {k}: checkpoint {tuple(arr.shape)} vs model {tuple(tgt.shape)}"
                )
            tgt._replace(arr.astype(tgt._array.dtype))
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    load_dict = set_state_dict

    # ------------------------------------------------------------------
    # functional bridge (TPU-native addition)
    # ------------------------------------------------------------------
    def raw_state(self) -> Dict[str, jax.Array]:
        """Flatten params+buffers to a dict of jax arrays (a pytree) for
        jit/pjit functional training."""
        out = {}
        for k, p in self.named_parameters():
            out[k] = p._array
        for k, b in self.named_buffers():
            out.setdefault(k, b._array)
        return out

    def load_raw_state(self, state: Dict[str, jax.Array]):
        for k, p in self.named_parameters():
            if k in state:
                p._array = state[k]
        for k, b in self.named_buffers():
            if k in state:
                b._array = state[k]
        return self

    def func_call(self, state: Dict[str, jax.Array], *args, training=None, **kwargs):
        """Run forward as a pure function of `state` (used under jit/pjit).

        Temporarily binds `state` into the parameter objects; safe under
        tracing because binding is per-call and restored in `finally`.
        """
        named_p = dict(self.named_parameters())
        named_b = dict(self.named_buffers())
        saved = {k: v._array for k, v in {**named_p, **named_b}.items()}
        prev_training = self.training
        try:
            if training is not None:
                self.train() if training else self.eval()
            for k, v in state.items():
                if k in named_p:
                    named_p[k]._array = v
                elif k in named_b:
                    named_b[k]._array = v
            return self(*args, **kwargs)
        finally:
            for k, t in {**named_p, **named_b}.items():
                t._array = saved[k]
            self.training = prev_training
            if training is not None:
                self.train() if prev_training else self.eval()

    # ------------------------------------------------------------------
    # dtype / device movement
    # ------------------------------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            d = dtypes.convert_dtype(dtype)
            for p in self.parameters():
                p._array = p._array.astype(d)
            for b in self.buffers():
                if jnp.issubdtype(b._array.dtype, jnp.floating):
                    b._array = b._array.astype(d)
            for l in self.sublayers(include_self=True):
                l._dtype = d
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def half(self):
        return self.to(dtype="float16")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def full_name(self):
        return self._name_scope

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [extra] if extra else []
        for name, layer in self._sub_layers.items():
            rep = repr(layer).split("\n")
            head = f"({name}): {rep[0]}"
            lines.append(head)
            lines.extend("  " + r for r in rep[1:])
        body = "\n  ".join(lines)
        return f"{type(self).__name__}({body})" if lines else f"{type(self).__name__}()"
