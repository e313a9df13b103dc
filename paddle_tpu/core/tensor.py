"""The eager Tensor.

TPU-native analog of the reference public tensor (`paddle/phi/api/include/tensor.h:82` +
pybind eager Tensor `paddle/fluid/pybind/eager.cc`): a handle over a device buffer
(here a `jax.Array`, i.e. a PJRT buffer) plus autograd metadata
(`fluid/eager/autograd_meta.h:61` — here `_grad_node`/`_out_index`/`_accum_node`).

Most arithmetic/ops methods are monkey-patched onto this class by
``paddle_tpu.ops`` (analog of `python/paddle/base/dygraph/tensor_patch_methods.py`).
"""
from __future__ import annotations

import itertools
from typing import Optional

import numpy as np

from ..framework import dtype as dtype_mod
from ..framework.place import Place
from . import autograd
from .lazy import LazyArray

_name_counter = itertools.count()


class Tensor:
    __slots__ = ("_data", "_stop_gradient", "_grad", "_grad_node", "_out_index",
                 "_accum_node", "_hooks", "name", "persistable", "_dist_meta",
                 "__weakref__", "__dict__")

    def __init__(self, data, stop_gradient: bool = True, name: Optional[str] = None):
        import jax.numpy as jnp

        if isinstance(data, Tensor):
            data = data._data
        elif isinstance(data, (np.ndarray, int, float, bool, list, tuple)):
            data = jnp.asarray(data)
        self._data = data
        if type(data) is LazyArray:
            data._owners.add(self)  # flush swaps in the concrete buffer
        self._stop_gradient = stop_gradient
        self._grad = None
        self._grad_node = None
        self._out_index = 0
        self._accum_node = None
        self._hooks = []
        self._dist_meta = None
        self.name = name or f"tensor_{next(_name_counter)}"
        self.persistable = False

    # -- metadata ----------------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    dim = ndim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def dtype(self) -> dtype_mod.DType:
        return dtype_mod.convert_dtype(np.dtype(self._data.dtype))

    @property
    def place(self) -> Place:
        dev = next(iter(self._data.devices()))
        return Place(dev.platform, dev.id)

    @property
    def stop_gradient(self) -> bool:
        return self._stop_gradient

    @stop_gradient.setter
    def stop_gradient(self, v: bool):
        self._stop_gradient = bool(v)

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    # -- grad --------------------------------------------------------------
    @property
    def grad(self) -> Optional["Tensor"]:
        return self._grad

    @grad.setter
    def grad(self, g):
        self._grad = g if (g is None or isinstance(g, Tensor)) else Tensor(g)

    def backward(self, grad_tensor=None, retain_graph=False):
        autograd.run_backward([self], [grad_tensor], retain_graph=retain_graph)

    def clear_gradient(self, set_to_zero: bool = False):
        if set_to_zero and self._grad is not None:
            import jax.numpy as jnp

            if getattr(self._grad, "is_selected_rows", False):
                # zero grad of a sparse param is dense zeros of the full shape
                self._grad = Tensor(jnp.zeros(tuple(self._grad.shape),
                                              self._grad.dtype),
                                    stop_gradient=True)
            else:
                self._grad = Tensor(jnp.zeros_like(self._grad._data),
                                    stop_gradient=True)
        else:
            self._grad = None

    clear_grad = clear_gradient

    def register_hook(self, hook):
        if type(self._data) is LazyArray and self._data._concrete is None:
            # a hooked intermediate must be a region OUTPUT with a real tape
            # edge (inside a fused region its cotangent never surfaces)
            from . import lazy

            lazy.sync(reason="hook")
        if self._stop_gradient and self._grad_node is None:
            raise RuntimeError("cannot register hook on a tensor that stops gradient")
        self._hooks.append(hook)

        class _Handle:
            def remove(_self):
                try:
                    self._hooks.remove(hook)
                except ValueError:
                    pass

        return _Handle()

    def _ensure_accum_node(self):
        if self._accum_node is None:
            self._accum_node = autograd.AccumulationNode(self)
        return self._accum_node

    # -- conversion --------------------------------------------------------
    def numpy(self) -> np.ndarray:
        return np.asarray(self._data)

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __int__(self):
        return int(self.item())

    def __float__(self):
        return float(self.item())

    def __bool__(self):
        if self.size != 1:
            raise ValueError("The truth value of a multi-element Tensor is ambiguous")
        return bool(self.item())

    def __index__(self):
        return int(self.item())

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-D tensor")
        return self._data.shape[0]

    def __iter__(self):
        # explicit iterator: legacy __getitem__ iteration never terminates
        # because XLA gathers clamp out-of-range indices instead of raising
        if self.ndim == 0:
            raise TypeError("iteration over a 0-D tensor")
        for i in range(self._data.shape[0]):
            yield self[i]

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    # jax pytree/array interop: jnp.asarray(tensor) works via __jax_array__
    def __jax_array__(self):
        return self._data

    def detach(self) -> "Tensor":
        t = Tensor(self._data, stop_gradient=True)
        return t

    def detach_(self):
        self._grad_node = None
        self._stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        from .. import ops

        return ops.assign(self)

    def pin_memory(self):
        return self

    def cpu(self):
        import jax

        t = Tensor(jax.device_get(self._data), stop_gradient=self._stop_gradient)
        return t

    def to(self, *args, **kwargs):
        # supports .to(dtype) / .to(device_str) minimal forms
        from .. import ops

        t = self
        for a in args:
            if isinstance(a, (str, dtype_mod.DType)) and not _looks_like_device(a):
                t = t.astype(a)
        if "dtype" in kwargs:
            t = t.astype(kwargs["dtype"])
        return t

    # filled in by ops patching: astype, cast, reshape, matmul, __add__ ...

    # -- misc --------------------------------------------------------------
    def set_value(self, value):
        import jax.numpy as jnp

        if isinstance(value, Tensor):
            value = value._data
        self._data = jnp.asarray(value, dtype=self._data.dtype).reshape(self._data.shape)
        return self

    def get_tensor(self):
        return self

    def value(self):
        return self

    def _copy_data_from(self, other: "Tensor"):
        self._data = other._data
        if type(self._data) is LazyArray:
            self._data._owners.add(self)

    def __repr__(self):
        grad_info = "" if self._stop_gradient else ", stop_gradient=False"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                f"place={self.place}{grad_info},\n       {self.numpy()})")

    __str__ = __repr__

    def __hash__(self):
        return id(self)


def _looks_like_device(s):
    return isinstance(s, str) and (s.split(":")[0] in ("cpu", "gpu", "tpu", "cuda"))


def _register_tensor_method(name):
    """Decorator used by ops modules to attach methods to Tensor."""

    def deco(fn):
        setattr(Tensor, name, fn)
        return fn

    return deco
