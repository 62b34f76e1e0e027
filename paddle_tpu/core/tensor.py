"""The eager Tensor.

TPU-native re-design of the reference's Python-visible eager tensor:
``paddle::Tensor`` (``paddle/phi/api/include/tensor.h``) + the pybind method
surface (``paddle/fluid/pybind/eager_method.cc``) + the Python monkey-patch
layer (``python/paddle/base/dygraph/tensor_patch_methods.py``).

A Tensor wraps a ``jax.Array`` (HBM-resident PJRT buffer on TPU — the
DenseTensor analog) plus autograd metadata (``stop_gradient``, ``grad``,
creator ``GradNode``).  Under ``jax.jit`` tracing ``_data`` is a jax Tracer,
which is what lets the whole eager API be traced into one XLA program by
``paddle_tpu.jit.to_static``.

Most computational methods (``__add__``, ``sum``, ``reshape``...) are
installed by ``paddle_tpu.ops`` at import time — the same monkey-patch
pattern the reference uses (``tensor_patch_methods.py:262``).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import dtype as dtype_mod
from .place import CPUPlace, Place, TPUPlace, _get_current_place


class Tensor:
    __slots__ = ("_data", "stop_gradient", "grad", "_grad_node", "_out_slot",
                 "name", "persistable", "_hooks", "trainable", "_dist_attr",
                 "__weakref__")

    def __init__(self, data, dtype=None, place=None, stop_gradient=True,
                 name=None):
        if isinstance(data, Tensor):
            data = data._data
        if not isinstance(data, jax.Array) and not _is_tracer(data):
            data = jnp.asarray(data, dtype=dtype_mod.convert_dtype(dtype))
        elif dtype is not None and data.dtype != dtype_mod.convert_dtype(dtype):
            data = data.astype(dtype_mod.convert_dtype(dtype))
        if place is not None and isinstance(data, jax.Array):
            data = jax.device_put(data, place.jax_device())
        self._data = data
        self.stop_gradient = stop_gradient
        self.grad = None
        self._grad_node = None
        self._out_slot = 0
        self.name = name or ""
        self.persistable = False
        self.trainable = True
        self._hooks = []
        self._dist_attr = None

    # -- metadata ---------------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def ndim(self):
        return self._data.ndim

    dim = ndim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def place(self) -> Place:
        if _is_tracer(self._data):
            return _get_current_place()
        dev = list(self._data.devices())[0]
        return TPUPlace(dev.id) if dev.platform == "tpu" \
            else CPUPlace(dev.id)

    @property
    def is_leaf(self):
        return self._grad_node is None

    # -- conversion -------------------------------------------------------
    def numpy(self):
        return np.asarray(self._data)

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        arr = self.numpy()
        return arr.astype(dtype) if dtype is not None else arr

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __bool__(self):
        return bool(self.item())

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-D tensor")
        return self._data.shape[0]

    def __index__(self):
        return int(self.item())

    # -- autograd ---------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        from ..autograd import engine

        engine.run_backward([self],
                            [grad_tensor] if grad_tensor is not None else None,
                            retain_graph=retain_graph)

    def clear_grad(self):
        self.grad = None

    clear_gradient = clear_grad

    def register_hook(self, hook):
        self._hooks.append(hook)

        class _Handle:
            def remove(handle_self):
                try:
                    self._hooks.remove(hook)
                except ValueError:
                    pass

        return _Handle()

    def detach(self) -> "Tensor":
        t = Tensor(self._data, stop_gradient=True)
        t.name = self.name
        return t

    def detach_(self):
        self._grad_node = None
        self.stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        # Differentiable copy (reference: assign op).
        from .. import ops

        return ops.assign(self)

    # -- sparse conversions (reference Tensor.to_sparse_coo/csr) ----------

    def to_sparse_coo(self, sparse_dim=None):
        from .. import sparse as _sp

        return _sp.dense_to_coo(self, sparse_dim)

    def to_sparse_csr(self):
        return self.to_sparse_coo().to_sparse_csr()

    # -- device movement --------------------------------------------------
    def to(self, *args, device=None, dtype=None, blocking=None, place=None):
        """Reference signature: Tensor.to(device=None, dtype=None,
        blocking=None) — positional args are classified; bools/None are
        ``blocking`` and never mistaken for a dtype."""
        for a in list(args) + [device]:
            if a is None or isinstance(a, bool):
                continue  # blocking flag or absent
            if isinstance(a, Place):
                place = a
            elif isinstance(a, str) and a.split(":")[0] in (
                    "cpu", "tpu", "gpu", "xpu", "cuda"):
                name, _, idx = a.partition(":")
                idx = int(idx) if idx else 0
                place = CPUPlace(idx) if name == "cpu" else TPUPlace(idx)
            elif dtype is None:
                dtype = a
        data = self._data
        if dtype is not None:
            data = data.astype(dtype_mod.convert_dtype(dtype))
        if place is not None:
            data = jax.device_put(data, place.jax_device())
        t = Tensor(data, stop_gradient=self.stop_gradient)
        return t

    def cpu(self):
        return self.to(CPUPlace(0))

    def cuda(self, device_id=0):
        return self.to(TPUPlace(device_id))

    def tpu(self, device_id=0):
        return self.to(TPUPlace(device_id))

    def pin_memory(self):
        return self

    # -- in-place value update (used by optimizers / load) ----------------
    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._data
        value = jnp.asarray(value, dtype=self.dtype)
        if tuple(value.shape) != tuple(self._data.shape):
            raise ValueError(
                f"set_value shape mismatch: {value.shape} vs {self._data.shape}")
        self._data = value

    def copy_(self, other, blocking=True):
        self.set_value(other)
        return self

    def _clear_data(self):
        self._data = None

    # -- repr -------------------------------------------------------------
    def __repr__(self):
        if _is_tracer(self._data):
            return (f"Tensor(shape={self.shape}, dtype={self.dtype}, "
                    f"<traced>)")
        prefix = "Parameter" if isinstance(self, EagerParamBase) else "Tensor"
        return (f"{prefix}(shape={self.shape}, dtype={self.dtype}, "
                f"place={self.place}, stop_gradient={self.stop_gradient},\n"
                f"       {np.asarray(self._data)})")

    __str__ = __repr__

    # jax pytree interop: Tensors flatten to their data.
    def __jax_array__(self):
        return self._data


class EagerParamBase(Tensor):
    """Trainable parameter (reference: python/paddle/base/framework.py
    EagerParamBase; created by Layer.create_parameter)."""

    # __dict__ slot: parameters accept arbitrary user attributes
    # (is_sequence_parallel, is_firstly_shared, ... — paddle allows this).
    __slots__ = ("optimize_attr", "regularizer", "do_model_average",
                 "need_clip", "is_distributed", "__dict__")

    def __init__(self, data, name=None, trainable=True):
        super().__init__(data, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.do_model_average = None
        self.need_clip = True
        self.is_distributed = False


Parameter = EagerParamBase


def _is_tracer(x):
    return isinstance(x, jax.core.Tracer)


def to_tensor(data, dtype=None, place=None, stop_gradient=True) -> Tensor:
    """paddle.to_tensor (reference: python/paddle/tensor/creation.py:673)."""
    if isinstance(data, Tensor):
        t = Tensor(data._data, dtype=dtype, place=place,
                   stop_gradient=stop_gradient)
        return t
    if dtype is None and not isinstance(data, (jax.Array, np.ndarray)):
        # Match paddle: python floats default to the default dtype.
        probe = np.asarray(data)
        if probe.dtype == np.float64:
            dtype = dtype_mod.get_default_dtype()
        elif probe.dtype == np.int64:
            dtype = dtype_mod.int64
    if isinstance(data, np.ndarray) and data.dtype == np.float64 \
            and dtype is None:
        dtype = dtype_mod.get_default_dtype()
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)
