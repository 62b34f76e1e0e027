"""Dtype system.

TPU-native re-design of the reference's ``phi::DataType`` enum
(``/root/reference/paddle/phi/common/data_type.h``) and the Python-level
dtype surface (``python/paddle/framework/dtype.py``).  We alias paddle-style
dtype names onto ``jax.numpy`` dtypes so everything interops with XLA with
zero conversion cost, and keep the reference's type-promotion semantics
(``paddle/phi/common/type_promotion.h``) via jax's numpy-compatible rules.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Paddle semantics require true int64 (labels, indices). jax truncates to
# int32 unless x64 is on; float defaults remain float32 because every
# creation path in this framework passes an explicit dtype.
jax.config.update("jax_enable_x64", True)

# Canonical dtype objects (numpy dtype instances — what jax uses natively).
bool_ = jnp.dtype("bool")
uint8 = jnp.dtype("uint8")
int8 = jnp.dtype("int8")
int16 = jnp.dtype("int16")
int32 = jnp.dtype("int32")
int64 = jnp.dtype("int64")
float16 = jnp.dtype("float16")
bfloat16 = jnp.dtype(jnp.bfloat16)
float32 = jnp.dtype("float32")
float64 = jnp.dtype("float64")
complex64 = jnp.dtype("complex64")
complex128 = jnp.dtype("complex128")
float8_e4m3fn = jnp.dtype(jnp.float8_e4m3fn)
float8_e5m2 = jnp.dtype(jnp.float8_e5m2)

_STR_TO_DTYPE = {
    "bool": bool_,
    "uint8": uint8,
    "int8": int8,
    "int16": int16,
    "int32": int32,
    "int64": int64,
    "float16": float16,
    "fp16": float16,
    "bfloat16": bfloat16,
    "bf16": bfloat16,
    "float32": float32,
    "fp32": float32,
    "float64": float64,
    "fp64": float64,
    "complex64": complex64,
    "complex128": complex128,
    "float8_e4m3fn": float8_e4m3fn,
    "float8_e5m2": float8_e5m2,
}

_FLOATING = {float16, bfloat16, float32, float64, float8_e4m3fn, float8_e5m2}
_INTEGER = {uint8, int8, int16, int32, int64}
_COMPLEX = {complex64, complex128}


def convert_dtype(dtype):
    """Normalize any dtype spec (str / np / jnp / Tensor dtype) to a numpy dtype."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        try:
            return _STR_TO_DTYPE[dtype]
        except KeyError:
            raise TypeError(f"Unsupported dtype string: {dtype!r}")
    if isinstance(dtype, np.dtype):
        return dtype
    # python builtins / numpy scalar types / jnp types
    try:
        return jnp.dtype(dtype)
    except TypeError:
        raise TypeError(f"Cannot convert {dtype!r} to a dtype")


def dtype_name(dtype) -> str:
    d = convert_dtype(dtype)
    return str(d)


def is_floating_point(dtype) -> bool:
    return convert_dtype(dtype) in _FLOATING


def is_integer(dtype) -> bool:
    d = convert_dtype(dtype)
    return d in _INTEGER or d == bool_


def is_complex(dtype) -> bool:
    return convert_dtype(dtype) in _COMPLEX


# Default dtype handling (reference: paddle.set_default_dtype,
# python/paddle/framework/framework.py:36).
_default_dtype = float32


def set_default_dtype(d):
    global _default_dtype
    d = convert_dtype(d)
    if d not in (float16, bfloat16, float32, float64):
        raise TypeError(
            f"set_default_dtype only supports floating dtypes, got {d}")
    _default_dtype = d


def get_default_dtype():
    return _default_dtype


# paddle.dtype — the dtype TYPE itself (reference framework/dtype.py
# exposes `paddle.dtype` as the class of dtype objects).
dtype = jnp.dtype
