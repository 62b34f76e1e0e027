"""Seeded weights of a Mamba-2 / attention hybrid (granitemoehybrid),
made on the device layer by layer.

As ``weights.py`` for the Llama-shaped configurations: the harness makes
the weights, hands them to the program as its parameters, and makes them
again from the same seed for the reference once the program's state is
freed.  A layer's leaves come from one jitted call (one program per
layer kind, the layer's number an argument), so neither side ever holds
more than the model itself and nothing compiles a program with four
hundred outputs.

Names and layout are the benchmark's own, every matrix ``[in, out]``:
``embed`` [V, H] (also the output head: tied), ``norm`` [H], and per
layer ``layers.<i>.``: ``ln1``, ``ln2`` [H], ``mlp_in`` [H, 2I] (gate then
up), ``mlp_out`` [I, H]; an attention layer ``q``, ``k``, ``v``, ``o``; a
Mamba layer ``in_proj`` [H, d_inner + conv_dim + heads] (z | xBC | dt),
``conv_w`` [K, conv_dim] (``out[t] = b + sum_k w[k] x[t - K + 1 + k]``),
``conv_b`` [conv_dim], ``dt_bias``, ``A_log``, ``D`` [heads], ``mnorm``
[d_inner], ``out_proj`` [d_inner, H].

Values (the configuration's ``assumed``): matrices and the embedding
N(0, ``initializer_range``); norm scales and ``D`` 1; and the Mamba-2
reference initialisation for the rest — ``A_log = log(U[1, 16])``,
``dt_bias`` the inverse softplus of a log-uniform draw in [1e-3, 1e-1],
convolution weight and bias U(+-1/sqrt(K)) — so that a head forgets over
1 to 1,000 tokens and the carried state matters.
"""
import functools
import math

import jax
import jax.numpy as jnp

from chipbench.flops_hybrid import mamba_dims
from chipbench.weights import key_of

DT_RANGE = (1e-3, 1e-1)
A_RANGE = (1.0, 16.0)

ONES = ("ln1", "ln2", "norm", "mnorm", "D")


def layer_shapes(cfg, kind):
    """leaf -> shape of one layer of that kind, in a fixed order (the
    order seeds the leaves)."""
    h, i, d = cfg["hidden_size"], cfg["shared_intermediate_size"], \
        cfg["head_dim"]
    out = {"ln1": (h,)}
    if kind == "attention":
        nq, nkv = cfg["num_attention_heads"] * d, \
            cfg["num_key_value_heads"] * d
        out.update(q=(h, nq), k=(h, nkv), v=(h, nkv), o=(nq, h))
    else:
        (inner, conv), nh = mamba_dims(cfg), cfg["mamba_n_heads"]
        out.update(in_proj=(h, inner + conv + nh),
                   conv_w=(cfg["mamba_d_conv"], conv), conv_b=(conv,),
                   dt_bias=(nh,), A_log=(nh,), D=(nh,), mnorm=(inner,),
                   out_proj=(inner, h))
    out.update(ln2=(h,), mlp_in=(h, 2 * i), mlp_out=(i, h))
    return out


def top_shapes(cfg):
    return {"embed": (cfg["vocab_size"], cfg["hidden_size"]),
            "norm": (cfg["hidden_size"],)}


def leaf_shapes(cfg):
    shapes = dict(top_shapes(cfg))
    for n, kind in enumerate(cfg["layer_types"]):
        shapes.update({f"layers.{n}.{k}": s
                       for k, s in layer_shapes(cfg, kind).items()})
    return shapes


def count(cfg):
    return sum(math.prod(s) for s in leaf_shapes(cfg).values())


def _value(cfg, key, name, shape):
    """One leaf in float32 (traceable)."""
    if name in ONES:
        return jnp.ones(shape, jnp.float32)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))
    if name == "dt_bias":
        lo, hi = (math.log(x) for x in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)
    if name in ("conv_w", "conv_b"):
        bound = 1.0 / math.sqrt(cfg["mamba_d_conv"])
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    return jax.random.normal(key, shape, jnp.float32) \
        * cfg["initializer_range"]


def _leaves(cfg, shapes, key, dtype):
    return {name: _value(cfg, jax.random.fold_in(key, j), name, shape)
            .astype(dtype) for j, (name, shape) in enumerate(shapes.items())}


@functools.lru_cache(maxsize=None)
def _maker(cfg_key, kind, dtype):
    """The jitted maker of one layer kind (or of the top leaves):
    ``(key, n) -> {leaf: array}``; layer n's leaves hang off
    ``fold_in(key, n + 1)``, the top leaves off ``fold_in(key, 0)``."""
    cfg = dict(cfg_key)
    shapes = top_shapes(cfg) if kind == "top" else layer_shapes(cfg, kind)

    def build(key, n):
        return _leaves(cfg, shapes, jax.random.fold_in(key, n), dtype)

    return jax.jit(build)


def _cfg_key(cfg):
    keep = ("hidden_size", "shared_intermediate_size", "head_dim",
            "num_attention_heads", "num_key_value_heads", "vocab_size",
            "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_d_conv", "mamba_n_groups", "initializer_range")
    return tuple((k, cfg[k]) for k in keep)


def top(cfg, seed, dtype):
    with jax.enable_x64(False):
        return _maker(_cfg_key(cfg), "top", jnp.dtype(dtype))(
            key_of(seed), jnp.int32(0))


def layer(cfg, seed, n, dtype):
    """Layer n's leaves, by their short names."""
    with jax.enable_x64(False):
        return _maker(_cfg_key(cfg), cfg["layer_types"][n],
                      jnp.dtype(dtype))(key_of(seed), jnp.int32(n + 1))


def make(cfg, seed, dtype):
    """All leaves as one dict (the reference's view)."""
    out = dict(top(cfg, seed, dtype))
    for n in range(cfg["num_hidden_layers"]):
        out.update({f"layers.{n}.{k}": v
                    for k, v in layer(cfg, seed, n, dtype).items()})
    return out
