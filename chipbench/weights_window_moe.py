"""Seeded weights of a decoder that mixes sliding-window and full
attention layers with a gated output and routed experts (the ``afmoe``
shape), made on the device layer by layer and straight in the engine's
dtype.

As ``weights_mla_moe.py``: the harness makes the weights, hands them to
the program as its parameters, and makes them again from the same seed
for the reference once the program's state is freed, a layer at a time
(one jitted maker per layer kind, the layer's number an argument).

Names and layout are the benchmark's own, every matrix ``[in, out]``:
``embed`` [V, H], ``norm`` [H], ``head`` [H, V] (untied), and per layer
``ln1`` .. ``ln4`` [H] (before the attention, on its branch, before the
feed-forward, on its branch); ``q`` [H, heads * D], ``k``, ``v``
[H, kv heads * D], ``gate`` [H, heads * D] (the output's gate), ``q_norm``,
``k_norm`` [D], ``o`` [heads * D, H]; a dense layer ``gate_up`` [H, 2 I]
(gate then up), ``down`` [I, H]; an expert layer ``router`` [H, E],
``router_bias`` [E], ``experts_gate_up`` [E, H, 2 F], ``experts_down``
[E, F, H], ``shared_gate_up`` [H, 2 F], ``shared_down`` [F, H].

Values (the configuration's ``assumed``): matrices, embedding and head
N(0, ``initializer_range``); norm scales 1; the router's bias N(0, 0.01).
"""
import functools
import math

import jax
import jax.numpy as jnp

from chipbench.weights import key_of

ONES = ("ln1", "ln2", "ln3", "ln4", "norm", "q_norm", "k_norm")
BIAS_STD = 0.01


def kinds(cfg):
    """Per layer: "dense" or "moe" (the attention's kind is no matter to
    the shapes)."""
    k = cfg["num_dense_layers"]
    return ["dense"] * k + ["moe"] * (cfg["num_hidden_layers"] - k)


def layer_shapes(cfg, kind):
    """leaf -> shape of one layer of that kind, in a fixed order (the
    order seeds the leaves)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = {"ln1": (h,), "q": (h, nh * d), "k": (h, nkv * d),
           "v": (h, nkv * d), "gate": (h, nh * d), "q_norm": (d,),
           "k_norm": (d,), "o": (nh * d, h), "ln2": (h,), "ln3": (h,),
           "ln4": (h,)}
    if kind == "dense":
        i = cfg["intermediate_size"]
        out.update(gate_up=(h, 2 * i), down=(i, h))
        return out
    f, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    out.update(router=(h, e), router_bias=(e,),
               experts_gate_up=(e, h, 2 * f), experts_down=(e, f, h),
               shared_gate_up=(h, 2 * f), shared_down=(f, h))
    return out


def top_shapes(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": (v, h), "norm": (h,), "head": (h, v)}


def count(cfg):
    return (sum(math.prod(s) for s in top_shapes(cfg).values())
            + sum(math.prod(s) for kind in kinds(cfg)
                  for s in layer_shapes(cfg, kind).values()))


def _value(cfg, key, name, shape):
    if name in ONES:
        return jnp.ones(shape, jnp.float32)
    std = BIAS_STD if name == "router_bias" else cfg["initializer_range"]
    return jax.random.normal(key, shape, jnp.float32) * std


def _cfg_key(cfg):
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "moe_intermediate_size",
            "num_experts", "vocab_size", "initializer_range")
    return tuple((k, cfg[k]) for k in keep)


@functools.lru_cache(maxsize=None)
def _maker(cfg_key, kind, dtype):
    """The jitted maker of one layer kind (or of the top leaves):
    ``(key, n) -> {leaf: array}``; layer n's leaves hang off
    ``fold_in(key, n + 1)``, the top leaves off ``fold_in(key, 0)``."""
    cfg = dict(cfg_key)
    shapes = top_shapes(cfg) if kind == "top" else layer_shapes(cfg, kind)

    def build(key, n):
        key = jax.random.fold_in(key, n)
        return {name: _value(cfg, jax.random.fold_in(key, j), name, shape)
                .astype(dtype)
                for j, (name, shape) in enumerate(shapes.items())}

    return jax.jit(build)


def top(cfg, seed, dtype):
    with jax.enable_x64(False):
        return _maker(_cfg_key(cfg), "top", jnp.dtype(dtype))(
            key_of(seed), jnp.int32(0))


def layer(cfg, seed, n, dtype):
    """Layer n's leaves, by their short names."""
    with jax.enable_x64(False):
        return _maker(_cfg_key(cfg), kinds(cfg)[n], jnp.dtype(dtype))(
            key_of(seed), jnp.int32(n + 1))
