"""Seeded weights, made on the device in one jitted call.

The harness makes the weights, hands them to the program as its
parameters, and makes them again from the same seed for the reference
once the program's state is freed.  Nothing the program made reaches
the reference.

Names and layout are the benchmark's own (HF-style leaf names, every
matrix ``[in, out]`` so that ``y = x @ W``): ``embed`` [V, H],
``layers.<i>.{q,k,v,o,gate,up,down}``, ``layers.<i>.{ln1,ln2}`` [H],
``norm`` [H], ``head`` [H, V].  Matrices and the embedding are
N(0, initializer_range) — 0.02, as the published config.json states —
and norm scales are 1.
"""
import math

import jax
import jax.numpy as jnp

INITIALIZER_RANGE = 0.02    # Mistral-7B-v0.3 config.json


def leaf_shapes(cfg):
    """name -> shape, in a fixed order (the order seeds the leaves)."""
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    shapes = {"embed": (v, h)}
    for n in range(cfg["num_hidden_layers"]):
        p = f"layers.{n}."
        shapes.update({
            p + "ln1": (h,), p + "q": (h, nq), p + "k": (h, nkv),
            p + "v": (h, nkv), p + "o": (nq, h), p + "ln2": (h,),
            p + "gate": (h, i), p + "up": (h, i), p + "down": (i, h)})
    shapes["norm"] = (h,)
    shapes["head"] = (h, v)
    return shapes


def is_norm(name):
    return name.rsplit(".", 1)[-1] in ("ln1", "ln2", "norm")


def key_of(seed):
    """A seed may be a little over 2**31: it is folded into the key in
    two halves, so that it never has to fit an int32."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), seed >> 16),
        seed & 0xFFFF)


def leaf(cfg, key, name, dtype, values=None):
    """One leaf's values; traceable, so a caller can make a leaf inside
    its own jitted program without holding all of them.  ``values`` names
    a narrower type whose values the leaf takes while it is stored as
    ``dtype``: a train step that computes in bf16 starts its float32
    master from bf16-rounded parameters, so program and reference are
    both given float32 weights that bf16 holds exactly."""
    shapes = leaf_shapes(cfg)
    if is_norm(name):
        return jnp.ones(shapes[name], dtype)
    n = list(shapes).index(name)
    x = jax.random.normal(jax.random.fold_in(key, n), shapes[name],
                          jnp.float32) * INITIALIZER_RANGE
    if values is not None:
        # (reduce_precision, not a cast there and back: XLA may drop a
        # pair of converts as excess precision, and does on the TPU)
        info = jnp.finfo(values)
        x = jax.lax.reduce_precision(x, info.nexp, info.nmant)
    return x.astype(dtype)


def make(cfg, seed, dtype, values=None):
    """All leaves as one dict, from one jitted call."""
    def build(key):
        return {name: leaf(cfg, key, name, dtype, values)
                for name in leaf_shapes(cfg)}

    with jax.enable_x64(False):
        return jax.jit(build)(key_of(seed))


def count(cfg):
    return sum(math.prod(shape) for shape in leaf_shapes(cfg).values())
