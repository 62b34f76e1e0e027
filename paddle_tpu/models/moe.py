"""The expert layer every model with routed experts shares: a sigmoid
router over all experts, the routing weights laid over the experts HELD
here, and the held experts' part of the routed sum (SwiGLU experts),
exact and dropless.  Functions of plain arrays; the three scalars a model
brings (experts a token, the routing scale, the router's bias) are
arguments.  ``models/mla_moe.py`` and ``models/window_moe.py`` import
them; the serving programs (``inference/server/latent_executor.py``,
``window_executor.py``) run them inside their chunk and decode programs.

**The share.**  A model is built with the ids of the routed experts whose
weights it holds (one chip's share under expert parallelism, or all of
them).  It routes over ALL experts with the whole router and computes
``sum_{e in sel, e in held} w_e E_e(h)``: the partial result the exchange
between chips would complete.  Nothing here stands in for absent chips.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

_F32 = jnp.float32
#: tokens up to which the routed experts run as one batched product (a
#: decode step); longer runs go expert by expert so that no
#: ``[experts, T, width]`` intermediate is ever held
_BATCHED_EXPERT_ROWS = 256
#: rows of a long run that go through an expert at a time
_EXPERT_BLOCK = 128


def swiglu_hidden(h, gate_up):
    gate, up = jnp.split(h @ gate_up, 2, axis=-1)
    return jax.nn.silu(gate) * up


def swiglu(h, gate_up, down):
    return swiglu_hidden(h, gate_up) @ down


def route(h, gate_w, bias, top_k, scale, eps=0.0):
    """The router over ALL experts: (ids [T, top_k], weights [T, top_k]
    float32).  Scores are sigmoids of ``h @ gate_w`` in float32; ``bias``
    [E] enters the choice only; of equal choices the lower id wins
    (``lax.top_k``); the chosen scores are normalised to sum 1 (over
    ``sum + eps`` where a model states one) and multiplied by ``scale``."""
    sc = jax.nn.sigmoid(jnp.matmul(h, gate_w, preferred_element_type=_F32))
    _, sel = jax.lax.top_k(sc + bias.astype(_F32), top_k)
    picked = jnp.take_along_axis(sc, sel, axis=-1)
    total = jnp.sum(picked, -1, keepdims=True)
    return sel, scale * picked / (total + eps if eps else total)


def held_weights(sel, w, held):
    """The routing weights laid over the held experts: [T, len(held)]
    float32, zero where a token did not choose that expert."""
    held = jnp.asarray(np.asarray(held, np.int32))
    hit = sel[:, :, None] == held[None, None, :]
    return jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)


def _layer_of(w):
    """An expert leaf ``[E, ...]`` as it is, or layer ``i`` of a stacked
    run given as ``(run [n, E, ...], i)``: addressed in the run, in place
    (as a scan's per-layer slice the TPU compiler copies the layer's
    experts, 1.6 GB at the published widths, before a long loop over
    them)."""
    if isinstance(w, tuple):
        return jax.lax.dynamic_index_in_dim(w[0], w[1], 0, keepdims=False)
    return w


def _expert_of(w, e):
    """Expert ``e``'s matrix of an expert leaf (see :func:`_layer_of`):
    ONE slice of the run, never the layer's experts first."""
    if isinstance(w, tuple):
        run, i = w
        at = [jnp.asarray(j, jnp.int32)
              for j in (i, e, *[0] * (run.ndim - 2))]
        return jax.lax.dynamic_slice(
            run, at, (1, 1) + run.shape[2:]).reshape(run.shape[2:])
    return jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False)


def routed_experts(h, dense_w, gate_up, down):
    """``sum_e dense_w[:, e] * E_e(h)`` over the held experts, float32
    [T, H].  gate_up [E, H, 2F], down [E, F, H] (or each a layer of a
    stacked run, :func:`_layer_of`).  Exact and dropless, with shapes that
    do not depend on the routing.  A few tokens (a decode step): every
    held expert sees every token in one batched product, and the routing
    weight (zero for a token that did not choose it) scales the result;
    the step is bound by the experts' bytes whatever the rows."""
    if h.shape[0] <= _BATCHED_EXPERT_ROWS:
        gate_up, down = _layer_of(gate_up), _layer_of(down)
        gu = jnp.einsum("th,ehf->etf", h, gate_up)
        gate, up = jnp.split(gu, 2, axis=-1)
        y = jnp.einsum("etf,efh->eth", jax.nn.silu(gate) * up, down,
                       preferred_element_type=_F32)
        return jnp.einsum("eth,te->th", y, dense_w)
    # a long run of tokens (a prefill chunk): expert by expert, and of each
    # expert only the blocks of rows that chose it.  The rows are ordered
    # choosers first; a block's rows are gathered, go through the expert,
    # are scaled by their routing weight (zero for a row that fills up the
    # last block, so the sum stays exact) and are added back in place.
    # Shapes do not depend on the routing, trip counts do: at 8 of 128
    # experts a token a held expert is chosen by a sixteenth of the rows.
    T, E = dense_w.shape
    B = _EXPERT_BLOCK if T % _EXPERT_BLOCK == 0 else T
    hit = dense_w > 0
    order = jnp.argsort(~hit, axis=0, stable=True).astype(jnp.int32)
    blocks = (jnp.sum(hit, axis=0, dtype=jnp.int32) + (B - 1)) // B

    def one(e, acc):
        rows = jax.lax.dynamic_index_in_dim(order, e, 1, keepdims=False)
        w_e = jax.lax.dynamic_index_in_dim(dense_w, e, 1, keepdims=False)

        def block(b, acc):
            idx = jax.lax.dynamic_slice_in_dim(rows, b * B, B)
            y = jnp.matmul(swiglu_hidden(h[idx], _expert_of(gate_up, e)),
                           _expert_of(down, e), preferred_element_type=_F32)
            return acc.at[idx].add(y * w_e[idx][:, None])

        return jax.lax.fori_loop(
            jnp.int32(0), jax.lax.dynamic_index_in_dim(blocks, e, 0, False),
            block, acc)

    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(E), one,
                             jnp.zeros(h.shape, _F32))
