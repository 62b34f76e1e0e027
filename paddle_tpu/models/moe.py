"""The expert layer every model with routed experts shares: a sigmoid
router over all experts, the routing weights laid over the experts HELD
here, and the held experts' part of the routed sum (SwiGLU experts),
exact and dropless.  Functions of plain arrays; the three scalars a model
brings (experts a token, the routing scale, the router's bias) are
arguments.  ``models/mla_moe.py`` and ``models/window_moe.py`` import
them; the serving programs (``inference/server/latent_executor.py``,
``window_executor.py``) run them inside their chunk and decode programs.

**Two forms, by the rows the function sees.**  A few rows (a decode step):
every held expert sees every row in one batched product.  A long run of
rows (a prefill chunk): the (token, held expert) pairs are sorted by
expert (:func:`sorted_rows`) and go through ONE grouped SwiGLU
(``ops/pallas_kernels/grouped_swiglu.py``: on the TPU a Pallas kernel
that reads each expert's matrices where they lie, elsewhere
``jax.lax.ragged_dot`` twice over the same rows), and each token gathers
its own ``top_k`` results back and sums them in float32.

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

from .. import obs
from ..ops.pallas_kernels import grouped_swiglu as _grouped

_F32 = jnp.float32
_I32 = jnp.int32
#: tokens up to which the routed experts run as one batched product (a
#: decode step); longer runs go through the grouped product, so that no
#: ``[experts, T, width]`` intermediate is ever held
_BATCHED_EXPERT_ROWS = 256


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
    (a scan's per-layer slice of the experts is a copy of 1.6 GB at the
    published widths where the TPU compiler cannot fuse it away)."""
    if isinstance(w, tuple):
        return jax.lax.dynamic_index_in_dim(w[0], w[1], 0, keepdims=False)
    return w


def grouped(rows):
    """Whether a run of ``rows`` tokens takes the grouped form."""
    return rows > _BATCHED_EXPERT_ROWS


def sorted_rows(sel, held, row_tile):
    """Where the (token, held expert) pairs of ``sel`` [T, top_k] lie when
    laid in the order of their experts, each expert's rows filled up to
    whole tiles of ``row_tile`` rows, with shapes that do not depend on
    the routing.  Compares, a running count and sums over ``[pairs, held]``
    and ONE scatter of the pairs' numbers: no sort and no gather of single
    numbers, which the TPU does one at a time (a sort of the pairs and a
    row -> token map gathered by it cost 0.3 ms a call more on the chip:
    PERF.md section 6, PR 37).  A dict of int32 arrays:

    - ``place`` [T, top_k]: the laid row of each pair, token by token an
      expert's rows; a pair whose expert is not held lies at ``rows``, one
      past the last laid row;
    - ``token`` [rows]: the token a laid row holds (0 for a row that fills
      up a tile or lies past the live tiles); ``rows`` is what the worst
      routing takes, every pair held and every expert's last tile holding
      one row;
    - ``group_rows`` [held]: the laid rows of each expert, filling counted;
    - ``tile_expert`` [rows / row_tile] and ``live``: the expert of each
      tile among the held, and how many tiles hold rows at all."""
    (T, k), E = sel.shape, len(held)
    P = T * k
    tiles = (P + E * (row_tile - 1)) // row_tile
    chose = sel.reshape(P, 1) == jnp.asarray(np.asarray(held, np.int32))
    seen = jnp.cumsum(chose, axis=0, dtype=_I32)     # [P, E], this pair too
    tiles_of = (seen[-1] + (row_tile - 1)) // row_tile
    ends = jnp.cumsum(tiles_of)
    laid = (ends - tiles_of) * row_tile              # an expert's first row
    place = jnp.where(chose.any(axis=1),
                      jnp.sum(jnp.where(chose, laid + seen - 1, 0), axis=1),
                      tiles * row_tile)
    token = jnp.zeros((tiles * row_tile,), _I32).at[place].set(
        jnp.arange(P, dtype=_I32) // k, mode="drop", unique_indices=True)
    tile_expert = jnp.minimum(jnp.sum(
        jnp.arange(tiles, dtype=_I32)[:, None] >= ends, axis=1, dtype=_I32),
        E - 1)
    return dict(place=place.reshape(T, k), token=token,
                group_rows=tiles_of * row_tile, tile_expert=tile_expert,
                live=ends[-1])


def routed_experts(h, sel, w, held, gate_up, down):
    """``sum_{e in sel, e in held} w_e E_e(h)``, float32 [T, H]: sel, w
    [T, top_k] as :func:`route` gives them, ``held`` the ids of the held
    experts, gate_up [held, H, 2F], down [held, F, H] (or each a layer of
    a stacked run, :func:`_layer_of`).  Exact and dropless, with shapes
    that do not depend on the routing.  A few tokens (a decode step):
    every held expert sees every token in one batched product, and the
    routing weight (zero for a token that did not choose it) scales the
    result; the step is bound by the experts' bytes whatever the rows."""
    T, H = h.shape
    if not grouped(T):
        gate_up, down = _layer_of(gate_up), _layer_of(down)
        gu = jnp.einsum("th,ehf->etf", h, gate_up)
        gate, up = jnp.split(gu, 2, axis=-1)
        y = jnp.einsum("etf,efh->eth", jax.nn.silu(gate) * up, down,
                       preferred_element_type=_F32)
        return jnp.einsum("eth,te->th", y, held_weights(sel, w, held))
    # a long run of tokens (a prefill chunk): ONE grouped product over the
    # pairs sorted by expert.  On the TPU every row tile belongs to one
    # expert (whose matrices the kernel reads once a tile, where they lie);
    # elsewhere the rows lie close and go through ragged_dot.  A token then
    # gathers its own top_k results and sums them, scaled, in float32.
    F = (down[0] if isinstance(down, tuple) else down).shape[-2]
    kernel = _grouped.supported(H, F, _grouped._on_tpu())
    row_tile = _grouped.ROW_TILE if kernel else 1
    lay = sorted_rows(sel, held, row_tile)
    place, rows = lay["place"], lay["token"].shape[0]
    obs.instant("experts.grouped", cat="serve", pairs=sel.size,
                row_tile=row_tile, tiles=rows // row_tile, kernel=kernel)
    x = h[lay["token"]]
    if kernel:
        y = _grouped.grouped_swiglu(x, gate_up, down, lay["tile_expert"],
                                    lay["live"])
    else:
        y = _grouped.grouped_swiglu_reference(
            x, _layer_of(gate_up), _layer_of(down), lay["group_rows"])
    # a row past the live tiles holds whatever the buffer held
    mine = jnp.where((place < rows)[:, :, None],
                     y[jnp.minimum(place, rows - 1)], 0.0)
    return jnp.sum(mine * w[:, :, None], axis=1)
