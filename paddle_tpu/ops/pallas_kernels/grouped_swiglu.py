"""The routed SwiGLU experts of a long run of rows (a prefill chunk) as ONE
grouped product over rows sorted by expert.

``models/moe.py`` lays the (token, held expert) pairs of a chunk in the
order of their experts, each expert's rows padded to whole row tiles
(:func:`~paddle_tpu.models.moe.sorted_rows`), so that every row tile
belongs to ONE expert.  For the rows ``x`` of expert ``e``:

    y = (silu(x W_gate[e]) * (x W_up[e])) W_down[e]

with bf16 (the rows' dtype) operands, float32 accumulation, the hidden rows
float32 until they enter the second product, and a float32 result.

One program a (row tile, panel of F) pair, the panels inner.  The expert
of a tile and the layer of a stacked run are PREFETCHED SCALARS in the
index maps: the kernel reads each expert's matrices where they lie, in the
leaf ``[E, H, 2F]`` / ``[E, F, H]`` or in layer ``i`` of a stacked run
``[n, E, ..]``, and nothing the size of an expert or of a layer is sliced
out before it.  Gate and up are the two halves of one leaf: the same array
under two index maps.  A panel is as wide as lets the three matrices of a
step stay double-buffered in VMEM (:func:`panel`).  Tiles past the live
count repeat the last live tile's block indices, so they fetch nothing,
and compute nothing.

Off the TPU the same rows go through ``jax.lax.ragged_dot`` twice
(:func:`grouped_swiglu_reference`), which is also the kernel's oracle in
the tests (interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
LANES = 128
#: rows of a tile: one pass of the MXU's 128 rows
ROW_TILE = 128
#: what the gate, up and down panels of a step may hold in VMEM, both
#: buffers counted, and the limit the call asks for (of the v5e's 128 MiB):
#: the row tile, the float32 output tile and the hidden rows ride beside
_PANEL_BYTES = 32 << 20
_VMEM_LIMIT = 64 << 20


def supported(hidden, ffn, on_tpu):
    """Shape gate of the compiled kernel: rows and panels are whole lane
    tiles."""
    return bool(on_tpu) and hidden % LANES == 0 and ffn % LANES == 0


def panel(hidden, ffn, itemsize):
    """The widest panel of F, a whole number of lane tiles that divides F,
    whose gate, up and down blocks fit :data:`_PANEL_BYTES`
    double-buffered."""
    fits = [f for f in range(LANES, ffn + 1, LANES) if ffn % f == 0
            and 2 * 3 * hidden * f * itemsize <= _PANEL_BYTES]
    return max(fits, default=LANES)


def _stacked(w):
    """An expert leaf ``[E, ..]`` or layer ``i`` of a stacked run ``(run
    [n, E, ..], i)`` as (a stacked run, the layer): a leaf is the one layer
    of a run of one (a reshape moves nothing)."""
    if isinstance(w, tuple):
        return w
    return w[None], 0


def grouped_swiglu_reference(x, gate_up, down, group_rows):
    """The grouped SwiGLU in ``jax.lax.ragged_dot``: x [M, H] rows in the
    order of their experts, ``group_rows`` [E] int32 the rows of each;
    gate_up [E, H, 2F] and down [E, F, H].  Returns [M, H] float32 (zeros
    past the last group's rows)."""
    gate, up = jnp.split(jax.lax.ragged_dot(
        x, gate_up, group_rows, preferred_element_type=_F32), 2, axis=-1)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    return jax.lax.ragged_dot(hidden, down, group_rows,
                              preferred_element_type=_F32)


def _kernel(expert_ref, live_ref, layer_ref, x_ref, gate_ref, up_ref,
            down_ref, o_ref):
    del expert_ref, layer_ref                 # the index maps read them
    p = pl.program_id(1)

    @pl.when(pl.program_id(0) < live_ref[0])
    def _tile():
        x = x_ref[...]
        gate = jnp.dot(x, gate_ref[0, 0], preferred_element_type=_F32)
        up = jnp.dot(x, up_ref[0, 0], preferred_element_type=_F32)
        part = jnp.dot((jax.nn.silu(gate) * up).astype(x.dtype),
                       down_ref[0, 0], preferred_element_type=_F32)

        # the output tile stays in VMEM over the panels of its row tile
        @pl.when(p == 0)
        def _first():
            o_ref[...] = part

        @pl.when(p > 0)
        def _rest():
            o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_swiglu_call(x, gate_up, down, layer, tile_expert, live,
                         interpret=False):
    """The jitted wrapper: the device trace names the kernel's event
    ``_grouped_swiglu_call [tpu_custom_call]`` after it (no decode
    kernel's reader matches that).  x [tiles * ROW_TILE, H]; gate_up
    [n, E, H, 2F], down [n, E, F, H]; layer, live int32 scalars;
    tile_expert [tiles] int32."""
    M, H = x.shape
    F = down.shape[2]
    tiles, tf = M // ROW_TILE, panel(H, F, x.dtype.itemsize)
    panels = F // tf

    def at(t, p, live):
        """A dead tile stays where the last live one ended."""
        dead = t >= live[0]
        return (jnp.where(dead, jnp.maximum(live[0] - 1, 0), t),
                jnp.where(dead, panels - 1, p))

    def rows(t, p, expert, live, layer):
        return at(t, p, live)[0], 0

    def gate(t, p, expert, live, layer, half=0):
        t, p = at(t, p, live)
        return layer[0], expert[t], 0, half * panels + p

    def down_(t, p, expert, live, layer):
        t, p = at(t, p, live)
        return layer[0], expert[t], p, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # tile -> expert, live tiles, layer
        grid=(tiles, panels),
        in_specs=[
            pl.BlockSpec((ROW_TILE, H), rows),
            pl.BlockSpec((1, 1, H, tf), gate),
            pl.BlockSpec((1, 1, H, tf), functools.partial(gate, half=1)),
            pl.BlockSpec((1, 1, tf, H), down_),
        ],
        out_specs=pl.BlockSpec((ROW_TILE, H), rows),
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            _kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((M, H), _F32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(jnp.asarray(tile_expert, jnp.int32),
          jnp.asarray(live, jnp.int32).reshape(1),
          jnp.asarray(layer, jnp.int32).reshape(1), x, gate_up, gate_up,
          down)


def _on_tpu():
    return jax.default_backend() == "tpu"


def _interpret():
    return jax.default_backend() != "tpu"


def grouped_swiglu(x, gate_up, down, tile_expert, live):
    """The kernel over rows laid by tile: x [tiles * ROW_TILE, H], tile
    ``t < live`` holding rows of expert ``tile_expert[t]`` alone.  Returns
    [tiles * ROW_TILE, H] float32; rows of the tiles past ``live`` hold
    whatever the buffer held."""
    (gate_up, layer), (down, _) = _stacked(gate_up), _stacked(down)
    return _grouped_swiglu_call(x, gate_up, down, layer, tile_expert, live,
                                interpret=_interpret())
