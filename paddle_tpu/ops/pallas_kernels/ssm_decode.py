"""Per-token state update and read-out of a selective state-space layer
(Mamba-2 decode), in place on the recurrent-state pool.

For every live sequence and head, with S the head's ``[P, N]`` state:

    S <- decay * S + xdt (x) B          decay = exp(dt A),  xdt = dt x  [P]
    y  = S C                             [P]    (the D x term is the caller's)

A decode step of a hybrid model moves little else: at 64 sequences the
36 state-space layers of granite-4.0-h-micro read and write 9.7 GB of
float32 state against 6.4 GB of weights, so this is a bandwidth kernel —
three multiplies and two adds per element moved.

**Layout.**  The pool is ``[layers, slots, G, N, k * P]`` float32: ``k``
heads sit side by side on the 128 lanes (``k = 128 // P``, 2 at P = 64),
the state dimension N on the sublanes, G = heads / k.  So a head pair's
state is one ``[N, 128]`` tile, the per-head scalars and ``xdt`` are
plain lane rows ``[G, k * P]`` (a reshape of ``[heads, P]``), the
read-out is a sublane reduction that leaves ``y`` as such a row, and only
``B`` and ``C`` — one ``[N]`` vector a sequence, shared by all heads —
have to stand as columns, which one 128 x 128 transpose each gives.
:func:`pack_state` / :func:`unpack_state` convert from and to the
textbook ``[heads, P, N]``.

**In place.**  The pool is aliased from input to output; the layer is a
prefetched scalar in the block index, so one call touches one layer's
blocks of the live slots and nothing else moves.  A slot that is not
live (free, or between two prefill chunks) is copied through unchanged,
bit for bit.  Inside a ``lax.scan`` over layers the pool is the carry.

The compiled kernel wants ``N == k * P == 128`` (:func:`supported`);
anything else, and every backend but the TPU, takes the ``jax.numpy``
form of the same update (:func:`ssm_decode_reference`), which is also
the kernel's oracle in the tests (interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def pack_factor(n_heads, head_dim):
    """Heads side by side on the lanes."""
    k = LANES // head_dim if head_dim <= LANES and LANES % head_dim == 0 \
        else 1
    return k if n_heads % k == 0 else 1


def state_shape(n_heads, head_dim, d_state):
    """One sequence's state in one layer, packed: [G, N, k * P]."""
    k = pack_factor(n_heads, head_dim)
    return (n_heads // k, d_state, k * head_dim)


def pack_state(S):
    """[..., heads, P, N] -> [..., G, N, k * P]."""
    *lead, nh, p, n = S.shape
    k = pack_factor(nh, p)
    S = S.reshape(*lead, nh // k, k, p, n)
    return jnp.moveaxis(S, -1, -3).reshape(*lead, nh // k, n, k * p)


def unpack_state(S, head_dim):
    """[..., G, N, k * P] -> [..., heads, P, N]."""
    *lead, g, n, kp = S.shape
    k = kp // head_dim
    S = S.reshape(*lead, g, n, k, head_dim)
    return jnp.moveaxis(S, -3, -1).reshape(*lead, g * k, head_dim, n)


def head_rows(a, head_dim, rows):
    """Per-head scalars [..., heads] as lane rows [..., G, k * P]."""
    return jnp.repeat(a, head_dim, axis=-1).reshape(*a.shape[:-1], *rows)


def supported(shape, on_tpu):
    """Shape gate of the compiled kernel (``shape`` = a packed state
    ``[G, N, k * P]``): square 128 x 128 tiles."""
    return bool(on_tpu) and shape[1] == LANES and shape[2] == LANES


def ssm_decode_reference(pool, layer, decay, xdt, B, C, live):
    """The update in ``jax.numpy``.  pool [L, S, G, N, kP]; layer int32
    scalar; decay, xdt [S, G, kP]; B, C [S, N]; live [S] bool.  Returns
    (y [S, G, kP], pool)."""
    old = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    new = (decay[:, :, None, :] * old
           + B[:, None, :, None] * xdt[:, :, None, :])
    y = jnp.sum(new * C[:, None, :, None], axis=2)
    new = jnp.where(live[:, None, None, None], new, old)
    return y, jax.lax.dynamic_update_index_in_dim(pool, new, layer, 0)


def _kernel(layer_ref, live_ref, pool_ref, decay_ref, xdt_ref, b_ref, c_ref,
            y_ref, out_ref, *, groups):
    s = pl.program_id(0)

    @pl.when(live_ref[s] != 0)
    def _update():
        n = b_ref.shape[-1]
        # B and C as columns over the state dimension, broadcast along
        # the lanes: broadcast the row down the sublanes and transpose
        b_col = jnp.broadcast_to(b_ref[0], (LANES, n)).T
        c_col = jnp.broadcast_to(c_ref[0], (LANES, n)).T
        for g in range(groups):
            row = pl.ds(g, 1)
            new = (decay_ref[0, row, :] * pool_ref[0, 0, g]
                   + b_col * xdt_ref[0, row, :])
            out_ref[0, 0, g] = new
            y_ref[0, row, :] = jnp.sum(new * c_col, axis=0, keepdims=True)

    @pl.when(live_ref[s] == 0)
    def _keep():
        out_ref[...] = pool_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_decode_call(pool, layer, decay, xdt, B, C, live, interpret=False):
    """The jitted wrapper: the device trace names the kernel's event
    ``_ssm_decode_call [tpu_custom_call]`` after it."""
    L, S, G, N, KP = pool.shape
    gb = 16 if G % 16 == 0 else G        # 1 MB of state a block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # the layer; who is live
        grid=(S, G // gb),
        in_specs=[
            pl.BlockSpec((1, 1, gb, N, KP),
                         lambda s, j, layer, live: (layer[0], s, j, 0, 0)),
            pl.BlockSpec((1, gb, KP), lambda s, j, layer, live: (s, j, 0)),
            pl.BlockSpec((1, gb, KP), lambda s, j, layer, live: (s, j, 0)),
            pl.BlockSpec((1, 1, N), lambda s, j, layer, live: (s, 0, 0)),
            pl.BlockSpec((1, 1, N), lambda s, j, layer, live: (s, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, gb, KP), lambda s, j, layer, live: (s, j, 0)),
            pl.BlockSpec((1, 1, gb, N, KP),
                         lambda s, j, layer, live: (layer[0], s, j, 0, 0)),
        ],
    )
    with jax.enable_x64(False):
        y, pool = pl.pallas_call(
            functools.partial(_kernel, groups=gb),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((S, G, KP), jnp.float32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            # operands count the two prefetched scalars: the pool is #2
            input_output_aliases={2: 1},
            interpret=interpret,
        )(jnp.asarray(layer, jnp.int32).reshape(1),
          live.astype(jnp.int32), pool, decay, xdt,
          B[:, None, :], C[:, None, :])
    return y, pool


def _on_tpu():
    return jax.default_backend() == "tpu"


def ssm_decode(pool, layer, decay, xdt, B, C, live):
    """One token for every slot of one layer, the pool updated in place
    (donate it).  Shapes as :func:`ssm_decode_reference`; all float32."""
    if supported(pool.shape[2:], _on_tpu()):
        return _ssm_decode_call(pool, layer, decay, xdt, B, C, live)
    return ssm_decode_reference(pool, layer, decay, xdt, B, C, live)
