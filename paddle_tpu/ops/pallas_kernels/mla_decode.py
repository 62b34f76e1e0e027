"""Absorbed-form decode attention of a latent attention (MLA) layer over
the paged latent pool.

A latent pool holds one row a token a layer, ``[c ; rope(k_pe)]`` (the
normed latent and the roped shared key, padded to whole 128-lane tiles),
and no heads: every query head of a sequence scores the SAME rows, and
the "value" is the row's own first ``rank`` columns.  With q the query
in the latent's space, already scaled (``models/mla_moe.absorb_query``):

    s[h, t] = q[h] . row[t]                     t < length
    p       = softmax(s)                        float32
    u[h]    = sum_t p[h, t] row[t, :rank]

One program a sequence, all heads at once (the heads are the matmul's
rows: 64 of them against one shared page).  The pages are fetched by the
page table UP TO THE SEQUENCE'S LENGTH — a loop over ``cdiv(length,
page_size)`` pages, double-buffered, with an online softmax — never the
``max_len`` window.  The pool is the flat view ``[layers * pages,
page_size, width]`` (``inference/paged._flat``) and stays in HBM: the
layer is a prefetched scalar and page ``i`` of sequence ``b`` is row
``layer * pages + table[b, i]``, so nothing the size of a layer is ever
sliced out, and inside a ``lax.scan`` over layers the pool is the carry.
A sequence of length 0 (a slot that is not live) reads nothing and
returns zeros.

Off the TPU the same attention is :func:`mla_decode_reference` in
``jax.numpy`` (a dense gather of every window), which is also the
kernel's oracle in the tests (interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def padded_width(width):
    """A row's width in the pool: whole 128-lane tiles."""
    return -(-width // LANES) * LANES


def supported(width, rank, page_size, on_tpu):
    """Shape gate of the compiled kernel: rows and the latent are whole
    lane tiles, a page is whole sublane tiles of bf16."""
    return (bool(on_tpu) and width % LANES == 0 and rank % LANES == 0
            and page_size % 16 == 0)


def mla_decode_reference(q, pool, layer, pages, lengths, tables, rank):
    """The attention in ``jax.numpy``.  q [S, heads, W] scaled; pool
    [layers * pages, page_size, W]; layer int32 scalar; lengths [S];
    tables [S, pages per sequence].  Returns u [S, heads, rank] in q's
    dtype."""
    S, _, W = q.shape
    ps = pool.shape[1]
    rows = pool[layer * pages + tables].reshape(S, tables.shape[1] * ps, W)
    s = jnp.einsum("shw,stw->sht", q, rows,
                   preferred_element_type=jnp.float32)
    seen = jnp.arange(rows.shape[1])[None, None, :] < lengths[:, None, None]
    s = jnp.where(seen, s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(seen, p, 0.0)
    u = jnp.einsum("sht,str->shr", p.astype(rows.dtype), rows[..., :rank],
                   preferred_element_type=jnp.float32)
    return (u / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)).astype(q.dtype)


def _kernel(len_ref, tbl_ref, layer_ref, q_ref, pool_hbm, o_ref, buf, sem, *,
            page_size, pages, rank):
    b = pl.program_id(0)
    # every scalar explicitly i32 (the repo's global x64 mode would turn
    # weak Python ints into i64, which Mosaic refuses)
    ps = jnp.int32(page_size)
    length = len_ref[b]
    n = pl.cdiv(length, ps)
    base = layer_ref[0] * jnp.int32(pages)

    def fetch(i, slot):
        return pltpu.make_async_copy(
            pool_hbm.at[base + tbl_ref[b, i]], buf.at[slot], sem.at[slot])

    @pl.when(n > 0)
    def _first():
        fetch(jnp.int32(0), jnp.int32(0)).start()

    q = q_ref[0]                                         # [heads, W]
    heads = q.shape[0]

    def page(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, jnp.int32(2))

        @pl.when(i + 1 < n)
        def _next():
            fetch(i + 1, 1 - slot).start()

        fetch(i, slot).wait()
        rows = buf[slot]                                 # [ps, W]
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        col = i * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = col < length
        s = jnp.where(seen, s, jnp.float32(-1e30))
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # a page's tail past the length holds whatever the page held
        # before: its weight is exactly zero
        p = jnp.where(seen, jnp.exp(s - m_new), jnp.float32(0.0))
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(rows.dtype), rows[:, :rank],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return m_new, l, alpha * acc + pv

    m0 = jnp.full((heads, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((heads, 1), jnp.float32)
    acc0 = jnp.zeros((heads, rank), jnp.float32)
    _, l, acc = jax.lax.fori_loop(jnp.int32(0), n, page, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, jnp.float32(1e-30))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pages", "rank", "interpret"))
def _mla_decode_call(q, pool, layer, lengths, tables, pages, rank,
                     interpret=False):
    """The jitted wrapper: the device trace names the kernel's event
    ``_mla_decode_call [tpu_custom_call]`` after it."""
    S, heads, W = q.shape
    ps = pool.shape[1]
    kernel = functools.partial(_kernel, page_size=ps, pages=pages, rank=rank)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # lengths, page table, layer
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, heads, W), lambda b, lens, tbl, layer:
                         (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, heads, rank), lambda b, lens, tbl, layer:
                               (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, ps, W), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, heads, rank), q.dtype),
            interpret=interpret,
        )(jnp.asarray(lengths, jnp.int32), jnp.asarray(tables, jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1), q, pool)


def _on_tpu():
    return jax.default_backend() == "tpu"


def mla_decode(q, pool, layer, pages, lengths, tables, rank):
    """One token's absorbed-form attention for every sequence of one
    layer; shapes as :func:`mla_decode_reference`."""
    if supported(q.shape[-1], rank, pool.shape[1], _on_tpu()):
        return _mla_decode_call(q, pool, layer, lengths, tables,
                                pages=int(pages), rank=int(rank))
    return mla_decode_reference(q, pool, layer, pages, lengths, tables, rank)
