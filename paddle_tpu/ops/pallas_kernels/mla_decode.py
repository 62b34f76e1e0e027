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
rows: 64 of them against the same rows).  The program walks the live
pages of the sequence's table — UP TO ITS LENGTH, never the ``max_len``
window — in BLOCKS of whole pages (:func:`block_pages`: 512 keys, 4
pages of 128), ``cdiv(live pages, pages a block)`` trips by the
prefetched length:

    start the DMAs of block j + 1's live pages   (the other VMEM slot)
    wait for block j's
    s      = q @ rows^T                  [heads, block]   float32
    s      masked to col < length
    m, l, acc  <- online softmax over the blocks, acc += p @ rows[:, :rank]

and divides once at the end.  A page is ONE DMA (``[page_size, width]``,
160 KB at the serving cell's size), so the block's copies fly while the
block before it is multiplied, and each trip's fixed cost — the wait,
the loop, the small products' fill and drain on the MXU, the softmax's
bookkeeping — is paid once a block and not once a page (one page a trip
ran at a third of the roofline, blocks of 2 to 12 pages all at ~60 % of
it: PERF.md section 6).  The tail: pages of the last block past the
live ones are not fetched, and their rows are zeroed in VMEM, which
holds what an earlier program left — a NaN bit pattern there would
poison ``p @ rows`` even at p == 0; rows of the last live page past the
length are the pool's own (written or zero), masked out of the scores.

The pool is the flat view ``[layers * pages, page_size, width]``
(``inference/paged._flat``) and stays in HBM: the layer is a prefetched
scalar and page ``i`` of sequence ``b`` is row ``layer * pages +
table[b, i]``, so nothing the size of a layer is ever sliced out, and
inside a ``lax.scan`` over layers the pool is the carry.  A sequence of
length 0 (a slot that is not live) reads nothing and returns zeros.
The products take their operands in the pool's dtype with float32
accumulation; max, exp, sums, the accumulator and the divide are
float32.

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

from ... import obs

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


BLOCK_KEYS = 512
VMEM_BUDGET = 4 << 20


def block_pages(page_size, width, itemsize):
    """Pages in one block of :func:`_kernel`'s walk: whole pages that make
    :data:`BLOCK_KEYS` keys — fewer where the scratch, two slots of a
    block's rows, would pass :data:`VMEM_BUDGET`, and one page where a
    page alone fills it.  Follows from the pool's shape alone."""
    keys = min(BLOCK_KEYS, VMEM_BUDGET // (2 * width * itemsize))
    return max(1, keys // page_size)


def _kernel(len_ref, tbl_ref, layer_ref, q_ref, pool_hbm, o_ref, buf, sem, *,
            page_size, pages, rank):
    # Scalars are explicitly i32 and combined by lax ops: the repo's
    # global x64 mode turns weak Python-int constants into i64 at
    # lowering, which Mosaic refuses.
    lax, i32 = jax.lax, jnp.int32
    b = pl.program_id(0)
    zero, one, two = i32(0), i32(1), i32(2)
    ps, bp = i32(page_size), i32(buf.shape[1] // page_size)
    length = len_ref[b]
    npages = lax.div(lax.add(length, lax.sub(ps, one)), ps)
    nblocks = lax.div(lax.add(npages, lax.sub(bp, one)), bp)
    base = lax.mul(layer_ref[0], i32(pages))

    def place(g):
        """Where page ``g`` of the sequence lands: the slot of its block
        and its rows there."""
        rows = lax.mul(lax.rem(g, bp), ps)
        return (lax.rem(lax.div(g, bp), two),
                pl.ds(pl.multiple_of(rows, page_size), page_size))

    def copy(g, pid):
        slot, rows = place(g)
        return pltpu.make_async_copy(pool_hbm.at[pid], buf.at[slot, rows],
                                     sem.at[slot])

    def start(g, carry):
        copy(g, lax.add(base, tbl_ref[b, g])).start()
        return carry

    def wait(g, carry):
        copy(g, zero).wait()             # a wait names no source
        return carry

    def scrub(g, carry):
        slot, rows = place(g)
        buf[slot, rows] = jnp.zeros((page_size, buf.shape[2]), buf.dtype)
        return carry

    q = q_ref[0]                                         # [heads, W]
    heads = q.shape[0]
    cols = lax.broadcasted_iota(i32, (heads, buf.shape[1]), 1)
    masked = jnp.full(cols.shape, -1e30, jnp.float32)

    def block(j, carry):
        m, l, acc = carry
        here = lax.mul(j, bp)                    # the block's pages:
        ahead = lax.add(here, bp)                # [here, ahead)
        live = lax.min(npages, ahead)            # those with keys end here
        # start the NEXT block's live pages (in the first trip this
        # block's too), so their copies fly while this block is multiplied
        lax.fori_loop(lax.select(lax.eq(j, zero), zero, ahead),
                      lax.min(npages, lax.add(ahead, bp)), start, 0)
        lax.fori_loop(here, live, wait, 0)       # wait for this block's
        # zero the rows past the live pages (the last block's): VMEM
        # scratch holds what an earlier program left
        lax.fori_loop(live, ahead, scrub, 0)

        rows = buf[lax.rem(j, two)]                      # [block, W]
        s = lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = lax.select(cols < lax.sub(length, lax.mul(here, ps)), s, masked)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)           # exactly 0 past the length
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(p.astype(rows.dtype), rows[:, :rank],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return m_new, l, alpha * acc + pv

    m0 = jnp.full((heads, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((heads, 1), jnp.float32)
    acc0 = jnp.zeros((heads, rank), jnp.float32)
    _, l, acc = lax.fori_loop(zero, nblocks, block, (m0, l0, acc0))
    # a sequence of length 0 (a slot that is not live) read nothing: zeros
    o_ref[0] = (acc / jnp.maximum(l, jnp.float32(1e-30))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pages", "rank", "interpret"))
def _mla_decode_call(q, pool, layer, lengths, tables, pages, rank,
                     interpret=False):
    """The jitted wrapper: the device trace names the kernel's event
    ``_mla_decode_call [tpu_custom_call]`` after it."""
    S, heads, W = q.shape
    ps = pool.shape[1]
    block = ps * block_pages(ps, W, pool.dtype.itemsize)
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
        scratch_shapes=[pltpu.VMEM((2, block, W), pool.dtype),  # two slots
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
    ps, W = pool.shape[1], q.shape[-1]
    kernel = supported(W, rank, ps, _on_tpu())
    # at trace time, once a traced call: which form ran, at what block
    # (0: the reference's dense gather, no walk)
    obs.instant("attn.mla_decode", cat="serve", kernel=kernel,
                block_pages=block_pages(ps, W, pool.dtype.itemsize)
                if kernel else 0)
    if kernel:
        return _mla_decode_call(q, pool, layer, lengths, tables,
                                pages=int(pages), rank=int(rank))
    return mla_decode_reference(q, pool, layer, pages, lengths, tables, rank)
