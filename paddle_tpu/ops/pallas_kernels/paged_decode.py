"""Fused paged-decode attention kernel (self-authored, #4).

Reference analog: ``paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu`` — single-token decode attention
against a block-table (paged) KV cache, the kernel behind the
reference's continuous-batching serving path.  The role, not the
design.

TPU design: one program per SEQUENCE, every KV head of it at once, and
its work follows the sequence's LENGTH, never the ``max_len`` window.
The program walks the live part of the sequence's block-table window in
BLOCKS of whole pages (:func:`block_pages`: 256 keys), ``cdiv(live
pages, pages a block)`` of them by the prefetched length:

    start the DMAs of block j + 1's live pages   (the other VMEM slot)
    wait for block j's
    s      = q @ K_block^T * scale       [KV, group, 256]  float32
    s      masked to first <= col < length
    m, l, acc  <- online softmax over the blocks, acc += p @ V_block

and divides once at the end.  On a WINDOW layer the walk begins at the
block of the sequence's first visible key (``starts``) and the table's
first entry stands for token ``bases`` (the pages behind the window were
released and the table shifted): one kernel for both kinds of layer, a
full layer passing zeros for both.  A page is ONE strided DMA for K and one
for V that takes all its KV heads, ``pool[layer, :, page]`` =
``[KV, page_size, head_dim]``, into one of two VMEM slots of one block,
so a block's copies fly while the block before it is multiplied.  What
the kernel's time is made of is descriptors and programs, not
arithmetic: with a program and a 4-KB DMA per (sequence, KV head) the
same block loop ran 1.2 ms a layer at the serving cell's size, slower
than the whole-window form it replaced (1.0 ms), with float32 and with
bf16 operands alike; with a program a sequence and a 32-KB DMA a page
it runs 0.25 ms (PERF.md section 6, PR 33).  The scratch is ``2 x KV x
256 x head_dim`` for K and for V whatever the window (8 KV heads of 128
in bf16: 4 x 512 KB).  Only the last block's rows past its live pages
are zeroed: VMEM scratch holds what an earlier program left, and a NaN
bit pattern in V would poison p @ V even at p == 0.  A row of length 0
(a padded batch row) reads nothing and returns zeros.

The products are batched over the KV heads and take their operands in
the POOL's dtype with float32 accumulation (bf16 x bf16 is exact in
float32; the scale multiplies the float32 scores, not q); max, exp,
sums, the accumulator and the divide are float32; the probabilities go
to the MXU in the pool's dtype, as the chunk program's and
``mla_decode``'s do.  With a float32 pool nothing is cast.  GQA rides
free: a KV head's q rows are the ``H // KV`` query heads sharing it.

Every loop has a dynamic trip count and nothing is unrolled over pages
or blocks: the traced body is the same ~90 equations at every window
and batch size (a serving engine traces it once per decode batch size;
PERF.md section 6, PRs 29 and 33).

What this fuses (vs ``inference/paged._dense_paged_attention``): the
jnp path materializes the gathered dense cache [B, KV, T, D] (x2) in
HBM, then runs einsum -> mask -> softmax -> einsum as separate XLA
fusions over HBM round-trips.  Here the page gather lands directly in
VMEM and every intermediate (scores, probs) lives and dies there; HBM
traffic is the theoretical floor (read each LIVE page once, write
[B, H, D] once).

Layout contract (matches PagedKVCache):
  q            [B, KV, G, D]      (G = H // KV query heads per KV head)
  k/v_pages    [L, KV, P, ps, D]  (the WHOLE pool, every layer; P = pages
                                   of a layer)
  layer        int32 scalar       the layer this call attends over
  lengths      [B]   int32        valid tokens per sequence
  page_indices [B, pps] int32     each sequence's block-table window
  starts       [B]   int32        first visible key (0: a full layer)
  bases        [B]   int32        token the table's first entry stands for
returns        [B, KV, G, D]

The layer is a prefetched scalar beside the lengths and the page table,
and a page's DMA reads ``pool[layer, kv, page]``: the pool stays where it
is in HBM, is only read, and nothing the size of a layer is ever sliced
out of it (on the TPU such a slice is a copy of 134 MB at the benchmark's
size).  Inside a ``lax.scan`` over layers the pool is the carry.  A pool
of one layer, ``[KV, P, ps, D]``, is the same pool with ``L = 1`` and
``layer = 0`` (:func:`paged_decode` reshapes it, a bitcast).  The int8
kernel (:func:`paged_decode_quant`) still takes one layer's pool and
still computes over the whole window (:func:`_gather_window`, a scratch
of the window's size): no cell runs it, and it moves onto the block body
with a per-page scale when one does (ROADMAP Queue 3).

TPU constraints (callers gate, inference/paged.py): D % 128 == 0 (lane
tiling), page_size % 8 == 0 (sublane tiling of the DMA'd page; 32 for
the int8 pools).
Off-TPU the kernel runs in interpreter mode (tests); serving uses the
dense jnp path there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def block_pages(page_size, kv_heads, head_dim, itemsize):
    """Pages in one block of :func:`_kernel`'s loop: whole pages that
    make 256 keys (two lane tiles of scores a query row, two MXU tiles of
    K and of V a head) — fewer where the scratch, two slots of K and of
    V for every KV head, would pass 4 MiB of VMEM, and one page where a
    page is longer.  Follows from the pool's shape alone."""
    keys = min(256, (4 << 20) // (4 * kv_heads * head_dim * itemsize))
    return max(1, keys // page_size)


def _kernel(len_ref, tbl_ref, layer_ref, start_ref, base_ref, q_ref, k_hbm,
            v_hbm, o_ref, k_buf, v_buf, sem, *, page_size, pages_per_seq,
            scale):
    # Scalars are explicitly i32 and combined by lax ops: the repo's
    # global x64 mode turns weak Python-int constants into i64 at
    # lowering, which Mosaic refuses; and an engine traces this body once
    # for every decode batch size, where each jnp operator on a tracer is
    # a jitted call of its own (PERF.md section 6, PR 33).
    lax, i32 = jax.lax, jnp.int32
    b = pl.program_id(0)
    layer = layer_ref[0]
    zero, one, two = i32(0), i32(1), i32(2)
    ps, bp = i32(page_size), i32(k_buf.shape[2] // page_size)
    # keys are counted from the table's first entry, which stands for
    # token ``base`` (0 on a full layer; a window layer's table begins
    # where its released pages end): the sequence reads keys
    # [first, length).  A verify window's last rows may name a length
    # past the table
    base = base_ref[b]
    length = lax.min(lax.sub(len_ref[b], base),
                     i32(pages_per_seq * page_size))
    first = lax.max(lax.sub(start_ref[b], base), zero)
    npages = lax.div(lax.add(length, lax.sub(ps, one)), ps)
    nblocks = lax.div(lax.add(npages, lax.sub(bp, one)), bp)
    page0 = lax.div(first, ps)                   # the first visible page
    block0 = lax.div(page0, bp)                  # and its block
    # nothing visible (a padded row, or a start at the length): no trip
    nblocks = lax.select(lax.lt(first, length), nblocks, block0)

    q = q_ref[0]                                         # [KV, G, D]
    KV, G, D = q.shape
    # the products' operands are the pool's dtype (bf16 x bf16 is exact
    # in float32); everything from the scores on is float32
    operand = jnp.promote_types(q.dtype, k_buf.dtype)
    q = q.astype(operand)

    def place(g):
        """Where page ``g`` of the window lands: the slot of its block
        and its rows there."""
        rows = lax.mul(lax.rem(g, bp), ps)
        return (lax.rem(lax.div(g, bp), two),
                pl.ds(pl.multiple_of(rows, page_size), page_size))

    def copies(g, pid):
        """Pool page ``pid`` as page ``g`` of the window: every KV head
        of it in ONE strided DMA for K and one for V."""
        slot, rows = place(g)
        return [pltpu.make_async_copy(pool.at[layer, :, pid],
                                      buf.at[slot, :, rows], sem.at[slot])
                for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf))]

    def start(g, carry):
        for dma in copies(g, tbl_ref[b, g]):
            dma.start()
        return carry

    def wait(g, carry):
        for dma in copies(g, zero):      # a wait names no source
            dma.wait()
        return carry

    def scrub(g, carry):
        slot, rows = place(g)
        v_buf[slot, :, rows] = jnp.zeros((KV, page_size, D), v_buf.dtype)
        return carry

    cols = lax.broadcasted_iota(i32, (KV, G, k_buf.shape[2]), 2)
    masked = jnp.full(cols.shape, -1e30, jnp.float32)

    def block(j, carry):
        m, l, acc = carry
        here = lax.mul(j, bp)                    # the block's pages:
        ahead = lax.add(here, bp)                # [here, ahead)
        live = lax.min(npages, ahead)            # those with keys end here
        # the first trip's block begins at the first visible page
        seen0 = lax.select(lax.eq(j, block0), page0, here)
        # start the NEXT block's live pages (in the first trip this
        # block's too), so its copies fly while this block is multiplied
        lax.fori_loop(lax.select(lax.eq(j, block0), page0, ahead),
                      lax.min(npages, lax.add(ahead, bp)), start, 0)
        lax.fori_loop(seen0, live, wait, 0)      # wait for this block's
        # zero V's rows before the first visible page (the first block's)
        # and past the live pages (the last block's): VMEM scratch holds
        # what an earlier program left, and a NaN bit pattern in V would
        # poison p @ V even at p == 0
        lax.fori_loop(here, seen0, scrub, 0)
        lax.fori_loop(live, ahead, scrub, 0)

        slot = lax.rem(j, two)
        k = k_buf[slot].astype(operand)                  # [KV, block, D]
        v = v_buf[slot].astype(operand)
        s = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
        at = lax.mul(here, ps)                   # the block's first key
        seen = jnp.logical_and(cols >= lax.sub(first, at),
                               cols < lax.sub(length, at))
        s = lax.select(seen, s * jnp.float32(scale), masked)
        m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)           # exactly 0 past the length
        l = alpha * l + jnp.sum(p, axis=2, keepdims=True)
        pv = lax.dot_general(p.astype(operand), v,
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
        return m_new, l, alpha * acc + pv

    m0 = jnp.full((KV, G, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((KV, G, 1), jnp.float32)
    acc0 = jnp.zeros((KV, G, D), jnp.float32)
    _, l, acc = lax.fori_loop(block0, nblocks, block, (m0, l0, acc0))
    # a row of length 0 (a padded batch row) read nothing: zeros
    o_ref[0] = (acc / jnp.maximum(l, jnp.float32(1e-30))) \
        .astype(o_ref.dtype)


def _interpret():
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("scale",))
def _call(q, k_pages, v_pages, lengths, page_indices, layer, starts, bases,
          scale):
    """The jitted wrapper: the device trace names the kernel's event
    ``_call [tpu_custom_call]`` after it (the benchmark's
    ``paged_decode_roofline`` matches that name)."""
    B, KV, G, D = q.shape
    ps = k_pages.shape[3]
    pps = page_indices.shape[1]
    block = ps * block_pages(ps, KV, D, k_pages.dtype.itemsize)
    kernel = functools.partial(_kernel, page_size=ps, pages_per_seq=pps,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # lengths + page table + layer + first visible keys + table bases
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, KV, G, D), lambda b, *scalars: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # K pool stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, KV, G, D),
                               lambda b, *scalars: (b, 0, 0, 0)),
        scratch_shapes=[                # two slots of one block each
            pltpu.VMEM((2, KV, block, D), k_pages.dtype),
            pltpu.VMEM((2, KV, block, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    # Mosaic rejects i64 grid/index constants from the repo's global
    # x64 mode — trace x64-off like every other kernel in this package.
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
            interpret=_interpret(),
        )(jnp.asarray(lengths, jnp.int32),
          jnp.asarray(page_indices, jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1),
          jnp.asarray(starts, jnp.int32), jnp.asarray(bases, jnp.int32),
          q, k_pages, v_pages)


def paged_decode(q, k_pages, v_pages, lengths, page_indices, layer=None,
                 starts=None, bases=None, scale=None):
    """Fused paged-decode attention over the page pool.

    q [B, H, D] (H % KV == 0); lengths [B]; page_indices [B, pps];
    k/v_pages either the whole pool ``[L, KV, P, ps, D]`` with ``layer``
    (an int32 scalar, traced or not) naming the layer to attend over, or
    one layer's pool ``[KV, P, ps, D]`` with no ``layer``.  Returns
    [B, H, D].  Pure function of its arguments (no custom VJP: decode is
    inference-only).

    A WINDOW layer names, per sequence, ``starts`` [B], the first key its
    query may see (``max(0, p + 1 - w)`` for the token at position ``p``)
    and ``bases`` [B], the token its table's first entry stands for (a
    multiple of the page size; the pages before it were released):
    ``lengths`` and ``starts`` count tokens of the sequence, the table
    covers tokens ``bases ..``.  The block loop begins at the first
    visible key's block and masks the keys before it.  Both default to
    zeros: a full layer, every key from token 0.
    """
    B, H, D = q.shape
    if k_pages.ndim == 4:
        if layer is not None:
            raise ValueError("a layer was named for a pool of one layer")
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    elif layer is None:
        raise ValueError("a pool [L, KV, P, ps, D] needs its layer named")
    KV = k_pages.shape[1]
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, KV, H // KV, D)
    zeros = jnp.zeros((B,), jnp.int32)
    out = _call(qg, k_pages, v_pages, lengths, page_indices, layer,
                zeros if starts is None else starts,
                zeros if bases is None else bases, float(scale))
    return out.reshape(B, H, D)


def _gather_window(page_of, npages, pools, bufs, sem, *, page_size,
                   pages_per_seq):
    """DMA pages ``0 .. npages`` of a sequence's window from each HBM
    pool (``page_of(pool, i)`` is page ``i``'s ``[page_size, D]`` slice
    of it) into its VMEM buffer, and zero the window's tail.

    EVERY needed copy is started before any is waited on (the DMA engine
    pipelines them).  The tail is zeroed because VMEM scratch holds
    garbage from the previous program, and a NaN bit pattern in V would
    poison p @ V even at p == 0.

    Three loops over pages, not an unroll: unrolled over a window of 128
    pages the kernel's body is ~400 conditionals, traced again for every
    decode batch size, and that trace was most of a serving engine's
    set-up (PERF.md section 6, PR 29)."""
    def rows(i):
        return pl.ds(pl.multiple_of(i * page_size, page_size), page_size)

    def copies(i):
        return [pltpu.make_async_copy(page_of(pool, i), buf.at[rows(i)], sem)
                for pool, buf in zip(pools, bufs)]

    def start(i, carry):
        for dma in copies(i):
            dma.start()
        return carry

    def zero(i, carry):
        for buf in bufs:
            buf[rows(i)] = jnp.zeros((page_size, buf.shape[-1]), buf.dtype)
        return carry

    def wait(i, carry):
        for dma in copies(i):
            dma.wait()
        return carry

    jax.lax.fori_loop(0, npages, start, 0)
    jax.lax.fori_loop(npages, jnp.int32(pages_per_seq), zero, 0)
    jax.lax.fori_loop(0, npages, wait, 0)


def _kernel_quant(len_ref, tbl_ref, ks_ref, vs_ref, q_ref, k_hbm, v_hbm,
                  o_ref, k_buf, v_buf, sem, *, page_size, pages_per_seq,
                  scale):
    """Int8-page variant (PT_QUANT=int8): the pools ride HBM→VMEM as
    int8 (half the bytes of bf16 — the decode step IS this stream) and
    the per-page f32 scales arrive via scalar prefetch; dequant is a
    per-page broadcast multiply on the f32 window right next to the MXU
    dots.  Math past the dequant is identical to ``_kernel``."""
    b = pl.program_id(0)
    kv = pl.program_id(1)
    length = len_ref[b]
    _gather_window(lambda pool, i: pool.at[kv, tbl_ref[b, i]],
                   pl.cdiv(length, jnp.int32(page_size)),
                   (k_hbm, v_hbm), (k_buf, v_buf), sem,
                   page_size=page_size, pages_per_seq=pages_per_seq)

    # Per-row dequant scale for the window: row r belongs to window page
    # r // page_size, whose pool page id is tbl[b, i] — a static unroll
    # over the (small) page window turns the SMEM scale gathers into a
    # [S_window, 1] VMEM vector.
    S = k_buf.shape[0]
    row_page = jax.lax.broadcasted_iota(jnp.int32, (S, 1), 0) \
        // jnp.int32(page_size)
    k_scale = jnp.zeros((S, 1), jnp.float32)
    v_scale = jnp.zeros((S, 1), jnp.float32)
    for i in range(pages_per_seq):
        pid = tbl_ref[b, i]
        k_scale = jnp.where(row_page == i, ks_ref[kv, pid], k_scale)
        v_scale = jnp.where(row_page == i, vs_ref[kv, pid], v_scale)

    q = q_ref[0, 0].astype(jnp.float32) * jnp.float32(scale)  # [G, D]
    k = k_buf[...].astype(jnp.float32) * k_scale     # [S_window, D]
    v = v_buf[...].astype(jnp.float32) * v_scale
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], S), 1)
    s = jnp.where(col < length, s, jnp.float32(-1e30))
    m = jnp.max(s, axis=1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=1, keepdims=True)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0, 0] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def _call_quant(q, k_pages, v_pages, lengths, page_indices, k_scales,
                v_scales, scale):
    B, KV, G, D = q.shape
    ps = k_pages.shape[2]
    pps = page_indices.shape[1]
    kernel = functools.partial(_kernel_quant, page_size=ps,
                               pages_per_seq=pps, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # lengths + page table + k/v page scales
        grid=(B, KV),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, kv, lens, tbl, ks, vs: (b, kv, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # K pool stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, kv, lens, tbl, ks, vs:
                               (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((pps * ps, D), k_pages.dtype),
            pltpu.VMEM((pps * ps, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
            interpret=_interpret(),
        )(jnp.asarray(lengths, jnp.int32),
          jnp.asarray(page_indices, jnp.int32),
          jnp.asarray(k_scales, jnp.float32),
          jnp.asarray(v_scales, jnp.float32), q, k_pages, v_pages)


def paged_decode_quant(q, k_pages, v_pages, lengths, page_indices,
                       k_scales, v_scales, scale=None):
    """Fused paged-decode attention over an int8 page pool.

    Same layout contract as :func:`paged_decode` with int8 pools plus
    per-page f32 scales ``[KV, P]`` (one per (kv-head, page), kept with
    the page table by PagedKVCache).
    """
    B, H, D = q.shape
    KV = k_pages.shape[0]
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, KV, H // KV, D)
    out = _call_quant(qg, k_pages, v_pages, lengths, page_indices,
                      k_scales, v_scales, float(scale))
    return out.reshape(B, H, D)


def supported(head_dim, page_size, on_tpu):
    """Shape gate for the compiled (non-interpret) kernel: D must tile
    to 128 lanes and a page to 8 sublanes.  The 8 holds for a bf16
    pool too, although bf16 packs 16 rows to a tile: on the v5e
    (jax 0.9) page_size 8 with a bf16 pool compiles and agrees with
    the dense reference (PERF.md "Bring-up on v5e") — the DMA lands a
    page at a static half-tile offset without complaint.  Off-TPU the
    interpreter imposes no tiling, but serving takes the dense path
    there (kernel-in-interpreter is test machinery, not a fast path)."""
    if not on_tpu:
        return False
    return head_dim % 128 == 0 and page_size % 8 == 0


def supported_quant(head_dim, page_size, on_tpu):
    """Gate for the int8-page kernel: int8 sublane tiling is 32, so the
    per-page DMA slices need page_size % 32 == 0 (vs 8 for the f32/bf16
    pools)."""
    if not on_tpu:
        return False
    return head_dim % 128 == 0 and page_size % 32 == 0


def paged_decode_spmd_rule(mesh, q_spec, k_spec, v_spec, len_spec,
                           tbl_spec, layer_spec=None, start_spec=None,
                           base_spec=None):
    """SPMD rule: shard the batch dim (the grid — programs are
    independent per sequence) and/or the head dim (a program takes
    whatever KV heads its shard holds — the pools' KV axis must carry
    the same sharding); D and the page axes are kernel-internal and
    must be replicated.  Output follows q."""
    return tuple(q_spec)[:2] + (None,)


def paged_decode_quant_spmd_rule(mesh, q_spec, k_spec, v_spec, len_spec,
                                 tbl_spec, ks_spec, vs_spec):
    """Same sharding story as :func:`paged_decode_spmd_rule`; the scale
    tables must carry the pools' KV sharding and are otherwise
    kernel-internal."""
    return tuple(q_spec)[:2] + (None,)


_HANDLE = None
_HANDLE_QUANT = None


def handle():
    """Custom-op handle (lazy — registration is global).  Registered as
    ``fused_paged_decode``: the dense fallback already owns the dynamic
    op name ``paged_decode_attention`` via ``cached_apply``, and custom
    ops must not shadow an existing name."""
    global _HANDLE
    if _HANDLE is None:
        from ...utils.cpp_extension import register_custom_op

        _HANDLE = register_custom_op(
            "fused_paged_decode", paged_decode,
            static_argnames=("scale",),
            spmd_rule=paged_decode_spmd_rule)
    return _HANDLE


def handle_quant():
    """Custom-op handle for the int8-page kernel, registered as
    ``fused_paged_decode_quant`` (same lazy-global pattern)."""
    global _HANDLE_QUANT
    if _HANDLE_QUANT is None:
        from ...utils.cpp_extension import register_custom_op

        _HANDLE_QUANT = register_custom_op(
            "fused_paged_decode_quant", paged_decode_quant,
            static_argnames=("scale",),
            spmd_rule=paged_decode_quant_spmd_rule)
    return _HANDLE_QUANT
