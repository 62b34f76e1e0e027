"""Fused paged-decode attention kernel (self-authored, #4).

Reference analog: ``paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu`` — single-token decode attention
against a block-table (paged) KV cache, the kernel behind the
reference's continuous-batching serving path.  The role, not the
design.

TPU design: one program per (sequence, kv-head).  The program DMAs the
pages of the sequence's block-table window that hold its tokens —
``[page_size, head_dim]`` K and V each — from the HBM page pool into
VMEM scratch (all copies started before any is waited on, so the gather
is one pipelined burst; a loop over pages, :func:`_gather_window`), then
computes the whole decode attention for that head group in VMEM:

    scores = q_group @ K_window^T * scale      [group, S_window]
    p      = softmax(scores  masked to length)
    out    = p @ V_window                      [group, head_dim]

No online-softmax machinery: a decode window is S_window = pages_per_seq
* page_size tokens, and one head's K+V window at S=1024, D=128 bf16 is
512 KB — it fits VMEM outright (same VMEM-residency argument as
``long_attention``).  GQA rides free: the q rows of one program are the
``H // KV`` query heads sharing that KV head.

What this fuses (vs ``inference/paged._dense_paged_attention``): the
jnp path materializes the gathered dense cache [B, KV, T, D] (x2) in
HBM, then runs einsum -> mask -> softmax -> einsum as separate XLA
fusions over HBM round-trips.  Here the page gather lands directly in
VMEM and every intermediate (scores, probs) lives and dies there; HBM
traffic is the theoretical floor (read each page once, write [B, H, D]
once).

Layout contract (matches PagedKVCache):
  q            [B, KV, G, D]      (G = H // KV query heads per KV head)
  k/v_pages    [L, KV, P, ps, D]  (the WHOLE pool, every layer; P = pages
                                   of a layer)
  layer        int32 scalar       the layer this call attends over
  lengths      [B]   int32        valid tokens per sequence
  page_indices [B, pps] int32     each sequence's block-table window
returns        [B, KV, G, D]

The layer is a prefetched scalar beside the lengths and the page table,
and a page's DMA reads ``pool[layer, kv, page]``: the pool stays where it
is in HBM, is only read, and nothing the size of a layer is ever sliced
out of it (on the TPU such a slice is a copy of 134 MB at the benchmark's
size).  Inside a ``lax.scan`` over layers the pool is the carry.  A pool
of one layer, ``[KV, P, ps, D]``, is the same pool with ``L = 1`` and
``layer = 0`` (:func:`paged_decode` reshapes it, a bitcast).  The int8
kernel (:func:`paged_decode_quant`) still takes one layer's pool.

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


def _kernel(len_ref, tbl_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
            v_buf, sem, *, page_size, pages_per_seq, scale):
    b = pl.program_id(0)
    kv = pl.program_id(1)
    layer = layer_ref[0]
    # Keep every scalar explicitly i32: the repo's global x64 mode turns
    # weak Python-int constants into i64 at lowering, and a mixed
    # i32/i64 divide fails StableHLO verification (interpret mode) and
    # Mosaic (compiled).
    length = len_ref[b]
    _gather_window(lambda pool, i: pool.at[layer, kv, tbl_ref[b, i]],
                   pl.cdiv(length, jnp.int32(page_size)),
                   (k_hbm, v_hbm), (k_buf, v_buf), sem,
                   page_size=page_size, pages_per_seq=pages_per_seq)

    q = q_ref[0, 0].astype(jnp.float32) * jnp.float32(scale)  # [G, D]
    k = k_buf[...].astype(jnp.float32)               # [S_window, D]
    v = v_buf[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    S = k.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], S), 1)
    s = jnp.where(col < length, s, jnp.float32(-1e30))
    m = jnp.max(s, axis=1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=1, keepdims=True)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0, 0] = o.astype(o_ref.dtype)


def _interpret():
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("scale",))
def _call(q, k_pages, v_pages, lengths, page_indices, layer, scale):
    """The jitted wrapper: the device trace names the kernel's event
    ``_call [tpu_custom_call]`` after it (the benchmark's
    ``paged_decode_roofline`` matches that name)."""
    B, KV, G, D = q.shape
    ps = k_pages.shape[3]
    pps = page_indices.shape[1]
    kernel = functools.partial(_kernel, page_size=ps, pages_per_seq=pps,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # lengths + page table + layer
        grid=(B, KV),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, kv, lens, tbl, layer: (b, kv, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # K pool stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, kv, lens, tbl, layer:
                               (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((pps * ps, D), k_pages.dtype),
            pltpu.VMEM((pps * ps, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA,
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
          jnp.asarray(layer, jnp.int32).reshape(1), q, k_pages, v_pages)


def paged_decode(q, k_pages, v_pages, lengths, page_indices, layer=None,
                 scale=None):
    """Fused paged-decode attention over the page pool.

    q [B, H, D] (H % KV == 0); lengths [B]; page_indices [B, pps];
    k/v_pages either the whole pool ``[L, KV, P, ps, D]`` with ``layer``
    (an int32 scalar, traced or not) naming the layer to attend over, or
    one layer's pool ``[KV, P, ps, D]`` with no ``layer``.  Returns
    [B, H, D].  Pure function of its arguments (no custom VJP: decode is
    inference-only).
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
    out = _call(qg, k_pages, v_pages, lengths, page_indices, layer,
                float(scale))
    return out.reshape(B, H, D)


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
                           tbl_spec, layer_spec=None):
    """SPMD rule: shard the batch dim (grid axis 0 — programs are
    independent per sequence) and/or the head dim (grid axis 1 — the
    pools' KV axis must carry the same sharding); D and the page axes
    are kernel-internal and must be replicated.  Output follows q."""
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
