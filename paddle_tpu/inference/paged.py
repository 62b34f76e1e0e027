"""Paged KV cache + decode attention for serving.

Reference: ``paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu``
(block/paged KV cache with a block table per sequence) and
``masked_multihead_attention`` (single-token decode attention against a
length-masked cache), the two kernels behind the reference Predictor's
continuous-batching serving path.

TPU-native: the page pool is one static [layers, n_kv, num_pages,
page_size, d] array for keys and one for values (XLA-friendly fixed
shape — page capacity plays the role of the reference's pre-allocated
block pool), the block table is a host-side free-list (allocation is
control plane, not compute), decode attention runs a Pallas TPU kernel
over the page pool, addressed by layer in place (dense gather fallback
off-TPU), and every write — a prefill span, a decode step's token —
patches whole pages of the donated pool by row of its flat view
(:func:`_write_span`, :func:`_put_token`), so no pool and no layer of
one is ever copied.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs as _obs
from ..analysis import CountedJit
from ..core.tensor import Tensor
from ..ops import quant as _quant
from ..ops import registry as _registry
from ..testing import faults as _faults

_op = _registry.cached_apply


def _on_tpu():
    return jax.default_backend() == "tpu"


# -- decode attention ops ----------------------------------------------


def masked_multihead_attention(q, k_cache, v_cache, lengths, name=None):
    """Single-token decode attention against a dense cache (reference
    masked_multihead_attention_kernel).

    q: [B, H, D]; k_cache/v_cache: [B, KV, T, D]; lengths: [B] valid
    token counts.  Returns [B, H, D].  Supports GQA (H % KV == 0).
    """

    def fn(q, kc, vc, lens):
        B, H, D = q.shape
        KV, T = kc.shape[1], kc.shape[2]
        g = H // KV
        qg = q.reshape(B, KV, g, D)
        logits = jnp.einsum("bkgd,bktd->bkgt", qg.astype(jnp.float32),
                            kc.astype(jnp.float32)) / np.sqrt(D)
        mask = jnp.arange(T)[None, None, None, :] < \
            lens[:, None, None, None]
        logits = jnp.where(mask, logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgt,bktd->bkgd", p, vc.astype(jnp.float32))
        return out.reshape(B, H, D).astype(q.dtype)

    wrap = isinstance(q, Tensor)
    out = _op("masked_multihead_attention", fn,
              q if wrap else Tensor(jnp.asarray(q)),
              Tensor(jnp.asarray(k_cache._data if isinstance(k_cache, Tensor)
                                 else k_cache)),
              Tensor(jnp.asarray(v_cache._data if isinstance(v_cache, Tensor)
                                 else v_cache)),
              Tensor(jnp.asarray(lengths._data if isinstance(lengths, Tensor)
                                 else lengths)))
    return out if wrap else out._data


def _window_attention(q, kc, vc, lengths, starts=None, scale=None):
    """Decode attention over gathered windows, float32: q [B, H, D];
    kc/vc [B, KV, T, D], each sequence's window laid dense; lengths [B]
    keys each query reads, from ``starts`` [B] on where given; the scores
    times ``scale`` (``1 / sqrt(D)`` unless given)."""
    B, H, D = q.shape
    KV, T = kc.shape[1], kc.shape[2]
    g = H // KV
    qg = q.reshape(B, KV, g, D)
    logits = jnp.einsum("bkgd,bktd->bkgt", qg.astype(jnp.float32),
                        kc.astype(jnp.float32))
    logits = logits / np.sqrt(D) if scale is None else logits * scale
    mask = jnp.arange(T)[None, None, None, :] < \
        lengths[:, None, None, None]
    if starts is not None:
        mask &= jnp.arange(T)[None, None, None, :] >= \
            starts[:, None, None, None]
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", p, vc.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)


def _dense_paged_attention(q, k_pages, v_pages, lengths, page_indices,
                           scale=None):
    """Reference semantics of the Pallas kernel, in plain XLA ops —
    the off-TPU fallback and the parity oracle for tests.

    q [B, H, D]; k/v_pages [KV, P, ps, D]; page_indices [B, pages_per_seq].
    """
    B, _, D = q.shape
    KV, _, ps, _ = k_pages.shape
    T = page_indices.shape[1] * ps
    # gather each sequence's pages -> dense [B, KV, T, D]
    kc = jnp.swapaxes(k_pages[:, page_indices], 0, 1)  # [B, KV, pps, ps, D]
    vc = jnp.swapaxes(v_pages[:, page_indices], 0, 1)
    return _window_attention(q, kc.reshape(B, KV, T, D),
                             vc.reshape(B, KV, T, D), lengths, scale=scale)


def _dense_pool_attention(q, k_pool, v_pool, lengths, page_indices, layer,
                          starts=None, bases=None, scale=None):
    """:func:`_dense_paged_attention` over layer ``layer`` (an int32
    scalar, traced or not) of the whole pools ``[L, KV, P, ps, D]``: each
    sequence's window is gathered by row from the pool viewed flat, so no
    layer is sliced out of it (a copy of that layer on the TPU).  A
    window layer gives ``starts`` and ``bases`` as the fused kernel takes
    them (``ops/pallas_kernels/paged_decode.py``)."""
    B, _, D = q.shape
    KV, ps = k_pool.shape[1], k_pool.shape[3]
    T = page_indices.shape[1] * ps
    rows = _rows(k_pool.shape, layer, page_indices)       # [B, KV, pps]
    if starts is not None:
        lengths, starts = lengths - bases, starts - bases
    return _window_attention(q, _flat(k_pool)[rows].reshape(B, KV, T, D),
                             _flat(v_pool)[rows].reshape(B, KV, T, D),
                             lengths, starts, scale)


def _dense_paged_attention_q(q, k_pages, v_pages, lengths, page_indices,
                             k_scales, v_scales, scale=None):
    """Int8-page analog of ``_dense_paged_attention`` — dequantize the
    GATHERED window (never the whole pool) with the per-page scales,
    then the same f32 einsum/softmax/einsum.  The off-TPU fallback and
    the parity oracle for the quant kernel."""
    B, H, D = q.shape
    KV, _, ps, _ = k_pages.shape
    pages_per_seq = page_indices.shape[1]
    T = pages_per_seq * ps
    kc = jnp.swapaxes(k_pages[:, page_indices], 0, 1)  # [B, KV, pps, ps, D]
    vc = jnp.swapaxes(v_pages[:, page_indices], 0, 1)
    ksc = jnp.swapaxes(k_scales[:, page_indices], 0, 1)  # [B, KV, pps]
    vsc = jnp.swapaxes(v_scales[:, page_indices], 0, 1)
    kc = kc.astype(jnp.float32) * ksc[..., None, None]
    vc = vc.astype(jnp.float32) * vsc[..., None, None]
    return _window_attention(q, kc.reshape(B, KV, T, D),
                             vc.reshape(B, KV, T, D), lengths, scale=scale)


def _select_impl(head_dim, page_size):
    """Resolve the decode-attention implementation.

    ``PT_PAGED_IMPL`` ∈ {auto, pallas, stock, dense} forces a path
    (how the CPU tests force the Pallas kernel in interpret mode);
    ``auto`` prefers the self-authored fused kernel when its shape gate
    passes, then the stock flash-style kernel, then the dense jnp
    gather.  The gate is load-bearing: a
    shape Mosaic refuses raises at compile time INSIDE a serving step,
    which the scheduler turns into one FAILED request after another —
    so ``auto`` must never pick a kernel for a shape it cannot
    compile."""
    import os

    from ..ops.pallas_kernels import paged_decode as _fused

    impl = os.environ.get("PT_PAGED_IMPL", "auto").lower()
    if impl not in ("auto", "pallas", "stock", "dense"):
        raise ValueError(
            f"PT_PAGED_IMPL={impl!r}: expected auto|pallas|stock|dense")
    if impl != "auto":
        return impl
    from ..ops import autotune as _autotune

    if _fused.supported(head_dim, page_size, _on_tpu()):
        # measured choice between the two compiled kernels, cached per
        # (device, shape); defaults to the fused kernel untuned
        return _autotune.lookup(
            "paged_decode_impl", (head_dim, page_size),
            default="pallas")
    if _on_tpu() and head_dim % 128 == 0:
        return "stock"
    return "dense"


def paged_decode_attention(q, k_pages, v_pages, lengths, page_indices,
                           pages_per_compute_block=4,
                           k_scales=None, v_scales=None, layer=None,
                           starts=None, bases=None, scale=None):
    """Decode attention over the page pool.  On TPU this is the
    self-authored fused kernel (``ops/pallas_kernels/paged_decode.py``:
    per sequence a loop over 256-key blocks of the LIVE pages, every
    KV head of a page in one DMA, double-buffered into VMEM, an online
    softmax across blocks — work by the length, not the window; a row
    of length 0 returns zeros) or
    the stock flash-style ``paged_attention`` kernel; elsewhere the
    dense-gather fallback jit-cached through the op registry.  Routing
    is overridable via ``PT_PAGED_IMPL`` (see ``_select_impl``).
    Returns a Tensor iff ``q`` is a Tensor.

    ``k_pages`` / ``v_pages`` are one layer's pool ``[KV, P, ps, D]``,
    or, with ``layer`` (an int32 scalar, traced inside a scan over
    layers), the WHOLE pools ``[L, KV, P, ps, D]``, addressed in place:
    the fused kernel reads ``pool[layer, kv, page]`` and the dense path
    gathers the windows by row of the flat pool, so neither slices a
    layer out.  Only the stock kernel, which takes one layer's pool,
    gets a ``dynamic_index_in_dim`` slice — a copy of that layer on the
    TPU, the price of ``PT_PAGED_IMPL=stock`` (PERF.md section 7).

    ``starts`` / ``bases`` [B] make the call a WINDOW layer's (with
    ``layer``): each sequence reads keys ``[starts, lengths)`` through a
    table whose first entry stands for token ``bases`` (the fused kernel's
    contract; the dense path masks the same keys).  The stock kernel and
    the int8 pool have no such inlet and refuse.

    ``scale`` multiplies the float32 scores on every path (a Python
    number, part of the program): ``1 / sqrt(D)`` unless given.  A caller
    whose queries arrive scaled, or whose ``D`` is wider than the model's
    head (heads folded onto the lanes), gives its own.

    ``k_scales``/``v_scales`` [KV, P] select the int8-page path
    (``PT_QUANT=int8``): the fused quant kernel when its (stricter)
    shape gate passes, else the dense dequantize-the-gather fallback —
    the stock kernel has no scale inlet, so quant never routes there.
    """
    wrap = isinstance(q, Tensor)
    q = q._data if wrap else jnp.asarray(q)
    lengths = jnp.asarray(lengths, jnp.int32)
    page_indices = jnp.asarray(page_indices, jnp.int32)

    windowed = starts is not None
    if windowed and (layer is None or k_scales is not None):
        raise ValueError("a window layer is attended in the whole plain "
                         "pools, its layer named")
    if k_scales is not None:
        from ..ops.pallas_kernels import paged_decode as _fused

        if layer is not None:
            raise ValueError("the int8 pool is attended a layer at a time")
        impl = _select_impl(q.shape[-1], k_pages.shape[2])
        if impl == "pallas" and (
                _fused.supported_quant(q.shape[-1], k_pages.shape[2],
                                       _on_tpu())
                or not _on_tpu()):
            out = _fused.handle_quant()(
                Tensor(q), Tensor(jnp.asarray(k_pages)),
                Tensor(jnp.asarray(v_pages)), Tensor(lengths),
                Tensor(page_indices),
                Tensor(jnp.asarray(k_scales, jnp.float32)),
                Tensor(jnp.asarray(v_scales, jnp.float32)), scale=scale)
        else:
            out = _op("paged_decode_attention_q",
                      _dense_paged_attention_q,
                      Tensor(q), Tensor(jnp.asarray(k_pages)),
                      Tensor(jnp.asarray(v_pages)), Tensor(lengths),
                      Tensor(page_indices),
                      Tensor(jnp.asarray(k_scales, jnp.float32)),
                      Tensor(jnp.asarray(v_scales, jnp.float32)),
                      scale=scale)
        return out if wrap else out._data

    impl = _select_impl(q.shape[-1], k_pages.shape[-2])
    args = [Tensor(q), Tensor(jnp.asarray(k_pages)),
            Tensor(jnp.asarray(v_pages)), Tensor(lengths),
            Tensor(page_indices)]
    if layer is not None:
        layer = jnp.asarray(layer, jnp.int32)
        args.append(Tensor(layer))
    if windowed:
        args += [Tensor(jnp.asarray(starts, jnp.int32)),
                 Tensor(jnp.asarray(bases, jnp.int32))]

    if impl == "pallas":
        from ..ops.pallas_kernels import paged_decode as _fused

        out = _fused.handle()(*args, scale=scale)
        return out if wrap else out._data
    if impl == "dense":
        name, fn = (("paged_decode_attention", _dense_paged_attention)
                    if layer is None else
                    ("paged_decode_attention_window", _dense_pool_attention)
                    if windowed else
                    ("paged_decode_attention_pool", _dense_pool_attention))
        out = _op(name, fn, *args, scale=scale)
        return out if wrap else out._data
    if windowed:
        raise NotImplementedError(
            "PT_PAGED_IMPL=stock: jax's paged_attention kernel reads every "
            "key from token 0 and cannot attend a window layer")
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention,
    )

    if layer is not None:
        k_pages, v_pages = (
            jax.lax.dynamic_index_in_dim(p, layer, 0, keepdims=False)
            for p in (k_pages, v_pages))

    blk = min(pages_per_compute_block, page_indices.shape[1])
    while page_indices.shape[1] % blk:
        blk -= 1
    # The stock kernel mixes int32/int64 under global x64 mode — trace
    # it x64-off (same guard as the flash-attention wrappers).  It also
    # applies NO logits scaling: pre-scale q (by 1/sqrt(D) unless told).
    q = q / np.sqrt(q.shape[-1]) if scale is None else q * scale
    with jax.enable_x64(False):
        out = paged_attention(
            jnp.asarray(q), jnp.asarray(k_pages),
            jnp.asarray(v_pages), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(page_indices, jnp.int32),
            pages_per_compute_block=blk)
    return Tensor(out) if wrap else out


# -- the pool's device-side writers --------------------------------------


def _flat(pool):
    """A page pool ``[A, KV, pages, page_size, W]`` as rows of whole
    pages ``[A * KV * pages, page_size, W]`` (a bitcast), so that one
    gather or scatter by row reaches any layer and head: slicing a layer
    out first is a copy of that layer's pool on the TPU."""
    return pool.reshape(-1, *pool.shape[3:])


def _rows(pool_shape, layer, pids):
    """Row of page ``pids[...]`` of every KV head of ``layer`` in the
    flat pool: ``[..., KV]`` -> inserted as axis 1."""
    _, KV, pages = pool_shape[:3]
    base = (layer * KV + jnp.arange(KV, dtype=pids.dtype)) * pages
    return base.reshape((1, KV) + (1,) * (pids.ndim - 1)) + pids[:, None]


def _put_token(flat, pool_shape, layer, pids, offs, x):
    """Write one token per sequence into layer ``layer`` of the flat
    pool: x [S, KV, W] goes to slot ``offs[s]`` of page ``pids[s]`` (a
    page id of ``pages`` or more is dropped).  Whole pages are read,
    patched and written back: a row per token is a sub-tile write, for
    which the TPU compiler re-lays the whole pool (PERF.md section 6,
    PR 27).  No two sequences may name the same page: each would write
    back its own patch of it, and one would win."""
    ps = pool_shape[3]
    rows = jnp.where((pids < pool_shape[2])[:, None],
                     _rows(pool_shape, layer, pids), flat.shape[0])
    old = flat.at[rows].get(mode="clip")                  # [S, KV, ps, W]
    here = jnp.arange(ps, dtype=offs.dtype)[None, :] == offs[:, None]
    new = jnp.where(here[:, None, :, None],
                    x[:, :, None, :].astype(flat.dtype), old)
    return flat.at[rows].set(new, mode="drop")


def _past_of(pool, layer, pids, dtype):
    """Pages ``pids`` (int32 ``[n]``) of layer ``layer`` laid dense,
    ``[KV, n * page_size, D]``: the past a prefill chunk attends to,
    gathered inside its program by row of the flat pool (slicing the
    layer out first copies that layer's pool on the TPU).  An int8 pool
    is ``(pages, scales)``: the pages' scales are gathered the same way
    and the window dequantized to ``dtype``; a plain pool keeps its own
    dtype.  Positions past the sequence's length are garbage and must be
    masked by the consumer."""
    pages, scales = pool if isinstance(pool, tuple) else (pool, None)
    KV, D = pages.shape[1], pages.shape[4]
    rows = _rows(pages.shape, layer, pids).T              # [KV, n]
    got = _flat(pages).at[rows].get(mode="clip")          # [KV, n, ps, D]
    if scales is not None:
        got = _quant.kv_dequant(
            got, scales.reshape(-1).at[rows].get(mode="clip"), dtype)
    return got.reshape(KV, -1, D)


def _write_span(kp, vp, k, v, pids, offs):
    """Write a token span's K and V, ``[L, KV, T, D]``, into both pools
    in ONE program.  The pools arrive donated (see
    :attr:`PagedKVCache.writer`), so the pages are patched in place and
    nothing else of the pool moves; the shape is keyed on ``T`` alone,
    never on the page ids or the position.

    An int8 pool is ``(pages, scales)`` and takes the page id and
    in-page slot of every token, int32 ``[T]``
    (``ops.quant.kv_write``).  A plain pool takes ``pids`` = the ids of
    the pages the span touches, in order, padded with ``num_pages``
    (dropped) to the ``(T - 1) // page_size + 2`` a span of ``T`` can
    touch, and ``offs`` = the first token's in-page slot: the touched
    pages are read, the span laid over them at ``offs``, and whole
    ``(page_size, head_dim)`` tiles written back.  A row per token is a
    sub-tile write, for which the TPU compiler re-lays the whole pool
    before and after the scatter (PERF.md section 6, PR 27).

    A latent pool (``PagedKVCache(latent=True)``) is the key pool alone:
    ``vp`` and ``v`` are ``None`` and stay so.

    A cache of several layer groups gives LISTS, one entry a group, of
    pools, spans, page ids and slots: every group's pages are patched in
    this one program, each by its own table's ids."""
    if isinstance(kp, list):
        done = [_write_span(*one) for one in zip(kp, vp, k, v, pids, offs)]
        return [d[0] for d in done], [d[1] for d in done]
    if isinstance(kp, tuple):
        return (_quant.kv_write(*kp, pids, offs, k),
                _quant.kv_write(*vp, pids, offs, v))

    if vp is None:
        return _write_rows(kp, k, pids, offs), None

    def put(pool, x):
        old = pool.at[:, :, pids].get(mode="clip")  # [L, KV, n, ps, D]
        L, KV, n, ps, D = old.shape
        span = jax.lax.dynamic_update_slice_in_dim(
            old.reshape(L, KV, n * ps, D), x.astype(pool.dtype), offs,
            axis=2)
        return pool.at[:, :, pids].set(span.reshape(old.shape),
                                       mode="drop")

    return put(kp, k), put(vp, v)


def _write_rows(pool, x, pids, offs):
    """:func:`_write_span`'s patch for a latent pool ``[L, 1, pages,
    page_size, W]``, x ``[L, 1, T, W]``: the touched pages of every layer
    are read, patched and written back BY ROW OF THE FLAT POOL, as
    :func:`_put_token` does.  Indexed as ``pool.at[:, :, pids]`` a row of
    more than 128 lanes makes the TPU compiler split the WHOLE pool by
    lanes into two copies (2 GB of temporaries and 10 ms a chunk at 640
    lanes; PERF.md section 6, PR 32)."""
    L, _, pages, ps, W = pool.shape
    flat = _flat(pool)
    rows = jnp.where((pids < pages)[None, :],
                     jnp.arange(L, dtype=pids.dtype)[:, None] * pages
                     + pids[None, :], flat.shape[0])            # [L, n]
    old = flat.at[rows].get(mode="clip")                        # [L, n, ps, W]
    span = jax.lax.dynamic_update_slice_in_dim(
        old.reshape(L, -1, W), x[:, 0].astype(pool.dtype), offs, axis=1)
    return flat.at[rows].set(span.reshape(old.shape),
                             mode="drop").reshape(pool.shape)


# -- block-table cache manager ------------------------------------------


class LayerGroup(NamedTuple):
    """The spec of a run of layers that keep their keys alike: ``n_layers``
    layers over a pool of ``num_pages`` pages; ``window`` None for FULL
    attention (every token is kept) or w for a SLIDING window (a query
    sees the last w keys, itself included; pages wholly behind the window
    are released); ``pages_per_seq`` the width of a sequence's table row
    (for a window group the pages of ``window + the longest span written
    at once``, plus one for misalignment; ``num_pages // max_seqs`` where
    none is given)."""

    n_layers: int
    num_pages: int
    window: int | None = None
    pages_per_seq: int | None = None


class _Group:
    """One layer group's state: its pools, its free list, its refcounts
    and its page-table rows.  ``base[seq]`` is the token a row's first
    entry stands for: 0 in a full group, the first token of the oldest
    page kept in a window group (a multiple of the page size)."""

    def __init__(self, spec, shape, dtype, max_seqs, max_pages_per_seq,
                 quant, latent):
        self.n_layers, self.num_pages = spec.n_layers, spec.num_pages
        self.window = spec.window
        self.max_pages_per_seq = max_pages_per_seq
        if quant == "int8":
            # int8 pages + one f32 scale per (layer, kv-head, page),
            # kept alongside the page table: a page's scale moves,
            # copies, and frees with the page.
            self.k_pages = jnp.zeros(shape, jnp.int8)
            self.v_pages = jnp.zeros(shape, jnp.int8)
            self.k_scales = jnp.zeros(shape[:3], jnp.float32)
            self.v_scales = jnp.zeros(shape[:3], jnp.float32)
        else:
            self.k_pages = jnp.zeros(shape, dtype)
            self.v_pages = None if latent else jnp.zeros(shape, dtype)
            self.k_scales = None
            self.v_scales = None
        self._free = list(range(spec.num_pages - 1, -1, -1))
        # page table: [max_seqs, max_pages_per_seq] int32; -1 = unset
        # (page id 0 is valid, so 0 cannot double as the sentinel)
        self.page_table = np.full((max_seqs, max_pages_per_seq), -1,
                                  np.int32)
        self.base = np.zeros((max_seqs,), np.int32)
        # per-page owner count: slots referencing it + the prefix index
        self.page_refs = np.zeros((spec.num_pages,), np.int32)
        #: pages dereferenced behind the window, a running sum
        self.released = 0


def _of_first_group(name):
    """A cache attribute that speaks of its first layer group: what every
    caller of a cache of one group reads and writes."""
    return property(lambda self: getattr(self.groups[0], name),
                    lambda self, value: setattr(self.groups[0], name, value))



class PagedKVCache:
    """Block-table KV cache (reference block_multi_head_attention's
    pre-allocated block pool + per-sequence block table).

    The pools are [L, KV, num_pages, page_size, D] device arrays; page
    allocation is a host-side free list (control plane).  Sequences are
    dense slots 0..max_seqs-1 with a fixed-size page table row each —
    static shapes end-to-end, so every compute step is one cached XLA
    program.

    Pages are REFCOUNTED (prefix-cache sharing, r11): a page is either
    on the free list (refcount 0) or held by one or more owners — slot
    page-table rows and/or the radix prefix index.  A page with
    refcount > 1 is read-only; every in-place write path goes through
    :meth:`make_writable`, which copy-on-writes a shared page into a
    fresh exclusively-owned one.  ``free()`` decrements instead of
    returning pages to the pool, so shared prefix pages survive the
    sequences that used them.  With no prefix cache attached every
    refcount is 0/1 and the behavior is bit-identical to the r10 code.

    The pools have ONE owner, this object's attributes.  Every program
    that writes pages — :attr:`writer` for a prefill span, the
    executor's decode and verify programs for their own tokens — takes
    them DONATED (:meth:`pools`) and its outputs replace them at once
    (:meth:`set_pools`), so a write patches pages in place instead of
    copying a pool.  A donated array is deleted: read ``k_pages`` /
    ``v_pages`` afresh for every use and keep none across a write.

    **A latent pool** (``latent=True``) is the same cache holding ONE row
    a token a layer and no values: the compressed KV of a latent
    attention (MLA) layer, ``[L, 1, num_pages, page_size, row]`` in
    ``k_pages`` with ``v_pages`` ``None``.  The page table, the
    allocator, ``reserve`` / ``trim`` / ``make_writable`` and the one
    donated ``serve.kv_write`` a span are this class's own; only the
    entry points that read K and V as heads (``append``, ``attend``, an
    int8 pool) are refused.

    **Layer groups** (``groups=[LayerGroup, ...]``): layers that keep
    their keys differently live in pools of their own, ``[L_g, KV,
    pages_g, page_size, D]``, each with its free list, its refcounts and
    its page-table rows, behind the ONE allocator interface: a slot, its
    length, ``reserve`` / ``_ensure_capacity`` (all groups or none),
    ``free``, and one donated ``serve.kv_write`` a span that patches every
    group's pages (``pools()`` then gives lists, one entry a group).  A
    FULL group keeps every token.  A WINDOW group keeps, per sequence,
    the pages that hold a token the next query can still see — tokens
    ``>= length + 1 - window`` — and what the span in flight writes:
    after every ``write_at`` and every :meth:`release` (a decode step)
    the pages wholly behind the window are dereferenced to the group's
    free list, on the host, after the program that last reads them was
    dispatched (programs run in dispatch order), and the row is shifted
    left so that its first entry is the oldest page kept
    (``groups[g].base[seq]`` is the token it stands for).  A released
    page is gone: what attaches pages by reference, rolls a length back
    or reads K and V of every token (``attach``, ``trim``, ``append``,
    ``attend``, the sharded writes, int8, latent) is refused for a cache
    of more than one group.  ``k_pages``, ``page_table``, ``num_pages``,
    ``free_pages`` and the other single-pool attributes speak of the FIRST
    group; a cache built without ``groups`` is one full group and behaves
    as it always has.
    """

    #: the first group's state under the names a cache of one group has
    #: always had
    k_pages = _of_first_group("k_pages")
    v_pages = _of_first_group("v_pages")
    k_scales = _of_first_group("k_scales")
    v_scales = _of_first_group("v_scales")
    page_table = _of_first_group("page_table")
    page_refs = _of_first_group("page_refs")
    num_pages = _of_first_group("num_pages")
    max_pages_per_seq = _of_first_group("max_pages_per_seq")
    _free = _of_first_group("_free")

    def __init__(self, n_layers, n_kv_heads, head_dim, num_pages,
                 page_size=16, max_seqs=8, dtype=jnp.bfloat16,
                 max_pages_per_seq=None, quant=None, latent=False,
                 groups=None):
        if groups is None:
            groups = [LayerGroup(n_layers, num_pages, None,
                                 max_pages_per_seq)]
        elif (sum(g.n_layers for g in groups) != n_layers
              or groups[0].num_pages != num_pages):
            raise ValueError(
                "the groups' layers must add up to n_layers, and "
                "num_pages is the first group's pool")
        self.n_layers = n_layers
        self.page_size = page_size
        self.max_seqs = max_seqs
        #: what consumers compute in — the pool storage dtype in the
        #: plain mode, the requested float dtype when the pool is int8.
        self.compute_dtype = dtype
        self.quant = _quant.quant_mode(quant)
        self.latent = bool(latent)
        if self.latent and (n_kv_heads != 1 or self.quant != "none"):
            raise NotImplementedError(
                "a latent pool is one row a token (n_kv_heads=1) in the "
                "compute dtype: it has no heads to split and no int8 form")
        if len(groups) > 1 and (self.latent or self.quant != "none"):
            raise NotImplementedError(
                "a cache of several layer groups holds K and V heads in "
                "the compute dtype: it has no latent and no int8 form")
        # Per-seq budget decoupled from the pool size: a serving pool is
        # deliberately OVERSUBSCRIBED (num_pages < max_seqs * budget) so
        # admission pressure is real and preemption has something to do.
        self.groups = [
            _Group(g, (g.n_layers, n_kv_heads, g.num_pages, page_size,
                       head_dim), dtype, max_seqs,
                   (g.num_pages // max_seqs if g.pages_per_seq is None
                    else g.pages_per_seq), self.quant, self.latent)
            for g in groups]
        self.lengths = np.zeros((max_seqs,), np.int32)
        self._active = [False] * max_seqs
        self.cow_count = 0         # copy-on-write page copies performed
        # optional callable(shortfall_pages) that tries to free pages
        # (the prefix cache's LRU eviction); consulted before any
        # "pool exhausted" raise
        self.reclaimer = None
        #: the device-side writer (``_write_span``), pools donated
        self.writer = CountedJit(_write_span, name="serve.kv_write",
                                 donate_argnums=(0, 1))

    def pools(self):
        """The jit-argument form of the KV pools: the bare page arrays
        in the plain mode, or ``(pages, scales)`` tuples on an int8
        pool — jit flattens the tuple, donation covers every leaf, and
        the programs branch on the pytree form at trace time.  A cache
        of several layer groups gives two LISTS, one entry a group."""
        if len(self.groups) > 1:
            return ([g.k_pages for g in self.groups],
                    [g.v_pages for g in self.groups])
        if self.quant == "int8":
            return ((self.k_pages, self.k_scales),
                    (self.v_pages, self.v_scales))
        return self.k_pages, self.v_pages

    def set_pools(self, kps, vps) -> None:
        """Store a program's updated pool outputs (the form
        :meth:`pools` gave it) back on the cache."""
        if len(self.groups) > 1:
            for g, kp, vp in zip(self.groups, kps, vps):
                g.k_pages, g.v_pages = kp, vp
        elif self.quant == "int8":
            (self.k_pages, self.k_scales), \
                (self.v_pages, self.v_scales) = kps, vps
        else:
            self.k_pages, self.v_pages = kps, vps

    # -- control plane (host) ------------------------------------------

    def allocate(self) -> int:
        """Claim a sequence slot."""
        for s in range(self.max_seqs):
            if not self._active[s]:
                self._active[s] = True
                self.lengths[s] = 0
                return s
        raise RuntimeError("no free sequence slots (continuous batching "
                           "is full) — free() a finished sequence first")

    def free(self, seq: int) -> None:
        """Release a sequence's pages — every ASSIGNED slot, not just
        length-covered ones, so reserved-but-unwritten pages (e.g. from
        a failed batch step) are recovered too.  A page returns to the
        free list only when its LAST owner lets go: pages shared with
        the prefix index (refcount > 1) merely drop a reference."""
        for g in self.groups:
            for pid in g.page_table[seq]:
                if pid >= 0:
                    self._deref(int(pid), g)
            g.page_table[seq] = -1
            g.base[seq] = 0
        self.lengths[seq] = 0
        self._active[seq] = False

    # -- refcounted page pool --------------------------------------------

    def _pop_page(self, g=None) -> int:
        g = self.groups[0] if g is None else g
        pid = g._free.pop()
        g.page_refs[pid] = 1
        return pid

    def _deref(self, pid: int, g=None) -> None:
        g = self.groups[0] if g is None else g
        g.page_refs[pid] -= 1
        if g.page_refs[pid] == 0:
            g._free.append(pid)
        elif g.page_refs[pid] < 0:
            raise AssertionError(
                f"page {pid} refcount went negative (double free)")

    def _reclaim(self, shortfall: int) -> None:
        """Ask the attached prefix cache (if any) to LRU-evict enough
        zero-refcount pages to cover ``shortfall`` — tried before any
        pool-exhausted raise, so eviction replaces preempt-and-recompute
        whenever cold cache entries are holding the pages."""
        if self.reclaimer is not None and shortfall > 0:
            self.reclaimer(shortfall)

    def attach(self, seq: int, page_ids, n_tokens: int) -> None:
        """Attach already-written pages BY REFERENCE (prefix-cache hit):
        the slot's first ``len(page_ids)`` table rows point at shared
        pages and the sequence length starts at ``n_tokens`` — prefill
        then begins at the first divergent token.  The final page may be
        partially covered (``n_tokens`` not page-aligned); the first
        write to it copy-on-writes."""
        self._one_group("attach")
        n_pages = len(page_ids)
        if n_tokens > n_pages * self.page_size:
            raise ValueError(
                f"attach: {n_tokens} tokens exceed {n_pages} pages "
                f"x {self.page_size}")
        if n_pages > self.max_pages_per_seq:
            raise RuntimeError(
                f"sequence {seq} needs {n_pages} pages > per-seq "
                f"budget {self.max_pages_per_seq}")
        for i, pid in enumerate(page_ids):
            if self.page_table[seq, i] >= 0:
                raise AssertionError(
                    f"attach over an assigned slot {i} of seq {seq}")
            self.page_table[seq, i] = int(pid)
            self.page_refs[int(pid)] += 1
        self.lengths[seq] = int(n_tokens)

    def make_writable(self, seq: int, start: int, end: int) -> None:
        """Copy-on-write guard: every page-table slot overlapping token
        positions [start, end) must be exclusively owned before an
        in-place write.  Shared pages (refcount > 1) get a fresh page
        with the prefix-resident contents copied; unshared pages are
        untouched, so with no prefix cache this is a no-op."""
        if end <= start:
            return
        ps = self.page_size
        for slot in range(start // ps, -(-end // ps)):
            pid = int(self.page_table[seq, slot])
            if pid >= 0 and self.page_refs[pid] > 1:
                self._cow(seq, slot)

    def _cow(self, seq: int, slot: int) -> None:
        self._one_group("copy-on-write")
        _faults.fire("prefix.cow", "before")
        if not self._free:
            self._reclaim(1)
        if not self._free:
            raise RuntimeError("KV page pool exhausted (copy-on-write "
                               "of a shared prefix page)")
        old = int(self.page_table[seq, slot])
        new = self._pop_page()
        # the prefix-resident slice lives below the write offset; the
        # whole-page copy is a superset (bytes past it are overwritten
        # or masked by the length)
        self.k_pages = self.k_pages.at[:, :, new].set(
            self.k_pages[:, :, old])
        if self.v_pages is not None:
            self.v_pages = self.v_pages.at[:, :, new].set(
                self.v_pages[:, :, old])
        if self.k_scales is not None:
            # a quantized page is meaningless without its scale — the
            # copy must carry both or the COW'd page dequantizes wrong
            self.k_scales = self.k_scales.at[:, :, new].set(
                self.k_scales[:, :, old])
            self.v_scales = self.v_scales.at[:, :, new].set(
                self.v_scales[:, :, old])
        self.page_table[seq, slot] = new
        self.page_refs[old] -= 1
        self.cow_count += 1
        h = _obs.handle()
        if h is not None:
            h.recorder.record("kv.cow", seq=seq, slot=slot,
                              old_page=old, new_page=new)
            h.registry.counter(
                "kv_cow_copies_total",
                "Copy-on-write duplications of shared KV pages").inc()
        _faults.fire("prefix.cow", "after")

    def _plan_missing(self, seq: int, new_len: int, g=None):
        """Slot-aware plan (-1 = unset): the list of page-table slots
        of group ``g`` (the first by default) that still need a page for
        ``seq`` to hold ``new_len`` tokens.  Idempotent across retries —
        already-assigned slots are never re-popped."""
        g = self.groups[0] if g is None else g
        need = -(-(new_len - int(g.base[seq])) // self.page_size)
        if need > g.max_pages_per_seq:
            raise RuntimeError(
                f"sequence {seq} needs {need} pages > per-seq budget "
                f"{g.max_pages_per_seq}")
        return [i for i in range(need) if g.page_table[seq, i] < 0]

    def _take(self, plans) -> None:
        """Commit ``[(group, seq, missing slots)]`` if EVERY group's free
        list covers its part, else raise with nothing changed.
        Prefix-cache eviction (the first group's) is tried first."""
        for g in self.groups:
            need = sum(len(m) for h, _, m in plans if h is g)
            if g is self.groups[0] and need > len(g._free):
                self._reclaim(need - len(g._free))
            if need > len(g._free):
                raise RuntimeError("KV page pool exhausted")
        for g, seq, missing in plans:
            for i in missing:
                g.page_table[seq, i] = self._pop_page(g)

    def _ensure_capacity(self, seq: int, new_len: int) -> None:
        self._take([(g, seq, self._plan_missing(seq, new_len, g))
                    for g in self.groups])

    def reserve(self, seqs, extra_tokens=1) -> None:
        """Batch-atomic capacity reservation: plan every sequence's
        missing slots in every layer group first, commit only if the
        WHOLE batch fits (a per-sequence loop would leak the earlier
        sequences' pages on a mid-batch failure).  Prefix-cache eviction
        is tried before giving up, so cold cached pages yield to live
        sequences.

        ``extra_tokens`` is one int for the whole batch or a per-seq
        sequence aligned with ``seqs`` (speculative decode reserves a
        clamped lookahead per sequence)."""
        seqs = list(seqs)
        extras = (list(extra_tokens)
                  if isinstance(extra_tokens, (list, tuple, np.ndarray))
                  else [extra_tokens] * len(seqs))
        self._take([(g, s, self._plan_missing(
            s, int(self.lengths[s]) + int(e), g))
            for g in self.groups for s, e in zip(seqs, extras)])

    def release(self, seqs) -> int:
        """Dereference, in every WINDOW group, the pages of ``seqs`` that
        lie wholly behind the window — every token of them before
        ``length + 1 - window``, which no later query sees — and shift
        each row left so that its first entry is the oldest page kept.
        Call it after the program that last reads those pages was
        dispatched (``write_at`` does, for the span it wrote; a decode
        step's executor does once the lengths have moved).  Returns the
        pages released."""
        ps, total = self.page_size, 0
        for gi, g in enumerate(self.groups):
            if g.window is None:
                continue
            freed = 0
            for seq in seqs:
                keep_from = max(0, int(self.lengths[seq]) + 1 - g.window)
                n = (keep_from - int(g.base[seq])) // ps
                if n <= 0:
                    continue
                row = g.page_table[seq]
                for pid in row[:n]:
                    if pid >= 0:
                        self._deref(int(pid), g)
                        freed += 1
                row[:-n] = row[n:]
                row[-n:] = -1
                g.base[seq] += n * ps
            if freed:
                g.released += freed
                _obs.instant("kv.release", cat="serve", group=gi,
                             pages=freed)
            total += freed
        return total

    def trim(self, seq: int) -> int:
        """Release every assigned page-table slot past the page cover of
        the sequence's CURRENT length (the rollback half of speculative
        decode: pages reserved for a draft window whose tail was
        rejected go back to the pool/refcount pool).  Returns the number
        of slots released.  Refcount-safe: a shared page merely drops
        this slot's reference."""
        self._one_group("trim")
        keep = -(-int(self.lengths[seq]) // self.page_size)
        freed = 0
        for slot in range(keep, self.max_pages_per_seq):
            pid = int(self.page_table[seq, slot])
            if pid >= 0:
                self._deref(pid)
                self.page_table[seq, slot] = -1
                freed += 1
        return freed

    # -- data plane (device) -------------------------------------------

    def prefill(self, seq: int, k, v) -> None:
        """Write a prompt's KV: k/v [L, KV, T, D]."""
        self.write_at(seq, k, v, 0)

    def write_at(self, seq: int, k, v, start: int) -> None:
        """Write a token span's KV at position ``start`` (chunked
        prefill): k/v [L, KV, T, D] covering positions
        ``start..start+T-1``.  Pages are allocated as needed; the
        sequence length becomes ``start + T``.  One dispatch of
        :attr:`writer` whatever the span's length; on an int8 pool the
        span is quantized on write (``ops.quant.kv_write``:
        scatter-max the touched pages' scales, requantize residents,
        write the new cells).  A latent pool takes its rows as ``k``
        and ``v=None``; a cache of several layer groups takes ``k`` and
        ``v`` as lists, one ``[L_g, KV, T, D]`` a group, writes them all
        in the one dispatch and then releases the pages the span left
        wholly behind a window (:meth:`release`)."""
        grouped = len(self.groups) > 1
        T = int(np.shape(k[0] if grouped else k)[2])
        self._ensure_capacity(seq, start + T)
        # shared pages in the write window are read-only: COW them
        # first (no-op when nothing is shared, i.e. no prefix cache)
        self.make_writable(seq, start, start + T)
        ps = self.page_size

        def touched(g):
            """The table entries of group ``g`` the span touches."""
            at = start - int(g.base[seq])
            return g.page_table[seq, at // ps: -(-(at + T) // ps)]

        rows = [touched(g) for g in self.groups]
        with _obs.span("kv.write", cat="serve",
                       pages=sum(len(r) for r in rows), dispatches=1):
            if self.quant == "int8":
                pos = start + np.arange(T)
                pids = self.page_table[seq][pos // ps]
                offs = (pos % ps).astype(np.int32)
                _faults.fire("quant.kv_write", "before")
            else:
                pids = []
                for g, row in zip(self.groups, rows):
                    padded = np.full(((T - 1) // ps + 2,), g.num_pages,
                                     np.int32)
                    padded[:len(row)] = row
                    pids.append(padded)
                # a window group's base is a whole page: one slot for all
                offs = [np.int32(start % ps)] * len(pids)
                if not grouped:
                    pids, offs = pids[0], offs[0]
            # the donated call comes last of what can fail, and its
            # outputs replace the deleted pools in the same statement
            self.set_pools(*self.writer(*self.pools(), k, v, pids, offs))
            if self.quant == "int8":
                _faults.fire("quant.kv_write", "after")
        self.lengths[seq] = start + T
        self.release([seq])         # nothing, where no group has a window

    def write_sharded(self, seq: int, k, v, start: int,
                      n_ranks: int) -> int:
        """Write one sequence-parallel prefill chunk's KV as
        ``n_ranks`` contiguous per-rank ranges (serve.prefill_sp):
        rank r owns positions ``start + r*(T/n) .. start +
        (r+1)*(T/n) - 1`` — the same stripes the ring-gathered
        attention computed.  Ranges land in ascending rank order, so
        the final sequence length is exactly ``start + T`` like one
        dense :meth:`write_at`; every range write is bracketed by the
        ``sp.shard`` fault point, and a raise there fails ONLY the
        bracketed request (the scheduler's serve.request isolation),
        never the pool.  Returns the number of ranges written."""
        self._one_group("write_sharded")
        T = int(np.shape(k)[2])
        if n_ranks < 1 or T % n_ranks:
            raise ValueError(
                f"sp chunk of {T} tokens does not split into "
                f"{n_ranks} equal per-rank ranges")
        cl = T // n_ranks
        for r in range(n_ranks):
            _faults.fire("sp.shard", "before")
            self.write_at(seq, k[:, :, r * cl:(r + 1) * cl],
                          v[:, :, r * cl:(r + 1) * cl], start + r * cl)
            _faults.fire("sp.shard", "after")
        return n_ranks

    def gather_shards(self, seq: int) -> int:
        """One-shot page all-gather at the prefill->decode transition
        of a sequence-parallel prefill: after it, every rank holds the
        sequence's full page set and decode runs byte-identical to the
        single-device path.  On this single-host pool the page arrays
        are already globally addressable, so the data movement itself
        is a no-op — what this models (and meters: the ``sp.gather``
        fault point plus ``sp_gather_pages_total``) is the one
        ``all_gather`` of pages a range-sharded multi-host pool pays
        HERE, once, instead of every decode step gathering across the
        mesh.  Returns the number of pages covered."""
        self._one_group("gather_shards")
        _faults.fire("sp.gather", "before")
        pages = -(-int(self.lengths[seq]) // self.page_size)
        h = _obs.handle()
        if h is not None:
            h.registry.counter(
                "sp_gather_pages_total",
                "KV pages all-gathered at sequence-parallel "
                "prefill->decode transitions",
            ).inc(pages)
        _faults.fire("sp.gather", "after")
        return pages

    def past_pages(self, seq: int, length=None, group=0):
        """The ids of the pages that cover a sequence's first ``length``
        tokens (all it holds by default), int32 ``[n]`` on the host:
        what a program needs to read that past out of the pools.  In a
        window group (``group``) the pages from the oldest one kept
        (token ``groups[group].base[seq]``) up to ``length``.  A copy,
        so that a program it was handed to never sees the table's later
        changes."""
        g = self.groups[group]
        L = int(self.lengths[seq]) if length is None else int(length)
        n = -(-(L - int(g.base[seq])) // self.page_size)
        row = g.page_table[seq, :n]
        if (row < 0).any():
            # an unset (-1) slot inside the requested length used to be
            # clipped to page 0 — silently serving another sequence's
            # KV.  That is always a caller bug: fail loudly instead.
            bad = int(np.argmax(row < 0))
            raise RuntimeError(
                f"past_pages: sequence {seq} page slot {bad} is "
                f"unset inside the requested length {L} "
                f"({n} pages) — refusing to read garbage from page 0")
        return row.copy()

    def gather_dense(self, seq: int, length=None, group=0):
        """Gather a sequence's pages into dense [L, KV, P, D] arrays
        (P = page-multiple cover of ``length``), eagerly and on the
        pool's device: the past-KV operand of the sequence-parallel
        chunk program and of the hybrid executor's, and what the cluster
        hand-off ships (the Llama-shaped chunk program reads its past
        inside the program, :func:`_past_of`).  Positions >= length are
        garbage and must be masked by the consumer.  Of a window group
        (``group``) the tokens from ``groups[group].base[seq]`` on."""
        g = self.groups[group]
        row = self.past_pages(seq, length, group)
        n = len(row)
        pids = jnp.asarray(row)
        k = g.k_pages[:, :, pids]             # [L, KV, n, ps, D]
        if self.latent:
            return k.reshape(k.shape[0], 1, n * self.page_size,
                             k.shape[4]), None
        v = g.v_pages[:, :, pids]
        if self.quant == "int8":
            _faults.fire("quant.dequant", "before")
            k = _quant.kv_dequant(k, self.k_scales[:, :, pids],
                                  self.compute_dtype)
            v = _quant.kv_dequant(v, self.v_scales[:, :, pids],
                                  self.compute_dtype)
            _faults.fire("quant.dequant", "after")
        sh = (k.shape[0], k.shape[1], n * self.page_size, k.shape[4])
        return k.reshape(sh), v.reshape(sh)

    def _one_group(self, what):
        if len(self.groups) > 1:
            raise NotImplementedError(
                f"PagedKVCache.{what}: a cache of several layer groups "
                f"releases the pages behind a window, so nothing attaches "
                f"pages by reference, rolls a length back or reads K and V "
                f"of every token through it")

    def _heads_only(self, what):
        if self.latent:
            raise NotImplementedError(
                f"PagedKVCache.{what}: a latent pool holds rows, not K "
                f"and V heads; its decode step writes and attends inside "
                f"the executor's program (server/latent_executor.py)")

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def free_slots(self) -> int:
        """Sequence slots that can still be claimed.  A WINDOW group
        bounds them too: a sequence holds at most ``max_pages_per_seq``
        of its pages whatever its length, so its pool seats ``num_pages
        // max_pages_per_seq`` sequences and no more are let in — a live
        sequence then never finds that pool exhausted."""
        free = self._active.count(False)
        for g in self.groups:
            if g.window is not None:
                free = min(free, g.num_pages // g.max_pages_per_seq
                           - self._active.count(True))
        return max(free, 0)

    def append(self, seqs, k, v) -> None:
        """Decode-step write: one new token per listed sequence.
        k/v: [L, KV, B, D] for B = len(seqs).

        Two-phase so a capacity failure mutates NOTHING: plan every
        sequence's allocation first, commit only if the whole batch
        fits (otherwise an earlier seq would record a length whose
        page slot never got written)."""
        self._heads_only("append")
        self._one_group("append")
        ps = self.page_size
        self.reserve(seqs, extra_tokens=1)  # batch-atomic
        for s in seqs:
            pos = int(self.lengths[s])
            self.make_writable(s, pos, pos + 1)
        pids, offs = [], []
        for s in seqs:
            pos = int(self.lengths[s])
            pids.append(int(self.page_table[s, pos // ps]))
            offs.append(pos % ps)
            self.lengths[s] = pos + 1
        pids = jnp.asarray(pids)
        offs = jnp.asarray(offs)
        if self.quant == "int8":
            _faults.fire("quant.kv_write", "before")
            self.k_pages, self.k_scales = _quant.kv_write(
                self.k_pages, self.k_scales, pids, offs, jnp.asarray(k))
            self.v_pages, self.v_scales = _quant.kv_write(
                self.v_pages, self.v_scales, pids, offs, jnp.asarray(v))
            _faults.fire("quant.kv_write", "after")
            return
        k = jnp.asarray(k, self.k_pages.dtype)
        v = jnp.asarray(v, self.v_pages.dtype)
        # advanced indexing: [L, KV, B, D] written at (page, offset)[B]
        self.k_pages = self.k_pages.at[:, :, pids, offs].set(k)
        self.v_pages = self.v_pages.at[:, :, pids, offs].set(v)

    def attend(self, layer: int, q, seqs,
               pages_per_compute_block=4):
        """Decode attention for one layer: q [B, H, D] over the listed
        sequences' pages."""
        self._heads_only("attend")
        self._one_group("attend")
        # clip -1 sentinels (unassigned slots beyond each length) to a
        # valid page id — the length mask excludes them from attention,
        # but gathers/kernel prefetch must stay in range
        table = jnp.asarray(np.maximum(self.page_table[seqs], 0))
        lens = jnp.asarray(self.lengths[seqs])
        return paged_decode_attention(
            q, self.k_pages[layer], self.v_pages[layer], lens, table,
            pages_per_compute_block=pages_per_compute_block,
            k_scales=(None if self.k_scales is None
                      else self.k_scales[layer]),
            v_scales=(None if self.v_scales is None
                      else self.v_scales[layer]))
