"""Int8 quantized serving path — weights and KV pages (``PT_QUANT``).

Two independent compressions share this module, both gated by one env
knob validated at engine build:

* **Weights** — per-channel symmetric int8 (the LLM.int8() recipe
  without outlier splitting: decoder matmul weights are well-behaved at
  serving time).  ``quantize_linear`` packs a weight into the
  :data:`QuantizedLinear` dict ``{"qweight": int8, "scale": f32}`` that
  rides the existing checkpoint/stacked-layer pytrees (``lax.scan``
  slices the dict leaves per layer like any other stacked param).
  Per-OUTPUT-channel scales commute with the contraction, so
  ``x @ w ≈ (x_f32 @ qw_f32) * scale`` — which is exactly what lets the
  Pallas kernels keep int8 tiles in VMEM and apply the scale next to
  the MXU op (``pallas_kernels/quant_matmul.py``, and the quant
  variants of ``grouped_gemm`` / ``paged_decode``).

* **KV pages** — per-page symmetric int8 (the KIVI observation, at page
  rather than channel granularity so the scale table rides with the
  page table: one f32 per ``(layer, kv_head, page)``).  Pages are
  append-only per run of tokens but a later token can exceed the scale
  a page was quantized at, so :func:`kv_write` is
  scatter-max-then-requantize: grow the touched pages' scales to cover
  the new tokens, requantize the already-resident cells by the
  old/new ratio, then write the new cells.  All of it is plain
  ``jnp`` — traceable, so the decode/verify programs do it in-graph,
  and the same helper serves the eager ``write_at`` path.

``PT_QUANT=none`` must stay bit-exact with the unquantized engine: the
none path never routes through this module's math (dispatch happens at
trace time on the pytree type), it only pays the env read.
"""
import os
import re

import numpy as np

__all__ = [
    "quant_mode", "quantize_per_channel", "dequantize",
    "quantize_linear", "is_quantized", "qmatmul", "quantize_state_dict",
    "kv_write", "kv_dequant",
]

#: recognized PT_QUANT values; fp8 is the named next rung (ROADMAP).
MODES = ("none", "int8")

#: symmetric int8 uses the balanced range so q == -q always round-trips.
QMAX = 127.0


def quant_mode(mode=None):
    """Resolve + validate the quantization mode.

    ``mode=None`` follows ``PT_QUANT`` (default ``none``); an explicit
    argument wins, same contract as the prefix-cache/async gates.
    Raises ``ValueError`` on anything outside :data:`MODES`.
    """
    if mode is None:
        mode = os.environ.get("PT_QUANT", "none").lower()
    if mode not in MODES:
        raise ValueError(
            f"PT_QUANT={mode!r}: expected one of {'|'.join(MODES)}")
    return mode


# ---------------------------------------------------------------------------
# weights: per-channel symmetric int8


def quantize_per_channel(w, contract_axis=-2):
    """``(qweight int8, scale f32)`` with one scale per output channel.

    ``contract_axis`` is the axis the matmul reduces over (``-2`` for
    the repo's ``[..., in, out]`` weight layout, so stacked
    ``[L, in, out]`` weights get a ``[L, 1, out]`` scale for free).
    Symmetric: ``scale = amax / 127``; zero channels quantize to zeros
    with scale 0 and dequantize exactly.
    """
    import jax.numpy as jnp

    w32 = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=contract_axis, keepdims=True)
    scale = (amax / QMAX).astype(jnp.float32)
    q = jnp.round(w32 / jnp.where(scale > 0, scale, 1.0))
    q = jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)
    return q, scale


def dequantize(qweight, scale, dtype=None):
    """Inverse of :func:`quantize_per_channel` (up to rounding)."""
    import jax.numpy as jnp

    out = qweight.astype(jnp.float32) * scale
    return out if dtype is None else out.astype(dtype)


def quantize_linear(w):
    """Pack one matmul weight into the ``QuantizedLinear`` dict.

    The dict is a plain pytree — it stacks, scans, donates, and
    checkpoints exactly like the dense weight it replaces.
    """
    from ..testing import faults

    faults.fire("quant.pack", "before")
    qweight, scale = quantize_per_channel(w)
    out = {"qweight": qweight, "scale": scale}
    faults.fire("quant.pack", "after")
    return out


def is_quantized(w):
    """True when ``w`` is a ``QuantizedLinear`` dict."""
    return isinstance(w, dict) and "qweight" in w and "scale" in w


#: param-path patterns quantized by default: the llama/bert projection
#: and MLP matmuls.  Embeddings, norms, biases, and the LM head stay in
#: the checkpoint dtype — they are small, and the head dominates drift.
DEFAULT_PATTERNS = (
    r"\.(q|k|v|o)_proj\.weight$",
    r"\.(gate|up|down)_proj\.weight$",
    r"\.(query|key|value)\.weight$",
    r"\.attention\.output\.dense\.weight$",
    r"\.(intermediate|output)\.dense\.weight$",
)


def quantize_state_dict(state, patterns=DEFAULT_PATTERNS):
    """Quantize matching matmul weights of a flat ``{path: array}``
    state dict in place of the dense arrays (non-matching entries pass
    through untouched)."""
    out = {}
    for name, w in state.items():
        if (getattr(w, "ndim", 0) >= 2
                and any(re.search(p, name) for p in patterns)):
            out[name] = quantize_linear(w)
        else:
            out[name] = w
    return out


def qmatmul(x, qlin, impl=None):
    """``x @ dequant(qlin)`` with the dequant fused next to the MXU.

    Routes to the Pallas ``quant_matmul`` kernel when the shapes pass
    its tile gate on TPU, else falls back to a dequant-then-dot in f32
    (per-output-channel scales commute with the contraction, so the
    scale is applied to the f32 product either way).  Result is cast
    back to ``x.dtype``.
    """
    import jax.numpy as jnp

    from .pallas_kernels import quant_matmul as _qmm

    qweight, scale = qlin["qweight"], qlin["scale"]
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = qweight.shape[-1]
    x2 = x.reshape((-1, k))
    if _qmm.use_pallas(x2.shape, qweight.shape, impl=impl):
        out2 = _qmm.quant_matmul(x2, qweight, scale.reshape((1, n)))
    else:
        out2 = (jnp.dot(x2.astype(jnp.float32),
                        qweight.astype(jnp.float32))
                * scale.reshape((1, n))).astype(x.dtype)
    return out2.reshape(lead + (n,))


# ---------------------------------------------------------------------------
# KV pages: per-page symmetric int8 with scatter-max requantize


def kv_write(pages, scales, pids, offs, vals):
    """Quantize-on-write into int8 KV pages; returns ``(pages, scales)``.

    ``pages``: int8 ``[..., num_pages, page_size, head_dim]``;
    ``scales``: f32 ``[..., num_pages]``; ``pids``/``offs``: int32
    ``[T]`` page id + in-page slot per token; ``vals``: float
    ``[..., T, head_dim]`` with leading dims matching ``pages``.

    Three steps, all scatter ``mode="drop"`` so the verify program's
    out-of-range sentinel pids (dropped writes) stay safe:

    1. scatter-max each touched page's scale up to cover the incoming
       tokens (``amax/127`` per token; duplicates of a page reduce to
       their max),
    2. requantize the touched pages' resident cells by ``s_old/s_new``
       (a no-op ratio of 1 when the scale didn't grow),
    3. write the new cells quantized at the settled scale.

    Traceable — the decode/verify programs run it in-graph; the eager
    ``PagedKVCache.write_at`` path calls the same function.
    """
    import jax.numpy as jnp

    v32 = vals.astype(jnp.float32)
    s_old = scales[..., pids]                                 # [..., T]
    needed = jnp.max(jnp.abs(v32), axis=-1) / QMAX            # [..., T]
    scales = scales.at[..., pids].max(needed, mode="drop")
    s_new = scales[..., pids]                                 # [..., T]
    # 2. requantize resident cells of touched pages.  Duplicate pids
    # write identical requantized blocks, so overlap is benign.
    ratio = jnp.where(s_new > 0, s_old / jnp.where(s_new > 0, s_new, 1.0),
                      1.0)
    touched = pages[..., pids, :, :].astype(jnp.float32)
    requant = jnp.clip(jnp.round(touched * ratio[..., None, None]),
                       -QMAX, QMAX).astype(jnp.int8)
    pages = pages.at[..., pids, :, :].set(requant, mode="drop")
    # 3. the new cells at the settled per-page scale.
    q = jnp.clip(jnp.round(v32 / jnp.where(s_new > 0, s_new, 1.0)
                           [..., None]),
                 -QMAX, QMAX).astype(jnp.int8)
    pages = pages.at[..., pids, offs, :].set(q, mode="drop")
    return pages, scales


def kv_dequant(pages, scales, dtype=None):
    """Dequantize int8 pages ``[..., ps, D]`` with per-page scales
    ``[...]`` broadcast over the trailing (slot, head_dim) axes."""
    import jax.numpy as jnp

    out = pages.astype(jnp.float32) * scales[..., None, None]
    return out if dtype is None else out.astype(dtype)


def kv_pool_bytes_per_page(cache):
    """Bytes one page costs in ``cache`` (k+v pools plus any scale
    rows) — the capacity-math denominator."""
    per = (cache.k_pages.nbytes + cache.v_pages.nbytes)
    ks = getattr(cache, "k_scales", None)
    if ks is not None:
        per += ks.nbytes + cache.v_scales.nbytes
    return int(np.ceil(per / cache.num_pages))
