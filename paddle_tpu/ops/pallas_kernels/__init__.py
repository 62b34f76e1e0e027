"""Self-authored Pallas TPU kernels (the repo's analog of the
reference's hand-written fusion kernels, paddle/phi/kernels/fusion/).

Unlike ``jax.experimental.pallas.ops.tpu`` stock kernels, these are
designed for this framework's hot paths and profiles:

- ``short_attention``: fused attention + softmax + DROPOUT for short
  sequences (BERT-class S<=1024), where materializing [B,H,S,S] probs
  and their dropout masks in HBM dominated the step (r4 profile:
  ~60 ms of a 180 ms BERT step).
- ``grouped_gemm``: both expert matmuls of a sort-dispatched MoE step
  for all experts in one kernel (MegaBlocks-style), the [E, C, F]
  hidden activation VMEM-resident per tile instead of an HBM
  round-trip.  Also carries the int8-expert-weight variant
  (``PT_QUANT=int8``) with dequant fused at the MXU.
- ``grouped_swiglu``: the routed SwiGLU experts of a prefill chunk
  (``models/moe.py``, serving) as one grouped product over rows sorted
  by expert, every 128-row tile one expert's, dropless: the tile's
  expert and the layer of a stacked run are prefetched scalars, so each
  expert's gate / up / down matrices are read where they lie in
  ``[E, H, 2F]`` / ``[E, F, H]`` (or ``[n, E, ..]``), in panels of F
  that stay double-buffered in VMEM; float32 hidden rows and result.
  (``grouped_gemm`` above is the TRAINING layer's: capacity buckets,
  gelu, biases, dropped rows.)
- ``paged_decode``: single-token decode attention over the paged KV
  pool, per sequence a double-buffered loop over 256-key blocks of
  the live pages (all KV heads of a page in one DMA) with an online
  softmax; the ``_quant``
  variant streams int8 pages with per-page scales via scalar prefetch
  (one DMA burst over the whole window still).
- ``quant_matmul``: activation x int8-weight GEMM with the
  per-output-channel dequant applied to the f32 accumulator at flush —
  the serving weight matmul under ``PT_QUANT=int8``.
"""
from .grouped_gemm import grouped_ffn  # noqa: F401
# NOTE: the quant_matmul FUNCTION is deliberately not re-exported here —
# it would shadow the submodule name; callers go through ops.quant.qmatmul.
from . import quant_matmul  # noqa: F401
from .short_attention import short_attention  # noqa: F401
