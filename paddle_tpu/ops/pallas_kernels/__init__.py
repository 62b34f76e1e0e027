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
