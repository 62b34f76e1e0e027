"""Grouped expert GEMM Pallas kernel (self-authored, #5).

Reference analog: the fused expert FFN kernels behind
``incubate/distributed/models/moe`` (phi/kernels/fusion MoE GEMMs) —
the role, not the design.  Technique lineage: MegaBlocks (Gale et al.,
2022) grouped GEMM over sort-dispatched expert buckets, replacing the
GShard mask-matmul formulation.

TPU design: tokens arrive already bucketed ``[E, C, H]`` (sort-based
dispatch, ``distributed/utils/moe_utils.sort_dispatch``).  One kernel
runs BOTH expert matmuls for every expert — grid ``(E, C/bc, F/bf)``
with the F-block axis innermost so each ``[bc, H]`` row block
accumulates its second GEMM into a VMEM f32 scratch across F blocks:

    h  = act(x_blk @ w1[e][:, fblk] + b1[e][fblk])   # [bc, bf], VMEM
    acc += h @ w2[e][fblk, :]                        # [bc, H],  VMEM
    out = acc + b2[e]          (written once, at the last F block)

The ``[E, C, F]`` hidden activation — the big HBM intermediate of the
batched-einsum path — never exists: each ``[bc, bf]`` tile of it lives
and dies in VMEM.  Per-expert weights stream through VMEM one
``[H, bf]`` / ``[bf, H]`` panel at a time, so arbitrary ``F`` fits the
16 MB budget.  The activation is applied per F block (elementwise, so
blocking over F is exact).

Backward is a hand-written VJP over saved ``(x, w1, b1, w2)`` — the
hidden activation is recomputed (checkpoint semantics; keeping it
would re-create exactly the HBM buffer the kernel exists to avoid) and
the derivative of the activation comes from ``jax.vjp`` of the same
elementwise function, so any supported activation differentiates
correctly.  The dw/dx contractions are plain batched jnp einsums — MXU
work XLA already schedules well (same split as rms_norm's dw).

Routing: ``PT_GROUPED_GEMM`` ∈ {auto, pallas, einsum}.  ``auto`` takes
the kernel on TPU when the shape gate passes (H and F tile to 128
lanes) and the batched-einsum fallback otherwise; ``pallas`` forces
the kernel (interpreter mode off-TPU — test machinery, not a fast
path).  Tiles ``(bc, bf)`` come from the autotune cache
(``grouped_gemm_blocks``, ops/autotune.py) like fa_blocks/paged_decode.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: default (row-block, f-block) tile: ~6 MB of VMEM live per program
#: (w1/w2 panels 2 MB each f32 + x/acc row blocks), safely under the
#: 16 MB budget with Pallas' input double-buffering.
_DEFAULT_BLOCKS = (128, 256)


def _act_fn(name):
    if name == "gelu":
        # Match ops.gelu (exact erf form), not jax.nn.gelu's tanh default.
        return lambda v: jax.nn.gelu(v, approximate=False)
    return getattr(jax.nn, name)


def _erf_f32(x):
    """erf for INSIDE the kernel: Mosaic (jax 0.9) lowers neither
    ``erf`` nor ``erfc`` ("Unimplemented primitive in Pallas TPU
    lowering: erfc" on the v5e).  XLA's own float32 form — x·P(x²)/Q(x²)
    on clamped x — so the kernel's exact GELU matches the einsum
    route's to 3.5e-7 absolute (measured against float64 erf)."""
    alpha = (0.00022905065861350646, 0.0034082910107109506,
             0.050955695062380861, 0.18520832239976145,
             1.128379143519084)
    beta = (-1.1791602954361697e-7, 0.000023547966471313185,
            0.0010179625278914885, 0.014070470171167667,
            0.11098505178285362, 0.49746925110067538, 1.0)
    x = jnp.clip(x, -3.7439211627767994, 3.7439211627767994)
    x2 = x * x
    num = jnp.float32(0.0)
    for c in alpha:
        num = num * x2 + jnp.float32(c)
    den = jnp.float32(0.0)
    for c in beta:
        den = den * x2 + jnp.float32(c)
    return x * num / den


def _kernel_act(name):
    """``_act_fn`` for the kernel body (f32 in, f32 out)."""
    if name == "gelu":
        return lambda v: 0.5 * v * (1.0 + _erf_f32(
            v * jnp.float32(0.7071067811865476)))
    return _act_fn(name)


def _interpret():
    return jax.default_backend() != "tpu"


def blocks(hidden, ffn):
    """(row_block, f_block) for an [*, hidden] x [hidden, ffn] expert —
    the autotune cache's winner when one is on record, else the
    default.  The f block must divide ffn; a stale cached winner that
    doesn't is discarded rather than obeyed."""
    from .. import autotune as _autotune

    bc, bf = _autotune.lookup("grouped_gemm_blocks", (hidden, ffn),
                              default=_DEFAULT_BLOCKS)
    bf = min(int(bf), ffn)
    while ffn % bf != 0 and bf > 1:
        bf //= 2
    if ffn % bf != 0:
        bf = ffn
    return int(bc), bf


def _kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref, acc, *,
            activation, n_fblocks):
    j = pl.program_id(2)
    x = x_ref[0].astype(jnp.float32)                 # [bc, H]
    w1 = w1_ref[0].astype(jnp.float32)               # [H, bf]
    h = _kernel_act(activation)(
        jax.lax.dot(x, w1, preferred_element_type=jnp.float32)
        + b1_ref[0].astype(jnp.float32))             # [bc, bf]
    contrib = jax.lax.dot(h, w2_ref[0].astype(jnp.float32),
                          preferred_element_type=jnp.float32)  # [bc, H]

    @pl.when(j == 0)
    def _init():
        acc[...] = contrib + b2_ref[0].astype(jnp.float32)

    @pl.when(j > 0)
    def _accum():
        acc[...] += contrib

    @pl.when(j == n_fblocks - 1)
    def _flush():
        o_ref[0] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("activation",))
def _pallas_ffn(x, w1, b1, w2, b2, activation):
    E, C, H = x.shape
    F = w1.shape[-1]
    bc, bf = blocks(H, F)
    bc = min(bc, max(8, -(-C // 8) * 8))  # tiny C: one padded row block
    pad = -C % bc
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    rows = x.shape[1]
    kernel = functools.partial(_kernel, activation=activation,
                               n_fblocks=F // bf)
    # Mosaic rejects i64 grid/index constants from the repo's global
    # x64 mode — trace x64-off like every other kernel in this package.
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid=(E, rows // bc, F // bf),
            in_specs=[
                pl.BlockSpec((1, bc, H), lambda e, i, j: (e, i, 0)),
                pl.BlockSpec((1, H, bf), lambda e, i, j: (e, 0, j)),
                pl.BlockSpec((1, 1, bf), lambda e, i, j: (e, 0, j)),
                pl.BlockSpec((1, bf, H), lambda e, i, j: (e, j, 0)),
                pl.BlockSpec((1, 1, H), lambda e, i, j: (e, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bc, H), lambda e, i, j: (e, i, 0)),
            out_shape=jax.ShapeDtypeStruct((E, rows, H), x.dtype),
            scratch_shapes=[pltpu.VMEM((bc, H), jnp.float32)],
            interpret=_interpret(),
        )(x, w1, b1, w2, b2)
    return out[:, :C]


def einsum_ffn(x, w1, b1, w2, b2, activation):
    """Batched-einsum fallback — the pre-fusion expert FFN body.  The
    [E, C, F] hidden activation round-trips HBM here; this is the
    baseline the kernel is measured against."""
    h = _act_fn(activation)(jnp.einsum("ech,ehf->ecf", x, w1) + b1)
    return jnp.einsum("ecf,efh->ech", h, w2) + b2


# -- int8 weights (PT_QUANT=int8, r19) --------------------------------------

def _qkernel(x_ref, w1_ref, s1_ref, b1_ref, w2_ref, s2_ref, b2_ref,
             o_ref, acc, *, activation, n_fblocks):
    """Same tiling as ``_kernel`` with int8 expert weights: the weight
    panels stream HBM→VMEM at half/quarter the bytes and the per-output-
    channel f32 scales are applied to the f32 products right next to
    the MXU dots (scales commute with the contractions; s2 is constant
    across F blocks, so scaling each contribution before accumulation
    is exact)."""
    j = pl.program_id(2)
    x = x_ref[0].astype(jnp.float32)                 # [bc, H]
    w1 = w1_ref[0].astype(jnp.float32)               # [H, bf] (int8 in)
    h = _kernel_act(activation)(
        jax.lax.dot(x, w1, preferred_element_type=jnp.float32)
        * s1_ref[0] + b1_ref[0].astype(jnp.float32))  # [bc, bf]
    contrib = jax.lax.dot(h, w2_ref[0].astype(jnp.float32),
                          preferred_element_type=jnp.float32) \
        * s2_ref[0]                                   # [bc, H]

    @pl.when(j == 0)
    def _init():
        acc[...] = contrib + b2_ref[0].astype(jnp.float32)

    @pl.when(j > 0)
    def _accum():
        acc[...] += contrib

    @pl.when(j == n_fblocks - 1)
    def _flush():
        o_ref[0] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("activation",))
def _pallas_ffn_q(x, qw1, s1, b1, qw2, s2, b2, activation):
    E, C, H = x.shape
    F = qw1.shape[-1]
    bc, bf = blocks(H, F)
    bc = min(bc, max(8, -(-C // 8) * 8))  # tiny C: one padded row block
    pad = -C % bc
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    rows = x.shape[1]
    kernel = functools.partial(_qkernel, activation=activation,
                               n_fblocks=F // bf)
    s1 = s1.astype(jnp.float32)
    s2 = s2.astype(jnp.float32)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid=(E, rows // bc, F // bf),
            in_specs=[
                pl.BlockSpec((1, bc, H), lambda e, i, j: (e, i, 0)),
                pl.BlockSpec((1, H, bf), lambda e, i, j: (e, 0, j)),
                pl.BlockSpec((1, 1, bf), lambda e, i, j: (e, 0, j)),
                pl.BlockSpec((1, 1, bf), lambda e, i, j: (e, 0, j)),
                pl.BlockSpec((1, bf, H), lambda e, i, j: (e, j, 0)),
                pl.BlockSpec((1, 1, H), lambda e, i, j: (e, 0, 0)),
                pl.BlockSpec((1, 1, H), lambda e, i, j: (e, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bc, H), lambda e, i, j: (e, i, 0)),
            out_shape=jax.ShapeDtypeStruct((E, rows, H), x.dtype),
            scratch_shapes=[pltpu.VMEM((bc, H), jnp.float32)],
            interpret=_interpret(),
        )(x, qw1, s1, b1, qw2, s2, b2)
    return out[:, :C]


# -- custom VJP over the kernel ------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fused(x, w1, b1, w2, b2, activation):
    return _pallas_ffn(x, w1, b1, w2, b2, activation)


def _fused_f(x, w1, b1, w2, b2, activation):
    return (_pallas_ffn(x, w1, b1, w2, b2, activation),
            (x, w1, b1, w2, b2))


def _fused_b(activation, saved, dy):
    x, w1, b1, w2, b2 = saved
    x32 = x.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    pre = jnp.einsum("ech,ehf->ecf", x32, w1.astype(jnp.float32)) \
        + b1.astype(jnp.float32)
    h, act_vjp = jax.vjp(_act_fn(activation), pre)
    dw2 = jnp.einsum("ecf,ech->efh", h, dy32).astype(w2.dtype)
    db2 = jnp.sum(dy32, axis=1, keepdims=True).astype(b2.dtype)
    dh = jnp.einsum("ech,efh->ecf", dy32, w2.astype(jnp.float32))
    dpre = act_vjp(dh)[0]
    dw1 = jnp.einsum("ech,ecf->ehf", x32, dpre).astype(w1.dtype)
    db1 = jnp.sum(dpre, axis=1, keepdims=True).astype(b1.dtype)
    dx = jnp.einsum("ecf,ehf->ech", dpre,
                    w1.astype(jnp.float32)).astype(x.dtype)
    return dx, dw1, db1, dw2, db2


_fused.defvjp(_fused_f, _fused_b)


# -- routing ----------------------------------------------------------------

def supported(hidden, ffn, on_tpu):
    """Shape gate for the compiled (non-interpret) kernel: both GEMM
    minor dims must tile to 128 lanes.  Off-TPU the interpreter imposes
    no tiling, but auto routing takes the einsum path there
    (kernel-in-interpreter is test machinery, not a fast path)."""
    if not on_tpu:
        return False
    return hidden % 128 == 0 and ffn % 128 == 0


def resolve_impl(hidden, ffn, impl=None):
    """'pallas' or 'einsum' for this shape.  ``impl``/PT_GROUPED_GEMM
    ∈ {auto, pallas, einsum}; auto = kernel on TPU when the shape gate
    passes."""
    impl = (impl or os.environ.get("PT_GROUPED_GEMM", "auto")).lower()
    if impl not in ("auto", "pallas", "einsum"):
        raise ValueError(
            f"PT_GROUPED_GEMM={impl!r}: expected auto|pallas|einsum")
    if impl == "auto":
        return "pallas" if supported(
            hidden, ffn, jax.default_backend() == "tpu") else "einsum"
    return impl


def grouped_ffn(x, w1, b1, w2, b2, activation="gelu", impl=None):
    """Grouped expert FFN over bucketed tokens.

    x [E, C, H]; w1 [E, H, F]; b1 [E, 1, F]; w2 [E, F, H]; b2 [E, 1, H]
    -> [E, C, H].  Differentiable on both routes (custom VJP over the
    kernel, native AD over the einsum fallback).

    ``w1``/``w2`` may instead be ``QuantizedLinear`` dicts
    (``ops.quant``): int8 ``qweight`` + per-output-channel f32
    ``scale``.  That path is inference-only (no VJP — training
    differentiates the dense weights) and fuses the dequant into the
    kernel; the einsum route dequantizes first.
    """
    from .. import quant as _quant

    if _quant.is_quantized(w1) or _quant.is_quantized(w2):
        if not (_quant.is_quantized(w1) and _quant.is_quantized(w2)):
            raise ValueError(
                "grouped_ffn: w1 and w2 must both be quantized")
        F = w1["qweight"].shape[-1]
        if resolve_impl(x.shape[-1], F, impl) == "pallas":
            return _pallas_ffn_q(x, w1["qweight"], w1["scale"], b1,
                                 w2["qweight"], w2["scale"], b2,
                                 activation)
        return einsum_ffn(x, _quant.dequantize(w1["qweight"],
                                               w1["scale"], x.dtype),
                          b1,
                          _quant.dequantize(w2["qweight"], w2["scale"],
                                            x.dtype),
                          b2, activation)
    if resolve_impl(x.shape[-1], w1.shape[-1], impl) == "pallas":
        return _fused(x, w1, b1, w2, b2, activation)
    return einsum_ffn(x, w1, b1, w2, b2, activation)


def grouped_ffn_spmd_rule(mesh, x_spec, w1_spec, b1_spec, w2_spec,
                          b2_spec):
    """SPMD rule: the expert (leading) dim may shard — programs are
    independent per expert, and all five operands must carry the same
    expert sharding (the EP layout global_scatter delivers); C, H and F
    are kernel-internal and must be replicated.  Output follows x."""
    return (tuple(x_spec)[:1] or (None,)) + (None, None)


_HANDLE = None


def handle():
    """Custom-op handle (lazy — registration is global).  Registered as
    ``grouped_expert_gemm`` so out-of-tree callers get dispatch/AMP/tape
    semantics; the MoE body calls ``grouped_ffn`` directly (it already
    runs inside a registered op's trace)."""
    global _HANDLE
    if _HANDLE is None:
        from ...utils.cpp_extension import register_custom_op

        _HANDLE = register_custom_op(
            "grouped_expert_gemm", grouped_ffn,
            static_argnames=("activation", "impl"),
            spmd_rule=grouped_ffn_spmd_rule)
    return _HANDLE
