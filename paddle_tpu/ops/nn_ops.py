"""Neural-network compute ops.

Reference kernels: ``paddle/phi/kernels/`` conv/pool/norm/embedding/
softmax/dropout (+ fused attention under ``phi/kernels/fusion/``), exposed
via ``python/paddle/nn/functional/``.  TPU-native: convs and attention map
to ``jax.lax`` convolutions / dot_general so XLA tiles them on the MXU;
norms are written as fusable elementwise chains (XLA fuses the whole
normalize+scale+shift into one kernel); dropout uses the counter-based PRNG.

NHWC vs NCHW: the reference defaults to NCHW.  We accept both and keep the
public default NCHW for API parity, transposing at the boundary — XLA's
layout assignment makes this free inside a jit region.
"""
from __future__ import annotations

import contextlib as _contextlib
import contextvars as _contextvars
import os
import functools as _functools

import numpy as np

import jax
import jax.numpy as jnp

from .registry import apply, register_op
from .random import default_generator


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        if len(v) == n:
            return tuple(int(x) for x in v)
        return tuple(int(v[0]) for _ in range(n))
    return tuple(int(v) for _ in range(n))


# -- convolution ------------------------------------------------------------

def _conv_dtype(x, w):
    """XLA convs reject mixed dtypes; follow the activation stream's
    dtype (bf16-first mixed precision: a fp32 master weight joins a
    bf16 stream as bf16 — the reference amp O2 conv behavior).  Applied
    by every conv variant."""
    if w.dtype != x.dtype:
        w = w.astype(x.dtype)
    return w


def _conv2d_plain(x, w, stride=(1, 1), padding=(0, 0), dilation=(1, 1),
                  groups=1, data_format="NCHW"):
    w = _conv_dtype(x, w)
    if isinstance(padding, str):
        pad = padding
    else:
        pad = [(padding[0], padding[0]), (padding[1], padding[1])]
    nhwc = os.environ.get("PT_CONV_NHWC")
    if data_format == "NCHW" and (nhwc == "1" or (
            nhwc is None and jax.default_backend() == "tpu")):
        # Compute in NHWC — the TPU's native conv layout (+8% measured
        # on the ResNet-50 bench); boundary transposes cancel between
        # layers under XLA.  PT_CONV_NHWC=0 restores direct NCHW.
        dn = jax.lax.conv_dimension_numbers(
            (x.shape[0], x.shape[2], x.shape[3], x.shape[1]),
            (w.shape[2], w.shape[3], w.shape[1], w.shape[0]),
            ("NHWC", "HWIO", "NHWC"))
        out = jax.lax.conv_general_dilated(
            jnp.transpose(x, (0, 2, 3, 1)),
            jnp.transpose(w, (2, 3, 1, 0)),
            window_strides=stride, padding=pad, rhs_dilation=dilation,
            dimension_numbers=dn, feature_group_count=groups)
        return jnp.transpose(out, (0, 3, 1, 2))
    dn = jax.lax.conv_dimension_numbers(
        x.shape, w.shape,
        ("NCHW", "OIHW", "NCHW") if data_format == "NCHW"
        else ("NHWC", "HWIO", "NHWC"))
    return jax.lax.conv_general_dilated(
        x, w, window_strides=stride, padding=pad, rhs_dilation=dilation,
        dimension_numbers=dn, feature_group_count=groups,
        preferred_element_type=None)


conv2d_op = register_op(
    "conv2d", _conv2d_plain,
    static_argnames=("stride", "padding", "dilation", "groups",
                     "data_format"))


def conv2d_raw(x, weight, stride=1, padding=0, dilation=1, groups=1,
               data_format="NCHW"):
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        pad = _pair(padding)
    return apply(conv2d_op, x, weight, stride=_pair(stride), padding=pad,
                 dilation=_pair(dilation), groups=int(groups),
                 data_format=data_format)


def _conv1d_plain(x, w, stride=1, padding=0, dilation=1, groups=1):
    w = _conv_dtype(x, w)
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NCH", "OIH", "NCH"))
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride,), padding=[(padding, padding)],
        rhs_dilation=(dilation,), dimension_numbers=dn,
        feature_group_count=groups)


conv1d_op = register_op(
    "conv1d", _conv1d_plain,
    static_argnames=("stride", "padding", "dilation", "groups"))


def _conv2d_transpose_plain(x, w, stride=(1, 1), padding=(0, 0),
                            output_padding=(0, 0), dilation=(1, 1), groups=1,
                            data_format="NCHW"):
    w = _conv_dtype(x, w)
    # Transposed conv = lhs-dilated conv with the kernel spatially
    # MIRRORED (the gradient-of-conv identity); without the flip only
    # symmetric kernels came out right (r4 torch-parity fix).  The
    # spatial axes depend on the weight layout: IOHW -> (-2, -1),
    # HWIO -> (0, 1).
    w = jnp.flip(w, axis=(-2, -1) if data_format == "NCHW" else (0, 1))
    dn = jax.lax.conv_dimension_numbers(
        x.shape, w.shape,
        ("NCHW", "IOHW", "NCHW") if data_format == "NCHW"
        else ("NHWC", "HWIO", "NHWC"))
    kh, kw = ((w.shape[2], w.shape[3]) if data_format == "NCHW"
              else (w.shape[0], w.shape[1]))
    pad = [(dilation[0] * (kh - 1) - padding[0],
            dilation[0] * (kh - 1) - padding[0] + output_padding[0]),
           (dilation[1] * (kw - 1) - padding[1],
            dilation[1] * (kw - 1) - padding[1] + output_padding[1])]
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=pad,
        lhs_dilation=stride, rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups)


conv2d_transpose_op = register_op(
    "conv2d_transpose", _conv2d_transpose_plain,
    static_argnames=("stride", "padding", "output_padding", "dilation",
                     "groups", "data_format"))


# -- pooling ----------------------------------------------------------------

def _max_pool2d_plain(x, kernel_size, stride, padding, ceil_mode=False,
                      data_format="NCHW"):
    if data_format == "NCHW":
        window = (1, 1) + kernel_size
        strides = (1, 1) + stride
        pads = ((0, 0), (0, 0),
                (padding[0], padding[0]), (padding[1], padding[1]))
    else:
        window = (1,) + kernel_size + (1,)
        strides = (1,) + stride + (1,)
        pads = ((0, 0), (padding[0], padding[0]),
                (padding[1], padding[1]), (0, 0))
    # -inf init is required for jax's reduce_window max transpose rule.
    neg = (-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
           else jnp.iinfo(x.dtype).min)
    return jax.lax.reduce_window(x, neg, jax.lax.max, window, strides, pads)


max_pool2d_op = register_op(
    "max_pool2d", _max_pool2d_plain,
    static_argnames=("kernel_size", "stride", "padding", "ceil_mode",
                     "data_format"))


def _avg_pool2d_plain(x, kernel_size, stride, padding, exclusive=True,
                      data_format="NCHW"):
    if data_format == "NCHW":
        window = (1, 1) + kernel_size
        strides = (1, 1) + stride
        pads = ((0, 0), (0, 0),
                (padding[0], padding[0]), (padding[1], padding[1]))
    else:
        window = (1,) + kernel_size + (1,)
        strides = (1,) + stride + (1,)
        pads = ((0, 0), (padding[0], padding[0]),
                (padding[1], padding[1]), (0, 0))
    summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, pads)
    if exclusive and (padding[0] or padding[1]):
        ones = jnp.ones_like(x)
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                       strides, pads)
        return summed / counts
    return summed / float(np.prod(kernel_size))


avg_pool2d_op = register_op(
    "avg_pool2d", _avg_pool2d_plain,
    static_argnames=("kernel_size", "stride", "padding", "exclusive",
                     "data_format"))


def _adaptive_avg_pool2d_plain(x, output_size, data_format="NCHW"):
    if data_format == "NHWC":
        x = jnp.transpose(x, (0, 3, 1, 2))
    n, c, h, w = x.shape
    oh, ow = output_size
    # When evenly divisible this is an exact mean-pool reshape.
    if h % oh == 0 and w % ow == 0:
        out = x.reshape(n, c, oh, h // oh, ow, w // ow).mean(axis=(3, 5))
    else:
        # General case: interval averages.
        hs = (np.arange(oh) * h // oh, ((np.arange(oh) + 1) * h + oh - 1) // oh)
        ws = (np.arange(ow) * w // ow, ((np.arange(ow) + 1) * w + ow - 1) // ow)
        rows = []
        for i in range(oh):
            cols = []
            for j in range(ow):
                cols.append(x[:, :, hs[0][i]:hs[1][i],
                              ws[0][j]:ws[1][j]].mean(axis=(2, 3)))
            rows.append(jnp.stack(cols, axis=-1))
        out = jnp.stack(rows, axis=-2)
    if data_format == "NHWC":
        out = jnp.transpose(out, (0, 2, 3, 1))
    return out


adaptive_avg_pool2d_op = register_op(
    "adaptive_avg_pool2d", _adaptive_avg_pool2d_plain,
    static_argnames=("output_size", "data_format"))


# -- normalization ----------------------------------------------------------

def _layer_norm_plain(x, weight=None, bias=None, epsilon=1e-5,
                      begin_norm_axis=-1):
    axes = tuple(range(begin_norm_axis % x.ndim, x.ndim)) \
        if begin_norm_axis != -1 else (x.ndim - 1,)
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    # Cast affine params to x's dtype — mixed-precision norms must not
    # promote the activation stream (see _rms_norm_plain).
    if weight is not None:
        out = out * weight.astype(out.dtype)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


layer_norm_op = register_op(
    "layer_norm", _layer_norm_plain,
    static_argnames=("epsilon", "begin_norm_axis"))


def _rms_norm_plain(x, weight=None, epsilon=1e-6):
    # Reference: phi/kernels/fusion rms_norm; compute in fp32 for stability.
    # The affine weight is cast to x's dtype: a fp32 master weight must NOT
    # promote a bf16 activation stream to fp32 (that silently turns every
    # downstream matmul into a slow fp32 MXU op).
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + epsilon)
    out = out.astype(dt)
    if weight is not None:
        out = out * weight.astype(dt)
    return out


rms_norm_op = register_op("rms_norm", _rms_norm_plain,
                          static_argnames=("epsilon",))


def _batch_norm_infer(x, mean, var, weight=None, bias=None, epsilon=1e-5,
                      data_format="NCHW"):
    if data_format == "NCHW" and x.ndim == 4:
        shape = (1, -1, 1, 1)
    elif x.ndim == 2:
        shape = (1, -1)
    else:
        shape = (1,) * (x.ndim - 1) + (-1,)
    # Stats/affine params cast to x's dtype (see _layer_norm_plain): fp32
    # running stats must not promote a bf16 activation stream — that
    # silently turns every downstream conv/matmul into fp32 (and XLA
    # convs hard-reject mixed dtypes).
    dt = x.dtype
    inv = jax.lax.rsqrt(var.astype(jnp.float32).reshape(shape)
                        + epsilon).astype(dt)
    out = (x - mean.astype(dt).reshape(shape)) * inv
    if weight is not None:
        out = out * weight.astype(dt).reshape(shape)
    if bias is not None:
        out = out + bias.astype(dt).reshape(shape)
    return out


batch_norm_infer_op = register_op(
    "batch_norm_infer", _batch_norm_infer,
    static_argnames=("epsilon", "data_format"))


def _batch_norm_stats(x, data_format="NCHW"):
    axes = (0, 2, 3) if (data_format == "NCHW" and x.ndim == 4) else \
        tuple(i for i in range(x.ndim) if i != x.ndim - 1) if x.ndim > 2 \
        else (0,)
    if data_format == "NCHW" and x.ndim == 4:
        axes = (0, 2, 3)
    mean = jnp.mean(x, axis=axes)
    var = jnp.var(x, axis=axes)
    return mean, var


batch_norm_stats_op = register_op(
    "batch_norm_stats", _batch_norm_stats, n_outputs=2,
    static_argnames=("data_format",))


def _bn_axes_shape(ndim, data_format):
    if ndim == 2:
        return (0,), (1, -1)
    if data_format in ("NCHW", "NCL", "NCDHW"):  # channel-first, any rank
        return (0,) + tuple(range(2, ndim)), \
            (1, -1) + (1,) * (ndim - 2)
    return tuple(range(ndim - 1)), (1,) * (ndim - 1) + (-1,)


def _bn_train_fwd(x, w, b, epsilon=1e-5, data_format="NCHW"):
    """Fused training-mode batch norm (reference batch_norm_kernel.cu
    role).  One fp32 sum/sumsq pass for the stats (E[x²]−E[x]², a
    single multi-output XLA fusion) instead of jnp.mean + jnp.var's
    separate passes — profiled r4: reduction fusions were 52% of the
    ResNet step."""
    axes, shape = _bn_axes_shape(x.ndim, data_format)
    n = 1
    for a in axes:
        n *= x.shape[a]
    xf = x.astype(jnp.float32)
    s = jnp.sum(xf, axis=axes)
    ss = jnp.sum(xf * xf, axis=axes)
    mean = s / n
    var = jnp.maximum(ss / n - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + epsilon)
    dt = x.dtype
    xhat = (x - mean.astype(dt).reshape(shape)) \
        * inv.astype(dt).reshape(shape)
    y = xhat * w.astype(dt).reshape(shape) + b.astype(dt).reshape(shape)
    return (y, mean, var), (x, w, mean, inv)


def _bn_train_bwd(saved, g, epsilon=1e-5, data_format="NCHW"):
    """2-pass BN backward: one fused (Σgy, Σgy·x̂) reduction + one
    elementwise dx pass — replaces autodiff's per-term reductions."""
    x, w, mean, inv = saved
    gy = g[0] if isinstance(g, (tuple, list)) else g
    axes, shape = _bn_axes_shape(x.ndim, data_format)
    n = 1
    for a in axes:
        n *= x.shape[a]
    dt = x.dtype
    xhat = (x - mean.astype(dt).reshape(shape)) \
        * inv.astype(dt).reshape(shape)
    gyf = gy.astype(jnp.float32)
    dbeta = jnp.sum(gyf, axis=axes)
    dgamma = jnp.sum(gyf * xhat.astype(jnp.float32), axis=axes)
    wi = (w.astype(jnp.float32) * inv).astype(dt).reshape(shape)
    dx = wi * (gy
               - (dbeta / n).astype(dt).reshape(shape)
               - xhat * (dgamma / n).astype(dt).reshape(shape))
    return (dx, dgamma.astype(w.dtype), dbeta.astype(w.dtype))


batch_norm_train_op = register_op(
    "batch_norm_train",
    lambda x, w, b, epsilon=1e-5, data_format="NCHW":
    _bn_train_fwd(x, w, b, epsilon, data_format)[0],
    fwd=_bn_train_fwd, bwd=_bn_train_bwd, n_outputs=3,
    static_argnames=("epsilon", "data_format"))


def _group_norm_plain(x, weight=None, bias=None, epsilon=1e-5, groups=32,
                      data_format="NCHW"):
    if data_format != "NCHW":
        x = jnp.moveaxis(x, -1, 1)
    n, c = x.shape[:2]
    spatial = x.shape[2:]
    xg = x.reshape(n, groups, c // groups, *spatial)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=axes, keepdims=True)
    out = ((xg - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x.shape)
    shape = (1, c) + (1,) * len(spatial)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    if data_format != "NCHW":
        out = jnp.moveaxis(out, 1, -1)
    return out


group_norm_op = register_op(
    "group_norm", _group_norm_plain,
    static_argnames=("epsilon", "groups", "data_format"))


# -- embedding --------------------------------------------------------------

def _embedding_plain(weight, ids, padding_idx=None):
    out = jnp.take(weight, ids, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return out


def _embedding_fwd(weight, ids, padding_idx=None):
    return _embedding_plain(weight, ids, padding_idx), (weight, ids)


def _embedding_bwd(saved, g, padding_idx=None):
    weight, ids = saved
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids != padding_idx)[..., None]
        g = g * mask.astype(g.dtype)
    gw = jnp.zeros(jnp.shape(weight), g.dtype)
    gw = gw.at[ids].add(g)
    return gw.astype(weight.dtype), None


embedding_op = register_op("embedding", _embedding_plain,
                           fwd=_embedding_fwd, bwd=_embedding_bwd,
                           static_argnames=("padding_idx",),
                           nondiff_argnums=(1,))


# -- softmax + cross entropy ------------------------------------------------

def _softmax_fwd(x, axis=-1):
    out = jax.nn.softmax(x, axis=axis)
    return out, out


def _softmax_bwd(out, g, axis=-1):
    inner = jnp.sum(out * g, axis=axis, keepdims=True)
    return (out * (g - inner),)


softmax_op = register_op("softmax",
                         lambda x, axis=-1: jax.nn.softmax(x, axis=axis),
                         fwd=_softmax_fwd, bwd=_softmax_bwd,
                         static_argnames=("axis",))

def _log_softmax_fwd(x, axis=-1):
    out = jax.nn.log_softmax(x, axis=axis)
    return out, out


def _log_softmax_bwd(out, g, axis=-1):
    return (g - jnp.exp(out) * jnp.sum(g, axis=axis, keepdims=True),)


log_softmax_op = register_op("log_softmax",
                             lambda x, axis=-1: jax.nn.log_softmax(
                                 x, axis=axis),
                             fwd=_log_softmax_fwd, bwd=_log_softmax_bwd,
                             static_argnames=("axis",))


def _softmax_ce_plain(logits, label, soft_label=False, ignore_index=-100,
                      axis=-1):
    # log_softmax in fp32: bf16 logits over a large vocab lose the loss
    # signal (reference softmax_with_cross_entropy also accumulates fp32).
    lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
    if soft_label:
        return -jnp.sum(label * lsm, axis=axis, keepdims=True)
    nll = -jnp.take_along_axis(lsm, label[..., None].astype(jnp.int32),
                               axis=axis)
    if ignore_index is not None:
        mask = (label != ignore_index)[..., None]
        nll = jnp.where(mask, nll, jnp.zeros_like(nll))
    return nll


def _softmax_ce_fwd(logits, label, soft_label=False, ignore_index=-100,
                    axis=-1):
    lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
    if soft_label:
        loss = -jnp.sum(label * lsm, axis=axis, keepdims=True)
    else:
        nll = -jnp.take_along_axis(lsm, label[..., None].astype(jnp.int32),
                                   axis=axis)
        if ignore_index is not None:
            mask = (label != ignore_index)[..., None]
            nll = jnp.where(mask, nll, jnp.zeros_like(nll))
        loss = nll
    return loss, (lsm, label)


def _softmax_ce_bwd(saved, g, soft_label=False, ignore_index=-100, axis=-1):
    lsm, label = saved
    sm = jnp.exp(lsm)
    if soft_label:
        glogits = g * (sm * jnp.sum(label, axis=axis, keepdims=True) - label)
        return glogits, None
    oh = jax.nn.one_hot(label, lsm.shape[axis], dtype=lsm.dtype, axis=axis)
    if ignore_index is not None:
        valid = (label != ignore_index)[..., None].astype(lsm.dtype)
    else:
        valid = 1.0
    glogits = g * (sm - oh) * valid
    return glogits, None


softmax_with_cross_entropy_op = register_op(
    "softmax_with_cross_entropy", _softmax_ce_plain,
    fwd=_softmax_ce_fwd, bwd=_softmax_ce_bwd,
    static_argnames=("soft_label", "ignore_index", "axis"),
    nondiff_argnums=(1,))


# -- fused lm-head + cross entropy ------------------------------------------

@_functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_cross_entropy(hidden, weight, labels, tied=False,
                               ignore_index=-100):
    """mean CE over ``hidden @ weight`` logits without materializing the
    fp32 log-softmax or a scatter in backward.

    hidden [N, H] (bf16 ok), weight [H, V] (or [V, H] when ``tied`` —
    an embedding table used as the output head), labels [N] int.
    Loss = mean over ALL rows with ignore_index rows contributing 0 —
    matching F.cross_entropy(reduction='mean', ignore_index=-100) on the
    same logits (reference softmax_with_cross_entropy semantics).

    Backward recomputes the logits (checkpoint-style) and forms
    d_logits = (softmax - onehot) directly in the logits dtype — the
    autodiff path through log_softmax+take_along_axis instead materializes
    a [N, V] fp32 tensor twice and a scatter-add, ~3x the HBM traffic at
    V=32k.  Reference parity: fused softmax_with_cross_entropy kernel
    (phi/kernels/gpu/cross_entropy_kernel.cu fused path)."""
    loss, _ = _flce_fwd(hidden, weight, labels, tied, ignore_index)
    return loss


def _flce_logits(hidden, weight, tied):
    if tied:
        return jnp.einsum("nh,vh->nv", hidden, weight)
    return jnp.einsum("nh,hv->nv", hidden, weight)


def _flce_fwd(hidden, weight, labels, tied, ignore_index):
    logits = _flce_logits(hidden, weight, tied)
    lf = logits.astype(jnp.float32)
    mx = jnp.max(lf, axis=-1)
    lse = mx + jnp.log(jnp.sum(jnp.exp(lf - mx[:, None]), axis=-1))
    lab = jnp.clip(labels, 0, logits.shape[-1] - 1).astype(jnp.int32)
    tgt = jnp.take_along_axis(lf, lab[:, None], axis=-1)[:, 0]
    valid = (labels != ignore_index)
    nll = jnp.where(valid, lse - tgt, 0.0)
    loss = jnp.mean(nll)
    return loss, (hidden, weight, labels, lse)


def _flce_bwd(tied, ignore_index, saved, g):
    hidden, weight, labels, lse = saved
    n, v = lse.shape[0], weight.shape[0] if tied else weight.shape[1]
    logits = _flce_logits(hidden, weight, tied)
    lab = jnp.clip(labels, 0, v - 1).astype(jnp.int32)
    valid = (labels != ignore_index)
    # softmax - onehot, scaled by g/N, zeroed on ignored rows; onehot via
    # fused iota compare (no scatter).
    sm = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
    oh = (jax.lax.broadcasted_iota(jnp.int32, (n, v), 1) == lab[:, None])
    scale = (g / n)
    dlogits = ((sm - oh.astype(jnp.float32))
               * (valid.astype(jnp.float32) * scale)[:, None]
               ).astype(hidden.dtype)
    if tied:
        dh = jnp.einsum("nv,vh->nh", dlogits, weight)
        dw = jnp.einsum("nv,nh->vh", dlogits, hidden)
    else:
        dh = jnp.einsum("nv,hv->nh", dlogits, weight)
        dw = jnp.einsum("nh,nv->hv", hidden, dlogits)
    return dh.astype(hidden.dtype), dw.astype(weight.dtype), None


fused_linear_cross_entropy.defvjp(_flce_fwd, _flce_bwd)


# -- dropout ----------------------------------------------------------------

def _dropout_fwd_key(x, key, p=0.5, mode="upscale_in_train"):
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, jnp.shape(x))
    if mode == "upscale_in_train":
        out = jnp.where(mask, x / keep, jnp.zeros_like(x))
    else:
        out = jnp.where(mask, x, jnp.zeros_like(x))
    return out, mask


_dropout_jit = jax.jit(_dropout_fwd_key, static_argnames=("p", "mode"))


def _dropout_bwd(mask, g, p=0.5, mode="upscale_in_train"):
    keep = 1.0 - p
    if mode == "upscale_in_train":
        return (jnp.where(mask, g / keep, jnp.zeros_like(g)),)
    return (jnp.where(mask, g, jnp.zeros_like(g)),)


class _DropoutOp:
    """Dropout needs a fresh key per call, so it bypasses register_op's
    uniform jit wrapping and draws from the default generator."""

    name = "dropout"
    n_outputs = 1
    jit_bwd = staticmethod(jax.jit(_dropout_bwd,
                                   static_argnames=("p", "mode")))

    @staticmethod
    def fwd(x, p=0.5, mode="upscale_in_train"):
        return _dropout_jit(x, default_generator.next_fast_key(), p=p,
                            mode=mode)


dropout_op = _DropoutOp()


def dropout_raw(x, p=0.5, training=True, mode="upscale_in_train"):
    from ..autograd import engine as _engine
    from ..core.tensor import Tensor

    if not training:
        if mode == "downscale_in_infer" and p > 0.0:
            from . import math as _m

            return _m.scale(x, scale=1.0 - p)
        return x
    if p == 0.0:
        return x
    need_grad = _engine.is_grad_enabled() and not x.stop_gradient
    out_data, mask = dropout_op.fwd(x._data, p=float(p), mode=mode)
    out = Tensor(out_data, stop_gradient=not need_grad)
    if need_grad:
        node = _engine.GradNode(dropout_op, mask, [x],
                                {"p": float(p), "mode": mode})
        node.bind_outputs([out])
    return out


# -- attention --------------------------------------------------------------

# Mosaic kernels cannot be partitioned by GSPMD: lowering one inside a
# jit whose operands span several devices raises "Mosaic kernels cannot
# be automatically partitioned. Please wrap the call in a shard_map".
# So a sharded step names, while it traces, the mesh it runs on and the
# axes that split the batch and the heads (``kernel_mesh``); attention
# picks that up as the static ``shard`` argument and runs its Pallas
# kernels per (batch, head) shard.  One device: ``shard`` is None and
# the kernel is called directly.
_KERNEL_MESH = _contextvars.ContextVar("kernel_mesh", default=None)


@_contextlib.contextmanager
def kernel_mesh(mesh, batch_axis=None, head_axis=None):
    """While active, attention traced in this context (thread or task)
    shards its Pallas kernels over ``mesh`` (a ``jax.sharding.Mesh``):
    dim 0 of [B, H, S, D] over ``batch_axis``, dim 1 over ``head_axis``
    (each a mesh axis name, a tuple of names, or None)."""
    token = _KERNEL_MESH.set((mesh, batch_axis, head_axis))
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def current_kernel_mesh():
    return _KERNEL_MESH.get()


def _per_shard(shard, kernel, *bhsd, seed=None):
    """``kernel(*bhsd)`` over [B, H, S, D] operands — per shard under
    ``shard = (mesh, batch_axis, head_axis)``, directly when None.

    ``seed`` (int32 scalar), when given, is the kernel's last argument.
    A kernel that hashes ``(seed, program id)`` sees only its shard's
    LOCAL program ids, so under a mesh each shard's seed is moved on by
    the programs of the shards before it
    (``short_attention.seed_at_program``): no two shards draw the same
    dropout mask."""
    if shard is None:
        return kernel(*bhsd) if seed is None else kernel(*bhsd, seed)
    mesh, batch_axis, head_axis = shard
    P = jax.sharding.PartitionSpec
    spec = P(batch_axis, head_axis, None, None)
    if seed is None:
        body, operands, in_specs = kernel, bhsd, (spec,) * len(bhsd)
    else:
        from .pallas_kernels.short_attention import seed_at_program

        axes = tuple(a for ax in (batch_axis, head_axis) if ax is not None
                     for a in ((ax,) if isinstance(ax, str) else ax))

        def body(*args):
            *local, seed = args
            b, h = local[0].shape[:2]
            first = (jax.lax.axis_index(axes) * (b * h)) if axes else 0
            return kernel(*local, seed_at_program(seed, first))

        operands, in_specs = bhsd + (seed,), (spec,) * len(bhsd) + (P(),)
    # check_vma=False: a pallas_call's outputs carry no varying-axes
    # annotation for the check to verify
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=spec, check_vma=False)(*operands)


def _fa_mod():
    from jax.experimental.pallas.ops.tpu import flash_attention as m

    return m


def _fit_block(block, n, floor=128):
    """Largest power-of-two-ish divisor of ``n`` that is <= ``block``
    (pallas requires seq_len % block == 0)."""
    block = min(block, n)
    while block > floor and n % block != 0:
        block //= 2
    return max(floor, block)


def _fa_block_sizes(q_seq_len, kv_seq_len, blocks=None):
    """Pallas flash-attention tile sizes.  ``blocks`` is a (block_q,
    block_k) pair; the default comes from the autotune cache
    (ops/autotune.py) — seeded with the v5e-measured 512/1024 (bigger q
    tiles than the library's 128 default keep the MXU busier per grid
    step), overridden by any per-shape measurement on record.  Tiles
    are clamped to divisors of the sequence lengths — pallas'
    _verify_block rejects non-dividing tiles (e.g. S=1536 with bk=1024)."""
    m = _fa_mod()
    from . import autotune as _autotune

    bq, bk = blocks if blocks is not None else _autotune.lookup(
        "fa_blocks", (q_seq_len, kv_seq_len), default=(512, 1024))
    bq = _fit_block(bq, q_seq_len)
    bk = _fit_block(bk, kv_seq_len)
    return m.BlockSizes(
        block_q=bq, block_k_major=bk, block_k=_fit_block(512, bk),
        block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk,
        block_k_dkv=_fit_block(512, bk), block_q_dkv=bq,
        block_k_major_dq=bk, block_k_dq=_fit_block(512, bk),
        block_q_dq=bq)


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_core(q, k, v, causal, scale, blocks):
    m = _fa_mod()
    bs = _fa_block_sizes(q.shape[2], k.shape[2], blocks)
    with jax.enable_x64(False):
        return m._flash_attention_impl(
            q, k, v, None, None, False, causal, scale,
            bs.block_b, bs.block_q, bs.block_k_major, bs.block_k, False)


def _flash_core_fwd(q, k, v, causal, scale, blocks):
    m = _fa_mod()
    bs = _fa_block_sizes(q.shape[2], k.shape[2], blocks)
    with jax.enable_x64(False):
        o, lse, mx = m._flash_attention_impl(
            q, k, v, None, None, True, causal, scale,
            bs.block_b, bs.block_q, bs.block_k_major, bs.block_k, False)
    return o, (q, k, v, o, lse, mx)


def _flash_core_bwd(causal, scale, blocks, res, do):
    m = _fa_mod()
    q, k, v, o, lse, mx = res
    bs = _fa_block_sizes(q.shape[2], k.shape[2], blocks)
    with jax.enable_x64(False):
        di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                     axis=-1)
        dk, dv = m._flash_attention_bwd_dkv(
            q, k, v, None, None, lse, mx, do, di,
            block_q_major=bs.block_q_major_dkv,
            block_k_major=bs.block_k_major_dkv,
            block_k=bs.block_k_dkv, block_q=bs.block_q_dkv,
            sm_scale=scale, causal=causal,
            mask_value=m.DEFAULT_MASK_VALUE, debug=False)
        dq, _ = m._flash_attention_bwd_dq(
            q, k, v, None, None, lse, mx, do, di,
            block_q_major=bs.block_q_dq, block_k_major=bs.block_k_major_dq,
            block_k=bs.block_k_dq, sm_scale=scale, causal=causal,
            mask_value=m.DEFAULT_MASK_VALUE, debug=False)
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _flash_attention_tpu(qt, kt, vt, causal, scale, blocks=None):
    """Pallas TPU flash attention ([B, H, S, D] layout), O(S)-memory.
    Reference parity: phi/kernels/gpu/flash_attn_kernel.h.

    Wraps the stock pallas kernel in our own custom_vjp so that BOTH the
    forward and backward kernel traces run with x64 disabled (the global
    x64 mode from core/dtype.py would make the kernels' weak-typed grid
    index arithmetic int64 and break mosaic lowering), and so the tile
    sizes are tunable (v5e-tuned defaults in _fa_block_sizes)."""
    return _flash_core(qt, kt, vt, bool(causal), float(scale), blocks)


def _sdpa_plain(q, k, v, mask=None, key=None, dropout=0.0, causal=False,
                scale=None, impl="auto", flash_blocks=None, shard=None):
    """Scaled dot-product attention, [B, S, H, D] layout (paddle flash-attn
    layout, nn/functional/flash_attention.py).  Computed in the MXU-friendly
    [B, H, S, D] internally.  ``key`` enables attention dropout.

    GQA (k/v heads < q heads) is computed by grouped einsum — K/V are
    NEVER materialized at q-head count (the reference flash kernel gets
    this from its head-broadcast support; repeat_interleave would burn
    HBM bandwidth).

    impl: "einsum" = XLA fused softmax-attention; "short" = the
    self-authored VMEM-resident Pallas kernel (TPU, no mask, Sq==Sk,
    S<=1024, S%128==0, D in {64, 128}, no GQA; supports in-kernel
    dropout); "flash" = stock Pallas flash kernel (TPU, no
    mask/dropout, Sq==Sk, D%128==0, S%512==0); "auto" picks short
    where its whole-[S,S]-in-VMEM regime applies, flash for long
    causal sequences (S>=1024), einsum otherwise.  The Pallas paths
    round differently from einsum (bf16 MXU accumulation) and the
    short kernel's dropout mask comes from its in-kernel counter hash,
    not the host key stream.

    shard: ``(mesh, batch_axis, head_axis)`` from :func:`kernel_mesh`
    when the caller's step spans several devices — the Pallas kernels
    then run per shard (GSPMD cannot partition a Mosaic kernel); the
    einsum path needs nothing, GSPMD partitions it.
    """
    B, Sq, H, D = q.shape
    Hkv, Sk = k.shape[2], k.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qt = jnp.swapaxes(q, 1, 2)  # B H S D
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    on_tpu = jax.devices()[0].platform == "tpu"
    # Self-authored q-blocked kernel with VMEM-resident K/V
    # (pallas_kernels/long_attention): measured 2.2x the stock flash
    # kernel at the llama bench shape (S=2048 D=128 fwd+bwd 5.0ms vs
    # 11.0ms) — at these S one head's K/V fits VMEM, so flash's
    # K-block pipeline is pure overhead.  Falls back to the stock
    # kernel via impl="flash" (e.g. S too large for resident K/V).
    # S cap 2048: the bwd kernel holds ~4 [block_q, S] f32
    # intermediates; past S=2048 they exceed scoped VMEM (and only
    # S<=2048 is benchmarked) — longer sequences take the stock
    # flash path below.
    long_ok = (mask is None and key is None and Sq == Sk
               and D % 128 == 0 and Sq % 256 == 0 and Sq <= 2048
               and Hkv == H and on_tpu)
    if impl == "auto" and long_ok and causal and Sq >= 1024:
        from . import autotune as _autotune
        from .pallas_kernels.long_attention import long_attention

        block_q = int(_autotune.lookup("long_attention_block_q",
                                       (Sq, D), default=256))
        out = _per_shard(
            shard, lambda q, k, v: long_attention(
                q, k, v, float(scale), block_q, bool(causal), None),
            qt, kt, vt)
        return jnp.swapaxes(out, 1, 2)
    # Self-authored short-sequence kernel (pallas_kernels/short_attention):
    # whole [S,S] scores VMEM-resident, in-kernel counter-hash dropout.
    # Beats einsum whenever one head's scores fit VMEM (S <= 1024) —
    # there the einsum path's HBM round-trips of [B,H,S,S] probs (and
    # dropout masks) dominate (r4 BERT profile).  Causal S == 1024 is
    # preempted by long_attention above.
    short_ok = (mask is None and Sq == Sk and Sq <= 1024
                and Sq % 128 == 0 and D % 64 == 0 and D <= 128
                and Hkv == H and on_tpu)
    use_short = short_ok and (impl == "auto" or impl == "short")
    if impl == "short" and not short_ok:
        raise ValueError(
            "impl='short' requires: TPU, no attn_mask, Sq == Sk <= "
            f"1024, seq % 128 == 0, head_dim % 64 == 0, no GQA; got "
            f"Sq={Sq} Sk={Sk} D={D} H={H} Hkv={Hkv} "
            f"mask={mask is not None}")
    if use_short:
        from .pallas_kernels import short_attention

        if key is not None:
            seed = jax.random.key_data(key).ravel()[-1].astype(jnp.int32)
            p_drop = float(dropout)
        else:
            seed = jnp.zeros((), jnp.int32)
            p_drop = 0.0
        with jax.enable_x64(False):
            out = _per_shard(
                shard, lambda q, k, v, seed: short_attention(
                    q, k, v, seed, float(scale), p_drop, bool(causal)),
                qt, kt, vt, seed=seed)
        return jnp.swapaxes(out, 1, 2)

    flash_ok = (mask is None and key is None and Sq == Sk
                and D % 128 == 0 and Sq % 512 == 0
                and on_tpu)
    if impl == "flash" and not flash_ok:
        raise ValueError(
            "impl='flash' requires: TPU backend, no attn_mask, no dropout, "
            f"Sq == Sk, head_dim % 128 == 0, seq % 512 == 0; got "
            f"Sq={Sq} Sk={Sk} D={D} mask={mask is not None} "
            f"dropout={key is not None} "
            f"platform={jax.devices()[0].platform}")
    # stock flash kernel path (impl="flash", or auto shapes the
    # resident-K/V kernel can't take)
    use_flash = impl == "flash" or (impl == "auto" and flash_ok
                                    and causal and Sq >= 1024)
    if use_flash:
        if Hkv != H:
            kt = jnp.repeat(kt, H // Hkv, axis=1)
            vt = jnp.repeat(vt, H // Hkv, axis=1)
        out = _per_shard(
            shard, lambda q, k, v: _flash_attention_tpu(
                q, k, v, causal, scale, blocks=flash_blocks),
            qt, kt, vt)
        return jnp.swapaxes(out, 1, 2)

    grouped = Hkv != H
    if grouped:
        g = H // Hkv
        qt = qt.reshape(B, Hkv, g, Sq, D)
        logits = jnp.einsum("bngqd,bnkd->bngqk", qt, kt) * scale
    else:
        logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if causal:
        causal_mask = jnp.tril(jnp.ones((Sq, Sk), dtype=bool), Sk - Sq)
        logits = jnp.where(causal_mask, logits,
                           jnp.finfo(logits.dtype).min)
    if mask is not None:
        if grouped and mask.ndim == 4:
            m = (mask.reshape(B, Hkv, H // Hkv, Sq, Sk)
                 if mask.shape[1] == H else mask[:, :, None])
            logits = logits + m
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1) \
        .astype(q.dtype)
    if key is not None and dropout > 0.0:
        keep = jax.random.bernoulli(key, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout),
                          jnp.zeros_like(probs))
    if grouped:
        out = jnp.einsum("bngqk,bnkd->bngqd", probs, vt)
        out = out.reshape(B, H, Sq, D)
    else:
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


sdpa_op = register_op(
    "scaled_dot_product_attention", _sdpa_plain,
    static_argnames=("dropout", "causal", "scale", "impl", "flash_blocks",
                     "shard"),
    nondiff_argnums=(3, 4))


# -- rope -------------------------------------------------------------------

def _rope_plain(q, k, cos, sin, position_ids=None, neox=True):
    """Rotary embedding on [B, S, H, D]; cos/sin are [S_max, D] tables.

    position_ids [B, S] selects table rows (left-padded / packed
    sequences); neox=True rotates half-split pairs, neox=False rotates
    interleaved even/odd pairs — matching the reference fused_rope's
    use_neox_rotary_style (phi/kernels/fusion fused_rope).
    """
    if position_ids is not None:
        c = cos[position_ids][:, :, None, :]   # [B, S, 1, D]
        s = sin[position_ids][:, :, None, :]
    else:
        S = q.shape[1]
        c = cos[None, :S, None, :]
        s = sin[None, :S, None, :]

    if neox:
        def rot(x):
            x1, x2 = jnp.split(x, 2, axis=-1)
            return jnp.concatenate([-x2, x1], axis=-1)
    else:
        def rot(x):
            x1 = x[..., 0::2]
            x2 = x[..., 1::2]
            return jnp.stack([-x2, x1], axis=-1).reshape(x.shape)

    return q * c + rot(q) * s, k * c + rot(k) * s


fused_rope_op = register_op("fused_rotary_position_embedding", _rope_plain,
                            n_outputs=2, static_argnames=("neox",),
                            nondiff_argnums=(4,))


# -- interpolate (nearest/bilinear) ----------------------------------------

def _interp_plain(x, size, mode="nearest", align_corners=False,
                  data_format="NCHW"):
    if data_format == "NCHW":
        x = jnp.transpose(x, (0, 2, 3, 1))
    method = {"nearest": "nearest", "bilinear": "linear",
              "bicubic": "cubic"}[mode]
    out = jax.image.resize(x, (x.shape[0], size[0], size[1], x.shape[3]),
                           method=method)
    if data_format == "NCHW":
        out = jnp.transpose(out, (0, 3, 1, 2))
    return out


interpolate_op = register_op(
    "interpolate", _interp_plain,
    static_argnames=("size", "mode", "align_corners", "data_format"))
