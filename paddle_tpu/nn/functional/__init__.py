"""paddle.nn.functional analog.

Reference: ``python/paddle/nn/functional/`` — thin wrappers binding the op
library to the nn API surface (linear/conv/norm/loss/attention/...).
"""
from __future__ import annotations

import numpy as np

from ... import ops
from ...core.tensor import Tensor
from ...ops import (  # noqa: F401  - re-exported activations
    celu, elu, gelu, glu, hardshrink, hardsigmoid, hardswish, hardtanh,
    leaky_relu, log_sigmoid, log_softmax, mish, prelu, relu, relu6, selu,
    sigmoid, silu, softmax, softplus, softshrink, softsign, swish, swiglu,
    tanh, tanhshrink, thresholded_relu,
)
from ...ops import nn_ops, registry
from ...ops.manipulation import pad  # noqa: F401
from ...ops.nn_ops import _pair


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b (W is [in, out] like the reference, ops.yaml `linear`)."""
    out = ops.matmul(x, weight)
    if bias is not None:
        # The reference `linear` op adds bias in the matmul's compute
        # dtype; without this, an fp32 bias would promote an autocast
        # bf16 matmul back to fp32.
        if bias.dtype != out.dtype:
            bias = ops.cast(bias, out.dtype)
        out = ops.add(out, bias)
    return out


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    return registry.apply(nn_ops.embedding_op, weight, x,
                          padding_idx=padding_idx)


def one_hot(x, num_classes, name=None):
    return ops.one_hot(x, num_classes)


# -- conv / pool ------------------------------------------------------------

def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    out = nn_ops.conv2d_raw(x, weight, stride, padding, dilation, groups,
                            data_format)
    if bias is not None:
        shape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        out = ops.add(out, ops.reshape(bias, shape))
    return out


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    out = registry.apply(nn_ops.conv1d_op, x, weight, stride=int(stride),
                         padding=int(padding) if not isinstance(
                             padding, (list, tuple)) else int(padding[0]),
                         dilation=int(dilation), groups=int(groups))
    if bias is not None:
        out = ops.add(out, ops.reshape(bias, (1, -1, 1)))
    return out


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     output_size=None, data_format="NCHW", name=None):
    out = registry.apply(
        nn_ops.conv2d_transpose_op, x, weight, stride=_pair(stride),
        padding=_pair(padding), output_padding=_pair(output_padding),
        dilation=_pair(dilation), groups=int(groups),
        data_format=data_format)
    if bias is not None:
        shape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        out = ops.add(out, ops.reshape(bias, shape))
    return out


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW", name=None):
    stride = stride if stride is not None else kernel_size
    if return_mask:
        from ...ops import nn_ops_nd as nd

        if ceil_mode:
            raise NotImplementedError(
                "max_pool2d(return_mask=True) does not support "
                "ceil_mode")
        if data_format == "NHWC":
            v, i = max_pool2d(ops.transpose(x, [0, 3, 1, 2]),
                              kernel_size, stride, padding,
                              return_mask=True)
            return (ops.transpose(v, [0, 2, 3, 1]),
                    ops.transpose(i, [0, 2, 3, 1]))
        return registry.apply(nd.max_pool_with_index_op, x,
                              kernel_size=_pair(kernel_size),
                              stride=_pair(stride),
                              padding=_pair(padding))
    return registry.apply(nn_ops.max_pool2d_op, x,
                          kernel_size=_pair(kernel_size),
                          stride=_pair(stride), padding=_pair(padding),
                          ceil_mode=bool(ceil_mode),
                          data_format=data_format)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW",
               name=None):
    stride = stride if stride is not None else kernel_size
    if ceil_mode or divisor_override is not None:
        # exact ceil/divisor semantics live in the generic N-d op
        from ...ops import nn_ops_nd as nd_ops

        if data_format == "NHWC":
            out = avg_pool2d(ops.transpose(x, [0, 3, 1, 2]),
                             kernel_size, stride, padding, ceil_mode,
                             exclusive, divisor_override)
            return ops.transpose(out, [0, 2, 3, 1])
        return registry.apply(
            nd_ops.avg_pool2d_g_op, x, kernel_size=_pair(kernel_size),
            stride=_pair(stride), padding=_pair(padding),
            ceil_mode=bool(ceil_mode), exclusive=bool(exclusive),
            divisor_override=None if divisor_override is None
            else float(divisor_override))
    return registry.apply(nn_ops.avg_pool2d_op, x,
                          kernel_size=_pair(kernel_size),
                          stride=_pair(stride), padding=_pair(padding),
                          exclusive=bool(exclusive),
                          data_format=data_format)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return registry.apply(nn_ops.adaptive_avg_pool2d_op, x,
                          output_size=_pair(output_size),
                          data_format=data_format)


# -- norms ------------------------------------------------------------------

def layer_norm(x, normalized_shape=None, weight=None, bias=None,
               epsilon=1e-5, name=None):
    if isinstance(normalized_shape, int):
        begin = -1
    elif normalized_shape is not None:
        begin = x.ndim - len(tuple(normalized_shape))
    else:
        begin = -1
    weight, bias = _norm_affine_pair(weight, bias)
    args = [x] + [a for a in (weight, bias) if a is not None]
    return registry.apply(nn_ops.layer_norm_op, *args,
                          epsilon=float(epsilon), begin_norm_axis=begin)


def _norm_affine_pair(weight, bias):
    """Norm ops take (weight[, bias]) positionally; a bias without a weight
    must not slide into the weight slot — substitute a ones weight."""
    if weight is None and bias is not None:
        from ... import ops as _ops

        weight = _ops.ones_like(bias)
    return weight, bias


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    if weight is not None:
        from ...core.flags import flag

        if flag("FLAGS_use_fused_rms_norm"):
            from ...ops.pallas_kernels.rms_norm import handle

            return handle()(x, weight, epsilon=float(epsilon))
        return registry.apply(nn_ops.rms_norm_op, x, weight,
                              epsilon=float(epsilon))
    return registry.apply(nn_ops.rms_norm_op, x, epsilon=float(epsilon))


def _bn_running_update(running_mean, running_var, mean_t, var_t,
                       momentum):
    """Update running stats in place (reference batch_norm semantics).
    NOT under a jit trace: storing a tracer into the persistent buffer
    would leak it (UnexpectedTracerError on any later use) and the
    "update" would never really happen.  Compiled train steps
    (CompiledTrainStep) therefore train with batch stats and leave
    running stats at their last eager value — functionalized buffer
    updates ride the to_static path (jit/__init__.py), which returns
    new buffer values explicitly."""
    import jax as _jax

    if running_mean is not None and not isinstance(
            mean_t._data, _jax.core.Tracer):
        m = momentum
        running_mean.set_value(
            m * running_mean._data + (1 - m) * mean_t._data)
        running_var.set_value(
            m * running_var._data + (1 - m) * var_t._data)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    weight_a, bias_a = _norm_affine_pair(weight, bias)
    if training and not use_global_stats and weight_a is not None \
            and bias_a is not None:
        # fused train-mode op: one stats pass + hand-written 2-pass VJP
        # (see nn_ops._bn_train_fwd; r4 ResNet profile)
        out, mean_t, var_t = registry.apply(
            nn_ops.batch_norm_train_op, x, weight_a, bias_a,
            epsilon=float(epsilon), data_format=data_format)
        _bn_running_update(running_mean, running_var, mean_t, var_t,
                           momentum)
        return out
    if training and not use_global_stats:
        mean_t, var_t = registry.apply(nn_ops.batch_norm_stats_op, x,
                                       data_format=data_format)
        _bn_running_update(running_mean, running_var, mean_t, var_t,
                           momentum)
        use_mean, use_var = mean_t, var_t
    else:
        use_mean, use_var = running_mean, running_var
    args = [x, use_mean, use_var] + [a for a in (weight_a, bias_a)
                                     if a is not None]
    return registry.apply(nn_ops.batch_norm_infer_op, *args,
                          epsilon=float(epsilon), data_format=data_format)


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW", name=None):
    weight, bias = _norm_affine_pair(weight, bias)
    args = [x] + [a for a in (weight, bias) if a is not None]
    return registry.apply(nn_ops.group_norm_op, *args,
                          epsilon=float(epsilon), groups=int(num_groups),
                          data_format=data_format)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    n = ops.norm(x, p=p, axis=axis, keepdim=True)
    n = ops.clip(n, min=epsilon)
    return ops.divide(x, n)


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    return nn_ops.dropout_raw(x, p=p, training=training, mode=mode)


# -- losses -----------------------------------------------------------------

def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return ops.mean(loss)
    if reduction == "sum":
        return ops.sum(loss)
    return loss


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    """Reference: python/paddle/nn/functional/loss.py cross_entropy."""
    if label_smoothing > 0.0:
        num_classes = input.shape[axis]
        if not soft_label:
            label = ops.one_hot(label, num_classes)
            soft_label = True
        label = ops.add(
            ops.scale(label, scale=1.0 - label_smoothing),
            ops.full([1], label_smoothing / num_classes,
                     dtype=str(input.dtype)))
    if not soft_label and label.ndim == input.ndim:
        label = ops.squeeze(label, axis=axis)
    loss = registry.apply(
        nn_ops.softmax_with_cross_entropy_op, input, label,
        soft_label=bool(soft_label),
        ignore_index=int(ignore_index), axis=int(axis))
    loss = ops.squeeze(loss, axis=-1)
    if weight is not None and not soft_label:
        w = ops.gather(weight, ops.reshape(label, [-1]))
        w = ops.reshape(w, loss.shape)
        loss = ops.multiply(loss, ops.cast(w, str(loss.dtype)))
    if reduction == "mean" and not soft_label and ignore_index is not None \
            and ignore_index >= 0:
        valid = ops.cast(ops.not_equal(label, ignore_index),
                         str(loss.dtype))
        denom = ops.maximum(ops.sum(valid),
                            ops.full([], 1.0, str(loss.dtype)))
        return ops.divide(ops.sum(loss), denom)
    return _reduce_loss(loss, reduction)


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    loss = registry.apply(nn_ops.softmax_with_cross_entropy_op, logits,
                          label if soft_label else ops.squeeze(label, -1)
                          if label.ndim == logits.ndim else label,
                          soft_label=bool(soft_label),
                          ignore_index=int(ignore_index), axis=int(axis))
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def mse_loss(input, label, reduction="mean", name=None):
    d = ops.subtract(input, label)
    return _reduce_loss(ops.multiply(d, d), reduction)


def l1_loss(input, label, reduction="mean", name=None):
    return _reduce_loss(ops.abs(ops.subtract(input, label)), reduction)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    d = ops.subtract(input, label)
    ad = ops.abs(d)
    quad = ops.multiply(ops.scale(ops.multiply(d, d), scale=0.5 / delta),
                        ops.ones_like(d))
    lin = ops.subtract(ad, ops.full([], 0.5 * delta, str(input.dtype)))
    loss = ops.where(ops.less_than(ad, ops.full([], delta,
                                                str(input.dtype))),
                     quad, lin)
    return _reduce_loss(loss, reduction)


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    picked = ops.neg(ops.squeeze(ops.take_along_axis(
        input, ops.unsqueeze(ops.cast(label, "int64"), -1), axis=-1), -1))
    if weight is not None:
        w = ops.gather(weight, ops.reshape(label, [-1]))
        picked = ops.multiply(picked, ops.reshape(w, picked.shape))
    return _reduce_loss(picked, reduction)


def binary_cross_entropy(input, label, weight=None, reduction="mean",
                         name=None):
    eps = 1e-12
    clipped = ops.clip(input, min=eps, max=1 - eps)
    loss = ops.neg(ops.add(
        ops.multiply(label, ops.log(clipped)),
        ops.multiply(ops.scale(label, scale=-1.0, bias=1.0),
                     ops.log(ops.scale(clipped, scale=-1.0, bias=1.0)))))
    if weight is not None:
        loss = ops.multiply(loss, weight)
    return _reduce_loss(loss, reduction)


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    # max(x,0) - x*y + log(1 + exp(-|x|))
    neg_abs = ops.neg(ops.abs(logit))
    loss = ops.add(
        ops.subtract(ops.relu(logit), ops.multiply(logit, label)),
        ops.log1p(ops.exp(neg_abs)))
    if pos_weight is not None:
        log_w = ops.add(
            ops.multiply(ops.subtract(pos_weight,
                                      ops.ones_like(pos_weight)), label),
            ops.ones_like(label))
        loss = ops.multiply(loss, log_w)
    if weight is not None:
        loss = ops.multiply(loss, weight)
    return _reduce_loss(loss, reduction)


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    if log_target:
        loss = ops.multiply(ops.exp(label), ops.subtract(label, input))
    else:
        safe = ops.maximum(label, ops.full([], 1e-12, str(label.dtype)))
        loss = ops.multiply(label, ops.subtract(ops.log(safe), input))
    if reduction == "batchmean":
        return ops.divide(ops.sum(loss),
                          ops.full([], float(input.shape[0]),
                                   str(input.dtype)))
    return _reduce_loss(loss, reduction)


def log_loss(input, label, epsilon=1e-4, name=None):
    return binary_cross_entropy(input, label, reduction="none")


# -- attention --------------------------------------------------------------

def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None, impl="auto",
                                 flash_blocks=None):
    """[batch, seq, heads, head_dim] layout — reference:
    python/paddle/nn/functional/flash_attention.py
    scaled_dot_product_attention.  GQA (key/value heads < query heads) is
    computed grouped, never materializing repeated K/V.  ``impl`` selects
    the attention kernel: "einsum" (XLA fused), "flash" (Pallas TPU
    flash kernel), or "auto"."""
    drop_key = None
    if dropout_p > 0.0 and training:
        from ...ops.random import default_generator

        drop_key = default_generator.next_fast_key()
    return registry.apply(nn_ops.sdpa_op, query, key, value, attn_mask,
                          drop_key, dropout=float(dropout_p),
                          causal=bool(is_causal), impl=impl,
                          flash_blocks=flash_blocks,
                          shard=nn_ops.current_kernel_mesh())


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    training=True, name=None):
    if return_softmax:
        raise NotImplementedError(
            "flash_attention(return_softmax=True) is not supported — the "
            "fused path never materializes the softmax matrix")
    out = scaled_dot_product_attention(query, key, value,
                                       dropout_p=dropout, is_causal=causal,
                                       training=training)
    return out, None


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True):
    """Reference: phi fused_rope (ops/yaml/fused_ops.yaml)."""
    import jax.numpy as jnp

    pos = position_ids._data if isinstance(position_ids, Tensor) \
        else position_ids
    qk = registry.apply(nn_ops.fused_rope_op, q, k,
                        ops.cast(Tensor(cos._data if isinstance(cos, Tensor)
                                        else jnp.asarray(cos)),
                                 str(q.dtype)),
                        ops.cast(Tensor(sin._data if isinstance(sin, Tensor)
                                        else jnp.asarray(sin)),
                                 str(q.dtype)),
                        pos, neox=bool(use_neox_rotary_style))
    qo, ko = qk
    return qo, ko, v


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    if size is None:
        h = int(x.shape[2] * scale_factor) if data_format == "NCHW" \
            else int(x.shape[1] * scale_factor)
        w = int(x.shape[3] * scale_factor) if data_format == "NCHW" \
            else int(x.shape[2] * scale_factor)
        size = (h, w)
    else:
        size = tuple(int(s) for s in size)
    return registry.apply(nn_ops.interpolate_op, x, size=size, mode=mode,
                          align_corners=bool(align_corners),
                          data_format=data_format)


upsample = interpolate


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    import jax

    k = _pair(kernel_sizes)
    s = _pair(strides)
    p = _pair(paddings)
    d = _pair(dilations)
    n, c, h, w = x.shape
    patches = jax.lax.conv_general_dilated_patches(
        x._data, filter_shape=k, window_strides=s,
        padding=[(p[0], p[0]), (p[1], p[1])], rhs_dilation=d,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    np_, cp, hp, wp = patches.shape
    return Tensor(patches.reshape(np_, cp, hp * wp))


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    n = label.shape[-1]
    smoothed = ops.scale(label, scale=1 - epsilon, bias=epsilon / n)
    return smoothed

from .extended import (  # noqa: F401,E402
    affine_grid, channel_shuffle, cosine_embedding_loss,
    cosine_similarity, ctc_loss, fold, gaussian_nll_loss, grid_sample,
    gumbel_softmax, hinge_embedding_loss, margin_ranking_loss,
    multi_label_soft_margin_loss, npair_loss, pairwise_distance,
    pixel_shuffle, pixel_unshuffle, poisson_nll_loss, soft_margin_loss,
    square_error_cost, triplet_margin_loss,
)


# -- N-d conv/pool tail (round 4 breadth; ops/nn_ops_nd.py) -----------------

def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCL", name=None):
    from ...ops import nn_ops_nd as nd

    out = registry.apply(nd.conv1d_transpose_op, x, weight,
                         stride=int(stride), padding=int(padding),
                         output_padding=int(output_padding),
                         dilation=int(dilation), groups=int(groups))
    if bias is not None:
        out = ops.add(out, ops.reshape(bias, (1, -1, 1)))
    return out


def _triple(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * 3


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1,
           groups=1, data_format="NCDHW", name=None):
    from ...ops import nn_ops_nd as nd

    out = registry.apply(nd.conv3d_op, x, weight,
                         stride=_triple(stride),
                         padding=_triple(padding),
                         dilation=_triple(dilation), groups=int(groups))
    if bias is not None:
        out = ops.add(out, ops.reshape(bias, (1, -1, 1, 1, 1)))
    return out


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCDHW", name=None):
    from ...ops import nn_ops_nd as nd

    out = registry.apply(nd.conv3d_transpose_op, x, weight,
                         stride=_triple(stride),
                         padding=_triple(padding),
                         output_padding=_triple(output_padding),
                         dilation=_triple(dilation), groups=int(groups))
    if bias is not None:
        out = ops.add(out, ops.reshape(bias, (1, -1, 1, 1, 1)))
    return out


def _pool_args(kernel_size, stride, padding, n):
    def tup(v):
        if isinstance(v, (list, tuple)):
            return tuple(int(x) for x in v)
        return (int(v),) * n

    stride = kernel_size if stride is None else stride
    return tup(kernel_size), tup(stride), tup(padding)


def max_pool1d(x, kernel_size, stride=None, padding=0,
               return_mask=False, ceil_mode=False, name=None):
    from ...ops import nn_ops_nd as nd

    k, s, p = _pool_args(kernel_size, stride, padding, 1)
    if return_mask:
        if ceil_mode:
            raise NotImplementedError(
                "max_pool1d(return_mask=True) does not support "
                "ceil_mode")
        return registry.apply(nd.max_pool_with_index_op, x,
                              kernel_size=k, stride=s, padding=p)
    return registry.apply(nd.max_pool1d_op, x, kernel_size=k, stride=s,
                          padding=p, ceil_mode=bool(ceil_mode))


def max_pool3d(x, kernel_size, stride=None, padding=0,
               return_mask=False, ceil_mode=False,
               data_format="NCDHW", name=None):
    from ...ops import nn_ops_nd as nd

    k, s, p = _pool_args(kernel_size, stride, padding, 3)
    if return_mask:
        if ceil_mode:
            raise NotImplementedError(
                "max_pool3d(return_mask=True) does not support "
                "ceil_mode")
        return registry.apply(nd.max_pool_with_index_op, x,
                              kernel_size=k, stride=s, padding=p)
    return registry.apply(nd.max_pool3d_op, x, kernel_size=k, stride=s,
                          padding=p, ceil_mode=bool(ceil_mode))


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, name=None):
    from ...ops import nn_ops_nd as nd

    k, s, p = _pool_args(kernel_size, stride, padding, 1)
    return registry.apply(nd.avg_pool1d_op, x, kernel_size=k, stride=s,
                          padding=p, ceil_mode=bool(ceil_mode),
                          exclusive=bool(exclusive))


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None,
               data_format="NCDHW", name=None):
    from ...ops import nn_ops_nd as nd

    k, s, p = _pool_args(kernel_size, stride, padding, 3)
    return registry.apply(
        nd.avg_pool3d_op, x, kernel_size=k, stride=s, padding=p,
        ceil_mode=bool(ceil_mode), exclusive=bool(exclusive),
        divisor_override=None if divisor_override is None
        else float(divisor_override))


def lp_pool1d(x, norm_type, kernel_size, stride=None, padding=0,
              ceil_mode=False, name=None):
    from ...ops import nn_ops_nd as nd

    k, s, p = _pool_args(kernel_size, stride, padding, 1)
    return registry.apply(nd.lp_pool1d_op, x, kernel_size=k, stride=s,
                          padding=p, norm_type=float(norm_type))


def lp_pool2d(x, norm_type, kernel_size, stride=None, padding=0,
              ceil_mode=False, data_format="NCHW", name=None):
    from ...ops import nn_ops_nd as nd

    k, s, p = _pool_args(kernel_size, stride, padding, 2)
    return registry.apply(nd.lp_pool2d_op, x, kernel_size=k, stride=s,
                          padding=p, norm_type=float(norm_type))


def _out_size(v, n):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


def adaptive_avg_pool1d(x, output_size, name=None):
    from ...ops import nn_ops_nd as nd

    return registry.apply(nd.adaptive_avg_pool1d_op, x,
                          output_size=_out_size(output_size, 1))


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    from ...ops import nn_ops_nd as nd

    return registry.apply(nd.adaptive_avg_pool3d_op, x,
                          output_size=_out_size(output_size, 3))


def _adaptive_max(x, output_size, n, return_mask):
    from ...ops import nn_ops_nd as nd

    op = {1: nd.adaptive_max_pool1d_op, 2: nd.adaptive_max_pool2d_op,
          3: nd.adaptive_max_pool3d_op}[n]
    out = registry.apply(op, x, output_size=_out_size(output_size, n))
    if return_mask:
        # indices recomputed via a full argmax pass per region is
        # rarely needed; reference returns (out, mask) — provide mask
        # via max_pool_with_index only for uniform regions
        raise NotImplementedError(
            "return_mask with adaptive max pooling is not supported; "
            "use max_poolNd(return_mask=True) with explicit kernels")
    return out


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    return _adaptive_max(x, output_size, 1, return_mask)


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    return _adaptive_max(x, output_size, 2, return_mask)


def adaptive_max_pool3d(x, output_size, return_mask=False, name=None):
    return _adaptive_max(x, output_size, 3, return_mask)


def _max_unpool(x, indices, n, kernel_size, stride=None, padding=0,
                output_size=None):
    from ...ops import nn_ops_nd as nd

    k, s, p = _pool_args(kernel_size, stride, padding, n)
    if output_size is None:
        out_spatial = tuple(
            (x.shape[2 + i] - 1) * s[i] - 2 * p[i] + k[i]
            for i in range(n))
    else:
        out_spatial = tuple(int(v) for v in output_size[-n:])
    return registry.apply(nd.max_unpool_op, x, indices,
                          out_spatial=out_spatial)


def max_unpool1d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCL", output_size=None, name=None):
    return _max_unpool(x, indices, 1, kernel_size, stride, padding,
                       output_size)


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCHW", output_size=None, name=None):
    return _max_unpool(x, indices, 2, kernel_size, stride, padding,
                       output_size)


def max_unpool3d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCDHW", output_size=None, name=None):
    return _max_unpool(x, indices, 3, kernel_size, stride, padding,
                       output_size)


def fractional_max_pool2d(x, output_size, kernel_size=None,
                          random_u=None, return_mask=False, name=None):
    from ...ops import nn_ops_nd as nd
    from ...ops.random import default_generator

    import jax as _jax

    if return_mask:
        raise NotImplementedError(
            "fractional_max_pool2d(return_mask=True) is not supported")
    if random_u is None:
        key = default_generator.next_key()
        random_u = float(_jax.random.uniform(key, ()))
    us = (float(random_u),) * 2
    return registry.apply(nd.fractional_max_pool_op, x,
                          output_size=_out_size(output_size, 2), us=us)


def fractional_max_pool3d(x, output_size, kernel_size=None,
                          random_u=None, return_mask=False, name=None):
    from ...ops import nn_ops_nd as nd
    from ...ops.random import default_generator

    import jax as _jax

    if return_mask:
        raise NotImplementedError(
            "fractional_max_pool3d(return_mask=True) is not supported")
    if random_u is None:
        key = default_generator.next_key()
        random_u = float(_jax.random.uniform(key, ()))
    us = (float(random_u),) * 3
    return registry.apply(nd.fractional_max_pool_op, x,
                          output_size=_out_size(output_size, 3), us=us)


# -- dropout/pad/misc tail ---------------------------------------------------

def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    """Channel-wise dropout for 5-D input (reference common.dropout3d:
    drops whole channels)."""
    if not training or p == 0.0:
        return x
    from ...ops import nn_ops as _nn
    from ...ops.random import default_generator

    import jax as _jax
    import jax.numpy as _jnp

    keep = 1.0 - p
    key = default_generator.next_fast_key()
    shape = ((x.shape[0], x.shape[1], 1, 1, 1)
             if data_format == "NCDHW"
             else (x.shape[0], 1, 1, 1, x.shape[-1]))
    mask = _jax.random.bernoulli(key, keep, shape)

    def fn(xd, mask, keep):
        return _jnp.where(mask, xd / keep, _jnp.zeros_like(xd))

    return registry.cached_apply("dropout3d", fn, x, Tensor(mask),
                                 keep=float(keep))


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    """Channel-wise dropout for 4-D input."""
    if not training or p == 0.0:
        return x
    from ...ops.random import default_generator

    import jax as _jax
    import jax.numpy as _jnp

    keep = 1.0 - p
    key = default_generator.next_fast_key()
    shape = ((x.shape[0], x.shape[1], 1, 1) if data_format == "NCHW"
             else (x.shape[0], 1, 1, x.shape[-1]))
    mask = _jax.random.bernoulli(key, keep, shape)

    def fn(xd, mask, keep):
        return _jnp.where(mask, xd / keep, _jnp.zeros_like(xd))

    return registry.cached_apply("dropout2d", fn, x, Tensor(mask),
                                 keep=float(keep))


def alpha_dropout(x, p=0.5, training=True, name=None):
    """SELU-preserving dropout (reference common.alpha_dropout)."""
    if not training or p == 0.0:
        return x
    from ...ops.random import default_generator

    import jax as _jax
    import jax.numpy as _jnp

    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = 1.0 - p
    a = (keep + alpha_p ** 2 * keep * (1 - keep)) ** -0.5
    b = -a * alpha_p * (1 - keep)
    key = default_generator.next_fast_key()
    mask = _jax.random.bernoulli(key, keep, tuple(x.shape))

    def fn(xd, mask, a, b, alpha_p):
        return a * _jnp.where(mask, xd, alpha_p) + b

    return registry.cached_apply("alpha_dropout", fn, x, Tensor(mask),
                                 a=float(a), b=float(b),
                                 alpha_p=float(alpha_p))


def feature_alpha_dropout(x, p=0.5, training=True, name=None):
    """alpha_dropout dropping whole channels."""
    if not training or p == 0.0:
        return x
    from ...ops.random import default_generator

    import jax as _jax
    import jax.numpy as _jnp

    alpha_p = -1.6732632423543772 * 1.0507009873554805
    keep = 1.0 - p
    a = (keep + alpha_p ** 2 * keep * (1 - keep)) ** -0.5
    b = -a * alpha_p * (1 - keep)
    key = default_generator.next_fast_key()
    shape = (x.shape[0], x.shape[1]) + (1,) * (x.ndim - 2)
    mask = _jax.random.bernoulli(key, keep, shape)

    def fn(xd, mask, a, b, alpha_p):
        return a * _jnp.where(mask, xd, alpha_p) + b

    return registry.cached_apply("feature_alpha_dropout", fn, x,
                                 Tensor(mask), a=float(a), b=float(b),
                                 alpha_p=float(alpha_p))


def zeropad2d(x, padding, data_format="NCHW", name=None):
    p = padding if isinstance(padding, (list, tuple)) else [padding] * 4
    left, right, top, bottom = (int(v) for v in p)
    # pad takes paddle's last-dim-first flat list: [W_l, W_r, H_t, H_b]
    return pad(x, [left, right, top, bottom])


def bilinear(x1, x2, weight, bias=None, name=None):
    """out[b, o] = x1[b, :] W[o] x2[b, :] + bias (reference
    common.bilinear; weight [out, in1, in2])."""
    def fn(a, b, w):
        import jax.numpy as _jnp

        return _jnp.einsum("bi,oij,bj->bo", a, w, b)

    out = registry.cached_apply("bilinear", fn, x1, x2, weight)
    if bias is not None:
        out = ops.add(out, bias)
    return out


def maxout(x, groups, axis=1, name=None):
    """reference activation.maxout: channel groups -> max."""
    def fn(xd, groups, axis):
        import jax.numpy as _jnp

        shape = list(xd.shape)
        c = shape[axis]
        shape[axis:axis + 1] = [groups, c // groups]
        return _jnp.max(xd.reshape(shape), axis=axis + 1)

    return registry.cached_apply("maxout", fn, x, groups=int(groups),
                                 axis=int(axis))


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    """reference sequence_mask: [..., maxlen] with 1 where idx < len."""
    import jax.numpy as _jnp

    data = x._data if isinstance(x, Tensor) else _jnp.asarray(x)
    if maxlen is None:
        import numpy as _np

        maxlen = int(_np.asarray(data).max())
    ar = _jnp.arange(int(maxlen))
    out = (ar[None, :] < data[..., None].astype(ar.dtype))
    from ...core import dtype as _dt

    return Tensor(out.astype(_dt.convert_dtype(dtype)))


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=True,
          name=None):
    """reference activation.rrelu: random leaky slope in train."""
    if not training:
        return ops.leaky_relu(x, (lower + upper) / 2.0)
    from ...ops.random import default_generator

    import jax as _jax
    import jax.numpy as _jnp

    key = default_generator.next_fast_key()
    slope = _jax.random.uniform(key, tuple(x.shape), _jnp.float32,
                                lower, upper)

    def fn(xd, slope):
        return _jnp.where(xd >= 0, xd, slope.astype(xd.dtype) * xd)

    return registry.cached_apply("rrelu", fn, x, Tensor(slope))


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    """reference norm.local_response_norm (cross-channel window)."""
    def fn(xd, size, alpha, beta, k):
        import jax as _jax
        import jax.numpy as _jnp

        sq = _jnp.square(xd)
        half = size // 2
        # sum over a channel window via padded reduce_window on axis 1
        window = (1, size) + (1,) * (xd.ndim - 2)
        pads = ((0, 0), (half, size - 1 - half)) +             ((0, 0),) * (xd.ndim - 2)
        s = _jax.lax.reduce_window(sq, 0.0, _jax.lax.add, window,
                                   (1,) * xd.ndim, pads)
        div = _jnp.power(k + alpha * s / size, beta)
        return xd / div

    return registry.cached_apply("local_response_norm", fn, x,
                                 size=int(size), alpha=float(alpha),
                                 beta=float(beta), k=float(k))


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9,
                  eps=1e-5, data_format="NCHW", name=None):
    """reference norm.instance_norm: per-(N, C) spatial stats."""
    def fn(*args, has_w, has_b, eps):
        import jax.numpy as _jnp

        xd = args[0]
        axes = tuple(range(2, xd.ndim))
        mu = _jnp.mean(xd, axes, keepdims=True)
        var = _jnp.var(xd, axes, keepdims=True)
        out = (xd - mu) * (1.0 / _jnp.sqrt(var + eps))
        shape = (1, -1) + (1,) * (xd.ndim - 2)
        i = 1
        if has_w:
            out = out * args[i].reshape(shape)
            i += 1
        if has_b:
            out = out + args[i].reshape(shape)
        return out

    args = [x] + [t for t in (weight, bias) if t is not None]
    return registry.cached_apply("instance_norm", fn, *args,
                                 has_w=weight is not None,
                                 has_b=bias is not None,
                                 eps=float(eps))


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW",
                   name=None):
    """reference extension.temporal_shift (TSM)."""
    def fn(xd, seg_num, shift_ratio):
        import jax.numpy as _jnp

        NT, C, H, W = xd.shape
        N = NT // seg_num
        v = xd.reshape(N, seg_num, C, H, W)
        c1 = int(C * shift_ratio)
        c2 = int(C * 2 * shift_ratio)
        left = _jnp.concatenate(
            [v[:, 1:, :c1], _jnp.zeros_like(v[:, :1, :c1])], 1)
        right = _jnp.concatenate(
            [_jnp.zeros_like(v[:, :1, c1:c2]), v[:, :-1, c1:c2]], 1)
        mid = v[:, :, c2:]
        return _jnp.concatenate([left, right, mid], 2).reshape(
            NT, C, H, W)

    return registry.cached_apply("temporal_shift", fn, x,
                                 seg_num=int(seg_num),
                                 shift_ratio=float(shift_ratio))


def gather_tree(ids, parents, name=None):
    """reference extension.gather_tree: beam-search backtrace
    [T, B, W]."""
    def fn(ids_d, parents_d):
        import jax as _jax
        import jax.numpy as _jnp

        T = ids_d.shape[0]

        def body(carry, t):
            beams = carry  # [B, W] beam index at step t+1
            tok = _jnp.take_along_axis(ids_d[t], beams, axis=1)
            par = _jnp.take_along_axis(parents_d[t], beams, axis=1)
            return par, tok

        W = ids_d.shape[2]
        init = _jnp.broadcast_to(_jnp.arange(W, dtype=ids_d.dtype),
                                 ids_d.shape[1:])
        _, toks = _jax.lax.scan(body, init,
                                _jnp.arange(T - 1, -1, -1))
        return toks[::-1]

    return registry.cached_apply("gather_tree", fn, ids, parents)


# -- loss tail (round 4 breadth) ---------------------------------------------

def dice_loss(input, label, epsilon=1e-5, name=None):
    """reference loss.dice_loss: 1 - 2|X∩Y| / (|X|+|Y|)."""
    def fn(p, y, eps):
        import jax
        import jax.numpy as _jnp

        yf = jax.nn.one_hot(
            y.squeeze(-1), p.shape[-1]).astype(p.dtype) \
            if y.shape[-1] == 1 else y.astype(p.dtype)
        red = tuple(range(1, p.ndim))
        inter = _jnp.sum(p * yf, red)
        union = _jnp.sum(p, red) + _jnp.sum(yf, red)
        return _jnp.mean(1.0 - (2.0 * inter + eps) / (union + eps))

    return registry.cached_apply("dice_loss", fn, input, label,
                                 eps=float(epsilon))


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25,
                       gamma=2.0, reduction="sum", name=None):
    """reference loss.sigmoid_focal_loss."""
    def fn(*args, alpha, gamma, reduction, has_norm):
        import jax
        import jax.numpy as _jnp

        lg, y = args[0], args[1]
        p = jax.nn.sigmoid(lg)
        ce = (_jnp.maximum(lg, 0) - lg * y
              + _jnp.log1p(_jnp.exp(-_jnp.abs(lg))))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * ((1 - p_t) ** gamma) * ce
        if has_norm:
            loss = loss / args[2]
        if reduction == "mean":
            return _jnp.mean(loss)
        if reduction == "sum":
            return _jnp.sum(loss)
        return loss

    args = [logit, label] + ([normalizer] if normalizer is not None
                             else [])
    return registry.cached_apply(
        "sigmoid_focal_loss", fn, *args, alpha=float(alpha),
        gamma=float(gamma), reduction=str(reduction),
        has_norm=normalizer is not None)


def multi_margin_loss(input, label, p=1, margin=1.0, weight=None,
                      reduction="mean", name=None):
    """reference loss.multi_margin_loss."""
    def fn(*args, p, margin, reduction, has_w):
        import jax.numpy as _jnp

        x, y = args[0], args[1]
        N, C = x.shape
        correct = _jnp.take_along_axis(x, y[:, None], 1)
        diff = _jnp.maximum(margin - correct + x, 0.0) ** p
        if has_w:
            diff = diff * args[2][y][:, None]
        mask = _jnp.arange(C)[None, :] != y[:, None]
        loss = _jnp.sum(diff * mask, -1) / C
        if reduction == "mean":
            return _jnp.mean(loss)
        if reduction == "sum":
            return _jnp.sum(loss)
        return loss

    args = [input, label] + ([weight] if weight is not None else [])
    return registry.cached_apply(
        "multi_margin_loss", fn, *args, p=int(p), margin=float(margin),
        reduction=str(reduction), has_w=weight is not None)


def triplet_margin_with_distance_loss(input, positive, negative,
                                      distance_function=None,
                                      margin=1.0, swap=False,
                                      reduction="mean", name=None):
    """reference loss.triplet_margin_with_distance_loss — custom
    distance callable (runs on Tensors, so any registry op works)."""
    from .extended import pairwise_distance

    dist = distance_function or (
        lambda a, b: pairwise_distance(a, b, p=2.0))
    d_pos = dist(input, positive)
    d_neg = dist(input, negative)
    if swap:
        d_swap = dist(positive, negative)
        d_neg = ops.minimum(d_neg, d_swap)
    loss = ops.clip(d_pos - d_neg + margin, min=0.0)
    if reduction == "mean":
        return ops.mean(loss)
    if reduction == "sum":
        return ops.sum(loss)
    return loss


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """reference loss.hsigmoid_loss (default complete-binary-tree
    path; custom path tables supported)."""
    import numpy as _np

    if path_table is not None:
        raise NotImplementedError(
            "custom path_table/path_code hsigmoid is not implemented; "
            "the default complete-tree mode matches the reference")
    # default tree: num_classes-1 internal nodes; label's path derived
    # from its binary representation (reference hierarchical_sigmoid).
    depth = int(_np.ceil(_np.log2(max(num_classes, 2))))

    def fn(x, y, w, *maybe_b, depth, num_classes, has_b):
        import jax.numpy as _jnp

        b = maybe_b[0] if has_b else None
        cur = y + num_classes  # heap index of the leaf (root = 1)
        loss = 0.0
        # walk up: CE at each INTERNAL node on the path; leaves at
        # shallow depths finish early (valid mask), so the implied
        # leaf probabilities normalize for any num_classes
        for _ in range(depth + 1):
            bit = (cur % 2).astype(x.dtype)
            parent = cur // 2
            valid = parent >= 1
            node = _jnp.clip(parent - 1, 0, w.shape[0] - 1)
            logit = _jnp.sum(x * w[node], -1)
            if b is not None:
                logit = logit + b[node]
            ce = _jnp.maximum(logit, 0) - logit * bit + _jnp.log1p(
                _jnp.exp(-_jnp.abs(logit)))
            loss = loss + _jnp.where(valid, ce, 0.0)
            cur = parent
        return _jnp.mean(loss)

    args = [input, label, weight] + ([bias] if bias is not None else [])
    return registry.cached_apply(
        "hsigmoid_loss", fn, *args, depth=depth,
        num_classes=int(num_classes), has_b=bias is not None)


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction="mean",
                         name=None):
    """reference loss.margin_cross_entropy (ArcFace-family combined
    margin: cos(m1·θ + m2) − m3 on the target logit)."""
    def fn(lg, y, m1, m2, m3, s, return_softmax, reduction):
        import jax
        import jax.numpy as _jnp

        cos = _jnp.clip(lg, -1.0, 1.0)
        theta = _jnp.arccos(cos)
        target = _jnp.cos(m1 * theta + m2) - m3
        onehot = jax.nn.one_hot(y, lg.shape[-1], dtype=lg.dtype)
        out = _jnp.where(onehot > 0, target, cos) * s
        lsm = jax.nn.log_softmax(out, -1)
        loss = -_jnp.take_along_axis(lsm, y[:, None], -1)[:, 0]
        if reduction == "mean":
            loss = _jnp.mean(loss)
        elif reduction == "sum":
            loss = _jnp.sum(loss)
        if return_softmax:
            return loss, _jnp.exp(lsm)
        return loss

    n_out = 2 if return_softmax else 1
    return registry.cached_apply(
        "margin_cross_entropy", fn, logits, label, m1=float(margin1),
        m2=float(margin2), m3=float(margin3), s=float(scale),
        return_softmax=bool(return_softmax), reduction=str(reduction),
        n_outputs=n_out)


def adaptive_log_softmax_with_loss(input, label, head_weight,
                                   tail_weights, cutoffs,
                                   head_bias=None, name=None):
    """reference loss.adaptive_log_softmax_with_loss (adaptive softmax
    over frequency-clustered vocab; returns (output, loss))."""
    def fn(*args, cutoffs, n_tails, has_bias):
        import jax
        import jax.numpy as _jnp

        x, y, hw = args[0], args[1], args[2]
        tails = args[3:3 + 2 * n_tails]
        hb = args[-1] if has_bias else None
        head_logits = x @ hw.T
        if hb is not None:
            head_logits = head_logits + hb
        head_lsm = jax.nn.log_softmax(head_logits, -1)
        shortlist = cutoffs[0]
        out = _jnp.zeros(y.shape, x.dtype)
        # shortlist tokens
        in_short = y < shortlist
        idx_short = _jnp.where(in_short, y, 0)
        out_short = _jnp.take_along_axis(head_lsm, idx_short[:, None],
                                         -1)[:, 0]
        out = _jnp.where(in_short, out_short, out)
        for t in range(n_tails):
            lo, hi = cutoffs[t], cutoffs[t + 1]
            proj, emb = tails[2 * t], tails[2 * t + 1]
            in_t = (y >= lo) & (y < hi)
            cluster_lsm = head_lsm[:, shortlist + t]
            h = x @ proj.T
            tail_logits = h @ emb.T
            tail_lsm = jax.nn.log_softmax(tail_logits, -1)
            rel = _jnp.clip(y - lo, 0, hi - lo - 1)
            out_t = cluster_lsm + _jnp.take_along_axis(
                tail_lsm, rel[:, None], -1)[:, 0]
            out = _jnp.where(in_t, out_t, out)
        return out, -_jnp.mean(out)

    flat_tails = []
    for pw in tail_weights:
        flat_tails.extend(pw)
    args = [input, label, head_weight] + list(flat_tails) + (
        [head_bias] if head_bias is not None else [])
    cutoffs = tuple(int(c) for c in cutoffs)
    return registry.cached_apply(
        "adaptive_log_softmax_with_loss", fn, *args,
        cutoffs=cutoffs, n_tails=len(tail_weights),
        has_bias=head_bias is not None, n_outputs=2)


def class_center_sample(label, num_classes, num_samples, group=None,
                        name=None):
    """reference common.class_center_sample: keep positive classes +
    uniformly sampled negatives; returns (remapped_label,
    sampled_class_centers)."""
    import numpy as _np

    from ...ops.random import default_generator

    y = _np.asarray(label._data if isinstance(label, Tensor) else label)
    pos = _np.unique(y)
    if len(pos) >= num_samples:
        sampled = pos
    else:
        rest = _np.setdiff1d(_np.arange(num_classes), pos)
        import jax as _jax

        key = default_generator.next_key()
        perm = _np.asarray(_jax.random.permutation(key, len(rest)))
        sampled = _np.concatenate(
            [pos, rest[perm[:num_samples - len(pos)]]])
    sampled = _np.sort(sampled)
    remap = _np.full(num_classes, -1, _np.int64)
    remap[sampled] = _np.arange(len(sampled))
    return (Tensor(_jnp_asarray(remap[y])),
            Tensor(_jnp_asarray(sampled)))


def _jnp_asarray(x):
    import jax.numpy as _jnp

    return _jnp.asarray(x)


def rnnt_loss(input, label, input_lengths, label_lengths, blank=0,
              fastemit_lambda=0.001, reduction="mean", name=None):
    """reference loss.rnnt_loss — RNN-Transducer loss via the standard
    log-domain alpha recursion (Graves 2012).  FastEmit (Yu et al.
    2021): lambda > 0 scales every emission arc's gradient by
    (1 + lambda), implemented as the equivalent objective
    L - lambda * sum(sg(gamma_emit) * emit_lp) with the emission-arc
    posteriors gamma from a full alpha-beta pass.
    input: [B, T, U+1, V] joint log-probs (pre-softmax), label: [B, U].
    """
    def fn(lg, y, t_len, u_len, blank, reduction, fastemit):
        import jax
        import jax.numpy as _jnp

        B, T, U1, V = lg.shape
        lsm = jax.nn.log_softmax(lg, -1)
        blank_lp = lsm[..., blank]                      # [B, T, U+1]
        y_idx = _jnp.concatenate(
            [y, _jnp.zeros((B, 1), y.dtype)], 1)[:, :U1]
        emit_lp = _jnp.take_along_axis(
            lsm, _jnp.broadcast_to(
                y_idx[:, None, :, None], (B, T, U1, 1)), -1)[..., 0]

        NEG = -1e30

        def step(alpha_prev, t):
            # alpha over u for time t: alpha[t, u] =
            #   logaddexp(alpha[t-1, u] + blank[t-1, u],
            #             alpha[t, u-1] + emit[t, u-1])
            from_blank = alpha_prev + blank_lp[:, t - 1, :]
            # sequential in u: a python loop (U is static and small)
            alphas = [from_blank[:, 0]]
            for u in range(1, U1):
                alphas.append(_jnp.logaddexp(
                    from_blank[:, u],
                    alphas[u - 1] + emit_lp[:, t, u - 1]))
            return _jnp.stack(alphas, 1), None

        alpha0 = _jnp.full((B, U1), NEG)
        alpha0 = alpha0.at[:, 0].set(0.0)
        for u in range(1, U1):
            alpha0 = alpha0.at[:, u].set(
                alpha0[:, u - 1] + emit_lp[:, 0, u - 1])
        alphas = [alpha0]
        for t in range(1, T):
            alphas.append(step(alphas[-1], t)[0])
        alpha = _jnp.stack(alphas, 1)                   # [B, T, U+1]
        t_idx = _jnp.clip(t_len - 1, 0, T - 1)
        u_idx = _jnp.clip(u_len, 0, U1 - 1)
        final = _jnp.take_along_axis(_jnp.take_along_axis(
            alpha, t_idx[:, None, None], 1)[:, 0],
            u_idx[:, None], 1)[:, 0]
        final = final + _jnp.take_along_axis(_jnp.take_along_axis(
            blank_lp, t_idx[:, None, None], 1)[:, 0],
            u_idx[:, None], 1)[:, 0]
        loss = -final
        if fastemit > 0.0:
            # beta recursion (mirror of alpha), per-sample lengths via
            # masks: beta[t, u] = logaddexp(
            #     blank[t, u] + beta[t+1, u],
            #     emit[t, u] + beta[t, u+1]);
            # at t == t_len-1 the blank arc terminates (only u==u_len).
            t_rng = _jnp.arange(T)[None, :]
            u_rng = _jnp.arange(U1)[None, :]
            t_valid = t_rng < t_len[:, None]
            u_valid = u_rng <= u_len[:, None]
            is_final_u = u_rng == u_len[:, None]
            NEGB = -1e30
            betas = [None] * T
            nxt = _jnp.full((B, U1), NEGB)
            for t in range(T - 1, -1, -1):
                final_t = (t_len - 1)[:, None] == t
                blank_cont = _jnp.where(
                    final_t, _jnp.where(is_final_u, 0.0, NEGB),
                    nxt) + blank_lp[:, t, :]
                vals = [None] * U1
                vals[U1 - 1] = blank_cont[:, U1 - 1]
                for u in range(U1 - 2, -1, -1):
                    vals[u] = _jnp.logaddexp(
                        blank_cont[:, u],
                        vals[u + 1] + emit_lp[:, t, u])
                cur = _jnp.stack(vals, 1)
                cur = _jnp.where(t_valid[:, t:t + 1] & u_valid, cur,
                                 NEGB)
                betas[t] = cur
                nxt = cur
            beta = _jnp.stack(betas, 1)                   # [B, T, U+1]
            beta_up = _jnp.concatenate(
                [beta[:, :, 1:], _jnp.full((B, T, 1), NEGB)], 2)
            gamma = _jnp.exp(alpha + emit_lp + beta_up
                             - final[:, None, None])
            gamma = jax.lax.stop_gradient(
                _jnp.where(_jnp.isfinite(gamma), gamma, 0.0))
            loss = loss - fastemit * _jnp.sum(gamma * emit_lp,
                                              axis=(1, 2))
        if reduction == "mean":
            return _jnp.mean(loss)
        if reduction == "sum":
            return _jnp.sum(loss)
        return loss

    return registry.cached_apply(
        "rnnt_loss", fn, input, label, input_lengths, label_lengths,
        blank=int(blank), reduction=str(reduction),
        fastemit=float(fastemit_lambda))


# -- in-place activation variants + attention aliases ------------------------

def _mk_act_inplace(fn_name):
    def _inplace(x, *args, **kw):
        from ...ops.manipulation import _autograd_proxy

        out = globals()[fn_name](_autograd_proxy(x), *args, **kw)
        x._data = out._data
        x._grad_node = out._grad_node
        x._out_slot = out._out_slot
        x.stop_gradient = out.stop_gradient and x.stop_gradient
        return x

    _inplace.__name__ = fn_name + "_"
    _inplace.__doc__ = f"In-place variant of ``{fn_name}``."
    return _inplace


relu_ = _mk_act_inplace("relu")
tanh_ = _mk_act_inplace("tanh")
elu_ = _mk_act_inplace("elu")
hardtanh_ = _mk_act_inplace("hardtanh")
leaky_relu_ = _mk_act_inplace("leaky_relu")
softmax_ = _mk_act_inplace("softmax")
thresholded_relu_ = _mk_act_inplace("thresholded_relu")


def flash_attn_qkvpacked(qkv, dropout=0.0, causal=False,
                         return_softmax=False, training=True,
                         name=None):
    """reference flash_attention.flash_attn_qkvpacked: qkv
    [B, S, 3, H, D] -> unpack and run the attention dispatch."""
    q = qkv[:, :, 0]
    k = qkv[:, :, 1]
    v = qkv[:, :, 2]
    out = scaled_dot_product_attention(
        q, k, v, dropout_p=dropout, is_causal=causal,
        training=training)
    if return_softmax:
        return out, None
    return out


def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k,
                                max_seqlen_q, max_seqlen_k, scale,
                                dropout=0.0, causal=False,
                                return_softmax=False, training=True,
                                name=None):
    """Varlen packed attention: computed per-sequence via the dense
    dispatch over the cu_seqlens segmentation (the reference kernel's
    semantics; throughput path on TPU prefers padded batches)."""
    import numpy as _np

    cq = _np.asarray(getattr(cu_seqlens_q, "_data", cu_seqlens_q))
    outs = []
    D = qkv.shape[-1]
    for i in range(len(cq) - 1):
        seg = qkv[int(cq[i]):int(cq[i + 1])]
        q, k, v = (seg[:, 0][None], seg[:, 1][None], seg[:, 2][None])
        if scale is not None:
            # sdpa applies 1/sqrt(D); pre-scale q for a custom scale
            q = ops.scale(q, float(scale) * float(np.sqrt(D)))
        o = scaled_dot_product_attention(
            q, k, v, dropout_p=dropout, is_causal=causal,
            training=training)
        outs.append(o[0])
    return ops.concat(outs, axis=0)


def flash_attention_with_sparse_mask(query, key, value,
                                     attn_mask_start_row_indices=None,
                                     attn_mask_start_row=0,
                                     dropout_p=0.0, is_causal=True,
                                     training=True, name=None):
    """Sparse-mask flash attention: materialized as a dense additive
    mask over the row-start indices (reference
    flash_attention_with_sparse_mask semantics)."""
    import jax.numpy as _jnp

    B, S = query.shape[0], query.shape[1]
    mask = None
    if attn_mask_start_row_indices is not None:
        starts = getattr(attn_mask_start_row_indices, "_data",
                         attn_mask_start_row_indices)
        rows = _jnp.arange(S)[None, None, :, None]
        mask_bool = rows >= starts[..., None, :][..., None, :, :] \
            if starts.ndim == 2 else rows >= starts
        mask = Tensor(_jnp.where(mask_bool, 0.0, -1e30))
    return scaled_dot_product_attention(
        query, key, value, attn_mask=mask, dropout_p=dropout_p,
        is_causal=is_causal, training=training)


def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None, name=None):
    """reference sparse_attention (CSR block mask) — computed as dense
    attention with the CSR pattern expanded to an additive mask (TPU
    has no CSR attention kernel; the pattern is honored exactly)."""
    import jax.numpy as _jnp

    offs = _np_of(sparse_csr_offset).astype(int)
    cols = _np_of(sparse_csr_columns).astype(int)
    B, H, S, D = query.shape
    mask = np.full((B, H, S, S), -1e30, np.float32)
    for b in range(B):
        for h in range(H):
            for r in range(S):
                lo, hi = offs[b, h, r], offs[b, h, r + 1]
                mask[b, h, r, cols[b, h, lo:hi]] = 0.0
    qt = ops.transpose(query, [0, 2, 1, 3])
    kt = ops.transpose(key, [0, 2, 1, 3])
    vt = ops.transpose(value, [0, 2, 1, 3])
    out = scaled_dot_product_attention(
        qt, kt, vt, attn_mask=Tensor(_jnp.asarray(mask)))
    return ops.transpose(out, [0, 2, 1, 3])


def _np_of(x):
    return np.asarray(getattr(x, "_data", x))
