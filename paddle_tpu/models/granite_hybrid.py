"""Granite-4.0-H style hybrid decoder: Mamba-2 mixers between a few
grouped-query attention layers that carry no positional rotation.

Published shape (``granitemoehybrid``, ibm-granite/granite-4.0-h-micro
``config.json``): a per-layer kind (``layer_types``), one shared SwiGLU
MLP per layer whose gate and up projections are one fused
``input_linear``, four scalar multipliers (embedding, residual,
attention, logits) and a tied output head.  With H the hidden size:

    x = embedding_multiplier * embed[ids]
    x = x + residual_multiplier * Mixer_l(RMSNorm(x))      Mixer_l: attention | Mamba-2
    x = x + residual_multiplier * MLP(RMSNorm(x))
    logits = RMSNorm(x) @ embed^T / logits_scaling

Attention is causal ``softmax(q k^T * attention_multiplier)`` with no
rotary embedding.  The Mamba-2 mixer splits ``h @ W_in`` into a gate
``z``, the convolved stream ``xBC`` and per-head ``dt``; after a causal
depthwise convolution and SiLU, ``xBC`` splits into ``x`` (heads of
``mamba_d_head``), ``B`` and ``C`` (one group of ``mamba_d_state``
shared by all heads), and each head runs the selective recurrence

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

followed by ``RMSNorm(y * silu(z)) * w`` and the output projection.

The mixers and the MLP are written ONCE here as functions of plain
arrays (one sequence, ``[T, ...]``): the eager model below calls them
over a whole sequence, the serving executor
(``inference/server/hybrid_executor.py``) over a prefill chunk, with the
convolution's tail and the recurrence's state carried between calls.  A
run of tokens goes through the recurrence in its chunked (SSD) form —
one block of matmuls per call (:func:`ssd_block`); the per-token form
lives in ``ops/pallas_kernels/ssm_decode.py``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as I
from ..ops import registry as _registry
from ..ops.nn_ops import _rms_norm_plain

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


@dataclass(frozen=True)
class GraniteHybridConfig:
    """The published keys (defaults: granite-4.0-h-micro)."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: tuple = (("mamba",) * 5 + ("attention",)
                          + ("mamba",) * 4) * 4
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    position_embedding_type: str = "nope"
    num_local_experts: int = 0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        refused = {
            "layer_types has another length than num_hidden_layers":
                len(self.layer_types) != self.num_hidden_layers,
            "a layer kind other than 'mamba' or 'attention'":
                set(self.layer_types) - {"mamba", "attention"},
            "mamba_n_groups != 1 (B and C are shared by all heads)":
                self.mamba_n_groups != 1,
            "mamba_expand * hidden_size != mamba_n_heads * mamba_d_head":
                self.mamba_expand * self.hidden_size != self.mamba_d_inner,
            "position_embedding_type other than 'nope'":
                self.position_embedding_type != "nope",
            "num_local_experts > 0 (the feed-forward is the shared MLP)":
                self.num_local_experts != 0,
            "a projection bias": (self.attention_bias
                                  or self.mamba_proj_bias),
            "a convolution without bias": not self.mamba_conv_bias,
            "an untied output head": not self.tie_word_embeddings,
            "hidden_size not a multiple of num_attention_heads":
                self.hidden_size % self.num_attention_heads,
        }
        bad = [what for what, is_so in refused.items() if is_so]
        if bad:
            raise NotImplementedError(
                f"models/granite_hybrid.py does not express: {bad}")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self):
        return self.mamba_d_inner + 2 * self.mamba_n_groups \
            * self.mamba_d_state

    @property
    def mamba_in_proj_dim(self):
        return self.mamba_d_inner + self.mamba_conv_dim + self.mamba_n_heads

    @staticmethod
    def tiny(**kw):
        """Two periods of M M A M at toy widths (tests)."""
        return GraniteHybridConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            shared_intermediate_size=96, num_hidden_layers=8,
            num_attention_heads=4, num_key_value_heads=2,
            layer_types=("mamba", "mamba", "attention", "mamba") * 2,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
            mamba_chunk_size=8, max_position_embeddings=256), **kw})


# -- the layer's parts, on plain arrays (one sequence) ---------------------

MAMBA_PARAMS = ("mamba.in_proj.weight", "mamba.conv1d.weight",
                "mamba.conv1d.bias", "mamba.dt_bias", "mamba.A_log",
                "mamba.D", "mamba.norm.weight", "mamba.out_proj.weight")
ATTENTION_PARAMS = ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
                    "self_attn.v_proj.weight", "self_attn.o_proj.weight")
SHARED_PARAMS = ("input_layernorm.weight", "post_attention_layernorm.weight",
                 "shared_mlp.input_linear.weight",
                 "shared_mlp.output_linear.weight")


def layer_param_names(kind):
    return (MAMBA_PARAMS if kind == "mamba" else ATTENTION_PARAMS) \
        + SHARED_PARAMS


def mlp(cfg, lp, h):
    """SwiGLU with gate and up in one ``input_linear``."""
    gate, up = jnp.split(h @ lp["shared_mlp.input_linear.weight"], 2, -1)
    return (jax.nn.silu(gate) * up) @ lp["shared_mlp.output_linear.weight"]


def mlp_residual(cfg, lp, x):
    h = _rms_norm_plain(x, lp["post_attention_layernorm.weight"],
                        epsilon=cfg.rms_norm_eps)
    return x + cfg.residual_multiplier * mlp(cfg, lp, h)


def mamba_project(cfg, lp, h):
    """``h @ W_in`` split into the gate z, the stream the convolution
    reads, and the per-head dt (before its bias and softplus)."""
    di, cd = cfg.mamba_d_inner, cfg.mamba_conv_dim
    zxbcdt = h @ lp["mamba.in_proj.weight"]
    return zxbcdt[..., :di], zxbcdt[..., di:di + cd], zxbcdt[..., di + cd:]


def causal_conv(xbc, tail, w, b):
    """Depthwise causal convolution of width K over ``xbc`` [T, C], whose
    K-1 predecessors are ``tail`` [K-1, C] (zeros at a sequence's start):
    ``out[t] = b + sum_k w[k] * x[t - (K-1) + k]``, then SiLU.  Returns
    (activated [T, C] float32, the new tail in ``tail``'s dtype)."""
    K, T = w.shape[0], xbc.shape[0]
    full = jnp.concatenate([tail.astype(_F32), xbc.astype(_F32)], axis=0)
    out = b.astype(_F32) + sum(w[k].astype(_F32) * full[k:k + T]
                               for k in range(K))
    return jax.nn.silu(out), full[T:].astype(tail.dtype)


def mamba_inputs(cfg, lp, conv_out, dt_raw):
    """The recurrence's operands from the activated convolution [.., C]
    and the raw dt [.., heads]: x [.., heads, P], B and C [.., N], and
    dt = softplus(dt + dt_bias), all float32."""
    di, n = cfg.mamba_d_inner, cfg.mamba_d_state
    x = conv_out[..., :di].reshape(
        *conv_out.shape[:-1], cfg.mamba_n_heads, cfg.mamba_d_head)
    dt = jax.nn.softplus(dt_raw.astype(_F32)
                         + lp["mamba.dt_bias"].astype(_F32))
    return x, conv_out[..., di:di + n], conv_out[..., di + n:], dt


def ssd_block(x, dt, A, B, C, S0):
    """The selective recurrence over one block of T tokens as matmuls
    (the "state-space dual" form).  x [T, heads, P]; dt [T, heads]
    (softplus applied); A [heads] (negative); B, C [T, N]; S0
    [heads, P, N]; all float32.  Returns (y [T, heads, P] without the D
    term, S_T).  With cs the running sum of dt * A:

        y_t = sum_{s<=t} (C_t . B_s) exp(cs_t - cs_s) dt_s x_s
              + exp(cs_t) S0 C_t
        S_T = exp(cs_T) S0 + sum_s exp(cs_T - cs_s) dt_s x_s (x) B_s

    Every exponent is <= 0.  Precision "highest": the state is float32
    and these are a few hundred MFLOP a layer."""
    T = x.shape[0]
    cs = jnp.cumsum(dt * A[None], axis=0)                      # [T, heads]
    xdt = x * dt[:, :, None]
    scores = jnp.einsum("tn,sn->ts", C, B, precision=_HIGHEST)
    causal = jnp.tril(jnp.ones((T, T), bool))
    diff = cs.T[:, :, None] - cs.T[:, None, :]                 # [heads, t, s]
    decay = jnp.where(causal[None], jnp.exp(jnp.where(causal[None],
                                                      diff, 0.0)), 0.0)
    y = jnp.einsum("hts,shp->thp", decay * scores[None], xdt,
                   precision=_HIGHEST)
    y = y + jnp.exp(cs)[:, :, None] * jnp.einsum(
        "tn,hpn->thp", C, S0, precision=_HIGHEST)
    to_end = jnp.exp(cs[-1][None] - cs)                        # [T, heads]
    S = jnp.exp(cs[-1])[:, None, None] * S0 + jnp.einsum(
        "shp,sn->hpn", xdt * to_end[:, :, None], B, precision=_HIGHEST)
    return y, S


def gated_norm_out(cfg, lp, y, z):
    """``RMSNorm(y * silu(z)) * w`` over the whole inner width (gate
    first, then norm), then the output projection."""
    dt = z.dtype
    g = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    g = _rms_norm_plain(g, lp["mamba.norm.weight"].astype(_F32),
                        epsilon=cfg.rms_norm_eps)
    return g.astype(dt) @ lp["mamba.out_proj.weight"]


def mamba_mixer(cfg, lp, h, tail, S0):
    """The Mamba-2 mixer over T tokens of one sequence, as one block.
    h [T, H]; tail [K-1, conv_dim]; S0 [heads, P, N] float32.  Returns
    (out [T, H], new tail, S_T)."""
    z, xbc, dt_raw = mamba_project(cfg, lp, h)
    conv_out, tail = causal_conv(xbc, tail, lp["mamba.conv1d.weight"],
                                 lp["mamba.conv1d.bias"])
    x, B, C, dt = mamba_inputs(cfg, lp, conv_out, dt_raw)
    A = -jnp.exp(lp["mamba.A_log"].astype(_F32))
    y, S = ssd_block(x, dt, A, B, C, S0)
    y = y + lp["mamba.D"].astype(_F32)[None, :, None] * x
    return gated_norm_out(cfg, lp, y.reshape(h.shape[0], -1), z), tail, S


def attention_qkv(cfg, lp, h):
    """q [T, heads, D] already times ``attention_multiplier``; k, v
    [T, kv heads, D].  No positional rotation."""
    T, d = h.shape[0], cfg.head_dim
    q = (h @ lp["self_attn.q_proj.weight"]).reshape(T, -1, d)
    k = (h @ lp["self_attn.k_proj.weight"]).reshape(T, -1, d)
    v = (h @ lp["self_attn.v_proj.weight"]).reshape(T, -1, d)
    return q * cfg.attention_multiplier, k, v


def attend(q, k, v, mask):
    """q [T, heads, D] (scaled); k, v [S, kv heads, D]; mask [T, S] of
    the keys each query may read.  Returns [T, heads * D]."""
    T, nh, d = q.shape
    g = nh // k.shape[1]
    qg = q.reshape(T, k.shape[1], g, d)
    s = jnp.einsum("tkgd,skd->kgts", qg, k).astype(_F32)
    s = jnp.where(mask[None, None], s, jnp.finfo(_F32).min)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("kgts,skd->tkgd", p, v).reshape(T, nh * d)


def attention_mixer(cfg, lp, h):
    """Causal attention of one whole sequence h [T, H]."""
    q, k, v = attention_qkv(cfg, lp, h)
    T = h.shape[0]
    o = attend(q, k, v, jnp.tril(jnp.ones((T, T), bool)))
    return o @ lp["self_attn.o_proj.weight"]


def mixer_input(cfg, lp, x):
    return _rms_norm_plain(x, lp["input_layernorm.weight"],
                           epsilon=cfg.rms_norm_eps)


def head(cfg, embed, norm_w, x):
    x = _rms_norm_plain(x, norm_w, epsilon=cfg.rms_norm_eps)
    return (x @ embed.T) / cfg.logits_scaling


def _layer_forward(x, *params, cfg, kind):
    """One layer over a batch of whole sequences x [B, T, H] (the eager
    model's op; a Mamba layer walks the sequence in blocks of
    ``mamba_chunk_size``, state and tail carried from block to block)."""
    lp = dict(zip(layer_param_names(kind), params))

    def one(xs):
        h = mixer_input(cfg, lp, xs)
        if kind == "attention":
            mixed = attention_mixer(cfg, lp, h)
        else:
            tail = jnp.zeros((cfg.mamba_d_conv - 1, cfg.mamba_conv_dim),
                             xs.dtype)
            S = jnp.zeros((cfg.mamba_n_heads, cfg.mamba_d_head,
                           cfg.mamba_d_state), _F32)
            outs = []
            for a in range(0, xs.shape[0], cfg.mamba_chunk_size):
                o, tail, S = mamba_mixer(
                    cfg, lp, h[a:a + cfg.mamba_chunk_size], tail, S)
                outs.append(o)
            mixed = jnp.concatenate(outs, axis=0)
        xs = xs + cfg.residual_multiplier * mixed
        return mlp_residual(cfg, lp, xs)

    return jax.vmap(one)(x)


def _head_forward(x, embed, norm_w, *, cfg):
    return head(cfg, embed, norm_w, x)


# -- the eager model ---------------------------------------------------------

class _Weights(nn.Layer):
    """A bag of parameters under one name (``mamba``, ``self_attn``,
    ``shared_mlp``): the math lives in the functions above."""

    def __init__(self, shapes, dtype, draw=True):
        super().__init__(dtype=dtype)
        for name, (shape, init) in shapes.items():
            if not draw:
                init = I.Constant(0.0)
            holder = self
            *path, leaf = name.split(".")
            for part in path:
                if part not in holder._sub_layers:
                    setattr(holder, part, nn.Layer(dtype=dtype))
                holder = holder._sub_layers[part]
            setattr(holder, leaf, holder.create_parameter(
                shape=list(shape), default_initializer=init))


class _InverseSoftplusLogUniform(I.Initializer):
    """dt_bias such that softplus(dt_bias) is log-uniform in [lo, hi]
    (the Mamba-2 reference initialisation)."""

    def __init__(self, lo=1e-3, hi=1e-1):
        self.lo, self.hi = lo, hi

    def __call__(self, shape, dtype):
        u = I.Uniform(np.log(self.lo), np.log(self.hi))(shape, jnp.float32)
        dt = jnp.exp(u)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class _LogUniform(I.Initializer):
    def __init__(self, lo, hi):
        self.lo, self.hi = lo, hi

    def __call__(self, shape, dtype):
        return jnp.log(I.Uniform(self.lo, self.hi)(shape, jnp.float32)) \
            .astype(dtype)


def _mixer_shapes(cfg, kind):
    h, d = cfg.hidden_size, cfg.head_dim
    w = I.Normal(0.0, cfg.initializer_range)
    if kind == "attention":
        nq, nkv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
        return {"q_proj.weight": ((h, nq), w), "k_proj.weight": ((h, nkv), w),
                "v_proj.weight": ((h, nkv), w), "o_proj.weight": ((nq, h), w)}
    k, nh = cfg.mamba_d_conv, cfg.mamba_n_heads
    conv = I.Uniform(-1.0 / np.sqrt(k), 1.0 / np.sqrt(k))
    return {"in_proj.weight": ((h, cfg.mamba_in_proj_dim), w),
            "conv1d.weight": ((k, cfg.mamba_conv_dim), conv),
            "conv1d.bias": ((cfg.mamba_conv_dim,), conv),
            "dt_bias": ((nh,), _InverseSoftplusLogUniform()),
            "A_log": ((nh,), _LogUniform(1.0, 16.0)),
            "D": ((nh,), I.Constant(1.0)),
            "norm.weight": ((cfg.mamba_d_inner,), I.Constant(1.0)),
            "out_proj.weight": ((cfg.mamba_d_inner, h), w)}


class GraniteHybridLayer(nn.Layer):
    def __init__(self, cfg, kind, draw=True):
        super().__init__(dtype=cfg.dtype)
        self.config, self.kind = cfg, kind
        h, i = cfg.hidden_size, cfg.shared_intermediate_size
        w = I.Normal(0.0, cfg.initializer_range)
        setattr(self, "mamba" if kind == "mamba" else "self_attn",
                _Weights(_mixer_shapes(cfg, kind), cfg.dtype, draw))
        self.shared_mlp = _Weights(
            {"input_linear.weight": ((h, 2 * i), w),
             "output_linear.weight": ((i, h), w)}, cfg.dtype, draw)
        one = I.Constant(1.0)
        self.input_layernorm = _Weights({"weight": ((h,), one)}, cfg.dtype,
                                        draw)
        self.post_attention_layernorm = _Weights({"weight": ((h,), one)},
                                                 cfg.dtype, draw)

    def forward(self, x):
        params = dict(self.named_parameters())
        names = layer_param_names(self.kind)
        if getattr(params[names[0]]._data, "is_deleted", lambda: False)():
            raise RuntimeError(
                "this layer's arrays were handed over to a ServingEngine "
                "(its executor stacks the recurrent layers and the device "
                "could not hold two copies): build the model again to run "
                "it eagerly")
        return _registry.cached_apply(
            "granite_hybrid_layer", _layer_forward, x,
            *(params[n] for n in names), cfg=self.config, kind=self.kind)


class GraniteHybridModel(nn.Layer):
    def __init__(self, cfg, draw=True):
        super().__init__(dtype=cfg.dtype)
        self.config = cfg
        self.embed_tokens = _Weights(
            {"weight": ((cfg.vocab_size, cfg.hidden_size),
                        I.Normal(0.0, cfg.initializer_range))}, cfg.dtype,
            draw)
        self.layers = nn.LayerList(
            [GraniteHybridLayer(cfg, kind, draw)
             for kind in cfg.layer_types])
        self.norm = _Weights({"weight": ((cfg.hidden_size,),
                                         I.Constant(1.0))}, cfg.dtype, draw)


class GraniteHybridForCausalLM(nn.Layer):
    """``model(input_ids [B, T]) -> logits [B, T, V]``.  Serving goes
    through ``ServingEngine(model, ...)``, which reads ``config`` and the
    parameters and picks the hybrid executor by ``layer_types``."""

    def __init__(self, config: GraniteHybridConfig, init_weights=True):
        """``init_weights=False``: every parameter is zero, for a model
        whose weights are loaded next (drawing 3.2 B random numbers that
        ``set_value`` replaces costs a minute of set-up on the chip)."""
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = GraniteHybridModel(config, draw=init_weights)

    def forward(self, input_ids):
        cfg = self.config
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        embed = self.model.embed_tokens.weight
        x = Tensor(embed._data[ids] * cfg.embedding_multiplier)
        for layer in self.model.layers:
            x = layer(x)
        return _registry.cached_apply(
            "granite_hybrid_head", _head_forward, x, embed,
            self.model.norm.weight, cfg=cfg)

    def num_params(self):
        return sum(int(np.prod(p.shape)) for p in self.parameters())
