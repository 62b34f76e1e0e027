"""A decoder that mixes SLIDING-WINDOW and FULL attention layers (three
sliding to one full as published), gates its attention's output, and
whose feed-forward is a mixture of sigmoid-routed experts beside a shared
expert after a few leading dense layers (the ``afmoe`` shape; Arcee
Trinity's ``config.json`` keys).

With H the hidden size, every norm an RMSNorm (``rms_norm_eps``, a
learned scale) and ``t = layer_types[n]``:

    x0 = E[ids] * sqrt(H)                    (``mup_enabled``)
    a  = N1(x)                               input_layernorm
    q  = RMSNorm_D(a Wq as [T, heads, D])    q_norm, over each head
    k  = RMSNorm_D(a Wk as [T, kv heads, D]) k_norm, over each head
    v  =           a Wv as [T, kv heads, D]
    g  = a Wg                                [T, heads * D]
    t == sliding_attention: q, k = rope(q), rope(k)   (``rope_theta``,
        pairs (i, i + D / 2)); t == full_attention: NO positional encoding
    visible(i, j): j <= i, and on a sliding layer also
        i - j < sliding_window (a query sees that many keys, itself
        included); query head h reads KV head h // (heads / kv heads)
    o  = softmax_j(q_i . k_j / sqrt(D)) v    (float32 softmax)
    x  = x + N2((o * sigmoid(g)) Wo)         post_attention_layernorm, on
                                             the BRANCH
    b  = N3(x)                               pre_mlp_layernorm
    n <  num_dense_layers: y = SwiGLU(b), ``intermediate_size`` wide
    n >= num_dense_layers: ``models/moe.py``'s expert layer:
        p = sigmoid(float32(b Wr)); the ``num_experts_per_tok`` experts
        with the largest p + bias (the bias enters the choice only);
        w = route_scale * p_S / (sum p_S + 1e-20) (``route_norm``);
        y = Shared(b) + sum_{e in S, e held} w_e Expert_e(b), SwiGLUs
        of ``moe_intermediate_size``
    x  = x + N4(y)                           post_mlp_layernorm, on the
                                             BRANCH
    logits = N(x) H                          the head is not tied

What of this the published ``config.json`` does not say (the gate, the
per-head q/k norms, NoPE on the full layers, the four norms' places, the
embedding's scale) is the ``afmoe`` modeling file as the benchmark's
configuration file lists it under ``assumed``.

The layer's parts are functions of plain arrays (one sequence,
``[T, ...]``), used by the eager model below and by the serving programs
(``inference/server/window_executor.py``) alike.  The model is built with
``held_experts`` as ``models/mla_moe.py``'s is (all by default).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as I
from ..ops import registry as _registry
from ..ops.nn_ops import _rms_norm_plain
from . import moe as _moe
from .granite_hybrid import _Weights
from .mla_moe import _softmax, rope

_F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"
#: added to the sum of the chosen scores before it divides them
ROUTE_EPS = 1e-20


@dataclass(frozen=True)
class WindowMoEConfig:
    """The published keys (defaults: Trinity-Mini)."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    layer_types: tuple | list | None = None
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_scale: float = 2.826
    route_norm: bool = True
    score_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 10000.0
    rope_scaling: dict | None = None
    rms_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    mup_enabled: bool = True
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        n, every = self.num_hidden_layers, self.global_attn_every_n_layers
        kinds = (tuple(FULL if (i + 1) % every == 0 else SLIDING
                       for i in range(n))
                 if self.layer_types is None
                 else tuple(self.layer_types)[:n])      # a cut in depth
        object.__setattr__(self, "layer_types", kinds)
        refused = {
            "a score_func other than 'sigmoid'": self.score_func != "sigmoid",
            "group-limited routing (n_group / topk_group != 1)":
                (self.n_group or 1) != 1 or (self.topk_group or 1) != 1,
            "rope_scaling": self.rope_scaling is not None,
            "route_norm false": not self.route_norm,
            "num_shared_experts != 1": self.num_shared_experts != 1,
            "a hidden_act other than 'silu'": self.hidden_act != "silu",
            "a tied output head": self.tie_word_embeddings,
            "more experts a token than experts":
                self.num_experts_per_tok > self.num_experts,
            "no expert layer (num_dense_layers >= num_hidden_layers)":
                self.num_dense_layers >= n,
            "fewer layer_types than layers": len(kinds) != n,
            f"a layer type other than {SLIDING!r} and {FULL!r}":
                bool(set(kinds) - {SLIDING, FULL}),
            "query heads that KV heads do not divide":
                self.num_attention_heads % self.num_key_value_heads,
            "an odd head_dim": self.head_dim % 2,
        }
        bad = [what for what, is_so in refused.items() if is_so]
        if bad:
            raise NotImplementedError(
                f"models/window_moe.py does not express: {bad}")

    def window_of(self, n):
        """Keys a query of layer ``n`` sees, itself included; None on a
        full layer."""
        return self.sliding_window if self.layer_types[n] == SLIDING \
            else None

    def is_dense(self, n):
        return n < self.num_dense_layers

    @property
    def embed_scale(self):
        return math.sqrt(self.hidden_size) if self.mup_enabled else 1.0

    @staticmethod
    def tiny(**kw):
        """Two dense and four expert layers (s, s | s, f, s, s) at toy
        widths, a window of 8 keys (tests)."""
        return WindowMoEConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=6,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            sliding_window=8, num_experts=16, num_experts_per_tok=4,
            max_position_embeddings=512, initializer_range=0.25), **kw})


# -- the layer's parts, on plain arrays (one sequence) ---------------------

ATTENTION_PARAMS = (
    "input_layernorm.weight", "self_attn.q_proj.weight",
    "self_attn.k_proj.weight", "self_attn.v_proj.weight",
    "self_attn.gate_proj.weight", "self_attn.q_norm.weight",
    "self_attn.k_norm.weight", "self_attn.o_proj.weight",
    "post_attention_layernorm.weight", "pre_mlp_layernorm.weight",
    "post_mlp_layernorm.weight")
DENSE_PARAMS = ("mlp.gate_up_proj.weight", "mlp.down_proj.weight")
MOE_PARAMS = ("mlp.router.gate.weight", "mlp.expert_bias",
              "mlp.experts.gate_up_proj", "mlp.experts.down_proj",
              "mlp.shared_experts.gate_up_proj.weight",
              "mlp.shared_experts.down_proj.weight")


def layer_param_names(dense):
    return ATTENTION_PARAMS + (DENSE_PARAMS if dense else MOE_PARAMS)


def _norm(cfg, x, w):
    return _rms_norm_plain(x, w, epsilon=cfg.rms_norm_eps)


def visible(i, j, window):
    """May the query at position i read the key at position j?  i and j
    broadcast; ``window`` None on a full layer."""
    seen = j <= i
    return seen if window is None else seen & (i - j < window)


def rope_tables(cfg, positions):
    """cos and sin ``[T, D / 2]`` float32 for integer positions [T]."""
    d = cfg.head_dim
    inv = jnp.asarray(1.0 / float(cfg.rope_theta)
                      ** (np.arange(0, d, 2, dtype=np.float64) / d), _F32)
    ang = positions.astype(_F32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def embed(cfg, table, ids):
    return table[ids] * jnp.asarray(cfg.embed_scale, table.dtype)


def attention_inputs(cfg, lp, x, positions, sliding):
    """From the residual stream x [T, H] at integer ``positions`` [T]:
    the normed (and on a sliding layer roped) queries [T, heads, D] and
    keys [T, kv heads, D], the values [T, kv heads, D] and the output's
    gate [T, heads * D] before its sigmoid."""
    T, D = x.shape[0], cfg.head_dim
    a = _norm(cfg, x, lp["input_layernorm.weight"])
    q = _norm(cfg, (a @ lp["self_attn.q_proj.weight"]).reshape(T, -1, D),
              lp["self_attn.q_norm.weight"])
    k = _norm(cfg, (a @ lp["self_attn.k_proj.weight"]).reshape(T, -1, D),
              lp["self_attn.k_norm.weight"])
    v = (a @ lp["self_attn.v_proj.weight"]).reshape(T, -1, D)
    if sliding:
        cos, sin = rope_tables(cfg, positions)
        q = rope(q, cos[:, None], sin[:, None])
        k = rope(k, cos[:, None], sin[:, None])
    return q, k, v, a @ lp["self_attn.gate_proj.weight"]


def attend(cfg, q, k, v, mask, head_block=None):
    """Masked attention of queries q [T, heads, D] over keys and values
    [S, kv heads, D]; mask [T, S].  ``head_block`` query heads of one KV
    head at a time (all of them by default): the scores are ``[block, T,
    S]`` float32.  Returns [T, heads * D] in v's dtype."""
    T, nh, D = q.shape
    KV = k.shape[1]
    G = nh // KV
    gb = G if head_block is None else head_block
    qb = jnp.moveaxis(q.reshape(T, KV * (G // gb), gb, D), 0, 2)
    kt, vt = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)   # [KV, S, D]
    scale = D ** -0.5

    def block(args):
        qh, i = args                                    # [gb, T, D]
        kv = i // (G // gb)
        kk = jax.lax.dynamic_index_in_dim(kt, kv, 0, keepdims=False)
        vv = jax.lax.dynamic_index_in_dim(vt, kv, 0, keepdims=False)
        s = jnp.einsum("gtd,sd->gts", qh, kk,
                       preferred_element_type=_F32) * scale
        p = _softmax(jnp.where(mask[None], s, jnp.finfo(_F32).min))
        return jnp.einsum("gts,sd->gtd", p.astype(vv.dtype), vv)

    o = jax.lax.map(block, (qb, jnp.arange(qb.shape[0], dtype=jnp.int32)))
    return jnp.moveaxis(o.reshape(nh, T, D), 0, 1).reshape(T, nh * D)


def attention_residual(cfg, lp, x, o, gate):
    """``x + N2((o * sigmoid(gate)) Wo)``: the attention's output [T,
    heads * D] through its gate, the output projection and the norm on
    the branch."""
    o = (o.astype(_F32) * jax.nn.sigmoid(gate.astype(_F32))).astype(x.dtype)
    return x + _norm(cfg, o @ lp["self_attn.o_proj.weight"],
                     lp["post_attention_layernorm.weight"])


def route(cfg, lp, h):
    """:func:`moe.route` with this model's router, bias and scalars."""
    return _moe.route(h, lp["mlp.router.gate.weight"], lp["mlp.expert_bias"],
                      cfg.num_experts_per_tok, cfg.route_scale, ROUTE_EPS)


def feed_forward(cfg, dense, lp, x, held):
    """The layer's second part on the residual stream x [T, H].  Returns
    (x after the residual, which held expert took which row
    [T, len(held)] bool — no column for a dense layer)."""
    b = _norm(cfg, x, lp["pre_mlp_layernorm.weight"])
    if dense:
        y = _moe.swiglu(b, lp["mlp.gate_up_proj.weight"],
                        lp["mlp.down_proj.weight"])
        took = jnp.zeros((x.shape[0], 0), bool)
    else:
        sel, w = route(cfg, lp, b)
        y = _moe.routed_experts(b, sel, w, held,
                                lp["mlp.experts.gate_up_proj"],
                                lp["mlp.experts.down_proj"])
        y = (y + _moe.swiglu(
            b, lp["mlp.shared_experts.gate_up_proj.weight"],
            lp["mlp.shared_experts.down_proj.weight"]).astype(_F32)) \
            .astype(x.dtype)
        took = _moe.held_weights(sel, w, held) > 0
    return x + _norm(cfg, y, lp["post_mlp_layernorm.weight"]), took


def head(cfg, norm_w, lm_head, x):
    """Logits of rows x [T, H], float32: bf16 logits stand 0.03 apart
    near the top of 200,192 of them, and the argmax would be taken over
    ties the arithmetic does not have."""
    return jnp.matmul(_norm(cfg, x, norm_w), lm_head,
                      preferred_element_type=_F32)


def _layer_forward(x, *params, cfg, n, held):
    """Layer ``n`` over a batch of whole sequences x [B, T, H]."""
    dense, window = cfg.is_dense(n), cfg.window_of(n)
    lp = dict(zip(layer_param_names(dense), params))

    def one(xs):
        at = jnp.arange(xs.shape[0])
        q, k, v, gate = attention_inputs(cfg, lp, xs, at, window is not None)
        o = attend(cfg, q, k, v, visible(at[:, None], at[None, :], window))
        xs = attention_residual(cfg, lp, xs, o, gate)
        return feed_forward(cfg, dense, lp, xs, held)[0]

    return jax.vmap(one)(x)


def _head_forward(x, norm_w, lm_head, *, cfg):
    return head(cfg, norm_w, lm_head, x)


# -- the eager model ---------------------------------------------------------

def _layer_shapes(cfg, dense, n_held):
    h, D = cfg.hidden_size, cfg.head_dim
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    w, one = I.Normal(0.0, cfg.initializer_range), I.Constant(1.0)
    shapes = {
        "input_layernorm.weight": ((h,), one),
        "self_attn.q_proj.weight": ((h, nh * D), w),
        "self_attn.k_proj.weight": ((h, nkv * D), w),
        "self_attn.v_proj.weight": ((h, nkv * D), w),
        "self_attn.gate_proj.weight": ((h, nh * D), w),
        "self_attn.q_norm.weight": ((D,), one),
        "self_attn.k_norm.weight": ((D,), one),
        "self_attn.o_proj.weight": ((nh * D, h), w),
        "post_attention_layernorm.weight": ((h,), one),
        "pre_mlp_layernorm.weight": ((h,), one),
        "post_mlp_layernorm.weight": ((h,), one),
    }
    if dense:
        i = cfg.intermediate_size
        shapes.update({"mlp.gate_up_proj.weight": ((h, 2 * i), w),
                       "mlp.down_proj.weight": ((i, h), w)})
        return shapes
    f = cfg.moe_intermediate_size
    shapes.update({
        "mlp.router.gate.weight": ((h, cfg.num_experts), w),
        # drawn, so that it changes choices (a trained bias is not zero)
        "mlp.expert_bias": ((cfg.num_experts,), I.Normal(0.0, 0.01)),
        "mlp.experts.gate_up_proj": ((n_held, h, 2 * f), w),
        "mlp.experts.down_proj": ((n_held, f, h), w),
        "mlp.shared_experts.gate_up_proj.weight": ((h, 2 * f), w),
        "mlp.shared_experts.down_proj.weight": ((f, h), w)})
    return shapes


class WindowMoELayer(nn.Layer):
    def __init__(self, cfg, n, held, draw=True):
        super().__init__(dtype=cfg.dtype)
        self.config, self.n, self.held = cfg, n, held
        shapes = _layer_shapes(cfg, cfg.is_dense(n), len(held))
        for part in sorted({name.split(".")[0] for name in shapes}):
            own = {k[len(part) + 1:]: v for k, v in shapes.items()
                   if k.startswith(part + ".")}
            setattr(self, part, _Weights(own, cfg.dtype, draw))

    def forward(self, x):
        params = dict(self.named_parameters())
        names = layer_param_names(self.config.is_dense(self.n))
        return _registry.cached_apply(
            "window_moe_layer", _layer_forward, x,
            *(params[name] for name in names), cfg=self.config, n=self.n,
            held=self.held)


class WindowMoEModel(nn.Layer):
    def __init__(self, cfg, held, draw=True):
        super().__init__(dtype=cfg.dtype)
        self.config = cfg
        self.embed_tokens = _Weights(
            {"weight": ((cfg.vocab_size, cfg.hidden_size),
                        I.Normal(0.0, cfg.initializer_range))}, cfg.dtype,
            draw)
        self.layers = nn.LayerList([
            WindowMoELayer(cfg, n, held, draw)
            for n in range(cfg.num_hidden_layers)])
        self.norm = _Weights({"weight": ((cfg.hidden_size,),
                                         I.Constant(1.0))}, cfg.dtype, draw)


class WindowMoEForCausalLM(nn.Layer):
    """``model(input_ids [B, T]) -> logits [B, T, V]``.  Serving goes
    through ``ServingEngine(model, ...)``, which reads ``config`` and the
    parameters and picks the window executor by ``layer_types``.

    ``held_experts``: the ids of the routed experts this model holds, in
    the order of its expert weights' first dimension (all
    ``num_experts`` by default).  ``init_weights=False`` leaves every
    parameter zero, for a model whose weights are loaded next."""

    def __init__(self, config: WindowMoEConfig, held_experts=None,
                 init_weights=True):
        super().__init__(dtype=config.dtype)
        held = tuple(range(config.num_experts) if held_experts is None
                     else (int(e) for e in held_experts))
        if (len(set(held)) != len(held) or not held
                or min(held) < 0 or max(held) >= config.num_experts):
            raise ValueError(
                f"held_experts must be distinct ids of the "
                f"{config.num_experts} routed experts, got {held}")
        self.config, self.held_experts = config, held
        self.model = WindowMoEModel(config, held, draw=init_weights)
        self.lm_head = _Weights(
            {"weight": ((config.hidden_size, config.vocab_size),
                        I.Normal(0.0, config.initializer_range))},
            config.dtype, init_weights)

    def forward(self, input_ids):
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        x = Tensor(embed(self.config, self.model.embed_tokens.weight._data,
                         ids))
        for layer in self.model.layers:
            x = layer(x)
        return _registry.cached_apply(
            "window_moe_head", _head_forward, x, self.model.norm.weight,
            self.lm_head.weight, cfg=self.config)

    def num_params(self):
        return sum(int(np.prod(p.shape)) for p in self.parameters())
