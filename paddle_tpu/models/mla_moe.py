"""A decoder of latent attention (MLA) layers whose feed-forward is a
mixture of sigmoid-routed experts beside a shared expert, after a few
leading dense layers (the DeepSeek-V3 shape; ``sarvam_mla``,
``deepseek_v3`` config.json keys).

With H the hidden size, ``h = RMSNorm(x)`` before each of a layer's two
parts and a residual after each:

**Latent attention, expanded form** (a whole sequence, a prefill chunk).
``q = h W_q`` as ``[T, heads, nope + rope]``, RMSNorm over each head's
width (``use_qk_norm``), split into ``q_nope`` and ``q_pe``.
``[c ; k_pe] = h W_kva`` (``[T, rank + rope]``); ``c <- RMSNorm(c)``;
``k_pe`` is ONE vector a token, shared by all heads.  Rope (YaRN
frequencies, :func:`yarn_inv_freq`) on ``q_pe`` and ``k_pe``.  **The
cached row is** ``[c ; rope(k_pe)]``: ``rank + rope`` values a token a
layer, held once, no heads, no V.  ``[k_nope ; v] = c W_kvb`` as
``[T, heads, nope + v]``; scores ``(q_nope . k_nope + q_pe . k_pe) * s``
with ``s = (nope + rope)^-0.5 * mscale^2``; causal softmax in float32;
``out = concat_h(p v) W_o``.

**Absorbed form** (decode).  With ``W_UK[h] = W_kvb[:, h, :nope]`` and
``W_UV[h] = W_kvb[:, h, nope:]``: ``q_lat[h] = q_nope[h] W_UK[h]^T``
(``rank`` wide), scores ``(q_lat[h] . c + q_pe[h] . k_pe) * s`` against
the cached rows themselves, ``u[h] = sum p c``, ``o[h] = u[h] W_UV[h]``.
The same numbers; no key or value of a past token is ever rebuilt
(:func:`absorb_query`, :func:`absorb_output`; the attention between them
is ``ops/pallas_kernels/mla_decode.py``).

**Feed-forward.**  The first ``first_k_dense_replace`` layers: SwiGLU of
``intermediate_size``.  The others: ``sc = sigmoid(h W_r)`` in float32
over ALL ``num_experts``; the ``num_experts_per_tok`` experts with the
largest ``sc + b`` (the bias ``b`` enters the choice only); weights
``routed_scaling_factor * sc[sel] / sum sc[sel]``;
``y = Shared(h) + sum_{e in sel} w_e E_e(h)``, every expert a SwiGLU of
``moe_intermediate_size``.  No token is dropped and no capacity exists.

**The share.**  The model is built with ``held_experts``, the ids of the
routed experts whose weights it holds (all of them by default; one
chip's share under expert parallelism).  It routes over all
``num_experts`` with the whole router and computes
``sum_{e in sel, e in held} w_e E_e(h)`` plus the shared expert: the
partial result the exchange between chips would complete.  Nothing here
stands in for the absent chips.

The layer's parts are functions of plain arrays (one sequence,
``[T, ...]``), used by the eager model below and by the serving programs
(``inference/server/latent_executor.py``) alike.  The router, the share
and the held experts' products are ``models/moe.py``'s, the one expert
layer every model with routed experts imports.
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
from .moe import (held_weights, routed_experts, swiglu,  # noqa: F401
                  swiglu_hidden)

_F32 = jnp.float32
_YARN = dict(type="deepseek_yarn", factor=40.0, beta_fast=32.0, beta_slow=1.0,
             mscale=1.0, mscale_all_dim=1.0,
             original_max_position_embeddings=4096)


@dataclass(frozen=True)
class MLAMoEConfig:
    """The published keys (defaults: sarvam-105b)."""

    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 32
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int | None = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    use_qk_norm: bool = True
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    moe_router_enable_expert_bias: bool = True
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 10000.0
    rope_scaling: tuple | dict | None = tuple(sorted(_YARN.items()))
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):     # hashable: a jit attr
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        yarn = self.yarn
        refused = {
            "q_lora_rank (a low-rank query projection)":
                self.q_lora_rank is not None,
            "a rope_scaling.type other than 'deepseek_yarn'":
                yarn is not None and yarn.get("type") != "deepseek_yarn",
            "num_shared_experts != 1": self.num_shared_experts != 1,
            "group-limited routing (n_group / topk_group != 1)":
                (self.n_group or 1) != 1 or (self.topk_group or 1) != 1,
            "a router without its expert bias":
                not self.moe_router_enable_expert_bias,
            "use_qk_norm false": not self.use_qk_norm,
            "a hidden_act other than 'silu'": self.hidden_act != "silu",
            "a tied output head": self.tie_word_embeddings,
            "more experts a token than experts":
                self.num_experts_per_tok > self.num_experts,
            "no expert layer (first_k_dense_replace >= num_hidden_layers)":
                self.first_k_dense_replace >= self.num_hidden_layers,
            "an odd qk_rope_head_dim": self.qk_rope_head_dim % 2,
        }
        bad = [what for what, is_so in refused.items() if is_so]
        if bad:
            raise NotImplementedError(
                f"models/mla_moe.py does not express: {bad}")

    @property
    def yarn(self):
        return None if self.rope_scaling is None else dict(self.rope_scaling)

    @property
    def layer_types(self):
        """The kind of every layer, as ``ServingEngine`` reads it."""
        k = self.first_k_dense_replace
        return ("mla_dense",) * k + ("mla_moe",) * (self.num_hidden_layers
                                                    - k)

    @property
    def q_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self):
        """The cached row: the normed latent and the roped shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @staticmethod
    def tiny(**kw):
        """One dense and three expert layers at toy widths (tests)."""
        return MLAMoEConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=4,
            num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, num_experts=16,
            num_experts_per_tok=4, max_position_embeddings=512,
            rope_scaling=dict(_YARN, factor=4.0,
                              original_max_position_embeddings=64)), **kw})


# -- rope ------------------------------------------------------------------

def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg):
    """The rotary frequencies ``[rope / 2]`` (float64 on the host): the
    plain ones where no scaling is configured, else YaRN's blend — a
    pair that turns more than ``beta_fast`` times inside the original
    context keeps its frequency, one that turns fewer than ``beta_slow``
    times has it divided by ``factor``, a linear ramp between."""
    d, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    plain = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    y = cfg.yarn
    if y is None:
        return plain

    def turns_to_dim(turns):
        return (d * math.log(y["original_max_position_embeddings"]
                             / (turns * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(turns_to_dim(y["beta_fast"])), 0)
    high = min(math.ceil(turns_to_dim(y["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return plain / y["factor"] * ramp + plain * (1.0 - ramp)


def rope_tables(cfg, positions):
    """cos and sin ``[T, rope / 2]`` float32 for integer positions [T],
    times YaRN's ``mscale / mscale_all_dim`` ratio (1 as published)."""
    inv = jnp.asarray(yarn_inv_freq(cfg), _F32)
    ang = positions.astype(_F32)[:, None] * inv[None, :]
    y = cfg.yarn
    m = 1.0 if y is None else (_yarn_mscale(y["factor"], y["mscale"])
                               / _yarn_mscale(y["factor"],
                                              y["mscale_all_dim"]))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def rope(x, cos, sin):
    """Rotate pairs ``(i, i + d / 2)`` of the last dimension (the
    half-split convention; cos and sin broadcast against x's leading
    dimensions), in float32."""
    half = x.shape[-1] // 2
    a, b = x[..., :half].astype(_F32), x[..., half:].astype(_F32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def softmax_scale(cfg):
    y = cfg.yarn
    m = 1.0 if y is None else _yarn_mscale(y["factor"], y["mscale_all_dim"])
    return cfg.q_head_dim ** -0.5 * m * m


# -- the layer's parts, on plain arrays (one sequence) ---------------------

ATTENTION_PARAMS = ("input_layernorm.weight", "self_attn.q_proj.weight",
                    "self_attn.q_norm.weight",
                    "self_attn.kv_a_proj_with_mqa.weight",
                    "self_attn.kv_a_layernorm.weight",
                    "self_attn.kv_b_proj.weight", "self_attn.o_proj.weight",
                    "post_attention_layernorm.weight")
DENSE_PARAMS = ("mlp.gate_up_proj.weight", "mlp.down_proj.weight")
MOE_PARAMS = ("mlp.gate.weight", "mlp.gate.e_score_correction_bias",
              "mlp.experts.gate_up_proj", "mlp.experts.down_proj",
              "mlp.shared_experts.gate_up_proj.weight",
              "mlp.shared_experts.down_proj.weight")


def layer_param_names(kind):
    return ATTENTION_PARAMS + (DENSE_PARAMS if kind == "mla_dense"
                               else MOE_PARAMS)


def _norm(cfg, x, w):
    return _rms_norm_plain(x, w, epsilon=cfg.rms_norm_eps)


def mla_project(cfg, lp, x, positions):
    """From the residual stream x [T, H] at integer ``positions`` [T]:
    ``q_nope`` [T, heads, nope], roped ``q_pe`` [T, heads, rope] and the
    row to cache ``[c ; rope(k_pe)]`` [T, rank + rope]."""
    T, r = x.shape[0], cfg.kv_lora_rank
    h = _norm(cfg, x, lp["input_layernorm.weight"])
    q = (h @ lp["self_attn.q_proj.weight"]).reshape(T, -1, cfg.q_head_dim)
    q = _norm(cfg, q, lp["self_attn.q_norm.weight"])
    ckv = h @ lp["self_attn.kv_a_proj_with_mqa.weight"]
    c = _norm(cfg, ckv[:, :r], lp["self_attn.kv_a_layernorm.weight"])
    cos, sin = rope_tables(cfg, positions)
    q_pe = rope(q[..., cfg.qk_nope_head_dim:], cos[:, None], sin[:, None])
    row = jnp.concatenate([c, rope(ckv[:, r:], cos, sin)], axis=-1)
    return q[..., :cfg.qk_nope_head_dim], q_pe, row


def _kv_b(cfg, lp):
    """``W_kvb`` as [rank, heads, nope + v]."""
    return lp["self_attn.kv_b_proj.weight"].reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)


def _softmax(s):
    """Softmax over the last axis with the row's largest score behind an
    optimization barrier.  Left to itself the TPU compiler fuses ``max ->
    broadcast -> subtract`` into one ``reduce-window`` as wide as the
    row, which at 4,096 keys and more ran 11 ms a call where the scores'
    bytes take 0.3 (PERF.md section 6, PR 32)."""
    m = jax.lax.optimization_barrier(jnp.max(s, axis=-1, keepdims=True))
    e = jnp.exp(s - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def attend_expanded(cfg, lp, q_nope, q_pe, rows, mask, head_block=None):
    """The expanded form: queries [T, heads, ..] against ``rows``
    [S, rank + rope] (everything they may read, cached or fresh), whose
    keys and values are rebuilt from the latent; mask [T, S].  Returns
    the attention's output through ``W_o``, [T, H].  ``head_block``
    heads at a time (all at once by default): the scores are
    ``[block, T, S]`` float32."""
    T, nh = q_nope.shape[:2]
    r, dn, dv = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    hb = nh if head_block is None else head_block
    kv = jnp.einsum("sr,rhd->hsd", rows[:, :r], _kv_b(cfg, lp))
    k_pe = rows[:, r:]
    scale = softmax_scale(cfg)

    def block(args):
        qn, qp, kvb = args                  # [hb, T, ..], [hb, S, dn + dv]
        s = (jnp.einsum("htd,hsd->hts", qn, kvb[..., :dn],
                        preferred_element_type=_F32)
             + jnp.einsum("htd,sd->hts", qp, k_pe,
                          preferred_element_type=_F32)) * scale
        s = jnp.where(mask[None], s, jnp.finfo(_F32).min)
        p = _softmax(s).astype(kvb.dtype)
        return jnp.einsum("hts,hsd->htd", p, kvb[..., dn:])

    def blocks(a):                          # [T, heads, d] -> [n, hb, T, d]
        return jnp.swapaxes(a, 0, 1).reshape(nh // hb, hb, *a.shape[:1],
                                             a.shape[2])

    o = jax.lax.map(block, (blocks(q_nope), blocks(q_pe),
                            kv.reshape(nh // hb, hb, *kv.shape[1:])))
    o = jnp.swapaxes(o.reshape(nh, T, dv), 0, 1).reshape(T, nh * dv)
    return o @ lp["self_attn.o_proj.weight"]


def absorb_query(cfg, lp, q_nope, q_pe):
    """The query in the latent's own space, scaled: ``[q_nope W_UK^T ;
    q_pe] * s``, [T, heads, rank + rope], to be scored against cached
    rows as they are."""
    w_uk = _kv_b(cfg, lp)[..., :cfg.qk_nope_head_dim]
    q_lat = jnp.einsum("thd,rhd->thr", q_nope, w_uk)
    q = jnp.concatenate([q_lat, q_pe], axis=-1)
    return (q.astype(_F32) * softmax_scale(cfg)).astype(q_nope.dtype)


def absorb_output(cfg, lp, u):
    """From ``u[h] = sum p c`` [T, heads, rank] to the attention's output
    through ``W_UV`` and ``W_o``, [T, H]."""
    w_uv = _kv_b(cfg, lp)[..., cfg.qk_nope_head_dim:]
    o = jnp.einsum("thr,rhd->thd", u.astype(w_uv.dtype), w_uv)
    return o.reshape(u.shape[0], -1) @ lp["self_attn.o_proj.weight"]


def attend_absorbed(q, rows, mask, rank):
    """The absorbed form in ``jax.numpy``: q [T, heads, rank + rope]
    (:func:`absorb_query`), rows [S, rank + rope] as cached, mask [T, S].
    Returns u [T, heads, rank] float32 (what the decode kernel computes
    over the page pool)."""
    s = jnp.einsum("thw,sw->hts", q, rows, preferred_element_type=_F32)
    p = jax.nn.softmax(jnp.where(mask[None], s, jnp.finfo(_F32).min), -1)
    return jnp.einsum("hts,sr->thr", p.astype(rows.dtype), rows[:, :rank],
                      preferred_element_type=_F32)


def route(cfg, lp, h):
    """:func:`moe.route` with this model's router, bias and scalars."""
    return _moe.route(h, lp["mlp.gate.weight"],
                      lp["mlp.gate.e_score_correction_bias"],
                      cfg.num_experts_per_tok, cfg.routed_scaling_factor)


def feed_forward(cfg, kind, lp, x, held):
    """The layer's second part on the residual stream x [T, H].  Returns
    (x after the residual, which held expert took which row
    [T, len(held)] bool — no column for a dense layer)."""
    h = _norm(cfg, x, lp["post_attention_layernorm.weight"])
    if kind == "mla_dense":
        return (x + swiglu(h, lp["mlp.gate_up_proj.weight"],
                           lp["mlp.down_proj.weight"]),
                jnp.zeros((x.shape[0], 0), bool))
    sel, w = route(cfg, lp, h)
    y = routed_experts(h, sel, w, held, lp["mlp.experts.gate_up_proj"],
                       lp["mlp.experts.down_proj"])
    y = y + swiglu(h, lp["mlp.shared_experts.gate_up_proj.weight"],
                   lp["mlp.shared_experts.down_proj.weight"]).astype(_F32)
    return x + y.astype(x.dtype), held_weights(sel, w, held) > 0


def head(cfg, norm_w, lm_head, x):
    """Logits of rows x [T, H], float32 (a matrix: one row alone is
    multiplied element by element, the head converted to float32 for it).
    bf16 logits stand 0.03 apart near the top of 65,536 of them: the
    argmax would be taken over ties the arithmetic does not have."""
    return jnp.matmul(_norm(cfg, x, norm_w), lm_head,
                      preferred_element_type=_F32)


def _layer_forward(x, *params, cfg, kind, held):
    """One layer over a batch of whole sequences x [B, T, H]: the
    expanded form under a causal mask."""
    lp = dict(zip(layer_param_names(kind), params))

    def one(xs):
        T = xs.shape[0]
        q_nope, q_pe, rows = mla_project(cfg, lp, xs, jnp.arange(T))
        xs = xs + attend_expanded(cfg, lp, q_nope, q_pe, rows,
                                  jnp.tril(jnp.ones((T, T), bool)))
        return feed_forward(cfg, kind, lp, xs, held)[0]

    return jax.vmap(one)(x)


def _head_forward(x, norm_w, lm_head, *, cfg):
    return head(cfg, norm_w, lm_head, x)


# -- the eager model ---------------------------------------------------------

def _layer_shapes(cfg, kind, n_held):
    h, nh = cfg.hidden_size, cfg.num_attention_heads
    w, one = I.Normal(0.0, cfg.initializer_range), I.Constant(1.0)
    shapes = {
        "input_layernorm.weight": ((h,), one),
        "self_attn.q_proj.weight": ((h, nh * cfg.q_head_dim), w),
        "self_attn.q_norm.weight": ((cfg.q_head_dim,), one),
        "self_attn.kv_a_proj_with_mqa.weight": ((h, cfg.latent_dim), w),
        "self_attn.kv_a_layernorm.weight": ((cfg.kv_lora_rank,), one),
        "self_attn.kv_b_proj.weight": (
            (cfg.kv_lora_rank,
             nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)), w),
        "self_attn.o_proj.weight": ((nh * cfg.v_head_dim, h), w),
        "post_attention_layernorm.weight": ((h,), one),
    }
    if kind == "mla_dense":
        i = cfg.intermediate_size
        shapes.update({"mlp.gate_up_proj.weight": ((h, 2 * i), w),
                       "mlp.down_proj.weight": ((i, h), w)})
        return shapes
    f = cfg.moe_intermediate_size
    shapes.update({
        "mlp.gate.weight": ((h, cfg.num_experts), w),
        # drawn, so that it changes choices (a trained bias is not zero)
        "mlp.gate.e_score_correction_bias": ((cfg.num_experts,),
                                             I.Normal(0.0, 0.01)),
        "mlp.experts.gate_up_proj": ((n_held, h, 2 * f), w),
        "mlp.experts.down_proj": ((n_held, f, h), w),
        "mlp.shared_experts.gate_up_proj.weight": ((h, 2 * f), w),
        "mlp.shared_experts.down_proj.weight": ((f, h), w)})
    return shapes


class MLAMoELayer(nn.Layer):
    def __init__(self, cfg, kind, held, draw=True):
        super().__init__(dtype=cfg.dtype)
        self.config, self.kind, self.held = cfg, kind, held
        shapes = _layer_shapes(cfg, kind, len(held))
        for part in ("input_layernorm", "self_attn",
                     "post_attention_layernorm", "mlp"):
            own = {k[len(part) + 1:]: v for k, v in shapes.items()
                   if k.startswith(part + ".")}
            setattr(self, part, _Weights(own, cfg.dtype, draw))

    def forward(self, x):
        params = dict(self.named_parameters())
        names = layer_param_names(self.kind)
        if getattr(params[names[-1]]._data, "is_deleted", lambda: False)():
            raise RuntimeError(
                "this layer's arrays were handed over to a ServingEngine "
                "(its executor stacks the expert layers and the device "
                "could not hold two copies): build the model again to run "
                "it eagerly")
        return _registry.cached_apply(
            "mla_moe_layer", _layer_forward, x, *(params[n] for n in names),
            cfg=self.config, kind=self.kind, held=self.held)


class MLAMoEModel(nn.Layer):
    def __init__(self, cfg, held, draw=True):
        super().__init__(dtype=cfg.dtype)
        self.config = cfg
        self.embed_tokens = _Weights(
            {"weight": ((cfg.vocab_size, cfg.hidden_size),
                        I.Normal(0.0, cfg.initializer_range))}, cfg.dtype,
            draw)
        self.layers = nn.LayerList([MLAMoELayer(cfg, kind, held, draw)
                                    for kind in cfg.layer_types])
        self.norm = _Weights({"weight": ((cfg.hidden_size,),
                                         I.Constant(1.0))}, cfg.dtype, draw)


class MLAMoEForCausalLM(nn.Layer):
    """``model(input_ids [B, T]) -> logits [B, T, V]``.  Serving goes
    through ``ServingEngine(model, ...)``, which reads ``config`` and the
    parameters and picks the latent executor by ``layer_types``.

    ``held_experts``: the ids of the routed experts this model holds, in
    the order of its expert weights' first dimension (all
    ``num_experts`` by default).  ``vocab_size`` is the slice of the
    vocabulary held: ids, logits and argmax are over it.
    ``init_weights=False`` leaves every parameter zero, for a model whose
    weights are loaded next."""

    def __init__(self, config: MLAMoEConfig, held_experts=None,
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
        self.model = MLAMoEModel(config, held, draw=init_weights)
        self.lm_head = _Weights(
            {"weight": ((config.hidden_size, config.vocab_size),
                        I.Normal(0.0, config.initializer_range))},
            config.dtype, init_weights)

    def forward(self, input_ids):
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        x = Tensor(self.model.embed_tokens.weight._data[ids])
        for layer in self.model.layers:
            x = layer(x)
        return _registry.cached_apply(
            "mla_moe_head", _head_forward, x, self.model.norm.weight,
            self.lm_head.weight, cfg=self.config)

    def num_params(self):
        return sum(int(np.prod(p.shape)) for p in self.parameters())
