"""The plain reference of the latent-attention + routed-experts
configuration (``sarvam_mla`` / DeepSeek-V3 shape) in float32 jax.numpy.

Written from the published description (DeepSeek-V2, arXiv:2405.04434
section 2.1 for the latent attention; DeepSeek-V3, arXiv:2412.19437
section 2.1.2 for the sigmoid router with its bias; YaRN, arXiv:2309.00071;
the model's config.json for the widths).  With ``h = RMSNorm(x)`` (eps
``rms_norm_eps``, learned scale) before each of a layer's two parts and a
residual after each:

    attention: q = h Wq as [S, heads, nope + rope], RMSNorm over each
        head's width, split into q_nope | q_pe
        [c | k_pe] = h Wkva;  c <- RMSNorm(c);  k_pe one vector a token
        rope on q_pe and k_pe (YaRN frequencies, pairs (i, i + rope/2))
        the row a cache would hold: [c | rope(k_pe)]
        [k_nope | v] = c Wkvb as [S, heads, nope + v]
        scores (q_nope . k_nope + q_pe . k_pe) * s,
            s = (nope + rope)^-0.5 * (0.1 ln(factor) + 1)^2
        causal softmax, out = concat_h(p v) Wo
    layer < first_k_dense_replace:  SwiGLU of intermediate_size
    other layers:  sc = sigmoid(h Wr) over ALL the router's experts;
        the k experts with the largest sc + b;  w = scaling * sc[sel] /
        sum sc[sel];  y = Shared(h) + sum_{e in sel, e held} w_e E_e(h)
    logits = RMSNorm(x) Whead

It is the EXPANDED form only: no cache, no absorbed projection, no
kernel; every token's keys and values are rebuilt from its latent.  The
share is the configuration's: it routes over the router's published
width and adds the experts whose ids are held (``share.held_experts``),
so what the absent experts would have added is left out here as in the
program; ids and logits are over the held slice of the vocabulary.  It
imports nothing from the program under test and takes nothing the
program made (its matmul, int8 control and RMSNorm are
``reference_hybrid.py``'s, the benchmark's own).  Every matmul runs at
precision "highest"; one sequence at
a time, attention a few heads at a time and the experts one at a time, so
that a float32 expert layer (3.7 GB) is never held; one jitted program
per layer kind, called layer after layer with that layer's weights only.

Controls, as in ``reference.py``: ``quant="int8"`` puts every matmul on
8-bit operands with a bf16 result (the nearest precision below bf16 the
v5e has hardware for); ``top_k`` routes to that many experts a token
instead of the published number (part of the mathematics left out).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# the plain pieces every reference of the benchmark shares: float32
# "highest" matmul (and its int8 control), RMSNorm
from chipbench.reference_hybrid import HIGHEST, f32, mm, rms_norm

HEAD_BLOCK = 2          # heads attended at a time: [2, S, S] float32


def yarn_frequencies(cfg):
    """The rope's inverse frequencies [rope / 2], float64 on the host.
    YaRN (``deepseek_yarn``): with ``d(t)`` the dimension whose pair
    turns t times inside the original context, pairs below d(beta_fast)
    keep theta^(-2i/d), pairs above d(beta_slow) get it divided by
    ``factor``, a linear ramp between."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    rs = cfg.get("rope_scaling")
    if not rs:
        return plain

    def dim_of(turns):
        return d * math.log(rs["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(dim_of(rs["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / rs["factor"] * ramp


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(cfg):
    rs = cfg.get("rope_scaling")
    m = _mscale(rs["factor"], rs["mscale_all_dim"]) if rs else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(cfg, x):
    """x [S, .., rope] at positions 0 .. S - 1."""
    rs = cfg.get("rope_scaling")
    m = (_mscale(rs["factor"], rs["mscale"])
         / _mscale(rs["factor"], rs["mscale_all_dim"])) if rs else 1.0
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_frequencies(cfg), jnp.float32)[None, :]
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg, w, h, quant):
    """Causal latent attention of one sequence h [S, H], expanded.
    Returns (out [S, H], the rows a cache would hold [S, rank + rope])."""
    s, nh, r = h.shape[0], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    q = rms_norm(mm(h, w["q"], quant).reshape(s, nh, -1), w["q_norm"], eps)
    q_nope, q_pe = q[..., :dn], rope(cfg, q[..., dn:])
    ckv = mm(h, w["kv_a"], quant)
    c, k_pe = rms_norm(ckv[:, :r], w["kv_norm"], eps), rope(cfg, ckv[:, r:])
    kv = mm(c, w["kv_b"], quant).reshape(s, nh, dn + dv)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scale = softmax_scale(cfg)

    def heads(args):
        qn, qp, kvb = args                          # [hb, S, ..]
        scores = (jnp.einsum("hqd,hkd->hqk", qn, kvb[..., :dn],
                             precision=HIGHEST)
                  + jnp.einsum("hqd,kd->hqk", qp, k_pe,
                               precision=HIGHEST)) * scale
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,hkd->hqd", probs, kvb[..., dn:],
                          precision=HIGHEST)

    def blocks(a):                                  # [S, heads, d]
        return jnp.swapaxes(a, 0, 1).reshape(nh // hb, hb, s, a.shape[-1])

    hb = HEAD_BLOCK if nh % HEAD_BLOCK == 0 else 1
    out = jax.lax.map(heads, (blocks(q_nope), blocks(q_pe), blocks(kv)))
    out = jnp.swapaxes(out.reshape(nh, s, dv), 0, 1).reshape(s, nh * dv)
    return mm(out, w["o"], quant), jnp.concatenate([c, k_pe], axis=-1)


def swiglu(h, gate_up, down, quant):
    gate, up = jnp.split(mm(h, gate_up, quant), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, down, quant)


def routed(cfg, w, h, quant, top_k):
    """Shared(h) + the held experts' part of the routed sum."""
    k = cfg["num_experts_per_tok"] if top_k is None else top_k
    first, end = cfg["share"]["held_experts"]
    sc = jax.nn.sigmoid(mm(h, w["router"], quant))
    _, sel = jax.lax.top_k(sc + f32(w["router_bias"]), k)
    picked = jnp.take_along_axis(sc, sel, axis=-1)
    wts = cfg["routed_scaling_factor"] * picked / picked.sum(-1,
                                                             keepdims=True)

    def one(acc, e):
        gate_up, down, eid = e
        w_e = jnp.sum(jnp.where(sel == eid, wts, 0.0), axis=-1)   # [S]
        return acc + w_e[:, None] * swiglu(h, gate_up, down, quant), None

    y, _ = jax.lax.scan(
        one, swiglu(h, w["shared_gate_up"], w["shared_down"], quant),
        (w["experts_gate_up"], w["experts_down"],
         jnp.arange(first, end, dtype=sel.dtype)))
    return y


def layer(cfg, kind, w, x, quant=None, top_k=None):
    """One layer on one sequence x [S, H]; ``w`` holds the layer's leaves
    by their short names.  Returns (x, the layer's rows [S, rank + rope])."""
    eps = cfg["rms_norm_eps"]
    out, rows = attention(cfg, w, rms_norm(x, w["ln1"], eps), quant)
    x = x + out
    h = rms_norm(x, w["ln2"], eps)
    if kind == "dense":
        return x + swiglu(h, w["gate_up"], w["down"], quant), rows
    return x + routed(cfg, w, h, quant, top_k), rows


def kinds(cfg):
    k = cfg["first_k_dense_replace"]
    return ["dense"] * k + ["moe"] * (cfg["num_hidden_layers"] - k)


class Scorer:
    """The reference over a few served sequences, LAYER BY LAYER: layer
    n's weights are asked for once (``layer_weights(n)``), every sequence
    goes through the layer, and the weights are dropped before the next
    layer's are made.  A sequence is padded to a multiple of ``bucket``
    (causal layers: the padding changes nothing before it), so a few
    shapes compile whatever the lengths."""

    def __init__(self, cfg, rows, quant=None, top_k=None, bucket=1024):
        self.cfg, self.rows, self.bucket, self.quant = cfg, rows, bucket, \
            quant
        self.fns = {kind: jax.jit(functools.partial(
            layer, cfg, kind, quant=quant, top_k=top_k))
            for kind in set(kinds(cfg))}
        self.embed = jax.jit(lambda e, ids: f32(e[ids]))
        self.norm = jax.jit(lambda x, g: rms_norm(x, g, cfg["rms_norm_eps"]))

        @jax.jit
        def head(w_head, hid, first):
            picked = jax.lax.dynamic_slice_in_dim(hid, first, rows, axis=0)
            return mm(picked, w_head, quant)

        self.head = head

    def _padded(self, ids, least):
        pad_to = -(-max(len(ids), least) // self.bucket) * self.bucket
        padded = np.zeros((pad_to,), np.int32)
        padded[: len(ids)] = ids
        return padded

    def forward(self, top, layer_weights, seqs, keep_rows=()):
        """Final-norm hidden states of every sequence of ``seqs`` (lists
        of ids; each padded as its ``least`` says: ``(ids, least)``), and
        for the sequences whose index is in ``keep_rows`` the rows of
        every layer ``[layers, len(ids), rank + rope]``."""
        with jax.enable_x64(False):
            xs = [self.embed(top["embed"],
                             jnp.asarray(self._padded(ids, least)))
                  for ids, least in seqs]
            kept = {i: [] for i in keep_rows}
            for n, kind in enumerate(kinds(self.cfg)):
                w = layer_weights(n)
                for i, x in enumerate(xs):
                    xs[i], rows = self.fns[kind](w, x)
                    if i in kept:
                        kept[i].append(np.asarray(
                            rows[:len(seqs[i][0])], np.float32))
                    del rows
                del w
            hidden = [self.norm(x, top["norm"]) for x in xs]
        return hidden, {i: np.stack(r) for i, r in kept.items()}

    def logits(self, top, hidden, first):
        """logits [rows, V] of positions first .. first + rows - 1."""
        with jax.enable_x64(False):
            return self.head(top["head"], hidden, jnp.int32(first))
