"""The plain reference of the window + full attention configuration with
a gated output and routed experts (``afmoe`` / Arcee Trinity shape) in
float32 jax.numpy.

Written from the model's config.json (the widths, the layer types, the
router's keys) and the ``afmoe`` modeling file as the configuration file
lists it under ``assumed`` (marked [A]: gated attention, per-head q/k
norms, no positional encoding on the full layers, four norms a layer two
of which sit on the branches, the embedding scaled by sqrt(H)).  With H
the hidden size, every norm an RMSNorm (eps ``rms_norm_eps``, a learned
scale) and ``t = layer_types[n]``:

    x0 = embed[ids] * sqrt(H)                                        [A]
    a  = N1(x)
    q  = RMSNorm_D(a Wq as [S, heads, D]);  k = RMSNorm_D(a Wk as
         [S, kv heads, D]);  v = a Wv;  g = a Wg                     [A]
    t == sliding_attention: q, k = rope(q), rope(k) (theta rope_theta,
         pairs (i, i + D/2)); t == full_attention: nothing            [A]
    visible(i, j): j <= i, and for sliding_attention also
         i - j < sliding_window
    o  = softmax_j(q_i . k_j / sqrt(D) where visible) v; query head h
         reads KV head h // (heads / kv heads)
    x  = x + N2((o * sigmoid(g)) Wo)                                 [A]
    b  = N3(x)                                                       [A]
    n <  num_dense_layers:  y = (silu(b Wgate) * (b Wup)) Wdown
    n >= num_dense_layers:  p = sigmoid(b Wr); S = the k experts with
         the largest p + bias; w = route_scale * p_S / (sum p_S + 1e-20)
         y = Shared(b) + sum_{e in S} w_e Expert_e(b)
    x  = x + N4(y)                                                   [A]
    logits = N(x) Whead

No cache, no kernel, no block table: every token's keys and values are
computed in place and the mask is ``visible(i, j)`` written out, over the
whole sequence on sliding layers too.  It imports nothing from the
program under test and takes nothing the program made (its matmul, int8
control and RMSNorm are ``reference_hybrid.py``'s, the benchmark's own).
Every matmul runs at precision "highest"; one sequence at a time,
attention a block of queries of one KV head at a time and the experts one
at a time, so that 13.8 k tokens and a float32 expert layer (3.4 GB) fit;
one jitted program per layer kind, called layer after layer with that
layer's weights only.

Controls: ``quant="int8"`` puts every matmul on 8-bit operands with a
bf16 result (the nearest precision below bf16 the v5e has hardware for);
``top_k`` routes to that many experts a token instead of the published
number; ``ignore_window`` lets a sliding layer's query see every earlier
key; ``rope_on_full`` rotates the full layers' queries and keys too.
Each leaves out or alters part of the mathematics.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

# the plain pieces every reference of the benchmark shares: float32
# "highest" matmul (and its int8 control), RMSNorm
from chipbench.reference_hybrid import HIGHEST, f32, mm, rms_norm

QUERY_BLOCK = 512       # queries of one KV head attended at a time
SLIDING, FULL = "sliding_attention", "full_attention"
ROUTE_EPS = 1e-20


def layer_types(cfg):
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def rope(cfg, x):
    """x [S, heads, D] at positions 0 .. S - 1, pairs (i, i + D / 2)."""
    d = cfg["head_dim"]
    freq = float(cfg["rope_theta"]) ** (
        -np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def visible(i, j, window):
    seen = j <= i
    return seen if window is None else seen & (i - j < window)


def attention(cfg, w, a, kind, quant, ignore_window, rope_on_full):
    """The gated attention branch of one sequence a [S, H] (normed
    input), before its norm.  Returns (out [S, H], the keys and values a
    cache would hold [S, 2, kv heads, D])."""
    s, d, eps = a.shape[0], cfg["head_dim"], cfg["rms_norm_eps"]
    q = rms_norm(mm(a, w["q"], quant).reshape(s, -1, d), w["q_norm"], eps)
    k = rms_norm(mm(a, w["k"], quant).reshape(s, -1, d), w["k_norm"], eps)
    v = mm(a, w["v"], quant).reshape(s, -1, d)
    gate = mm(a, w["gate"], quant)
    if kind == SLIDING or rope_on_full:
        q, k = rope(cfg, q), rope(cfg, k)
    window = cfg["sliding_window"] if kind == SLIDING and not ignore_window \
        else None
    hq, hkv = q.shape[1], k.shape[1]
    qb = min(QUERY_BLOCK, s)
    keys = jnp.arange(s)

    def block(args):
        qs, at, head = args                     # [qb, G, D], [qb], scalar
        kk = jax.lax.dynamic_index_in_dim(k, head, 1, keepdims=False)
        vv = jax.lax.dynamic_index_in_dim(v, head, 1, keepdims=False)
        scores = jnp.einsum("qgd,sd->gqs", qs, kk, precision=HIGHEST) \
            / np.sqrt(d)
        seen = visible(at[:, None], keys[None, :], window)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("gqs,sd->qgd", probs, vv, precision=HIGHEST)

    # [S, kv, G, D] -> blocks [kv * S / qb, qb, G, D], KV head by KV head
    qg = jnp.moveaxis(q.reshape(s // qb, qb, hkv, hq // hkv, d), 2, 0) \
        .reshape(-1, qb, hq // hkv, d)
    at = jnp.tile(keys.reshape(s // qb, qb), (hkv, 1))
    heads = jnp.repeat(jnp.arange(hkv), s // qb)
    out = jax.lax.map(block, (qg, at, heads))
    out = jnp.moveaxis(out.reshape(hkv, s, hq // hkv, d), 0, 1) \
        .reshape(s, hq * d)
    return (mm(out * jax.nn.sigmoid(gate), w["o"], quant),
            jnp.stack([k, v], axis=1))


def swiglu(h, gate_up, down, quant):
    gate, up = jnp.split(mm(h, gate_up, quant), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, down, quant)


def routed(cfg, w, h, quant, top_k, experts=None):
    """Shared(h) + the routed sum over ``experts`` (ids ``(first, end)``
    into the layer's experts; all of them by default)."""
    k = cfg["num_experts_per_tok"] if top_k is None else top_k
    first, end = experts or (0, cfg["num_experts"])
    sc = jax.nn.sigmoid(mm(h, w["router"], quant))
    _, sel = jax.lax.top_k(sc + f32(w["router_bias"]), k)
    picked = jnp.take_along_axis(sc, sel, axis=-1)
    wts = cfg["route_scale"] * picked / (picked.sum(-1, keepdims=True)
                                         + ROUTE_EPS)

    def one(acc, e):
        gate_up, down, eid = e
        w_e = jnp.sum(jnp.where(sel == eid, wts, 0.0), axis=-1)   # [S]
        return acc + w_e[:, None] * swiglu(h, gate_up, down, quant), None

    y, _ = jax.lax.scan(
        one, swiglu(h, w["shared_gate_up"], w["shared_down"], quant),
        (w["experts_gate_up"][first:end], w["experts_down"][first:end],
         jnp.arange(first, end, dtype=sel.dtype)))
    return y


def layer(cfg, kind, dense, w, x, quant=None, top_k=None,
          ignore_window=False, rope_on_full=False):
    """One layer on one sequence x [S, H]; ``w`` holds the layer's leaves
    by their short names.  Returns (x, its keys and values
    [S, 2, kv heads, D])."""
    eps = cfg["rms_norm_eps"]
    out, kv = attention(cfg, w, rms_norm(x, w["ln1"], eps), kind, quant,
                        ignore_window, rope_on_full)
    x = x + rms_norm(out, w["ln2"], eps)
    b = rms_norm(x, w["ln3"], eps)
    y = (swiglu(b, w["gate_up"], w["down"], quant) if dense
         else routed(cfg, w, b, quant, top_k))
    return x + rms_norm(y, w["ln4"], eps), kv


class Scorer:
    """The reference over a few served sequences, LAYER BY LAYER: layer
    n's weights are asked for once (``layer_weights(n)``), every sequence
    goes through the layer, and the weights are dropped before the next
    layer's are made.  A sequence is padded to a multiple of ``bucket``
    (every mask is causal: the padding changes nothing before it), so a
    few shapes compile whatever the lengths."""

    def __init__(self, cfg, rows, quant=None, top_k=None,
                 ignore_window=False, rope_on_full=False, bucket=1024):
        self.cfg, self.rows, self.bucket, self.quant = cfg, rows, bucket, \
            quant
        self.kinds = [(kind, n < cfg["num_dense_layers"])
                      for n, kind in enumerate(layer_types(cfg))]
        self.fns = {key: jax.jit(functools.partial(
            layer, cfg, *key, quant=quant, top_k=top_k,
            ignore_window=ignore_window, rope_on_full=rope_on_full))
            for key in set(self.kinds)}
        scale = np.float32(np.sqrt(cfg["hidden_size"]))
        self.embed = jax.jit(lambda e, ids: f32(e[ids]) * scale)
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

    def forward(self, top, layer_weights, seqs, keep_rows=(),
                keep_layers=()):
        """Final-norm hidden states of every sequence of ``seqs`` (lists
        of ids; each padded as its ``least`` says: ``(ids, least)``), and
        for the sequences whose index is in ``keep_rows`` the keys and
        values of the layers in ``keep_layers``: ``{index: {layer:
        [len(ids), 2, kv heads, D]}}``."""
        with jax.enable_x64(False):
            xs = [self.embed(top["embed"],
                             jnp.asarray(self._padded(ids, least)))
                  for ids, least in seqs]
            kept = {i: {} for i in keep_rows}
            for n, key in enumerate(self.kinds):
                w = layer_weights(n)
                for i, x in enumerate(xs):
                    xs[i], kv = self.fns[key](w, x)
                    if i in kept and n in keep_layers:
                        kept[i][n] = np.asarray(kv[:len(seqs[i][0])],
                                                np.float32)
                    del kv
                del w
            hidden = [self.norm(x, top["norm"]) for x in xs]
        return hidden, kept

    def logits(self, top, hidden, first):
        """logits [rows, V] of positions first .. first + rows - 1."""
        with jax.enable_x64(False):
            return self.head(top["head"], hidden, jnp.int32(first))
