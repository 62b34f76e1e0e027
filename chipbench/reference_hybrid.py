"""The plain reference of the hybrid configuration: a Mamba-2 / attention
decoder (granitemoehybrid) in float32 jax.numpy.

Written from the published description (Mamba-2: Dao & Gu,
arXiv:2405.21060, section 7's block; the model's config.json for the
layer kinds, the widths and the four multipliers).  With H the hidden
size and eps ``rms_norm_eps``:

    x = embedding_multiplier * embed[ids]
    layer l:  x = x + residual_multiplier * Mixer_l(RMSNorm(x))
              x = x + residual_multiplier * MLP(RMSNorm(x))
    MLP(h):   [g | u] = h W_in;  (silu(g) * u) W_out
    logits  = RMSNorm(x) embed^T / logits_scaling

    attention: q, k, v = h Wq, h Wk, h Wv (no positional rotation);
        causal softmax(q k^T * attention_multiplier) v, then Wo; query
        head h reads KV head h // (heads / kv heads)
    Mamba-2:  [z | xBC | dt] = h W_in
        xBC = silu(b + sum_k w[k] * xBC[t - (K-1) + k])     (zeros before 0)
        [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
        per head, S_0 = 0:  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
                            y_t = S_t C_t + D x_t
        out = (RMSNorm(y * silu(z)) * w) W_out

The recurrence is taken TOKEN BY TOKEN (a ``lax.scan`` over time), never
in a chunked form, so it shares no algorithm with the program.  It
imports nothing from the program under test and takes nothing the
program made.  Every matmul runs at precision "highest"; one sequence at
a time; one jitted program per layer kind, called layer after layer, so
depth costs no compile time.

Controls, as in ``reference.py``: ``quant="int8"`` puts every matmul on
8-bit operands with a bf16 result (the nearest precision below bf16 the
v5e has hardware for); ``state="bfloat16"`` rounds the recurrent state
``S_t`` to bf16 after every token (a state cache that does not keep
float32).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def f32(x):
    return x.astype(jnp.float32)


def _through(x, dtype):
    """x rounded to ``dtype``'s values (reduce_precision: XLA may drop a
    cast there and back)."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _int8_round(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def mm(x, w, quant=None):
    """x [.., K] @ w [K, N] in float32 at precision "highest"; with
    ``quant="int8"`` as an int8 path of a bf16 program would: bf16 in,
    8-bit operands per row / column of the contraction, bf16 out."""
    x, w = f32(x), f32(w)
    if quant is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    if quant != "int8":
        raise ValueError(f"unknown control precision {quant!r}")
    x = _int8_round(_through(x, jnp.bfloat16), -1)
    w = _int8_round(_through(w, jnp.bfloat16), 0)
    return _through(jnp.matmul(x, w, precision=HIGHEST), jnp.bfloat16)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * f32(w)


def mlp(cfg, w, x, quant):
    h = rms_norm(x, w["ln2"], cfg["rms_norm_eps"])
    gate, up = jnp.split(mm(h, w["mlp_in"], quant), 2, axis=-1)
    return x + cfg["residual_multiplier"] * mm(
        jax.nn.silu(gate) * up, w["mlp_out"], quant)


def attention(cfg, w, h, quant):
    """Causal grouped-query attention of one sequence h [S, H], scores
    times ``attention_multiplier``, no rotary embedding."""
    s, d = h.shape[0], cfg["head_dim"]
    q = mm(h, w["q"], quant).reshape(s, -1, d)
    k = mm(h, w["k"], quant).reshape(s, -1, d)
    v = mm(h, w["v"], quant).reshape(s, -1, d)
    hq, hkv = q.shape[1], k.shape[1]
    qg = q.reshape(s, hkv, hq // hkv, d)
    scores = jnp.einsum("qkgd,skd->kgqs", qg, k, precision=HIGHEST)
    scores = scores * cfg["attention_multiplier"]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("kgqs,skd->qkgd", probs, v, precision=HIGHEST)
    return mm(out.reshape(s, hq * d), w["o"], quant)


def mamba(cfg, w, h, quant, state, keep):
    """The Mamba-2 mixer of one sequence h [S, H], the recurrence token
    by token.  Returns (out [S, H], the state after token ``keep``
    [heads, P, N]; zeros where no token is ``keep``)."""
    s = h.shape[0]
    nh, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], \
        cfg["mamba_d_state"]
    inner, kw = nh * p, cfg["mamba_d_conv"]
    zxbcdt = mm(h, w["in_proj"], quant)
    conv = zxbcdt.shape[-1] - inner - nh
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv],
                  zxbcdt[:, inner + conv:])
    padded = jnp.concatenate([jnp.zeros((kw - 1, conv), jnp.float32), xbc])
    cw = f32(w["conv_w"])
    xbc = jax.nn.silu(f32(w["conv_b"]) + sum(
        cw[k] * padded[k:k + s] for k in range(kw)))
    x = xbc[:, :inner].reshape(s, nh, p)
    b, c = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + f32(w["dt_bias"]))                # [S, heads]
    a = -jnp.exp(f32(w["A_log"]))                               # [heads]

    def step(carry, t):
        S, kept = carry
        i, x_t, b_t, c_t, dt_t = t
        S = (jnp.exp(dt_t * a)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        if state is not None:
            S = _through(S, jnp.dtype(state))
        kept = jnp.where(i == keep, S, kept)
        return (S, kept), jnp.sum(S * c_t[None, None, :], axis=-1)

    zero = jnp.zeros((nh, p, n), jnp.float32)
    (_, kept), y = jax.lax.scan(step, (zero, zero),
                                (jnp.arange(s), x, b, c, dt))
    y = y + f32(w["D"])[None, :, None] * x
    y = y.reshape(s, inner) * jax.nn.silu(z)
    y = rms_norm(y, w["mnorm"], cfg["rms_norm_eps"])
    return mm(y, w["out_proj"], quant), kept


def layer(cfg, kind, w, x, keep, quant=None, state=None):
    """One layer on one sequence x [S, H]; ``w`` holds the layer's leaves
    by their short names.  Returns (x, the recurrent state after token
    ``keep``; None for an attention layer)."""
    h = rms_norm(x, w["ln1"], cfg["rms_norm_eps"])
    mixed, kept = ((attention(cfg, w, h, quant), None)
                   if kind == "attention"
                   else mamba(cfg, w, h, quant, state, keep))
    return mlp(cfg, w, x + cfg["residual_multiplier"] * mixed, quant), kept


def layer_weights(w, n):
    p = f"layers.{n}."
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def make_forward(cfg, quant=None, state=None):
    """``(w, ids [S]) -> final-norm hidden states [S, H]``: one jitted
    program per layer kind, called layer after layer.  With ``keep`` it
    returns beside them every recurrent layer's state after token
    ``keep``, ``[recurrent layers, heads, P, N]``; ``depth`` stops after
    that many layers (the hidden states are then that layer's)."""
    fns = {kind: jax.jit(functools.partial(layer, cfg, kind, quant=quant,
                                           state=state))
           for kind in set(cfg["layer_types"])}
    embed = jax.jit(lambda e, ids: f32(e[ids]) * cfg["embedding_multiplier"])
    norm = jax.jit(lambda x, g: rms_norm(x, g, cfg["rms_norm_eps"]))

    def forward(w, ids, keep=None, depth=None):
        with jax.enable_x64(False):
            x = embed(w["embed"], jnp.asarray(ids, jnp.int32))
            at, states = jnp.int32(-1 if keep is None else keep), []
            for n, kind in enumerate(cfg["layer_types"][:depth]):
                x, kept = fns[kind](layer_weights(w, n), x, at)
                if kept is not None:
                    states.append(kept)
            hidden = norm(x, w["norm"])
            return hidden if keep is None else (hidden, jnp.stack(states))

    return forward


def logits(cfg, w, hidden, quant=None):
    return mm(hidden, f32(w["embed"]).T, quant) / cfg["logits_scaling"]


class Scorer:
    """The reference over one served sequence at a time.  A sequence is
    padded to a multiple of ``bucket`` (causal layers: the padding changes
    nothing before it), so a few shapes compile whatever the lengths."""

    def __init__(self, cfg, rows, quant=None, state=None, bucket=512):
        self.rows, self.bucket = rows, bucket
        self.forward = make_forward(cfg, quant, state)

        @jax.jit
        def head(w_embed, hid, first):
            picked = jax.lax.dynamic_slice_in_dim(hid, first, rows, axis=0)
            return logits(cfg, {"embed": w_embed}, picked, quant)

        self.head = head

    def _padded(self, ids, least):
        pad_to = -(-max(len(ids), least) // self.bucket) * self.bucket
        padded = np.zeros((pad_to,), np.int32)
        padded[: len(ids)] = ids
        return padded

    def __call__(self, w, ids, first):
        """logits [rows, V] of positions first .. first + rows - 1: the
        whole sequence goes through the layers and only the wanted rows
        through the head."""
        hid = self.forward(w, self._padded(ids, first + self.rows))
        with jax.enable_x64(False):
            return self.head(w["embed"], hid, jnp.int32(first))

    def states(self, w, ids, depth=None):
        """The recurrent layers' states after the last of ``ids``
        ``[recurrent layers, heads, P, N]``, of the first ``depth`` layers
        (all of them by default)."""
        return self.forward(w, self._padded(ids, 0), keep=len(ids) - 1,
                            depth=depth)[1]
