"""The plain reference: a Mistral/Llama-style decoder in float32 jax.numpy.

Written from the published description (Mistral 7B, arXiv 2310.06825,
and the model's config.json): token embedding, pre-norm decoder layers
of RMSNorm -> grouped-query attention with rotary position embedding
(the "rotate half" form, base ``rope_theta``) -> residual -> RMSNorm ->
SwiGLU MLP -> residual, a final RMSNorm and an untied output head; for
training the mean next-token cross-entropy and AdamW (decoupled weight
decay, bias-corrected moments, global-norm clipping) on float32 state.

It imports nothing from the program under test and takes nothing the
program made.  Every matmul runs at precision "highest" (on a TPU a
float32 matmul is otherwise a single bf16 pass).  It works on one
sequence at a time and the callers go through a batch row by row, so
that it fits beside nothing else on the chip.

``quant`` puts the reference in the program's place at the nearest
precision below bf16 that this chip has hardware for — int8 (the v5e's
MXU runs int8 at twice its bf16 rate, and has no fp8): every matmul takes
bf16 inputs, rounds both operands to 8-bit integers per row/column of
the contraction, and gives a bf16 result, the gradient passing straight
through.  That is the
control of the comparison that decides ``correct``; with ``quant=None``
nothing is rounded.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# -- arithmetic ------------------------------------------------------------

def _int8_round(x, axis):
    """x rounded to 127 levels of its absolute maximum along ``axis``
    (the contraction), the gradient passing straight through."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _through(x, dtype):
    """x rounded to ``dtype``'s values, the gradient passing straight
    through.  (reduce_precision: XLA may drop a cast there and back.)"""
    info = jnp.finfo(dtype)
    return x + jax.lax.stop_gradient(
        jax.lax.reduce_precision(x, info.nexp, info.nmant) - x)


def mm(x, w, quant=None):
    """x [.., K] @ w [K, N] in float32 at precision "highest"."""
    x, w = f32(x), f32(w)
    if quant is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    if quant != "int8":
        raise ValueError(f"unknown control precision {quant!r}")
    # as an int8 path of a bf16 program would: bf16 in, 8-bit operands,
    # exact accumulation, bf16 out
    x = _int8_round(_through(x, jnp.bfloat16), -1)
    w = _int8_round(_through(w, jnp.bfloat16), 0)
    return _through(jnp.matmul(x, w, precision=HIGHEST), jnp.bfloat16)


def f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * f32(w)


def rope(x, positions, theta):
    """x [S, heads, D]; rotates pairs (i, i + D/2) by position * theta^(-2i/D)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None]   # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q, k, v):
    """Causal grouped-query attention.  q [S, Hq, D], k and v [S, Hkv, D];
    query head h reads KV head h // (Hq / Hkv)."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(s, hkv, hq // hkv, d)
    scores = jnp.einsum("qkgd,skd->kgqs", qg, k, precision=HIGHEST)
    scores = scores / np.sqrt(d)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("kgqs,skd->qkgd", probs, v, precision=HIGHEST)
    return out.reshape(s, hq * d)


def layer(cfg, w, p, x, positions, quant):
    """One decoder layer on one sequence x [S, H]; ``p`` = "layers.<i>."."""
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    s = x.shape[0]
    h = rms_norm(x, w[p + "ln1"], eps)
    q = mm(h, w[p + "q"], quant).reshape(s, -1, d)
    k = mm(h, w[p + "k"], quant).reshape(s, -1, d)
    v = mm(h, w[p + "v"], quant).reshape(s, -1, d)
    q = rope(q, positions, cfg["rope_theta"])
    k = rope(k, positions, cfg["rope_theta"])
    x = x + mm(attention(q, k, v), w[p + "o"], quant)
    h = rms_norm(x, w[p + "ln2"], eps)
    gate, up = mm(h, w[p + "gate"], quant), mm(h, w[p + "up"], quant)
    return x + mm(jax.nn.silu(gate) * up, w[p + "down"], quant)


def hidden_states(cfg, w, ids, quant=None, remat=False):
    """ids [S] -> the final-norm hidden states [S, H], float32."""
    x = f32(w["embed"][ids])
    positions = jnp.arange(ids.shape[0])
    for n in range(cfg["num_hidden_layers"]):
        f = functools.partial(layer, cfg, w, f"layers.{n}.", quant=quant)
        if remat:
            f = jax.checkpoint(f)
        x = f(x, positions)
    return rms_norm(x, w["norm"], cfg["rms_norm_eps"])


def logits(w, hidden, quant=None):
    return mm(hidden, w["head"], quant)


def sequence_loss(cfg, w, ids, labels, quant=None):
    """Summed next-token cross-entropy of one sequence (labels given)."""
    lg = logits(w, hidden_states(cfg, w, ids, quant, remat=True), quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


def batch_loss(cfg, w, ids, labels, quant=None):
    """Mean cross-entropy over every token of ids [B, S], row by row."""
    row = jax.checkpoint(
        lambda w, i, l: sequence_loss(cfg, w, i, l, quant))

    def body(total, xs):
        return total + row(w, *xs), None

    total, _ = jax.lax.scan(body, jnp.float32(0), (ids, labels))
    return total / ids.size


# -- training: three steps of AdamW ----------------------------------------

def some_rows(x, every):
    """Every ``every``-th row of a matrix; a vector whole."""
    return x[::every] if x.ndim > 1 else x


def leaf_norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def clip_by_global_norm(grads, max_norm):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-12))
    return {k: g * scale for k, g in grads.items()}


def adamw(hyper, no_decay, t, w, m, v, grads):
    """One AdamW update (Loshchilov & Hutter, decoupled decay) on the
    already clipped gradient; t counts from 1."""
    b1, b2, eps = hyper["beta1"], hyper["beta2"], hyper["eps"]
    lr, wd = hyper["lr"], hyper["weight_decay"]
    new_w, new_m, new_v = {}, {}, {}
    for k in w:
        new_m[k] = b1 * m[k] + (1 - b1) * grads[k]
        new_v[k] = b2 * v[k] + (1 - b2) * jnp.square(grads[k])
        mhat = new_m[k] / (1 - b1 ** t)
        vhat = new_v[k] / (1 - b2 ** t)
        decay = 0.0 if no_decay(k) else wd
        new_w[k] = (w[k] * (1 - lr * decay)
                    - lr * mhat / (jnp.sqrt(vhat) + eps))
    return new_w, new_m, new_v


def train_steps(cfg, hyper, make_weights, batches, no_decay, quant=None,
                master_values=None, keep=None):
    """Follows ``len(batches)`` optimizer steps from the seeded weights.

    ``make_weights()`` gives the float32 starting weights (called
    twice: the start, and again for the change at the end, so that the
    start is not held through the steps).  Returns the loss of every
    step, the norm of the first (clipped) gradient by leaf, the norm of
    the parameters' change after the last step by leaf, and the raw
    first gradient's norm by leaf (which decides the leaves that count).
    With ``keep=n`` also host copies of every n-th row of that gradient
    and of that change, leaf by leaf (``grad_leaves``, ``change_leaves``),
    and their norms, for the numbers that compare directions.

    ``quant`` and ``master_values`` are the controls: matmuls on int8
    operands, and the master weights rounded to a narrower type's values
    after every update ("bfloat16": a step that keeps no float32 master).
    """
    with jax.enable_x64(False):
        grad_fn = jax.jit(jax.value_and_grad(
            lambda w, i, l: batch_loss(cfg, w, i, l, quant)))
        norms = jax.jit(lambda t: {k: leaf_norm(x) for k, x in t.items()})
        clip = jax.jit(
            lambda g: clip_by_global_norm(g, hyper["grad_clip_norm"]),
            donate_argnums=0)
        update = jax.jit(
            lambda t, w, m, v, g: adamw(hyper, no_decay, t, w, m, v, g),
            donate_argnums=(1, 2, 3, 4))
        change = jax.jit(lambda a, b: {k: a[k] - b[k] for k in a})
        rows = jax.jit(lambda t: {k: some_rows(x, keep) for k, x in t.items()})
        narrow = jax.jit(lambda t: {k: _through(x, master_values)
                                    for k, x in t.items()}, donate_argnums=0)

        w = make_weights()
        m = v = None
        losses, first_raw, first_clipped, out = [], None, None, {}
        for t, (ids, labels) in enumerate(batches, start=1):
            loss, grads = grad_fn(w, jnp.asarray(ids), jnp.asarray(labels))
            losses.append(float(loss))
            if t == 1:
                first_raw = host(norms(grads))
            grads = clip(grads)
            if t == 1:
                first_clipped = host(norms(grads))
                if keep:
                    out["grad_leaves"] = jax.device_get(rows(grads))
                m = jax.tree.map(jnp.zeros_like, grads)
                v = jax.tree.map(jnp.zeros_like, grads)
            w, m, v = update(jnp.float32(t), w, m, v, grads)
            del grads
            if master_values is not None:
                w = narrow(w)
        del m, v
        moved = change(w, make_weights())
        if keep:
            out["change_leaves"] = jax.device_get(rows(moved))
        out.update(losses=losses, grad_norms=first_clipped,
                   raw_grad_norms=first_raw, change_norms=host(norms(moved)))
    return out


def host(tree):
    """A dict of device scalars as Python floats."""
    return {k: float(x) for k, x in jax.device_get(tree).items()}


# -- serving: score served tokens -------------------------------------------

def make_scorer(cfg, pad_to, rows):
    """A jitted ``(w, ids [pad_to], first) -> logits [rows, V]`` for the
    positions first .. first + rows - 1: the whole padded sequence goes
    through the layers (causal, so the padding changes nothing before
    it), and only the wanted rows through the head.  Returns
    ``(scorer, quant_scorer)``: the float32 reference and the int8
    control, the same code."""
    def score(quant, w, ids, first):
        hid = hidden_states(cfg, w, ids, quant)
        picked = jax.lax.dynamic_slice_in_dim(hid, first, rows, axis=0)
        return logits(w, picked, quant)

    def build(quant):
        fn = jax.jit(functools.partial(score, quant))

        def call(w, ids, first):
            with jax.enable_x64(False):
                padded = np.zeros((pad_to,), np.int32)
                padded[: len(ids)] = ids
                return fn(w, jnp.asarray(padded), jnp.int32(first))
        return call

    return build(None), build("int8")

