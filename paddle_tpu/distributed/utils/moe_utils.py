"""Expert-parallel MoE token exchange.

Reference: ``python/paddle/distributed/utils/moe_utils.py:20`` (global_scatter)
and ``:153`` (global_gather) — imperative NCCL all-to-alls moving a ragged,
count-described token buffer between expert-parallel ranks; used by
``incubate/distributed/models/moe/moe_layer.py:263``.

TPU-native re-design: ragged count-based exchange is hostile to XLA (dynamic
shapes defeat MXU tiling), so the exchange is expressed over *fixed-capacity*
buffers.  Each source device builds ``[E, C, H]`` — its contribution to every
expert, C slots per (expert, source) — and one ``lax.all_to_all`` over the
'ep' mesh axis delivers ``[E_local, n*C, H]`` to each expert owner.  The
inverse all-to-all returns expert outputs to token owners.  Capacity C plays
the role of the reference's local_count/global_count bookkeeping; overflow
tokens are dropped exactly as the reference's capacity-clipped gates do.

These helpers are jax-level and must run inside a ``shard_map`` region whose
mesh binds ``axis_name`` (see ``MoELayer(dispatch_mode='alltoall')``).

Two dispatch implementations coexist (``PT_MOE_IMPL`` ∈ {auto, fused,
einsum}):

* ``einsum`` — the GShard mask-matmul formulation (Lepikhin et al.,
  2020): one-hot einsums over dense ``dispatch [T, E, C]`` and
  ``slot_mask [T, k, E, C]`` masks.  Simple, but the masks round-trip
  HBM and their contractions are almost entirely multiply-by-zero.
* ``fused`` — MegaBlocks-style (Gale et al., 2022) sort-based dispatch:
  stable-sort token slots by expert id (the same variadic ``lax.sort``
  trick topk uses for SPMD-friendliness), within-expert positions from
  the sorted offsets, capacity clip, and a direct ``take`` of tokens
  into ``[E, C, H]`` buckets — no ``[T, E, C]``-sized intermediate
  exists anywhere in the program.  The expert FFN then runs through the
  grouped GEMM kernel (``ops/pallas_kernels/grouped_gemm.py``) and the
  combine is a gather back to token order weighted by gate probs.

``auto`` takes the fused path on TPU when the hidden dim tiles to 128
lanes, einsum otherwise.  Both paths drop the same overflow tokens: the
stable sort preserves the flat ``(t, k)`` order within an expert, which
is exactly the order the einsum path's running cumsum counts.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp


def global_scatter(expert_in, axis_name, n):
    """Send per-expert token buffers to the experts' owner devices.

    expert_in: [E, C, H] — this device's contribution to every global expert
    (expert e lives on device ``e // (E//n)``).  Returns [E_local, n*C, H]:
    the local experts' inputs, slots grouped by source device.
    """
    E, C, H = expert_in.shape
    e_local = E // n
    x = expert_in.reshape(n, e_local, C, H)
    # After the exchange, leading axis indexes the *source* device.
    y = jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                           tiled=True)
    return y.transpose(1, 0, 2, 3).reshape(e_local, n * C, H)


def global_gather(expert_out, axis_name, n):
    """Inverse of :func:`global_scatter`.

    expert_out: [E_local, n*C, H] (local experts' outputs, slots grouped by
    source device).  Returns [E, C, H]: this device's slots filled with the
    outputs of every global expert.
    """
    e_local, nC, H = expert_out.shape
    C = nC // n
    x = expert_out.reshape(e_local, n, C, H).transpose(1, 0, 2, 3)
    y = jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                           tiled=True)
    return y.reshape(n * e_local, C, H)


def dispatch_masks(probs, idx, num_experts, capacity):
    """Capacity-clipped routing masks from gate decisions.

    probs: [T, E] gate probabilities; idx: [T, k] top-k expert ids.
    Returns (dispatch [T, E, C], slot_mask [T, k, E, C], keep [T, k]) —
    constant (stop-gradient) routing masks; gradients train the gate through
    the combine weights and the aux loss, as in the reference gates.
    """
    T, E = probs.shape
    assert E == num_experts, (E, num_experts)
    k = idx.shape[-1]
    C = capacity
    assign = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [T, k, E]
    assign_te = assign.reshape(T * k, E)
    pos_in_e = jnp.cumsum(assign_te, axis=0) - 1.0
    pos = jnp.sum(pos_in_e * assign_te, axis=-1).reshape(T, k)
    keep = pos < C
    pos = jnp.clip(pos, 0, C - 1).astype(jnp.int32)
    cap_onehot = jax.nn.one_hot(pos, C, dtype=jnp.float32)  # [T, k, C]
    assign_kept = assign * keep[..., None].astype(jnp.float32)
    dispatch = jnp.einsum("tke,tkc->tec", assign_kept, cap_onehot)
    slot_mask = jnp.einsum("tke,tkc->tkec", assign_kept, cap_onehot)
    dispatch = jax.lax.stop_gradient(dispatch)
    slot_mask = jax.lax.stop_gradient(slot_mask)
    return dispatch, slot_mask, jax.lax.stop_gradient(keep)


def resolve_moe_impl(hidden, impl=None):
    """'fused' or 'einsum' for this hidden width.  ``impl`` (or
    ``PT_MOE_IMPL``) ∈ {auto, fused, einsum}; auto = fused on TPU when
    the hidden dim tiles to 128 lanes (the grouped-GEMM/VMEM layout
    gate), einsum otherwise — CPU always resolves to einsum under auto
    so the measured-good default never changes off-TPU."""
    impl = (impl or os.environ.get("PT_MOE_IMPL", "auto")).lower()
    if impl not in ("auto", "fused", "einsum"):
        raise ValueError(
            f"PT_MOE_IMPL={impl!r}: expected auto|fused|einsum")
    if impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        return "fused" if (on_tpu and hidden % 128 == 0) else "einsum"
    return impl


def sort_dispatch(idx, num_experts, capacity):
    """Sort-based routing plan from top-k expert ids (no dense masks).

    idx: [T, k] top-k expert ids.  Returns a dict of stop-gradient
    index/mask arrays:

      src_tok [E*C] int32  token id filling each expert slot (0 if empty)
      filled  [E*C] bool   slot actually holds a token
      slot    [T, k] int32 expert slot of each (token, choice) (0 if
                           dropped — always mask with ``keep``)
      keep    [T, k] bool  choice survived the capacity clip

    Construction: flatten to ``[T*k]`` expert ids, stable variadic
    ``lax.sort`` carrying the flat position payload, within-expert
    position = sorted rank − first-occurrence offset (one
    ``searchsorted`` over the sorted ids — O(E log Tk), no [T*k, E]
    one-hot), capacity clip, then two O(T*k) scatters build the
    slot→token and (t, k)→slot maps.  Drop order matches
    :func:`dispatch_masks` exactly: the stable sort preserves flat
    (t, k) order within an expert — the order the einsum path's
    cumsum counts.
    """
    T, k = idx.shape
    E, C = num_experts, capacity
    tk = T * k
    e_flat = idx.reshape(tk).astype(jnp.int32)
    flat_pos = jnp.arange(tk, dtype=jnp.int32)
    se, sflat = jax.lax.sort((e_flat, flat_pos), dimension=0, num_keys=1,
                             is_stable=True)
    starts = jnp.searchsorted(se, jnp.arange(E, dtype=jnp.int32),
                              side="left").astype(jnp.int32)
    pos = flat_pos - starts[se]
    keep_s = pos < C
    slot_s = se * C + jnp.minimum(pos, C - 1)
    # Overflow entries scatter to index E*C, which mode='drop' discards.
    slot_write = jnp.where(keep_s, slot_s, E * C)
    src_tok = jnp.zeros([E * C], jnp.int32).at[slot_write].set(
        sflat // k, mode="drop")
    filled = jnp.zeros([E * C], jnp.bool_).at[slot_write].set(
        True, mode="drop")
    # Unsort: slot/keep in flat (t, k) order.
    slot_f = jnp.zeros([tk], jnp.int32).at[sflat].set(
        jnp.where(keep_s, slot_s, 0))
    keep_f = jnp.zeros([tk], jnp.bool_).at[sflat].set(keep_s)
    sg = jax.lax.stop_gradient
    return {"src_tok": sg(src_tok), "filled": sg(filled),
            "slot": sg(slot_f.reshape(T, k)),
            "keep": sg(keep_f.reshape(T, k))}


def fused_dispatch(tokens, plan, capacity):
    """Take tokens directly into [E, C, H] expert buckets (empty slots
    zeroed).  Differentiable w.r.t. tokens (gather; its transpose is
    the scatter-add the einsum path's mask contraction computes)."""
    H = tokens.shape[-1]
    picked = jnp.take(tokens, plan["src_tok"], axis=0)   # [E*C, H]
    picked = picked * plan["filled"][:, None].astype(tokens.dtype)
    return picked.reshape(-1, capacity, H)


def fused_combine(y, plan, gate_w):
    """Scatter-combine expert outputs back to token order, weighted by
    gate probs.  y: [E, C, H]; gate_w: [T, k] (already keep-masked, so
    a dropped choice contributes exactly 0 and routes no gradient)."""
    T, k = plan["slot"].shape
    y_flat = y.reshape(-1, y.shape[-1])                  # [E*C, H]
    picked = jnp.take(y_flat, plan["slot"].reshape(T * k),
                      axis=0).reshape(T, k, -1)          # [T, k, H]
    return jnp.einsum("tkh,tk->th", picked, gate_w.astype(y.dtype))


def _aux_loss(probs, idx, num_experts, kind, axis_name=None):
    """GShard/Switch load-balance loss: E * sum_e(me * ce)."""
    if kind == "naive":
        return jnp.zeros([], jnp.float32)
    p32 = probs.astype(jnp.float32)
    top1 = idx[:, 0]
    me = jnp.mean(p32, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top1, num_experts, dtype=jnp.float32),
                  axis=0)
    if axis_name is not None:
        me = jax.lax.pmean(me, axis_name)
        ce = jax.lax.pmean(ce, axis_name)
    return jnp.sum(me * ce) * num_experts


def ep_moe_local(tokens, wg, w1, b1, w2, b2, *, axis_name, n, num_experts,
                 top_k, capacity, activation, gate_kind, impl=None):
    """Per-device EP MoE body (runs inside shard_map over ``axis_name``;
    ``axis_name=None`` runs the same body single-device — the dense
    MoELayer path uses it that way).

    tokens: [T_local, H]; wg: [H, E] replicated gate; w1/b1/w2/b2: this
    device's expert slice ([E_local, H, F] etc).  Returns (out [T_local, H],
    aux_loss scalar).  ``impl`` overrides PT_MOE_IMPL for this call.
    """
    E = num_experts
    logits = tokens.astype(jnp.float32) @ wg.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(probs, top_k)
    aux = _aux_loss(probs, idx, E, gate_kind, axis_name)

    impl = resolve_moe_impl(tokens.shape[-1], impl)
    cdt = tokens.dtype
    if impl == "fused":
        plan = sort_dispatch(idx, E, capacity)
        keep = plan["keep"]
        expert_in = fused_dispatch(tokens, plan, capacity)  # [E, C, H]
    else:
        dispatch, slot_mask, keep = dispatch_masks(probs, idx, E, capacity)
        expert_in = jnp.einsum("tec,th->ech", dispatch.astype(cdt), tokens)

    gate_w = jnp.take_along_axis(probs, idx, axis=-1)  # [T, k]
    if top_k > 1:
        denom = jnp.clip(jnp.sum(gate_w, axis=-1, keepdims=True), 1e-9)
        gate_w = gate_w / denom
    gate_w = gate_w * keep.astype(gate_w.dtype)

    if axis_name is not None:
        xin = global_scatter(expert_in, axis_name, n)  # [E_local, n*C, H]
    else:
        xin = expert_in
    if impl == "fused":
        from ...ops.pallas_kernels.grouped_gemm import grouped_ffn

        y_local = grouped_ffn(xin, w1, b1, w2, b2, activation)
    else:
        if activation == "gelu":
            # Match ops.gelu (exact erf form), not jax.nn.gelu's tanh
            # default.
            def act(v):
                return jax.nn.gelu(v, approximate=False)
        else:
            act = getattr(jax.nn, activation)
        h = act(jnp.einsum("ech,ehf->ecf", xin, w1) + b1)
        y_local = jnp.einsum("ecf,efh->ech", h, w2) + b2
    if axis_name is not None:
        y = global_gather(y_local, axis_name, n)  # [E, C, H]
    else:
        y = y_local
    if impl == "fused":
        out = fused_combine(y, plan, gate_w)
    else:
        slot_out = jnp.einsum("ech,tkec->tkh", y, slot_mask.astype(cdt))
        out = jnp.einsum("tkh,tk->th", slot_out, gate_w.astype(cdt))
    return out, aux
