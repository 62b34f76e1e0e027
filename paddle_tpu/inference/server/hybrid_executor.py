"""HybridExecutor — the execution backend for a model whose layers are of
more than one kind: state-space (Mamba-2) mixers between a few attention
layers (``models/granite_hybrid.py``).

It stands where :class:`~.executor.PagedExecutor` stands and gives the
scheduler the same slot-granular operations (``alloc_slot`` /
``free_slot`` / ``prepare_write`` / ``prefill`` / ``prefill_chunk`` /
``decode``), so the scheduler, admission, chunked prefill and preemption
are the ones every model gets.  What differs is the state behind a slot:

- the :class:`~..paged.PagedKVCache` holds pages for the ATTENTION layers
  only (4 of 40 in granite-4.0-h-micro), written by the cache's own
  donated ``serve.kv_write``;
- a :class:`~..state_cache.RecurrentStateCache` holds one fixed-size row
  per slot for every state-space layer.

The layer kinds are composed, not copied: the mixers and the MLP are the
functions of ``models/granite_hybrid.py``, used by both programs here.
Consecutive state-space layers form a RUN whose parameters are stacked
once at build, and a run is one ``lax.scan`` (5 runs and 4 attention
layers compile, not 40 layers); an attention layer between two runs is
inlined and aliases the model's own arrays.

**What is held, decided at build.**  Both caches are allocated when the
executor is built, before any run is stacked, so sizes the device cannot
hold are refused at ``ServingEngine(...)``.  The stacked runs are a
second copy of the recurrent layers (5.5 of granite-4.0-h-micro's
6.4 GB).  Where the device reports its memory and cannot hold that copy
beside the model's own, the executor TAKES THE RECURRENT LAYERS OVER:
each run's eager arrays are deleted as the run is stacked
(``took_over_weights``); the model can then neither run eagerly nor
build a second engine, and says so.  Where both copies fit (and on the
CPU, which reports nothing) the model is left whole.

Two programs:

``serve.hybrid_chunk`` — one prefill chunk of one sequence, keyed on the
  chunk's length and the past's page cover.  It reads the slot's rows
  from the (undonated) state pools in-graph — zeros when ``start == 0``,
  so no reset is ever dispatched — runs the recurrence in its chunked
  form, one block per chunk, and returns the chunk's K/V for the page
  writer and the rows after the chunk for the state cache's one donated
  write.

``serve.hybrid_decode`` — one token for every live slot.  ONE program
  whatever the batch: it runs over all ``max_seqs`` slots under a live
  mask (a program per batch size, as the Llama executor compiles, is 64
  compilations of 40 layers here).  The KV pools and the state pools are
  donated; the state update is the Pallas kernel
  ``ops/pallas_kernels/ssm_decode.py`` on the pool carried through the
  scan; a slot that is not live keeps its state bit for bit and writes
  no page.

**The KV pool at a head size under 128.**  A TPU tile is 128 lanes wide;
a pool whose last dimension is a head of 64 is padded to twice its size
in HBM and re-laid for every gather.  So the cache is built with ``fold``
KV heads side by side in its last dimension (``[A, KV / fold, pages,
page_size, fold * D]``, ``fold * D <= 128``): every page is whole tiles,
the same ``PagedKVCache`` — its page table, ``write_at``,
``gather_dense`` — serves unchanged, and the pool is one the fused
paged-decode kernel reads as it stands: ``KV / fold`` rows of
``fold * D`` lanes.  The decode step attends through
:func:`~..paged.paged_decode_attention` over that pool in place, by the
sequences' lengths (:func:`_folded_attention`): a query head is laid over
its whole folded row, zero outside its own head's lanes, so the kernel's
two products contract or produce whole rows (twice the arithmetic of a
64-wide head, the same bytes; decode attention is bound by bytes), and
the output keeps each head's own lanes.
"""
from __future__ import annotations

import functools
import itertools

import numpy as np

import jax
import jax.numpy as jnp

from ... import obs
from ...analysis import CountedJit
from ...models import granite_hybrid as gh
from ...models import moe
from ...ops.pallas_kernels import ssm_decode as _ssm
from ...ops.pallas_kernels.paged_decode import block_pages
from ..paged import (
    PagedKVCache, _flat, _put_token, paged_decode_attention,
)
from ..state_cache import RecurrentStateCache
from .handoff import Handoff

_F32 = jnp.float32
#: device memory left for the programs' temporaries when the executor
#: decides what fits (the decode program's are 0.14 GB at the benchmark's
#: size, a chunk's under 0.5 GB)
_HEADROOM = 1 << 30


def _free_device_bytes():
    """Bytes the default device can still give, or None where it does
    not say (the CPU)."""
    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats["bytes_in_use"])


def _fold_factor(n_kv_heads, head_dim, lanes=128):
    """KV heads side by side in the pool's last dimension: the most that
    divide the KV heads and fit the lanes."""
    return max([f for f in range(1, n_kv_heads + 1)
                if n_kv_heads % f == 0 and f * head_dim <= lanes] or [1])


def _folded_attention(q, k_pages, v_pages, layer, lengths, tables, fold):
    """Decode attention of one layer over the folded pools ``[A, KV /
    fold, pages, page_size, fold * D]``, in place.  q [S, heads, D],
    already scaled (the scores are taken as they come: scale 1); lengths
    [S] keys each sequence reads, 0 for a slot that is not live (the
    kernel reads nothing for it); tables [S, pages per sequence].
    Returns [S, heads * D].

    A KV row holds ``fold`` heads.  A query head's row is laid over the
    whole row, zero outside its own head's lanes, which makes this a
    call of :func:`~..paged.paged_decode_attention` with ``KV / fold`` KV
    heads of ``fold * D`` lanes and ``fold`` times the query rows a KV
    head; of each output row the head's own lanes are kept."""
    S, nh, D = q.shape
    KVf, W = k_pages.shape[1], k_pages.shape[4]
    g = nh // (KVf * fold)
    own = jnp.eye(fold, dtype=q.dtype)[None, None, :, None, :, None]
    qw = (q.reshape(S, KVf, fold, g, 1, D) * own).reshape(S, nh, W)
    o = paged_decode_attention(qw.astype(k_pages.dtype), k_pages, v_pages,
                               lengths, tables, layer=layer, scale=1.0)
    return (o.reshape(S, KVf, fold, g, fold, D) * own).sum(axis=4) \
        .reshape(S, nh * D)


@jax.jit
def _stack_layers(layers):
    """[{name: array}] of one run's layers -> {name: [layers, ...]}."""
    return {name: jnp.stack([lp[name] for lp in layers])
            for name in layers[0]}


@functools.partial(jax.jit, static_argnums=3)
def _read_slot(ssm, conv, slot, d_head):
    """One slot's rows of every pool, unpacked, layers in order (one
    program whatever the slot)."""
    def row(pool, axis):
        return jax.lax.dynamic_index_in_dim(pool, slot, axis, keepdims=False)

    return (jnp.concatenate([_ssm.unpack_state(row(p, 1), d_head)
                             for p in ssm]),
            jnp.concatenate([row(p, 2) for p in conv]))


class SlotExecutor:
    """What the scheduler and the engine's status page ask of an executor
    whose programs are composed from a model's layer kinds, whatever the
    kinds: the slot-granular control plane over ``self.cache`` (a
    :class:`~..paged.PagedKVCache`).  :class:`HybridExecutor` and
    ``latent_executor.LatentExecutor`` add their state and their two
    programs."""

    #: what the engine's status page reads of every executor
    quant = "none"
    sp_degree = 1
    sp_prefill_tokens = 0
    _sp_axis = None
    aot_ladder = None
    #: layers of routed experts (an executor of such a model counts its own)
    n_expert_layers = 0

    def sp_min_tokens_effective(self) -> int:
        return 0

    @property
    def free_slots(self) -> int:
        return self.cache.free_slots

    @property
    def free_pages(self) -> int:
        return self.cache.free_pages

    def pages_for(self, tokens: int) -> int:
        return -(-int(tokens) // self.cache.page_size)

    def prepare_write(self, sid: int, start: int, n_tokens: int) -> None:
        self.cache._ensure_capacity(sid, start + n_tokens)

    def prefill(self, sid: int, prompt_ids) -> int:
        """A whole prompt is a chunk that starts at 0 and is final."""
        return self.prefill_chunk(sid, prompt_ids, 0, True)

    def grouped_expert_layers(self, tokens: int) -> int:
        """The expert layers a chunk of ``tokens`` tokens runs as ONE
        grouped product over rows sorted by expert (``models/moe.py``):
        every expert layer of the model or, for a short chunk or a model
        without routed experts, none.  ``Scheduler._prefill`` puts it on
        the chunk's ``req.prefill`` span."""
        return self.n_expert_layers if moe.grouped(tokens) else 0

    def _count_experts(self, counts):
        """One decode step's expert counter (int32 ``[expert layers,
        held]``, what the decode program of a model with routed experts
        returns beside its tokens) into the executor's running sums
        ``expert_rows``, ``expert_steps``, ``experts_hit`` and
        ``expert_max_over_mean``, and the step's ``moe.load`` instant."""
        if not counts.size:
            return
        self.expert_rows += counts
        self.expert_steps += 1
        hit = int((counts > 0).sum())
        self.experts_hit += hit
        mean = counts.mean(axis=1)
        ratio = float(np.mean(counts.max(axis=1) / np.maximum(mean, 1e-9)))
        self.expert_max_over_mean += ratio
        obs.instant("moe.load", cat="serve", max=int(counts.max()),
                    mean=float(counts.mean()), hit=hit)


class HybridExecutor(SlotExecutor):
    def __init__(self, model, max_seqs=4, page_size=16, max_len=256,
                 dtype=jnp.float32, num_pages=None):
        cfg = model.config
        self.config = cfg
        self.max_len = int(max_len)
        state = {k: v._data for k, v in model.state_dict().items()}
        if any(a.is_deleted() for a in state.values()):
            raise ValueError(
                "HybridExecutor: this model's recurrent layers were handed "
                "over to an engine built from it before (the device could "
                "not hold two copies); build the model again")

        # runs of one kind, in order: ("mamba", n) is a scanned run of n
        # stacked layers, ("attention", 1) one inlined layer
        self.segments, starts = [], []
        for kind, group in itertools.groupby(cfg.layer_types):
            n, at = len(list(group)), sum(m for _, m in self.segments)
            self.segments += [(kind, n)] if kind == "mamba" else \
                [(kind, 1)] * n
            starts += [at] if kind == "mamba" else range(at, at + n)

        def layer(i, kind):
            return {name: state[f"model.layers.{i}.{name}"]
                    for name in gh.layer_param_names(kind)}

        # -- what the device has to hold, refused here if it cannot -------
        self.kv_fold = _fold_factor(cfg.num_key_value_heads, cfg.head_dim)
        pages_per_seq = -(-self.max_len // page_size)
        num_pages = (max_seqs * pages_per_seq if num_pages is None
                     else int(num_pages))
        n_attention = cfg.layer_types.count("attention")
        state_args = dict(
            runs=[n for kind, n in self.segments if kind == "mamba"],
            max_seqs=max_seqs,
            ssm_shape=_ssm.state_shape(cfg.mamba_n_heads, cfg.mamba_d_head,
                                       cfg.mamba_d_state),
            conv_shape=(cfg.mamba_d_conv - 1, cfg.mamba_conv_dim),
            dtype=dtype)
        pools = (RecurrentStateCache.bytes_for(**state_args)
                 + 2 * n_attention * cfg.num_key_value_heads * cfg.head_dim
                 * num_pages * page_size * jnp.dtype(dtype).itemsize)
        stacks = sum(a.nbytes for (kind, n), at in zip(self.segments, starts)
                     if kind == "mamba" for i in range(at, at + n)
                     for a in layer(i, kind).values())
        free = _free_device_bytes()
        if free is not None and pools + _HEADROOM > free:
            raise ValueError(
                f"HybridExecutor: {pools / 1e9:.2f} GB of recurrent state "
                f"and KV pages for max_seqs={max_seqs}, max_len="
                f"{self.max_len} do not fit the {free / 1e9:.2f} GB the "
                f"device has free beside the model")
        #: the device cannot hold the model's own copy of the recurrent
        #: layers beside the stacked one: each run's eager arrays are
        #: deleted as the run is stacked (the model cannot run eagerly
        #: or build another engine afterwards; attention layers,
        #: embedding and norm are aliased, never copied)
        self.took_over_weights = (free is not None
                                  and pools + stacks + _HEADROOM > free)
        self.cache = PagedKVCache(
            n_layers=n_attention,
            n_kv_heads=cfg.num_key_value_heads // self.kv_fold,
            head_dim=cfg.head_dim * self.kv_fold, num_pages=num_pages,
            page_size=page_size, max_seqs=max_seqs, dtype=dtype,
            max_pages_per_seq=pages_per_seq)
        self.state = RecurrentStateCache(**state_args)
        #: keys in a block of the decode kernel's loop (``exec.prep``)
        self._decode_block = page_size * block_pages(
            page_size, cfg.num_key_value_heads // self.kv_fold,
            cfg.head_dim * self.kv_fold, jnp.dtype(dtype).itemsize)

        # -- the parameters: a run is stacked, one run at a time ----------
        self.params = []
        for (kind, n), at in zip(self.segments, starts):
            if kind == "attention":
                self.params.append(layer(at, kind))
                continue
            layers = [layer(i, kind) for i in range(at, at + n)]
            # one program a run length (not one eager stack a leaf)
            self.params.append(jax.block_until_ready(_stack_layers(layers)))
            if self.took_over_weights:
                for lp in layers:
                    for a in lp.values():
                        a.delete()
            del layers
        self.params = tuple(self.params)
        self.tops = {"embed": state["model.embed_tokens.weight"],
                     "norm_w": state["model.norm.weight"]}
        del state
        #: the past of a prompt's first chunk: no key, no value
        self._no_past = jnp.zeros(
            self.cache.k_pages.shape[:2] + (0, self.cache.k_pages.shape[4]),
            dtype)
        self.last_token = {}
        self.handoff = Handoff()
        #: (sid, n_tokens) per prefill dispatch, as PagedExecutor keeps
        self.prefill_events = []
        self._jit_chunk = CountedJit(self._chunk_fwd,
                                     name="serve.hybrid_chunk")
        self._jit_decode = CountedJit(self._decode_fwd,
                                      name="serve.hybrid_decode",
                                      donate_argnums=(5, 6, 8, 9))

    @property
    def programs(self) -> dict:
        return {"hybrid_chunk": self._jit_chunk,
                "hybrid_decode": self._jit_decode,
                "kv_write": self.cache.writer,
                "state_write": self.state.writer}

    # -- pure forwards -------------------------------------------------------

    def _chunk_fwd(self, params, tops, ids, start, past_k, past_v, slot,
                   ssm_pools, conv_pools):
        """ids [C] at positions ``start .. start + C - 1`` of the sequence
        in ``slot``; past_k / past_v [A, KV / fold, P, fold * D], the
        attention layers' already-written keys and values gathered dense
        (positions >= start masked).  Returns (the greedy token after the
        chunk's last position, chunk k and v [A, KV / fold, C, fold * D],
        and per run the slot's state rows after the chunk)."""
        cfg = self.config
        C, P = ids.shape[0], past_k.shape[2]
        x = tops["embed"][ids] * cfg.embedding_multiplier
        fresh = start == 0
        mask = jnp.concatenate(
            [jnp.broadcast_to((jnp.arange(P) < start)[None], (C, P)),
             jnp.tril(jnp.ones((C, C), bool))], axis=1)

        width = cfg.head_dim * self.kv_fold

        def unfold(past):
            """[KV / fold, P, fold * D] -> [P, KV, D]."""
            return jnp.swapaxes(past, 0, 1).reshape(
                P, cfg.num_key_value_heads, cfg.head_dim)

        def mamba_layer(x, layer):
            lp, S0, tail = layer
            out, tail, S = gh.mamba_mixer(
                cfg, lp, gh.mixer_input(cfg, lp, x), tail,
                _ssm.unpack_state(S0, cfg.mamba_d_head))
            x = gh.mlp_residual(cfg, lp, x + cfg.residual_multiplier * out)
            return x, (_ssm.pack_state(S), tail)

        ks, vs, new_ssm, new_conv = [], [], [], []
        for (kind, _), lp in zip(self.segments, params):
            if kind == "mamba":
                r = len(new_ssm)
                S0 = jax.lax.dynamic_index_in_dim(ssm_pools[r], slot, 1,
                                                  keepdims=False)
                tail = jax.lax.dynamic_index_in_dim(conv_pools[r], slot, 2,
                                                    keepdims=False)
                x, (S, tail) = jax.lax.scan(
                    mamba_layer, x,
                    (lp, jnp.where(fresh, 0.0, S0),
                     jnp.where(fresh, 0, tail).astype(tail.dtype)))
                new_ssm.append(S)
                new_conv.append(tail)
                continue
            a = len(ks)
            q, k, v = gh.attention_qkv(cfg, lp, gh.mixer_input(cfg, lp, x))
            kf = jnp.concatenate([unfold(past_k[a]).astype(k.dtype), k], 0)
            vf = jnp.concatenate([unfold(past_v[a]).astype(v.dtype), v], 0)
            o = gh.attend(q, kf, vf, mask) @ lp["self_attn.o_proj.weight"]
            x = gh.mlp_residual(cfg, lp, x + cfg.residual_multiplier * o)
            ks.append(jnp.swapaxes(k.reshape(C, -1, width), 0, 1))
            vs.append(jnp.swapaxes(v.reshape(C, -1, width), 0, 1))
        logits = gh.head(cfg, tops["embed"], tops["norm_w"], x[-1])
        return (jnp.argmax(logits).astype(jnp.int32), jnp.stack(ks),
                jnp.stack(vs), tuple(new_ssm), tuple(new_conv))

    def _decode_fwd(self, params, tops, ids, positions, live, k_pages,
                    v_pages, tables, ssm_pools, conv_pools):
        """One token for every slot: ids, positions [S] (the token's
        position), live [S] bool, tables [S, pages per sequence].  A slot
        that is not live computes on whatever it holds and writes
        nothing.  Returns (tokens [S], and the four pools)."""
        cfg = self.config
        S = ids.shape[0]
        ps, num_pages = self.cache.page_size, self.cache.num_pages
        nh, p = cfg.mamba_n_heads, cfg.mamba_d_head
        rows = self.state.ssm_shape[::2]
        x = tops["embed"][ids] * cfg.embedding_multiplier      # [S, H]
        pids = jnp.where(live, tables[jnp.arange(S), positions // ps],
                         num_pages)
        offs = positions % ps
        lengths = jnp.where(live, positions + 1, 0)
        width = cfg.head_dim * self.kv_fold

        def mamba_layer(carry, layer):
            x, ssm, conv = carry
            lp, i = layer
            z, xbc, dt_raw = gh.mamba_project(
                cfg, lp, gh.mixer_input(cfg, lp, x))
            tail = jax.lax.dynamic_index_in_dim(conv, i, 0, keepdims=False)
            window = jnp.concatenate(
                [tail.astype(_F32), xbc.astype(_F32)[None]], axis=0)
            w = lp["mamba.conv1d.weight"].astype(_F32)
            conv_out = jax.nn.silu(
                lp["mamba.conv1d.bias"].astype(_F32)
                + jnp.sum(w[:, None, :] * window, axis=0))
            conv = jax.lax.dynamic_update_index_in_dim(
                conv, jnp.where(live[None, :, None],
                                window[1:].astype(conv.dtype), tail), i, 0)
            xs, B, C, dt = gh.mamba_inputs(cfg, lp, conv_out, dt_raw)
            A = -jnp.exp(lp["mamba.A_log"].astype(_F32))
            y, ssm = _ssm.ssm_decode(
                ssm, i, _ssm.head_rows(jnp.exp(dt * A[None]), p, rows),
                (xs * dt[:, :, None]).reshape(S, *rows), B, C, live)
            y = y.reshape(S, nh, p) + lp["mamba.D"].astype(_F32)[
                None, :, None] * xs
            out = gh.gated_norm_out(cfg, lp, y.reshape(S, nh * p), z)
            x = gh.mlp_residual(cfg, lp, x + cfg.residual_multiplier * out)
            return (x, ssm, conv), None

        ssm_pools, conv_pools = list(ssm_pools), list(conv_pools)
        pool_shape = k_pages.shape
        kf, vf = _flat(k_pages), _flat(v_pages)
        r = a = 0
        for (kind, n), lp in zip(self.segments, params):
            if kind == "mamba":
                (x, ssm_pools[r], conv_pools[r]), _ = jax.lax.scan(
                    mamba_layer, (x, ssm_pools[r], conv_pools[r]),
                    (lp, jnp.arange(n, dtype=jnp.int32)))
                r += 1
                continue
            q, k, v = gh.attention_qkv(cfg, lp, gh.mixer_input(cfg, lp, x))
            kf = _put_token(kf, pool_shape, a, pids, offs,
                            k.reshape(S, -1, width))
            vf = _put_token(vf, pool_shape, a, pids, offs,
                            v.reshape(S, -1, width))
            o = _folded_attention(q, kf.reshape(pool_shape),
                                  vf.reshape(pool_shape), a, lengths, tables,
                                  self.kv_fold)
            o = o.astype(x.dtype) @ lp["self_attn.o_proj.weight"]
            x = gh.mlp_residual(cfg, lp, x + cfg.residual_multiplier * o)
            a += 1
        logits = gh.head(cfg, tops["embed"], tops["norm_w"], x)
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                kf.reshape(pool_shape), vf.reshape(pool_shape),
                tuple(ssm_pools), tuple(conv_pools))

    # -- slot-granular control plane ----------------------------------------

    @property
    def state_bytes(self) -> int:
        return self.state.nbytes

    @property
    def state_slots_used(self) -> int:
        return self.state.slots_used

    def slot_state(self, sid: int):
        """One slot's recurrent state as the model's equations have it:
        (SSM state ``[recurrent layers, heads, P, N]`` float32, conv tail
        ``[recurrent layers, K - 1, conv_dim]``), copies on the device,
        layers in the model's order.  What a snapshot would hold."""
        return _read_slot(*self.state.pools(), np.int32(sid),
                          self.config.mamba_d_head)

    def alloc_slot(self) -> int:
        sid = self.cache.allocate()
        self.state.allocate(sid)
        return sid

    def free_slot(self, sid: int) -> None:
        """Pages back to the pool; the state row is simply dropped (the
        slot's next owner starts from zero, a resumed request
        recomputes)."""
        self.cache.free(sid)
        self.state.free(sid)
        self.last_token.pop(sid, None)

    def prefill_chunk(self, sid: int, chunk_ids, start: int,
                      final: bool) -> int | None:
        """One prefill chunk at position ``start``: attends the slot's
        written pages, carries the slot's recurrent state.  When
        ``final``, records and returns the first greedy token."""
        cache = self.cache
        if start:
            with obs.span("kv.gather", cat="serve", past_tokens=start):
                past_k, past_v = cache.gather_dense(sid, start)
        else:
            past_k = past_v = self._no_past
        with self.handoff.prep(tokens=len(chunk_ids)) as io:
            ids = io.put(np.asarray(chunk_ids), jnp.int32)
            at, slot = np.int32(start), np.int32(sid)
            io.host(at, slot)
        self.prefill_events.append((sid, int(ids.shape[0])))
        with obs.span("state.read", cat="serve", slot=int(sid),
                      start=int(start)):
            ssm, conv = self.state.pools()
        tok, k, v, new_ssm, new_conv = self._jit_chunk(
            self.params, self.tops, ids, at, past_k, past_v, slot, ssm,
            conv)
        del ssm, conv
        cache.write_at(sid, k, v, start)
        self.state.write(sid, new_ssm, new_conv, int(ids.shape[0]))
        if not final:
            return None
        tok = int(io.fetch("prefill_chunk", tok))
        self.last_token[sid] = tok
        return tok

    def decode(self, sids) -> dict:
        """One greedy token for each listed slot; returns {sid: token}."""
        sids = list(sids)
        if not sids:
            return {}
        cache = self.cache
        # how much of the window the fused kernel's block loop visits:
        # the blocks that hold one of the lengths + 1 keys a sequence
        # reads, in every attention layer, of the blocks of every window
        block = self._decode_block
        with self.handoff.prep(
                batch=len(sids),
                blocks=int(cache.n_layers * (
                    cache.lengths[sids] // block + 1).sum()),
                window_blocks=cache.n_layers * len(sids) * -(
                    -cache.max_pages_per_seq * cache.page_size
                    // block)) as io:
            cache.reserve(sids, extra_tokens=1)
            n = cache.max_seqs
            ids = np.zeros((n,), np.int32)
            positions = np.zeros((n,), np.int32)
            live = np.zeros((n,), bool)
            ids[sids] = [self.last_token[s] for s in sids]
            positions[sids] = cache.lengths[sids]
            live[sids] = True
            tables = np.maximum(cache.page_table, 0)
            io.host(ids, positions, live, tables)
            kp, vp = cache.pools()
            ssm, conv = self.state.pools()
        toks, kp, vp, ssm, conv = self._jit_decode(
            self.params, self.tops, ids, positions, live, kp, vp, tables,
            ssm, conv)
        cache.set_pools(kp, vp)
        self.state.set_pools(ssm, conv)
        cache.lengths[sids] += 1
        toks = io.fetch("decode", toks)
        out = {}
        for s in sids:
            out[s] = self.last_token[s] = int(toks[s])
        return out
