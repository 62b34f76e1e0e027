"""WindowExecutor — the execution backend for a model that mixes
sliding-window and full attention layers and routes its feed-forward
over experts (``models/window_moe.py``).

A sibling of :class:`~.latent_executor.LatentExecutor` under the same
:class:`~.hybrid_executor.SlotExecutor` control plane: the scheduler,
admission, chunked prefill and preemption by recompute are the ones every
model gets, and the programs are COMPOSED from the model's layer kinds,
whose parts are the functions of ``models/window_moe.py`` and
``models/moe.py``.  Every layer is inlined and aliases the model's own
arrays: nothing is stacked, so the build holds no second copy of a weight
(a whole model of 32 layers would want its periods under a scan; ROADMAP
D14).

**What is held.**  One :class:`~..paged.PagedKVCache` of TWO layer groups
(:class:`~..paged.LayerGroup`): the full layers' pool grows with a
sequence's length; the sliding layers' pool keeps, per sequence, the
pages that hold a key the next query can still see plus what a chunk
writes — ``ceil((window + prefill_chunk) / page_size) + 1`` pages a
sequence whatever its length — and releases what falls wholly behind the
window after every chunk and every decode step.  Both pools are
allocated after the model's arrays exist and sizes the device cannot
hold are refused at ``ServingEngine(...)``.

**Admission** keeps the scheduler's scalar interface, because one
conservative number serves each pool.  ``pages_for`` / ``free_pages``
speak of the FULL group, the only pool whose demand grows with a
request's length.  The window group's demand is bounded per sequence, so
it bounds ``free_slots`` instead (:attr:`~..paged.PagedKVCache.free_slots`:
its pool seats ``num_pages // pages_per_seq`` sequences): a request is
admitted only if the full group can take its target length AND the window
group its bounded span, and an admitted request never finds the window
pool exhausted.

Two programs:

``serve.window_chunk`` — one prefill chunk of one sequence, keyed on the
  chunk's length and the full group's past pages (the window group's past
  follows from the start).  It reads the past out of the (undonated)
  pools inside the program, by page id (:func:`~..paged._past_of`): all
  of it on a full layer, the pages that hold the last ``window`` tokens
  on a sliding one, under the mask ``visible(i, j)`` of the model; stock
  XLA attention, a few heads at a time.  Returns the chunk's K and V by
  group for the cache's ONE donated ``serve.kv_write``.

``serve.window_decode`` — one token for every slot.  ONE program whatever
  the batch, over all ``max_seqs`` slots under a live mask; both groups'
  pools are donated and carried through the layers; the token goes in by
  :func:`~..paged._put_token` and the attention is
  ``ops/pallas_kernels/paged_decode.py`` over the pool in place, which on
  a sliding layer starts at the first visible key's block through a
  table whose first entry stands for the group's ``base``.  Beside the
  tokens it returns, for the same blocking read, how many rows each held
  expert took in each expert layer (int32 ``[expert layers, held]``, live
  slots only).
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ...analysis import CountedJit
from ...models import window_moe as wm
from ...ops.pallas_kernels import paged_decode as _fused
from ..paged import (LayerGroup, PagedKVCache, _flat, _past_of, _put_token,
                     paged_decode_attention)
from .handoff import Handoff
from .hybrid_executor import _HEADROOM, SlotExecutor, _free_device_bytes
from .latent_executor import _head_block

FULL, WINDOW = 0, 1         # the cache's layer groups, in this order


class WindowExecutor(SlotExecutor):
    def __init__(self, model, max_seqs=4, page_size=16, max_len=256,
                 dtype=jnp.float32, num_pages=None, prefill_chunk=None):
        cfg = model.config
        self.config, self.held = cfg, tuple(model.held_experts)
        self.max_len = int(max_len)
        kinds = cfg.layer_types
        if wm.FULL not in kinds:
            raise NotImplementedError(
                "WindowExecutor: a model without a full-attention layer "
                "(admission speaks of the full group's pool)")
        #: layer -> (its group, its place in the group's pool)
        self.slot_of = [(g, kinds[:n].count(kinds[n]))
                        for n, g in enumerate(
                            FULL if k == wm.FULL else WINDOW for k in kinds)]
        state = {k: v._data for k, v in model.state_dict().items()}
        self.params = tuple(
            {name: state[f"model.layers.{n}.{name}"]
             for name in wm.layer_param_names(cfg.is_dense(n))}
            for n in range(cfg.num_hidden_layers))
        self.tops = {"embed": state["model.embed_tokens.weight"],
                     "norm_w": state["model.norm.weight"],
                     "lm_head": state["lm_head.weight"]}
        del state
        self.n_expert_layers = cfg.num_hidden_layers - cfg.num_dense_layers

        # -- what the device has to hold, refused here if it cannot -------
        pages_per_seq = -(-self.max_len // page_size)
        num_pages = (max_seqs * pages_per_seq if num_pages is None
                     else int(num_pages))
        # a window row: the window and the longest span written at once,
        # plus a page of misalignment; never more than a whole sequence
        span = self.max_len if prefill_chunk is None else int(prefill_chunk)
        window_row = min(-(-(cfg.sliding_window + span) // page_size) + 1,
                         pages_per_seq)
        groups = [LayerGroup(kinds.count(wm.FULL), num_pages, None,
                             pages_per_seq),
                  LayerGroup(kinds.count(wm.SLIDING), max_seqs * window_row,
                             cfg.sliding_window, window_row)]
        page = (2 * cfg.num_key_value_heads * page_size * cfg.head_dim
                * jnp.dtype(dtype).itemsize)
        pools = sum(g.n_layers * g.num_pages for g in groups) * page
        free = _free_device_bytes()
        if free is not None and pools + _HEADROOM > free:
            raise ValueError(
                f"WindowExecutor: {pools / 1e9:.2f} GB of KV pages for "
                f"max_seqs={max_seqs}, max_len={self.max_len} do not fit "
                f"the {free / 1e9:.2f} GB the device has free beside the "
                f"model")
        self.cache = PagedKVCache(
            n_layers=cfg.num_hidden_layers,
            n_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            num_pages=num_pages, page_size=page_size, max_seqs=max_seqs,
            dtype=dtype, groups=groups)
        #: keys in a block of the decode kernel's loop (``exec.prep``)
        self._decode_block = page_size * _fused.block_pages(
            page_size, cfg.num_key_value_heads, cfg.head_dim,
            jnp.dtype(dtype).itemsize)
        self.last_token = {}
        self.handoff = Handoff()
        #: (sid, n_tokens, start) per prefill dispatch (PagedExecutor keeps
        #: the first two; the start says which keys a window layer saw)
        self.prefill_events = []
        #: running sums of the decode program's expert counter, as
        #: LatentExecutor keeps them
        self.expert_rows = np.zeros((self.n_expert_layers, len(self.held)),
                                    np.int64)
        self.expert_steps = 0
        self.experts_hit = 0
        self.expert_max_over_mean = 0.0
        #: by group: the pages in use after each chunk and decode step,
        #: summed (``page_samples`` of them), and the pages released
        #: behind the window: running sums, read at a window's two ends
        self.pages_used = [0, 0]
        self.page_samples = 0
        self._jit_chunk = CountedJit(self._chunk_fwd,
                                     name="serve.window_chunk")
        self._jit_decode = CountedJit(self._decode_fwd,
                                      name="serve.window_decode",
                                      donate_argnums=(5, 6))

    @property
    def programs(self) -> dict:
        return {"window_chunk": self._jit_chunk,
                "window_decode": self._jit_decode,
                "kv_write": self.cache.writer}

    @property
    def pages_released(self) -> list:
        return [g.released for g in self.cache.groups]

    # -- pure forwards -------------------------------------------------------

    def _chunk_fwd(self, params, tops, ids, start, k_pools, v_pools, pids,
                   window_base):
        """ids [C] at positions ``start .. start + C - 1``; the pools by
        group (read only); pids by group the int32 page ids that cover
        the past this chunk may read: tokens ``0 .. start`` in the full
        group, ``window_base .. start`` in the window group.  Returns
        (the greedy token after the chunk's last position, the chunk's
        K and V by group ``[layers of the group, KV, C, D]``)."""
        cfg, ps = self.config, self.cache.page_size
        C = ids.shape[0]
        x = wm.embed(cfg, tops["embed"], ids)
        at = start + jnp.arange(C, dtype=jnp.int32)
        bases = (jnp.int32(0), window_base)
        # by group: the positions of the keys a layer reads (the past's
        # pages, then the chunk) and whether a query may read each
        masks = []
        for g, window in ((FULL, None), (WINDOW, cfg.sliding_window)):
            past = bases[g] + jnp.arange(pids[g].shape[0] * ps,
                                         dtype=jnp.int32)
            keys = jnp.concatenate([past, at])
            written = jnp.concatenate([past < start, jnp.ones((C,), bool)])
            masks.append(wm.visible(at[:, None], keys[None, :], window)
                         & written[None, :])
        heads = cfg.num_attention_heads // cfg.num_key_value_heads
        ks, vs = ([], []), ([], [])
        for n, lp in enumerate(params):
            g, place = self.slot_of[n]
            q, k, v, gate = wm.attention_inputs(cfg, lp, x, at, g == WINDOW)
            kf, vf = k, v
            if pids[g].shape[0]:
                kf, vf = (jnp.concatenate([jnp.swapaxes(_past_of(
                    pool[g], place, pids[g], x.dtype), 0, 1), new])
                    for pool, new in ((k_pools, k), (v_pools, v)))
            o = wm.attend(cfg, q, kf, vf, masks[g],
                          head_block=_head_block(heads, C, kf.shape[0]))
            x = wm.attention_residual(cfg, lp, x, o, gate)
            x, _ = wm.feed_forward(cfg, cfg.is_dense(n), lp, x, self.held)
            ks[g].append(jnp.swapaxes(k, 0, 1))
            vs[g].append(jnp.swapaxes(v, 0, 1))
        logits = wm.head(cfg, tops["norm_w"], tops["lm_head"], x[-1:])
        return (jnp.argmax(logits[0]).astype(jnp.int32),
                [jnp.stack(one) for one in ks],
                [jnp.stack(one) for one in vs])

    def _decode_fwd(self, params, tops, ids, positions, live, k_pools,
                    v_pools, tables, window_bases):
        """One token for every slot: ids, positions [S] (the token's
        position), live [S] bool; by group the pools and the tables
        [S, pages a sequence]; ``window_bases`` [S] the token the window
        group's first table entry stands for.  A slot that is not live
        writes no page and attends nothing.  Returns (tokens [S], the
        pools by group, rows each held expert took [expert layers, held]
        int32)."""
        cfg, ps = self.config, self.cache.page_size
        S = ids.shape[0]
        x = wm.embed(cfg, tops["embed"], ids)                    # [S, H]
        lengths = jnp.where(live, positions + 1, 0)
        offs = positions % ps
        bases = (jnp.zeros_like(window_bases), window_bases)
        starts = jnp.maximum(positions + 1 - cfg.sliding_window, 0)
        shapes = [pool.shape for pool in k_pools]
        # the page the token goes to, by group (none for a dead slot)
        pids = [jnp.where(live, tables[g][jnp.arange(S),
                                          (positions - bases[g]) // ps],
                          shapes[g][2]) for g in (FULL, WINDOW)]
        kf, vf = [_flat(p) for p in k_pools], [_flat(p) for p in v_pools]
        took = []
        for n, lp in enumerate(params):
            g, place = self.slot_of[n]
            q, k, v, gate = wm.attention_inputs(cfg, lp, x, positions,
                                                g == WINDOW)
            kf[g] = _put_token(kf[g], shapes[g], place, pids[g], offs, k)
            vf[g] = _put_token(vf[g], shapes[g], place, pids[g], offs, v)
            window = {} if g == FULL else dict(starts=starts,
                                               bases=bases[g])
            o = paged_decode_attention(
                q, kf[g].reshape(shapes[g]), vf[g].reshape(shapes[g]),
                lengths, tables[g], layer=jnp.int32(place), **window)
            x = wm.attention_residual(cfg, lp, x, o.reshape(S, -1), gate)
            x, rows = wm.feed_forward(cfg, cfg.is_dense(n), lp, x,
                                      self.held)
            if not cfg.is_dense(n):
                took.append(jnp.sum(rows & live[:, None], axis=0,
                                    dtype=jnp.int32))
        logits = wm.head(cfg, tops["norm_w"], tops["lm_head"], x)
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                [f.reshape(s) for f, s in zip(kf, shapes)],
                [f.reshape(s) for f, s in zip(vf, shapes)],
                jnp.stack(took))

    # -- slot-granular control plane ----------------------------------------

    def alloc_slot(self) -> int:
        return self.cache.allocate()

    def free_slot(self, sid: int) -> None:
        self.cache.free(sid)
        self.last_token.pop(sid, None)

    def slot_kv(self, sid: int, layer: int):
        """What the pools hold for a slot in ``layer``: (the token its
        first row stands for, K ``[tokens, KV, D]``, V the same), copies
        on the device in the pool's dtype.  A full layer gives every
        token from 0; a sliding layer the pages it still keeps."""
        g, place = self.slot_of[layer]
        base = int(self.cache.groups[g].base[sid])
        k, v = self.cache.gather_dense(sid, group=g)
        n = int(self.cache.lengths[sid]) - base
        return (base, jnp.swapaxes(k[place, :, :n], 0, 1),
                jnp.swapaxes(v[place, :, :n], 0, 1))

    def _count_pages(self):
        for g, group in enumerate(self.cache.groups):
            self.pages_used[g] += group.num_pages - len(group._free)
        self.page_samples += 1

    def prefill_chunk(self, sid: int, chunk_ids, start: int,
                      final: bool) -> int | None:
        """One prefill chunk at position ``start``: attends the slot's
        written pages inside the program.  When ``final``, records and
        returns the first greedy token."""
        cache = self.cache
        with self.handoff.prep(tokens=len(chunk_ids)) as io:
            ids = io.put(np.asarray(chunk_ids), jnp.int32)
            pids = [cache.past_pages(sid, start, g) for g in (FULL, WINDOW)]
            at = np.int32(start)
            base = np.int32(cache.groups[WINDOW].base[sid])
            io.host(at, *pids, base)
            k_pools, v_pools = cache.pools()
        self.prefill_events.append((sid, int(ids.shape[0]), int(start)))
        tok, k, v = self._jit_chunk(self.params, self.tops, ids, at, k_pools,
                                    v_pools, pids, base)
        del k_pools, v_pools
        cache.write_at(sid, k, v, start)     # and releases behind the window
        self._count_pages()
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
        cache, cfg = self.cache, self.config
        full, window = cache.groups
        block = self._decode_block
        # the 256-key blocks the kernel's loop visits, summed over the
        # layers of each kind, of the blocks of every table
        reads = cache.lengths[sids] + 1
        seen_from = np.maximum(reads - cfg.sliding_window, 0) \
            - window.base[sids]
        with self.handoff.prep(
                batch=len(sids),
                blocks=int(full.n_layers * (-(-reads // block)).sum()
                           + window.n_layers * (
                               -(-(reads - window.base[sids]) // block)
                               - seen_from // block).sum()),
                window_blocks=len(sids) * sum(
                    g.n_layers * -(-g.max_pages_per_seq * cache.page_size
                                   // block) for g in cache.groups)) as io:
            cache.reserve(sids, extra_tokens=1)
            n = cache.max_seqs
            ids = np.zeros((n,), np.int32)
            positions = np.zeros((n,), np.int32)
            live = np.zeros((n,), bool)
            ids[sids] = [self.last_token[s] for s in sids]
            positions[sids] = cache.lengths[sids]
            live[sids] = True
            tables = [np.maximum(g.page_table, 0) for g in cache.groups]
            bases = window.base.copy()
            io.host(ids, positions, live, *tables, bases)
            k_pools, v_pools = cache.pools()
        toks, k_pools, v_pools, counts = self._jit_decode(
            self.params, self.tops, ids, positions, live, k_pools, v_pools,
            tables, bases)
        cache.set_pools(k_pools, v_pools)
        cache.lengths[sids] += 1
        # the program that last reads them is dispatched: the pages now
        # wholly behind the window go back to their pool
        cache.release(sids)
        self._count_pages()
        toks, counts = io.fetch("decode", (toks, counts))
        self._count_experts(counts)
        out = {}
        for s in sids:
            out[s] = self.last_token[s] = int(toks[s])
        return out
