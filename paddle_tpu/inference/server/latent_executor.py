"""LatentExecutor — the execution backend for a model of latent attention
(MLA) layers with a routed-expert feed-forward (``models/mla_moe.py``).

A sibling of :class:`~.hybrid_executor.HybridExecutor` under the same
:class:`~.hybrid_executor.SlotExecutor` control plane: the scheduler,
admission, chunked prefill and preemption by recompute are the ones every
model gets, and the programs are COMPOSED from the model's layer kinds,
whose parts are the functions of ``models/mla_moe.py``.  Leading dense
layers are inlined and alias the model's own arrays; a run of expert
layers is stacked once at build and is one ``lax.scan``.

**What is held.**  One :class:`~..paged.PagedKVCache` of the latent kind:
a row ``[c ; rope(k_pe)]`` a token a layer, padded to whole 128-lane
tiles (576 -> 640 at the published widths; the compiler would pad the
tile all the same), behind the cache's own page table, allocator and one
donated ``serve.kv_write`` a chunk.  No V pool, no heads, no second
cache.  The stacked run is a second copy of the expert layers: where the
device reports its memory and cannot hold both, the executor TAKES THE
EXPERT LAYERS OVER leaf by leaf (each leaf's eager arrays are deleted as
soon as their stack exists; ``took_over_weights``), and the pool is
allocated after the last stack, so the build's peak is the model plus
one stacked leaf.

Two programs:

``serve.mla_chunk`` — one prefill chunk of one sequence in the EXPANDED
  form, keyed on the chunk's length and the past's page count.  It reads
  the past's rows out of the (undonated) pool inside the program, by
  page id (:func:`~..paged._past_of`), rebuilds keys and values from
  them, and returns the chunk's rows for the cache's writer.

``serve.mla_decode`` — one token for every slot in the ABSORBED form.
  ONE program whatever the batch, over all ``max_seqs`` slots under a
  live mask; the pool is donated and carried through the layer scan; the
  token's row goes in by :func:`~..paged._put_token` and the attention is
  ``ops/pallas_kernels/mla_decode.py`` over the pool in place.  Beside
  the tokens it returns, for the same blocking read, how many rows each
  held expert took in each expert layer (int32 ``[expert layers, held]``,
  live slots only): what happens inside a program cannot be a host span.
"""
from __future__ import annotations

import itertools

import numpy as np

import jax
import jax.numpy as jnp

from ...analysis import CountedJit
from ...models import mla_moe as mm
from ...ops.pallas_kernels import mla_decode as _mla
from ..paged import PagedKVCache, _flat, _past_of, _put_token
from .handoff import Handoff
from .hybrid_executor import _HEADROOM, SlotExecutor, _free_device_bytes

_EXPERT_LEAVES = ("mlp.experts.gate_up_proj", "mlp.experts.down_proj")
#: bytes of float32 scores a prefill chunk's attention holds at a time
_SCORE_BYTES = 256 << 20


@jax.jit
def _stack(*leaves):
    """One leaf of a run's layers -> [layers, ...], in one program (an
    eager ``jnp.stack`` first copies every operand to add its axis)."""
    return jnp.stack(leaves)


def _head_block(n_heads, chunk, keys):
    """Heads a prefill chunk attends at a time: the most (a power of two
    that divides the heads) whose scores fit :data:`_SCORE_BYTES`."""
    hb = n_heads
    while hb > 1 and (hb * chunk * keys * 4 > _SCORE_BYTES or n_heads % hb):
        hb //= 2
    return hb


class LatentExecutor(SlotExecutor):
    def __init__(self, model, max_seqs=4, page_size=16, max_len=256,
                 dtype=jnp.float32, num_pages=None):
        cfg = model.config
        self.config, self.held = cfg, tuple(model.held_experts)
        self.max_len = int(max_len)
        state = {k: v._data for k, v in model.state_dict().items()}
        if any(a.is_deleted() for a in state.values()):
            raise ValueError(
                "LatentExecutor: this model's expert layers were handed "
                "over to an engine built from it before (the device could "
                "not hold two copies); build the model again")

        # runs of one kind, in order: ("mla_moe", n) is a scanned run of n
        # stacked layers, ("mla_dense", 1) one inlined layer
        self.segments, starts = [], []
        for kind, group in itertools.groupby(cfg.layer_types):
            n, at = len(list(group)), sum(m for _, m in self.segments)
            self.segments += [(kind, n)] if kind == "mla_moe" else \
                [(kind, 1)] * n
            starts += [at] if kind == "mla_moe" else range(at, at + n)
        self.n_expert_layers = cfg.layer_types.count("mla_moe")

        def leaf(i, name):
            return state[f"model.layers.{i}.{name}"]

        # -- what the device has to hold, refused here if it cannot -------
        self.rank = cfg.kv_lora_rank
        self.row_width = _mla.padded_width(cfg.latent_dim)
        pages_per_seq = -(-self.max_len // page_size)
        num_pages = (max_seqs * pages_per_seq if num_pages is None
                     else int(num_pages))
        pool = (cfg.num_hidden_layers * num_pages * page_size
                * self.row_width * jnp.dtype(dtype).itemsize)
        names = mm.layer_param_names("mla_moe")
        stacks = sum(leaf(i, name).nbytes
                     for (kind, n), at in zip(self.segments, starts)
                     if kind == "mla_moe" for i in range(at, at + n)
                     for name in names)
        free = _free_device_bytes()
        if free is not None and pool + _HEADROOM > free:
            raise ValueError(
                f"LatentExecutor: {pool / 1e9:.2f} GB of latent pages for "
                f"max_seqs={max_seqs}, max_len={self.max_len} do not fit "
                f"the {free / 1e9:.2f} GB the device has free beside the "
                f"model")
        #: the device cannot hold the model's own copy of the expert
        #: layers beside the stacked one (see the module's docstring)
        self.took_over_weights = (free is not None
                                  and pool + stacks + _HEADROOM > free)

        # -- the parameters: a run is stacked leaf by leaf -----------------
        self.params = []
        for (kind, n), at in zip(self.segments, starts):
            if kind == "mla_dense":
                self.params.append({name: leaf(at, name) for name
                                    in mm.layer_param_names(kind)})
                continue
            run = {}
            for name in names:
                own = [leaf(i, name) for i in range(at, at + n)]
                run[name] = jax.block_until_ready(_stack(*own))
                if self.took_over_weights:
                    for a in own:
                        a.delete()
                del own
            self.params.append(run)
        self.params = tuple(self.params)
        self.tops = {"embed": state["model.embed_tokens.weight"],
                     "norm_w": state["model.norm.weight"],
                     "lm_head": state["lm_head.weight"]}
        del state
        self.cache = PagedKVCache(
            n_layers=cfg.num_hidden_layers, n_kv_heads=1,
            head_dim=self.row_width, num_pages=num_pages,
            page_size=page_size, max_seqs=max_seqs, dtype=dtype,
            max_pages_per_seq=pages_per_seq, latent=True)
        self.last_token = {}
        self.handoff = Handoff()
        #: (sid, n_tokens) per prefill dispatch, as PagedExecutor keeps
        self.prefill_events = []
        #: running sums of the decode program's expert counter: rows each
        #: held expert took [expert layers, held]; decode steps counted;
        #: (layer, expert) pairs that took a row; and the sum over steps
        #: and layers of busiest expert's rows over the mean
        self.expert_rows = np.zeros((self.n_expert_layers, len(self.held)),
                                    np.int64)
        self.expert_steps = 0
        self.experts_hit = 0
        self.expert_max_over_mean = 0.0
        self._jit_chunk = CountedJit(self._chunk_fwd, name="serve.mla_chunk")
        self._jit_decode = CountedJit(self._decode_fwd,
                                      name="serve.mla_decode",
                                      donate_argnums=(5,))

    @property
    def programs(self) -> dict:
        return {"mla_chunk": self._jit_chunk, "mla_decode": self._jit_decode,
                "kv_write": self.cache.writer}

    # -- pure forwards -------------------------------------------------------

    def _pad(self, rows):
        """Rows [.., rank + rope] laid into the pool's padded width."""
        return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1)
                       + [(0, self.row_width - rows.shape[-1])])

    def _layers(self, params, x, carry, layer_fn):
        """Every layer in order: ``layer_fn(kind, lp, layer, x, carry) ->
        (x, carry, out)``; a run of expert layers under ``lax.scan`` with
        its layers' numbers.  Returns (x, carry, [out per segment, each
        with its layers as the first axis])."""
        outs, at = [], 0
        for (kind, n), lp in zip(self.segments, params):
            if kind == "mla_dense":
                x, carry, out = layer_fn(kind, lp, at, x, carry)
                out = out[None]
            else:
                # the experts stay whole outside the scan and each layer
                # addresses its own in place (models/moe.py, _layer_of)
                big = {k: v for k, v in lp.items() if k in _EXPERT_LEAVES}
                small = {k: v for k, v in lp.items() if k not in big}

                def step(c, xs, kind=kind, big=big, at=at):
                    own = {k: (v, xs[1] - at) for k, v in big.items()}
                    x, carry, out = layer_fn(kind, {**xs[0], **own}, xs[1],
                                             *c)
                    return (x, carry), out

                (x, carry), out = jax.lax.scan(
                    step, (x, carry),
                    (small, jnp.arange(at, at + n, dtype=jnp.int32)))
            outs.append(out)
            at += n
        return x, carry, outs

    def _chunk_fwd(self, params, tops, ids, start, pool, pids):
        """ids [C] at positions ``start .. start + C - 1``; pool the
        latent pool (read only); pids int32 [n] the pages that cover the
        ``start`` tokens already written.  Returns (the greedy token
        after the chunk's last position, the chunk's rows
        [layers, 1, C, width] for the page writer)."""
        cfg = self.config
        C, P = ids.shape[0], pids.shape[0] * self.cache.page_size
        x = tops["embed"][ids]
        positions = start + jnp.arange(C, dtype=jnp.int32)
        mask = jnp.concatenate(
            [jnp.broadcast_to((jnp.arange(P) < start)[None], (C, P)),
             jnp.tril(jnp.ones((C, C), bool))], axis=1)
        hb = _head_block(cfg.num_attention_heads, C, P + C)

        def layer(kind, lp, n, x, carry):
            q_nope, q_pe, rows = mm.mla_project(cfg, lp, x, positions)
            seen = rows
            if P:
                past = _past_of(pool, n, pids, x.dtype)[0, :, :cfg.latent_dim]
                seen = jnp.concatenate([past.astype(rows.dtype), rows], 0)
            x = x + mm.attend_expanded(cfg, lp, q_nope, q_pe, seen, mask,
                                       head_block=hb)
            x, _ = mm.feed_forward(cfg, kind, lp, x, self.held)
            return x, carry, self._pad(rows)[None]

        x, _, rows = self._layers(params, x, (), layer)
        rows = jnp.concatenate(rows, axis=0)
        logits = mm.head(cfg, tops["norm_w"], tops["lm_head"], x[-1:])
        return jnp.argmax(logits[0]).astype(jnp.int32), rows

    def _decode_fwd(self, params, tops, ids, positions, live, pool, tables):
        """One token for every slot: ids, positions [S] (the token's
        position), live [S] bool, tables [S, pages per sequence].  A slot
        that is not live writes no page and attends nothing.  Returns
        (tokens [S], the pool, rows each held expert took
        [expert layers, held] int32)."""
        cfg = self.config
        S = ids.shape[0]
        ps, num_pages = self.cache.page_size, self.cache.num_pages
        pool_shape = pool.shape
        x = tops["embed"][ids]                                  # [S, H]
        pids = jnp.where(live, tables[jnp.arange(S), positions // ps],
                         num_pages)
        offs = positions % ps
        lengths = jnp.where(live, positions + 1, 0)

        def layer(kind, lp, n, x, flat):
            q_nope, q_pe, rows = mm.mla_project(cfg, lp, x, positions)
            flat = _put_token(flat, pool_shape, n, pids, offs,
                              self._pad(rows)[:, None, :])
            q = self._pad(mm.absorb_query(cfg, lp, q_nope, q_pe))
            u = _mla.mla_decode(q, flat, n, num_pages, lengths, tables,
                                self.rank)
            x = x + mm.absorb_output(cfg, lp, u)
            x, took = mm.feed_forward(cfg, kind, lp, x, self.held)
            return x, flat, jnp.sum(took & live[:, None], axis=0,
                                    dtype=jnp.int32)

        x, flat, took = self._layers(params, x, _flat(pool), layer)
        counts = jnp.concatenate(
            [t for t, (kind, _) in zip(took, self.segments)
             if kind == "mla_moe"], axis=0)
        logits = mm.head(cfg, tops["norm_w"], tops["lm_head"], x)
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                flat.reshape(pool_shape), counts)

    # -- slot-granular control plane ----------------------------------------

    def alloc_slot(self) -> int:
        return self.cache.allocate()

    def free_slot(self, sid: int) -> None:
        self.cache.free(sid)
        self.last_token.pop(sid, None)

    def slot_rows(self, sid: int):
        """The latent rows the pool holds for a slot, as the model's
        equations have them: ``[layers, tokens, rank + rope]`` in the
        pool's dtype (a copy on the device)."""
        rows, _ = self.cache.gather_dense(sid)
        n = int(self.cache.lengths[sid])
        return rows[:, 0, :n, :self.config.latent_dim]

    def prefill_chunk(self, sid: int, chunk_ids, start: int,
                      final: bool) -> int | None:
        """One prefill chunk at position ``start``: attends the slot's
        written pages inside the program.  When ``final``, records and
        returns the first greedy token."""
        cache = self.cache
        with self.handoff.prep(tokens=len(chunk_ids)) as io:
            ids = io.put(np.asarray(chunk_ids), jnp.int32)
            pids = cache.past_pages(sid, start)
            at = np.int32(start)
            io.host(at, pids)
            pool, _ = cache.pools()
        self.prefill_events.append((sid, int(ids.shape[0])))
        tok, rows = self._jit_chunk(self.params, self.tops, ids, at, pool,
                                    pids)
        del pool
        cache.write_at(sid, rows, None, start)
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
        with self.handoff.prep(batch=len(sids)) as io:
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
            pool, _ = cache.pools()
        toks, pool, counts = self._jit_decode(
            self.params, self.tops, ids, positions, live, pool, tables)
        cache.set_pools(pool, None)
        cache.lengths[sids] += 1
        toks, counts = io.fetch("decode", (toks, counts))
        self._count_experts(counts)
        out = {}
        for s in sids:
            out[s] = self.last_token[s] = int(toks[s])
        return out
