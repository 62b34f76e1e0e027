"""PagedExecutor — the model-execution backend of the serving stack.

This is the data plane the continuous-batching scheduler drives: it owns
the stacked model parameters, the jitted prefill/decode programs and the
:class:`~paddle_tpu.inference.paged.PagedKVCache` page pool.  It knows
NOTHING about queues, priorities or deadlines — those live in
``scheduler.py`` — it only exposes slot-granular operations:

  * ``prefill(sid, ids)``          whole-prompt prefill, one program
  * ``prefill_chunk(sid, ids, t0)``chunked prefill: attend past pages,
                                   write the chunk's KV at offset t0
  * ``decode(sids)``               one greedy token for an explicit
                                   batch of slots
  * ``decode_n(sids, n)``          n greedy tokens, feedback on device
"""
from __future__ import annotations

import os
import warnings

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as _P

from ... import obs
from ...analysis import CountedJit, ProgramContract, register_program
from ...ops import quant as _quant
from ...ops.nn_ops import _rms_norm_plain, _rope_plain
from ...ops.pallas_kernels.paged_decode import block_pages
from ...testing import faults as _faults
from .handoff import Handoff
from ..paged import (
    PagedKVCache, _flat, _past_of, _put_token, paged_decode_attention,
)


def _sp_prefill_enabled() -> bool:
    """PT_SP_PREFILL={off,on} — sequence-parallel prefill of long
    prompts over a mesh (serve.prefill_sp).  Off is bit-exact r22."""
    mode = os.environ.get("PT_SP_PREFILL", "off").lower()
    if mode not in ("off", "on"):
        raise ValueError(f"PT_SP_PREFILL={mode!r}: expected off|on")
    return mode == "on"


def _sp_min_tokens_default() -> int:
    """PT_SP_PREFILL_MIN_TOKENS — raw prompt-length threshold above
    which prefill is planned sequence-parallel (floor-quantized onto
    the AOT bucket ladder when one is armed)."""
    return int(os.environ.get("PT_SP_PREFILL_MIN_TOKENS", "64"))


def _mm(x, w):
    """Weight matmul that dispatches on the weight's pytree form at
    TRACE time: a plain array keeps the exact pre-quant jaxpr
    (PT_QUANT=none stays bit-exact by construction), a QuantizedLinear
    dict routes through the fused-dequant path."""
    if _quant.is_quantized(w):
        return _quant.qmatmul(x, w)
    return x @ w


#: the stacked decoder weights quantized under PT_QUANT=int8 — the
#: seven per-layer projection matmuls.  Embedding, norms, RoPE tables
#: and the LM head stay in the checkpoint dtype (small, and the head
#: dominates logit drift).
_QUANT_LAYER_WEIGHTS = (
    "self_attn.q_proj.weight", "self_attn.k_proj.weight",
    "self_attn.v_proj.weight", "self_attn.o_proj.weight",
    "mlp.gate_proj.weight", "mlp.up_proj.weight",
    "mlp.down_proj.weight",
)


class _PendingDecode:
    """Unrealized device output of one async decode dispatch.

    ``wait()`` is the commit fence: ONE host transfer (the in-graph
    argmax already reduced logits to an int32 [B] row), then the
    last-token bookkeeping the sync path does inline.  Idempotent, so
    a fault-interrupted commit can be re-driven safely."""

    __slots__ = ("_ex", "sids", "_dev", "_out")

    def __init__(self, ex, sids, dev):
        self._ex = ex
        self.sids = sids
        self._dev = dev
        self._out = None

    def wait(self) -> dict:
        if self._out is None:
            toks = np.asarray(self._dev)      # the single device_get
            out = {}
            for i, s in enumerate(self.sids):
                tok = int(toks[i])
                self._ex.last_token[s] = tok
                out[s] = tok
            self._out = out
            self._dev = None
        return self._out


class _PendingVerify:
    """Unrealized device outputs of one async speculative-verify
    dispatch: the sort-packed token block and per-seq counts stay on
    device until ``wait()``, which also applies the length/last-token
    bookkeeping the sync :meth:`PagedExecutor.verify` does inline."""

    __slots__ = ("_ex", "sids", "_packed", "_emit_n", "_out")

    def __init__(self, ex, sids, packed, emit_n):
        self._ex = ex
        self.sids = sids
        self._packed = packed
        self._emit_n = emit_n
        self._out = None

    def wait(self):
        if self._out is None:
            cache = self._ex.cache
            packed = np.asarray(self._packed)
            counts = np.asarray(self._emit_n)
            out, accepted = {}, {}
            off = 0
            for i, s in enumerate(self.sids):
                n = int(counts[i])
                toks = [int(t) for t in packed[off:off + n]]
                off += n
                cache.lengths[s] += n
                self._ex.last_token[s] = toks[-1]
                out[s] = toks
                accepted[s] = n - 1
            self._out = (out, accepted)
            self._packed = self._emit_n = None
        return self._out


class PagedExecutor:
    """Execution backend over the paged KV cache.

    ``num_pages=None`` sizes the pool so every slot can reach
    ``max_len`` (the legacy engine's sizing).  A serving deployment
    passes a smaller pool to oversubscribe: the per-seq page budget
    stays ``max_len // page_size`` but the POOL can run dry, which is
    what makes admission control and preemption meaningful.
    """

    def __init__(self, model, max_seqs=4, page_size=16, max_len=256,
                 dtype=jnp.float32, num_pages=None, quant=None,
                 sp_mesh=None, sp_prefill=None, sp_min_tokens=None,
                 sp_axis=None):
        from ...models.generation import _stack_layer_params
        from ...models.llama import _rope_tables

        cfg = model.config
        self.config = cfg
        self.max_len = int(max_len)
        # PT_QUANT gate (ops/quant.py): validated here so a bogus value
        # fails the engine build, not the first decode step
        self.quant = _quant.quant_mode(quant)
        state = {k: v._data for k, v in model.state_dict().items()}
        self.layers = _stack_layer_params(state, cfg.num_hidden_layers)
        if self.quant == "int8":
            # stacked [L, in, out] projections -> QuantizedLinear dicts
            # ({qweight int8 [L, in, out], scale f32 [L, 1, out]});
            # lax.scan slices the dict leaves per layer like any other
            # stacked param, so the forwards only change at _mm()
            for name in _QUANT_LAYER_WEIGHTS:
                self.layers[name] = _quant.quantize_linear(
                    self.layers[name])
        embed = jnp.asarray(state["llama.embed_tokens.weight"])
        cos, sin = _rope_tables(cfg)
        # non-layer weights travel as jit ARGUMENTS: closed-over arrays
        # are baked into the HLO as literals, and multi-MB constants
        # (embed/head at vocab 32k) bloat every program and its compile
        # tied embeddings: alias the SAME buffer and transpose in-graph
        # (embed.T here would materialize a duplicate vocab x hidden
        # array in HBM); _head() applies the orientation.
        self._tied = bool(cfg.tie_word_embeddings)
        self.tops = {
            "embed": embed,
            "norm_w": jnp.asarray(state["llama.norm.weight"]),
            "head_w": (embed if self._tied
                       else jnp.asarray(state["lm_head.weight"])),
            "cos": jnp.asarray(cos),
            "sin": jnp.asarray(sin),
        }

        pages_per_seq = -(-max_len // page_size)
        self.cache = PagedKVCache(
            n_layers=cfg.num_hidden_layers,
            n_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            num_pages=(max_seqs * pages_per_seq if num_pages is None
                       else int(num_pages)),
            page_size=page_size, max_seqs=max_seqs, dtype=dtype,
            max_pages_per_seq=pages_per_seq, quant=self.quant)
        #: keys in one block of the fused decode kernel's loop
        self._decode_block = page_size * block_pages(
            page_size, cfg.num_key_value_heads, cfg.head_dim,
            jnp.dtype(self.cache.compute_dtype).itemsize)
        h = obs.handle()
        if h is not None:
            h.registry.gauge(
                "kv_pool_dtype",
                "KV page pool storage dtype (value 1 marks the active "
                "dtype)", labels=("dtype",)).labels(
                dtype=str(np.dtype(self.cache.k_pages.dtype))).set(1)
            h.registry.gauge(
                "quant_mode",
                "Serving quantization mode (PT_QUANT; value 1 marks "
                "the active mode)", labels=("mode",)).labels(
                mode=self.quant).set(1)
        self.last_token = {}
        # what prefill_chunk and decode hand the device outside their
        # programs, and their blocking reads (handoff.py)
        self.handoff = Handoff()
        # (sid, n_tokens) per prefill dispatch — the audit trail the
        # prefix-cache tests use to assert prefill FLOPs covered only
        # the novel suffix of a warm request
        self.prefill_events = []
        # every program is a CountedJit (analysis/audit.py): trace and
        # dispatch counters come with the jit wrapper, and the unjitted
        # fn doubles as the lint registration target below
        self._jit_prefill = CountedJit(self._prefill_fwd,
                                       name="serve.prefill")
        # the chunk program only READS the pools (its past, gathered
        # in-graph by page id), so it donates nothing: it hands its K/V
        # to the cache's own writer (serve.kv_write), which takes the
        # pools donated next, and a donated array has one owner.  Decode
        # and verify take the pools themselves donated, and the call
        # sites replace them with the outputs at once, so every page
        # write is in place instead of a copy of GBs of KV
        self._jit_chunk = CountedJit(self._chunk_fwd,
                                     name="serve.prefill_chunk")
        self._jit_decode = CountedJit(self._decode_fwd,
                                      name="serve.decode",
                                      donate_argnums=(4, 5))
        # async twin of serve.decode with the greedy argmax folded
        # in-graph: the only transferable output is an int32 [B] token
        # row, so the double-buffered scheduler's commit fence moves
        # one small vector instead of [B, V] logits
        self._jit_decode_async = CountedJit(self._decode_tok_fwd,
                                            name="serve.decode_async",
                                            donate_argnums=(4, 5))
        self._jit_decode_n = CountedJit(self._decode_n_fwd,
                                        name="serve.decode_n",
                                        static_argnames=("n",),
                                        donate_argnums=(4, 5))
        self._jit_verify = CountedJit(self._verify_fwd,
                                      name="serve.verify",
                                      donate_argnums=(3, 4))
        # -- sequence-parallel prefill (serve.prefill_sp) -------------
        # param forces on/off, None follows PT_SP_PREFILL; off (the
        # default) builds no program and changes nothing — bit-exact
        # r22.  Armed, long-prompt chunks stripe across the mesh's sp
        # axis: each rank ring-gathers the chunk K/V into canonical
        # order and runs the UNMODIFIED dense mask/softmax on its row
        # stripe, so the output is bit-identical to _chunk_fwd.
        sp_on = (_sp_prefill_enabled() if sp_prefill is None
                 else bool(sp_prefill))
        self._sp_mesh = None
        self._sp_jmesh = None
        self._sp_axis = None
        self._sp_n = 1
        self._jit_chunk_sp = None
        if sp_on:
            mesh, axis = self._resolve_sp_mesh(sp_mesh, sp_axis)
            if mesh is not None and mesh.get_dim_size(axis) > 1:
                self._sp_mesh = mesh
                self._sp_jmesh = mesh.jax_mesh
                self._sp_axis = axis
                self._sp_n = int(mesh.get_dim_size(axis))
                self._jit_chunk_sp = CountedJit(
                    self._sp_chunk_fwd, name="serve.prefill_sp",
                    donate_argnums=(4, 5))
        self._sp_min_tokens = (int(sp_min_tokens)
                               if sp_min_tokens is not None
                               else _sp_min_tokens_default())
        # slots holding range-sharded pages from an sp chunk: the
        # prefill->decode gather must fire for these even when the
        # (small) FINAL chunk itself routed to the dense program
        self._sp_written = set()
        self.sp_prefill_tokens = 0
        self.rollback_pages = 0
        # AOT plane state (core/aot.py): a non-None ladder switches the
        # executor into bucketed-shape mode — the scheduler quantizes
        # prefill chunks onto the rungs and prefill_chunk pads the past
        # cover onto page buckets.  None (PT_AOT=off) is bit-exact r17.
        self.aot_ladder = None
        self._aot_page_buckets = None
        self._aot_sealed = False
        self._aot_config = None
        self._register_contracts()

    @property
    def programs(self) -> dict:
        """The jitted programs, by contract name suffix (prefill_sp
        only when the sequence-parallel plane is armed)."""
        progs = {"prefill": self._jit_prefill,
                 "prefill_chunk": self._jit_chunk,
                 "decode": self._jit_decode,
                 "decode_async": self._jit_decode_async,
                 "decode_n": self._jit_decode_n,
                 "verify": self._jit_verify}
        if self._jit_chunk_sp is not None:
            progs["prefill_sp"] = self._jit_chunk_sp
        return progs

    # -- sequence-parallel plane ----------------------------------------

    @staticmethod
    def _resolve_sp_mesh(sp_mesh, sp_axis):
        """(1-D ProcessMesh, axis name) for sequence-parallel prefill.

        ``sp_mesh=None`` builds a 1-D mesh over every local device.  A
        multi-dim mesh (the dp x sep hybrid a training job hands over)
        is reduced to the 1-D submesh along ``sp_axis`` — auto-detected
        as ``sp`` then ``sep``, else the largest dim — by fixing every
        other dim at index 0: prefill shards the SEQUENCE, so exactly
        one mesh axis participates.  Returns (None, None) when no
        multi-device axis exists (the caller disarms)."""
        from ...distributed.auto_parallel import ProcessMesh

        if sp_mesh is None:
            n = jax.device_count()
            if n < 2:
                return None, None
            return (ProcessMesh(list(range(n)), dim_names=["sp"]),
                    sp_axis or "sp")
        mesh = sp_mesh
        if sp_axis is None:
            for cand in ("sp", "sep"):
                if cand in mesh.dim_names:
                    sp_axis = cand
                    break
            else:
                sp_axis = max(mesh.dim_names, key=mesh.get_dim_size)
        for d in list(mesh.dim_names):
            if d != sp_axis and mesh.ndim > 1:
                mesh = mesh.get_mesh_with_dim(d, 0)
        return mesh, sp_axis

    @property
    def sp_degree(self) -> int:
        """Ranks a sequence-parallel chunk stripes across (1 = the
        plane is off and every prompt takes the single-device path)."""
        return self._sp_n if self._jit_chunk_sp is not None else 1

    def sp_min_tokens_effective(self) -> int:
        """The sequence-parallel length threshold the scheduler plans
        with: the raw PT_SP_PREFILL_MIN_TOKENS, floor-quantized onto
        the armed bucket ladder so the threshold sits ON a warmed rung
        — AOT warmup covers every (prefill_sp x rung) pair at or above
        it and a sealed engine never misses.  Below the lowest rung the
        lowest rung is the floor."""
        raw = self._sp_min_tokens
        ladder = self.aot_ladder
        if ladder is None:
            return raw
        rung = ladder.floor(raw)
        return int(rung) if rung is not None else int(min(ladder.rungs))

    # speculative-decode audit counters, kept as properties over the
    # CountedJit wrapper: traces counts how many times _verify_fwd was
    # TRACED (re-traces mean shape churn), dispatches how many verify
    # steps ran — the no-host-loop test asserts dispatches >> traces
    # while tokens >> dispatches
    @property
    def verify_traces(self) -> int:
        return self._jit_verify.traces

    @property
    def verify_dispatches(self) -> int:
        return self._jit_verify.dispatches

    def _pool_sds(self):
        """ShapeDtypeStruct mirror of ``cache.pools()`` for contracts
        and AOT warmup."""
        c = self.cache
        kp = jax.ShapeDtypeStruct(jnp.shape(c.k_pages),
                                  c.k_pages.dtype)
        if self.quant == "int8":
            sc = jax.ShapeDtypeStruct(jnp.shape(c.k_scales),
                                      c.k_scales.dtype)
            return (kp, sc)
        return kp

    def _register_contracts(self):
        """Register the serving programs' graph contracts at
        representative shapes (lint traces ShapeDtypeStructs only — no
        device work).  The chunk program is linted with a past of one
        page; serve.prefill_sp's dense past picks cover == chunk length
        so the donation aliasing opportunity is visible to the checker.

        Quantized builds register under ``.int8``-suffixed names: the
        registry is replace-by-name and lint_graph builds BOTH engine
        flavors, so the suffix keeps the quantized decode/verify
        programs linted alongside (not instead of) the plain ones.  The
        contract ``compute_dtype`` comes from the cache's COMPUTE dtype,
        never the pool storage dtype — the int8→f32 dequant inside the
        programs is the point, not an upcast violation."""
        cache = self.cache
        cfg = self.config
        L = cfg.num_hidden_layers
        KV, D = cfg.num_key_value_heads, cfg.head_dim
        ps, B, pps = cache.page_size, cache.max_seqs, \
            cache.max_pages_per_seq
        sfx = ".int8" if self.quant == "int8" else ""

        def sds(tree):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype),
                tree)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        layers, tops = sds(self.layers), sds(self.tops)
        kp = self._pool_sds()
        past = jax.ShapeDtypeStruct((L, KV, ps, D),
                                    cache.compute_dtype)
        # reduced-precision compute => bf16 serving build: flag big f32
        # intermediates as upcasts (f32 builds skip the check)
        cd = np.dtype(cache.compute_dtype)
        common = dict(
            compute_dtype=str(cd) if cd.itemsize < 4 else None,
            # single-device programs must stay collective-free
            expected_collectives={},
            # checkpoint restore sweeps this hook (registry.aot_warmup)
            # so a rolled-back replica re-warms its executables; a no-op
            # until the engine has run aot_warmup once
            aot_hook=self._aot_rewarm,
        )
        register_program(ProgramContract(
            name="serve.prefill" + sfx, fn=self._prefill_fwd,
            args=(layers, tops, i32(1, 2 * ps)), **common))
        register_program(ProgramContract(
            name="serve.prefill_chunk" + sfx, fn=self._chunk_fwd,
            args=(layers, tops, i32(1, ps), i32(), kp, kp, i32(1), i32()),
            **common))
        if self._jit_chunk_sp is not None:
            # the ONLY serving program allowed collectives, and its
            # inventory is exact: the per-layer ring-gather costs
            # 2*(n-1) ppermute hops (k and v, counted once for the
            # scan body), and the final-logits row costs exactly one
            # all_gather at the end — anything else (a stray psum, a
            # per-layer all_gather) is a regression lint must catch.
            # Host-sync stays banned like every serving program.
            nsp = self._sp_n
            register_program(ProgramContract(
                name="serve.prefill_sp" + sfx, fn=self._sp_chunk_fwd,
                args=(layers, tops, i32(1, nsp * max(2, ps)), i32(),
                      past, past, i32()),
                donate_argnums=self._jit_chunk_sp.donate_argnums,
                **{**common,
                   "expected_collectives": {"ppermute": 2 * (nsp - 1),
                                            "all_gather": 1}}))
        # the cache's span writer at one page of tokens (int8: page id
        # and slot per token; plain: the two pages a mid-page start
        # touches, and the first slot)
        span = ((i32(ps), i32(ps)) if self.quant == "int8"
                else (i32(2), i32()))
        register_program(ProgramContract(
            name="serve.kv_write" + sfx, fn=cache.writer.fn,
            args=(kp, kp, past, past) + span,
            donate_argnums=cache.writer.donate_argnums, **common))
        register_program(ProgramContract(
            name="serve.decode" + sfx, fn=self._decode_fwd,
            args=(layers, tops, i32(B), i32(B), kp, kp, i32(B),
                  i32(B, pps)),
            donate_argnums=self._jit_decode.donate_argnums, **common))
        register_program(ProgramContract(
            name="serve.decode_async" + sfx, fn=self._decode_tok_fwd,
            args=(layers, tops, i32(B), i32(B), kp, kp, i32(B),
                  i32(B, pps)),
            donate_argnums=self._jit_decode_async.donate_argnums,
            **common))
        register_program(ProgramContract(
            name="serve.decode_n" + sfx, fn=self._decode_n_fwd,
            args=(layers, tops, i32(B), i32(B), kp, kp, i32(B),
                  i32(B, pps)),
            kwargs={"n": 2},
            donate_argnums=self._jit_decode_n.donate_argnums, **common))
        register_program(ProgramContract(
            name="serve.verify" + sfx, fn=self._verify_fwd,
            args=(layers, tops, i32(B, 2), kp, kp, i32(B), i32(B, pps),
                  i32(B)),
            donate_argnums=self._jit_verify.donate_argnums, **common))

    # -- AOT warmup (core/aot.py) ---------------------------------------

    def aot_warmup(self, prefill_chunk=None, compile_cache=None,
                   spec_window=None, decode_n_steps=(), ladder=None):
        """Pre-compile every (program x shape-rung) pair the bucketed
        executor can dispatch, so a warmed engine serves with ZERO
        post-warmup traces.

        The shape universe is finite by construction:

        * ``serve.prefill_chunk`` — chunk length runs over the pow2
          ``ladder`` rungs (the scheduler floor-quantizes onto them and
          any prompt decomposes into descending rungs), the past's
          page ids over the feasible page buckets (a chunk of C at rung
          r can only ever see ``<= ceil((max_len - C) / page_size)``
          past pages; the pools keep their one shape).  Whole-prompt
          prefill is routed through this program
          (``serve.prefill`` has an unbounded [1, S] shape — the reason
          chunking exists).
        * ``serve.decode`` / ``serve.decode_async`` / ``serve.verify``
          — batch runs over exactly 1..max_seqs (``verify`` only when
          ``spec_window`` gives the draft window W = k + 1).
        * ``serve.decode_n`` — per requested static ``n``.

        Each entry resolves warm (already in-process) / disk (the
        persistent ``compile_cache``) / compile; a failing entry is
        recorded and skipped — warmup must never take the engine down.
        Returns the warmup report and arms ``self.aot_ladder``.
        """
        import time as _time

        from ...core import aot

        kvc = self.cache
        cfg = self.config
        L = cfg.num_hidden_layers
        KV, D = cfg.num_key_value_heads, cfg.head_dim
        ps, pps = kvc.page_size, kvc.max_pages_per_seq
        # serve.prefill_sp's past arrives dense in the COMPUTE dtype
        # (int8 pools dequantize inside gather_dense), so its past SDS
        # must not mirror the pool storage dtype
        past_dt = kvc.compute_dtype

        def sds(tree):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype),
                tree)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        layers, tops = sds(self.layers), sds(self.tops)
        kp = self._pool_sds()

        cap = (min(int(prefill_chunk), self.max_len)
               if prefill_chunk else self.max_len)
        if ladder is None:
            ladder = aot.BucketLadder.pow2(cap)
        buckets = aot.page_buckets(pps)

        plan = []  # (CountedJit, args, kwargs)
        for C in ladder.rungs:
            # feasible past covers for a chunk of C: the chunk's last
            # token still fits in max_len, so past <= max_len - C
            pmax = aot.bucket_pages(-(-(self.max_len - C) // ps),
                                    buckets)
            for b in (x for x in buckets if x <= pmax):
                plan.append((self._jit_chunk,
                             (layers, tops, i32(1, C), i32(), kp, kp,
                              i32(b), i32()), {}))
        if self.sp_degree > 1:
            # sequence-parallel rungs: a chunk only stripes when its
            # length splits evenly across the ranks, so warmup covers
            # exactly the (prefill_sp x rung) pairs the scheduler can
            # dispatch — the sp_min_tokens_effective() floor sits on a
            # rung by construction
            nsp = self._sp_n
            for C in (c for c in ladder.rungs
                      if c % nsp == 0 and c >= 2 * nsp):
                pmax = aot.bucket_pages(-(-(self.max_len - C) // ps),
                                        buckets)
                for b in (x for x in buckets if x <= pmax):
                    past = jax.ShapeDtypeStruct((L, KV, b * ps, D),
                                                past_dt)
                    plan.append((self._jit_chunk_sp,
                                 (layers, tops, i32(1, C), i32(),
                                  past, past, i32()), {}))
        for B in range(1, kvc.max_seqs + 1):
            dec = (layers, tops, i32(B), i32(B), kp, kp, i32(B),
                   i32(B, pps))
            plan.append((self._jit_decode, dec, {}))
            plan.append((self._jit_decode_async, dec, {}))
            for n in decode_n_steps:
                plan.append((self._jit_decode_n, dec, {"n": int(n)}))
            if spec_window:
                plan.append((self._jit_verify,
                             (layers, tops, i32(B, int(spec_window)),
                              kp, kp, i32(B), i32(B, pps), i32(B)), {}))

        t0 = _time.perf_counter()
        report = {"compile": 0, "disk": 0, "warm": 0, "failed": [],
                  "programs": {}, "ladder": ladder.rungs,
                  "page_buckets": buckets}
        for prog, args, kwargs in plan:
            try:
                how = prog.aot_compile(args, kwargs,
                                       cache=compile_cache)
            except Exception as e:  # a failed entry must not kill warmup
                report["failed"].append((prog.name, str(e)))
                continue
            report[how] += 1
            report["programs"][prog.name] = \
                report["programs"].get(prog.name, 0) + 1
        report["entries"] = len(plan)
        report["seconds"] = round(_time.perf_counter() - t0, 3)

        self.aot_ladder = ladder
        self._aot_page_buckets = buckets
        self._aot_config = dict(prefill_chunk=prefill_chunk,
                                compile_cache=compile_cache,
                                spec_window=spec_window,
                                decode_n_steps=tuple(decode_n_steps),
                                ladder=ladder)
        h = obs.handle()
        if h is not None:
            h.recorder.record("aot.warmup", **{
                k: report[k] for k in
                ("compile", "disk", "warm", "entries", "seconds")})
        return report

    def _aot_rewarm(self):
        """Contract ``aot_hook``: re-run the last warmup configuration
        (checkpoint restore / guardian rollback path); no-op until the
        engine has warmed once."""
        if self._aot_config is None:
            return None
        return self.aot_warmup(**self._aot_config)

    def seal(self):
        """PT_AOT=strict: forbid post-warmup compilation.  Every warmed
        program's table is sealed (a miss raises AotMissError) and
        whole-prompt ``prefill`` — un-bucketable, routed through chunks
        by the scheduler — starts refusing direct calls too."""
        if self.aot_ladder is None:
            raise ValueError("seal() before aot_warmup()")
        for prog in self.programs.values():
            if prog._exe:
                prog.seal()
        self._aot_sealed = True

    def _head(self, x, tops):
        w = tops["head_w"]
        return x @ (w.T if self._tied else w)

    # -- pure forwards --------------------------------------------------

    def _prefill_fwd(self, layers, tops, ids):
        """[1, S] prompt -> (last-token logits [V], k [L,KV,S,D],
        v [L,KV,S,D]) — plain causal attention, KV returned for the
        page writer."""
        cfg = self.config
        nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        B, S = ids.shape
        x = tops["embed"][ids]
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        scale = 1.0 / np.sqrt(d)

        def block(x, lp):
            h = _rms_norm_plain(x, lp["input_layernorm.weight"],
                                epsilon=cfg.rms_norm_eps)
            q = _mm(h, lp["self_attn.q_proj.weight"]) \
                .reshape(B, S, nh, d)
            k = _mm(h, lp["self_attn.k_proj.weight"]) \
                .reshape(B, S, nkv, d)
            v = _mm(h, lp["self_attn.v_proj.weight"]) \
                .reshape(B, S, nkv, d)
            q, k = _rope_plain(q, k, tops["cos"], tops["sin"],
                               position_ids=pos)
            g = nh // nkv
            qt = jnp.swapaxes(q, 1, 2)              # [B, nh, S, d]
            kt = jnp.swapaxes(k, 1, 2)              # [B, nkv, S, d]
            vt = jnp.swapaxes(v, 1, 2)
            if g > 1:                               # GQA: expand KV heads
                kt = jnp.repeat(kt, g, axis=1)
                vt = jnp.repeat(vt, g, axis=1)
            # standard 4-D attention: the 5-D grouped einsum + rank-5
            # masked-broadcast variant compiled pathologically slowly on
            # the TPU AOT path (95s+ for 2 layers; minutes at vocab 32k)
            logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
            causal = jnp.tril(jnp.ones((S, S), bool))
            logits = jnp.where(causal[None, None], logits,
                               jnp.finfo(logits.dtype).min)
            p = jax.nn.softmax(logits.astype(jnp.float32), -1) \
                .astype(x.dtype)
            o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
            o = jnp.swapaxes(o, 1, 2).reshape(B, S, nh * d)
            x = x + _mm(o, lp["self_attn.o_proj.weight"])
            h2 = _rms_norm_plain(x, lp["post_attention_layernorm.weight"],
                                 epsilon=cfg.rms_norm_eps)
            gate = _mm(h2, lp["mlp.gate_proj.weight"])
            up = _mm(h2, lp["mlp.up_proj.weight"])
            x = x + _mm(jax.nn.silu(gate) * up,
                        lp["mlp.down_proj.weight"])
            return x, (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))

        x, (ks, vs) = jax.lax.scan(block, x, layers)
        x = _rms_norm_plain(x, tops["norm_w"], epsilon=cfg.rms_norm_eps)
        return self._head(x[:, -1], tops)[0], ks[:, 0], vs[:, 0]

    def _chunk_fwd(self, layers, tops, ids, pos0, k_pages, v_pages, pids,
                   past_len):
        """Chunked-prefill forward: ids [1, C] at positions
        ``pos0..pos0+C-1``.  The sequence's already-written KV is read
        from the pools (the form :meth:`PagedKVCache.pools` gives, not
        donated: this program writes no page) inside the layer scan:
        ``pids`` int32 [n] names the pages that cover it, each layer
        gathers its own ``[KV, P, D]`` by row of the flat pool
        (:func:`~..paged._past_of`; P = n * page_size, positions >=
        past_len masked, so ``pids`` may be padded with any valid page
        id).  Returns (last-position logits [V], chunk k [L,KV,C,D],
        chunk v [L,KV,C,D]).

        This is what lets the scheduler interleave one long prompt's
        prefill with in-flight decodes: each scheduler iteration runs
        ONE chunk, so a 10k-token prompt never stalls the decode batch
        for its whole prefill."""
        cfg = self.config
        nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        B, C = ids.shape
        P = pids.shape[0] * self.cache.page_size
        past_dt = self.cache.compute_dtype
        x = tops["embed"][ids]
        pos = pos0 + jnp.broadcast_to(jnp.arange(C)[None], (B, C))
        scale = 1.0 / np.sqrt(d)
        # past cols valid below past_len; chunk cols causal within chunk
        mask = jnp.concatenate(
            [jnp.broadcast_to((jnp.arange(P) < past_len)[None], (C, P)),
             jnp.tril(jnp.ones((C, C), bool))], axis=1)  # [C, P+C]

        def block(x, lp_layer):
            lp, layer = lp_layer
            pk = _past_of(k_pages, layer, pids, past_dt)
            pv = _past_of(v_pages, layer, pids, past_dt)
            h = _rms_norm_plain(x, lp["input_layernorm.weight"],
                                epsilon=cfg.rms_norm_eps)
            q = _mm(h, lp["self_attn.q_proj.weight"]) \
                .reshape(B, C, nh, d)
            k = _mm(h, lp["self_attn.k_proj.weight"]) \
                .reshape(B, C, nkv, d)
            v = _mm(h, lp["self_attn.v_proj.weight"]) \
                .reshape(B, C, nkv, d)
            q, k = _rope_plain(q, k, tops["cos"], tops["sin"],
                               position_ids=pos)
            g = nh // nkv
            qt = jnp.swapaxes(q, 1, 2)              # [B, nh, C, d]
            kt = jnp.swapaxes(k, 1, 2)              # [B, nkv, C, d]
            vt = jnp.swapaxes(v, 1, 2)
            kf = jnp.concatenate([pk[None].astype(kt.dtype), kt], axis=2)
            vf = jnp.concatenate([pv[None].astype(vt.dtype), vt], axis=2)
            if g > 1:                               # GQA: expand KV heads
                kf = jnp.repeat(kf, g, axis=1)
                vf = jnp.repeat(vf, g, axis=1)
            logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kf) * scale
            logits = jnp.where(mask[None, None], logits,
                               jnp.finfo(logits.dtype).min)
            p = jax.nn.softmax(logits.astype(jnp.float32), -1) \
                .astype(x.dtype)
            o = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
            o = jnp.swapaxes(o, 1, 2).reshape(B, C, nh * d)
            x = x + _mm(o, lp["self_attn.o_proj.weight"])
            h2 = _rms_norm_plain(x, lp["post_attention_layernorm.weight"],
                                 epsilon=cfg.rms_norm_eps)
            gate = _mm(h2, lp["mlp.gate_proj.weight"])
            up = _mm(h2, lp["mlp.up_proj.weight"])
            x = x + _mm(jax.nn.silu(gate) * up,
                        lp["mlp.down_proj.weight"])
            return x, (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))

        x, (ks, vs) = jax.lax.scan(
            block, x,
            (layers, jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)))
        x = _rms_norm_plain(x, tops["norm_w"], epsilon=cfg.rms_norm_eps)
        return self._head(x[:, -1], tops)[0], ks[:, 0], vs[:, 0]

    def _sp_chunk_fwd(self, layers, tops, ids, pos0, past_k, past_v,
                      past_len):
        """Sequence-parallel twin of :meth:`_chunk_fwd`: the chunk's
        ``C`` rows stripe contiguously across the mesh's sp axis (rank
        r owns rows ``[r*C/n, (r+1)*C/n)``), weights/past-KV stay
        replicated, and the outputs are the SAME (logits [V], chunk k/v
        [L, KV, C, D]) — k/v assembled sequence-sharded by the
        out_specs.

        Bit-identity with the single-device program is the design
        constraint (the off-gate, recovery and the prefix cache all
        compare token streams exactly), which rules out the training
        ring's online softmax: instead each rank ring-gathers the chunk
        K/V into canonical order (:func:`ring_gather_seq`, n-1 ppermute
        hops each for k and v) and runs the unmodified dense
        mask/softmax/PV math on its row stripe, so every per-(row, col)
        dot product — and every reduction order — is byte-for-byte the
        dense path's.  The final logits row lives on the last rank, so
        one ``all_gather`` of the last hidden row ends the program:
        total collective inventory exactly {ppermute: 2*(n-1),
        all_gather: 1}, which the registered contract pins.

        ``check_vma=False``: a plain ``all_gather`` leaves its output
        typed as varying over the axis, so shard_map's check cannot
        infer the replication ``out_specs`` declares for the logits."""
        rep = _P()
        mapped = jax.shard_map(
            self._sp_chunk_local, mesh=self._sp_jmesh,
            in_specs=(jax.tree.map(lambda _: rep, layers),
                      jax.tree.map(lambda _: rep, tops),
                      _P(None, self._sp_axis), rep, rep, rep, rep),
            out_specs=(rep, _P(None, None, self._sp_axis, None),
                       _P(None, None, self._sp_axis, None)),
            check_vma=False)
        return mapped(layers, tops, ids, pos0, past_k, past_v,
                      past_len)

    def _sp_chunk_local(self, layers, tops, ids, pos0, past_k, past_v,
                        past_len):
        """Per-rank body of :meth:`_sp_chunk_fwd`.  ``ids`` [1, C/n] is
        this rank's row stripe; everything else is replicated."""
        from ...distributed.ring_attention import ring_gather_seq

        cfg = self.config
        nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        axis, n = self._sp_axis, self._sp_n
        B, Cl = ids.shape
        C = Cl * n
        P = past_k.shape[2]
        r = jax.lax.axis_index(axis)
        x = tops["embed"][ids]
        rows = r * Cl + jnp.arange(Cl)               # global row ids
        pos = pos0 + jnp.broadcast_to(rows[None], (B, Cl))
        scale = 1.0 / np.sqrt(d)
        # same mask as _chunk_fwd, restricted to this rank's rows:
        # past cols valid below past_len; chunk cols causal globally
        mask = jnp.concatenate(
            [jnp.broadcast_to((jnp.arange(P) < past_len)[None],
                              (Cl, P)),
             rows[:, None] >= jnp.arange(C)[None]], axis=1)

        def block(x, lp_kv):
            lp, pk, pv = lp_kv
            h = _rms_norm_plain(x, lp["input_layernorm.weight"],
                                epsilon=cfg.rms_norm_eps)
            q = _mm(h, lp["self_attn.q_proj.weight"]) \
                .reshape(B, Cl, nh, d)
            k = _mm(h, lp["self_attn.k_proj.weight"]) \
                .reshape(B, Cl, nkv, d)
            v = _mm(h, lp["self_attn.v_proj.weight"]) \
                .reshape(B, Cl, nkv, d)
            q, k = _rope_plain(q, k, tops["cos"], tops["sin"],
                               position_ids=pos)
            g = nh // nkv
            qt = jnp.swapaxes(q, 1, 2)              # [B, nh, Cl, d]
            kt = jnp.swapaxes(k, 1, 2)              # [B, nkv, Cl, d]
            vt = jnp.swapaxes(v, 1, 2)
            # every rank needs every chunk key: ring-gather the K/V
            # stripes into canonical order (the bit-exact alternative
            # to streaming blocks through an online softmax)
            ktf = ring_gather_seq(kt, axis, n)      # [B, nkv, C, d]
            vtf = ring_gather_seq(vt, axis, n)
            kf = jnp.concatenate([pk[None].astype(ktf.dtype), ktf],
                                 axis=2)
            vf = jnp.concatenate([pv[None].astype(vtf.dtype), vtf],
                                 axis=2)
            if g > 1:                               # GQA: expand KV heads
                kf = jnp.repeat(kf, g, axis=1)
                vf = jnp.repeat(vf, g, axis=1)
            logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kf) * scale
            logits = jnp.where(mask[None, None], logits,
                               jnp.finfo(logits.dtype).min)
            p = jax.nn.softmax(logits.astype(jnp.float32), -1) \
                .astype(x.dtype)
            o = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
            o = jnp.swapaxes(o, 1, 2).reshape(B, Cl, nh * d)
            x = x + _mm(o, lp["self_attn.o_proj.weight"])
            h2 = _rms_norm_plain(x, lp["post_attention_layernorm.weight"],
                                 epsilon=cfg.rms_norm_eps)
            gate = _mm(h2, lp["mlp.gate_proj.weight"])
            up = _mm(h2, lp["mlp.up_proj.weight"])
            x = x + _mm(jax.nn.silu(gate) * up,
                        lp["mlp.down_proj.weight"])
            return x, (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))

        x, (ks, vs) = jax.lax.scan(block, x, (layers, past_k, past_v))
        x = _rms_norm_plain(x, tops["norm_w"], epsilon=cfg.rms_norm_eps)
        # the chunk's last row lives on the last rank: one all_gather
        # of the final hidden row, then every rank computes the same
        # replicated logits (the head matmul is cheap at [1, V])
        last = jax.lax.all_gather(x[:, -1], axis)     # [n, B, h]
        return self._head(last[n - 1], tops)[0], ks[:, 0], vs[:, 0]

    def _paged_layers(self, layers, tops, x, pos, k_pages, v_pages, pids,
                      offs, lens, tables):
        """The layer stack of the decode and verify programs: x [B, W, h]
        holds W new tokens a sequence at positions ``pos`` [B, W].  Each
        layer writes their K/V into slot ``offs`` of page ``pids`` (both
        [B, W]; a page id of ``num_pages`` is dropped) and then attends
        (write-then-attend, so the self term is in the pool): row (b, w)
        reads ``lens[b * W + w]`` keys through ``tables`` [B * W, pps].
        Returns (x, k_pages', v_pages').

        Plain pools ``[L, KV, P, ps, D]`` are a CARRY of the scan, never
        sliced, stacked or copied: a token goes in by whole pages of the
        pool viewed flat (:func:`~..paged._put_token`, once per window
        position, since a window's tokens share pages) and the attention
        addresses the layer in place (``paged_decode_attention(..,
        layer=layer)``).  Pools that are scanned inputs and stacked
        outputs are copied by the TPU compiler, layer by layer and then
        whole (PERF.md section 6, PR 29).  An int8 pool is a ``(pages,
        scales)`` tuple and is scanned that way still: its write grows a
        page's scale (``ops.quant.kv_write``) and its kernel takes one
        layer's pool."""
        cfg = self.config
        nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        B, W = pos.shape

        def qkv(x, lp):
            h = _rms_norm_plain(x, lp["input_layernorm.weight"],
                                epsilon=cfg.rms_norm_eps)
            q = _mm(h, lp["self_attn.q_proj.weight"]) \
                .reshape(B, W, nh, d)
            k = _mm(h, lp["self_attn.k_proj.weight"]) \
                .reshape(B, W, nkv, d)
            v = _mm(h, lp["self_attn.v_proj.weight"]) \
                .reshape(B, W, nkv, d)
            q, k = _rope_plain(q, k, tops["cos"], tops["sin"],
                               position_ids=pos)
            return q.reshape(B * W, nh, d), k, v

        def rest(x, o, lp):
            o = o.reshape(B, W, nh * d).astype(x.dtype)
            x = x + _mm(o, lp["self_attn.o_proj.weight"])
            h2 = _rms_norm_plain(x, lp["post_attention_layernorm.weight"],
                                 epsilon=cfg.rms_norm_eps)
            gate = _mm(h2, lp["mlp.gate_proj.weight"])
            up = _mm(h2, lp["mlp.up_proj.weight"])
            return x + _mm(jax.nn.silu(gate) * up,
                           lp["mlp.down_proj.weight"])

        if isinstance(k_pages, tuple):
            pids_f, offs_f = pids.reshape(-1), offs.reshape(-1)

            def block_q(x, lp_kv):
                lp, kp, vp = lp_kv
                q, k, v = qkv(x, lp)
                # quantize the new tokens on write (scale grow +
                # resident requant; kv_write drops the sentinel page id
                # like the plain patch), attend with the scales
                kp = _quant.kv_write(
                    *kp, pids_f, offs_f,
                    jnp.swapaxes(k.reshape(B * W, nkv, d), 0, 1))
                vp = _quant.kv_write(
                    *vp, pids_f, offs_f,
                    jnp.swapaxes(v.reshape(B * W, nkv, d), 0, 1))
                o = paged_decode_attention(
                    q, kp[0], vp[0], lens, tables,
                    k_scales=kp[1], v_scales=vp[1])
                return rest(x, o, lp), (kp, vp)

            x, (kps, vps) = jax.lax.scan(
                block_q, x, (layers, k_pages, v_pages))
            return x, kps, vps

        shape = k_pages.shape

        def block(carry, lp_layer):
            x, kf, vf = carry
            lp, layer = lp_layer
            q, k, v = qkv(x, lp)
            for w in range(W):
                kf = _put_token(kf, shape, layer, pids[:, w], offs[:, w],
                                k[:, w])
                vf = _put_token(vf, shape, layer, pids[:, w], offs[:, w],
                                v[:, w])
            o = paged_decode_attention(
                q, kf.reshape(shape), vf.reshape(shape), lens, tables,
                layer=layer)                          # [B * W, nh, d]
            return (rest(x, o, lp), kf, vf), None

        (x, kf, vf), _ = jax.lax.scan(
            block, (x, _flat(k_pages), _flat(v_pages)),
            (layers, jnp.arange(shape[0], dtype=jnp.int32)))
        return x, kf.reshape(shape), vf.reshape(shape)

    def _decode_fwd(self, layers, tops, ids, positions, k_pages, v_pages,
                    lengths, page_tables):
        """One token per active sequence: ids [B], positions [B] (the
        token's position).  Each layer writes the new token's KV into
        its page, then attends over lengths+1 keys of the pool
        (:meth:`_paged_layers`, the window of one token); the donated
        pools come back updated in place.
        Returns (logits [B, V], k_pages', v_pages')."""
        ps = self.cache.page_size
        B = ids.shape[0]
        pids = page_tables[jnp.arange(B), positions // ps]  # [B]
        x, kps, vps = self._paged_layers(
            layers, tops, tops["embed"][ids][:, None], positions[:, None],
            k_pages, v_pages, pids[:, None], (positions % ps)[:, None],
            lengths + 1, page_tables)
        x = _rms_norm_plain(x, tops["norm_w"],
                            epsilon=self.config.rms_norm_eps)
        return self._head(x[:, 0], tops), kps, vps

    def _decode_tok_fwd(self, layers, tops, ids, positions, k_pages,
                        v_pages, lengths, page_tables):
        """:meth:`_decode_fwd` with the greedy argmax folded in-graph
        (the spec-verify program already does this): the async executor
        keeps the step's entire host sync down to one int32 [B]
        transfer at the commit fence.  Returns (tokens [B], k_pages',
        v_pages')."""
        logits, kps, vps = self._decode_fwd(
            layers, tops, ids, positions, k_pages, v_pages, lengths,
            page_tables)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), kps, vps

    def _verify_fwd(self, layers, tops, ids, k_pages, v_pages, lengths,
                    page_tables, limits):
        """Speculative-verify forward: every running sequence's draft
        window in ONE program.  ``ids`` [B, W] is each sequence's last
        committed token followed by its (padded) draft; window token w
        sits at position ``lengths[b] + w``.  ``limits`` [B] caps how
        many window tokens each sequence may commit (page budget /
        length cap / actual draft length), 1 <= limit <= W.

        Write-then-attend like _decode_fwd, widened to the window
        (:meth:`_paged_layers`): each layer patches all valid window KV
        into the pages (positions past a sequence's limit get the page
        id ``num_pages`` and are dropped), then attends with B*W query
        rows through the SAME paged_decode_attention — row (b, w) masked
        to lengths[b]+w+1 keys, so causality inside the window comes
        from the length mask, not a new kernel.

        Greedy acceptance in-graph: with t = argmax(logits) per window
        position, draft token w+1 is accepted iff every earlier draft
        token matched the model's choice — so the committed stream is
        bit-identical to plain greedy decode by construction.  The
        ragged accepted prefixes are packed with one variadic
        ``lax.sort`` (the MoE-dispatch trick): valid (b, w) cells keep
        their rank key, invalid cells sort to the tail, and the host
        reads ONE dense token vector + per-seq counts — no [B, k] host
        loop anywhere.

        Returns (packed_tokens [B*W], emit_n [B], k_pages', v_pages').
        """
        ps = self.cache.page_size
        B, W = ids.shape
        pps = page_tables.shape[1]
        num_pages = (k_pages[0] if isinstance(k_pages, tuple)
                     else k_pages).shape[2]
        w = jnp.arange(W, dtype=jnp.int32)[None]
        pos = lengths[:, None] + w                     # [B, W]
        slot = pos // ps
        pids = jnp.take_along_axis(page_tables,
                                   jnp.minimum(slot, pps - 1), axis=1)
        # invalid window cells (past the commit limit, or past the
        # per-seq page budget) get the page id that every write drops
        valid_w = (w < limits[:, None]) & (slot < pps)
        # one attention row per window cell; the +w+1 length mask is
        # the in-window causal mask
        x, kps, vps = self._paged_layers(
            layers, tops, tops["embed"][ids], pos, k_pages, v_pages,
            jnp.where(valid_w, pids, num_pages), pos % ps,
            (pos + 1).reshape(-1), jnp.repeat(page_tables, W, axis=0))
        x = _rms_norm_plain(x, tops["norm_w"],
                            epsilon=self.config.rms_norm_eps)
        t = jnp.argmax(self._head(x, tops), -1).astype(jnp.int32)
        # accepted = longest prefix of drafts matching the model's own
        # greedy choices; always commit 1 + accepted (the model's next
        # token after the accepted run), clamped to the per-seq limit
        m = (ids[:, 1:] == t[:, :-1]).astype(jnp.int32)
        acc = jnp.sum(jnp.cumprod(m, axis=1), axis=1)
        emit_n = jnp.minimum(acc + 1, limits)
        rank = jnp.arange(B * W, dtype=jnp.int32).reshape(B, W)
        key = jnp.where(jnp.arange(W)[None] < emit_n[:, None],
                        rank, B * W).reshape(-1)
        _, packed = jax.lax.sort((key, t.reshape(-1)), num_keys=1,
                                 is_stable=True)
        return packed, emit_n, kps, vps

    def _decode_n_fwd(self, layers, tops, ids, positions, k_pages,
                      v_pages, lengths, page_tables, n):
        """``n`` greedy steps in ONE dispatched program: the argmax
        feedback stays on device (greedy needs no host), so the
        per-token dispatch + fetch cost is amortized n ways — the decode
        analog of CompiledTrainStep.multi_step."""

        def body(carry, _):
            ids, positions, kp, vp, lengths = carry
            logits, kp, vp = self._decode_fwd(
                layers, tops, ids, positions, kp, vp, lengths,
                page_tables)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (nxt, positions + 1, kp, vp, lengths + 1), nxt

        carry, toks = jax.lax.scan(
            body, (ids, positions, k_pages, v_pages, lengths), None,
            length=n)
        _ids, _pos, kp, vp, _len = carry
        return toks, kp, vp

    # -- slot-granular control plane ------------------------------------

    @property
    def free_slots(self) -> int:
        return self.cache.free_slots

    @property
    def free_pages(self) -> int:
        return self.cache.free_pages

    def pages_for(self, tokens: int) -> int:
        return -(-int(tokens) // self.cache.page_size)

    def alloc_slot(self) -> int:
        return self.cache.allocate()

    def free_slot(self, sid: int) -> None:
        self.cache.free(sid)
        self.last_token.pop(sid, None)
        self._sp_written.discard(sid)

    def attach_prefix(self, sid: int, page_ids, n_tokens: int) -> None:
        """Point a fresh slot's page table at already-computed prefix
        pages (cache hit): chunked prefill then starts at token
        ``n_tokens`` instead of 0."""
        self.cache.attach(sid, page_ids, n_tokens)

    def prepare_write(self, sid: int, start: int, n_tokens: int) -> None:
        """Pre-commit the page work for a prefill chunk covering
        positions [start, start + n_tokens): allocate missing pages
        (prefix eviction is tried before pool-exhausted) and
        copy-on-write any shared page in the window.  The scheduler
        calls this BEFORE its per-request fault bracket so a pool raise
        or an injected ``prefix.cow`` fault preempts/retries cleanly
        instead of failing the request."""
        self.cache._ensure_capacity(sid, start + n_tokens)
        self.cache.make_writable(sid, start, start + n_tokens)

    def prefill(self, sid: int, prompt_ids) -> int:
        """Whole-prompt prefill into an allocated slot; returns the
        first greedy token."""
        if self._aot_sealed:
            from ...core.aot import AotMissError

            raise AotMissError(
                "[serve.prefill] PT_AOT=strict: whole-prompt prefill "
                "has an unbounded [1, S] shape and cannot be warmed — "
                "the scheduler routes prompts through prefill_chunk's "
                "bucket ladder instead")
        with self.handoff.prep(tokens=len(prompt_ids)) as io:
            ids = io.put(np.asarray(prompt_ids)[None], jnp.int32)
        self.prefill_events.append((sid, int(ids.shape[1])))
        logits, k, v = self._jit_prefill(self.layers, self.tops, ids)
        self.cache.prefill(sid, k, v)
        tok = int(io.fetch("prefill", lambda: io.argmax(logits)))
        self.last_token[sid] = tok
        return tok

    def prefill_chunk(self, sid: int, chunk_ids, start: int,
                      final: bool) -> int | None:
        """One prefill chunk at position ``start``; attends the slot's
        already-written pages, which the program reads from the pools
        itself: the host hands it their ids, dispatches it, and then
        the page writer.  When ``final``, records and returns the
        prompt's first greedy token; else returns None."""
        cache = self.cache
        with self.handoff.prep(tokens=len(chunk_ids)) as io:
            ids = np.asarray(chunk_ids, np.int32)[None]
            pids = cache.past_pages(sid, start)
            if self.aot_ladder is not None:
                # bucket the past's page cover so its shape comes from
                # the finite warmup set: pad with a valid page id — the
                # in-graph `arange(P) < past_len` mask drops the padded
                # columns entirely, so numerics are exact
                from ...core.aot import bucket_pages

                b = bucket_pages(len(pids), self._aot_page_buckets)
                pids = np.pad(pids, (0, max(b - len(pids), 0)))
            at = np.int32(start)
            io.host(ids, at, pids, at)
            kp, vp = cache.pools()
        self.prefill_events.append((sid, int(ids.shape[1])))
        # the past of an int8 pool is dequantized inside the program:
        # the chaos tests' bracket around that read is the dispatch
        int8 = self.quant == "int8"
        if int8:
            _faults.fire("quant.dequant", "before")
        logits, k, v = self._jit_chunk(
            self.layers, self.tops, ids, at, kp, vp, pids, at)
        if int8:
            _faults.fire("quant.dequant", "after")
        cache.write_at(sid, k, v, start)
        if not final:
            return None
        if sid in self._sp_written:
            # earlier chunks of this prompt landed range-sharded: the
            # prefill->decode page gather still belongs to THIS
            # transition even though the last (short) chunk ran dense
            self.cache.gather_shards(sid)
            self._sp_written.discard(sid)
        tok = int(io.fetch("prefill_chunk", lambda: io.argmax(logits)))
        self.last_token[sid] = tok
        return tok

    def prefill_sp(self, sid: int, chunk_ids, start: int,
                   final: bool) -> int | None:
        """One SEQUENCE-PARALLEL prefill chunk at position ``start``:
        the chunk stripes across the mesh (serve.prefill_sp), its KV
        lands in the pool as per-rank ranges (``write_sharded``), and
        the final chunk all-gathers the pages once so decode runs
        byte-identical to the single-device path.  Same signature and
        same results as :meth:`prefill_chunk` — the scheduler swaps
        one for the other above the length threshold."""
        n = self.sp_degree
        # stripes of a single row hit XLA's matrix-VECTOR matmul path,
        # whose accumulation order differs from the gemm the dense
        # program runs — measurably (1e-6) non-bit-identical on CPU.
        # A chunk must give every rank >= 2 rows; anything smaller
        # takes the single-device program (same results by definition).
        if n <= 1 or int(np.shape(chunk_ids)[0]) < 2 * n:
            return self.prefill_chunk(sid, chunk_ids, start, final)
        past_k, past_v = self.cache.gather_dense(sid, start)
        if self.aot_ladder is not None:
            # page-bucket the past cover exactly like prefill_chunk:
            # the in-graph past_len mask zeroes the padding
            from ...core.aot import bucket_pages

            ps = self.cache.page_size
            pages = past_k.shape[2] // ps
            b = bucket_pages(pages, self._aot_page_buckets)
            if b > pages:
                pad = ((0, 0), (0, 0), (0, (b - pages) * ps), (0, 0))
                past_k = jnp.pad(past_k, pad)
                past_v = jnp.pad(past_v, pad)
        ids = jnp.asarray(np.asarray(chunk_ids)[None], jnp.int32)
        C = int(ids.shape[1])
        if C % n:
            raise ValueError(
                f"sp prefill chunk of {C} tokens does not stripe over "
                f"{n} ranks — the scheduler must plan sp chunks on "
                f"rank-divisible rungs")
        self.prefill_events.append((sid, C))
        # placement bracket: the pool (and everything derived from it,
        # like the gathered past) lives on the scheduler's home device,
        # while the shard_map program computes over the mesh's device
        # set — committed single-device operands would be refused.  The
        # past-KV broadcast IN and the chunk-KV landing OUT are exactly
        # the per-chunk transfers a range-sharded sp prefill pays, made
        # explicit here so the pool's own placement never changes and
        # the dense programs (plain jit AND rigid AOT-compiled
        # executables) keep their single-device signatures.
        rep = jax.NamedSharding(self._sp_jmesh, _P())
        past_k = jax.device_put(past_k, rep)
        past_v = jax.device_put(past_v, rep)
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            logits, k, v = self._jit_chunk_sp(
                self.layers, self.tops, ids, jnp.int32(start), past_k,
                past_v, jnp.int32(start))
        k = jax.device_put(k, self.cache.k_pages.sharding)
        v = jax.device_put(v, self.cache.v_pages.sharding)
        self.cache.write_sharded(sid, k, v, start, n)
        self._sp_written.add(sid)
        self.sp_prefill_tokens += C
        h = obs.handle()
        if h is not None:
            h.registry.counter(
                "sp_prefill_tokens_total",
                "prompt tokens prefilled sequence-parallel over the "
                "mesh",
            ).inc(C)
        if not final:
            return None
        self.cache.gather_shards(sid)
        self._sp_written.discard(sid)
        io = self.handoff
        tok = int(io.fetch("prefill_sp", lambda: io.argmax(logits)))
        self.last_token[sid] = tok
        return tok

    def decode(self, sids) -> dict:
        """One greedy decode step over an explicit batch of slots.
        Returns {sid: next_token}."""
        sids = list(sids)
        if not sids:
            return {}
        cache = self.cache
        # how much of the window the fused kernel's block loop visits:
        # blocks that hold one of the lengths + 1 keys a sequence reads,
        # of the blocks of every window
        block = self._decode_block
        with self.handoff.prep(
                batch=len(sids),
                blocks=int((cache.lengths[sids] // block + 1).sum()),
                window_blocks=len(sids) * -(
                    -cache.max_pages_per_seq * cache.page_size
                    // block)) as io:
            # batch-atomic page reservation BEFORE the jitted
            # write-then-attend: a per-sequence loop would strand
            # earlier sequences' fresh pages when a later one exhausts
            # the pool
            cache.reserve(sids, extra_tokens=1)
            # the in-graph page write must never land on a shared page
            for s in sids:
                pos = int(cache.lengths[s])
                cache.make_writable(s, pos, pos + 1)
            ids = io.put([self.last_token[s] for s in sids], jnp.int32)
            positions = io.put([int(cache.lengths[s]) for s in sids],
                               jnp.int32)
            tables = io.put(np.maximum(cache.page_table[sids], 0))
            lengths = io.put(cache.lengths[sids])
            kp, vp = self.cache.pools()
        logits, kps, vps = self._jit_decode(
            self.layers, self.tops, ids, positions, kp, vp, lengths,
            tables)
        self.cache.set_pools(kps, vps)
        for s in sids:
            cache.lengths[s] += 1
        # single batched argmax + ONE host transfer for the whole step
        toks = io.fetch("decode", lambda: io.argmax(logits, axis=-1))
        out = {}
        for i, s in enumerate(sids):
            tok = int(toks[i])
            self.last_token[s] = tok
            out[s] = tok
        return out

    def decode_async(self, sids) -> _PendingDecode:
        """Dispatch one greedy decode step WITHOUT realizing the
        result.  All page work and the length bookkeeping happen now —
        so the scheduler can plan the NEXT step against post-step
        lengths while the device runs — and the returned pending
        object's :meth:`~_PendingDecode.wait` is the step's only host
        sync point (one transfer, last-token updates)."""
        sids = list(sids)
        if not sids:
            return _PendingDecode(self, [], np.zeros((0,), np.int32))
        cache = self.cache
        cache.reserve(sids, extra_tokens=1)
        for s in sids:
            pos = int(cache.lengths[s])
            cache.make_writable(s, pos, pos + 1)
        ids = jnp.asarray([self.last_token[s] for s in sids], jnp.int32)
        positions = jnp.asarray([int(cache.lengths[s]) for s in sids],
                                jnp.int32)
        tables = jnp.asarray(np.maximum(cache.page_table[sids], 0))
        lengths = jnp.asarray(cache.lengths[sids])
        kp, vp = self.cache.pools()
        toks, kps, vps = self._jit_decode_async(
            self.layers, self.tops, ids, positions, kp, vp, lengths,
            tables)
        self.cache.set_pools(kps, vps)
        for s in sids:
            cache.lengths[s] += 1
        return _PendingDecode(self, sids, toks)

    def verify(self, sids, drafts, limits, k):
        """Speculative decode step: run each listed slot's draft window
        through one jitted verify forward and commit the longest
        model-agreed prefix plus the model's own next token.

        ``drafts`` and ``limits`` align with ``sids``: up to ``k``
        proposed tokens and the per-seq commit cap (>= 1; the caller
        clamps it to the page budget, the remaining generation cap and
        the actual draft length).  Returns ({sid: [tokens...]},
        {sid: accepted_draft_tokens}); every sequence advances by
        1 + accepted tokens, exactly the greedy stream.
        """
        sids = list(sids)
        if not sids:
            return {}, {}
        cache = self.cache
        W = int(k) + 1
        limits = [int(x) for x in limits]
        # batch-atomic per-seq lookahead reservation, then the COW
        # guard over each window — same write discipline as decode()
        cache.reserve(sids, extra_tokens=limits)
        for s, lim in zip(sids, limits):
            pos = int(cache.lengths[s])
            cache.make_writable(s, pos, pos + lim)
        ids = np.zeros((len(sids), W), np.int32)
        for i, (s, dr) in enumerate(zip(sids, drafts)):
            ids[i, 0] = self.last_token[s]
            dr = np.asarray(dr, np.int32).reshape(-1)[:k]
            ids[i, 1:1 + len(dr)] = dr
        tables = jnp.asarray(np.maximum(cache.page_table[sids], 0))
        lengths = jnp.asarray(cache.lengths[sids])
        kp, vp = self.cache.pools()
        packed, emit_n, kps, vps = self._jit_verify(
            self.layers, self.tops, jnp.asarray(ids), kp, vp, lengths,
            tables, jnp.asarray(limits, jnp.int32))
        self.cache.set_pools(kps, vps)
        # ONE host transfer: the sort-packed token block + counts;
        # splitting it is per-SEQUENCE host work, never per-token-cell
        packed = np.asarray(packed)
        counts = np.asarray(emit_n)
        out, accepted = {}, {}
        off = 0
        for i, s in enumerate(sids):
            n = int(counts[i])
            toks = [int(t) for t in packed[off:off + n]]
            off += n
            cache.lengths[s] += n
            self.last_token[s] = toks[-1]
            out[s] = toks
            accepted[s] = n - 1
        return out, accepted

    def verify_async(self, sids, drafts, limits, k) -> _PendingVerify:
        """:meth:`verify` split at its one natural sync point: the
        jitted window verification is dispatched here (pages reserved,
        windows COW'd, KV written in-graph), and the packed-token /
        count transfers plus all length bookkeeping move into the
        returned pending object's :meth:`~_PendingVerify.wait`."""
        sids = list(sids)
        if not sids:
            return _PendingVerify(self, [], np.zeros((0,), np.int32),
                                  np.zeros((0,), np.int32))
        cache = self.cache
        W = int(k) + 1
        limits = [int(x) for x in limits]
        cache.reserve(sids, extra_tokens=limits)
        for s, lim in zip(sids, limits):
            pos = int(cache.lengths[s])
            cache.make_writable(s, pos, pos + lim)
        ids = np.zeros((len(sids), W), np.int32)
        for i, (s, dr) in enumerate(zip(sids, drafts)):
            ids[i, 0] = self.last_token[s]
            dr = np.asarray(dr, np.int32).reshape(-1)[:k]
            ids[i, 1:1 + len(dr)] = dr
        tables = jnp.asarray(np.maximum(cache.page_table[sids], 0))
        lengths = jnp.asarray(cache.lengths[sids])
        kp, vp = self.cache.pools()
        packed, emit_n, kps, vps = self._jit_verify(
            self.layers, self.tops, jnp.asarray(ids), kp, vp, lengths,
            tables, jnp.asarray(limits, jnp.int32))
        self.cache.set_pools(kps, vps)
        return _PendingVerify(self, sids, packed, emit_n)

    def rollback(self, sids) -> int:
        """Release pages reserved for rejected draft positions: trim
        every listed slot's page table back to its committed length.
        Returns total pages released."""
        freed = sum(self.cache.trim(s) for s in sids)
        self.rollback_pages += freed
        return freed

    def decode_n(self, sids, n) -> dict:
        """``n`` greedy tokens per listed slot in one dispatch.
        Returns {sid: [tok_1..tok_n]}.  Pages for all n tokens are
        reserved up front (batch-atomic), so the in-graph page writes
        can never overflow a sequence's table."""
        sids = list(sids)
        if not sids:
            return {}
        cache = self.cache
        cache.reserve(sids, extra_tokens=n)
        for s in sids:
            pos = int(cache.lengths[s])
            cache.make_writable(s, pos, pos + n)
        ids = jnp.asarray([self.last_token[s] for s in sids], jnp.int32)
        positions = jnp.asarray([int(cache.lengths[s]) for s in sids],
                                jnp.int32)
        tables = jnp.asarray(np.maximum(cache.page_table[sids], 0))
        lengths = jnp.asarray(cache.lengths[sids])
        kp, vp = self.cache.pools()
        toks, kps, vps = self._jit_decode_n(
            self.layers, self.tops, ids, positions, kp, vp, lengths,
            tables, n=int(n))
        self.cache.set_pools(kps, vps)
        toks = np.asarray(toks)                     # [n, B]
        out = {}
        for i, s in enumerate(sids):
            cache.lengths[s] += n
            self.last_token[s] = int(toks[-1, i])
            out[s] = toks[:, i].tolist()
        return out
