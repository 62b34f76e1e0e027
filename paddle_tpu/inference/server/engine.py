"""User-facing continuous-batching serving engine.

Single-threaded by design: ``submit()`` enqueues, ``step()`` runs one
scheduler iteration, and handles pull results by driving ``step()``
themselves.  This keeps every test deterministic (the logical clock IS
the iteration count) while the control flow matches what a threaded
front-end would do per tick.

    engine = ServingEngine(model, max_seqs=4, page_size=16)
    h = engine.submit(prompt_ids, max_new_tokens=32)
    for tok in h.stream():   # drives engine.step() under the hood
        ...
    engine.stats()           # SLO metrics dict
"""
from __future__ import annotations

import os

import jax.numpy as jnp

from .executor import PagedExecutor, _sp_prefill_enabled
from .hybrid_executor import HybridExecutor
from .latent_executor import LatentExecutor
from .metrics import EngineMetrics
from .prefix_cache import PrefixCache
from .request import Request, RequestHandle, RequestState
from .scheduler import Scheduler
from .spec_decode import SpecDecode, spec_mode
from .wal import resolve_wal, wal_enabled
from .window_executor import WindowExecutor


def _prefix_cache_enabled() -> bool:
    mode = os.environ.get("PT_PREFIX_CACHE", "off").lower()
    if mode not in ("off", "on"):
        raise ValueError(
            f"PT_PREFIX_CACHE={mode!r}: expected off|on")
    return mode == "on"


def _async_exec_enabled() -> bool:
    mode = os.environ.get("PT_ASYNC_EXEC", "off").lower()
    if mode not in ("off", "on"):
        raise ValueError(
            f"PT_ASYNC_EXEC={mode!r}: expected off|on")
    return mode == "on"


#: why the engine's optional features are refused, by the kind of state
#: a composed executor keeps
_RECURRENT = ("a model with recurrent (state-space) layers: its "
              "per-sequence state is one row of the recurrent-state cache, "
              "which cannot be attached by reference, rolled back, handed "
              "over or quantised like KV pages")
_LATENT = ("a model with latent attention (MLA) layers: its pages hold "
           "one compressed row a token and no K or V heads, which the "
           "prefix index, the verify, decode_n, sequence-parallel, int8, "
           "AOT and recovery programs of the paged executor do not read")


_WINDOWED = ("a model that mixes sliding-window and full attention layers: "
             "its cache releases the pages behind the window, so a page "
             "cannot be attached by the prefix index, rolled back after a "
             "verify, handed over or replayed, and its two pools are not "
             "what the decode_n, sequence-parallel, int8 and AOT programs "
             "of the paged executor read")


def _refuse(wanted: dict, why: str) -> None:
    """Every feature that assumes "state = pages of K and V heads" is
    refused when the engine is built, by name, never run wrong."""
    asked = [name for name, on in wanted.items() if on]
    if asked:
        raise NotImplementedError(
            f"ServingEngine: {', '.join(asked)} not supported for {why}")


class ServingEngine:
    def __init__(self, model, max_seqs=4, page_size=16, max_len=256,
                 dtype=jnp.float32, num_pages=None, policy="fifo",
                 prefill_chunk=None, eos_token_id=None,
                 max_preemptions=4, prefix_cache=None,
                 spec_decode=None, clock=None, slos=None,
                 slo_rules=None, async_exec=None, aot=None,
                 compile_cache=None, decode_n_steps=(), quant=None,
                 wal=None, sp_mesh=None, sp_prefill=None,
                 sp_min_tokens=None, sp_axis=None):
        # quant: None = follow PT_QUANT (default none, bit-exact legacy
        # path); "none"/"int8" force it (tests).  int8 = per-channel
        # int8 projection weights + per-page int8 KV pools.
        # sp_prefill: None = follow PT_SP_PREFILL (default off,
        # bit-exact legacy path); True/False force it.  On, prompts at
        # or above sp_min_tokens (PT_SP_PREFILL_MIN_TOKENS) prefill
        # sequence-parallel over sp_mesh's sp axis (default: a 1-D
        # mesh over every local device).
        # the programs follow the model's layer kinds: a model with
        # state-space layers gets the hybrid executor (a recurrent-state
        # cache beside the paged KV pool), one with latent attention
        # layers the latent executor (a latent page pool), one that mixes
        # sliding-window and full attention layers the window executor
        # (a cache of two layer groups), behind the same slot interface
        kinds = set(getattr(model.config, "layer_types", ()))
        recurrent = "mamba" in kinds
        latent = bool(kinds & {"mla_dense", "mla_moe"})
        windowed = "sliding_attention" in kinds
        if recurrent or latent or windowed:
            from paddle_tpu.core import aot as _aot
            from paddle_tpu.ops import quant as _quant

            _refuse({
                "prefix cache": (_prefix_cache_enabled()
                                 if prefix_cache is None else prefix_cache),
                "speculative decoding": (
                    spec_mode() == "ngram" if spec_decode is None
                    else spec_decode not in (False, "off")),
                "async execution": (_async_exec_enabled()
                                    if async_exec is None else async_exec),
                "decode_n": bool(decode_n_steps),
                "sequence-parallel prefill": (
                    _sp_prefill_enabled() if sp_prefill is None
                    else sp_prefill),
                "int8 quantisation": _quant.quant_mode(quant) != "none",
                "AOT warm-up": (_aot.mode() if aot is None
                                else aot) != "off",
                "write-ahead log": (wal_enabled() if wal is None
                                    else wal is not False),
            }, _RECURRENT if recurrent else _LATENT if latent else _WINDOWED)
            # a window row holds the window and one chunk: its executor
            # is told the chunk
            more = {"prefill_chunk": prefill_chunk} if windowed else {}
            self.executor = (HybridExecutor if recurrent else LatentExecutor
                             if latent else WindowExecutor)(
                model, max_seqs=max_seqs, page_size=page_size,
                max_len=max_len, dtype=dtype, num_pages=num_pages, **more)
        else:
            self.executor = PagedExecutor(
                model, max_seqs=max_seqs, page_size=page_size,
                max_len=max_len, dtype=dtype, num_pages=num_pages,
                quant=quant, sp_mesh=sp_mesh, sp_prefill=sp_prefill,
                sp_min_tokens=sp_min_tokens, sp_axis=sp_axis)
        # clock: injectable wall-clock source for the SLO metrics and
        # per-request timestamps (default time.perf_counter; seeded
        # tests pass obs.LogicalClock() for exact ms percentiles)
        self.metrics = EngineMetrics(
            max_seqs=max_seqs, num_pages=self.executor.cache.num_pages,
            clock=clock)
        # prefix_cache: None = follow PT_PREFIX_CACHE (default off,
        # bit-exact legacy path); True/False force it (tests)
        if prefix_cache is None:
            prefix_cache = _prefix_cache_enabled()
        self.prefix = None
        if prefix_cache:
            self.prefix = PrefixCache(
                self.executor.cache,
                on_evict=self.metrics.on_prefix_evict)
            # allocation shortfalls try LRU eviction of cold cached
            # pages before raising pool-exhausted (eviction is cheaper
            # than preempt-and-recompute)
            self.executor.cache.reclaimer = self.prefix.evict
        # spec_decode: None = follow PT_SPEC_DECODE (default off,
        # bit-exact legacy path); "off"/"ngram" or False/True force it
        # (tests).  "ngram" drafts from each request's own
        # prompt+generated history — no second model.
        if spec_decode is None:
            spec_decode = spec_mode() == "ngram"
        elif isinstance(spec_decode, str):
            if spec_decode not in ("off", "ngram"):
                raise ValueError(
                    f"spec_decode={spec_decode!r}: expected off|ngram")
            spec_decode = spec_decode == "ngram"
        self.spec = SpecDecode() if spec_decode else None
        # async_exec: None = follow PT_ASYNC_EXEC (default off,
        # bit-exact legacy path); True/False force it (tests).
        # On = double-buffered steps: unrealized dispatch, next-step
        # planning overlapped behind the device, commit at the fence.
        if async_exec is None:
            async_exec = _async_exec_enabled()
        # wal: None = follow PT_WAL (default off, bit-exact legacy
        # path); False forces off (a cluster passes its own shared
        # journal or False so engines never double-resolve the env);
        # a path/WriteAheadLog forces on (tests, recovery).
        self.wal = resolve_wal(wal)
        self.dedup_hits = 0
        self.scheduler = Scheduler(
            self.executor, self.metrics, policy=policy,
            prefill_chunk=prefill_chunk, eos_token_id=eos_token_id,
            max_preemptions=max_preemptions, prefix_cache=self.prefix,
            spec=self.spec, async_exec=async_exec, wal=self.wal)
        self._next_rid = 0
        # aot: None = follow PT_AOT (default off, bit-exact legacy
        # path); "off"/"warm"/"strict" force it (tests).  warm =
        # AOT-compile every (program x shape-rung) pair at build via
        # the persistent compile cache; strict additionally seals the
        # programs so a post-warmup miss raises instead of compiling
        # mid-traffic.  compile_cache: a core.aot.CompileCache, a cache
        # dir path, or None for the PT_COMPILE_CACHE default.
        from paddle_tpu.core import aot as aot_mod

        if aot is None:
            aot = aot_mod.mode()
        if aot not in aot_mod.MODES:
            raise ValueError(f"aot={aot!r}: expected off|warm|strict")
        self.compile_cache = None
        self._aot_report = None
        self.aot_mode = aot
        if aot != "off":
            if not isinstance(compile_cache, aot_mod.CompileCache):
                self.compile_cache = aot_mod.CompileCache(
                    path=compile_cache)
            else:
                self.compile_cache = compile_cache
            self._aot_report = self.executor.aot_warmup(
                prefill_chunk=prefill_chunk,
                compile_cache=self.compile_cache,
                spec_window=(self.spec.k + 1 if self.spec else None),
                decode_n_steps=decode_n_steps)
            if aot == "strict":
                self.executor.seal()
            from paddle_tpu import obs as _obs

            if _obs.handle() is not None:
                _obs.handle().statusz["compile_cache"] = \
                    self.compile_cache.statusz
        # health plane: when telemetry is on, the engine owns an SLO
        # engine evaluated once per step, beats the "serving"
        # heartbeat, and feeds the /statusz pool/occupancy provider.
        # slos: None = stock serving objectives; [] disables; a list
        # of health.*Objective customizes (tests pass tight TTFT
        # objectives with LogicalClock-scale burn windows).
        from paddle_tpu import obs
        from paddle_tpu.obs import health

        self._health = None
        h = obs.handle()
        if h is not None:
            if slos is None:
                slos = health.default_serving_slos()
            if slos:
                self._health = health.SLOEngine(
                    slos, rules=slo_rules or health.DEFAULT_BURN_RULES,
                    handle=h, source="serving",
                    now=self.metrics._t_start)
            h.statusz["serving"] = self._statusz
            if self.wal is not None:
                # a cluster re-registers its own provider after its
                # engines are built (last registration wins)
                h.statusz["durability"] = self._durability_statusz

    # -- submission ------------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens=16, priority=0,
               deadline=None, on_token=None, rid=None) -> RequestHandle:
        """Enqueue a request; admission happens at the next step().

        ``deadline`` is in scheduler iterations (logical steps) from
        submission; ``on_token(rid, tok)`` streams tokens as they land.
        """
        if rid is None:
            # auto rids must never collide with client-supplied rids:
            # skip ahead until unused so an anonymous submit can never
            # silently dedup to someone else's stream
            rid = f"req-{self._next_rid}"
            while rid in self.scheduler.requests:
                self._next_rid += 1
                rid = f"req-{self._next_rid}"
        elif rid in self.scheduler.requests:
            # idempotent duplicate submit: at-least-once clients get
            # the ORIGINAL handle (live or terminal), never a second
            # stream — the dedup is journaled so recovery replays to
            # the same exactly-once outcome
            return self._dedup(rid, self.scheduler.requests[rid])
        req = Request(rid, prompt_ids, max_new_tokens=max_new_tokens,
                      priority=priority, deadline=deadline,
                      on_token=on_token, arrival_seq=self._next_rid,
                      clock=self.metrics.clock)
        self._next_rid += 1
        if len(req.prompt_ids) == 0:
            raise ValueError("prompt_ids must be non-empty")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.wal is not None:
            # journal acceptance BEFORE the scheduler sees the request
            # so no accepted request can outrun its submit record
            self.wal.append({
                "t": "submit", "rid": rid,
                "prompt": req.prompt_ids.tolist(),
                "max_new": req.max_new_tokens,
                "prio": req.priority, "deadline": req.deadline})
        self.scheduler.add(req)
        return RequestHandle(self, req)

    def _dedup(self, rid, req) -> RequestHandle:
        self.dedup_hits += 1
        if self.wal is not None:
            self.wal.append({"t": "dedup", "rid": rid})
        from paddle_tpu import obs

        h = obs.handle()
        if h is not None:
            h.events.log("req.dedup", rid=rid,
                         state=req.state.value)
        return RequestHandle(self, req)

    def cancel(self, rid) -> None:
        """Flag a request for cancellation; it turns CANCELLED at the
        start of the next step() (pages freed there, not here)."""
        req = self.scheduler.requests.get(rid)
        if req is not None and not req.terminal:
            req.cancel_flag = True

    # -- driving ---------------------------------------------------------

    def step(self) -> dict:
        """One scheduler iteration; returns {rid: [new tokens]}."""
        out = self.scheduler.step()
        if self._health is not None:
            # reuse the timestamp metrics.on_step just read so the
            # health plane adds no clock reads to the step path
            self._health.evaluate(step=self.scheduler.tick,
                                  now=self.metrics._t_last)
            from paddle_tpu import obs

            obs.beat("serving", now=self.metrics._t_last)
        return out

    def run(self, max_steps=100000) -> dict:
        """Step until no request is in flight; returns stats()."""
        while self.scheduler.has_work():
            if self.scheduler.tick >= max_steps:
                raise RuntimeError(
                    f"serving engine did not drain in {max_steps} steps")
            self.step()
        return self.stats()

    # -- introspection ---------------------------------------------------

    @property
    def tick(self) -> int:
        return self.scheduler.tick

    @property
    def in_flight(self) -> int:
        s = self.scheduler
        return len(s.queue) + len(s.prefilling) + len(s.running)

    def request(self, rid):
        return self.scheduler.requests.get(rid)

    def stats(self) -> dict:
        return self.metrics.stats()

    def _statusz(self) -> dict:
        """/statusz provider: live pool/occupancy plus the
        request-state counts from stats()."""
        cache = self.executor.cache
        s = self.scheduler
        return {
            "tick": s.tick,
            "in_flight": self.in_flight,
            "queued": len(s.queue),
            "prefilling": len(s.prefilling),
            "running": len(s.running),
            "pool": {
                "num_pages": cache.num_pages,
                "free_pages": cache.free_pages,
                "used_pages": cache.num_pages - cache.free_pages,
            },
            "quant": {
                "mode": self.executor.quant,
                "kv_pool_dtype": str(cache.k_pages.dtype),
                "weight_format": ("int8+per-channel-scale"
                                  if self.executor.quant == "int8"
                                  else "checkpoint"),
                "kv_scale_bytes": (0 if cache.k_scales is None else
                                   cache.k_scales.nbytes
                                   + cache.v_scales.nbytes),
            },
            "sp": {
                "mode": ("on" if self.executor.sp_degree > 1
                         else "off"),
                "degree": self.executor.sp_degree,
                "axis": self.executor._sp_axis,
                "min_tokens": self.executor.sp_min_tokens_effective(),
                "prefill_tokens": self.executor.sp_prefill_tokens,
            },
            "async": {
                "mode": "on" if s.async_mode else "off",
                "replans": s.replans,
                "host_overlap_ratio": s.host_overlap_ratio,
                "step_phase_seconds": dict(s.last_phase_seconds),
                "phase_seconds_total": dict(s.phase_totals),
            },
            "stats": self.stats(),
        }

    def _durability_statusz(self) -> dict:
        return {
            "wal": None if self.wal is None else self.wal.statusz(),
            "dedup_hits": self.dedup_hits,
        }
