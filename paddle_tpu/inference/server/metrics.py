"""SLO observability for the serving engine.

Two clocks run side by side: the LOGICAL clock (scheduler iterations —
what deterministic tests assert on) and the wall clock (ms
percentiles in ``stats()``).  Per-request TTFT/TPOT/queue-wait are
recorded in both; engine-level occupancy and page utilization are
step-averaged over the window where any request was in flight, so idle
tails don't dilute them.
"""
from __future__ import annotations

import time

import numpy as np

from ... import obs
from .request import RequestState


def _pct(values, q):
    vals = [v for v in values if v is not None]
    if not vals:
        return None
    return float(np.percentile(np.asarray(vals, np.float64), q))


#: logical-step buckets for the step-denominated histograms (a tick is
#: an iteration, not a duration — latency buckets would be nonsense).
_STEP_BUCKETS = (0, 1, 2, 4, 8, 16, 32, 64, 128, 256)


class EngineMetrics:
    """Accumulates per-request and engine-level serving statistics.

    ``clock`` is injectable (default ``time.perf_counter``) so seeded
    load tests can assert the ms percentiles exactly — pass
    ``obs.LogicalClock()`` and every TTFT/TPOT read is deterministic.
    When telemetry is on and no clock is given, the obs bundle's clock
    is used so the SLO numbers and the trace timestamps share one
    timeline.  When telemetry is on, every hook also publishes into
    the process-wide metric registry (``serve_*`` families); the
    ``stats()`` dict API is unchanged.
    """

    def __init__(self, max_seqs: int, num_pages: int, clock=None):
        self.max_seqs = max_seqs
        self.num_pages = num_pages
        self._obs = obs.handle()
        if clock is None:
            clock = (self._obs.clock if self._obs is not None
                     else time.perf_counter)
        self.clock = clock
        self._declare_metrics()
        self.steps = 0
        self.busy_steps = 0           # steps with >= 1 in-flight request
        self.decode_tokens = 0
        self.decode_slot_steps = 0    # sum of decode batch sizes
        self.prefill_tokens = 0
        self.preemptions = 0
        self.draft_proposed = 0       # speculative draft tokens offered
        self.draft_accepted = 0       # ...committed by verification
        self.spec_steps = 0           # verify dispatches
        self.submitted = 0
        self.prefix_hits = 0          # admissions that attached pages
        self.cached_tokens = 0        # prompt tokens served from cache
        self.evicted_pages = 0        # prefix-tree pages LRU-evicted
        self.occupancy_sum = 0.0      # decode-batch fill over busy steps
        self.page_util_sum = 0.0      # pool occupancy over busy steps
        self.state_counts = {s.value: 0 for s in RequestState
                             if s.value not in ("queued", "prefilling",
                                                "running")}
        self._completed = []          # per-request metric dicts
        self._t_start = self.clock()
        self._t_last = self._t_start

    def _declare_metrics(self):
        """Declare the serve_* registry families once (idempotent —
        several engines in one process share the counters)."""
        h = self._obs
        if h is None:
            return
        r = h.registry
        self._m = {
            "submitted": r.counter(
                "serve_requests_submitted_total",
                "Requests accepted by ServingEngine.submit"),
            "terminal": r.counter(
                "serve_requests_total",
                "Requests reaching a terminal state", labels=("state",)),
            "steps": r.counter(
                "serve_steps_total", "Scheduler iterations"),
            "decode_tokens": r.counter(
                "serve_decode_tokens_total", "Tokens emitted by decode"),
            "prefill_tokens": r.counter(
                "serve_prefill_tokens_total", "Prompt tokens prefilled"),
            "cached_tokens": r.counter(
                "serve_cached_tokens_total",
                "Prompt tokens attached from the prefix cache"),
            "prefix_hits": r.counter(
                "serve_prefix_hits_total",
                "Admissions that attached cached prefix pages"),
            "evicted_pages": r.counter(
                "serve_evicted_pages_total",
                "Prefix-tree pages LRU-evicted"),
            "preemptions": r.counter(
                "serve_preemptions_total",
                "Requests preempted for recompute"),
            "spec_steps": r.counter(
                "serve_spec_steps_total", "Speculative verify steps"),
            "draft_proposed": r.counter(
                "serve_draft_proposed_total",
                "Speculative draft tokens offered"),
            "draft_accepted": r.counter(
                "serve_draft_accepted_total",
                "Speculative draft tokens committed"),
            "occupancy": r.gauge(
                "serve_batch_occupancy",
                "Decode batch fill fraction (last busy step)"),
            "page_util": r.gauge(
                "serve_page_utilization",
                "KV page pool occupancy (last busy step)"),
            "ttft_s": r.histogram(
                "serve_ttft_seconds", "Time to first token"),
            "tpot_s": r.histogram(
                "serve_tpot_seconds", "Time per output token"),
            "queue_wait": r.histogram(
                "serve_queue_wait_steps",
                "Scheduler iterations queued before admission",
                buckets=_STEP_BUCKETS),
            "ttft_steps": r.histogram(
                "serve_ttft_steps",
                "Scheduler iterations from submit to first token",
                buckets=_STEP_BUCKETS),
        }

    # -- event hooks (called by the scheduler) --------------------------

    def on_submit(self, req, step):
        self.submitted += 1
        req.submit_step = step
        req.submit_time = self.clock()
        if self._obs is not None:
            self._m["submitted"].inc()

    def on_sched(self, req, step):
        if req.sched_step is None:
            req.sched_step = step

    def on_first_token(self, req, step):
        if req.first_token_step is None:
            req.first_token_step = step

    def on_decode_tokens(self, n):
        # legacy one-token-per-slot decode: slots == tokens
        self.on_decode_step(slots=n, tokens=n)

    def on_decode_step(self, slots, tokens):
        self.decode_tokens += tokens
        self.decode_slot_steps += slots
        if self._obs is not None:
            self._m["decode_tokens"].inc(tokens)

    def on_spec(self, proposed, accepted):
        self.spec_steps += 1
        self.draft_proposed += int(proposed)
        self.draft_accepted += int(accepted)
        if self._obs is not None:
            self._m["spec_steps"].inc()
            self._m["draft_proposed"].inc(int(proposed))
            self._m["draft_accepted"].inc(int(accepted))

    def on_prefill_tokens(self, n):
        self.prefill_tokens += n
        if self._obs is not None:
            self._m["prefill_tokens"].inc(n)

    def on_preempt(self, req):
        self.preemptions += 1
        if self._obs is not None:
            self._m["preemptions"].inc()

    def on_prefix_hit(self, tokens):
        self.prefix_hits += 1
        self.cached_tokens += int(tokens)
        if self._obs is not None:
            self._m["prefix_hits"].inc()
            self._m["cached_tokens"].inc(int(tokens))

    def on_prefix_evict(self, n_pages):
        self.evicted_pages += int(n_pages)
        if self._obs is not None:
            self._m["evicted_pages"].inc(int(n_pages))
            self._obs.events.log("kv.evict", pages=int(n_pages))

    def on_terminal(self, req, step):
        req.finish_step = step
        req.finish_time = self.clock()
        self.state_counts[req.state.value] += 1
        if self._obs is not None:
            self._m["terminal"].labels(state=req.state.value).inc()
        self._completed.append({
            "queue_wait_steps": (None if req.sched_step is None
                                 or req.submit_step is None
                                 else req.sched_step - req.submit_step),
            "ttft_steps": (None if req.first_token_step is None
                           else req.first_token_step - req.submit_step),
            "ttft_s": (None if req.first_token_time is None
                       else req.first_token_time - req.submit_time),
            "tpot_s": (None if len(req.generated) < 2
                       or req.last_token_time is None
                       else (req.last_token_time - req.first_token_time)
                       / (len(req.generated) - 1)),
            # logical-clock TPOT: scheduler iterations per generated
            # token.  1.0 for plain decode; < 1.0 once speculative
            # steps commit multiple tokens per iteration.
            "tpot_steps": (None if len(req.generated) < 2
                           or req.first_token_step is None
                           else (req.finish_step - req.first_token_step)
                           / (len(req.generated) - 1)),
            "tokens": len(req.generated),
        })
        if self._obs is not None:
            d = self._completed[-1]
            for key, hist in (("ttft_s", "ttft_s"),
                              ("tpot_s", "tpot_s"),
                              ("queue_wait_steps", "queue_wait"),
                              ("ttft_steps", "ttft_steps")):
                if d[key] is not None:
                    self._m[hist].observe(d[key])

    def on_step(self, decode_batch: int, pages_used: int,
                in_flight: int):
        self.steps += 1
        self._t_last = self.clock()
        if self._obs is not None:
            self._m["steps"].inc()
        if in_flight:
            self.busy_steps += 1
            occ = decode_batch / max(self.max_seqs, 1)
            util = pages_used / max(self.num_pages, 1)
            self.occupancy_sum += occ
            self.page_util_sum += util
            if self._obs is not None:
                self._m["occupancy"].set(occ)
                self._m["page_util"].set(util)

    # -- report ---------------------------------------------------------

    def stats(self) -> dict:
        wall = max(self._t_last - self._t_start, 1e-9)
        done = self._completed
        busy = max(self.busy_steps, 1)
        return {
            "steps": self.steps,
            "wall_s": round(wall, 4),
            "requests": dict(self.state_counts,
                             submitted=self.submitted),
            "preemptions": self.preemptions,
            "decode_tokens": self.decode_tokens,
            "prefill_tokens": self.prefill_tokens,
            # prefix-cache effectiveness: what fraction of prompt
            # tokens were served from shared pages instead of prefilled
            "cached_tokens": self.cached_tokens,
            "prefix_hit_rate": round(
                self.cached_tokens
                / max(self.cached_tokens + self.prefill_tokens, 1), 4),
            "evicted_pages": self.evicted_pages,
            "throughput_tok_s": round(self.decode_tokens / wall, 2),
            # speculative decode effectiveness: fraction of drafted
            # tokens committed, and how far each sequence advances per
            # decode slot-step (1.0 = plain greedy; > 1.0 = spec wins)
            "draft_acceptance_rate": round(
                self.draft_accepted / max(self.draft_proposed, 1), 4),
            "tokens_per_decode_step": round(
                self.decode_tokens / max(self.decode_slot_steps, 1), 4),
            "batch_occupancy": round(self.occupancy_sum / busy, 4),
            "page_utilization": round(self.page_util_sum / busy, 4),
            "queue_wait_steps_p50": _pct(
                [d["queue_wait_steps"] for d in done], 50),
            "queue_wait_steps_p99": _pct(
                [d["queue_wait_steps"] for d in done], 99),
            "ttft_steps_p50": _pct([d["ttft_steps"] for d in done], 50),
            "ttft_ms_p50": _ms(_pct([d["ttft_s"] for d in done], 50)),
            "ttft_ms_p99": _ms(_pct([d["ttft_s"] for d in done], 99)),
            "tpot_ms_p50": _ms(_pct([d["tpot_s"] for d in done], 50)),
            "tpot_ms_p99": _ms(_pct([d["tpot_s"] for d in done], 99)),
            "tpot_steps_p50": _pct([d["tpot_steps"] for d in done], 50),
            "tpot_steps_p99": _pct([d["tpot_steps"] for d in done], 99),
        }


def _ms(seconds):
    return None if seconds is None else round(seconds * 1e3, 3)
