"""Multi-replica serving fleet over the shared logical clock.

One :class:`ServingEngine` is a single box; the fleet wraps N of them
(each with its own page pool and executor) behind a :class:`Router`
that places every request by **prefix affinity** — probe each
replica's radix tree with the read-only
:meth:`PrefixCache.match_len` — falling back to page-pool headroom
and queue depth, so shared-prefix traffic lands where its KV pages
already live (SGLang-style radix-affinity scheduling).  Elastic
scale: :meth:`ServingCluster.drain` closes one replica's admission
and re-steers its queued requests while in-flight work finishes in
place; :meth:`ServingCluster.join` builds a fresh replica whose AOT
warmup resolves from the fleet's shared persistent compile cache, so
a new box serves in seconds.  Opt-in disaggregation
(``disaggregated=True``) splits roles DistServe-style: prefill
replicas compute prompt KV, then ship each finished sequence's pages
to a decode replica as ONE bulk copy through the pool's
``gather_dense``/``write_at`` seams — pages land refcounted, and the
COW/prefix invariants hold on both pools.

Determinism: replicas step in lockstep — one cluster ``step()`` steps
every live replica once — and greedy streams depend only on weights +
prompt (page identity never enters the numerics), so per-request token
streams are bit-identical to a single engine whatever the routing,
and across drain/join re-steers and KV handoffs, in all four serving
variants (plain / prefix / spec / async).

Gate: ``PT_CLUSTER`` (off|on; anything else raises).  Off, the
cluster degenerates to ONE replica with a pass-through router — the
bit-exact single-engine path.

Fault points: ``route.pick`` brackets one placement decision,
``replica.drain`` / ``replica.join`` bracket the elastic transitions,
``kv.handoff`` brackets one page shipment.  All four DEGRADE on an
injected raise — fallback placement, aborted transition, or the
request keeps decoding where it is — never request loss (the
aot.cache discipline: a dead replica is a miss, not a crash).

Survivability (the :class:`ReplicaSupervisor`): replicas heartbeat on
the logical clock; a crash, hang, or escaping exception marks the
replica FAILED and every request it held fails over — re-queued
through the router for a bit-identical prompt+generated re-prefill
(the preemption-recompute idiom) on a healthy replica, handles
untouched.  Failed replicas auto-restart after exponential backoff
(engine rebuilt, AOT re-warmed from the shared persistent compile
cache) under a consecutive-failure circuit breaker that permanently
retires flappers.  Admission control (``max_queue`` +
deadline-aware early rejection) sheds saturating load as terminal
REJECTED-with-retry-after — never silent loss.  Chaos points:
``replica.fail`` (crash/hang/raise consumed in-process),
``replica.restart``, ``req.failover``, ``req.shed``.
"""
from __future__ import annotations

import os
import time
import zlib

import numpy as np

from ... import obs
from ...testing import faults
from . import wal as wal_mod
from .engine import ServingEngine
from .wal import resolve_wal, stream_crc
from .request import (Request, RequestHandle, RequestRejected,
                      RequestState)


def _cluster_enabled() -> bool:
    mode = os.environ.get("PT_CLUSTER", "off").lower()
    if mode not in ("off", "on"):
        raise ValueError(f"PT_CLUSTER={mode!r}: expected off|on")
    return mode == "on"


#: replica lifecycle states (statusz/gauge encoding in this order;
#: the survivability states append so r20 gauge values are unchanged).
REPLICA_STATES = ("active", "draining", "drained",
                  "failed", "restarting", "retired")

#: states in which a replica no longer steps or holds live requests.
DEAD_STATES = ("drained", "failed", "restarting", "retired")


class Replica:
    """One engine plus its fleet-side control state."""

    __slots__ = ("name", "engine", "role", "state",
                 "last_beat", "hung", "fail_streak", "fails",
                 "restarts", "restart_at", "probation_until")

    def __init__(self, name, engine, role="mixed"):
        self.name = name
        self.engine = engine
        self.role = role            # mixed | prefill | decode
        self.state = "active"
        # survivability bookkeeping (the ReplicaSupervisor's state):
        self.last_beat = 0          # cluster tick of the last full step
        self.hung = False           # injected silent stall in progress
        self.fail_streak = 0        # consecutive failures (breaker)
        self.fails = 0              # lifetime failures
        self.restarts = 0           # lifetime successful restarts
        self.restart_at = None      # tick of the next restart attempt
        self.probation_until = None  # healthy-until tick resets streak

    @property
    def depth(self) -> int:
        """Queue depth the router balances on: everything holding or
        waiting for a slot."""
        s = self.engine.scheduler
        return len(s.queue) + len(s.prefilling) + len(s.running)

    @property
    def admitting(self) -> bool:
        return (self.state == "active" and not self.hung
                and self.role in ("mixed", "prefill"))

    def __repr__(self):
        return (f"Replica({self.name}, role={self.role}, "
                f"state={self.state}, depth={self.depth})")


class Router:
    """Placement policy over the admitting replicas.

    ``affinity`` (default): maximize the prefix-affinity probe
    (tokens of the prompt already resident in the replica's radix
    tree), tie-broken by sequence-parallel fit (a long prompt prefers
    a mesh-backed replica that can stripe its prefill), then lowest
    queue depth, then most free pages, then lowest replica index —
    fully deterministic.  ``random``: seeded uniform pick, the tests'
    control arm.
    """

    POLICIES = ("affinity", "random")

    def __init__(self, policy="affinity", seed=0):
        if policy not in self.POLICIES:
            raise ValueError(
                f"router policy must be one of {self.POLICIES}, "
                f"got {policy!r}")
        self.policy = policy
        self._rng = np.random.RandomState(seed)
        self.decisions = 0
        self.affinity_hits = 0     # picks that landed on cached pages
        self.degraded = 0          # injected-fault fallback placements

    def pick(self, candidates, prompt_ids):
        """(replica, affinity_tokens) for one request.

        The admitting flag is re-checked HERE, at decision time, not
        just when the candidate list was snapshotted: a replica whose
        ``drain()`` (or failure) landed between the snapshot and the
        pick must never win the placement.  When every candidate went
        stale the original list is kept — the caller owns the
        no-admitting-replica error path.
        """
        live = [r for r in candidates if r.admitting]
        if live:
            candidates = live
        if self.policy == "random":
            return candidates[int(self._rng.randint(
                len(candidates)))], 0
        best, best_key = None, None
        for i, rep in enumerate(candidates):
            prefix = rep.engine.prefix
            aff = (prefix.match_len(prompt_ids)
                   if prefix is not None else 0)
            ex = rep.engine.executor
            # long prompts score toward a mesh-backed replica: when
            # this prompt meets the replica's sequence-parallel
            # threshold, its prefill cost divides by the sp degree
            # there.  Ranked BELOW affinity (resident prefix pages
            # save recompute outright) and ABOVE depth; zero on every
            # replica of an sp-free fleet, so those orderings are
            # byte-identical to r22.
            sp_fit = int(getattr(ex, "sp_degree", 1) > 1
                         and len(prompt_ids)
                         >= ex.sp_min_tokens_effective())
            key = (aff, sp_fit, -rep.depth, ex.free_pages, -i)
            if best is None or key > best_key:
                best, best_key = rep, key
        if best_key[0] > 0:
            self.affinity_hits += 1
        return best, best_key[0]


class ReplicaSupervisor:
    """Crash/hang detection and closed-loop recovery for one fleet.

    Detection is two-pronged, both deterministic on the logical
    clock's side: a replica that completes a step beats
    (``last_beat = cluster tick``, mirrored into the obs heartbeat
    plane as ``replica.<name>``); one that misses ``beat_timeout``
    consecutive beats — a hang, silent or injected — is marked FAILED.
    ``watchdog_s`` (off by default: wall time is nondeterministic)
    additionally bounds one step's wall-clock; a step that finishes
    but blows the deadline fails the replica AFTER its tokens are
    kept (they are valid — greedy streams depend only on weights +
    prompt).

    Failure handling is the tentpole loop: every non-terminal request
    on the failed replica is failed over — re-queued through the
    :class:`Router` for a bit-identical prompt+generated re-prefill
    (the preemption-recompute path; prefix-cache hits make it cheap)
    on a healthy replica, its :class:`RequestHandle` untouched.  With
    no healthy target the request parks on the cluster's orphan list
    (never lost) and re-homes as soon as a replica restarts or joins.

    Restart is automatic (``auto_restart``): after an exponential
    backoff (``backoff_base * 2**(streak-1)`` ticks) the replica
    rebuilds its engine — AOT re-warmed from the fleet's shared
    persistent compile cache, zero new compiles — and rejoins
    admission.  A circuit breaker retires it permanently once
    ``fail_streak`` exceeds ``restart_budget``; the streak resets
    only after the replica survives a probation window
    (``2 * beat_timeout`` ticks), so a flapping replica keeps
    accumulating strikes.
    """

    def __init__(self, cluster, beat_timeout=3, watchdog_s=None,
                 auto_restart=True, restart_budget=3, backoff_base=2):
        if int(beat_timeout) < 1:
            raise ValueError(
                f"beat_timeout must be >= 1, got {beat_timeout}")
        self.cluster = cluster
        self.beat_timeout = int(beat_timeout)
        self.watchdog_s = (None if watchdog_s is None
                           else float(watchdog_s))
        self.auto_restart = bool(auto_restart)
        self.restart_budget = int(restart_budget)
        self.backoff_base = max(1, int(backoff_base))
        self.probation = 2 * self.beat_timeout

    # -- supervised stepping --------------------------------------------

    def step_replica(self, rep) -> dict:
        """One replica step under supervision; returns its emitted
        map ({} when the replica stalled, crashed, or failed)."""
        cl = self.cluster
        hit = faults.consume("replica.fail", "before")
        if hit is not None:
            action = hit[0]
            if action == "hang":
                rep.hung = True     # silent: no step, no beat
            elif action == "crash":
                self.fail(rep, "crash")
                return {}
            else:                   # raise & friends: exception path
                self.fail(rep, f"injected:{action}")
                return {}
        if rep.hung:
            return {}
        t0 = (time.monotonic() if self.watchdog_s is not None
              else None)
        try:
            out = rep.engine.step()
        except Exception as e:
            # one replica's step blowing up must not take the fleet
            # down: confine it, fail the replica, fail over its work.
            self.fail(rep, f"{type(e).__name__}: {e}", error=e)
            return {}
        rep.last_beat = cl._tick
        if cl._obs is not None:
            obs.beat(f"replica.{rep.name}",
                     now=rep.engine.metrics._t_last)
        if t0 is not None and time.monotonic() - t0 > self.watchdog_s:
            # the step finished but blew its wall-clock deadline: the
            # tokens it emitted are valid and are returned — the
            # replica is failed afterwards.
            self.fail(rep, "watchdog")
        return out

    # -- detection + recovery loop --------------------------------------

    def poll(self) -> None:
        """Once per cluster step: missed-beat detection, probation
        expiry, due restarts, orphan re-homing."""
        cl = self.cluster
        tick = cl._tick
        for rep in list(cl.replicas):
            if rep.state in ("active", "draining"):
                if tick - rep.last_beat >= self.beat_timeout:
                    self.fail(rep, "missed_beats")
                elif (rep.fail_streak
                      and rep.probation_until is not None
                      and tick >= rep.probation_until):
                    rep.fail_streak = 0     # survived probation
                    rep.probation_until = None
            elif (rep.state == "failed" and self.auto_restart
                  and rep.restart_at is not None
                  and tick >= rep.restart_at):
                self.restart(rep)
        if cl._orphans:
            self._rehome()

    def fail(self, rep, reason, error=None) -> None:
        """Mark one replica FAILED and fail over every non-terminal
        request it holds.  Idempotent on already-dead replicas."""
        cl = self.cluster
        if rep.state in ("failed", "restarting", "retired", "drained"):
            return
        # a HUNG replica stopped stepping but its engine is intact:
        # the page pool is still readable, so running requests can be
        # salvaged (KV pages migrated) instead of re-prefilled.  A
        # crashed/raising replica's engine is garbage — capture the
        # distinction BEFORE the hung flag is cleared below.
        salvageable = cl.salvage and (
            rep.hung or reason in ("missed_beats", "watchdog"))
        in_flight = rep.engine.in_flight
        rep.state = "failed"
        rep.hung = False
        rep.fails += 1
        rep.fail_streak += 1
        rep.probation_until = None
        if cl._obs is not None:
            cl._obs.events.log(
                "replica.fail", replica=rep.name, reason=reason,
                in_flight=in_flight, fail_streak=rep.fail_streak,
                tick=cl._tick)
            cl._obs.recorder.record(
                "replica.fail", replica=rep.name, reason=reason,
                tick=cl._tick)
        try:
            faults.fire("replica.fail", "after")
        except faults.InjectedFault:
            pass            # the failure is already being handled
        # strip every live request off the dead scheduler (its engine
        # is garbage — the restart path rebuilds it from scratch, so
        # no slot/page bookkeeping is owed here) and fail each over.
        sch = rep.engine.scheduler
        live = [r for r in sch.requests.values() if not r.terminal]
        for req in live:
            for pool in (sch.queue, sch.prefilling, sch.running):
                if req in pool:
                    pool.remove(req)
            sch.requests.pop(req.rid, None)
            if sch.spec is not None:
                try:
                    sch.spec.on_release(req)
                except Exception:
                    pass    # dead engine's draft state is garbage too
            cl._owner.pop(req.rid, None)
            if salvageable and req.state is RequestState.RUNNING \
                    and req.sid is not None \
                    and self._salvage(req, rep):
                continue
            self._failover(req, rep)
        # schedule the restart — or trip the breaker.
        if rep.fail_streak > self.restart_budget:
            self.retire(rep)
        elif self.auto_restart:
            backoff = self.backoff_base * (
                2 ** (rep.fail_streak - 1))
            rep.restart_at = cl._tick + backoff
        if error is not None and cl._obs is not None:
            obs.auto_dump(f"replica-failed-{rep.name}",
                          extra={"replica": rep.name,
                                 "reason": reason})

    def _failover(self, req, src) -> None:
        """Fail one request over to a healthy replica (or the orphan
        list).  The recompute resume is the preemption idiom: prefill
        prompt+generated from scratch, decode resumes bit-identically
        after the already-streamed tokens."""
        cl = self.cluster
        cl.failovers += 1
        if cl._obs is not None:
            cl._obs.registry.counter(
                "cluster_failovers_total",
                "Requests failed over off a dead replica").inc()
        if not req.terminal:
            req.resume_ids = np.concatenate(
                [req.prompt_ids,
                 np.asarray(req.generated, np.int32)]).astype(np.int32)
            req.prefill_done = 0
            req.sid = None
            req.state = RequestState.QUEUED
        placed = self._place(req, src=src)
        if not placed:
            cl._orphans.append(req)
            if cl._obs is not None:
                cl._obs.events.log(
                    "req.failover", rid=req.rid, src=src.name,
                    dst=None, orphaned=1,
                    tokens_done=len(req.generated), tick=cl._tick)

    def _salvage(self, req, src) -> bool:
        """Migrate one RUNNING request's committed KV pages off a hung
        replica onto an admitting one through the dense gather→write
        handoff path, skipping the re-prefill entirely: decoding
        resumes from the same last token over the same pages, so the
        stream continues bit-identically at recompute-free cost.

        The copy is crc32-verified end to end (gather source → land →
        re-gather target); any mismatch, capacity shortfall, injected
        ``kv.salvage`` raise, or unreadable source degrades to False —
        the caller falls back to the recompute failover, never loss."""
        cl = self.cluster
        src_ex = src.engine.executor
        try:
            length = int(src_ex.cache.lengths[req.sid])
        except Exception:
            return False
        if length <= 0:
            return False
        dst = None
        for cand in sorted(
                (r for r in cl._admitting() if r is not src),
                key=lambda r: (r.depth, -r.engine.executor.free_pages)):
            ex = cand.engine.executor
            if ex.free_slots >= 1 \
                    and ex.free_pages >= ex.pages_for(length + 1):
                dst = cand
                break
        if dst is None:
            return False
        try:
            faults.fire("kv.salvage", "before")
            k, v = src_ex.cache.gather_dense(req.sid, length)
        except Exception:
            cl.salvages_failed += 1
            return False
        # gather_dense pads to the page-multiple cover: positions >=
        # length are garbage and must never enter the checksum
        k = np.asarray(k)[:, :, :length]
        v = np.asarray(v)[:, :, :length]
        crc = zlib.crc32(v.tobytes(), zlib.crc32(k.tobytes()))
        if faults.poll("kv.salvage") is not None:
            # injected in-flight corruption: the verify MUST catch it
            k = k.copy()
            k.flat[k.size // 2] = k.flat[k.size // 2] + 1
        dst_ex = dst.engine.executor
        dst_sid = dst_ex.alloc_slot()
        crc_got = None
        try:
            dst_ex.cache.write_at(dst_sid, k, v, 0)
            k2, v2 = dst_ex.cache.gather_dense(dst_sid, length)
            k2 = np.asarray(k2)[:, :, :length]
            v2 = np.asarray(v2)[:, :, :length]
            crc_got = zlib.crc32(v2.tobytes(), zlib.crc32(k2.tobytes()))
        except Exception:
            pass
        if crc_got != crc:
            # corrupt copy: give the pages back, recompute instead
            dst_ex.free_slot(dst_sid)
            cl.salvages_failed += 1
            if cl._obs is not None:
                cl._obs.events.log(
                    "kv.salvage", rid=req.rid, src=src.name,
                    dst=dst.name, ok=0, crc_ok=0, tokens=length,
                    tick=cl._tick)
            return False
        dst_ex.last_token[dst_sid] = src_ex.last_token[req.sid]
        try:
            faults.fire("kv.salvage", "after")
        except faults.InjectedFault:
            pass            # pages landed verified: the salvage commits
        req.sid = dst_sid
        dst_sch = dst.engine.scheduler
        dst_sch.requests[req.rid] = req
        dst_sch.running.append(req)
        dst_sch._pending = None     # stale async plan must replan
        if dst_sch.spec is not None:
            dst_sch.spec.on_running(req)
        cl._owner[req.rid] = dst
        cl.failovers += 1           # a salvage IS a (cheap) failover
        cl.salvages += 1
        pages = int((dst_ex.cache.page_table[dst_sid] >= 0).sum())
        cl.salvaged_pages += pages
        if cl._obs is not None:
            cl._obs.registry.counter(
                "cluster_failovers_total",
                "Requests failed over off a dead replica").inc()
            cl._obs.registry.counter(
                "kv_pages_salvaged_total",
                "KV pages migrated off hung replicas").inc(pages)
            cl._obs.events.log(
                "kv.salvage", rid=req.rid, src=src.name, dst=dst.name,
                ok=1, crc_ok=1, tokens=length, pages=pages,
                tick=cl._tick)
            cl._obs.tracer.instant(
                "kv.salvage", cat="cluster", trace_id=req.rid,
                src=src.name, dst=dst.name, tokens=length, pages=pages)
        return True

    def _place(self, req, src=None) -> bool:
        """Route one failed-over request onto an admitting replica;
        False when none exists.  An injected ``req.failover`` raise
        degrades to the first admitting replica — never loss."""
        cl = self.cluster
        targets = cl._admitting()
        if not targets:
            return False
        degraded = False
        try:
            faults.fire("req.failover", "before")
            dst, aff = cl.router.pick(targets, req.resume_ids)
        except faults.InjectedFault:
            cl.router.degraded += 1
            dst, aff, degraded = targets[0], 0, True
        dst.engine.scheduler.add(req)
        cl._owner[req.rid] = dst
        if cl._obs is not None:
            cl._obs.events.log(
                "req.failover", rid=req.rid,
                src=None if src is None else src.name, dst=dst.name,
                orphaned=0, aff_tokens=int(aff), degraded=int(degraded),
                tokens_done=len(req.generated), tick=cl._tick)
        try:
            faults.fire("req.failover", "after")
        except faults.InjectedFault:
            pass            # the migration is already committed
        return True

    def _rehome(self) -> None:
        """Drain the orphan list onto whatever is admitting now."""
        cl = self.cluster
        remaining = []
        for req in cl._orphans:
            if req.terminal:
                continue
            if not self._place(req):
                remaining.append(req)
        cl._orphans[:] = remaining

    # -- restart + circuit breaker --------------------------------------

    def restart(self, rep):
        """One automatic restart attempt: rebuild the engine (AOT
        re-warmed from the fleet's shared persistent compile cache)
        and rejoin admission.  A failed attempt counts against the
        breaker budget and doubles the backoff."""
        cl = self.cluster
        if rep.state != "failed":
            raise ValueError(
                f"cannot restart {rep.name}: state={rep.state!r}")
        rep.state = "restarting"
        rep.restart_at = None
        try:
            faults.fire("replica.restart", "before")
            eng = cl._build_engine()
            faults.fire("replica.restart", "after")
        except Exception:
            cl.restarts_failed += 1
            rep.fail_streak += 1
            if cl._obs is not None:
                cl._obs.events.log(
                    "replica.restart", replica=rep.name, ok=0,
                    fail_streak=rep.fail_streak, tick=cl._tick)
            if rep.fail_streak > self.restart_budget:
                self.retire(rep)
            else:
                rep.state = "failed"
                backoff = self.backoff_base * (
                    2 ** (rep.fail_streak - 1))
                rep.restart_at = cl._tick + backoff
            return None
        rep.engine = eng
        rep.state = "active"
        rep.hung = False
        rep.last_beat = cl._tick
        rep.restarts += 1
        rep.probation_until = cl._tick + self.probation
        cl.restarts += 1
        if cl._obs is not None:
            report = eng._aot_report or {}
            cl._obs.events.log(
                "replica.restart", replica=rep.name, ok=1,
                restarts=rep.restarts,
                aot_compiled=int(report.get("compile", 0)),
                aot_disk=int(report.get("disk", 0)), tick=cl._tick)
        self._rehome()
        return rep

    def retire(self, rep) -> None:
        """Circuit breaker: permanently remove a flapping replica
        from rotation (state ``retired`` — never restarted)."""
        cl = self.cluster
        if rep.state == "retired":
            return
        rep.state = "retired"
        rep.restart_at = None
        cl.retired += 1
        if cl._obs is not None:
            cl._obs.events.log(
                "replica.retire", replica=rep.name,
                fail_streak=rep.fail_streak,
                budget=self.restart_budget, tick=cl._tick)

    def statusz(self) -> dict:
        return {
            "beat_timeout": self.beat_timeout,
            "watchdog_s": self.watchdog_s,
            "auto_restart": self.auto_restart,
            "restart_budget": self.restart_budget,
            "backoff_base": self.backoff_base,
            "probation": self.probation,
        }


class ServingCluster:
    """N engine replicas behind a :class:`Router`, stepped in lockstep
    on one logical clock.  Exposes the single-engine driving surface
    (``submit`` / ``step`` / ``run`` / ``tick`` / ``in_flight`` /
    ``stats``), so :func:`paddle_tpu.testing.load.run_load` drives a
    fleet exactly like one engine.

    ``cluster``: None = follow ``PT_CLUSTER`` (default off — the
    cluster collapses to one replica, bit-exact single-engine);
    True/False force it (tests).  Engine keyword arguments
    (``max_seqs``, ``page_size``, ``prefix_cache``, ``aot``, ...)
    apply to every replica.
    """

    def __init__(self, model, n_replicas=2, cluster=None,
                 router_policy="affinity", router_seed=0,
                 disaggregated=False, n_prefill=None, clock=None,
                 compile_cache=None, beat_timeout=3, watchdog_s=None,
                 auto_restart=True, restart_budget=3, backoff_base=2,
                 max_queue=None, shed_deadlines=None, wal=None,
                 salvage=True, **engine_kwargs):
        if cluster is None:
            cluster = _cluster_enabled()
        self.enabled = bool(cluster)
        if not self.enabled:
            n_replicas, disaggregated = 1, False
        n_replicas = int(n_replicas)
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        if disaggregated and n_replicas < 2:
            raise ValueError(
                "disaggregated mode needs >= 2 replicas "
                "(at least one prefill and one decode role)")
        kinds = set(getattr(model.config, "layer_types", ()))
        if "mamba" in kinds:
            # a hand-off moves a request's pages, or replays it from the
            # journal; a recurrent state row is neither
            raise NotImplementedError(
                "ServingCluster: cluster hand-off not supported for a "
                "model with recurrent (state-space) layers")
        if kinds & {"mla_dense", "mla_moe"}:
            # the hand-off ships K and V heads; a latent pool has neither
            raise NotImplementedError(
                "ServingCluster: cluster hand-off not supported for a "
                "model with latent attention (MLA) layers")
        if "sliding_attention" in kinds:
            # the hand-off ships every token's K and V; the pages behind
            # a window were released
            raise NotImplementedError(
                "ServingCluster: cluster hand-off not supported for a "
                "model that mixes sliding-window and full attention layers")
        self.model = model
        self.disaggregated = bool(disaggregated)
        self._engine_kwargs = dict(engine_kwargs)
        # durable serving: ONE write-ahead journal shared by the whole
        # fleet (wal: None = follow PT_WAL, default off/bit-exact;
        # a path or WriteAheadLog forces on).  The cluster resolves
        # the gate once and pins the engines to its decision — two
        # engines must never race separate writers onto one journal
        # directory.
        self.wal = resolve_wal(wal)
        self._engine_kwargs["wal"] = (self.wal if self.wal is not None
                                      else False)
        # salvage: migrate a HUNG victim's committed KV pages to the
        # failover target instead of re-prefilling (crash victims
        # still recompute — a crashed engine's pool is garbage)
        self.salvage = bool(salvage)
        self._clock = clock
        # one persistent compile cache shared by the whole fleet when
        # AOT is in play: join() re-warms a fresh replica from disk
        from paddle_tpu.core import aot as aot_mod

        aot = engine_kwargs.get("aot")
        if aot is None:
            aot = aot_mod.mode()
        self._compile_cache = None
        if aot != "off":
            if isinstance(compile_cache, aot_mod.CompileCache):
                self._compile_cache = compile_cache
            else:
                self._compile_cache = aot_mod.CompileCache(
                    path=compile_cache)
        self.router = Router(policy=router_policy, seed=router_seed)
        self.replicas: list = []
        self._n_built = 0
        self._tick = 0
        self._next_rid = 0
        self._owner: dict = {}      # rid -> Replica (current home)
        self.handoffs = 0
        self.handoff_tokens = 0
        self.handoffs_skipped = 0
        self.drains = 0
        self.drains_aborted = 0
        self.joins = 0
        self.joins_aborted = 0
        self.resteered = 0
        # survivability plane: supervisor policy + counters.  All of
        # it is inert without failures — a fault-free run is
        # bit-exact r20 whatever the knobs.
        self.supervisor = ReplicaSupervisor(
            self, beat_timeout=beat_timeout, watchdog_s=watchdog_s,
            auto_restart=auto_restart, restart_budget=restart_budget,
            backoff_base=backoff_base)
        # admission control: max_queue bounds the fleet-wide queued
        # backlog; shed_deadlines (default: on iff max_queue is set)
        # early-rejects requests whose deadline the router can already
        # prove unmeetable.  Both default OFF-equivalent so legacy
        # submits are untouched.
        self.max_queue = None if max_queue is None else int(max_queue)
        self.shed_deadlines = (self.max_queue is not None
                               if shed_deadlines is None
                               else bool(shed_deadlines))
        self.failovers = 0
        self.sheds = 0
        self.restarts = 0
        self.restarts_failed = 0
        self.retired = 0
        self.salvages = 0           # hung-replica KV-page migrations
        self.salvages_failed = 0    # fell back to recompute
        self.salvaged_pages = 0
        self.dedup_hits = 0         # duplicate submits deduplicated
        self._orphans: list = []    # failed-over, awaiting a home
        self._served: dict = {}     # rid -> terminal Request restored
        #                             from the WAL (served from the log)
        self.recovery = None        # report dict set by recover()
        self.recovered_handles = {}  # rid -> handle, set by recover()
        self._obs = obs.handle()
        n_pre = 0
        if self.disaggregated:
            n_pre = (max(1, n_replicas // 2) if n_prefill is None
                     else int(n_prefill))
            if not 1 <= n_pre < n_replicas:
                raise ValueError(
                    f"n_prefill must be in [1, {n_replicas - 1}], "
                    f"got {n_pre}")
        for i in range(n_replicas):
            role = "mixed"
            if self.disaggregated:
                role = "prefill" if i < n_pre else "decode"
            self._build_replica(role)
        if self._obs is not None:
            self._obs.statusz["cluster"] = self._statusz
            self._obs.statusz["survivability"] = \
                self._survivability_statusz
            self._obs.statusz["durability"] = self._durability_statusz

    def _build_engine(self) -> ServingEngine:
        """One replica engine, AOT-warmed (when on) from the fleet's
        shared persistent compile cache — the join() AND restart
        rebuild path."""
        eng = ServingEngine(self.model, clock=self._clock,
                            compile_cache=self._compile_cache,
                            **self._engine_kwargs)
        # a fresh engine (restart/join) registers its engine-scoped
        # durability provider; the fleet-level view stays authoritative
        if self._obs is not None:
            self._obs.statusz["durability"] = self._durability_statusz
        return eng

    def _build_replica(self, role="mixed") -> Replica:
        name = f"r{self._n_built}"
        self._n_built += 1
        rep = Replica(name, self._build_engine(), role=role)
        rep.last_beat = self._tick
        self.replicas.append(rep)
        return rep

    def replica(self, name) -> Replica:
        for rep in self.replicas:
            if rep.name == name:
                return rep
        raise KeyError(f"no replica named {name!r} "
                       f"(have {[r.name for r in self.replicas]})")

    # -- routing + submission -------------------------------------------

    def _admitting(self):
        return [r for r in self.replicas if r.admitting]

    def _recovering(self) -> bool:
        """True when capacity is expected back: some replica is mid
        restart or failed with a restart already scheduled."""
        return any(
            r.state == "restarting"
            or (r.state == "failed" and r.restart_at is not None)
            for r in self.replicas)

    def _route(self, rid, prompt_ids, resteer=False):
        cands = self._admitting()
        if not cands:
            raise RuntimeError(
                "ServingCluster: no admitting replica "
                "(all draining/drained)")
        self.router.decisions += 1
        degraded = False
        try:
            faults.fire("route.pick", "before")
            rep, aff = self.router.pick(cands, prompt_ids)
        except faults.InjectedFault:
            # degraded placement: deterministic fallback to the first
            # admitting replica — the request is never dropped
            self.router.degraded += 1
            rep, aff, degraded = cands[0], 0, True
        if not degraded:
            try:
                faults.fire("route.pick", "after")
            except faults.InjectedFault:
                self.router.degraded += 1
                degraded = True     # decision stands; nothing was lost
        if self._obs is not None:
            self._obs.events.log(
                "route.decide", rid=rid, replica=rep.name,
                policy=self.router.policy, aff_tokens=int(aff),
                depth=rep.depth,
                free_pages=rep.engine.executor.free_pages,
                degraded=int(degraded), resteer=int(resteer),
                tick=self._tick)
        return rep, aff

    def submit(self, prompt_ids, max_new_tokens=16, priority=0,
               deadline=None, on_token=None, rid=None) -> RequestHandle:
        """Route one request to a replica; the returned handle drives
        the whole CLUSTER (handle.result()/stream() step every
        replica), so it stays live across re-steers and handoffs."""
        if rid is None:
            # auto rids must never collide with journaled, recovered or
            # client-supplied rids: skip ahead until unused (recover()
            # also advances _next_rid past every replayed req-N)
            rid = f"req-{self._next_rid}"
            while self._known(rid) is not None:
                self._next_rid += 1
                rid = f"req-{self._next_rid}"
        known = self._known(rid)
        if known is not None:
            # idempotent duplicate submit: at-least-once clients get
            # the ORIGINAL request back (live, orphaned, or terminal —
            # including streams recovered from the WAL), never a
            # second stream; the dedup is journaled.
            self.dedup_hits += 1
            if self.wal is not None:
                self.wal.append({"t": "dedup", "rid": rid})
            if self._obs is not None:
                self._obs.events.log("req.dedup", rid=rid,
                                     state=known.state.value,
                                     tick=self._tick)
            return RequestHandle(self, known)
        self._next_rid += 1
        verdict = self._shed_verdict(deadline)
        if verdict is not None:
            shed = self._shed(rid, prompt_ids, max_new_tokens,
                              priority, deadline, on_token, verdict)
            if shed is not None:
                return shed     # REJECTED terminal, never silent loss
        if not self._admitting() and self._recovering():
            # the whole admitting set is down but a restart is already
            # scheduled: park the request on the orphan list (never
            # refused, never lost) — the supervisor re-homes it the
            # moment a replica rejoins.
            req = Request(rid, prompt_ids,
                          max_new_tokens=max_new_tokens,
                          priority=priority, deadline=deadline,
                          on_token=on_token)
            if len(req.prompt_ids) == 0:
                raise ValueError("prompt_ids must be non-empty")
            if req.max_new_tokens < 1:
                raise ValueError("max_new_tokens must be >= 1")
            self._orphans.append(req)
            if self.wal is not None:
                # parked submits are accepted work: journal them like
                # any other so a crash while orphaned still recovers
                self.wal.append({
                    "t": "submit", "rid": rid,
                    "prompt": req.prompt_ids.tolist(),
                    "max_new": req.max_new_tokens,
                    "prio": req.priority, "deadline": req.deadline})
            if self._obs is not None:
                self._obs.events.log("req.parked", rid=rid,
                                     tick=self._tick)
            return RequestHandle(self, req)
        rep, _ = self._route(rid, np.asarray(
            prompt_ids, np.int32).reshape(-1))
        handle = rep.engine.submit(
            prompt_ids, max_new_tokens=max_new_tokens,
            priority=priority, deadline=deadline, on_token=on_token,
            rid=rid)
        self._owner[rid] = rep
        return RequestHandle(self, handle._req)

    # -- admission control (overload shedding) --------------------------

    def _queued_total(self) -> int:
        return len(self._orphans) + sum(
            len(r.engine.scheduler.queue) for r in self.replicas
            if r.state not in DEAD_STATES)

    def _shed_verdict(self, deadline):
        """(reason, retry_after_steps) to reject NOW, else None.

        Deterministic on the logical clock: the backlog bound counts
        every queued-not-admitted request fleet-wide; the deadline
        check uses a lower bound on TTFT (one prefill step plus the
        queue overflow ahead of the best replica) — if even the bound
        misses the deadline, admission would only discover the same
        truncation later, holding pages the whole wait.
        """
        queued = self._queued_total()
        if self.max_queue is not None and queued >= self.max_queue:
            return ("overload", max(1, queued - self.max_queue + 1))
        if deadline is not None and self.shed_deadlines:
            best = None
            for rep in self._admitting():
                est = 1 + max(0, rep.depth
                              - rep.engine.executor.cache.max_seqs)
                if best is None or est < best:
                    best = est
            if best is not None and best > int(deadline):
                return ("deadline_unmeetable",
                        max(1, best - int(deadline)))
        return None

    def _shed(self, rid, prompt_ids, max_new_tokens, priority,
              deadline, on_token, verdict):
        """Reject one request at the boundary: terminal REJECTED with
        a retry-after hint.  An injected ``req.shed`` before-raise
        degrades to ADMITTING the request (returns None) — shedding
        must never turn into loss."""
        reason, retry_after = verdict
        try:
            faults.fire("req.shed", "before")
        except faults.InjectedFault:
            return None
        req = Request(rid, prompt_ids, max_new_tokens=max_new_tokens,
                      priority=priority, deadline=deadline,
                      on_token=on_token)
        req.state = RequestState.REJECTED
        req.finish_reason = reason
        req.retry_after = int(retry_after)
        req.error = RequestRejected(rid, reason, retry_after)
        self.sheds += 1
        # NOT added to the dedup set: a retry_after verdict is an
        # invitation to resubmit the same rid after backing off
        if self.wal is not None:
            self.wal.append({"t": "reject", "rid": rid,
                             "reason": reason,
                             "retry_after": int(retry_after)})
        if self._obs is not None:
            self._obs.registry.counter(
                "cluster_shed_total",
                "Requests rejected by cluster admission control").inc()
            self._obs.events.log(
                "req.shed", rid=rid, reason=reason,
                retry_after=int(retry_after),
                queued=self._queued_total(), tick=self._tick)
        try:
            faults.fire("req.shed", "after")
        except faults.InjectedFault:
            pass                # the rejection is already terminal
        return RequestHandle(self, req)

    def cancel(self, rid) -> None:
        rep = self._owner.get(rid)
        if rep is not None:
            rep.engine.cancel(rid)
            return
        for req in self._orphans:   # cancelled while awaiting a home
            if req.rid == rid and not req.terminal:
                req.cancel_flag = True

    def request(self, rid):
        return self._known(rid)

    def _known(self, rid):
        """The live/terminal Request for ``rid`` wherever it lives —
        its owning replica, the WAL-restored terminal set, or the
        orphan list — else None."""
        rep = self._owner.get(rid)
        if rep is not None:
            req = rep.engine.request(rid)
            if req is not None:
                return req
        req = self._served.get(rid)
        if req is not None:
            return req
        for req in self._orphans:
            if req.rid == rid:
                return req
        return None

    # -- driving ---------------------------------------------------------

    def step(self) -> dict:
        """One cluster iteration: every live replica steps once (the
        shared logical clock) under the supervisor's watch, then
        disaggregated migrations run, the supervisor polls (missed-
        beat detection, restarts, orphan re-homing) and finished
        drains are retired.  Returns the merged {rid: [tokens]} map."""
        self._tick += 1
        emitted: dict = {}
        for rep in list(self.replicas):
            if rep.state in DEAD_STATES:
                continue
            for rid, toks in self.supervisor.step_replica(rep).items():
                emitted.setdefault(rid, []).extend(toks)
        if self.disaggregated:
            self._migrate()
        self.supervisor.poll()
        for rep in self.replicas:
            if rep.state == "draining" and rep.engine.in_flight == 0:
                rep.state = "drained"
                if self._obs is not None:
                    self._obs.events.log("replica.drained",
                                         replica=rep.name,
                                         tick=self._tick)
        self._publish_gauges()
        return emitted

    def run(self, max_steps=100000) -> dict:
        while self.in_flight:
            if self._tick >= max_steps:
                raise RuntimeError(
                    f"cluster did not drain in {max_steps} steps")
            self.step()
        return self.stats()

    @property
    def tick(self) -> int:
        return self._tick

    @property
    def in_flight(self) -> int:
        return len(self._orphans) + sum(
            rep.engine.in_flight for rep in self.replicas
            if rep.state not in DEAD_STATES)

    # -- elastic scale ---------------------------------------------------

    def fail(self, name, reason="operator") -> Replica:
        """Force one replica FAILED (ops hook and the tests' kill
        switch): in-flight requests fail over immediately, the
        supervisor owns the restart/breaker follow-up."""
        rep = self.replica(name) if not isinstance(name, Replica) \
            else name
        self.supervisor.fail(rep, reason)
        return rep

    def drain(self, name) -> Replica:
        """Close one replica's admission and re-steer its queued
        requests; prefilling/running work finishes in place and the
        replica retires (state ``drained``) once idle.  Refuses to
        drain the last admitting replica — the fleet must keep
        accepting traffic.

        Idempotency is deterministic: draining an already
        ``draining``/``drained`` replica is a pure no-op (same object
        back, no counters, no re-steer, no fault firing); draining a
        ``failed``/``restarting``/``retired`` replica raises — there
        is nothing to hand off and pretending otherwise would hide a
        dead box from the operator."""
        rep = self.replica(name) if not isinstance(name, Replica) \
            else name
        if rep.state in ("draining", "drained"):
            if self._obs is not None:
                self._obs.events.log("replica.drain", replica=rep.name,
                                     idempotent=1, tick=self._tick)
            return rep
        if rep.state != "active":
            raise ValueError(
                f"cannot drain {rep.name}: state={rep.state!r} "
                f"(only active replicas drain)")
        targets = [r for r in self.replicas
                   if r is not rep and r.admitting]
        if rep.admitting and not targets:
            raise RuntimeError(
                f"cannot drain {rep.name}: it is the last admitting "
                f"replica")
        try:
            faults.fire("replica.drain", "before")
        except faults.InjectedFault:
            # drain aborted before anything moved: replica stays active
            self.drains_aborted += 1
            if self._obs is not None:
                self._obs.events.log("replica.drain", replica=rep.name,
                                     aborted=1, tick=self._tick)
            return rep
        rep.state = "draining"
        sch = rep.engine.scheduler
        moved = list(sch.queue)
        for req in moved:
            sch.queue.remove(req)
            sch.requests.pop(req.rid, None)
            self._owner.pop(req.rid, None)
        for req in moved:
            dst, aff = self.router.pick(targets, req.resume_ids)
            dst.engine.scheduler.add(req)
            self._owner[req.rid] = dst
            self.resteered += 1
            if self._obs is not None:
                self._obs.events.log(
                    "route.decide", rid=req.rid, replica=dst.name,
                    policy=self.router.policy, aff_tokens=int(aff),
                    depth=dst.depth,
                    free_pages=dst.engine.executor.free_pages,
                    degraded=0, resteer=1, tick=self._tick)
        try:
            faults.fire("replica.drain", "after")
        except faults.InjectedFault:
            pass                    # the drain is already committed
        self.drains += 1
        if self._obs is not None:
            self._obs.events.log(
                "replica.drain", replica=rep.name, aborted=0,
                resteered=len(moved), in_flight=rep.engine.in_flight,
                tick=self._tick)
        return rep

    def join(self, role=None):
        """Add a fresh replica to the fleet.  Under AOT the new
        engine's warmup resolves from the shared persistent compile
        cache (disk hits, zero compiles) — elastic join in seconds.
        Returns the new :class:`Replica`, or None when an injected
        ``replica.join`` fault aborts the build (fleet unchanged).

        Deterministic while a drain is in progress: the join commits
        independently (fresh name, fresh engine), never resurrects or
        touches the draining replica, and the draining replica's
        re-steered queue may land on the newcomer on the NEXT routing
        decision only — the in-progress transition is untouched."""
        if role is None:
            role = "decode" if self.disaggregated else "mixed"
        if role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                f"join role must be mixed|prefill|decode, got {role!r}")
        try:
            faults.fire("replica.join", "before")
        except faults.InjectedFault:
            self.joins_aborted += 1
            if self._obs is not None:
                self._obs.events.log("replica.join", aborted=1,
                                     tick=self._tick)
            return None
        rep = self._build_replica(role=role)
        try:
            faults.fire("replica.join", "after")
        except faults.InjectedFault:
            pass            # engine built and warmed: join committed
        self.joins += 1
        if self._obs is not None:
            report = rep.engine._aot_report or {}
            self._obs.events.log(
                "replica.join", replica=rep.name, role=role, aborted=0,
                aot_compiled=int(report.get("compile", 0)),
                aot_disk=int(report.get("disk", 0)), tick=self._tick)
        return rep

    # -- disaggregated prefill -> decode handoff ------------------------

    def _migrate(self):
        decode_reps = [r for r in self.replicas
                       if r.role == "decode" and r.state == "active"]
        if not decode_reps:
            return
        for rep in self.replicas:
            if rep.role != "prefill" or rep.state == "drained":
                continue
            for req in list(rep.engine.scheduler.running):
                self._handoff(rep, req, decode_reps)

    def _handoff(self, src, req, decode_reps) -> bool:
        """Ship one RUNNING sequence's KV pages from a prefill replica
        to a decode replica as one bulk copy, then move the request.
        Skips (request keeps decoding on the source — degradation,
        never loss) when no decode replica has room or an injected
        ``kv.handoff`` before-fault fires."""
        src_ex = src.engine.executor
        length = int(src_ex.cache.lengths[req.sid])
        dst = None
        for cand in sorted(
                decode_reps,
                key=lambda r: (r.depth, -r.engine.executor.free_pages)):
            ex = cand.engine.executor
            if ex.free_slots >= 1 \
                    and ex.free_pages >= ex.pages_for(length + 1):
                dst = cand
                break
        if dst is None:
            self.handoffs_skipped += 1
            return False
        try:
            faults.fire("kv.handoff", "before")
        except faults.InjectedFault:
            self.handoffs_skipped += 1
            if self._obs is not None:
                self._obs.events.log("kv.handoff", rid=req.rid,
                                     src=src.name, dst=dst.name,
                                     skipped=1, tick=self._tick)
            return False
        dst_ex = dst.engine.executor
        k, v = src_ex.cache.gather_dense(req.sid, length)
        dst_sid = dst_ex.alloc_slot()
        dst_ex.cache.write_at(dst_sid, k[:, :, :length],
                              v[:, :, :length], 0)
        dst_ex.last_token[dst_sid] = src_ex.last_token[req.sid]
        try:
            faults.fire("kv.handoff", "after")
        except faults.InjectedFault:
            pass    # pages landed refcounted: the handoff commits
        src_sch = src.engine.scheduler
        if src_sch.spec is not None:
            src_sch.spec.on_release(req)
        src_sch.running.remove(req)
        src_sch.requests.pop(req.rid, None)
        src_ex.free_slot(req.sid)
        src_sch._pending = None   # any parked plan names the old sid
        dst_sch = dst.engine.scheduler
        req.sid = dst_sid
        dst_sch.requests[req.rid] = req
        dst_sch.running.append(req)
        dst_sch._pending = None   # predicted running set just changed
        if dst_sch.spec is not None:
            dst_sch.spec.on_running(req)
        self._owner[req.rid] = dst
        self.handoffs += 1
        self.handoff_tokens += length
        pages = int((dst_ex.cache.page_table[dst_sid] >= 0).sum())
        if self._obs is not None:
            self._obs.events.log(
                "kv.handoff", rid=req.rid, src=src.name, dst=dst.name,
                skipped=0, tokens=length, pages=pages, tick=self._tick)
            self._obs.tracer.instant(
                "kv.handoff", cat="serve", trace_id=req.rid,
                src=src.name, dst=dst.name, tokens=length)
        return True

    # -- observability ---------------------------------------------------

    def _publish_gauges(self):
        h = self._obs
        if h is None:
            return
        reg = h.registry
        g_pages = reg.gauge("cluster_replica_free_pages",
                            "Free KV pages on one fleet replica",
                            labels=("replica",))
        g_depth = reg.gauge(
            "cluster_replica_in_flight",
            "Queued+prefilling+running requests on one fleet replica",
            labels=("replica",))
        g_state = reg.gauge(
            "cluster_replica_state",
            "Replica lifecycle (0=active, 1=draining, 2=drained, "
            "3=failed, 4=restarting, 5=retired)",
            labels=("replica",))
        for rep in self.replicas:
            g_pages.labels(replica=rep.name).set(
                rep.engine.executor.free_pages)
            g_depth.labels(replica=rep.name).set(rep.depth)
            g_state.labels(replica=rep.name).set(
                REPLICA_STATES.index(rep.state))
        reg.gauge("cluster_replicas_active",
                  "Fleet replicas currently accepting work").set(
            sum(1 for r in self.replicas if r.state == "active"))
        reg.gauge("cluster_orphan_requests",
                  "Failed-over requests still awaiting a healthy "
                  "replica").set(len(self._orphans))

    def _statusz(self) -> dict:
        return {
            "tick": self._tick,
            "enabled": self.enabled,
            "disaggregated": self.disaggregated,
            "router": {
                "policy": self.router.policy,
                "decisions": self.router.decisions,
                "affinity_hits": self.router.affinity_hits,
                "degraded": self.router.degraded,
                "resteered": self.resteered,
            },
            "handoffs": {
                "done": self.handoffs,
                "tokens": self.handoff_tokens,
                "skipped": self.handoffs_skipped,
            },
            "drains": {"done": self.drains,
                       "aborted": self.drains_aborted},
            "joins": {"done": self.joins,
                      "aborted": self.joins_aborted},
            "survivability": {
                "failovers": self.failovers,
                "shed": self.sheds,
                "orphans": len(self._orphans),
                "restarts": {"done": self.restarts,
                             "failed": self.restarts_failed},
                "retired": self.retired,
            },
            "replicas": [
                {
                    "name": rep.name,
                    "role": rep.role,
                    "state": rep.state,
                    "tick": rep.engine.tick,
                    "in_flight": rep.engine.in_flight,
                    "queued": len(rep.engine.scheduler.queue),
                    "running": len(rep.engine.scheduler.running),
                    "pool": {
                        "num_pages":
                            rep.engine.executor.cache.num_pages,
                        "free_pages": rep.engine.executor.free_pages,
                    },
                    "prefix": (None if rep.engine.prefix is None
                               else rep.engine.prefix.stats()),
                }
                for rep in self.replicas
            ],
        }

    def _durability_statusz(self) -> dict:
        """/statusz provider: WAL segment/fsync state, dedup hits,
        salvage counters and the last recovery report."""
        return {
            "wal": None if self.wal is None else self.wal.statusz(),
            "dedup_hits": self.dedup_hits,
            "salvage": {
                "enabled": self.salvage,
                "done": self.salvages,
                "failed": self.salvages_failed,
                "pages": self.salvaged_pages,
            },
            "recovery": self.recovery,
        }

    def _survivability_statusz(self) -> dict:
        """/statusz provider: supervisor policy, recovery counters,
        and the per-replica breaker table."""
        return {
            "tick": self._tick,
            "policy": self.supervisor.statusz(),
            "admission": {
                "max_queue": self.max_queue,
                "shed_deadlines": self.shed_deadlines,
                "queued": self._queued_total(),
            },
            "failovers": self.failovers,
            "shed": self.sheds,
            "orphans": len(self._orphans),
            "restarts": {"done": self.restarts,
                         "failed": self.restarts_failed},
            "retired": self.retired,
            "replicas": [
                {
                    "name": rep.name,
                    "state": rep.state,
                    "hung": rep.hung,
                    "last_beat": rep.last_beat,
                    "missed_beats": max(0, self._tick - rep.last_beat),
                    "fails": rep.fails,
                    "fail_streak": rep.fail_streak,
                    "restarts": rep.restarts,
                    "restart_at": rep.restart_at,
                    "probation_until": rep.probation_until,
                }
                for rep in self.replicas
            ],
        }

    def stats(self) -> dict:
        """Aggregate fleet stats plus each replica's full engine
        stats.  ``agg_tok_per_step`` is the fleet-level throughput on
        the LOGICAL clock — decode tokens per cluster tick — the
        scaling count the tests read (wall time cannot scale when N
        simulated replicas share one CPU)."""
        per = {rep.name: rep.engine.stats() for rep in self.replicas}
        reqs: dict = {}
        for p in per.values():
            for k, n in p["requests"].items():
                reqs[k] = reqs.get(k, 0) + n
        decode = sum(p["decode_tokens"] for p in per.values())
        prefill = sum(p["prefill_tokens"] for p in per.values())
        cached = sum(p["cached_tokens"] for p in per.values())
        return {
            "steps": self._tick,
            "replicas": len(self.replicas),
            "requests": reqs,
            "decode_tokens": decode,
            "prefill_tokens": prefill,
            "cached_tokens": cached,
            "agg_tok_per_step": round(decode / max(self._tick, 1), 4),
            "prefix_hit_rate": round(
                cached / max(cached + prefill, 1), 4),
            "router": {
                "policy": self.router.policy,
                "decisions": self.router.decisions,
                "affinity_hits": self.router.affinity_hits,
                "degraded": self.router.degraded,
                "resteered": self.resteered,
            },
            "handoffs": self.handoffs,
            "handoffs_skipped": self.handoffs_skipped,
            "failovers": self.failovers,
            "shed": self.sheds,
            "orphans": len(self._orphans),
            "restarts": self.restarts,
            "restarts_failed": self.restarts_failed,
            "retired": self.retired,
            "salvages": self.salvages,
            "salvages_failed": self.salvages_failed,
            "salvaged_pages": self.salvaged_pages,
            "dedup_hits": self.dedup_hits,
            "wal_appended": (0 if self.wal is None
                             else self.wal.appended),
            "per_replica": per,
        }

    # -- whole-process crash recovery -----------------------------------

    @classmethod
    def recover(cls, model, wal_dir, **kwargs) -> "ServingCluster":
        """Rebuild a serving fleet from its write-ahead journal after
        a whole-process crash (SIGKILL included).

        Replays the journal (torn tails truncated, corrupt records
        skipped and counted), rebuilds the cluster — AOT re-warmed
        from the persistent compile cache when configured, so a warmed
        cache means zero fresh compiles — and then settles every
        journaled request into exactly one of:

        - **served from the log**: a finish record whose token count
          and crc32 match the replayed stream (or a reject record)
          restores the terminal request verbatim — no recompute;
        - **resubmitted**: anything in flight at the crash (or whose
          tail records were torn/corrupt) re-enters through the
          preemption-recompute idiom — prompt + replayed tokens
          re-prefill and decoding resumes, so the final stream is
          bit-identical to an uninterrupted run.

        Journaling continues into the same directory (a fresh
        segment), so recovery is itself crash-safe and repeatable.
        Client resubmits of any journaled rid dedupe to the restored
        request (at-least-once submission, exactly-once result).
        ``cluster.recovery`` holds the report; ``recovered_handles``
        maps every journaled rid to a live handle.  Deadlines are not
        reconstructed — the logical clock restarted.
        """
        records, report = wal_mod.replay(wal_dir)
        cl = cls(model, wal=wal_dir, **kwargs)
        by: dict = {}
        order: list = []
        for rec in records:
            t, rid = rec.get("t"), rec.get("rid")
            if rid is None:
                continue
            e = by.get(rid)
            if e is None:
                e = by[rid] = {"tokens": []}
                order.append(rid)
            if t == "submit":
                if "reject" in e:
                    # shed rids are deliberately not deduped, so a
                    # submit record AFTER a reject is the client's
                    # post-backoff retry: it supersedes the rejection
                    # and starts a fresh stream
                    e["submit"] = rec
                    e["tokens"] = []
                    del e["reject"]
                elif "submit" not in e:
                    e["submit"] = rec   # at-least-once: first write wins
            elif t == "token":
                # only the contiguous-from-zero prefix is trustworthy:
                # a corrupt interior token record leaves a gap, and a
                # token past a gap must be recomputed, not replayed (a
                # later incarnation's re-journaled tokens re-extend the
                # prefix exactly where the verified copy ends)
                if int(rec.get("i", len(e["tokens"]))) == len(e["tokens"]):
                    e["tokens"].append(int(rec["tok"]))
            elif t == "finish":
                e["finish"] = rec
            elif t == "reject":
                e["reject"] = rec
        # advance the auto-rid counter past every journaled req-N so a
        # fresh anonymous submit can never collide with (and silently
        # dedup to) a recovered request
        for rid in by:
            if isinstance(rid, str) and rid.startswith("req-"):
                try:
                    cl._next_rid = max(cl._next_rid, int(rid[4:]) + 1)
                except ValueError:
                    pass
        served = resubmitted = 0
        cl.recovered_handles = {}
        for seq, rid in enumerate(order):
            e = by[rid]
            sub = e.get("submit")
            if sub is None:
                if "reject" in e:
                    # shed at the boundary and never resubmitted: the
                    # rejection (with its retry_after) was already
                    # delivered live, and shed rids are deliberately
                    # not deduped — nothing to restore
                    continue
                # lifecycle records without a submit record (its line
                # was corrupt): there is no prompt to recompute from —
                # surface it in the report, the client's at-least-once
                # resubmit serves it fresh
                report["corrupt"] += 1
                continue
            req = Request(rid, np.asarray(sub["prompt"], np.int32),
                          max_new_tokens=sub["max_new"],
                          priority=sub.get("prio", 0),
                          arrival_seq=seq)
            req.recovered = True
            fin, rej, toks = e.get("finish"), e.get("reject"), e["tokens"]
            if rej is not None:
                req.state = RequestState.REJECTED
                req.finish_reason = rej["reason"]
                req.retry_after = int(rej["retry_after"])
                req.error = RequestRejected(rid, rej["reason"],
                                            rej["retry_after"])
                # like the live shed path, NOT added to the dedup set:
                # a retry_after verdict is an invitation to resubmit
                # the same rid after backing off
                served += 1
            elif fin is not None and fin["n"] == len(toks) \
                    and fin["crc"] == stream_crc(toks):
                # the journaled stream is provably complete: serve it
                # straight from the log, zero recompute
                req.generated = list(toks)
                req.state = RequestState(fin["state"])
                req.finish_reason = fin["reason"]
                if req.state is RequestState.FAILED:
                    req.error = RuntimeError(fin["reason"])
                cl._served[rid] = req
                served += 1
            else:
                # in flight at the crash (or its finish/token records
                # were torn): the preemption-recompute idiom resumes
                # it bit-identically after the replayed prefix
                req.generated = list(toks)
                req.resume_ids = np.concatenate(
                    [req.prompt_ids,
                     np.asarray(toks, np.int32)]).astype(np.int32)
                req.prefill_done = 0
                req.state = RequestState.QUEUED
                if not cl.supervisor._place(req):
                    cl._orphans.append(req)
                resubmitted += 1
            cl.recovered_handles[rid] = RequestHandle(cl, req)
        cl.recovery = {
            "segments": report["segments"],
            "records": report["records"],
            "corrupt": report["corrupt"],
            "torn_bytes": report["torn_bytes"],
            "served_from_log": served,
            "resubmitted": resubmitted,
            "orphaned": len(cl._orphans),
        }
        if cl.wal is not None:
            cl.wal.append({"t": "recover", **cl.recovery})
            cl.wal.fsync()
        if cl._obs is not None:
            cl._obs.events.log("wal.replay", dir=os.fspath(wal_dir),
                               **cl.recovery)
        return cl
