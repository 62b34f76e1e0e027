"""Serving health plane: declarative SLOs, error-budget burn-rate
alerts, and the payloads behind ``/healthz`` / ``/statusz``.

The model is the SRE multi-window multi-burn-rate recipe: an
objective declares a target fraction of good events (e.g. "99% of
requests see TTFT <= 250 ms"), the error budget is ``1 - target``, and
the burn rate over a window is the observed bad fraction divided by
the budget (burn 1.0 = spending exactly the budget; 14.4 over a 5 m
and a 1 h window together = the classic page-now pair).  An alert rule
fires only when BOTH its short and long window exceed the threshold —
the short window gives fast detection, the long one keeps a brief
blip from paging.

Everything reads the obs clock and the metric registry, so on a
:class:`~paddle_tpu.obs.trace.LogicalClock` the whole plane — burn
values, fire/resolve steps — is exact and unit-testable.  Objectives
read CUMULATIVE counters and take window deltas between snapshots, so
evaluation frequency only affects resolution, never correctness.

Exported series::

    slo_burn_rate{slo,window}      # per evaluated window
    slo_budget_remaining{slo}      # over the longest rule window
    slo_alert_state{slo}           # 0=ok 1=warn 2=page

State transitions emit ``alert.fire`` / ``alert.resolve`` flight
events (which tee into the structured event log).
"""
from __future__ import annotations

import os
import sys
from collections import deque, namedtuple

#: (short_s, long_s, threshold, severity) — fires when the burn rate
#: over BOTH windows is >= threshold.
BurnRule = namedtuple("BurnRule", "short_s long_s threshold severity")

#: Google SRE defaults: fast 5m/1h pair pages at 14.4x budget burn,
#: slow 6h/3d pair warns at 1.0x (budget exhausted on trend).
DEFAULT_BURN_RULES = (
    BurnRule(short_s=300.0, long_s=3600.0, threshold=14.4,
             severity="page"),
    BurnRule(short_s=21600.0, long_s=259200.0, threshold=1.0,
             severity="warn"),
)

SEVERITY_RANK = {"ok": 0, "warn": 1, "page": 2}


def _check_target(name, target):
    if not 0.0 < target < 1.0:
        raise ValueError(f"SLO {name!r}: target must be in (0, 1), "
                         f"got {target}")


class LatencyObjective:
    """"``target`` fraction of observations land at or below
    ``threshold_s``" over a registry histogram family.

    ``threshold_s`` must be one of the family's bucket upper bounds —
    the good-count is then exact (cumulative bucket count), not an
    interpolation.  A mismatched threshold raises at first read.
    """

    def __init__(self, name, family, threshold_s, target):
        _check_target(name, target)
        self.name = name
        self.family = family
        self.threshold_s = float(threshold_s)
        self.target = float(target)

    def read(self, registry):
        """Cumulative ``(bad, total)`` summed over all children."""
        fam = registry.get(self.family)
        if fam is None:
            return 0, 0
        try:
            idx = fam.buckets.index(self.threshold_s)
        except ValueError:
            raise ValueError(
                f"SLO {self.name!r}: threshold {self.threshold_s} is "
                f"not a bucket bound of {self.family} "
                f"(buckets: {fam.buckets})")
        good = total = 0
        for child in fam._children.values():
            good += sum(child.counts[:idx + 1])
            total += child.count
        return total - good, total

    def describe(self):
        return {"kind": "latency", "family": self.family,
                "threshold_s": self.threshold_s}


class RatioObjective:
    """"At most ``1 - target`` of events are bad" over two counter
    selectors.

    ``bad`` / ``total`` are ``(family, labels)`` pairs; ``labels`` is a
    subset filter over the family's children (``None`` = sum all).
    """

    def __init__(self, name, bad, total, target):
        _check_target(name, target)
        self.name = name
        self.bad = bad
        self.total = total
        self.target = float(target)

    @staticmethod
    def _sum(registry, selector):
        family, labels = selector
        fam = registry.get(family)
        if fam is None:
            return 0.0
        acc = 0.0
        for key, child in fam._children.items():
            if labels:
                child_labels = dict(zip(fam.labelnames, key))
                if any(child_labels.get(k) != str(v)
                       for k, v in labels.items()):
                    continue
            acc += child.value
        return acc

    def read(self, registry):
        return (self._sum(registry, self.bad),
                self._sum(registry, self.total))

    def describe(self):
        return {"kind": "ratio", "bad": list(self.bad[0:1]) + [
            self.bad[1] or {}], "total": self.total[0]}


def default_serving_slos():
    """The stock serving objectives: TTFT p99 <= 250 ms and request
    error rate <= 0.1%."""
    return [
        LatencyObjective("serve_ttft", "serve_ttft_seconds",
                         threshold_s=0.25, target=0.99),
        RatioObjective(
            "serve_errors",
            bad=("serve_requests_total", {"state": "failed"}),
            total=("serve_requests_submitted_total", None),
            target=0.999),
    ]


def default_train_slos():
    """The stock training objective: at most 1% of optimizer steps
    flagged anomalous by the guardian (NaN/Inf loss, grad blowup,
    loss spike)."""
    return [
        RatioObjective(
            "train_anomalies",
            bad=("guardian_anomalies_total", None),
            total=("train_steps_total", None),
            target=0.99),
    ]


class SLOEngine:
    """Evaluates objectives against the registry, maintains the
    per-SLO burn-rate windows, and runs the OK→WARN→PAGE alert state
    machine.

    Built only when telemetry is on (callers follow the producer
    idiom: check ``obs.handle()`` first).  ``evaluate`` is driven from
    the owner's step loop — ``ServingEngine.step`` and ``Model.fit``.
    """

    def __init__(self, objectives, rules=DEFAULT_BURN_RULES,
                 handle=None, source="serving", now=None):
        if handle is None:
            from .. import obs
            handle = obs.handle()
        if handle is None:
            raise RuntimeError("SLOEngine requires telemetry on "
                               "(obs.handle() is None)")
        self._h = handle
        self.source = source
        self.objectives = list(objectives)
        self.rules = tuple(BurnRule(*r) for r in rules)
        if not self.rules:
            raise ValueError("SLOEngine needs at least one BurnRule")
        for r in self.rules:
            if r.severity not in ("warn", "page"):
                raise ValueError(f"unknown severity {r.severity!r}")
            if r.short_s > r.long_s:
                raise ValueError(f"rule windows must be short<=long: {r}")
        self.windows = tuple(sorted({w for r in self.rules
                                     for w in (r.short_s, r.long_s)}))
        self._max_window = max(self.windows)
        r = handle.registry
        self._g_burn = r.gauge(
            "slo_burn_rate",
            "Error-budget burn rate per SLO and window",
            labels=("slo", "window"))
        self._g_budget = r.gauge(
            "slo_budget_remaining",
            "Fraction of error budget left over the longest window",
            labels=("slo",))
        self._g_state = r.gauge(
            "slo_alert_state", "Alert state: 0=ok 1=warn 2=page",
            labels=("slo",))
        names = [o.name for o in self.objectives]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate SLO names: {names}")
        self._samples = {}   # name -> deque[(t, bad, total)]
        self._state = {}
        self._last = {}      # name -> latest table row
        t0 = handle.clock() if now is None else now
        for obj in self.objectives:
            bad, total = obj.read(r)
            self._samples[obj.name] = deque([(t0, bad, total)])
            self._state[obj.name] = "ok"
            self._g_state.labels(slo=obj.name).set(0)
        # newest engine wins per source (same convention as statusz
        # providers): rebuilding a ServingEngine or re-entering fit
        # must not accumulate stale SLO rows
        handle.slo_engines[:] = [e for e in handle.slo_engines
                                 if e.source != source] + [self]

    # -- burn math ------------------------------------------------------

    @staticmethod
    def _baseline(dq, cutoff):
        """Latest sample at or before ``cutoff``; the oldest retained
        sample when the window predates history."""
        base = dq[0]
        for s in dq:
            if s[0] <= cutoff:
                base = s
            else:
                break
        return base

    def _burn(self, dq, now, window, budget):
        t_b, bad_b, total_b = self._baseline(dq, now - window)
        t_n, bad_n, total_n = dq[-1]
        d_total = total_n - total_b
        if d_total <= 0:
            return 0.0
        return ((bad_n - bad_b) / d_total) / budget

    # -- the step hook --------------------------------------------------

    def evaluate(self, step=None, now=None):
        """Take one snapshot of every objective, update burn gauges,
        and advance the alert state machine.  ``step`` is the owner's
        logical step, stamped into alert events so deterministic tests
        can assert the exact firing step; owners driving a hot loop
        pass ``now`` (a timestamp they already read) so evaluation
        adds no clock reads."""
        h = self._h
        if now is None:
            now = h.clock()
        for obj in self.objectives:
            budget = 1.0 - obj.target
            bad, total = obj.read(h.registry)
            dq = self._samples[obj.name]
            dq.append((now, bad, total))
            # keep one sample older than the longest window as the
            # baseline; drop the rest of the stale prefix
            while len(dq) >= 2 and dq[1][0] <= now - self._max_window:
                dq.popleft()
            burns = {w: self._burn(dq, now, w, budget)
                     for w in self.windows}
            for w, b in burns.items():
                self._g_burn.labels(slo=obj.name,
                                    window=f"{w:g}s").set(b)
            remaining = 1.0 - burns[self._max_window]
            self._g_budget.labels(slo=obj.name).set(remaining)

            new_state = "ok"
            for rule in self.rules:
                if (burns[rule.short_s] >= rule.threshold
                        and burns[rule.long_s] >= rule.threshold
                        and SEVERITY_RANK[rule.severity]
                        > SEVERITY_RANK[new_state]):
                    new_state = rule.severity
            old_state = self._state[obj.name]
            if new_state != old_state:
                self._state[obj.name] = new_state
                self._g_state.labels(slo=obj.name).set(
                    SEVERITY_RANK[new_state])
                rising = (SEVERITY_RANK[new_state]
                          > SEVERITY_RANK[old_state])
                h.recorder.record(
                    "alert.fire" if rising else "alert.resolve",
                    slo=obj.name, source=self.source, step=step,
                    severity=new_state,
                    burn=round(max(burns.values()), 4),
                    **{"from": old_state, "to": new_state})
            self._last[obj.name] = {
                "slo": obj.name,
                "source": self.source,
                "target": obj.target,
                "state": self._state[obj.name],
                "burn": {f"{w:g}s": round(b, 4)
                         for w, b in burns.items()},
                "budget_remaining": round(remaining, 4),
                "bad": bad,
                "total": total,
                "objective": obj.describe(),
            }
        return self.table()

    def state(self, name):
        return self._state[name]

    def table(self):
        """Latest per-SLO rows (the ``/statusz`` SLO table)."""
        return [self._last.get(o.name,
                               {"slo": o.name, "source": self.source,
                                "target": o.target, "state": "ok",
                                "burn": {}, "budget_remaining": 1.0,
                                "bad": 0, "total": 0,
                                "objective": o.describe()})
                for o in self.objectives]


# -- endpoint payloads (shared by httpd and tools) -----------------------

def build_info():
    import jax

    from .. import __version__ as pt_version
    return {"project": "paddle_tpu", "version": pt_version,
            "python": sys.version.split()[0], "jax": jax.__version__}


def healthz_payload(handle, stale_after_s=None):
    """Liveness + last-step staleness.  Returns ``(ok, payload)``;
    a component is stale when its heartbeat is older than
    ``stale_after_s`` (env ``PT_OBS_STALE_S``, default 600)."""
    if stale_after_s is None:
        stale_after_s = float(os.environ.get("PT_OBS_STALE_S", "600"))
    now = handle.clock()
    components = {}
    ok = True
    for name, ts in sorted(handle.heartbeats.items()):
        age = now - ts
        stale = age > stale_after_s
        ok = ok and not stale
        components[name] = {"last_beat_ts": round(ts, 6),
                            "age_s": round(age, 6), "stale": stale}
    return ok, {"status": "ok" if ok else "stale",
                "now": round(now, 6),
                "stale_after_s": stale_after_s,
                "components": components}


def statusz_payload(handle):
    """The ``/statusz`` JSON: build info, heartbeats, the SLO table
    from every live :class:`SLOEngine`, and per-component provider
    payloads (pool/occupancy from the serving engine, step
    phases from training)."""
    slos = []
    for eng in handle.slo_engines:
        slos.extend(eng.table())
    providers = {}
    for name in sorted(handle.statusz):
        try:
            providers[name] = handle.statusz[name]()
        except Exception as e:  # a dead provider must not kill /statusz
            providers[name] = {"error": repr(e)}
    return {
        "build": build_info(),
        "now": round(handle.clock(), 6),
        "heartbeats": {k: round(v, 6)
                       for k, v in sorted(handle.heartbeats.items())},
        "slos": slos,
        "providers": providers,
        "event_log": {"seq": handle.events.seq,
                      "tail": len(handle.events),
                      "path": handle.events.path},
    }
