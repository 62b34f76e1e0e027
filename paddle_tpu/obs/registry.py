"""Process-wide metric registry: counters, gauges, histograms.

Reference shape: the Prometheus client-library data model (a registry
of metric FAMILIES, each fanning out to children per label-value
tuple), because that is what every serving fleet scrapes.  Two export
surfaces:

- :meth:`MetricRegistry.prometheus_text` — the text exposition format
  (``# HELP`` / ``# TYPE`` headers, ``_bucket{le=...}``/``_sum``/
  ``_count`` triplets for histograms), deterministically ordered so
  seeded tests can assert on the exact string.
- :meth:`MetricRegistry.snapshot` — the same data as a plain JSON-able
  dict for programmatic consumers (``tools/obs_dump.py``).

No background threads, no atomics beyond the GIL: producers are the
single-threaded scheduler / train loop, and the registry is swapped
wholesale by ``obs.configure`` rather than mutated concurrently.
"""
from __future__ import annotations

import math
import re

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

#: Prometheus default latency buckets (seconds) — wide enough for both
#: sub-ms scheduler ticks and multi-second compiles.
DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


def _check_name(kind, name, regex=_NAME_RE):
    if not regex.match(name):
        raise ValueError(f"invalid {kind} name {name!r}")


def _fmt(v):
    """Deterministic sample rendering: integral values print as ints
    (``3`` not ``3.0``), the rest via repr of the float."""
    f = float(v)
    if math.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _escape(v):
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
                 .replace("\n", "\\n")


class _Counter:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def inc(self, n=1):
        if n < 0:
            raise ValueError(f"counters only go up; inc({n})")
        self.value += n


class _Gauge:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v):
        self.value = float(v)

    def inc(self, n=1):
        self.value += n

    def dec(self, n=1):
        self.value -= n


class _Histogram:
    __slots__ = ("buckets", "counts", "sum", "count")

    def __init__(self, buckets):
        self.buckets = buckets            # ascending upper bounds
        self.counts = [0] * (len(buckets) + 1)  # + overflow (+Inf)
        self.sum = 0.0
        self.count = 0

    def observe(self, v):
        v = float(v)
        self.sum += v
        self.count += 1
        for i, ub in enumerate(self.buckets):
            if v <= ub:
                self.counts[i] += 1
                return
        self.counts[-1] += 1


_CHILD = {"counter": _Counter, "gauge": _Gauge, "histogram": _Histogram}


class Family:
    """One named metric family; children keyed by label-value tuple.

    A family declared with no label names acts as its own single child:
    ``registry.counter("x").inc()`` works without ``.labels()``.
    """

    def __init__(self, name, mtype, help="", labelnames=(),
                 buckets=None):
        _check_name("metric", name)
        self.name = name
        self.type = mtype
        self.help = help
        self.labelnames = tuple(labelnames)
        for ln in self.labelnames:
            _check_name("label", ln, _LABEL_RE)
        self.buckets = (tuple(buckets) if buckets is not None
                        else DEFAULT_BUCKETS)
        if mtype == "histogram" and \
                list(self.buckets) != sorted(self.buckets):
            raise ValueError(f"histogram buckets must ascend: "
                             f"{self.buckets}")
        self._children = {}
        if not self.labelnames:
            self._children[()] = self._make_child()

    def _make_child(self):
        if self.type == "histogram":
            return _Histogram(self.buckets)
        return _CHILD[self.type]()

    def labels(self, **kv):
        if set(kv) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, "
                f"got {tuple(sorted(kv))}")
        key = tuple(str(kv[ln]) for ln in self.labelnames)
        child = self._children.get(key)
        if child is None:
            child = self._children[key] = self._make_child()
        return child

    # -- label-less convenience (proxy to the default child) ------------

    def _default(self):
        if self.labelnames:
            raise ValueError(f"{self.name} has labels {self.labelnames};"
                             f" use .labels(...)")
        return self._children[()]

    def inc(self, n=1):
        self._default().inc(n)

    def set(self, v):
        self._default().set(v)

    def dec(self, n=1):
        self._default().dec(n)

    def observe(self, v):
        self._default().observe(v)


class MetricRegistry:
    """Name -> :class:`Family`; declaration is idempotent (the same
    name with the same type/labels returns the existing family, a
    conflicting redeclaration raises)."""

    def __init__(self):
        self._families = {}

    def _declare(self, name, mtype, help, labels, buckets=None):
        fam = self._families.get(name)
        if fam is not None:
            if fam.type != mtype or fam.labelnames != tuple(labels):
                raise ValueError(
                    f"metric {name!r} redeclared as {mtype}"
                    f"{tuple(labels)} (was {fam.type}{fam.labelnames})")
            return fam
        fam = Family(name, mtype, help=help, labelnames=labels,
                     buckets=buckets)
        self._families[name] = fam
        return fam

    def counter(self, name, help="", labels=()):
        return self._declare(name, "counter", help, labels)

    def gauge(self, name, help="", labels=()):
        return self._declare(name, "gauge", help, labels)

    def histogram(self, name, help="", labels=(), buckets=None):
        return self._declare(name, "histogram", help, labels, buckets)

    def get(self, name):
        return self._families.get(name)

    # -- export ----------------------------------------------------------

    @staticmethod
    def _labelstr(labelnames, key, extra=None):
        # label keys sorted by name: the exposition never depends on
        # declaration order
        parts = [f'{ln}="{_escape(v)}"'
                 for ln, v in sorted(zip(labelnames, key))]
        if extra:
            parts.append(extra)
        return "{" + ",".join(parts) + "}" if parts else ""

    def prometheus_text(self):
        lines = []
        for name in sorted(self._families):
            fam = self._families[name]
            if fam.help:
                lines.append(f"# HELP {name} {fam.help}")
            lines.append(f"# TYPE {name} {fam.type}")
            for key in sorted(fam._children):
                child = fam._children[key]
                if fam.type == "histogram":
                    cum = 0
                    for ub, c in zip(fam.buckets, child.counts):
                        cum += c
                        ls = self._labelstr(fam.labelnames, key,
                                            f'le="{_fmt(ub)}"')
                        lines.append(f"{name}_bucket{ls} {cum}")
                    ls = self._labelstr(fam.labelnames, key, 'le="+Inf"')
                    lines.append(f"{name}_bucket{ls} {child.count}")
                    ls = self._labelstr(fam.labelnames, key)
                    lines.append(f"{name}_sum{ls} {_fmt(child.sum)}")
                    lines.append(f"{name}_count{ls} {child.count}")
                else:
                    ls = self._labelstr(fam.labelnames, key)
                    lines.append(f"{name}{ls} {_fmt(child.value)}")
        return "\n".join(lines) + ("\n" if lines else "")

    def snapshot(self):
        out = {}
        for name in sorted(self._families):
            fam = self._families[name]
            samples = []
            for key in sorted(fam._children):
                child = fam._children[key]
                labels = dict(zip(fam.labelnames, key))
                if fam.type == "histogram":
                    samples.append({
                        "labels": labels,
                        "buckets": {_fmt(ub): c for ub, c in
                                    zip(fam.buckets, child.counts)},
                        "overflow": child.counts[-1],
                        "sum": child.sum,
                        "count": child.count,
                    })
                else:
                    samples.append({"labels": labels,
                                    "value": child.value})
            out[name] = {"type": fam.type, "help": fam.help,
                         "samples": samples}
        return out
