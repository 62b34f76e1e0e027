"""Roofline/MFU attribution: analytical cost × measured wall time.

The cost model (``analysis.cost``) prices every registered
:class:`ProgramContract` once; this module joins those static numbers
with runtime signals — step wall time from the producers / Tracer
spans, HBM watermarks from ``device.memory`` — and publishes the
result through the obs plane:

* gauges ``program_mfu{program}``, ``program_hbm_gbps{program}``,
  ``program_flops{program}``, ``roofline_bound{program,bound}``
  (1 on the active classification, 0 on the other),
  ``hbm_peak_bytes`` / ``hbm_bytes_in_use`` / ``hbm_bytes_limit``,
  and ``step_phase_seconds{program,phase}`` from :class:`StepTimer`;
* Perfetto counter tracks (``perf.mfu``, ``perf.hbm``) in the
  Chrome-trace export via ``Tracer.counter``.

Everything here is behind the same ``PT_OBS`` gate as the rest of the
plane: with obs off every entry point is one ``None`` check, and with
obs on the join must stay inside the ≤3% ``obs_overhead`` bench
contract — hence the cost trace is cached on the contract (first call
only, normally absorbed by the warmup/compile step), HBM sampling is
throttled to every :data:`HBM_SAMPLE_EVERY` publishes (the no-stats
fallback walks ``jax.live_arrays()``), and attribution failures are
remembered so a broken program never re-prices per step.
"""
from __future__ import annotations

import jax

#: Per-chip peak dense FLOP/s (bf16) by device_kind substring.  One
#: table for the whole repo — bench.py delegates here.  Figures are the
#: published per-chip peaks (Google Cloud TPU documentation; v5e: 197
#: TFLOP/s bf16, 819 GB/s HBM).  A device_kind with no row raises.
PEAK_FLOPS = (
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5", 197e12),   # v5e / v5 lite family
    ("v4", 275e12),
    ("cpu", 1e12),    # nominal, keeps CPU-run MFU figures finite
)

#: Per-chip peak HBM bandwidth (bytes/s) by device_kind substring.
PEAK_HBM_BYTES_S = (
    ("v6", 1638e9),
    ("v5p", 2765e9),
    ("v5", 819e9),
    ("v4", 1228e9),
    ("cpu", 50e9),    # nominal DDR-class figure
)

#: Publish HBM watermarks every N-th on_program/end_step call per
#: program: the live-array fallback on statless backends is O(arrays).
HBM_SAMPLE_EVERY = 16

_hbm_calls = {}          # program -> publish-call count
_failed_cost = set()     # programs whose cost trace raised: don't retry


def _device_kind():
    d = jax.devices()[0]
    return (getattr(d, "device_kind", "") or d.platform).lower()


def _lookup(table, kind):
    for sub, v in table:
        if sub in kind:
            return v
    raise LookupError(
        f"no peak figures for device_kind {kind!r}: add a row (with its "
        f"source) to obs/perf.py — an unknown device is an error, not "
        f"a default")


def peak_flops_per_chip(device_kind=None):
    """Peak dense FLOP/s for one chip (bf16), from the device kind."""
    return _lookup(PEAK_FLOPS, (device_kind or _device_kind()).lower())


def peak_hbm_bytes_s(device_kind=None):
    """Peak HBM bandwidth (bytes/s) for one chip."""
    return _lookup(PEAK_HBM_BYTES_S,
                   (device_kind or _device_kind()).lower())


def ridge_intensity(device_kind=None):
    """FLOPs/byte at the roofline ridge: programs above it are
    compute-bound, below it bandwidth-bound."""
    kind = (device_kind or _device_kind()).lower()
    return peak_flops_per_chip(kind) / peak_hbm_bytes_s(kind)


def program_cost(name):
    """CostReport for a registered program, or None (unknown program,
    lazy shapes not captured yet, or a previously failed trace)."""
    if name in _failed_cost:
        return None
    from ..analysis import registered

    contract = registered().get(name)
    if contract is None:
        return None
    try:
        return contract.cost()
    except Exception:
        # A program whose cost trace raises must never break (or keep
        # re-pricing inside) the train/serve step.
        _failed_cost.add(name)
        return None


def roofline(cost, wall_s, device_kind=None):
    """Join one CostReport with a measured wall time.

    Returns ``{mfu, hbm_gbps, intensity, bound, flops, hbm_bytes}``;
    ``bound`` classifies against the machine ridge point."""
    if cost is None or wall_s is None or wall_s <= 0:
        return None
    kind = (device_kind or _device_kind()).lower()
    achieved_flops_s = cost.flops / wall_s
    return {
        "mfu": achieved_flops_s / peak_flops_per_chip(kind),
        "hbm_gbps": cost.hbm_bytes / wall_s / 1e9,
        "intensity": cost.arithmetic_intensity,
        "bound": ("compute"
                  if cost.arithmetic_intensity >= ridge_intensity(kind)
                  else "bandwidth"),
        "flops": cost.flops,
        "hbm_bytes": cost.hbm_bytes,
        "wall_s": wall_s,
    }


def _publish(h, name, rl):
    reg = h.registry
    reg.gauge("program_mfu", "Model FLOP utilization per program",
              labels=("program",)).labels(program=name).set(rl["mfu"])
    reg.gauge("program_hbm_gbps", "Achieved HBM GB/s per program",
              labels=("program",)).labels(program=name) \
       .set(rl["hbm_gbps"])
    reg.gauge("program_flops", "Analytical FLOPs per program call",
              labels=("program",)).labels(program=name).set(rl["flops"])
    bound = reg.gauge("roofline_bound",
                      "1 on the active roofline classification",
                      labels=("program", "bound"))
    for b in ("compute", "bandwidth"):
        bound.labels(program=name, bound=b).set(
            1.0 if rl["bound"] == b else 0.0)
    h.tracer.counter("perf.mfu", cat="perf",
                     **{name: round(rl["mfu"], 6)})
    h.tracer.counter("perf.hbm", cat="perf",
                     **{name: round(rl["hbm_gbps"], 3)})


def sample_hbm(h=None):
    """Publish HBM watermark gauges (unthrottled — callers throttle)."""
    from paddle_tpu import obs

    h = h if h is not None else obs.handle()
    if h is None:
        return None
    try:
        from ..device import memory

        wm = memory.watermarks()
    except Exception:
        return None
    reg = h.registry
    reg.gauge("hbm_bytes_in_use", "Current HBM bytes in use") \
       .set(wm["bytes_in_use"])
    reg.gauge("hbm_peak_bytes", "Peak HBM bytes in use") \
       .set(wm["peak_bytes_in_use"])
    reg.gauge("hbm_bytes_limit", "HBM capacity") \
       .set(wm["bytes_limit"])
    h.tracer.counter("perf.hbm_bytes", cat="perf",
                     in_use=wm["bytes_in_use"],
                     peak=wm["peak_bytes_in_use"])
    return wm


def on_program(name, wall_s):
    """Producer entry point: attribute one timed call of a registered
    program.  No-op when obs is off, when the program has no cost yet
    (lazy shapes), or when pricing previously failed."""
    from paddle_tpu import obs

    h = obs.handle()
    if h is None:
        return None
    rl = roofline(program_cost(name), wall_s)
    if rl is None:
        return None
    _publish(h, name, rl)
    n = _hbm_calls.get(name, 0)
    _hbm_calls[name] = n + 1
    if n % HBM_SAMPLE_EVERY == 0:
        sample_hbm(h)
    return rl


def attribute_from_tracer(mapping=None, min_spans=1):
    """Pull-model attribution for programs timed by existing spans
    (the serving scheduler): scan the tracer ring, join mean span wall
    time per name with the program's cost, publish, and return
    ``{program: roofline_dict}``.

    ``mapping`` renames span → program (e.g. ``{"req.prefill":
    "serve.prefill"}``); span names that already match a registered
    program need no entry.  Zero hot-path cost: call at stats/export
    time, not per step."""
    from paddle_tpu import obs

    h = obs.handle()
    if h is None:
        return {}
    from ..analysis import registered

    names = set(registered())
    mapping = dict(mapping or {})
    walls = {}   # program -> [durations]
    for s in h.tracer.spans:
        if s.dur is None:
            continue
        prog = mapping.get(s.name, s.name if s.name in names else None)
        if prog is not None:
            walls.setdefault(prog, []).append(s.dur)
    out = {}
    for prog, durs in sorted(walls.items()):
        if len(durs) < min_spans:
            continue
        rl = roofline(program_cost(prog), sum(durs) / len(durs))
        if rl is None:
            continue
        rl["spans"] = len(durs)
        _publish(h, prog, rl)
        out[prog] = rl
    return out


class StepTimer:
    """Per-step phase breakdown (data-wait / compute / checkpoint /
    obs) for the train loop.

    Null-safe: with obs off every method is one attribute check.  Use::

        timer = StepTimer("train.step")
        with timer.phase("data_wait"):
            batch = next(loader)
        with timer.phase("compute"):
            loss = step(batch)
        timer.end_step()   # publishes phase gauges + roofline

    ``end_step`` publishes ``step_phase_seconds{program,phase}`` per
    phase and, when the program has a cost, the roofline gauges from
    the compute-phase wall time (compute is what the analytical FLOPs
    model; data-wait/checkpoint/obs are host overhead)."""

    PHASES = ("data_wait", "compute", "checkpoint", "obs")

    def __init__(self, program="train.step"):
        self.program = program
        self.steps = 0
        self._acc = {}

    class _Phase:
        __slots__ = ("timer", "name", "_t0", "_clock")

        def __init__(self, timer, name, clock):
            self.timer = timer
            self.name = name
            self._clock = clock
            self._t0 = None

        def __enter__(self):
            if self._clock is not None:
                self._t0 = self._clock()
            return self

        def __exit__(self, *exc):
            if self._clock is not None:
                acc = self.timer._acc
                acc[self.name] = (acc.get(self.name, 0.0)
                                  + self._clock() - self._t0)
            return False

    def phase(self, name):
        from paddle_tpu import obs

        h = obs.handle()
        return self._Phase(self, name,
                           h.clock if h is not None else None)

    def phase_seconds(self):
        """Accumulated {phase: seconds} for the step in flight."""
        return dict(self._acc)

    def end_step(self):
        """Publish and reset the per-step accumulators; returns the
        step's {phase: seconds} (empty when obs is off)."""
        from paddle_tpu import obs

        out, self._acc = self._acc, {}
        h = obs.handle()
        if h is None:
            return {}
        self.steps += 1
        fam = h.registry.gauge("step_phase_seconds",
                               "Wall seconds per step phase",
                               labels=("program", "phase"))
        for ph in self.PHASES:
            if ph in out:
                fam.labels(program=self.program, phase=ph).set(out[ph])
        if out:
            h.tracer.counter("perf.step_phases", cat="perf",
                             **{ph: round(v, 6)
                                for ph, v in sorted(out.items())})
        compute = out.get("compute")
        if compute:
            rl = roofline(program_cost(self.program), compute)
            if rl is not None:
                _publish(h, self.program, rl)
                if (self.steps - 1) % HBM_SAMPLE_EVERY == 0:
                    sample_hbm(h)
        return out


def reset():
    """Clear module-level perf state (failed-cost memo, HBM sampling
    counters); tests call this alongside ``obs.reset``."""
    _hbm_calls.clear()
    _failed_cost.clear()
