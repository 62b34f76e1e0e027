"""Analytical cost, HBM watermarks and step phases for the obs plane.

The cost model (``analysis.cost``) prices every registered
:class:`ProgramContract` once; :func:`program_cost` hands that count
to readers (``profiler.summary(with_flops=True)``).  What this module
publishes through the obs plane is what the process can truthfully
know about itself:

* gauges ``hbm_peak_bytes`` / ``hbm_bytes_in_use`` / ``hbm_bytes_limit``
  from ``device.memory`` (``memory_stats()`` where the backend has it),
  and ``step_phase_seconds{program,phase}`` from :class:`StepTimer`;
* Perfetto counter tracks (``perf.hbm_bytes``, ``perf.step_phases``)
  in the Chrome-trace export via ``Tracer.counter``.

No rate, utilization or roofline share is computed here: a dispatch's
host wall time is not the program's time on the device.  Those come
from ``chipbench/run.py`` on the chip (``mfu.train`` / ``mfu.serve``
and the tracer's spans on the profiler's clock).

Everything here is behind the same ``PT_OBS`` gate as the rest of the
plane: with obs off every entry point is one ``None`` check.  The cost
trace is cached on the contract, and a failed one is remembered so a
broken program never re-prices per step.
"""
from __future__ import annotations

#: :func:`sample_hbm` publishes on every N-th call per program: the
#: live-array fallback on statless backends is O(arrays).
HBM_SAMPLE_EVERY = 16

_hbm_calls = {}          # program -> sample_hbm call count
_failed_cost = set()     # programs whose cost trace raised: don't retry


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


def sample_hbm(program, h=None):
    """Publish the HBM watermark gauges on every
    :data:`HBM_SAMPLE_EVERY`-th call for ``program`` (the first
    included); returns the watermarks when it sampled, else None."""
    from paddle_tpu import obs

    h = h if h is not None else obs.handle()
    if h is None:
        return None
    n = _hbm_calls.get(program, 0)
    _hbm_calls[program] = n + 1
    if n % HBM_SAMPLE_EVERY:
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


class StepTimer:
    """Per-step phase breakdown (data-wait / compute / checkpoint /
    obs) for the train loop.

    Null-safe: with obs off every method is one attribute check.  Use::

        timer = StepTimer("train.step")
        with timer.phase("data_wait"):
            batch = next(loader)
        with timer.phase("compute"):
            loss = step(batch)
        timer.end_step()   # publishes the phase gauges

    ``end_step`` publishes ``step_phase_seconds{program,phase}`` per
    phase (host wall time: compute is the dispatch, data-wait /
    checkpoint / obs are host overhead) and, for a step that computed,
    takes the throttled HBM watermark sample."""

    PHASES = ("data_wait", "compute", "checkpoint", "obs")

    def __init__(self, program="train.step"):
        self.program = program
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
        if out.get("compute"):
            sample_hbm(self.program, h)
        return out


def reset():
    """Clear module-level perf state (failed-cost memo, HBM sampling
    counters); tests call this alongside ``obs.reset``."""
    _hbm_calls.clear()
    _failed_cost.clear()
