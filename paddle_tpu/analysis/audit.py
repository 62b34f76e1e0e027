"""Runtime retrace/dispatch audit — the sixth check.

``CountedJit`` is a drop-in ``jax.jit`` replacement that counts how
many times the wrapped function was TRACED (re-traces mean shape churn)
and how many times it was DISPATCHED — replacing the hand-rolled
``verify_traces``/``verify_dispatches`` counters the serving executor
carried.  ``DispatchAuditor`` is the context manager that asserts the
counts over a block: an extra dispatch (a hidden host loop) or an extra
trace (a shape leak) raises :class:`GraphContractError`.

``CountedJit.aot_compile`` is the AOT plane's entry point: it compiles
the program at an abstract signature (``jax.ShapeDtypeStruct`` leaves —
no real buffers) via ``lower().compile()``, consults the persistent
:class:`~paddle_tpu.core.aot.CompileCache` first, and installs the
executable in a per-program table that ``__call__`` checks before
falling back to the normal jit path.  A table hit NEVER traces; a
``seal()``-ed program (PT_AOT=strict) raises
:class:`~paddle_tpu.core.aot.AotMissError` on a miss instead of
silently compiling mid-traffic.
"""
from __future__ import annotations

import functools
import time
import warnings

import jax

from .. import obs
from .contract import GraphContractError


class CountedJit:
    """jax.jit wrapper with trace/dispatch counters.

    The trace counter is bumped by a host-side effect INSIDE the traced
    body (it runs once per trace, never per dispatch — the same trick
    the executor's verify program used); the dispatch counter is bumped
    per call.  ``fn`` exposes the unjitted callable for ProgramContract
    registration, so the lint path and the execution path share one
    function object.
    """

    def __init__(self, fn, *, name=None, donate_argnums=(),
                 static_argnames=(), **jit_kwargs):
        self.name = name or getattr(fn, "__name__", "program")
        self.traces = 0
        self.dispatches = 0
        self._fn = fn
        self.donate_argnums = tuple(donate_argnums)
        self._obs = obs.handle()
        # AOT executable table: abstract signature -> jax.stages.Compiled
        self._exe = {}
        self._sealed = False
        self.aot_hits = 0
        self.aot_misses = 0

        @functools.wraps(fn)
        def counted(*args, **kwargs):
            self.traces += 1
            h = self._obs
            if h is not None:
                # a (re)trace is the compile event production debugging
                # cares about: journal it and count per program
                h.recorder.record("jit.trace", program=self.name,
                                  traces=self.traces)
                h.registry.counter(
                    "jit_traces_total",
                    "XLA traces (compiles/retraces) per program",
                    labels=("program",)).labels(program=self.name).inc()
            return fn(*args, **kwargs)

        self._jit = jax.jit(counted,
                            donate_argnums=self.donate_argnums,
                            static_argnames=tuple(static_argnames),
                            **jit_kwargs)

    @property
    def fn(self):
        return self._fn

    def __call__(self, *args, **kwargs):
        self.dispatches += 1
        h = self._obs
        if h is not None:
            h.registry.counter(
                "jit_dispatches_total",
                "Jitted program dispatches per program",
                labels=("program",)).labels(program=self.name).inc()
        # the one span every serving program's dispatch gets (only the
        # serving executor builds CountedJits); ``traced``: this
        # dispatch traced, and compiled or loaded, the program.  The
        # call is made from THIS frame: one more Python frame between
        # here and the jitted call made every trace a fifth slower on
        # the chip's host (PERF.md section 6, PR 26)
        traces = self.traces
        with obs.span("jit.dispatch", cat="serve", program=self.name,
                      traced=False) as sp:
            try:
                return (self._aot_executable(args, kwargs)
                        or self._jit)(*args, **kwargs)
            finally:
                if self.traces > traces:
                    sp.set(traced=True)

    def _aot_executable(self, args, kwargs):
        """The executable installed for this signature, or None (the jit
        path); a miss on a sealed program raises."""
        if not self._exe:
            return None
        from ..core import aot

        exe = self._exe.get(aot.signature(args, kwargs))
        if exe is not None:
            self.aot_hits += 1
            return exe
        self.aot_misses += 1
        if self._sealed:
            raise aot.AotMissError(
                f"[{self.name}] PT_AOT=strict: dispatch at an "
                f"un-warmed signature after seal() — the shape "
                f"ladder must cover every runtime shape "
                f"({aot.signature(args, kwargs)})")
        return None

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def aot_compile(self, args, kwargs=None, cache=None):
        """AOT-compile at an abstract signature and install the
        executable; returns how it was satisfied.

        ``args``/``kwargs`` follow the call convention of ``__call__``
        with arrays replaced by ``jax.ShapeDtypeStruct`` leaves (static
        kwargs stay concrete python values).  Resolution order:

        * ``'warm'``    — already in this process's table
        * ``'disk'``    — deserialized from the persistent ``cache``
          (zero traces: the compile happened in an earlier process)
        * ``'compile'`` — lowered and compiled now (this traces the
          body ONCE, bumping ``traces`` — warmup cost, paid off-path)
        """
        from ..core import aot
        from ..testing import faults

        kwargs = dict(kwargs or {})
        sig = aot.signature(args, kwargs)
        if sig in self._exe:
            return "warm"
        key = cache.key(self.name, sig) if cache is not None else None
        if cache is not None:
            exe = cache.load(key, program=self.name)
            if exe is not None:
                self._exe[sig] = exe
                return "disk"
        t0 = time.perf_counter()
        faults.fire("aot.lower", "before")
        with warnings.catch_warnings():
            # AOT lowering of a donating program at SDS avals warns
            # that donated buffers are unused — expected: there are no
            # real buffers to donate at lowering time
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            lowered = self._jit.lower(*args, **kwargs)
            faults.fire("aot.lower", "after")
            faults.fire("aot.compile", "before")
            exe = lowered.compile()
        faults.fire("aot.compile", "after")
        secs = time.perf_counter() - t0
        self._exe[sig] = exe
        h = self._obs
        if h is not None:
            h.registry.histogram(
                "aot_compile_seconds",
                "AOT lower+compile wall seconds per program",
                labels=("program",)).labels(program=self.name).observe(
                secs)
            h.recorder.record("aot.compile", program=self.name,
                              seconds=round(secs, 4))
        if cache is not None:
            cache.store(key, exe, program=self.name, sig=sig)
        return "compile"

    def seal(self):
        """Forbid post-warmup misses (PT_AOT=strict): once sealed, a
        dispatch whose signature is not in the table raises AotMissError
        instead of tracing."""
        if not self._exe:
            raise ValueError(
                f"[{self.name}] seal() before any aot_compile(): a "
                f"sealed empty table would reject every dispatch")
        self._sealed = True

    def __repr__(self):
        return (f"CountedJit({self.name}, traces={self.traces}, "
                f"dispatches={self.dispatches})")


class DispatchAuditor:
    """Assert trace/dispatch counts of CountedJit programs over a block.

    ::

        with DispatchAuditor(ex.programs["verify"],
                             max_traces=max_seqs) as aud:
            eng.run()
        assert aud.dispatches == eng.metrics.spec_steps

    Exact expectations (``dispatches=``, ``traces=``) and ceilings
    (``max_dispatches=``, ``max_traces=``) are checked at block exit;
    a mismatch raises GraphContractError naming the program set.  The
    live ``dispatches``/``traces`` properties report the block's deltas
    for assertions that need runtime quantities (e.g. scheduler-step
    counts only known after the run).
    """

    def __init__(self, *programs, dispatches=None, max_dispatches=None,
                 traces=None, max_traces=None):
        if not programs:
            raise ValueError("DispatchAuditor needs at least one "
                             "CountedJit program")
        self.programs = programs
        self._expect = dict(dispatches=dispatches,
                            max_dispatches=max_dispatches,
                            traces=traces, max_traces=max_traces)
        self._t0 = self._d0 = 0

    def _sums(self):
        return (sum(p.traces for p in self.programs),
                sum(p.dispatches for p in self.programs))

    @property
    def traces(self):
        return self._sums()[0] - self._t0

    @property
    def dispatches(self):
        return self._sums()[1] - self._d0

    def expect(self, **kwargs):
        """Set/override expectations mid-block, for quantities only
        known after the audited work ran (they are enforced at exit)."""
        for k, v in kwargs.items():
            if k not in self._expect:
                raise TypeError(f"unknown expectation {k!r}")
            self._expect[k] = v

    def __enter__(self):
        self._t0, self._d0 = self._sums()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        names = ", ".join(p.name for p in self.programs)
        t, d = self.traces, self.dispatches
        e = self._expect
        if e["dispatches"] is not None and d != e["dispatches"]:
            raise GraphContractError(
                f"[{names}] dispatch audit: {d} dispatches in block, "
                f"expected exactly {e['dispatches']}")
        if e["max_dispatches"] is not None and d > e["max_dispatches"]:
            raise GraphContractError(
                f"[{names}] dispatch audit: {d} dispatches in block "
                f"exceed the ceiling {e['max_dispatches']} — a hidden "
                f"host loop is dispatching per item")
        if e["traces"] is not None and t != e["traces"]:
            raise GraphContractError(
                f"[{names}] retrace audit: {t} traces in block, "
                f"expected exactly {e['traces']}")
        if e["max_traces"] is not None and t > e["max_traces"]:
            raise GraphContractError(
                f"[{names}] retrace audit: {t} traces in block exceed "
                f"the ceiling {e['max_traces']} — shapes are churning "
                f"and every change recompiles")
        return False
