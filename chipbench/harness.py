"""What every cell's driver needs: the log, the compile clock, the
collector's clock, the device's description and memory, the profiler
window, percentiles and the judgement of the numbers compared."""
import contextlib
import gc
import glob
import os
import shutil
import sys
import time

T0 = time.perf_counter()        # as near to process start as an import is


def log(msg):
    print(f"[chipbench +{time.perf_counter() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


class CompileClock:
    """Sums jax's own backend-compile events: programs compiled or
    loaded from the persistent cache (a cache hit still fires the
    event; it then times the load that stood in for the compile)."""

    def __init__(self, jax):
        self.compile_s, self.compiles = 0.0, 0
        self.cache_hits = self.cache_misses = 0
        mon = jax.monitoring
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


class GcClock:
    """``with GcClock() as c:`` sums the time Python's collector takes
    inside the block (``c.seconds``, ``c.collections``), so that a
    stalled step can be laid at its door or not."""

    def __enter__(self):
        self.seconds, self.collections, self._t = 0.0, 0, None
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)
        return False

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.seconds += time.perf_counter() - self._t
            self.collections += 1
            self._t = None


def device_info(jax):
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(jax):
    """The peak on the fullest chip, as the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def bytes_in_use(jax):
    return (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between
    order statistics; ``values`` must not be empty."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class TraceWindow:
    """The profiler around one window.  ``with tw:`` traces; afterwards
    ``tw.path`` is the .xplane.pb written under ``<out_dir>`` (inside the
    checkout, listed in .gitignore) and ``tw.cleanup()`` removes it."""

    def __init__(self, jax, out_dir, on):
        self.jax, self.dir, self.on, self.path = jax, out_dir, on, None

    def __enter__(self):
        if self.on:
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            self.jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        if self.on:
            self.jax.profiler.stop_trace()
            found = sorted(glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb")))
            self.path = found[-1] if found else None
        return False

    def span(self, name):
        """A host span on the profiler's clock: what the harness was in."""
        if not self.on:
            return contextlib.nullcontext()
        return self.jax.profiler.TraceAnnotation(name)

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def judge(checks):
    """``checks`` is a list of (name, value, limit): correct when every
    value is a number at or under its limit.  Returns (correct, rows)
    where rows is what is printed beside the result."""
    rows, ok = {}, True
    for name, value, limit in checks:
        good = value is not None and value == value and value <= limit
        ok = ok and good
        rows[name] = {"value": value, "limit": limit}
    return ok, rows
