"""Device (HBM) memory statistics.

Reference: ``paddle/phi/core/memory/stats.h`` (StatAllocator host/device
peak stats) surfaced as ``paddle.device.cuda.max_memory_allocated`` etc.
(``python/paddle/device/cuda/__init__.py``).

TPU-native: the allocator is PJRT's.  When the backend exposes
``jax.Device.memory_stats()`` (bytes_in_use / peak_bytes_in_use /
bytes_limit) those are authoritative; backends that don't (the CPU
client) fall back to client-side live-buffer accounting over
``jax.live_arrays()`` — the StatAllocator strategy, with the peak tracked
as the max observed at stat calls.  ``reset_max_memory_allocated``
establishes a session baseline in both regimes (PJRT cannot reset its
lifetime peak).
"""
from __future__ import annotations

import jax

_peak: dict = {}  # device-key -> running max of observed bytes_in_use
_baseline_active: set = set()  # devices where reset_... established a base


def _device(device=None):
    if device is None:
        return jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    if isinstance(device, str):
        parts = device.split(":")  # "tpu:0" / "gpu:1" / "cpu"
        idx = int(parts[1]) if len(parts) > 1 else 0
        if parts[0]:
            # Honor the platform prefix: on a mixed-backend process the
            # bare global index could resolve to a different platform
            # than requested (round-2 advisor finding).
            try:
                return jax.devices(parts[0])[idx]
            except RuntimeError:
                pass  # unknown platform → fall back to the global list
        return jax.devices()[idx]
    return device


def _live_bytes(dev):
    """Client-side accounting: addressable bytes of live arrays on dev."""
    total = 0
    for a in jax.live_arrays():
        try:
            devs = a.devices()
        except Exception:
            continue
        if dev in devs:
            total += a.nbytes // max(1, len(devs))
    return int(total)


def _bytes_in_use(dev):
    st = dev.memory_stats()
    if st:
        return int(st.get("bytes_in_use", 0)), st
    return _live_bytes(dev), None


def memory_allocated(device=None):
    """Bytes currently held by live buffers on the device."""
    dev = _device(device)
    cur, _ = _bytes_in_use(dev)
    key = repr(dev)
    _peak[key] = max(_peak.get(key, 0), cur)
    return cur


def max_memory_allocated(device=None):
    """Peak bytes in use — PJRT's lifetime peak when available (and no
    reset was requested), else the max observed at stat calls since the
    baseline."""
    dev = _device(device)
    cur, st = _bytes_in_use(dev)
    key = repr(dev)
    _peak[key] = max(_peak.get(key, 0), cur)
    if st and key not in _baseline_active:
        return int(st.get("peak_bytes_in_use", cur))
    return _peak[key]


def reset_max_memory_allocated(device=None):
    dev = _device(device)
    cur, _ = _bytes_in_use(dev)
    key = repr(dev)
    _peak[key] = cur
    _baseline_active.add(key)


def memory_reserved(device=None):
    """Bytes the allocator has from the system; PJRT pools the whole HBM,
    so this reports the usable limit (0 when the backend won't say)."""
    dev = _device(device)
    st = dev.memory_stats()
    if st:
        return int(st.get("bytes_reservable_limit",
                          st.get("bytes_limit", 0)))
    return 0


def max_memory_reserved(device=None):
    return memory_reserved(device)


def watermarks(device=None):
    """One-call HBM snapshot for the perf plane: current / peak /
    limit bytes.  Costs one ``memory_stats()`` on PJRT backends; on
    backends without stats it walks ``jax.live_arrays()`` — callers on
    hot paths must throttle (obs.perf samples every N steps)."""
    dev = _device(device)
    cur, st = _bytes_in_use(dev)
    key = repr(dev)
    _peak[key] = max(_peak.get(key, 0), cur)
    if st and key not in _baseline_active:
        peak = int(st.get("peak_bytes_in_use", cur))
    else:
        peak = _peak[key]
    limit = int(st.get("bytes_limit", 0)) if st else 0
    return {"bytes_in_use": int(cur), "peak_bytes_in_use": peak,
            "bytes_limit": limit}


def get_device_properties(device=None):
    dev = _device(device)
    st = dev.memory_stats() or {}
    return {
        "name": getattr(dev, "device_kind", str(dev)),
        "platform": dev.platform,
        "total_memory": int(st.get("bytes_limit", 0)),
    }


def empty_cache():
    """PJRT owns the pool; nothing to release (API-compat no-op)."""
