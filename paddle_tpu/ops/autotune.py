"""Shape-keyed kernel autotune cache.

Reference analog: the exhaustive-search cudnn workspace the reference
wraps around conv (``paddle/phi/kernels/gpudnn/conv_kernel.cu``'s
``FLAGS_cudnn_exhaustive_search`` + cached AlgorithmsCache) — pick a
kernel configuration by measuring once per shape, then replay the
winner forever.

TPU-native: the tunables are Pallas tile/config choices (flash-attention
block sizes, long_attention block_q, rms_norm row-block, paged-decode
impl choice), the key is (device_kind, kernel, shape-key), and the cache
has three layers:

  1. process memory (dict — the hot path is one dict hit),
  2. a JSON file shared across processes (``PT_AUTOTUNE_CACHE``, default
     ``autotune.json`` under ``core.aot.cache_root()`` — inside jax's
     placed compilation-cache directory, never a home directory) so
     one measured run seeds every later run that sees that cache,
  3. a built-in seed table of winners proven in PERF.md (e.g. the
     512/1024 flash-attention tiles on v5e) so a fresh install starts
     from measured-good, not library defaults.

``lookup`` never measures (safe at trace time — it is pure host work);
``tune`` measures candidates via a caller-supplied thunk on a miss and
records the winner.  ``PT_AUTOTUNE=0`` disables both layers 2 and 3 and
makes ``lookup`` return its default (the escape hatch when a stale
cache entry is suspected).
"""
from __future__ import annotations

import json
import os
import time

import jax

# -- key / storage ------------------------------------------------------

_MEM: dict = {}

#: winners proven by measurement in PERF.md, keyed (device substring,
#: kernel).  Applies to every shape of that kernel on that device —
#: shape-specific measurements (layers 1/2) override.
_SEED = {
    # PERF.md r4: flash tiles 512/1024 beat the library's 128 default
    # on v5e at the llama/bert shapes (MXU stays busier per grid step).
    ("v5 lite", "fa_blocks"): (512, 1024),
    # PERF.md r4: long_attention fwd block_q=256 (bwd VMEM cap).
    ("v5 lite", "long_attention_block_q"): 256,
}


def enabled() -> bool:
    return os.environ.get("PT_AUTOTUNE", "1") != "0"


def device_kind() -> str:
    try:
        return jax.devices()[0].device_kind
    except Exception:  # pragma: no cover - no backend at all
        return "unknown"


def cache_path() -> str:
    # shares the PT_CACHE_DIR root with the AOT compile cache — one
    # directory to ship/mount to pre-warm a fresh replica
    from ..core.aot import cache_root

    return os.environ.get(
        "PT_AUTOTUNE_CACHE",
        os.path.join(cache_root(), "autotune.json"))


def _key(kernel, shape_key) -> str:
    flat = "x".join(str(s) for s in tuple(shape_key)) or "-"
    return f"{device_kind()}|{kernel}|{flat}"


def _freeze(v):
    """JSON round-trips tuples as lists; winners are compared/unpacked
    as tuples."""
    return tuple(v) if isinstance(v, list) else v


def _load_disk() -> dict:
    try:
        with open(cache_path()) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _store_disk(key: str, value) -> None:
    """Best-effort read-merge-write (atomic rename); losing a race just
    costs a re-measurement in some later process."""
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        disk = _load_disk()
        disk[key] = value
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(disk, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:  # pragma: no cover - read-only FS etc.
        pass


def clear_memory_cache() -> None:
    """Test hook: drop layer 1 so disk/seed layers are exercised."""
    _MEM.clear()


# -- query / record -----------------------------------------------------

def lookup(kernel, shape_key, default):
    """Cached winner for (device, kernel, shape) or ``default``.  Never
    measures — safe anywhere, including inside a trace."""
    key = _key(kernel, shape_key)
    if key in _MEM:
        return _MEM[key]
    if not enabled():
        return default
    disk = _load_disk()
    if key in disk:
        _MEM[key] = _freeze(disk[key])
        return _MEM[key]
    kind = device_kind().lower()
    for (dev_sub, kern), win in _SEED.items():
        if kern == kernel and dev_sub in kind:
            _MEM[key] = win
            return win
    return default


def record(kernel, shape_key, value) -> None:
    """Store a winner in memory (+ disk when enabled)."""
    key = _key(kernel, shape_key)
    _MEM[key] = _freeze(value)
    if enabled():
        _store_disk(key, list(value) if isinstance(value, tuple)
                    else value)


def tune(kernel, shape_key, candidates, measure, default=None):
    """Winner for (device, kernel, shape): cached if known, else each
    candidate is timed with ``measure(candidate) -> seconds`` and the
    fastest is recorded.  A candidate whose measurement raises is
    skipped (e.g. a tile the shape can't take); if every candidate
    fails, ``default`` is returned uncached.
    """
    hit = lookup(kernel, shape_key, None)
    if hit is not None:
        return hit
    best, best_t = None, float("inf")
    for cand in candidates:
        try:
            t = measure(cand)
        except Exception:
            continue
        if t < best_t:
            best, best_t = cand, t
    if best is None:
        return default
    record(kernel, shape_key, best)
    return best


# -- measurement helper -------------------------------------------------

def measure_thunk(fn, iters=8):
    """Per-iteration seconds for ``fn``: compile + warm once, then time
    ``iters`` back-to-back calls ending in ``block_until_ready`` (jax
    returns before the device finishes; the fence makes the host clock
    cover the device work)."""
    jax.block_until_ready(fn())  # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters
