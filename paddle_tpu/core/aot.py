"""AOT compilation plane — kill cold-start with warmed executables.

A fresh process compiles every program before its first token: fatal
for elastic serving (a preempted replica re-compiles the world first)
and for the guardian rollback path.  The fix has three parts,
mirroring what the alpa/levanter-style JAX stacks do:

1. **AOT compile without real buffers** — ``CountedJit.aot_compile``
   (analysis/audit.py) drives ``jit(fn).lower(*ShapeDtypeStruct)
   .compile()`` and installs the resulting executable in a per-program
   table keyed by the abstract call signature; a dispatch whose
   signature hits the table runs the executable directly, so a warmed
   program NEVER re-traces.
2. **A persistent compile cache** — :class:`CompileCache` serializes
   executables (``jax.experimental.serialize_executable``) under a
   manifest keyed like the autotune cache keys tiles: (program,
   abstract shapes/dtypes, backend, device kind, jax/jaxlib version).
   A second process deserializes instead of compiling — zero traces,
   seconds instead of minutes.  Corrupt or version-skewed entries are
   dropped and recompiled, never a crash.
3. **A formal shape-bucket ladder** — :class:`BucketLadder` (powers of
   two by default) makes the runtime shape set finite: chunked prefill
   decomposes a prompt into descending ladder rungs, the past's page
   ids pad to a bucketed page count (padded columns masked by
   ``past_len``, so numerics are exact), and the decode-family batch
   sizes enumerate ``1..max_seqs``.  ``PagedExecutor.aot_warmup``
   pre-compiles every (program x rung) pair at engine build, and
   ``CheckpointManager`` restore invokes the same warmup so rollback
   resumes in seconds.

Gating: ``PT_AOT={off,warm,strict}``.  ``off`` (default) is bit-exact
r17 — no ladder, no table, no signature hashing on the dispatch path.
``warm`` pre-compiles and falls back to normal jit tracing on a miss.
``strict`` seals every program after warmup: a post-warmup miss raises
:class:`AotMissError` — the serving-fleet contract (a replica that
would silently compile mid-traffic must fail loudly instead).

Cache layout: ``PT_CACHE_DIR`` is the shared cache root (the autotune
cache lives beside it); unset, it is ``paddle_tpu/`` inside jax's own
compilation-cache directory (``JAX_COMPILATION_CACHE_DIR``, else the
fixed ``<checkout>/.jax_cache`` — ``utils.jax_cache_dir``), so what
decides which kernel ``auto`` picks travels with the placed cache and
never comes from a home directory.  ``PT_COMPILE_CACHE`` (default
``<root>/compile``) holds ``manifest.json`` + one pickled serialized
executable per entry.  Building a :class:`CompileCache` never touches
the process-global ``jax_compilation_cache_dir``.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import time

MODES = ("off", "warm", "strict")

#: manifest/entry schema version — bump on any layout change so stale
#: caches are dropped (never mis-deserialized).  v2: entries record the
#: device assignment they were compiled for.
CACHE_VERSION = 2


class AotMissError(RuntimeError):
    """A sealed (PT_AOT=strict) program was dispatched at a shape the
    warmup never compiled — the post-warmup-miss contract violation."""


def mode() -> str:
    m = os.environ.get("PT_AOT", "off").strip().lower()
    if m not in MODES:
        raise ValueError(f"PT_AOT must be one of {MODES}, got {m!r}")
    return m


def cache_root() -> str:
    """Shared on-disk cache root (``PT_CACHE_DIR``): the compile cache
    and the autotune cache both live under it.  Default: ``paddle_tpu/``
    inside jax's compilation-cache directory."""
    root = os.environ.get("PT_CACHE_DIR")
    if root:
        return root
    from ..utils import jax_cache_dir

    return os.path.join(jax_cache_dir(), "paddle_tpu")


def compile_cache_dir() -> str:
    return os.environ.get("PT_COMPILE_CACHE",
                          os.path.join(cache_root(), "compile"))


# -- abstract call signature --------------------------------------------------

def signature(args, kwargs=None) -> str:
    """Deterministic string for one call's abstract signature: the
    pytree structure plus every leaf's (shape, dtype) — or ``repr`` for
    static python leaves.  Concrete arrays and the ShapeDtypeStructs
    the warmup lowers with produce the SAME string, which is what lets
    a warmed executable claim the real dispatch."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(
        (tuple(args), dict(kwargs or {})))
    parts = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{tuple(shape)}/{dtype}")
        else:
            parts.append(f"py:{leaf!r}")
    return f"{treedef}|{';'.join(parts)}"


# -- the shape-bucket ladder --------------------------------------------------

class BucketLadder:
    """Sorted positive rungs a runtime quantity is quantized onto.

    ``floor(n)`` (largest rung <= n) drives chunked prefill: taking the
    floor rung of the remaining prompt each step decomposes any length
    into descending rungs (for powers of two, its binary expansion), so
    every chunk the executor ever sees is a rung.  ``ceil(n)`` (smallest
    rung >= n) drives padding-style bucketing (the past-KV page cover).
    """

    def __init__(self, rungs):
        rungs = sorted({int(r) for r in rungs})
        if not rungs or rungs[0] < 1:
            raise ValueError(f"BucketLadder needs positive rungs, "
                             f"got {rungs}")
        self.rungs = tuple(rungs)

    @classmethod
    def pow2(cls, cap, lo=1) -> "BucketLadder":
        """Powers of two from ``lo`` up to (at most) ``cap``."""
        cap, r = int(cap), int(lo)
        if cap < r:
            raise ValueError(f"pow2 ladder cap {cap} < lo {lo}")
        rungs = []
        while r <= cap:
            rungs.append(r)
            r *= 2
        return cls(rungs)

    def floor(self, n):
        """Largest rung <= n, or None when n is below the ladder."""
        n = int(n)
        best = None
        for r in self.rungs:
            if r > n:
                break
            best = r
        return best

    def ceil(self, n):
        """Smallest rung >= n, or None when n is above the ladder."""
        n = int(n)
        for r in self.rungs:
            if r >= n:
                return r
        return None

    def chunks(self, total):
        """Descending rung decomposition of ``total`` — exactly the
        chunk sequence the scheduler produces for a prompt."""
        out, left = [], int(total)
        while left > 0:
            r = self.floor(left)
            if r is None:
                raise ValueError(
                    f"{left} is below the smallest rung "
                    f"{self.rungs[0]}")
            out.append(r)
            left -= r
        return out

    def __contains__(self, n):
        return int(n) in self.rungs

    def __repr__(self):
        return f"BucketLadder{self.rungs}"


def page_buckets(max_pages) -> tuple:
    """Past-KV page-cover buckets: 0 (no past), powers of two, and the
    per-seq page budget itself as the cap."""
    out, r = [0], 1
    while r < int(max_pages):
        out.append(r)
        r *= 2
    out.append(int(max_pages))
    return tuple(sorted(set(out)))


def bucket_pages(n, buckets):
    """Smallest bucket >= n (capped at the top bucket)."""
    n = int(n)
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


# -- the persistent executable cache -----------------------------------------

class CompileCache:
    """On-disk cache of serialized AOT executables + manifest.

    Layout: ``<dir>/manifest.json`` mapping key -> {program, file,
    bytes, version}; one ``aot-<key>.pkl`` per entry holding the
    serialized executable payload and its in/out pytree defs.  Keys
    hash (program name, abstract signature, backend, device kind,
    jax/jaxlib versions, CACHE_VERSION) — the autotune-cache discipline
    applied to executables.

    Every read path is crash-proof: an unreadable manifest, a missing
    or truncated entry file, a bit-flipped pickle, or a version-skewed
    entry is dropped (``errors`` bumped) and the caller recompiles.
    The ``aot.cache`` fault point brackets one entry load so the
    serviceability tests can inject exactly those failures.
    """

    def __init__(self, path=None):
        self.path = str(path) if path is not None else compile_cache_dir()
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.errors = 0
        self.bytes_written = 0

    # -- keys ---------------------------------------------------------------

    @staticmethod
    def _versions():
        import jax
        import jaxlib

        try:
            backend = jax.default_backend()
            kind = jax.devices()[0].device_kind
        except Exception:  # pragma: no cover - no backend at all
            backend, kind = "none", "unknown"
        return (backend, kind, jax.__version__, jaxlib.__version__)

    def key(self, program: str, sig: str) -> str:
        raw = "|".join((program, sig) + self._versions()
                       + (f"v{CACHE_VERSION}",))
        return hashlib.sha256(raw.encode()).hexdigest()[:32]

    # -- manifest -----------------------------------------------------------

    def _manifest_path(self) -> str:
        return os.path.join(self.path, "manifest.json")

    def manifest(self) -> dict:
        """Parsed manifest ({} on any read problem); a version-skewed
        manifest is dropped wholesale — its entry files are unreadable
        by THIS build anyway."""
        try:
            with open(self._manifest_path()) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return {"version": CACHE_VERSION, "entries": {}}
        if (not isinstance(doc, dict)
                or doc.get("version") != CACHE_VERSION
                or not isinstance(doc.get("entries"), dict)):
            self.errors += 1
            return {"version": CACHE_VERSION, "entries": {}}
        return doc

    def _write_manifest(self, mutate) -> None:
        """Read-merge-write under atomic rename (the autotune-cache
        discipline); losing a race costs one recompile somewhere."""
        try:
            os.makedirs(self.path, exist_ok=True)
            doc = self.manifest()
            mutate(doc["entries"])
            tmp = f"{self._manifest_path()}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, self._manifest_path())
        except OSError:  # pragma: no cover - read-only FS etc.
            pass

    def drop(self, key: str) -> None:
        """Remove one (corrupt/stale) entry: manifest row + file."""
        entry = self.manifest()["entries"].get(key)
        self._write_manifest(lambda e: e.pop(key, None))
        if entry and isinstance(entry, dict) and entry.get("file"):
            try:
                os.unlink(os.path.join(self.path, entry["file"]))
            except OSError:
                pass

    # -- load / store -------------------------------------------------------

    def load(self, key: str, program: str = "?"):
        """Deserialize-and-load the cached executable for ``key``, or
        None on a miss.  EVERY failure mode — injected fault, torn
        file, bit rot, version skew — degrades to a miss (entry
        dropped) so the caller compiles fresh."""
        from ..testing import faults

        entry = self.manifest()["entries"].get(key)
        fpath = (os.path.join(self.path, entry["file"])
                 if isinstance(entry, dict) and entry.get("file")
                 else None)
        try:
            faults.fire("aot.cache", "before", path=fpath)
            if fpath is None or not os.path.isfile(fpath):
                raise FileNotFoundError(key)
            with open(fpath, "rb") as f:
                blob = pickle.load(f)
            if (not isinstance(blob, dict)
                    or blob.get("versions") != list(self._versions())
                    or blob.get("cache_version") != CACHE_VERSION):
                raise ValueError("compile-cache entry version skew")
            import jax
            from jax.experimental.serialize_executable import (
                deserialize_and_load)

            # load onto the device assignment the entry was compiled
            # for — the default is EVERY local device, which a
            # single-device program then refuses at dispatch
            by_id = {d.id: d for d in jax.devices()}
            exe = deserialize_and_load(
                blob["payload"], blob["in_tree"], blob["out_tree"],
                execution_devices=[by_id[i] for i in blob["device_ids"]])
            faults.fire("aot.cache", "after", path=fpath)
        except FileNotFoundError:
            self._count(program, hit=False)
            return None
        except Exception:
            # corrupt / truncated / injected: drop and recompile —
            # never a crash
            self.errors += 1
            if entry is not None:
                self.drop(key)
            self._count(program, hit=False)
            return None
        self._count(program, hit=True)
        return exe

    def store(self, key: str, exe, program: str = "?",
              sig: str = "") -> bool:
        """Serialize ``exe`` under ``key``; best-effort (False on any
        failure — persistence is an optimization, never a requirement).
        """
        try:
            from jax.experimental.serialize_executable import serialize

            payload, in_tree, out_tree = serialize(exe)
            # the ordered device assignment the program was compiled
            # for (one process: every device is local)
            devices = exe.runtime_executable().local_devices()
            blob = {"cache_version": CACHE_VERSION,
                    "versions": list(self._versions()),
                    "program": program,
                    "device_ids": [d.id for d in devices],
                    "payload": payload,
                    "in_tree": in_tree, "out_tree": out_tree}
            os.makedirs(self.path, exist_ok=True)
            fname = f"aot-{key}.pkl"
            tmp = os.path.join(self.path, f"{fname}.{os.getpid()}.tmp")
            with open(tmp, "wb") as f:
                pickle.dump(blob, f)
            nbytes = os.path.getsize(tmp)
            os.replace(tmp, os.path.join(self.path, fname))
            self._write_manifest(lambda e: e.__setitem__(key, {
                "program": program, "file": fname, "bytes": nbytes,
                "sig": sig[:200], "version": CACHE_VERSION}))
            self.stores += 1
            self.bytes_written += nbytes
            return True
        except Exception:
            self.errors += 1
            return False

    # -- accounting ---------------------------------------------------------

    def _count(self, program, hit: bool) -> None:
        if hit:
            self.hits += 1
        else:
            self.misses += 1
        from .. import obs

        h = obs.handle()
        if h is not None:
            name = ("aot_cache_hits_total" if hit
                    else "aot_cache_misses_total")
            h.registry.counter(
                name, "Persistent compile-cache "
                + ("hits" if hit else "misses") + " per program",
                labels=("program",)).labels(program=program).inc()
            ents = self.manifest()["entries"]
            h.registry.gauge(
                "aot_cache_entries",
                "Entries in the persistent compile cache").set(len(ents))
            h.registry.gauge(
                "aot_cache_bytes",
                "Total bytes of serialized executables on disk").set(
                sum(int(e.get("bytes", 0)) for e in ents.values()
                    if isinstance(e, dict)))

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def statusz(self) -> dict:
        """/statusz compile-cache provider payload."""
        ents = self.manifest()["entries"]
        by_prog: dict = {}
        for e in ents.values():
            if isinstance(e, dict):
                p = e.get("program", "?")
                by_prog[p] = by_prog.get(p, 0) + 1
        return {
            "dir": self.path,
            "entries": len(ents),
            "bytes": sum(int(e.get("bytes", 0)) for e in ents.values()
                         if isinstance(e, dict)),
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": round(self.hit_rate, 4),
            "stores": self.stores,
            "errors": self.errors,
            "programs": by_prog,
        }
