"""Misc utilities (reference: python/paddle/utils/)."""
from __future__ import annotations


def try_import(name):
    import importlib

    try:
        return importlib.import_module(name)
    except ImportError:
        return None


def flatten(nested):
    """Flatten nested lists/tuples/dicts to a leaf list (paddle.utils.flatten)."""
    out = []

    def rec(x):
        if isinstance(x, dict):
            for k in sorted(x):
                rec(x[k])
        elif isinstance(x, (list, tuple)):
            for v in x:
                rec(v)
        else:
            out.append(x)

    rec(nested)
    return out


def map_structure(fn, structure):
    if isinstance(structure, dict):
        return {k: map_structure(fn, v) for k, v in structure.items()}
    if isinstance(structure, (list, tuple)):
        return type(structure)(map_structure(fn, v) for v in structure)
    return fn(structure)


def unique_name(prefix="tmp"):
    global _name_counter
    _name_counter += 1
    return f"{prefix}_{_name_counter}"


_name_counter = 0


def run_check():
    """paddle.utils.run_check analog: verify the device works."""
    import jax

    from .. import ops

    x = ops.ones([2, 2])
    y = ops.matmul(x, x)
    assert float(y.numpy()[0, 0]) == 2.0
    dev = jax.devices()[0]
    print(f"paddle_tpu is installed and working on {dev.device_kind} "
          f"({jax.device_count()} device(s)).")
    return True


class unique_name:  # noqa: N801 — namespace (reference utils/unique_name.py)
    """Name generator: unique_name.generate('fc') -> 'fc_0', 'fc_1', ..."""

    _counters: dict = {}

    @classmethod
    def generate(cls, key):
        n = cls._counters.get(key, 0)
        cls._counters[key] = n + 1
        return f"{key}_{n}"

    @classmethod
    def guard(cls, new_generator=None):
        import contextlib

        @contextlib.contextmanager
        def _guard():
            saved = dict(cls._counters)
            cls._counters.clear()
            try:
                yield
            finally:
                cls._counters.clear()
                cls._counters.update(saved)

        return _guard()


def jax_cache_dir():
    """Where jax's persistent compilation cache lives for this program:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment places it, else
    the fixed ``<checkout>/.jax_cache``.  The path is part of nothing's
    key but a directory that moves never hits, so it is never built
    from a temp name, pid or timestamp."""
    import os

    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(cache_dir=None, min_compile_secs=0):
    """Turn on jax's persistent XLA compilation cache and return the
    directory in use.  Where ``JAX_COMPILATION_CACHE_DIR`` is set jax
    has already read it: the cache stays there and ``cache_dir`` is
    ignored.  Otherwise the cache goes to ``cache_dir`` (default
    :func:`jax_cache_dir`).  Errors propagate — a cache that cannot be
    enabled is reported, not swallowed.

    min_compile_secs defaults to 0 because the chip machine is sealed
    and thrown away after each run: nothing survives but the cache, so
    every program it does not hold is compiled again, however small."""
    import os

    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache_dir = jax_cache_dir()
    else:
        cache_dir = str(cache_dir) if cache_dir else jax_cache_dir()
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def compile_cache_entries(cache_dir):
    """Programs in a jax compilation-cache directory (its ``*-cache``
    files; 0 for a directory that is not there)."""
    import os

    try:
        return sum(f.endswith("-cache") for f in os.listdir(cache_dir))
    except OSError:
        return 0


from . import cpp_extension  # noqa: E402,F401
from .cpp_extension import register_custom_op  # noqa: E402,F401
