"""Per-op microbenchmark: eager dispatch vs jitted execution.

The reference gates per-op perf regressions in CI
(``tools/ci_op_benchmark.sh`` + ``check_op_benchmark_result.py``); this
is the TPU-native analog, and it also answers SURVEY §7 hard-part #1
("eager-mode performance: dispatch -> compile cache") with numbers: for
each hot op it reports

- ``eager_us``: wall time of one eager ``registry.apply`` call (Tensor
  in/out — includes dispatch, the executable-cache hit, autograd-meta
  bookkeeping);
- ``jit_us``:  the same computation inside one pre-compiled jax.jit;
- ``overhead_x = eager/jit``: the eager tax.

Run: ``python bench_ops.py [--ops matmul,add] [--repeat 200]``.
Prints one JSON line per op and a trailing summary line.  The committed
snapshot (``benchmarks/ops_snapshot.json``) is a non-gating report for
spotting dispatch-path regressions across rounds; regenerate with
``python bench_ops.py --snapshot`` (CPU numbers are machine-dependent —
compare ratios, not absolutes).

Timing note: every timed loop ends with ``block_until_ready`` — jax
returns before the device finishes, so an unfenced loop times the
enqueue.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def _build_cases():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import nn  # noqa: F401

    rng = np.random.RandomState(0)
    f32 = np.float32

    a512 = paddle.to_tensor(rng.randn(512, 512).astype(f32))
    b512 = paddle.to_tensor(rng.randn(512, 512).astype(f32))
    v = paddle.to_tensor(rng.randn(64, 1024).astype(f32))
    w_emb = paddle.to_tensor(rng.randn(1000, 256).astype(f32))
    ids = paddle.to_tensor(rng.randint(0, 1000, (64, 128)))
    g = paddle.to_tensor(rng.randn(1024,).astype(f32))
    qkv = paddle.to_tensor(rng.randn(4, 128, 8, 64).astype(f32))

    cases = {
        "matmul": (lambda: paddle.matmul(a512, b512),
                   lambda: a512._data @ b512._data),
        "add": (lambda: paddle.add(v, v),
                lambda: v._data + v._data),
        "multiply": (lambda: paddle.multiply(v, v),
                     lambda: v._data * v._data),
        "softmax": (lambda: paddle.nn.functional.softmax(v, axis=-1),
                    lambda: jax.nn.softmax(v._data, axis=-1)),
        "layer_norm": (
            lambda: paddle.nn.functional.layer_norm(v, [1024], g, g),
            lambda: _jax_layer_norm(v._data, g._data)),
        "reduce_sum": (lambda: paddle.sum(v),
                       lambda: jnp.sum(v._data)),
        "transpose": (lambda: paddle.transpose(a512, [1, 0]),
                      lambda: jnp.transpose(a512._data)),
        "embedding": (
            lambda: paddle.nn.functional.embedding(ids, w_emb),
            lambda: jnp.take(w_emb._data, ids._data, axis=0)),
        "sdpa": (
            lambda: paddle.nn.functional.scaled_dot_product_attention(
                qkv, qkv, qkv, is_causal=True),
            lambda: _jax_sdpa(qkv._data)),
    }
    return cases


def _jax_layer_norm(x, g):
    import jax
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + g


def _jax_sdpa(q):
    import jax.numpy as jnp

    from paddle_tpu.ops.nn_ops import _sdpa_plain

    return _sdpa_plain(q, q, q, causal=True, impl="einsum")


def _force(x):
    """Fence: wait until the device has produced ``x``."""
    import jax

    from paddle_tpu.core.tensor import Tensor

    return jax.block_until_ready(x._data if isinstance(x, Tensor) else x)


def _time(fn, repeat):
    fn()  # compile / cache warmup
    _force(fn())
    t0 = time.perf_counter()
    out = None
    for _ in range(repeat):
        out = fn()
    _force(out)
    return (time.perf_counter() - t0) / repeat * 1e6  # us


def run(ops=None, repeat=200):
    import jax

    import paddle_tpu

    cases = _build_cases()
    if ops:
        unknown = sorted(set(ops) - set(cases))
        if unknown:
            raise SystemExit(
                f"unknown op(s) {unknown}; available: {sorted(cases)}")
        cases = {k: v for k, v in cases.items() if k in ops}
    results = []
    with paddle_tpu.no_grad():
        for name, (eager_fn, plain_fn) in cases.items():
            jitted = jax.jit(plain_fn)
            eager_us = _time(eager_fn, repeat)
            jit_us = _time(jitted, repeat)
            row = {"op": name, "eager_us": round(eager_us, 2),
                   "jit_us": round(jit_us, 2),
                   "overhead_x": round(eager_us / max(jit_us, 1e-9), 2)}
            results.append(row)
            print(json.dumps(row))
    med = sorted(r["overhead_x"] for r in results)[len(results) // 2]
    summary = {"summary": "eager_dispatch_overhead",
               "platform": jax.devices()[0].platform,
               "median_overhead_x": med, "n_ops": len(results)}
    print(json.dumps(summary))
    return results, summary


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--ops", default=None,
                   help="comma-separated subset of op names")
    p.add_argument("--repeat", type=int, default=200)
    p.add_argument("--snapshot", action="store_true",
                   help="write benchmarks/ops_snapshot.json")
    args = p.parse_args()
    ops = args.ops.split(",") if args.ops else None
    results, summary = run(ops, args.repeat)
    if args.snapshot:
        import os

        root = os.path.dirname(os.path.abspath(__file__))
        os.makedirs(os.path.join(root, "benchmarks"), exist_ok=True)
        with open(os.path.join(root, "benchmarks",
                               "ops_snapshot.json"), "w") as f:
            json.dump({"results": results, "summary": summary}, f,
                      indent=1)
        print("wrote benchmarks/ops_snapshot.json", file=sys.stderr)


if __name__ == "__main__":
    main()
