"""Microbench: einsum vs flash attention at the bench shape (fwd+bwd).

Timing protocol: chain iterations through a data dependency and end in
a host transfer of the result, which cannot complete before the device
has finished.
"""
import time
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")
from paddle_tpu.ops.nn_ops import _sdpa_plain


def bench(fn, args, iters=30):
    out = fn(*args)
    _ = np.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[0]
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _ = np.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[0]
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    B, H, S, D = 8, 16, 2048, 128
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.bfloat16)

    def mk(impl, blocks=None):
        def loss(q, k, v):
            out = _sdpa_plain(q, k, v, causal=True, impl=impl,
                              flash_blocks=blocks)
            return jnp.sum(out.astype(jnp.float32))
        return jax.jit(loss), jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    print("shape B%d H%d S%d D%d bf16 causal" % (B, H, S, D))
    # useful flops (causal): fwd = 2 mms * 2*B*H*S*S*D / 2
    fwd_fl = 2 * 2 * B * H * S * S * D / 2
    configs = [("einsum", None)]
    for bq, bk in [(128, 128), (256, 512), (512, 512), (512, 1024),
                   (1024, 1024), (512, 2048), (2048, 2048)]:
        configs.append(("flash", (bq, bk)))
    for impl, blocks in configs:
        tag = impl if blocks is None else "flash %4d/%4d" % blocks
        try:
            f, g = mk(impl, blocks)
            tf = bench(f, (q, k, v))
            tg = bench(g, (q, k, v))
            print("%-16s fwd %7.2f ms (%5.1f TF/s)  fwd+bwd %7.2f ms"
                  % (tag, tf, fwd_fl / tf / 1e9, tg))
        except Exception as e:
            print("%-16s FAILED: %s" % (tag, str(e)[:120]))


if __name__ == "__main__":
    main()
