"""Self-authored fused paged-decode attention kernel vs the dense
oracle (reference block_multi_head_attention semantics).  Off-TPU the
kernel runs in Pallas interpreter mode — same kernel body, no tiling
constraints — so the fusion logic (DMA page gather, length masking,
GQA grouping, window-tail zeroing) is exercised everywhere.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas_kernels.paged_decode import (
    paged_decode, supported,
)


def _oracle(q, k_pages, v_pages, lens, table):
    """Independent numpy oracle over the gathered dense cache."""
    B, H, D = q.shape
    KV, _, ps, _ = k_pages.shape
    T = table.shape[1] * ps
    g = H // KV
    out = np.zeros((B, H, D), np.float32)
    for b in range(B):
        kc = k_pages[:, table[b]].reshape(KV, T, D).astype(np.float64)
        vc = v_pages[:, table[b]].reshape(KV, T, D).astype(np.float64)
        for h in range(H):
            kv = h // g
            lg = (q[b, h].astype(np.float64)
                  @ kc[kv, :lens[b]].T) / np.sqrt(D)
            p = np.exp(lg - lg.max())
            p /= p.sum()
            out[b, h] = p @ vc[kv, :lens[b]]
    return out


def _mk(rng, B, H, KV, D, P, ps, pps, dtype=np.float32):
    q = rng.randn(B, H, D).astype(dtype)
    kp = rng.randn(KV, P, ps, D).astype(dtype)
    vp = rng.randn(KV, P, ps, D).astype(dtype)
    table = rng.choice(P, size=(B, pps), replace=False).astype(np.int32)
    return q, kp, vp, table


def test_matches_oracle_full_lengths():
    rng = np.random.RandomState(0)
    q, kp, vp, table = _mk(rng, B=2, H=4, KV=4, D=32, P=16, ps=4, pps=3)
    lens = np.array([12, 12], np.int32)
    got = paged_decode(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                       lens, table)
    np.testing.assert_allclose(np.asarray(got),
                               _oracle(q, kp, vp, lens, table),
                               rtol=2e-4, atol=2e-4)


def test_matches_oracle_mixed_lengths_and_gqa():
    """Ragged batch + GQA: the length mask and the per-kv-head q-row
    grouping must both hold."""
    rng = np.random.RandomState(1)
    q, kp, vp, table = _mk(rng, B=3, H=8, KV=2, D=16, P=32, ps=4, pps=4)
    lens = np.array([16, 7, 1], np.int32)
    got = paged_decode(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                       lens, table)
    np.testing.assert_allclose(np.asarray(got),
                               _oracle(q, kp, vp, lens, table),
                               rtol=2e-4, atol=2e-4)


def test_partial_last_page():
    """A length that ends mid-page: the mask, not the page boundary,
    decides the attention span."""
    rng = np.random.RandomState(2)
    q, kp, vp, table = _mk(rng, B=1, H=2, KV=2, D=8, P=8, ps=4, pps=2)
    lens = np.array([5], np.int32)        # one full page + one token
    got = paged_decode(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                       lens, table)
    np.testing.assert_allclose(np.asarray(got),
                               _oracle(q, kp, vp, lens, table),
                               rtol=2e-4, atol=2e-4)


def test_unassigned_window_tail_is_inert():
    """Pages past ceil(len/ps) are never DMA'd (the table may hold a
    clipped -1 sentinel there) — the kernel's zero-fill + mask must
    make them unreachable."""
    rng = np.random.RandomState(3)
    q, kp, vp, table = _mk(rng, B=1, H=2, KV=1, D=8, P=8, ps=4, pps=4)
    lens = np.array([4], np.int32)        # only page 0 valid
    poisoned = table.copy()
    poisoned[0, 1:] = 0                   # clipped sentinels, arbitrary
    got_a = paged_decode(jnp.asarray(q), jnp.asarray(kp),
                         jnp.asarray(vp), lens, poisoned)
    poisoned[0, 1:] = 3                   # different garbage pages
    got_b = paged_decode(jnp.asarray(q), jnp.asarray(kp),
                         jnp.asarray(vp), lens, poisoned)
    np.testing.assert_allclose(np.asarray(got_a), np.asarray(got_b),
                               rtol=0, atol=0)
    np.testing.assert_allclose(np.asarray(got_a),
                               _oracle(q, kp, vp, lens, table),
                               rtol=2e-4, atol=2e-4)


def test_bfloat16_pool():
    rng = np.random.RandomState(4)
    q, kp, vp, table = _mk(rng, B=2, H=4, KV=2, D=16, P=16, ps=8, pps=2)
    lens = np.array([16, 9], np.int32)
    got = paged_decode(jnp.asarray(q, jnp.bfloat16),
                       jnp.asarray(kp, jnp.bfloat16),
                       jnp.asarray(vp, jnp.bfloat16), lens, table)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), _oracle(q, kp, vp, lens, table),
        rtol=5e-2, atol=5e-2)


def test_head_grouping_rejects_bad_ratio():
    rng = np.random.RandomState(5)
    q, kp, vp, table = _mk(rng, B=1, H=3, KV=2, D=8, P=8, ps=4, pps=2)
    with pytest.raises(ValueError, match="multiple"):
        paged_decode(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                     np.array([8], np.int32), table)


def test_supported_gate():
    assert supported(head_dim=128, page_size=16, on_tpu=True)
    assert not supported(head_dim=64, page_size=16, on_tpu=True)
    assert not supported(head_dim=128, page_size=6, on_tpu=True)
    assert not supported(head_dim=128, page_size=16, on_tpu=False)


def test_the_kernel_body_does_not_grow_with_the_window():
    """The page DMAs are loops, not an unroll: the traced program is as
    long at 64 pages a sequence as at 4.  (Unrolled, the kernel was
    re-traced for every decode batch size at ~400 conditionals a trace,
    most of a serving engine's set-up.)"""
    import jax

    def eqns(pps):
        rng = np.random.RandomState(0)
        q, kp, vp, table = _mk(rng, B=2, H=4, KV=2, D=16, P=2 * pps, ps=4,
                               pps=pps)
        text = str(jax.make_jaxpr(paged_decode)(
            q, kp, vp, np.array([3, 4 * pps], np.int32), table))
        return text.count("\n")

    assert eqns(64) == eqns(4)
