"""Self-authored fused paged-decode attention kernel vs the dense
oracle (reference block_multi_head_attention semantics).  Off-TPU the
kernel runs in Pallas interpreter mode — same kernel body, no tiling
constraints — so the fusion logic (DMA page gather block by block,
length masking, the running softmax across blocks, GQA grouping, the
scrubbed tail of the last block) is exercised everywhere.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas_kernels.paged_decode import (
    block_pages, paged_decode, supported,
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


def _traced(B, pps, ps=4, dtype=np.float32):
    """The jaxpr of one call at a batch of ``B`` and ``pps`` pages a
    sequence."""
    import jax

    rng = np.random.RandomState(0)
    q, kp, vp, table = _mk(rng, B=B, H=4, KV=2, D=16, P=B * pps, ps=ps,
                           pps=pps, dtype=dtype)
    lens = np.full((B,), ps * pps, np.int32)
    lens[0] = 3
    return jax.make_jaxpr(paged_decode)(q, kp, vp, lens, table)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it (the jitted
    wrapper, the kernel's body, its loops and conditionals)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _equations(sub)
    return n


def test_the_kernel_body_does_not_grow_with_the_window():
    """The page DMAs and the blocks are loops, not an unroll: the traced
    program holds as many equations at 64 pages a sequence as at 4.
    (Unrolled, the kernel was re-traced for every decode batch size at
    ~400 conditionals a trace, most of a serving engine's set-up.)"""
    few, many = _traced(2, 4).jaxpr, _traced(2, 64).jaxpr
    assert _equations(many) == _equations(few) > 50
    # as the pretty-printer has it too, but for where it wraps a line
    # (a longer shape wraps earlier)
    assert abs(str(many).count("\n") - str(few).count("\n")) <= 2


# -- the block loop (PR 33) ---------------------------------------------------
#
# A block is 256 keys: four pages of 64 here, so a window of 12 pages is
# three blocks, and sixteen pages of 16 as in the serving cell.

BLOCK = 256
EDGES = (1, BLOCK - 1, BLOCK, BLOCK + 1, 3 * BLOCK - 1, 3 * BLOCK)


def _run(q, kp, vp, lens, table):
    return np.asarray(paged_decode(jnp.asarray(q), jnp.asarray(kp),
                                   jnp.asarray(vp), lens, table),
                      np.float32)


@pytest.mark.parametrize("ps", [16, 64])
def test_a_block_is_256_keys_of_whole_pages(ps):
    """... at the serving cell's pool (8 KV heads x 128, bf16: 2 MiB of
    scratch) and at these tests'; fewer keys where every KV head's two
    slots of K and V would pass 4 MiB; never less than a page."""
    assert block_pages(ps, 8, 128, 2) * ps == BLOCK
    assert block_pages(ps, 2, 16, 4) * ps == BLOCK
    assert block_pages(ps, 32, 128, 4) * ps == 64
    assert block_pages(512, 8, 128, 2) == 1
    assert block_pages(24, 8, 128, 2) == 10


@pytest.mark.parametrize("ps", [16, 64])
def test_lengths_at_every_edge_of_a_block_in_one_batch(ps):
    """Ragged batch with GQA whose lengths sit on, before and after each
    edge of a block of a three-block window: the trip count, the last
    block's mask and the running softmax across blocks all hold."""
    rng = np.random.RandomState(6)
    pps = 3 * BLOCK // ps
    q, kp, vp, table = _mk(rng, B=len(EDGES), H=8, KV=2, D=16,
                           P=len(EDGES) * pps + 1, ps=ps, pps=pps)
    lens = np.array(EDGES, np.int32)
    np.testing.assert_allclose(_run(q, kp, vp, lens, table),
                               _oracle(q, kp, vp, lens, table),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("length", EDGES)
def test_one_sequence_at_an_edge_of_a_block(length):
    """Each edge alone, after a program of the same call that filled
    both scratch slots to the brim (sequence 0 reads the whole window):
    what it left there is not seen."""
    rng = np.random.RandomState(length)
    q, kp, vp, table = _mk(rng, B=2, H=4, KV=1, D=32, P=24, ps=64, pps=12)
    lens = np.array([3 * BLOCK, length], np.int32)
    np.testing.assert_allclose(_run(q, kp, vp, lens, table),
                               _oracle(q, kp, vp, lens, table),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("lens", [(0, 300, 0, 1), (0, 0, 0, 0)],
                         ids=["mixed", "all"])
def test_a_row_of_length_zero_reads_nothing_and_is_finite(lens):
    """A padded batch row (length 0) returns zeros, whatever its table
    row names, and the live rows beside it are unmoved by it."""
    rng = np.random.RandomState(7)
    q, kp, vp, table = _mk(rng, B=4, H=4, KV=2, D=16, P=48, ps=64, pps=12)
    kp[:, table[0]] = np.nan
    vp[:, table[0]] = np.nan
    lens = np.array(lens, np.int32)
    got = _run(q, kp, vp, lens, table)
    assert np.isfinite(got).all()
    assert not got[lens == 0].any()
    live = lens > 0
    if live.any():
        np.testing.assert_allclose(
            got[live], _oracle(q[live], kp, vp, lens[live], table[live]),
            rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("length", [100, BLOCK, 2 * BLOCK + 70])
def test_nothing_dead_is_read_or_used(length):
    """Every page past the length holds NaN in both pools, and the
    table's dead entries point at a NaN page: a dead page that was
    fetched, or a scratch row left unscrubbed under a zero weight, would
    show as NaN.  (Sequence 0 runs first and leaves live numbers of its
    own in both slots.)"""
    rng = np.random.RandomState(length)
    ps, pps = 64, 12
    q, kp, vp, table = _mk(rng, B=2, H=4, KV=2, D=16, P=24, ps=ps, pps=pps)
    lens = np.array([3 * BLOCK, length], np.int32)
    want = _oracle(q, kp, vp, lens, table)
    used = -(-length // ps)
    kp[:, table[1, used:]] = np.nan
    vp[:, table[1, used:]] = np.nan
    nan_page = np.full((2, 1, ps, 16), np.nan, np.float32)
    kp, vp = (np.concatenate([pool, nan_page], axis=1) for pool in (kp, vp))
    table[1, used:] = 24                        # the page of NaN
    got = _run(q, kp, vp, lens, table)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("ps", [16, 64])
def test_bfloat16_pool_across_three_blocks(ps):
    """The pool's dtype is the products' operand dtype: a bf16 pool at a
    length that spans three blocks stays inside the bf16 tolerance of
    the whole-window form."""
    rng = np.random.RandomState(8)
    pps = 3 * BLOCK // ps
    q, kp, vp, table = _mk(rng, B=2, H=4, KV=2, D=16, P=2 * pps, ps=ps,
                           pps=pps)
    lens = np.array([2 * BLOCK + 77, BLOCK + 1], np.int32)
    got = paged_decode(jnp.asarray(q, jnp.bfloat16),
                       jnp.asarray(kp, jnp.bfloat16),
                       jnp.asarray(vp, jnp.bfloat16), lens, table)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), _oracle(q, kp, vp, lens, table),
        rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("other", [(2, 64), (32, 4), (32, 64)],
                         ids=["window", "batch", "both"])
def test_the_traced_body_is_the_same_at_every_window_and_batch(other):
    """As many equations at 64 pages a sequence as at 4, and at a batch
    of 32 as at 2: the serving cell traces this body once for each of
    its 32 decode batch sizes."""
    assert _equations(_traced(*other).jaxpr) \
        == _equations(_traced(2, 4).jaxpr)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_scratch_does_not_grow_with_the_window(dtype):
    """Two slots of one block of every KV head for K and for V,
    whatever the window: the VMEM the kernel asks for at 512 pages a
    sequence is what it asks for at 16."""
    import re

    def scratch(pps):
        text = str(_traced(2, pps, ps=16, dtype=dtype))
        return sorted(set(re.findall(r"Ref<vmem>\{(\w+)\[([\d,]+)\]\}",
                                     text)))

    name = "f32" if dtype is np.float32 else "bf16"
    assert scratch(512) == scratch(16)
    assert scratch(16) == [(name, f"2,2,{BLOCK},16")]    # [slot, KV, keys, D]


# -- the windowed start (PR 34) ----------------------------------------------
#
# A window layer reads keys [start, length) through a table whose first
# entry stands for token ``base``: the walk begins at the start's block.

def _window_oracle(q, k_pages, v_pages, lens, table, starts, bases):
    """Dense masked attention in numpy float64: row b reads the keys of
    tokens ``starts[b] .. lens[b] - 1``, token t at table position
    ``t - bases[b]``."""
    B, H, D = q.shape
    KV, _, ps, _ = k_pages.shape
    T = table.shape[1] * ps
    out = np.zeros((B, H, D), np.float32)
    for b in range(B):
        kc = k_pages[:, table[b]].reshape(KV, T, D).astype(np.float64)
        vc = v_pages[:, table[b]].reshape(KV, T, D).astype(np.float64)
        lo, hi = starts[b] - bases[b], lens[b] - bases[b]
        if hi <= lo:
            continue
        for h in range(H):
            kv = h // (H // KV)
            lg = (q[b, h].astype(np.float64) @ kc[kv, lo:hi].T) / np.sqrt(D)
            p = np.exp(lg - lg.max())
            out[b, h] = (p / p.sum()) @ vc[kv, lo:hi]
    return out


def _run_window(q, kp, vp, lens, table, starts, bases):
    return np.asarray(paged_decode(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), lens, table,
        starts=jnp.asarray(starts, jnp.int32),
        bases=jnp.asarray(bases, jnp.int32)), np.float32)


WINDOW_STARTS = {
    "zero": 0, "mid-page": 37, "page-aligned": 64, "block-aligned": BLOCK,
    "past-a-block": BLOCK + 70, "last-key": None}


@pytest.mark.parametrize("ps", [16, 64])
@pytest.mark.parametrize("which", sorted(WINDOW_STARTS))
def test_a_window_starts_at_its_first_visible_key(ps, which):
    """Starts at 0, inside a page, on a page's edge, on a block's edge and
    past one, and at the last key: the keys before the start are masked
    whatever the table's first pages hold (NaN here, as a released page
    another sequence took over may)."""
    rng = np.random.RandomState(11)
    pps = 3 * BLOCK // ps
    q, kp, vp, table = _mk(rng, B=3, H=4, KV=2, D=16, P=4 * pps, ps=ps,
                           pps=pps)
    lens = np.array([3 * BLOCK, 2 * BLOCK + 5, BLOCK + 71], np.int32)
    start = WINDOW_STARTS[which]
    starts = (lens - 1 if start is None
              else np.minimum(start, lens - 1)).astype(np.int32)
    bases = np.zeros(3, np.int32)
    want = _window_oracle(q, kp, vp, lens, table, starts, bases)
    # what lies wholly before the first visible page is never read
    for b in range(3):
        dead = table[b, :starts[b] // ps]
        kp[:, dead] = np.nan
        vp[:, dead] = np.nan
    got = _run_window(q, kp, vp, lens, table, starts, bases)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("ps", [16, 64])
def test_a_shifted_table_stands_for_its_base(ps):
    """The table's first entry stands for token ``base``: the same keys
    read through a table shifted left by the released pages give the same
    output as through the unshifted one."""
    rng = np.random.RandomState(12)
    pps = 3 * BLOCK // ps
    q, kp, vp, table = _mk(rng, B=3, H=8, KV=2, D=16, P=4 * pps, ps=ps,
                           pps=pps)
    lens = np.array([3 * BLOCK - 3, 2 * BLOCK + 5, 300], np.int32)
    starts = np.array([BLOCK + 130, BLOCK - 1, 0], np.int32)
    whole = _run_window(q, kp, vp, lens, table, starts, np.zeros(3, np.int32))
    shifted, bases = np.zeros_like(table), starts // ps * ps
    for b in range(3):
        n = bases[b] // ps
        shifted[b, :pps - n] = table[b, n:]
    got = _run_window(q, kp, vp, lens, shifted, starts, bases)
    np.testing.assert_allclose(got, whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        whole, _window_oracle(q, kp, vp, lens, table, starts,
                              np.zeros(3, np.int32)), rtol=2e-4, atol=2e-4)


def test_zeros_for_starts_and_bases_are_the_full_layer():
    rng = np.random.RandomState(13)
    q, kp, vp, table = _mk(rng, B=2, H=4, KV=2, D=16, P=40, ps=16, pps=20)
    lens = np.array([301, 17], np.int32)
    zeros = np.zeros(2, np.int32)
    assert np.array_equal(_run(q, kp, vp, lens, table),
                          _run_window(q, kp, vp, lens, table, zeros, zeros))


def test_the_dense_fallback_masks_the_same_window():
    from paddle_tpu.inference.paged import _dense_pool_attention

    rng = np.random.RandomState(14)
    q, kp, vp, table = _mk(rng, B=2, H=4, KV=2, D=16, P=40, ps=16, pps=8)
    lens = np.array([200, 150], np.int32)
    starts, bases = np.array([140, 75], np.int32), np.array([128, 64],
                                                            np.int32)
    got = _dense_pool_attention(
        jnp.asarray(q), jnp.asarray(kp)[None], jnp.asarray(vp)[None],
        jnp.asarray(lens), jnp.asarray(table), 0, jnp.asarray(starts),
        jnp.asarray(bases))
    np.testing.assert_allclose(
        np.asarray(got), _window_oracle(q, kp, vp, lens, table, starts,
                                        bases), rtol=2e-4, atol=2e-4)


# -- the entry's scale ---------------------------------------------------------

@pytest.mark.parametrize("impl", ["dense", "pallas"])
@pytest.mark.parametrize("pool", ["layer", "whole", "window", "int8"])
def test_a_given_scale_multiplies_the_scores_on_every_path(monkeypatch, impl,
                                                           pool):
    """``paged_decode_attention(.., scale=s)`` is the default call with the
    queries times ``s * sqrt(D)``: on one layer's pool, on the whole pools
    with a layer named, on a window layer and on the int8 pool, through
    the fused kernel and through the dense fallback (a caller whose
    queries arrive scaled, or whose rows are wider than its heads, says
    1.0 and none of them divides by sqrt(D) behind its back)."""
    from paddle_tpu.inference.paged import paged_decode_attention

    monkeypatch.setenv("PT_PAGED_IMPL", impl)
    rng = np.random.RandomState(35)
    q, kp, vp, table = _mk(rng, B=2, H=4, KV=2, D=16, P=40, ps=32, pps=10)
    lens, scale = np.array([300, 33], np.int32), 0.37
    kw = {}
    if pool != "layer":
        kp, vp = np.stack([kp[::-1], kp]), np.stack([vp[::-1], vp])
        kw = dict(layer=1)
    if pool == "window":
        kw.update(starts=np.array([40, 0], np.int32),
                  bases=np.array([32, 0], np.int32))
    if pool == "int8":
        kp, vp = (rng.randint(-127, 128, p[1].shape).astype(np.int8)
                  for p in (kp, vp))
        kw = dict(k_scales=rng.rand(2, 40).astype(np.float32) * 0.02,
                  v_scales=rng.rand(2, 40).astype(np.float32) * 0.02)
    got = paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), lens, table, scale=scale,
                                 **kw)
    want = paged_decode_attention(jnp.asarray(q * scale * 4.0),
                                  jnp.asarray(kp), jnp.asarray(vp), lens,
                                  table, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    plain = paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                                   jnp.asarray(vp), lens, table, **kw)
    assert np.abs(np.asarray(got) - np.asarray(plain)).max() > 1e-2
