"""The page pool's one device-side writer (``PagedKVCache.writer``,
``serve.kv_write``): a span's bytes land where a plain page-by-page NumPy
loop puts them, the program is keyed on the span's length alone, the pools
are donated and replaced at once, and a fault before the write leaves the
cache usable."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import DispatchAuditor, ProgramContract
from paddle_tpu.inference.paged import PagedKVCache
from paddle_tpu.inference.server import PagedExecutor, check_pool_invariants
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.quant import QMAX
from paddle_tpu.testing import faults

L, KV, D, PS, PAGES, SEQS = 2, 2, 16, 4, 24, 3


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def make_cache(kind, rng):
    """A tiny pool with something in every cell, so that a write that
    strays shows, and page ids that are not their slots' numbers."""
    cache = PagedKVCache(L, KV, D, PAGES, page_size=PS, max_seqs=SEQS,
                         dtype=jnp.bfloat16 if kind == "bf16" else jnp.float32,
                         max_pages_per_seq=8,
                         quant="int8" if kind == "int8" else None)
    cache._free = [int(p) for p in rng.permutation(PAGES)]
    shape = cache.k_pages.shape
    if kind == "int8":
        cache.k_pages = jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        cache.v_pages = jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        cache.k_scales = jnp.asarray(rng.rand(*shape[:3]) * 0.02, jnp.float32)
        cache.v_scales = jnp.asarray(rng.rand(*shape[:3]) * 0.02, jnp.float32)
    else:
        cache.k_pages = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
        cache.v_pages = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    return cache


def pool_bytes(cache):
    """The pools (and scales) on the host, as writable arrays."""
    return [np.array(a) for a in jax.tree.leaves(cache.pools())]


def span_of(rng, kind, T):
    x = rng.randn(L, KV, T, D).astype(np.float32)
    return np.asarray(jnp.asarray(x, jnp.bfloat16)) if kind == "bf16" else x


def reference_write(pages, scales, row, x, start):
    """The old loop, on the host: one page at a time, each token row to
    ``pages[:, :, pid, off]``.  On an int8 pool (``scales`` given) a page's
    scale first grows to cover its new rows, its resident cells follow the
    scale, and the rows are quantized at it (``ops.quant.kv_write``)."""
    T, t = x.shape[2], 0
    while t < T:
        pos = start + t
        off = pos % PS
        n = min(PS - off, T - t)
        pid = int(row[pos // PS])
        new = x[:, :, t:t + n]
        if scales is None:
            pages[:, :, pid, off:off + n] = new
        else:
            old = scales[:, :, pid]
            # (the compiled program multiplies by the rounded 1/127)
            need = np.abs(new).max(axis=(2, 3)) * (np.float32(1) / QMAX)
            s = np.maximum(old, need)
            ratio = np.where(s > 0, old / np.where(s > 0, s, 1), 1)
            pages[:, :, pid] = np.clip(np.rint(
                pages[:, :, pid].astype(np.float32)
                * ratio[..., None, None].astype(np.float32)), -QMAX, QMAX)
            pages[:, :, pid, off:off + n] = np.clip(np.rint(
                new / np.where(s > 0, s, 1)[..., None, None]), -QMAX, QMAX)
            scales[:, :, pid] = s
        t += n


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("T", [1, PS - 1, PS, 3 * PS + 5])
@pytest.mark.parametrize("start", [0, 2 * PS, PS + 3])
def test_span_lands_byte_for_byte(kind, T, start):
    rng = np.random.RandomState(1000 * start + T)
    cache = make_cache(kind, rng)
    other, seq = cache.allocate(), cache.allocate()
    cache.write_at(other, *(span_of(rng, kind, 5),) * 2, 0)
    if start:
        cache.write_at(seq, span_of(rng, kind, start),
                       span_of(rng, kind, start), 0)
    before = pool_bytes(cache)
    want = [a.copy() for a in before]
    k, v = span_of(rng, kind, T), span_of(rng, kind, T)
    cache.write_at(seq, k, v, start)
    assert int(cache.lengths[seq]) == start + T
    check_pool_invariants(cache)
    row = cache.page_table[seq]
    if kind == "int8":
        reference_write(want[0], want[1], row, k, start)
        reference_write(want[2], want[3], row, v, start)
    else:
        reference_write(want[0], None, row, k, start)
        reference_write(want[1], None, row, v, start)
    got = pool_bytes(cache)
    for w, g in zip(want, got):
        assert g.dtype == w.dtype and g.tobytes() == w.tobytes()
    # no page but the span's own has moved: not the other sequence's, not
    # a free one, not this sequence's earlier ones
    mine = [int(p) for p in row[start // PS:-(-(start + T) // PS)]]
    rest = np.setdiff1d(np.arange(PAGES), mine)
    assert len(rest) == PAGES - len(mine) < PAGES
    for b, g in zip(before, got):
        assert g[:, :, rest].tobytes() == b[:, :, rest].tobytes()
        assert g[:, :, mine].tobytes() != b[:, :, mine].tobytes()


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_one_program_a_span_length(kind):
    """The program is keyed on T: another slot, start and set of page ids
    (and a ragged against an aligned start) trace nothing new."""
    rng = np.random.RandomState(2)
    cache = make_cache(kind, rng)
    a, b = cache.allocate(), cache.allocate()
    with DispatchAuditor(cache.writer, traces=1, dispatches=3):
        cache.write_at(a, *(span_of(rng, kind, 6),) * 2, 0)
        cache.write_at(b, *(span_of(rng, kind, 6),) * 2, 0)
        cache.write_at(a, *(span_of(rng, kind, 6),) * 2, 6)
    with DispatchAuditor(cache.writer, traces=1, dispatches=1):
        cache.write_at(b, *(span_of(rng, kind, 7),) * 2, 6)
    assert cache.writer.name == "serve.kv_write"


def test_empty_span_only_sets_the_length():
    rng = np.random.RandomState(3)
    cache = make_cache("bf16", rng)
    seq = cache.allocate()
    cache.write_at(seq, *(span_of(rng, "bf16", 5),) * 2, 0)
    before = pool_bytes(cache)
    cache.write_at(seq, *(span_of(rng, "bf16", 0),) * 2, 5)
    assert int(cache.lengths[seq]) == 5
    for b, a in zip(before, pool_bytes(cache)):
        assert a.tobytes() == b.tobytes()


# -- donation -----------------------------------------------------------------


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


EX_KW = dict(max_seqs=2, page_size=4, max_len=64)


@pytest.mark.parametrize("quant", [None, "int8"])
def test_pools_are_donated_and_replaced(model, quant):
    """The write consumes the old pools (nothing can read them again) and
    every reader after it finds the new ones: the past-KV gather, a second
    chunk's write, a decode step."""
    ex = PagedExecutor(model, quant=quant, **EX_KW)
    cache = ex.cache
    ids = np.random.RandomState(4).randint(1, 256, (14,)).astype(np.int32)
    sid = ex.alloc_slot()
    old = jax.tree.leaves(cache.pools())
    assert ex.prefill_chunk(sid, ids[:8], 0, False) is None
    assert all(a.is_deleted() for a in old)
    assert not any(a.is_deleted() for a in jax.tree.leaves(cache.pools()))
    k, _ = cache.gather_dense(sid, 8)
    assert k.shape[2] == 8 and np.isfinite(np.asarray(k, np.float32)).all()
    tok = ex.prefill_chunk(sid, ids[8:], 8, True)
    assert int(cache.lengths[sid]) == 14
    out = ex.decode([sid])
    assert set(out) == {sid} and int(cache.lengths[sid]) == 15
    check_pool_invariants(cache)
    # ... and the stream is the whole-prompt program's, which writes the
    # same prompt through one span of 14
    ref = PagedExecutor(model, quant=quant, **EX_KW)
    rsid = ref.alloc_slot()
    assert ref.prefill(rsid, ids) == tok and ref.decode([rsid])[rsid] == out[sid]
    # the compiled program aliases each pool leaf to its output
    args = (*cache.pools(), jnp.zeros((2, 2, 8, 16), cache.compute_dtype),
            jnp.zeros((2, 2, 8, 16), cache.compute_dtype))
    args += ((np.zeros((8,), np.int32),) * 2 if quant else
             (np.zeros((3,), np.int32), np.int32(0)))
    text = cache.writer.lower(*args).compile().as_text()
    header = text[:text.index("\n")]
    assert "input_output_alias" in header
    assert header.count("-alias)") == len(jax.tree.leaves(cache.pools()))


def test_donation_miss_lint_covers_the_writer(model):
    ex = PagedExecutor(model, **EX_KW)
    contract = analysis.registered()["serve.kv_write"]
    assert contract.donate_argnums == ex.cache.writer.donate_argnums == (0, 1)
    assert analysis.lint_contract(contract).ok
    undonated = ProgramContract(
        name="test.kv_write_undonated", fn=ex.cache.writer.fn,
        args=contract.example_args())
    found = analysis.lint_contract(undonated).violations
    assert len([v for v in found if v.check == "donation-miss"]) == 2


# -- faults -------------------------------------------------------------------


@pytest.mark.parametrize("point,quant,sp", [
    ("quant.kv_write", "int8", False),
    ("sp.shard", None, True),
])
def test_fault_before_the_write_leaves_the_cache_usable(model, point, quant,
                                                        sp):
    """The fault points lie before the donated call: a raise there leaves
    the pools as they were (alive, every byte), and the same write goes
    through afterwards."""
    ex = PagedExecutor(model, quant=quant, **EX_KW)
    cache = ex.cache
    rng = np.random.RandomState(5)
    k, v = rng.randn(2, 2, 8, 16).astype(np.float32), \
        rng.randn(2, 2, 8, 16).astype(np.float32)
    seq = cache.allocate()
    cache.write_at(seq, k[:, :, :3], v[:, :, :3], 0)
    before = pool_bytes(cache)

    def write(c, s):
        if sp:
            c.write_sharded(s, k, v, 3, 2)
        else:
            c.write_at(s, k, v, 3)

    faults.arm(point, "before", 1, "raise")
    with pytest.raises(faults.InjectedFault):
        write(cache, seq)
    faults.reset()
    assert int(cache.lengths[seq]) == 3
    for b, a in zip(before, pool_bytes(cache)):
        assert a.tobytes() == b.tobytes()
    write(cache, seq)
    assert int(cache.lengths[seq]) == 11
    check_pool_invariants(cache)
    clean = PagedExecutor(model, quant=quant, **EX_KW).cache
    cseq = clean.allocate()
    clean.write_at(cseq, k[:, :, :3], v[:, :, :3], 0)
    write(clean, cseq)
    for a, b in zip(cache.gather_dense(seq), clean.gather_dense(cseq)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
