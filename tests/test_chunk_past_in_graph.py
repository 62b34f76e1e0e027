"""A prefill chunk reads its past from the page pools inside its program
(PR 31): ``serve.prefill_chunk`` takes the pools and the past's page ids
and gathers each layer's past by row of the flat pool.

Held here, on the CPU at tiny widths: the program's logits and chunk K/V
equal, bit for bit, those of the form it replaced (the same ``_chunk_fwd``
arithmetic fed ``cache.gather_dense``'s dense arrays, kept below as the
control) for float32, bf16 and int8 pools, for an empty past, one partial
page, several pages with a ragged length, page ids padded as the AOT ladder
pads them, and a past of shared prefix pages; they agree with a whole-prompt
float32 forward; and an engine that chunks its prompts serves the tokens of
one that prefills them whole.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference.paged import _past_of
from paddle_tpu.inference.server import PagedExecutor, ServingEngine
from paddle_tpu.inference.server import executor as executor_module
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

PS = 4
PROMPT = np.random.RandomState(7).randint(1, 256, (40,)).astype(np.int32)
OTHER = np.random.RandomState(8).randint(1, 256, (13,)).astype(np.int32)

# pool kind -> executor keywords, and how far the chunk's logits may lie
# from the float32 whole-prompt forward's (over the RMS of its logits)
POOLS = {
    "f32": (dict(dtype=jnp.float32), 1e-5),
    "bf16": (dict(dtype=jnp.bfloat16), 2e-2),
    "int8": (dict(dtype=jnp.float32, quant="int8"), 2e-1),
}
# past tokens -> the chunk that follows them
PASTS = {"none": 0, "partial-page": 3, "ragged-pages": 14}


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=3,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


def _executor(model, pool):
    """An executor whose pool already holds another sequence, so that page
    ids are not 0, 1, 2, .. and a wrong row shows."""
    ex = PagedExecutor(model, max_seqs=4, page_size=PS, max_len=64,
                       **POOLS[pool][0])
    ex.prefill(ex.alloc_slot(), OTHER)
    return ex


def _with_past(ex, start):
    """A fresh slot holding PROMPT[:start], written by chunks of 5."""
    sid = ex.alloc_slot()
    for at in range(0, start, 5):
        ex.prefill_chunk(sid, PROMPT[at:min(at + 5, start)], at, False)
    return sid


def _in_graph(ex, sid, chunk, start, pids=None):
    """The program as ``prefill_chunk`` dispatches it."""
    pids = ex.cache.past_pages(sid, start) if pids is None else pids
    kp, vp = ex.cache.pools()
    return jax.jit(ex._chunk_fwd)(
        ex.layers, ex.tops, chunk[None], np.int32(start), kp, vp, pids,
        np.int32(start))


def _dense_past(ex, sid, chunk, start, pad_pages=0):
    """The control: the form this replaced.  ``gather_dense``'s arrays
    (padded with zeros, as the AOT branch padded them) go in where the
    pools go, and each layer takes its own slice of them."""
    past_k, past_v = ex.cache.gather_dense(sid, start)
    pad = ((0, 0), (0, 0), (0, pad_pages * PS), (0, 0))
    past_k, past_v = jnp.pad(past_k, pad), jnp.pad(past_v, pad)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(executor_module, "_past_of",
                   lambda past, layer, pids, dtype: past[layer])
        return jax.jit(ex._chunk_fwd)(
            ex.layers, ex.tops, chunk[None], np.int32(start), past_k,
            past_v, np.zeros(past_k.shape[2] // PS, np.int32),
            np.int32(start))


def _assert_same_bits(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("past", list(PASTS))
@pytest.mark.parametrize("pool", list(POOLS))
def test_the_chunk_equals_the_dense_past_form_bit_for_bit(model, pool, past):
    ex = _executor(model, pool)
    start = PASTS[past]
    sid = _with_past(ex, start)
    chunk = PROMPT[start:start + 9]
    got = _in_graph(ex, sid, chunk, start)
    _assert_same_bits(got, _dense_past(ex, sid, chunk, start))
    # .. and the float32 whole-prompt forward of the same tokens
    logits, k, v = ex._prefill_fwd(ex.layers, ex.tops,
                                   PROMPT[None, :start + 9])
    rms = float(jnp.sqrt(jnp.mean(logits ** 2)))
    tol = POOLS[pool][1] if start else 1e-5
    assert float(jnp.max(jnp.abs(got[0] - logits))) <= tol * rms
    for mine, whole in ((got[1], k), (got[2], v)):
        np.testing.assert_allclose(
            np.asarray(mine), np.asarray(whole[:, :, start:]),
            rtol=0, atol=tol * float(jnp.max(jnp.abs(whole))))


@pytest.mark.parametrize("pool", list(POOLS))
def test_padded_page_ids_are_masked_exactly(model, pool):
    """The AOT ladder pads the page ids to a bucket with a valid page id
    (here another sequence's page and the sequence's own last one): the
    mask drops those columns, as it dropped the zeros the dense past was
    padded with."""
    ex = _executor(model, pool)
    sid = _with_past(ex, 14)
    chunk = PROMPT[14:22]
    pids = ex.cache.past_pages(sid, 14)
    padded = np.concatenate(
        [pids, ex.cache.past_pages(0)[:3], pids[-1:]]).astype(np.int32)
    got = _in_graph(ex, sid, chunk, 14, padded)
    _assert_same_bits(got, _dense_past(ex, sid, chunk, 14, pad_pages=4))
    # a softmax over 32 columns sums in another order than one over 16:
    # against the program without padding, closeness and not bits
    for g, w in zip(got, _in_graph(ex, sid, chunk, 14)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=0, atol=2e-6)


@pytest.mark.parametrize("pool", list(POOLS))
def test_a_past_of_shared_prefix_pages_is_read_and_left_alone(model, pool):
    """A slot attached to another's pages (a prefix-cache hit ending inside
    a page) reads them in the program; the chunk's write then goes to a
    copy of the shared page, and the owner's pages keep their bits."""
    ex = _executor(model, pool)
    owner = _with_past(ex, 10)
    shared = [int(p) for p in ex.cache.past_pages(owner, 10)]
    before = [np.asarray(p[:, :, shared]) for p in
              jax.tree.leaves(ex.cache.pools())]
    sid = ex.alloc_slot()
    ex.attach_prefix(sid, shared, 10)
    chunk = PROMPT[10:17]
    got = _in_graph(ex, sid, chunk, 10)
    _assert_same_bits(got, _dense_past(ex, sid, chunk, 10))
    _assert_same_bits(got, _in_graph(ex, owner, chunk, 10))
    ex.prepare_write(sid, 10, len(chunk))
    ex.prefill_chunk(sid, chunk, 10, True)
    assert ex.cache.cow_count == 1
    after = [np.asarray(p[:, :, shared]) for p in
             jax.tree.leaves(ex.cache.pools())]
    for was, now in zip(before, after):
        np.testing.assert_array_equal(was, now)


def test_the_past_comes_out_of_the_pool_by_row():
    """``_past_of`` against plain indexing, every layer, page ids out of
    order and repeated; an int8 pool comes out dequantized."""
    rng = np.random.RandomState(2)
    pool = jnp.asarray(rng.randn(3, 2, 12, PS, 8).astype(np.float32))
    pids = np.array([7, 0, 11, 7, 3], np.int32)
    q = jnp.asarray(rng.randint(-127, 128, pool.shape).astype(np.int8))
    scales = jnp.asarray(rng.rand(3, 2, 12).astype(np.float32))
    for layer in range(3):
        want = np.asarray(pool)[layer][:, pids].reshape(2, 5 * PS, 8)
        got = _past_of(pool, jnp.int32(layer), pids, jnp.float32)
        np.testing.assert_array_equal(np.asarray(got), want)
        want = (np.asarray(q, np.float32)[layer][:, pids]
                * np.asarray(scales)[layer][:, pids, None, None])
        got = _past_of((q, scales), jnp.int32(layer), pids, jnp.float32)
        np.testing.assert_array_equal(np.asarray(got),
                                      want.reshape(2, 5 * PS, 8))
    assert _past_of(pool, 0, pids[:0], jnp.float32).shape == (2, 0, 8)


PROMPTS = [np.random.RandomState(s).randint(1, 256, (n,)).astype(np.int32)
           for s, n in ((21, 27), (22, 9), (23, 18))]
ENGINES = {
    "plain": {},
    "aot": dict(aot="warm"),
    "prefix-cache": dict(prefix_cache=True),
    "int8": dict(quant="int8"),
}


@pytest.mark.parametrize("how", list(ENGINES))
def test_chunked_prompts_serve_the_tokens_of_whole_prompt_prefill(
        model, how, tmp_path):
    """Prompts of up to four chunks (the prefix cache sees the first one
    twice, so its second run starts from attached pages): the engine's
    tokens are those of an engine that prefills every prompt whole."""
    kw = {**dict(max_seqs=2, page_size=PS, max_len=64, aot="off"),
          **ENGINES[how]}
    if how == "aot":
        kw["compile_cache"] = str(tmp_path)
    prompts = PROMPTS + PROMPTS[:1]
    whole = ServingEngine(model, **{**kw, "aot": "off",
                                    "prefix_cache": False})
    want = [whole.submit(p, max_new_tokens=6).result() for p in prompts]
    eng = ServingEngine(model, prefill_chunk=8, **kw)
    got = [eng.submit(p, max_new_tokens=6).result() for p in prompts]
    assert got == want
    chunks = eng.executor.programs["prefill_chunk"]
    assert chunks.dispatches >= 9
    assert whole.executor.programs["prefill_chunk"].dispatches == 0
    if how == "prefix-cache":
        assert eng.metrics.prefix_hits > 0
