"""The decode and verify programs carry the KV pools through their layer
scan and address them by layer in place (PR 29): the layer-addressed
attention equals the attention over the sliced layer, a decode step
touches one slot of one page a sequence and layer and nothing else, and
every program that runs the shared layer stack emits the tokens a dense
float32 recomputation of the model gives — also through the fused
kernel's block loop (PR 33; interpret mode), across the edge of a block.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference.paged import (
    _dense_paged_attention, _flat, _put_token, paged_decode_attention,
)
from paddle_tpu.inference.server import PagedExecutor
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.pallas_kernels.paged_decode import paged_decode

# -- (a) the layer-addressed attention ------------------------------------

L, KV, P, PS, D, H, PPS = 3, 2, 12, 4, 16, 4, 3


def _pool_case():
    """A 3-layer pool whose page ids mean another page in every layer,
    ragged lengths, and a table that hands the same ids to each layer."""
    rng = np.random.RandomState(5)
    kp = rng.randn(L, KV, P, PS, D).astype(np.float32)
    vp = rng.randn(L, KV, P, PS, D).astype(np.float32)
    q = rng.randn(3, H, D).astype(np.float32)
    table = rng.choice(P, size=(3, PPS), replace=False).astype(np.int32)
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            np.array([12, 5, 1], np.int32), table)


def _kernel_traced_layer(q, kp, vp, lens, table, layer):
    return jax.jit(paged_decode)(q, kp, vp, lens, table, jnp.int32(layer))


def _entry(q, kp, vp, lens, table, layer):
    return paged_decode_attention(q, kp, vp, lens, table, layer=layer)


@pytest.mark.parametrize("layer", range(L))
@pytest.mark.parametrize("path,impl", [
    pytest.param(paged_decode, None, id="kernel"),
    pytest.param(_kernel_traced_layer, None, id="kernel-traced-layer"),
    pytest.param(_entry, "pallas", id="entry-pallas"),
    pytest.param(_entry, "dense", id="entry-dense"),
])
def test_layer_addressed_attention_equals_the_sliced_layer(
        monkeypatch, path, impl, layer):
    if impl:
        monkeypatch.setenv("PT_PAGED_IMPL", impl)
    q, kp, vp, lens, table = _pool_case()
    want = _dense_paged_attention(q, kp[layer], vp[layer],
                                  jnp.asarray(lens), jnp.asarray(table))
    got = path(q, kp, vp, lens, table, layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_a_pool_and_its_layer_must_come_together():
    q, kp, vp, lens, table = _pool_case()
    with pytest.raises(ValueError, match="needs its layer"):
        paged_decode(q, kp, vp, lens, table)
    with pytest.raises(ValueError, match="pool of one layer"):
        paged_decode(q, kp[0], vp[0], lens, table, 0)


# -- (b) what a token write touches ------------------------------------------


@pytest.mark.parametrize("layer", range(L))
def test_a_token_goes_into_one_slot_and_a_bad_page_id_is_dropped(layer):
    """Sequence 0 writes slot 2 of page 7, sequence 1 names the page id
    past the pool (a padded or invalid cell): one slot of one page of
    each KV head of ``layer`` changes, everything else is bit-identical."""
    rng = np.random.RandomState(layer)
    pool = rng.randn(L, KV, P, PS, D).astype(np.float32)
    x = rng.randn(2, KV, D).astype(np.float32)
    got = np.asarray(_put_token(
        _flat(jnp.asarray(pool)), pool.shape, jnp.int32(layer),
        jnp.asarray([7, P], jnp.int32), jnp.asarray([2, 1], jnp.int32),
        jnp.asarray(x))).reshape(pool.shape)
    want = pool.copy()
    want[layer, :, 7, 2] = x[0]
    np.testing.assert_array_equal(got, want)


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=3,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


PROMPT_LENS = (5, 9, 3)


def _started(model, lens=PROMPT_LENS, page_size=4, max_len=64, **kw):
    """An executor with three sequences prefilled at ragged lengths."""
    ex = PagedExecutor(model, max_seqs=4, page_size=page_size,
                       max_len=max_len, **kw)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 256, (n,)).astype(np.int32) for n in lens]
    sids = []
    for p in prompts:
        sids.append(ex.alloc_slot())
        ex.prefill(sids[-1], p)
    return ex, sids, prompts


STEPPERS = {
    "decode": lambda ex, sids: ex.decode(sids),
    "decode_async": lambda ex, sids: ex.decode_async(sids).wait(),
    "decode_n": lambda ex, sids: ex.decode_n(sids, 3),
    "verify": lambda ex, sids: ex.verify(
        sids, [[1, 2]] * len(sids), [3, 1, 2], 2),
}


@pytest.mark.parametrize("how", list(STEPPERS))
def test_a_step_leaves_every_untouched_page_bit_identical(model, how):
    """After one step of each program, a page of any layer changes only
    where a live sequence's new positions lie: the slots of the tokens
    it may commit, in its own pages."""
    ex, sids, _ = _started(model)
    cache = ex.cache
    before = [np.asarray(p) for p in cache.pools()]
    start = {s: int(cache.lengths[s]) for s in sids}
    wrote = {"decode": [1] * 3, "decode_async": [1] * 3,
             "decode_n": [3] * 3, "verify": [3, 1, 2]}[how]
    STEPPERS[how](ex, sids)
    may_change = np.zeros(before[0].shape[2:4], bool)      # [P, ps]
    for s, n in zip(sids, wrote):
        for pos in range(start[s], start[s] + n):
            may_change[cache.page_table[s, pos // 4], pos % 4] = True
    for was, now in zip(before, (np.asarray(p) for p in cache.pools())):
        same = (was == now).all(axis=(0, 1, 4))            # [P, ps]
        assert same[~may_change].all()
        assert not same[may_change].any()


# -- (c) the tokens, against a dense float32 recomputation -------------------


def _recomputed(model, prompt, answer):
    """Greedy tokens of ONE dense float32 forward over prompt + answer:
    what the model puts after every prefix of it."""
    ids = np.concatenate([prompt, answer[:-1]]).astype(np.int64)
    logits = model(paddle.to_tensor(ids[None])).numpy()[0]
    return np.argmax(logits[len(prompt) - 1:], axis=-1)


def _drive(ex, sids, how, steps):
    out = {s: [ex.last_token[s]] for s in sids}
    for _ in range(-(-steps // 3) if how == "decode_n" else steps):
        got = STEPPERS[how](ex, sids)
        for s in sids:
            out[s] += got[s] if how == "decode_n" else [got[s]]
    return out


def _drive_verify(ex, sids, ahead, steps):
    """Windows of two drafts from ``ahead`` (the tokens to come), the
    second one wrong every other round: drafts are accepted, rejected,
    and a rejected draft's K/V is written over by the next window."""
    out = {s: [ex.last_token[s]] for s in sids}
    accepted = rounds = 0
    while min(len(v) for v in out.values()) < steps:
        drafts = []
        for s in sids:
            d = list(ahead[s][len(out[s]):len(out[s]) + 2])
            if rounds % 2 and len(d) == 2:
                d[1] = (d[1] + 1) % 256
            drafts.append(d)
        toks, acc = ex.verify(sids, drafts, [1 + len(d) for d in drafts], 2)
        ex.rollback(sids)
        for s in sids:
            out[s] += toks[s]
        accepted += sum(acc.values())
        rounds += 1
    assert rounds < accepted < 2 * rounds * len(sids)
    return out


@pytest.mark.parametrize("how", list(STEPPERS))
def test_tokens_equal_a_dense_float32_recomputation(model, how):
    """44 tokens a sequence from ragged starts at a page of 4: every
    sequence crosses ten page boundaries, none in step with another."""
    ex, sids, prompts = _started(model)
    if how == "verify":
        ahead = _drive(*_started(model)[:2], "decode", 50)
        out = _drive_verify(ex, sids, ahead, 44)
    else:
        out = _drive(ex, sids, how, 44)
    for s, p in zip(sids, prompts):
        answer = np.asarray(out[s][:44])
        assert len(answer) == 44
        np.testing.assert_array_equal(answer, _recomputed(model, p, answer))


def test_the_int8_pool_keeps_its_scanned_form_and_its_tokens(model):
    """The int8 pool is a (pages, scales) tuple and takes the other
    branch of the same layer stack: it runs, and decode_n agrees with
    decode step by step as it did."""
    a, sa, _ = _started(model, quant="int8")
    b, sb, _ = _started(model, quant="int8")
    assert isinstance(a.cache.pools()[0], tuple)
    one = [a.decode(sa) for _ in range(6)]
    many = b.decode_n(sb, 6)
    for x, y in zip(sa, sb):
        assert [t[x] for t in one] == many[y]


# -- (d) the same programs through the fused kernel's block loop (PR 33) ----
#
# Pages of 32 tokens: a block of the kernel is 8 pages = 256 keys and the
# window of 320 tokens is two blocks.  Sequence 0 starts six tokens
# before the edge of a block and decodes across it, sequence 1 starts
# past it, sequence 2 stays in the first page.

BLOCK_LENS = (250, 259, 3)
BLOCK_KW = dict(page_size=32, max_len=320)


@pytest.fixture(scope="module")
def long_model():
    paddle.seed(12)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=512)
    return LlamaForCausalLM(cfg)


@pytest.fixture
def fused_kernel(monkeypatch):
    """The Pallas kernel (interpreted here) instead of the CPU's dense
    path."""
    monkeypatch.setenv("PT_PAGED_IMPL", "pallas")


@pytest.mark.parametrize("how", list(STEPPERS))
def test_tokens_through_the_block_loop_equal_a_dense_float32_recomputation(
        long_model, fused_kernel, how):
    """Twelve tokens a sequence with the lengths on both sides of a
    block's edge; a verify window's three rows read 255, 256 and 257
    keys of one sequence in one call, each row its own trip count."""
    ex, sids, prompts = _started(long_model, BLOCK_LENS, **BLOCK_KW)
    if how == "verify":
        ahead = _drive(*_started(long_model, BLOCK_LENS, **BLOCK_KW)[:2],
                       "decode", 18)
        out = _drive_verify(ex, sids, ahead, 12)
    else:
        out = _drive(ex, sids, how, 12)
    for s, p in zip(sids, prompts):
        answer = np.asarray(out[s][:12])
        assert len(answer) == 12
        np.testing.assert_array_equal(answer,
                                      _recomputed(long_model, p, answer))


def test_an_aot_warmed_engine_serves_through_the_block_loop(
        long_model, fused_kernel, tmp_path):
    """The AOT ladder's programs (every decode batch size compiled ahead,
    a prompt cut into rungs whose past page ids are padded to a bucket)
    give the dense float32 recomputation's tokens with the kernel in
    them, a long and a short request decoding side by side."""
    from paddle_tpu.inference.server import ServingEngine

    eng = ServingEngine(long_model, aot="warm", compile_cache=str(tmp_path),
                        max_seqs=2, prefill_chunk=64, **BLOCK_KW)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 256, (n,)).astype(np.int32)
               for n in (251, 40)]
    handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run()
    for h, p in zip(handles, prompts):
        answer = np.asarray(h.tokens)
        assert len(answer) == 10
        np.testing.assert_array_equal(answer,
                                      _recomputed(long_model, p, answer))


def test_a_decode_step_says_how_many_blocks_it_reads(long_model):
    """``exec.prep``'s decode span carries the kernel's work beside the
    batch: ``blocks`` = sum of cdiv(length + 1, 256) over the batch,
    ``window_blocks`` = batch x the window's blocks."""
    from paddle_tpu import obs

    ex, sids, _ = _started(long_model, BLOCK_LENS, **BLOCK_KW)
    seen = []
    for _ in range(7):
        ex.decode(sids)
        seen.append([s for s in obs.tracer().spans
                     if s.name == "exec.prep"][-1].args)
    # sequence 0 reads 251..257 keys: its second block in the seventh step
    assert [a["blocks"] for a in seen] == [4] * 6 + [5]
    assert {a["window_blocks"] for a in seen} == {6}
    assert {a["batch"] for a in seen} == {3}
