"""The paged executor's slot interface, driven by hand, vs the dense
KV-cache decoder: greedy tokens must match exactly, including staggered
admission and freeing (reference: the Predictor's
block_multi_head_attention serving loop).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.server import PagedExecutor
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import LlamaDecoder


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


def _executor(model, max_seqs=2, max_len=64):
    return PagedExecutor(model, max_seqs=max_seqs, page_size=4,
                         max_len=max_len)


def _admit(ex, prompt):
    """Prefill one prompt into a fresh slot; returns the slot id."""
    sid = ex.alloc_slot()
    ex.prefill(sid, np.asarray(prompt))
    return sid


def _step(ex):
    """One greedy decode step over every active slot."""
    return ex.decode(sorted(ex.last_token))


def _dense_tokens(model, prompt, n):
    dec = LlamaDecoder(model)
    out = dec.generate(np.asarray(prompt)[None], max_new_tokens=n)
    return list(np.asarray(out)[0])


def test_paged_engine_matches_dense_decoder(model):
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 256, (7,)).astype(np.int32)
    n = 6
    want = _dense_tokens(model, prompt, n)

    ex = _executor(model)
    sid = _admit(ex, prompt)
    got = [ex.last_token[sid]]
    for _ in range(n - 1):
        got.append(_step(ex)[sid])
    assert got == [int(t) for t in want], (got, want)


def test_paged_engine_continuous_batching(model):
    """Two sequences admitted at different times decode together and
    each still matches its dense-decoder output."""
    rng = np.random.RandomState(1)
    p1 = rng.randint(0, 256, (5,)).astype(np.int32)
    p2 = rng.randint(0, 256, (9,)).astype(np.int32)
    want1 = _dense_tokens(model, p1, 5)
    want2 = _dense_tokens(model, p2, 3)

    ex = _executor(model)
    s1 = _admit(ex, p1)
    got1 = [ex.last_token[s1]]
    got1.append(_step(ex)[s1])           # s1 decodes alone
    s2 = _admit(ex, p2)                  # s2 joins mid-flight
    got2 = [ex.last_token[s2]]
    for _ in range(2):
        out = _step(ex)                  # both decode in one batch
        got1.append(out[s1])
        got2.append(out[s2])
    out = _step(ex)
    got1.append(out[s1])
    ex.free_slot(s1)                     # s1 leaves; s2 continues
    assert got1 == [int(t) for t in want1], (got1, want1)
    assert got2 == [int(t) for t in want2], (got2, want2)
    assert s1 not in ex.last_token


def test_paged_engine_slot_reuse(model):
    """Freed pages/slots are reused by later requests."""
    rng = np.random.RandomState(2)
    ex = _executor(model, max_seqs=1, max_len=32)
    p = rng.randint(0, 256, (6,)).astype(np.int32)
    s = _admit(ex, p)
    _step(ex)
    ex.free_slot(s)
    s2 = _admit(ex, p)                   # slot comes back
    assert s2 == s
    assert _step(ex)[s2] is not None


def test_decode_n_matches_per_step(model):
    """n greedy tokens in one dispatch == n sequential decode steps
    (the device-resident feedback loop must be bit-identical)."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 256, (5,)).astype(np.int32),
               rng.randint(0, 256, (9,)).astype(np.int32)]
    n = 6

    a = _executor(model)
    sids_a = [_admit(a, p) for p in prompts]
    per_step = {s: [] for s in sids_a}
    for _ in range(n):
        out = _step(a)
        for s, t in out.items():
            per_step[s].append(t)

    b = _executor(model)
    sids_b = [_admit(b, p) for p in prompts]
    fused = b.decode_n(sids_b, n)
    for sa, sb in zip(sids_a, sids_b):
        assert fused[sb] == per_step[sa], (fused[sb], per_step[sa])
    # executor state advanced consistently: another plain step agrees
    nxt_a, nxt_b = _step(a), _step(b)
    for sa, sb in zip(sids_a, sids_b):
        assert nxt_a[sa] == nxt_b[sb]
