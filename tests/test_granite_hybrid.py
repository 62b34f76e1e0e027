"""The Mamba-2 / attention hybrid (models/granite_hybrid.py) on the normal
path: the eager model and the default ServingEngine against the plain
reference (chipbench/reference_hybrid.py, the recurrence token by token),
at a tiny size on the CPU — hidden 64, two periods of M M A M, chunk 8,
seeded weights, float32.

Tolerances.  Everything here is float32 on the CPU, where a matmul is
exact to rounding: the program and the reference differ by the ORDER of
float32 sums only (the chunked form against the token-by-token one), a
few 1e-6 of the logits' scale after 8 layers.  Logits are held to 2e-4
of their RMS; served tokens to a gap of 1e-3 RMS below the reference's
best (0 unless two logits tie to rounding).  State kept "bit for bit" is
compared with array_equal.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from chipbench import program_hybrid, reference_hybrid, weights_hybrid
from paddle_tpu.inference.server import ServingCluster, ServingEngine
from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.ops.pallas_kernels import ssm_decode

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "chipbench", "bench", "configs",
                       "tiny-hybrid.json")) as f:
    CFG = json.load(f)
SEED = 2_800_000_001
ENGINE = dict(max_seqs=4, page_size=4, max_len=64, prefill_chunk=8)
LOGIT_TOL, TOKEN_TOL = 2e-4, 1e-3


@pytest.fixture(scope="module")
def model():
    m = program_hybrid.build_model(CFG, jnp.float32)
    m.eval()
    program_hybrid.load_weights(m, CFG, SEED, jnp.float32)
    return m


@pytest.fixture(scope="module")
def reference():
    w = weights_hybrid.make(CFG, SEED, jnp.float32)
    forward = reference_hybrid.make_forward(CFG)

    def logits(ids):
        return np.asarray(reference_hybrid.logits(CFG, w, forward(w, ids)))
    return logits


def prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(
        0, CFG["vocab_size"], (n,)).astype(np.int32)


def gap_of(reference, ids, tokens):
    """How far below the reference's best each served token lies, over
    the RMS of the reference's logits (teacher-forced on what was
    served)."""
    seq = np.concatenate([ids, np.asarray(tokens[:-1], np.int32)])
    lg = reference(seq)[len(ids) - 1:]
    at = lg[np.arange(len(tokens)), tokens]
    return float((lg.max(-1) - at).max() / np.sqrt(np.square(lg).mean()))


def serve(model, prompts, new=9, **kw):
    eng = ServingEngine(model, **{**ENGINE, **kw})
    handles = [eng.submit(p, max_new_tokens=new) for p in prompts]
    eng.run()
    assert all(h.metrics()["state"] == "finished" for h in handles), \
        [h.metrics() for h in handles]
    return eng, [list(h.tokens) for h in handles]


# -- the model ---------------------------------------------------------------

def test_config_counts_and_refusals():
    cfg = gh.GraniteHybridConfig()
    assert cfg.layer_types.count("mamba") == 36
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    assert (cfg.head_dim, cfg.mamba_d_inner, cfg.mamba_conv_dim,
            cfg.mamba_in_proj_dim) == (64, 4096, 4352, 8512)
    for bad in (dict(mamba_n_groups=2), dict(position_embedding_type="rope"),
                dict(num_local_experts=4), dict(tie_word_embeddings=False),
                dict(layer_types=("mamba",) * 3)):
        with pytest.raises(NotImplementedError):
            gh.GraniteHybridConfig(**bad)


def test_eager_logits_match_the_reference(model, reference):
    """Two sequences of 21 tokens: three blocks of the chunked form."""
    ids = np.stack([prompt(21, 1), prompt(21, 2)])
    out = np.asarray(model(paddle.to_tensor(ids))._data)
    assert model.num_params() == weights_hybrid.count(CFG)
    for row, got in zip(ids, out):
        want = reference(row)
        assert np.abs(got - want).max() <= LOGIT_TOL * np.sqrt(
            np.square(want).mean())


@pytest.mark.parametrize("T", [1, 3, 8, 13])
def test_chunked_block_equals_the_token_recurrence(T):
    """ssd_block against the recurrence stepped token by token, from a
    state that is not zero."""
    rng = np.random.default_rng(T)
    nh, p, n = 4, 8, 16
    x = jnp.asarray(rng.normal(size=(T, nh, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.5, size=(T, nh)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, size=(nh,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(T, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(T, n)), jnp.float32)
    S = jnp.asarray(rng.normal(size=(nh, p, n)), jnp.float32)
    y, S_end = gh.ssd_block(x, dt, A, B, C, S)
    for t in range(T):
        S = (jnp.exp(dt[t] * A)[:, None, None] * S
             + (dt[t][:, None] * x[t])[:, :, None] * B[t][None, None, :])
        np.testing.assert_allclose(y[t], jnp.sum(S * C[t], -1), rtol=1e-4,
                                   atol=1e-4)
    np.testing.assert_allclose(S_end, S, rtol=1e-4, atol=1e-5)


def test_state_packing_round_trips():
    S = jnp.arange(2 * 8 * 16 * 16, dtype=jnp.float32).reshape(2, 8, 16, 16)
    packed = ssm_decode.pack_state(S)
    assert packed.shape == (2,) + ssm_decode.state_shape(8, 16, 16)
    assert np.array_equal(ssm_decode.unpack_state(packed, 16), S)


# -- serving through both caches ---------------------------------------------

@pytest.mark.parametrize("n", [5, 7, 8, 9, 16, 17, 23])
def test_prefill_in_chunks_then_decode(model, reference, n):
    """Prompt lengths that end before, on and after a chunk edge (chunks
    of 8; 5 and 7 take the whole-prompt path)."""
    _, (tokens,) = serve(model, [prompt(n)])
    assert gap_of(reference, prompt(n), tokens) <= TOKEN_TOL
    assert tokens == reference(np.concatenate(
        [prompt(n), tokens[:-1]]).astype(np.int32))[n - 1:].argmax(-1).tolist()


def test_a_batch_of_mixed_lengths(model, reference):
    prompts = [prompt(n, 3) for n in (3, 8, 12, 29)]
    eng, served = serve(model, prompts, new=12)
    for p, tokens in zip(prompts, served):
        assert gap_of(reference, p, tokens) <= TOKEN_TOL
    # alone or in a batch, a request is served the same tokens
    for p, tokens in zip(prompts, served):
        assert serve(model, [p], new=12)[1] == [tokens]
    ex = eng.executor
    assert ex.state_slots_used == 0 and ex.free_pages == ex.cache.num_pages
    assert ex.state_bytes == 6 * 4 * (4 * 8 * 16 * 16 + 4 * 3 * 160)
    assert ex.cache.k_pages.shape[0] == 2       # the attention layers only


def test_a_reused_slot_leaks_no_state(model):
    """Six requests through one slot: each is served what it is served on
    an engine of its own."""
    prompts = [prompt(n, 4) for n in (9, 4, 17, 8, 11, 6)]
    _, served = serve(model, prompts, max_seqs=1)
    for p, tokens in zip(prompts, served):
        assert serve(model, [p])[1] == [tokens]


def slot_rows(ex, slot):
    ssm, conv = ex.state.pools()
    return [np.asarray(p[:, slot]) for p in ssm] \
        + [np.asarray(p[:, :, slot]) for p in conv]


def test_a_reused_slot_starts_from_zero(model):
    """The rows a prompt leaves in a slot that another sequence has used
    (prefilled and decoded in) are, bit for bit, the rows it leaves in a
    slot nobody has used: the first chunk reads zeros, whatever is there."""
    fresh = ServingEngine(model, **ENGINE).executor
    s = fresh.alloc_slot()
    fresh.prepare_write(s, 0, 7)
    tok = fresh.prefill(s, prompt(7, 8))
    used = ServingEngine(model, **ENGINE).executor
    u = used.alloc_slot()
    used.prepare_write(u, 0, 8)
    used.prefill(u, prompt(8, 9))
    for _ in range(3):
        used.decode([u])
    assert any(np.abs(r).max() > 0 for r in slot_rows(used, u))
    used.free_slot(u)
    assert used.alloc_slot() == u
    used.prepare_write(u, 0, 7)
    assert used.prefill(u, prompt(7, 8)) == tok
    assert all(np.array_equal(a, b) for a, b in
               zip(slot_rows(fresh, s), slot_rows(used, u)))


def test_a_preempted_request_resumes_with_the_same_tokens(model):
    prompts = [prompt(13, 5), prompt(10, 5)]
    _, want = serve(model, prompts, new=14)
    eng = ServingEngine(model, **ENGINE)
    handles = [eng.submit(p, max_new_tokens=14) for p in prompts]
    for _ in range(6):
        eng.step()
    victim = eng.request(handles[0].rid)
    assert 0 < len(victim.generated) < 14
    eng.scheduler._preempt(victim)
    assert eng.executor.state_slots_used == 1
    eng.run()
    assert victim.preempt_count == 1
    assert [list(h.tokens) for h in handles] == want


def test_a_slot_between_two_chunks_keeps_its_state(model):
    """Slot 1 has had the first chunk of its prompt; slot 0 decodes three
    tokens meanwhile; slot 1's rows are the same bits afterwards."""
    eng = ServingEngine(model, **ENGINE)
    ex = eng.executor
    a, b = ex.alloc_slot(), ex.alloc_slot()
    ex.prepare_write(a, 0, 6)
    ex.prefill(a, prompt(6, 6))
    ex.prepare_write(b, 0, 8)
    assert ex.prefill_chunk(b, prompt(20, 6)[:8], 0, final=False) is None

    before, other = slot_rows(ex, b), slot_rows(ex, a)
    for _ in range(3):
        ex.decode([a])
    assert all(np.array_equal(x, y) for x, y in zip(before, slot_rows(ex, b)))
    assert not any(np.array_equal(x, y)
                   for x, y in zip(other, slot_rows(ex, a)))
    # and the second chunk goes on from them: the same first token as the
    # prompt prefilled in one go on another engine
    ex.prepare_write(b, 8, 12)
    ex.prefill_chunk(b, prompt(20, 6)[8:16], 8, final=False)
    ex.prepare_write(b, 16, 4)
    tok = ex.prefill_chunk(b, prompt(20, 6)[16:], 16, final=True)
    assert tok == serve(model, [prompt(20, 6)], new=1)[1][0][0]


def test_a_slots_state_is_the_references_after_the_same_tokens(model):
    """``slot_state``: what the pools hold for a request in flight, read
    back as the equations have it, against the reference's recurrence
    over the tokens the engine has taken in (two prefill chunks and five
    decode steps; the last emitted token is not taken in yet).  Float32
    both sides: the orders of summation differ, a few 1e-6 of a head's
    norm."""
    eng = ServingEngine(model, **ENGINE)
    ids = prompt(13, 11)
    h = eng.submit(ids, max_new_tokens=20)
    while len(h.tokens) < 6:
        eng.step()
    taken = np.concatenate([ids, np.asarray(h.tokens[:-1], np.int32)])
    ssm, conv = eng.executor.slot_state(eng.request(h.rid).sid)
    n = CFG["layer_types"].count("mamba")
    assert ssm.shape == (n, CFG["mamba_n_heads"], CFG["mamba_d_head"],
                         CFG["mamba_d_state"])
    assert conv.shape == (n, CFG["mamba_d_conv"] - 1,
                          eng.executor.config.mamba_conv_dim)
    w = weights_hybrid.make(CFG, SEED, jnp.float32)
    scorer = reference_hybrid.Scorer(CFG, rows=4, bucket=16)
    want = np.asarray(scorer.states(w, taken))
    norms = np.sqrt(np.square(want).sum((-1, -2)))
    off = np.sqrt(np.square(np.asarray(ssm) - want).sum((-1, -2)))
    assert (off <= 1e-5 * norms).all(), (off / norms).max()
    # the padding after the last token changes nothing, nor does stopping
    # after the first recurrent layer
    assert np.array_equal(np.asarray(scorer.states(w, taken, depth=1)),
                          want[:1])
    unpadded = reference_hybrid.make_forward(CFG)(w, taken,
                                                  keep=len(taken) - 1)[1]
    np.testing.assert_allclose(unpadded, want, rtol=1e-6, atol=1e-9)


# -- decode attention: the paged-decode entry over the folded pool ---------------

#: three requests that read across pages of 4 and across the kernel's
#: 256-key block while they decode, and one that leaves after three steps
LONG_LENS, LEAVES, LONG_STEPS = (3, 250, 9, 254), 2, 9


def fly(model, impl):
    """The executor driven slot by slot under ``PT_PAGED_IMPL=impl`` (the
    dense path the CPU takes by itself; the fused kernel, which the chip
    takes, in the interpreter): four prompts prefilled whole, nine decode
    steps, slot 2 freed after the third so that a dead slot with a stale
    table sits inside the batch.  Then the decode program's own function
    once more, eagerly and with nothing donated, for the next step's
    LOGITS.  Returns (prompts, tokens, logits, ``exec.prep`` args) by
    slot."""
    from paddle_tpu import obs
    from paddle_tpu.inference.server.hybrid_executor import HybridExecutor

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PT_PAGED_IMPL", impl)
        ex = HybridExecutor(model, max_seqs=4, page_size=4, max_len=288,
                            dtype=jnp.float32)
        prompts, tokens, preps = {}, {}, []
        for n in LONG_LENS:
            sid = ex.alloc_slot()
            prompts[sid] = prompt(n, 21)
            tokens[sid] = [ex.prefill(sid, prompts[sid])]
        live = sorted(tokens)
        for step in range(LONG_STEPS):
            if step == 3:
                ex.free_slot(LEAVES)
                live.remove(LEAVES)
            reads = ex.cache.lengths[live] + 1
            for sid, tok in ex.decode(live).items():
                tokens[sid].append(tok)
            preps.append((reads, [s for s in obs.tracer().spans
                                  if s.name == "exec.prep"][-1].args))
        cache, seen = ex.cache, []
        mp.setattr(gh, "head", lambda *a, head=gh.head:
                   seen.append(head(*a)) or seen[-1])
        cache.reserve(live, extra_tokens=1)
        ids, positions = np.zeros((2, cache.max_seqs), np.int32)
        alive = np.zeros((cache.max_seqs,), bool)
        ids[live] = [ex.last_token[s] for s in live]
        positions[live] = cache.lengths[live]
        alive[live] = True
        ex._decode_fwd(ex.params, ex.tops, ids, positions, alive,
                       *cache.pools(), np.maximum(cache.page_table, 0),
                       *ex.state.pools())
    return prompts, tokens, {s: np.asarray(seen[0])[s] for s in live}, preps


@pytest.fixture(scope="module")
def flight(model):
    """``flight(impl)``: :func:`fly`, once a path for the module."""
    flown = {}

    def of(impl):
        if impl not in flown:
            flown[impl] = fly(model, impl)
        return flown[impl]
    return of


@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_decode_over_the_folded_pool_serves_the_models_tokens(
        model, reference, flight, impl):
    """Every token of every slot is the reference's greedy choice after
    the same tokens (the 250- and 254-token requests read keys 251..263:
    across pages and the first 256-key block), the short one's also the
    eager model's; the slot that left was served right while it lived.
    ``attention_multiplier`` is 1/16 here against 1/4 = 1/sqrt(head_dim)
    (and 1/sqrt(32) of the folded row): a scale that is not the
    configuration's moves every logit."""
    prompts, tokens, _, _ = flight(impl)
    assert CFG["attention_multiplier"] not in (16 ** -0.5, 32 ** -0.5)
    assert [len(tokens[s]) for s in sorted(tokens)] == [10, 10, 4, 10]
    for sid, ids in prompts.items():
        seq = np.concatenate([ids, tokens[sid][:-1]]).astype(np.int32)
        assert tokens[sid] == reference(seq)[len(ids) - 1:] \
            .argmax(-1).tolist()
        assert gap_of(reference, ids, tokens[sid]) <= TOKEN_TOL
    seq = np.concatenate([prompts[0], tokens[0][:-1]]).astype(np.int32)
    eager = np.asarray(model(paddle.to_tensor(seq[None]))._data)[0]
    assert tokens[0] == eager[len(prompts[0]) - 1:].argmax(-1).tolist()


@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_decode_over_the_folded_pool_computes_the_models_logits(
        model, reference, flight, impl):
    """The tenth step's logits of the three live slots (263 and 259 keys
    read, and 12) against the reference's after the same tokens, and the
    short one's against the eager model's."""
    prompts, tokens, logits, _ = flight(impl)
    assert sorted(logits) == [0, 1, 3]
    for sid, got in logits.items():
        seq = np.concatenate([prompts[sid], tokens[sid]]).astype(np.int32)
        want = reference(seq)[-1]
        assert np.abs(got - want).max() <= LOGIT_TOL * np.sqrt(
            np.square(want).mean())
    seq = np.concatenate([prompts[0], tokens[0]]).astype(np.int32)
    want = np.asarray(model(paddle.to_tensor(seq[None]))._data)[0, -1]
    assert np.abs(logits[0] - want).max() <= LOGIT_TOL * np.sqrt(
        np.square(want).mean())


def test_a_decode_step_says_how_many_blocks_it_reads(flight):
    """``exec.prep``'s decode span carries the kernel's work beside the
    batch: ``blocks`` = both attention layers x the sum of cdiv(keys
    read, 256) over the batch, ``window_blocks`` = layers x batch x the
    two blocks of a 288-token window."""
    _, _, _, preps = flight("dense")
    assert [a["batch"] for _, a in preps] == [4] * 3 + [3] * 6
    for reads, args in preps:
        assert args["blocks"] == 2 * int((-(-reads // 256)).sum())
        assert args["window_blocks"] == 2 * len(reads) * 2
    # the 254-token request reads its 257th key in the third step, the
    # 250-token one in the seventh
    assert [a["blocks"] for _, a in preps] == \
        [8] * 2 + [10] + [8] * 3 + [10] * 3


def window_gather_attention(q, k_flat, v_flat, pool_shape, layer, lengths,
                            tables, fold):
    """The decode attention this executor had before it called the fused
    kernel, kept as the reference: every sequence's whole window gathered
    dense in the pool's dtype, float32 accumulation, each query row laid
    over its folded KV row and zero outside its own head's lanes."""
    import jax

    from paddle_tpu.inference.paged import _rows

    S, nh, D = q.shape
    KVf, ps, W = pool_shape[1], pool_shape[3], pool_shape[4]
    g = nh // (KVf * fold)
    T = tables.shape[1] * ps
    rows = _rows(pool_shape, layer, tables)               # [S, KVf, pps]
    kc = k_flat[rows].reshape(S, KVf, T, W)
    vc = v_flat[rows].reshape(S, KVf, T, W)
    own = jnp.eye(fold, dtype=q.dtype)[None, None, :, None, :, None]
    qw = (q.reshape(S, KVf, fold, g, 1, D) * own) \
        .reshape(S, KVf, fold * g, W).astype(kc.dtype)
    s = jnp.einsum("skxl,sktl->skxt", qw, kc,
                   preferred_element_type=jnp.float32)
    seen = jnp.arange(T)[None, None, None, :] < lengths[:, None, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    o = jnp.einsum("skxt,sktl->skxl", p.astype(vc.dtype), vc,
                   preferred_element_type=jnp.float32)
    o = o.reshape(S, KVf, fold, g, fold, D) * own.astype(jnp.float32)
    return o.sum(axis=4).reshape(S, nh * D)


@pytest.mark.parametrize("impl,dtype,tol", [
    ("dense", jnp.float32, 1e-5), ("pallas", jnp.float32, 1e-5),
    ("dense", jnp.bfloat16, 2e-2), ("pallas", jnp.bfloat16, 2e-2)])
def test_the_widened_query_call_equals_the_window_gather(monkeypatch, impl,
                                                         dtype, tol):
    """``_folded_attention`` (the paged-decode entry with ``fold`` times
    the query rows a folded KV row, scale 1) against the gather it
    replaced, on random pools at the model's head size: 3 layers x 2
    folded rows of two 64-wide heads, pages of 16, lengths of one key, a
    page's edge, a block's edge and beyond, and a dead row (length 0),
    which is not compared — the kernel gives it zeros.  In bf16 the two
    round the probabilities alike and differ by the order of the sums."""
    import jax

    from paddle_tpu.inference.paged import _flat
    from paddle_tpu.inference.server import hybrid_executor as hx

    monkeypatch.setenv("PT_PAGED_IMPL", impl)
    shape, fold, nh, D = (3, 2, 128, 16, 128), 2, 8, 64
    lengths = np.array([1, 16, 0, 256, 257, 300], np.int32)
    S, pps = len(lengths), 20
    kk, kv, kq, kt = jax.random.split(jax.random.PRNGKey(35), 4)
    k_pages = jax.random.normal(kk, shape, jnp.float32).astype(dtype)
    v_pages = jax.random.normal(kv, shape, jnp.float32).astype(dtype)
    q = (jax.random.normal(kq, (S, nh, D), jnp.float32) * 0.2).astype(dtype)
    tables = np.asarray(jax.random.permutation(kt, shape[2]))[:S * pps] \
        .reshape(S, pps).astype(np.int32)
    got = np.asarray(hx._folded_attention(
        q, k_pages, v_pages, 1, lengths, tables, fold), np.float32)
    want = np.asarray(window_gather_attention(
        q, _flat(k_pages), _flat(v_pages), shape, 1, lengths, tables, fold))
    assert got.shape == want.shape == (S, nh * D)
    live = lengths > 0
    assert np.abs(got[live] - want[live]).max() <= tol
    assert np.isfinite(got).all()
    if impl == "pallas":
        assert not got[~live].any()


# -- what is held, decided at build --------------------------------------------

def fresh_model():
    m = program_hybrid.build_model(CFG, jnp.float32)
    m.eval()
    program_hybrid.load_weights(m, CFG, SEED, jnp.float32)
    return m


def test_no_weight_is_drawn_for_a_model_that_will_be_loaded():
    m = gh.GraniteHybridForCausalLM(
        gh.GraniteHybridConfig.tiny(), init_weights=False)
    assert all(not np.asarray(p._data).any() for p in m.parameters())
    drawn = gh.GraniteHybridForCausalLM(gh.GraniteHybridConfig.tiny())
    assert np.asarray(drawn.model.embed_tokens.weight._data).any()


def test_both_caches_are_allocated_at_build(model):
    ex = ServingEngine(model, **ENGINE).executor
    ssm, conv = ex.state.pools()
    assert sum(p.nbytes for p in ssm + conv) == ex.state_bytes > 0
    assert ex.took_over_weights is False        # the CPU reports nothing
    assert model.model.layers[0].mamba.in_proj.weight._data is not None


def test_pools_the_device_cannot_hold_are_refused_at_build(model,
                                                           monkeypatch):
    from paddle_tpu.inference.server import hybrid_executor as hx

    monkeypatch.setattr(hx, "_free_device_bytes", lambda: 1 << 10)
    monkeypatch.setattr(hx, "_HEADROOM", 0)
    with pytest.raises(ValueError, match="do not fit"):
        ServingEngine(model, **ENGINE)


def test_the_recurrent_layers_are_taken_over_where_two_copies_do_not_fit(
        model, monkeypatch):
    """A device that holds the pools but not a second copy of the
    recurrent layers: each run's eager arrays are deleted as it is
    stacked, the engine serves the same tokens, the model says what
    happened, and the aliased leaves (attention, embedding) live on."""
    from paddle_tpu.inference.server import hybrid_executor as hx

    prompts = [prompt(11, 21), prompt(5, 22)]
    _, want = serve(model, prompts, new=6)
    whole = ServingEngine(model, **ENGINE).executor
    pools = whole.state_bytes + whole.cache.k_pages.nbytes * 2
    mine = fresh_model()
    monkeypatch.setattr(hx, "_HEADROOM", 0)
    monkeypatch.setattr(hx, "_free_device_bytes", lambda: pools + 1)
    eng, got = serve(mine, prompts, new=6)
    assert got == want
    assert eng.executor.took_over_weights is True
    layers = mine.model.layers
    kinds = CFG["layer_types"]
    assert layers[kinds.index("mamba")].mamba.in_proj.weight \
        ._data.is_deleted()
    assert not layers[kinds.index("attention")].self_attn.q_proj.weight \
        ._data.is_deleted()
    assert not mine.model.embed_tokens.weight._data.is_deleted()
    with pytest.raises(RuntimeError, match="handed over"):
        mine(paddle.to_tensor(prompts[0][None]))
    with pytest.raises(ValueError, match="handed over"):
        ServingEngine(mine, **ENGINE)


# -- the decode kernel -------------------------------------------------------

@pytest.mark.parametrize("heads,live", [(8, [1, 0, 1, 1, 0]),
                                        (32, [0, 1, 1])])
def test_pallas_kernel_in_interpret_mode(heads, live):
    """The compiled kernel's layout (P 64, N 128) through the Pallas
    interpreter against the jax.numpy form: float32 elementwise, so equal
    to a few ulps; a slot that is not live keeps its bits."""
    rng = np.random.default_rng(heads)
    L, S, P, N = 3, len(live), 64, 128
    shape = ssm_decode.state_shape(heads, P, N)
    assert shape == (heads // 2, 128, 128)
    assert ssm_decode.supported(shape, True)
    assert not ssm_decode.supported(shape, False)
    pool = jnp.asarray(rng.normal(size=(L, S) + shape), jnp.float32)
    decay = ssm_decode.head_rows(jnp.asarray(
        rng.uniform(0.1, 1, size=(S, heads)), jnp.float32), P, shape[::2])
    xdt = jnp.asarray(rng.normal(size=(S,) + shape[::2]), jnp.float32)
    B = jnp.asarray(rng.normal(size=(S, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(S, N)), jnp.float32)
    live = jnp.asarray(live, bool)
    y0, p0 = ssm_decode.ssm_decode_reference(pool, jnp.int32(1), decay, xdt,
                                             B, C, live)
    y1, p1 = ssm_decode._ssm_decode_call(pool, jnp.int32(1), decay, xdt, B,
                                         C, live, interpret=True)
    on = np.asarray(live)
    np.testing.assert_allclose(p1, p0, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y1)[on], np.asarray(y0)[on],
                               rtol=1e-5, atol=1e-4)
    assert np.array_equal(np.asarray(p1[1])[~on], np.asarray(pool[1])[~on])
    assert np.array_equal(np.asarray(p1)[[0, 2]], np.asarray(pool)[[0, 2]])


def test_the_update_is_the_textbook_recurrence():
    rng = np.random.default_rng(9)
    S, nh, P, N = 3, 8, 16, 16
    shape = ssm_decode.state_shape(nh, P, N)
    state = jnp.asarray(rng.normal(size=(S, nh, P, N)), jnp.float32)
    decay = jnp.asarray(rng.uniform(0.1, 1, size=(S, nh)), jnp.float32)
    xdt = jnp.asarray(rng.normal(size=(S, nh, P)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(S, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(S, N)), jnp.float32)
    y, pool = ssm_decode.ssm_decode(
        ssm_decode.pack_state(state)[None], jnp.int32(0),
        ssm_decode.head_rows(decay, P, shape[::2]),
        xdt.reshape(S, *shape[::2]), B, C, jnp.ones((S,), bool))
    want = decay[:, :, None, None] * state \
        + xdt[:, :, :, None] * B[:, None, None, :]
    np.testing.assert_allclose(ssm_decode.unpack_state(pool[0], P), want,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        y.reshape(S, nh, P), jnp.einsum("shpn,sn->shp", want, C),
        rtol=1e-5, atol=1e-5)


# -- what is refused at build ------------------------------------------------

@pytest.mark.parametrize("feature,kwargs", [
    ("prefix cache", dict(prefix_cache=True)),
    ("speculative decoding", dict(spec_decode="ngram")),
    ("async execution", dict(async_exec=True)),
    ("decode_n", dict(decode_n_steps=(4,))),
    ("sequence-parallel prefill", dict(sp_prefill=True)),
    ("int8 quantisation", dict(quant="int8")),
    ("AOT warm-up", dict(aot="warm")),
    ("write-ahead log", dict(wal="/nonexistent/journal")),
])
def test_refused_at_build(model, feature, kwargs):
    with pytest.raises(NotImplementedError, match=feature):
        ServingEngine(model, **ENGINE, **kwargs)


@pytest.mark.parametrize("var,feature", [
    ("PT_PREFIX_CACHE", "prefix cache"), ("PT_ASYNC_EXEC", "async"),
    ("PT_SP_PREFILL", "sequence-parallel"), ("PT_WAL", "write-ahead")])
def test_refused_when_the_environment_asks(model, monkeypatch, var, feature):
    monkeypatch.setenv(var, "on")
    with pytest.raises(NotImplementedError, match=feature):
        ServingEngine(model, **ENGINE)


def test_cluster_hand_off_is_refused(model):
    with pytest.raises(NotImplementedError, match="cluster hand-off"):
        ServingCluster(model, n_replicas=2)


def test_a_llama_model_still_gets_the_paged_executor():
    from paddle_tpu.inference.server.executor import PagedExecutor
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    eng = ServingEngine(LlamaForCausalLM(LlamaConfig.tiny()), **ENGINE)
    assert type(eng.executor) is PagedExecutor
