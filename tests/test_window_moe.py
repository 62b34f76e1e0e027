"""The window + full attention model with routed experts at toy widths on
the CPU: the eager model and the engine (prefill in chunks, then decode
through both layer groups of the cache) against the benchmark's plain
reference — logits and held K/V rows, not tokens — for lengths below, at
and far beyond the window; the cache's two groups alone (what is released
and when, what a window group's pool is bounded by, admission); the share
of the experts; the engine's refusals; the spans and counters."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from chipbench import (flops_window_moe, program_window_moe,
                       reference_window_moe, weights_window_moe)
from paddle_tpu import obs
from paddle_tpu.inference.paged import LayerGroup, PagedKVCache
from paddle_tpu.inference.server import ServingCluster, ServingEngine
from paddle_tpu.inference.server.window_executor import WindowExecutor
from paddle_tpu.models import mla_moe, moe
from paddle_tpu.models import window_moe as wm
from paddle_tpu.models.window_moe import (WindowMoEConfig,
                                          WindowMoEForCausalLM)

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "chipbench", "bench", "configs",
                       "tiny-window-moe.json")) as f:
    CFG = json.load(f)
SEED = 3_400_000_001
WINDOW = CFG["sliding_window"]                  # 8 keys
ENGINE = dict(max_seqs=4, page_size=4, max_len=64, prefill_chunk=8)
LOGIT_TOL, ROW_TOL, TOKEN_TOL = 2e-4, 2e-5, 1e-3
SLIDING0, FULL0, LAST = 0, 3, 5                 # s s | s f s s


def fresh_model():
    m = program_window_moe.build_model(CFG, jnp.float32)
    m.eval()
    program_window_moe.load_weights(m, CFG, SEED, jnp.float32)
    return m


@pytest.fixture(scope="module")
def model():
    return fresh_model()


@pytest.fixture(scope="module")
def reference():
    """``ids -> (logits [len, V], {layer: K and V [len, 2, kv, D]})`` by
    the benchmark's plain reference."""
    scorer = reference_window_moe.Scorer(CFG, rows=1, bucket=8)
    top = weights_window_moe.top(CFG, SEED, jnp.float32)

    def run(ids):
        hidden, rows = scorer.forward(
            top, lambda n: weights_window_moe.layer(CFG, SEED, n,
                                                    jnp.float32),
            [(ids, 0)], keep_rows=[0], keep_layers=range(6))
        lg = reference_window_moe.mm(hidden[0][:len(ids)], top["head"])
        return np.asarray(lg), rows[0]
    return run


def prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(
        0, CFG["vocab_size"], (n,)).astype(np.int32)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def gap_of(reference, ids, tokens):
    seq = np.concatenate([ids, np.asarray(tokens[:-1], np.int32)])
    lg = reference(seq)[0][len(ids) - 1:]
    at = lg[np.arange(len(tokens)), tokens]
    return float((lg.max(-1) - at).max() / np.sqrt(np.square(lg).mean()))


def serve(model, prompts, new=9, **kw):
    eng = ServingEngine(model, **{**ENGINE, **kw})
    handles = [eng.submit(p, max_new_tokens=new) for p in prompts]
    eng.run()
    assert all(h.metrics()["state"] == "finished" for h in handles), \
        [h.metrics() for h in handles]
    return eng, [list(h.tokens) for h in handles]


def layer_params(model, n):
    p = f"model.layers.{n}."
    return {k[len(p):]: v._data for k, v in model.state_dict().items()
            if k.startswith(p)}


# -- the configuration ---------------------------------------------------------

def test_config_kinds_and_refusals():
    cfg = WindowMoEConfig()                     # the published model
    assert cfg.layer_types == ((wm.SLIDING,) * 3 + (wm.FULL,)) * 8
    assert [cfg.window_of(n) for n in (0, 3)] == [2048, None]
    assert cfg.is_dense(1) and not cfg.is_dense(2)
    assert abs(cfg.embed_scale - 2048 ** 0.5) < 1e-12
    assert hash(cfg) == hash(WindowMoEConfig())     # a jit attribute
    # a cut in depth keeps the published list and runs its first layers
    cut = WindowMoEConfig(num_hidden_layers=6, layer_types=list(
        cfg.layer_types))
    assert cut.layer_types == (wm.SLIDING,) * 3 + (wm.FULL,) \
        + (wm.SLIDING,) * 2 == WindowMoEConfig.tiny().layer_types
    for what, kw in [
            ("score_func", dict(score_func="softmax")),
            ("group-limited", dict(n_group=8, topk_group=4)),
            ("rope_scaling", dict(rope_scaling={"type": "linear",
                                                "factor": 2.0})),
            ("route_norm", dict(route_norm=False)),
            ("num_shared_experts", dict(num_shared_experts=2)),
            ("tied output head", dict(tie_word_embeddings=True)),
            ("no expert layer", dict(num_dense_layers=32)),
            ("layer type", dict(layer_types=["chunked_attention"] * 32)),
            ("fewer layer_types", dict(layer_types=[wm.FULL] * 3))]:
        with pytest.raises(NotImplementedError, match=what):
            WindowMoEConfig(**kw)
    with pytest.raises(ValueError, match="held_experts"):
        WindowMoEForCausalLM(WindowMoEConfig.tiny(), held_experts=[3, 3],
                             init_weights=False)


def test_the_published_model_has_its_parameters_by_hand():
    """ISSUE 34's arithmetic from the model's own shapes, with no array
    made: attention 27,263,232, an expert 6,291,456, an expert layer
    839,131,520, a dense layer 65,020,160, embedding and head 409,993,216
    each; 4,306,554,880 in all at six layers = 8.61 GB in bf16."""
    cfg = WindowMoEConfig(num_hidden_layers=6)

    def count(dense):
        return sum(int(np.prod(shape)) for shape, _ in
                   wm._layer_shapes(cfg, dense, 128).values())

    attention = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    assert attention == 27_263_232
    assert count(True) == attention + 4 * 2048 + 3 * 2048 * 6144 \
        == 65_020_160
    assert count(False) == attention + 4 * 2048 + 2048 * 128 + 128 \
        + 129 * 6_291_456 == 839_131_520
    total = 2 * count(True) + 4 * count(False) + 2 * 409_993_216 + 2048
    assert total == 4_306_554_880
    published = dict(vars(cfg), layer_types=list(cfg.layer_types))
    assert flops_window_moe.params(published) == total \
        == weights_window_moe.count(published)
    assert round(2 * total / 1e9, 2) == 8.61


def test_the_mask_is_the_models_window():
    i, j = np.arange(12)[:, None], np.arange(12)[None, :]
    seen = np.asarray(wm.visible(i, j, 4))
    assert seen[7].tolist() == [False] * 4 + [True] * 4 + [False] * 4
    assert seen.sum(1).tolist() == [1, 2, 3] + [4] * 9   # itself included
    assert np.array_equal(np.asarray(wm.visible(i, j, None)), j <= i)
    assert np.array_equal(
        np.asarray(reference_window_moe.visible(i, j, 4)), seen)


def test_rope_on_sliding_layers_only(model):
    """A full layer's keys do not depend on the position; a sliding
    layer's are the same keys rotated by it."""
    cfg, x = model.config, jax.random.normal(jax.random.PRNGKey(0), (5, 64))
    lp = layer_params(model, FULL0)
    at, later = jnp.arange(5), jnp.arange(5) + 17
    _, k0, v0, _ = wm.attention_inputs(cfg, lp, x, at, False)
    _, k1, v1, _ = wm.attention_inputs(cfg, lp, x, later, False)
    assert np.array_equal(k0, k1) and np.array_equal(v0, v1)
    _, ks, _, _ = wm.attention_inputs(cfg, lp, x, later, True)
    cos, sin = wm.rope_tables(cfg, later)
    np.testing.assert_allclose(
        ks, mla_moe.rope(k0, cos[:, None], sin[:, None]), atol=1e-6)
    assert rel(ks, k0) > 0.1
    # q and k are normed over each head before the rope: unit RMS
    np.testing.assert_allclose(np.sqrt(np.square(np.asarray(k0)).mean(-1)),
                               1.0, rtol=1e-3)


# -- the eager model and the engine against the reference -----------------------

def test_eager_model_agrees_with_the_reference(model, reference):
    ids = prompt(29, 1)                     # far beyond the window of 8
    want, _ = reference(ids)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
    assert np.abs(got - want).max() / np.sqrt(np.square(want).mean()) \
        < LOGIT_TOL


#: prompt lengths below, at and far beyond the window of 8 keys, with the
#: chunk's edges on the window's edge (chunks of 8) and off it (of 6, 5)
LENGTHS = [(3, 8), (8, 8), (9, 8), (16, 8), (21, 8), (40, 8), (37, 6),
           (23, 5), (50, None)]


@pytest.mark.parametrize("n,chunk", LENGTHS)
def test_engine_prefill_and_decode_agree_with_the_reference(
        model, reference, n, chunk):
    """Chunked prefill, then nine decode steps, through both layer groups:
    every served token is the reference's own argmax up to a logit gap
    of rounding, and what the engine holds afterwards in a sliding, the
    full and the last layer is the reference's K and V over exactly the
    span the next query sees."""
    ids = prompt(n, 2)
    eng = ServingEngine(model, **{**ENGINE, "prefill_chunk": chunk})
    h = eng.submit(ids, max_new_tokens=9)
    while len(h.tokens) < 8:
        eng.step()
    sid = eng.request(h.rid).sid
    taken = np.concatenate([ids, np.asarray(h.tokens[:-1], np.int32)])
    _, want = reference(taken)
    ex, ps = eng.executor, ENGINE["page_size"]
    for layer in (SLIDING0, FULL0, LAST):
        base, k, v = ex.slot_kv(sid, layer)
        seen = 0 if layer == FULL0 else max(0, len(taken) + 1 - WINDOW)
        assert base == seen // ps * ps, (layer, base)
        got = np.stack([np.asarray(k), np.asarray(v)], axis=1)
        assert got.shape[0] == len(taken) - base
        assert rel(got, want[layer][base:]) < ROW_TOL, layer
    eng.run()
    assert gap_of(reference, ids, list(h.tokens)) < TOKEN_TOL
    assert ex.cache.free_pages == ex.cache.num_pages
    assert all(len(g._free) == g.num_pages for g in ex.cache.groups)


def test_a_full_decode_batch_of_mixed_lengths(model, reference):
    """Four sequences decode in one batch, two inside the window and two
    far beyond it: the kernel's trip counts differ by sequence and by
    layer."""
    prompts = [prompt(n, 3) for n in (5, 44, 7, 30)]
    eng, toks = serve(model, prompts, new=12)
    for p, t in zip(prompts, toks):
        assert gap_of(reference, p, t) < TOKEN_TOL
    ex = eng.executor
    assert ex.pages_released[1] > 0 and ex.pages_released[0] == 0
    assert ex.expert_steps > 0 and ex.expert_rows.sum() > 0


def test_the_kernel_path_agrees_in_interpret_mode(model, reference,
                                                  monkeypatch):
    """The decode program with the fused kernel (interpreted here) in
    place of the dense gather: the same tokens' logits."""
    monkeypatch.setenv("PT_PAGED_IMPL", "pallas")
    ids = prompt(26, 4)
    _, (toks,) = serve(model, [ids], new=6)
    assert gap_of(reference, ids, toks) < TOKEN_TOL


def test_a_preempted_request_recomputes_through_the_window(model, reference):
    """A full-group pool too small for both requests: one is preempted and
    resumed by recompute; both answers are the reference's."""
    prompts = [prompt(30, 5), prompt(28, 6)]
    eng, toks = serve(model, prompts, new=10, num_pages=18)
    assert eng.stats()["preemptions"] >= 1
    for p, t in zip(prompts, toks):
        assert gap_of(reference, p, t) < TOKEN_TOL


# -- the cache's layer groups alone ------------------------------------------------

def grouped(window=8, chunk=8, max_seqs=3, ps=4, full_pages=48,
            window_pages=None):
    row = -(-(window + chunk) // ps) + 1
    return PagedKVCache(
        n_layers=3, n_kv_heads=1, head_dim=4, num_pages=full_pages,
        page_size=ps, max_seqs=max_seqs, dtype=jnp.float32,
        groups=[LayerGroup(1, full_pages, None, 16),
                LayerGroup(2, max_seqs * row if window_pages is None
                           else window_pages, window, row)])


def span(cache, T, value=1.0):
    return [jnp.full((g.n_layers, 1, T, 4), value, jnp.float32)
            for g in cache.groups]


def test_pages_are_released_exactly_when_wholly_behind_the_window():
    """Window 8, pages of 4.  After a write the next query at position
    ``length`` sees keys ``>= length - 7``; a page goes when ALL its
    tokens lie before that, never earlier."""
    cache = grouped()
    full, win = cache.groups
    s = cache.allocate()
    obs.reset()
    seen = []
    for start, T in [(0, 8), (8, 3), (11, 1), (12, 8), (20, 5)]:
        k = span(cache, T)
        cache.write_at(s, k, k, start)
        length = start + T
        first_seen = max(0, length + 1 - 8)
        assert win.base[s] == first_seen // 4 * 4
        held = int((win.page_table[s] >= 0).sum())
        assert held == -(-(length - win.base[s]) // 4)
        assert int((full.page_table[s] >= 0).sum()) == -(-length // 4)
        seen.append((int(win.base[s]), held))
    assert seen == [(0, 2), (4, 2), (4, 2), (12, 2), (16, 3)]
    assert win.released == 4 and full.released == 0
    released = [s_.args for s_ in obs.tracer().spans
                if s_.name == "kv.release"]
    assert [r["pages"] for r in released] == [1, 2, 1]
    assert all(r["group"] == 1 for r in released)
    # a decode step: reserve, the length moves, release
    for _ in range(9):
        cache.reserve([s], extra_tokens=1)
        cache.lengths[s] += 1
        cache.release([s])
    assert cache.lengths[s] == 34 and win.base[s] == 24
    cache.free(s)
    assert len(win._free) == win.num_pages and win.base[s] == 0
    assert len(full._free) == full.num_pages


def test_a_window_pool_is_bounded_over_a_long_decode():
    """Three sequences prefill 8-token chunks and decode 40 tokens each:
    a sequence never holds more than (window + chunk) / page + 1 pages of
    the window group, and the pool sized for exactly that never runs
    out."""
    cache = grouped(max_seqs=3)
    win = cache.groups[1]
    bound = -(-(8 + 8) // 4) + 1
    assert win.max_pages_per_seq == bound and win.num_pages == 3 * bound
    sids = [cache.allocate() for _ in range(3)]
    for start in (0, 8, 16):
        for s in sids:
            k = span(cache, 8)
            cache._ensure_capacity(s, start + 8)        # prepare_write
            assert (win.page_table[s] >= 0).sum() <= bound
            cache.write_at(s, k, k, start)
    for _ in range(40):
        cache.reserve(sids, extra_tokens=1)
        assert all((win.page_table[s] >= 0).sum() <= bound for s in sids)
        cache.lengths[sids] += 1
        cache.release(sids)
    # 64 tokens each: the next query sees keys 57.., the row begins at 56
    assert win.released == 3 * 14 and (win.base[sids] == 56).all()
    assert (win.page_table[sids] >= 0).sum(1).max() <= 3
    assert int((cache.groups[0].page_table[sids] >= 0).sum()) == 3 * 16


def test_a_released_page_is_never_a_visible_one():
    """The rows a window group still holds after a long run are the last
    ``window - 1`` tokens written and more, bit for bit."""
    cache = grouped()
    s = cache.allocate()
    for start in range(0, 40, 8):
        k = [jnp.broadcast_to(
            (start + jnp.arange(8, dtype=jnp.float32))[None, None, :, None],
            (g.n_layers, 1, 8, 4)) for g in cache.groups]
        cache.write_at(s, k, k, start)
    win = cache.groups[1]
    k, _ = cache.gather_dense(s, group=1)
    held = np.asarray(k[0, 0, :40 - win.base[s], 0])
    assert win.base[s] == 32
    assert held.tolist() == list(range(32, 40))
    every, _ = cache.gather_dense(s, group=0)
    assert np.asarray(every[0, 0, :40, 0]).tolist() == list(range(40))


def test_one_group_is_the_cache_it_always_was():
    """A cache built without groups, and one built with the one full
    group spelled out: the same tables, free lists and pools after the
    same calls (every caller of the parent's cache)."""
    def play(cache):
        rng = np.random.default_rng(0)
        a, b = cache.allocate(), cache.allocate()
        for s, (start, T) in [(a, (0, 7)), (b, (0, 3)), (a, (7, 6)),
                              (b, (3, 9))]:
            k = jnp.asarray(rng.normal(size=(2, 1, T, 4)), jnp.float32)
            cache.write_at(s, k, -k, start)
        cache.reserve([a, b], extra_tokens=2)
        cache.trim(a)
        cache.free(b)
        return (cache.page_table.copy(), list(cache._free),
                cache.page_refs.copy(), np.asarray(cache.k_pages),
                np.asarray(cache.v_pages), cache.lengths.copy(),
                cache.free_pages, cache.free_slots)

    plain = play(PagedKVCache(2, 1, 4, 12, page_size=4, max_seqs=3,
                              dtype=jnp.float32))
    spelled = play(PagedKVCache(2, 1, 4, 12, page_size=4, max_seqs=3,
                                dtype=jnp.float32,
                                groups=[LayerGroup(2, 12)]))
    for x, y in zip(plain, spelled):
        assert np.array_equal(x, y)
    cache = PagedKVCache(2, 1, 4, 12, page_size=4, max_seqs=3)
    assert len(cache.groups) == 1 and cache.groups[0].window is None
    assert not isinstance(cache.pools()[0], list)
    assert cache.release([0]) == 0              # nothing has a window


def test_what_a_released_page_forbids_is_refused_by_name():
    cache = grouped()
    s = cache.allocate()
    for what, call in [
            ("attach", lambda: cache.attach(s, [0], 4)),
            ("trim", lambda: cache.trim(s)),
            ("append", lambda: cache.append([s], None, None)),
            ("attend", lambda: cache.attend(0, None, [s])),
            ("write_sharded", lambda: cache.write_sharded(s, None, None, 0,
                                                          2)),
            ("gather_shards", lambda: cache.gather_shards(s))]:
        with pytest.raises(NotImplementedError, match=what):
            call()
    for kw in (dict(quant="int8"), dict(latent=True)):
        with pytest.raises(NotImplementedError, match="layer groups"):
            PagedKVCache(2, 1, 4, 8, groups=[LayerGroup(1, 8),
                                             LayerGroup(1, 8, 4, 4)], **kw)
    with pytest.raises(ValueError, match="add up"):
        PagedKVCache(3, 1, 4, 8, groups=[LayerGroup(1, 8),
                                         LayerGroup(1, 8, 4, 4)])


def test_a_reservation_takes_both_groups_or_neither():
    cache = grouped(full_pages=3, max_seqs=2)
    full, win = cache.groups
    s = cache.allocate()
    with pytest.raises(RuntimeError, match="KV page pool exhausted"):
        cache._ensure_capacity(s, 16)       # 4 full pages of 3
    assert len(full._free) == 3 and len(win._free) == win.num_pages
    assert (win.page_table[s] < 0).all()
    cache = grouped(window_pages=2, max_seqs=2)
    full, win = cache.groups
    s = cache.allocate()
    with pytest.raises(RuntimeError, match="KV page pool exhausted"):
        cache.reserve([s], extra_tokens=12)     # 3 window pages of 2
    assert len(full._free) == full.num_pages and len(win._free) == 2


# -- admission ----------------------------------------------------------------------

def test_admission_holds_for_both_pools(model):
    """``pages_for`` / ``free_pages`` speak of the full group; the window
    group's bounded span is a seat (``free_slots``).  A request is let in
    only if both can take it."""
    eng = ServingEngine(model, **ENGINE)
    ex = eng.executor
    full, win = ex.cache.groups
    row = -(-(WINDOW + 8) // 4) + 1
    assert (win.max_pages_per_seq, win.num_pages) == (row, 4 * row)
    assert (full.max_pages_per_seq, full.num_pages) == (16, 64)
    assert ex.pages_for(21) == 6 and ex.free_pages == 64
    assert ex.free_slots == 4


def _admitted(eng, prompts, steps=3):
    handles = [eng.submit(p, max_new_tokens=40) for p in prompts]
    for _ in range(steps):
        eng.step()
    return [eng.request(h.rid).sid is not None for h in handles]


def test_admission_refuses_when_only_the_full_pool_is_short(model):
    """Eight full pages: the first request's 30 tokens take them all; the
    second waits although the window pool and the slots are free."""
    eng = ServingEngine(model, **{**ENGINE, "num_pages": 9})
    got = _admitted(eng, [prompt(30, 7), prompt(20, 8)])
    ex = eng.executor
    assert got == [True, False]
    assert ex.free_slots == 3
    assert len(ex.cache.groups[1]._free) >= 3 * 5


def test_admission_refuses_when_only_the_window_pool_is_short(model,
                                                              monkeypatch):
    """A window pool that seats two sequences under four slots: the third
    request waits although the full pool has room and a slot is free, and
    the two admitted never find the window pool exhausted."""
    sound = WindowExecutor.__init__

    def two_seats(self, *a, **kw):
        from paddle_tpu.inference import paged

        real = paged.LayerGroup

        def group(n_layers, num_pages, window=None, pages_per_seq=None):
            if window is not None:
                num_pages = 2 * pages_per_seq
            return real(n_layers, num_pages, window, pages_per_seq)

        from paddle_tpu.inference.server import window_executor
        monkeypatch.setattr(window_executor, "LayerGroup", group)
        sound(self, *a, **kw)

    monkeypatch.setattr(WindowExecutor, "__init__", two_seats)
    eng = ServingEngine(model, **ENGINE)
    ex = eng.executor
    assert ex.cache.groups[1].num_pages == 10 and ex.free_slots == 2
    prompts = [prompt(12, 9), prompt(33, 10), prompt(9, 11)]
    handles = [eng.submit(p, max_new_tokens=14) for p in prompts]
    for _ in range(3):
        eng.step()
    assert [eng.request(h.rid).sid is not None for h in handles] == \
        [True, True, False]
    assert ex.cache._active.count(False) == 2 and ex.free_slots == 0
    assert ex.free_pages > ex.pages_for(10)
    eng.run()
    assert all(h.metrics()["state"] == "finished" for h in handles)
    assert eng.stats()["preemptions"] == 0


# -- the share -------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [19, 300])
def test_the_shares_add_up_to_the_uncut_layer(model, reference, rows):
    """Four models that hold experts 0..3, 4..7, 8..11, 12..15 of an
    expert layer, with the layer's own weights: the routed parts add up,
    the shared expert counted once, to what the model that holds all 16
    gives, and to the reference's layer: in a decode step's batched
    product (19 rows) and in a chunk's grouped product (300)."""
    cfg = model.config
    lp = layer_params(model, 2)
    assert moe.grouped(rows) == (rows == 300)
    x = jax.random.normal(jax.random.PRNGKey(4), (rows, 64))
    b = wm._norm(cfg, x, lp["pre_mlp_layernorm.weight"])
    sel, w = wm.route(cfg, lp, b)
    shared = moe.swiglu(b, lp["mlp.shared_experts.gate_up_proj.weight"],
                        lp["mlp.shared_experts.down_proj.weight"])
    whole = moe.routed_experts(
        b, sel, w, range(16),
        lp["mlp.experts.gate_up_proj"], lp["mlp.experts.down_proj"])
    parts = [moe.routed_experts(
        b, sel, w, range(lo, lo + 4),
        lp["mlp.experts.gate_up_proj"][lo:lo + 4],
        lp["mlp.experts.down_proj"][lo:lo + 4]) for lo in range(0, 16, 4)]
    assert rel(sum(parts), whole) < 1e-6
    leaves = weights_window_moe.layer(CFG, SEED, 2, jnp.float32)
    want = reference_window_moe.routed(CFG, leaves, b, None, None)
    assert rel(sum(parts) + shared, want) < 1e-5
    by_range = [reference_window_moe.routed(CFG, leaves, b, None, None,
                                            experts=(lo, lo + 4))
                for lo in range(0, 16, 4)]
    assert rel(sum(by_range) - 3 * np.asarray(shared), want) < 1e-5
    # the model built with a share computes that share
    held = WindowMoEForCausalLM(cfg, held_experts=range(4, 8),
                                init_weights=False)
    assert held.held_experts == (4, 5, 6, 7)
    assert dict(held.named_parameters())[
        "model.layers.2.mlp.experts.gate_up_proj"].shape == [4, 64, 64]


def test_the_router_is_the_shared_one_with_this_models_scalars(model):
    cfg, lp = model.config, layer_params(model, 3)
    h = jax.random.normal(jax.random.PRNGKey(9), (7, 64))
    sel, w = wm.route(cfg, lp, h)
    sc = 1 / (1 + np.exp(-np.asarray(h @ lp["mlp.router.gate.weight"],
                                     np.float64)))
    choice = sc + np.asarray(lp["mlp.expert_bias"], np.float64)
    assert np.array_equal(np.sort(np.asarray(sel), -1),
                          np.sort(np.argsort(-choice, -1)[:, :4], -1))
    picked = np.take_along_axis(sc, np.asarray(sel), -1)
    np.testing.assert_allclose(w, 2.826 * picked / picked.sum(-1,
                                                              keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.826, rtol=1e-5)


# -- the engine's choice and its refusals --------------------------------------------

def test_the_engine_picks_the_window_executor_by_layer_types(model):
    eng = ServingEngine(model, **ENGINE)
    assert isinstance(eng.executor, WindowExecutor)
    assert set(eng.executor.programs) == {"window_chunk", "window_decode",
                                          "kv_write"}
    assert [(g.n_layers, g.window) for g in eng.executor.cache.groups] == \
        [(1, None), (5, 8)]
    # nothing is stacked: the programs' parameters are the model's arrays
    own = dict(model.named_parameters())
    assert eng.executor.params[2]["mlp.experts.gate_up_proj"] is \
        own["model.layers.2.mlp.experts.gate_up_proj"]._data


@pytest.mark.parametrize("name,kw", [
    ("prefix cache", dict(prefix_cache=True)),
    ("speculative decoding", dict(spec_decode="ngram")),
    ("async execution", dict(async_exec=True)),
    ("decode_n", dict(decode_n_steps=(4,))),
    ("sequence-parallel prefill", dict(sp_prefill=True)),
    ("int8 quantisation", dict(quant="int8")),
    ("AOT warm-up", dict(aot="warm")),
    ("write-ahead log", dict(wal="/nonexistent/journal"))])
def test_unsupported_features_are_refused_by_name(model, name, kw):
    with pytest.raises(NotImplementedError) as e:
        ServingEngine(model, **ENGINE, **kw)
    assert name in str(e.value) and "sliding-window" in str(e.value)


def test_the_cluster_hand_off_is_refused(model):
    with pytest.raises(NotImplementedError, match="sliding-window"):
        ServingCluster(model, n_replicas=2, **ENGINE)


def test_a_model_without_a_full_layer_is_refused():
    cfg = WindowMoEConfig.tiny(layer_types=[wm.SLIDING] * 6)
    m = WindowMoEForCausalLM(cfg, init_weights=False)
    with pytest.raises(NotImplementedError, match="full-attention layer"):
        ServingEngine(m, **ENGINE)


# -- spans and counters -------------------------------------------------------------------

def test_a_long_chunk_runs_its_experts_grouped(model, monkeypatch):
    """With the batched product kept to 4 rows (so that a toy chunk of 8
    is a long run and a decode step of 2 is not), the engine serves the
    same tokens, a chunk's ``req.prefill`` span says how many expert
    layers ran grouped (absent on the chunk of 3 rows), and each expert
    layer of each traced chunk program leaves its static sizes."""
    prompts = [prompt(27, 12), prompt(6, 13)]
    _, want = serve(model, prompts, new=5)
    monkeypatch.setattr(moe, "_BATCHED_EXPERT_ROWS", 4)
    obs.reset()
    eng, got = serve(model, prompts, new=5)
    assert got == want
    spans = list(obs.tracer().spans)
    chunks = [s.args for s in spans if s.name == "req.prefill"]
    assert sorted(c["tokens"] for c in chunks) == [3, 6, 8, 8, 8]
    assert eng.executor.n_expert_layers == 4
    assert all(c.get("experts.grouped_layers") == (4 if c["tokens"] > 4
                                                   else None)
               for c in chunks)
    sizes = [s.args for s in spans if s.name == "experts.grouped"]
    assert sizes and len(sizes) % 4 == 0
    assert all(a["pairs"] in (6 * 4, 8 * 4) and a["tiles"] == a["pairs"]
               and a["row_tile"] == 1 and not a["kernel"] for a in sizes)
    assert not any("experts.grouped_layers" in s.args for s in spans
                   if s.name != "req.prefill")


def test_spans_and_counters_of_a_run(model):
    obs.reset()
    eng, _ = serve(model, [prompt(27, 12), prompt(6, 13)], new=11)
    spans = [s for s in obs.tracer().spans if s.ph is None]
    names = {s.name for s in spans}
    assert {"kv.release", "kv.write", "exec.prep", "jit.dispatch",
            "moe.load", "exec.fetch"} <= names
    programs = {s.args["program"] for s in spans if s.name == "jit.dispatch"}
    assert {"serve.window_chunk", "serve.window_decode",
            "serve.kv_write"} <= programs
    chunks = [s for s in spans if s.name == "req.prefill"]
    writes = [s for s in spans if s.name == "kv.write"]
    assert len(writes) == len(chunks) == 5      # 4 + 1 chunks, one write each
    # chunks of 8 rows run their experts as a decode step does: batched
    assert not any("experts.grouped_layers" in s.args for s in spans)
    assert "experts.grouped" not in names
    assert all(s.args["dispatches"] == 1 for s in writes)
    released = [s.args for s in spans if s.name == "kv.release"]
    ex = eng.executor
    assert sum(r["pages"] for r in released) == ex.pages_released[1] > 0
    assert all(r["group"] == 1 for r in released)
    preps = [s.args for s in spans if s.name == "exec.prep"
             and "blocks" in s.args]
    assert len(preps) == ex.expert_steps >= 10
    # toy pages make a block of 256 keys hold every table: one block a
    # layer a sequence, of one a layer a sequence
    assert all(p["blocks"] == p["window_blocks"] == 6 * p["batch"]
               for p in preps)
    loads = [s.args for s in spans if s.name == "moe.load"]
    assert len(loads) == len(preps)
    assert all(0 < a["hit"] <= 64 for a in loads)
    # 4 expert layers x 4 choices a token x the live rows of each step
    assert ex.expert_rows.sum() == 4 * 4 * sum(p["batch"] for p in preps)
    assert ex.expert_rows.shape == (4, 16)
    assert ex.page_samples == 5 + len(preps)
    assert ex.pages_used[0] > ex.pages_used[1] > 0
    assert [e[1:] for e in ex.prefill_events if e[0] == ex.prefill_events[0][0]
            ][:2] == [(8, 0), (8, 8)]


def test_blocks_follow_the_visible_span(model):
    """At pages of 128 and a window of 512 the kernel's block is 256 keys:
    a full layer's blocks follow the length, a sliding layer's the
    visible span through its shifted table."""
    cfg = WindowMoEConfig.tiny(sliding_window=512,
                               max_position_embeddings=4096)
    m = WindowMoEForCausalLM(cfg)
    m.eval()
    eng = ServingEngine(m, max_seqs=2, page_size=128, max_len=2048,
                        prefill_chunk=512)
    obs.reset()
    h = eng.submit(prompt(1500, 14), max_new_tokens=3)
    eng.run()
    assert len(h.tokens) == 3
    prep = [s.args for s in obs.tracer().spans if s.name == "exec.prep"
            and "blocks" in s.args][0]
    # the query at position 1,500 reads 1,501 keys on the full layer (6
    # blocks) and keys 989..1,500 on five sliding ones, whose table
    # begins at token 896: keys 93..604 of it, blocks 0, 1 and 2
    assert prep["blocks"] == 6 + 5 * 3
    assert prep["window_blocks"] == 8 + 5 * 5   # 16 and 9 pages a row
