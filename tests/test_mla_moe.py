"""Latent attention + routed experts (models/mla_moe.py) on the normal
path: the eager model and the default ServingEngine against plain
references — ``chipbench/reference_mla_moe.py`` (float32 "highest",
expanded form, no cache) for logits and cached rows, and numpy written
out in this file for the rope's frequencies, the router and the uncut
expert layer of the share test — at a tiny size on the CPU: hidden 64,
one dense and three expert layers, 16 routed experts of which 4 are held,
chunk 8, seeded weights, float32.

Tolerances.  Everything here is float32 on the CPU, where a matmul is
exact to rounding: program and reference differ by the ORDER of float32
sums only (absorbed against expanded, a batched product against a loop
over experts).  Logits are held to 2e-4 of their RMS, rows and layer
outputs to 2e-5 of their norm; served tokens to a gap of 1e-3 RMS below
the reference's best (0 unless two logits tie to rounding).
"""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import paddle_tpu as paddle
from chipbench import program_mla_moe, reference_mla_moe, weights_mla_moe
from paddle_tpu import obs
from paddle_tpu.inference.paged import PagedKVCache
from paddle_tpu.inference.server import ServingCluster, ServingEngine
from paddle_tpu.inference.server.latent_executor import LatentExecutor
from paddle_tpu.models import mla_moe as mm
from paddle_tpu.models import moe
from paddle_tpu.models.mla_moe import MLAMoEConfig, MLAMoEForCausalLM
from paddle_tpu.ops.pallas_kernels import mla_decode

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "chipbench", "bench", "configs",
                       "tiny-mla-moe.json")) as f:
    CFG = json.load(f)
SEED = 3_200_000_001
ENGINE = dict(max_seqs=4, page_size=4, max_len=64, prefill_chunk=8)
LOGIT_TOL, ROW_TOL, TOKEN_TOL = 2e-4, 2e-5, 1e-3
LAYERS = CFG["num_hidden_layers"]


def fresh_model():
    m = program_mla_moe.build_model(CFG, jnp.float32)
    m.eval()
    program_mla_moe.load_weights(m, CFG, SEED, jnp.float32)
    return m


@pytest.fixture(scope="module")
def model():
    return fresh_model()


@pytest.fixture(scope="module")
def reference():
    """``ids -> (logits [len, V], rows [layers, len, rank + rope])`` by
    the benchmark's plain reference."""
    scorer = reference_mla_moe.Scorer(CFG, rows=1, bucket=8)
    top = weights_mla_moe.top(CFG, SEED, jnp.float32)

    def run(ids):
        hidden, rows = scorer.forward(
            top, lambda n: weights_mla_moe.layer(CFG, SEED, n, jnp.float32),
            [(ids, 0)], keep_rows=[0])
        lg = reference_mla_moe.mm(hidden[0][:len(ids)], top["head"])
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


# -- the configuration and the rope ---------------------------------------

def test_config_kinds_and_refusals():
    cfg = MLAMoEConfig()                    # the published model
    assert cfg.layer_types == ("mla_dense",) + ("mla_moe",) * 31
    assert (cfg.q_head_dim, cfg.latent_dim) == (192, 576)
    assert MLAMoEConfig.tiny().layer_types.count("mla_moe") == 3
    assert hash(cfg) == hash(MLAMoEConfig())    # a jit attribute
    for what, kw in [
            ("q_lora_rank", dict(q_lora_rank=1536)),
            ("rope_scaling.type", dict(rope_scaling={"type": "linear",
                                                     "factor": 2.0})),
            ("num_shared_experts", dict(num_shared_experts=2)),
            ("group-limited", dict(n_group=8, topk_group=4)),
            ("expert bias", dict(moe_router_enable_expert_bias=False)),
            ("tied output head", dict(tie_word_embeddings=True)),
            ("no expert layer", dict(first_k_dense_replace=32))]:
        with pytest.raises(NotImplementedError, match=what):
            MLAMoEConfig(**kw)
    with pytest.raises(ValueError, match="held_experts"):
        MLAMoEForCausalLM(MLAMoEConfig.tiny(), held_experts=[3, 3],
                          init_weights=False)


def test_yarn_tables_against_the_formula():
    """The published rope: theta 10000, 64 dims, factor 40 over 4096,
    beta 32 / 1.  A pair that turns more than 32 times in 4,096 positions
    keeps its frequency, one that turns less than once has it divided by
    40, a linear ramp over the pairs between; cos and sin carry
    mscale / mscale_all_dim = 1; the softmax scale is 192^-0.5 m^2 with
    m = 0.1 ln 40 + 1."""
    cfg = MLAMoEConfig()
    d, base, factor, orig = 64, 10000.0, 40.0, 4096

    def pair_of(turns):         # the (real-valued) pair that turns so often
        return d * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    lo, hi = math.floor(pair_of(32)), math.ceil(pair_of(1))
    assert (lo, hi) == (10, 23)
    want = []
    for i in range(d // 2):
        plain = base ** (-2 * i / d)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want.append(plain * (1 - ramp) + plain / factor * ramp)
    got = mm.yarn_inv_freq(cfg)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert got[0] == 1.0 and abs(got[10] / base ** (-20 / 64) - 1) < 1e-12
    np.testing.assert_allclose(got[23:], [base ** (-2 * i / d) / 40
                                          for i in range(23, 32)])
    pos = jnp.asarray([0, 1, 4095, 131071], jnp.int32)
    cos, sin = mm.rope_tables(cfg, pos)
    ang = np.asarray(pos, np.float64)[:, None] * np.asarray(want)[None]
    # float32 angles: the table's own precision at position 131,071
    np.testing.assert_allclose(cos, np.cos(ang), atol=2e-2)
    np.testing.assert_allclose(np.asarray(cos[:3]), np.cos(ang[:3]),
                               atol=3e-4)
    np.testing.assert_allclose(np.asarray(sin[:3]), np.sin(ang[:3]),
                               atol=3e-4)
    m = 0.1 * math.log(40) + 1
    assert abs(mm.softmax_scale(cfg) - 192 ** -0.5 * m * m) < 1e-12
    assert abs(mm.softmax_scale(cfg) - 0.1352) < 1e-4
    np.testing.assert_allclose(reference_mla_moe.yarn_frequencies(
        {"qk_rope_head_dim": 64, "rope_theta": 10000,
         "rope_scaling": cfg.yarn}), want, rtol=1e-12)
    # the rotation itself: pairs (i, i + d / 2), norm kept
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    y = np.asarray(mm.rope(x, cos, sin))
    a, b = np.asarray(x[:, :32]), np.asarray(x[:, 32:])
    np.testing.assert_allclose(y[:, :32], a * cos - b * sin, atol=1e-6)
    np.testing.assert_allclose(y[:, 32:], b * cos + a * sin, atol=1e-6)


# -- the router -------------------------------------------------------------

def _router(logits, bias, k=3, scaling=2.5):
    """``route`` on chosen logits: the router's matrix is the identity."""
    e = logits.shape[-1]
    cfg = MLAMoEConfig.tiny(hidden_size=e, num_attention_heads=4,
                            num_experts=e, num_experts_per_tok=k,
                            routed_scaling_factor=scaling)
    lp = {"mlp.gate.weight": jnp.eye(e, dtype=jnp.float32),
          "mlp.gate.e_score_correction_bias": jnp.asarray(bias, jnp.float32)}
    sel, w = mm.route(cfg, lp, jnp.asarray(logits, jnp.float32))
    return np.asarray(sel), np.asarray(w)


def test_router_sigmoid_bias_in_the_choice_only_normalised_and_scaled():
    logits = np.array([[2.0, -1.0, 0.5, 1.0, -3.0, 0.0, 0.1, -0.2]])
    sc = 1 / (1 + np.exp(-logits[0]))
    sel, w = _router(logits, np.zeros(8))
    assert sel.tolist() == [[0, 3, 2]]
    np.testing.assert_allclose(w[0], 2.5 * sc[[0, 3, 2]]
                               / sc[[0, 3, 2]].sum(), rtol=1e-6)
    np.testing.assert_allclose(w.sum(), 2.5, rtol=1e-6)
    # a bias lifts expert 4 (score 0.047) into the choice; its WEIGHT is
    # still made of its score, not of score + bias
    bias = np.zeros(8)
    bias[4] = 0.9
    sel, w = _router(logits, bias)
    assert sel.tolist() == [[4, 0, 3]]
    np.testing.assert_allclose(w[0], 2.5 * sc[[4, 0, 3]]
                               / sc[[4, 0, 3]].sum(), rtol=1e-6)


def test_router_ties_go_to_the_lower_id():
    logits = np.array([[0.3, 1.0, 0.3, 1.0, 0.3, -1.0, 0.3, 0.3]])
    sel, _ = _router(logits, np.zeros(8), k=4)
    assert sel.tolist() == [[1, 3, 0, 2]]


def test_held_weights_are_zero_for_experts_not_chosen_or_not_held():
    sel = jnp.asarray([[5, 1, 9], [2, 6, 5]])
    w = jnp.asarray([[0.5, 0.3, 0.2], [0.6, 0.3, 0.1]])
    got = np.asarray(mm.held_weights(sel, w, (4, 5, 6, 7)))
    np.testing.assert_allclose(got, [[0, 0.5, 0, 0], [0, 0.1, 0.3, 0]])


# -- the attention's two forms ------------------------------------------------

@pytest.mark.parametrize("T,past", [(1, 13), (8, 0), (5, 11)])
def test_expanded_equals_absorbed(model, T, past):
    """Queries at positions past .. past + T - 1 against every row up to
    their own: the expanded form (keys and values rebuilt from the
    latent) and the absorbed one (the query taken into the latent's
    space, the rows scored as they are) give the same output."""
    cfg, lp = model.config, layer_params(model, 1)
    x = jax.random.normal(jax.random.PRNGKey(T), (past + T, 64))
    q_nope, q_pe, rows = mm.mla_project(cfg, lp, x, jnp.arange(past + T))
    q_nope, q_pe = q_nope[past:], q_pe[past:]
    mask = (jnp.arange(past + T)[None, :]
            <= (past + jnp.arange(T))[:, None])
    a = mm.attend_expanded(cfg, lp, q_nope, q_pe, rows, mask)
    u = mm.attend_absorbed(mm.absorb_query(cfg, lp, q_nope, q_pe), rows,
                           mask, cfg.kv_lora_rank)
    b = mm.absorb_output(cfg, lp, u)
    assert rel(b, a) < ROW_TOL
    two = mm.attend_expanded(cfg, lp, q_nope, q_pe, rows, mask, head_block=2)
    assert rel(two, a) < 1e-6


# -- the eager model ---------------------------------------------------------

def test_eager_logits_and_rows_match_the_reference(model, reference):
    ids = prompt(23, 1)
    want, _ = reference(ids)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
    assert np.abs(got - want).max() / np.sqrt(np.square(want).mean()) \
        < LOGIT_TOL


def test_the_grouped_product_equals_the_batched_product(model, monkeypatch):
    """A long run of rows goes through the grouped product over rows
    sorted by expert (off the TPU ``lax.ragged_dot``; the kernel itself:
    tests/test_grouped_swiglu.py): the same layer as the batched product
    of a decode step, at the row count that separates them and above."""
    cfg, lp = model.config, layer_params(model, 2)
    held = model.held_experts
    x = jax.random.normal(jax.random.PRNGKey(3), (19, 64))
    batched, took = mm.feed_forward(cfg, "mla_moe", lp, x, held)
    monkeypatch.setattr(moe, "_BATCHED_EXPERT_ROWS", 0)
    grouped, took2 = mm.feed_forward(cfg, "mla_moe", lp, x, held)
    assert rel(grouped, batched) < 1e-6
    assert np.array_equal(took, took2) and took.shape == (19, 4)
    # the held experts are a share: most pairs lie on none of them
    assert 0 < np.asarray(took).sum() < 19 * cfg.num_experts_per_tok / 2
    # a layer of a stacked run, addressed in place, is that layer
    run = {k: (jnp.stack([v * 0, v]), jnp.int32(1)) if "experts." in k
           and "shared" not in k else v for k, v in lp.items()}
    in_place, _ = mm.feed_forward(cfg, "mla_moe", run, x, held)
    assert rel(in_place, grouped) < 1e-6
    # the choice is the row count the function sees, and nothing else
    monkeypatch.undo()
    assert not moe.grouped(256) and moe.grouped(257)
    x300 = jax.random.normal(jax.random.PRNGKey(5), (300, 64))
    obs.tracer().configure()
    long_run, _ = mm.feed_forward(cfg, "mla_moe", lp, x300, held)
    assert [s.args["pairs"] for s in obs.tracer().spans
            if s.name == "experts.grouped"] \
        == [300 * cfg.num_experts_per_tok]
    monkeypatch.setattr(moe, "_BATCHED_EXPERT_ROWS", 300)
    whole, _ = mm.feed_forward(cfg, "mla_moe", lp, x300, held)
    assert rel(long_run, whole) < 1e-6


# -- the share ------------------------------------------------------------

def _plain_expert_layer(w, h, held, k, scaling):
    """The expert layer in numpy float64, written out: sigmoid scores,
    the k largest score + bias (lower id first on a tie), weights scaling
    * score / sum of the chosen scores; the shared expert, and the chosen
    experts that are in ``held`` (ids into the uncut layer's experts)."""
    f = {n: np.asarray(v, np.float64) for n, v in w.items()}

    def swiglu(x, gate_up, down):
        g, u = np.split(x @ gate_up, 2, axis=-1)
        return (g / (1 + np.exp(-g)) * u) @ down

    sc = 1 / (1 + np.exp(-(h @ f["router"])))
    out = swiglu(h, f["shared_gate_up"], f["shared_down"])
    routed = np.zeros_like(out)
    for t in range(h.shape[0]):
        order = np.argsort(-(sc[t] + f["router_bias"]), kind="stable")[:k]
        for e in order:
            if e in held:
                routed[t] += (scaling * sc[t, e] / sc[t, order].sum()
                              * swiglu(h[t], f["experts_gate_up"][e],
                                       f["experts_down"][e]))
    return out, routed


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One expert layer with all 16 experts' weights; four chips each
    hold 4.  The shared expert and the router are the same on every
    chip: the routed parts of the four shares, with the shared expert
    counted once, add up to the uncut layer — by the program's
    ``feed_forward`` told which experts it holds, by the benchmark's
    reference given the same share, and both against numpy."""
    cfg = dict(CFG, num_experts=16, share=dict(CFG["share"],
                                               held_experts=[0, 16]))
    w = weights_mla_moe.layer(cfg, SEED, 1, jnp.float32)
    mcfg = MLAMoEConfig.tiny(num_experts=16, num_experts_per_tok=4)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(7), (21, 64)))
    h = np.asarray(reference_mla_moe.rms_norm(
        jnp.asarray(x), w["ln2"], CFG["rms_norm_eps"]), np.float64)
    shared, uncut = _plain_expert_layer(w, h, set(range(16)), 4, 2.5)
    assert np.abs(uncut).max() > 0.1        # the experts do move it

    def lp_of(held):
        ids = jnp.asarray(held)
        return {"post_attention_layernorm.weight": w["ln2"],
                "mlp.gate.weight": w["router"],
                "mlp.gate.e_score_correction_bias": w["router_bias"],
                "mlp.experts.gate_up_proj": w["experts_gate_up"][ids],
                "mlp.experts.down_proj": w["experts_down"][ids],
                "mlp.shared_experts.gate_up_proj.weight":
                    w["shared_gate_up"],
                "mlp.shared_experts.down_proj.weight": w["shared_down"]}

    program, reference, took = [], [], 0
    for first in (0, 4, 8, 12):
        held = tuple(range(first, first + 4))
        out, t = mm.feed_forward(mcfg, "mla_moe", lp_of(held),
                                 jnp.asarray(x), held)
        program.append(np.asarray(out, np.float64) - x - shared)
        took += int(np.asarray(t).sum())
        share = dict(cfg, share=dict(cfg["share"],
                                     held_experts=[first, first + 4]))
        part = dict(w, experts_gate_up=w["experts_gate_up"][first:first + 4],
                    experts_down=w["experts_down"][first:first + 4])
        reference.append(np.asarray(reference_mla_moe.routed(
            share, part, jnp.asarray(h, jnp.float32), None, None),
            np.float64) - shared)
        _, plain = _plain_expert_layer(w, h, set(held), 4, 2.5)
        assert rel(program[-1], plain) < ROW_TOL
        assert rel(reference[-1], plain) < ROW_TOL
    assert took == 21 * 4                   # every choice lands on one chip
    assert rel(sum(program), uncut) < ROW_TOL
    assert rel(sum(reference), uncut) < ROW_TOL
    whole, _ = mm.feed_forward(mcfg, "mla_moe", lp_of(range(16)),
                               jnp.asarray(x), tuple(range(16)))
    assert rel(np.asarray(whole) - x, shared + uncut) < ROW_TOL


# -- through the default engine -------------------------------------------

def held_rows(eng, sid):
    return np.asarray(eng.executor.slot_rows(sid))


@pytest.mark.parametrize("n", [5, 8, 21])
def test_prefill_in_chunks_then_decode_is_the_full_forward(model, reference,
                                                           n):
    """A prompt prefilled in chunks of 8 (expanded form, the past read
    from the pool) and 9 tokens decoded (absorbed form, the kernel's
    ``jax.numpy`` over the pool): every served token is the reference's
    best to rounding, and the rows the pool then holds in EVERY layer —
    the last layer's have been through all of the model but its head —
    are the reference's full forward over the same tokens."""
    ids = prompt(n, 3)
    eng = ServingEngine(model, **ENGINE)
    h = eng.submit(ids, max_new_tokens=9)
    while len(h.tokens) < 8:
        eng.step()
    sid = eng.request(h.rid).sid
    tokens = list(h.tokens)
    seq = np.concatenate([ids, np.asarray(tokens[:-1], np.int32)])
    assert int(eng.executor.cache.lengths[sid]) == len(seq)
    _, want = reference(seq)
    got = held_rows(eng, sid)
    assert got.shape == want.shape == (LAYERS, len(seq), 40)
    for layer in range(LAYERS):
        assert rel(got[layer], want[layer]) < ROW_TOL, layer
    eng.run()
    assert gap_of(reference, ids, list(h.tokens)) < TOKEN_TOL
    assert type(eng.executor) is LatentExecutor


def test_a_batch_of_mixed_lengths(model, reference):
    prompts = [prompt(n, 4) for n in (3, 17, 8, 30, 12, 1)]
    eng, tokens = serve(model, prompts, new=7)
    for ids, toks in zip(prompts, tokens):
        assert gap_of(reference, ids, toks) < TOKEN_TOL
    assert eng.executor.free_pages == eng.executor.cache.num_pages


def test_a_preempted_request_resumes_with_the_same_rows(model, reference):
    prompts = [prompt(13, 5), prompt(10, 5)]
    _, want = serve(model, prompts, new=14)
    eng = ServingEngine(model, **ENGINE)
    handles = [eng.submit(p, max_new_tokens=14) for p in prompts]
    for _ in range(6):
        eng.step()
    victim = eng.request(handles[0].rid)
    assert 0 < len(victim.generated) < 14
    eng.scheduler._preempt(victim)
    while len(handles[0].tokens) < 12:
        eng.step()
    assert victim.preempt_count == 1
    seq = np.concatenate([prompts[0], np.asarray(handles[0].tokens[:-1],
                                                 np.int32)])
    _, rows = reference(seq)
    got = held_rows(eng, eng.request(handles[0].rid).sid)
    assert rel(got[-1], rows[-1]) < ROW_TOL
    eng.run()
    assert [list(h.tokens) for h in handles] == want


# -- the latent pool ----------------------------------------------------------

def test_the_latent_pool_is_one_pool_behind_the_same_page_table(model):
    eng = ServingEngine(model, **ENGINE)
    cache = eng.executor.cache
    assert type(cache) is PagedKVCache and cache.latent
    assert cache.v_pages is None and cache.pools()[1] is None
    # 40 values a token, padded to whole lanes, one "head"
    assert cache.k_pages.shape == (LAYERS, 1, 4 * 16, 4, 128)
    h = eng.submit(prompt(21, 6), max_new_tokens=9)
    writes = cache.writer.dispatches
    obs.reset()
    eng.step()                                      # chunk 0..7
    assert cache.writer.dispatches == writes + 1    # one donated write
    spans = [s for s in obs.tracer().spans if s.name == "kv.write"]
    assert [s.args["dispatches"] for s in spans] == [1]
    assert cache.num_pages - cache.free_pages == 2  # 8 tokens, pages of 4
    eng.step()
    eng.step()                                      # 21 tokens: 6 pages
    sid = eng.request(h.rid).sid
    assert int(cache.lengths[sid]) == 21
    assert cache.num_pages - cache.free_pages == 6
    assert (cache.page_table[sid, :6] >= 0).all()
    assert (cache.page_table[sid, 6:] == -1).all()
    eng.step()                                      # decode: token 22
    eng.step()
    eng.step()                                      # token 24: still 6 pages
    assert cache.num_pages - cache.free_pages == 6
    cache.reserve([sid], extra_tokens=9)            # a look-ahead ...
    assert cache.num_pages - cache.free_pages == 9, cache.lengths[sid]
    assert cache.trim(sid) == 3                     # ... given back
    cache.make_writable(sid, 0, 24)                 # nothing shared: no copy
    assert cache.cow_count == 0
    eng.run()
    assert cache.free_pages == cache.num_pages
    for what in (lambda: cache.append([0], None, None),
                 lambda: cache.attend(0, None, [0])):
        with pytest.raises(NotImplementedError, match="latent pool"):
            what()
    with pytest.raises(NotImplementedError, match="latent pool"):
        PagedKVCache(2, 2, 128, 8, latent=True)
    with pytest.raises(NotImplementedError, match="latent pool"):
        PagedKVCache(2, 1, 128, 8, latent=True, quant="int8")


def test_a_long_chunk_runs_its_experts_grouped(model, monkeypatch):
    """With the batched product kept to 4 rows (so that a toy chunk of 8
    is a long run and a decode step is not), the engine serves the same
    tokens, a chunk's ``req.prefill`` span says how many expert layers
    ran grouped (absent on the chunk of 3 rows), and the scanned run's
    one traced body leaves its static sizes once a chunk program."""
    prompts = [prompt(27, 12), prompt(6, 13)]
    _, want = serve(model, prompts, new=5)
    monkeypatch.setattr(moe, "_BATCHED_EXPERT_ROWS", 4)
    obs.reset()
    eng, got = serve(model, prompts, new=5)
    assert got == want
    spans = list(obs.tracer().spans)
    chunks = [s.args for s in spans if s.name == "req.prefill"]
    assert sorted(c["tokens"] for c in chunks) == [3, 6, 8, 8, 8]
    layers = eng.executor.n_expert_layers
    assert layers == CFG["num_hidden_layers"] - 1
    assert all(c.get("experts.grouped_layers") == (layers if c["tokens"] > 4
                                                   else None)
               for c in chunks)
    k = model.config.num_experts_per_tok
    sizes = [s.args for s in spans if s.name == "experts.grouped"]
    programs = {s.args["tokens"] for s in spans if s.name == "exec.prep"
                and s.args.get("tokens", 0) > 4}
    assert len(sizes) >= len(programs) == 2
    assert all(a["pairs"] in (6 * k, 8 * k) and a["tiles"] == a["pairs"]
               and a["row_tile"] == 1 and not a["kernel"] for a in sizes)


def test_the_decode_program_counts_the_rows_each_held_expert_took(model):
    obs.reset()
    eng, _ = serve(model, [prompt(9, 8), prompt(4, 8)], new=6)
    ex = eng.executor
    loads = [s for s in obs.tracer().spans if s.name == "moe.load"]
    # tokens 2..6 are decoded; the first request starts a step ahead
    assert ex.expert_steps == len(loads) == 6
    assert ex.expert_rows.shape == (3, 4)
    # two sequences a step, at most 4 choices each a layer, all on held
    assert 0 < ex.expert_rows.sum() <= 10 * 3 * 4
    assert ex.experts_hit == sum(s.args["hit"] for s in loads)
    assert ex.expert_rows.max() <= 10
    assert {"serve.mla_chunk", "serve.mla_decode", "serve.kv_write"} == \
        {p.name for p in ex.programs.values()}


# -- the decode kernel -------------------------------------------------------

def walk_matches_the_numpy_form(lengths, ps, per_seq):
    """Four sequences of ``lengths`` through the compiled kernel's layout
    (rows of 256 lanes, a latent of 128, bf16 pages of ``ps``, tables of
    ``per_seq`` pages, each sequence its own pages) in the Pallas TPU
    interpreter — DMAs land at their wait, VMEM scratch starts as NaN —
    against the ``jax.numpy`` form, on two layers of three; then again
    with every page that no sequence reads up to its length — those past
    each length, and those the unused table entries name — full of NaN:
    nothing past the live pages reaches the output.  A sequence of length
    0 (a slot that is not live) reads nothing and returns zeros."""
    S, heads, W, rank, L = 4, 8, 256, 128, 3
    pages = S * per_seq
    q = jax.random.normal(jax.random.PRNGKey(0), (S, heads, W)) \
        .astype(jnp.bfloat16)
    pool = jax.random.normal(jax.random.PRNGKey(1), (L * pages, ps, W)) \
        .astype(jnp.bfloat16)
    tables = jnp.asarray(np.random.default_rng(2).permutation(pages)
                         .reshape(S, per_seq), jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    live = np.asarray(lens) > 0
    for layer in (0, 2):
        read = np.zeros(L * pages, bool)
        for t, n in zip(np.asarray(tables), lengths):
            read[layer * pages + t[:-(-n // ps)]] = True
        poisoned = jnp.where(jnp.asarray(read)[:, None, None], pool,
                             jnp.nan).astype(pool.dtype)
        want = np.asarray(mla_decode.mla_decode_reference(
            q, pool, jnp.int32(layer), pages, lens, tables, rank), np.float32)
        for held in (pool, poisoned):
            got = mla_decode._mla_decode_call(
                q, held, jnp.int32(layer), lens, tables, pages=pages,
                rank=rank, interpret=pltpu.InterpretParams())
            assert got.shape == (S, heads, rank)
            got = np.asarray(got, np.float32)
            np.testing.assert_allclose(got[live], want[live], atol=2e-2)
            assert not got[~live].any()


@pytest.mark.parametrize("lengths", [
    [37, 0, 64, 1], [16, 15, 17, 48],
    # several blocks of 32 pages (512 keys) each
    [1152, 1100, 600, 1025],
    # ends inside a block's last page, on a block's edge, one past it
    [511, 512, 513, 1024],
    # one page, one token, nothing
    [16, 1, 0, 17],
    # nothing first and last
    [0, 1100, 513, 0],
])
def test_pallas_kernel_in_interpret_mode(lengths):
    """Pages of 16, so a block of 512 keys is 32 pages: lengths that end
    inside a page, on a page's or a block's edge, span several blocks,
    and zero (:func:`walk_matches_the_numpy_form`)."""
    assert mla_decode.supported(256, 128, 16, on_tpu=True)
    assert not mla_decode.supported(40, 32, 4, on_tpu=True)
    assert mla_decode.padded_width(576) == 640
    assert mla_decode.block_pages(16, 256, 2) == 32
    walk_matches_the_numpy_form(lengths, ps=16, per_seq=72)


@pytest.mark.parametrize("lengths", [
    [2048, 1, 128, 129],        # four blocks; one token; a page's edge
    [512, 513, 1536, 1535],     # a block's edge and one past; 3 blocks
    [0, 2000, 0, 640],          # nothing around the longest
    [300, 700, 1100, 1900],     # last blocks of 3, 2, 1, 3 pages
])
def test_the_walk_in_blocks_of_the_cells_four_pages(lengths):
    """Pages of 128 as in the serving cell's pool, so a block is 4 pages,
    and tables of 16 pages: up to four blocks a sequence, a last block
    of 1 to 4 live pages (:func:`walk_matches_the_numpy_form`)."""
    assert mla_decode.block_pages(128, 256, 2) == 4
    walk_matches_the_numpy_form(lengths, ps=128, per_seq=16)


@pytest.mark.parametrize("ps, width, itemsize, pages", [
    (128, 640, 2, 4),           # the serving cell's pool: 512 keys
    (16, 256, 2, 32),
    (64, 576, 2, 8),
    (128, 640, 4, 4),           # a float32 pool: 2.6 MB, in the budget
    (128, 4096, 2, 2),          # the budget binds: 256 keys
    (16, 8192, 4, 4),           # 64 keys of 16
    (256, 2048, 4, 1),          # two slots of one page fill it
    (4096, 640, 2, 1),          # a page alone fills it
    (1024, 4096, 4, 1),
])
def test_the_block_follows_the_pools_shape(ps, width, itemsize, pages):
    """512 keys of whole pages, fewer where two slots of a block would
    pass the VMEM budget, one where a page alone fills it."""
    n = mla_decode.block_pages(ps, width, itemsize)
    assert n == pages
    assert n == 1 or (n * ps <= mla_decode.BLOCK_KEYS and
                      2 * n * ps * width * itemsize <= mla_decode.VMEM_BUDGET)


def test_the_kernels_form_and_block_are_recorded_once_a_trace(monkeypatch):
    """``attn.mla_decode`` records at trace time which form ran and at
    what block: the kernel at the serving cell's pool (pages of 128 x 640
    lanes: 4 pages a block), the ``jax.numpy`` form off the TPU (0)."""
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    args = (sds((64, 64, 640), jnp.bfloat16),
            sds((5 * 4096, 128, 640), jnp.bfloat16), sds((), i32),
            sds((64,), i32), sds((64, 64), i32))

    def traced():
        obs.reset()
        jax.eval_shape(lambda q, pool, layer, lengths, tables:
                       mla_decode.mla_decode(q, pool, layer, 4096, lengths,
                                             tables, 512), *args)
        return [s.args for s in obs.tracer().spans
                if s.name == "attn.mla_decode"]

    assert traced() == [{"kernel": False, "block_pages": 0}]
    monkeypatch.setattr(mla_decode, "_on_tpu", lambda: True)
    assert traced() == [{"kernel": True, "block_pages": 4}]


def test_the_kernels_numpy_form_is_the_absorbed_attention(model):
    """``mla_decode_reference`` over a paged pool = ``attend_absorbed``
    over the same rows laid dense."""
    cfg = model.config
    rows = jax.random.normal(jax.random.PRNGKey(4), (11, 40))
    q = jax.random.normal(jax.random.PRNGKey(5), (1, 4, 40))
    pool = jnp.zeros((2 * 6, 4, 128)).at[6 + jnp.asarray([5, 2, 3])].set(
        jnp.pad(rows, [(0, 1), (0, 88)]).reshape(3, 4, 128))
    got = mla_decode.mla_decode(
        jnp.pad(q, [(0, 0), (0, 0), (0, 88)]), pool, jnp.int32(1), 6,
        jnp.asarray([11]), jnp.asarray([[5, 2, 3, 0]]), cfg.kv_lora_rank)
    want = mm.attend_absorbed(q, rows, jnp.ones((1, 11), bool),
                              cfg.kv_lora_rank)
    assert rel(got[0], want[0]) < ROW_TOL


# -- what is held, and what is refused at build -------------------------------

def test_no_weight_is_drawn_for_a_model_that_will_be_loaded():
    m = program_mla_moe.build_model(CFG, jnp.float32)
    assert not any(np.asarray(p._data).any() for p in m.parameters())
    assert m.held_experts == (4, 5, 6, 7)
    assert m.config.num_experts == 16       # the router's whole width
    assert m.num_params() == weights_mla_moe.count(CFG)


def test_pools_the_device_cannot_hold_are_refused_at_build(model,
                                                           monkeypatch):
    from paddle_tpu.inference.server import latent_executor as lx

    monkeypatch.setattr(lx, "_free_device_bytes", lambda: 1 << 10)
    monkeypatch.setattr(lx, "_HEADROOM", 0)
    with pytest.raises(ValueError, match="do not fit"):
        ServingEngine(model, **ENGINE)


def test_the_expert_layers_are_taken_over_where_two_copies_do_not_fit(
        model, monkeypatch):
    """A device that holds the pool but not a second copy of the expert
    layers: each leaf's eager arrays are deleted as it is stacked, the
    engine serves the same tokens, the model says what happened, and the
    aliased leaves (the dense layer, embedding, head) live on."""
    from paddle_tpu.inference.server import latent_executor as lx

    prompts = [prompt(11, 21), prompt(5, 22)]
    whole, want = serve(model, prompts, new=6)
    assert whole.executor.took_over_weights is False    # the CPU says nothing
    mine = fresh_model()
    monkeypatch.setattr(lx, "_HEADROOM", 0)
    monkeypatch.setattr(lx, "_free_device_bytes",
                        lambda: whole.executor.cache.k_pages.nbytes + 1)
    eng, got = serve(mine, prompts, new=6)
    assert got == want
    assert eng.executor.took_over_weights is True
    layers = mine.model.layers
    assert layers[1].mlp.experts.gate_up_proj._data.is_deleted()
    assert layers[3].self_attn.q_proj.weight._data.is_deleted()
    assert not layers[0].mlp.gate_up_proj.weight._data.is_deleted()
    assert not mine.lm_head.weight._data.is_deleted()
    with pytest.raises(RuntimeError, match="handed over"):
        mine(paddle.to_tensor(prompts[0][None]))
    with pytest.raises(ValueError, match="handed over"):
        ServingEngine(mine, **ENGINE)


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
    with pytest.raises(NotImplementedError,
                       match=feature + ".*latent attention"):
        ServingEngine(model, **ENGINE, **kwargs)


@pytest.mark.parametrize("var,feature", [
    ("PT_PREFIX_CACHE", "prefix cache"), ("PT_ASYNC_EXEC", "async"),
    ("PT_SP_PREFILL", "sequence-parallel"), ("PT_WAL", "write-ahead")])
def test_refused_when_the_environment_asks(model, monkeypatch, var, feature):
    monkeypatch.setenv(var, "on")
    with pytest.raises(NotImplementedError, match=feature):
        ServingEngine(model, **ENGINE)


def test_cluster_hand_off_is_refused(model):
    with pytest.raises(NotImplementedError,
                       match="cluster hand-off.*latent attention"):
        ServingCluster(model, n_replicas=2)
