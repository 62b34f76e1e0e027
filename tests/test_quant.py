"""Quantized serving path (PT_QUANT=int8).

Two contracts, tested at every layer they ride through:

* ``PT_QUANT=none`` (the default) is the legacy path BIT-EXACT: the
  forwards dispatch on the weight pytree at trace time, the pools keep
  their dtype and signatures, and a seeded serving load — plain,
  prefix-cached, speculative and async variants — emits identical
  per-step maps whether the mode comes from the env, the param, or is
  left unset, with the refcount audit green after every step.
* ``int8`` trades bounded logit drift for halved pool bytes: the
  per-channel weight pack round-trips within its scale bound, the
  engine drains the same loads (invariants green), logits stay inside
  the drift bound vs the bf16 forward, COW copies a shared quantized
  page WITH its scale, AOT warmup covers the int8 pool programs, and
  an injected raise at every quant.* fault point x phase leaves the
  engine serviceable.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.server import (
    RequestState, ServingEngine, check_pool_invariants,
)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops import quant
from paddle_tpu.testing import faults
from paddle_tpu.testing.load import LoadSpec, generate_load


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


ENGINE_KW = dict(max_seqs=2, page_size=4, max_len=128)

PROMPT = np.random.RandomState(3).randint(1, 256, (9,)).astype(np.int32)

LOAD_SPEC = LoadSpec(n_requests=8, mean_interarrival=2.0,
                     prompt_len=(4, 12), max_new=(6, 10), vocab=256,
                     seed=23, prefix_share=0.6, prefix_len=10,
                     prefix_pool=2, repeat_share=0.5, repeat_period=3)
# undersized pool: decode growth forces preemption so the quantized
# pool's refcount/COW discipline is exercised under pressure
TIGHT_KW = dict(max_seqs=2, page_size=4, max_len=64, num_pages=11,
                prefill_chunk=8)


def _drive_load(model, spec, engine_kw, check_invariants=False,
                on_error="raise"):
    """Replay the seeded load step by step, recording the PER-STEP
    emission maps (stricter than per-request streams)."""
    eng = ServingEngine(model, **engine_kw)
    pending = sorted(generate_load(spec),
                     key=lambda w: (w["arrival_tick"], w["rid"]))
    handles, errors, per_step = {}, [], []
    while pending or eng.in_flight:
        assert eng.tick < 3000, "load did not drain"
        while pending and pending[0]["arrival_tick"] <= eng.tick:
            w = pending.pop(0)
            handles[w["rid"]] = eng.submit(
                w["prompt_ids"], max_new_tokens=w["max_new_tokens"],
                rid=w["rid"])
        try:
            per_step.append(eng.step())
        except faults.InjectedFault as e:
            if on_error != "continue":
                raise
            errors.append(e)
        if check_invariants:
            check_pool_invariants(eng.executor.cache, eng.prefix)
    return eng, handles, errors, per_step


def _variant_kw(variant):
    kw = dict(TIGHT_KW)
    if "prefix" in variant:
        kw["prefix_cache"] = True
    if "spec" in variant:
        kw["spec_decode"] = "ngram"
    if "async" in variant:
        kw["async_exec"] = True
    return kw


# -- weight pack/unpack -------------------------------------------------


def test_pack_round_trip_within_scale_bound():
    rng = np.random.RandomState(0)
    w = np.asarray(rng.randn(3, 32, 48) * 0.3, np.float32)
    q, s = quant.quantize_per_channel(w)
    assert np.asarray(q).dtype == np.int8
    assert np.asarray(s).shape == (3, 1, 48)
    back = np.asarray(quant.dequantize(q, s))
    # symmetric rounding: every element lands within half a quantum
    # of its channel's scale
    assert np.all(np.abs(back - w) <= 0.5 * np.asarray(s) + 1e-7)
    # channel amax maps exactly onto the int8 endpoint
    assert np.asarray(q).max() == 127 or np.asarray(q).min() == -127


def test_quantize_linear_state_format():
    rng = np.random.RandomState(1)
    w = np.asarray(rng.randn(2, 16, 24), np.float32)
    qlin = quant.quantize_linear(w)
    assert quant.is_quantized(qlin)
    assert set(qlin) == {"qweight", "scale"}
    assert not quant.is_quantized(w)
    # qmatmul == dequant-then-matmul within float error
    x = np.asarray(rng.randn(4, 16), np.float32)
    got = np.asarray(quant.qmatmul(x, {"qweight": qlin["qweight"][0],
                                       "scale": qlin["scale"][0]}))
    want = x @ np.asarray(quant.dequantize(qlin["qweight"][0],
                                           qlin["scale"][0]))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -- mode knob ----------------------------------------------------------


def test_env_gate(model, monkeypatch):
    monkeypatch.setenv("PT_QUANT", "int8")
    assert ServingEngine(model, **ENGINE_KW).executor.quant == "int8"
    monkeypatch.setenv("PT_QUANT", "none")
    assert ServingEngine(model, **ENGINE_KW).executor.quant == "none"
    monkeypatch.delenv("PT_QUANT")
    assert ServingEngine(model, **ENGINE_KW).executor.quant == "none"
    # param forces over env
    monkeypatch.setenv("PT_QUANT", "int8")
    eng = ServingEngine(model, quant="none", **ENGINE_KW)
    assert eng.executor.quant == "none"
    monkeypatch.setenv("PT_QUANT", "fp4")
    with pytest.raises(ValueError, match="PT_QUANT"):
        ServingEngine(model, **ENGINE_KW)
    with pytest.raises(ValueError, match="PT_QUANT"):
        quant.quant_mode("int4")


def test_none_mode_is_legacy_path(model):
    """quant='none' keeps plain weights, an unquantized pool and no
    scale arrays — the pre-quant serving build, structurally."""
    eng = ServingEngine(model, quant="none", **ENGINE_KW)
    ex = eng.executor
    assert ex.cache.k_scales is None and ex.cache.v_scales is None
    assert ex.cache.k_pages.dtype == ex.cache.compute_dtype
    for name in ("self_attn.q_proj.weight", "mlp.down_proj.weight"):
        assert not quant.is_quantized(ex.layers[name])


# -- PT_QUANT=none bit-parity under load --------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("variant", [
    "plain",
    "prefix",
    "spec",
    "async",
])
def test_none_load_parity(model, variant, monkeypatch):
    """The acceptance-criteria run: the seeded load on an undersized
    pool emits bit-identical PER-STEP maps with PT_QUANT=none set via
    env, via param, and left unset — per serving variant — with the
    refcount audit green after every step."""
    kw = _variant_kw(variant)
    monkeypatch.delenv("PT_QUANT", raising=False)
    _, h_def, _, steps_def = _drive_load(model, LOAD_SPEC, kw)
    monkeypatch.setenv("PT_QUANT", "none")
    _, h_env, _, steps_env = _drive_load(model, LOAD_SPEC, kw,
                                         check_invariants=True)
    monkeypatch.delenv("PT_QUANT")
    _, h_par, _, steps_par = _drive_load(
        model, LOAD_SPEC, dict(kw, quant="none"))
    assert steps_env == steps_def and steps_par == steps_def, variant
    for rid in h_def:
        assert h_env[rid].tokens == h_def[rid].tokens, (variant, rid)
        assert h_par[rid].tokens == h_def[rid].tokens, (variant, rid)
        assert h_env[rid].state == h_def[rid].state, (variant, rid)


# -- int8 under load ----------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("variant", [
    "plain",
    "prefix",
    "spec",
    "async",
])
def test_int8_load_drains_with_invariants(model, variant):
    """The int8 engine drains the same seeded loads — preemption,
    prefix COW/eviction, spec windows with rollback, async double
    buffering all over the quantized pool — with the refcount audit
    green after every step and every request terminal."""
    kw = dict(_variant_kw(variant), quant="int8")
    eng, handles, _, _ = _drive_load(model, LOAD_SPEC, kw,
                                     check_invariants=True)
    assert eng.executor.cache.k_pages.dtype == np.int8
    for rid, hd in handles.items():
        assert hd.state in (RequestState.FINISHED,
                            RequestState.TRUNCATED), (variant, rid)
        assert len(hd.tokens) > 0, (variant, rid)
    if "prefix" not in variant:
        assert eng.executor.free_pages == eng.executor.cache.num_pages


def test_int8_logit_drift_bound(model):
    """The accuracy side of the trade: int8 weights + int8 KV hold the
    prefill logits within a small relative RMS of the full-precision
    forward, and the greedy stream exists (drift never turns into NaN
    or a dead engine)."""
    import jax.numpy as jnp

    ex_n = ServingEngine(model, quant="none", **ENGINE_KW).executor
    ex_q = ServingEngine(model, quant="int8", **ENGINE_KW).executor
    rng = np.random.RandomState(5)
    worst = 0.0
    for _ in range(3):
        ids = jnp.asarray(rng.randint(1, 256, (1, 16)), jnp.int32)
        ln, _, _ = ex_n._jit_prefill(ex_n.layers, ex_n.tops, ids)
        lq, _, _ = ex_q._jit_prefill(ex_q.layers, ex_q.tops, ids)
        ln = np.asarray(ln, np.float64)
        lq = np.asarray(lq, np.float64)
        assert np.isfinite(lq).all()
        rel = (np.sqrt(np.mean((ln - lq) ** 2))
               / (np.sqrt(np.mean(ln ** 2)) + 1e-12))
        worst = max(worst, rel)
    assert worst < 0.05, worst


# -- COW on a quantized shared page -------------------------------------


def test_cow_copies_quantized_page_with_scale(model):
    """A shared int8 page diverging mid-page copies pages AND scales:
    the writer's copy requantizes independently while the cached
    original keeps serving the exact prefix stream."""
    rng = np.random.RandomState(9)
    common = rng.randint(1, 256, (14,)).astype(np.int32)
    pa = np.concatenate([common, rng.randint(1, 256, (4,))]) \
        .astype(np.int32)
    pb = np.concatenate([common, rng.randint(1, 256, (7,))]) \
        .astype(np.int32)

    def streams(quant_mode, prefix_cache):
        eng = ServingEngine(model, prefix_cache=prefix_cache,
                            quant=quant_mode, **ENGINE_KW)
        out = [eng.submit(p, max_new_tokens=8).result()
               for p in (pa, pb)]
        check_pool_invariants(eng.executor.cache, eng.prefix)
        return eng, out

    eng, warm = streams("int8", True)
    # prompt b extends the shared prefix mid-page -> one COW, and the
    # copied page carries its own scale row from the copy point on
    assert eng.executor.cache.cow_count >= 1
    assert eng.stats()["cached_tokens"] > 0
    _, cold = streams("int8", False)
    assert warm == cold  # the COW'd quantized page reads back exactly


# -- AOT warmup over the int8 pool --------------------------------------


@pytest.mark.slow
def test_aot_warmup_covers_int8_pool(model, tmp_path):
    """aot='warm' over a quantized build: every (program x rung) entry
    compiles against the (pages, scales) pool signature, nothing
    fails, and the warmed engine serves with zero post-warmup traces."""
    eng = ServingEngine(model, quant="int8", aot="warm",
                        prefill_chunk=8, compile_cache=str(tmp_path),
                        **ENGINE_KW)
    rep = eng._aot_report
    assert rep is not None and rep["entries"] > 0
    assert not rep["failed"], rep["failed"]
    traces_before = {n: p.traces
                     for n, p in eng.executor.programs.items()}
    want = ServingEngine(model, quant="int8", prefill_chunk=8,
                         **ENGINE_KW).submit(
        PROMPT, max_new_tokens=8).result()
    assert eng.submit(PROMPT, max_new_tokens=8).result() == want
    for n, p in eng.executor.programs.items():
        if p.dispatches:
            assert p.traces == traces_before[n], n  # warmed, no retrace


# -- fault matrix -------------------------------------------------------


@pytest.mark.parametrize("phase", [
    pytest.param("before", marks=pytest.mark.slow),
    "after",
])
def test_quant_pack_fault_fails_the_build(model, phase):
    """quant.pack fires during weight quantization at engine BUILD: the
    constructor raises (no half-quantized engine escapes), and a fresh
    build after disarm serves the exact stream."""
    want = ServingEngine(model, quant="int8", **ENGINE_KW).submit(
        PROMPT, max_new_tokens=8).result()
    faults.arm("quant.pack", phase, 2, "raise")
    with pytest.raises(faults.InjectedFault):
        ServingEngine(model, quant="int8", **ENGINE_KW)
    faults.reset()
    eng = ServingEngine(model, quant="int8", **ENGINE_KW)
    assert eng.submit(PROMPT, max_new_tokens=8).result() == want


@pytest.mark.parametrize("phase,point", [
    ("before", "quant.kv_write"),
    pytest.param("after", "quant.kv_write", marks=pytest.mark.slow),
    pytest.param("before", "quant.dequant", marks=pytest.mark.slow),
    pytest.param("after", "quant.dequant", marks=pytest.mark.slow),
])
def test_quant_fault_confined_to_one_request(model, point, phase):
    """An injected raise at the host-side quantized page write or the
    dequantizing gather lands inside the per-request bracket: the hit
    request fails ALONE (pages freed, audit green), every other stream
    is exact, and the engine accepts the same prompt again after."""
    kw = dict(ENGINE_KW, prefill_chunk=8, quant="int8")
    base = ServingEngine(model, **kw)
    want = {"a": base.submit(PROMPT, max_new_tokens=8,
                             rid="a").result(),
            "b": base.submit(PROMPT[:5], max_new_tokens=8,
                             rid="b").result()}
    faults.reset()
    faults.arm(point, phase, 1, "raise")
    eng = ServingEngine(model, **kw)
    ha = eng.submit(PROMPT, max_new_tokens=8, rid="a")
    hb = eng.submit(PROMPT[:5], max_new_tokens=8, rid="b")
    while eng.in_flight:
        assert eng.tick < 500
        eng.step()
        check_pool_invariants(eng.executor.cache)
    # the first prefill chunk hit the fault: request a fails alone...
    assert ha.state is RequestState.FAILED, (point, phase)
    assert hb.state is RequestState.FINISHED
    assert hb.tokens == want["b"], (point, phase)
    # ...its pages come back, and the engine serves the same prompt
    faults.reset()
    assert eng.submit(PROMPT, max_new_tokens=8).result() == want["a"]
    assert eng.executor.free_pages == eng.executor.cache.num_pages


# -- capacity arithmetic ------------------------------------------------


def test_pool_bytes_per_page_ratio(model):
    """The capacity multiplier comes from this layout math:
    int8 pages + f32 per-page scales must stay under 5/9 of the f32
    pool bytes (>= 1.8x pages at a fixed byte budget)."""
    bf = ServingEngine(model, quant="none", **ENGINE_KW)
    q8 = ServingEngine(model, quant="int8", **ENGINE_KW)
    bpp_f = quant.kv_pool_bytes_per_page(bf.executor.cache)
    bpp_q = quant.kv_pool_bytes_per_page(q8.executor.cache)
    assert bpp_f / bpp_q >= 1.8
