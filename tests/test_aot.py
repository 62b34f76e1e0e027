"""AOT compilation plane (core/aot.py, PT_AOT) — kill cold-start.

The acceptance contract, asserted end-to-end:

* warmup AOT-compiles every (program x shape-rung) pair EXACTLY once
  (the trace counters are the proof: lowering traces the counted body,
  disk deserialization and table hits never do);
* a warmed engine serves the seeded load — plain, prefix-cache,
  speculative and async-exec variants — with ZERO post-warmup traces
  and streams bit-identical to PT_AOT=off;
* a second process against the same cache dir resolves every entry
  from disk: zero compiles, zero traces, hits > 0;
* PT_AOT=off is the untouched legacy path (no ladder, no tables);
* PT_AOT=strict seals the programs — whole-prompt prefill and any
  un-warmed signature raise AotMissError instead of compiling
  mid-traffic;
* every aot.* fault point (lower / compile / cache) degrades to a
  failed warmup entry or a cache miss, never a dead engine.
"""
import json
import os
import pickle
import shutil
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import aot
from paddle_tpu.inference.server import RequestState, ServingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
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


@pytest.fixture(scope="module")
def cache_dir():
    d = tempfile.mkdtemp(prefix="pt-aot-test-")
    yield d
    shutil.rmtree(d, ignore_errors=True)


@pytest.fixture(scope="module")
def warm_engine(model, cache_dir):
    """The FIRST engine against the module cache dir: every plan entry
    compiles fresh and persists — later engines come off disk."""
    return ServingEngine(model, aot="warm", compile_cache=cache_dir,
                         **KW)


KW = dict(max_seqs=2, page_size=4, max_len=64, num_pages=11,
          prefill_chunk=8)

PROMPT = np.random.RandomState(2).randint(1, 256, (8,)).astype(np.int32)

LOAD_SPEC = LoadSpec(n_requests=8, mean_interarrival=2.0,
                     prompt_len=(4, 12), max_new=(6, 10), vocab=256,
                     seed=21, prefix_share=0.6, prefix_len=10,
                     prefix_pool=2, repeat_share=0.5, repeat_period=3)


def _traces(eng):
    return sum(p.traces for p in eng.executor.programs.values())


def _drive(eng, spec=LOAD_SPEC):
    """Replay the seeded load; returns {rid: handle}."""
    pending = sorted(generate_load(spec),
                     key=lambda w: (w["arrival_tick"], w["rid"]))
    handles = {}
    while pending or eng.in_flight:
        assert eng.tick < 3000, "load did not drain"
        while pending and pending[0]["arrival_tick"] <= eng.tick:
            w = pending.pop(0)
            handles[w["rid"]] = eng.submit(
                w["prompt_ids"], max_new_tokens=w["max_new_tokens"],
                rid=w["rid"])
        eng.step()
    return handles


@pytest.fixture(scope="module")
def plain_off(model):
    """The PT_AOT=off baseline streams for the seeded load."""
    eng = ServingEngine(model, aot="off", **KW)
    handles = _drive(eng)
    return {rid: (h.tokens, h.state) for rid, h in handles.items()}


# -- ladder / bucket units ----------------------------------------------


def test_ladder_pow2_and_floor_ceil():
    lad = aot.BucketLadder.pow2(8)
    assert lad.rungs == (1, 2, 4, 8)
    assert lad.floor(7) == 4 and lad.floor(8) == 8 and lad.floor(1) == 1
    assert lad.ceil(3) == 4 and lad.ceil(9) is None
    assert 4 in lad and 3 not in lad
    below = aot.BucketLadder((4, 8))
    assert below.floor(3) is None


def test_ladder_chunks_decompose_any_length():
    lad = aot.BucketLadder.pow2(8)
    for total in range(1, 64):
        out = lad.chunks(total)
        assert sum(out) == total
        assert all(c in lad for c in out)
        assert out == sorted(out, reverse=True)


def test_ladder_rejects_bad_rungs():
    with pytest.raises(ValueError, match="positive"):
        aot.BucketLadder([0, 4])
    with pytest.raises(ValueError, match="positive"):
        aot.BucketLadder([])
    with pytest.raises(ValueError, match="below the smallest"):
        aot.BucketLadder((4, 8)).chunks(6)


def test_page_buckets_cover():
    assert aot.page_buckets(14) == (0, 1, 2, 4, 8, 14)
    assert aot.page_buckets(16) == (0, 1, 2, 4, 8, 16)
    b = aot.page_buckets(14)
    assert aot.bucket_pages(0, b) == 0
    assert aot.bucket_pages(3, b) == 4
    assert aot.bucket_pages(14, b) == 14
    assert aot.bucket_pages(99, b) == 14  # capped at the budget


def test_signature_concrete_matches_sds():
    import jax
    import jax.numpy as jnp

    x = jnp.ones((4, 2), jnp.float32)
    sds = jax.ShapeDtypeStruct((4, 2), jnp.float32)
    assert aot.signature((x,), {}) == aot.signature((sds,), {})
    assert aot.signature((x,), {"n": 2}) != aot.signature((x,), {"n": 3})
    assert aot.signature((x,), {}) != aot.signature(
        (jnp.ones((4, 3), jnp.float32),), {})


def test_mode_env_gate(monkeypatch):
    monkeypatch.delenv("PT_AOT", raising=False)
    assert aot.mode() == "off"
    for m in aot.MODES:
        monkeypatch.setenv("PT_AOT", m)
        assert aot.mode() == m
    monkeypatch.setenv("PT_AOT", "eager")
    with pytest.raises(ValueError, match="PT_AOT"):
        aot.mode()


def test_cache_root_env(monkeypatch):
    monkeypatch.setenv("PT_CACHE_DIR", "/tmp/pt-root")
    monkeypatch.delenv("PT_COMPILE_CACHE", raising=False)
    assert aot.cache_root() == "/tmp/pt-root"
    assert aot.compile_cache_dir() == "/tmp/pt-root/compile"
    monkeypatch.setenv("PT_COMPILE_CACHE", "/tmp/pt-cc")
    assert aot.compile_cache_dir() == "/tmp/pt-cc"


def test_fault_points_registered():
    for point in ("aot.lower", "aot.compile", "aot.cache"):
        assert point in faults.REGISTERED


# -- CountedJit AOT table + persistent cache (unit) ---------------------


def _unit_prog(name="unit.double"):
    from paddle_tpu.analysis.audit import CountedJit

    return CountedJit(lambda x: x * 2.0, name=name)


def _sds(*shape):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.float32)


def test_aot_compile_then_zero_trace_dispatch(tmp_path):
    import jax.numpy as jnp

    cc = aot.CompileCache(str(tmp_path))
    prog = _unit_prog()
    assert prog.aot_compile((_sds(4),), cache=cc) == "compile"
    assert prog.traces == 1
    assert prog.aot_compile((_sds(4),), cache=cc) == "warm"
    x = jnp.ones((4,), jnp.float32)
    np.testing.assert_allclose(np.asarray(prog(x)), 2.0)
    assert prog.traces == 1 and prog.aot_hits == 1
    # off-table shape: falls back to plain jit (warm mode contract)
    y = jnp.ones((6,), jnp.float32)
    np.testing.assert_allclose(np.asarray(prog(y)), 2.0)
    assert prog.traces == 2 and prog.aot_misses == 1


def test_second_program_resolves_from_disk(tmp_path):
    import jax.numpy as jnp

    cc = aot.CompileCache(str(tmp_path))
    _unit_prog().aot_compile((_sds(4),), cache=cc)
    assert cc.stores == 1 and cc.bytes_written > 0
    # fresh program object, fresh cache handle = a new process's view
    cc2 = aot.CompileCache(str(tmp_path))
    prog2 = _unit_prog()
    assert prog2.aot_compile((_sds(4),), cache=cc2) == "disk"
    assert prog2.traces == 0
    np.testing.assert_allclose(
        np.asarray(prog2(jnp.ones((4,), jnp.float32))), 2.0)
    assert prog2.traces == 0 and cc2.hits == 1
    assert cc2.hit_rate == 1.0


def test_sealed_miss_raises(tmp_path):
    import jax.numpy as jnp

    prog = _unit_prog()
    with pytest.raises(ValueError, match="seal"):
        prog.seal()
    prog.aot_compile((_sds(4),))
    prog.seal()
    prog(jnp.ones((4,), jnp.float32))  # warmed shape still serves
    with pytest.raises(aot.AotMissError, match="un-warmed"):
        prog(jnp.ones((5,), jnp.float32))


def test_corrupt_entry_drops_and_recompiles(tmp_path):
    cc = aot.CompileCache(str(tmp_path))
    _unit_prog().aot_compile((_sds(4),), cache=cc)
    ents = cc.manifest()["entries"]
    assert len(ents) == 1
    fpath = os.path.join(str(tmp_path),
                         next(iter(ents.values()))["file"])
    with open(fpath, "wb") as f:
        f.write(b"not a pickle")
    cc2 = aot.CompileCache(str(tmp_path))
    prog2 = _unit_prog()
    assert prog2.aot_compile((_sds(4),), cache=cc2) == "compile"
    assert cc2.errors >= 1
    # dropped, then re-stored by the recompile
    assert len(cc2.manifest()["entries"]) == 1
    with open(os.path.join(
            str(tmp_path),
            next(iter(cc2.manifest()["entries"].values()))["file"]),
            "rb") as f:
        assert pickle.load(f)["cache_version"] == aot.CACHE_VERSION


def test_version_skewed_manifest_dropped(tmp_path):
    cc = aot.CompileCache(str(tmp_path))
    with open(os.path.join(str(tmp_path), "manifest.json"), "w") as f:
        json.dump({"version": 999, "entries": {"k": {}}}, f)
    assert cc.manifest()["entries"] == {}
    assert cc.errors >= 1


# -- engine warmup: every pair exactly once -----------------------------


def test_warmup_compiles_every_pair_exactly_once(warm_engine):
    rep = warm_engine._aot_report
    assert rep["entries"] > 0 and not rep["failed"]
    # fresh cache dir: everything compiled, nothing warm/disk
    assert rep["compile"] == rep["entries"]
    assert rep["disk"] == 0 and rep["warm"] == 0
    # lowering traces the counted body once per entry — the
    # exactly-once proof
    assert _traces(warm_engine) == rep["compile"]
    assert set(rep["programs"]) >= {"serve.prefill_chunk",
                                    "serve.decode",
                                    "serve.decode_async"}
    # idempotent re-warm (the checkpoint-restore hook): all warm
    rep2 = warm_engine.executor._aot_rewarm()
    assert rep2["warm"] == rep2["entries"]
    assert rep2["compile"] == 0 and rep2["disk"] == 0
    assert _traces(warm_engine) == rep["compile"]


def test_off_mode_is_untouched_legacy(model):
    eng = ServingEngine(model, aot="off", **KW)
    assert eng.aot_mode == "off"
    assert eng.compile_cache is None and eng._aot_report is None
    assert eng.executor.aot_ladder is None
    assert all(not p._exe for p in eng.executor.programs.values())


def test_engine_env_gate(model, cache_dir, warm_engine, monkeypatch):
    monkeypatch.setenv("PT_AOT", "warm")
    monkeypatch.setenv("PT_COMPILE_CACHE", cache_dir)
    eng = ServingEngine(model, **KW)
    assert eng.aot_mode == "warm"
    assert eng._aot_report["disk"] == eng._aot_report["entries"]
    monkeypatch.setenv("PT_AOT", "bogus")
    with pytest.raises(ValueError, match="PT_AOT"):
        ServingEngine(model, **KW)
    # explicit param forces over env
    monkeypatch.setenv("PT_AOT", "strict")
    eng2 = ServingEngine(model, aot="off", **KW)
    assert eng2.aot_mode == "off"
    assert eng2.executor.aot_ladder is None


# -- zero post-warmup traces + bit-parity under load --------------------


@pytest.mark.parametrize("variant", [
    "plain",
    pytest.param("prefix", marks=pytest.mark.slow),
    pytest.param("spec", marks=pytest.mark.slow),
    pytest.param("async", marks=pytest.mark.slow),
])
def test_warmed_load_zero_traces_and_parity(model, cache_dir,
                                            warm_engine, plain_off,
                                            variant):
    kw = dict(KW)
    if variant == "prefix":
        kw["prefix_cache"] = True
    if variant == "spec":
        kw["spec_decode"] = "ngram"
    if variant == "async":
        kw["async_exec"] = True
    if variant == "plain":
        eng, want = warm_engine, plain_off
    else:
        off = ServingEngine(model, aot="off", **kw)
        want = {rid: (h.tokens, h.state)
                for rid, h in _drive(off).items()}
        eng = ServingEngine(model, aot="warm", compile_cache=cache_dir,
                            **kw)
    t0 = _traces(eng)
    handles = _drive(eng)
    assert _traces(eng) == t0, f"{variant}: post-warmup trace"
    for rid, (tokens, state) in want.items():
        assert handles[rid].tokens == tokens, (variant, rid)
        assert handles[rid].state == state, (variant, rid)
    # whole prompts ride the ladder: serve.prefill never dispatches
    assert eng.executor.programs["prefill"].dispatches == 0
    if variant == "prefix":
        s = eng.stats()
        assert s["preemptions"] > 0, "load must exercise preemption"
    if variant == "spec":
        assert "serve.verify" in eng._aot_report["programs"]


def test_whole_prompt_routes_through_ladder(model, cache_dir,
                                            warm_engine):
    """No prefill_chunk configured: under a ladder the scheduler still
    decomposes whole prompts into rungs (serve.prefill has an
    unboundable [1, S] shape), bit-identical to the legacy path."""
    kw = {k: v for k, v in KW.items() if k != "prefill_chunk"}
    base = ServingEngine(model, aot="off", **kw)
    want = base.submit(PROMPT, max_new_tokens=6).result()
    assert base.executor.programs["prefill"].dispatches > 0
    eng = ServingEngine(model, aot="warm", compile_cache=cache_dir,
                        **kw)
    t0 = _traces(eng)
    assert eng.submit(PROMPT, max_new_tokens=6).result() == want
    assert _traces(eng) == t0
    assert eng.executor.programs["prefill"].dispatches == 0
    assert eng.executor.programs["prefill_chunk"].dispatches > 0


def test_decode_n_rungs_warmed(model, cache_dir, warm_engine):
    eng = ServingEngine(model, aot="warm", compile_cache=cache_dir,
                        decode_n_steps=(2,), **KW)
    rep = eng._aot_report
    assert rep["programs"].get("serve.decode_n") == KW["max_seqs"]
    assert not rep["failed"]


# -- second process: everything from disk -------------------------------


_WORKER = r"""
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.inference.server import ServingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

paddle.seed(11)
cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=128)
eng = ServingEngine(LlamaForCausalLM(cfg), max_seqs=2, page_size=4,
                    max_len=64, num_pages=11, prefill_chunk=8,
                    aot="warm", compile_cache=sys.argv[1])
rep = eng._aot_report
prompt = np.random.RandomState(2).randint(1, 256, (8,)).astype(np.int32)
tokens = eng.submit(prompt, max_new_tokens=6).result()
print(json.dumps({
    "compile": rep["compile"], "disk": rep["disk"],
    "entries": rep["entries"],
    "traces": sum(p.traces for p in eng.executor.programs.values()),
    "hits": eng.compile_cache.hits, "tokens": tokens}))
"""


def test_second_process_reuses_cache(model, cache_dir, warm_engine):
    base = ServingEngine(model, aot="off", **KW)
    want = base.submit(PROMPT, max_new_tokens=6).result()
    proc = subprocess.run(
        [sys.executable, "-c", _WORKER, cache_dir],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PT_FAULTS": ""})
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["compile"] == 0, "second process must not compile"
    assert out["disk"] == out["entries"] > 0
    assert out["traces"] == 0, "second process must not trace"
    assert out["hits"] >= out["entries"]
    assert out["tokens"] == want


# -- strict mode --------------------------------------------------------


def test_strict_serves_sealed_from_disk(model, cache_dir, warm_engine,
                                        plain_off):
    eng = ServingEngine(model, aot="strict", compile_cache=cache_dir,
                        **KW)
    rep = eng._aot_report
    assert rep["disk"] == rep["entries"] and rep["compile"] == 0
    assert _traces(eng) == 0
    handles = _drive(eng)
    assert _traces(eng) == 0
    assert sum(p.aot_misses
               for p in eng.executor.programs.values()) == 0
    for rid, (tokens, state) in plain_off.items():
        assert handles[rid].tokens == tokens
        assert handles[rid].state == state
    # sealed: the un-warmable whole-prompt program refuses to run
    with pytest.raises(aot.AotMissError, match="prefill"):
        eng.executor.prefill(0, np.arange(1, 6, dtype=np.int32))
    # engine still serviceable after the refused call
    h = eng.submit(PROMPT, max_new_tokens=4)
    eng.run()
    assert h.state is RequestState.FINISHED


def test_seal_requires_warmup(model):
    eng = ServingEngine(model, aot="off", **KW)
    with pytest.raises(ValueError, match="aot_warmup"):
        eng.executor.seal()


# -- fault points: warmup and cache must degrade, never die -------------


@pytest.mark.parametrize("point", ["aot.lower", "aot.compile"])
@pytest.mark.parametrize("phase", ["before", "after"])
def test_warmup_fault_fails_only_that_entry(model, point, phase):
    eng = ServingEngine(model, aot="off", **KW)
    faults.arm(point, phase, 1, "raise")
    with tempfile.TemporaryDirectory() as d:
        cc = aot.CompileCache(d)
        rep = eng.executor.aot_warmup(
            prefill_chunk=8, compile_cache=cc,
            ladder=aot.BucketLadder((8,)))
    assert len(rep["failed"]) == 1, (point, phase)
    assert rep["compile"] == rep["entries"] - 1
    faults.reset()
    # the engine is warmed (ladder armed) and serves; the failed entry
    # falls back to plain jit on first dispatch
    h = eng.submit(PROMPT, max_new_tokens=6)
    eng.run()
    assert h.state is RequestState.FINISHED
    assert len(h.tokens) == 6


@pytest.mark.parametrize("phase", ["before", "after"])
def test_cache_fault_degrades_to_recompile(model, cache_dir,
                                           warm_engine, phase):
    eng = ServingEngine(model, aot="off", **KW)
    cc = aot.CompileCache(cache_dir)
    faults.arm("aot.cache", phase, 1, "raise")
    rep = eng.executor.aot_warmup(prefill_chunk=8, compile_cache=cc)
    assert not rep["failed"], phase
    # the faulted entry degraded to a miss and recompiled; the rest
    # came off disk
    assert rep["compile"] == 1 and rep["disk"] == rep["entries"] - 1
    assert cc.errors >= 1
    # the recompile re-stored it: the manifest is whole again
    assert cc.statusz()["entries"] >= rep["entries"]
    faults.reset()
    h = eng.submit(PROMPT, max_new_tokens=4)
    eng.run()
    assert h.state is RequestState.FINISHED


# -- checkpoint restore re-warms ----------------------------------------


def test_ckpt_restore_rewarm_hook(tmp_path, warm_engine):
    import jax.numpy as jnp

    from paddle_tpu.distributed.ckpt_commit import CheckpointManager

    calls = []
    mgr = CheckpointManager(
        str(tmp_path), world_size=1, rank=0,
        aot_warmup=lambda: calls.append(
            warm_engine.executor._aot_rewarm()))
    sd = {"w": jnp.ones((2, 2))}
    mgr.save(sd, step=1)
    mgr.wait()
    assert mgr.load({"w": jnp.zeros((2, 2))}) == 1
    assert len(calls) == 1
    assert calls[0]["warm"] == calls[0]["entries"] > 0


def test_ckpt_restore_default_sweep(tmp_path, model, cache_dir,
                                    warm_engine, monkeypatch):
    """No explicit hook: load() sweeps the registered program
    contracts' aot hooks when PT_AOT != off (and must swallow any
    hook failure)."""
    import jax.numpy as jnp

    from paddle_tpu import analysis
    from paddle_tpu.distributed.ckpt_commit import CheckpointManager

    # a fresh warm engine registers its contracts last, so the sweep
    # resolves ITS hook deterministically
    eng = ServingEngine(model, aot="warm", compile_cache=cache_dir,
                        **KW)
    out = analysis.aot_warmup()
    reps = [r for r in out.values() if isinstance(r, dict)]
    assert reps and any(r.get("warm") == r.get("entries") > 0
                        for r in reps)
    mgr = CheckpointManager(str(tmp_path), world_size=1, rank=0)
    sd = {"w": jnp.ones((2,))}
    mgr.save(sd, step=3)
    mgr.wait()
    monkeypatch.setenv("PT_AOT", "warm")
    assert mgr.load({"w": jnp.zeros((2,))}) == 3
    assert eng.executor.aot_ladder is not None
