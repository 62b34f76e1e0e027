"""Performance introspection plane: analytical jaxpr cost model with
exact FLOP/byte counts, model-vs-cost-model FLOP agreement, StepTimer
phase breakdown on the logical clock, the throttled HBM watermark
sample, Perfetto counter tracks, and the PT_OBS=off bit-parity contract
with the perf layer wired.

Same conventions as test_obs.py: everything runs on
:class:`obs.LogicalClock`, and producers cache ``obs.handle()`` at
construction so every on-path test configures the plane BEFORE building
the engine / train step under test.
"""
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import analysis, obs
from paddle_tpu.analysis import (
    CostReport, estimate_cost, estimate_fn_cost,
    transformer_flops_per_token,
)
from paddle_tpu.inference.server import ServingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.training import CompiledTrainStep
from paddle_tpu.obs import perf
from paddle_tpu.obs.trace import LogicalClock
from paddle_tpu.testing import faults
from paddle_tpu.testing.load import LoadSpec, generate_load, run_load

f32 = jnp.float32


def _sds(*shape, dtype=f32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    obs.reset()
    yield
    faults.reset()
    obs.reset()


def _on(**kw):
    kw.setdefault("clock", LogicalClock())
    return obs.configure(mode="on", **kw)


# -- cost model: exact FLOP / byte counts -------------------------------------

def test_dot_general_exact_counts():
    # (4,8) @ (8,16): 2·4·16·8 = 1024 FLOPs, f32 operands 640 B in,
    # (4,16) f32 out 256 B.
    rep = estimate_fn_cost(lambda a, b: a @ b, _sds(4, 8), _sds(8, 16))
    assert rep.flops == 1024
    assert rep.matmul_flops == 1024
    assert rep.conv_flops == 0
    assert rep.elementwise_flops == 0
    assert rep.bytes_in == 640
    assert rep.bytes_out == 256
    assert rep.hbm_bytes == (rep.bytes_in + rep.bytes_out
                             + rep.bytes_peak_intermediate)
    assert rep.arithmetic_intensity == rep.flops / rep.hbm_bytes
    assert rep.by_primitive == {"dot_general": 1024}


def test_mlp_decomposes_into_matmul_and_elementwise():
    # x(2,4)·W1(4,8)+b1 -> max(.,0) -> ·W2(8,4)+b2:
    # matmul 128+128, add 16+8, max 16 => 296 total.
    def mlp(x, w1, b1, w2, b2):
        h = jnp.maximum(x @ w1 + b1, 0.0)
        return h @ w2 + b2

    rep = estimate_fn_cost(mlp, _sds(2, 4), _sds(4, 8), _sds(8),
                           _sds(8, 4), _sds(4))
    assert rep.matmul_flops == 256
    assert rep.elementwise_flops == 40
    assert rep.flops == 296
    assert rep.by_primitive == {"add": 24, "dot_general": 256, "max": 16}


def test_reduction_counts_input_elements():
    rep = estimate_fn_cost(lambda x: jnp.sum(x), _sds(4, 8))
    assert rep.by_primitive.get("reduce_sum") == 32
    assert rep.elementwise_flops == 32


def test_scan_multiplies_body_by_trip_count():
    # 2-step scan, body (4,)@(4,4) = 32 FLOPs/step => 64 total.
    w = jnp.zeros((4, 4), f32)

    def f(x):
        def body(carry, _):
            return (carry @ w).astype(f32), None

        out, _ = jax.lax.scan(body, x, None, length=2)
        return out

    rep = estimate_fn_cost(f, _sds(4))
    assert rep.matmul_flops == 64
    assert rep.flops == 64


def test_cond_prices_worst_branch():
    # (4,4)@(4,4) = 128 FLOPs on one branch, identity on the other.
    w = jnp.zeros((4, 4), f32)

    def f(pred, x):
        return jax.lax.cond(pred,
                            lambda v: (v @ w).astype(f32),
                            lambda v: v, x)

    rep = estimate_fn_cost(f, _sds(dtype=jnp.bool_), _sds(4, 4))
    assert rep.matmul_flops == 128
    assert rep.flops == 128


def test_pjit_subjaxpr_recursion():
    rep = estimate_fn_cost(jax.jit(lambda a, b: a @ b),
                           _sds(4, 8), _sds(8, 16))
    assert rep.flops == 1024


def test_shard_map_subjaxpr_recursion():
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    f = jax.shard_map(lambda a, b: a @ b, mesh=mesh,
                  in_specs=(P(), P()), out_specs=P())
    rep = estimate_fn_cost(f, _sds(4, 8), _sds(8, 16))
    assert rep.flops == 1024


def test_estimate_cost_rejects_non_jaxpr():
    with pytest.raises(TypeError):
        estimate_cost({"not": "a jaxpr"})


def test_report_asdict_carries_derived_fields():
    rep = estimate_fn_cost(lambda a, b: a @ b, _sds(4, 8), _sds(8, 16))
    d = rep.asdict()
    assert d["hbm_bytes"] == rep.hbm_bytes
    assert d["arithmetic_intensity"] == round(rep.arithmetic_intensity, 4)
    assert "CostReport" in str(rep)


# -- model-vs-cost-model agreement --------------------------------------------

def test_transformer_flops_closed_form():
    assert transformer_flops_per_token(10, 2, 4, 8) == 6 * 10 + 12 * 2 * 4 * 8


def test_llama_flops_per_token_matches_cost_model_home(model):
    # model.flops_per_token must agree with the single formula home in
    # analysis.cost to the digit.
    cfg = model.config
    n = model.num_params()
    for seq in (16, 512):
        want = (6 * n + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq)
        assert model.flops_per_token(seq) == want
        assert transformer_flops_per_token(
            n, cfg.num_hidden_layers, cfg.hidden_size, seq) == want


# -- ProgramContract.cost(): every hot program priced -------------------------

def test_registered_programs_carry_cost_reports(model):
    step = CompiledTrainStep(model, lr=1e-3)
    ids = np.random.RandomState(0).randint(
        0, 256, (2, 16)).astype(np.int64)
    step.step(ids, ids)
    eng = ServingEngine(model, prefill_chunk=8, max_seqs=2, page_size=4,
                        max_len=64)
    reg = analysis.registered()
    for name in ("train.step", "train.guarded_step", "serve.prefill",
                 "serve.prefill_chunk", "serve.decode", "serve.decode_n",
                 "serve.verify"):
        assert name in reg, f"{name} not registered"
        cost = reg[name].cost()
        assert isinstance(cost, CostReport), name
        assert cost.flops > 0 and cost.hbm_bytes > 0, name
        assert reg[name].cost() is cost, f"{name} cost not cached"
    del eng, step


def test_program_cost_unknown_is_none():
    assert perf.program_cost("no.such.program") is None


# -- StepTimer on the logical clock -------------------------------------------

def test_steptimer_phase_breakdown_exact():
    h = _on(clock=LogicalClock(tick=1.0))
    t = perf.StepTimer("demo.step")
    with t.phase("data_wait"):
        pass
    with t.phase("compute"):
        pass
    assert t.phase_seconds() == {"data_wait": 1.0, "compute": 1.0}
    out = t.end_step()
    assert out == {"data_wait": 1.0, "compute": 1.0}
    assert t.phase_seconds() == {}           # accumulators reset
    samples = h.registry.snapshot()["step_phase_seconds"]["samples"]
    got = {s["labels"]["phase"]: s["value"] for s in samples
           if s["labels"]["program"] == "demo.step"}
    assert got == {"data_wait": 1.0, "compute": 1.0}


def test_steptimer_is_noop_when_obs_off():
    t = perf.StepTimer()
    with t.phase("compute"):
        pass
    assert t.phase_seconds() == {}
    assert t.end_step() == {}


# -- HBM watermarks: the one device reading the plane takes ------------------

def test_train_step_samples_hbm_watermarks_and_no_rate(model):
    h = _on()
    step = CompiledTrainStep(model, lr=1e-3)
    ids = np.random.RandomState(0).randint(
        0, 256, (2, 16)).astype(np.int64)
    for _ in range(2):
        step.step(ids, ids)
    prom = h.registry.prometheus_text()
    for fam in ("hbm_peak_bytes", "hbm_bytes_in_use", "hbm_bytes_limit"):
        assert fam in prom
    # no rate or share from a dispatch's host wall time
    assert "program_mfu" not in prom and "roofline_bound" not in prom
    tracks = [s for s in h.tracer.spans
              if s.ph == "C" and s.name == "perf.hbm_bytes"]
    assert len(tracks) == 1                 # step 1 sampled, step 2 not
    # the throttle is per program: every HBM_SAMPLE_EVERY-th call
    took = [perf.sample_hbm("demo.step") is not None
            for _ in range(perf.HBM_SAMPLE_EVERY + 1)]
    assert took == [True] + [False] * (perf.HBM_SAMPLE_EVERY - 1) + [True]


# -- chrome trace: counter tracks + thread metadata ---------------------------

def test_chrome_export_counter_tracks_and_thread_names():
    h = _on()
    h.tracer.counter("perf.step_phases", cat="perf", demo=0.5)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "trace.json")
        h.tracer.export_chrome(path)
        doc = json.loads(open(path).read())
    evs = doc["traceEvents"]
    counters = [e for e in evs if e.get("ph") == "C"]
    assert counters and counters[0]["name"] == "perf.step_phases"
    assert counters[0]["args"] == {"demo": 0.5}
    threads = {e["args"]["name"] for e in evs
               if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert {"train", "serving"} <= threads


# -- PT_OBS=off bit-parity with the perf layer wired --------------------------

LOAD_SPEC = dict(n_requests=6, mean_interarrival=2.0, prompt_len=(4, 20),
                 max_new=(3, 8), vocab=256, seed=7)
LOGICAL_STATS = ("steps", "requests", "preemptions", "decode_tokens",
                 "prefill_tokens", "batch_occupancy", "page_utilization",
                 "queue_wait_steps_p50", "ttft_steps_p50")


def _seeded_load(model):
    eng = ServingEngine(model, prefill_chunk=8, max_seqs=2, page_size=4,
                        max_len=64)
    work = generate_load(LoadSpec(**LOAD_SPEC))
    res = run_load(eng, work)
    toks = {w["rid"]: res["handles"][w["rid"]].tokens for w in work}
    return (toks, {k: res["stats"][k] for k in LOGICAL_STATS},
            res["stats"])


def test_off_path_is_bit_identical_with_perf_wired(model):
    toks_off, stats_off, raw_off = _seeded_load(model)
    _on()
    toks_on, stats_on, raw_on = _seeded_load(model)
    assert toks_on == toks_off
    assert stats_on == stats_off
    # stats() is the same dict on both paths: no host-clock roofline
    assert "roofline" not in raw_off and "roofline" not in raw_on
