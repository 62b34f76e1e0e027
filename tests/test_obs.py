"""Unified telemetry plane: registry semantics, trace-ID propagation,
flight-recorder bound + crash dumps, and the PT_OBS=off parity contract.

Everything runs on :class:`obs.LogicalClock` — timestamps, durations
and histogram percentiles are exact, never wall-time-flaky.  Producers
cache ``obs.handle()`` at construction, so every test configures the
plane BEFORE building the engine / train step under test.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, obs
from paddle_tpu.distributed.ckpt_commit import CheckpointManager
from paddle_tpu.inference.server import RequestState, ServingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.training import CompiledTrainStep
from paddle_tpu.obs.flight import FlightRecorder
from paddle_tpu.obs.registry import MetricRegistry
from paddle_tpu.obs.trace import LogicalClock, Tracer
from paddle_tpu.testing import faults
from paddle_tpu.testing.load import LoadSpec, generate_load, run_load
from paddle_tpu.training import (
    GuardedTrainStep, GuardianAbort, GuardianPolicy,
)


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


ENGINE_KW = dict(max_seqs=2, page_size=4, max_len=64)


def _prompts(seed, lens):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 256, (n,)).astype(np.int32) for n in lens]


# -- metric registry ----------------------------------------------------------

def test_counter_gauge_semantics():
    r = MetricRegistry()
    c = r.counter("reqs_total", "requests")
    c.inc()
    c.inc(3)
    g = r.gauge("occupancy")
    g.set(5)
    g.dec(2)
    snap = r.snapshot()
    assert snap["reqs_total"]["samples"][0]["value"] == 4
    assert snap["occupancy"]["samples"][0]["value"] == 3
    with pytest.raises(ValueError):
        c.inc(-1)


def test_labelled_family_and_redeclare():
    r = MetricRegistry()
    fam = r.counter("faults_total", "by point", labels=("point",))
    fam.labels(point="serve.step").inc()
    fam.labels(point="serve.step").inc()
    fam.labels(point="ckpt.commit").inc()
    # idempotent redeclare returns the same family
    assert r.counter("faults_total", labels=("point",)) is fam
    # conflicting redeclare (different type) is an error
    with pytest.raises(ValueError):
        r.gauge("faults_total")
    # unknown label key is an error
    with pytest.raises(ValueError):
        fam.labels(monitor="x")
    text = r.prometheus_text()
    assert '# TYPE faults_total counter' in text
    assert 'faults_total{point="serve.step"} 2' in text
    assert 'faults_total{point="ckpt.commit"} 1' in text


def test_histogram_exposition_is_cumulative():
    r = MetricRegistry()
    h = r.histogram("wait_s", "queue wait", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    text = r.prometheus_text()
    assert 'wait_s_bucket{le="1"} 1' in text
    assert 'wait_s_bucket{le="2"} 2' in text
    assert 'wait_s_bucket{le="4"} 3' in text
    assert 'wait_s_bucket{le="+Inf"} 4' in text
    assert "wait_s_count 4" in text
    assert "wait_s_sum 105" in text


def test_prometheus_text_deterministic_ordering():
    def build(order):
        r = MetricRegistry()
        for name in order:
            r.counter(name).inc()
        fam = r.counter("z_lbl", labels=("b", "a"))
        fam.labels(b="2", a="1").inc()
        return r.prometheus_text()

    # family insertion order must not leak into the exposition
    assert build(["b_total", "a_total"]) == build(["a_total", "b_total"])
    assert 'z_lbl{a="1",b="2"} 1' in build(["a_total"])


# -- logical clock / tracer ---------------------------------------------------

def test_logical_clock_is_exact():
    clk = LogicalClock(start=0.0, tick=0.001)
    assert clk() == pytest.approx(0.001)
    assert clk() == pytest.approx(0.002)
    t = Tracer(clock=clk, annotate=False)
    with t.span("unit", cat="host"):
        pass
    (sp,) = t.spans
    # one read on enter, one on exit: dur is exactly one tick
    assert sp.dur == pytest.approx(0.001)


def test_tracer_ring_is_bounded():
    t = Tracer(clock=LogicalClock(), capacity=3, annotate=False)
    for i in range(5):
        t.instant(f"e{i}")
    assert len(t.spans) == 3
    assert t.dropped == 2
    assert [s.name for s in t.spans] == ["e2", "e3", "e4"]


def test_chrome_export_schema(tmp_path):
    t = Tracer(clock=LogicalClock(), annotate=False)
    with t.span("work", cat="serve", trace_id="r1", tick=3):
        t.instant("mark", cat="serve", trace_id="r1")
    path = str(tmp_path / "trace.json")
    t.export_chrome(path)
    doc = json.loads(open(path).read())
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M"                    # process_name meta
    phx = [e for e in evs if e["ph"] == "X"]
    phi = [e for e in evs if e["ph"] == "i"]
    assert phx and phi
    assert phx[0]["name"] == "work"
    assert phx[0]["args"]["trace_id"] == "r1"
    assert phx[0]["tid"] == 1                     # serve lane
    assert phx[0]["ts"] >= 0 and phx[0]["dur"] >= 1  # microseconds


# -- flight recorder ----------------------------------------------------------

def test_flight_ring_bound_and_seq():
    fr = FlightRecorder(clock=LogicalClock(), capacity=4)
    for i in range(10):
        fr.record("tick", i=i)
    assert len(fr) == 4
    seqs = [e["seq"] for e in fr.events()]
    assert seqs == [7, 8, 9, 10]                  # monotonic past wrap
    lines = fr.dump(reason="unit").splitlines()
    head = json.loads(lines[0])["flight_recorder"]
    assert head["reason"] == "unit"
    assert head["total_events"] == 10
    assert head["dumped"] == 4
    assert [json.loads(ln)["i"] for ln in lines[1:]] == [6, 7, 8, 9]


def test_dump_on_guardian_abort(tmp_path, monkeypatch):
    monkeypatch.setenv("PT_OBS_DUMP_DIR", str(tmp_path / "dumps"))
    h = _on()

    class _Reg(nn.Layer):
        def __init__(self):
            super().__init__()
            self.l1 = nn.Linear(8, 16)
            self.l2 = nn.Linear(16, 1)

        def forward(self, x, y):
            d = self.l2(paddle.tanh(self.l1(x))) - y
            return (d * d).mean()

    paddle.seed(0)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), world_size=1, rank=0)
    g = GuardedTrainStep(
        CompiledTrainStep(_Reg(), lr=1e-2), manager=mgr,
        policy=GuardianPolicy(window=8, min_history=4, skip_budget=1,
                              rollback_budget=1))

    def _batch(i):
        rng = np.random.RandomState(1000 + i)
        return (rng.randn(4, 8).astype(np.float32),
                rng.randn(4, 1).astype(np.float32))

    for i in range(3):
        g.step(*_batch(i + 1))
    faults.reset("guard.nan_loss:before:*=inject")
    with pytest.raises(GuardianAbort):
        for _ in range(8):
            g.step(*_batch(g.global_step + 1))

    # crash path dumped the ring: in-memory text + one file per dump
    assert h.recorder.dumps >= 1
    kinds = [e["kind"] for e in h.recorder.events()]
    assert "guardian.skip" in kinds
    assert "guardian.rollback" in kinds
    assert kinds[-1] == "guardian.abort"
    seqs = [e["seq"] for e in h.recorder.events()]
    assert seqs == sorted(seqs)
    text = h.recorder.last_dump
    assert '"guardian.abort"' in text
    files = os.listdir(tmp_path / "dumps")
    assert any(f.startswith("flight-") and f.endswith(".jsonl")
               for f in files)
    prom = h.registry.prometheus_text()
    assert "guardian_aborts_total 1" in prom
    assert "guardian_skips_total" in prom
    assert "guardian_rollbacks_total" in prom


# -- serving integration: trace IDs across the lifecycle ----------------------

def test_trace_ids_span_preemption(model):
    """One request's trace ID must thread submit -> admit -> prefill ->
    preempt -> re-admit -> prefill -> finish, and the preemption must
    land in both the flight ring and the metric registry."""
    h = _on()
    eng = ServingEngine(model, num_pages=8, **ENGINE_KW)
    handles = [eng.submit(p, max_new_tokens=8)
               for p in _prompts(1, (7, 13, 21))]
    stats = eng.run()
    assert stats["preemptions"] >= 1
    assert all(hd.state is RequestState.FINISHED for hd in handles)

    victim = next(hd for hd in handles if hd.num_preemptions >= 1)
    names = [s.name for s in h.tracer.spans
             if s.args.get("trace_id") == victim.rid]
    assert names[0] == "req.submit"
    assert names[-1] == "req.finish"
    i_pre = names.index("req.preempt")
    # admitted+prefilled before the preemption, and again after it
    assert "req.admit" in names[:i_pre]
    assert "req.prefill" in names[:i_pre]
    assert "req.admit" in names[i_pre:]
    assert "req.prefill" in names[i_pre:]
    # re-admission is marked as a resume
    admits = [s for s in h.tracer.spans
              if s.name == "req.admit"
              and s.args.get("trace_id") == victim.rid]
    assert admits[-1].args["resume"] == 1

    kinds = [e["kind"] for e in h.recorder.events()]
    assert "serve.preempt" in kinds
    prom = h.registry.prometheus_text()
    assert "serve_preemptions_total" in prom
    assert "serve_requests_submitted_total 3" in prom
    assert "serve_ttft_steps_bucket" in prom
    assert "jit_traces_total{" in prom
    assert "jit_dispatches_total{" in prom


def test_spec_rollback_traced(model):
    """Rejected draft windows leave per-request rollback marks in the
    trace and the registry counts proposals vs acceptances."""
    h = _on()
    eng = ServingEngine(model, spec_decode="ngram", **ENGINE_KW)
    prompt = np.tile(np.random.RandomState(2)
                     .randint(1, 256, (4,)).astype(np.int32), 6)
    hd = eng.submit(prompt, max_new_tokens=12)
    eng.run()
    assert hd.state is RequestState.FINISHED
    m = eng.metrics
    assert m.draft_proposed > 0
    assert m.draft_accepted < m.draft_proposed   # rejections happened
    rolls = [s for s in h.tracer.spans if s.name == "req.spec_rollback"]
    assert rolls and all(s.args["trace_id"] == hd.rid for s in rolls)
    assert any(e["kind"] == "spec.rollback" for e in h.recorder.events())
    prom = h.registry.prometheus_text()
    assert "serve_draft_proposed_total" in prom
    assert "serve_draft_accepted_total" in prom


def test_request_failure_dumps_flight(model, monkeypatch, tmp_path):
    monkeypatch.setenv("PT_OBS_DUMP_DIR", str(tmp_path))
    h = _on()
    eng = ServingEngine(model, **ENGINE_KW)
    faults.arm("serve.request", "before", 1, "raise")
    bad = eng.submit(_prompts(3, (9,))[0], max_new_tokens=4)
    eng.run()
    assert bad.state is RequestState.FAILED
    assert any(e["kind"] == "serve.request_failed"
               for e in h.recorder.events())
    assert h.recorder.dumps == 1
    assert f"request-failed-{bad.rid}" in h.recorder.last_dump
    assert os.listdir(tmp_path)                   # file dump landed


# -- PT_OBS=off parity --------------------------------------------------------

LOAD_SPEC = dict(n_requests=6, mean_interarrival=2.0,
                 prompt_len=(4, 20), max_new=(3, 8), vocab=256, seed=7)
LOGICAL_STATS = ("steps", "requests", "preemptions", "decode_tokens",
                 "prefill_tokens", "batch_occupancy", "page_utilization",
                 "queue_wait_steps_p50", "ttft_steps_p50")


def _seeded_load(model):
    eng = ServingEngine(model, prefill_chunk=8, **ENGINE_KW)
    work = generate_load(LoadSpec(**LOAD_SPEC))
    res = run_load(eng, work)
    return ({w["rid"]: res["handles"][w["rid"]].tokens for w in work},
            {k: res["stats"][k] for k in LOGICAL_STATS})


def test_off_path_is_bit_identical(model):
    """The telemetry plane must never perturb computation: token
    streams and logical-clock stats match exactly with obs on vs off."""
    obs.configure(mode="off")
    toks_off, stats_off = _seeded_load(model)
    _on()
    toks_on, stats_on = _seeded_load(model)
    assert toks_on == toks_off
    assert stats_on == stats_off


def test_off_handle_costs_nothing():
    """PT_OBS off: no operator plane is built, by recording either; the
    span still lands in the one tracer (tests/test_obs_spans.py holds
    its cost to a count per step)."""
    obs.configure(mode="off")
    assert obs.handle() is None
    assert not obs.enabled()
    assert obs.dump() is None
    with obs.span("x") as sp:
        sp.set(a=1)
    (s,) = obs.tracer().spans
    assert (s.name, s.args, s.parent) == ("x", {"a": 1}, None)
    assert obs.handle() is None and obs.tracer() is obs.tracer()


def test_env_gate_rejects_bogus(monkeypatch):
    monkeypatch.setenv("PT_OBS", "banana")
    obs.reset()
    with pytest.raises(ValueError, match="PT_OBS"):
        obs.handle()


# -- serviceability fault points ----------------------------------------------

def test_obs_dump_fault_point():
    _on()
    obs.event("unit", i=1)
    faults.arm("obs.dump", "before", 1, "raise")
    with pytest.raises(faults.InjectedFault):
        obs.dump(reason="unit")
    # one-shot: the next dump goes through
    assert '"unit"' in obs.dump(reason="unit")


def test_obs_export_fault_point(tmp_path):
    h = _on()
    h.tracer.instant("unit")
    faults.arm("obs.export", "before", 1, "raise")
    with pytest.raises(faults.InjectedFault):
        h.tracer.export_chrome(str(tmp_path / "t.json"))
    h.tracer.export_chrome(str(tmp_path / "t.json"))
    assert json.loads(open(tmp_path / "t.json").read())["traceEvents"]


def test_faults_journal_into_flight():
    """Every tripped fault point self-journals: the ring and the
    per-point counter both see it."""
    h = _on()
    faults.arm("serve.step", "before", 1, "raise")
    with pytest.raises(faults.InjectedFault):
        faults.fire("serve.step", "before")
    evs = [e for e in h.recorder.events() if e["kind"] == "fault.fired"]
    assert evs and evs[-1]["point"] == "serve.step"
    assert ('fault_fired_total{point="serve.step"} 1'
            in h.registry.prometheus_text())


# -- profiler export round-trip (satellite) -----------------------------------

def test_profiler_export_roundtrip(tmp_path):
    from paddle_tpu import profiler

    prof = profiler.Profiler(timer_only=True)
    prof.start()
    x = paddle.to_tensor(np.random.randn(16, 16).astype(np.float32))
    for _ in range(2):
        with profiler.RecordEvent("matmul_step"):
            paddle.matmul(x, x)
        prof.step()
    prof.stop()
    path = str(tmp_path / "prof.json")
    prof.export(path, format="json")
    res = profiler.load_profiler_result(path)
    names = [e["name"] for e in res.events]
    assert names.count("matmul_step") == 2
    assert any(row[0] == "matmul_step" for row in res.span_table())
    with pytest.raises(ValueError):
        prof.export(str(tmp_path / "x.bin"), format="protobuf")
