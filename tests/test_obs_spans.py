"""The one tracer, on the default path: spans with parents and request ids
through ``ServingEngine.step()`` and ``CompiledTrainStep.step()`` with no
``PT_*`` variable set, what a span costs as a count per step, the ring's
bound, the ``pt:`` annotation prefix and the compile listener."""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.inference.server import ServingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.training import CompiledTrainStep
from paddle_tpu.obs.trace import ANNOTATION_PREFIX, LogicalClock, Tracer


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("PT_OBS", raising=False)
    obs.reset()
    yield
    obs.reset()


def family(spans):
    """id -> span, and ancestors(span) -> names from its parent upward."""
    by_id = {s.id: s for s in spans}

    def ancestors(s):
        names = []
        while s.parent is not None:
            s = by_id[s.parent]
            names.append(s.name)
        return names
    return by_id, ancestors


def self_time(spans, span):
    """A span's duration less what its children cover."""
    return span.dur - sum(c.dur for c in spans
                          if c.parent == span.id and c.dur is not None)


# -- the tracer itself ---------------------------------------------------------

def test_parents_and_self_time_on_the_logical_clock():
    obs.configure(mode="off", clock=LogicalClock(tick=1.0))
    with obs.span("outer") as outer:
        with obs.span("a"):
            obs.instant("mark", trace_id="r1")
            with obs.span("leaf"):
                pass
        with obs.span("b"):
            pass
    spans = list(obs.tracer().spans)
    by_name = {s.name: s for s in spans}
    assert [s.name for s in spans] == ["mark", "leaf", "a", "b", "outer"]
    assert by_name["outer"].parent is None
    assert by_name["a"].parent == by_name["b"].parent == outer.id
    assert by_name["leaf"].parent == by_name["mark"].parent \
        == by_name["a"].id
    assert by_name["mark"].args == {"trace_id": "r1"}
    # reads: outer 1, a 2, mark 3, leaf 4-5, a ends 6, b 7-8, outer ends 9
    assert (by_name["outer"].ts, by_name["outer"].dur) == (1.0, 8.0)
    assert (by_name["a"].ts, by_name["a"].dur) == (2.0, 4.0)
    assert self_time(spans, by_name["outer"]) == 8.0 - 4.0 - 1.0
    assert self_time(spans, by_name["a"]) == 4.0 - 1.0
    assert len({s.id for s in spans}) == len(spans)


def test_spans_are_recorded_with_the_operator_plane_off():
    assert obs.handle() is None
    with obs.span("x", cat="train", k=1):
        obs.instant("y")
    assert [s.name for s in obs.tracer().spans] == ["y", "x"]
    assert obs.handle() is None and not obs.enabled()
    # the plane, when on, holds the same tracer
    h = obs.configure(mode="on")
    assert h.tracer is obs.tracer()


def test_ring_is_bounded_and_counts_what_it_dropped():
    obs.configure(mode="off", trace_capacity=4)
    for i in range(7):
        obs.instant("i", n=i)
    tr = obs.tracer()
    assert len(tr.spans) == 4 and tr.dropped == 3
    assert [s.args["n"] for s in tr.spans] == [3, 4, 5, 6]
    obs.reset()
    assert obs.tracer().dropped == 0 and obs.tracer().capacity == 65536


def test_each_thread_has_its_own_open_spans():
    seen = {}

    def other():
        with obs.span("there") as sp:
            seen["parent"] = sp.parent

    with obs.span("here"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    assert seen["parent"] is None


def test_every_span_enters_an_annotation_with_the_prefix(monkeypatch):
    names = []

    class Annotation:
        def __init__(self, name):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with obs.span("serve.step"):
        with obs.span("exec.fetch"):
            pass
    assert ANNOTATION_PREFIX == "pt:"
    assert names == ["pt:serve.step", "pt:exec.fetch"]


def test_chrome_export_carries_id_and_parent(tmp_path):
    with obs.span("outer", cat="serve"):
        obs.instant("req.admit", cat="serve", trace_id="r")
    path = obs.tracer().export_chrome(str(tmp_path / "t.json"))
    with open(path) as f:
        events = [e for e in json.load(f)["traceEvents"] if e["ph"] != "M"]
    inst, outer = events
    assert outer["ph"] == "X" and outer["args"]["parent"] is None
    assert inst["ph"] == "i" and inst["args"]["parent"] == outer["args"]["id"]
    assert inst["args"]["trace_id"] == "r"


def test_compile_listener_records_a_fresh_jit():
    def fresh_program_for_the_listener(x):
        return jnp.sin(x) * 3 + 1

    jax.jit(fresh_program_for_the_listener)(jnp.ones((3, 5, 7))).block_until_ready()
    mine = [s for s in obs.tracer().spans
            if "fresh_program_for_the_listener" in s.args.get("fun_name", "")]
    assert [(s.name, s.args["fun_name"]) for s in mine] == [
        ("jit.trace", "fresh_program_for_the_listener"),
        ("jit.lower", "jit(fresh_program_for_the_listener)"),
        ("jit.compile", "jit(fresh_program_for_the_listener)")]
    assert all(s.dur > 0 and s.cat == "jit" for s in mine)
    # the inner jits' traces lie inside their caller's: a reader takes
    # the union of the intervals
    trace = mine[0]
    inner = [s for s in obs.tracer().spans if s.name == "jit.trace"
             and s.args["fun_name"] in ("sin", "multiply", "add")]
    assert inner and all(
        trace.ts - 1e-3 <= s.ts and s.ts + s.dur <= trace.ts + trace.dur
        for s in inner)
    # a second call compiles nothing
    n = len(obs.tracer().spans)
    jax.jit(fresh_program_for_the_listener)(jnp.ones((3, 5, 7)))
    assert not [s for s in list(obs.tracer().spans)[n:]
                if s.name == "jit.compile"]


def test_compile_spans_stay_off_an_injected_clock():
    clock = LogicalClock()
    obs.configure(mode="off", clock=clock)
    jax.jit(lambda x: jnp.cos(x) - 2)(jnp.ones((2, 7))).block_until_ready()
    assert clock.reads == 0 and not obs.tracer().spans


def test_a_tracer_of_ones_own():
    """The class still stands alone (tools build their own)."""
    tr = Tracer(clock=LogicalClock(), capacity=8, annotate=False)
    with tr.span("a"):
        tr.complete("timed", 0.25, fun_name="f")
    timed, a = tr.spans
    assert timed.parent == a.id and timed.dur == 0.25
    assert not obs.tracer().spans


# -- the serving engine, nothing set -------------------------------------------

ENGINE_KW = dict(max_seqs=4, page_size=4, max_len=64, prefill_chunk=8)


def serve(model, lens=(5, 19), new=4):
    eng = ServingEngine(model, **ENGINE_KW)
    rng = np.random.RandomState(0)
    handles = [eng.submit(rng.randint(1, 256, (n,)).astype(np.int32),
                          max_new_tokens=new) for n in lens]
    eng.run()
    assert all(len(h.tokens) == new for h in handles)
    return eng, handles, [s for s in obs.tracer().spans if s.cat != "jit"]


def test_serving_span_tree(model):
    eng, handles, spans = serve(model)
    assert obs.handle() is None
    by_id, ancestors = family(spans)
    steps = [s for s in spans if s.name == "serve.step"]
    assert [s.args["tick"] for s in steps] == list(range(1, eng.tick + 1))
    assert all(s.parent is None for s in steps)
    for s in spans:
        if s.name in ("exec.fetch", "jit.dispatch", "kv.write", "kv.gather",
                      "exec.prep", "serve.sweep", "serve.decode",
                      "serve.admit", "req.prefill"):
            assert ancestors(s)[-1] == "serve.step", s
        if s.name in ("kv.write", "kv.gather"):
            assert ancestors(s)[0] == "req.prefill"
        if s.name == "exec.fetch":
            assert ancestors(s)[0] == {"decode": "serve.decode"}.get(
                s.args["what"], "req.prefill")
    # the control plane's children, in the order of a step
    kids = [s.name for s in spans
            if s.parent == steps[1].id and s.dur is not None]
    assert kids[:3] == ["serve.sweep", "serve.decode", "serve.admit"]
    assert set(kids[3:]) <= {"req.prefill"}
    # every request-scoped record carries its rid, in lifecycle order
    rids = {h.rid for h in handles}
    for s in spans:
        if s.name.startswith("req."):
            assert s.args["trace_id"] in rids, s
    for rid in rids:
        names = [s.name for s in spans if s.args.get("trace_id") == rid]
        assert names[0] == "req.submit" and names[1] == "req.admit"
        assert names[-1] == "req.finish" and "req.first_token" in names
        assert names.index("req.prefill") < names.index("req.first_token")
    admits = [s for s in spans if s.name == "serve.admit"]
    assert sum(s.args["admitted"] for s in admits) == 2
    # the 19-token prompt: chunks of 8, 8, 3 over pages of 4 tokens
    writes = [by_id[s.id].args for s in spans if s.name == "kv.write"
              and by_id[s.parent].args["trace_id"] == handles[1].rid]
    assert writes == [{"pages": 2, "dispatches": 1}] * 2 \
        + [{"pages": 1, "dispatches": 1}]
    # ... and `dispatches` is what ran under the span: the one writer
    for w in (s for s in spans if s.name == "kv.write"):
        assert [(k.name, k.args["program"]) for k in spans
                if k.parent == w.id] == [("jit.dispatch", "serve.kv_write")]


def test_a_prefill_chunk_is_one_program_and_one_write(model):
    """On the Llama path the host hands the device a chunk in two
    dispatches: the chunk's program, which reads its past from the pools
    itself (no ``kv.gather``, first chunk or not), and the page writer."""
    _, _, spans = serve(model, lens=(19, 30), new=3)
    chunks = [s for s in spans if s.name == "req.prefill"]
    assert [c.args["start"] for c in chunks].count(0) == 2
    assert len(chunks) == 3 + 4
    assert not [s for s in spans if s.name == "kv.gather"]
    for c in chunks:
        kids = [s for s in spans if s.parent == c.id]
        assert [k.name for k in kids][:3] == [
            "exec.prep", "jit.dispatch", "kv.write"]
        assert [k.name for k in kids[3:]] == \
            ["exec.fetch"] * bool(c.args["final"])
        assert kids[1].args["program"] == "serve.prefill_chunk"
        assert kids[2].args["dispatches"] == 1


def test_traced_is_true_exactly_on_first_shapes(model):
    eng, _, spans = serve(model, lens=(5, 19), new=6)
    seen, want = set(), []
    events = iter(eng.executor.prefill_events)
    for s in spans:
        if s.name != "jit.dispatch":
            continue
        # a shape is new when its program has not run at this batch size
        # (decode), chunk length and past cover (prefill), span length
        # alone (the page writer, under kv.write under req.prefill)
        parent = next(p for p in spans if p.id == s.parent)
        if s.args["program"] == "serve.decode":
            shape = ("decode", parent.args["batch"])
        elif s.args["program"] == "serve.kv_write":
            chunk = next(p for p in spans if p.id == parent.parent)
            shape = ("kv_write", chunk.args["tokens"])
        else:
            shape = (s.args["program"], next(events)[1],
                     -(-parent.args["start"] // 4))
        want.append(shape not in seen)
        seen.add(shape)
    got = [s.args["traced"] for s in spans if s.name == "jit.dispatch"]
    assert got == want and True in got and False in got
    progs = [*eng.executor.programs.values(), eng.executor.cache.writer]
    assert sum(got) == sum(p.traces for p in progs)
    assert len(got) == sum(p.dispatches for p in progs)


def test_span_budget_of_a_step(model):
    """A decode-only step records at most 8 spans, a prefill chunk at
    most 6 more, none per token or page; instants are per request."""
    eng, _, spans = serve(model, lens=(5, 19, 30), new=12)
    timed = [s for s in spans if s.dur is not None]
    steps = [s for s in timed if s.name == "serve.step"]
    _, ancestors = family(spans)
    decode_only = 0
    for step in steps:
        inside = [s for s in timed if s is step
                  or (s.ts >= step.ts and s.ts + s.dur <= step.ts + step.dur
                      and "serve.step" in ancestors(s))]
        chunks = sum(s.name == "req.prefill" for s in inside)
        assert len(inside) <= 8 + 6 * chunks, [s.name for s in inside]
        decode_only += not chunks
    assert decode_only >= 5
    instants = [s for s in spans if s.dur is None]
    assert len(instants) == 4 * 3       # submit, admit, first_token, finish


# -- the train step, nothing set -----------------------------------------------

def test_train_step_spans(model):
    step = CompiledTrainStep(model, lr=1e-3)
    ids = np.random.RandomState(0).randint(0, 256, (2, 17)).astype(np.int32)
    n0 = len(obs.tracer().spans)
    for _ in range(3):
        loss = step.step(ids[:, :-1], ids[:, 1:])
    assert np.isfinite(float(loss))
    assert obs.handle() is None
    spans = [s for s in list(obs.tracer().spans)[n0:] if s.cat != "jit"]
    assert len(spans) <= 4 * 3
    tops = [s for s in spans if s.name == "train.step"]
    assert [s.args["t"] for s in tops] == [1, 2, 3]
    for top in tops:
        assert top.parent is None
        kids = [s for s in spans if s.parent == top.id]
        assert [s.name for s in kids] == ["train.place", "jit.dispatch"]
        assert kids[1].args == {"program": "train.step"}
        assert self_time(spans, top) >= 0


# -- the hybrid engine (state-space layers beside attention) --------------------

@pytest.fixture(scope="module")
def hybrid():
    from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                  GraniteHybridForCausalLM)

    paddle.seed(12)
    return GraniteHybridForCausalLM(GraniteHybridConfig.tiny())


#: the contract's spans (PERF.md section 3) every serving path emits
CONTRACT = {"serve.step", "serve.sweep", "serve.decode", "serve.admit",
            "req.prefill", "exec.prep", "kv.gather", "kv.write",
            "jit.dispatch", "exec.fetch", "req.submit", "req.admit",
            "req.first_token", "req.finish"}


def test_hybrid_spans_are_the_contracts_and_the_states(hybrid):
    eng, handles, spans = serve(hybrid, lens=(5, 19), new=6)
    names = {s.name for s in spans}
    assert CONTRACT <= names
    assert {"state.read", "state.write", "state.alloc",
            "state.free"} <= names
    by_id, ancestors = family(spans)
    for s in spans:
        if s.name in ("state.read", "state.write", "kv.write", "kv.gather"):
            assert ancestors(s)[0] == "req.prefill", s
            assert ancestors(s)[-1] == "serve.step"
    # one read and one write a chunk, the write one donated dispatch
    chunks = [s for s in spans if s.name == "req.prefill"]
    assert len(chunks) == 1 + 3         # 5 whole; 19 = 8 + 8 + 3
    for c in chunks:
        kids = [s for s in spans if s.parent == c.id]
        assert [k.name for k in kids].count("state.read") == 1
        (write,) = [k for k in kids if k.name == "state.write"]
        assert write.args["tokens"] == c.args["tokens"]
        assert write.args["dispatches"] == 1
        assert [(k.name, k.args["program"]) for k in spans
                if k.parent == write.id] == [("jit.dispatch",
                                              "serve.state_write")]
        read = next(k for k in kids if k.name == "state.read")
        assert read.args["start"] == c.args["start"]
        # the past is gathered only where there is one
        assert [k.name for k in kids].count("kv.gather") == \
            (c.args["start"] > 0)
    # a slot's alloc and free, one each a request
    for name in ("state.alloc", "state.free"):
        assert len([s for s in spans if s.name == name]) == len(handles)
    # ONE decode program whatever the batch, one chunk program a shape
    progs = {s.args["program"] for s in spans if s.name == "jit.dispatch"}
    assert progs == {"serve.hybrid_chunk", "serve.hybrid_decode",
                     "serve.kv_write", "serve.state_write"}
    assert eng.executor.programs["hybrid_decode"].traces == 1


def test_hybrid_span_budget_of_a_step(hybrid):
    """A decode-only step records at most 8 spans, a prefill chunk at most
    11 more (its own, the gather, the prep, the state's read, the
    program, two writes with their dispatches, the fetch); none per
    token, page or layer."""
    eng, _, spans = serve(hybrid, lens=(5, 19, 30), new=12)
    timed = [s for s in spans if s.dur is not None]
    steps = [s for s in timed if s.name == "serve.step"]
    _, ancestors = family(spans)
    decode_only = 0
    for step in steps:
        inside = [s for s in timed if s is step
                  or (s.ts >= step.ts and s.ts + s.dur <= step.ts + step.dur
                      and "serve.step" in ancestors(s))]
        chunks = sum(s.name == "req.prefill" for s in inside)
        assert len(inside) <= 8 + 11 * chunks, [s.name for s in inside]
        decode_only += not chunks
    assert decode_only >= 5
    instants = [s for s in spans if s.dur is None]
    assert len(instants) == 6 * 3       # + state.alloc, state.free


# -- what a step hands the device, and when the device drained ------------------

@pytest.fixture(scope="module")
def latent():
    from paddle_tpu.models.mla_moe import MLAMoEConfig, MLAMoEForCausalLM

    paddle.seed(13)
    return MLAMoEForCausalLM(MLAMoEConfig.tiny())


@pytest.fixture(scope="module")
def windowed():
    from paddle_tpu.models.window_moe import (WindowMoEConfig,
                                              WindowMoEForCausalLM)

    paddle.seed(14)
    return WindowMoEForCausalLM(WindowMoEConfig.tiny())


#: fixture -> (executor, spans a prefill chunk may add to a step's 8,
#: host arrays and eager ops of a decode step)
EXECUTORS = {"model": ("PagedExecutor", 6, 4, 3),
             "hybrid": ("HybridExecutor", 11, 4, 0),
             "latent": ("LatentExecutor", 6, 4, 0),
             "windowed": ("WindowExecutor", 6, 6, 0)}
#: the page and state writers' arguments go to the device under
#: ``kv.write`` / ``state.write``, which count their own dispatches
WRITERS = ("serve.kv_write", "serve.state_write")


class Seen:
    """``jnp`` or ``jax`` as a serving module sees it: the calls that put
    an array on the device or run a device op outside a program are
    tallied (a call with a tracer among its arguments is a program's
    own body being traced, and is not)."""

    WATCHED = ("asarray", "array", "device_put", "argmax")

    def __init__(self, module, tally):
        self._module, self._tally = module, tally

    def __getattr__(self, name):
        real = getattr(self._module, name)
        if name not in self.WATCHED:
            return real

        def counted(*args, **kw):
            leaves = jax.tree_util.tree_leaves((args, kw))
            if not any(isinstance(x, jax.core.Tracer) for x in leaves):
                kind = "eager" if name == "argmax" else "h2d"
                self._tally[kind] += 1
                # jnp.asarray(x, dtype) converts on the device
                dtype = args[1] if len(args) > 1 else kw.get("dtype")
                if name in ("asarray", "array") and dtype is not None:
                    self._tally["eager"] += 1
            return real(*args, **kw)
        return counted


def watch(monkeypatch):
    """Puts :class:`Seen` in the place of ``jnp`` and ``jax`` in the four
    executor modules and the hand-over's own, and counts the host values
    among the arguments of every program but the writers.  Returns the
    tally."""
    from paddle_tpu.analysis import CountedJit
    from paddle_tpu.inference.server import (executor, handoff,
                                             hybrid_executor,
                                             latent_executor,
                                             window_executor)

    tally = {"h2d": 0, "eager": 0}
    for mod in (executor, handoff, hybrid_executor, latent_executor,
                window_executor):
        for name in ("jnp", "jax"):
            if hasattr(mod, name):
                monkeypatch.setattr(mod, name,
                                    Seen(getattr(mod, name), tally))
    call = CountedJit.__call__

    def counted(self, *args, **kw):
        if self.name not in WRITERS:
            tally["h2d"] += sum(
                isinstance(x, (np.ndarray, np.generic, int, float))
                for x in jax.tree_util.tree_leaves(args))
        return call(self, *args, **kw)

    monkeypatch.setattr(CountedJit, "__call__", counted)
    return tally


def steps_of(eng, tally, lens=(5, 19), new=4):
    """Serves ``lens`` step by step: per step the spans it recorded, and
    what ``tally`` saw handed to the device during it."""
    rng = np.random.RandomState(0)
    handles = [eng.submit(rng.randint(1, 256, (n,)).astype(np.int32),
                          max_new_tokens=new) for n in lens]
    out = []
    while eng.scheduler.has_work():
        n, before = len(obs.tracer().spans), dict(tally)
        eng.step()
        out.append(([s for s in list(obs.tracer().spans)[n:]
                     if s.cat != "jit"],
                    {k: tally[k] - before[k] for k in tally}))
    assert all(len(h.tokens) == new for h in handles)
    return out


def claimed(spans):
    """What a step's ``exec.prep`` and ``exec.fetch`` spans say was
    handed to the device."""
    marks = [s for s in spans if s.name in ("exec.prep", "exec.fetch")]
    return {"h2d": sum(s.args.get("h2d", 0) for s in marks),
            "eager": sum(s.args["eager"] for s in marks)}


@pytest.mark.parametrize("which", EXECUTORS)
def test_a_step_counts_what_it_hands_the_device(request, monkeypatch, which):
    executor, chunk_spans, h2d, eager = EXECUTORS[which]
    eng = ServingEngine(request.getfixturevalue(which), **ENGINE_KW)
    assert type(eng.executor).__name__ == executor
    steps_of(eng, {})               # every shape traced and compiled
    obs.configure(mode="off", clock=LogicalClock())
    steps = steps_of(eng, watch(monkeypatch))
    spans = [s for step, _ in steps for s in step]
    assert not any(s.args["traced"] for s in spans
                   if s.name == "jit.dispatch")
    # every blocking read says when the device drained, inside itself:
    # the logical clock makes the three reads strictly ordered
    fetches = [s for s in spans if s.name == "exec.fetch"]
    # (the Llama path alone takes a prompt that fits one chunk whole)
    assert {s.args["what"] for s in fetches} - {"prefill"} == {
        "decode", "prefill_chunk"}
    assert all(s.ts < s.args["ready"] < s.ts + s.dur for s in fetches)
    # the counts are what was seen handed over, step by step
    decode_only = 0
    for step, seen in steps:
        assert claimed(step) == seen, [(s.name, s.args) for s in step]
        timed = [s for s in step if s.dur is not None]
        chunks = sum(s.name == "req.prefill" for s in timed)
        assert len(timed) <= 8 + chunk_spans * chunks, \
            [s.name for s in timed]
        if not chunks and any(s.name == "exec.prep" for s in step):
            decode_only += 1
            assert seen == {"h2d": h2d, "eager": eager}
            (prep,) = [s for s in step if s.name == "exec.prep"]
            (fetch,) = [s for s in step if s.name == "exec.fetch"]
            assert prep.args["h2d"] == h2d
            assert prep.args["eager"] + fetch.args["eager"] == eager
    assert decode_only >= 2


def test_a_transfer_beside_the_hand_over_is_caught(model, monkeypatch):
    """What the test above stands on: a line in an executor that puts an
    array on the device without going through ``Handoff`` is seen and
    not claimed."""
    from paddle_tpu.inference.server import executor

    eng = ServingEngine(model, **ENGINE_KW)
    steps_of(eng, {})
    tally = watch(monkeypatch)
    decode = eng.executor.decode

    def decode_with_a_stray_line(sids):
        executor.jnp.asarray(np.zeros((1,), np.int32))
        return decode(sids)

    monkeypatch.setattr(eng.executor, "decode", decode_with_a_stray_line)
    steps = steps_of(eng, tally)
    off = [seen["h2d"] - claimed(step)["h2d"] for step, seen in steps]
    assert set(off) == {0, 1} and all(
        d == any(s.name == "serve.decode" and s.args["batch"]
                 for s in step) for d, (step, _) in zip(off, steps))


def test_a_dtype_on_the_put_is_one_eager_convert(monkeypatch):
    """``Handoff.put`` counts the ``convert_element_type`` that
    ``jnp.asarray(x, dtype)`` runs on the device, and none without a
    dtype: held to the primitives jax executes eagerly."""
    from jax._src import dispatch

    from paddle_tpu.inference.server.handoff import Handoff

    ran = []
    compiled = dispatch.xla_primitive_callable
    monkeypatch.setattr(
        dispatch, "xla_primitive_callable",
        lambda prim, **kw: ran.append(prim.name) or compiled(prim, **kw))
    io = Handoff()
    io.put([3, 1, 2], jnp.int32)
    assert (io.h2d, io.eager, ran) == (1, 1, ["convert_element_type"])
    io.put(np.arange(3, dtype=np.int32))
    io.host(np.zeros(2), np.int32(1))
    assert (io.h2d, io.eager, ran) == (4, 1, ["convert_element_type"])
    assert int(io.fetch("decode", lambda: io.argmax(jnp.ones((2, 5))))) == 0
    assert io.eager == 2
