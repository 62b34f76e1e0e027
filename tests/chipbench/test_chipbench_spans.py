"""The per-layer metrics that read the program's own spans
(``source: program_span``): the tiny cells' generators are driven here as
``chipbench/run.py`` drives them, the record is handed to each reader, and
the window the readers find has to hold exactly the steps the harness
counted.  Each reader's arithmetic is then held to a ring made by hand —
real spans of the program's tracer on a clock the test sets."""
import argparse
import math
import os

import jax
import pytest

from chipbench import harness, program_spans, run, spec
from paddle_tpu import obs

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench",
                    "BENCHMARK.json")
SERVE, TRAIN = "tiny-serve.tiny-closed", "tiny-train.tiny-steady"
READERS = [("host_exposed_pct.serve", SERVE), ("queue_wait_p50_ms", SERVE),
           ("kv_write_dispatches_per_chunk", SERVE), ("warm_trace_s", SERVE),
           ("warm_trace_s", TRAIN), ("train_step_host_ms_p50", TRAIN)]


def drive(workload):
    """One traced run of a tiny cell's generator on the CPU: (record,
    cell).  The ring is emptied first: other tests of this process ran
    steps too."""
    bench = spec.load_benchmark(TINY)
    cell = spec.cell(bench, workload)
    args = argparse.Namespace(seed=3_000_000_007, seconds=0.3, trace=1)
    ctx = run.Context(jax, bench, cell, args, harness.CompileClock(jax))
    generator = spec.load_module(bench, "generators",
                                 cell["traffic"]["generator"])
    obs.reset()
    record = generator.run(ctx)
    ctx.trace.cleanup()
    return record, cell


@pytest.fixture(scope="module")
def runs():
    """Each tiny cell run once, with the ring as its run left it."""
    done = {}

    def get(workload):
        if workload not in done:
            record, cell = drive(workload)
            done[workload] = record, cell, program_spans.ring()
        return done[workload]
    return get


@pytest.fixture
def ring_of(monkeypatch):
    def put(spans):
        monkeypatch.setattr(program_spans, "ring", lambda: spans)
    return put


def reader(name):
    return spec.load_module(spec.load_benchmark(), "layer_metrics", name)


@pytest.mark.parametrize("workload", [SERVE, TRAIN])
def test_window_holds_the_steps_the_harness_counted(runs, workload):
    record, cell, spans = runs(workload)
    assert spans, "the run left no spans"
    t0, t1 = program_spans.window(record, cell, spans)
    assert t1 - t0 == pytest.approx(record["facts"]["window_s"])
    step = program_spans.STEP[record["facts"]["kind"]]
    assert record["facts"]["steps"] > 3
    assert len(program_spans.named(spans, step, t0, t1)) \
        == record["facts"]["steps"]
    # the steps of set-up before it and of the traced stretch after it
    assert any(s[2] == step and s[4] <= t0 for s in spans)
    assert any(s[2] == step and s[4] > t1 for s in spans)
    # nothing compiled inside the window, by the program's own spans too
    assert not [s for s in spans if s[2] == "jit.compile" and t0 < s[4] <= t1]


@pytest.mark.parametrize("name,workload", READERS)
def test_reader_reads_a_number_from_a_tiny_run(runs, ring_of, name, workload):
    record, cell, spans = runs(workload)
    ring_of(spans)
    value = reader(name).read(record, cell, None)
    assert isinstance(value, float) and math.isfinite(value) and value >= 0
    if name == "host_exposed_pct.serve":
        assert 0 < value < 100
    if name == "kv_write_dispatches_per_chunk":     # two scatters a page
        assert value >= 2
    if name == "warm_trace_s":
        assert 0 < value < 600


# -- a ring made by hand -------------------------------------------------------

class Hand:
    """A clock that reads what the test last set."""
    t = 0.0

    def __call__(self):
        return self.t


def play(clock, script):
    """Records ``script`` through the program's own tracer: a span is
    (name, start, end, args, children), an instant (name, at, args)."""
    for item in script:
        if len(item) == 3:
            name, clock.t, args = item
            obs.instant(name, **args)
            continue
        name, start, end, args, children = item
        clock.t = start
        with obs.span(name, **args):
            play(clock, children)
            clock.t = end


def rid(r):
    return {"trace_id": r}


SERVE_CELL = {"traffic": {"prompt_lens": [8], "clients": 1,
                          "preroll_requests": 1}}
SERVE_RECORD = {"facts": {"kind": "serve", "window_s": 3.0, "steps": 3}}
SERVE_SCRIPT = [
    # warm-up: one prompt length, one client
    ("req.submit", 0.0, rid("w0")), ("req.submit", 0.05, rid("w1")),
    ("serve.step", 0.1, 0.9, {}, [("req.finish", 0.8, rid("w0")),
                                  ("req.finish", 0.85, rid("w1"))]),
    # pre-roll: the loop's first request finishes, its client sends L1
    ("req.submit", 1.0, rid("L0")),
    ("serve.step", 1.1, 1.5, {}, [
        ("exec.fetch", 1.2, 1.3, {}, []),
        ("req.finish", 1.4, rid("L0"))]),
    ("req.submit", 1.6, rid("L1")),
    # the window: three steps from 2.0, 3.0 s long
    ("serve.step", 2.0, 2.9, {}, [
        ("serve.sweep", 2.0, 2.01, {}, []),
        ("serve.decode", 2.01, 2.5, {}, [
            ("exec.prep", 2.02, 2.1, {}, []),
            ("jit.dispatch", 2.1, 2.2, {}, []),
            ("exec.fetch", 2.2, 2.4, {}, [])]),
        ("serve.admit", 2.5, 2.6, {}, [("req.admit", 2.55, rid("L1"))]),
        ("req.prefill", 2.6, 2.9, rid("L1"), [
            ("kv.gather", 2.7, 2.72, {}, []),
            ("exec.prep", 2.72, 2.73, {}, []),
            ("jit.dispatch", 2.73, 2.75, {}, []),
            ("kv.write", 2.75, 2.8, {"pages": 2, "dispatches": 4}, []),
            ("exec.fetch", 2.8, 2.9, {}, [])])]),
    ("req.submit", 2.95, rid("L2")),
    ("serve.step", 3.0, 3.9, {}, [
        ("serve.decode", 3.0, 3.5, {}, [
            ("exec.prep", 3.0, 3.1, {}, []),
            ("jit.dispatch", 3.1, 3.2, {}, []),
            ("exec.fetch", 3.2, 3.5, {}, [])]),
        ("serve.admit", 3.5, 3.6, {}, [])]),
    ("serve.step", 4.0, 4.9, {}, [
        ("serve.decode", 4.0, 4.4, {}, [
            ("jit.dispatch", 4.1, 4.2, {}, []),
            ("exec.fetch", 4.2, 4.4, {}, [])]),
        ("serve.admit", 4.45, 4.55, {}, [("req.admit", 4.5, rid("L2"))]),
        ("req.prefill", 4.6, 4.9, rid("L2"), [
            ("kv.gather", 4.6, 4.61, {}, []),
            ("kv.write", 4.7, 4.8, {"pages": 1, "dispatches": 2}, [])])]),
    ("req.submit", 4.95, rid("L3")),        # never admitted in the window
    # the traced stretch after it
    ("serve.step", 5.2, 5.9, {}, [
        ("serve.decode", 5.2, 5.6, {}, [
            ("jit.dispatch", 5.3, 5.4, {}, []),
            ("exec.fetch", 5.4, 5.6, {}, [])]),
        ("serve.admit", 5.6, 5.7, {}, [("req.admit", 5.65, rid("L3"))])]),
]

TRAIN_CELL = {"traffic": {"check_steps": 1}}
TRAIN_RECORD = {"facts": {"kind": "train", "window_s": 2.5, "steps": 3}}
TRAIN_SCRIPT = [
    ("train.step", 0.0, 1.0, {}, []),
    ("train.step", 2.0, 2.004, {}, [("train.place", 2.0, 2.001, {}, [])]),
    ("train.step", 3.0, 3.002, {}, []),
    ("train.step", 4.0, 4.010, {}, []),
    ("train.step", 6.0, 6.1, {}, []),
]


@pytest.fixture
def hand():
    """The program's tracer on a clock the test sets; returns
    ``made(script)`` -> the ring as the readers get it."""
    clock = Hand()
    obs.configure(mode="off", clock=clock)

    def made(script, compiles=()):
        for at, name, dur in compiles:
            clock.t = at
            obs.tracer().complete(name, dur, cat="jit")
        play(clock, script)
        return program_spans.ring()
    yield made
    obs.reset()


COMPILES = [(0.5, "jit.trace", 0.3), (0.6, "jit.lower", 0.2),
            (0.9, "jit.compile", 0.1)]


@pytest.mark.parametrize("record,cell,script,want", [
    (SERVE_RECORD, SERVE_CELL, SERVE_SCRIPT, (2.0, 5.0)),
    (TRAIN_RECORD, TRAIN_CELL, TRAIN_SCRIPT, (2.0, 4.5))])
def test_hand_made_window(hand, record, cell, script, want):
    spans = hand(script)
    assert program_spans.window(record, cell, spans) == pytest.approx(want)
    # a window that holds another number of steps than the harness
    # counted gives no number
    other = {"facts": dict(record["facts"], steps=2)}
    assert program_spans.window(other, cell, spans) is None
    # nor does a ring without the set-up the cell describes
    longer = {"traffic": dict(cell["traffic"], preroll_requests=9,
                              check_steps=9)}
    assert program_spans.window(record, longer, spans) is None


@pytest.mark.parametrize("name,record,cell,script,want", [
    # gaps 2.4->2.7, 2.9->3.1, 3.5->4.1, 4.4->4.6 of a 3-s window
    ("host_exposed_pct.serve", SERVE_RECORD, SERVE_CELL, SERVE_SCRIPT,
     100 * 1.3 / 3.0),
    # L2 waits 2.95->4.5, L3 4.95->the window's end; L1 was sent before it
    ("queue_wait_p50_ms", SERVE_RECORD, SERVE_CELL, SERVE_SCRIPT,
     1e3 * (1.55 + 0.05) / 2),
    ("kv_write_dispatches_per_chunk", SERVE_RECORD, SERVE_CELL, SERVE_SCRIPT,
     (4 + 2) / 2),
    # [0.2, 0.5] and [0.4, 0.6]; the backend's compile is not Python's time
    ("warm_trace_s", SERVE_RECORD, SERVE_CELL, SERVE_SCRIPT, 0.4),
    ("warm_trace_s", TRAIN_RECORD, TRAIN_CELL, TRAIN_SCRIPT, 0.4),
    ("train_step_host_ms_p50", TRAIN_RECORD, TRAIN_CELL, TRAIN_SCRIPT, 4.0),
])
def test_reader_arithmetic(hand, ring_of, name, record, cell, script, want):
    ring_of(hand(script, COMPILES))
    assert reader(name).read(record, cell, None) == pytest.approx(want)


def test_exposed_time_is_split_by_the_span_it_lay_in(hand):
    spans = hand(SERVE_SCRIPT)
    gaps = program_spans.exposed(spans, 2.0, 5.0)
    assert gaps == pytest.approx([(2.4, 2.7), (2.9, 3.1), (3.5, 4.1),
                                  (4.4, 4.6)])
    parts = program_spans.split(spans, gaps)
    want = {"serve.decode": 0.1 + 0.1,      # the token loop; 4.0->4.1
            "serve.admit": 0.1 + 0.1 + 0.1, "req.prefill": 0.1,
            "exec.prep": 0.1,
            "serve.step": 0.3 + 0.05 + 0.05,    # 3.6->3.9; around admit
            "outside": 0.1 + 0.1}               # between two step() calls
    assert {k: v for k, v in parts.items() if abs(v) > 1e-9} \
        == pytest.approx(want)
    assert sum(parts.values()) == pytest.approx(1.3)


def test_a_ring_that_dropped_spans_gives_nothing(hand, monkeypatch):
    hand(SERVE_SCRIPT)
    monkeypatch.setattr(obs.tracer(), "dropped", 1)
    assert program_spans.ring() is None
    assert reader("queue_wait_p50_ms").read(SERVE_RECORD, SERVE_CELL,
                                            None) is None


def test_a_program_without_the_tracer_gives_nothing(monkeypatch):
    """The parent commit: the readers leave their metrics out and do not
    raise."""
    monkeypatch.delattr(obs, "tracer")
    for name, _ in READERS:
        record = TRAIN_RECORD if name.startswith("train") else SERVE_RECORD
        assert reader(name).read(record, SERVE_CELL, None) is None


def test_the_benchmark_names_the_five_metrics():
    bench = spec.load_benchmark()
    rows = {m["name"]: m for m in bench["per_layer"]
            if m["source"] == "program_span"}
    assert set(rows) == {name for name, _ in READERS}
    for name, row in rows.items():
        assert callable(reader(name).read)
        ends = {m["name"] for m in bench["end_to_end"]
                if set(row["workloads"]) <= set(m.get(
                    "workloads", [w["name"] for w in bench["workloads"]]))}
        assert row["moves"] in ends
    # appended to the list, in the issue's order
    assert [m["name"] for m in bench["per_layer"]][-5:] == [
        "host_exposed_pct.serve", "queue_wait_p50_ms",
        "kv_write_dispatches_per_chunk", "warm_trace_s",
        "train_step_host_ms_p50"]
