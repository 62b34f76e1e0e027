"""The program's account of when the device had nothing to do
(``chipbench/device_account.py``) and the six per-layer metrics that read
it: each reader's arithmetic held to a ring made by hand on a clock the
test sets, then each tiny serving cell run once through
``chipbench/run.py``'s own ``main`` with ``BENCHMARK.account.json`` (the
four executors), and the real ``BENCHMARK.json``'s six rows."""
import contextlib
import io
import json
import math
import os

import pytest

from chipbench import device_account, harness, program_spans, run, spec
from paddle_tpu import obs
# the hand-made ring's tools (a clock the test sets, a script played
# through the program's own tracer) are the span readers' tests'
from test_chipbench_spans import SERVE_CELL, hand, rid, ring_of  # noqa: F401
from test_chipbench_yardstick import SOURCES, UNIT

ACCOUNT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench",
                       "BENCHMARK.account.json")
NAMES = ["decode_inflight_ms_p50", "starved_copy_ms_per_step",
         "starved_prep_ms_per_step", "starved_sched_ms_per_step",
         "device_calls_per_decode_step", "idle_seen_pct.serve"]
#: cell -> what a decode step hands the device: its program, the host
#: arrays (Llama: ids, positions, tables, lengths put one by one; the slot
#: executors: ids, positions, live and the page tables as the program's
#: numpy arguments, the window executor two tables and the bases) and the
#: eager ops (Llama: two converts riding on the puts, the argmax)
CELLS = {"tiny-serve.tiny-closed": 1 + 4 + 3,
         "tiny-hybrid.tiny-chat": 1 + 4,
         "tiny-mla-moe.tiny-long": 1 + 4,
         "tiny-window-moe.tiny-mixed": 1 + 6}


def reader(name):
    return spec.load_module(spec.load_benchmark(), "layer_metrics", name)


# -- a ring made by hand -------------------------------------------------------

def fetch(start, end, ready, what="decode", eager=0):
    args = {"what": what, "eager": eager}
    if ready is not None:
        args["ready"] = ready
    return ("exec.fetch", start, end, args, [])


def prep(start, end, h2d, eager=0):
    return ("exec.prep", start, end, {"h2d": h2d, "eager": eager}, [])


def dispatch(start, end, program="serve.decode"):
    return ("jit.dispatch", start, end, {"program": program}, [])


def script(ready=True):
    """Three window steps from 2.0 (the first with a NON-final chunk, so
    the second's decode is dispatched on a device that may still be
    busy), two traced steps from 5.2, the loop's tail at 8.0.  With
    ``ready`` false one read does not say when it was ready."""
    return [
        ("req.submit", 0.0, rid("w0")), ("req.submit", 0.05, rid("w1")),
        ("serve.step", 0.1, 0.9, {}, [("req.finish", 0.8, rid("w0")),
                                      ("req.finish", 0.85, rid("w1"))]),
        ("req.submit", 1.0, rid("L0")),
        ("serve.step", 1.1, 1.5, {}, [
            ("serve.decode", 1.1, 1.45, {}, [
                dispatch(1.15, 1.2), fetch(1.2, 1.3, 1.25)]),
            ("req.finish", 1.4, rid("L0"))]),
        ("req.submit", 1.6, rid("L1")),
        # A: the device drained at 1.25; decode 2.1 -> 2.35; a chunk
        ("serve.step", 2.0, 2.9, {}, [
            ("serve.sweep", 2.0, 2.01, {}, []),
            ("serve.decode", 2.01, 2.5, {}, [
                prep(2.02, 2.1, 4, 2), dispatch(2.1, 2.2),
                fetch(2.2, 2.4, 2.35, eager=1)]),
            ("serve.admit", 2.5, 2.6, {}, [("req.admit", 2.55, rid("L1"))]),
            ("req.prefill", 2.6, 2.9, rid("L1"), [
                prep(2.62, 2.7, 3),
                dispatch(2.7, 2.75, "serve.prefill_chunk"),
                ("kv.write", 2.75, 2.8, {"pages": 2, "dispatches": 1},
                 [dispatch(2.76, 2.79, "serve.kv_write")])])]),
        # B: its decode is dispatched behind the chunk, not on a drained
        # device
        ("serve.step", 3.0, 3.9, {}, [
            ("serve.decode", 3.0, 3.5, {}, [
                prep(3.0, 3.1, 4, 2), dispatch(3.1, 3.2),
                fetch(3.2, 3.5, 3.4 if ready else None, eager=1)]),
            ("serve.admit", 3.5, 3.6, {}, [])]),
        # C: drained at 3.4; decode 4.1 -> 4.3; then starved to the
        # window's end
        ("serve.step", 4.0, 4.9, {}, [
            ("serve.decode", 4.0, 4.45, {}, [
                prep(4.02, 4.1, 5, 2), dispatch(4.1, 4.2),
                fetch(4.2, 4.4, 4.3, eager=1)]),
            ("serve.admit", 4.45, 4.55, {}, [])]),
        # the traced stretch
        ("serve.step", 5.2, 5.9, {}, [
            ("serve.decode", 5.2, 5.6, {}, [
                dispatch(5.3, 5.4), fetch(5.4, 5.6, 5.5)]),
            ("serve.admit", 5.6, 5.7, {}, [])]),
        ("serve.step", 6.0, 6.8, {}, [
            ("serve.decode", 6.0, 6.5, {}, [
                prep(6.0, 6.1, 4), dispatch(6.1, 6.2),
                fetch(6.2, 6.5, 6.45)])]),
        # the loop's tail, after the profiler stopped
        ("serve.step", 8.0, 8.5, {}, [
            ("serve.decode", 8.0, 8.5, {}, [
                dispatch(8.1, 8.2), fetch(8.2, 8.4, 8.3)])]),
    ]


def record(traced_steps=2):
    return {"facts": {"kind": "serve", "window_s": 3.0, "steps": 3,
                      "traced": {"steps": traced_steps}},
            "trace": {"window_s": 1.9, "busy_s": 0.4}}


GAPS = [(2.0, 2.1),     # the window opens inside a gap
        (2.35, 2.7), (3.4, 4.1),
        (4.3, 5.0)]     # cut by the window's end
WANT = {
    # the decodes of A (2.1 -> 2.35) and C (4.1 -> 4.3); B's follows a
    # chunk that nothing waited for
    "decode_inflight_ms_p50": 1e3 * (0.25 + 0.2) / 2,
    # ready -> the read's end: 2.35-2.4, 3.4-3.5, 4.3-4.4, over 3 steps
    "starved_copy_ms_per_step": 1e3 * 0.25 / 3,
    # exec.prep 2.02-2.1, 2.62-2.7, 4.02-4.1
    "starved_prep_ms_per_step": 1e3 * 0.24 / 3,
    # sweep .01; decode .01 + .1 + .02 + .05; admit .1 + .1 + .1; the
    # chunk's own .02; the step's .3 + .35
    "starved_sched_ms_per_step": 1e3 * 1.16 / 3,
    # B: 1 + 4 + (2 + 1); C: 1 + 5 + (2 + 1); A holds a chunk
    "device_calls_per_decode_step": (8 + 9) / 2,
    # (5.2, 5.3) + (5.5, 6.1) + (6.45, 6.8) of 1.9 - 0.4 idle seconds
    "idle_seen_pct.serve": 100 * 1.05 / 1.5,
}


def test_starved_gaps_and_the_account(hand):
    spans = hand(script())
    gaps = device_account.starved(spans, 2.0, 5.0)
    assert gaps == pytest.approx(GAPS)
    parts = device_account.account(spans, gaps)
    assert parts == pytest.approx({"total": 1.85, "copy": 0.25, "prep": 0.24,
                                   "sched": 1.16, "harness": 0.2})
    assert parts["total"] == pytest.approx(
        parts["copy"] + parts["prep"] + parts["sched"] + parts["harness"])
    # the account is the old exposed gaps begun earlier: at `ready`, not
    # at the read's end, and through the window's first gap
    exposed = program_spans.exposed(spans, 2.0, 5.0)
    assert sum(b - a for a, b in exposed) == pytest.approx(1.85 - 0.25 - 0.1)


def test_drained_dispatches_pair_with_the_next_read(hand):
    spans = hand(script())
    pairs = device_account.drained_dispatches(spans, 2.0, 5.0)
    assert [(d[5]["program"], d[3], f[5]["ready"]) for d, f in pairs] == [
        ("serve.decode", 2.1, 2.35),
        # the chunk was the first hand-over since 2.35; the next read
        # is B's decode, whose own dispatch is not on a drained device
        ("serve.prefill_chunk", 2.7, 3.4),
        ("serve.decode", 4.1, 4.3)]


@pytest.mark.parametrize("steps,want", [
    (2, (5.2, 6.8)),
    (3, None),      # the third step after the window is the loop's tail
    (4, None),      # the ring holds three
    (0, None)])
def test_traced_stretch(hand, steps, want):
    spans = hand(script())
    assert device_account.traced_stretch(record(steps), spans, 5.0) == want


@pytest.mark.parametrize("name", NAMES)
def test_reader_arithmetic(hand, ring_of, name):
    ring_of(hand(script()))
    assert reader(name).read(record(), SERVE_CELL, None) \
        == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_a_read_that_does_not_say_when_it_was_ready_gives_nothing(
        hand, ring_of, name):
    """The parent commit's ring: ``exec.fetch`` without ``ready``,
    ``exec.prep`` without ``h2d``."""
    spans = hand(script(ready=False))
    assert device_account.starved(spans, 2.0, 5.0) is None
    assert device_account.drained_dispatches(spans, 2.0, 5.0) is None
    bare = [s[:5] + ({k: v for k, v in s[5].items()
                      if k not in ("ready", "h2d", "eager")},)
            for s in spans]
    for ring in (spans, bare):
        ring_of(ring)
        # (the count of hand-overs stands on ``h2d`` alone)
        if ring is bare or name != "device_calls_per_decode_step":
            assert reader(name).read(record(), SERVE_CELL, None) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_tracer_gives_nothing(monkeypatch, name):
    monkeypatch.delattr(obs, "tracer")
    assert reader(name).read(record(), SERVE_CELL, None) is None


# -- the tiny cells, one run each through run.main -----------------------------

@pytest.fixture(scope="module")
def rehearsals(tmp_path_factory):
    """``get(workload)`` -> (the printed line, the record, the cell, the
    values the readers returned before the rehearsal struck them out,
    the ring as the run left it); each tiny cell is run once.  The
    profiler writes under a directory of this module's own: the
    checkout's ``.chipbench_trace`` is emptied by every traced rehearsal
    of every other test file, which other workers run meanwhile."""
    done = {}
    traces = str(tmp_path_factory.mktemp("chipbench_trace"))
    window = harness.TraceWindow

    def get(workload):
        if workload in done:
            return done[workload]
        seen = {}
        per_layer = run.per_layer

        def spy(bench, cell, record, peaks):
            out = per_layer(bench, cell, record, peaks)
            seen.update(record=record, cell=cell,
                        values={k: v["value"] for k, v in out.items()})
            return out

        obs.reset()
        out = io.StringIO()
        with pytest.MonkeyPatch.context() as mp, \
                contextlib.redirect_stdout(out):
            mp.setattr(run, "per_layer", spy)
            mp.setattr(harness, "TraceWindow",
                       lambda jax, out_dir, on: window(jax, traces, on))
            rc = run.main(["--workload", workload, "--seed", "3000000007",
                           "--seconds", "0.3", "--trace", "1"],
                          bench_path=ACCOUNT, rehearse=True)
        lines = out.getvalue().strip().splitlines()
        assert rc == 0 and len(lines) == 1, lines
        done[workload] = (json.loads(lines[0]), seen["record"], seen["cell"],
                          seen["values"], program_spans.ring())
        return done[workload]
    return get


@pytest.mark.parametrize("workload", CELLS)
def test_a_rehearsal_prints_the_names_struck_out(rehearsals, workload):
    line, record, _, values, _ = rehearsals(workload)
    assert line["correct"] and line["rehearsal"]
    # a CPU trace has no device plane, so the one metric that stands on
    # the device's idle seconds is left out, like device_idle_pct.serve
    assert set(line["metrics"]) == set(NAMES) - {"idle_seen_pct.serve"}
    assert all(m["value"] is None for m in line["metrics"].values())
    assert record["trace"]["busy_s"] is None


@pytest.mark.parametrize("workload", CELLS)
def test_the_readers_on_each_executors_ring(rehearsals, ring_of, workload):
    _, record, cell, values, spans = rehearsals(workload)
    assert all(isinstance(v, float) and math.isfinite(v) and v >= 0
               for v in values.values()), values
    # counts repeat exactly, whatever the machine
    assert values["device_calls_per_decode_step"] == CELLS[workload]
    # the device's part of a decode step is inside the host's step
    assert 0 < values["decode_inflight_ms_p50"] \
        <= 1e3 * harness.percentile(record["facts"]["decode_step_s"], 50)
    t0, t1 = program_spans.window(record, cell, spans)
    gaps = device_account.starved(spans, t0, t1)
    assert len(gaps) >= record["facts"]["steps"] - 1
    parts = device_account.account(spans, gaps)
    assert all(v >= -1e-9 for v in parts.values()), parts
    assert parts["total"] <= t1 - t0
    by_span = program_spans.split(spans, gaps)
    assert parts["prep"] >= by_span["exec.prep"] - 1e-9 > 0
    for part in ("copy", "prep", "sched"):
        assert values[f"starved_{part}_ms_per_step"] == pytest.approx(
            1e3 * parts[part] / record["facts"]["steps"])
    # the traced stretch is found, and against a device that was busy
    # for half of it the account sees a share of the idle half
    stretch = device_account.traced_stretch(record, spans, t1)
    assert stretch is not None and stretch[0] >= t1
    inside = [s for s in spans if s[2] == "serve.step"
              and stretch[0] <= s[3] and s[4] <= stretch[1]]
    assert len(inside) == record["facts"]["traced"]["steps"]
    ring_of(spans)
    trace = dict(record["trace"], busy_s=record["trace"]["window_s"] / 2)
    seen = reader("idle_seen_pct.serve").read(
        dict(record, trace=trace), cell, None)
    assert 0 < seen < 200


# -- the real benchmark's six rows ---------------------------------------------

def test_the_benchmarks_six_rows():
    bench = spec.load_benchmark()
    rows = bench["per_layer"][-6:]
    assert [m["name"] for m in rows] == NAMES
    serve = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    layers = {m["layer"] for m in bench["per_layer"][:-6]}
    for row in rows:
        assert callable(reader(row["name"]).read)
        assert UNIT.match(row["unit"]) and row["source"] in SOURCES
        assert row["better"] in ("lower", "higher")
        assert row["moves"] == "serve_tokens_per_s"
        assert row["layer"] in layers
        assert set(row["workloads"]) <= set(serve["workloads"])
        assert set(row) == {"name", "unit", "better", "source", "layer",
                            "moves", "workloads"}
    # the tiny benchmark file holds the same six rows over its own cells
    with open(ACCOUNT) as f:
        tiny = json.load(f)
    assert [{k: v for k, v in m.items() if k != "workloads"}
            for m in tiny["per_layer"]] == [
        {k: v for k, v in m.items() if k != "workloads"} for m in rows]
    assert all(m["workloads"] == list(CELLS) for m in tiny["per_layer"])
