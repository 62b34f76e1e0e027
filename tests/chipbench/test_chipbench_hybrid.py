"""The hybrid (Mamba-2 + attention) configuration's part of the yardstick:
the tiny cell rehearsed on the CPU through chipbench.run's own functions,
the counts of ``flops_hybrid`` and ``kernels/ssm_decode`` against a hand
count at the published widths, the mix's weights from the two published
means and the assumed sigma, and the faults and controls through the
run's own ``judge``."""
import json
import math
import os
from statistics import NormalDist

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import (control, flops_hybrid, harness, reference_hybrid,
                       roofline, run, spec, weights_hybrid)
from chipbench.kernels import ssm_decode as ssm_counts
from paddle_tpu import obs

# the tiny hybrid cell has a benchmark file of its own beside the accepted
# one (which is the benchmark's, and no model PR's to edit); both name
# files under the same tests/chipbench/bench
BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench")
TINY = os.path.join(BENCH, "BENCHMARK.hybrid.json")
TINY_LLAMA = os.path.join(BENCH, "BENCHMARK.json")
CELL = "tiny-hybrid.tiny-chat"
REAL = "granite4h-micro-serve.chat64"


@pytest.fixture
def rehearse(capsys):
    """conftest's ``rehearse`` over this file's benchmark: one tiny cell as
    ``chipbench/run.py`` would run it, without the look for a chip."""
    def go(workload, seed=3_000_000_007, seconds=0.3, trace=0):
        capsys.readouterr()
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      bench_path=TINY, rehearse=True)
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0 and len(out) == 1, out
        return json.loads(out[-1])
    return go


@pytest.fixture(scope="module")
def real():
    return spec.cell(spec.load_benchmark(), REAL)


@pytest.fixture(scope="module")
def tiny_cfg():
    return spec.cell(spec.load_benchmark(TINY), CELL)["config"]


def _failing(line):
    return [k for k, row in line["checks"].items()
            if not row["value"] <= row["limit"]]


# -- the cell on the CPU -------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_to_its_last_line(rehearse, trace, monkeypatch, tmp_path):
    # a trace directory of this test's own: the other test files trace
    # into <checkout>/.chipbench_trace, perhaps at this moment in another
    # worker, and a TraceWindow empties its directory when it opens
    window = harness.TraceWindow
    monkeypatch.setattr(harness, "TraceWindow", lambda jax, out_dir, on:
                        window(jax, str(tmp_path / "trace"), on))
    obs.reset()     # the span readers count this run's requests alone
    line = rehearse(CELL, trace=trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    cell = spec.cell(spec.load_benchmark(TINY), CELL)
    wanted = {m["name"] for m in cell["per_layer" if trace else "end_to_end"]
              if not (trace and m["source"] == "device_trace")}
    assert set(line["metrics"]) == wanted
    if trace:
        assert {"mfu.serve_hybrid", "decode_hbm_share_pct",
                "state_write_dispatches_per_chunk"} <= wanted
    assert all(m["value"] is None for m in line["metrics"].values())
    assert line["checks"]["short_answers"] == {"value": 0.0, "limit": 0}
    assert set(line["checks"]) == {"served_token_gap", "state_row_gap",
                                   "short_answers"}
    assert 0 <= line["checks"]["state_row_gap"]["value"] < 1e-5


def test_new_readers_read_nothing_from_a_llama_run():
    """On a program without recurrent state (the accepted serve cell, or a
    parent commit) the new readers return nothing and do not raise."""
    bench = spec.load_benchmark(TINY_LLAMA)
    cell = spec.cell(bench, "tiny-serve.tiny-closed")
    record = {"facts": {"decode_calls": [[4, 80]], "decode_step_s": [0.01],
                        "kind": "serve", "window_s": 1.0, "steps": 0,
                        "traced": {"decode_calls": [[4, 80]]}},
              "trace": {"kernels": {}, "busy_s": 1.0}, "bench": bench}
    peaks = spec.peaks(bench, None)
    for name in ("decode_hbm_share_pct", "ssm_decode_roofline",
                 "ssm_decode_device_share_pct",
                 "state_write_dispatches_per_chunk"):
        reader = spec.load_module(bench, "layer_metrics", name)
        assert reader.read(record, cell, peaks) is None, name


def test_state_writes_are_counted_from_their_dispatch_children(monkeypatch):
    """The reader counts the ``jit.dispatch`` spans recorded inside a
    ``state.write``: a second program issued there moves the metric
    whatever the span says of itself."""
    from chipbench import program_spans

    spans = [(1, None, "serve.step", 0.0, 9.0, {}),
             (2, 1, "req.prefill", 1.0, 4.0, {}),
             (3, 2, "jit.dispatch", 1.1, 1.2, {"program": "chunk"}),
             (4, 2, "state.write", 2.0, 3.0, {"dispatches": 1}),
             (5, 4, "jit.dispatch", 2.1, 2.4, {"program": "state_write"}),
             (6, 4, "jit.dispatch", 2.5, 2.9, {"program": "another"}),
             (7, 1, "req.prefill", 5.0, 8.0, {}),
             (8, 7, "state.write", 6.0, 7.0, {"dispatches": 1}),
             (9, 8, "jit.dispatch", 6.1, 6.9, {"program": "state_write"})]
    monkeypatch.setattr(program_spans, "load", lambda r, c: (spans, 0.5, 9))
    reader = spec.load_module(spec.load_benchmark(), "layer_metrics",
                              "state_write_dispatches_per_chunk")
    assert reader.read({}, {}, None) == 1.5
    monkeypatch.setattr(program_spans, "load",
                        lambda r, c: (spans[:3], 0.5, 9))
    assert reader.read({}, {}, None) is None    # no recurrent state


def test_every_per_layer_row_has_a_reader_and_moves_what_its_cells_report():
    """What ``test_chipbench_spans.py::test_the_benchmark_names_the_five_
    metrics`` checked of PR 26's five rows, for the list as PR 28 extended
    it (that test pins the list's last five names and is red since the
    append; PERF.md section 7): every row has a reader, every
    ``program_span`` row's ``moves`` is an end-to-end metric that each of
    its cells reports, the five accepted rows stand in their order and
    the new one follows them."""
    bench = spec.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    reported = {m["name"]: set(m.get("workloads", cells))
                for m in bench["end_to_end"]}
    for row in bench["per_layer"]:
        reader = spec.load_module(bench, "layer_metrics", row["name"])
        assert callable(reader.read), row["name"]
        assert set(row.get("workloads", cells)) <= reported[row["moves"]], \
            row["name"]
    spans = [m["name"] for m in bench["per_layer"]
             if m["source"] == "program_span"]
    assert spans == ["host_exposed_pct.serve", "queue_wait_p50_ms",
                     "kv_write_dispatches_per_chunk", "warm_trace_s",
                     "train_step_host_ms_p50",
                     "state_write_dispatches_per_chunk"]
    assert [m["name"] for m in bench["per_layer"]][-5:] == [
        "mfu.serve_hybrid", "decode_hbm_share_pct", "ssm_decode_roofline",
        "ssm_decode_device_share_pct", "state_write_dispatches_per_chunk"]


FORM = spec.load_module(
    {"root": spec.ROOT, "paths": ["tests/chipbench"]}, ".",
    "test_chipbench_yardstick").TestBenchmarkFile


@pytest.mark.parametrize("check", sorted(
    n for n in vars(FORM) if n.startswith("test_")))
def test_the_tiny_hybrid_benchmark_file_has_the_benchmarks_form(check):
    """``BENCHMARK.hybrid.json`` is held to what the yardstick's own tests
    ask of the real file and of the accepted tiny one."""
    getattr(FORM(), check)(spec.load_benchmark(TINY))


# -- counts at the published widths --------------------------------------------

def test_published_keys_are_unchanged(real):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next((r for r in rows if r["name"] == "granite-4.0-h-micro"), None)
    if row is None:
        pytest.skip("the catalog is not in this sandbox")
    cfg = real["config"]
    assert {k: cfg[k] for k in row["config"]} == row["config"]
    assert real["config_row"]["source"] == row["source_url"]
    assert cfg["reduced"] == {} and real["config_row"]["reduced"] == []
    assert set(cfg["assumed"]) == {"head_dim", "initializer_range",
                                   "mamba_init", "ssm_state_dtype"}
    assert cfg["engine"] == {
        "dtype": "bfloat16", "max_seqs": 64, "page_size": 16,
        "max_len": 2048, "prefill_chunk": 256, "num_pages": None}
    assert cfg["engine"]["prefill_chunk"] == cfg["mamba_chunk_size"]


def test_parameter_and_state_counts_by_hand(real):
    cfg = real["config"]
    mamba = (2048 * 8512 + 4096 * 2048          # in_proj, out_proj
             + 4352 * 4 + 4352 + 4096 + 3 * 64  # conv, gated norm, A D dt
             + 2048 * 16384 + 8192 * 2048       # the shared MLP
             + 2 * 2048)                        # two norms
    attention = (2 * 2048 * 2048 + 2 * 2048 * 512
                 + 2048 * 16384 + 8192 * 2048 + 2 * 2048)
    assert (mamba, attention) == (76_182_976, 60_821_504)
    assert flops_hybrid.mamba_layer_params(cfg) == mamba
    assert flops_hybrid.attention_layer_params(cfg) == attention
    total = 36 * mamba + 4 * attention + 100352 * 2048 + 2048
    assert total == 3_191_396_096       # the final norm's 2,048 included
    assert flops_hybrid.params(cfg) == total == weights_hybrid.count(cfg)
    # 75.5 MB of float32 state a sequence, and the convolution's tail
    assert flops_hybrid.state_bytes_per_sequence(cfg) == \
        36 * (64 * 64 * 128 * 4 + 4352 * 3 * 2) == 76_437_504
    assert flops_hybrid.kv_bytes_per_token(cfg) == 4 * 2 * 8 * 64 * 2 == 8192
    # ISSUE 28's step: 64 live sequences, weights once, state in and out
    step = flops_hybrid.decode_step_bytes(cfg, 64, 0)
    assert step == 2 * total + 2 * 64 * 76_437_504
    assert 19.0e-3 < step / 819e9 < 20.0e-3


def test_serve_flops_by_hand(real):
    cfg = real["config"]
    matmul = 36 * (2048 * 8512 + 4096 * 2048) + 4 * (
        2 * 2048 * 2048 + 2 * 2048 * 512) + 40 * 3 * 2048 * 8192
    assert flops_hybrid.layer_matmul_params(cfg) == matmul
    recurrence = 36 * 5 * 64 * 64 * 128
    got = flops_hybrid.serve_flops(cfg, tokens=10, sampled=3, context_sum=700)
    assert got == (10 * (2 * matmul + recurrence) + 3 * 2 * 2048 * 100352
                   + 4 * 4 * 32 * 64 * 700)


def test_ssm_decode_counts(real):
    sh = ssm_counts.shape(real["config"], 64)
    assert ssm_counts.flops(sh) == 5 * 64 * 64 * 64 * 128
    assert ssm_counts.bytes(sh) == 2 * 64 * 64 * 64 * 128 * 4 \
        + 64 * (3 * 4096 + 2 * 128) * 4
    peaks = spec.peaks(spec.load_benchmark(), "TPU v5 lite")
    least, bound = roofline.least_seconds(ssm_counts, sh, "decode", peaks)
    assert bound == "bytes" and 0.32e-3 < least < 0.34e-3
    import re
    assert re.search(ssm_counts.PATTERNS["decode"][0],
                     "_ssm_decode_call.22 [tpu_custom_call] f32[64,32,128]")
    assert not re.search(ssm_counts.PATTERNS["decode"][0],
                         "_call.3 [tpu_custom_call] bf16[32,8,4,128]")


@pytest.mark.parametrize("what,sigma", [("prompt", 1.0), ("answer", 0.9)])
def test_the_mix_is_the_published_means_binned(real, what, sigma):
    """A log-normal through the published mean with the assumed sigma,
    binned at the geometric midpoints; each weight within 0.0075 of it,
    and the deck deals evenly."""
    t = real["traffic"]
    lens, weights = t[f"{what}_lens"], t[f"{what}_weights"]
    assert t["assumed"][f"{what}_sigma"] == sigma
    mean = t["published"][f"{what}_tokens"]["mean"]
    mu = math.log(mean) - sigma ** 2 / 2
    cdf = [NormalDist().cdf((math.log(math.sqrt(a * b)) - mu) / sigma)
           for a, b in zip(lens, lens[1:])]
    fit = [hi - lo for lo, hi in zip([0.0] + cdf, cdf + [1.0])]
    assert max(abs(w - f) for w, f in zip(weights, fit)) < 0.0075
    assert sum(weights) == pytest.approx(1.0)
    assert [round(w * t["deck"]) for w in weights] == \
        pytest.approx([w * t["deck"] for w in weights])
    mix_mean = sum(a * w for a, w in zip(lens, weights))
    assert mix_mean == pytest.approx({"prompt": 166.4, "answer": 323.2}[what])


def test_the_cell_is_the_issues_table(real):
    t = real["traffic"]
    assert (t["clients"], t["deck"], t["preroll_requests"],
            t["check_requests"], t["trace_seconds"]) == (64, 100, 64, 12, 6)
    assert t["prompt_lens"] == [32, 64, 128, 256, 512, 1024]
    assert t["prompt_weights"] == [0.22, 0.25, 0.26, 0.17, 0.07, 0.03]
    assert t["answer_lens"] == [64, 128, 256, 512, 1024]
    assert t["answer_weights"] == [0.15, 0.25, 0.30, 0.20, 0.10]
    assert t["generator"] == "closed_loop_hybrid" and t["eos"] is None
    assert t["kernels"] == ["ssm_decode"]
    assert real["workload"]["chips"] == 1
    assert max(t["prompt_lens"]) + max(t["answer_lens"]) <= \
        real["config"]["engine"]["max_len"]
    names = {m["name"] for m in real["per_layer"]}
    assert {"mfu.serve_hybrid", "decode_hbm_share_pct", "ssm_decode_roofline",
            "ssm_decode_device_share_pct",
            "state_write_dispatches_per_chunk"} <= names
    assert not {"mfu.serve", "paged_decode_roofline"} & names
    assert {m["name"] for m in real["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}


# -- the reference and its controls --------------------------------------------

def test_weights_repeat_and_differ_by_seed(tiny_cfg):
    a = weights_hybrid.make(tiny_cfg, 2**31 + 5, jnp.float32)
    b = weights_hybrid.make(tiny_cfg, 2**31 + 5, jnp.float32)
    c = weights_hybrid.make(tiny_cfg, 2**31 + 6, jnp.float32)
    assert set(a) == set(weights_hybrid.leaf_shapes(tiny_cfg))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["layers.0.in_proj"], c["layers.0.in_proj"])
    assert not np.array_equal(a["layers.0.in_proj"], a["layers.1.in_proj"])
    # the Mamba-2 initialisation: heads forget over 1 to 1,000 tokens
    dt = np.log1p(np.exp(np.asarray(a["layers.0.dt_bias"], np.float64)))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    A = np.exp(np.asarray(a["layers.0.A_log"], np.float64))
    assert A.min() >= 1 and A.max() <= 16
    assert np.abs(np.asarray(a["layers.0.conv_w"])).max() <= 0.5
    assert np.all(np.asarray(a["layers.0.D"]) == 1)


def test_the_reference_imports_nothing_of_the_program():
    with open(reference_hybrid.__file__) as f:
        text = f.read()
    assert "paddle_tpu" not in text.replace("program under test", "")
    assert "lax.scan(step" in text      # the recurrence, token by token


def test_controls_change_the_reference(tiny_cfg):
    """int8 operands and a bf16 state both move the reference's logits;
    the bf16 state moves nothing before the first recurrent layer has
    carried anything (one token)."""
    w = weights_hybrid.make(tiny_cfg, 7, jnp.float32)
    ids = np.random.default_rng(0).integers(0, 256, (24,)).astype(np.int32)
    plain = reference_hybrid.make_forward(tiny_cfg)(w, ids)
    for kw in (dict(quant="int8"), dict(state="bfloat16")):
        other = reference_hybrid.make_forward(tiny_cfg, **kw)(w, ids)
        assert float(jnp.abs(other - plain).max()) > 1e-4, kw
    scorer = reference_hybrid.Scorer(tiny_cfg, rows=8, bucket=16)
    lg = scorer(w, ids, 10)
    assert lg.shape == (8, 256)
    full = reference_hybrid.logits(tiny_cfg, w, plain)
    np.testing.assert_allclose(lg, full[10:18], rtol=1e-5, atol=1e-6)


def test_a_token_altered_where_it_is_produced(monkeypatch, rehearse):
    from paddle_tpu.inference.server.hybrid_executor import HybridExecutor

    sound, calls = HybridExecutor.decode, [0]

    def decode(self, sids):
        out = sound(self, sids)
        calls[0] += 1
        if calls[0] % 2 == 0:
            for sid in out:
                out[sid] = self.last_token[sid] = \
                    (out[sid] + 1) % self.config.vocab_size
        return out

    monkeypatch.setattr(HybridExecutor, "decode", decode)
    line = rehearse(CELL, seconds=0.5)
    assert line["correct"] is False
    assert _failing(line) == ["served_token_gap"]


def test_a_state_pool_that_keeps_bf16_fails_the_cell(monkeypatch, rehearse):
    """The fault ``served_token_gap`` cannot see: the program rounds its
    recurrent state to bf16 after every decode step.  Every served token
    is still the reference's; the state rows are not."""
    from paddle_tpu.inference.server.hybrid_executor import HybridExecutor

    sound = HybridExecutor.decode

    def decode(self, sids):
        out = sound(self, sids)
        ssm, conv = self.state.pools()
        self.state.set_pools(
            [p.astype(jnp.bfloat16).astype(jnp.float32) for p in ssm], conv)
        return out

    monkeypatch.setattr(HybridExecutor, "decode", decode)
    line = rehearse(CELL, seconds=0.5)
    assert line["correct"] is False
    assert _failing(line) == ["state_row_gap"]


def test_controls_are_read_beside_the_program(capsys):
    rows = control.main(["--workload", CELL, "--seeds", "2147483659",
                         "--seconds", "0.3"], bench_path=TINY, rehearse=True)
    (row,) = rows
    got = row["readings"]
    assert set(got) == {"program", "int8", "bf16_state"}
    limits = spec.limits(spec.load_benchmark(TINY), CELL)
    assert row["correct"] is True
    assert got["program"]["served_token_gap"] <= limits["served_token_gap"]
    assert got["program"]["state_row_gap"] <= limits["state_row_gap"] / 10
    # each control goes through the run's own judgement.  By the served
    # tokens they separate from the program only at real widths in bf16
    # (the limits file has the chip's readings, and the test below holds
    # them to judge); by the state rows a float32 program separates from
    # either here too
    assert row["verdicts"] == {"int8": False, "bf16_state": False}
    assert got["bf16_state"]["state_row_gap"] > 10 * limits["state_row_gap"]
    assert got["bf16_state"]["served_token_gap"] <= \
        limits["served_token_gap"]


def test_the_chips_readings_through_judge(real):
    """The committed limits against the readings they were set from, each
    with room on both sides.  ``served_token_gap``: the program's largest
    reading is correct, the int8 control's smallest is not; the bf16-state
    control mostly serves the reference's own tokens and passes it.
    ``state_row_gap``: the program's largest is correct, the bf16-state
    control's smallest is not — the one number that holds the cell to
    the float32 state its configuration states."""
    from chipbench import compare

    bench = spec.load_benchmark()
    limits = spec.limits(bench, REAL)
    with open(os.path.join(bench["root"], "chipbench", "limits",
                           REAL + ".json")) as f:
        read = json.load(f)["readings"]

    def verdict(**numbers):
        sound = {"served_token_gap": 0.0, "state_row_gap": 0.0,
                 "short_answers": 0.0}
        return harness.judge(compare.checks({**sound, **numbers},
                                            limits))[0]

    assert verdict(served_token_gap=max(read["program_served_token_gap"]),
                   state_row_gap=max(read["program_state_row_gap"])) is True
    assert verdict(served_token_gap=min(read["control_int8"])) is False
    assert verdict(served_token_gap=1.0) is False       # an altered token
    assert verdict(short_answers=1.0) is False
    assert verdict(state_row_gap=float("nan")) is False     # none held
    gap = limits["served_token_gap"]
    assert gap >= 2 * max(read["program_served_token_gap"])
    assert gap <= min(read["control_int8"]) / 2
    # the state's precision: by the tokens it passes (on most seeds) ...
    assert verdict(served_token_gap=sorted(
        read["control_bf16_state"])[len(read["control_bf16_state"]) // 2])
    # ... by the state rows it never does, with room on both sides
    low = min(read["control_bf16_state_state_row_gap"])
    assert verdict(state_row_gap=low) is False
    assert limits["state_row_gap"] >= 3 * max(read["program_state_row_gap"])
    assert limits["state_row_gap"] <= low / 3
