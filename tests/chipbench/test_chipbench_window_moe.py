"""The window + full attention configuration's part of the yardstick: the
tiny cell rehearsed on the CPU through chipbench.run's own functions (the
same generator, reference and readers at toy widths), the counts of
``flops_window_moe`` and ``kernels/paged_decode_window`` against a hand
count at the published widths, the configuration file against the
catalog, and the planted faults and controls through the run's own
``judge``."""
import json
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import (control, flops_window_moe, harness,
                       reference_window_moe, roofline, run, spec,
                       weights_window_moe)
from chipbench.kernels import paged_decode_window as kernel_counts
from paddle_tpu import obs

# the tiny cell has a benchmark file of its own beside the accepted ones
# (which are the benchmark's, and no model PR's to edit)
BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench")
TINY = os.path.join(BENCH, "BENCHMARK.window_moe.json")
TINY_LLAMA = os.path.join(BENCH, "BENCHMARK.json")
CELL = "tiny-window-moe.tiny-mixed"
REAL = "trinity-mini-serve.mixed64"
NEW = ["mfu.serve_window_moe", "decode_hbm_share_pct.window_moe",
       "window_decode_roofline", "window_decode_device_share_pct",
       "window_pool_occupancy_pct"]
SHARED = ["device_idle_pct.serve", "decode_batch_mean", "ttft_p50_ms",
          "ttft_p85_ms", "itl_p95_ms", "decode_step_ms_p50",
          "prefill_step_ms_p50", "kv_pool_occupancy_pct", "warm_programs",
          "host_exposed_pct.serve", "queue_wait_p50_ms",
          "kv_write_dispatches_per_chunk", "warm_trace_s",
          "expert_load_max_over_mean"]
CHECKS = {"served_token_gap", "kv_row_gap", "deep_row_gap", "short_answers"}


@pytest.fixture
def rehearse(capsys):
    def go(workload, seed=3_000_000_007, seconds=0.3, trace=0):
        capsys.readouterr()
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      bench_path=TINY, rehearse=True)
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0 and len(out) == 1, out
        return json.loads(out[-1])
    return go


@pytest.fixture
def traced_record(rehearse, monkeypatch, tmp_path):
    """A traced rehearsal of the tiny cell: (the record its readers got,
    its cell, the peaks)."""
    window = harness.TraceWindow
    monkeypatch.setattr(harness, "TraceWindow", lambda jax, out_dir, on:
                        window(jax, str(tmp_path / "trace"), on))
    seen = {}
    sound = run.per_layer

    def per_layer(bench, cell, record, peaks):
        seen.update(record=record, cell=cell, peaks=peaks,
                    values={k: v["value"] for k, v in
                            sound(bench, cell, record, peaks).items()})
        return sound(bench, cell, record, peaks)

    monkeypatch.setattr(run, "per_layer", per_layer)
    obs.reset()     # the span readers count this run's requests alone
    rehearse(CELL, trace=1, seconds=0.5)
    return seen


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
    window = harness.TraceWindow
    monkeypatch.setattr(harness, "TraceWindow", lambda jax, out_dir, on:
                        window(jax, str(tmp_path / "trace"), on))
    obs.reset()
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
        assert {"mfu.serve_window_moe", "decode_hbm_share_pct.window_moe",
                "window_pool_occupancy_pct", "expert_load_max_over_mean",
                "kv_write_dispatches_per_chunk"} <= wanted
    assert all(m["value"] is None for m in line["metrics"].values())
    assert set(line["checks"]) == CHECKS
    assert line["checks"]["short_answers"] == {"value": 0.0, "limit": 0}
    assert 0 <= line["checks"]["kv_row_gap"]["value"] < 1e-5
    assert 0 <= line["checks"]["deep_row_gap"]["value"] < 1e-5


def test_the_readers_read_the_counters_and_the_one_write_a_chunk(
        traced_record):
    """What a traced rehearsal's line strikes out, read again from the
    same record: one donated write a chunk for BOTH groups' pools, the
    full group's pool in use, the window group's from the executor's own
    sums, the expert counter, and shares that are numbers."""
    got, facts = traced_record["values"], traced_record["record"]["facts"]
    assert got["kv_write_dispatches_per_chunk"] == 1.0
    assert 0 < got["kv_pool_occupancy_pct"] < 100
    pages = facts["pages"]
    assert pages["pool"] == [64, 20] and pages["samples"] > 0
    assert pages["released"][0] == 0 < pages["released"][1]
    assert got["window_pool_occupancy_pct"] == pytest.approx(
        100 * pages["used"][1] / pages["samples"] / 20)
    # a row of 5 pages a sequence, 2-3 of them in use: the release shows
    assert 10 < got["window_pool_occupancy_pct"] < 70
    experts = facts["experts"]
    assert experts["steps"] > 0 and experts["rows"] > 0
    # 4 expert layers x 16 experts: at most 64 take a row in a step
    assert 0 < experts["hit"] <= 64 * experts["steps"]
    assert 1.0 <= got["expert_load_max_over_mean"] <= 16.0
    assert got["mfu.serve_window_moe"] > 0
    assert got["decode_hbm_share_pct.window_moe"] > 0
    # the keys a sliding layer's queries see: never more than 8 a token
    calls, seen = facts["decode_calls"], facts["decode_window_keys"]
    assert len(calls) == len(seen) > 0
    assert all(w <= min(keys, 8 * batch) for (batch, keys), w
               in zip(calls, seen))
    assert any(w < keys for (_, keys), w in zip(calls, seen))
    assert 0 < facts["seen_window_sum"] < facts["context_sum"]
    assert len(facts["traced"]["decode_window_keys"]) == \
        len(facts["traced"]["decode_calls"])


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_reads_a_number_from_a_tiny_run(traced_record,
                                                         name):
    """Each of the five readers, on the tiny run's own record; the two
    that read the device's trace get the kernel's seconds a chip's trace
    would hold (a CPU trace has no device plane)."""
    record = dict(traced_record["record"])
    calls = len(record["facts"]["traced"]["decode_calls"])
    record["trace"] = dict(record["trace"], busy_s=1.0, kernels={
        "paged_decode_window": {"decode": {"calls": 6 * calls,
                                           "seconds": 0.25}}})
    reader = spec.load_module(spec.load_benchmark(), "layer_metrics", name)
    value = reader.read(record, traced_record["cell"],
                        traced_record["peaks"])
    assert isinstance(value, float) and value > 0, name
    if name == "window_decode_device_share_pct":
        assert value == 25.0


def test_the_roofline_counts_visible_keys_by_layer_kind(traced_record):
    """One full layer at the lengths and five sliding ones at the windowed
    keys: a kernel that read every key on every layer would be counted
    MORE bytes than this, so its share reads low, never high."""
    record, cell = dict(traced_record["record"]), traced_record["cell"]
    traced = record["facts"]["traced"]
    record["trace"] = dict(record["trace"], kernels={
        "paged_decode_window": {"decode": {"calls": 1, "seconds": 1.0}}})
    reader = spec.load_module(spec.load_benchmark(), "layer_metrics",
                              "window_decode_roofline")
    peaks = traced_record["peaks"]
    got = reader.read(record, cell, peaks)

    def least(batch, keys):
        return roofline.least_seconds(kernel_counts, kernel_counts.shape(
            cell["config"], batch, keys), "decode", peaks)[0]

    want = sum(least(b, k) + 5 * least(b, w) for (b, k), w in
               zip(traced["decode_calls"], traced["decode_window_keys"]))
    assert got == pytest.approx(100 * want)
    uniform = sum(6 * least(b, k) for b, k in traced["decode_calls"])
    assert want < uniform


def test_new_readers_read_nothing_from_a_llama_run():
    """On a program of another shape (the accepted serve cell, or a parent
    commit without the counters) the new readers return nothing and do not
    raise."""
    bench = spec.load_benchmark(TINY_LLAMA)
    cell = spec.cell(bench, "tiny-serve.tiny-closed")
    record = {"facts": {"decode_calls": [[4, 80]], "decode_step_s": [0.01],
                        "kind": "serve", "window_s": 1.0, "steps": 0,
                        "layer_tokens": 10, "sampled_tokens": 4,
                        "context_sum": 100,
                        "traced": {"decode_calls": [[4, 80]]}},
              "trace": {"kernels": {}, "busy_s": 1.0}, "bench": bench}
    peaks = spec.peaks(bench, None)
    for name in NEW:
        reader = spec.load_module(spec.load_benchmark(), "layer_metrics",
                                  name)
        assert reader.read(record, cell, peaks) is None, name
    # ... nor from this cell's record on a parent without the counters
    record["facts"]["traced"] = None
    window_cell = spec.cell(spec.load_benchmark(), REAL)
    for name in NEW:
        reader = spec.load_module(spec.load_benchmark(), "layer_metrics",
                                  name)
        assert reader.read(record, window_cell, peaks) is None, name


def test_the_benchmarks_rows_by_name():
    """The rows this PR appends and the lists it extends, by NAME and
    membership, never by position or length, so that the next append
    leaves this test green: every row has a reader, every row's ``moves``
    is an end-to-end metric that each of its cells reports, the five new
    rows read this cell alone, the cell is on the fourteen shared lists
    and on no other."""
    bench = spec.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    assert REAL in cells and cells.count(REAL) == 1
    reported = {m["name"]: set(m.get("workloads", cells))
                for m in bench["end_to_end"]}
    rows = {m["name"]: m for m in bench["per_layer"]}
    assert len(rows) == len(bench["per_layer"])
    for name, row in rows.items():
        reader = spec.load_module(bench, "layer_metrics", name)
        assert callable(reader.read), name
        assert set(row.get("workloads", cells)) <= reported[row["moves"]], \
            name
    for name in NEW:
        assert rows[name]["workloads"] == [REAL], name
        assert rows[name]["moves"] == "serve_tokens_per_s"
    for name in SHARED:
        assert REAL in rows[name]["workloads"], name
    for name in set(rows) - set(NEW) - set(SHARED):
        assert REAL not in rows[name]["workloads"], name
    assert REAL in reported["serve_tokens_per_s"]
    assert REAL not in reported["train_tokens_per_s"]
    assert {rows[n]["layer"] for n in NEW} == {
        "serving loop", "serving data plane", "kernels"}
    assert {rows[n]["source"] for n in NEW} == {
        "host_clock", "device_trace", "program_counter"}
    assert rows["window_decode_roofline"]["unit"] == "%"
    (cfg_row,) = [c for c in bench["configs"]
                  if c["name"] == "trinity-mini-serve"]
    assert cfg_row["reduced"] == ["num_hidden_layers"]
    (wl,) = [w for w in bench["workloads"] if w["name"] == REAL]
    assert (wl["config"], wl["traffic"], wl["chips"]) == \
        ("trinity-mini-serve", "mixed64", 1)


FORM = spec.load_module(
    {"root": spec.ROOT, "paths": ["tests/chipbench"]}, ".",
    "test_chipbench_yardstick").TestBenchmarkFile


@pytest.mark.parametrize("check", sorted(
    n for n in vars(FORM) if n.startswith("test_")))
def test_the_tiny_benchmark_file_has_the_benchmarks_form(check):
    getattr(FORM(), check)(spec.load_benchmark(TINY))


@pytest.mark.parametrize("check", sorted(
    n for n in vars(FORM) if n.startswith("test_")))
def test_the_benchmark_file_keeps_its_form_with_the_new_cell(check):
    getattr(FORM(), check)(spec.load_benchmark())


# -- the configuration and the counts at the published widths ----------------

def test_published_keys_are_unchanged_and_the_cut_is_stated(real):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next((r for r in rows if r["name"] == "Trinity-Mini"), None)
    if row is None:
        pytest.skip("the catalog is not in this sandbox")
    cfg = real["config"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (value, cfg[key]) == (32, 6)
            assert cfg["reduced"][key]["published"] == value
            assert cfg["reduced"][key]["here"] == cfg[key]
            assert cfg["reduced"][key]["why"]
        else:
            assert cfg[key] == value, key       # layer_types whole, too
    assert real["config_row"]["source"] == row["source_url"] == cfg["source"]
    assert real["config_row"]["reduced"] == ["num_hidden_layers"] \
        == list(cfg["reduced"])
    assert flops_window_moe.layer_types(cfg) == [
        "sliding_attention"] * 3 + ["full_attention"] \
        + ["sliding_attention"] * 2
    assert {"embedding_scale", "q_norm_k_norm", "gate_proj", "rope",
            "sliding_mask", "four_norms", "router", "expert_bias",
            "initializer_range"} <= set(cfg["assumed"])
    assert "8.4 M parameters a layer" in cfg["assumed"]["note"]
    assert "all 128 experts" in cfg["deployment"] and cfg["distorts"]
    assert cfg["engine"] == {
        "dtype": "bfloat16", "max_seqs": 64, "page_size": 128,
        "max_len": 14336, "prefill_chunk": 1024, "num_pages": None}
    assert set(cfg["engine_notes"]) >= {"page_size", "prefill_chunk",
                                        "num_pages", "size"}
    # the guide's floors: a whole period and four layers after the dense
    # ones; every expert and the whole vocabulary are held
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] >= 4 \
        == cfg["global_attn_every_n_layers"]
    assert (cfg["num_experts"], cfg["vocab_size"]) == (128, 200192)


def test_parameter_counts_by_hand(real):
    """ISSUE 34's arithmetic: attention 27.26 M a layer (q, o and the gate
    8,388,608 each, k and v 1,048,576 each, two 128-wide norms), four
    norms 8,192, a dense layer 65,020,160, an expert 6,291,456, an expert
    layer 839,131,520, embedding and head 409,993,216 each, the final
    norm 2,048; 4,306,554,880 in all = 8.61 GB in bf16."""
    cfg = real["config"]
    matrices = 3 * 2048 * 4096 + 2 * 2048 * 512
    expert = 3 * 2048 * 1024
    assert (matrices + 256, expert) == (27_263_232, 6_291_456)
    assert flops_window_moe.attention_params(cfg) == matrices
    assert flops_window_moe.expert_params(cfg) == expert
    norms = 4 * 2048 + 2 * 128
    dense = matrices + norms + 3 * 2048 * 6144
    moe = matrices + norms + 2048 * 128 + 128 + 129 * expert
    assert (dense, moe) == (65_020_160, 839_131_520)
    total = 2 * dense + 4 * moe + 2 * 200192 * 2048 + 2048
    assert total == 4_306_554_880
    assert flops_window_moe.params(cfg) == total \
        == weights_window_moe.count(cfg)
    assert round(2 * total / 1e9, 2) == 8.61
    # the pools: 1 layer x 64 x 112 pages and 5 layers x 64 x 25 pages of
    # 4 heads x 128 tokens x 128 dims, K and V
    page = 2 * 4 * 128 * 128 * 2
    assert 64 * 112 * page == 1_879_048_192
    assert 5 * 64 * 25 * page == 2_097_152_000
    assert 6 * 64 * 112 * page == 11_274_289_152    # a uniform cache
    assert flops_window_moe.kv_bytes_per_token(cfg) == 2048
    assert flops_window_moe.layers(cfg) == (1, 5, 2, 4)


def test_serve_flops_and_decode_bytes_by_hand(real):
    cfg = real["config"]
    attention, expert = 27_262_976, 6_291_456
    per_token = (6 * attention + 2 * 3 * 2048 * 6144
                 + 4 * (2048 * 128 + (1 + 8) * expert))
    assert flops_window_moe.matmul_params_per_token(cfg) == per_token
    got = flops_window_moe.serve_flops(cfg, tokens=10, sampled=3,
                                       seen_full=9000, seen_window=700)
    assert got == (10 * 2 * per_token + 3 * 2 * 2048 * 200192
                   + 4 * 32 * 128 * (9000 + 5 * 700))
    # min(position + 1, window) a token, in closed form
    for first, n, w in [(0, 5, 8), (0, 20, 8), (5, 10, 8), (7, 3, 8),
                        (8, 4, 8), (100, 7, 8), (0, 1024, 2048),
                        (1024, 1024, 2048), (2048, 1024, 2048)]:
        assert flops_window_moe.seen_by_window(first, n, w) == \
            sum(min(p + 1, w) for p in range(first, first + n))
    # ISSUE 34's step: 98 % of 512 experts hit, 64 sequences of ~7.5 k
    # live keys on the full layer and 2,048 on five sliding ones
    step = flops_window_moe.decode_step_bytes(
        cfg, keys_full=64 * 7500, keys_window=64 * 2048, experts_hit=502)
    fixed = 6 * attention + 2 * 3 * 2048 * 6144 \
        + 4 * (2048 * 128 + expert) + 2048 * 200192
    assert step == 2 * (fixed + 502 * expert) \
        + (64 * 7500 + 5 * 64 * 2048) * 2048
    assert 12.0e-3 < step / 819e9 < 12.6e-3
    assert flops_window_moe.decode_step_bytes(cfg, 0, 0, 0) == 2 * fixed
    assert 7.6e9 < 2 * (fixed + 502 * expert) < 7.9e9


def test_window_decode_counts(real):
    sh = kernel_counts.shape(real["config"], 64, 64 * 2048)
    assert kernel_counts.flops(sh) == 4 * 32 * 128 * 64 * 2048
    assert kernel_counts.bytes(sh) == 64 * 2048 * 2048 + 2 * 64 * 32 * 128 * 2
    peaks = spec.peaks(spec.load_benchmark(), "TPU v5 lite")
    least, bound = roofline.least_seconds(kernel_counts, sh, "decode", peaks)
    # 268 MB and 2.1 GFLOP a sliding layer a step: 8 FLOP a byte
    assert bound == "bytes" and 0.32e-3 < least < 0.34e-3
    pat = kernel_counts.PATTERNS["decode"][0]
    assert re.search(pat, "_call.3 [tpu_custom_call] bf16[64,4,8,128]")
    assert not re.search(pat, "_mla_decode_call.7 [tpu_custom_call]")
    assert not re.search(pat, "_ssm_decode_call.22 [tpu_custom_call]")


def test_the_cell_is_the_issues_table(real):
    t = real["traffic"]
    assert (t["clients"], t["deck"], t["trace_seconds"]) == (64, 100, 6)
    assert t["prompt_lens"] == [1024, 6144, 12288]
    assert t["answer_lens"] == [512, 1024, 1536]
    assert t["prompt_weights"] == t["answer_weights"] == [0.3, 0.4, 0.3]
    assert round(sum(n * w for n, w in zip(
        t["prompt_lens"], t["prompt_weights"])), 6) == 6451.2
    assert sum(n * w for n, w in zip(t["answer_lens"],
                                     t["answer_weights"])) == 1024
    assert (t["ramp_tokens"], t["preroll_requests"], t["check_requests"],
            t["row_requests"]) == (16, 36, 3, 2)
    assert t["generator"] == "closed_loop_window_moe" and t["eos"] is None
    assert t["sampling"] == "greedy" and t["shared_prefixes"] is False
    assert t["kernels"] == ["paged_decode_window"]
    assert set(t["assumed"]) == {"lists", "ratio", "why"}
    assert "2407.00079" in t["source"]
    assert real["workload"]["chips"] == 1
    cfg = real["config"]
    assert max(t["prompt_lens"]) + max(t["answer_lens"]) <= \
        cfg["engine"]["max_len"]
    assert all(n % cfg["engine"]["prefill_chunk"] == 0
               for n in t["prompt_lens"])      # whole chunks: twelve programs
    # the shortest request never leaves the window, the longest reads a
    # sixth of its keys on a sliding layer
    assert min(t["prompt_lens"]) + min(t["answer_lens"]) \
        <= cfg["sliding_window"] < max(t["prompt_lens"]) // 5
    names = {m["name"] for m in real["per_layer"]}
    assert names == set(NEW) | set(SHARED)
    assert {m["name"] for m in real["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    limits = spec.limits(spec.load_benchmark(), REAL)
    assert set(limits) == CHECKS and limits["short_answers"] == 0


# -- where the window opens ------------------------------------------------------

PR32 = spec.load_module({"root": spec.ROOT, "paths": ["tests/chipbench"]},
                        ".", "test_chipbench_mla_moe")


@pytest.mark.parametrize("seed", [0, 7, 2147483801, 3_400_000_019])
def test_the_window_opens_where_the_span_readers_look_for_it(real, seed):
    """``program_spans.py`` opens the window after the step in which the
    ``preroll_requests``-th request of the loop finished.  The cell's
    pre-roll (the scheduler's step order played without a model) ends in
    that very step, with every client started well before it."""
    ramp_steps, loop, t = PR32._preroll(real, seed)
    assert len(loop.live) == t["clients"]
    assert len(loop.done) >= t["preroll_requests"]
    done_before = sum(1 for s in loop.done
                      if len(s.stamps) and s.stamps[-1] < loop.steps[-1]["t"])
    assert done_before < t["preroll_requests"]
    # ~22 steps a client (16 tokens and a prefill of 1 to 12 chunks)
    assert ramp_steps + 200 < len(loop.steps)
    assert t["clients"] + t["preroll_requests"] == t["deck"]
    assert 1200 < ramp_steps < 1700


# -- the reference and its controls --------------------------------------------

def test_weights_repeat_and_differ_by_seed(tiny_cfg):
    a = weights_window_moe.layer(tiny_cfg, 2**31 + 5, 2, jnp.float32)
    b = weights_window_moe.layer(tiny_cfg, 2**31 + 5, 2, jnp.float32)
    c = weights_window_moe.layer(tiny_cfg, 2**31 + 6, 2, jnp.float32)
    d = weights_window_moe.layer(tiny_cfg, 2**31 + 5, 3, jnp.float32)
    assert set(a) == set(weights_window_moe.layer_shapes(tiny_cfg, "moe"))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["q"], c["q"])
    assert not np.array_equal(a["q"], d["q"])
    assert a["router"].shape == (64, 16)
    assert a["experts_gate_up"].shape == (16, 64, 64)   # every expert held
    assert a["gate"].shape == a["q"].shape == (64, 64)
    assert all(np.all(np.asarray(a[k]) == 1) for k in
               ("ln1", "ln2", "ln3", "ln4", "q_norm", "k_norm"))
    bias = np.asarray(a["router_bias"], np.float64)
    assert 0 < np.abs(bias).max() < 0.05            # N(0, 0.01): it is drawn
    assert set(weights_window_moe.layer(tiny_cfg, 1, 0, jnp.float32)) == \
        set(weights_window_moe.layer_shapes(tiny_cfg, "dense"))
    assert weights_window_moe.kinds(tiny_cfg) == ["dense"] * 2 + ["moe"] * 4


def test_the_reference_imports_nothing_of_the_program():
    with open(reference_window_moe.__file__) as f:
        text = f.read()
    assert "paddle_tpu" not in text.replace("program under test", "")
    imports = re.findall(r"^(?:import|from) (\S+)", text, flags=re.M)
    assert set(imports) == {"functools", "jax", "jax.numpy", "numpy",
                            "chipbench.reference_hybrid"}
    # no cache, no kernel, no block table: the mask is written out
    code = text[text.index('"""', 3):]
    assert "page" not in code and "pallas" not in code
    assert "visible(at[:, None], keys[None, :], window)" in code


def test_controls_change_the_reference(tiny_cfg):
    """Each control against the plain reference on 24 tokens (a window of
    8): int8 operands move the first layer's rows; one expert fewer moves
    nothing before the first expert layer; the window ignored moves
    nothing inside the first 8 tokens and the first layer's OUTPUT after
    them (its own K and V not at all); rope on the full layer moves that
    layer's keys and nothing before it."""
    ids = np.random.default_rng(0).integers(0, 256, (24,)).astype(np.int32)
    top = weights_window_moe.top(tiny_cfg, 7, jnp.float32)

    def go(**kw):
        scorer = reference_window_moe.Scorer(tiny_cfg, rows=8, bucket=8,
                                             **kw)
        hidden, rows = scorer.forward(
            top, lambda n: weights_window_moe.layer(tiny_cfg, 7, n,
                                                    jnp.float32),
            [(ids, 18)], keep_rows=[0], keep_layers=range(6))
        return (np.asarray(hidden[0]), rows[0],
                scorer.logits(top, hidden[0], 10))

    plain, rows, lg = go()
    assert rows[0].shape == (24, 2, 2, 16) and lg.shape == (8, 256)
    low, low_rows, _ = go(quant="int8")
    assert np.abs(low_rows[0] - rows[0]).max() > 1e-3
    less, less_rows, _ = go(top_k=3)
    assert all(np.array_equal(less_rows[n], rows[n]) for n in (0, 1, 2))
    assert np.abs(less_rows[3] - rows[3]).max() > 1e-3
    wide, wide_rows, _ = go(ignore_window=True)
    assert np.array_equal(wide_rows[0], rows[0])
    assert np.array_equal(wide_rows[1][:8], rows[1][:8])
    assert np.abs(wide_rows[1][8:] - rows[1][8:]).max() > 1e-3
    roped, roped_rows, _ = go(rope_on_full=True)
    assert all(np.array_equal(roped_rows[n], rows[n]) for n in (0, 1, 2))
    assert np.abs(roped_rows[3][:, 0] - rows[3][:, 0]).max() > 1e-3  # K
    assert np.array_equal(roped_rows[3][:, 1], rows[3][:, 1])        # V
    for other in (low, less, wide, roped):
        assert np.abs(other - plain).max() > 1e-3


# -- planted faults, through the run's own judge -----------------------------

def test_a_token_altered_where_it_is_produced(monkeypatch, rehearse):
    from paddle_tpu.inference.server.window_executor import WindowExecutor

    sound, calls = WindowExecutor.decode, [0]

    def decode(self, sids):
        out = sound(self, sids)
        calls[0] += 1
        if calls[0] % 5 == 0:
            out = {s: (t + 1) % 256 for s, t in out.items()}
            self.last_token.update(out)
        return out

    monkeypatch.setattr(WindowExecutor, "decode", decode)
    line = rehearse(CELL, seconds=0.5)
    assert line["correct"] is False
    assert "served_token_gap" in _failing(line)


def test_a_pool_rounded_to_8_bits_fails_the_cell(monkeypatch, rehearse):
    """Both groups' pools keep 8 bits of every value after each write: the
    rows held no longer match in the first sliding layer."""
    from paddle_tpu.inference.paged import PagedKVCache

    sound = PagedKVCache.set_pools

    def set_pools(self, kps, vps):
        def rough(pool):
            scale = jnp.max(jnp.abs(pool)) / 127.0 + 1e-30
            return (jnp.round(pool / scale) * scale).astype(pool.dtype)
        sound(self, [rough(p) for p in kps], [rough(p) for p in vps])

    monkeypatch.setattr(PagedKVCache, "set_pools", set_pools)
    line = rehearse(CELL, seconds=0.5)
    assert line["correct"] is False
    assert "kv_row_gap" in _failing(line)


def test_top_3_routing_fails_the_cell(monkeypatch, rehearse):
    """The program routes every token to one expert fewer than published
    (3 of 16 here): the last layer's rows see it."""
    from paddle_tpu.models import moe

    sound = moe.route

    def route(h, gate_w, bias, top_k, scale, eps=0.0):
        return sound(h, gate_w, bias, top_k - 1, scale, eps)

    monkeypatch.setattr(moe, "route", route)
    line = rehearse(CELL, seconds=0.5)
    assert line["correct"] is False
    assert "deep_row_gap" in _failing(line)


def test_rope_on_the_full_layer_fails_the_cell(monkeypatch, rehearse):
    from paddle_tpu.models import window_moe as wm

    sound = wm.attention_inputs
    monkeypatch.setattr(wm, "attention_inputs", lambda cfg, lp, x, at, s:
                        sound(cfg, lp, x, at, True))
    line = rehearse(CELL, seconds=0.5)
    assert line["correct"] is False
    assert {"kv_row_gap", "deep_row_gap"} <= set(_failing(line))


def test_a_program_that_ignores_the_window_fails_the_cell(monkeypatch,
                                                          rehearse):
    """The program built with a window no request reaches: its sliding
    layers attend every key and its cache keeps every page, so the rows
    it holds begin before the visible span — the comparison says so
    without reading a number."""
    from chipbench import program_window_moe

    sound = program_window_moe.build_model
    monkeypatch.setattr(program_window_moe, "build_model", lambda cfg, dtype:
                        sound(dict(cfg, sliding_window=10**6), dtype))
    line = rehearse(CELL, seconds=0.5)
    assert line["correct"] is False
    failing = _failing(line)
    assert {"kv_row_gap", "deep_row_gap"} <= set(failing)
    assert line["checks"]["deep_row_gap"]["value"] == float("inf")


def test_a_wider_mask_alone_fails_the_cell(monkeypatch, rehearse):
    """The chunk program's mask lets a sliding layer's query see two keys
    more than the window: the cache releases what it should, the rows of
    the last layer no longer match."""
    from paddle_tpu.models import window_moe as wm

    sound = wm.visible
    monkeypatch.setattr(wm, "visible", lambda i, j, window: sound(
        i, j, None if window is None else window + 2))
    line = rehearse(CELL, seconds=0.5)
    assert line["correct"] is False
    assert "deep_row_gap" in _failing(line)


def test_a_release_that_does_not_happen_fails_the_run(monkeypatch, rehearse):
    """The window group's pool is sized for window + chunk a sequence: a
    cache that never releases outgrows a sequence's row, and the run ends
    there (the scheduler lets the error out of ``step()``), not in a
    quiet pass."""
    from paddle_tpu.inference.paged import PagedKVCache

    monkeypatch.setattr(PagedKVCache, "release", lambda self, seqs: 0)
    with pytest.raises(RuntimeError, match="pages > per-seq budget 5"):
        rehearse(CELL, seconds=0.3)


def test_a_page_released_too_early_fails_the_cell(monkeypatch, rehearse):
    """A release one page too eager: a page with visible keys is gone from
    the rows held (and another sequence may write it)."""
    from paddle_tpu.inference.paged import PagedKVCache

    sound = PagedKVCache.release

    def release(self, seqs):
        self.lengths[seqs] += self.page_size
        try:
            return sound(self, seqs)
        finally:
            self.lengths[seqs] -= self.page_size

    monkeypatch.setattr(PagedKVCache, "release", release)
    line = rehearse(CELL, seconds=0.5)
    assert line["correct"] is False
    assert set(_failing(line)) & {"kv_row_gap", "deep_row_gap",
                                  "served_token_gap"}


def test_controls_are_read_beside_the_program(capsys):
    rows = control.main(["--workload", CELL, "--seeds", "2147483659",
                         "--seconds", "0.3"], bench_path=TINY, rehearse=True)
    (row,) = rows
    got = row["readings"]
    assert set(got) == {"program", "int8", "top_k_less_1", "window_ignored",
                        "rope_on_full"}
    assert all(set(nums) == CHECKS for nums in got.values())
    assert row["correct"] is True
    assert row["verdicts"] == {"int8": False, "top_k_less_1": False,
                               "window_ignored": False,
                               "rope_on_full": False}
    limits = spec.limits(spec.load_benchmark(TINY), CELL)
    # the two controls that are this architecture's fail by the last
    # layer's rows, as the cell asks of a request beyond the window
    for who in ("window_ignored", "rope_on_full", "top_k_less_1"):
        assert got[who]["deep_row_gap"] > limits["deep_row_gap"], who
    assert got["int8"]["kv_row_gap"] > limits["kv_row_gap"]
    assert got["program"]["deep_row_gap"] < limits["deep_row_gap"] / 100
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1])["verdicts"] == row["verdicts"]
