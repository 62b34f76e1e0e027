"""The latent-attention + routed-experts configuration's part of the
yardstick: the tiny cell rehearsed on the CPU through chipbench.run's own
functions (the same generator, reference and readers at toy widths), the
counts of ``flops_mla_moe`` and ``kernels/mla_decode`` against a hand
count at the published widths, the configuration file against the
catalog, and the planted faults and controls through the run's own
``judge``."""
import json
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import (compare, control, flops_mla_moe, harness,
                       reference_mla_moe, roofline, run, spec,
                       weights_mla_moe)
from chipbench.kernels import mla_decode as mla_counts
from paddle_tpu import obs

# the tiny cell has a benchmark file of its own beside the accepted ones
# (which are the benchmark's, and no model PR's to edit)
BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench")
TINY = os.path.join(BENCH, "BENCHMARK.mla_moe.json")
TINY_LLAMA = os.path.join(BENCH, "BENCHMARK.json")
CELL = "tiny-mla-moe.tiny-long"
REAL = "sarvam105b-serve.long64"
NEW = ["mfu.serve_mla_moe", "decode_hbm_share_pct.mla_moe",
       "mla_decode_roofline", "mla_decode_device_share_pct",
       "expert_load_max_over_mean"]
SHARED = ["device_idle_pct.serve", "decode_batch_mean", "ttft_p50_ms",
          "ttft_p85_ms", "itl_p95_ms", "decode_step_ms_p50",
          "prefill_step_ms_p50", "kv_pool_occupancy_pct", "warm_programs",
          "host_exposed_pct.serve", "queue_wait_p50_ms",
          "kv_write_dispatches_per_chunk", "warm_trace_s"]


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
        assert {"mfu.serve_mla_moe", "decode_hbm_share_pct.mla_moe",
                "expert_load_max_over_mean",
                "kv_write_dispatches_per_chunk"} <= wanted
    assert all(m["value"] is None for m in line["metrics"].values())
    assert set(line["checks"]) == {"served_token_gap", "latent_row_gap",
                                   "deep_row_gap", "short_answers"}
    assert line["checks"]["short_answers"] == {"value": 0.0, "limit": 0}
    assert 0 <= line["checks"]["latent_row_gap"]["value"] < 1e-5
    assert 0 <= line["checks"]["deep_row_gap"]["value"] < 1e-5


def test_the_readers_read_the_counter_and_the_one_write_a_chunk(
        rehearse, monkeypatch, tmp_path):
    """What a traced rehearsal's line strikes out, read again from the
    same record: one donated write a chunk, a pool in use, the expert
    counter's two readings, and a share of the peak that is a number."""
    window = harness.TraceWindow
    monkeypatch.setattr(harness, "TraceWindow", lambda jax, out_dir, on:
                        window(jax, str(tmp_path / "trace"), on))
    seen = {}
    sound = run.per_layer

    def per_layer(bench, cell, record, peaks):
        out = sound(bench, cell, record, peaks)
        seen.update({k: v["value"] for k, v in out.items()},
                    experts=record["facts"]["experts"])
        return out

    monkeypatch.setattr(run, "per_layer", per_layer)
    obs.reset()
    rehearse(CELL, trace=1, seconds=0.5)
    assert seen["kv_write_dispatches_per_chunk"] == 1.0
    assert 0 < seen["kv_pool_occupancy_pct"] < 100
    experts = seen["experts"]
    assert experts["steps"] > 0 and experts["rows"] > 0
    # 3 expert layers x 4 held experts: at most 12 take a row in a step
    assert 0 < experts["hit"] <= 12 * experts["steps"]
    assert 1.0 <= seen["expert_load_max_over_mean"] <= 4.0
    assert seen["mfu.serve_mla_moe"] > 0
    assert seen["decode_hbm_share_pct.mla_moe"] > 0


def test_new_readers_read_nothing_from_a_llama_run():
    """On a program of another shape (the accepted serve cell, or a parent
    commit without the counter) the new readers return nothing and do not
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


def test_every_per_layer_row_has_a_reader_and_moves_what_its_cells_report():
    """The list as this PR extends it (``test_chipbench_hybrid.py`` pins
    its last five names as PR 28 left them and is red since this append,
    as ``test_chipbench_spans.py``'s pin is since PR 28's; PERF.md section
    7): every row has a reader, every row's ``moves`` is an end-to-end
    metric that each of its cells reports, PR 28's five stand in their
    order and the new five follow them; the new cell is appended to the
    thirteen shared lists and to no other."""
    bench = spec.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[-1] == REAL and len(cells) == 4
    reported = {m["name"]: set(m.get("workloads", cells))
                for m in bench["end_to_end"]}
    for row in bench["per_layer"]:
        reader = spec.load_module(bench, "layer_metrics", row["name"])
        assert callable(reader.read), row["name"]
        assert set(row.get("workloads", cells)) <= reported[row["moves"]], \
            row["name"]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-10:-5] == [
        "mfu.serve_hybrid", "decode_hbm_share_pct", "ssm_decode_roofline",
        "ssm_decode_device_share_pct", "state_write_dispatches_per_chunk"]
    assert names[-5:] == NEW
    for row in bench["per_layer"]:
        if row["name"] in NEW:
            assert row["workloads"] == [REAL]
            assert row["moves"] == "serve_tokens_per_s"
        elif row["name"] in SHARED:
            assert row["workloads"][-1] == REAL, row["name"]
        else:
            assert REAL not in row["workloads"], row["name"]
    assert REAL in reported["serve_tokens_per_s"]
    roofs = [m for m in bench["per_layer"] if m["name"].endswith("_roofline")]
    assert all(m["unit"] == "%" for m in roofs)


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
    row = next((r for r in rows if r["name"] == "sarvam-105b"), None)
    if row is None:
        pytest.skip("the catalog is not in this sandbox")
    cfg = real["config"]
    cut = {"num_hidden_layers": (32, 5), "num_experts": (128, 32),
           "vocab_size": (262144, 65536)}
    for key, value in row["config"].items():
        if key in cut:
            assert (value, cfg[key]) == cut[key], key
            assert cfg["reduced"][key]["published"] == value
            assert cfg["reduced"][key]["here"] == cfg[key]
        else:
            assert cfg[key] == value, key
    assert real["config_row"]["source"] == row["source_url"] == cfg["source"]
    assert real["config_row"]["reduced"] == list(cut) == list(cfg["reduced"])
    assert cfg["share"] == {"chips_sharing_a_layer": 4, "this_chip": 0,
                            "router_experts": 128, "held_experts": [0, 32],
                            "vocab_rows": [0, 65536]}
    assert set(cfg["assumed"]) == {
        "q_lora_rank", "router", "use_qk_norm", "rope_pairs",
        "initializer_range", "expert_bias", "latent_pool_dtype"}
    assert "expert parallelism" in cfg["deployment"]
    assert cfg["engine"] == {
        "dtype": "bfloat16", "max_seqs": 64, "page_size": 128,
        "max_len": 8192, "prefill_chunk": 1024, "num_pages": None}
    # the guide's floors: four layers after the dense one, 8 experts, 1/8
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= 262144


def test_parameter_counts_by_hand(real):
    """ISSUE 32's arithmetic: attention 94.63 M a layer, an expert 25.17 M,
    an expert layer of one chip's share 925.6 M, the dense layer 295.96 M,
    embedding + head 536.9 M each; 4.535 B in all = 9.07 GB in bf16."""
    cfg = real["config"]
    attention = 4096 * 12288 + 4096 * 576 + 512 * 16384 + 8192 * 4096
    expert = 3 * 4096 * 2048
    assert (attention, expert) == (94_633_984, 25_165_824)
    assert flops_mla_moe.attention_params(cfg) == attention
    assert flops_mla_moe.expert_params(cfg) == expert
    norms = 2 * 4096 + 512 + 192
    moe = attention + norms + 4096 * 128 + 128 + 33 * expert
    dense = attention + norms + 3 * 4096 * 16384
    assert round(moe / 1e6, 1) == 925.6 and round(dense / 1e6, 2) == 295.97
    total = dense + 4 * moe + 2 * 65536 * 4096 + 4096
    assert flops_mla_moe.params(cfg) == total == weights_mla_moe.count(cfg)
    assert round(total / 1e9, 3) == 4.535 and round(2 * total / 1e9, 2) == 9.07
    # the latent pool: 5 layers x 64 x 8,192 tokens x 640 lanes x 2 B
    assert 5 * 64 * 8192 * 640 * 2 == 3_355_443_200
    assert flops_mla_moe.latent_bytes_per_token(cfg) == 5 * 1152


def test_serve_flops_and_decode_bytes_by_hand(real):
    cfg = real["config"]
    attention, expert = 94_633_984, 25_165_824
    per_token = (5 * attention + 3 * 4096 * 16384
                 + 4 * (4096 * 128 + (1 + 2) * expert))
    assert flops_mla_moe.held_choices_per_token(cfg) == 2.0
    assert flops_mla_moe.matmul_params_per_token(cfg) == per_token
    got = flops_mla_moe.serve_flops(cfg, tokens=10, sampled=3,
                                    context_sum=700)
    assert got == (10 * 2 * per_token + 3 * 2 * 4096 * 65536
                   + 5 * 2 * 64 * 320 * 700)
    # ISSUE 32's step: every held expert hit, 64 x 4.7 k live rows
    step = flops_mla_moe.decode_step_bytes(cfg, keys=64 * 4700,
                                           experts_hit=128)
    fixed = 5 * attention + 3 * 4096 * 16384 + 4 * (4096 * 128 + expert) \
        + 4096 * 65536
    assert step == 2 * (fixed + 128 * expert) + 64 * 4700 * 5760
    assert 12.0e-3 < step / 819e9 < 13.0e-3
    assert flops_mla_moe.decode_step_bytes(cfg, 0, 0) == 2 * fixed


def test_mla_decode_counts(real):
    sh = mla_counts.shape(real["config"], 64, 64 * 4700)
    assert mla_counts.flops(sh) == 2 * 64 * (576 + 512) * 64 * 4700
    assert mla_counts.bytes(sh) == 64 * 4700 * 1152 + 64 * 64 * 1088 * 2
    peaks = spec.peaks(spec.load_benchmark(), "TPU v5 lite")
    least, bound = roofline.least_seconds(mla_counts, sh, "decode", peaks)
    # 347 MB and 42 GFLOP a layer a step: 121 FLOP a byte, bound by bytes
    assert bound == "bytes" and 0.42e-3 < least < 0.44e-3
    assert 117 < mla_counts.flops(sh) / mla_counts.bytes(sh) < 122
    pat = mla_counts.PATTERNS["decode"][0]
    assert re.search(pat, "_mla_decode_call.7 [tpu_custom_call] "
                          "bf16[64,64,512]")
    assert not re.search(pat, "_call.3 [tpu_custom_call] bf16[32,8,4,128]")
    assert not re.search(pat, "_ssm_decode_call.22 [tpu_custom_call]")


def test_the_cell_is_the_issues_table(real):
    t = real["traffic"]
    assert (t["clients"], t["deck"], t["trace_seconds"]) == (64, 100, 6)
    assert t["prompt_lens"] == [2048, 4096, 6144]
    assert t["answer_lens"] == [512, 1024, 1536]
    assert t["prompt_weights"] == t["answer_weights"] == [0.3, 0.4, 0.3]
    assert sum(n * w for n, w in zip(t["prompt_lens"],
                                     t["prompt_weights"])) == 4096
    assert sum(n * w for n, w in zip(t["answer_lens"],
                                     t["answer_weights"])) == 1024
    assert t["generator"] == "closed_loop_mla_moe" and t["eos"] is None
    assert t["sampling"] == "greedy" and t["shared_prefixes"] is False
    assert t["kernels"] == ["mla_decode"]
    assert set(t["assumed"]) == {"lists", "ratio", "why"}
    assert real["workload"]["chips"] == 1
    cfg = real["config"]
    assert max(t["prompt_lens"]) + max(t["answer_lens"]) <= \
        cfg["engine"]["max_len"]
    assert all(n % cfg["engine"]["prefill_chunk"] == 0
               for n in t["prompt_lens"])      # whole chunks: six programs
    names = {m["name"] for m in real["per_layer"]}
    assert names == set(NEW) | set(SHARED)
    assert {m["name"] for m in real["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}


# -- where the window opens ------------------------------------------------------

class _StepOrder:
    """The scheduler's step order without a model (``Scheduler.step``:
    every running request decodes one token, the waiting ones are
    admitted, each request in prefill takes one chunk and emits its first
    token with its last), behind what ``closed_loop.Loop`` uses of an
    engine."""

    def __init__(self, chunk):
        from types import SimpleNamespace as NS

        self.chunk, self.reqs, self.live = chunk, {}, []
        self.executor = NS(prefill_events=[], free_pages=0,
                           cache=NS(num_pages=0))
        self.NS = NS

    def submit(self, prompt, max_new_tokens):
        r = self.NS(rid=len(self.reqs), plen=len(prompt), n=0,
                    asked=max_new_tokens, prefill_done=0, terminal=False,
                    state=self.NS(value="finished"), finish_reason="length")
        self.reqs[r.rid] = r
        self.live.append(r)
        return r

    def request(self, rid):
        return self.reqs[rid]

    def step(self):
        emitted = {}
        for r in self.live:
            if r.prefill_done == r.plen:
                emitted[r.rid] = [0]
            else:
                r.prefill_done = min(r.plen, r.prefill_done + self.chunk)
                self.executor.prefill_events.append(r.rid)
                if r.prefill_done == r.plen:
                    emitted[r.rid] = [0]
        for rid in emitted:
            r = self.reqs[rid]
            r.n += 1
            r.terminal = r.n == r.asked
        self.live = [r for r in self.live if not r.terminal]
        return emitted


def _preroll(cell, seed, **changed):
    import contextlib
    from types import SimpleNamespace as NS

    bench = spec.load_benchmark()
    gen = spec.load_module(bench, "generators", "closed_loop_mla_moe")
    base = spec.load_module(bench, "generators", "closed_loop")
    traffic = dict(cell["traffic"], **changed)
    loop = base.Loop(
        _StepOrder(cell["config"]["engine"]["prefill_chunk"]),
        base.Dealer({"vocab_size": 4}, traffic, seed), 1,
        NS(span=lambda name: contextlib.nullcontext()))
    return gen.preroll(loop, traffic), loop, traffic


@pytest.mark.parametrize("seed", [0, 7, 1423182242, 2147483801, 2**31 + 5,
                                  3_000_000_007])
def test_the_window_opens_where_the_span_readers_look_for_it(real, seed):
    """``program_spans.py`` opens the window after the step in which the
    ``preroll_requests``-th request of the loop finished.  The cell's
    pre-roll ends in that very step, with every client started well
    before it and in flight."""
    ramp_steps, loop, t = _preroll(real, seed)
    assert len(loop.live) == t["clients"]
    # the pre-roll's last step is the one that finish happened in
    assert len(loop.done) >= t["preroll_requests"]
    done_before = sum(1 for s in loop.done
                      if len(s.stamps) and s.stamps[-1] < loop.steps[-1]["t"])
    assert done_before < t["preroll_requests"]
    # the ramp is over long before, ~19 steps a client (16 tokens and its
    # prefill), and the deck's first pass has just been dealt
    assert ramp_steps + 200 < len(loop.steps)
    assert t["clients"] + t["preroll_requests"] == t["deck"]
    assert 1100 < ramp_steps < 1300


def test_a_preroll_shorter_than_the_ramp_ends_the_run(real):
    with pytest.raises(SystemExit, match="preroll_requests is too small"):
        _preroll(real, 7, preroll_requests=4)


# -- the reference and its controls --------------------------------------------

def test_weights_repeat_and_differ_by_seed(tiny_cfg):
    a = weights_mla_moe.layer(tiny_cfg, 2**31 + 5, 1, jnp.float32)
    b = weights_mla_moe.layer(tiny_cfg, 2**31 + 5, 1, jnp.float32)
    c = weights_mla_moe.layer(tiny_cfg, 2**31 + 6, 1, jnp.float32)
    d = weights_mla_moe.layer(tiny_cfg, 2**31 + 5, 2, jnp.float32)
    assert set(a) == set(weights_mla_moe.layer_shapes(tiny_cfg, "moe"))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["kv_a"], c["kv_a"])
    assert not np.array_equal(a["kv_a"], d["kv_a"])
    assert a["router"].shape == (64, 16)            # the router's whole width
    assert a["experts_gate_up"].shape == (4, 64, 64)    # the experts held
    assert np.all(np.asarray(a["kv_norm"]) == 1)
    bias = np.asarray(a["router_bias"], np.float64)
    assert 0 < np.abs(bias).max() < 0.05            # N(0, 0.01): it is drawn
    assert set(weights_mla_moe.layer(tiny_cfg, 1, 0, jnp.float32)) == \
        set(weights_mla_moe.layer_shapes(tiny_cfg, "dense"))


def test_the_reference_imports_nothing_of_the_program():
    with open(reference_mla_moe.__file__) as f:
        text = f.read()
    assert "paddle_tpu" not in text.replace("program under test", "")
    imports = re.findall(r"^(?:import|from) (\S+)", text, flags=re.M)
    assert set(imports) == {"functools", "math", "jax", "jax.numpy", "numpy",
                            "chipbench.reference_hybrid"}
    # expanded form only: no absorbed projection, no cache
    assert "w_uk" not in text.lower() and "absorb" not in text.replace(
        "no absorbed projection", "")


def test_controls_change_the_reference(tiny_cfg):
    """int8 operands move the rows of the first layer and the logits; one
    expert fewer a token moves nothing before the first expert layer and
    the hidden states after it."""
    ids = np.random.default_rng(0).integers(0, 256, (24,)).astype(np.int32)
    top = weights_mla_moe.top(tiny_cfg, 7, jnp.float32)

    def go(**kw):
        scorer = reference_mla_moe.Scorer(tiny_cfg, rows=8, bucket=8, **kw)
        hidden, rows = scorer.forward(
            top, lambda n: weights_mla_moe.layer(tiny_cfg, 7, n,
                                                 jnp.float32),
            [(ids, 18)], keep_rows=[0])
        return np.asarray(hidden[0]), rows[0], scorer.logits(top, hidden[0],
                                                             10)

    plain, rows, lg = go()
    assert rows.shape == (4, 24, 40) and lg.shape == (8, 256)
    low, low_rows, _ = go(quant="int8")
    assert np.abs(low_rows[0] - rows[0]).max() > 1e-3
    assert np.abs(low - plain).max() > 1e-3
    less, less_rows, _ = go(top_k=3)
    assert np.array_equal(less_rows[:2], rows[:2])  # layers 0 and 1
    assert np.abs(less_rows[2] - rows[2]).max() > 1e-3
    assert np.abs(less - plain).max() > 1e-3


# -- planted faults, through the run's own judge -----------------------------

def test_a_token_altered_where_it_is_produced(monkeypatch, rehearse):
    from paddle_tpu.inference.server.latent_executor import LatentExecutor

    sound, calls = LatentExecutor.decode, [0]

    def decode(self, sids):
        out = sound(self, sids)
        calls[0] += 1
        if calls[0] % 2 == 0:
            for sid in out:
                out[sid] = self.last_token[sid] = \
                    (out[sid] + 1) % self.config.vocab_size
        return out

    monkeypatch.setattr(LatentExecutor, "decode", decode)
    line = rehearse(CELL, seconds=0.5)
    assert line["correct"] is False
    assert "served_token_gap" in _failing(line)


def test_a_pool_rounded_to_8_bits_fails_the_cell(monkeypatch, rehearse):
    """The program keeps its latent rows at 8 bits a value (a page's rows
    rounded to 1/127 of their largest after every write): the rows it
    holds in the first layer are no longer the reference's."""
    from paddle_tpu.inference.paged import PagedKVCache

    sound = PagedKVCache.set_pools

    def set_pools(self, kps, vps):
        scale = jnp.max(jnp.abs(kps), axis=-1, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        sound(self, (jnp.round(kps / scale) * scale).astype(kps.dtype), vps)

    monkeypatch.setattr(PagedKVCache, "set_pools", set_pools)
    line = rehearse(CELL, seconds=0.5)
    assert line["correct"] is False
    assert "latent_row_gap" in _failing(line)


def test_top_7_routing_fails_the_cell(monkeypatch, rehearse):
    """The program routes every token to one expert fewer than published
    (3 of 16 here): the rows of the first layer do not see it, the rows
    of the last layer do."""
    from paddle_tpu.models import mla_moe as mm

    sound = mm.route

    def route(cfg, lp, h):
        import dataclasses
        return sound(dataclasses.replace(
            cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1), lp, h)

    monkeypatch.setattr(mm, "route", route)
    line = rehearse(CELL, seconds=0.5)
    assert line["correct"] is False
    failing = _failing(line)
    assert "deep_row_gap" in failing and "latent_row_gap" not in failing


def test_controls_are_read_beside_the_program(capsys):
    rows = control.main(["--workload", CELL, "--seeds", "2147483659",
                         "--seconds", "0.3"], bench_path=TINY, rehearse=True)
    (row,) = rows
    got = row["readings"]
    assert set(got) == {"program", "int8", "top_k_less_1"}
    limits = spec.limits(spec.load_benchmark(TINY), CELL)
    assert row["correct"] is True
    for name in ("served_token_gap", "latent_row_gap", "deep_row_gap"):
        assert got["program"][name] <= limits[name] / 10, name
    # each control goes through the run's own judgement, and fails it by
    # the number meant to catch it
    assert row["verdicts"] == {"int8": False, "top_k_less_1": False}
    assert got["int8"]["latent_row_gap"] > 10 * limits["latent_row_gap"]
    assert got["top_k_less_1"]["latent_row_gap"] == 0.0
    assert got["top_k_less_1"]["deep_row_gap"] > 10 * limits["deep_row_gap"]


def test_the_chips_readings_through_judge(real):
    """The committed limits against the readings they were set from: the
    program's largest reading of every number is correct, each control's
    smallest reading of the number meant to catch it is not."""
    bench = spec.load_benchmark()
    limits = spec.limits(bench, REAL)
    with open(os.path.join(bench["root"], "chipbench", "limits",
                           REAL + ".json")) as f:
        read = json.load(f)["readings"]
    sound = {"served_token_gap": 0.0, "latent_row_gap": 0.0,
             "deep_row_gap": 0.0, "short_answers": 0.0}

    def verdict(**numbers):
        return harness.judge(compare.checks({**sound, **numbers},
                                            limits))[0]

    assert set(limits) == set(sound)
    assert verdict(**{k: max(read["program_" + k]) for k in sound
                      if k != "short_answers"}) is True
    assert verdict(latent_row_gap=min(
        read["control_int8_latent_row_gap"])) is False
    assert verdict(deep_row_gap=min(
        read["control_top_k_less_1_deep_row_gap"])) is False
    assert verdict(served_token_gap=3.0) is False       # an altered token
    assert verdict(short_answers=1.0) is False
    assert verdict(latent_row_gap=float("nan")) is False    # none held
    for name, ctl in (("latent_row_gap", "control_int8_latent_row_gap"),
                      ("deep_row_gap", "control_top_k_less_1_deep_row_gap")):
        assert limits[name] >= 1.5 * max(read["program_" + name]), name
        assert limits[name] <= min(read[ctl]) / 1.5, name
