"""The yardstick's own arithmetic: the trace reduction on a recorded
trace, the FLOP and byte counts against hand-worked values, the
percentile, the deck of the closed loop, and BENCHMARK.json's form."""
import json
import math
import os
import re

import numpy as np
import pytest

from chipbench import flops, harness, roofline, spec, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = spec.load_benchmark()
TINY = spec.load_benchmark(os.path.join(HERE, "bench", "BENCHMARK.json"))
MISTRAL = json.load(open(os.path.join(
    spec.ROOT, "chipbench", "configs", "mistral7b-train.json")))
V5E = spec.peaks(BENCH, "TPU v5 lite")


# -- (b) trace_reduce on a recorded trace --------------------------------

@pytest.fixture(scope="module")
def trace():
    """0.4 s of mistral7b-train.steady on a v5e (chip call 1 of PR 25),
    names already compacted."""
    with open(os.path.join(HERE, "fixtures", "train_trace.json")) as f:
        return json.load(f)


def test_trace_window_and_busy(trace):
    lo, hi = trace_reduce.window(trace)
    assert hi - lo == 400_000_000
    r = trace_reduce.reduce(trace)
    assert r["window_s"] == pytest.approx(0.4)
    assert 0.39 < r["busy_s"] < 0.4          # a train step keeps the chip busy
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / 0.4)
    assert len(r["device_ops"]) == 10
    assert r["device_ops"][0][1] >= r["device_ops"][1][1]


def test_busy_is_a_union_not_a_sum():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["d", 100, 50]]
    assert trace_reduce.busy_ns(ev, 0, 40) == 20       # [0,15] + [30,35]
    assert trace_reduce.busy_ns(ev, 0, 120) == 40      # d clipped at 120
    assert trace_reduce.gaps(ev, 0, 40) == [(15, 30), (35, 40)]


def test_gaps_go_to_what_the_host_was_in():
    t = {"devices": {"/device:TPU:0": [["op", 0, 10], ["op", 60, 10]]},
         "host": [["cb:window", 0, 100], ["cb:step", 0, 20],
                  ["cb:submit", 20, 35], ["cb:step", 55, 45]]}
    r = trace_reduce.reduce(t)
    assert r["busy_s"] == pytest.approx(20e-9)
    assert r["longest_gaps"][0] == ["submit", pytest.approx(50e-9)]
    assert dict(r["idle_gaps"])["step"] == pytest.approx(30e-9)


def test_kernel_time_by_pattern(trace):
    kernel = spec.load_module(BENCH, "kernels", "train_attention")
    r = trace_reduce.reduce(trace, {"train_attention": kernel.PATTERNS})
    rows = r["kernels"]["train_attention"]
    # one whole step and a part: forward twice a layer under remat
    assert rows["fwd"]["calls"] == 7 and rows["bwd_dkv"]["calls"] == 3
    assert rows["bwd_dq"]["calls"] == 2 and "bwd" not in rows
    assert rows["fwd"]["seconds"] == pytest.approx(0.012587978)


@pytest.mark.parametrize("text,want", [
    ('%fusion.312 = (f32[8192,32768]{1,0:T(8,128)}, bf16[8192]{0}) '
     'fusion(bf16[8192,4096]{1,0} %x), kind=kOutput, calls=%fc',
     "fusion.312 [kOutput] f32[8192,32768]"),
    ('%_sdpa_plain.14 = (bf16[4,32,2048,128]{3,2,1,0:T(8,128)(2,1)}, '
     'f32[4,32,2048,128]{3,2,1,0}) custom-call(bf16[4,32,2048,128]{3,2,1,0} '
     '%a), custom_call_target="tpu_custom_call", frontend_attributes={}',
     "_sdpa_plain.14 [tpu_custom_call] bf16[4,32,2048,128]"),
    ('%while.6 = (s32[]{:T(128)}, bf16[4,2048,4096]{2,1,0}) while((s32[]) '
     '%tuple), condition=%cond, body=%body', None),
    ('%broadcast_in_dim.285 = bf16[4,8,4,2048,128]{4,3,2,1,0:T(8,128)(2,1)} '
     'broadcast(bf16[4,8,2048,128]{3,2,1,0} %c), dimensions={0,1,3,4}',
     "broadcast_in_dim.285 [broadcast] bf16[4,8,4,2048,128]"),
])
def test_compact_names(text, want):
    assert trace_reduce.compact(text) == want


# -- (d) FLOPs and bytes against hand-worked values -------------------------

def test_mistral_parameter_counts():
    # per layer: q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    assert flops.layer_matmul_params(MISTRAL) == 218_103_808
    assert flops.head_params(MISTRAL) == 134_217_728
    assert flops.matmul_params(MISTRAL) == 2 * 218_103_808 + 134_217_728


def test_train_flops_per_token():
    # 6 x 570,425,344 matmul parameters + 3 x (2 layers x 4 x 32 x 128 x 1024.5)
    want = 6 * 570_425_344 + 3 * 2 * 4 * 32 * 128 * 1024.5
    assert flops.train_flops_per_token(MISTRAL, 2048) == pytest.approx(want)
    assert want == pytest.approx(3.523e9, rel=1e-3)


def test_serve_flops():
    cfg = dict(MISTRAL, num_hidden_layers=8)
    got = flops.serve_flops(cfg, tokens=1000, sampled=10, context_sum=50_000)
    want = (2 * 8 * 218_103_808 * 1000 + 2 * 134_217_728 * 10
            + 8 * 4 * 32 * 128 * 50_000)
    assert got == want


@pytest.mark.parametrize("phase,units", [("fwd", 2), ("bwd_dkv", 2.5),
                                          ("bwd_dq", 2.5), ("bwd", 5)])
def test_train_attention_counts(phase, units):
    k = spec.load_module(BENCH, "kernels", "train_attention")
    sh = k.shape(MISTRAL, 4, 2048)
    u = 4 * 32 * 2048 ** 2 * 128              # 68,719,476,736
    assert k.flops(sh, phase) == units * u
    q, kv, stats = 4 * 2048 * 32 * 128 * 2, 4 * 2048 * 8 * 128 * 2, 4 * 32 * 2048 * 4
    whole = 4 * q + 4 * kv + stats
    assert k.bytes(sh, phase) == {"fwd": 2 * q + 2 * kv + stats, "bwd": whole,
                                  "bwd_dkv": whole / 2, "bwd_dq": whole / 2}[phase]
    least, bound = roofline.least_seconds(k, sh, phase, V5E)
    assert bound == "flops" and least == pytest.approx(units * u / 197e12)


def test_paged_decode_counts():
    k = spec.load_module(BENCH, "kernels", "paged_decode")
    sh = k.shape(MISTRAL, 32, 16_000)        # 32 sequences, 16,000 keys in all
    assert k.flops(sh) == 4 * 32 * 128 * 16_000
    assert k.bytes(sh) == 2 * 16_000 * 8 * 128 * 2 + 2 * 32 * 32 * 128 * 2
    least, bound = roofline.least_seconds(k, sh, "decode", V5E)
    assert bound == "bytes" and least == pytest.approx(66_060_288 / 819e9)


def test_roofline_share_reads_nothing_as_nothing():
    assert roofline.share_pct(1.0, 0) is None
    assert roofline.share_pct(1.0, 4.0) == 25.0
    assert roofline.share_pct(5.0, 4.0) == 125.0      # never clipped


def test_peaks_refuse_an_unknown_device():
    assert V5E["bf16_flops_per_s"] == 197e12 and V5E["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks(BENCH, "cpu")


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (95, 3.85), (100, 4.0)])
def test_percentile(q, want):
    assert harness.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def test_judge():
    ok, rows = harness.judge([("a", 0.5, 1.0), ("b", 0.0, 0)])
    assert ok and rows["a"] == {"value": 0.5, "limit": 1.0}
    assert not harness.judge([("a", 1.5, 1.0)])[0]
    assert not harness.judge([("a", float("nan"), 1.0)])[0]
    assert not harness.judge([("a", None, 1.0)])[0]


def test_checks_need_a_limit_for_every_number():
    from chipbench import compare

    rows = compare.checks({"a": 0.5, "b": 2.0}, {"a": 1.0, "b": None})
    assert rows == [("a", 0.5, 1.0)]        # b has no upper reading
    with pytest.raises(SystemExit):
        compare.checks({"a": 0.5, "c": 1.0}, {"a": 1.0})


def _leaves(**kw):
    return {k: np.asarray(v, np.float32) for k, v in kw.items()}


def test_worst_leaf_is_measured_against_the_median_leaf():
    from chipbench import compare

    want = {"losses": [10.0], "grad_norms": {"x": 1.0, "y": 2.0, "z": 1e-9},
            "raw_grad_norms": {"x": 1.0, "y": 2.0, "z": 1e-9},
            "change_norms": {"x": 1.0, "y": 2.0, "z": 5.0},
            "grad_leaves": _leaves(x=[1, 0], y=[0, 2], z=[1e-9, 0]),
            "change_leaves": _leaves(x=[1, 0], y=[0, 2], z=[3, 4])}
    got = {"losses": [10.001], "grad_norms": {"x": 1.1, "y": 2.0, "z": 0.1},
           "change_norms": {"x": 1.0, "y": 2.0, "z": 0.0},
           "grad_leaves": _leaves(x=[0.11, 0], y=[0, 0.2], z=[0, 0.01]),
           "grad_scale": 10.0,
           "change_leaves": _leaves(x=[1, 0], y=[0, 2], z=[0, 0])}
    n = compare.train_numbers(got, want)
    assert n["loss_gap_step1"][0] == pytest.approx(1e-4)
    # z's gradient is all but zero: its gap counts against the median leaf (1.0)
    assert n["grad_norm_gap"][0] == pytest.approx(0.1)
    assert n["grad_diff_gap"][0] == pytest.approx(0.1)
    # and z is left out of the change by the rule on the reference's gradient
    assert n["change_norm_gap"][0] == 0.0 == n["change_diff_gap"][0]
    del got["change_norms"]["y"]
    assert compare.train_numbers(got, want)["change_norm_gap"][0] != \
        compare.train_numbers(got, want)["change_norm_gap"][0]     # NaN


@pytest.mark.parametrize("update,norm_gap,diff_gap", [
    ([-3.0, -4.0], 0.0, 2.0),       # flipped: the norm sees nothing
    ([0.0, 0.0], 1.0, 1.0),         # a state left unchanged
    ([4.0, 3.0], 0.0, 2 ** 0.5 / 5),  # the same length, another direction
])
def test_a_norm_is_blind_to_direction_and_a_difference_is_not(
        update, norm_gap, diff_gap):
    from chipbench import compare

    ref = _leaves(x=[3, 4])
    want = {"losses": [], "grad_norms": {"x": 5.0},
            "raw_grad_norms": {"x": 5.0}, "change_norms": {"x": 5.0},
            "grad_leaves": ref, "change_leaves": ref}
    got = {"losses": [], "grad_norms": {"x": 5.0}, "grad_leaves": ref,
           "change_norms": {"x": float(np.linalg.norm(update))},
           "change_leaves": _leaves(x=update)}
    n = compare.train_numbers(got, want)
    assert n["change_norm_gap"][0] == pytest.approx(norm_gap)
    assert n["change_diff_gap"][0] == pytest.approx(diff_gap)
    assert n["grad_diff_gap"][0] == 0.0


# -- the closed loop's deck --------------------------------------------------

def test_deck_is_the_same_work_for_every_seed():
    gen = spec.load_module(BENCH, "generators", "closed_loop")
    traffic = spec.cell(BENCH, "mistral7b-serve.closed32")["traffic"]
    cfg = {"vocab_size": 32768}
    deck = gen.deck(traffic)
    assert len(deck) == 100
    assert sorted(p for p, _ in deck).count(1720) == 8
    assert sum(a for _, a in deck) == 15 * 32 + 21 * 64 + 27 * 128 + 37 * 256
    assert max(p + a for p, a in deck) <= 2048
    one, two = gen.Dealer(cfg, traffic, 7), gen.Dealer(cfg, traffic, 3_000_000_019)
    a = [(len(p), n) for p, n in (one.next() for _ in range(200))]
    b = [(len(p), n) for p, n in (two.next() for _ in range(200))]
    assert a != b and a[:100] != a[100:]
    # pass after pass through the deck: every hundred requests are the deck
    for blk in (a[:100], a[100:], b[:100], b[100:]):
        assert sorted(blk) == sorted(deck)
    again = gen.Dealer(cfg, traffic, 7)
    p0, n0 = again.next()
    q0, m0 = gen.Dealer(cfg, traffic, 7).next()
    assert (p0 == q0).all() and n0 == m0 == a[0][1]


@pytest.mark.parametrize("lens,weights,median,mean,kept", [
    ("prompt", "prompt_weights", 1020, 1155, (200, 1792)),
    ("answer", "answer_weights", 129, 211, (0, None))])
def test_the_mix_is_the_published_trace_binned(lens, weights, median, mean, kept):
    """The weights in the traffic file are what its ``fit`` says: a
    log-normal through the published median and mean, binned at the
    geometric midpoints of the list (prompts: only what the replica
    takes, renormalised; answers: everything above goes to the cap)."""
    from statistics import NormalDist

    traffic = spec.cell(BENCH, "mistral7b-serve.closed32")["traffic"]
    pub = traffic["published"][lens + "_tokens"]
    assert (pub["median"], pub["mean"]) == (median, mean)
    sigma = math.sqrt(2 * math.log(mean / median))

    def cdf(x):
        if x is None:
            return 1.0
        return NormalDist().cdf(math.log(x / median) / sigma) if x else 0.0

    sizes = traffic[lens + "_lens"]
    edges = [kept[0]] + [math.sqrt(a * b) for a, b in zip(sizes, sizes[1:])] \
        + [kept[1]]
    mass = [cdf(hi) - cdf(lo) for lo, hi in zip(edges, edges[1:])]
    want = [m / sum(mass) for m in mass]
    assert traffic[weights] == pytest.approx(want, abs=0.008)
    assert sum(traffic[weights]) == pytest.approx(1.0)


# -- (e) BENCHMARK.json is well formed ---------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.mark.parametrize("bench", [BENCH, TINY], ids=["real", "tiny"])
class TestBenchmarkFile:
    def test_keys(self, bench):
        assert set(bench) - {"root"} == {
            "command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
        assert 1 <= bench["run_seconds"] <= 51
        assert all(not w.startswith("/") and ".." not in w
                   for w in bench["command"])

    def test_names_and_units(self, bench):
        rows = (bench["configs"] + bench["workloads"] + bench["end_to_end"]
                + bench["per_layer"])
        for row in rows:
            assert NAME.match(row["name"]), row["name"]
        metrics = bench["end_to_end"] + bench["per_layer"]
        assert len({m["name"] for m in metrics}) == len(metrics)
        for m in metrics:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
        for m in bench["end_to_end"]:
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.1
        for w in bench["workloads"]:
            assert w["chips"] in (1, 4) and len(w["why"]) <= 200
            assert NAME.match(w["traffic"]) and NAME.match(w["config"])

    def test_every_cell_has_its_files_and_metrics(self, bench):
        for w in bench["workloads"]:
            cell = spec.cell(bench, w["name"])      # config + traffic exist
            spec.limits(bench, w["name"])
            spec.load_module(bench, "generators", cell["traffic"]["generator"])
            for k in cell["traffic"]["kernels"]:
                spec.load_module(bench, "kernels", k)
            e2e = {m["name"] for m in cell["end_to_end"]}
            assert "setup_s" in e2e and len(e2e) >= 2
            assert cell["per_layer"]
            for m in cell["per_layer"]:
                spec.load_module(bench, "layer_metrics", m["name"])

    def test_per_layer_metrics_move_what_their_cells_report(self, bench):
        e2e = {m["name"]: m for m in bench["end_to_end"]}
        cells = {w["name"] for w in bench["workloads"]}
        layers = {}
        for m in bench["per_layer"]:
            moved = e2e[m["moves"]]
            for w in m["workloads"]:
                assert w in cells
                assert w in moved.get("workloads", cells), (m["name"], w)
            layers.setdefault(m["layer"], []).append(m["name"])
            if m["name"].endswith("_roofline") or "mfu" in m["name"]:
                assert m["unit"] == "%"
        assert set(bench["paths"]) <= {"chipbench", "tests/chipbench",
                                       "tests/chipbench/bench"}

    def test_config_files_state_their_cut(self, bench):
        for c in bench["configs"]:
            assert c["file"].startswith(tuple(bench["paths"]))
            with open(os.path.join(spec.ROOT, c["file"])) as f:
                cfg = json.load(f)
            assert set(c["reduced"]) == set(cfg["reduced"])
            for key in ("source", "assumed", "deployment"):
                assert key in cfg
            for width in ("hidden_size", "intermediate_size", "head_dim"):
                assert width not in c["reduced"]


def test_mistral_widths_are_the_published_ones():
    for name in ("mistral7b-train", "mistral7b-serve"):
        cfg = json.load(open(os.path.join(
            spec.ROOT, "chipbench", "configs", name + ".json")))
        published = {"hidden_size": 4096, "intermediate_size": 14336,
                     "num_attention_heads": 32, "num_key_value_heads": 8,
                     "vocab_size": 32768, "rope_theta": 1e6,
                     "rms_norm_eps": 1e-5, "max_position_embeddings": 32768,
                     "tie_word_embeddings": False, "sliding_window": None}
        assert {k: cfg[k] for k in published} == published
        assert list(cfg["reduced"]) == ["num_hidden_layers"]
