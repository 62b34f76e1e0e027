"""(a) Both cells end to end at tiny widths on the CPU, through
chipbench.run's own functions: the last line's keys, and no device
metric from a CPU run.  The tiny cells are files under
tests/chipbench/bench and one entry each in that BENCHMARK.json —
nothing in chipbench/ knows of them."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import spec

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench",
                    "BENCHMARK.json")

CELLS = ["tiny-train.tiny-steady", "tiny-serve.tiny-closed"]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_to_its_last_line(rehearse, workload, trace):
    line = rehearse(workload, trace=trace)
    keys = list(line)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert "checks" in keys and line["rehearsal"].startswith("cpu")
    assert ("breakdown" in keys) == bool(trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    cell = spec.cell(spec.load_benchmark(TINY), workload)
    wanted = {m["name"] for m in cell["per_layer" if trace else "end_to_end"]}
    if trace:       # readers of the device trace find nothing on a CPU
        wanted -= {m["name"] for m in cell["per_layer"]
                   if m["source"] == "device_trace"}
        if line["device"]["memory_peak_bytes"] is None:     # nor a memory peak
            wanted -= {"peak_hbm_gb.train"}
        assert line["device"]["busy_s"] is None
        assert line["device"]["window_s"] > 0
    assert set(line["metrics"]) == wanted
    # a CPU run gives counts and correctness, never a device metric
    assert all(m["value"] is None for m in line["metrics"].values())
    for row in line["checks"].values():
        assert row["value"] <= row["limit"]


def test_same_seed_same_numbers(rehearse):
    a = rehearse("tiny-train.tiny-steady", seed=11)["checks"]
    b = rehearse("tiny-train.tiny-steady", seed=11)["checks"]
    c = rehearse("tiny-train.tiny-steady", seed=12)["checks"]
    assert a == b and a != c


def test_no_chip_no_result_line():
    """The command itself, here where jax finds no TPU: non-zero, and
    nothing on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "chipbench", "run.py"),
         "--workload", "mistral7b-train.steady", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=spec.ROOT, timeout=300)
    assert p.returncode == 2 and p.stdout == ""
    assert "no CPU path" in p.stderr


def test_a_set_gate_is_refused(monkeypatch, rehearse):
    monkeypatch.setenv("PT_PREFIX_CACHE", "on")
    with pytest.raises(SystemExit, match="PT_PREFIX_CACHE"):
        rehearse("tiny-serve.tiny-closed")
