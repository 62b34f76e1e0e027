"""Helpers for the benchmark's own tests: the tiny cells, which are
defined by files under tests/chipbench/bench alone, driven on the CPU
through chipbench.run's own functions."""
import json
import os

import pytest

from chipbench import run

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench",
                    "BENCHMARK.json")


@pytest.fixture
def rehearse(capsys):
    """Runs one tiny cell as ``chipbench/run.py`` would, without the look
    for a chip; returns the parsed last line of stdout."""
    def go(workload, seed=3_000_000_007, seconds=0.3, trace=0):
        capsys.readouterr()
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      bench_path=TINY, rehearse=True)
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0 and len(out) == 1, out
        return json.loads(out[-1])
    return go
