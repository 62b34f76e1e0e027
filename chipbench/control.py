#!/usr/bin/env python3
"""Reads the numbers that set the limits, on the chip, at a cell's own size.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

For every seed it drives the cell as a run does (a short window) and
prints, as one JSON line per seed, the program's reading of each number
compared, and beside it the controls': the reference put in the
program's place at the precision below the one the configuration states,
and for a training cell the reference with half of the batch left out.
Every control's numbers go through the same limits and the same
judgement as a run's (``verdicts``: name -> the ``correct`` it would
get, which has to be false).  The benchmark's own runs never run this; the
limits in ``limits/<workload>.json`` were set from its lines (PERF.md
gives the readings).  All seeds go through one process, one after the
other, so the chip is taken once.
"""
import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

from chipbench import compare, harness, run as runner, spec  # noqa: E402


def controls_of(facts):
    """who -> the numbers a run would have had compared, for every control
    the generator read beside the program."""
    if "readings" in facts:                                     # training
        return {who: nums for who, nums in facts["readings"].items()
                if who != "program"}
    scored = facts["scored"]                                    # serving
    if "control_token_gap" not in scored:
        return {}
    return {"int8": {"served_token_gap": scored["control_token_gap"],
                     "short_answers": 0.0}}


def main(argv=None, bench_path=None, rehearse=False):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--no-control", action="store_true",
                    help="the program's readings only")
    args = ap.parse_args(argv)

    bench = spec.load_benchmark(bench_path)
    cell = spec.cell(bench, args.workload)

    jax, clock = runner.take_chip(bench, cell, rehearse)
    generator = spec.load_module(bench, "generators",
                                 cell["traffic"]["generator"])
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        one = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
        ctx = runner.Context(jax, bench, cell, one, clock)
        record = generator.run(ctx, control=not args.no_control)
        correct, _ = harness.judge(record["checks"])
        f = record["facts"]
        row = {"workload": args.workload, "seed": seed,
               "correct": correct and record["failed"] == 0,
               "readings": f.get("readings") or f.get("scored"),
               "worst_leaves": f.get("worst_leaves"),
               "verdicts": {who: harness.judge(
                   compare.checks(nums, ctx.limits))[0]
                   for who, nums in controls_of(f).items()}}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


if __name__ == "__main__":
    main()
