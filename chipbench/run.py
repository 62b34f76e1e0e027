#!/usr/bin/env python3
"""Runs one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which touches jax once and starts no other.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result line: there is no CPU path.  The last line of stdout is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with --trace 0, its per-layer metrics with
--trace 1), ``device`` and, last, ``checks``: every number compared
beside its limit.
"""
import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))      # the checkout's root

from chipbench import harness, spec  # noqa: E402
from chipbench.harness import log  # noqa: E402


class Context:
    """What a generator's ``run(ctx)`` gets."""

    def __init__(self, jax, bench, cell, args, clock):
        import jax.numpy as jnp

        self.jax, self.jnp = jax, jnp
        self.bench, self.cell, self.clock = bench, cell, clock
        self.seed, self.seconds, self.traced = args.seed, args.seconds, \
            bool(args.trace)
        self.trace = harness.TraceWindow(
            jax, os.path.join(bench["root"], ".chipbench_trace"), self.traced)
        self.limits = spec.limits(bench, cell["workload"]["name"])
        self.setup_s = None

    def window_started(self, t):
        self.setup_s = t - harness.T0


def require_chips(jax, chips, rehearse):
    devs = jax.devices()
    if rehearse:
        if devs[0].platform != "cpu":
            sys.exit("chipbench: a rehearsal is for the CPU")
        return
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: the cell needs {chips} TPU chip(s); jax found "
              f"{len(devs)} {devs[0].platform!r} device(s). There is no "
              f"CPU path; nothing was run.", file=sys.stderr)
        sys.exit(2)


def take_chip(bench, cell, rehearse):
    """The one touch of jax: the chips the cell asks for, the compile
    cache, the compile clock.  Returns (jax, clock)."""
    import jax

    require_chips(jax, cell["workload"]["chips"], rehearse)
    if not rehearse:    # a rehearsal's CPU programs are of no use to a chip
        from paddle_tpu.utils import enable_compile_cache

        log("compile cache: " + enable_compile_cache(
            os.path.join(bench["root"], ".jax_cache")))
    return jax, harness.CompileClock(jax)


def per_layer(bench, cell, record, peaks):
    """Each per-layer metric from its own reader; one that finds nothing
    to read is left out."""
    out = {}
    for m in cell["per_layer"]:
        reader = spec.load_module(bench, "layer_metrics", m["name"])
        value = reader.read(record, cell, peaks)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def brief(facts):
    """The facts without their long lists, for the log."""
    return {k: brief(v) if isinstance(v, dict) else v
            for k, v in facts.items()
            if not isinstance(v, list) or len(v) < 8}


def result_line(bench, cell, ctx, record, device, peaks):
    correct, rows = harness.judge(record["checks"])
    correct = correct and record["failed"] == 0
    device = dict(device, memory_peak_bytes=record["memory_peak_bytes"])
    line = {"correct": correct, "attempted": record["attempted"],
            "failed": record["failed"]}
    if ctx.traced:
        trace = record["trace"]
        line["metrics"] = per_layer(bench, cell, record, peaks)
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["device"] = device
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    else:
        values = dict(record["end_to_end"], setup_s=ctx.setup_s)
        line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                       "unit": m["unit"]}
                           for m in cell["end_to_end"]}
        line["device"] = device
    line["checks"] = rows
    return line


def main(argv=None, bench_path=None, rehearse=False):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files in .chipbench_trace/ "
                         "(to look at by hand; see trace_reduce.py)")
    args = ap.parse_args(argv)

    bench = spec.load_benchmark(bench_path)
    cell = spec.cell(bench, args.workload)

    jax, clock = take_chip(bench, cell, rehearse)
    device = harness.device_info(jax)
    # (a rehearsal exercises the readers on the table's first row; its
    # values are struck out below)
    peaks = spec.peaks(bench, None if rehearse else device["kind"])

    log(f"{args.workload} seed {args.seed} on {device}")

    ctx = Context(jax, bench, cell, args, clock)
    generator = spec.load_module(bench, "generators",
                                 cell["traffic"]["generator"])
    record = generator.run(ctx)
    record["bench"] = bench
    if ctx.traced:
        from chipbench import trace_reduce

        t = time.perf_counter()
        patterns = {
            name: spec.load_module(bench, "kernels", name).PATTERNS
            for name in cell["traffic"].get("kernels", [])}
        trace = trace_reduce.load(ctx.trace.path)
        if rehearse:
            # a CPU trace has the harness's spans and no device plane
            lo, hi = trace_reduce.window(trace)
            record["trace"] = {
                "window_s": (hi - lo) / 1e9, "busy_s": None,
                "idle_share": None, "kernels": {}, "device_ops": [],
                "idle_gaps": []}
        else:
            record["trace"] = trace_reduce.reduce(trace, patterns)
        if not args.keep_trace:
            ctx.trace.cleanup()
        log(f"trace reduced in {time.perf_counter() - t:.1f} s: busy "
            f"{record['trace']['busy_s']} s of "
            f"{record['trace']['window_s']:.3f} s")
    line = result_line(bench, cell, ctx, record, device, peaks)
    if rehearse:
        # a CPU run gives counts and correctness, never a device metric
        for m in line["metrics"].values():
            m["value"] = None
        line["rehearsal"] = "cpu: not a measurement"
    log("facts: " + json.dumps(brief(record["facts"])))
    for name, row in line["checks"].items():
        print(f"check {name}: {row['value']} (limit {row['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
