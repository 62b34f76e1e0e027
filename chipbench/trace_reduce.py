"""From a profiler trace to numbers.

``load(path)`` reads the .xplane.pb that jax's profiler wrote (with
jax's own reader, nothing else) into a plain dict:

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...]},
     "host": [[name, start_ns, dur_ns], ...]}

``devices`` holds each chip's XLA operations (the "XLA Ops" line, names
shortened by ``compact``; a while loop's or a call's own event, which
only spans its children, is left out), ``host`` the harness's own spans
(``cb:<what>``), both on the profiler's one clock.  Everything else works on that dict, so the tests
check it on a small recorded trace kept as JSON.

    window(trace)               the ``cb:window`` span: (start, end)
    busy_ns(events, lo, hi)     union of the op intervals inside [lo, hi]
    reduce(trace, patterns)     busy_s, window_s, idle share, seconds by
                                kernel pattern, the top ops, the longest
                                idle gaps by what the host was in
"""
import re

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "cb:"

# The profiler names a device event by its whole HLO instruction:
#   %fusion.312 = (f32[8192,32768]{..}, ..) fusion(..), kind=kOutput, calls=..
#   %_sdpa_plain.14 = (bf16[4,32,2048,128]{..}, ..) custom-call(..),
#       custom_call_target="tpu_custom_call", ..
# compact() keeps the instruction's name, what kind of thing it is (a
# custom call's target — "tpu_custom_call" is a Pallas/Mosaic kernel — or
# a fusion's kind) and the first result shape:
#   fusion.312 [kOutput] f32[8192,32768]
#   _sdpa_plain.14 [tpu_custom_call] bf16[4,32,2048,128]
_INSTR = re.compile(r"^%(\S+) = ")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_FUSION = re.compile(r"\bkind=(k\w+)")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_SHAPE = re.compile(r"\b[a-z]+\d*\[[\d,]*\]")
# an instruction that only spans other events of the same line
_CONTAINER = re.compile(
    r"(\bcondition=%|\bbody=%|\bbranch_computations=|\btrue_computation=)"
    r"|^%call[.\d]* = ")


def compact(text):
    """The short name of a device event, or None for a while loop, a
    conditional or a call, whose event only spans its children's."""
    if _CONTAINER.search(text):
        return None
    m = _INSTR.match(text)
    if not m:
        return text[:80]
    rest = text[m.end():]
    tag = (_TARGET.search(rest) or _FUSION.search(rest)
           or _OPCODE.search(rest))
    shape = _SHAPE.search(rest)
    return (f"{m.group(1)} [{tag.group(1) if tag else ''}] "
            f"{shape.group(0) if shape else ''}").strip()


def load(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                names = {}
                events = []
                for ev in line.events:
                    if ev.name not in names:
                        names[ev.name] = compact(ev.name)
                    if names[ev.name] is not None:
                        events.append([names[ev.name], int(ev.start_ns),
                                       int(ev.duration_ns)])
                trace["devices"][plane.name] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace["host"].extend(
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events
                    if ev.name.startswith(HOST_PREFIX))
    trace["host"].sort(key=lambda e: e[1])
    return trace


def describe(path, top=40):
    """What a trace holds, for a first look by hand: planes, lines,
    event counts, the names that take most time and one event's stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            out.append(f"  line {line.name!r}: {len(evs)} events, "
                       f"{lo:.0f} .. {hi:.0f} ns")
            by = {}
            for e in evs:
                t = by.setdefault(e.name, [0, 0.0])
                t[0] += 1
                t[1] += e.duration_ns
            ranked = sorted(by.items(), key=lambda kv: -kv[1][1])[:top]
            for name, (n, ns) in ranked:
                out.append(f"    {ns / 1e6:10.3f} ms  x{n:<6d} {name[:150]}")
            try:
                stats = {str(k): str(v)[:200] for k, v in evs[0].stats}
            except Exception as e:      # a look by hand, not a metric
                stats = f"<no stats: {e}>"
            out.append(f"    first event stats: {stats}")
    return "\n".join(out)


def window(trace):
    """(start_ns, end_ns) of the harness's ``cb:window`` span."""
    for name, start, dur in trace["host"]:
        if name == HOST_PREFIX + "window":
            return start, start + dur
    raise ValueError("the trace holds no cb:window span")


def _clip(events, lo, hi):
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b, name))
    out.sort()
    return out


def _merge(intervals):
    """Sorted (a, b, ..) -> the disjoint intervals of their union."""
    merged = []
    for a, b, *_ in intervals:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(events, lo, hi):
    return sum(b - a for a, b in _merge(_clip(events, lo, hi)))


def gaps(events, lo, hi):
    """The idle intervals of one chip inside [lo, hi]."""
    out, at = [], lo
    for a, b in _merge(_clip(events, lo, hi)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def host_phase(trace, a, b):
    """What the harness was in for most of [a, b]: the innermost
    ``cb:`` span (other than the window) that covers most of it."""
    best, best_cover = "outside any span", 0
    for name, start, dur in trace["host"]:
        if name == HOST_PREFIX + "window":
            continue
        cover = min(b, start + dur) - max(a, start)
        # a later (inner, or equal) span wins ties: spans are sorted by start
        if cover > 0 and cover >= best_cover:
            best, best_cover = name[len(HOST_PREFIX):], cover
    return best


def matching(events, patterns):
    """Events whose name matches one of ``patterns`` (regular expressions,
    searched)."""
    regs = [re.compile(p) for p in patterns]
    return [e for e in events if any(r.search(e[0]) for r in regs)]


def reduce(trace, patterns=None, top=10):
    """The numbers a traced run reports.  ``patterns`` is
    {kernel: {phase: [regex, ...]}}; the result's ``kernels`` gives, per
    kernel and phase, the calls and the summed device seconds inside the
    window (averaged over the chips used)."""
    lo, hi = window(trace)
    chips = sorted(trace["devices"])
    if not chips:
        raise ValueError("the trace holds no device plane")
    n = len(chips)
    busy = sum(busy_ns(trace["devices"][c], lo, hi) for c in chips) / n
    out = {"window_s": (hi - lo) / 1e9, "busy_s": busy / 1e9,
           "idle_share": 1.0 - busy / (hi - lo), "chips": n}

    by_name = {}
    for c in chips:
        for a, b, name in _clip(trace["devices"][c], lo, hi):
            by_name[name] = by_name.get(name, 0) + (b - a)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    out["device_ops"] = [[name, ns / n / 1e9] for name, ns in ranked]

    # idle gaps of the first chip, summed by what the host was in
    idle = [(host_phase(trace, a, b), b - a)
            for a, b in gaps(trace["devices"][chips[0]], lo, hi)]
    by_phase = {}
    for phase, ns in idle:
        by_phase[phase] = by_phase.get(phase, 0) + ns
    out["idle_gaps"] = [[ph, ns / 1e9] for ph, ns in sorted(
        by_phase.items(), key=lambda kv: -kv[1])[:top]]
    out["longest_gaps"] = [[ph, ns / 1e9] for ph, ns in sorted(
        idle, key=lambda g: -g[1])[:5]]

    out["kernels"] = {}
    for kernel, phases in (patterns or {}).items():
        rows = {}
        for phase, pats in phases.items():
            calls, ns = 0, 0
            for c in chips:
                # whole calls only: a call cut by the window's edge
                # would count as one and time as a part
                hit = [e for e in matching(trace["devices"][c], pats)
                       if e[1] >= lo and e[1] + e[2] <= hi]
                calls += len(hit)
                ns += sum(e[2] for e in hit)
            if calls:
                rows[phase] = {"calls": calls / n, "seconds": ns / n / 1e9}
        out["kernels"][kernel] = rows
    return out


def main(argv=None):
    """``python3 -m chipbench.trace_reduce describe <xplane.pb>`` prints
    what a trace holds; ``... dump <xplane.pb> <out.json> [seconds]``
    writes the plain dict of the window's first ``seconds`` (a fixture
    for the tests)."""
    import json
    import sys

    argv = sys.argv[1:] if argv is None else argv
    if argv[0] == "describe":
        print(describe(argv[1]))
    elif argv[0] == "dump":
        trace = load(argv[1])
        lo, hi = window(trace)
        if len(argv) > 3:
            hi = lo + int(float(argv[3]) * 1e9)
        keep = lambda evs: [e for e in evs if e[1] >= lo and e[1] + e[2] <= hi]  # noqa: E731
        out = {"devices": {c: keep(evs) for c, evs in trace["devices"].items()},
               "host": keep(trace["host"]) + [[HOST_PREFIX + "window", lo, hi - lo]]}
        with open(argv[2], "w") as f:
            json.dump(out, f, separators=(",", ":"))
    else:
        raise SystemExit(main.__doc__)


if __name__ == "__main__":
    main()
