"""Share of the device's idle seconds that the program's own account
sees, over the TRACED stretch: the seconds of
``device_account.starved`` inside it over the device trace's idle
seconds (``window_s - busy_s``).  The account is a subset of the true
idle time but for the page writer's tail behind a final chunk, so a
reading over ~101 means the device was handed a program the account
does not know of; what is missing from 100 is launch and wake-up
latency, which no host span bounds."""
from chipbench import device_account, program_spans
from chipbench.harness import log


def read(record, cell, peaks):
    trace = record["trace"]
    got = program_spans.load(record, cell)
    if got is None or trace["busy_s"] is None:
        return None
    spans, _, t1 = got
    stretch = device_account.traced_stretch(record, spans, t1)
    if stretch is None:
        return None
    gaps = device_account.starved(spans, *stretch)
    if gaps is None:
        return None
    seen = device_account.account(spans, gaps)["total"]
    idle = trace["window_s"] - trace["busy_s"]
    log(f"idle seen: {seen:.3f} s of the trace's {idle:.3f} s idle in "
        f"{stretch[1] - stretch[0]:.3f} s of steps")
    return 100.0 * seen / idle if idle > 0 else None
