"""prep_idle_share: the share of the traced window in which the first
chip runs no operation while the host is inside the program's
``engine.schedule``, ``engine.stage`` or ``engine.collect`` span --
the device's idle time that host preparation accounts for."""
import devtrace
import spans

PREP = {"engine.schedule", "engine.stage", "engine.collect"}


def read(ctx):
    host = ctx["trace"]["host"]
    win = spans.window(host)
    dev = ctx["trace"]["devices"].get(ctx["device_ids"][0], [])
    if win is None or not dev:
        return None
    lo, hi = win
    prep = spans.clipped([e for e in host if e[2] in PREP], lo, hi)
    if not prep:
        return None
    gaps = sorted(devtrace.idle_gaps(dev, lo, hi))
    idle = spans.overlap_ns(gaps, devtrace.merged(prep))
    return 100.0 * idle / (hi - lo)
