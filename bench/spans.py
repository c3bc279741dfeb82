"""The program's named host spans (``engine.*``, ``repro.fed.spans``) in
a flattened trace (``devtrace.load``), clipped to the traced window.

A span's self time is its duration minus the union of the ``engine.*``
spans nested in it (deeper on the host and inside its interval); JAX's
own events nested in it, such as dispatches and transfers, count
towards it.  A trace whose program writes no such span reads None.
"""
import devtrace

PREFIX = "engine."
WINDOW = "bench.window"


def window(host):
    """[start_ns, end_ns) of the traced window, or None."""
    for s, d, name, _ in host:
        if name == WINDOW:
            return s, s + d
    return None


def clipped(events, lo, hi) -> list:
    """[[start_ns, dur_ns], ...] of the events' parts inside [lo, hi)."""
    out = []
    for s, d, *_ in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append([a, b - a])
    return out


def self_ns(host, names):
    """Self nanoseconds of the spans named in ``names`` inside the
    window, summed; None where the window holds none of them."""
    win = window(host)
    if win is None:
        return None
    lo, hi = win
    spans = [e for e in host if e[2].startswith(PREFIX)]
    picked = [e for e in spans if e[2] in names and clipped([e], lo, hi)]
    if not picked:
        return None
    total = 0.0
    for s, d, _, depth in picked:
        a, b = max(s, lo), min(s + d, hi)
        nested = [e for e in spans
                  if e[3] > depth and e[0] >= s and e[0] + e[1] <= s + d]
        total += (b - a) - devtrace.busy_ns(clipped(nested, a, b))
    return total


def per_round_ms(ctx, names):
    ns = self_ns(ctx["trace"]["host"], names)
    return None if ns is None else ns * 1e-6 / ctx["trace_rounds"]


def overlap_ns(xs, ys) -> float:
    """Length of the intersection of two sorted lists of disjoint
    [a, b) intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total
