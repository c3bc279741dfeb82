"""Reduction of a JAX profiler trace to the numbers the metrics read.

A trace is first flattened to plain lists (:func:`load`), so the
reduction can be checked on a small recorded excerpt without the
profiler: ``{"devices": {id: [[start_ns, dur_ns, name], ...]},
"host": [[start_ns, dur_ns, name, depth], ...]}``.  Device events are the
operations of each TPU plane's ``XLA Ops`` line; host events are every
event of the host plane, with the depth of their nesting on their line.
"""
from __future__ import annotations

import glob
import re

OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def load(log_dir) -> dict:
    """Flatten the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, "
                           f"found {len(files)}")
    pd = ProfileData.from_file(files[0])
    devices, host = {}, []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            ops = [[e.start_ns, e.duration_ns, e.name]
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                stack = []
                for e in sorted(line.events, key=lambda e: e.start_ns):
                    while stack and stack[-1] <= e.start_ns:
                        stack.pop()
                    host.append([e.start_ns, e.duration_ns, e.name,
                                 len(stack)])
                    stack.append(e.start_ns + e.duration_ns)
    return {"devices": devices, "host": host}


def merged(events) -> list:
    """The union of the events' intervals as sorted disjoint [a, b)."""
    out = []
    for s, d, *_ in sorted(events, key=lambda e: e[0]):
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events) -> float:
    return float(sum(b - a for a, b in merged(events)))


def idle_gaps(events, start_ns: float, end_ns: float) -> list:
    """Intervals of [start_ns, end_ns) in which no event runs, longest
    first."""
    gaps, cur = [], start_ns
    for a, b in merged(events):
        if a > cur:
            gaps.append([cur, min(a, end_ns)])
        cur = max(cur, b)
    if cur < end_ns:
        gaps.append([cur, end_ns])
    gaps = [g for g in gaps if g[1] > g[0]]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def label(gap, host) -> str:
    """What the host was doing in ``gap``: the deepest host event that
    covers at least half of it, else the one that overlaps it most, else
    ``"host idle"``."""
    a, b = gap
    cover = partial = None
    for s, d, name, depth in host:
        ov = min(b, s + d) - max(a, s)
        if ov <= 0:
            continue
        if ov >= 0.5 * (b - a):
            if cover is None or depth > cover[0]:
                cover = (depth, name)
        elif partial is None or ov > partial[0]:
            partial = (ov, name)
    if cover is not None:
        return cover[1]
    return partial[1] if partial is not None else "host idle"


def matching(events, pattern: str) -> list:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[2])]


def total_ns(events) -> float:
    return float(sum(e[1] for e in events))


def op_name(event_name: str) -> str:
    """``%masked_sum_2d.8 = s32[...] custom-call(...)`` -> ``masked_sum_2d``:
    the HLO instruction's name without its ``%``, its numeric suffix and
    its text."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def top_ops(events, n: int = 10) -> list:
    """[[name, seconds], ...]: the ``n`` HLO op names with the most device
    time, summed over their events.  ``while`` ops are left out: they
    span the ops of their body, which are counted themselves."""
    acc = {}
    for _, d, name in events:
        op = op_name(name)
        if op.startswith("while"):
            continue
        acc[op] = acc.get(op, 0.0) + d
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]

