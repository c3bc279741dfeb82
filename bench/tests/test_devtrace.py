"""The trace reduction, on hand-made events and on a recorded excerpt of
a chip trace (``data/trace_excerpt.json``)."""
import json
import pathlib

import numpy as np
import pytest

import devtrace

EXCERPT = pathlib.Path(__file__).parent / "data" / "trace_excerpt.json"


def _timeline_busy(events, lo, hi):
    """Busy nanoseconds by brute force on a 1-ns grid."""
    grid = np.zeros(int(hi - lo), bool)
    for s, d, *_ in events:
        grid[int(s - lo):int(s + d - lo)] = True
    return int(grid.sum())


def test_union_gaps_and_labels_on_hand_made_events():
    ev = [[0, 10, "%a.1 = x"], [5, 10, "%b = y"], [30, 5, "%a.2 = x"],
          [31, 2, "%c = z"], [50, 0, "%d = z"]]
    assert devtrace.merged(ev) == [[0, 15], [30, 35], [50, 50]]
    assert devtrace.busy_ns(ev) == 20
    assert devtrace.idle_gaps(ev, 0, 60) == [[15, 30], [35, 50], [50, 60]]
    host = [[10, 40, "outer", 0], [16, 4, "inner", 1], [36, 2, "tiny", 0]]
    assert devtrace.label([15, 30], host) == "outer"
    assert devtrace.label([16, 24], host) == "inner"
    assert devtrace.label([100, 110], host) == "host idle"
    assert devtrace.op_name("%a.12 = f32[2] fusion(x)") == "a"
    top = devtrace.top_ops(ev + [[0, 99, "%while.3 = (s32[]) while(x)"]])
    assert top[0][0] == "a" and top[0][1] == pytest.approx(15e-9)
    assert all(name != "while" for name, _ in top)


def test_recorded_excerpt():
    tr = json.loads(EXCERPT.read_text())
    dev = tr["devices"]["0"]
    lo = min(e[0] for e in dev)
    hi = max(e[0] + e[1] for e in dev)
    assert devtrace.busy_ns(dev) == _timeline_busy(dev, lo, hi)
    gaps = devtrace.idle_gaps(dev, lo, hi)
    assert sum(b - a for a, b in gaps) == pytest.approx(
        (hi - lo) - devtrace.busy_ns(dev))
    assert all(g[1] - g[0] >= h[1] - h[0] for g, h in zip(gaps, gaps[1:]))
    kern = devtrace.matching(dev, r"^%masked_sum_2d(\.\d+)? = ")
    assert kern and all("custom-call" in e[2] for e in kern)
    top = dict(devtrace.top_ops(dev, n=50))
    assert top["masked_sum_2d"] == pytest.approx(
        devtrace.total_ns(kern) * 1e-9)
    assert "while" not in top
    labels = {devtrace.label(g, tr["host"]) for g in gaps[:5]}
    assert labels and "host idle" not in labels


def test_metric_readers_on_the_excerpt():
    """Each trace reader reads the recorded excerpt, and reads nothing
    (None, never 0) from a trace with no device events."""
    import run

    tr = json.loads(EXCERPT.read_text())
    tr["devices"] = {int(k): v for k, v in tr["devices"].items()}
    dev = tr["devices"][0]
    lo = min(e[0] for e in dev)
    hi = max(e[0] + e[1] for e in dev)
    ctx = {"trace": tr, "device_ids": [0], "window_s": (hi - lo) * 1e-9,
           "trace_rounds": 10, "chips": 1, "n_params": 101632,
           "traffic": {"cohort": 10},
           "peaks": run.load_peaks("TPU v5 lite")}
    empty = dict(ctx, trace={"devices": {0: []}, "host": []})
    for name in ("device_idle_share", "secure_combine_ms",
                 "secure_combine_roofline"):
        reader = run.load_module(run.BENCH / "metrics" / f"{name}.py")
        v = reader.read(ctx)
        assert v is not None and v > 0, name
        assert reader.read(empty) is None, name
    idle = run.load_module(run.BENCH / "metrics" / "device_idle_share.py")
    assert 0 < idle.read(ctx) < 100

