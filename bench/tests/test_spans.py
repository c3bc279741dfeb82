"""The readers of the program's host spans (``bench/spans.py`` and the
``schedule_ms``, ``stage_ms``, ``dispatch_ms`` and ``prep_idle_share``
metrics), on hand-made events and on a recorded excerpt of a chip trace
(``data/trace_excerpt_spans.json``)."""
import json
import pathlib

import pytest

import run
import spans

EXCERPT = pathlib.Path(__file__).parent / "data" / "trace_excerpt_spans.json"
READERS = ("schedule_ms", "stage_ms", "dispatch_ms", "prep_idle_share")


def _readers():
    return {n: run.load_module(run.BENCH / "metrics" / f"{n}.py")
            for n in READERS}


# Two calls cut by the window [100, 1100): the first call's engine.run
# starts before it, the second's ends after it.  JAX's own events
# (DevicePut, PjitFunction) nested in a span count towards it.
HOST = [
    [100, 1000, "bench.window", 0],
    [50, 500, "engine.run", 1],
    [60, 100, "engine.schedule", 2],
    [170, 80, "engine.stage", 2],
    [180, 30, "DevicePut", 3],
    [260, 200, "engine.loop", 2],
    [270, 40, "engine.chunk", 3],
    [275, 10, "PjitFunction(dynamic_slice)", 4],
    [320, 10, "engine.probe", 3],
    [340, 100, "engine.sync", 3],
    [470, 20, "engine.collect", 2],
    [1000, 300, "engine.run", 1],
    [1010, 50, "engine.schedule", 2],
    [1070, 100, "engine.stage", 2],
]
DEVICE = [[90, 20, "%a = x"], [200, 20, "%b = x"], [280, 150, "%c = x"],
          [1050, 20, "%d = x"]]


def _ctx(host, device, rounds):
    return {"trace": {"host": host, "devices": {0: device}},
            "device_ids": [0], "trace_rounds": rounds}


def test_self_time_clipped_to_the_window():
    # engine.run: [100, 550) in the window, less schedule [100, 160),
    # stage [170, 250), loop [260, 460), collect [470, 490): 90 of the
    # first call, 100 - 50 - 30 = 20 of the second
    assert spans.window(HOST) == (100, 1100)
    assert spans.self_ns(HOST, {"engine.run"}) == 90 + 20
    assert spans.self_ns(HOST, {"engine.loop"}) == 200 - 40 - 10 - 100
    assert spans.self_ns(HOST, {"engine.schedule"}) == 60 + 50
    assert spans.self_ns(HOST, {"engine.stage"}) == 80 + 30
    assert spans.self_ns(HOST, {"engine.nothing"}) is None
    assert spans.overlap_ns([[0, 10], [20, 30]], [[5, 25]]) == 10


def test_readers_on_hand_made_events():
    r = _readers()
    ctx = _ctx(HOST, DEVICE, rounds=2)
    assert r["schedule_ms"].read(ctx) == pytest.approx((60 + 50) / 2 * 1e-6)
    assert r["stage_ms"].read(ctx) == pytest.approx((80 + 20 + 30) / 2
                                                    * 1e-6)
    assert r["dispatch_ms"].read(ctx) == pytest.approx((40 + 10) / 2 * 1e-6)
    # idle [110, 200), [220, 280), [430, 1050), [1070, 1100) against
    # prep [100, 160), [170, 250), [470, 490), [1010, 1060),
    # [1070, 1100): 50 + 30 + 30 + 20 + 40 + 30 of 1000
    assert r["prep_idle_share"].read(ctx) == pytest.approx(20.0)


def test_readers_read_nothing_without_the_spans():
    """A program that writes no ``engine.*`` span (the harness also runs
    such a parent) reads None, never 0, and does not raise."""
    bare = [e for e in HOST if not e[2].startswith("engine.")]
    for name, reader in _readers().items():
        assert reader.read(_ctx(bare, DEVICE, 2)) is None, name
        assert reader.read(_ctx(HOST[1:], DEVICE, 2)) is None, name
    assert _readers()["prep_idle_share"].read(_ctx(HOST, [], 2)) is None


def _segment_share(host, dev, lo, hi, names):
    """Brute force: the share of [lo, hi) in which no device event runs
    and some host event named in ``names`` does, over the elementary
    segments between all their boundaries."""
    prep = [e for e in host if e[2] in names]
    pts = sorted({lo, hi} | {p for e in dev + prep
                             for p in (e[0], e[0] + e[1]) if lo < p < hi})
    idle = 0.0
    for a, b in zip(pts, pts[1:]):
        m = (a + b) / 2
        busy = any(s <= m < s + d for s, d, _ in dev)
        inside = any(s <= m < s + d for s, d, *_ in prep)
        idle += (b - a) * (inside and not busy)
    return 100.0 * idle / (hi - lo)


def test_readers_on_the_recorded_excerpt():
    """From a chip trace of cell A: the end of one call (its last chunk,
    probe, sync and collect; its engine.run and engine.loop begin before
    the window) and the start of the next (schedule, stage, two chunks
    and probes; its engine.run and engine.loop end after it).  Read with
    ``trace_rounds`` 1, so each number is per excerpt."""
    tr = json.loads(EXCERPT.read_text())
    host, dev = tr["host"], tr["devices"]["0"]
    ctx = _ctx(host, dev, rounds=1)
    r = _readers()
    # one engine.schedule, fully inside
    assert r["schedule_ms"].read(ctx) == pytest.approx(78.845959)
    # engine.stage 13,243,749 ns + engine.collect 3,153,360 ns
    assert r["stage_ms"].read(ctx) == pytest.approx(16.397109)
    # chunks 2,927,650 + 2,523,060 + 2,596,711 and probes 446,260 +
    # 479,651 + 470,849 ns; the dispatches nested in them count
    assert r["dispatch_ms"].read(ctx) == pytest.approx(9.444181)
    # the first engine.run, cut to [0, 8,815,210), less its loop's part
    # [0, 5,285,240) and its collect: 376,610; the second, cut to
    # [8,931,840, 107,170,289), less schedule, stage and its loop's part
    # [101,045,149, 107,170,289): 23,601
    assert spans.self_ns(host, {"engine.run"}) == 376_610 + 23_601
    # engine.loop less chunks, probes and sync: 26,000 + 54,869
    assert spans.self_ns(host, {"engine.loop"}) == 26_000 + 54_869
    lo, hi = spans.window(host)
    share = r["prep_idle_share"].read(ctx)
    assert share == pytest.approx(_segment_share(
        host, dev, lo, hi, {"engine.schedule", "engine.stage",
                            "engine.collect"}))
    assert share == pytest.approx(88.0111464475)
