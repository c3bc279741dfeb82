"""Named phases of a run: the host spans of ``engine.run`` (``History.spans``,
on the profiler's clock) and the device scopes of its round body.

* the recorder — self time is duration minus nested spans, whatever
  raises;
* ``History.spans`` — every phase named, their self times add up to the
  run, ``wall_seconds`` is ``engine.loop`` with its children;
* results — bit-identical run to run, and with the spans and scopes
  taken out;
* device scopes — ``client_upload`` / ``secure_combine`` / ``server_step``
  in the sync and async modes of the round body, ``eval_probe`` in the
  probe, in the op metadata (locations) of the lowered programs;
* the ``profile_dir`` trace — the run's named host phases are in it.
"""
import contextlib
import math
import re
import time
import types

import jax
import numpy as np
import pytest

from repro.data import partition, synthetic
from repro.fed import engine, runtime
from repro.fed.spans import Spans
from repro.fed.staleness import ConstantDiscount, StalenessConfig

PHASES = {"engine.run", "engine.schedule", "engine.stage", "engine.loop",
          "engine.chunk", "engine.probe", "engine.sync", "engine.collect"}
LOOP = ("engine.loop", "engine.chunk", "engine.probe", "engine.sync")


@pytest.fixture(scope="module")
def small():
    data = synthetic.classification_dataset(n_train=400, n_test=100, seed=0)
    part = partition.iid(400, 8, seed=0)
    kw = dict(batch_size=5, rounds=4, eval_every=2, eval_samples=100,
              seed=2, hidden=16, secure=True)
    return data, part, kw


# the async mode at τ ≤ 1: a seed-drawn trace over a two-snapshot ring
TAU1 = StalenessConfig(max_staleness=1, schedule=ConstantDiscount())
MODES = {"sync": {}, "async": {"staleness": TAU1}}


def _series(h):
    return (h.rounds, h.metrics, h.slack, h.cum_uplink_bytes)


def _assert_same(a, b):
    (pa, ha), (pb, hb) = a, b
    for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert _series(ha) == _series(hb)


def test_recorder_self_time_excludes_nested_spans():
    rec = Spans()
    with rec("outer") as outer:
        time.sleep(0.01)
        with rec("inner") as inner:
            time.sleep(0.02)
        with rec("inner"):
            pass
        with pytest.raises(KeyError):
            with rec("raises"):
                raise KeyError("x")
    s = rec.seconds
    assert set(s) == {"outer", "inner", "raises"}
    assert inner.seconds >= 0.02 and s["inner"] >= inner.seconds
    assert outer.seconds >= 0.03
    assert math.isclose(s["outer"] + s["inner"] + s["raises"],
                        outer.seconds, rel_tol=1e-9)
    assert 0.01 <= s["outer"] < outer.seconds - 0.02


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_history_spans_name_and_cover_every_phase(small, mode):
    data, part, kw = small
    t0 = time.perf_counter()
    _, h = runtime.run_alg1(data, part, **MODES[mode], **kw)
    took = time.perf_counter() - t0
    assert set(h.spans) == PHASES
    assert all(v >= 0 for v in h.spans.values())
    assert sum(h.spans.values()) <= took
    assert math.isclose(h.wall_seconds, sum(h.spans[k] for k in LOOP),
                        rel_tol=1e-9)
    assert h.as_dict()["spans"] == h.spans


def test_same_seed_bit_identical_and_without_spans_or_scopes(small,
                                                             monkeypatch):
    """A second run of the seed repeats the first bit for bit, and so
    does a run whose spans and device scopes are taken out (no
    annotation, no timing, programs traced without ``named_scope``)."""
    data, part, kw = small
    first = {}
    for mode, extra in MODES.items():
        first[mode] = runtime.run_alg1(data, part, **extra, **kw)
        _assert_same(first[mode], runtime.run_alg1(data, part, **extra, **kw))

    class Unrecorded:
        def __init__(self):
            self.seconds = {}

        def __call__(self, name):
            return contextlib.nullcontext(types.SimpleNamespace(seconds=0.0))

    cached = (engine._chunk_fn, engine._measure_fn)
    monkeypatch.setattr(engine, "Spans", Unrecorded)
    monkeypatch.setattr(engine, "scoped", lambda name: (lambda fn: fn))
    try:
        for b in cached:            # trace the programs anew, unscoped
            b.cache_clear()
        for mode, extra in MODES.items():
            _assert_same(first[mode],
                         runtime.run_alg1(data, part, **extra, **kw))
    finally:
        monkeypatch.undo()
        for b in cached:
            b.cache_clear()


def _lowered(monkeypatch, run_kwargs, data, part):
    """Run once with every chunk program and the probe spied on: the
    lowered text, with locations, of each program on its first call."""
    texts = {}

    def spy(name, fn):
        def call(*args):
            if name not in texts:
                texts[name] = fn.lower(*args).as_text(debug_info=True)
            return fn(*args)
        return call

    real = (engine._chunk_fn, engine._measure_fn)
    monkeypatch.setattr(engine, "_chunk_fn",
                        lambda *a, **k: spy("chunk", real[0](*a, **k)))
    monkeypatch.setattr(engine, "_measure_fn",
                        lambda task: spy("probe", real[1](task)))
    runtime.run_alg1(data, part, **run_kwargs)
    monkeypatch.undo()
    return texts


@pytest.mark.parametrize("mode", ["sync", "async", "plain"])
def test_lowered_programs_carry_device_scopes(small, monkeypatch, mode):
    data, part, kw = small
    kw = dict(kw)
    if mode == "async":
        kw["staleness"] = TAU1
    elif mode == "plain":
        kw["secure"] = False
    texts = _lowered(monkeypatch, kw, data, part)
    want = {"chunk": ("client_upload", "secure_combine", "server_step"),
            "probe": ("eval_probe",)}
    if mode == "plain":     # the linear path: no combine of messages
        want["chunk"] = ("client_upload", "server_step")
    assert set(texts) == {"chunk", "probe"}
    for name, text in texts.items():
        for scope in want[name]:   # "…/<scope>/<op>", "vmap(<scope>)/…"
            assert re.search(f'["/(]{scope}[/)]', text), (mode, name, scope)
    if mode == "plain":
        assert not re.search(r'["/(](secure_)?combine[/)]', texts["chunk"])


def test_profile_dir_writes_trace(small, tmp_path):
    """The trace covers the whole call: every host phase of the run is
    in it (``jax.profiler.ProfileData``), and tracing changes no result."""
    data, part, kw = small
    prof = tmp_path / "trace"
    traced = runtime.run_alg1(data, part, profile_dir=str(prof), **kw)
    assert all(np.isfinite(traced[1].train_cost))
    written = list(prof.rglob("*.xplane.pb"))
    assert len(written) == 1, written
    pd = jax.profiler.ProfileData.from_file(str(written[0]))
    host = {e.name for plane in pd.planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events}
    assert {"engine.run", "engine.schedule", "engine.stage",
            "engine.chunk", "engine.collect"} <= host
    _assert_same(traced, runtime.run_alg1(data, part, **kw))
