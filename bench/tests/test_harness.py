"""A whole run of the harness with the chip lookup skipped: it passes on
the program as it is, and ``correct`` comes out false for each fault a
training cell can have, planted in the program underneath the timed
path.  Also: with no TPU the command exits non-zero and prints no
result."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap
import time

import jax
import pytest

import run
import tiny

BENCH = pathlib.Path(__file__).resolve().parents[1]
PEAKS = run.load_peaks("TPU v5 lite")
CELL = "mlp-paper.cohort512-secure"


def _clear():
    from repro.fed import engine
    engine._chunk_fn.cache_clear()
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _fresh_programs():
    """A planted fault is compiled into the engine's cached chunk: start
    and leave every test with none cached."""
    _clear()
    yield
    _clear()


def _run(name, devices=None):
    return run.run_cell(tiny.ctx(name), 2 ** 31 + 3, 0.5, False,
                        devices or jax.devices()[:1], PEAKS, time.time(),
                        log=sys.stderr)


def test_sound_program_is_correct():
    res = _run(CELL)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["metrics"]["round_ms"]["value"] > 0


def test_state_left_unchanged_is_not_correct(monkeypatch):
    from repro.core import protocol
    monkeypatch.setattr(protocol.SSCAUnconstrained, "server_step",
                        lambda self, params, state, agg: (params, state))
    res = _run(CELL)
    assert not res["correct"], res["checks"]


def test_half_batch_is_not_correct(monkeypatch):
    from repro.fed.tasks.mlp import MLPTask
    full = MLPTask.loss_sum

    def half(self, params, batch):
        x, y, w = batch
        h = x.shape[0] // 2
        return full(self, params, (x[:h], y[:h], 2 * w[:h]))

    monkeypatch.setattr(MLPTask, "loss_sum", half)
    res = _run(CELL)
    assert not res["correct"], res["checks"]


_MESH = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{src!r}, {bench!r}, {tests!r}]
    import jax
    import run, tiny
    if {fault}:
        jax.lax.psum = lambda x, axis_name, **kw: x
    ctx = tiny.ctx("mlp-paper.cohort512-secure")
    ctx["cell"] = dict(ctx["cell"], chips=4)
    res = run.run_cell(ctx, 2 ** 31 + 9, 0.5, False, jax.devices()[:4],
                       run.load_peaks("TPU v5 lite"), time.time(),
                       log=sys.stderr)
    print(json.dumps(res))
""")


@pytest.mark.parametrize("fault", [False, True])
def test_exchange_between_chips(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _MESH.format(src=str(BENCH.parent / "src"), bench=str(BENCH),
                        tests=str(BENCH / "tests"), fault=fault)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is (not fault), res["checks"]


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 1), "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "correct" not in out.stdout
