"""Cell C (``granite-code-8b.cohort4-secure``) at a size a CPU test run
holds: the configuration file states the registered Granite Code 8B,
and through the harness's own program and check the plain reference
(``bench/configs/granite-code-8b.py``) passes the program under the
cell's limits, while a state left unchanged fails them.

At the cell's τ = 1 a round's step is mostly the proximal shrink of ω,
and on the chip the bfloat16 program's trajectory drifts from the
float32 reference's as far as a half batch moves it, so the cell's
limits (PERF.md §2) cannot fail the half batch.  Here, with float32
activations, the check's numbers still tell it from the program by
orders of magnitude."""
import time

import jax
import jax.numpy as jnp
import pytest

import run

CELL = "granite-code-8b.cohort4-secure"
# the block's widths cut down, float32 activations so that the program's
# products are exact float32 here
SMALL_CONFIG = {"hidden_size": 256, "num_attention_heads": 4,
                "num_key_value_heads": 1, "head_dim": 64,
                "intermediate_size": 512, "vocab_size": 512,
                "activation_dtype": "float32"}
SMALL_TRAFFIC = {"clients": 16, "samples_per_client": 2, "seq_len": 64,
                 "test_samples": 4, "eval_samples": 4}


def _ctx():
    c = run.load_cell(CELL)
    c["config"] = dict(c["config"], **SMALL_CONFIG)
    c["traffic"] = dict(c["traffic"], **SMALL_TRAFFIC)
    return c


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


def _run():
    return run.run_cell(_ctx(), 2 ** 31 + 15, 0.5, False, jax.devices()[:1],
                        run.load_peaks("TPU v5 lite"), time.time())


def test_configuration_is_the_registered_model():
    import dataclasses

    from repro.configs import get_config

    c = run.load_cell(CELL)
    config, mod = c["config"], c["module"]
    published = dict(config, **config["published"])
    assert dataclasses.replace(
        mod._model_config(published), num_layers=published[
            "num_hidden_layers"], vocab_size=published["vocab_size"]) \
        == get_config(config["program_config"])
    task = mod.task(config, c["traffic"])
    key = jax.random.key(0)
    want = jax.eval_shape(task.init_params, key)
    got = jax.eval_shape(
        lambda k: mod._data_and_params(k, config, 8, 8, 16)[2], key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in
               zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    assert sum(x.size for x in jax.tree.leaves(got)) \
        == config["num_parameters"]
    # 6·P·T over 8,192 tokens plus causal attention: about 12.4 TFLOP
    assert mod.model_flops_per_round(config, c["traffic"]) \
        == pytest.approx(1.23695e13, rel=1e-5)


def test_tokens_come_from_the_vocabulary_slice():
    c = _ctx()
    data, _ = c["module"].make(c["config"], c["traffic"], 2 ** 31 + 3)
    tokens = data.x_train
    assert tokens.shape == (32, 64) and tokens.dtype == jnp.int32
    assert int(tokens.min()) >= 0 and int(tokens.max()) < 512
    # Zipf(1.1) over 512 ids: the most frequent takes about a fifth
    top = float(jnp.mean(tokens == 0))
    assert 0.08 < top < 0.25, top


def test_sound_program_is_correct():
    res = _run()
    assert res["correct"], res["checks"]


def test_half_of_the_cohort_at_double_weight_moves_step_diff(monkeypatch):
    """The half_batch fault of the limits' readings, planted in the
    program: the secure combine sums the first two of the four members'
    uploads, twice each.  It reads ``step_diff`` 0.11-0.12 here, under
    the cell's limit; the sound program reads 1.4e-5 (float32 on both
    sides), so a thousand times the program's reading still lies below
    the fault's."""
    from repro.fed.aggregation import SecureAggregation
    sound = _run()["checks"]["step_diff"]["value"]
    _clear()
    full = SecureAggregation.combine_messages

    def half(self, wmsgs, key, alive=None):
        def keep(m):
            h = m.shape[0] // 2
            return jnp.concatenate([2 * m[:h], jnp.zeros_like(m[h:])])
        return full(self, jax.tree.map(keep, wmsgs), key, alive)

    monkeypatch.setattr(SecureAggregation, "combine_messages", half)
    faulty = _run()["checks"]["step_diff"]["value"]
    assert faulty > 1000 * sound, (faulty, sound)


def test_state_left_unchanged_is_not_correct(monkeypatch):
    from repro.core import protocol
    monkeypatch.setattr(protocol.SSCAUnconstrained, "server_step",
                        lambda self, params, state, agg: (params, state))
    res = _run()
    assert not res["correct"], res["checks"]
