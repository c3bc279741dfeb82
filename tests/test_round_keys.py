"""Hash-consed per-round keys: ``engine._round_keys`` derives the
per-round ``fold_in`` key words once per (seed, rounds), outside the scan
body.  The cached rows must be bit-identical to the in-loop derivation
they replaced: the mask, PRF and stochastic-rounding streams hang off
these words, so one flipped bit breaks every secure trace.
"""
import jax
import numpy as np

from repro.fed import engine


def test_round_keys_match_in_loop_fold_in_bitwise():
    """Row t−1 of the cached array holds exactly the key words of
    ``fold_in(key(seed + 10_000), t)`` — the derivation the scan body
    used to run per round."""
    seed, rounds = 7, 5
    rows = np.asarray(engine._round_keys(seed, rounds))
    base = jax.random.key(seed + 10_000)
    for t in range(1, rounds + 1):
        want = np.asarray(jax.random.key_data(
            jax.random.fold_in(base, t)))
        np.testing.assert_array_equal(rows[t - 1], want)


def test_round_keys_streams_bit_identical_through_wrap():
    """Feeding a cached row through ``wrap_key_data`` yields the same
    downstream random stream as the live fold_in key."""
    row = engine._round_keys(3, 4)[2]
    live = jax.random.fold_in(jax.random.key(3 + 10_000), 3)
    a = jax.random.normal(jax.random.wrap_key_data(row), (16,))
    b = jax.random.normal(live, (16,))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_round_keys_hash_consed():
    """Same (seed, rounds) returns the same cached array object — the
    derivation runs once per config per process."""
    assert engine._round_keys(11, 6) is engine._round_keys(11, 6)
    assert engine._round_keys(11, 6) is not engine._round_keys(12, 6)
