"""The batch schedule draws bit for bit what the plain full-population
draw gives.

:func:`repro.data.partition.sample_schedule` keys every client of the
population (the draw contract) but selects, masks and gathers only the
rows its output keeps.  ``_plain_schedule`` below is the straightforward
body it replaces: key, select and gather every client, then keep the
cohort's rows.  Both must agree exactly on uniform, cohort, ragged,
small-client and multi-block partitions, and through
:func:`repro.fed.engine.build_schedule` with local steps and with
groups.
"""
import numpy as np
import pytest

from repro.data import partition
from repro.data.partition import Partition, sample_schedule
from repro.fed import aggregation, engine, runtime


def _plain_schedule(part: Partition, batch_size: int, round_ids,
                    seed: int = 0, cohorts=None,
                    block_elems: int = 1 << 20) -> np.ndarray:
    """Reference: the full-population draw, then row selection."""
    round_ids = np.asarray(round_ids, np.int64)
    sizes = part.sizes
    i_cl = part.num_clients
    width = max(int(sizes.max()), batch_size)
    no_repl = sizes >= batch_size
    if cohorts is not None:
        cohorts = np.asarray(cohorts, np.int64)
        rows = cohorts.shape[1]
    else:
        rows = i_cl
    out = np.empty((len(round_ids), rows, batch_size), np.int64)
    any_repl = bool((~no_repl).any())
    block = max(1, block_elems // width)
    col = np.arange(width)[None, :]
    for k, t in enumerate(round_ids):
        rng = np.random.default_rng(np.random.SeedSequence([seed, int(t)]))
        full = np.empty((i_cl, batch_size), np.int64)
        for lo in range(0, i_cl, block):
            hi = min(lo + block, i_cl)
            sz = sizes[lo:hi, None]
            keys = rng.random((hi - lo, width), dtype=np.float32)
            keys[col >= sz] = np.inf
            sel = np.argpartition(keys, batch_size - 1,
                                  axis=1)[:, :batch_size]
            padded = part.flat[part.offsets[lo:hi, None]
                               + np.where(col < sz, col, 0)]
            full[lo:hi] = np.take_along_axis(padded, sel, axis=1)
        if any_repl:
            u = rng.random((i_cl, batch_size))
            wr = part.flat[part.offsets[:, None]
                           + (u * sizes[:, None]).astype(np.int64)]
            full = np.where(no_repl[:, None], full, wr)
        out[k] = full if cohorts is None else full[cohorts[k]]
    return out


def _ragged(sizes, seed=0):
    """A partition with the given client sizes over a shuffled range."""
    perm = np.random.default_rng(seed).permutation(int(np.sum(sizes)))
    cuts = np.cumsum(sizes)[:-1]
    return Partition.from_indices(np.split(perm, cuts))


def _case(name):
    """(partition, batch size, cohort size or None) of each case."""
    rng = np.random.default_rng(5)
    if name == "uniform_all_in":
        return partition.iid(600, 10, seed=0), 20, 10
    if name == "cohort_few_of_many":
        return partition.iid(3000, 300, seed=1), 4, 7
    if name == "ragged":
        return _ragged(rng.integers(12, 90, size=40)), 8, 9
    if name == "small_clients":
        return _ragged(rng.integers(1, 30, size=50)), 12, 11
    if name == "small_clients_all_in":
        return _ragged(rng.integers(1, 30, size=20)), 12, None
    raise KeyError(name)


CASES = ["uniform_all_in", "cohort_few_of_many", "ragged",
         "small_clients", "small_clients_all_in"]


@pytest.mark.parametrize("name", CASES)
def test_schedule_matches_plain_draw(name):
    part, b, s = _case(name)
    ids = np.asarray([1, 4, 9, 2])
    co = None if s is None \
        else partition.sample_cohorts(part.num_clients, s, ids, seed=3)
    got = sample_schedule(part, b, ids, seed=3, cohorts=co)
    np.testing.assert_array_equal(
        got, _plain_schedule(part, b, ids, seed=3, cohorts=co))


def test_schedule_matches_plain_draw_across_blocks(monkeypatch):
    """A skewed partition whose hot client makes ``width`` large enough
    that one block holds a few clients: the row selection must follow
    every block boundary, cohort rows in any order, duplicates too."""
    sizes = np.full(23, 6)
    sizes[13] = 700
    part = _ragged(sizes, seed=2)
    monkeypatch.setattr(partition, "_BLOCK_ELEMS", 3000)   # 4 clients a block
    ids = np.asarray([3, 8])
    co = np.asarray([[22, 0, 13, 4, 4, 9, 3, 17],
                     [5, 6, 7, 8, 12, 13, 14, 1]])
    for b in (5, 8):        # 8 > 6: most clients draw with replacement
        for cohorts in (None, co):
            np.testing.assert_array_equal(
                sample_schedule(part, b, ids, seed=7, cohorts=cohorts),
                _plain_schedule(part, b, ids, seed=7, cohorts=cohorts,
                                block_elems=3000))


@pytest.mark.parametrize("e_axis,groups", [(True, None), (False, 3)])
def test_build_schedule_matches_plain_draw(e_axis, groups):
    """Through the engine's schedule: local steps repeat the cohort over
    E round ids, groups permute the cohort rows before the draw."""
    part = _ragged(np.random.default_rng(1).integers(3, 40, size=30))
    rounds, steps, b, s = 3, 2, 6, 9
    cohorts, idx = engine.build_schedule(part, b, rounds, steps, seed=4,
                                         e_axis=e_axis, cohort_size=s,
                                         groups=groups)
    ids = engine._round_ids(rounds, steps, e_axis)
    per_id = np.repeat(cohorts, steps, axis=0) if e_axis else cohorts
    ref = _plain_schedule(part, b, ids, seed=4, cohorts=per_id)
    if e_axis:
        ref = ref.reshape(rounds, steps, s, b).transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(idx, ref)


@pytest.mark.parametrize("algo,draws", [("alg1", 1), ("fedavg", 2)])
def test_run_records_sample_rows(dataset, fed_partition, algo, draws):
    """A run's ledger says how many rows the schedule keeps (S, or E·S
    with E local steps) against how many the draw contract keys (I, or
    E·I)."""
    run = {"alg1": runtime.run_alg1, "fedavg": runtime.run_fedavg}[algo]
    _, h = run(dataset, fed_partition, batch_size=10, rounds=2,
               eval_every=2, eval_samples=100, seed=1,
               aggregation=aggregation.sampled(4))
    assert h.comm["sample_rows_kept_per_round"] == 4 * draws
    assert h.comm["sample_rows_keyed_per_round"] == 10 * draws
