"""Async round mode: staleness traces, discount schedules, dropout-
tolerant secure aggregation, and the engine-level bit-identity
contracts.

Three layers, mirroring how the subsystem composes:

* trace / schedule layer — ``sample_staleness`` is seed-stable, bounded,
  and drawn on its own rng stream (independent of the cohort / batch /
  group draws, like the PR 5 / PR 7 stream-separation tests);
  ``discount_reweight`` preserves the cohort weight mass exactly.
* mask layer — the Bonawitz ``alive`` path: the masked sum over
  survivors equals the plain survivor sum **bit for bit**, for the
  unrolled pairwise path, the scan path, the Pallas kernel (interpret
  mode) and the hierarchical within-group ring — including sentinel-
  padded cohorts.
* engine layer — async with an all-zero trace is bit-identical to the
  synchronous engine (the mesh variants live in
  ``tests/async_engine_check.py``); dropouts change the trajectory but
  keep it finite, and the recovery wire is charged to the ledger.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import partition, synthetic
from repro.data.partition import sample_staleness
from repro.fed import aggregation, runtime
from repro.fed.staleness import (ConstantDiscount, PolynomialDiscount,
                                 StalenessConfig, diurnal_delay_probs,
                                 discount_reweight, dropped_per_round,
                                 round_times)
from repro.kernels import ops as kops
from repro.kernels import secure_agg

ROUNDS = np.arange(1, 7, dtype=np.int64)


# ---------------------------------------------------------------------------
# staleness trace: seed stability, bounds, stream separation
# ---------------------------------------------------------------------------

def test_trace_none_probs_is_all_zero_without_rng():
    tr = sample_staleness(8, ROUNDS, seed=5, delay_probs=None)
    assert tr.shape == (6, 8) and not tr.any()


def test_trace_seed_stable_and_bounded():
    probs = [0.5, 0.3, 0.2]
    a = sample_staleness(10, ROUNDS, seed=7, delay_probs=probs)
    b = sample_staleness(10, ROUNDS, seed=7, delay_probs=probs)
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 0 and a.max() <= 2
    c = sample_staleness(10, ROUNDS, seed=8, delay_probs=probs)
    assert (a != c).any()


def test_trace_rows_keyed_on_round_ids_not_positions():
    """Round t's delays depend on t, not on where t sits in the id list —
    the same random-access contract the cohort/batch draws honor."""
    probs = [0.4, 0.3, 0.3]
    full = sample_staleness(6, ROUNDS, seed=3, delay_probs=probs)
    sub = sample_staleness(6, ROUNDS[::2], seed=3, delay_probs=probs)
    np.testing.assert_array_equal(sub, full[::2])


def test_trace_stream_independent_of_cohort_batch_group_draws():
    """Drawing the staleness trace must not perturb — nor be perturbed
    by — the cohort, batch and group streams: every draw is keyed on its
    own SeedSequence tag, so interleaving them changes nothing."""
    part = partition.iid(200, 10, seed=0)
    probs = [0.6, 0.4]
    co0 = partition.sample_cohorts(10, 4, ROUNDS, seed=11)
    sch0 = partition.sample_schedule(part, 8, ROUNDS, seed=11, cohorts=co0)
    gr0 = partition.sample_groups(4, 2, ROUNDS, seed=11)
    tr0 = sample_staleness(4, ROUNDS, seed=11, delay_probs=probs)
    # interleaved redraws, same seeds
    tr1 = sample_staleness(4, ROUNDS, seed=11, delay_probs=probs)
    co1 = partition.sample_cohorts(10, 4, ROUNDS, seed=11)
    tr2 = sample_staleness(4, ROUNDS, seed=11, delay_probs=probs)
    sch1 = partition.sample_schedule(part, 8, ROUNDS, seed=11, cohorts=co1)
    gr1 = partition.sample_groups(4, 2, ROUNDS, seed=11)
    np.testing.assert_array_equal(tr0, tr1)
    np.testing.assert_array_equal(tr0, tr2)
    np.testing.assert_array_equal(co0, co1)
    np.testing.assert_array_equal(sch0, sch1)
    np.testing.assert_array_equal(gr0, gr1)
    # ...and the streams are actually distinct: the trace draw under the
    # uniform 2-point distribution is not the cohort draw's parity (a
    # shared stream would make them deterministic functions of another)
    assert not np.array_equal(tr0, co0[:, :4] % 2)


def test_trace_property_seed_stable_bounded_distributed():
    hyp = pytest.importorskip("hypothesis")
    given, settings, st = hyp.given, hyp.settings, hyp.strategies

    @given(s=st.integers(1, 12), d=st.integers(1, 5),
           seed=st.integers(0, 2 ** 16))
    @settings(max_examples=20, deadline=None)
    def check(s, d, seed):
        probs = np.ones(d + 1) / (d + 1)
        ids = np.arange(1, 40, dtype=np.int64)
        a = sample_staleness(s, ids, seed=seed, delay_probs=probs)
        b = sample_staleness(s, ids, seed=seed, delay_probs=probs)
        np.testing.assert_array_equal(a, b)          # seed-stable
        assert a.min() >= 0 and a.max() <= d         # bounded by D-1
        if s * len(ids) >= 200 and d >= 1:
            # loose LLN sanity: every delay value shows up under the
            # uniform distribution on ≥200 draws
            assert len(np.unique(a)) == d + 1

    check()


def test_trace_per_round_probs_rows():
    probs = np.zeros((6, 3))
    probs[:3, 0] = 1.0          # rounds 1-3: always fresh
    probs[3:, 2] = 1.0          # rounds 4-6: always delay 2
    tr = sample_staleness(5, ROUNDS, seed=0, delay_probs=probs)
    assert not tr[:3].any() and (tr[3:] == 2).all()


def test_trace_validation():
    with pytest.raises(ValueError):
        sample_staleness(4, ROUNDS, delay_probs=[-0.1, 1.1])
    with pytest.raises(ValueError):
        sample_staleness(4, ROUNDS, delay_probs=[0.0, 0.0])
    with pytest.raises(ValueError):
        sample_staleness(4, ROUNDS, delay_probs=np.ones((3, 2)))  # T != 6


# ---------------------------------------------------------------------------
# discount schedules + mass-preserving reweighting
# ---------------------------------------------------------------------------

def test_polynomial_discount_values():
    d = PolynomialDiscount(0.5)
    out = np.asarray(d.discount(jnp.arange(4)))
    np.testing.assert_allclose(out, (1.0 + np.arange(4)) ** -0.5, rtol=1e-6)
    assert out[0] == 1.0                       # fresh uploads untouched
    assert (np.diff(out) < 0).all()
    assert (PolynomialDiscount(0.0).discount(jnp.arange(4)) == 1.0).all()
    assert (ConstantDiscount().discount(jnp.arange(4)) == 1.0).all()
    with pytest.raises(ValueError):
        PolynomialDiscount(-1.0)


def test_discount_reweight_identity_at_ones_bitwise():
    w = jnp.asarray([0.1, 0.3, 0.0, 0.6], jnp.float32)
    out = discount_reweight(w, jnp.ones(4, jnp.float32))
    assert (np.asarray(out) == np.asarray(w)).all()


def test_discount_reweight_mass_and_dropout():
    w = jnp.asarray([0.25, 0.25, 0.25, 0.25], jnp.float32)
    d = jnp.asarray([1.0, 0.5, 0.0, 1.0], jnp.float32)
    out = np.asarray(discount_reweight(w, d))
    assert abs(out.sum() - 1.0) < 1e-6         # Σλ' = Σλ
    assert out[2] == 0.0                       # dropped slot contributes 0
    # all dropped -> zero weights, not NaN
    z = np.asarray(discount_reweight(w, jnp.zeros(4)))
    assert (z == 0).all()


def test_round_times_and_dropped():
    tr = np.asarray([[0, 0, 0], [1, 0, 2], [4, 0, 0]])
    np.testing.assert_array_equal(round_times(tr, "sync", 2), [1, 3, 4])
    np.testing.assert_array_equal(round_times(tr, "async", 2), [1, 1, 1])
    np.testing.assert_array_equal(round_times(tr, "drop", 2), [1, 1, 1])
    np.testing.assert_array_equal(dropped_per_round(tr, 2), [0, 0, 1])
    with pytest.raises(ValueError):
        round_times(tr, "nope", 2)


def test_diurnal_probs_rows_normalized():
    p = diurnal_delay_probs(40, max_delay=3, straggler_frac=0.5, period=10)
    assert p.shape == (40, 4)
    np.testing.assert_allclose(p.sum(axis=1), 1.0, atol=1e-12)
    assert p[0, 0] == 1.0                      # t=0: no stragglers
    assert p[5, 1:].sum() > 0.4                # peak of the period


def test_config_validation_and_hashability():
    cfg = StalenessConfig(max_staleness=3, delay_probs=[0.5, 0.5])
    assert isinstance(hash(cfg), int)          # engine cache key
    assert cfg.delay_probs == (0.5, 0.5)
    with pytest.raises(ValueError):
        StalenessConfig(max_staleness=-1)
    with pytest.raises(ValueError):
        StalenessConfig(max_staleness=True)


# ---------------------------------------------------------------------------
# dropout cancellation: masked survivor sum == plain survivor sum, bitwise
# ---------------------------------------------------------------------------

SB = 20


def _msgs(n, d=37, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))


def _survivor_sum_grid(msgs, alive):
    """The oracle: quantize each survivor onto the fixed-point grid, sum
    in Z_{2^32}, dequantize."""
    q = secure_agg.quantize(msgs, SB)
    tot = jnp.sum(q * jnp.asarray(alive, jnp.int32)[:, None], axis=0,
                  dtype=jnp.int32)
    return secure_agg.dequantize(tot, SB)


@pytest.mark.parametrize("n,alive", [
    (4, [1, 0, 1, 1]),                   # unrolled pairwise path
    (4, [0, 0, 0, 0]),                   # everyone dropped
    (20, [1] * 15 + [0] * 5),            # lax.scan path (> UNROLL_MAX)
    (1, [0]),                            # degenerate single client
])
def test_masked_survivor_sum_bitwise(n, alive):
    msgs = _msgs(n)
    key = jax.random.key_data(jax.random.key(42))
    got = secure_agg.dequantize(secure_agg.masked_sum_flat(
        msgs.reshape(n, -1), key, SB,
        alive=jnp.asarray(alive, jnp.int32)), SB)
    want = _survivor_sum_grid(msgs, alive)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want).reshape(-1))


def test_masked_survivor_sum_sharded_bitwise():
    """Directed partial sums from two shards merge to the same survivor
    total — the alive path composes with the mesh psum decomposition."""
    n, alive = 6, jnp.asarray([1, 1, 0, 1, 0, 1], jnp.int32)
    msgs = _msgs(n)
    key = jax.random.key_data(jax.random.key(9))
    parts = [secure_agg.masked_partial_sum_flat(
        msgs.reshape(n, -1)[o:o + 3], key, SB, client_offset=o,
        num_clients=n, alive=alive) for o in (0, 3)]
    got = secure_agg.dequantize(parts[0] + parts[1], SB)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_survivor_sum_grid(msgs, alive)).reshape(-1))


def test_masked_survivor_sum_pallas_kernel_bitwise():
    """ops.secure_quant_sum routes alive through the Pallas kernel
    (interpret mode on CPU) — same survivor bits as the XLA reference."""
    n, alive = 5, jnp.asarray([1, 0, 1, 1, 0], jnp.int32)
    msgs = {"w": _msgs(n, 29), "b": _msgs(n, 7, seed=1)}
    key = jax.random.key_data(jax.random.key(7))
    for use_kernel in (False, True):
        got = kops.secure_dequantize(
            kops.secure_quant_sum(msgs, key, scale_bits=SB, alive=alive,
                                  interpret=True, use_kernel=use_kernel),
            SB)
        for name in msgs:
            want = _survivor_sum_grid(msgs[name], alive)
            np.testing.assert_array_equal(np.asarray(got[name]),
                                          np.asarray(want))


def test_alive_none_matches_all_ones():
    n = 8
    msgs = _msgs(n)
    key = jax.random.key_data(jax.random.key(3))
    a = secure_agg.masked_sum_flat(msgs.reshape(n, -1), key, SB)
    b = secure_agg.masked_sum_flat(msgs.reshape(n, -1), key, SB,
                                   alive=jnp.ones(n, jnp.int32))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("s,groups", [(12, 3), (10, 3)])   # 10: padded
def test_hierarchical_dropout_within_group_bitwise(s, groups):
    """Group-local mask cancellation: the tree combine with dropped
    members equals the plain survivor sum on the grid — including the
    sentinel-padded cohort (G ∤ S), whose pads stay alive with zero
    uploads."""
    rng = np.random.default_rng(5)
    msgs = {"w": _msgs(s, 23, seed=5)}
    alive = jnp.asarray(rng.integers(0, 2, size=s), jnp.int32)
    key = jax.random.key(13)
    agg = aggregation.hierarchical(groups=groups)
    got = agg.combine_messages(msgs, key, alive=alive)
    want = _survivor_sum_grid(msgs["w"], alive)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(want))


def test_recovery_bytes_per_drop():
    assert aggregation.plain().recovery_bytes_per_drop(10) == 0
    assert aggregation.sampled(4).recovery_bytes_per_drop(10) == 0
    assert aggregation.secure().recovery_bytes_per_drop(10) == 4 * 9
    assert aggregation.secure(num_sampled=4).recovery_bytes_per_drop(10) \
        == 4 * 3
    # hierarchical: blast radius is one group (M members), not the cohort
    hier = aggregation.hierarchical(groups=2)
    assert hier.recovery_bytes_per_drop(10) == 4 * (5 - 1)


# ---------------------------------------------------------------------------
# engine-level: zero trace == sync, dropouts finite + charged
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_setup():
    data = synthetic.classification_dataset(n_train=400, n_test=100, seed=0)
    part = partition.iid(400, 8, seed=0)
    kw = dict(batch_size=5, rounds=4, eval_every=2, eval_samples=100,
              seed=2, hidden=16)
    return data, part, kw


@pytest.mark.parametrize("extra", [
    {}, {"secure": True},
    {"aggregation": aggregation.hierarchical(groups=2)},
])
def test_async_zero_trace_bitwise_sync(small_setup, extra):
    data, part, kw = small_setup
    _, hs = runtime.run_alg1(data, part, **kw, **extra)
    _, ha = runtime.run_alg1(data, part, **kw, **extra,
                             staleness=StalenessConfig(max_staleness=2))
    assert hs.train_cost == ha.train_cost
    assert hs.test_accuracy == ha.test_accuracy


def test_async_zero_trace_bitwise_sync_fedavg(small_setup):
    data, part, kw = small_setup
    _, hs = runtime.run_fedavg(data, part, **kw, local_steps=2)
    _, ha = runtime.run_fedavg(data, part, **kw, local_steps=2,
                               staleness=StalenessConfig(max_staleness=1))
    assert hs.train_cost == ha.train_cost
    assert hs.test_accuracy == ha.test_accuracy


def test_async_nonzero_trace_runs_and_charges_recovery(small_setup):
    data, part, kw = small_setup
    cfg = StalenessConfig(max_staleness=1,
                          delay_probs=[0.4, 0.3, 0.2, 0.1])  # 2,3 drop
    _, h = runtime.run_alg1(data, part, **kw, secure=True, staleness=cfg)
    assert all(np.isfinite(h.train_cost))
    a = h.comm["async"]
    tr = sample_staleness(8, np.arange(1, 5, dtype=np.int64), 2,
                          cfg.delay_probs)
    assert a["dropped_total"] == int((tr > 1).sum()) > 0
    assert a["recovery_bytes_per_drop"] == 4 * 7
    assert a["recovery_bytes_total"] == a["dropped_total"] * 4 * 7
    # the discounted/dropped trajectory actually moved off the sync one
    _, hs = runtime.run_alg1(data, part, **kw, secure=True)
    assert hs.train_cost != h.train_cost


def test_explicit_trace_and_validation(small_setup):
    data, part, kw = small_setup
    tr = np.zeros((4, 8), np.int64)
    tr[1, 3] = 1
    cfg = StalenessConfig(max_staleness=1)
    _, h = runtime.run_alg1(data, part, **kw, staleness=cfg,
                            staleness_trace=tr)
    assert all(np.isfinite(h.train_cost))
    with pytest.raises(ValueError, match="staleness_trace requires"):
        runtime.run_alg1(data, part, **kw, staleness_trace=tr)
    with pytest.raises(ValueError, match="shape"):
        runtime.run_alg1(data, part, **kw, staleness=cfg,
                         staleness_trace=np.zeros((2, 8), np.int64))
    with pytest.raises(ValueError, match=">= 0"):
        runtime.run_alg1(data, part, **kw, staleness=cfg,
                         staleness_trace=np.full((4, 8), -1))


# ---------------------------------------------------------------------------
# engine-level pinned trajectories (subprocess — see async_engine_check.py)
# ---------------------------------------------------------------------------

def _run_check(args):
    from _subprocess import run_check
    run_check("async_engine_check.py", *args, marker="ASYNC_CHECK_OK")


def test_async_zero_trace_pinned_single_device():
    """Async + all-zero trace reproduces the synchronous run of the same
    configuration bitwise, for all seven plain/secure/sampled/compressed
    configurations."""
    _run_check([])


@pytest.mark.slow
def test_async_zero_trace_and_mesh_invariance_client_mesh():
    """Same on a 2-virtual-device client mesh, plus: a *nonzero* trace
    (stale uploads + dropouts) gives bitwise-identical trajectories on
    the mesh and on a single device for the mesh-invariant cases."""
    _run_check(["--mesh"])
