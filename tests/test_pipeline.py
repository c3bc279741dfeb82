"""Pipelined round mode: the software-pipelined engine that overlaps
round t+1's cohort compute with round t's in-flight secure combine.

Three layers:

* key derivation — ``_round_keys`` hash-conses the per-round ``fold_in``
  key words out of the scan body; the cached rows must be bit-identical
  to the in-loop derivation they replaced (the mask/PRF streams hang off
  these words, so one flipped bit breaks every secure trace).
* engine layer — ``pipeline=True`` reproduces the async bounded-
  staleness mode at the constant τ≡1 trace bit-for-bit on every
  aggregation path (subprocess harness:
  ``tests/pipeline_engine_check.py``; the mesh variant also pins the
  chunked ``ppermute`` ring against the flat ``lax.psum`` bitwise).
* tooling — the ``profile_dir`` hook writes a ``jax.profiler`` trace
  around the whole call, the run's named host phases in it; the comm
  ledger reports the pipeline's +1 snapshot-slot memory model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import partition, synthetic
from repro.fed import engine, runtime
from repro.fed.staleness import ConstantDiscount, StalenessConfig


# ---------------------------------------------------------------------------
# hash-consed per-round keys
# ---------------------------------------------------------------------------

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


# ---------------------------------------------------------------------------
# engine-level: validation, ledger, profiler hook
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_setup():
    data = synthetic.classification_dataset(n_train=400, n_test=100, seed=0)
    part = partition.iid(400, 8, seed=0)
    kw = dict(batch_size=5, rounds=4, eval_every=2, eval_samples=100,
              seed=2, hidden=16)
    return data, part, kw


def test_pipeline_rejects_staleness(small_setup):
    data, part, kw = small_setup
    cfg = StalenessConfig(max_staleness=1, schedule=ConstantDiscount())
    with pytest.raises(ValueError, match="pipeline=True IS the constant"):
        runtime.run_alg1(data, part, pipeline=True, staleness=cfg, **kw)


def test_pipeline_ledger_reports_snapshot_slot(small_setup):
    data, part, kw = small_setup
    _, h = runtime.run_alg1(data, part, pipeline=True, **kw)
    assert h.comm["pipeline"] == {"enabled": True, "depth": 1,
                                  "extra_snapshot_slots": 1}
    assert all(np.isfinite(h.train_cost))
    _, h_flat = runtime.run_alg1(data, part, **kw)
    assert "pipeline" not in h_flat.comm


def test_pipeline_matches_async_tau1_single_device(small_setup):
    """The in-process spot check of the subprocess harness' contract —
    linear fast path, final params and trajectories bitwise."""
    data, part, kw = small_setup
    tau1 = StalenessConfig(max_staleness=1, schedule=ConstantDiscount())
    trace = np.ones((kw["rounds"], 8), np.int64)
    p_a, h_a = runtime.run_alg1(data, part, staleness=tau1,
                                staleness_trace=trace, **kw)
    p_p, h_p = runtime.run_alg1(data, part, pipeline=True, **kw)
    for a, b in zip(jax.tree.leaves(p_a), jax.tree.leaves(p_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert h_a.train_cost == h_p.train_cost
    assert h_a.test_accuracy == h_p.test_accuracy


def test_profile_dir_writes_trace(small_setup, tmp_path):
    """The trace covers the whole call: every host phase of the run is
    in it (``jax.profiler.ProfileData``), and tracing changes no result."""
    data, part, kw = small_setup
    prof = tmp_path / "trace"
    p, h = runtime.run_alg1(data, part, pipeline=True,
                            profile_dir=str(prof), **kw)
    assert all(np.isfinite(h.train_cost))
    written = list(prof.rglob("*.xplane.pb"))
    assert len(written) == 1, written
    pd = jax.profiler.ProfileData.from_file(str(written[0]))
    host = {e.name for plane in pd.planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events}
    assert {"engine.run", "engine.schedule", "engine.stage",
            "engine.chunk", "engine.collect"} <= host
    p0, h0 = runtime.run_alg1(data, part, pipeline=True, **kw)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (h.rounds, h.metrics, h.slack) == (h0.rounds, h0.metrics,
                                               h0.slack)


# ---------------------------------------------------------------------------
# chunked ring psum: single-device short-circuit
# ---------------------------------------------------------------------------

def test_ring_psum_single_shard_short_circuit():
    """``num_shards == 1`` must behave exactly like ``lax.psum`` over a
    trivial axis (identity) for every dtype."""
    from repro.kernels import ops as kops
    tree = {"a": jnp.arange(13, dtype=jnp.int32),
            "b": jnp.linspace(0.0, 1.0, 7, dtype=jnp.float32)}

    def f(t):
        return kops.ring_psum_chunked(t, "x", num_shards=1, chunks=4)

    out = jax.vmap(f, axis_name="x")(jax.tree.map(lambda v: v[None], tree))
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out[k][0]),
                                      np.asarray(tree[k]))


# ---------------------------------------------------------------------------
# engine-level pinned A/Bs (subprocess — see pipeline_engine_check.py)
# ---------------------------------------------------------------------------

def _run_check(args):
    from _subprocess import run_check
    run_check("pipeline_engine_check.py", *args, marker="PIPELINE_CHECK_OK",
              timeout=1800)


def test_pipeline_bit_identity_single_device():
    """pipeline=True == async τ≡1, bitwise, for the plain / secure /
    top-k+secure / sketched / FedAvg-mean / hierarchical paths on one
    device (plus the pipeline+staleness rejection)."""
    _run_check([])


@pytest.mark.slow
def test_pipeline_bit_identity_client_mesh():
    """Same on a 2-virtual-device mesh — where the consume runs the
    chunked ppermute ring — plus the sentinel-padded S=5 cohort, the
    replicated-arena variant, and the direct ring == psum bitwise
    unit check."""
    _run_check(["--mesh"])
