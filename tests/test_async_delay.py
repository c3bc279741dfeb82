"""What the async mode computes at τ ≡ 1: a server one round late.

``staleness=StalenessConfig(max_staleness=1, schedule=ConstantDiscount())``
with an all-ones ``staleness_trace`` makes every cohort member upload
against the params of the round before: round t's uploads are computed
at ω^{t−1} (ω^0, the initial params, in round 1) and ``server_step``
applies their combine to ω^t.  A plain host loop replays that from the
run's own ``build_schedule`` cohorts and batches, combining the members'
uploads by a plain float64 weighted sum.  It calls only the algorithm's
``client_upload`` / ``server_step`` and the compressor's per-member
``compress``: what is under test is the ring's delay, not those.

The same loop without the delay (uploads at ω^t) must miss the
tolerance, so the test sees the delay and not only that training runs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fedavg, protocol, ssca
from repro.core.schedules import paper_schedules, sgd_learning_rate
from repro.data import partition, synthetic
from repro.fed import aggregation, compression, engine, runtime
from repro.fed.staleness import ConstantDiscount, StalenessConfig
from repro.fed.tasks.base import LocalObjective, SumLoss
from repro.fed.tasks.mlp import MLPTask

CLIENTS, BATCH, ROUNDS, SEED = 8, 5, 4, 2
TAU1 = StalenessConfig(max_staleness=1, schedule=ConstantDiscount())


def _alg1(task):
    rho, gamma = paper_schedules(BATCH)
    hp = ssca.SSCAHyperParams(tau=0.1, lam=1e-5, rho=rho, gamma=gamma)
    return protocol.SSCAUnconstrained(loss_fn=SumLoss(task), hp=hp)


def _fedavg(task):
    hp = fedavg.SGDHyperParams(lr=sgd_learning_rate(0.5, 0.3),
                               local_steps=2)
    return protocol.FedAvg(loss_fn=LocalObjective(task, 1e-5), hp=hp)


# (algorithm, aggregation, compressor).  top-k quantizes its kept values
# to 8 bits on a power-of-two lattice that refines the secure grid, so
# the masked sum of its uploads is exact.
CASES = {
    "plain": (_alg1, None, None),
    "secure": (_alg1, aggregation.secure(), None),
    "fedavg-mean": (_fedavg, None, None),
    "hier-secure": (_alg1, aggregation.hierarchical(aggregation.secure(),
                                                    groups=2), None),
    "topk-secure": (_alg1, aggregation.secure(),
                    compression.topk(0.25, bits=8)),
}


@pytest.fixture(scope="module")
def setup():
    data = synthetic.classification_dataset(n_train=400, n_test=100, seed=0)
    part = partition.iid(400, CLIENTS, seed=0)
    task = MLPTask(k=data.x_train.shape[1], hidden=16,
                   l=data.y_train.shape[1])
    return data, part, task


def _replay(data, part, alg, agg, compressor, params0, delay):
    """The final params of ``ROUNDS`` rounds whose uploads are computed
    ``delay`` rounds late (0 or 1), at the params and state of the round
    they computed at."""
    cohorts, idx = engine.build_schedule(
        part, BATCH, ROUNDS, alg.local_steps, SEED,
        e_axis=alg.combine == "mean", cohort_size=CLIENTS,
        groups=getattr(agg, "groups", None))
    lam = np.asarray(alg.client_weights(part, BATCH), np.float32)
    upload = jax.jit(alg.client_upload)
    step = jax.jit(alg.server_step)
    compress = None if compressor is None else jax.jit(compressor.compress)
    params, state = params0, alg.init_state(params0)
    before = (params, state)             # ω^0: round 1 has no older params
    resid = {}
    for t in range(1, ROUNDS + 1):
        at_p, at_s = before if delay else (params, state)
        words = jax.random.key_data(jax.random.fold_in(
            jax.random.key(SEED + 10_000), t)).reshape(-1)
        k0, k1 = words[0].astype(jnp.uint32), words[-1].astype(jnp.uint32)
        total = None
        for i, c in enumerate(cohorts[t - 1]):
            rows = idx[t - 1, i]
            x, y = data.x_train[rows], data.y_train[rows]
            if alg.combine == "sum":     # λ_i rides in the sample weights
                msg = upload(at_p, at_s,
                             (x, y, np.full(rows.shape, lam[c], np.float32)))
            else:                        # a model, weighted afterwards
                msg = jax.tree.map(lambda m: lam[c] * m,
                                   upload(at_p, at_s, (x, y)))
            if compress is not None:
                r = resid.get(int(c), jax.tree.map(jnp.zeros_like, msg))
                msg, resid[int(c)] = compress(msg, r, k0, k1, jnp.uint32(c))
            msg = jax.tree.map(lambda m: np.asarray(m, np.float64), msg)
            total = msg if total is None else jax.tree.map(np.add, total, msg)
        before = (params, state)
        params, state = step(params, state,
                             jax.tree.map(jnp.float32, total))
    return params


def _max_diff(a, b):
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _tolerance(alg, agg, params):
    bits = getattr(agg, "scale_bits", None)
    if bits is None:
        # float32 reassociation: the engine takes the cohort's uploads as
        # one super-batch gradient or a vmap, the replay member by member
        # and summed in float64; that moves a few ulps a round, and T = 4
        # rounds stay well inside 64 ulps of the largest parameter
        scale = max(float(np.max(np.abs(np.asarray(p))))
                    for p in jax.tree.leaves(params))
        return 64 * np.finfo(np.float32).eps * scale
    # the secure grid: the masked sum rounds each of the S uploads onto
    # the 2^-scale_bits grid, so a round's combine is off the plain sum
    # by at most S·2^-scale_bits an entry.  SSCA takes that into lin
    # with weight ρ_t ≤ 1 and moves ω by convex steps towards
    # ω̄ = −lin/(2τ), so T rounds leave ω within T/(2τ)·S·2^-scale_bits
    return ROUNDS / (2 * alg.hp.tau) * CLIENTS * 2.0 ** -int(bits)


@pytest.mark.parametrize("case", list(CASES))
def test_async_tau1_matches_delayed_reference(setup, case):
    data, part, task = setup
    make, agg, compressor = CASES[case]
    alg = make(task)
    params0 = task.init_params(jax.random.key(SEED))
    got, _ = runtime.run(task, alg, data, part, batch_size=BATCH,
                         rounds=ROUNDS, params=params0, seed=SEED,
                         eval_every=ROUNDS, eval_samples=100,
                         aggregation=agg, compressor=compressor,
                         staleness=TAU1,
                         staleness_trace=np.ones((ROUNDS, CLIENTS),
                                                 np.int64))
    tol = _tolerance(alg, agg, got)
    delayed = _max_diff(got, _replay(data, part, alg, agg, compressor,
                                     params0, delay=1))
    undelayed = _max_diff(got, _replay(data, part, alg, agg, compressor,
                                       params0, delay=0))
    assert delayed <= tol, (case, delayed, tol)
    assert undelayed > tol, (case, undelayed, tol)
