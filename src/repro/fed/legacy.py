"""The seed's per-round Python drivers, preserved verbatim-in-spirit.

These are the pre-engine loops: one jitted call and one host-side batch
gather per round.  They are kept as the numerical reference for the
scan-chunked engine — ``tests/test_engine.py`` asserts paired-seed
trajectory equality.  New code should use :mod:`repro.fed.engine` via
the :mod:`repro.fed.runtime` wrappers.

Note on determinism: these drivers draw batches through the current
(vectorized) :func:`repro.data.partition.sample_minibatches`, whose
stream is seed-stable but *not* bit-identical to the seed commit's
per-client ``SeedSequence`` draws — so engine↔legacy comparisons pair
exactly, while trajectories recorded before the sampler change differ
in their mini-batch realizations (same distribution, same convergence
claims).
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import constrained, fedavg, ssca
from repro.core.schedules import paper_schedules, sgd_learning_rate
from repro.data.partition import Partition, sample_minibatches
from repro.fed import engine
from repro.fed.engine import History, record
from repro.fed.tasks.mlp import MLPTask
from repro.mlpapp import model as mlp


def evaluator(data, eval_samples: int):
    """MLP-task probe under the seed drivers' call signature (the engine's
    evaluator is task-parametric; these drivers are MLP-only by design).
    Metric dims only enter through the params, so the default task shares
    the compiled probe with the runtime's MLP path."""
    return engine.evaluator(MLPTask(), data, eval_samples)


def _round_batch(data, part: Partition, batch_size: int, t: int, seed: int):
    """Gather every client's mini-batch into one weighted super-batch."""
    idx = sample_minibatches(part, batch_size, t, seed)      # (I, B)
    flat = idx.reshape(-1)
    x = jnp.asarray(data.x_train[flat])
    y = jnp.asarray(data.y_train[flat])
    w = np.repeat(part.weights(batch_size), batch_size)      # N_i/(B·N) each
    return x, y, jnp.asarray(w)


def _weighted_ce_sum(params, batch):
    """Σ_n w_n · ce_n — so grad = ĝ^t of eq. (2) with exact paper weights."""
    x, y, w = batch
    logp = jax.nn.log_softmax(mlp.logits(params, x), axis=-1)
    return -jnp.sum(w * jnp.sum(y * logp, axis=-1))


def run_alg1(data, part: Partition, *, batch_size: int, rounds: int,
             lam: float = 1e-5, tau: float = 0.1, seed: int = 0,
             params: Optional[mlp.MLPParams] = None,
             hidden: int = 128, eval_every: int = 1,
             eval_samples: int = 10000) -> tuple[mlp.MLPParams, History]:
    """Algorithm 1 on the eq.-(11) objective, one dispatch per round."""
    k, l = data.x_train.shape[1], data.y_train.shape[1]
    if params is None:
        params = mlp.init_params(jax.random.key(seed), k, hidden, l)
    rho, gamma = paper_schedules(batch_size)
    hp = ssca.SSCAHyperParams(tau=tau, lam=lam, rho=rho, gamma=gamma)
    one_round = jax.jit(ssca.round_fn(_weighted_ce_sum, hp))

    state = ssca.init(params)
    measure = evaluator(data, eval_samples)
    hist = History()
    t0 = time.perf_counter()
    for t in range(1, rounds + 1):
        batch = _round_batch(data, part, batch_size, t, seed)
        params, state = one_round(params, state, batch)
        if t % eval_every == 0 or t == rounds:
            record(hist, t, measure, params)
    hist.wall_seconds = time.perf_counter() - t0
    return params, hist


def run_alg2(data, part: Partition, *, batch_size: int, rounds: int,
             limit_u: float = 0.13, tau: float = 0.1, c: float = 1e5,
             seed: int = 0, params: Optional[mlp.MLPParams] = None,
             hidden: int = 128, eval_every: int = 1,
             eval_samples: int = 10000) -> tuple[mlp.MLPParams, History]:
    """Algorithm 2 on eq. (18): min ‖ω‖² s.t. F(ω) ≤ U."""
    k, l = data.x_train.shape[1], data.y_train.shape[1]
    if params is None:
        params = mlp.init_params(jax.random.key(seed), k, hidden, l)
    rho, gamma = paper_schedules(batch_size)
    hp = constrained.ConstrainedHyperParams(tau=tau, c=c, rho=rho, gamma=gamma)
    one_round = jax.jit(constrained.round_fn(_weighted_ce_sum, limit_u, hp))
    state = constrained.init(params)
    measure = evaluator(data, eval_samples)
    hist = History()
    t0 = time.perf_counter()
    for t in range(1, rounds + 1):
        batch = _round_batch(data, part, batch_size, t, seed)
        params, state = one_round(params, state, batch)
        if t % eval_every == 0 or t == rounds:
            record(hist, t, measure, params, slack=float(state.slack[0]))
    hist.wall_seconds = time.perf_counter() - t0
    return params, hist


def run_fedsgd(data, part: Partition, *, batch_size: int, rounds: int,
               lam: float = 1e-5, lr_a: float = 0.5, lr_alpha: float = 0.3,
               seed: int = 0, params: Optional[mlp.MLPParams] = None,
               hidden: int = 128, eval_every: int = 1,
               eval_samples: int = 10000) -> tuple[mlp.MLPParams, History]:
    """E = 1 SGD baseline [3],[4] on the same objective as Algorithm 1."""
    k, l = data.x_train.shape[1], data.y_train.shape[1]
    if params is None:
        params = mlp.init_params(jax.random.key(seed), k, hidden, l)

    def loss(p, batch):
        reg = sum(jnp.vdot(w, w) for w in jax.tree.leaves(p)).real
        return _weighted_ce_sum(p, batch) + lam * reg

    hp = fedavg.SGDHyperParams(lr=sgd_learning_rate(lr_a, lr_alpha))
    one_round = jax.jit(fedavg.fedsgd_round(loss, hp))
    measure = evaluator(data, eval_samples)
    hist = History()
    t0 = time.perf_counter()
    for t in range(1, rounds + 1):
        x, y, w = _round_batch(data, part, batch_size, t, seed)
        params = one_round(params, (x, y, w), jnp.float32(t))
        if t % eval_every == 0 or t == rounds:
            record(hist, t, measure, params)
    hist.wall_seconds = time.perf_counter() - t0
    return params, hist


def run_fedavg(data, part: Partition, *, batch_size: int, rounds: int,
               local_steps: int = 2, lam: float = 1e-5, lr_a: float = 0.5,
               lr_alpha: float = 0.3, seed: int = 0,
               params: Optional[mlp.MLPParams] = None, hidden: int = 128,
               eval_every: int = 1,
               eval_samples: int = 10000) -> tuple[mlp.MLPParams, History]:
    """FedAvg [3] / PR-SGD [5]: E local steps per round, then model average.

    Per-client batches are (I, E, B) samples; aggregation weight N_i/N.
    """
    k, l = data.x_train.shape[1], data.y_train.shape[1]
    if params is None:
        params = mlp.init_params(jax.random.key(seed), k, hidden, l)

    def loss(p, batch):
        x, y = batch
        reg = sum(jnp.vdot(w, w) for w in jax.tree.leaves(p)).real
        return mlp.cross_entropy(p, (x, y)) + lam * reg

    hp = fedavg.SGDHyperParams(lr=sgd_learning_rate(lr_a, lr_alpha),
                               local_steps=local_steps)
    one_round = jax.jit(fedavg.fedavg_round(loss, hp))
    cw = jnp.asarray(part.sizes / part.total, jnp.float32)
    measure = evaluator(data, eval_samples)
    hist = History()
    t0 = time.perf_counter()
    for t in range(1, rounds + 1):
        xs, ys = [], []
        for e in range(local_steps):
            idx = sample_minibatches(part, batch_size,
                                     t * 1000 + e, seed)     # (I, B)
            xs.append(data.x_train[idx])
            ys.append(data.y_train[idx])
        xb = jnp.asarray(np.stack(xs, 1))   # (I, E, B, K)
        yb = jnp.asarray(np.stack(ys, 1))
        params = one_round(params, (xb, yb), cw, jnp.float32(t))
        if t % eval_every == 0 or t == rounds:
            record(hist, t, measure, params)
    hist.wall_seconds = time.perf_counter() - t0
    return params, hist
