"""Single-host federated simulation runtime (the paper's experimental rig).

Simulates the server + I clients of Section II for **any**
:class:`repro.fed.tasks.base.FedTask` — the paper's MNIST MLP (the
default, for back-compat with the seed-era call signatures), a reduced
decoder-only LM, RWKV-6, or any user task.  All four algorithms of
Section VI are thin wrappers over :func:`run`: each builds one
:class:`repro.core.protocol.FedAlgorithm` from the *task's* loss and
hands it to the unified scan-chunked driver in :mod:`repro.fed.engine`,
composed with any :mod:`repro.fed.aggregation` strategy and any
:mod:`repro.fed.compression` compressor:

* Algorithm 1 (mini-batch SSCA, unconstrained)      — ``run_alg1``
* Algorithm 2 (mini-batch SSCA, constrained)        — ``run_alg2``
* FedSGD / SGD with E=1 [3],[4]                     — ``run_fedsgd``
* FedAvg / parallel-restarted SGD with E>1 [3],[5]  — ``run_fedavg``

Every runner accepts ``task=`` (``None`` ⇒ the MLP task, with its
hidden width taken from the legacy ``hidden=`` kwarg and input/label
dims inferred from the data) plus ``aggregation=`` / ``compressor=`` /
``mesh=``, so secure aggregation, partial participation, compressed
uploads and client-mesh sharding work for all four algorithms × all
tasks — including secure Algorithm 2, per the paper's §III-B.  The
engine underneath is cohort-native: with a partial-participation
strategy (``aggregation.sampled(S)`` / ``secure(num_sampled=S)``) every
per-round cost — batch gathers, uploads, masking, mesh shards, wire
bytes — is O(S) in the cohort, so ``I=10_000, S=8`` runs at the cost of
a 8-client round on the same hardware (see
:mod:`repro.fed.engine` and the README's "Scaling the client
population").

The mini-batch schedule is shared across algorithms (same seed ⇒ same
sample draws) so convergence comparisons are paired.  The seed's
per-round drivers live on in :mod:`repro.fed.legacy` as the numerical
reference.
"""
from __future__ import annotations

from typing import Optional

from repro.core import constrained, fedavg, protocol, ssca
from repro.core.schedules import paper_schedules, sgd_learning_rate
from repro.data.partition import Partition
from repro.fed import aggregation as agg_mod
from repro.fed import engine
from repro.fed.engine import History  # noqa: F401  (public re-export)
# Back-compat: the seed exposed these here; tests import them.
from repro.fed.legacy import _round_batch, _weighted_ce_sum  # noqa: F401
from repro.fed.tasks.base import FedTask, LocalObjective, SumLoss
from repro.fed.tasks.mlp import MLPTask

_evaluator = engine.evaluator   # back-compat alias


def _resolve_task(task: Optional[FedTask], data, hidden: int) -> FedTask:
    """``task=None`` keeps the seed-era contract: the paper's MLP with
    input/label widths read off the data and the ``hidden=`` kwarg."""
    if task is not None:
        return task
    k, l = data.x_train.shape[1], data.y_train.shape[1]
    return MLPTask(k=k, hidden=hidden, l=l)


def _resolve_aggregation(aggregation, secure: bool):
    """``secure=True`` is shorthand for ``aggregation=secure()``; passing
    both is ambiguous and refused rather than silently dropping one."""
    if secure and aggregation is not None:
        raise ValueError(
            "pass either secure=True or an explicit aggregation=, not both")
    return agg_mod.secure() if secure else aggregation


def run(task: FedTask, algorithm: protocol.FedAlgorithm, data,
        part: Partition, *, batch_size: int, rounds: int, params=None,
        seed: int = 0, eval_every: int = 1, eval_samples: int = 10000,
        aggregation: Optional[agg_mod.Aggregation] = None,
        compressor=None, mesh=None, staleness=None,
        staleness_trace=None, arena=None,
        profile_dir=None) -> tuple:
    """The generic task × algorithm entry all four wrappers reduce to.

    ``params=None`` initializes from ``task.init_params(key(seed))``
    (in :func:`engine.run`).  ``arena=`` ("sharded" — the mesh default —
    or "replicated") places the population-resident (I, …) state; see
    :func:`repro.fed.engine.run`.
    """
    return engine.run(algorithm, data, part, task=task,
                      batch_size=batch_size, rounds=rounds, params=params,
                      seed=seed, eval_every=eval_every,
                      eval_samples=eval_samples, aggregation=aggregation,
                      compressor=compressor, mesh=mesh,
                      staleness=staleness,
                      staleness_trace=staleness_trace, arena=arena,
                      profile_dir=profile_dir)


def run_alg1(data, part: Partition, *, batch_size: int, rounds: int,
             lam: float = 1e-5, tau: float = 0.1, seed: int = 0,
             params=None, task: Optional[FedTask] = None,
             hidden: int = 128, eval_every: int = 1,
             eval_samples: int = 10000, secure: bool = False,
             fused: bool = False,
             aggregation: Optional[agg_mod.Aggregation] = None,
             compressor=None, mesh=None, staleness=None,
             staleness_trace=None, arena=None,
             profile_dir=None) -> tuple:
    """Algorithm 1 on the eq.-(11) objective F(ω) + λ‖ω‖².

    ``secure=True`` is shorthand for ``aggregation=aggregation.secure()``
    (Bonawitz-style pairwise masking in Z_{2^32} — the server only ever
    sees Σ_i q_i).  ``fused=True`` runs the server update through the
    Pallas fused kernel.
    """
    task = _resolve_task(task, data, hidden)
    rho, gamma = paper_schedules(batch_size)
    hp = ssca.SSCAHyperParams(tau=tau, lam=lam, rho=rho, gamma=gamma)
    alg = protocol.SSCAUnconstrained(loss_fn=SumLoss(task), hp=hp,
                                     fused=fused)
    aggregation = _resolve_aggregation(aggregation, secure)
    return run(task, alg, data, part, batch_size=batch_size, rounds=rounds,
               params=params, seed=seed, eval_every=eval_every,
               eval_samples=eval_samples, aggregation=aggregation,
               compressor=compressor, mesh=mesh, staleness=staleness,
               staleness_trace=staleness_trace, arena=arena,
               profile_dir=profile_dir)


def run_alg2(data, part: Partition, *, batch_size: int, rounds: int,
             limit_u: float = 0.13, tau: float = 0.1, c: float = 1e5,
             seed: int = 0, params=None, task: Optional[FedTask] = None,
             hidden: int = 128, eval_every: int = 1,
             eval_samples: int = 10000, secure: bool = False,
             aggregation: Optional[agg_mod.Aggregation] = None,
             compressor=None, mesh=None, staleness=None,
             staleness_trace=None, arena=None,
             profile_dir=None) -> tuple:
    """Algorithm 2 on eq. (18): min ‖ω‖² s.t. F(ω) ≤ U.

    ``secure=True`` masks the (value, gradient) upload q1 — the secure
    constrained variant the paper's §III-B requires."""
    task = _resolve_task(task, data, hidden)
    rho, gamma = paper_schedules(batch_size)
    hp = constrained.ConstrainedHyperParams(tau=tau, c=c, rho=rho,
                                            gamma=gamma)
    alg = protocol.SSCAConstrained(cost_fn=SumLoss(task),
                                   limit_u=limit_u, hp=hp)
    aggregation = _resolve_aggregation(aggregation, secure)
    return run(task, alg, data, part, batch_size=batch_size, rounds=rounds,
               params=params, seed=seed, eval_every=eval_every,
               eval_samples=eval_samples, aggregation=aggregation,
               compressor=compressor, mesh=mesh, staleness=staleness,
               staleness_trace=staleness_trace, arena=arena,
               profile_dir=profile_dir)


def run_fedsgd(data, part: Partition, *, batch_size: int, rounds: int,
               lam: float = 1e-5, lr_a: float = 0.5, lr_alpha: float = 0.3,
               seed: int = 0, params=None, task: Optional[FedTask] = None,
               hidden: int = 128, eval_every: int = 1,
               eval_samples: int = 10000,
               aggregation: Optional[agg_mod.Aggregation] = None,
               compressor=None, mesh=None, staleness=None,
               staleness_trace=None, arena=None,
               profile_dir=None) -> tuple:
    """E = 1 SGD baseline [3],[4] on the same objective as Algorithm 1."""
    task = _resolve_task(task, data, hidden)
    hp = fedavg.SGDHyperParams(lr=sgd_learning_rate(lr_a, lr_alpha))
    alg = protocol.FedSGD(loss_fn=SumLoss(task), hp=hp, lam=lam)
    return run(task, alg, data, part, batch_size=batch_size, rounds=rounds,
               params=params, seed=seed, eval_every=eval_every,
               eval_samples=eval_samples, aggregation=aggregation,
               compressor=compressor, mesh=mesh, staleness=staleness,
               staleness_trace=staleness_trace, arena=arena,
               profile_dir=profile_dir)


def run_fedavg(data, part: Partition, *, batch_size: int, rounds: int,
               local_steps: int = 2, lam: float = 1e-5, lr_a: float = 0.5,
               lr_alpha: float = 0.3, seed: int = 0,
               params=None, task: Optional[FedTask] = None,
               hidden: int = 128, eval_every: int = 1,
               eval_samples: int = 10000,
               aggregation: Optional[agg_mod.Aggregation] = None,
               compressor=None, mesh=None, staleness=None,
               staleness_trace=None, arena=None,
               profile_dir=None) -> tuple:
    """FedAvg [3] / PR-SGD [5]: E local steps per round, then model average.

    Per-client batches are (I, E, B) samples; aggregation weight N_i/N.
    The local objective is the task's mean loss + λ‖ω‖²
    (:class:`repro.fed.tasks.base.LocalObjective` — a frozen dataclass,
    so equal ``run_fedavg`` calls build equal algorithm instances and
    the engine reuses one compiled chunk across runs).
    """
    task = _resolve_task(task, data, hidden)
    hp = fedavg.SGDHyperParams(lr=sgd_learning_rate(lr_a, lr_alpha),
                               local_steps=local_steps)
    alg = protocol.FedAvg(loss_fn=LocalObjective(task, lam), hp=hp)
    return run(task, alg, data, part, batch_size=batch_size, rounds=rounds,
               params=params, seed=seed, eval_every=eval_every,
               eval_samples=eval_samples, aggregation=aggregation,
               compressor=compressor, mesh=mesh, staleness=staleness,
               staleness_trace=staleness_trace, arena=arena,
               profile_dir=profile_dir)
