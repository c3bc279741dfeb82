"""Algorithm 1 of the paper, replayed by the plain float32 reference.

A traffic mix whose ``algorithm.name`` is ``alg1`` runs the program's
``repro.fed.runtime.run_alg1`` with the mix's algorithm arguments, and is
replayed here: the same cohorts and mini-batches (:mod:`reference`'s
copies of the program's draw streams), the eq.-(2) client weights, a
plain weighted sum where the program aggregates, and the SSCA recursions
(14)/(15), the closed form (16)/(17) and the move (4) of the paper.

A plain sum is what ``plain``, ``sampled`` and ``secure`` aggregation
compute, the last to its fixed-point grid; any other aggregation, a
compressor or an engine option changes the mathematics and needs a
replay of its own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference

EXACT_SUMS = ("plain", "sampled", "secure")


def replayable(traffic: dict):
    """Raise unless this module replays what ``traffic`` runs."""
    kind = traffic["aggregation"]["kind"]
    if kind not in EXACT_SUMS:
        raise ValueError(f"alg1's replay sums plainly; aggregation {kind!r} "
                         "needs a replay of its own")
    extra = set(traffic).intersection(("compressor", "staleness", "engine"))
    if extra:
        raise ValueError(f"alg1's replay has no {sorted(extra)}")


@functools.partial(jax.jit, static_argnames=("tau", "lam"))
def ssca_step(params, lin, beta, grad, rho, gamma, *, tau, lam):
    """(14)/(15), (13), (16)/(17) and (4) on every leaf, in float32."""
    lin = jax.tree.map(lambda l, g, w: (1 - rho) * l + rho * (g - 2 * tau * w),
                       lin, grad, params)
    beta = jax.tree.map(lambda b, w: (1 - rho) * b + rho * w, beta, params)
    bar = jax.tree.map(lambda l, b: -(l + 2 * lam * b) / (2 * tau), lin, beta)
    params = jax.tree.map(lambda w, wb: (1 - gamma) * w + gamma * wb,
                          params, bar)
    return params, lin, beta


def replay(traffic: dict, params, grad_sum, cost, part, seed: int,
           *, fault: str | None = None):
    """Replay the ``rounds_per_call`` rounds of one call from ``params``.

    ``grad_sum(params, ids, w)`` returns Σ_n w_n ∇ℓ_n over the training
    rows ``ids``; ``cost(params)`` the eval cost the program's probe
    reports.  Returns the final params, the first round's aggregate
    gradient, and ``{round: cost}`` at round 0 and every eval point.
    ``fault`` is planted as :func:`reference.rounds` says.
    """
    alg = traffic["algorithm"]
    rounds, every = traffic["rounds_per_call"], traffic["eval_every"]
    (a1, al1), (a2, al2) = reference.schedules(traffic["batch_size"])
    lin = jax.tree.map(jnp.zeros_like, params)
    beta = jax.tree.map(jnp.zeros_like, params)
    costs = {0: float(cost(params))}
    first_grad = None
    for k, (ids, w) in enumerate(reference.rounds(traffic, part, seed,
                                                  fault=fault)):
        t = np.float32(k + 1)
        g = grad_sum(params, ids, w)
        if first_grad is None:
            first_grad = g
        rho = np.float32(a1) / t ** np.float32(al1)
        gamma = np.float32(a2) / t ** np.float32(al2)
        params, lin, beta = ssca_step(params, lin, beta, g, rho, gamma,
                                      tau=alg["tau"], lam=alg["lam"])
        if (k + 1) % every == 0 or k + 1 == rounds:
            costs[k + 1] = float(cost(params))
    return params, first_grad, costs
