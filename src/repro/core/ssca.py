"""Algorithm 1 — mini-batch SSCA for unconstrained federated optimization.

Generic (pytree) form of the paper's Section III with the canonical surrogate
(6):

    f̄0(ω, ω^t, x) = ∇f0(ω^t, x)ᵀ (ω − ω^t) + τ ‖ω − ω^t‖²

Under (6) the recursively-averaged surrogate (2) is the quadratic

    F̄0^t(ω) = ⟨B^t, ω⟩ + τ‖ω‖²  (+ 2λ ⟨β^t, ω⟩ for an ℓ2-regularized objective)

with the paper's recursions (14)/(15) generalized to one linear-coefficient
pytree ``lin`` shaped like ω:

    lin^t  = (1 − ρ^t) lin^{t−1} + ρ^t (ĝ^t − 2τ ω^t)          # (14)/(15)
    β^t    = (1 − ρ^t) β^{t−1}  + ρ^t ω^t                       # (13)

where ĝ^t = Σ_i (N_i/BN) Σ_{n∈N_i^t} ∇f0(ω^t, x_n) is the aggregated client
message (the upload `q0`).  Problem 2 then has the closed form (16)/(17):

    ω̄^t = −(lin^t + 2λ β^t) / (2τ)

and the iterate moves by (4):  ω^{t+1} = (1 − γ^t) ω^t + γ^t ω̄^t.

Everything here is pure-functional and jit/pjit friendly: the server update
is elementwise over the (sharded) state, so no collectives beyond the
gradient aggregation are introduced.

**Bounded delay.**  Nothing in the recursion requires ĝ^t to be computed
at ω^t: the CSSCA convergence framework (arXiv 1801.08266) only needs
the surrogate error to vanish in the ρ-averaged limit, and a gradient
evaluated at ω^{t−τ} with τ ≤ K perturbs lin^t by O(ρ^t · Σ‖ω^{t−j+1} −
ω^{t−j}‖) — a term the diminishing γ-schedule shrinks and the (1−ρ)
averaging contracts.  This is what the async engine relies on: stale
uploads (from the staleness ring buffer, discounted per
:mod:`repro.fed.staleness`) enter the same recursion unchanged, and an
all-fresh round is bit-identical to the synchronous path.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.schedules import PowerLaw, paper_schedules

PyTree = Any


class SSCAHyperParams(NamedTuple):
    tau: float = 0.1          # strong-convexity constant of (6)
    lam: float = 0.0          # ℓ2 regularization weight λ (eq. 11)
    rho: PowerLaw = PowerLaw(0.9, 0.3)
    gamma: PowerLaw = PowerLaw(0.9, 0.35)


class SSCAState(NamedTuple):
    """Server-side surrogate state (sharded like the parameters)."""

    step: jnp.ndarray  # t, starts at 1
    lin: PyTree        # B^t — EMA of (ĝ − 2τω)
    beta: PyTree       # β^t — EMA of ω (only consumed when λ > 0)


def init(params: PyTree, with_beta: bool = True) -> SSCAState:
    """``with_beta=False`` (λ = 0 objectives) skips the β buffer — saves one
    model-sized state tensor for large-scale LM training."""
    zeros = jax.tree.map(jnp.zeros_like, params)
    beta = jax.tree.map(jnp.zeros_like, params) if with_beta else None
    return SSCAState(step=jnp.asarray(1, jnp.int32), lin=zeros, beta=beta)


def client_message(grad_fn: Callable[[PyTree, Any], PyTree],
                   params: PyTree, batch: Any, weight) -> PyTree:
    """The upload ``q0(ω^t, (x_n))`` for surrogate (6): weighted batch grad.

    ``weight`` is ``N_i / (B N)`` — the paper's aggregation weight, so the
    server-side sum over clients equals ĝ^t in eq. (2).
    """
    g = grad_fn(params, batch)
    return jax.tree.map(lambda x: x * weight, g)


def ema(old: PyTree, new: PyTree, rho) -> PyTree:
    return jax.tree.map(lambda o, n: (1.0 - rho) * o + rho * n, old, new)


def solve_surrogate(state: SSCAState, hp: SSCAHyperParams) -> PyTree:
    """Closed-form minimizer of Problem 2 under surrogate (6): (16)/(17)."""
    two_tau = 2.0 * hp.tau
    if hp.lam:
        return jax.tree.map(
            lambda b, bt: -(b + 2.0 * hp.lam * bt) / two_tau,
            state.lin, state.beta)
    return jax.tree.map(lambda b: -b / two_tau, state.lin)


def server_update(state: SSCAState, params: PyTree, grad_agg: PyTree,
                  hp: SSCAHyperParams, *, fused: bool = False,
                  interpret: Optional[bool] = None
                  ) -> tuple[PyTree, SSCAState]:
    """One server round: recursions (14)/(15), closed form (16)/(17), move (4).

    ``grad_agg`` is the already-aggregated ĝ^t (sum of client messages; under
    pjit this is the psum over the (`pod`,`data`) axes).

    ``fused=True`` runs the whole update as one Pallas elementwise pass
    per leaf (:mod:`repro.kernels.ssca_update`) — one HBM read of
    (ω, lin, ĝ) and one write of (ω', lin') instead of four round-trips,
    plus β's read and write only when λ > 0 (at λ = 0 β is neither
    streamed nor made, and a β the state carries rides through
    unchanged, as on the tree-map path).  ``interpret`` defaults to True
    off-TPU (the kernel's validation mode); both paths compute identical
    math in f32.
    """
    t = state.step.astype(jnp.float32)
    rho = hp.rho(t)
    gamma = hp.gamma(t)

    if fused:
        from repro.kernels import ops
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        if hp.lam and state.beta is None:
            raise ValueError("λ > 0 needs the β state: ssca.init(params, "
                             "with_beta=True)")
        new_params, lin, beta = ops.ssca_update(
            params, state.lin, grad_agg, state.beta if hp.lam else None,
            rho=rho, gamma=gamma, tau=hp.tau, lam=hp.lam,
            interpret=interpret)
        new_state = SSCAState(step=state.step + 1, lin=lin,
                              beta=beta if hp.lam else state.beta)
        return new_params, new_state

    lin = ema(state.lin,
              jax.tree.map(lambda g, w: g - 2.0 * hp.tau * w, grad_agg, params),
              rho)
    beta = ema(state.beta, params, rho) if hp.lam else state.beta
    new_state = SSCAState(step=state.step + 1, lin=lin, beta=beta)

    omega_bar = solve_surrogate(new_state, hp)
    new_params = jax.tree.map(
        lambda w, wb: (1.0 - gamma) * w + gamma * wb, params, omega_bar)
    return new_params, new_state


def round_fn(loss_fn: Callable[[PyTree, Any], jnp.ndarray],
             hp: SSCAHyperParams,
             aggregate: Optional[Callable[[PyTree], PyTree]] = None):
    """Build a jittable one-round function ``(params, state, batch, weight)``.

    ``aggregate`` injects the cross-client reduction (identity on a single
    host where ``batch`` already carries every client's samples; a
    ``lax.psum`` over the data axes under shard_map/pjit).
    """
    grad_fn = jax.grad(loss_fn)

    def one_round(params, state, batch, weight=1.0):
        msg = client_message(grad_fn, params, batch, weight)
        if aggregate is not None:
            msg = aggregate(msg)
        return server_update(state, params, msg, hp)

    return one_round


def surrogate_value(state: SSCAState, hp: SSCAHyperParams,
                    params: PyTree) -> jnp.ndarray:
    """F̄0^t(ω) up to its constant term — used by tests/diagnostics."""
    lin_dot = sum(jnp.vdot(b, w) for b, w in
                  zip(jax.tree.leaves(state.lin), jax.tree.leaves(params)))
    sq = sum(jnp.vdot(w, w) for w in jax.tree.leaves(params))
    val = lin_dot + hp.tau * sq
    if hp.lam:
        beta_dot = sum(jnp.vdot(b, w) for b, w in
                       zip(jax.tree.leaves(state.beta), jax.tree.leaves(params)))
        val = val + 2.0 * hp.lam * beta_dot
    return val


def surrogate_grad(state: SSCAState, hp: SSCAHyperParams,
                   params: PyTree) -> PyTree:
    """∇F̄^t(ω) = lin^t + 2τω (+ 2λβ^t) — used to verify the Theorem-1
    consistency condition ‖∇F̄^t(ω^t) − ∇F(ω^t)‖ → 0 ([11, Lemma 1])."""
    g = jax.tree.map(lambda b, w: b + 2.0 * hp.tau * w, state.lin, params)
    if hp.lam and state.beta is not None:
        g = jax.tree.map(lambda gg, bt: gg + 2.0 * hp.lam * bt,
                         g, state.beta)
    return g


def kkt_residual(grad: PyTree) -> jnp.ndarray:
    """‖∇F0(ω)‖₂ — the unconstrained KKT (stationarity) residual.

    Uses ``sum(g*g)`` per leaf rather than ``vdot`` — vdot's flatten forces
    the SPMD partitioner to all-gather sharded gradients (observed +27 GiB
    on llama3-8b); an axis-less reduction stays shard-local + one scalar
    all-reduce."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grad)))


def default_hparams(batch_size: int, tau: float = 0.1,
                    lam: float = 0.0) -> SSCAHyperParams:
    rho, gamma = paper_schedules(batch_size)
    return SSCAHyperParams(tau=tau, lam=lam, rho=rho, gamma=gamma)
