"""The ``FedAlgorithm`` protocol — one interface for all four algorithms.

The journal extension of the source paper (arXiv:2104.06011) treats the
sample-based and feature-based SSCA variants as one family behind a shared
surrogate-update interface, and the underlying CSSCA framework
(arXiv:1801.08266) is agnostic to how the stochastic estimate is
aggregated.  This module encodes both facts structurally: every federated
algorithm is a triple

    init_state(params)                  -> state            (server side)
    client_upload(params, state, batch) -> message          (per client)
    server_step(params, state, agg)     -> (params, state)  (server side)

where ``agg`` is the *aggregated* client message — produced by any
strategy from :mod:`repro.fed.aggregation` (plain sum, secure masking,
partial participation).  The generic driver in :mod:`repro.fed.engine`
runs any ``FedAlgorithm`` × any aggregation as one ``lax.scan`` over
rounds.

Algorithms are **model-agnostic**: each constructor takes its loss as a
callable — in practice a :class:`repro.fed.tasks.base.SumLoss` view of a
:class:`repro.fed.tasks.base.FedTask` (sum-combine) or a
:class:`repro.fed.tasks.base.LocalObjective` (mean-combine) — so the
same four implementations train the paper's MLP, a reduced transformer,
or RWKV-6 unchanged.  Loss callables must be hashable and compare equal
when built from equal tasks (the frozen-dataclass wrappers are; raw
bound methods are *not* — CPython compares ``__self__`` by identity):
the engine's compiled-chunk cache keys on the algorithm instance.

Aggregation semantics are declared, not hard-coded:

* ``combine = "sum"`` — the upload is a per-sample-weighted statistic
  (the mini-batch gradient of Σ_n w_n ℓ_n); ``batch`` is ``(x, y, w)``
  with ``w`` the eq.-(2) weights N_i/(B·N).  The upload map must be
  *additive in the batch*:

      upload(batch_i ⊎ batch_j) == upload(batch_i) + upload(batch_j)

  This lets the engine evaluate linear aggregations (plain, sampled)
  directly on the concatenated weighted super-batch — one gradient, no
  per-client message tensors — while non-linear strategies (secure
  masking) call ``client_upload`` per client on its own (x, y, λ_i·1)
  slice and combine the explicit messages.  Both paths compute the same
  aggregate.
* ``combine = "mean"`` — messages are per-client *models* (FedAvg);
  ``batch`` is ``(x, y)`` and the aggregator forms a weighted average
  with λ_i = N_i/N, re-normalized over the participating subset.

All methods must be jit/vmap/scan-compatible: ``state`` is a pytree of
arrays, ``client_upload`` is vmapped over the leading client axis of
``batch``, and ``server_step`` runs inside the scan body.

**Delayed uploads** (the async engine's bounded-staleness mode): a
client that computed at round t−τ uploads against the *params of that
round* — the engine gathers them from a ring buffer of recent
snapshots and calls ``client_upload`` with the historical params.  The
protocol addition is :meth:`FedAlgorithm.client_state`: the slice of
server state a client's upload actually reads, which must be
snapshotted alongside params for the replay to be faithful.  Sum-
combine algorithms here upload pure gradients of (params, batch) — the
state argument is ignored — so the default is the empty tuple and the
ring carries params only; FedAvg's local SGD reads the round counter
(its lr schedule), so it returns the full ``CounterState``.  The
aggregated estimate a delayed cohort produces is exactly the CSSCA
delayed-information regime (arXiv 1801.08266 §V): the surrogate
recursion contracts bounded-delay perturbations, no algorithm change
needed.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import constrained, fedavg, ssca

PyTree = Any


class UploadSpec(NamedTuple):
    """Wire metadata of one client upload: how many elements the message
    carries, across how many pytree leaves, at what element width.  The
    communication ledger (:mod:`repro.fed.compression`) turns this into
    exact bytes per round for any compressor × aggregation combination.
    """
    elements: int       # scalar entries in the message pytree
    leaves: int         # leaf count (per-leaf scale/exponent overhead)
    elem_bytes: int     # dense wire width of one element


@runtime_checkable
class FedAlgorithm(Protocol):
    """Structural interface consumed by :func:`repro.fed.engine.run`.

    Uploads may pass through a :mod:`repro.fed.compression` strategy
    before aggregation; a stateful compressor's per-client residual (the
    error-feedback slot) is threaded by the engine as an extra scan-carry
    element alongside ``state``, sharded over the client mesh.
    """

    combine: str        # "sum" | "mean"
    local_steps: int    # E — mini-batches per client per round

    def init_state(self, params: PyTree) -> PyTree: ...

    def client_upload(self, params: PyTree, state: PyTree,
                      batch: Any) -> PyTree: ...

    def client_state(self, state: PyTree) -> PyTree: ...

    def server_step(self, params: PyTree, state: PyTree,
                    agg: PyTree) -> tuple[PyTree, PyTree]: ...

    def client_weights(self, part, batch_size: int) -> np.ndarray: ...

    # values may be device scalars — the engine defers the host read
    # (one batched device_get after the timed loop), float()-ing at
    # History-fill time
    def round_metrics(self, state: PyTree) -> Dict[str, Any]: ...

    def upload_spec(self, params: PyTree) -> UploadSpec: ...


def _param_count(params: PyTree) -> int:
    return sum(int(np.prod(w.shape)) for w in jax.tree.leaves(params))


class _Base:
    """Shared defaults: E=1, sum-combine with eq.-(2) weights, a dense
    float32 model-shaped upload."""

    combine = "sum"
    local_steps = 1
    upload_dtype = jnp.float32

    def client_weights(self, part, batch_size: int) -> np.ndarray:
        return part.weights(batch_size)            # N_i / (B·N)

    def client_state(self, state) -> PyTree:
        """The state slice ``client_upload`` reads — what the async
        engine must snapshot in its staleness ring buffer next to the
        params.  Sum-combine uploads here are pure functions of (params,
        batch): nothing to snapshot.  If this returns non-empty, it must
        be a pytree ``client_upload`` accepts *as its state argument*
        (the engine replays the upload with the historical snapshot in
        place of the live state)."""
        del state
        return ()

    def round_metrics(self, state) -> Dict[str, float]:
        return {}

    def upload_spec(self, params) -> UploadSpec:
        return UploadSpec(
            elements=_param_count(params),
            leaves=len(jax.tree.leaves(params)),
            elem_bytes=jnp.dtype(self.upload_dtype).itemsize)


class CounterState(NamedTuple):
    """State of the stateless SGD baselines: just the round counter t."""
    step: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class SSCAUnconstrained(_Base):
    """Algorithm 1 (mini-batch SSCA, unconstrained) behind the protocol.

    ``loss_fn(params, (x, y, w))`` is the per-sample-weighted batch sum
    Σ_n w_n ℓ_n, so its gradient on the weighted super-batch is exactly
    ĝ^t of eq. (2) — and the per-client gradient (w = λ_i) is the secure
    upload q0.

    ``fused=True`` routes the server update through the Pallas fused
    kernel (:mod:`repro.kernels.ssca_update`); the tree-map path is the
    fallback and the numerical reference.
    """
    loss_fn: Callable[[PyTree, Any], jnp.ndarray]
    hp: ssca.SSCAHyperParams
    fused: bool = False

    def init_state(self, params):
        # β (recursion (13)) only enters the step through 2λβ: a λ = 0
        # objective carries none
        return ssca.init(params, with_beta=bool(self.hp.lam))

    def client_upload(self, params, state, batch):
        return jax.grad(self.loss_fn)(params, batch)

    def server_step(self, params, state, agg):
        return ssca.server_update(state, params, agg, self.hp,
                                  fused=self.fused)


@dataclasses.dataclass(frozen=True)
class SSCAConstrained(_Base):
    """Algorithm 2 (constrained, exact penalty) behind the protocol.

    The upload is q1 = (mini-batch cost value, gradient); the objective
    ‖ω‖² is known to the server, so q0 needs no upload (paper §V-B).
    Secure aggregation of this tuple is what the paper's §III-B requires
    and the seed omitted: both the value and the gradient are masked.
    """
    cost_fn: Callable[[PyTree, Any], jnp.ndarray]   # weighted batch sum
    limit_u: float
    hp: constrained.ConstrainedHyperParams

    def init_state(self, params):
        return constrained.init(params, num_constraints=1)

    def client_upload(self, params, state, batch):
        return jax.value_and_grad(self.cost_fn)(params, batch)

    def server_step(self, params, state, agg):
        val, grad = agg
        t = state.step.astype(jnp.float32)
        rho, gamma = self.hp.rho(t), self.hp.gamma(t)
        grads = jax.tree.map(lambda g: g[None], grad)        # stack M=1
        state = constrained.update_constraint_surrogate(
            state, params, jnp.reshape(val, (1,)), grads, self.hp.tau, rho)
        lin1 = jax.tree.map(lambda l: l[0], state.lin_c)
        omega_bar, s, _ = constrained.solve_lemma1(
            lin1, state.a_c[0], self.limit_u, self.hp.tau, self.hp.c)
        new_params = jax.tree.map(
            lambda w, wb: (1.0 - gamma) * w + gamma * wb, params, omega_bar)
        new_state = state._replace(step=state.step + 1, slack=s[None])
        return new_params, new_state

    def round_metrics(self, state):
        # a *device* scalar, not float(): the engine batches all metric
        # reads into one device_get after the timed loop, so a per-round
        # host sync here would put eval transfer latency back inside the
        # wall-clock
        return {"slack": state.slack[0]}

    def upload_spec(self, params) -> UploadSpec:
        return UploadSpec(                                   # + the value
            elements=_param_count(params) + 1,
            leaves=len(jax.tree.leaves(params)) + 1,
            elem_bytes=jnp.dtype(self.upload_dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class FedSGD(_Base):
    """E = 1 SGD baseline [3],[4] on F(ω) + λ‖ω‖².

    The ℓ2 term is server-side (its gradient 2λω needs no data), so the
    client upload is the plain weighted mini-batch gradient — identical
    uplink to Algorithm 1.
    """
    loss_fn: Callable[[PyTree, Any], jnp.ndarray]   # weighted batch sum
    hp: fedavg.SGDHyperParams
    lam: float = 0.0

    def init_state(self, params):
        return CounterState(step=jnp.asarray(1, jnp.int32))

    def client_upload(self, params, state, batch):
        return jax.grad(self.loss_fn)(params, batch)

    def server_step(self, params, state, agg):
        lr = self.hp.lr(state.step.astype(jnp.float32))
        g = jax.tree.map(lambda gg, w: gg + 2.0 * self.lam * w, agg, params)
        new_params = jax.tree.map(lambda w, gg: w - lr * gg, params, g)
        return new_params, CounterState(step=state.step + 1)


@dataclasses.dataclass(frozen=True)
class FedAvg(_Base):
    """FedAvg [3] / parallel-restarted SGD [5]: E local steps, model avg.

    The upload is the locally-updated *model*; ``combine="mean"`` tells the
    aggregation layer to average with λ_i = N_i/N (re-normalized over the
    sampled subset under partial participation — standard FedAvg client
    sampling).
    """
    loss_fn: Callable[[PyTree, Any], jnp.ndarray]   # local objective (mean)
    hp: fedavg.SGDHyperParams

    combine = "mean"

    @property
    def local_steps(self) -> int:
        return int(self.hp.local_steps)

    def init_state(self, params):
        return CounterState(step=jnp.asarray(1, jnp.int32))

    def client_upload(self, params, state, batch):
        lr = self.hp.lr(state.step.astype(jnp.float32))
        return fedavg.local_sgd(self.loss_fn, self.hp)(params, batch, lr)

    def client_state(self, state):
        # local SGD reads the round counter (lr schedule): a delayed
        # client must replay with the lr of the round it computed at
        return state

    def server_step(self, params, state, agg):
        return agg, CounterState(step=state.step + 1)

    def client_weights(self, part, batch_size: int) -> np.ndarray:
        return (part.sizes / part.total).astype(np.float32)  # N_i / N
