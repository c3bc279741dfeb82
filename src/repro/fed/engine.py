"""The unified federated driver: one ``lax.scan`` per eval interval.

The engine is **task-agnostic**: it runs any
:class:`repro.core.protocol.FedAlgorithm` (which closes over a
:class:`repro.fed.tasks.base.FedTask`'s loss) with any
:class:`repro.fed.aggregation.Aggregation` strategy and any
:mod:`repro.fed.compression` compressor, over any task's data — the
MNIST MLP, a reduced decoder-only LM, RWKV-6 — as one device-resident
loop.  It is also **cohort-native**: per-round cost is O(S) in the
participating cohort size S, never O(I) in the population — the design
point that lets one process simulate I in the tens of thousands with a
small per-round cohort (the paper's sampled-connected-clients regime):

1. the per-round cohorts (T, S) and their mini-batch index schedule
   (T, S, [E,] B) are drawn up front (one vectorized host call each —
   :func:`repro.data.partition.sample_cohorts` /
   :func:`~repro.data.partition.sample_schedule`) and transferred once;
   nothing (T, I, ·)-shaped is ever materialized;
2. the training arrays live on device; per-round batches are device-side
   gathers of the cohort's indices inside the scan body (tasks declare
   row-indexable ``x_train`` / ``y_train``);
3. rounds between eval points run as one ``lax.scan`` — one XLA dispatch
   per eval interval instead of per round;
4. params, state and compressor state are **donated** to the chunk
   executable (``donate_argnums``), so the scan updates the model in
   place instead of doubling HBM residency per chunk;
5. with ``mesh=`` (a 1-D client mesh from
   :func:`repro.launch.mesh.make_client_mesh`) the round body runs under
   ``shard_map`` over the client axis: **the cohort — not the
   population — is sharded**, so ``I=10_000, S=8`` runs on the same
   2-device mesh as ``I=16``.  Each device owns S/D cohort slots,
   computes their uploads locally, and the server aggregate is one
   ``psum`` — secure aggregation psums *int32 masked fixed-point
   partials*, so the sharded aggregate is bit-identical to the
   single-device one.  When the device count does not divide S, the
   cohort is padded host-side with zero-weight sentinel slots (dropped
   on every write-back), so any (S, device-count) combination runs.
   ``mesh=None`` (default) is the single-device fallback.

There is exactly **one** scan-body builder (:func:`_chunk_fn`).  Per
round the body is:  gather the cohort's (S, [E,] B) client batches →
vmap ``client_upload`` over the S cohort members → [compress per
member, with the error-feedback residual gathered from / scattered back
to a **population-resident (I, …) arena** in the structured scan carry —
see :mod:`repro.fed.compression`] → aggregate (plain / secure /
sampled, over cohort members only) → ``server_step``.  The carry is
:class:`RoundCarry`; the compressor-state slot is the empty pytree
``()`` when no compressor is set, so the uncompressed trace is
numerically untouched.  With S = I the cohort is the identity and
trajectories are bit-identical to the pre-cohort engine (pinned by
``tests/test_task_bitexact.py``).

The body has two modes, chosen when it is traced.  Synchronous (the
default): every upload is computed at the current params.  Async
(``staleness=``): the carry's params slot is a ring of the last K+1
snapshots and each cohort slot uploads against the snapshot of its
trace delay τ.  A one-round-late server — every upload at ω^{t−1} — is
the async mode at ``max_staleness=1`` with a constant discount and an
all-ones trace.

Evaluation happens at chunk boundaries on the host through the task's
jitted metric probe (one compile per task, shared across runs),
recording the task-declared metric schema into :class:`History`.  The
exact wire bytes of every round are recorded in the ledger.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.protocol import FedAlgorithm
from repro.data.partition import (Partition, sample_cohorts,
                                  sample_groups, sample_schedule,
                                  sample_staleness)
from repro.fed import arena as arena_mod
from repro.fed import compression as compression_mod
from repro.fed import staleness as staleness_mod
from repro.fed.aggregation import Aggregation, PlainAggregation
from repro.fed.spans import Spans, scoped

PyTree = Any

_LEGACY_METRICS = ("train_cost", "test_accuracy", "sparsity")


@dataclasses.dataclass
class History:
    """Per-eval-point diagnostics; the benchmarks turn these into figures.

    ``metrics`` maps each **task-declared** metric name to its
    per-eval-point series (aligned with ``rounds``).  The MLP task's
    names — ``train_cost`` / ``test_accuracy`` / ``sparsity`` — are also
    exposed as attribute views into the same lists for back-compat with
    the seed-era callers; other tasks read ``metrics`` directly.

    The communication ledger lives here: ``uplink_bytes_per_round`` /
    ``downlink_bytes_per_round`` are the *exact* wire bytes of one round
    (dtype-, sparsity- and mask-overhead-aware, summed over the S
    participating clients — see :func:`repro.fed.compression.round_bytes`
    and the ``comm`` breakdown), and ``cum_uplink_bytes`` is the
    cumulative uplink at each eval point, aligned with ``rounds`` — the
    x-axis of the paper's accuracy-vs-communication comparison.  For a
    task that trains on token sequences, ``comm["tokens_per_round"]``
    counts the tokens the cohort trains on in a round.
    ``comm["sample_rows_kept_per_round"]`` counts the rows the batch
    draw selects and gathers in a round (S, or E·S with E local steps),
    ``comm["sample_rows_keyed_per_round"]`` the rows whose keys the draw
    contract consumes (I, or E·I): their ratio is how much of the host
    sampling follows the cohort (:func:`repro.data.partition.sample_schedule`).

    (The float32-dense ``uplink_floats_per_round`` element count, wrong
    under compression / int32 masking / partial participation, went
    through its deprecation cycle and has been removed.)

    Only the engine fills the ledger; histories from the legacy
    reference drivers leave the byte fields 0 and ``cum_uplink_bytes``
    empty.

    ``wall_seconds`` is the engine's device loop, from its first
    dispatch to the ``block_until_ready`` after the last, on
    ``time.perf_counter``.  ``spans`` is where the whole run's host time
    went: the self seconds of each named phase of :func:`run` (the
    ``engine.*`` spans, :mod:`repro.fed.spans`), so ``wall_seconds`` is
    ``engine.loop`` plus ``engine.chunk``, ``engine.probe`` and
    ``engine.sync``.
    """
    rounds: List[int] = dataclasses.field(default_factory=list)
    metrics: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    slack: List[float] = dataclasses.field(default_factory=list)
    cum_uplink_bytes: List[int] = dataclasses.field(default_factory=list)
    uplink_bytes_per_round: int = 0
    downlink_bytes_per_round: int = 0
    comm: Dict[str, Any] = dataclasses.field(default_factory=dict)
    wall_seconds: float = 0.0
    spans: Dict[str, float] = dataclasses.field(default_factory=dict)

    def metric(self, name: str) -> List[float]:
        """The (live, appendable) series for ``name`` — the *write*
        accessor (:func:`record` uses it); inserts the series if absent."""
        return self.metrics.setdefault(name, [])

    # Back-compat read views of the MLP metric schema.  Reads must not
    # mutate: a history for a task without e.g. "sparsity" would grow a
    # spurious empty series (breaking metrics == task.metric_names and
    # serialized schemas) if a logging helper merely touched the
    # attribute — so an absent metric reads as a throwaway empty list.
    @property
    def train_cost(self) -> List[float]:
        return self.metrics.get("train_cost", [])

    @property
    def test_accuracy(self) -> List[float]:
        return self.metrics.get("test_accuracy", [])

    @property
    def sparsity(self) -> List[float]:
        return self.metrics.get("sparsity", [])

    def as_dict(self) -> Dict[str, Any]:
        d = {"rounds": list(self.rounds),
             "metrics": {k: list(v) for k, v in self.metrics.items()},
             "slack": list(self.slack),
             "cum_uplink_bytes": list(self.cum_uplink_bytes),
             "uplink_bytes_per_round": self.uplink_bytes_per_round,
             "downlink_bytes_per_round": self.downlink_bytes_per_round,
             "comm": dict(self.comm),
             "wall_seconds": self.wall_seconds,
             "spans": dict(self.spans)}
        # seed-era flat keys, kept for serialized-schema compatibility
        for k in _LEGACY_METRICS:
            d[k] = list(self.metrics.get(k, []))
        return d


# One compiled probe per *task* (not per run): tasks are frozen
# dataclasses, so equal tasks share one executable across a multi-seed
# benchmark sweep — per-run closures used to re-jit (and so re-compile)
# the identical computation on every run.
@functools.lru_cache(maxsize=32)
def _measure_fn(task):
    return jax.jit(scoped("eval_probe")(task.measure))


def evaluator(task, data, eval_samples: int, seed: int = 123):
    """The task's metric probe on a fixed eval subset.

    Returns ``measure(params) -> {metric_name: scalar}`` per the task's
    declared ``metric_names``.  Eval data is passed as jit arguments to
    the per-task cached probe (a closure would embed it as HLO constants
    and trigger multi-second constant folding per compile — and a
    per-run jit wrapper would recompile per run)."""
    rng = np.random.default_rng(seed)
    tr = rng.choice(len(data.x_train), size=min(eval_samples,
                                                len(data.x_train)),
                    replace=False)
    xe_tr = jnp.asarray(data.x_train[tr]); ye_tr = jnp.asarray(data.y_train[tr])
    xe_te = jnp.asarray(data.x_test); ye_te = jnp.asarray(data.y_test)
    probe = _measure_fn(task)

    def measure(params):
        return probe(params, xe_tr, ye_tr, xe_te, ye_te)
    return measure


def record(hist: History, t: int, measure, params, slack: float = 0.0):
    vals = measure(params)
    if not isinstance(vals, dict):
        # seed-era probes (the legacy drivers') return the MLP 3-tuple
        vals = dict(zip(_LEGACY_METRICS, vals))
    hist.rounds.append(t)
    for k, v in vals.items():
        hist.metric(k).append(float(v))
    hist.slack.append(float(slack))
    if hist.uplink_bytes_per_round:
        # ledger-carrying histories (the engine's) get the cumulative
        # uplink curve; legacy/reference histories, which never fill the
        # byte fields, keep an empty list rather than a false all-zero one
        hist.cum_uplink_bytes.append(t * hist.uplink_bytes_per_round)


_DEVICE_CACHE: "collections.OrderedDict[int, tuple]" = \
    collections.OrderedDict()
_DEVICE_CACHE_SIZE = 4


def _staged(host_array) -> jnp.ndarray:
    """Device-resident view of a host array, cached by identity — the
    training set is transferred once per process, not once per run (at
    fig1 scale the 188 MB x_train re-upload would otherwise dominate
    short runs).  Small LRU: sweeps over many distinct datasets evict
    one-at-a-time instead of pinning dead copies (or dropping the live
    one).  Holding the host reference keeps the id stable."""
    hit = _DEVICE_CACHE.get(id(host_array))
    if hit is not None and hit[0] is host_array:
        _DEVICE_CACHE.move_to_end(id(host_array))
        return hit[1]
    while len(_DEVICE_CACHE) >= _DEVICE_CACHE_SIZE:
        _DEVICE_CACHE.popitem(last=False)
    dev = jnp.asarray(host_array)
    _DEVICE_CACHE[id(host_array)] = (host_array, dev)
    return dev


def _round_ids(rounds: int, local_steps: int, e_axis: bool) -> np.ndarray:
    """The per-(round, local-step) sampling ids of the seed drivers:
    t for the one-shot (sum-combine) algorithms, t·1000 + e for the
    local-step (FedAvg-style) drivers — including E = 1, so engine and
    legacy trajectories stay paired under the same seed."""
    ts = np.arange(1, rounds + 1, dtype=np.int64)
    if not e_axis:
        return ts
    return (ts[:, None] * 1000 + np.arange(local_steps)).reshape(-1)


def build_schedule(part: Partition, batch_size: int, rounds: int,
                   local_steps: int, seed: int, e_axis: bool = False,
                   cohort_size: Optional[int] = None,
                   groups: Optional[int] = None):
    """The scan-visible schedule: per-round cohorts plus their batches.

    Returns ``(cohorts, idx)`` — ``cohorts`` is (T, S) sorted client ids
    (:func:`repro.data.partition.sample_cohorts`; the identity when
    S = I), ``idx`` is (T, S, B) for sum-combine algorithms or
    (T, S, E, B) when ``e_axis`` (mean-combine local-step algorithms —
    the E axis is kept even for E = 1, since the client scans it as
    local steps; the round's cohort is shared by its E local steps).

    ``groups`` (hierarchical aggregation) applies the per-round group
    permutation (:func:`repro.data.partition.sample_groups`) to each
    cohort row, so group g of the two-level tree is the contiguous block
    [g·M, (g+1)·M).  The batch draw is keyed on *client ids*, not row
    positions, so permuting the cohort never changes any client's
    batches — the participating set, weights and per-client samples are
    identical with or without grouping.

    Index memory is O(T·S·B): with S ≪ I the old (T·E, I, B) tensor is
    never allocated (pinned by ``tests/test_population.py``).
    """
    i = part.num_clients
    s = i if cohort_size is None else int(cohort_size)
    cohorts = sample_cohorts(i, s, np.arange(1, rounds + 1,
                                             dtype=np.int64), seed)
    if groups is not None and int(groups) > 1:
        perm = sample_groups(s, int(groups),
                             np.arange(1, rounds + 1, dtype=np.int64),
                             seed)
        cohorts = np.take_along_axis(cohorts, perm, axis=1)
    ids = _round_ids(rounds, local_steps, e_axis)
    per_id = cohorts if not e_axis \
        else np.repeat(cohorts, local_steps, axis=0)
    idx = sample_schedule(part, batch_size, ids, seed,
                          cohorts=per_id)                    # (T·E, S, B)
    if e_axis:
        idx = idx.reshape(rounds, local_steps, s,
                          batch_size).transpose(0, 2, 1, 3)
    return cohorts, idx


class RoundCarry(NamedTuple):
    """The structured scan carry of the (single) round body.

    ``cstate`` is the optional compressor slot: a **population-resident
    arena** of per-client error-feedback residuals with a leading (I, …)
    client axis when a stateful compressor is set (each round gathers
    the cohort's rows, compresses, and scatters the updated residuals
    back — non-participants' residuals ride through untouched), the
    empty pytree ``()`` otherwise — an empty slot adds no arrays, so the
    uncompressed trace's numerics are untouched."""
    params: PyTree
    state: PyTree
    cstate: PyTree


@jax.jit
def _fold_round_keys(key_data, ts):
    key = jax.random.wrap_key_data(key_data)
    return jax.vmap(
        lambda t: jax.random.key_data(jax.random.fold_in(key, t)))(ts)


@functools.lru_cache(maxsize=32)
def _round_keys(seed: int, rounds: int) -> jnp.ndarray:
    """Hash-consed per-round aggregation keys: row t-1 holds the key
    *words* of ``fold_in(key(seed + 10_000), t)`` — the mask/PRF/
    stochastic-rounding key every strategy derives its round streams
    from.  fold_in is an integer hash (bit-deterministic under vmap), so
    feeding the cached words through ``wrap_key_data`` in the scan body
    yields streams bit-identical to the in-scan derivation this replaces
    — asserted by ``tests/test_round_keys.py`` — while the derivation
    itself leaves the timed loop (it used to re-run per round per chunk
    inside every scan body)."""
    key_data = jax.random.key_data(jax.random.key(seed + 10_000))
    ts = jnp.arange(1, rounds + 1, dtype=jnp.int32)
    return _fold_round_keys(key_data, ts)


def _device_scopes(algorithm: FedAlgorithm, aggregation: Aggregation):
    """The round body's stable device scopes (``jax.named_scope``):
    ``(upload, server_step, in_combine)`` -- the algorithm's client
    upload under ``client_upload``, its server step under
    ``server_step``, and a decorator for the aggregation's combine:
    ``secure_combine`` for a masked strategy, ``combine`` otherwise."""
    masked = getattr(aggregation, "scale_bits", None) is not None
    return (scoped("client_upload")(algorithm.client_upload),
            scoped("server_step")(algorithm.server_step),
            scoped("secure_combine" if masked else "combine"))


@functools.lru_cache(maxsize=64)
def _chunk_fn(algorithm: FedAlgorithm, aggregation: Aggregation,
              compressor=None, mesh=None, staleness=None, plan=None,
              ring_meta=None):
    """The jitted scan-over-rounds body — the engine's *only* scan-body
    builder — cached per (algorithm, aggregation, compressor, mesh,
    staleness, arena plan, ring layout).

    ``compressor=None`` (or the identity, normalized to ``None`` by
    :func:`run`) keeps the compressor slot of the :class:`RoundCarry`
    empty and skips the per-client compress stage entirely, so
    compressed and uncompressed programs never share numerics-relevant
    structure and the identity trajectory stays bit-identical.

    All four cache keys are hashable (frozen dataclasses /
    ``jax.sharding.Mesh``) and the data arrays are passed as arguments
    (not closed over), so repeated ``run`` calls — the multi-seed
    benchmark loops — reuse one compiled executable instead of
    re-tracing a fresh closure per run.  ``params`` (the snapshot ring
    in async mode), ``state`` and ``cstate`` are donated: the scan's
    carry update happens in place instead of holding both the old and
    new model/state per chunk.

    One round body, three statically-selected upload paths — all of them
    O(S) in the cohort, regardless of I:

    * sum-combine × linear aggregation × no compressor — the aggregate
      is evaluated directly on the round-weighted cohort super-batch
      (``client_upload`` is additive in the batch, see
      :mod:`repro.core.protocol`).  One gradient per round; per-client
      message tensors (S× model size of HBM traffic) are never
      materialized.
    * sum-combine, messages materialized (secure aggregation and/or a
      compressor) — per-member uploads computed under vmap over the S
      cohort slots with each member's λ'_i folded into its per-sample
      weights, optionally compressed per member (error-feedback residual
      gathered from / scattered back to the (I, …) arena in the carry),
      then combined by the strategy.
    * mean-combine (FedAvg) — per-member models under vmap; a compressor
      compresses the *model delta* m_i − ω^t (top-k of an update is
      sparsification; top-k of a raw model would discard it) and the
      weighted message λ'_i(ω^t + Δ̂_i) is reassembled afterwards;
      uncompressed messages are weighted directly.

    A **sketched** compressor (:mod:`repro.fed.sketch`, marked by
    ``sketched = True``) changes the wire *shape*, so it threads
    differently, in two phases: the weighted message plus residual is
    encoded into a (rows, cols) count-sketch per member and the
    *sketches* are aggregated by the strategy (they are linear, so the
    secure masked Z_{2^32} sum is the sketch of the summed update
    bit-for-bit); the server ranks a top-k support from the aggregate
    sketch, and the members' values at the broadcast support —
    stochastically rounded onto the secure grid client-side — travel as
    a second (k,)-shaped aggregation under a fresh mask key.  Each
    member then debits its own on-grid phase-2 upload from its input —
    top-k error feedback (residual == input − applied, exactly) into
    the same (I, …) residual arena.  For
    mean-combine the λ'_i weighting moves *before* the encode (the
    sketch's bucket values must stay on the fixed-point grid), and the
    aggregate is ω^t + the reassembled update (Σ λ' = 1).

    Under a client mesh the same paths run per **cohort shard**
    (``shard_map`` over the mesh's first axis): cohort ids and round
    weights are computed identically on every device from the replicated
    cohort row, then sliced to the local S/D slots; uploads stay local
    and the aggregate is one ``psum`` — of the super-batch statistic
    (linear strategies) or of the strategy's partial combine (secure:
    int32 masked fixed-point uploads keyed on cohort positions, whose
    wraparound psum reproduces the single-device Z_{2^32} aggregate
    bit-for-bit).  Sentinel-padded cohort slots (id = I, present when
    D ∤ S) carry exact-zero weights and are dropped from every scatter
    (``mode="drop"``).

    ``plan`` (an :class:`repro.fed.arena.ArenaPlan`, the default on any
    mesh) selects the **home-sharded arena**: the population-resident
    (I, …) state — the EF residual arena, the population weight vector
    and (``ring_meta``) each async ring snapshot — is sharded by client
    home device, resident O(I/D·model) per device.  Cohort rows are
    gathered by a masked per-device slice + one bitcast psum (each row
    leaves exactly one device, never reduced in float), compressed
    position-sharded as before, replicated with one placed psum, and
    written back owner-locally (collective-free).  ``plan=None`` on a
    mesh is the replicated-arena reference mode: every device holds
    every client's row, the cohort's updated rows are rebuilt everywhere
    (one flattened-axes placed psum — O(S·model), cohort-sized) and
    scattered identically on every device.  Both modes are bit-identical
    to each other and to the single device (exact row movement either
    way — pinned by ``tests/sharded_arena_check.py`` and the
    ``mlp_reference.json`` harnesses, which run the sharded default).

    ``staleness`` (a :class:`repro.fed.staleness.StalenessConfig`) turns
    on the **async round mode**: the carry's params slot becomes a ring
    buffer of the last K+1 (params, client-state) snapshots, every
    cohort slot gathers its upload base from the ring at its trace delay
    (delays past K are dropouts: weight forced to 0, residuals
    untouched, and — under secure aggregation — the slot's pair masks
    cancelled via the kernels' ``alive`` path), stale uploads are
    discounted and the cohort weights renormalized
    (:func:`repro.fed.staleness.discount_reweight`), and the new params
    are pushed into the ring after ``server_step``.  Every inserted
    operation is an exact identity on an all-zero trace (gathers of
    ring slot 0, ``·1.0`` float scales, ``·1`` int32 mask gates), so
    async-with-zero-trace reproduces the synchronous trajectories
    bit-for-bit; the sync program itself is untouched (all branches are
    trace-time constants).
    """
    combine = algorithm.combine
    compressed = compressor is not None
    sketched = compressed and getattr(compressor, "sketched", False)
    g_tot = getattr(aggregation, "groups", None)
    is_async = staleness is not None
    k_max = staleness.max_staleness if is_async else 0
    upload, server_step, in_combine = _device_scopes(algorithm, aggregation)

    def chunk(params, state, cstate, x_train, y_train, weights,
              cohort_chunk, idx_chunk, keyw_chunk, *rest, shard=None,
              hier=None):
        # async mode threads the (T, S) staleness trace chunk after the
        # (T, W) per-round key words; params is then the snapshot ring
        # (phist, cshist) instead of a bare pytree
        if is_async:
            (stale_chunk,) = rest
        num_clients = plan.num_clients if plan is not None \
            else weights.shape[0]

        def one_round(carry, xs):
            me = _apsum = None
            if plan is not None:
                me = arena_mod.shard_index(plan)

                def _apsum(tree_):
                    # the arena's one routing reduction: a psum over
                    # every mesh axis the home-sharded rows span
                    return jax.lax.psum(tree_, plan.axes)

            if is_async:
                (phist_in, cshist), state, cstate = carry
                cohort_t, idx_t, kw_t, stale_t = xs
                packed = None
                if ring_meta is None:
                    phist = phist_in
                else:
                    # reconstruct the full snapshot ring from this
                    # device's packed column block: one placed psum,
                    # exact bit movement (each column has exactly one
                    # contributor)
                    packed = staleness_mod.ring_unshard(
                        phist_in, ring_meta, me, _apsum)
                    phist = staleness_mod.unpack_ring(packed, ring_meta)
                params = jax.tree.map(lambda h: h[0], phist)
                has_cs = len(jax.tree.leaves(cshist)) > 0
            else:
                params, state, cstate = carry
                cohort_t, idx_t, kw_t = xs
            # the round key arrives pre-derived: _round_keys hash-conses
            # the fold_in(session_key, t) words host-side once per run
            key_t = jax.random.wrap_key_data(kw_t)

            def _push_carry(params, state, cstate):
                # async ring update: the new snapshot enters at slot 0,
                # the oldest falls off the end (K+1 snapshots live)
                if not is_async:
                    return RoundCarry(params, state, cstate), None

                def push(h, v):
                    return jnp.concatenate([v[None], h[:-1]], axis=0)

                if ring_meta is None:
                    nph = jax.tree.map(lambda h, p: push(h, p), phist,
                                       params)
                else:
                    # pack the new snapshot, shift the packed ring,
                    # carry only this device's column block
                    nph = staleness_mod.ring_localize(
                        push(packed,
                             staleness_mod.pack_snapshot(params,
                                                         ring_meta)),
                        ring_meta, me)
                ncs = jax.tree.map(lambda h, c: push(h, c), cshist,
                                   algorithm.client_state(state))
                return ((nph, ncs), state, cstate), None

            # cohort-wide round weights, computed identically on every
            # device from the replicated cohort row: gather the cohort's
            # population weights — sentinel pads (id = I) clamp in the
            # replicated gather / hit their dead stored-zero row in the
            # home-sharded one, and are forced to exact zero either way
            # — then apply the strategy's reweighting.
            live_full = cohort_t < num_clients
            if plan is None:
                w_c = jnp.where(live_full, weights[cohort_t], 0.0)
            else:
                w_c = jnp.where(
                    live_full,
                    arena_mod.gather_rows(plan, weights, cohort_t, me,
                                          _apsum), 0.0)
            rw_full = aggregation.cohort_weights(w_c, combine, num_clients)
            tau_full = alive_full = alive_i32 = None
            if is_async:
                # delays past the ring bound are dropouts: discount 0
                # (the reweight renormalizes over survivors) plus mask
                # cancellation in the combine; within the bound the
                # schedule's d(τ) applies.  Trace pads (sentinel slots)
                # arrive as 0 — alive, zero-weighted.
                alive_full = stale_t <= k_max
                tau_full = jnp.minimum(stale_t, k_max)
                disc = jnp.where(alive_full,
                                 staleness.discount(tau_full),
                                 jnp.float32(0.0))
                rw_full = staleness_mod.discount_reweight(rw_full, disc)
                alive_i32 = alive_full.astype(jnp.int32)
            offset = 0
            rw, cids, live = rw_full, cohort_t, live_full
            tau, alive_loc = tau_full, alive_full
            alive_rows = None
            if hier is not None:
                # 2-D (groups, clients) mesh: the replicated flat cohort
                # row is blocked (G, M_pad); this device owns the
                # (g_loc, m_loc) tile at (g_off, m_off) and flattens it
                # back to a local cohort slice for the upload vmap
                g_loc, m_loc = idx_t.shape[0], idx_t.shape[1]
                m_pad = cohort_t.shape[0] // g_tot
                g_off = jax.lax.axis_index(hier[0]) * g_loc
                m_off = jax.lax.axis_index(hier[1]) * m_loc

                def _tile(v):
                    return jax.lax.dynamic_slice(
                        v.reshape(g_tot, m_pad), (g_off, m_off),
                        (g_loc, m_loc)).reshape(-1)

                rw, cids, live = (_tile(rw_full), _tile(cohort_t),
                                  _tile(live_full))
                if is_async:
                    tau, alive_loc = _tile(tau_full), _tile(alive_full)
                    # the inner combine of each local group cancels masks
                    # over the group's full member row (global positions)
                    alive_rows = jax.lax.dynamic_slice(
                        alive_i32.reshape(g_tot, m_pad), (g_off, 0),
                        (g_loc, m_pad))
                idx_t = idx_t.reshape((g_loc * m_loc,) + idx_t.shape[2:])
            s_loc = idx_t.shape[0]
            if shard is not None:
                offset = jax.lax.axis_index(shard) * s_loc
                rw = jax.lax.dynamic_slice(rw_full, (offset,), (s_loc,))
                cids = jax.lax.dynamic_slice(cohort_t, (offset,), (s_loc,))
                live = jax.lax.dynamic_slice(live_full, (offset,), (s_loc,))
                if is_async:
                    tau = jax.lax.dynamic_slice(tau_full, (offset,),
                                                (s_loc,))
                    alive_loc = jax.lax.dynamic_slice(alive_full,
                                                      (offset,), (s_loc,))

            @in_combine
            def _combine(msgs, key):
                # the one aggregation entry point of every message path:
                # single-device uses the strategy's full-view combine
                # (messages merge linearly, so the sharded variants
                # below reproduce it bit-for-bit); a 1-D client mesh
                # psums the strategy's partial; the 2-D group mesh
                # routes through the hierarchical tree — level 1 psums
                # inner partials over the members axis, level 2 merges
                # the group partials (masked in the ring for a secure
                # inner) and psums over the groups axis.
                if hier is not None:
                    grouped = jax.tree.map(
                        lambda x: x.reshape((g_loc, m_loc) + x.shape[1:]),
                        msgs)
                    return aggregation.finalize_combine(
                        aggregation.tree_combine(
                            grouped, key, group_offset=g_off,
                            member_offset=m_off, members=m_pad,
                            num_groups=g_tot,
                            reduce_members=lambda p: jax.lax.psum(
                                p, hier[1]),
                            reduce_groups=lambda p: jax.lax.psum(
                                p, hier[0]),
                            alive=alive_rows))
                if not is_async:
                    # the sync programs stay byte-identical: no alive
                    # keyword ever reaches a strategy
                    if shard is None:
                        return aggregation.combine_messages(msgs, key)
                    return aggregation.finalize_combine(
                        jax.lax.psum(aggregation.partial_combine(
                            msgs, key, offset, cohort_t.shape[0]), shard))
                if shard is None:
                    return aggregation.combine_messages(msgs, key,
                                                        alive=alive_i32)
                return aggregation.finalize_combine(
                    jax.lax.psum(aggregation.partial_combine(
                        msgs, key, offset, cohort_t.shape[0],
                        alive=alive_i32), shard))

            if not compressed and combine == "sum" \
                    and not aggregation.needs_messages:
                # linear fast path: one upload on the weighted super-batch
                flat = idx_t.reshape(-1)                     # (S·B,)
                n_per = idx_t.shape[-1]
                if is_async:
                    # bucketed super-batch: one gradient per ring slot,
                    # the slot's super-batch weights masked to the
                    # members at that delay.  Zero-weight buckets yield
                    # exact-zero gradients (the weight scales every
                    # per-sample cotangent), so an all-zero trace — all
                    # mass in bucket 0, evaluated at phist[0] == params —
                    # reproduces the sync aggregate bitwise.
                    bucket_w = jnp.where(
                        tau[None, :] == jnp.arange(k_max + 1)[:, None],
                        rw[None, :], 0.0)                    # (K+1, S)
                    wrep = jnp.repeat(bucket_w, n_per, axis=1)
                    bx, by = x_train[flat], y_train[flat]
                    # unrolled over the (small, static) ring: slot k's
                    # gradient is the *same program* as the sync upload,
                    # so bucket 0 at phist[0] matches it bit-for-bit
                    agg = upload(
                        jax.tree.map(lambda h: h[0], phist), state,
                        (bx, by, wrep[0]))
                    for k in range(1, k_max + 1):
                        g_k = upload(
                            jax.tree.map(lambda h, _k=k: h[_k], phist),
                            state, (bx, by, wrep[k]))
                        agg = jax.tree.map(lambda a, g: a + g, agg, g_k)
                else:
                    batch = (x_train[flat], y_train[flat],
                             jnp.repeat(rw, n_per))
                    agg = upload(params, state, batch)
                if shard is not None:
                    agg = jax.lax.psum(agg, shard)
                params, state = server_step(params, state, agg)
                return _push_carry(params, state, cstate)

            pslots = None
            if is_async:
                # per-slot *elementwise* upload bases (delta/reassembly
                # anchors): a (S_loc, …) row gather per leaf — gathers
                # and elementwise ops are bit-deterministic, so slot-0
                # rows reproduce the sync broadcast exactly
                pslots = jax.tree.map(lambda h: h[tau], phist)

            def _ring_select(fn_k):
                # The upload *computation* is matmul-heavy and its bits
                # can depend on how the batch dimension is carved up —
                # a vmap over stacked ring params need not match the
                # sync broadcast vmap bit-for-bit.  So evaluate the
                # broadcast program once per ring slot (slot 0 IS the
                # sync program) and select each cohort row at its delay:
                # an all-zero trace takes every ``where`` else-branch
                # and the sync output rides through untouched.
                out = fn_k(0)
                for k in range(1, k_max + 1):
                    sel = tau == k
                    out_k = fn_k(k)
                    out = jax.tree.map(
                        lambda o, ok, _s=sel: jnp.where(
                            _s.reshape((-1,) + (1,) * (o.ndim - 1)),
                            ok, o),
                        out, out_k)
                return out

            def _vmap_upload(batch):
                def at_slot(k):
                    p_k = jax.tree.map(lambda h, _k=k: h[_k], phist)
                    s_k = jax.tree.map(lambda h, _k=k: h[_k], cshist) \
                        if has_cs else state
                    return jax.vmap(upload, in_axes=(None, None, 0))(
                        p_k, s_k, batch)
                if not is_async:
                    return jax.vmap(upload, in_axes=(None, None, 0))(
                        params, state, batch)
                return _ring_select(at_slot)

            if combine == "sum":
                xb, yb = x_train[idx_t], y_train[idx_t]      # (S, B, ·)
                ws = jnp.broadcast_to(rw[:, None], idx_t.shape)
                raw = _vmap_upload((xb, yb, ws))
            else:                                            # mean: models
                batch = (x_train[idx_t], y_train[idx_t])     # (S, E, B, ·)
                models = _vmap_upload(batch)
                raw = models if not compressed else \
                    jax.tree.map(lambda m, p: m - p, models,
                                 pslots if is_async else params)

            if compressed:
                # gather the cohort's residuals from the (I, …) arena;
                # PRF streams are keyed on *global* client ids, so a
                # client's rounding/threshold draws are identical
                # whichever cohort slot (or device) it lands on.  Under
                # the home-sharded plan the full cohort's rows are
                # routed out of the local (L, …) blocks (masked slice +
                # one bitcast psum) and then sliced to this device's
                # cohort slots — exactly the rows `a[cids]` reads in the
                # replicated modes, bit for bit.
                if plan is None:
                    resid = jax.tree.map(lambda a: a[cids], cstate)
                else:
                    def _local_rows(v):
                        if hier is not None:
                            g = v.reshape((g_tot, m_pad) + v.shape[1:])
                            tile = jax.lax.dynamic_slice(
                                g, (g_off, m_off) + (0,) * (v.ndim - 1),
                                (g_loc, m_loc) + v.shape[1:])
                            return tile.reshape((g_loc * m_loc,)
                                                + v.shape[1:])
                        return jax.lax.dynamic_slice(
                            v, (offset,) + (0,) * (v.ndim - 1),
                            (s_loc,) + v.shape[1:])

                    resid = jax.tree.map(
                        _local_rows,
                        arena_mod.gather_rows(plan, cstate, cohort_t,
                                              me, _apsum))
                kd = jax.random.key_data(key_t).reshape(-1) \
                    .astype(jnp.uint32)
                k0, k1 = kd[0], kd[-1]

                # sentinel-padded slots (mesh padding) must contribute
                # nothing: their messages are forced to zero here, and
                # their residual rows are dropped by the scatter below.
                # In async mode dropped slots (τ > K) gate identically —
                # their upload never arrived, whatever the strategy does
                # with its own alive mask.
                live_eff = live if not is_async \
                    else jnp.logical_and(live, alive_loc)

                def _gate(c):
                    m = live_eff.reshape((-1,) + (1,) * (c.ndim - 1))
                    return jnp.where(m, c, jnp.zeros_like(c))

                def _keep_dropped(new_resid):
                    # a dropped slot's upload never left the client, so
                    # nothing was applied: its error-feedback residual
                    # rides through the round unchanged
                    if not is_async:
                        return new_resid
                    return jax.tree.map(
                        lambda nr, od: jnp.where(
                            alive_loc.reshape(
                                (-1,) + (1,) * (nr.ndim - 1)), nr, od),
                        new_resid, resid)

                def _scatter_resid(cstate, new_resid):
                    if plan is not None:
                        # home-sharded write-back: replicate the
                        # cohort's updated rows (one placed bitcast
                        # psum), then every device writes only the rows
                        # it homes — the write itself is collective-
                        # free, and sentinel / foreign rows are routed
                        # out of range and dropped
                        if hier is not None:
                            rows = arena_mod.replicate_rows_2d(
                                new_resid, (g_tot, m_pad),
                                (g_loc, m_loc), (g_off, m_off), _apsum)
                        else:
                            rows = arena_mod.replicate_rows(
                                new_resid, cohort_t.shape[0], offset,
                                _apsum)
                        return arena_mod.scatter_rows(
                            plan, cstate, rows, cohort_t, live_full, me)
                    if hier is not None:
                        # one placed psum over the flattened (group,
                        # client) axes rebuilds the whole (G·M_pad, …)
                        # update block on every device, slot order
                        # matching the flat cohort row (bitcast — exact
                        # row movement, replacing the two ordered
                        # all_gathers this path used to chain), so the
                        # replicated arena stays replicated bit-for-bit
                        upd = arena_mod.replicate_rows_2d(
                            new_resid, (g_tot, m_pad), (g_loc, m_loc),
                            (g_off, m_off),
                            lambda t_: jax.lax.psum(t_, hier))
                        at_ids = cohort_t
                    elif shard is None:
                        upd, at_ids = new_resid, cids
                    else:
                        # cohort-sized collective: every device sees all
                        # S updated rows and applies the identical
                        # scatter, so the replicated arena stays
                        # replicated bit-for-bit
                        upd = jax.tree.map(
                            lambda u: jax.lax.all_gather(
                                u, shard, axis=0, tiled=True), new_resid)
                        at_ids = cohort_t
                    return jax.tree.map(
                        lambda a, u: a.at[at_ids].set(u, mode="drop"),
                        cstate, upd)

                if sketched:
                    # weighted message + residual → (rows, cols) sketch
                    # per member; λ' is applied *before* the encode (the
                    # bucket values must stay on the fixed-point grid)
                    if combine == "sum":
                        inp = jax.tree.map(                  # λ' in ws
                            lambda m, r: m.astype(jnp.float32) + r,
                            raw, resid)
                    else:
                        inp = jax.tree.map(
                            lambda d, r: rw.reshape(
                                (-1,) + (1,) * (d.ndim - 1))
                            * d.astype(jnp.float32) + r, raw, resid)

                    # phase 1: masked sketch sum → top-k support
                    sk = _gate(jax.vmap(
                        lambda m, c: compressor.encode(m, k0, k1, c)
                    )(inp, cids.astype(jnp.uint32)))
                    like = jax.tree.map(lambda x: x[0], inp)
                    support = compressor.support(_combine(sk, key_t), like)
                    # phase 2: values at the broadcast support, rounded
                    # onto the secure grid client-side (the masked sum
                    # then equals what the clients uploaded, bit-exact)
                    # and masked under a fresh stream (a reused
                    # pair-mask stream across the two uploads would
                    # cancel in each sum but expose their difference).
                    # The fresh stream is *derived* from the round's
                    # pair secrets by domain separation — fold_in of
                    # the round key, no second pair-seed exchange — so
                    # the ledger's one per-peer seed charge per round
                    # covers both masked uploads.
                    vals = jax.vmap(
                        lambda m, c: compressor.values(m, support,
                                                       k0, k1, c)
                    )(inp, cids.astype(jnp.uint32))
                    agg_v = _combine(
                        _gate(vals), jax.random.fold_in(key_t, 0x5EED))
                    dec = compressor.reassemble(agg_v, support, like)
                    # top-k error feedback with the debit equal to the
                    # member's own on-grid phase-2 upload: the residual
                    # keeps the rounding error (r' = inp − applied)
                    new_resid = jax.vmap(
                        lambda m, v: compressor.update_residual(
                            m, support, v))(inp, vals)
                    cstate = _scatter_resid(cstate, _keep_dropped(new_resid))
                    if is_async and combine == "mean":
                        # the slots' λ'-weighted deltas were taken
                        # against *their own* ring snapshots; the base
                        # the reassembled update applies to is therefore
                        # ω^t + Σ_i λ'_i (ω^{t−τ_i} − ω^t), computed
                        # from replicated full-cohort quantities so
                        # every device agrees.  The shift is an exact
                        # zero on an all-zero trace, and the ``where``
                        # keeps even the −0.0 + x edge bit-identical to
                        # the sync ``params + dec`` expression.
                        pfull = jax.tree.map(lambda h: h[tau_full], phist)

                        def _base_shift(p, pf):
                            w = rw_full.reshape((-1,) + (1,) * p.ndim)
                            return jnp.sum(w * (pf - p[None]), axis=0)

                        shift = jax.tree.map(_base_shift, params, pfull)
                        dec = jax.tree.map(
                            lambda s, d: jnp.where(s == 0, d, s + d),
                            shift, dec)
                    agg = dec if combine == "sum" else jax.tree.map(
                        lambda p, d: p + d, params, dec)
                    params, state = server_step(params, state, agg)
                    return _push_carry(params, state, cstate)

                comp, new_resid = jax.vmap(
                    lambda m, r, c: compressor.compress(m, r, k0, k1, c)
                )(raw, resid, cids.astype(jnp.uint32))
                comp = jax.tree.map(_gate, comp)
                cstate = _scatter_resid(cstate, _keep_dropped(new_resid))
                if combine == "sum":
                    msgs = comp                              # λ' in ws
                else:
                    msgs = jax.tree.map(
                        lambda d, p: rw.reshape(
                            (-1,) + (1,) * (d.ndim - 1)) * (p + d),
                        comp, pslots if is_async else params)
            elif combine == "sum":
                msgs = raw                                   # λ' in ws
            else:
                msgs = jax.tree.map(
                    lambda m: m * rw.reshape((-1,) + (1,) * (m.ndim - 1)),
                    raw)

            agg = _combine(msgs, key_t)
            params, state = server_step(params, state, agg)
            return _push_carry(params, state, cstate)

        if is_async:
            # the carry's params slot is the snapshot ring (phist,
            # cshist); run() passes it in and reads params back out of
            # ring slot 0 at the chunk boundary
            carry, _ = jax.lax.scan(
                one_round, (params, state, cstate),
                (cohort_chunk, idx_chunk, keyw_chunk, stale_chunk))
            return carry
        carry, _ = jax.lax.scan(one_round,
                                RoundCarry(params, state, cstate),
                                (cohort_chunk, idx_chunk, keyw_chunk))
        return carry.params, carry.state, carry.cstate

    # only the carry is donated: the int32 cohort, index and staleness
    # chunks have no output to alias into, and keyw_chunk's rows come
    # from the host-cached _round_keys array, reused across chunks and runs
    donate = (0, 1, 2)
    n_tail = 1 if is_async else 0      # [stale_chunk]
    if mesh is None:
        return jax.jit(chunk, donate_argnums=donate)

    spec = jax.sharding.PartitionSpec
    # the population-resident (I_pad, …) state — residual arena and
    # weight vector — shards its leading (home-device) dim over every
    # mesh axis under a plan; without one it is replicated (the
    # reference mode).  The async carry slot is (phist, cshist): the
    # packed ring shards its flat column dim, cshist stays replicated.
    row_spec = spec() if plan is None else spec(plan.axes)
    if is_async:
        carry_spec = (spec() if ring_meta is None
                      else spec(None, plan.axes), spec())
    else:
        carry_spec = spec()

    if tuple(mesh.axis_names) == ("groups", "clients"):
        # hierarchical 2-D mesh: idx_chunk arrives group-blocked
        # (T, G, M_pad, …) from run() and shards its (group, member)
        # dims; the flat (T, G·M_pad) cohort rows are replicated, and
        # both tree reductions are psums inside the body
        hier_axes = mesh.axis_names

        def hier_body(params, state, cstate, x_train, y_train, weights,
                      cohort_chunk, idx_chunk, keyw_chunk, *rest):
            return chunk(params, state, cstate, x_train, y_train,
                         weights, cohort_chunk, idx_chunk, keyw_chunk,
                         *rest, hier=hier_axes)

        fn = jax.shard_map(
            hier_body, mesh=mesh, check_vma=False,
            in_specs=(carry_spec, spec(), row_spec, spec(), spec(),
                      row_spec, spec(),
                      spec(None, "groups", "clients"), spec())
            + (spec(),) * n_tail,
            out_specs=(carry_spec, spec(), row_spec))
        return jax.jit(fn, donate_argnums=donate)

    axis = mesh.axis_names[0]

    def sharded_body(params, state, cstate, x_train, y_train, weights,
                     cohort_chunk, idx_chunk, keyw_chunk, *rest):
        return chunk(params, state, cstate, x_train, y_train, weights,
                     cohort_chunk, idx_chunk, keyw_chunk, *rest,
                     shard=axis)

    # the cohort axis of idx_chunk is sharded; cohort ids, key words
    # and the staleness-trace rows are replicated (their rows belong to
    # per-round cohort positions, not to a device)
    fn = jax.shard_map(
        sharded_body, mesh=mesh, check_vma=False,
        in_specs=(carry_spec, spec(), row_spec, spec(), spec(),
                  row_spec, spec(), spec(None, axis), spec())
        + (spec(),) * n_tail,
        out_specs=(carry_spec, spec(), row_spec))
    return jax.jit(fn, donate_argnums=donate)


def _block_schedule(cohorts, schedule, g: int, m: int, m_pad: int,
                    sentinel: int):
    """Group-block a (T, S) cohort / (T, S, …) index schedule for the
    2-D hierarchical mesh: cohorts come back flat (T, G·M_pad) with each
    group's members contiguous, the schedule comes back (T, G, M_pad, …)
    ready to shard ``P(None, "groups", "clients")``.  Sentinel slots
    (id = ``sentinel``, zero round weight, index-0 batches) fill the
    last group's tail (G ∤ S) and the member-axis pad (shards ∤ M)."""
    t, s = cohorts.shape
    pad1 = g * m - s
    if pad1:
        cohorts = np.concatenate(
            [cohorts, np.full((t, pad1), sentinel, cohorts.dtype)], 1)
        schedule = np.pad(
            schedule, [(0, 0), (0, pad1)] + [(0, 0)] * (schedule.ndim - 2))
    cohorts = cohorts.reshape(t, g, m)
    schedule = schedule.reshape((t, g, m) + schedule.shape[2:])
    pad2 = m_pad - m
    if pad2:
        cohorts = np.pad(cohorts, [(0, 0), (0, 0), (0, pad2)],
                         constant_values=sentinel)
        schedule = np.pad(schedule, [(0, 0), (0, 0), (0, pad2)]
                          + [(0, 0)] * (schedule.ndim - 3))
    return cohorts.reshape(t, g * m_pad), schedule


def _upload_avals(algorithm: FedAlgorithm, x_train, y_train,
                  batch_size: int, params: PyTree):
    """Shape/dtype skeleton of one client's upload message — the template
    for per-client compressor state (error-feedback residuals)."""
    xb = jax.ShapeDtypeStruct((batch_size,) + x_train.shape[1:],
                              x_train.dtype)
    yb = jax.ShapeDtypeStruct((batch_size,) + y_train.shape[1:],
                              y_train.dtype)
    if algorithm.combine == "sum":
        batch = (xb, yb, jax.ShapeDtypeStruct((batch_size,), jnp.float32))
    else:
        e = algorithm.local_steps
        batch = (jax.ShapeDtypeStruct((e,) + xb.shape, xb.dtype),
                 jax.ShapeDtypeStruct((e,) + yb.shape, yb.dtype))
    state = jax.eval_shape(algorithm.init_state, params)
    return jax.eval_shape(algorithm.client_upload, params, state, batch)


def _upload_shapes(algorithm: FedAlgorithm, x_train, y_train,
                   batch_size: int, params: PyTree) -> tuple:
    """Per-client leaf shapes of one upload message, in tree order."""
    aval = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    return _traced_upload_shapes(algorithm, aval(x_train), aval(y_train),
                                 batch_size, treedef,
                                 tuple(map(aval, leaves)))


@functools.lru_cache(maxsize=64)
def _traced_upload_shapes(algorithm, x_train, y_train, batch_size, treedef,
                          leaves) -> tuple:
    # cached: tracing the upload again every call would cost a host-bound
    # cell several milliseconds a call
    avals = _upload_avals(algorithm, x_train, y_train, batch_size,
                          jax.tree_util.tree_unflatten(treedef, leaves))
    return tuple(x.shape for x in jax.tree.leaves(avals))


def run(algorithm: FedAlgorithm, data, part: Partition, *, task,
        batch_size: int, rounds: int, params: Optional[PyTree] = None,
        seed: int = 0, eval_every: int = 1, eval_samples: int = 10000,
        aggregation: Optional[Aggregation] = None,
        compressor=None, mesh=None, staleness=None,
        staleness_trace=None,
        arena: Optional[str] = None,
        profile_dir=None) -> tuple[PyTree, History]:
    """Run ``algorithm`` on ``task`` for ``rounds`` rounds.

    ``task`` — a :class:`repro.fed.tasks.base.FedTask`; it supplies the
    metric schema and the jitted eval probe (and, when ``params`` is
    ``None``, the initial parameters).  ``data`` must match the task's
    client-batch layout (``task.default_data(...)`` produces one).

    Returns the final parameters and the :class:`History` (task metrics,
    the communication ledger and the self seconds of the run's named
    host phases, ``spans``).  ``seed`` controls the parameter
    init (when ``params`` is ``None``), the cohort draw, the mini-batch
    schedule and the per-round aggregation / compression key (mask /
    stochastic-rounding derivation).

    ``compressor`` — a :mod:`repro.fed.compression` strategy applied to
    every client upload before aggregation (``None`` or
    ``compression.identity()``: dense uploads, bit-identical
    trajectories).  Stateful compressors (top-k error feedback) keep a
    per-client residual in a population-resident (I, …) arena slot of
    the scan carry; each round gathers and scatters only the cohort's
    rows.

    ``mesh`` — a 1-D client mesh (:func:`repro.launch.mesh.make_client_mesh`)
    shards each round's **cohort** over the mesh devices with psum
    aggregation; cohorts are sentinel-padded to a device multiple when
    needed, so any population size I and cohort size S run on any device
    count.  ``None`` runs single-device.

    ``staleness`` — a :class:`repro.fed.staleness.StalenessConfig` turns
    on the async round mode: a seed-stable staleness trace (drawn on its
    own rng stream by :func:`repro.data.partition.sample_staleness`, or
    supplied explicitly as ``staleness_trace``, a (rounds, cohort)
    integer array) assigns every (round, cohort-slot) a delay τ; slots
    upload against the params of round t−τ from a ring buffer of the
    last K+1 snapshots, stale uploads are discounted per the config's
    schedule, and delays past K become dropouts (weight 0, secure pair
    masks cancelled, recovery bytes charged to ``History.comm["async"]``).
    An all-zero trace is bit-identical to ``staleness=None``.  With
    ``max_staleness=1``, a ``ConstantDiscount`` and an all-ones trace
    every upload is one round late: round t's uploads are computed at
    ω^{t−1} and their combine is applied to ω^t.

    ``arena`` — placement of the population-resident (I, …) state on a
    mesh.  ``"sharded"`` (the default whenever ``mesh`` is set) homes
    each client's row — EF residuals, population weight, each async
    ring snapshot — on one device (:mod:`repro.fed.arena`), so resident
    bytes per device scale O(I/D·model); ``"replicated"`` keeps the
    pre-PR-9 every-device-holds-everything layout (the memory-bench
    reference).  The two are **bit-identical** — rows are routed as
    uint32 bitcasts, never reduced in float — so the choice is purely a
    memory/layout knob.  Ignored without a mesh (single-device has
    nothing to shard).

    ``profile_dir`` — when set, the whole call runs inside a
    ``jax.profiler`` trace written there (one trace per run): the
    device's operations beside the run's named host phases (the
    ``engine.*`` spans of ``History.spans``), so each idle gap of the
    device can be put down to a phase.
    """
    spans = Spans()
    if profile_dir is not None:
        jax.profiler.start_trace(str(profile_dir))
    try:
        with spans("engine.run"):
            return _run(algorithm, data, part, spans, task=task,
                        batch_size=batch_size, rounds=rounds, params=params,
                        seed=seed, eval_every=eval_every,
                        eval_samples=eval_samples, aggregation=aggregation,
                        compressor=compressor, mesh=mesh,
                        staleness=staleness,
                        staleness_trace=staleness_trace, arena=arena)
    finally:
        if profile_dir is not None:
            jax.profiler.stop_trace()


def _run(algorithm: FedAlgorithm, data, part: Partition, spans: Spans, *,
         task, batch_size: int, rounds: int, params, seed: int,
         eval_every: int, eval_samples: int, aggregation, compressor, mesh,
         staleness, staleness_trace,
         arena: Optional[str]) -> tuple[PyTree, History]:
    """:func:`run`'s body, its phases timed by ``spans``: host sampling
    (``engine.schedule``), staging (``engine.stage``), the device loop
    (``engine.loop``: ``engine.chunk`` dispatches, ``engine.probe``
    eval probes, the final ``engine.sync``) and the result transfer
    (``engine.collect``)."""
    aggregation = aggregation if aggregation is not None \
        else PlainAggregation()
    if compressor is not None and compressor.is_identity:
        compressor = None       # same trace, cache entry and trajectory
    comp_grid = getattr(compressor, "scale_bits", None)
    agg_grid = getattr(aggregation, "scale_bits", None)
    if comp_grid is not None and agg_grid is not None \
            and int(comp_grid) != int(agg_grid):
        # a grid-emitting compressor (the count-sketch) is only lossless
        # under secure aggregation when the two fixed-point grids agree;
        # a mismatch would silently re-round every bucket off-grid and
        # break the bit-exact masked merge — refuse it up front
        raise ValueError(
            f"compressor scale_bits={int(comp_grid)} != aggregation "
            f"scale_bits={int(agg_grid)}: the compressor emits values on "
            "the 2^-scale_bits fixed-point grid and the secure masked sum "
            "is only exact when the grids match")
    cohort = aggregation.cohort_size(part.num_clients)   # validates range
    groups = getattr(aggregation, "groups", None)
    if staleness_trace is not None and staleness is None:
        raise ValueError(
            "staleness_trace requires the async round mode: pass a "
            "repro.fed.staleness.StalenessConfig as staleness=")
    if params is None:
        params = task.init_params(jax.random.key(seed))
    with spans("engine.schedule"):
        cohorts, schedule = build_schedule(
            part, batch_size, rounds, algorithm.local_steps, seed,
            e_axis=algorithm.combine == "mean", cohort_size=cohort,
            groups=groups)
        trace = None
        if staleness is not None:
            if staleness_trace is None:
                trace = sample_staleness(cohort,
                                         np.arange(1, rounds + 1,
                                                   dtype=np.int64),
                                         seed, staleness.delay_probs)
            else:
                trace = np.asarray(staleness_trace, np.int64)
                if trace.shape != (rounds, cohort):
                    raise ValueError(
                        f"staleness_trace shape {trace.shape} != (rounds, "
                        f"cohort) = {(rounds, cohort)}")
                if (trace < 0).any():
                    raise ValueError("staleness_trace delays must be >= 0")
        trace_pad = trace
        if mesh is not None:
            axes = tuple(mesh.axis_names)
            if groups is not None:
                if axes != ("groups", "clients"):
                    raise ValueError(
                        "HierarchicalAggregation shards over a 2-D "
                        "(groups, clients) mesh — "
                        "launch.mesh.make_group_mesh"
                        f" — not axes {axes}: a flat cohort shard cannot "
                        "host the tree's two reductions")
                dg, dc = mesh.shape["groups"], mesh.shape["clients"]
                g = int(groups)
                if g % dg:
                    raise ValueError(
                        f"groups={g} must be a multiple of the mesh's groups"
                        f" axis ({dg} shards): a group cannot span the axis "
                        "its level-2 combine reduces over")
                m = -(-cohort // g)
                m_pad = -(-m // dc) * dc
                cohorts, schedule = _block_schedule(cohorts, schedule, g, m,
                                                    m_pad, part.num_clients)
                if trace_pad is not None:
                    # pad slots get delay 0: alive, zero-weighted — the
                    # same convention the single-device hier path applies
                    trace_pad, _ = _block_schedule(trace_pad,
                                                   trace_pad[..., None],
                                                   g, m, m_pad, 0)
            elif axes == ("groups", "clients"):
                raise ValueError(
                    "a (groups, clients) mesh needs a "
                    "HierarchicalAggregation — flat strategies shard over "
                    "the 1-D make_client_mesh")
            else:
                ndev = mesh.shape[axes[0]]
                pad = (-cohort) % ndev
                if pad:
                    # pad the cohort to a device multiple with the
                    # sentinel id I (zero round weight, writes dropped)
                    # so D ∤ S still runs — S = 1 on a 2-device mesh
                    # included
                    cohorts = np.concatenate(
                        [cohorts, np.full((rounds, pad), part.num_clients,
                                          np.int64)], 1)
                    widths = ([(0, 0), (0, pad)]
                              + [(0, 0)] * (schedule.ndim - 2))
                    schedule = np.pad(schedule, widths)
                    if trace_pad is not None:
                        trace_pad = np.concatenate(
                            [trace_pad, np.zeros((rounds, pad), np.int64)], 1)
    with spans("engine.stage"):
        if arena not in (None, "replicated", "sharded"):
            raise ValueError(
                f"arena={arena!r} not in (None, 'replicated', 'sharded')")
        plan = None
        if mesh is not None and (arena or "sharded") == "sharded":
            plan = arena_mod.make_plan(part.num_clients, mesh)
        cohort_dev = jnp.asarray(cohorts, jnp.int32)             # one transfer
        idx_dev = jnp.asarray(schedule, jnp.int32)               # one transfer
        x_train = _staged(data.x_train)
        y_train = _staged(data.y_train)
        weights = jnp.asarray(algorithm.client_weights(part, batch_size),
                              jnp.float32)
        arena_sharding = None
        if plan is not None:
            # the population weight vector is itself (I,)-resident: pad to
            # the home layout (dead tail rows store exact zeros — the
            # sentinel's reads) and home-shard it like the arena.  Built
            # under jit with out_shardings so each device materializes only
            # its own rows — the full (I_pad, …) array never exists on any
            # single device (at real populations it would not fit one)
            arena_sharding = jax.sharding.NamedSharding(
                mesh, arena_mod.shard_spec(plan))
            weights = jax.jit(lambda w: arena_mod.pad_rows(w, plan),
                              out_shardings=arena_sharding)(weights)
        # per-round aggregation keys, hash-consed host-side: the fold_in
        # chain leaves the scan body
        keyw = _round_keys(seed, rounds)
        stale_dev = None if trace_pad is None \
            else jnp.asarray(trace_pad, jnp.int32)

        # chunk inputs are donated — never hand the caller's param buffers to
        # the donating executable (the caller may reuse them across runs)
        params = jax.tree.map(jnp.array, params)
        state = algorithm.init_state(params)
        ring = None
        ring_meta = None
        if staleness is not None:
            # snapshot ring, newest first: slot 0 holds the current params;
            # rounds earlier than the run see the init point, so a delayed
            # slot in round 1 replays against the initial params
            depth = staleness.max_staleness + 1
            phist = jax.tree.map(lambda p: jnp.repeat(p[None], depth, axis=0),
                                 params)
            cshist = jax.tree.map(lambda c: jnp.repeat(jnp.asarray(c)[None],
                                                       depth, axis=0),
                                  algorithm.client_state(state))
            if plan is not None:
                # home-sharded mode: each ring snapshot shards its packed
                # flat column dim over the mesh — O((K+1)/D·model) resident
                # per device.  Falls back to the replicated ring when a
                # param leaf cannot route losslessly (non-4-byte dtype).
                ring_meta = staleness_mod.ring_meta(params, plan.num_shards)
            if ring_meta is not None:
                phist = jax.device_put(
                    staleness_mod.pack_ring(phist, ring_meta),
                    jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec(None, plan.axes)))
            ring = (phist, cshist)
        cstate: PyTree = ()
        if compressor is not None:
            avals = _upload_avals(algorithm, x_train, y_train, batch_size,
                                  params)
            if plan is None:
                cstate = compressor.init_client_state(avals, part.num_clients)
            else:
                # home-shard the EF arena at birth: out_shardings makes XLA
                # produce each device's (L, …) block in place — no full
                # (I_pad, model) transient on the home device
                cstate = jax.jit(
                    lambda: compressor.init_client_state(
                        avals, plan.total_rows),
                    out_shardings=arena_sharding)()
        run_chunk = _chunk_fn(algorithm, aggregation, compressor, mesh,
                              staleness, plan, ring_meta)
        measure = evaluator(task, data, eval_samples)
        ledger = compression_mod.round_bytes(algorithm, aggregation,
                                             compressor, params,
                                             part.num_clients)
        # spans: filled as each phase ends, engine.run last
        hist = History(uplink_bytes_per_round=ledger.uplink_total,
                       downlink_bytes_per_round=ledger.downlink_total,
                       comm=ledger.as_dict(), spans=spans.seconds)
        # the batch draw a round: rows selected and gathered (S, or E·S
        # with local steps) against rows the draw contract keys (I each)
        draws = algorithm.local_steps if algorithm.combine == "mean" else 1
        hist.comm["sample_rows_kept_per_round"] = cohort * draws
        hist.comm["sample_rows_keyed_per_round"] = part.num_clients * draws
        mask_words = getattr(aggregation, "mask_words", None)
        if mask_words is not None \
                and not hasattr(compressor, "wire_elements"):
            # one masked upload a client a round (a sketch's two phases
            # are two combines of their own sizes, not counted here)
            shards = 1 if mesh is None else mesh.shape[mesh.axis_names[0]]
            hist.comm.update(mask_words(
                _upload_shapes(algorithm, x_train, y_train, batch_size,
                               params),
                -(-cohort // shards) * shards, shards))
        tokens = getattr(task, "tokens_per_sample", None)
        if tokens:
            # what an LM cohort trains on a round: S·E·B sequences
            hist.comm["tokens_per_round"] = (
                cohort * algorithm.local_steps * batch_size * int(tokens))
        if staleness is not None:
            # async accounting: stats over the *real* cohort slots
            # (trace pre-padding) plus the exact seed-share recovery wire
            # charged per dropped slot by the strategy
            k = staleness.max_staleness
            dropped = staleness_mod.dropped_per_round(trace, k)
            rec_fn = getattr(aggregation, "recovery_bytes_per_drop", None)
            rec_per = int(rec_fn(part.num_clients)) if rec_fn else 0
            hist.comm["async"] = {
                "max_staleness": k,
                "stale_fraction": float((trace > 0).mean()),
                "dropped_total": int(dropped.sum()),
                "dropout_rate": float(dropped.sum() / trace.size),
                "recovery_bytes_per_drop": rec_per,
                "recovery_bytes_total": int(dropped.sum()) * rec_per,
            }
    done = 0
    # eval probes are *deferred*: measure() / round_metrics() return
    # device values that stay device-side until one batched device_get
    # after the timed loop — a per-interval float() would force a host
    # sync inside the timed region
    evals: list = []
    with spans("engine.loop") as loop:
        while done < rounds:
            n = min(eval_every, rounds - done)
            with spans("engine.chunk"):
                if staleness is None:
                    params, state, cstate = run_chunk(
                        params, state, cstate, x_train, y_train,
                        weights, cohort_dev[done:done + n],
                        idx_dev[done:done + n], keyw[done:done + n])
                else:
                    ring, state, cstate = run_chunk(
                        ring, state, cstate, x_train, y_train, weights,
                        cohort_dev[done:done + n],
                        idx_dev[done:done + n], keyw[done:done + n],
                        stale_dev[done:done + n])
                    if ring_meta is None:
                        params = jax.tree.map(lambda h: h[0], ring[0])
                    else:
                        # slot 0 out of the packed sharded ring — then
                        # *replicate* it: eager slices of the column-
                        # sharded packed array stay device-sharded, and
                        # a sharded params input would make the jitted
                        # eval probe partition (and so reassociate) its
                        # reductions — the replicated layout keeps eval
                        # bit-identical to the replicated-ring mode
                        params = jax.device_put(
                            staleness_mod.unpack_snapshot(ring[0],
                                                          ring_meta),
                            jax.sharding.NamedSharding(
                                mesh, jax.sharding.PartitionSpec()))
            done += n
            with spans("engine.probe"):
                evals.append((done, measure(params),
                              algorithm.round_metrics(state)))
        with spans("engine.sync"):
            jax.block_until_ready((params, [e[1] for e in evals],
                                   [e[2] for e in evals]))
    hist.wall_seconds = loop.seconds
    with spans("engine.collect"):
        # one batched transfer replays record()'s exact History semantics
        for t_pt, vals, rmet in jax.device_get(evals):
            if not isinstance(vals, dict):
                vals = dict(zip(_LEGACY_METRICS, vals))
            hist.rounds.append(int(t_pt))
            for k_, v in vals.items():
                hist.metric(k_).append(float(v))
            hist.slack.append(float(rmet.get("slack", 0.0)))
            if hist.uplink_bytes_per_round:
                hist.cum_uplink_bytes.append(
                    int(t_pt) * hist.uplink_bytes_per_round)
    return params, hist
