"""Composable cross-client aggregation strategies — cohort-native.

The CSSCA framework underlying the paper (arXiv:1801.08266) is agnostic
to *how* the stochastic estimate Σ_i λ_i m_i is formed — it only needs
the aggregate.  This module makes that a first-class, interchangeable
layer, and makes partial participation **cohort-native**: a strategy
declares how many clients participate per round (:meth:`cohort_size`),
the engine draws that cohort host-side into the schedule
(:func:`repro.data.partition.sample_cohorts`), and everything downstream
— batch gathers, uploads, reweighting, masking, the wire ledger — only
ever touches the S cohort members.  Nothing in a round is O(I); the old
formulation (full-I round weights with I−S zeros masking wasted uploads)
is gone.

A strategy has these parts:

* ``cohort_size(num_clients)`` — S, the number of clients that
  participate in (and upload during) one round.  The engine sizes the
  per-round schedule, the vmap over client uploads, and the client-mesh
  shards by this.
* ``cohort_weights(weights, combine, num_clients)`` — the effective
  per-client weights λ'_i for the round, computed **from the gathered
  cohort's weights** (shape (S,), already gathered by the engine from
  the population weight vector; sentinel-padded slots arrive as exact
  zeros).  Partial participation lives here: sum-combine cohorts are
  rescaled by I/S (unbiased — E[Σ_{i∈S} (I/S) λ_i m_i] = Σ_i λ_i m_i),
  mean-combine cohorts re-normalize to Σ λ' = 1 (FedAvg-style).  S = I
  short-circuits to the identity so full participation is bit-identical
  to :class:`PlainAggregation`.
* ``needs_messages`` — whether the server must see *individual* client
  uploads.  Linear strategies (plain, sampled) don't: since the upload
  map of every sum-combine algorithm is additive in its batch,
  Σ_i λ'_i upload(batch_i) == upload(⊎_i λ'-weighted batch_i), and the
  engine evaluates the aggregate directly on the weighted cohort
  super-batch — no per-client message tensors are ever materialized.
* ``combine_messages(wmsgs, key)`` — reduction over explicit pre-weighted
  per-cohort-member messages (leading axis S), for strategies that do
  need them.
* ``partial_combine(wmsgs, key, cohort_offset, cohort_size)`` /
  ``finalize_combine(partial)`` — the *sharded* decomposition of
  ``combine_messages``: each device reduces its local slice of the
  cohort (cohort positions [offset, offset + S_loc) of S), the partials
  are ``psum``-ed over the client mesh axis, and ``finalize_combine``
  maps the summed partial to the aggregate.  For every strategy here the
  partial is a plain pytree sum — float messages for linear strategies,
  *int32 fixed-point masked uploads* for secure aggregation, whose psum
  is the exact Z_{2^32} wraparound sum.  ``combine_messages`` is
  definitionally ``finalize(partial(whole cohort))``.

All strategies work with all four algorithms — including secure
Algorithm 2, which the paper's §III-B requires: its (value, gradient)
upload tuple is just another pytree here.

Secure aggregation is Bonawitz-style pairwise additive masking done in
**modular integer arithmetic** (the production construction): client
messages are fixed-point quantized to int32, pair masks are uniform over
Z_{2^32} and cancel *exactly* under wraparound addition — the unmasked
aggregate is bit-for-bit the sum of the quantized messages, with no
floating-point mask residue.  Pair-mask streams are keyed on **cohort
positions** (0 … S−1): only the S participating clients exchange pair
seeds, so the masking protocol itself is O(S), not O(I) — with
``num_sampled=`` set, S of I clients are drawn per round exactly like
:class:`SampledClients` and masking runs over that cohort only.  Two
implementations:

* ``streaming=True`` (default) — the streaming path of
  :mod:`repro.kernels.secure_agg`: quantization, counter-based pair-mask
  generation and the signed Z_{2^32} accumulate fused in one pass over
  the message (Pallas kernel on TPU, masks generated in VMEM; XLA
  elsewhere).  O(S·model) traffic, nothing pair-shaped ever touches HBM.
* ``streaming=False`` — the retired reference path: all P = S(S−1)/2
  pair masks materialized as model-sized tensors and combined by a
  signed tensordot.  O(P·model) traffic; it lives with the kernel
  oracles (:func:`repro.kernels.ref.secure_masked_combine`) and is
  imported lazily only when explicitly requested, so the hot path never
  loads it.  Kept as the bit-exactness reference and the benchmark
  baseline.

Both return bit-identical aggregates (mod-2^32 addition is exactly
associative/commutative), so the choice is purely a performance axis.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as _kops
from repro.kernels import secure_agg as _sa

PyTree = Any


@runtime_checkable
class Aggregation(Protocol):
    needs_messages: bool

    def cohort_size(self, num_clients: int) -> int: ...

    def cohort_weights(self, weights: jnp.ndarray, combine: str,
                       num_clients: int) -> jnp.ndarray: ...

    def combine_messages(self, wmsgs: PyTree, key, alive=None) -> PyTree: ...

    def partial_combine(self, wmsgs: PyTree, key, cohort_offset,
                        cohort_size: int, alive=None) -> PyTree: ...

    def finalize_combine(self, partial: PyTree) -> PyTree: ...

    # -- communication-ledger hooks (repro.fed.compression) ------------

    def participants(self, num_clients: int) -> int: ...

    def uplink_wire_bytes(self, payload_bytes: int, dense_elements: int,
                          num_clients: int) -> int: ...

    def recovery_bytes_per_drop(self, num_clients: int) -> int: ...


def _sum_clients(wmsgs: PyTree) -> PyTree:
    """Σ_i m_i over the leading cohort axis of every leaf."""
    return jax.tree.map(lambda m: jnp.sum(m, axis=0), wmsgs)


def _validated_cohort(num_sampled: Optional[int], num_clients: int) -> int:
    """S for a strategy with an optional ``num_sampled``; range-checked
    against the population (raised eagerly by the engine before any
    schedule is drawn)."""
    if num_sampled is None:
        return num_clients
    s = int(num_sampled)
    if not 1 <= s <= num_clients:
        raise ValueError(
            f"num_sampled={s} out of range [1, {num_clients}]")
    return s


def _cohort_reweight(weights, combine: str, num_clients: int, s: int):
    """The partial-participation reweighting on gathered cohort weights.

    * sum-combine: λ'_i = (I/S)·λ_i — with λ_i = N_i/(B·N) this is the
      unbiased N_i·I/(S·B·N) estimator of the full sum.
    * mean-combine: λ'_i = λ_i / Σ_{j∈cohort} λ_j (standard FedAvg
      client-sampling re-normalization, Σ λ' = 1 exactly).

    S = I returns the weights untouched (both corrections are the
    identity only up to float rounding), so full participation stays
    bit-identical to :class:`PlainAggregation`.  Sentinel-padded slots
    (engine mesh padding) arrive as exact zeros and stay exact zeros.
    """
    if s == num_clients:
        return weights
    if combine == "mean":
        return weights / jnp.sum(weights)
    return weights * (num_clients / s)


class _LinearCombine:
    """Shared sharded decomposition for strategies whose combine is a
    plain sum: the partial is the local sum, finalize is identity.  Also
    the shared ledger hooks: a linear strategy puts the compressor's
    payload on the wire as-is (full participation by default)."""

    def cohort_size(self, num_clients: int) -> int:
        return num_clients

    def partial_combine(self, wmsgs, key, cohort_offset, cohort_size,
                        alive=None):
        # a dropped linear client simply carries weight 0 (the engine's
        # staleness reweighting already zeroed it) — no mask state to
        # cancel, so ``alive`` needs no arithmetic here
        del key, cohort_offset, cohort_size, alive
        return _sum_clients(wmsgs)

    def finalize_combine(self, partial):
        return partial

    def participants(self, num_clients: int) -> int:
        return num_clients

    def uplink_wire_bytes(self, payload_bytes: int, dense_elements: int,
                          num_clients: int) -> int:
        del dense_elements, num_clients
        return payload_bytes

    def recovery_bytes_per_drop(self, num_clients: int) -> int:
        del num_clients  # nothing to recover without masks
        return 0


@dataclasses.dataclass(frozen=True)
class PlainAggregation(_LinearCombine):
    """Full participation, plain weighted sum — the eq.-(2) server."""

    needs_messages = False

    def cohort_weights(self, weights, combine, num_clients):
        del combine, num_clients  # deterministic, full participation
        return weights

    def combine_messages(self, wmsgs, key, alive=None):
        del key, alive
        return _sum_clients(wmsgs)


@dataclasses.dataclass(frozen=True)
class SampledClients(_LinearCombine):
    """Partial participation: S of I clients per round (uniform, without
    replacement), the millions-of-users serving regime.

    Cohort-native: :meth:`cohort_size` tells the engine to draw S-client
    cohorts into the schedule and to vmap uploads over S — per-round
    compute, memory and wire cost are O(S) however large I grows.  The
    reweighting (:func:`_cohort_reweight`) acts on the gathered cohort's
    weights only; there is no full-I mask anywhere.
    """
    num_sampled: int

    needs_messages = False

    def cohort_size(self, num_clients: int) -> int:
        return _validated_cohort(self.num_sampled, num_clients)

    def cohort_weights(self, weights, combine, num_clients):
        return _cohort_reweight(weights, combine, num_clients,
                                int(self.num_sampled))

    def combine_messages(self, wmsgs, key, alive=None):
        del key, alive  # selection already folded into the cohort schedule
        return _sum_clients(wmsgs)

    def participants(self, num_clients: int) -> int:
        del num_clients  # exactly S clients upload every round
        return int(self.num_sampled)


@dataclasses.dataclass(frozen=True)
class SecureAggregation:
    """Pairwise-masked aggregation in Z_{2^32} (Bonawitz et al., 2017;
    honest-but-curious server, no dropout handling).

    Cohort member at position p uploads
    quant(λ'_p m_p) + Σ_{q>p} PRG(s_pq) − Σ_{q<p} PRG(s_qp)  (mod 2^32);
    the server adds the S uploads with int32 wraparound and every mask
    cancels exactly, recovering Σ_p quant(λ'_p m_p) bit-for-bit.  The
    server never sees an individual message — each upload is one-time-
    padded by masks uniform over Z_{2^32}.  Mask streams are keyed on
    cohort *positions*, so the pair-seed exchange involves only the S
    participants of the round.

    ``num_sampled`` — optional partial participation: S of I clients per
    round, drawn into the schedule exactly like :class:`SampledClients`
    (uniform without replacement, sum-combine weights rescaled by I/S,
    unbiased) with pair masking over the cohort members only.  ``None``
    is full participation.

    ``scale_bits`` sets the fixed-point grid 2^-scale_bits; the true
    aggregate must satisfy |Σ λ m| < 2^(31−scale_bits) per entry (2048 at
    the default — comfortable for gradient-scale messages).  Validated at
    construction: at least one integer bit must remain below the sign.

    ``streaming`` selects the fused one-pass implementation (default;
    Pallas kernel on TPU — see :mod:`repro.kernels.secure_agg`) versus
    the mask-materializing reference.  Aggregates are bit-identical.
    """
    scale_bits: int = 20

    streaming: bool = True

    num_sampled: Optional[int] = None

    needs_messages = True

    def __post_init__(self):
        b = self.scale_bits
        if isinstance(b, bool) or not isinstance(b, (int, np.integer)) \
                or not 1 <= int(b) <= 30:
            raise ValueError(
                f"scale_bits={b!r} outside [1, 30]: the int32 fixed point"
                " needs one sign bit and at least one integer bit")
        s = self.num_sampled
        if s is not None and (isinstance(s, bool)
                              or not isinstance(s, (int, np.integer))
                              or int(s) < 1):
            raise ValueError(f"num_sampled={s!r} must be a positive int "
                             "(or None for full participation)")

    def cohort_size(self, num_clients: int) -> int:
        return _validated_cohort(self.num_sampled, num_clients)

    def cohort_weights(self, weights, combine, num_clients):
        # clients apply their own λ'_i before masking; under partial
        # participation λ' carries the same unbiased I/S rescale as
        # SampledClients (each client knows I, S and its own N_i)
        return _cohort_reweight(weights, combine, num_clients,
                                self.cohort_size(num_clients))

    # -- communication-ledger hooks ------------------------------------

    def participants(self, num_clients: int) -> int:
        return self.cohort_size(num_clients)

    def uplink_wire_bytes(self, payload_bytes: int, dense_elements: int,
                          num_clients: int) -> int:
        """Masked uploads travel as the *dense* Z_{2^32} ring element —
        4 bytes per masked entry regardless of the compressor (a sparse
        or b-bit payload cannot stay sparse/narrow under one-time-pad
        masking without revealing the support or the range), plus one
        4-byte pair-seed share per cohort peer per round.  Compression
        still shapes the message *content* (and quantized-on-grid
        uploads make the masked aggregate exact); shrinking secure wire
        bytes needs dimension reduction before masking — which is what
        :mod:`repro.fed.sketch` does: ``dense_elements`` arrives as the
        compressor's declared masked dimension (``wire_elements``, the
        sum over *all* of the round's masked uploads — the sketch's two
        phases contribute rows·cols + k), so a sketched upload is
        charged per sketch bucket, sublinear in the model.  The per-peer
        seed share is charged once per **round**, not per masked upload:
        a multi-phase round derives each phase's mask stream from the
        same exchanged pair secret by domain separation (exactly how the
        engine folds the round key for the sketch's phase 2), so no
        second exchange ever happens."""
        del payload_bytes
        return self.wire_bytes_for_peers(
            dense_elements, self.cohort_size(num_clients) - 1)

    @staticmethod
    def wire_bytes_for_peers(dense_elements: int, peers: int) -> int:
        """The masked-upload wire formula with an explicit peer count —
        the hierarchical tree reuses it with peers = M−1 (group members)
        instead of S−1 (the whole cohort)."""
        return 4 * dense_elements + 4 * peers

    def recovery_bytes_per_drop(self, num_clients: int) -> int:
        """Seed-share recovery wire per dropped slot: each of the S−1
        surviving peers uploads its 4-byte share of the dropped slot's
        pair secret so the server can regenerate (and cancel) the ±PRG
        streams the survivors' uploads still carry."""
        return 4 * (self.cohort_size(num_clients) - 1)

    @staticmethod
    def mask_words(elements: int, cohort: int, shards: int = 1) -> dict:
        """Mask words of a round's combine of ``cohort`` masked uploads of
        ``elements`` entries each, over ``shards`` devices of the client
        axis (the cohort as the engine pads it, a multiple of them).

        ``mask_words_per_round``: what the combine kernel generates
        (:func:`repro.kernels.secure_agg.mask_words`, on every shard).
        ``mask_words_needed_per_round``: the distinct pair words of the
        protocol, a cross-shard pair once on each of its two endpoint
        shards, over the upload's real entries.  The first is never
        below the second: no pair's mask is skipped."""
        s_loc = cohort // shards
        rows = -(-elements // _sa.LANES)
        streams = s_loc * (s_loc - 1) // 2 + s_loc * (cohort - s_loc)
        return {"mask_words_per_round":
                shards * _sa.mask_words(s_loc, cohort, rows),
                "mask_words_needed_per_round": shards * streams * elements}

    def partial_combine(self, wmsgs, key, cohort_offset, cohort_size,
                        alive=None):
        return _kops.secure_quant_sum(
            wmsgs, jax.random.key_data(key), scale_bits=self.scale_bits,
            client_offset=cohort_offset, num_clients=cohort_size,
            alive=alive)

    def finalize_combine(self, partial):
        return _kops.secure_dequantize(partial, self.scale_bits)

    # -- single-host combine -------------------------------------------

    def combine_messages(self, wmsgs, key, alive=None):
        n = jax.tree.leaves(wmsgs)[0].shape[0]
        if self.streaming or alive is not None:
            # dropout recovery always runs the streaming path (the
            # reference predates it; the two are bit-identical anyway)
            return self.finalize_combine(
                self.partial_combine(wmsgs, key, 0, n, alive))
        # the retired O(P·model) mask-materializing path lives with the
        # kernel oracles and is imported only when explicitly requested
        from repro.kernels import ref as _ref
        return _ref.secure_masked_combine(wmsgs, key, self.scale_bits)


@dataclasses.dataclass(frozen=True)
class HierarchicalAggregation:
    """Two-level tree combine: clients → G edge aggregators → server.

    Wraps any inner aggregation.  The round's S cohort members are
    blocked into G groups of M = ⌈S/G⌉ (a seed-stable per-round
    permutation drawn in the schedule — :func:`repro.data.partition.
    sample_groups`); each group runs the *inner* combine over its M
    members (level 1), and the G group partials are merged by a second
    combine at the root (level 2).  Root ingest and root-visible mask
    state drop from O(S) to O(G); each client's pair-seed state drops
    from O(S) to O(M).

    Bit-identity — the whole point of the construction:

    * secure inner: level 1 is the Bonawitz masked sum over the group
      (per-group mask streams, key folded with the *global* group id so
      no two groups ever share a stream), producing an int32 ring
      partial; level 2 re-masks those partials **directly in Z_{2^32}**
      (:func:`repro.kernels.ops.secure_ring_partial_sum`, streams
      domain-separated by the kernel's group tag) — no dequantize/
      requantize round trip.  Since mod-2^32 addition is exactly
      associative and every mask cancels at its level, the root equals
      the flat masked sum *bit-for-bit*.
    * linear inner (plain / sampled): level 2 is a plain sum of group
      sums — identical to the flat sum whenever the float additions are
      exact (e.g. on-grid messages), and the trajectory-level contract
      is the same regrouping-of-a-sum argument.

    Level-2 dispatch is by *dtype*: int32 group partials (any ring-
    -quantizing inner) get the masked ring merge, float partials a plain
    sum — so the combinator composes with future inner strategies
    without knowing their class.

    ``groups=1`` degenerates to the inner aggregation (one group holding
    the whole cohort, level 2 a no-op sum over one row).  Nesting
    ``Hierarchical`` inside ``Hierarchical`` is rejected — the mesh and
    the PRF domain separation are built for exactly two levels.
    """
    inner: Any
    groups: int

    needs_messages = True

    def __post_init__(self):
        g = self.groups
        if isinstance(g, bool) or not isinstance(g, (int, np.integer)) \
                or int(g) < 1:
            raise ValueError(f"groups={g!r} must be a positive int")
        if isinstance(self.inner, HierarchicalAggregation):
            raise ValueError("Hierarchical(Hierarchical(...)) is not "
                             "supported: the tree has exactly two levels")

    # -- delegation: who participates and with what weights ------------

    def cohort_size(self, num_clients: int) -> int:
        s = self.inner.cohort_size(num_clients)
        if self.groups > s:
            raise ValueError(
                f"groups={self.groups} exceeds the cohort size {s}")
        return s

    def cohort_weights(self, weights, combine, num_clients):
        return self.inner.cohort_weights(weights, combine, num_clients)

    @property
    def scale_bits(self):
        """The inner fixed-point grid (None for linear inners) — exposed
        so the engine's compressor/aggregation grid check sees through
        the tree."""
        return getattr(self.inner, "scale_bits", None)

    def members(self, num_clients: int) -> int:
        """M, the per-group member count: ⌈S/G⌉ (the last group is
        sentinel-padded when G ∤ S)."""
        s = self.cohort_size(num_clients)
        return -(-s // self.groups)

    def _ring_inner(self) -> bool:
        return getattr(self.inner, "scale_bits", None) is not None

    # -- the tree ------------------------------------------------------

    def tree_combine(self, grouped: PyTree, key, *, group_offset=0,
                     member_offset=0, members: Optional[int] = None,
                     num_groups: Optional[int] = None,
                     reduce_members=None, reduce_groups=None,
                     alive=None) -> PyTree:
        """The two-level combine over group-blocked messages.

        ``grouped`` leaves carry a leading (G_loc, M_loc, ...) — the
        local slice of the (G, M) grid.  Level 1 runs the inner
        ``partial_combine`` per group row with the round key folded by
        the **global** group id (member positions [member_offset,
        member_offset + M_loc) of ``members``); ``reduce_members`` (the
        engine's psum over the mesh's "clients" axis, or None when every
        member is local) completes the group sums.  Level 2 merges the
        local group rows — masked in the ring for int32 partials, plain
        sum for float — and ``reduce_groups`` (psum over "groups")
        completes the root.  Returns the *pre-finalize* aggregate, same
        contract as ``partial_combine``.

        ``alive`` (optional (G_loc, M) 0/1 rows) is dropout recovery with
        a per-group blast radius: a dropped member's masks only ever
        involve its M−1 group peers, so cancellation happens inside the
        group's level-1 combine and no other group is touched.  Edge
        aggregators are servers and never drop, so level 2 needs none.
        """
        g_loc = jax.tree.leaves(grouped)[0].shape[0]
        m = jax.tree.leaves(grouped)[0].shape[1] if members is None \
            else int(members)
        gids = jnp.arange(g_loc, dtype=jnp.uint32) \
            + jnp.asarray(group_offset).astype(jnp.uint32)

        # lax.scan, not vmap: the inner masked sum pushes its uploads
        # through optimization_barrier (no batching rule), and scan also
        # keeps the trace O(1) in the local group count
        def one_group(_, xs):
            if alive is None:
                rows, gid = xs
                row_alive = None
            else:
                rows, gid, row_alive = xs
            return None, self.inner.partial_combine(
                rows, jax.random.fold_in(key, gid), member_offset, m,
                alive=row_alive)

        xs = (grouped, gids) if alive is None else (grouped, gids, alive)
        _, level1 = jax.lax.scan(one_group, None, xs)

        ng = self.groups if num_groups is None else int(num_groups)
        if reduce_members is not None:
            level1 = reduce_members(level1)
        if all(x.dtype == jnp.int32 for x in jax.tree.leaves(level1)):
            partial = _kops.secure_ring_partial_sum(
                level1, jax.random.key_data(key),
                group_offset=group_offset, num_groups=ng)
        else:
            partial = _sum_clients(level1)
        if reduce_groups is not None:
            partial = reduce_groups(partial)
        return partial

    def _group(self, wmsgs: PyTree, cohort: int) -> PyTree:
        """(S, ...) leaves → (G, M, ...): zero-pad the cohort axis to
        G·M (sentinel members — quantize to 0, masks still cancel) and
        block contiguously.  The schedule's group permutation has
        already reordered the cohort, so blocking is a reshape."""
        g = self.groups
        m = -(-cohort // g)
        pad = g * m - cohort

        def blk(x):
            if pad:
                x = jnp.concatenate(
                    [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
            return x.reshape(g, m, *x.shape[1:])

        return jax.tree.map(blk, wmsgs)

    def _group_alive(self, alive, cohort: int):
        """(S,) alive bits → (G, M) rows.  Sentinel pads stay alive=1:
        their uploads are exact zeros either way, and keeping their mask
        streams live means the padded group's combine stays bit-identical
        to the unpadded protocol (all pad masks cancel in the total)."""
        g = self.groups
        m = -(-cohort // g)
        pad = g * m - cohort
        alive = alive.astype(jnp.int32)
        if pad:
            alive = jnp.concatenate([alive, jnp.ones((pad,), jnp.int32)])
        return alive.reshape(g, m)

    def partial_combine(self, wmsgs, key, cohort_offset, cohort_size,
                        alive=None):
        if not (isinstance(cohort_offset, int) and cohort_offset == 0):
            raise ValueError(
                "HierarchicalAggregation only decomposes over a 2-D "
                "(groups, clients) mesh (launch.mesh.make_group_mesh); "
                "a flat cohort shard cannot host the two reductions")
        del cohort_size
        s = jax.tree.leaves(wmsgs)[0].shape[0]
        if alive is not None:
            alive = self._group_alive(alive, s)
        return self.tree_combine(self._group(wmsgs, s), key, alive=alive)

    def finalize_combine(self, partial):
        return self.inner.finalize_combine(partial)

    def combine_messages(self, wmsgs, key, alive=None):
        return self.finalize_combine(self.partial_combine(wmsgs, key, 0,
                                                          None, alive))

    # -- communication-ledger hooks ------------------------------------

    def participants(self, num_clients: int) -> int:
        return self.inner.participants(num_clients)

    def uplink_wire_bytes(self, payload_bytes: int, dense_elements: int,
                          num_clients: int) -> int:
        """Per-client wire under the tree: a secure inner exchanges pair
        seeds with its M−1 *group* peers only (O(S/G), not O(S)); the
        masked payload itself is unchanged.  Linear inners are untouched
        by grouping."""
        if self._ring_inner():
            return self.inner.wire_bytes_for_peers(
                dense_elements, self.members(num_clients) - 1)
        return self.inner.uplink_wire_bytes(payload_bytes, dense_elements,
                                            num_clients)

    def recovery_bytes_per_drop(self, num_clients: int) -> int:
        """Group-local seed-share recovery: only the dropped slot's M−1
        group peers hold shares of its pair secret — the blast radius of
        a drop is one group, not the cohort."""
        if not self._ring_inner():
            return self.inner.recovery_bytes_per_drop(num_clients)
        return 4 * (self.members(num_clients) - 1)

    def group_uplink_bytes(self, payload_bytes: int, dense_elements: int,
                           num_clients: int) -> int:
        """Level-2 wire: each of the G edge aggregators uploads one
        group partial to the root — a dense ring element plus G−1 group-
        level pair seeds for a secure inner, the plain payload
        otherwise.  This is also the root's ingest."""
        del num_clients
        if self._ring_inner():
            return self.groups * self.inner.wire_bytes_for_peers(
                dense_elements, self.groups - 1)
        return self.groups * payload_bytes

    # -- bench bookkeeping ---------------------------------------------

    def mask_pair_count(self, num_clients: int) -> int:
        """Live pair-mask streams per round: G·M(M−1)/2 within groups
        plus G(G−1)/2 across them (0 for a maskless inner).  Flat secure
        holds S(S−1)/2."""
        if not self._ring_inner():
            return 0
        g, m = self.groups, self.members(num_clients)
        return g * (m * (m - 1) // 2) + g * (g - 1) // 2

    def root_ingest_bytes(self, dense_elements: int,
                          num_clients: int) -> int:
        """Bytes crossing into the root per round: G group partials
        (4-byte ring words / f32) instead of S client uploads."""
        del num_clients
        return self.groups * 4 * dense_elements


def plain() -> PlainAggregation:
    return PlainAggregation()


def secure(scale_bits: int = 20, streaming: bool = True,
           num_sampled: Optional[int] = None) -> SecureAggregation:
    return SecureAggregation(scale_bits=scale_bits, streaming=streaming,
                             num_sampled=num_sampled)


def sampled(num_sampled: int) -> SampledClients:
    return SampledClients(num_sampled=num_sampled)


def hierarchical(inner: Optional[Any] = None,
                 groups: int = 16) -> HierarchicalAggregation:
    """Two-level tree over ``inner`` (default: streaming secure)."""
    return HierarchicalAggregation(
        inner=secure() if inner is None else inner, groups=groups)
