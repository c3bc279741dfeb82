"""Hierarchical (two-level tree) aggregation invariants.

The combinator's contract is *exact regrouping*: for every inner
aggregation, blocking the cohort into G groups, combining within groups
and merging the group partials returns the flat combine bit-for-bit —
in Z_{2^32} because mod-2^32 addition is exactly associative and every
mask cancels at its own level, in float on on-grid (integer × 2^-20)
messages because those sums are exact.  The mask streams of the two
levels must be domain-separated (no (seed, counter) reuse), and the
ledger must charge the tree's wire — O(S/G) peers per client plus an
O(G) edge-to-root hop — exactly.  Mesh == single-device bit-identity
lives in ``tests/sharded_engine_check.py``.

The regrouping and domain-separation properties run twice: always on a
deterministic (S, n, G, seed) grid, and — when hypothesis is installed
(CI) — fuzzed over the full parameter space.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover - env-dependent
    HAVE_HYPOTHESIS = False

from repro.data.partition import sample_groups
from repro.fed import aggregation as ag
from repro.fed import compression, runtime
from repro.fed import sketch as fsk
from repro.kernels import ops as kops
from repro.kernels import secure_agg as sa

SETTINGS = dict(max_examples=15, deadline=None)
SCALE = 2.0 ** -20

# (s, n, groups, seed): G = 1, G = S, G | S, G ∤ S, scan-path S > 16
GRID = [(2, 7, 1, 0), (5, 3, 2, 1), (10, 16, 4, 2), (13, 37, 5, 3),
        (8, 5, 8, 4), (21, 12, 4, 5)]


def _grid_msgs(rng, s, n):
    """Messages exactly representable on the 2^-20 fixed-point grid —
    float sums of these are exact, so bit-equality is meaningful for
    linear inners too."""
    return {"w": jnp.asarray(rng.integers(-4000, 4001, (s, n)) * SCALE,
                             jnp.float32),
            "b": jnp.asarray(rng.integers(-4000, 4001, (s, max(1, n // 2)))
                             * SCALE, jnp.float32)}


def _assert_tree_equals_flat(inner, s, n, groups, seed):
    rng = np.random.default_rng(seed)
    msgs = _grid_msgs(rng, s, n)
    key = jax.random.key(seed)
    flat = inner.combine_messages(msgs, key)
    tree = ag.HierarchicalAggregation(inner=inner, groups=groups) \
        .combine_messages(msgs, key)
    for a, b in zip(jax.tree.leaves(flat), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _assert_sketch_tree_equals_flat(s, groups, seed):
    """Sketched wire under the tree: count-sketch tables are ring-linear
    messages, so the grouped masked sketch sum equals the flat one
    bit-for-bit (the PR 6 property, preserved through the hierarchy)."""
    rng = np.random.default_rng(seed)
    comp = fsk.sketch(rows=2, cols=64, fraction=0.1, keep=8)
    inp = {"w": jnp.asarray(rng.integers(-4000, 4001, (s, 50)) * SCALE,
                            jnp.float32)}
    cids = jnp.arange(s, dtype=jnp.uint32)
    sk = jax.vmap(lambda m, c: comp.encode(m, jnp.uint32(seed),
                                           jnp.uint32(seed ^ 0xA5), c)
                  )(inp, cids)
    key = jax.random.key(seed)
    flat = ag.secure().combine_messages(sk, key)
    tree = ag.hierarchical(ag.secure(), groups=groups) \
        .combine_messages(sk, key)
    for a, b in zip(jax.tree.leaves(flat), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestGroupedEqualsFlat:
    @pytest.mark.parametrize("s,n,groups,seed", GRID)
    def test_secure_inner_bitwise(self, s, n, groups, seed):
        _assert_tree_equals_flat(ag.secure(), s, n, groups, seed)

    @pytest.mark.parametrize("s,n,groups,seed", GRID)
    def test_plain_inner_bitwise(self, s, n, groups, seed):
        _assert_tree_equals_flat(ag.plain(), s, n, groups, seed)

    @pytest.mark.parametrize("s,groups,seed",
                             [(4, 2, 0), (9, 3, 1), (10, 4, 2)])
    def test_sketch_messages_bitwise(self, s, groups, seed):
        _assert_sketch_tree_equals_flat(s, groups, seed)

    def test_ring_partial_sum_masks_cancel(self):
        """Sharded level 2: the masked ring partials of disjoint group
        shards sum to the plain int32 sum — every group-level mask
        cancels exactly across shards."""
        rng = np.random.default_rng(7)
        q = {"p": jnp.asarray(rng.integers(-2**30, 2**30, (6, 17)),
                              jnp.int32)}
        kd = jax.random.key_data(jax.random.key(3))
        whole = kops.secure_ring_partial_sum(q, kd, group_offset=0,
                                             num_groups=6)
        lo = kops.secure_ring_partial_sum(
            jax.tree.map(lambda x: x[:2], q), kd, group_offset=0,
            num_groups=6)
        hi = kops.secure_ring_partial_sum(
            jax.tree.map(lambda x: x[2:], q), kd, group_offset=2,
            num_groups=6)
        np.testing.assert_array_equal(np.asarray(whole["p"]),
                                      np.asarray(lo["p"] + hi["p"]))
        np.testing.assert_array_equal(
            np.asarray(whole["p"]),
            np.sum(np.asarray(q["p"], np.int64), 0).astype(np.int32))


if HAVE_HYPOTHESIS:
    class TestGroupedEqualsFlatFuzzed:
        @given(s=st.integers(2, 24), n=st.integers(1, 40),
               groups=st.integers(1, 24), seed=st.integers(0, 2**16))
        @settings(**SETTINGS)
        def test_secure_inner_bitwise(self, s, n, groups, seed):
            _assert_tree_equals_flat(ag.secure(), s, n, min(groups, s),
                                     seed)

        @given(s=st.integers(2, 24), n=st.integers(1, 40),
               groups=st.integers(1, 24), seed=st.integers(0, 2**16))
        @settings(**SETTINGS)
        def test_plain_inner_bitwise(self, s, n, groups, seed):
            _assert_tree_equals_flat(ag.plain(), s, n, min(groups, s),
                                     seed)

        @given(s=st.integers(2, 12), groups=st.integers(2, 12),
               seed=st.integers(0, 2**16))
        @settings(max_examples=8, deadline=None)
        def test_sketch_messages_bitwise(self, s, groups, seed):
            _assert_sketch_tree_equals_flat(s, min(groups, s), seed)


def _assert_levels_domain_separated(k0, k1, lo, hi):
    """No counter reuse across levels: for the same (lo, hi) id pair the
    group-tagged key words yield a different pair seed — and a different
    mask stream — than the client-level round key, so a group partial's
    masks can never be differenced against any client upload of the
    same round."""
    k0u, k1u = np.uint32(k0), np.uint32(k1)
    gk0, gk1 = sa.group_key_words(k0u, k1u)
    s_client = sa.pair_seed(k0u, k1u, np.uint32(lo), np.uint32(hi))
    s_group = sa.pair_seed(np.asarray(gk0), np.asarray(gk1),
                           np.uint32(lo), np.uint32(hi))
    assert int(s_client) != int(s_group)
    counters = jnp.arange(32, dtype=jnp.uint32)
    assert not bool(jnp.all(
        sa.mask_bits(jnp.uint32(s_client), counters)
        == sa.mask_bits(jnp.uint32(s_group), counters)))


class TestDomainSeparation:
    @pytest.mark.parametrize("k0,k1,lo,hi",
                             [(0, 0, 0, 1), (1234, 5678, 3, 7),
                              (2**32 - 1, 17, 0, 63),
                              (0xDEADBEEF, 0xC0FFEE, 5, 6)])
    def test_group_level_seeds_disjoint(self, k0, k1, lo, hi):
        _assert_levels_domain_separated(k0, k1, lo, hi)

    def test_per_group_level1_keys_distinct(self):
        """Level-1 streams are keyed per *global* group id (fold_in of
        the round key): distinct groups never share a mask stream even
        at identical member positions."""
        key = jax.random.key(11)
        kds = [tuple(int(w) for w in np.asarray(
                   jax.random.key_data(jax.random.fold_in(key, g)))
                   .reshape(-1)) for g in range(8)]
        assert len(set(kds)) == 8
        # and none equals the round key itself (whose tagged transform
        # keys level 2)
        assert tuple(int(w) for w in
                     np.asarray(jax.random.key_data(key)).reshape(-1)) \
            not in set(kds)

    def test_group_tag_mixes_both_words(self):
        gk0, gk1 = sa.group_key_words(np.uint32(1234), np.uint32(5678))
        assert int(gk0) != 1234 and int(gk1) != 5678


if HAVE_HYPOTHESIS:
    class TestDomainSeparationFuzzed:
        @given(k0=st.integers(0, 2**32 - 1), k1=st.integers(0, 2**32 - 1),
               lo=st.integers(0, 63), span=st.integers(1, 64))
        @settings(**SETTINGS)
        def test_group_level_seeds_disjoint(self, k0, k1, lo, span):
            _assert_levels_domain_separated(k0, k1, lo, lo + span)


class TestGroupDraw:
    def test_permutation_seed_stable_and_valid(self):
        a = sample_groups(10, 3, np.arange(1, 5, dtype=np.int64), seed=9)
        b = sample_groups(10, 3, np.arange(1, 5, dtype=np.int64), seed=9)
        np.testing.assert_array_equal(a, b)
        assert a.shape == (4, 10)
        for row in a:
            np.testing.assert_array_equal(np.sort(row), np.arange(10))

    def test_groups_one_is_identity(self):
        a = sample_groups(6, 1, np.arange(1, 4, dtype=np.int64), seed=0)
        np.testing.assert_array_equal(
            a, np.broadcast_to(np.arange(6), (3, 6)))

    def test_rounds_differ(self):
        a = sample_groups(32, 4, np.arange(1, 9, dtype=np.int64), seed=0)
        assert any(not np.array_equal(a[0], a[t]) for t in range(1, 8))


class TestLedger:
    def test_tree_wire_arithmetic(self):
        """Hand-computed: S=12, G=4 → M=3.  Per-client secure wire is
        4·dense + 4·(M−1); the edge-to-root hop is G·(4·dense + 4·(G−1));
        pair state is G·M(M−1)/2 + G(G−1)/2; root ingest is G·4·dense."""
        h = ag.hierarchical(ag.secure(num_sampled=12), groups=4)
        dense = 10
        assert h.members(12) == 3
        assert h.uplink_wire_bytes(0, dense, 12) == 4 * 10 + 4 * 2  # 48
        assert ag.secure(num_sampled=12).uplink_wire_bytes(0, dense, 12) \
            == 4 * 10 + 4 * 11                                      # 84
        assert h.group_uplink_bytes(0, dense, 12) \
            == 4 * (4 * 10 + 4 * 3)                                 # 208
        assert h.mask_pair_count(12) == 4 * 3 + 6                   # 18
        assert h.root_ingest_bytes(dense, 12) == 4 * 4 * 10         # 160

    def test_plain_inner_untouched(self):
        h = ag.hierarchical(ag.plain(), groups=4)
        assert h.uplink_wire_bytes(777, 10, 12) == 777
        assert h.group_uplink_bytes(777, 10, 12) == 4 * 777
        assert h.mask_pair_count(12) == 0

    def test_round_bytes_totals(self):
        """The engine ledger charges S per-client uploads at the group
        peer count plus one edge-to-root hop, exactly.  Hand-computed:
        dense = 103, S = 12, G = 4, M = 3 → per-client 4·103 + 4·2 = 420,
        edge hop 4·(4·103 + 4·3) = 1696, total 12·420 + 1696 = 6736."""
        from repro.core import protocol, ssca
        params = {"w": jnp.zeros((100,)), "b": jnp.zeros((3,))}
        alg = protocol.SSCAUnconstrained(loss_fn=None,
                                         hp=ssca.SSCAHyperParams())
        h = ag.hierarchical(ag.secure(num_sampled=12), groups=4)
        rb = compression.round_bytes(alg, h, None, params, 100)
        assert rb.uplink_per_client == 4 * 103 + 4 * 2
        assert rb.breakdown["group_uplink_bytes"] == 4 * (4 * 103 + 4 * 3)
        assert rb.uplink_total == 12 * 420 + 1696
        assert rb.participants == 12

    def test_flat_round_bytes_have_no_group_hop(self):
        from repro.core import protocol, ssca
        params = {"w": jnp.zeros((100,)), "b": jnp.zeros((3,))}
        alg = protocol.SSCAUnconstrained(loss_fn=None,
                                         hp=ssca.SSCAHyperParams())
        rb = compression.round_bytes(alg, ag.secure(num_sampled=12), None,
                                     params, 100)
        assert rb.breakdown["group_uplink_bytes"] == 0
        assert rb.uplink_total == rb.uplink_per_client * 12

    def test_tree_cuts_root_ingest_and_mask_pairs_at_s512(self):
        """A cohort of 512 in 16 groups of 32, the paper's MLP: the root
        takes 16 group partials in place of 512 uploads, and the live pair
        masks fall from 512·511/2 = 130,816 to 16·(32·31/2) + 16·15/2 =
        8,056.  Each count must fall at least 4×."""
        from repro.core import protocol, ssca
        params = {"w1": jnp.zeros((784, 128)), "w2": jnp.zeros((128, 10))}
        alg = protocol.SSCAUnconstrained(loss_fn=None,
                                         hp=ssca.SSCAHyperParams())
        pop = 100_000
        flat = ag.secure(num_sampled=512)
        tree = ag.hierarchical(ag.secure(num_sampled=512), groups=16)
        rb_flat = compression.round_bytes(alg, flat, None, params, pop)
        rb_tree = compression.round_bytes(alg, tree, None, params, pop)
        dense = rb_flat.breakdown["upload_elements"]
        assert dense == 784 * 128 + 128 * 10
        # what reaches the root: every masked upload (flat), or the edge
        # aggregators' hop, whose dense part is root_ingest_bytes (tree)
        flat_ingest = rb_flat.uplink_total
        tree_ingest = rb_tree.breakdown["group_uplink_bytes"]
        assert tree_ingest == tree.root_ingest_bytes(dense, pop) \
            + 16 * 4 * 15
        assert flat_ingest >= 4 * tree_ingest, (flat_ingest, tree_ingest)
        flat_pairs = flat.mask_words(dense, 512)[
            "mask_words_needed_per_round"] // dense
        tree_pairs = tree.mask_pair_count(pop)
        assert (flat_pairs, tree_pairs) == (130_816, 8_056)
        assert flat_pairs >= 4 * tree_pairs


class TestValidation:
    def test_groups_must_be_positive_int(self):
        with pytest.raises(ValueError):
            ag.hierarchical(ag.secure(), groups=0)
        with pytest.raises(ValueError):
            ag.HierarchicalAggregation(inner=ag.secure(), groups=True)

    def test_no_nesting(self):
        with pytest.raises(ValueError):
            ag.hierarchical(ag.hierarchical(ag.secure(), groups=2),
                            groups=2)

    def test_groups_cannot_exceed_cohort(self):
        h = ag.hierarchical(ag.secure(num_sampled=4), groups=8)
        with pytest.raises(ValueError):
            h.cohort_size(100)

    def test_scale_bits_sees_through(self):
        assert ag.hierarchical(ag.secure(scale_bits=18), groups=2) \
            .scale_bits == 18
        assert ag.hierarchical(ag.plain(), groups=2).scale_bits is None


class TestEngineBitIdentity:
    def test_hier_secure_equals_flat_secure_final_params(self):
        """The acceptance invariant, single-device: the full engine run
        under Hierarchical(secure(), G) — permuted cohorts, per-group
        masked sums, ring-masked level 2 — lands on bit-identical final
        parameters to flat secure, G ∤ S included."""
        from repro.data import partition, synthetic
        data = synthetic.classification_dataset(n_train=400, n_test=100,
                                                seed=0)
        part = partition.iid(400, 8, seed=0)
        kw = dict(batch_size=5, rounds=4, eval_every=2, eval_samples=100,
                  seed=3)
        p_flat, _ = runtime.run_alg1(data, part, secure=True, **kw)
        for g in (2, 3):                       # 3 ∤ 8: padded last group
            p_h, _ = runtime.run_alg1(
                data, part,
                aggregation=ag.hierarchical(ag.secure(), groups=g), **kw)
            for a, b in zip(jax.tree.leaves(p_flat),
                            jax.tree.leaves(p_h)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
