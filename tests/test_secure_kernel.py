"""Streaming secure-aggregation kernel: bit-exactness and edge cases.

Every implementation — the Pallas kernel (interpret mode on CPU), the
XLA streaming paths (pairwise full-view and directed shard-local), and
the PR-1 mask-materializing reference — must return the *bit-identical*
aggregate: addition mod 2^32 is exactly associative/commutative, so mask
cancellation leaves precisely Σ_i quant(m_i) regardless of formulation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fed import aggregation
from repro.kernels import ops, secure_agg


def _alg2_messages(key, n):
    """(value, gradient) pytree shaped like a secure Algorithm-2 upload,
    with deliberately awkward leaf sizes (odd, prime, scalar-per-client)
    so the flatten+pad path is exercised."""
    ks = jax.random.split(key, 4)
    return (jax.random.normal(ks[0], (n,)),                  # scalar leaf
            {"w1": jax.random.normal(ks[1], (n, 7, 13)),     # 91: odd
             "w2": jax.random.normal(ks[2], (n, 3)),
             "w3": jax.random.normal(ks[3], (n, 257))})      # prime > 128


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _mlp_messages(key, n):
    """Uploads of the paper's MLP: 101,632 entries, 794 rows of 128 —
    not a multiple of any tile the kernel's plan picks."""
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (n, 784, 128)),
            "w2": jax.random.normal(k2, (n, 128, 10))}


def _lm_messages(key, n):
    """Uploads shaped like a decoder's leaves: wide leaves the kernel
    reads in their own (rows, D) views, D a multiple of 128 above 128,
    with a leaf between them that it concatenates, so the message is cut
    into five calls at nonzero counter bases."""
    ks = jax.random.split(key, 5)
    return (jax.random.normal(ks[0], (n, 1, 256)),            # a norm gain
            jax.random.normal(ks[1], (n, 256, 384)),
            jax.random.normal(ks[2], (n, 7, 13)),             # concatenated
            jax.random.normal(ks[3], (n, 384, 256)),
            jax.random.normal(ks[4], (n, 256)))


def _alive(n, dropped):
    return jnp.asarray([0 if i in dropped else 1 for i in range(n)],
                       jnp.int32)


# the awkward leaves flatten to 3 rows (below a single tile), the MLP to
# 794 rows (two tiles of 400, or 20 of 40, with the last one partial)
@pytest.mark.parametrize(
    "n,make",
    [pytest.param(n, _alg2_messages, id=str(n)) for n in (1, 2, 5, 8, 17)]
    + [pytest.param(10, _mlp_messages, id="10-794rows"),
       pytest.param(5, _lm_messages, id="5-lm-own-views")])
def test_kernel_bit_exact_vs_reference(n, make):
    """Pallas kernel (interpret), XLA streaming, and the reference
    mask-materializing path agree bit-for-bit — including I=1 (no pairs)
    and the odd-leaf padding cases."""
    msgs = make(jax.random.key(0), n)
    key = jax.random.key(11)
    ref = aggregation.secure(streaming=False).combine_messages(msgs, key)
    stream = aggregation.secure().combine_messages(msgs, key)
    kd = jax.random.key_data(key)
    krn = ops.secure_dequantize(
        ops.secure_quant_sum(msgs, kd, scale_bits=20, interpret=True), 20)
    _assert_tree_equal(ref, stream)
    _assert_tree_equal(ref, krn)


@pytest.mark.parametrize("n,offsets,dropped,make", [
    pytest.param(6, (0, 4), (), _alg2_messages, id="6-split4"),
    pytest.param(17, (0, 5, 12), (0, 16), _mlp_messages,
                 id="17-3shards-drop2"),
    pytest.param(5, (0, 2), (3,), _alg2_messages, id="5-split2-drop1"),
    pytest.param(6, (0, 1, 4), (2,), _lm_messages,
                 id="6-lm-3shards-drop1"),
])
def test_kernel_bit_exact_vs_xla_partials_across_shards(n, offsets, dropped,
                                                        make):
    """Shard-local partial sums (kernel and XLA directed paths) combine
    by plain int32 addition to the full-view aggregate bit-for-bit —
    cross-shard pair masks are regenerated identically on both endpoint
    devices (counter-mode streams) and cancel in the combine, and the
    kernel memoizes only the pairs within a shard.  Each shard's kernel
    partial, which still carries its masks, equals the XLA path's on the
    flattened message: every element is masked at its flat index."""
    msgs = make(jax.random.key(2), n)
    kd = jax.random.key_data(jax.random.key(3))
    alive = _alive(n, dropped) if dropped else None
    full = ops.secure_quant_sum(msgs, kd, scale_bits=20, use_kernel=False,
                                alive=alive)
    bounds = list(offsets) + [n]
    parts = {}
    for interpret in (False, True):
        parts[interpret] = [ops.secure_quant_sum(
            jax.tree.map(lambda m: m[lo:hi], msgs), kd, scale_bits=20,
            client_offset=lo, num_clients=n, alive=alive, use_kernel=False,
            interpret=interpret) for lo, hi in zip(bounds, bounds[1:])]
        _assert_tree_equal(full,
                           jax.tree.map(lambda *p: sum(p), *parts[interpret]))
    for xla, kernel in zip(parts[False], parts[True]):
        _assert_tree_equal(xla, kernel)


def _explicit_plan_partials(width, budget, base, memo):
    """Each shard's kernel partial under the plan ``budget`` gives, for a
    (20, ``width``) message at counter base ``base``, against the XLA
    directed path on the flat message those counters index (``base``
    zeros in front)."""
    n, offsets, dropped = 10, (0, 3, 7), (2, 9)
    rows = 20                                   # three 8-row tiles
    msgs = jax.random.normal(jax.random.key(4), (n, rows, width))
    kd = jnp.asarray(jax.random.key_data(jax.random.key(5)), jnp.uint32)
    alive = _alive(n, dropped) if dropped else None
    bounds = list(offsets) + [n]
    for lo, hi in zip(bounds, bounds[1:]):
        plan = secure_agg._plan(hi - lo, n, rows, width, budget=budget)
        assert plan.memo == memo and plan.tile == 8 and plan.cols == 128
        scalars = [kd, jnp.asarray([lo], jnp.uint32)]
        if alive is not None:
            scalars.append(alive.astype(jnp.uint32))
        got = secure_agg._masked_sum(
            msgs[lo:hi], jnp.concatenate(scalars), plan, scale_bits=20,
            num_clients=n, interpret=True, with_alive=alive is not None,
            counter_base=base)
        flat = jnp.pad(msgs[lo:hi].reshape(hi - lo, -1), ((0, 0), (base, 0)))
        want = secure_agg.masked_partial_sum_flat(flat, kd, 20, lo, n,
                                                  alive)[base:]
        np.testing.assert_array_equal(np.asarray(got).reshape(-1),
                                      np.asarray(want))


@pytest.mark.parametrize("width,base", [(128, 0), (384, 1000)],
                         ids=["128", "384-base1000"])
def test_directed_fallback_bit_exact(width, base):
    """The directed schedule, which the plan keeps for a cohort whose
    pending uploads would not fit VMEM (reached here with a budget of
    0), gives the same partials as the XLA directed path — also over
    three 128-lane column blocks of a wider message at a counter base."""
    _explicit_plan_partials(width, 0, base, memo=False)


def test_memoized_blocks_bit_exact_in_own_view():
    """The memoized schedule over 8-row, 128-lane blocks of a 384-wide
    message (a budget that fits one such block beside the pending
    uploads) at a counter base gives the XLA directed path's partials."""
    _explicit_plan_partials(384, (4 + 4) * 8 * 128 * 4, 1000, memo=True)


@pytest.mark.parametrize("i_loc", [10, 512])
def test_plan_covers_the_mlp_with_at_most_800_rows(i_loc):
    """At the MLP's 794 rows the plan pads to 800, keeps its VMEM within
    the budget, and generates each pair word once: what the engine's
    ledger records, no less than the protocol needs."""
    rows, n = 794, 101_632
    plan = secure_agg._plan(i_loc, i_loc, rows)
    assert plan.memo and plan.tile % 8 == 0 and plan.tile % plan.chunk == 0
    assert -(-rows // plan.tile) * plan.tile <= 800
    assert plan.vmem_bytes <= secure_agg.VMEM_BUDGET
    words = aggregation.SecureAggregation.mask_words(
        [(784, 128), (128, 10)], i_loc)
    assert plan.words == words["mask_words_per_round"]
    needed = words["mask_words_needed_per_round"]
    assert needed == i_loc * (i_loc - 1) // 2 * n
    assert needed <= plan.words <= 1.01 * needed


def _kernel_messages(fn, *args):
    """The message shape of every masked_sum_2d call that tracing ``fn``
    makes."""
    shapes = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.params.get("name") == "masked_sum_2d":
                shapes.append(eqn.invars[0].aval.shape)
            for sub in eqn.params.values():
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    walk(getattr(sub, "jaxpr", sub))

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return shapes


@pytest.mark.parametrize("i_loc", [10, 512])
def test_mlp_message_makes_one_kernel_call(i_loc):
    """The MLP's two leaves (784, 128) and (128, 10) need no relayout:
    they stay one concatenated call of 794 rows of 128 at counter base
    0, under the plan the MLP cells have always run."""
    msgs = jax.eval_shape(lambda: _mlp_messages(jax.random.key(0), i_loc))
    kd = jax.random.key_data(jax.random.key(1))
    calls = ops.combine_calls([m.shape[1:] for m in msgs.values()])
    assert calls == [ops.CombineCall(range(0, 2), 0, 101_632, 794, 128,
                                     True)]
    assert _kernel_messages(
        lambda m: ops.secure_quant_sum(m, kd, scale_bits=20,
                                       use_kernel=True), msgs) \
        == [(i_loc, 794, 128)]
    words = aggregation.SecureAggregation.mask_words(
        [(784, 128), (128, 10)], i_loc)
    assert words["mask_words_per_round"] \
        == secure_agg._plan(i_loc, i_loc, 794).words
    assert words["combine_flat_elements_per_round"] == i_loc * 101_632


@pytest.mark.parametrize("shards", [1, 2])
def test_mask_words_of_an_lm_upload_sum_its_kernel_calls(shards):
    """An LM-shaped upload's ledger counts the words of the kernel calls
    the combine actually makes, one a wide leaf and one for the leaf
    between them, and the concatenated entries: the (7, 13) leaf's 91."""
    cohort = 4
    msgs = jax.eval_shape(lambda: _lm_messages(jax.random.key(0),
                                               cohort // shards))
    kd = jax.random.key_data(jax.random.key(1))
    traced = _kernel_messages(
        lambda m: ops.secure_quant_sum(m, kd, scale_bits=20,
                                       client_offset=0, num_clients=cohort,
                                       use_kernel=True), msgs)
    s_loc = cohort // shards
    assert traced == [(s_loc, 1, 256), (s_loc, 256, 384), (s_loc, 1, 128),
                      (s_loc, 384, 256), (s_loc, 1, 256)]
    words = aggregation.SecureAggregation.mask_words(
        [m.shape[1:] for m in msgs], cohort, shards)
    assert words["mask_words_per_round"] == shards * sum(
        secure_agg._plan(s_loc, cohort, rows, width).words
        for _, rows, width in traced)
    n = sum(int(np.prod(m.shape[1:])) for m in msgs)
    streams = s_loc * (s_loc - 1) // 2 + s_loc * (cohort - s_loc)
    assert words["mask_words_needed_per_round"] == shards * streams * n
    assert words["mask_words_per_round"] \
        >= words["mask_words_needed_per_round"]
    assert words["combine_flat_elements_per_round"] == cohort * 91


def test_plan_takes_the_directed_schedule_only_past_the_budget():
    """The memoized schedule holds while an 8-row pending scratch fits
    (about 3,000 local clients at the budget); past that, the directed
    one, which expands each cross pair on both ends."""
    fits = secure_agg.VMEM_BUDGET // (8 * 128 * 4) - 4
    assert secure_agg._plan(fits, fits, 794).memo
    big = secure_agg._plan(fits + 1, fits + 1, 794)
    assert not big.memo
    assert big.words == (fits + 1) * fits * 800 * 128
    # one device of a four-chip cohort of 512: local pairs once, the
    # pairs with the other 384 clients once per endpoint
    shard = secure_agg._plan(128, 512, 794)
    assert shard.memo
    assert shard.words == (128 * 127 // 2 + 128 * 384) * 800 * 128


def test_secure_engine_records_mask_words(dataset, fed_partition):
    """A secure run's ledger carries the combine kernel's mask words and
    the protocol's, for S = 10 clients of the MLP upload."""
    from repro.fed import runtime
    _, h = runtime.run_alg1(dataset, fed_partition, batch_size=10,
                            rounds=2, eval_every=2, eval_samples=100,
                            seed=1, aggregation=aggregation.secure())
    n = h.comm["breakdown"]["upload_elements"]
    rows = -(-n // 128)
    assert h.comm["mask_words_needed_per_round"] == 45 * n
    assert h.comm["mask_words_per_round"] \
        == secure_agg._plan(10, 10, rows).words \
        >= h.comm["mask_words_needed_per_round"]


def test_large_client_count_scan_path_bit_exact():
    """Above UNROLL_MAX_CLIENTS the XLA paths switch from unrolled mask
    streams (HLO grows as I²) to a lax.scan over clients; aggregates and
    cross-shard partial combines stay bit-exact."""
    n = secure_agg.UNROLL_MAX_CLIENTS + 4
    msgs = {"w": jax.random.normal(jax.random.key(9), (n, 33))}
    key = jax.random.key(10)
    ref = aggregation.secure(streaming=False).combine_messages(msgs, key)
    stream = aggregation.secure().combine_messages(msgs, key)
    _assert_tree_equal(ref, stream)
    kd = jax.random.key_data(key)
    half = n // 2
    p0 = ops.secure_quant_sum(jax.tree.map(lambda m: m[:half], msgs), kd,
                              scale_bits=20, client_offset=0,
                              num_clients=n, use_kernel=False)
    p1 = ops.secure_quant_sum(jax.tree.map(lambda m: m[half:], msgs), kd,
                              scale_bits=20, client_offset=half,
                              num_clients=n, use_kernel=False)
    comb = ops.secure_dequantize(
        jax.tree.map(lambda a, b: a + b, p0, p1), 20)
    _assert_tree_equal(ref, comb)


def test_four_word_key_data_accepted():
    """PRNG impls with 4-word keys (rbg/unsafe_rbg) must work: the PRF
    takes its two words from the first/last key words."""
    msgs = {"w": jax.random.normal(jax.random.key(1), (3, 17))}
    kd4 = jnp.asarray([7, 11, 13, 17], jnp.uint32)
    out = ops.secure_quant_sum(msgs, kd4, scale_bits=20, use_kernel=False)
    want = jnp.sum(secure_agg.quantize(msgs["w"], 20), axis=0)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(out["w"]))


def test_aggregate_is_plain_quantized_sum():
    """The unmasked aggregate equals Σ_i quant(m_i) exactly (the
    quantization error bound of the secure tests is inherited)."""
    n = 5
    msgs = {"w": jax.random.normal(jax.random.key(4), (n, 33))}
    kd = jax.random.key_data(jax.random.key(5))
    want = jnp.sum(secure_agg.quantize(msgs["w"], 20), axis=0)
    got = ops.secure_quant_sum(msgs, kd, scale_bits=20, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got["w"]))


def test_partial_view_hides_individual_message():
    """A single client's masked partial is one-time-padded: statistically
    far from its raw quantized message, and re-keyed across rounds."""
    n = 4
    msgs = {"w": jax.random.normal(jax.random.key(6), (n, 64)) * 0.1}
    one = jax.tree.map(lambda m: m[:1], msgs)
    kd1 = jax.random.key_data(jax.random.key(7))
    kd2 = jax.random.key_data(jax.random.key(8))
    raw = secure_agg.quantize(msgs["w"][0], 20)
    m1 = ops.secure_quant_sum(one, kd1, scale_bits=20, client_offset=0,
                              num_clients=n, use_kernel=False)["w"]
    m2 = ops.secure_quant_sum(one, kd2, scale_bits=20, client_offset=0,
                              num_clients=n, use_kernel=False)["w"]
    far = np.abs(np.asarray(m1, np.int64) - np.asarray(raw, np.int64))
    assert np.median(far) > 2 ** 24                  # mask ≫ message scale
    assert np.abs(np.asarray(m1, np.int64)
                  - np.asarray(m2, np.int64)).min() > 0   # fresh per round


def test_mask_streams_look_uniform():
    """Counter-mode mask words: mean bit balance within 1% of 1/2 over a
    64k-word stream (a smoke check on the PRF, not a statistical suite)."""
    counters = jnp.arange(1 << 16, dtype=jnp.uint32)
    seed = secure_agg.pair_seed(jnp.uint32(123), jnp.uint32(456),
                                jnp.uint32(2), jnp.uint32(7))
    bits = np.asarray(secure_agg.mask_bits(seed, counters))
    ones = np.unpackbits(bits.view(np.uint8)).mean()
    assert abs(ones - 0.5) < 0.01


def test_scale_bits_validated_at_construction():
    for bad in (0, 31, -3, 20.0, True):
        with pytest.raises(ValueError, match="scale_bits"):
            aggregation.SecureAggregation(scale_bits=bad)
    assert aggregation.secure(scale_bits=12).scale_bits == 12
    # numpy integers (config files, bench rows) are valid
    assert aggregation.SecureAggregation(
        scale_bits=np.int64(16)).scale_bits == 16


def test_secure_run_streaming_matches_reference_trajectory(dataset,
                                                           fed_partition):
    """End-to-end engine parity: the streaming secure path drives the
    identical trajectory as the reference path (aggregates bit-equal ⇒
    identical server math)."""
    from repro.fed import runtime
    kw = dict(batch_size=10, rounds=4, eval_every=2, eval_samples=300,
              seed=5)
    _, h_ref = runtime.run_alg1(dataset, fed_partition,
                                aggregation=aggregation.secure(
                                    streaming=False), **kw)
    _, h_str = runtime.run_alg1(dataset, fed_partition,
                                aggregation=aggregation.secure(), **kw)
    np.testing.assert_array_equal(h_ref.train_cost, h_str.train_cost)
