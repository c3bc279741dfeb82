"""The sketched secure wire (fed/sketch.py + kernels/sketch.py).

The contracts:

* the fused Pallas encode (interpret mode) and the XLA scatter-add
  fallback consume the same PRF words and accumulate in int32 — they
  are bit-identical, not merely close;
* sketches are linear **in the ring**: for on-grid inputs,
  encode(a) + encode(b) == encode(a + b) bit-for-bit, and the masked
  Z_{2^32} sum of client sketches (SecureAggregation, streaming and
  mask-materializing reference alike) equals the sketch of the summed
  update exactly;
* the two-phase protocol is self-consistent: with a clean sketch
  (occupancy << 1) the median-of-rows support recovers planted heavy
  hitters, phase-2 values are stochastically rounded onto the secure
  grid client-side (the secure quantizer is the identity on them),
  reassembly is their exact masked sum, and the residual debit is
  exactly input − applied — rounding error included;
* the ledger charges the secure wire per sketch bucket —
  4·(rows·cols + k) + 4·peers per client — which is where the >= 10x
  sublinear-wire claim lives;
* the retired mask-materializing reference lives in kernels/ref.py and
  is not imported by the aggregation hot path;
* end-to-end: sketch + secure through the engine gives a >= 10x
  ledger-certified secure-uplink reduction at <= 1 point of
  final-accuracy loss against dense-secure.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fed import aggregation, compression, runtime
from repro.fed import sketch as fsk
from repro.kernels import sketch as ksk

GRID = np.float32(2.0 ** -20)       # the secure fixed-point resolution


def _on_grid(rng, n, span=64):
    """f32 vector of exact grid points (stochastic rounding becomes
    deterministic, so only hashing/masking is under test)."""
    return jnp.asarray(rng.integers(-span, span + 1, size=n)
                       .astype(np.float32) * GRID)


def _encode_keys():
    k0 = jnp.uint32(0xA1B2C3D4)
    k1 = jnp.uint32(0x1F2E3D4C)
    return k0, k1


# ---------------------------------------------------------------------------
# kernel == XLA fallback, bit-for-bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_rows,rows,cols",
                         [(1, 1, 64), (7, 4, 128), (9, 3, 256),
                          (12, 2, 64), (17, 3, 128), (32, 8, 512)])
def test_kernel_bit_exact_vs_xla(n_rows, rows, cols):
    """Includes n_rows % BLOCK_ROWS != 0 shapes: the kernel zero-pads
    the message to a whole number of blocks before the pallas_call, so
    there is never a partial boundary block whose (TPU-undefined)
    padding could be reduced into the live sketch."""
    rng = np.random.default_rng(7 * n_rows + rows)
    x = jnp.asarray(rng.normal(size=(n_rows, ksk.LANES)) * 0.1,
                    jnp.float32)
    su = jnp.asarray([0xDEAD_BEEF, 0, 0x5EED_C0DE], jnp.uint32)
    ref = ksk.sketch_encode_xla(x, su, rows=rows, cols=cols,
                                scale_bits=20)
    ker = ksk.sketch_encode_kernel(x, su, rows=rows, cols=cols,
                                   scale_bits=20, interpret=True)
    np.testing.assert_array_equal(np.asarray(ker), np.asarray(ref))


def test_estimator_linear_in_sketch():
    """estimate(S_a + S_b) == estimate(S_a) + estimate(S_b) exactly —
    the mean-of-rows estimator commutes with sketch addition (gathers
    are linear, the row mean divides by a power of two)."""
    rng = np.random.default_rng(0)
    su = lambda base: jnp.asarray([base, 0, 0x5EED_C0DE], jnp.uint32)
    enc = lambda v, b: ksk.sketch_encode_xla(
        v.reshape(2, ksk.LANES), su(b), rows=4, cols=128, scale_bits=20)
    a, b = _on_grid(rng, 2 * ksk.LANES), _on_grid(rng, 2 * ksk.LANES)
    sa, sb = enc(a, 1).astype(jnp.float32), enc(b, 2).astype(jnp.float32)
    counters = jnp.arange(2 * ksk.LANES, dtype=jnp.uint32)
    lhs = ksk.sketch_estimate(sa + sb, counters, 0x5EED_C0DE)
    rhs = ksk.sketch_estimate(sa, counters, 0x5EED_C0DE) \
        + ksk.sketch_estimate(sb, counters, 0x5EED_C0DE)
    np.testing.assert_array_equal(np.asarray(lhs), np.asarray(rhs))


# ---------------------------------------------------------------------------
# ring merge-linearity under masking (the zero-protocol-change claim)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("streaming", [True, False],
                         ids=["streaming", "reference"])
def test_masked_sketch_sum_is_sketch_of_sum(streaming):
    """Masked Z_{2^32} sum of client sketches == sketch of the summed
    message, bit-for-bit — for both secure paths (the reference is the
    relocated kernels/ref.py oracle)."""
    rng = np.random.default_rng(3)
    n = 3 * ksk.LANES
    comp = fsk.sketch(rows=4, cols=256, fraction=0.05, keep=n)
    k0, k1 = _encode_keys()
    msgs = [{"w": _on_grid(rng, n)} for _ in range(4)]
    sks = jnp.stack([comp.encode(m, k0, k1, jnp.uint32(c))
                     for c, m in enumerate(msgs)])
    agg = aggregation.secure(streaming=streaming).combine_messages(
        sks, jax.random.key(11))
    total = {"w": sum(m["w"] for m in msgs)}
    direct = comp.encode(total, k0, k1, jnp.uint32(99))
    np.testing.assert_array_equal(np.asarray(agg), np.asarray(direct))


def test_reference_path_not_imported_on_hot_path():
    """aggregation must not pull the O(P·model) mask-materializing
    reference (kernels/ref.py) at import time — it loads lazily, only
    when streaming=False is explicitly requested."""
    code = ("import sys; import repro.fed.aggregation; "
            "assert 'repro.kernels.ref' not in sys.modules, 'hot path'; "
            "import repro.fed.engine; "
            "assert 'repro.kernels.ref' not in sys.modules, 'engine'; "
            "print('LAZY_OK')")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "PATH": "/usr/bin:/bin"}, cwd=str(
            __import__("pathlib").Path(__file__).resolve().parent.parent))
    assert "LAZY_OK" in out.stdout, out.stderr


# ---------------------------------------------------------------------------
# the two-phase protocol, step by step
# ---------------------------------------------------------------------------

def test_support_recovers_planted_heavy_hitters():
    """Clean regime (occupancy << 1): the median-of-rows top-k of the
    aggregate sketch is exactly the planted support."""
    rng = np.random.default_rng(5)
    n = 4 * ksk.LANES
    heavy = rng.choice(n, size=8, replace=False)
    comp = fsk.sketch(rows=5, cols=1024, fraction=8 / n, keep=16)
    k0, k1 = _encode_keys()
    msgs = []
    for c in range(3):
        v = np.zeros(n, np.float32)
        v[heavy] = (rng.uniform(1.0, 2.0, size=8)
                    * np.sign(rng.normal(size=8))).astype(np.float32)
        v += rng.normal(size=n).astype(np.float32) * 1e-3
        msgs.append({"w": jnp.asarray(np.round(v / GRID) * GRID)})
    sks = jnp.stack([comp.encode(m, k0, k1, jnp.uint32(c))
                     for c, m in enumerate(msgs)])
    agg = aggregation.secure().combine_messages(sks, jax.random.key(0))
    sup = comp.support(agg, msgs[0])
    assert set(np.asarray(sup).tolist()) == set(heavy.tolist())


def test_values_reassemble_and_residual_are_exact():
    """Phase 2 on on-grid messages (stochastic rounding is the
    identity): reassemble(Σ values) is the exact sum at the support,
    and the residual debit satisfies residual == input − applied  per
    client, elementwise."""
    rng = np.random.default_rng(9)
    n = 2 * ksk.LANES
    comp = fsk.sketch(rows=4, cols=256, fraction=0.1, keep=32)
    k0, k1 = _encode_keys()
    msgs = [{"w": _on_grid(rng, n)} for _ in range(3)]
    support = jnp.asarray(rng.choice(n, size=comp._k(n), replace=False)
                          .astype(np.int32))
    vals = jnp.stack([comp.values(m, support, k0, k1, jnp.uint32(c))
                      for c, m in enumerate(msgs)])
    agg_vals = jnp.sum(vals, axis=0)
    dec = comp.reassemble(agg_vals, support, msgs[0])
    expect = np.zeros(n, np.float32)
    total = sum(np.asarray(m["w"]) for m in msgs)
    expect[np.asarray(support)] = total[np.asarray(support)]
    np.testing.assert_array_equal(np.asarray(dec["w"]), expect)
    for c, m in enumerate(msgs):
        r = comp.update_residual(m, support, vals[c])
        applied = np.zeros(n, np.float32)
        applied[np.asarray(support)] = \
            np.asarray(m["w"])[np.asarray(support)]
        np.testing.assert_array_equal(
            np.asarray(r["w"]), np.asarray(m["w"]) - applied)


def test_phase2_rounds_onto_grid_and_residual_tracks_applied():
    """Off-grid messages: phase-2 values are stochastically rounded
    onto the 2^-scale_bits grid *client-side* (within one grid step of
    the true value, and a fixed point of the secure quantizer — the
    masked sum is exactly the sum of the uploads), and the residual
    debits the *rounded* value, so residual == input − applied holds
    exactly and the rounding error stays inside the error-feedback
    loop."""
    from repro.kernels import secure_agg as sag
    rng = np.random.default_rng(21)
    n = 2 * ksk.LANES
    comp = fsk.sketch(rows=4, cols=256, fraction=0.1, keep=32)
    k0, k1 = _encode_keys()
    m = {"w": jnp.asarray(rng.normal(size=n).astype(np.float32))}
    support = jnp.asarray(rng.choice(n, size=comp._k(n), replace=False)
                          .astype(np.int32))
    vals = comp.values(m, support, k0, k1, jnp.uint32(3))
    scaled = np.asarray(vals).astype(np.float64) / GRID
    np.testing.assert_array_equal(scaled, np.round(scaled))     # on grid
    true = np.asarray(m["w"])[np.asarray(support)]
    assert np.abs(np.asarray(vals) - true).max() <= GRID        # one step
    assert (np.asarray(vals) != true).any()     # genuinely off-grid input
    rt = sag.dequantize(sag.quantize(vals, 20), 20)
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(vals))
    r = comp.update_residual(m, support, vals)
    expect = np.asarray(m["w"]).copy()
    expect[np.asarray(support)] -= np.asarray(vals)
    np.testing.assert_array_equal(np.asarray(r["w"]), expect)


def test_engine_refuses_scale_bits_mismatch(dataset, fed_partition):
    """sketch(scale_bits=16) under secure(scale_bits=20) would silently
    re-round every bucket off-grid, breaking the bit-exact masked merge
    — the engine refuses the pair up front."""
    with pytest.raises(ValueError, match="scale_bits"):
        runtime.run_alg1(dataset, fed_partition, batch_size=10, rounds=2,
                         eval_every=1, eval_samples=100, hidden=32,
                         compressor=fsk.sketch(scale_bits=16),
                         aggregation=aggregation.secure())


def test_config_validation():
    with pytest.raises(ValueError, match="power of two"):
        fsk.sketch(cols=100)
    with pytest.raises(ValueError, match="rows"):
        fsk.sketch(rows=0)
    with pytest.raises(ValueError, match="fraction"):
        fsk.sketch(fraction=0.0)
    with pytest.raises(ValueError, match="keep"):
        fsk.sketch(keep=0)
    with pytest.raises(ValueError, match="scale_bits"):
        fsk.CountSketchCompressor(scale_bits=31)


# ---------------------------------------------------------------------------
# the ledger: the secure wire is charged per sketch bucket
# ---------------------------------------------------------------------------

def test_round_bytes_sketch_secure_wire():
    params = {"w": jnp.zeros((25_000,)), "b": jnp.zeros((450,))}
    from repro.core import protocol, ssca
    alg = protocol.SSCAUnconstrained(loss_fn=None,
                                     hp=ssca.SSCAHyperParams())
    comp = fsk.sketch(rows=4, cols=512, fraction=0.015)
    n, k = 25_450, comp._k(25_450)
    rb = compression.round_bytes(alg, aggregation.secure(), comp,
                                 params, num_clients=8)
    assert rb.breakdown["wire_elements"] == 4 * 512 + k
    assert rb.uplink_per_client == 4 * (4 * 512 + k) + 4 * 7
    # the support broadcast rides the downlink
    assert rb.downlink_per_client == 4 * n + 4 * k
    dense = compression.round_bytes(alg, aggregation.secure(), None,
                                    params, num_clients=8)
    assert dense.uplink_per_client / rb.uplink_per_client >= 10.0
    # plain wire: the sketch payload is still 4·(R·C + k)
    rb_plain = compression.round_bytes(alg, aggregation.plain(), comp,
                                       params, num_clients=8)
    assert rb_plain.uplink_per_client == 4 * (4 * 512 + k)


# ---------------------------------------------------------------------------
# end to end: sketch + secure through the engine
# ---------------------------------------------------------------------------

def test_engine_sketch_secure_learns_at_10x():
    """The acceptance smoke: the ledger certifies >= 10x fewer secure
    uplink bytes than dense-secure at <= 1 point of final-accuracy loss,
    with dense-secure itself above 90 %.  Run at the headline's own
    configuration: 4,000 samples over 8 clients, h32, 300 rounds (the
    two-phase error-feedback loop needs them to close), 1,000 held-out
    samples."""
    from repro.data import partition, synthetic
    data = synthetic.classification_dataset(n_train=4000, n_test=1000,
                                            seed=0)
    part = partition.iid(4000, 8, seed=0)
    kw = dict(batch_size=10, rounds=300, eval_every=75, eval_samples=1000,
              seed=0, hidden=32, aggregation=aggregation.secure())
    comp = fsk.sketch(rows=4, cols=512, fraction=0.015, keep=64)
    _, hd = runtime.run_alg1(data, part, **kw)
    _, hs = runtime.run_alg1(data, part, compressor=comp, **kw)
    assert hd.uplink_bytes_per_round / hs.uplink_bytes_per_round >= 10.0
    assert hs.comm["breakdown"]["compressor"] == "sketch"
    assert hs.train_cost[-1] < 0.5 * hs.train_cost[0]
    assert hd.test_accuracy[-1] > 0.9
    assert hd.test_accuracy[-1] - hs.test_accuracy[-1] <= 0.01
