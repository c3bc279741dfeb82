"""Fused count-sketch encode kernel (stochastic round + hash + sign +
bucket-accumulate in one pass).

The sketched secure wire (:mod:`repro.fed.sketch`) needs one per-client
primitive: project the flattened upload message x ∈ R^n into a CSVec-
style count-sketch S ∈ Z^{rows×cols} (FetchSGD), with the bucket values
landing **exactly on the secure fixed-point grid** so the sketch can be
pairwise-masked and summed in Z_{2^32} by the existing secure-
aggregation stack with zero protocol changes.  Per element j and sketch
row r:

1. **stochastic fixed-point round** — q_j = ⌊x_j·2^s⌋ + 1[u_j < frac]
   with u_j a per-(round, client) counter-mode uniform: the unbiased
   projection of the message onto the grid 2^-s (E[q_j·2^-s] = x_j).
   Rounding the *inputs* (not the buckets) is what makes everything
   after it exact integer arithmetic;
2. **hash + sign** — one PRF word w = F(seed_r, j) gives the bucket
   h_r(j) = w mod cols (cols a power of two: the low bits, no modulo
   bias) and the Rademacher sign σ_r(j) = 1 − 2·w[31];
3. **bucket accumulate** — S[r, h_r(j)] += σ_r(j)·q_j with int32
   wraparound: *exactly* associative and commutative, so every
   accumulation order — XLA scatter-add, the kernel's one-hot
   reduction, any blocking — produces the bit-identical sketch, and
   sketches **merge linearly in the ring**: encode(a) + encode(b) ==
   encode(a + b) for on-grid inputs, the property that lets the masked
   Z_{2^32} sum of client sketches equal the sketch of the summed
   update bit-for-bit.

The hash/sign PRF is the *same* counter-mode construction as the
secure-aggregation masks (:func:`repro.kernels.secure_agg.mask_bits`),
keyed on a **static sketch seed shared by all clients and rounds**
(sketches must merge across clients, so the hash functions cannot be
per-client) — while the rounding stream is keyed per (round, client)
like :mod:`repro.kernels.compress`, so placement on the client mesh
never changes any client's draws.

Layout mirrors :mod:`repro.kernels.compress`: a Pallas kernel blocked
over (BLOCK_ROWS, 128) input tiles accumulating the (rows, cols) sketch
across the grid in VMEM, and an XLA scatter-add path used off-TPU
(auto-selected).  Because the accumulation is integer, the two paths
are bit-identical — not merely statistically equivalent.

Two server-side unsketch estimators, with distinct roles:
:func:`sketch_estimate` is the **mean-of-rows** x̂_j = (1/R) Σ_r
σ_r(j)·S[r, h_r(j)] — unbiased over the hash stream and *linear in the
sketch* (Σ_i estimate(S_i) == estimate(Σ_i S_i) exactly), the two
properties the property tests pin; :func:`sketch_estimate_median` is
the **median-of-rows** classical recovery, robust to bucket-collision
outliers and therefore what the sketched secure wire uses to *rank*
coordinates for its top-k support (exact values then travel in a second
masked phase — see :mod:`repro.fed.sketch`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compress import _uniform
from repro.kernels.secure_agg import _GOLD, _mix32, mask_bits

BLOCK_ROWS = 8          # input rows per grid step (8·128 = 1024 elements)
LANES = 128


def row_seed(sketch_seed, r):
    """PRF seed of sketch row r — static per sketch configuration (every
    client and round hashes identically, or sketches would not merge)."""
    return _mix32(jnp.uint32(sketch_seed)
                  ^ ((jnp.uint32(r) + 1) * _GOLD))


def hash_and_sign(rseed, counters, cols: int):
    """One PRF word per element → (bucket uint32 in [0, cols), sign ±1
    int32).  ``cols`` must be a power of two: the bucket is the word's
    low bits (uniform, no modulo bias), the sign its top bit."""
    w = mask_bits(rseed, counters)
    h = w & np.uint32(cols - 1)
    sgn = (1 - 2 * (w >> 31).astype(jnp.int32))
    return h, sgn


def _round_to_grid(x, counters, seed, scale_bits: int):
    """Unbiased stochastic round of f32 onto the int grid units 2^-s —
    the same draw-per-counter construction as
    :mod:`repro.kernels.compress` (exact zeros stay exact zeros — the
    uniform draw u ∈ [0, 1) never beats a zero fraction — so lane and
    block padding never contributes to a bucket)."""
    y = x * jnp.float32(2.0 ** scale_bits)
    low = jnp.floor(y)
    u = _uniform(mask_bits(seed, counters))
    return (low + (u < (y - low)).astype(jnp.float32)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# XLA path
# ---------------------------------------------------------------------------

def sketch_encode_xla(x, scalars_u32, *, rows: int, cols: int,
                      scale_bits: int):
    """(R, 128) f32 message → (rows, cols) int32 bucket sums (grid units).

    ``scalars_u32``: (3,) [rounding-stream seed, counter base, sketch
    seed].  Element counters are base + row·128 + col — the enumeration
    the kernel uses, so both paths consume identical PRF words; the
    int32 scatter-add makes them bit-identical regardless of order.
    """
    shape = x.shape
    ri = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    ci = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    counters = (scalars_u32[1] + ri * np.uint32(shape[1]) + ci).reshape(-1)
    q = _round_to_grid(x, counters.reshape(shape), scalars_u32[0],
                       scale_bits).reshape(-1)
    out = []
    for r in range(rows):
        h, sgn = hash_and_sign(row_seed(scalars_u32[2], r), counters, cols)
        out.append(jnp.zeros((cols,), jnp.int32).at[h].add(sgn * q))
    return jnp.stack(out)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _make_kernel(rows: int, cols: int, scale_bits: int):
    def kernel(x_ref, su_ref, out_ref):
        shape = x_ref.shape                                  # (block, 128)
        seed, base, skseed = su_ref[0, 0], su_ref[0, 1], su_ref[0, 2]
        pid = pl.program_id(0)
        pid_base = pid.astype(jnp.uint32) \
            * np.uint32(shape[0] * shape[1])
        ri = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
        ci = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
        counters = base + pid_base + ri * np.uint32(shape[1]) + ci
        q = _round_to_grid(x_ref[...], counters, seed, scale_bits)
        # bucket accumulate as a one-hot reduction (TPU has no scatter):
        # (block, 128, cols) compare + sum — int32 adds, so the order
        # difference vs the XLA scatter is invisible bit-for-bit
        bucket_iota = jax.lax.broadcasted_iota(
            jnp.uint32, (shape[0], shape[1], cols), 2)
        contribs = []
        for r in range(rows):
            h, sgn = hash_and_sign(row_seed(skseed, r), counters, cols)
            onehot = h[..., None] == bucket_iota
            contribs.append(jnp.sum(
                jnp.where(onehot, (sgn * q)[..., None], 0), axis=(0, 1)))
        block = jnp.stack(contribs)                          # (rows, cols)

        @pl.when(pid == 0)
        def _init():
            out_ref[...] = block

        @pl.when(pid > 0)
        def _accumulate():
            out_ref[...] = out_ref[...] + block

    return kernel


@functools.partial(jax.jit, static_argnames=("rows", "cols", "scale_bits",
                                             "interpret"))
def sketch_encode_kernel(x, scalars_u32, *, rows: int, cols: int,
                         scale_bits: int, interpret: bool = False):
    """The fused Pallas pass: blocked over the message, the (rows, cols)
    int32 sketch accumulated in VMEM across grid steps.

    The message is zero-padded to a whole number of blocks *before* the
    ``pallas_call``: a partial boundary block would otherwise be filled
    by the TPU pipeline with **undefined** values (interpret mode
    zero-fills, which hides the hazard on CPU), and unlike an
    element-wise kernel — whose garbage padding lanes are discarded
    along with the output padding — this kernel *reduces* its input
    into the live (rows, cols) sketch, so undefined padding would
    corrupt real buckets.  Explicit zero rows are harmless: an exact
    zero stochastically rounds to an exact zero (see
    :func:`_round_to_grid`) and contributes nothing to any bucket, and
    the valid rows keep their element counters, so the result stays
    bit-identical to the XLA path for every ``n_rows``.

    The scalars sit in SMEM as one (1, 3) row, so that under ``vmap``
    (one sketch per client) their batched block still spans the array's
    last two dimensions, as Mosaic requires."""
    n_rows, lanes = x.shape
    block = min(BLOCK_ROWS, n_rows)
    pad = (-n_rows) % block
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    grid = ((n_rows + pad) // block,)
    return pl.pallas_call(
        _make_kernel(rows, cols, scale_bits),
        grid=grid,
        in_specs=[pl.BlockSpec((block, lanes), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((rows, cols), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.int32),
        interpret=interpret,
    )(x, scalars_u32.reshape(1, -1))


def sketch_encode(x, scalars_u32, *, rows: int, cols: int, scale_bits: int,
                  use_kernel=None, interpret: bool = False):
    """Dispatch: Pallas on TPU (or under ``interpret=True`` for CPU
    validation), XLA scatter-add elsewhere.  Bit-identical either way
    (integer accumulation)."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel or interpret:
        return sketch_encode_kernel(x, scalars_u32, rows=rows, cols=cols,
                                    scale_bits=scale_bits,
                                    interpret=interpret)
    return sketch_encode_xla(x, scalars_u32, rows=rows, cols=cols,
                             scale_bits=scale_bits)


# ---------------------------------------------------------------------------
# the unsketch estimator (server-side; XLA — R gathers, once per round)
# ---------------------------------------------------------------------------

def sketch_estimate(sk, counters, sketch_seed):
    """Mean-of-rows count-sketch estimate at the given element counters.

    ``sk``: (rows, cols) f32 sketch (grid values or any linear combine
    of sketches); ``counters``: (m,) uint32 flat element positions.
    Returns (m,) f32 — unbiased over the hash stream, and **linear in
    sk**: estimate(Σ_i sk_i) = Σ_i estimate(sk_i) exactly (the per-row
    gathers and the power-of-two row mean commute with the sum).
    """
    rows, cols = sk.shape
    acc = jnp.zeros(counters.shape, jnp.float32)
    for r in range(rows):
        h, sgn = hash_and_sign(row_seed(sketch_seed, r), counters, cols)
        acc = acc + sgn.astype(jnp.float32) * sk[r, h]
    return acc / np.float32(rows)


def sketch_estimate_median(sk, counters, sketch_seed):
    """Median-of-rows estimate — the classical count-sketch recovery:
    |x̂_j − x_j| ≤ O(‖tail‖₂/√cols) w.h.p., because the median rejects
    the rows where coordinate j collided with a heavy bucket (the mean
    averages such outliers in).  Not linear in ``sk`` — use it to *rank*
    coordinates (support selection), and fetch exact values separately
    (:mod:`repro.fed.sketch`'s phase 2) rather than applying it as the
    update."""
    rows, cols = sk.shape
    terms = []
    for r in range(rows):
        h, sgn = hash_and_sign(row_seed(sketch_seed, r), counters, cols)
        terms.append(sgn.astype(jnp.float32) * sk[r, h])
    return jnp.median(jnp.stack(terms), axis=0)
