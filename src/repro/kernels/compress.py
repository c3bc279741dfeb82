"""Fused upload-compression kernel (stochastic round + top-k mask).

The communication layer (:mod:`repro.fed.compression`) needs two
per-client primitives on the flattened upload message:

1. **stochastic rounding** onto a power-of-two lattice q·Δ, Δ = 2^e —
   the unbiased QSGD-style b-bit quantizer: y = x/Δ is rounded to
   ⌊y⌋ + 1[u < frac(y)] with u a per-element uniform draw, so
   E[round(y)] = y exactly (up to the 2⁻²⁴ resolution of the float32
   uniform);
2. **threshold masking** |x| ≥ θ with the complementary residual x − out
   — the top-k sparsifier's apply step (the threshold θ, a global order
   statistic, is computed once per message by ``lax.top_k`` outside the
   blocked kernel) and the error-feedback update in the same pass.

Both are fused into one blocked pass over the (R, 128) message —
mask, quantize the survivors, and emit (compressed, residual) without a
second read of the input.  The random bits come from the *same*
counter-mode PRF as the secure-aggregation kernel
(:func:`repro.kernels.secure_agg.mask_bits`): each (round, client) pair
owns an independent stream, any block of which is generated from its
element counters alone.  That makes the kernel blockable, makes the
sharded engine reproducible (a client's stream is identical on whichever
device owns it), and — because the XLA fallback evaluates the *identical*
element-wise expression on the identical counters — makes the Pallas and
XLA paths **bit-identical**, not merely statistically equivalent.

Power-of-two Δ is what makes the quantizer compose with secure
aggregation: every output q·2^e with e ≥ −scale_bits sits *exactly* on
the Z_{2^32} fixed-point grid of :mod:`repro.kernels.secure_agg`, so
masking happens on the already-quantized message and the secure
aggregate of compressed uploads equals the plain sum bit-for-bit.

Layout mirrors :mod:`repro.kernels.secure_agg`: a Pallas kernel blocked
over (BLOCK_ROWS, 128) tiles with all randomness generated in VMEM, and
an XLA path used off-TPU (auto-selected, like
:func:`repro.kernels.ops.secure_quant_sum`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.secure_agg import _GOLD, _M1, _mix32, mask_bits

BLOCK_ROWS = 256
LANES = 128

_U32_RES = np.float32(2.0 ** -32)


def client_stream_seed(key0, key1, cid):
    """Per-(round, client) seed of the stochastic-rounding stream.

    Same construction discipline as :func:`secure_agg.pair_seed` but over
    a single client id — the draw that breaks ties between clients must
    be independent across clients and re-keyed every round, or two
    clients quantizing equal values would make correlated errors and the
    aggregate's error would not concentrate.
    """
    s = _mix32(key0 ^ (jnp.uint32(cid) * _GOLD))
    return _mix32(s ^ (key1 * _M1))


def _uniform(bits):
    """uint32 PRF words → float32 uniforms in [0, 1).

    The TPU kernel compiler has no uint32 → float32 cast, so the word is
    converted as two 16-bit halves, each cast through int32.  Both terms
    are exact in float32, so the sum is rounded once: it is the correctly
    rounded uint32, the same bits a direct cast gives.
    """
    hi = (bits >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & np.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return (hi * np.float32(65536.0) + lo) * _U32_RES


def _compress_block(x, counters, seed, thr, delta, lbound: int,
                    quantize: bool, masked: bool):
    """The shared element-wise body: mask → stochastic round → residual.

    Evaluated verbatim by both the XLA path and the Pallas kernel (same
    ops on the same counters ⇒ bit-identical outputs).  ``lbound`` is the
    static level bound L = 2^(b−1) − 1; the scale choice in
    :mod:`repro.fed.compression` guarantees |x/Δ| ≤ L, so the clip is a
    no-op except for degenerate inputs (all-zero messages, inf/nan).
    """
    out = x
    if quantize:
        y = x / delta
        low = jnp.floor(y)
        u = _uniform(mask_bits(seed, counters))
        q = low + (u < (y - low)).astype(jnp.float32)
        q = jnp.clip(q, -float(lbound), float(lbound))
        out = q * delta
    if masked:
        out = jnp.where(jnp.abs(x) >= thr, out, 0.0)
    return out, x - out


# ---------------------------------------------------------------------------
# XLA path
# ---------------------------------------------------------------------------

def compress_2d_xla(x, scalars_u32, scalars_f32, *, lbound: int,
                    quantize: bool, masked: bool):
    """(R, 128) f32 → (compressed, residual), both (R, 128) f32.

    ``scalars_u32``: (2,) [stream seed, counter base]; ``scalars_f32``:
    (2,) [threshold θ, lattice step Δ].  Element counters are
    base + row·128 + col — the same enumeration the kernel uses, so the
    two paths consume identical PRF words.
    """
    shape = x.shape
    row = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    counters = scalars_u32[1] + row * np.uint32(shape[1]) + col
    return _compress_block(x, counters, scalars_u32[0], scalars_f32[0],
                           scalars_f32[1], lbound, quantize, masked)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _make_kernel(lbound: int, quantize: bool, masked: bool):
    def kernel(x_ref, su_ref, sf_ref, out_ref, res_ref):
        shape = out_ref.shape                                # (block, 128)
        seed, base = su_ref[0, 0], su_ref[0, 1]
        thr, delta = sf_ref[0, 0], sf_ref[0, 1]
        pid_base = pl.program_id(0).astype(jnp.uint32) \
            * np.uint32(shape[0] * shape[1])
        row = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
        col = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
        counters = base + pid_base + row * np.uint32(shape[1]) + col
        out, res = _compress_block(x_ref[...], counters, seed, thr, delta,
                                   lbound, quantize, masked)
        out_ref[...] = out
        res_ref[...] = res

    return kernel


@functools.partial(jax.jit, static_argnames=("lbound", "quantize",
                                             "masked", "interpret"))
def compress_2d_kernel(x, scalars_u32, scalars_f32, *, lbound: int,
                       quantize: bool, masked: bool,
                       interpret: bool = False):
    """The fused Pallas pass: blocked over rows, PRF words in VMEM.

    The scalars sit in SMEM as (1, 2) rows, so that under ``vmap`` (the
    engine compresses every client's upload at once) their batched block
    still spans the array's last two dimensions, as Mosaic requires.
    """
    rows, lanes = x.shape
    block = min(BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, block),)
    out_sds = (jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
               jax.ShapeDtypeStruct((rows, lanes), jnp.float32))
    return pl.pallas_call(
        _make_kernel(lbound, quantize, masked),
        grid=grid,
        in_specs=[pl.BlockSpec((block, lanes), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=(pl.BlockSpec((block, lanes), lambda i: (i, 0)),
                   pl.BlockSpec((block, lanes), lambda i: (i, 0))),
        out_shape=out_sds,
        interpret=interpret,
    )(x, scalars_u32.reshape(1, -1), scalars_f32.reshape(1, -1))


def compress_2d(x, scalars_u32, scalars_f32, *, lbound: int, quantize: bool,
                masked: bool, use_kernel=None, interpret: bool = False):
    """Dispatch: Pallas on TPU (or under ``interpret=True`` for CPU
    validation), XLA elsewhere.  Outputs are bit-identical either way."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel or interpret:
        return compress_2d_kernel(x, scalars_u32, scalars_f32,
                                  lbound=lbound, quantize=quantize,
                                  masked=masked, interpret=interpret)
    return compress_2d_xla(x, scalars_u32, scalars_f32, lbound=lbound,
                           quantize=quantize, masked=masked)
