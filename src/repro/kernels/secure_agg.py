"""Streaming secure-aggregation kernel (quantize + mask + Z_{2^32} sum).

The PR-1 secure path materialized every pair mask as a full model-sized
tensor — ``(P, model)`` HBM traffic with P = I(I−1)/2 — then combined
them through an ``(I, P) × (P, model)`` tensordot.  This module replaces
that with a *streaming* formulation: one pass over the per-client
message shard that fuses

1. fixed-point quantization  q_i = round(λ_i m_i · 2^scale_bits) → int32,
2. counter-based pair-mask generation (masks exist only in registers /
   VMEM, never in HBM), and
3. the signed Z_{2^32} accumulate of the masked uploads
   q̃_i = q_i + Σ_{j>i} PRG(s_ij) − Σ_{j<i} PRG(s_ji)  (mod 2^32),

emitting only the (model)-sized aggregate Σ_i q̃_i — O(I·model) HBM
traffic instead of O(I²·model).  Because addition mod 2^32 is exactly
associative and commutative, every formulation here (pairwise, directed
per-client, Pallas-blocked) returns the *bit-identical* aggregate
Σ_i q_i — mask cancellation is exact, with no floating-point residue.

Mask streams are a counter-mode PRF: ``bits = F(s_ab, position)`` where
``s_ab`` is the pair's shared seed (derived from the round key and the
ordered client ids) and ``position`` is the element's index in the
flattened message.  Counter-mode is what makes the kernel streamable
(any block of the mask is generated independently) and what makes the
*sharded* path work: the two endpoint devices of a cross-shard pair
regenerate the same stream locally — exactly how Bonawitz-style clients
expand a shared seed, no mask ever crosses the wire.  ``F`` here is two
keyed murmur3 finalizer rounds — a fast non-cryptographic stand-in with
the right interface; a deployment swaps in a crypto PRF (the correctness
property, exact cancellation, is PRF-independent).

Three interchangeable implementations (all bit-identical):

* :func:`masked_sum_flat`         — XLA, pairwise (P mask streams), the
                                    single-host fast path.
* :func:`masked_partial_sum_flat` — XLA, directed per-client streams for
                                    a client *shard*; the per-device body
                                    of the sharded engine (psum-ready).
* :func:`masked_sum_2d`           — the Pallas kernel: blocked over the
                                    message, masks generated in VMEM.

Masked uploads pass through ``optimization_barrier`` in the XLA paths:
in the protocol they cross the client→server trust boundary, so the
compiler must not algebraically cancel ±mask pairs (which would silently
turn the benchmark into a plain quantized sum).

**Dropout recovery** (Bonawitz seed-share recovery, the async engine's
missing-upload case): every path takes an optional ``alive`` vector —
0/1 over the *global* cohort positions.  A dropped slot d contributes no
upload at all (``alive[d]`` zeroes its masked message), and every
survivor's directed mask stream against d is cancelled
(``alive[peer]`` zeroes the ±PRG(s_id) term).  In the real protocol the
survivors' uploads *do* carry those masks and the server subtracts them
after recovering d's pair seeds from the survivors' secret shares;
because Z_{2^32} addition is exact, folding the cancellation into the
per-slot mask sum is bit-identical to that two-step subtraction — the
masked sum over survivors equals the plain survivor sum ``Σ_{alive} q_i``
bit-for-bit.  ``alive=None`` keeps the exact pre-dropout program (no
multiplies inserted).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 256
LANES = 128

# Below this client count the XLA paths unroll the per-pair / per-peer
# mask streams into straight-line code (fastest on CPU: everything fuses
# into the accumulate).  Above it the unrolled HLO would grow as I² —
# the regression PR-1 removed from the seed — so the directed formulation
# switches to a lax.scan over clients (O(1) trace size, peers vectorized).
UNROLL_MAX_CLIENTS = 16

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_GOLD = np.uint32(0x9E3779B9)

# Domain-separation tag of the **group level** of the hierarchical
# two-level tree (fed/aggregation.py Hierarchical): group partials are
# re-masked across the G edge aggregators with streams keyed on the
# round key words XOR'd with this tag (same discipline as the sketch's
# _PHASE2_TAG) — so a group-level (seed, counter) pair can never collide
# with a client-level pair of the same round and no mask word is ever
# reused across the two levels.
_GROUP_TAG = np.uint32(0x47525550)


def _mix32(x):
    """murmur3 fmix32 — a bijective avalanche on uint32."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def pair_seed(key0, key1, lo, hi):
    """Shared mask-stream seed s_{lo,hi} for the ordered pair lo < hi.

    Symmetric in nothing: the (lo, hi) ordering is part of the seed, and
    the sign convention (+ for the lower id, − for the higher) is applied
    by the caller.  key0/key1 are the round key words — fresh masks every
    round.
    """
    s = _mix32(key0 ^ (lo * _GOLD))
    s = _mix32(s ^ (hi * _M1))
    return _mix32(s ^ key1)


def mask_bits(seed, counters):
    """Counter-mode mask words: uniform-looking uint32 per position."""
    h = _mix32(counters ^ seed)
    return _mix32(h ^ (seed + _GOLD))


def _i32(bits):
    return jax.lax.bitcast_convert_type(bits, jnp.int32)


def group_key_words(key0, key1):
    """Round key words for the tree's group level.

    Both words are avalanched through :data:`_GROUP_TAG` so every group-
    level ``pair_seed`` draws from a stream disjoint from the client-level
    streams of the same round — the two levels of the hierarchy never
    share a (seed, counter) pair even though they reuse the same PRF.
    """
    return (_mix32(jnp.asarray(key0, jnp.uint32) ^ _GROUP_TAG),
            _mix32(jnp.asarray(key1, jnp.uint32) ^ _GROUP_TAG))


def quantize(m, scale_bits: int):
    """Fixed-point grid 2^-scale_bits → int32 (round-half-even)."""
    return jnp.round(m.astype(jnp.float32)
                     * jnp.float32(2.0 ** scale_bits)).astype(jnp.int32)


def dequantize(q, scale_bits: int):
    return q.astype(jnp.float32) / jnp.float32(2.0 ** scale_bits)


# ---------------------------------------------------------------------------
# XLA streaming paths
# ---------------------------------------------------------------------------

def _masked_partial_sum_scan(q, key0, key1, client_offset,
                             num_clients: int, alive=None):
    """Large-I directed formulation: lax.scan over the local clients
    (trace size independent of I), peer mask streams vectorized per
    client.  Bit-identical to the unrolled paths (mod-2^32 exactness);
    slower per element on CPU than the fused unrolled code, but the
    unrolled HLO grows as I² and is the wrong trade past
    ``UNROLL_MAX_CLIENTS``."""
    i_loc, n = q.shape
    counters = jnp.arange(n, dtype=jnp.uint32)
    peers = jnp.arange(num_clients, dtype=jnp.uint32)

    def one_client(acc, xs):
        q_i, li = xs
        i = (jnp.asarray(client_offset) + li).astype(jnp.uint32)
        seeds = pair_seed(key0, key1, jnp.minimum(i, peers),
                          jnp.maximum(i, peers))
        bits = mask_bits(seeds[:, None], counters[None, :])
        sgn = jnp.where(peers == i, 0,
                        jnp.where(i < peers, 1, -1)).astype(jnp.int32)
        if alive is not None:
            # the server's post-hoc cancellation of dropped peers' masks,
            # folded into the stream sign (exact in Z_2^32)
            sgn = sgn * alive.astype(jnp.int32)
        upload = q_i + jnp.sum(sgn[:, None] * _i32(bits), axis=0)
        if alive is not None:
            upload = upload * alive[i.astype(jnp.int32)]
        upload = jax.lax.optimization_barrier(upload)
        return acc + upload, None

    out, _ = jax.lax.scan(one_client, jnp.zeros((n,), jnp.int32),
                          (q, jnp.arange(i_loc, dtype=jnp.int32)))
    return out


def masked_sum_flat(msgs_flat, key_data, scale_bits: int, alive=None):
    """Full-view streaming masked sum: (I, n) f32 → (n,) int32.

    One mask stream per pair (the server-side simulation may memoize the
    pair's shared stream — both endpoints expand the same seed), applied
    +into the lower client's upload and −into the higher's; uploads then
    cross the trust boundary (optimization_barrier) and are summed with
    int32 wraparound.  ``alive`` (optional (I,) 0/1) drops clients with
    exact mask cancellation — see the module docstring.
    """
    i_cl, n = msgs_flat.shape
    q = quantize(msgs_flat, scale_bits)
    if alive is not None:
        alive = alive.astype(jnp.int32)
    if i_cl == 1:
        return q[0] if alive is None else q[0] * alive[0]
    key0, key1 = key_data[0], key_data[1]
    if i_cl > UNROLL_MAX_CLIENTS:
        return _masked_partial_sum_scan(q, key0, key1, 0, i_cl, alive)
    counters = jnp.arange(n, dtype=jnp.uint32)
    # per-client accumulator chains (plain vector adds) instead of
    # scattered updates into one (I, n) buffer — the 2·P sequential
    # dynamic-update-slices serialized the whole combine
    uploads = [q[i] for i in range(i_cl)]
    lo, hi = np.triu_indices(i_cl, k=1)
    for a, b in zip(lo, hi):
        m = _i32(mask_bits(pair_seed(key0, key1, jnp.uint32(a),
                                     jnp.uint32(b)), counters))
        if alive is None:
            uploads[a] = uploads[a] + m
            uploads[b] = uploads[b] - m
        else:
            # each survivor's stream against a dropped peer is cancelled
            uploads[a] = uploads[a] + alive[b] * m
            uploads[b] = uploads[b] - alive[a] * m
    if alive is not None:
        uploads = [u * alive[i] for i, u in enumerate(uploads)]
    uploads = jax.lax.optimization_barrier(uploads)
    out = uploads[0]
    for u in uploads[1:]:
        out = out + u
    return out


def masked_ring_partial_sum(q, key0, key1, client_offset,
                            num_clients: int, alive=None):
    """Directed masked sum of already-quantized rows: (I_loc, n) int32 →
    (n,) int32.

    The ring-only core of :func:`masked_partial_sum_flat`, split out so
    the hierarchical tree can re-mask *group partials* — which are
    already int32 ring elements — without a dequantize/requantize round
    trip (which is only exact below 2^24 and would break bit-identity
    for accumulated sums).  Same directed-stream protocol: local rows
    are global ids [offset, offset + I_loc), every peer stream is
    regenerated locally, and a psum/plain sum over all shards cancels
    every mask exactly (mod-2^32 associativity).
    """
    i_loc, n = q.shape
    if alive is not None:
        alive = alive.astype(jnp.int32)
    if num_clients == 1:
        return q[0] if alive is None else q[0] * alive[0]
    if num_clients > UNROLL_MAX_CLIENTS:
        return _masked_partial_sum_scan(q, key0, key1, client_offset,
                                        num_clients, alive)
    counters = jnp.arange(n, dtype=jnp.uint32)
    uploads = []
    for li in range(i_loc):
        i = (jnp.asarray(client_offset) + li).astype(jnp.uint32)
        tot = jnp.zeros((n,), jnp.int32)
        for j in range(num_clients):      # directed: every peer stream
            ju = jnp.uint32(j)
            m = _i32(mask_bits(pair_seed(key0, key1, jnp.minimum(i, ju),
                                         jnp.maximum(i, ju)), counters))
            sgn = jnp.where(ju == i, 0,
                            jnp.where(i < ju, 1, -1)).astype(jnp.int32)
            if alive is not None:
                sgn = sgn * alive[j]
            tot = tot + sgn * m
        up = q[li] + tot
        if alive is not None:
            up = up * alive[i.astype(jnp.int32)]
        uploads.append(up)
    uploads = jax.lax.optimization_barrier(uploads)
    out = uploads[0]
    for u in uploads[1:]:
        out = out + u
    return out


def masked_partial_sum_flat(msgs_flat, key_data, scale_bits: int,
                            client_offset, num_clients: int, alive=None):
    """Shard-local streaming masked sum: (I_loc, n) f32 → (n,) int32.

    The local clients are global ids [offset, offset + I_loc); each
    regenerates the directed mask streams against *all* peers (cross-
    shard pairs are regenerated on both endpoint devices — counter-mode
    makes the streams identical).  psum of the per-shard partials over
    the client axis recovers the full-view aggregate bit-for-bit.
    ``client_offset`` may be a traced scalar (``axis_index`` under
    shard_map).
    """
    q = quantize(msgs_flat, scale_bits)
    return masked_ring_partial_sum(q, key_data[0], key_data[1],
                                   client_offset, num_clients, alive)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _make_kernel(num_clients: int, scale_bits: int, with_alive: bool = False):
    scale = float(2.0 ** scale_bits)

    def kernel(msgs_ref, sc_ref, out_ref):
        # grid (row block r, local client li): one client's rows per step,
        # folded into the block's int32 accumulator (the output block stays
        # resident in VMEM across the client axis)
        shape = out_ref.shape                                # (block, 128)
        li = pl.program_id(1)
        key0, key1, offset = sc_ref[0], sc_ref[1], sc_ref[2]
        base = pl.program_id(0).astype(jnp.uint32) \
            * np.uint32(shape[0] * shape[1])
        row = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
        col = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
        counters = base + row * np.uint32(shape[1]) + col
        q = jnp.round(msgs_ref[...].astype(jnp.float32) * scale) \
            .astype(jnp.int32)
        i = offset + li.astype(jnp.uint32)
        if num_clients > 1:

            def peer(jj, tot):
                j = jj.astype(jnp.uint32)
                bits = mask_bits(
                    pair_seed(key0, key1, jnp.minimum(i, j),
                              jnp.maximum(i, j)), counters)
                sgn = jnp.where(j == i, 0,
                                jnp.where(i < j, 1, -1)).astype(jnp.int32)
                if with_alive:
                    # alive bits ride behind the key words; dynamic
                    # scalar load per peer
                    sgn = sgn * sc_ref[3 + jj].astype(jnp.int32)
                return tot + sgn * _i32(bits)

            q = q + jax.lax.fori_loop(0, num_clients, peer,
                                      jnp.zeros(shape, jnp.int32))
        if with_alive:
            q = q * sc_ref[3 + i.astype(jnp.int32)].astype(jnp.int32)

        @pl.when(li == 0)
        def _init():
            out_ref[...] = q

        @pl.when(li > 0)
        def _accumulate():
            out_ref[...] = out_ref[...] + q

    return kernel


@functools.partial(jax.jit, static_argnames=("scale_bits", "num_clients",
                                             "interpret", "with_alive"))
def masked_sum_2d(msgs, scalars, *, scale_bits: int, num_clients: int,
                  interpret: bool = False, with_alive: bool = False):
    """The streaming kernel: (I_loc, R, 128) f32 messages → (R, 128) int32.

    ``scalars``: (3,) uint32 — [key0, key1, client_offset] — or, with
    ``with_alive=True``, (3 + num_clients,) uint32 with the 0/1 alive
    bits of every global cohort position appended (dropout recovery: the
    kernel cancels dropped peers' mask streams and zeroes dropped rows'
    uploads, exactly as the XLA paths do).  The scalars sit in SMEM.  The
    grid runs over (row block, local client): each step quantizes one
    client's block, regenerates its directed mask streams for the block's
    counter range in VMEM, applies them with int32 wraparound, and adds
    the masked upload into the block's accumulator — masks never touch
    HBM, and VMEM holds one client block at a time whatever I_loc is.
    Use :func:`repro.kernels.ops.secure_quant_sum` for arbitrary message
    pytrees.
    """
    i_loc, rows, lanes = msgs.shape
    block = min(BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, block), i_loc)
    return pl.pallas_call(
        _make_kernel(num_clients, scale_bits, with_alive),
        grid=grid,
        in_specs=[pl.BlockSpec((None, block, lanes),
                               lambda r, li: (li, r, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((block, lanes), lambda r, li: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(msgs, scalars)
