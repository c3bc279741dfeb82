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

**How many mask words the kernel generates.**  A device holding I_loc of
the cohort's S clients generates, per row of the message it covers,
I_loc(I_loc−1)/2 + I_loc(S−I_loc) streams' words: each pair with both
clients on the device once, each pair with a client elsewhere once on
each endpoint device (the other endpoint holds the other copy).  That is
the least the protocol allows, and it is no less masking: every client's
upload q̃_i is still formed in full, with every one of its pair masks,
before it is added to the aggregate.  A local pair's stream is expanded
at the lower client's step, added into its upload, and subtracted from
the higher client's pending upload, which waits in VMEM until that
client's own step completes it — the word the two endpoints would each
have derived from their shared seed, derived once.  The message, in
the (rows, W) view its caller gives it, is covered by blocks of 8-row
multiples (:func:`_plan`), so rows that do not exist cost at most the
few of a partial last block.  Only a cohort whose
pending uploads do not fit VMEM even 8 rows at a time (several thousand
clients on one device) keeps the directed schedule, I_loc(S−1) streams,
in which each client expands all of its own.

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
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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

SUBLANES = 8
# VMEM the kernel's blocks may take: the memoized schedule's pending
# uploads plus the double-buffered message and aggregate blocks.  A v5e
# compiles a kernel under a 16 MiB scoped limit by default; the rest is
# left to the compiler.
VMEM_BUDGET = 12 * 2 ** 20
# (8, 128) slabs that one pair's mask is expanded over at a time, 8-row
# slabs of a block up to 8·128 lanes wide: the chunk's running upload, its
# counters and the PRF's temporaries stay in the 64 vector registers.
CHUNK_SLABS = 8
# Peers whose masks one loop iteration expands.  One peer's PRF is a chain
# of about twenty dependent vector ops, too short to fill the ALUs alone;
# on a TPU v5e at 512 local clients the kernel took 92.6 ms expanding one
# peer an iteration, 54.7 ms with four and 52.2 ms with eight.
PEER_UNROLL = 8
# XLA's tile of a 1-D uint32 array on a TPU (the seed table's rows)
SEED_TILE = 1024


class _Plan(NamedTuple):
    """How :func:`masked_sum_2d` schedules one call's mask words."""
    tile: int        # message rows per grid step
    cols: int        # message columns per grid step, a multiple of 128
    chunk: int       # rows a pair's mask is expanded over at a time
    memo: bool       # local pairs expanded once; else directed streams
    words: int       # mask words the call generates
    vmem_bytes: int  # pending uploads + double-buffered blocks


def _plan(i_loc: int, num_clients: int, rows: int, width: int = LANES,
          budget: int = VMEM_BUDGET) -> _Plan:
    """Tile and schedule for ``i_loc`` local clients of ``num_clients``
    over a message of ``rows`` rows of ``width`` (a multiple of 128).

    A block is ``cols`` wide: 128 times the largest divisor of
    width / 128, at most :data:`CHUNK_SLABS`, whose 8-row pending
    scratch still fits, so a pair's mask over one 8-row chunk of the
    block stays in registers.  The memoized schedule keeps every local
    client's pending upload for one block in VMEM, (I_loc, tile, cols)
    int32.  The tile is the multiple of 8 rows that fits ``budget``
    beside the double-buffered blocks and covers ``rows`` with the
    fewest padded rows, then the fewest blocks (a partial last block is
    computed in full).  Only where not even an 8-row, 128-wide scratch
    fits does the call take the directed schedule, which keeps none.
    At ``width`` 128 a block is one 128-lane column.
    """
    buffers = 4                     # message and aggregate blocks, two each
    lanes = width // LANES

    def slab(d):                    # bytes of 8 rows of a d·128-wide block
        return SUBLANES * LANES * d * 4

    d = max((d for d in range(1, min(lanes, CHUNK_SLABS) + 1)
             if lanes % d == 0 and (i_loc + buffers) * slab(d) <= budget),
            default=1)
    memo = (i_loc + buffers) * slab(d) <= budget
    per_slab = ((i_loc if memo else 0) + buffers) * slab(d)
    if rows <= SUBLANES:
        tile = chunk = rows
    else:
        k_max = max(1, min(budget // per_slab, rows // SUBLANES))
        k = min(range(1, k_max + 1),
                key=lambda k: (-(-rows // (k * SUBLANES)) * k, -k))
        tile = k * SUBLANES
        chunk = SUBLANES * max(
            e for e in range(1, min(k, CHUNK_SLABS // d) + 1) if k % e == 0)
    if memo:
        streams = i_loc * (i_loc - 1) // 2 + i_loc * (num_clients - i_loc)
    else:
        streams = i_loc * (num_clients - 1)
    padded = -(-rows // tile) * tile
    vmem = per_slab * -(-tile // SUBLANES)
    return _Plan(tile, LANES * d, chunk, memo, streams * padded * width,
                 vmem)


def _seed_table(scalars, i_loc: int, width: int):
    """Row-major (I_loc, width) uint32, flat: local client i's row holds
    the pair seed it shares with each global peer j < width (the
    diagonal and the columns past the cohort go unused)."""
    i = scalars[2] + jnp.arange(i_loc, dtype=jnp.uint32)[:, None]
    j = jnp.arange(width, dtype=jnp.uint32)[None, :]
    return pair_seed(scalars[0], scalars[1], jnp.minimum(i, j),
                     jnp.maximum(i, j)).reshape(-1)


def _peer_loop(lo, hi, body, u):
    """``u = body(j, u)`` for j in [lo, hi), :data:`PEER_UNROLL` peers an
    iteration: their PRF chains are independent, so the scheduler can
    interleave them."""
    full = jnp.maximum(hi - lo, 0) // PEER_UNROLL

    def step(t, u):
        for d in range(PEER_UNROLL):
            u = body(lo + t * PEER_UNROLL + d, u)
        return u

    u = jax.lax.fori_loop(jnp.int32(0), full, step, u)
    return jax.lax.fori_loop(lo + full * PEER_UNROLL, hi, body, u)


def _make_kernel(plan: _Plan, i_loc: int, num_clients: int,
                 scale_bits: int, with_alive: bool, width: int,
                 counter_base: int):
    scale = float(2.0 ** scale_bits)
    tile, cols, chunk = plan.tile, plan.cols, plan.chunk

    def kernel(msgs_ref, seeds_ref, sc_ref, out_ref, *pending):
        # grid (row block r, column block cb, local client li): the
        # aggregate block and the pending uploads stay resident in VMEM
        # across the client axis
        r, cb, li = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        offset = sc_ref[2].astype(jnp.int32)
        i = offset + li                                   # global id

        @pl.when(li == 0)
        def _init():
            out_ref[...] = jnp.zeros(out_ref.shape, jnp.int32)
            for ref in pending:
                ref[...] = jnp.zeros(ref.shape, jnp.int32)

        if plan.memo:
            lower_end, upper_start = offset, offset + i_loc
        else:
            lower_end, upper_start = i, i + 1

        def weighted(j, m):
            # alive bits ride behind the key words: a dropped peer's
            # stream is cancelled, exactly as the XLA paths do
            return sc_ref[3 + j].astype(jnp.int32) * m if with_alive else m

        def one_chunk(c, carry):
            start = pl.multiple_of(c * chunk, chunk)
            rows = pl.ds(start, chunk)
            row = jax.lax.broadcasted_iota(jnp.uint32, (chunk, cols), 0)
            col = jax.lax.broadcasted_iota(jnp.uint32, (chunk, cols), 1)
            # the element's index in the flattened message
            counters = (np.uint32(counter_base)
                        + r.astype(jnp.uint32) * np.uint32(tile * width)
                        + cb.astype(jnp.uint32) * np.uint32(cols)
                        + start.astype(jnp.uint32) * np.uint32(width)
                        + row * np.uint32(width) + col)

            def words(j):
                return _i32(mask_bits(seeds_ref[j], counters))

            u = jnp.round(msgs_ref[rows, :] * scale).astype(jnp.int32)
            # peers below the local range (directed: below this client)
            u = _peer_loop(jnp.int32(0), lower_end,
                           lambda j, u: u - weighted(j, words(j)), u)
            if plan.memo:
                (pend_ref,) = pending
                u = u + pend_ref[li, rows, :]

                def local(lj, u):
                    # pair (i, offset + lj), i lower: one expansion, added
                    # here and owed by the higher client's pending upload
                    m = words(offset + lj)
                    pend_ref[lj, rows, :] = (pend_ref[lj, rows, :]
                                             - weighted(i, m))
                    return u + weighted(offset + lj, m)

                u = _peer_loop(li + 1, jnp.int32(i_loc), local, u)
            u = _peer_loop(upper_start, jnp.int32(num_clients),
                           lambda j, u: u + weighted(j, words(j)), u)
            if with_alive:
                u = u * sc_ref[3 + i].astype(jnp.int32)
            # the client's masked upload is whole: only now does it cross
            # into the aggregate
            out_ref[rows, :] = out_ref[rows, :] + u
            return carry

        jax.lax.fori_loop(0, tile // chunk, one_chunk, 0)

    return kernel


def _masked_sum(msgs, scalars, plan: _Plan, *, scale_bits: int,
                num_clients: int, interpret: bool, with_alive: bool,
                counter_base: int = 0):
    """:func:`masked_sum_2d` under an explicit plan."""
    i_loc, rows, width = msgs.shape
    tile, cols = plan.tile, plan.cols
    assert counter_base + rows * width <= 2 ** 32, \
        "mask counters are uint32: the flat message must stay below 2^32"
    # a client's seed row is one SMEM block of the flat table, whose
    # length has to be a multiple of XLA's 1-D tile
    seeds = -(-num_clients // SEED_TILE) * SEED_TILE
    scratch = [pltpu.VMEM((i_loc, tile, cols), jnp.int32)] \
        if plan.memo else []
    return pl.pallas_call(
        _make_kernel(plan, i_loc, num_clients, scale_bits, with_alive,
                     width, counter_base),
        grid=(pl.cdiv(rows, tile), width // cols, i_loc),
        in_specs=[pl.BlockSpec((None, tile, cols),
                               lambda r, cb, li: (li, r, cb)),
                  pl.BlockSpec((seeds,), lambda r, cb, li: (li,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((tile, cols), lambda r, cb, li: (r, cb)),
        out_shape=jax.ShapeDtypeStruct((rows, width), jnp.int32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(msgs, _seed_table(scalars, i_loc, seeds), scalars)


def mask_words(i_loc: int, num_clients: int, rows: int, width: int) -> int:
    """Mask words one :func:`masked_sum_2d` call generates."""
    return _plan(i_loc, num_clients, rows, width).words


@functools.partial(jax.jit, static_argnames=("scale_bits", "num_clients",
                                             "counter_base", "interpret",
                                             "with_alive"))
def masked_sum_2d(msgs, scalars, *, scale_bits: int, num_clients: int,
                  counter_base: int = 0, interpret: bool = False,
                  with_alive: bool = False):
    """The streaming kernel: (I_loc, R, W) f32 messages → (R, W) int32,
    W a multiple of 128.

    Element (row, col) is element ``counter_base + row·W + col`` of the
    flattened message: that index is its mask counter, so a message cut
    into several calls, each in its own (R, W) view, is masked exactly as
    the whole message flattened to (R, 128) in one call.

    ``scalars``: (3,) uint32 — [key0, key1, client_offset] — or, with
    ``with_alive=True``, (3 + num_clients,) uint32 with the 0/1 alive
    bits of every global cohort position appended (dropout recovery: the
    kernel cancels dropped peers' mask streams and zeroes dropped rows'
    uploads, exactly as the XLA paths do).  The scalars sit in SMEM, and
    so does the row of pair seeds of the step's client (an XLA-built
    table of I_loc rows).  The grid runs over (row block, column
    block, local client): each step quantizes one client's block, forms
    its masked upload in VMEM — its pending sum from lower local peers,
    plus one expansion of each pair it shares with a higher local peer
    (whose pending sum it debits), plus its directed streams against the
    other devices' clients — and only then adds it into the block's
    accumulator.  Masks never touch HBM.  The block comes from
    :func:`_plan`.  Use :func:`repro.kernels.ops.secure_quant_sum` for
    arbitrary message pytrees.
    """
    i_loc, rows, width = msgs.shape
    return _masked_sum(msgs, scalars, _plan(i_loc, num_clients, rows, width),
                       scale_bits=scale_bits, num_clients=num_clients,
                       interpret=interpret, with_alive=with_alive,
                       counter_base=counter_base)
