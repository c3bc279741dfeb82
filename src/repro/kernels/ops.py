"""Jit'd public wrappers around the Pallas kernels.

Handle arbitrary shapes (flatten + pad to lane multiples), GQA head
mapping, and dtype plumbing.  ``interpret=True`` executes the kernel body
in Python on CPU — the validation mode used by the test suite; on a real
TPU the same calls compile to Mosaic.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as _fa
from repro.kernels import rwkv6_scan as _rw
from repro.kernels import secure_agg as _sa
from repro.kernels import ssca_update as _su

PyTree = Any
LANES = _su.LANES


def ssca_update(params: PyTree, lin: PyTree, grads: PyTree,
                beta: Optional[PyTree] = None, *, rho, gamma, tau: float,
                lam: float = 0.0, interpret: bool = False):
    """Fused Algorithm-1 server update over a whole pytree.

    Runs the fused kernel on each leaf's own float32 view: (rows, last
    dim) where the last dim is a multiple of 128 (no relayout on a TPU),
    else (N/128, 128), padding only a leaf whose size needs it.  With
    ``beta=None`` (a λ = 0 objective) the kernel neither reads nor
    writes β and the third result is None; a given ``beta`` is streamed
    and returned updated per (13), whatever ``lam`` is.  Returns
    (params', lin', beta').
    """
    leaves_w, treedef = jax.tree_util.tree_flatten(params)
    leaves_l = jax.tree.leaves(lin)
    leaves_g = jax.tree.leaves(grads)
    leaves_b = [None] * len(leaves_w) if beta is None \
        else jax.tree.leaves(beta)
    scalars = jnp.asarray([rho, gamma, tau, lam], jnp.float32)
    new_w, new_l, new_b = [], [], []
    for w, l, g, b in zip(leaves_w, leaves_l, leaves_g, leaves_b):
        lanes = w.shape[-1] if w.ndim and w.shape[-1] % LANES == 0 \
            else LANES
        pad = (-w.size) % lanes

        def view(x):
            x = x.astype(jnp.float32).reshape(-1)
            return (jnp.pad(x, (0, pad)) if pad else x).reshape(-1, lanes)

        def back(v):
            return v.reshape(-1)[:w.size].reshape(w.shape).astype(w.dtype)

        w2, l2, b2 = _su.ssca_update_2d(
            view(w), view(l), view(g), None if b is None else view(b),
            scalars, interpret=interpret)
        new_w.append(back(w2))
        new_l.append(back(l2))
        if b is not None:
            new_b.append(back(b2))
    tree = functools.partial(jax.tree_util.tree_unflatten, treedef)
    return tree(new_w), tree(new_l), None if beta is None else tree(new_b)


class CombineCall(NamedTuple):
    """One :func:`repro.kernels.secure_agg.masked_sum_2d` call of
    :func:`secure_quant_sum`'s kernel path."""
    leaves: range    # the message leaves it covers, in tree order
    offset: int      # their first element's index in the flat message
    size: int        # their elements a client
    rows: int        # the call's (rows, width) view of them
    width: int
    concat: bool     # concatenated and padded to (rows, 128)


def _own_view(shape) -> bool:
    """A leaf whose last dim D is a multiple of 128 above 128: its
    (size/D, D) view is a bitcast of the upload, and its (size/128, 128)
    view would be a relayout on a TPU."""
    return len(shape) > 0 and shape[-1] > LANES and shape[-1] % LANES == 0


def combine_calls(shapes) -> list[CombineCall]:
    """The kernel calls of :func:`secure_quant_sum` over a message of
    per-client leaf ``shapes`` (tree order): each leaf that
    :func:`_own_view` admits alone, in its own (size/D, D) view; each
    maximal run of the other leaves concatenated and padded to 128
    lanes."""
    sizes = [int(np.prod(s)) for s in shapes]
    calls, start, offset = [], 0, 0
    while start < len(shapes):
        stop = start + 1
        if _own_view(shapes[start]):
            d = shapes[start][-1]
            calls.append(CombineCall(range(start, stop), offset,
                                     sizes[start], sizes[start] // d, d,
                                     False))
        else:
            while stop < len(shapes) and not _own_view(shapes[stop]):
                stop += 1
            size = sum(sizes[start:stop])
            calls.append(CombineCall(range(start, stop), offset, size,
                                     -(-size // LANES), LANES, True))
        offset += calls[-1].size
        start = stop
    return calls


def secure_quant_sum(wmsgs: PyTree, key_data, *, scale_bits: int,
                     client_offset=0, num_clients: Optional[int] = None,
                     alive=None, interpret: bool = False,
                     use_kernel: Optional[bool] = None) -> PyTree:
    """Streaming masked quantized aggregate over a message pytree.

    Every leaf carries a leading client axis (I_loc, ...).  Runs the
    streaming secure aggregation (:mod:`repro.kernels.secure_agg` —
    quantize + counter-based pair masks + Z_{2^32} accumulate in one
    pass) over the tree flattened to one (I_loc, n) message, and returns
    the (n,) int32 aggregate in per-leaf shape.  The kernel path reads
    the message in the calls :func:`combine_calls` gives, each masked at
    its elements' flat indices, so the aggregate and every shard's
    partial are the same words as the flat message's; the XLA paths
    concatenate the tree.  Masks are never materialized at model size.

    ``client_offset``/``num_clients`` give the shard's global client ids
    ([offset, offset + I_loc) of num_clients) for the sharded engine —
    psum the returned int32 pytree over the client axis, then
    :func:`secure_dequantize`.  ``alive`` (optional (num_clients,) 0/1)
    enables dropout recovery: dropped positions contribute nothing and
    every survivor's mask stream against them is cancelled, so the
    aggregate equals the plain survivor sum bit-for-bit (see
    :mod:`repro.kernels.secure_agg`).  ``use_kernel=None`` auto-selects
    the Pallas kernel on TPU and the XLA streaming path elsewhere (the
    kernel is also used under ``interpret=True`` for CPU validation).
    """
    leaves, treedef = jax.tree_util.tree_flatten(wmsgs)
    i_loc = leaves[0].shape[0]
    shapes = [x.shape[1:] for x in leaves]
    nc = i_loc if num_clients is None else int(num_clients)
    # 2-word PRF key from whatever key_data the PRNG impl yields (threefry
    # keys are (2,), rbg/unsafe_rbg are (4,) — take the first/last words)
    kd = jnp.asarray(key_data, jnp.uint32).reshape(-1)
    key_data = jnp.stack([kd[0], kd[-1]])
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel or interpret:
        scalars = [key_data,
                   jnp.asarray(client_offset).astype(jnp.uint32).reshape(1)]
        if alive is not None:
            scalars.append(jnp.asarray(alive).astype(jnp.uint32).reshape(-1))
        scalars = jnp.concatenate(scalars)
        out = []
        for call in combine_calls(shapes):
            if call.concat:
                msgs = jnp.concatenate(
                    [leaves[k].astype(jnp.float32).reshape(i_loc, -1)
                     for k in call.leaves], axis=1)
                pad = call.rows * LANES - call.size
                if pad:
                    # padded slots are dropped below: they never leave
                    # the call, so their counters may repeat the next's
                    msgs = jnp.pad(msgs, ((0, 0), (0, pad)))
            else:
                msgs = leaves[call.leaves[0]].astype(jnp.float32)
            agg = _sa.masked_sum_2d(
                msgs.reshape(i_loc, call.rows, call.width), scalars,
                scale_bits=scale_bits, num_clients=nc,
                counter_base=call.offset, with_alive=alive is not None,
                interpret=interpret)
            if call.concat:
                out += _split(agg.reshape(-1),
                              [shapes[k] for k in call.leaves])
            else:
                out.append(agg.reshape(shapes[call.leaves[0]]))
        return jax.tree_util.tree_unflatten(treedef, out)
    flat = jnp.concatenate(
        [x.astype(jnp.float32).reshape(i_loc, -1) for x in leaves], axis=1)
    if isinstance(client_offset, int) and client_offset == 0 \
            and i_loc == nc:
        agg = _sa.masked_sum_flat(flat, key_data, scale_bits, alive)
    else:
        agg = _sa.masked_partial_sum_flat(flat, key_data, scale_bits,
                                          client_offset, nc, alive)
    return jax.tree_util.tree_unflatten(treedef, _split(agg, shapes))


def _split(flat, shapes) -> list:
    """The leaves of ``shapes`` from the front of a flat array."""
    out, off = [], 0
    for shape in shapes:
        size = int(np.prod(shape))
        out.append(flat[off:off + size].reshape(shape))
        off += size
    return out


def secure_ring_partial_sum(partials: PyTree, key_data, *, group_offset=0,
                            num_groups: Optional[int] = None) -> PyTree:
    """Group-level masked merge of already-quantized partial sums.

    Level 2 of the hierarchical tree: every leaf carries a leading group
    axis (G_loc, ...) of **int32 ring elements** (the within-group masked
    sums of level 1).  Flattens the tree, re-masks each group partial
    with the directed counter-mode streams keyed by the *group-tagged*
    round key (:func:`repro.kernels.secure_agg.group_key_words` —
    domain-separated from all client-level streams), and sums with int32
    wraparound.  No dequantize/requantize round trip: the masking acts
    directly in Z_{2^32}, so psum of the returned pytree over the group
    axis equals the plain sum of all partials bit-for-bit.

    ``group_offset``/``num_groups`` give the shard's global group ids,
    mirroring :func:`secure_quant_sum`'s client ids.
    """
    leaves, treedef = jax.tree_util.tree_flatten(partials)
    g_loc = leaves[0].shape[0]
    shapes = [x.shape[1:] for x in leaves]
    ng = g_loc if num_groups is None else int(num_groups)
    kd = jnp.asarray(key_data, jnp.uint32).reshape(-1)
    key0, key1 = _sa.group_key_words(kd[0], kd[-1])
    flat = jnp.concatenate(
        [x.astype(jnp.int32).reshape(g_loc, -1) for x in leaves], axis=1)
    agg = _sa.masked_ring_partial_sum(flat, key0, key1, group_offset, ng)
    return jax.tree_util.tree_unflatten(treedef, _split(agg, shapes))


def secure_dequantize(agg_q: PyTree, scale_bits: int) -> PyTree:
    """int32 fixed-point aggregate pytree → f32 (grid 2^-scale_bits)."""
    return jax.tree.map(lambda q: _sa.dequantize(q, scale_bits), agg_q)


def flash_attention(q, k, v, *, block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """Causal GQA flash attention.

    q: (B, S, H, Dh); k/v: (B, S, Hkv, Dh).  Returns (B, S, H, Dh).
    Head_dim is zero-padded to a multiple of 128 (softmax scale uses the
    true Dh); kv heads are index-mapped to q heads without materializing
    the GQA repeat (k/v are reshaped per kv-head and the group dim folds
    into the batch axis of the kernel grid).
    """
    b, s, h, dh = q.shape
    hkv = k.shape[2]
    gsz = h // hkv
    scale = dh ** -0.5
    pad = (-dh) % 128
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, pad)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
    dp = dh + pad
    # (B, S, Hkv, G, D) -> (B·Hkv·G, S, D); k/v broadcast over G
    qb = q.reshape(b, s, hkv, gsz, dp).transpose(0, 2, 3, 1, 4) \
        .reshape(b * hkv * gsz, s, dp)
    kb = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (b, hkv, gsz, s, dp)).reshape(b * hkv * gsz, s, dp)
    vb = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (b, hkv, gsz, s, dp)).reshape(b * hkv * gsz, s, dp)
    bq = min(block_q, s)
    bk = min(block_k, s)
    o = _fa.flash_attention_bhsd(qb, kb, vb, scale, block_q=bq, block_k=bk,
                                 interpret=interpret)
    o = o.reshape(b, hkv, gsz, s, dp).transpose(0, 3, 1, 2, 4) \
        .reshape(b, s, h, dp)
    return o[..., :dh]


def rwkv6_wkv(r, k, v, w, u, *, chunk: int = 16, interpret: bool = False):
    """WKV with data-dependent decay.

    r/k/v/w: (B, S, H, Dh) with w ∈ (0, 1] the per-token decay; u: (H, Dh).
    Returns (B, S, H, Dh) f32.
    """
    b, s, h, dh = r.shape
    lw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-20))
    lw = jnp.clip(lw, -5.0, 0.0)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, dh)

    rb, kb, vb, lb = map(to_bh, (r, k, v, lw))
    ub = jnp.broadcast_to(u[None], (b, h, dh)).reshape(b * h, 1, dh)
    o = _rw.rwkv6_wkv_bh(rb, kb, vb, lb, ub, chunk=min(chunk, s),
                         interpret=interpret)
    return o.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
