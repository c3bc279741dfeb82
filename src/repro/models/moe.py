"""Mixture-of-Experts feed-forward with top-k token-choice routing.

TPU-idiomatic dispatch: routing is resolved *per example* (sort over the
S·k within-example assignments, capacity-bounded scatter into an
``(E, C, D)`` buffer, grouped expert einsum, weighted combine).  Sorting
along an unsharded axis keeps the dispatch collective-free under pjit; the
expert einsum is the only op touching the expert-sharded (model) axis, so
XLA inserts exactly the all-to-all pair the MoE literature expects.

Includes the standard load-balance auxiliary loss (Switch/GShard form) —
part of ``f0`` for SSCA purposes.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class MoEOutput(NamedTuple):
    y: jnp.ndarray          # (B, S, D)
    aux_loss: jnp.ndarray   # scalar load-balance loss
    dropped_frac: jnp.ndarray  # diagnostics: fraction of assignments dropped


def capacity_for(seq: int, k: int, num_experts: int,
                 capacity_factor: float = 1.25) -> int:
    c = int(seq * k * capacity_factor / num_experts) + 1
    return max(1, min(c, seq * k))


def route(x, w_router, k: int):
    """Router in f32. x: (B, S, D) -> (gates (B,S,k), idx (B,S,k), probs)."""
    logits = jnp.einsum('bsd,de->bse', x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, idx, probs


def load_balance_loss(probs, idx, num_experts: int):
    """GShard aux loss: E · Σ_e (mean prob to e) · (mean fraction routed e)."""
    me = jnp.mean(probs, axis=(0, 1))                       # (E,)
    assign = jax.nn.one_hot(idx[..., 0], num_experts)       # top-1 fraction
    ce = jnp.mean(assign, axis=(0, 1))
    return num_experts * jnp.sum(me * ce)


def moe_ffn(x, params, *, num_experts: int, k: int,
            capacity_factor: float = 1.25) -> MoEOutput:
    """x: (B, S, D).  params: router (D,E), wg/wu (E,D,F), wd (E,F,D),
    optionally shared_{wg,wu,wd} for a shared expert (llama4-style)."""
    b, s, d = x.shape
    e = num_experts
    cap = capacity_for(s, k, e, capacity_factor)
    gates, idx, probs = route(x, params["router"], k)

    def dispatch_one(xe, idx_e, gates_e):
        """Per-example routing. xe: (S, D); idx/gates: (S, k)."""
        sk = s * k
        flat_e = idx_e.reshape(sk)
        flat_g = gates_e.reshape(sk)
        order = jnp.argsort(flat_e)
        e_sorted = flat_e[order]
        tok = order // k
        pos = jnp.arange(sk) - jnp.searchsorted(e_sorted, e_sorted, side='left')
        keep = pos < cap
        pos_c = jnp.where(keep, pos, 0)
        buf = jnp.zeros((e, cap, d), xe.dtype)
        buf = buf.at[e_sorted, pos_c].add(
            jnp.where(keep[:, None], xe[tok], 0.0))
        return buf, (order, e_sorted, tok, pos_c, keep, flat_g)

    bufs, aux = jax.vmap(dispatch_one)(x, idx, gates)        # (B, E, C, D)

    # Grouped expert SwiGLU: (B,E,C,D) x (E,D,F) — E is the sharded axis.
    g = jax.nn.silu(jnp.einsum('becd,edf->becf', bufs, params["wg"]))
    u = jnp.einsum('becd,edf->becf', bufs, params["wu"])
    y_buf = jnp.einsum('becf,efd->becd', g * u, params["wd"])

    def combine_one(ybuf, pack):
        order, e_sorted, tok, pos_c, keep, flat_g = pack
        gathered = ybuf[e_sorted, pos_c]                     # (S·k, D)
        w = jnp.where(keep, flat_g[order], 0.0)
        out = jnp.zeros((s, d), ybuf.dtype)
        return out.at[tok].add(gathered * w[:, None].astype(ybuf.dtype))

    y = jax.vmap(combine_one)(y_buf, aux)
    if "shared_wg" in params:
        g = jax.nn.silu(x @ params["shared_wg"])
        y = y + (g * (x @ params["shared_wu"])) @ params["shared_wd"]

    aux_loss = load_balance_loss(probs, idx, e)
    kept = jnp.mean(aux[4].astype(jnp.float32))   # aux[4] = keep, (B, S·k)
    return MoEOutput(y.astype(x.dtype), aux_loss, 1.0 - kept)


# ---------------------------------------------------------------------------
# Expert-parallel MoE under shard_map (the production path)
# ---------------------------------------------------------------------------
#
# The pjit/scatter formulation above is correct but the SPMD partitioner
# replicates the (E, C, D) dispatch buffer per device (data-dependent
# scatter), which costs ~80 GiB/device on the 235B/400B MoE configs.  The
# shard_map formulation makes every op *local*: each device routes its own
# batch shard, builds buffers only for its local experts (gather, not
# scatter), runs the expert einsum on its expert shard (FSDP-gathering the
# expert weights' d_model dim from the data axis), scatters locally into a
# (B_loc, S, D) accumulator, and psums over the `model` axis to combine
# contributions from all expert owners — the MoE combine collective.

def _shard_map(f, in_specs, out_specs):
    """``shard_map`` over the ambient mesh (``jax.set_mesh``)."""
    return jax.shard_map(f, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _slots_for_experts(idx_e, gates_e, e_lo, e_loc: int, cap: int, k: int):
    """Per-example slot map for experts [e_lo, e_lo+e_loc).

    idx_e, gates_e: (S, k).  Returns (tok_idx (e_loc, C), gate (e_loc, C),
    valid (e_loc, C)) — which token each expert slot reads, its combine
    weight, and slot validity."""
    s = idx_e.shape[0]
    sk = s * k
    flat_e = idx_e.reshape(sk)
    flat_g = gates_e.reshape(sk)
    order = jnp.argsort(flat_e)
    e_sorted = flat_e[order]
    tok_sorted = order // k
    g_sorted = flat_g[order]
    my_experts = e_lo + jnp.arange(e_loc)
    start = jnp.searchsorted(e_sorted, my_experts, side='left')
    end = jnp.searchsorted(e_sorted, my_experts, side='right')
    slot = start[:, None] + jnp.arange(cap)[None, :]          # (e_loc, C)
    valid = slot < end[:, None]
    slot_c = jnp.clip(slot, 0, sk - 1)
    return tok_sorted[slot_c], g_sorted[slot_c], valid


def moe_ffn_sharded(x, params, *, num_experts: int, k: int,
                    capacity_factor: float = 1.25,
                    dp_axes=("data",), tp_axis: str = "model",
                    fsdp_axis="data",
                    weight_mode: str = "fsdp") -> MoEOutput:
    """Expert-parallel MoE.  Must be called under the production mesh.

    weight_mode:
    * "fsdp" (train default) — expert weights (E@tp, D@fsdp, F); the
      d_model shard is all-gathered from the data axis per layer (cheap
      relative to a train step's math, required for optimizer-state fit).
    * "stationary" (decode) — expert weights (E@tp, D, F@fsdp); weights
      never move: the (tiny) decode batch is replicated across the data
      axis instead, every device computes its (expert, d_ff) shard, and
      one small psum over (data, model) combines.  Kills the per-token
      weight gather that dominates MoE decode collectives.
    """
    b, s, d = x.shape
    e = num_experts
    cap = capacity_for(s, k, e, capacity_factor)

    stationary = weight_mode == "stationary"

    def local_fn(x_blk, router, ewg, ewu, ewd):
        """x_blk: (B_loc, S, D) (replicated over tp; over data too when
        stationary); ewg/ewu: (E_loc, D/fsdp, F) or (E_loc, D, F/fsdp);
        ewd: (E_loc, F, D/fsdp) or (E_loc, F/fsdp, D)."""
        e_loc = ewg.shape[0]
        tp_i = jax.lax.axis_index(tp_axis)
        e_lo = tp_i * e_loc
        gates, idx, probs = route(x_blk, router, k)
        tok, gate, valid = jax.vmap(
            lambda i_, g_: _slots_for_experts(i_, g_, e_lo, e_loc, cap, k)
        )(idx, gates)                                  # (B_loc, e_loc, C)

        # FSDP-gather the expert weights' d_model dim from the data axis
        # (train path only; stationary mode never moves weights).
        if fsdp_axis is not None and not stationary:
            ewg = jax.lax.all_gather(ewg, fsdp_axis, axis=1, tiled=True)
            ewu = jax.lax.all_gather(ewu, fsdp_axis, axis=1, tiled=True)
            ewd = jax.lax.all_gather(ewd, fsdp_axis, axis=2, tiled=True)

        def one_example(xe, tok_e, gate_e, valid_e):
            buf = xe[tok_e.reshape(-1)].reshape(e_loc, cap, d)
            buf = jnp.where(valid_e[..., None], buf, 0.0)
            g = jax.nn.silu(jnp.einsum('ecd,edf->ecf', buf, ewg))
            u = jnp.einsum('ecd,edf->ecf', buf, ewu)
            yb = jnp.einsum('ecf,efd->ecd', g * u, ewd)
            w = jnp.where(valid_e, gate_e, 0.0)
            out = jnp.zeros((s, d), yb.dtype)
            return out.at[tok_e.reshape(-1)].add(
                (yb * w[..., None].astype(yb.dtype)).reshape(-1, d))

        y = jax.vmap(one_example)(x_blk, tok, gate, valid)
        # combine across expert owners (+ d_ff shards when stationary)
        axes = (tp_axis, fsdp_axis) if (stationary and fsdp_axis) \
            else tp_axis
        y = jax.lax.psum(y, axes)
        aux = load_balance_loss(probs, idx, e)
        kept = jax.lax.psum(jnp.sum(valid.astype(jnp.float32)), tp_axis)
        expected = jnp.float32(x_blk.shape[0] * s * k)
        dropped = 1.0 - jnp.minimum(kept / expected, 1.0)
        return y, aux, dropped

    from jax.sharding import PartitionSpec as P
    dp = tuple(dp_axes) if dp_axes else ()
    bspec = dp if (dp and x.shape[0] > 1 and not stationary) else None
    if stationary:
        in_specs = (P(None, None, None),                    # x replicated
                    P(None, None),
                    P(tp_axis, None, fsdp_axis),            # ewg (E, D, F@d)
                    P(tp_axis, None, fsdp_axis),
                    P(tp_axis, fsdp_axis, None))            # ewd (E, F@d, D)
    else:
        in_specs = (P(bspec, None, None),                   # x
                    P(None, None),                          # router (D, E)
                    P(tp_axis, fsdp_axis, None),            # ewg (E, D, F)
                    P(tp_axis, fsdp_axis, None),            # ewu
                    P(tp_axis, None, fsdp_axis))            # ewd (E, F, D)
    out_specs = (P(bspec, None, None), P(), P())
    fn = _shard_map(local_fn, in_specs, out_specs)
    y, aux, dropped = fn(x, params["router"], params["wg"], params["wu"],
                         params["wd"])
    if "shared_wg" in params:
        g = jax.nn.silu(x @ params["shared_wg"])
        y = y + (g * (x @ params["shared_wu"])) @ params["shared_wd"]
    return MoEOutput(y.astype(x.dtype), aux, dropped)
