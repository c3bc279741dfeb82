"""Fused SSCA server-update kernel (the paper's per-round hot path).

One elementwise pass over the (sharded) parameter shard fuses all four
update equations of Algorithm 1 with the canonical surrogate (6):

    lin'  = (1−ρ)·lin + ρ·(g − 2τ·ω)          # recursion (14)/(15)
    β'    = (1−ρ)·β  + ρ·ω                     # recursion (13)   [λ>0 only]
    ω̄     = −(lin' + 2λβ') / (2τ)              # closed form (16)/(17)
    ω'    = (1−γ)·ω + γ·ω̄                      # iterate move (4)

Run unfused this is 4 HBM round-trips over 3–4 model-sized tensors; fused
it is one read of (ω, lin, β, g) and one write of (ω', lin', β') — the
update becomes strictly HBM-bandwidth-bound at its floor.  A λ = 0
objective has no β: its variant reads (ω, lin, g) and writes (ω', lin'),
20 bytes a parameter where the λ > 0 kernel moves 28.

TPU mapping: each parameter leaf runs as a 2-D array whose minor
dimension is a multiple of 128 — its own (rows, last dim) shape where
the last dim is one, so the view is free of any relayout, else its
words as (N/128, 128) — tiled by :func:`_block` into tiles of
BLOCK_ROWS·128 words: 256 KiB a float32 operand in VMEM (4 inputs + 3
outputs, double-buffered, ≈ 3.7 MB, well under the ~16 MB v5e VMEM
budget).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 512
LANES = 128
MAX_BLOCK_COLS = 2048


def _kernel(w_ref, lin_ref, g_ref, *refs, with_beta: bool):
    if with_beta:
        beta_ref, scalars_ref, w_out, lin_out, beta_out = refs
    else:
        scalars_ref, w_out, lin_out = refs
    rho = scalars_ref[0]
    gamma = scalars_ref[1]
    tau = scalars_ref[2]
    lam = scalars_ref[3]
    w = w_ref[...].astype(jnp.float32)
    lin = lin_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)

    lin_new = (1.0 - rho) * lin + rho * (g - 2.0 * tau * w)      # (14)/(15)
    if with_beta:
        beta = beta_ref[...].astype(jnp.float32)
        beta_new = (1.0 - rho) * beta + rho * w                  # (13)
        omega_bar = -(lin_new + 2.0 * lam * beta_new) / (2.0 * tau)
        beta_out[...] = beta_new.astype(beta_out.dtype)
    else:
        omega_bar = -lin_new / (2.0 * tau)                       # (16)/(17)
    w_new = (1.0 - gamma) * w + gamma * omega_bar                # (4)

    w_out[...] = w_new.astype(w_out.dtype)
    lin_out[...] = lin_new.astype(lin_out.dtype)


def _block(rows: int, cols: int) -> tuple[int, int]:
    """A (rows, cols) tile of about BLOCK_ROWS·128 words: the widest
    multiple of 128 lanes up to MAX_BLOCK_COLS that divides ``cols``,
    and the rows that fill the rest (all rows where there are fewer)."""
    lanes = max(c for c in range(LANES, min(cols, MAX_BLOCK_COLS) + 1, LANES)
                if cols % c == 0)
    return min(rows, BLOCK_ROWS * LANES // lanes), lanes


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssca_update_2d(w, lin, g, beta, scalars, *, interpret: bool = False):
    """w/lin/g/beta: (R, C) same dtype, C a multiple of 128; scalars:
    (4,) f32 [ρ, γ, τ, λ].

    Returns (w', lin', β').  ``beta=None`` (a λ = 0 objective) runs the
    variant without β: three inputs, two outputs, (w', lin', None), and
    ω̄ = −lin'/(2τ).  Use :func:`repro.kernels.ops.ssca_update` for
    arbitrary-shaped pytrees.
    """
    rows, cols = w.shape
    block = _block(rows, cols)
    grid = (pl.cdiv(rows, block[0]), cols // block[1])
    spec = pl.BlockSpec(block, lambda i, j: (i, j))
    with_beta = beta is not None
    arrays = (w, lin, g, beta) if with_beta else (w, lin, g)
    outs = [w, lin, beta] if with_beta else [w, lin]
    new = pl.pallas_call(
        functools.partial(_kernel, with_beta=with_beta),
        grid=grid,
        in_specs=[spec] * len(arrays)
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[spec] * len(outs),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in outs],
        interpret=interpret,
    )(*arrays, scalars)
    return tuple(new) if with_beta else (new[0], new[1], None)
