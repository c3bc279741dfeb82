"""Fused SSCA server-update kernel (the paper's per-round hot path).

One elementwise pass over the (sharded) parameter shard fuses all four
update equations of Algorithm 1 with the canonical surrogate (6):

    lin'  = (1−ρ)·lin + ρ·(g − 2τ·ω)          # recursion (14)/(15)
    β'    = (1−ρ)·β  + ρ·ω                     # recursion (13)   [λ>0 only]
    ω̄     = −(lin' + 2λβ') / (2τ)              # closed form (16)/(17)
    ω'    = (1−γ)·ω + γ·ω̄                      # iterate move (4)

Run unfused this is 4 HBM round-trips over 3–4 model-sized tensors; fused
it is one read of (ω, lin, β, g) and one write of (ω', lin', β') — the
update becomes strictly HBM-bandwidth-bound at its floor.

TPU mapping: inputs are reshaped to (N/128, 128) and tiled (BLOCK_ROWS,
128) — lane-dim 128 keeps the VPU fully occupied; BLOCK_ROWS=512 puts
~1.3 MB per operand in VMEM (4 inputs + 3 outputs ≈ 4.6 MB, well under
the ~16 MB v5e VMEM budget).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 512
LANES = 128


def _kernel(w_ref, lin_ref, g_ref, beta_ref, scalars_ref,
            w_out, lin_out, beta_out):
    rho = scalars_ref[0]
    gamma = scalars_ref[1]
    tau = scalars_ref[2]
    lam = scalars_ref[3]
    w = w_ref[...].astype(jnp.float32)
    lin = lin_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    beta = beta_ref[...].astype(jnp.float32)

    lin_new = (1.0 - rho) * lin + rho * (g - 2.0 * tau * w)      # (14)/(15)
    beta_new = (1.0 - rho) * beta + rho * w                      # (13)
    omega_bar = -(lin_new + 2.0 * lam * beta_new) / (2.0 * tau)  # (16)/(17)
    w_new = (1.0 - gamma) * w + gamma * omega_bar                # (4)

    w_out[...] = w_new.astype(w_out.dtype)
    lin_out[...] = lin_new.astype(lin_out.dtype)
    beta_out[...] = beta_new.astype(beta_out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssca_update_2d(w, lin, g, beta, scalars, *, interpret: bool = False):
    """w/lin/g/beta: (R, 128) same dtype; scalars: (4,) f32 [ρ, γ, τ, λ].

    Returns (w', lin', β').  Use :func:`repro.kernels.ops.ssca_update` for
    arbitrary-shaped pytrees (it flattens, pads and reshapes).
    """
    rows = w.shape[0]
    block = min(BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, block),)
    spec = pl.BlockSpec((block, LANES), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct(w.shape, w.dtype),
                 jax.ShapeDtypeStruct(lin.shape, lin.dtype),
                 jax.ShapeDtypeStruct(beta.shape, beta.dtype)]
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[spec, spec, spec],
        out_shape=out_shape,
        interpret=interpret,
    )(w, lin, g, beta, scalars)
