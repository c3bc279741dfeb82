"""A plain float32 reference of a dense GQA decoder LM: the Llama-style
block that Granite Code 8B (arXiv:2405.04324) stacks.

Straightforward ``jax.numpy`` for one sequence at a time, every product
at ``Precision.HIGHEST``, a Python loop over the layers, no kernel,
cache or batching, nothing imported from the model zoo:

    x    = E[tokens]
    per layer:
      h  = RMSNorm(x);  q, k, v = h·Wq, h·Wk, h·Wv   (H, Hkv heads of Dh)
      q, k = RoPE(q), RoPE(k)          (base θ, rotate-half pairs i, i+Dh/2)
      x += softmax(q·kᵀ/√Dh + causal mask)·v · Wo
           (query head j reads key-value head j // (H/Hkv))
      x += (silu(RMSNorm(x)·Wg) ⊙ RMSNorm(x)·Wu)·Wd
    logits = RMSNorm(x)·Eᵀ   (tied embedding)
    loss   = mean over positions of −log softmax(logits[:-1])[tokens[1:]]

Departures from the published description: the RMSNorm gain is stored
as its offset from 1 (the program's parameter layout, ``(1 + w)``),
which is the same function of the same number of parameters; and the
vocabulary is whatever the parameters' embedding holds (a chip's slice
of it in the benchmark's cut).  The parameter pytree is the program's:
``{"embed", "final_norm", "blocks": {name: (L, ...)}}``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _dot(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + gain)


def rope(x, theta):
    """x: (S, heads, Dh); pairs (i, i + Dh/2) rotate by pos·θ^(−2i/Dh)."""
    s, _, dh = x.shape
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs   # (S, Dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(params, tokens, *, heads, kv_heads, head_dim, theta, eps):
    """(S,) int tokens -> (S, V) float32 logits."""
    x = params["embed"].astype(jnp.float32)[tokens]
    s = tokens.shape[0]
    blocks = params["blocks"]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    group = heads // kv_heads
    for i in range(blocks["wq"].shape[0]):
        p = {k: v[i].astype(jnp.float32) for k, v in blocks.items()}
        h = rms_norm(x, p["attn_norm"], eps)
        q = rope(_dot("sd,de->se", h, p["wq"]).reshape(s, heads, head_dim),
                 theta)
        k = rope(_dot("sd,de->se", h, p["wk"]).reshape(s, kv_heads,
                                                      head_dim), theta)
        v = _dot("sd,de->se", h, p["wv"]).reshape(s, kv_heads, head_dim)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        scores = _dot("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(head_dim))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        o = _dot("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
        x = x + _dot("se,ed->sd", o.reshape(s, heads * head_dim), p["wo"])
        h = rms_norm(x, p["ffn_norm"], eps)
        f = jax.nn.silu(_dot("sd,df->sf", h, p["wg"])) \
            * _dot("sd,df->sf", h, p["wu"])
        x = x + _dot("sf,fd->sd", f, p["wd"])
    x = rms_norm(x, params["final_norm"].astype(jnp.float32), eps)
    return _dot("sd,vd->sv", x, params["embed"])


def sequence_loss(params, tokens, **arch):
    """Mean next-token cross-entropy of one sequence."""
    logp = jax.nn.log_softmax(logits(params, tokens, **arch)[:-1], -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], -1))


def arch_of(cfg) -> dict:
    """The reference's keyword arguments from a program ``ModelConfig``."""
    return dict(heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, theta=cfg.rope_theta,
                eps=cfg.norm_eps)


def weighted_loss_and_grad(params, tokens, weights, arch):
    """Σ_n w_n ℓ_n over the sequences ``tokens`` (N, S) and its gradient,
    one sequence at a time."""
    fn = jax.jit(jax.value_and_grad(
        lambda p, t: sequence_loss(p, t, **arch)))
    total, grad = 0.0, None
    with jax.default_matmul_precision("highest"):
        for t, w in zip(tokens, weights):
            v, g = fn(params, t)
            total = total + w * v
            g = jax.tree.map(lambda x: w * x, g)
            grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    return total, grad
