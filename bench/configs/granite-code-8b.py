"""granite-code-8b: one Granite Code 8B layer at its published widths.

arXiv:2405.04324: a dense decoder of 36 layers, hidden 4,096, 32 query
heads and 8 key-value heads of 128, SwiGLU of width 14,336, RMSNorm,
RoPE, a tied embedding of 49,152 tokens.  The cell holds one chip's
share (``granite-code-8b.json``): one layer and an eighth of the
vocabulary.

Program side: token sequences and weights made on the device from the
seed in one jitted call, and the task ``run_alg1`` trains
(:func:`repro.fed.tasks.transformer.chip_share_task`).  Reference side:
the same block in plain ``jax.numpy`` at float32, for one sequence at a
time, every product through the reference's :class:`reference.Dot`,
importing nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCK_LEAVES = ("attn_norm", "ffn_norm", "wq", "wk", "wv", "wo", "wg",
                "wu", "wd")


def _shapes(config) -> dict:
    """The program's parameter layout: layer-stacked block leaves."""
    d, f = config["hidden_size"], config["intermediate_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    n = config["num_hidden_layers"]
    return {"attn_norm": (n, d), "ffn_norm": (n, d), "wq": (n, d, q),
            "wk": (n, d, kv), "wv": (n, d, kv), "wo": (n, q, d),
            "wg": (n, d, f), "wu": (n, d, f), "wd": (n, f, d)}


def _data_and_params(key, config, n_train, n_test, seq_len):
    """Zipf token ids over the vocabulary slice (inverse CDF of a
    uniform draw), N(0, init_scale^2) matrices, RMSNorm gains at 1
    (stored as their offset 0)."""
    vocab, d = config["vocab_size"], config["hidden_size"]
    kd, ke, kb = jax.random.split(key, 3)
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    cdf = jnp.cumsum(ranks ** -config["data"]["zipf_exponent"])
    u = jax.random.uniform(kd, (n_train + n_test, seq_len)) * cdf[-1]
    tokens = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1) \
        .astype(jnp.int32)
    scale = config["init_scale"]
    shapes = _shapes(config)
    keys = dict(zip(BLOCK_LEAVES, jax.random.split(kb, len(BLOCK_LEAVES))))
    blocks = {k: (jnp.zeros(s, jnp.float32) if k.endswith("_norm")
                  else scale * jax.random.normal(keys[k], s))
              for k, s in shapes.items()}
    params = {"embed": scale * jax.random.normal(ke, (vocab, d)),
              "final_norm": jnp.zeros((d,), jnp.float32),
              "blocks": blocks}
    return tokens[:n_train], tokens[n_train:], params


def make(config, traffic, seed):
    """(data, params0) on the default device: one jitted call."""
    from repro.fed.tasks.base import TaskData

    fn = jax.jit(functools.partial(
        _data_and_params, config=config,
        n_train=traffic["clients"] * traffic["samples_per_client"],
        n_test=traffic["test_samples"], seq_len=traffic["seq_len"]))
    x_tr, x_te, params = fn(jax.random.key(seed))
    # tokens are their own labels: the loss shifts them
    return TaskData(x_tr, x_tr, x_te, x_te), params


def _model_config(config):
    """The program's registered configuration with the file's widths and
    dtypes (the same values, at the cell's size)."""
    import dataclasses

    from repro.configs import get_config

    return dataclasses.replace(
        get_config(config["program_config"]),
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"], param_dtype=config["param_dtype"],
        activ_dtype=config["activation_dtype"])


def task(config, traffic):
    from repro.fed.tasks.transformer import chip_share_task

    return chip_share_task(_model_config(config),
                           num_layers=config["num_hidden_layers"],
                           vocab=config["vocab_size"],
                           seq_len=traffic["seq_len"])


def model_flops_per_round(config, traffic) -> float:
    """The clients' forward and backward FLOPs of one round.

    With T = S·B·seq_len tokens a round, P the parameters that enter a
    matrix product (the projections and the FFN of every layer, and the
    tied embedding as the unembedding), L layers, H·Dh the query width:

        6·P·T + S·B·L · 6·seq_len²·H·Dh

    6·P·T is 2 FLOPs a weight a token forward and 4 backward; the second
    term is causal attention, whose q·kᵀ and p·v products each take
    2·seq_len²·H·Dh FLOPs over the full square, half of it visible
    (2·seq_len²·H·Dh forward), and three times that forward and
    backward.  Norms, RoPE, softmax and the embedding gather are left
    out; recomputation is not counted."""
    d, f = config["hidden_size"], config["intermediate_size"]
    hq = config["num_attention_heads"] * config["head_dim"]
    hkv = config["num_key_value_heads"] * config["head_dim"]
    layers, seq = config["num_hidden_layers"], traffic["seq_len"]
    matmul = layers * (2 * d * hq + 2 * d * hkv + 3 * d * f) \
        + config["vocab_size"] * d
    seqs = traffic["cohort"] * traffic["batch_size"]
    return 6.0 * matmul * seqs * seq + seqs * layers * 6.0 * seq * seq * hq


# --------------------------------------------------------------------------
# Plain reference
# --------------------------------------------------------------------------

def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + gain)


def _rope(x, theta):
    """x: (S, heads, Dh); pairs (i, i + Dh/2) turn by pos·θ^(−2i/Dh)."""
    s, _, dh = x.shape
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _sequence_loss(params, tokens, *, heads, kv_heads, head_dim, theta, eps,
                   dot):
    """Mean next-token cross-entropy of one (S,) sequence: embedding,
    per layer RMSNorm, GQA causal softmax attention with RoPE (query head
    j reads key-value head j // (H/Hkv)), RMSNorm, SwiGLU, then RMSNorm
    and the tied unembedding.  Departure: each RMSNorm gain is stored as
    its offset from 1, the program's layout."""
    x = params["embed"][tokens]
    s = tokens.shape[0]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    blocks = params["blocks"]
    for i in range(blocks["wq"].shape[0]):
        p = {k: v[i] for k, v in blocks.items()}
        h = _rms_norm(x, p["attn_norm"], eps)
        q = _rope(dot("sd,de->se", h, p["wq"]).reshape(s, heads, head_dim),
                  theta)
        k = _rope(dot("sd,de->se", h, p["wk"]).reshape(s, kv_heads,
                                                       head_dim), theta)
        v = dot("sd,de->se", h, p["wv"]).reshape(s, kv_heads, head_dim)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        scores = dot("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(head_dim))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        o = dot("hqk,khd->qhd", probs, v).reshape(s, heads * head_dim)
        x = x + dot("se,ed->sd", o, p["wo"])
        h = _rms_norm(x, p["ffn_norm"], eps)
        f = jax.nn.silu(dot("sd,df->sf", h, p["wg"])) \
            * dot("sd,df->sf", h, p["wu"])
        x = x + dot("sf,fd->sd", f, p["wd"])
    x = _rms_norm(x, params["final_norm"], eps)
    logp = jax.nn.log_softmax(dot("sd,vd->sv", x, params["embed"])[:-1], -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], -1))


class Reference:
    """Σ w ∇ℓ over training sequences and the eval cost, at ``dot``'s
    precision, one sequence at a time (so that a gradient and its
    running sum are the only model-sized buffers it adds)."""

    def __init__(self, config, traffic, data, eval_ids, dot):
        self.tokens = data.x_train
        self.eval_tokens = data.x_train[jnp.asarray(eval_ids)]
        loss = functools.partial(
            _sequence_loss, heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"], theta=config["rope_theta"],
            eps=config["rms_norm_eps"], dot=dot)

        def accumulate(acc, params, tokens, w):
            g = jax.grad(loss)(params, tokens)
            return jax.tree.map(lambda a, gg: a + w * gg, acc, g)

        self._accumulate = jax.jit(accumulate, donate_argnums=0)
        self._loss = jax.jit(loss)

    def grad_sum(self, params, ids, w):
        acc = jax.tree.map(jnp.zeros_like, params)
        for i, wi in zip(ids, w):
            acc = self._accumulate(acc, params, self.tokens[int(i)],
                                   jnp.float32(wi))
        return acc

    def cost(self, params):
        return sum(self._loss(params, t)
                   for t in self.eval_tokens) / len(self.eval_tokens)
