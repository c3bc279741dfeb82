"""mlp-paper: the paper's Section-V model, 784 -> 128 swish -> 10 softmax.

Program side: the data, the initial weights (one jitted call from the
seed, on the device) and the task that ``run_alg1`` trains.  Reference
side: the same model in plain ``jax.numpy`` with every product through
the reference's :class:`reference.Dot`, importing nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _data_and_params(key, n_train, n_test, k, j, l, rank, noise, background,
                     init_scale):
    """The semantics of the program's MNIST stand-in, made on the device:
    smooth class prototypes from a 4x4 sine-cosine basis, per-class
    low-rank variation, pixel noise, each split scaled into [0, 1], then
    the lowest ``background`` share of training pixels clipped to 0."""
    kp, kc, kv, ktr, kte, kw1, kw2 = jax.random.split(key, 7)
    side = int(round(k ** 0.5))
    xs = jnp.linspace(0.0, 1.0, side)
    gx, gy = jnp.meshgrid(xs, xs)
    basis = jnp.stack([jnp.sin((a + 1) * jnp.pi * gx)
                       * jnp.cos((b + 1) * jnp.pi * gy)
                       for a in range(4) for b in range(4)], -1)
    coef = jax.random.normal(kc, (l, basis.shape[-1]))
    protos = coef @ basis.reshape(-1, basis.shape[-1]).T
    protos = protos / (jnp.abs(protos).max(axis=1, keepdims=True) + 1e-9)
    var_dirs = jax.random.normal(kv, (l * rank, k)) / jnp.sqrt(k)

    def make(kk, n):
        ky, kr, kn = jax.random.split(kk, 3)
        ys = jax.random.randint(ky, (n,), 0, l)
        coefs = jax.random.normal(kr, (n, rank))
        onehot = jax.nn.one_hot(ys, l, dtype=jnp.float32)
        mix = (onehot[:, :, None] * coefs[:, None, :]).reshape(n, l * rank)
        x = protos[ys] + mix @ var_dirs + noise * jax.random.normal(kn, (n, k))
        x = (x - x.min()) / (x.max() - x.min() + 1e-9)
        return x, onehot

    x_tr, y_tr = make(ktr, n_train)
    x_te, y_te = make(kte, n_test)
    thr = jnp.quantile(x_tr, background)
    scale = x_tr.max() - thr + 1e-9
    x_tr = jnp.clip((x_tr - thr) / scale, 0.0, 1.0)
    x_te = jnp.clip((x_te - thr) / scale, 0.0, 1.0)
    w1 = init_scale * jax.random.normal(kw1, (j, k))
    w2 = init_scale * jax.random.normal(kw2, (l, j))
    return (x_tr, y_tr, x_te, y_te), (w1, w2)


def make(config, traffic, seed):
    """(data, params0) on the default device: one jitted call."""
    from repro.fed.tasks.base import TaskData
    from repro.mlpapp.model import MLPParams

    d = config["data"]
    n_train = traffic["clients"] * traffic["samples_per_client"]
    fn = jax.jit(functools.partial(
        _data_and_params, n_train=n_train, n_test=traffic["test_samples"],
        k=config["input_dim"], j=config["hidden_size"],
        l=config["num_classes"], rank=d["low_rank"], noise=d["noise"],
        background=d["background_share"], init_scale=config["init_scale"]))
    data, (w1, w2) = fn(jax.random.key(seed))
    return TaskData(*data), MLPParams(w1, w2)


def task(config, traffic):
    from repro.fed.tasks.mlp import MLPTask
    return MLPTask(k=config["input_dim"], hidden=config["hidden_size"],
                   l=config["num_classes"])


def model_flops_per_round(config, traffic) -> float:
    """Forward and backward of every participating sample: 6 FLOPs per
    weight per sample (no biases; the swish and softmax are not counted)."""
    n = config["input_dim"] * config["hidden_size"] \
        + config["hidden_size"] * config["num_classes"]
    return 6.0 * n * traffic["cohort"] * traffic["batch_size"]


# --------------------------------------------------------------------------
# Plain reference
# --------------------------------------------------------------------------

def _logits(params, x, dot):
    w1, w2 = params[0], params[1]
    h = dot("nk,jk->nj", x, w1)
    h = h * jax.nn.sigmoid(h)
    return dot("nj,lj->nl", h, w2)


def _loss_sum(params, x, y, w, dot):
    logp = jax.nn.log_softmax(_logits(params, x, dot), axis=-1)
    return -jnp.sum(w * jnp.sum(y * logp, axis=-1))


def _mean_ce(params, x, y, dot):
    logp = jax.nn.log_softmax(_logits(params, x, dot), axis=-1)
    return -jnp.mean(jnp.sum(y * logp, axis=-1))


class Reference:
    """Σ w ∇ℓ over training rows and the eval cost, at ``dot``'s precision."""

    def __init__(self, config, traffic, data, eval_ids, dot):
        self.x, self.y = data.x_train, data.y_train
        self.xe = data.x_train[jnp.asarray(eval_ids)]
        self.ye = data.y_train[jnp.asarray(eval_ids)]
        self._grad = jax.jit(jax.grad(functools.partial(_loss_sum, dot=dot)))
        self._cost = jax.jit(functools.partial(_mean_ce, dot=dot))

    def grad_sum(self, params, ids, w):
        ids = jnp.asarray(ids)
        return self._grad(params, self.x[ids], self.y[ids], jnp.asarray(w))

    def cost(self, params):
        return self._cost(params, self.xe, self.ye)
