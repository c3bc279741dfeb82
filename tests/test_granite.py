"""Granite Code 8B's block through the normal federated path, against
the plain float32 reference (``tests/lm_reference.py``) on seeded random
weights, at a size the CPU holds: d_model 256, 4 query heads of 64 with
one key-value head, d_ff 512, a 512-token slice of the vocabulary,
sequences of 64 tokens, the published rotary base and norm epsilon.

Each tolerance is set from what the comparison measured here, with its
reason, and is tight enough that the same program one precision step
lower fails it."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_reference as ref
from repro.configs import get_config
from repro.core.schedules import paper_schedules
from repro.data.partition import Partition
from repro.fed import aggregation, runtime
from repro.fed.engine import build_schedule
from repro.fed.tasks import TaskData
from repro.fed.tasks.transformer import chip_share_task

SMALL = dict(d_model=256, num_heads=4, num_kv_heads=1, head_dim=64,
             d_ff=512)
LAYERS, VOCAB, SEQ = 2, 512, 64

# (loss relative error, worst leaf's gradient relative error) by the
# activation dtype the program computes in.  float32: what f32 round-off
# leaves between two orders of the same sums (measured 7.6e-8 and
# 9.8e-7), with ten times room.  bfloat16, the configuration's stated
# dtype: bf16 operands round to 2^-9 and the error of the gradient
# builds over the layers' products (measured 3.0e-5 and 1.1e-2), with
# about five times room.
TOL = {"float32": (1e-6, 1e-5), "bfloat16": (3e-4, 5e-2)}
# one precision step below each
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def _task(activ_dtype):
    cfg = dataclasses.replace(get_config("granite-8b"), **SMALL,
                              activ_dtype=activ_dtype)
    return chip_share_task(cfg, num_layers=LAYERS, vocab=VOCAB, seq_len=SEQ)


def _worst_leaf(a, b):
    return max(float(jnp.linalg.norm((x - y).ravel())
                     / jnp.linalg.norm(y.ravel()))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                               strict=True))


def _errors(activ_dtype, ref_dtype=None):
    """The program's Σ w ℓ and its gradient at ``activ_dtype`` against
    the reference, on the weights of the task at ``ref_dtype``."""
    task = _task(activ_dtype)
    params = _task(ref_dtype or activ_dtype).init_params(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (3, SEQ), 0, VOCAB)
    w = jnp.asarray([0.5, 0.3, 0.2])
    loss, grad = jax.value_and_grad(task.loss_sum)(params,
                                                   (tokens, tokens, w))
    rl, rg = ref.weighted_loss_and_grad(params, tokens, w,
                                        ref.arch_of(task.cfg))
    return abs(float(loss) - float(rl)) / float(rl), _worst_leaf(grad, rg)


def test_chip_share_keeps_published_widths():
    full = get_config("granite-8b")
    task = chip_share_task("granite-8b", num_layers=1, vocab=6144,
                           seq_len=2048)
    cut = task.cfg
    assert (cut.num_layers, cut.vocab_size) == (1, 6144)
    for key in ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
                "rope_theta", "norm_eps", "param_dtype", "activ_dtype"):
        assert getattr(cut, key) == getattr(full, key), key
    assert cut.name == "granite-8b[layers 1/36, vocab 6144/49152]"
    assert (full.rope_theta, full.norm_eps) == (1e7, 1e-5)
    assert (cut.param_dtype, cut.activ_dtype) == ("float32", "bfloat16")
    shapes = jax.eval_shape(task.init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 243_281_920
    with pytest.raises(ValueError, match="chip's share"):
        chip_share_task("granite-8b", num_layers=37, vocab=6144, seq_len=8)


@pytest.mark.parametrize("activ_dtype", TOL)
def test_loss_and_grad_match_reference(activ_dtype):
    loss_err, grad_err = _errors(activ_dtype)
    tol_loss, tol_grad = TOL[activ_dtype]
    assert loss_err < tol_loss and grad_err < tol_grad, (loss_err, grad_err)


@pytest.mark.parametrize("activ_dtype", TOL)
def test_lower_precision_fails_the_tolerance(activ_dtype):
    loss_err, grad_err = _errors(LOWER[activ_dtype], activ_dtype)
    tol_loss, tol_grad = TOL[activ_dtype]
    assert loss_err >= tol_loss or grad_err >= tol_grad, (loss_err,
                                                          grad_err)


CLIENTS, PER_CLIENT, COHORT, ROUNDS, TAU = 16, 2, 4, 3, 1.0
# By the worst leaf, the norm of the difference between the program's
# and the replay's parameter change over the call, over the replay
# leaf's change or the median leaf's, whichever is larger (the
# benchmark's ``step_diff``).  Secure aggregation's 2^-20 fixed-point
# grid leaves about 1e-6 a coordinate in each round's aggregate, which
# reads 1.1e-5 here (plain aggregation: under 1e-6); the tolerance gives
# that nine times room.  bfloat16 activations read 6.4e-4 and fail it.
STEP_TOL = 1e-4


def _secure_call(task, data, part, params, seed):
    return runtime.run_alg1(
        data, part, task=task, params=params, batch_size=1, rounds=ROUNDS,
        tau=TAU, lam=0.0, fused=True, seed=seed, eval_every=ROUNDS, eval_samples=4,
        aggregation=aggregation.secure(scale_bits=20, num_sampled=COHORT))


def _replay(task, params, tokens, part, seed):
    """Algorithm 1 (λ = 0) over the program's own cohort and batch
    draws, the reference's gradient in place of the program's upload and
    a plain sum in place of the masked one."""
    cohorts, idx = build_schedule(part, 1, ROUNDS, 1, seed,
                                  cohort_size=COHORT)
    w = part.sizes / part.total * (CLIENTS / COHORT)         # N_i/(BN)·I/S
    (rho, gamma) = paper_schedules(1)
    lin = jax.tree.map(jnp.zeros_like, params)
    for t in range(1, ROUNDS + 1):
        ids = idx[t - 1].reshape(-1)
        _, g = ref.weighted_loss_and_grad(
            params, tokens[ids], w[cohorts[t - 1]], ref.arch_of(task.cfg))
        r, gm = float(rho(t)), float(gamma(t))
        lin = jax.tree.map(lambda l, gg, p: (1 - r) * l
                           + r * (gg - 2 * TAU * p), lin, g, params)
        params = jax.tree.map(lambda p, l: (1 - gm) * p
                              + gm * (-l / (2 * TAU)), params, lin)
    return params


def _step_error(activ_dtype):
    task = _task(activ_dtype)
    n = CLIENTS * PER_CLIENT
    tokens = jax.random.randint(jax.random.key(3), (n, SEQ), 0, VOCAB)
    data = TaskData(tokens, tokens, tokens[:4], tokens[:4])
    part = Partition(np.arange(n), np.arange(CLIENTS) * PER_CLIENT,
                     np.full(CLIENTS, PER_CLIENT))
    params0 = _task("float32").init_params(jax.random.key(4))
    seed = 2 ** 31 + 5
    got, hist = _secure_call(task, data, part, params0, seed)
    want = _replay(task, params0, tokens, part, seed)
    assert hist.comm["tokens_per_round"] == COHORT * SEQ
    moved = [float(jnp.linalg.norm((r - a).ravel())) for r, a in
             zip(jax.tree.leaves(want), jax.tree.leaves(params0))]
    floor = float(np.median(moved))
    return max(float(jnp.linalg.norm((p - r).ravel())) / max(m, floor)
               for p, r, m in zip(jax.tree.leaves(got),
                                  jax.tree.leaves(want), moved))


def test_secure_run_alg1_matches_reference_replay():
    err = _step_error("float32")
    assert err < STEP_TOL, err


def test_secure_run_alg1_at_bfloat16_fails_the_float32_tolerance():
    assert _step_error("bfloat16") >= STEP_TOL


def test_upload_scopes_and_token_counter(dataset, fed_partition):
    """The LM upload's device work is named by scope inside
    ``client_upload``, and only a task of token sequences counts tokens
    in ``History.comm``."""
    task = _task("bfloat16")
    params = task.init_params(jax.random.key(0))
    tokens = jnp.zeros((2, SEQ), jnp.int32)
    text = jax.jit(jax.grad(task.loss_sum)).lower(
        params, (tokens, tokens, jnp.ones(2))).as_text(debug_info=True)
    for scope in ("attention", "ffn", "unembed", "loss"):
        # a scope reads "attention/..." or, under autodiff, "jvp(loss)/..."
        assert re.search(rf'["/(]{scope}\)?/', text), scope
    _, hist = runtime.run_alg1(dataset, fed_partition, batch_size=10,
                               rounds=1, eval_samples=100)
    assert "tokens_per_round" not in hist.comm
