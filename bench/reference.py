"""What every plain reference shares, independent of the program.

The draw streams of a call, copied from the program's documented
contract: ``SeedSequence([seed, t, 0xC0407])`` for the cohort of round
t, ``SeedSequence([seed, t])`` for every client's batch keys, and a
``default_rng(123)`` eval subset; the rows and eq.-(2) weights of each
round (:func:`rounds`); the paper's step-size schedules.  The recursions
of each algorithm are in ``algorithms/<name>.py``.

Matrix products go through :class:`Dot`, whose mode sets their precision:
``"f32"`` is float32 at ``Precision.HIGHEST`` (the reference itself) and
``"fp8"`` rounds every operand, and in the backward pass every cotangent,
to float8_e4m3 with a per-tensor scale (the control, one precision step
below the bfloat16 operands that the configurations state).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

COHORT_STREAM = 0xC0407
EVAL_SEED = 123
_BLOCK_ELEMS = 1 << 20
# Section VI of the paper: (a1, a2, alpha) per batch size; gamma's
# exponent is alpha + 0.05, and other batch sizes take B=100's row above
# 10 and B=10's row otherwise.
_PAPER_TABLE = {1: (0.4, 0.4, 0.4), 10: (0.6, 0.9, 0.3),
                100: (0.9, 0.9, 0.3)}
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


# --------------------------------------------------------------------------
# Draw streams
# --------------------------------------------------------------------------

def cohorts(num_clients: int, size: int, rounds: int, seed: int):
    """(T, S) sorted client ids of rounds 1..T (identity at S = I)."""
    if size == num_clients:
        return np.broadcast_to(np.arange(num_clients), (rounds, size)).copy()
    out = np.empty((rounds, size), np.int64)
    for k in range(rounds):
        rng = np.random.default_rng(
            np.random.SeedSequence([seed, k + 1, COHORT_STREAM]))
        out[k] = np.sort(rng.choice(num_clients, size=size, replace=False))
    return out


def batches(flat, offsets, sizes, batch_size: int, rounds: int, seed: int,
            cohort_ids):
    """(T, S, B) sample ids: every client draws B of its N_i samples
    without replacement (the B smallest of N_i float32 keys), or with
    replacement when N_i < B, from the round's full-population stream."""
    i_cl = len(sizes)
    width = max(int(sizes.max()), batch_size)
    no_repl = sizes >= batch_size
    block = max(1, _BLOCK_ELEMS // width)
    col = np.arange(width)[None, :]
    out = np.empty((rounds, cohort_ids.shape[1], batch_size), np.int64)
    for k in range(rounds):
        rng = np.random.default_rng(np.random.SeedSequence([seed, k + 1]))
        full = np.empty((i_cl, batch_size), np.int64)
        for lo in range(0, i_cl, block):
            hi = min(lo + block, i_cl)
            sz = sizes[lo:hi, None]
            keys = rng.random((hi - lo, width), dtype=np.float32)
            keys[col >= sz] = np.inf
            sel = np.argpartition(keys, batch_size - 1,
                                  axis=1)[:, :batch_size]
            padded = flat[offsets[lo:hi, None] + np.where(col < sz, col, 0)]
            full[lo:hi] = np.take_along_axis(padded, sel, axis=1)
        if not no_repl.all():
            u = rng.random((i_cl, batch_size))
            wr = flat[offsets[:, None] + (u * sizes[:, None]).astype(np.int64)]
            full = np.where(no_repl[:, None], full, wr)
        out[k] = full[cohort_ids[k]]
    return out


def eval_subset(n_train: int, eval_samples: int):
    rng = np.random.default_rng(EVAL_SEED)
    return rng.choice(n_train, size=min(eval_samples, n_train), replace=False)


def schedules(batch_size: int):
    a1, a2, alpha = _PAPER_TABLE.get(
        batch_size, _PAPER_TABLE[100] if batch_size > 10 else _PAPER_TABLE[10])
    return (a1, alpha), (a2, alpha + 0.05)


# --------------------------------------------------------------------------
# Matrix products at a stated precision
# --------------------------------------------------------------------------

def _q8(x):
    """Round to float8_e4m3 with a per-tensor scale (amax -> 448)."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum8(spec, a, b):
    return _einsum(spec, _q8(a), _q8(b))


def _einsum8_fwd(spec, a, b):
    return _einsum8(spec, a, b), (a, b)


def _einsum8_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(functools.partial(_einsum, spec), _q8(a), _q8(b))
    return vjp(_q8(g))


_einsum8.defvjp(_einsum8_fwd, _einsum8_bwd)


@dataclasses.dataclass(frozen=True)
class Dot:
    """``dot(spec, a, b)``: an einsum whose operands are float32 at
    HIGHEST (``mode="f32"``) or rounded to scaled float8 (``"fp8"``)."""
    mode: str = "f32"

    def __call__(self, spec, a, b):
        if self.mode == "f32":
            return _einsum(spec, a, b)
        if self.mode == "fp8":
            return _einsum8(spec, a, b)
        raise ValueError(f"unknown precision mode {self.mode!r}")


# --------------------------------------------------------------------------
# The rows of each round
# --------------------------------------------------------------------------

def rounds(traffic: dict, part, seed: int, *, fault: str | None = None):
    """For each round of one call, the training rows its cohort uses and
    their eq.-(2) weights N_i/(B·N), scaled by I/S for a sampled cohort:
    ``(ids, w)``, flat over the cohort's mini-batches.

    ``fault`` plants a fault, so that what a faulty program would read
    can be measured: ``"half_batch"`` drops the second half of the
    round's rows and doubles the weight of the rest; ``"no_exchange"``
    keeps only the first quarter of the cohort (one device's share of a
    four-chip round, its partial never summed with the other chips').
    """
    flat, offsets, sizes = part
    num_clients, size = traffic["clients"], traffic["cohort"]
    batch, count = traffic["batch_size"], traffic["rounds_per_call"]
    coh = cohorts(num_clients, size, count, seed)
    idx = batches(flat, offsets, sizes, batch, count, seed, coh)
    w_client = sizes / (batch * int(sizes.sum()))
    if size < num_clients:
        w_client = w_client * (num_clients / size)
    for k in range(count):
        ids = idx[k]                                       # (S, B)
        w = np.broadcast_to(w_client[coh[k]][:, None], ids.shape)
        ids, w = ids.reshape(-1), w.astype(np.float32).reshape(-1)
        if fault == "half_batch":
            half = max(1, ids.size // 2)
            ids, w = ids[:half], 2 * w[:half]
        elif fault == "no_exchange":
            keep = max(1, size // 4) * batch
            ids, w = ids[:keep], w[:keep]
        elif fault is not None:
            raise ValueError(f"unknown fault {fault!r}")
        yield ids, w
