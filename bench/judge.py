"""The numbers that decide ``correct``, and their verdict.

Training cells compare what a ``run_alg1`` call returned with the plain
reference replaying the same rounds (:mod:`reference`):

* ``loss_gap`` -- at every eval point of the call, the gap between the
  program's eval cost and the reference's, over the largest drop of the
  reference's cost from round 0;
* ``step_gap`` -- by the worst leaf, the gap between the norms of the
  program's and the reference's parameter change over the call, over the
  reference leaf's norm or the median leaf's, whichever is larger;
* ``step_diff`` -- by the worst leaf, the norm of the difference of the
  two changes, over the same denominator.

Leaves whose first-round reference gradient is under a thousandth of the
median leaf's move by round-off alone and are left out of the two step
numbers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUIET_LEAF = 1e-3


def _norm(x) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def numbers(params0, prog_params, prog_costs: dict, ref_params, ref_costs:
            dict, ref_grad1) -> dict:
    """The compared numbers of one call (see the module docstring)."""
    drop = max(abs(ref_costs[0] - c) for t, c in ref_costs.items() if t)
    points = [t for t in prog_costs if t in ref_costs and t > 0]
    if not points:
        raise ValueError("the call reported no eval point the reference has")
    loss_gap = max(abs(prog_costs[t] - ref_costs[t]) for t in points) / drop
    l0 = jax.tree.leaves(params0)
    lp = jax.tree.leaves(prog_params)
    lr = jax.tree.leaves(ref_params)
    g1 = np.array([_norm(g) for g in jax.tree.leaves(ref_grad1)])
    counted = g1 >= QUIET_LEAF * np.median(g1)
    dp = [_norm(p - a) for p, a in zip(lp, l0)]
    dr = np.array([_norm(r - a) for r, a in zip(lr, l0)])
    dd = [_norm(p - r) for p, r in zip(lp, lr)]
    floor = np.median(dr[counted])
    step_gap = step_diff = 0.0
    for k in np.flatnonzero(counted):
        den = max(dr[k], floor)
        step_gap = max(step_gap, abs(dp[k] - dr[k]) / den)
        step_diff = max(step_diff, dd[k] / den)
    return {"loss_gap": float(loss_gap), "step_gap": float(step_gap),
            "step_diff": float(step_diff)}


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the ``{name: {"value", "limit"}}`` table of the
    numbers the cell's limits name.  A number that is not finite, or over
    its limit, fails."""
    if not limits:
        raise ValueError("the cell has no limits: read them with "
                         "calibrate.py before it is judged")
    checks, ok = {}, True
    for name in limits:
        v, lim = values[name], float(limits[name])
        checks[name] = {"value": v, "limit": lim}
        ok = ok and bool(np.isfinite(v)) and v <= lim
    return ok, checks
