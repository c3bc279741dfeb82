"""Fig. 3: model sparsity ‖ω‖² vs training cost trade-off.

(a) Algorithm 1 sweeping λ; (b) Algorithm 2 sweeping U.  The paper's
claim (iv): Algorithm 2 traces a better frontier (it solves min ‖ω‖²
s.t. cost ≤ U directly).  Derived: (final cost, final ‖ω‖²) pairs.
"""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.common import dataset, emit, fed_partition, timed
from repro.fed import runtime

LAMBDAS = (1e-6, 1e-5, 1e-4, 5e-4, 2e-3, 5e-3, 1e-2)
LIMITS = (0.05, 0.13, 0.3, 0.45, 0.6, 1.0)
ROUNDS = 100
BATCH = 100


def main(out_json: str = "EXPERIMENTS/fig3_tradeoff.json") -> None:
    data = dataset()
    part = fed_partition()
    frontier = {"alg1": [], "alg2": []}
    for lam in LAMBDAS:
        (_, h), us = timed(runtime.run_alg1, data, part, batch_size=BATCH,
                           rounds=ROUNDS, lam=lam, eval_every=ROUNDS,
                           eval_samples=5000)
        frontier["alg1"].append({"lam": lam, "cost": h.train_cost[-1],
                                 "sparsity": h.sparsity[-1],
                                 "acc": h.test_accuracy[-1]})
        emit(f"fig3a/alg1_lam{lam:g}", us / ROUNDS,
             f"cost={h.train_cost[-1]:.4f} |w|^2={h.sparsity[-1]:.1f}")
    for u in LIMITS:
        (_, h), us = timed(runtime.run_alg2, data, part, batch_size=BATCH,
                           rounds=ROUNDS, limit_u=u, eval_every=ROUNDS,
                           eval_samples=5000)
        frontier["alg2"].append({"U": u, "cost": h.train_cost[-1],
                                 "sparsity": h.sparsity[-1],
                                 "acc": h.test_accuracy[-1],
                                 "slack": h.slack[-1]})
        emit(f"fig3b/alg2_U{u:g}", us / ROUNDS,
             f"cost={h.train_cost[-1]:.4f} |w|^2={h.sparsity[-1]:.1f} "
             f"slack={h.slack[-1]:.4f}")
    Path(out_json).parent.mkdir(parents=True, exist_ok=True)
    Path(out_json).write_text(json.dumps(frontier, indent=1))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
