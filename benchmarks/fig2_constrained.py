"""Fig. 1(b) + Fig. 2(b): Algorithm 2 (constrained) at B = 1, 10, 100 with
cost limit U = 0.13 — the paper's "explicitly specify the training cost"
claim.  Derived: final cost vs U, final slack, accuracy."""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.common import (ROUNDS, SEEDS, dataset, emit, fed_partition,
                               mean_history, timed)
from repro.fed import runtime

LIMIT_U = 0.13


def main(out_json: str = "EXPERIMENTS/fig2_constrained.json",
         rounds: int = ROUNDS) -> None:
    data = dataset()
    part = fed_partition()
    results = {}
    for b in (1, 10, 100):
        hs = []
        us = 0.0
        for seed in SEEDS:
            (_, h), t_us = timed(
                runtime.run_alg2, data, part, batch_size=b, rounds=rounds,
                limit_u=LIMIT_U, eval_every=5, eval_samples=5000, seed=seed)
            hs.append(h)
            us += t_us
        cost = mean_history(hs, "train_cost")
        acc = mean_history(hs, "test_accuracy")
        slack = mean_history(hs, "slack")
        sp = mean_history(hs, "sparsity")
        key = f"alg2_B{b}_U{LIMIT_U}"
        results[key] = {"rounds": hs[0].rounds, "train_cost": cost.tolist(),
                        "test_accuracy": acc.tolist(),
                        "slack": slack.tolist(), "sparsity": sp.tolist()}
        emit(f"fig1b/{key}", us / (len(SEEDS) * rounds),
             f"cost={cost[-1]:.4f} (U={LIMIT_U}) acc={acc[-1]:.4f} "
             f"slack={slack[-1]:.4f} |w|^2={sp[-1]:.1f}")
    Path(out_json).parent.mkdir(parents=True, exist_ok=True)
    Path(out_json).write_text(json.dumps(results, indent=1))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
