"""Ablation: sensitivity of Algorithm 1 to the surrogate constant τ.

The paper only states τ > 0 suffices (below eq. (6)) and uses τ = 0.1.
This ablation maps the practical stability window on the §VI setting:
effective early step ≈ ρ¹γ¹/(2τ), so small τ ⇒ aggressive steps (risk of
the softmax-saturation divergence we document in repro.data.synthetic),
large τ ⇒ slow early progress.

Standalone:  PYTHONPATH=src python -m benchmarks.ablation_tau
"""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.common import dataset, emit, fed_partition, timed
from repro.fed import runtime

TAUS = (0.02, 0.05, 0.1, 0.3, 1.0, 3.0)
ROUNDS = 80
BATCH = 100


def main(out_json: str = "EXPERIMENTS/ablation_tau.json") -> None:
    data = dataset()
    part = fed_partition()
    rows = {}
    for tau in TAUS:
        (_, h), us = timed(runtime.run_alg1, data, part, batch_size=BATCH,
                           rounds=ROUNDS, tau=tau, eval_every=20,
                           eval_samples=5000)
        rows[str(tau)] = {"train_cost": h.train_cost,
                          "test_accuracy": h.test_accuracy}
        emit(f"ablation/tau{tau:g}", us / ROUNDS,
             f"cost={h.train_cost[-1]:.4f} acc={h.test_accuracy[-1]:.4f}")
    Path(out_json).parent.mkdir(parents=True, exist_ok=True)
    Path(out_json).write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
