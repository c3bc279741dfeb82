"""Scan-chunked engine vs the seed per-round driver: equal numerics, wall.

Two phases per batch size (the fig1 sweep B = 1, 10, 100):

1. **Equal numerics** — run both drivers at the fig1 eval cadence with
   the same seed and assert the train-cost trajectories match (the
   engine evaluates the identical weighted super-batch gradient, so the
   match is float-exact up to scan reassociation).
2. **Round-loop race** — time both drivers over ROUNDS rounds with a
   terminal eval only, isolating the per-round driver cost the engine
   removes (host-side sampling + gather + one XLA dispatch per round).
   Reported as legacy/engine speedup; small batches are dispatch-bound
   and show the full effect, B=100 is compute-bound.

    PYTHONPATH=src python benchmarks/engine_speedup.py
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from benchmarks.common import dataset, emit, fed_partition
from repro.fed import legacy, runtime

ROUNDS = 300
REPS = 3
TRAJ_ROUNDS = 40


def main(out_json: str = "EXPERIMENTS/engine_speedup.json") -> None:
    data = dataset()
    part = fed_partition()
    results = {}

    for b in (1, 10, 100):
        # 1. equal numerics: paired-seed trajectory match
        _, h_eng = runtime.run_alg1(data, part, batch_size=b,
                                    rounds=TRAJ_ROUNDS, eval_every=5,
                                    eval_samples=2000, seed=0)
        _, h_leg = legacy.run_alg1(data, part, batch_size=b,
                                   rounds=TRAJ_ROUNDS, eval_every=5,
                                   eval_samples=2000, seed=0)
        gap = float(np.max(np.abs(np.asarray(h_eng.train_cost)
                                  - np.asarray(h_leg.train_cost))))
        assert gap < 1e-4, f"trajectory mismatch at B={b}: {gap}"

        # 2. round-loop race (terminal eval only)
        walls = {}
        for name, fn in (("legacy", legacy.run_alg1),
                         ("engine", runtime.run_alg1)):
            ts = []
            for rep in range(REPS):
                _, h = fn(data, part, batch_size=b, rounds=ROUNDS,
                          eval_every=ROUNDS, eval_samples=1000,
                          seed=rep + 1)
                ts.append(h.wall_seconds)
            walls[name] = min(ts)
        speedup = walls["legacy"] / walls["engine"]
        results[f"B{b}"] = {"trajectory_gap": gap,
                            "legacy_s": walls["legacy"],
                            "engine_s": walls["engine"],
                            "speedup": speedup}
        emit(f"engine_speedup/B{b}",
             walls["engine"] / ROUNDS * 1e6,
             f"legacy={walls['legacy']:.2f}s engine={walls['engine']:.2f}s "
             f"speedup={speedup:.2f}x traj_gap={gap:.1e}")

    small = [results[f"B{b}"]["speedup"] for b in (1, 10)]
    emit("engine_speedup/summary", 0.0,
         f"dispatch-bound speedups: {['%.2fx' % s for s in small]} "
         f"(target >= 2x)")
    Path(out_json).parent.mkdir(parents=True, exist_ok=True)
    Path(out_json).write_text(json.dumps(results, indent=1))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
