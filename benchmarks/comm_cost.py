"""Communication cost (§I / §VI): uplink bytes per round are identical
across Algorithm 1 and the SGD baselines (one model-sized message per
client per round) — the win is *fewer rounds to a target cost*.

Derived: bytes-to-target = uplink_bytes_per_round × rounds_to(cost ≤ θ),
using the engine's exact ledger (``History.uplink_bytes_per_round`` —
already summed over participating clients).  The deprecated
float32-dense ``uplink_floats_per_round`` is no longer read here (it
now warns on read; see the README removal timeline).  For the
compressed-upload comparison (accuracy vs cumulative bytes under
qsgd/top-k) see ``examples/compressed_uploads.py``.
"""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.common import SEEDS, dataset, emit, fed_partition, timed
from repro.fed import runtime

TARGETS = (1.0, 0.5, 0.2)
ROUNDS = 100
BATCH = 100


def rounds_to(h, target):
    for r, c in zip(h.rounds, h.train_cost):
        if c <= target:
            return r
    return None


def main(out_json: str = "EXPERIMENTS/comm_cost.json") -> None:
    data = dataset()
    part = fed_partition()
    results = {}
    for name, runner, kwargs in (
            ("alg1_ssca", runtime.run_alg1, {}),
            ("fedsgd_e1", runtime.run_fedsgd,
             {"lr_a": 2.0, "lr_alpha": 0.3}),
            ("fedavg_e2", runtime.run_fedavg,
             {"local_steps": 2, "lr_a": 2.0, "lr_alpha": 0.3})):
        (_, h), us = timed(runner, data, part, batch_size=BATCH,
                           rounds=ROUNDS, eval_every=1, eval_samples=5000,
                           seed=SEEDS[0], **kwargs)
        row = {"uplink_bytes_per_round": h.uplink_bytes_per_round,
               "downlink_bytes_per_round": h.downlink_bytes_per_round,
               "comm": h.comm}
        for θ in TARGETS:
            r = rounds_to(h, θ)
            row[f"rounds_to_{θ}"] = r
            row[f"gbytes_to_{θ}"] = (
                None if r is None
                else r * h.uplink_bytes_per_round / 1e9)
        results[name] = row
        emit(f"comm/{name}", us / ROUNDS,
             " ".join(f"r@{θ}={row[f'rounds_to_{θ}']}" for θ in TARGETS)
             + f" bytes/round={h.uplink_bytes_per_round}")
    Path(out_json).parent.mkdir(parents=True, exist_ok=True)
    Path(out_json).write_text(json.dumps(results, indent=1))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
