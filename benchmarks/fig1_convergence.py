"""Fig. 1(a) + Fig. 2(a): Algorithm 1 vs the SGD baselines [3]-[5].

Training cost / test accuracy vs round, batch sizes B = 1, 10, 100, plus
the equal-computation comparison (Alg 1 at B=10/100 vs FedAvg at B=5/50,
E=2).  Derived column: final train cost | final accuracy | rounds to reach
cost 0.5.
"""
from __future__ import annotations

import json
from pathlib import Path


from benchmarks.common import (ROUNDS, SEEDS, dataset, emit, fed_partition,
                               mean_history, timed)
from repro.fed import runtime


def rounds_to(hist_rounds, costs, target):
    for r, c in zip(hist_rounds, costs):
        if c <= target:
            return r
    return -1


def main(out_json: str = "EXPERIMENTS/fig1_convergence.json",
         rounds: int = ROUNDS) -> None:
    data = dataset()
    part = fed_partition()
    results = {}

    for algo, runner, kwargs in (
        ("alg1_ssca", runtime.run_alg1, {}),
        ("fedsgd_e1", runtime.run_fedsgd, {"lr_a": 2.0, "lr_alpha": 0.3}),
    ):
        for b in (1, 10, 100):
            hs = []
            us = 0.0
            for seed in SEEDS:
                (_, h), t_us = timed(
                    runner, data, part, batch_size=b, rounds=rounds,
                    eval_every=5, eval_samples=5000, seed=seed, **kwargs)
                hs.append(h)
                us += t_us
            cost = mean_history(hs, "train_cost")
            acc = mean_history(hs, "test_accuracy")
            key = f"{algo}_B{b}"
            results[key] = {"rounds": hs[0].rounds,
                            "train_cost": cost.tolist(),
                            "test_accuracy": acc.tolist()}
            emit(f"fig1a/{key}", us / (len(SEEDS) * rounds),
                 f"cost={cost[-1]:.4f} acc={acc[-1]:.4f} "
                 f"r@0.5={rounds_to(hs[0].rounds, cost, 0.5)}")

    # equal per-client computation: FedAvg E=2 at half batch
    for b_avg, b_ssca in ((5, 10), (50, 100)):
        hs = []
        us = 0.0
        for seed in SEEDS:
            (_, h), t_us = timed(
                runtime.run_fedavg, data, part, batch_size=b_avg,
                rounds=rounds, local_steps=2, eval_every=5,
                eval_samples=5000, seed=seed, lr_a=2.0, lr_alpha=0.3)
            hs.append(h)
            us += t_us
        cost = mean_history(hs, "train_cost")
        acc = mean_history(hs, "test_accuracy")
        key = f"fedavg_e2_B{b_avg}"
        results[key] = {"rounds": hs[0].rounds,
                        "train_cost": cost.tolist(),
                        "test_accuracy": acc.tolist()}
        emit(f"fig1a/{key}", us / (len(SEEDS) * rounds),
             f"cost={cost[-1]:.4f} acc={acc[-1]:.4f} "
             f"r@0.5={rounds_to(hs[0].rounds, cost, 0.5)} "
             f"(equal-compute vs alg1_B{b_ssca})")

    # heterogeneity (the paper's §I motivation): Dirichlet(0.3) non-IID
    # clients — multiple local steps lose their edge, SSCA's single
    # aggregated surrogate round does not.
    from repro.data import partition as _part
    labels = data.y_train.argmax(1)
    part_niid = _part.dirichlet(labels, 10, alpha=0.3, seed=0)
    for algo, runner, kwargs in (
            ("alg1_ssca", runtime.run_alg1, {}),
            ("fedavg_e2", runtime.run_fedavg,
             {"local_steps": 2, "lr_a": 2.0, "lr_alpha": 0.3})):
        hs = []
        us = 0.0
        for seed in SEEDS:
            (_, h), t_us = timed(
                runner, data, part_niid, batch_size=50, rounds=rounds,
                eval_every=5, eval_samples=5000, seed=seed, **kwargs)
            hs.append(h)
            us += t_us
        cost = mean_history(hs, "train_cost")
        acc = mean_history(hs, "test_accuracy")
        key = f"noniid_{algo}_B50"
        results[key] = {"rounds": hs[0].rounds,
                        "train_cost": cost.tolist(),
                        "test_accuracy": acc.tolist()}
        emit(f"fig1a/{key}", us / (len(SEEDS) * rounds),
             f"cost={cost[-1]:.4f} acc={acc[-1]:.4f} (dirichlet 0.3)")

    Path(out_json).parent.mkdir(parents=True, exist_ok=True)
    Path(out_json).write_text(json.dumps(results, indent=1))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
