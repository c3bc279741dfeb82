"""Secure aggregation and partial participation on all four algorithms.

Demonstrates the composable aggregation layer: the same run_* wrappers
accept any strategy from ``repro.fed.aggregation`` —

* ``secure()``  — Bonawitz-style pairwise masking in Z_{2^32}; the server
  only ever sees Σ_i q_i (here: Algorithm 2's (value, gradient) upload,
  the paper's §III-B requirement).
* ``sampled(S)`` — S of I clients per round, the millions-of-users
  serving regime; unbiased for the SSCA/FedSGD gradient sums, weight
  re-normalized for FedAvg.

    PYTHONPATH=src python examples/secure_participation.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

from repro.data import partition, synthetic
from repro.fed import aggregation, runtime


def main():
    data = synthetic.classification_dataset(n_train=20000, n_test=2000,
                                            seed=0)
    part = partition.iid(len(data.x_train), num_clients=10, seed=0)
    common = dict(batch_size=100, rounds=40, eval_every=20,
                  eval_samples=5000)

    print("=== Algorithm 2, plain vs secure aggregation (§III-B) ===")
    _, h_plain = runtime.run_alg2(data, part, limit_u=0.4, **common)
    _, h_sec = runtime.run_alg2(data, part, limit_u=0.4, secure=True,
                                **common)
    for r, cp, cs in zip(h_plain.rounds, h_plain.train_cost,
                         h_sec.train_cost):
        print(f"  round {r:3d}: plain cost {cp:.6f}   secure cost {cs:.6f}"
              f"   |Δ| {abs(cp - cs):.2e}")

    print("\n=== Algorithm 1, full vs 4-of-10 client participation ===")
    _, h_full = runtime.run_alg1(data, part, **common)
    _, h_part = runtime.run_alg1(data, part,
                                 aggregation=aggregation.sampled(4),
                                 **common)
    for r, cf, cs in zip(h_full.rounds, h_full.train_cost,
                         h_part.train_cost):
        print(f"  round {r:3d}: full {cf:.4f}   sampled(4/10) {cs:.4f}")

    print("\n=== FedAvg, secure model averaging, 2 local steps ===")
    _, h = runtime.run_fedavg(data, part, local_steps=2, lr_a=2.0,
                              aggregation=aggregation.secure(), **common)
    for r, c, a in zip(h.rounds, h.train_cost, h.test_accuracy):
        print(f"  round {r:3d}: train cost {c:.4f}  test acc {a:.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
