"""Quickstart: the paper's Algorithm 1 end-to-end in ~60 lines.

Trains the Section-V model (784 → 128 swish → 10 softmax) on the synthetic
MNIST-stand-in with 10 federated clients via mini-batch SSCA, and compares
one SGD baseline round-for-round.

    PYTHONPATH=src python examples/quickstart.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

from repro.data import partition, synthetic
from repro.fed import runtime


def main():
    print("generating federated dataset (N=20000, I=10, K=784, L=10)...")
    data = synthetic.classification_dataset(n_train=20000, n_test=2000,
                                            seed=0)
    part = partition.iid(len(data.x_train), num_clients=10, seed=0)

    print("\n=== Algorithm 1 (mini-batch SSCA), B=100, T=60 ===")
    _, h_ssca = runtime.run_alg1(data, part, batch_size=100, rounds=60,
                                 lam=1e-5, eval_every=10)
    for r, c, a in zip(h_ssca.rounds, h_ssca.train_cost,
                       h_ssca.test_accuracy):
        print(f"  round {r:3d}: train cost {c:.4f}  test acc {a:.4f}")

    print("\n=== FedSGD baseline [3], same batch, same uplink ===")
    _, h_sgd = runtime.run_fedsgd(data, part, batch_size=100, rounds=60,
                                  lr_a=2.0, lr_alpha=0.3, eval_every=10)
    for r, c, a in zip(h_sgd.rounds, h_sgd.train_cost,
                       h_sgd.test_accuracy):
        print(f"  round {r:3d}: train cost {c:.4f}  test acc {a:.4f}")

    print(f"\nSSCA final cost {h_ssca.train_cost[-1]:.4f} "
          f"vs FedSGD {h_sgd.train_cost[-1]:.4f} "
          f"(same {h_ssca.uplink_bytes_per_round} uplink bytes/round) — "
          "the paper's claim (i).")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
