"""Algorithm 2: constrained federated optimization with an explicit
training-cost budget (the paper's Section V-B / eq. (18)).

    min ‖ω‖²  s.t.  F(ω) ≤ U

Shows (a) the cost converging onto the limit U with zero slack, (b) the
practical penalty continuation c_j ↑ ∞ loop of Theorem 2, and (c) the
sparsity/cost trade-off against Algorithm 1's λ-sweep.

    PYTHONPATH=src python examples/constrained_training.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

from repro.core.constrained import penalty_continuation
from repro.data import partition, synthetic
from repro.fed import runtime


def main():
    data = synthetic.classification_dataset(n_train=20000, n_test=2000,
                                            seed=0)
    part = partition.iid(len(data.x_train), 10, seed=0)

    print("=== Algorithm 2 with U = 0.3 (B=100, T=80) ===")
    params, h = runtime.run_alg2(data, part, batch_size=100, rounds=80,
                                 limit_u=0.3, eval_every=10)
    for r, c, s, sp in zip(h.rounds, h.train_cost, h.slack, h.sparsity):
        print(f"  round {r:3d}: cost {c:.4f} (U=0.3)  slack {s:.4f}  "
              f"|w|^2 {sp:7.1f}")

    print("\n=== penalty continuation c_j = 1e3 -> 1e4 -> 1e5 ===")
    p = None
    for c in penalty_continuation([1e3, 1e4, 1e5]):
        p, h = runtime.run_alg2(data, part, batch_size=100, rounds=40,
                                limit_u=0.3, c=c, eval_every=40, params=p)
        print(f"  c={c:g}: cost {h.train_cost[-1]:.4f} "
              f"slack {h.slack[-1]:.5f}")

    print("\n=== trade-off frontier (paper Fig. 3) ===")
    for u in (0.1, 0.3, 0.6):
        _, h = runtime.run_alg2(data, part, batch_size=100, rounds=60,
                                limit_u=u, eval_every=60)
        print(f"  Alg2 U={u}:    cost {h.train_cost[-1]:.4f}  "
              f"|w|^2 {h.sparsity[-1]:8.1f}  acc {h.test_accuracy[-1]:.4f}")
    for lam in (1e-5, 1e-4, 1e-3):
        _, h = runtime.run_alg1(data, part, batch_size=100, rounds=60,
                                lam=lam, eval_every=60)
        print(f"  Alg1 λ={lam:g}: cost {h.train_cost[-1]:.4f}  "
              f"|w|^2 {h.sparsity[-1]:8.1f}  acc {h.test_accuracy[-1]:.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
