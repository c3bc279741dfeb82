"""Benchmark entry point — one module per paper table/figure.

``python -m benchmarks.run [--quick]`` prints ``name,us_per_call,derived``
CSV rows for:

* fig1  — Fig. 1(a)/2(a): Alg 1 vs SGD baselines, B ∈ {1,10,100} (+ the
          equal-computation FedAvg comparison)
* fig2  — Fig. 1(b)/2(b): Alg 2 convergence under the cost limit U
* fig3  — Fig. 3: sparsity–cost trade-off frontiers (λ-sweep vs U-sweep)
* comm  — communication cost to target (§I/§VI)
* ablation — τ-sensitivity of Algorithm 1 (beyond-paper)
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced rounds (CI mode)")
    ap.add_argument("--only", nargs="*", default=None,
                    choices=["fig1", "fig2", "fig3", "comm", "ablation"])
    args = ap.parse_args()
    rounds = 30 if args.quick else 100

    def want(name):
        return args.only is None or name in args.only

    print("name,us_per_call,derived")
    if want("fig1"):
        from benchmarks import fig1_convergence
        fig1_convergence.main(rounds=rounds)
    if want("fig2"):
        from benchmarks import fig2_constrained
        fig2_constrained.main(rounds=rounds)
    if want("fig3"):
        from benchmarks import fig3_tradeoff
        fig3_tradeoff.main()
    if want("comm"):
        from benchmarks import comm_cost
        comm_cost.main()
    if want("ablation"):
        from benchmarks import ablation_tau
        ablation_tau.main()


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
