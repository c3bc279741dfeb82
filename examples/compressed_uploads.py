"""Compressed client uploads: top-k + error feedback under secure
aggregation, with the communication ledger.

SSCA Algorithm 1 runs three ways on the same data and seed —

* dense float32 uploads (the baseline wire),
* 8-bit stochastic quantization (unbiased, power-of-two lattice: the
  quantized uploads sit exactly on the secure Z_{2^32} fixed-point grid,
  so masked aggregation of compressed messages is exact),
* top-k(10%) sparsification with 8-bit values and per-client error
  feedback, composed with Bonawitz-style secure aggregation —

and the per-round byte ledger (``History.uplink_bytes_per_round`` /
``cum_uplink_bytes``) shows what each configuration actually puts on the
wire.  Note the secure rows: masking requires the dense int32 ring
representation, so sparsity helps convergence-per-round but not secure
wire bytes — the accuracy-vs-bytes win belongs to the plain rows.

    PYTHONPATH=src python examples/compressed_uploads.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

from repro.data import partition, synthetic
from repro.fed import aggregation, compression, runtime


def main():
    data = synthetic.classification_dataset(n_train=20000, n_test=2000,
                                            seed=0)
    part = partition.iid(len(data.x_train), num_clients=10, seed=0)
    common = dict(batch_size=100, rounds=60, eval_every=20,
                  eval_samples=5000)

    configs = [
        ("dense / plain", None, None),
        ("qsgd-8b / plain", compression.qsgd(8), None),
        ("topk-10%-8b / plain", compression.topk(0.1, bits=8), None),
        ("topk-10%-8b / secure", compression.topk(0.1, bits=8),
         aggregation.secure()),
    ]
    results = []
    for name, comp, agg in configs:
        _, h = runtime.run_alg1(data, part, compressor=comp,
                                aggregation=agg, **common)
        results.append((name, h))
        bd = h.comm["breakdown"]
        print(f"=== {name} ===")
        print(f"  payload/client {bd['payload_bytes']:>9,} B"
              f"   wire/client {h.comm['uplink_per_client']:>9,} B"
              f"   (+{bd['wire_overhead_bytes']:,} B wire overhead)")
        for r, c, a, b in zip(h.rounds, h.train_cost, h.test_accuracy,
                              h.cum_uplink_bytes):
            print(f"  round {r:3d}: cost {c:.4f}  acc {a:.4f}  "
                  f"cum uplink {b / 1e6:8.2f} MB")

    base = results[0][1]
    print("\n=== ledger summary (vs dense/plain) ===")
    print(f"{'configuration':24s} {'MB uplink':>10s} {'reduction':>10s}"
          f" {'final acc':>10s}")
    for name, h in results:
        red = base.cum_uplink_bytes[-1] / h.cum_uplink_bytes[-1]
        print(f"{name:24s} {h.cum_uplink_bytes[-1] / 1e6:10.2f}"
              f" {red:9.1f}x {h.test_accuracy[-1]:10.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
