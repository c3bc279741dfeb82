"""The sketched secure wire: accuracy vs cumulative *secure* uplink
bytes, dense vs qsgd vs top-k+EF vs count-sketch.

Every configuration here runs under Bonawitz-style secure aggregation.
That is the point: masking forces each upload to travel as the dense
Z_{2^32} ring element, so qsgd and top-k — which shrink the *plain*
wire nicely — put exactly as many bytes on the *secure* wire as dense
uploads do.  The count-sketch (:mod:`repro.fed.sketch`) is the one
compressor that reduces the masked dimension itself: clients sketch
into rows×cols buckets on the fixed-point grid, the masks are applied
to the sketch, and the server's wraparound sum of masked sketches is
the sketch of the summed update — so the secure uplink drops to
4·(rows·cols + k) bytes per client, sublinear in the model, while
two-phase recovery (sketch ranks the support, a second masked upload
carries the exact values) plus per-client error feedback keeps the
trajectory within a fraction of a percent of dense accuracy.

    PYTHONPATH=src python examples/sketched_uploads.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

from repro.data import partition, synthetic
from repro.fed import aggregation, compression, runtime
from repro.fed import sketch


def main():
    data = synthetic.classification_dataset(n_train=4000, n_test=1000,
                                            seed=0)
    part = partition.iid(len(data.x_train), num_clients=8, seed=0)
    common = dict(batch_size=10, rounds=300, eval_every=75,
                  eval_samples=1000, hidden=32, seed=0,
                  aggregation=aggregation.secure())

    configs = [
        ("dense / secure", None),
        ("qsgd-8b / secure", compression.qsgd(8)),
        ("topk-10%-8b / secure", compression.topk(0.1, bits=8)),
        ("sketch-4x512 / secure",
         sketch.sketch(rows=4, cols=512, fraction=0.015, keep=64)),
    ]
    results = []
    for name, comp in configs:
        _, h = runtime.run_alg1(data, part, compressor=comp, **common)
        results.append((name, h))
        bd = h.comm["breakdown"]
        print(f"=== {name} ===")
        print(f"  masked elements {bd['wire_elements']:>9,}"
              f"   wire/client {h.comm['uplink_per_client']:>9,} B"
              f"   downlink/client"
              f" {h.comm['downlink_per_client']:>9,} B")
        for r, c, a, b in zip(h.rounds, h.train_cost, h.test_accuracy,
                              h.cum_uplink_bytes):
            print(f"  round {r:3d}: cost {c:.4f}  acc {a:.4f}  "
                  f"cum secure uplink {b / 1e6:8.2f} MB")

    base = results[0][1]
    print("\n=== secure-wire summary (vs dense/secure) ===")
    print(f"{'configuration':24s} {'MB uplink':>10s} {'reduction':>10s}"
          f" {'final acc':>10s}")
    for name, h in results:
        red = base.cum_uplink_bytes[-1] / h.cum_uplink_bytes[-1]
        print(f"{name:24s} {h.cum_uplink_bytes[-1] / 1e6:10.2f}"
              f" {red:9.1f}x {h.test_accuracy[-1]:10.4f}")
    print("\nqsgd/top-k cannot shrink the masked wire (dense ring "
          "uploads); only the sketch's dimension reduction does.")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
