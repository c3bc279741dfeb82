"""Asynchronous rounds under a diurnal straggler trace: accuracy vs
*simulated wall-clock*, sync vs bounded-staleness async vs
drop-stragglers.

The trace (:func:`repro.fed.staleness.diurnal_delay_probs` →
:func:`repro.data.partition.sample_staleness`) swings the straggler
fraction sinusoidally, like a fleet crossing time zones.  Three ways to
run the same schedule:

* **sync** — the barrier waits for the slowest cohort member every
  round: all uploads arrive fresh (best trajectory per round), but a
  round costs 1 + max τ time units.
* **async** — rounds tick at unit time; a slot that computed at round
  t−τ uploads against the params of that round (gathered from the
  engine's K+1-deep staleness ring) and is discounted by (1+τ)^(−a);
  delays past K are dropouts — under secure aggregation the server
  cancels the dropped slot's pair masks exactly (the masked survivor
  sum is bit-identical to the plain survivor sum) and the seed-share
  recovery wire is charged to the ledger, printed below.
* **drop-stragglers** — K = 0: unit rounds, every delayed upload
  discarded and the round renormalized over the survivors.

    PYTHONPATH=src python examples/async_stragglers.py [--secure]
        [--rounds 60] [--clients 8]
"""
import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

import numpy as np

from repro.data import partition, synthetic
from repro.data.partition import sample_staleness
from repro.fed import aggregation, runtime, staleness


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--secure", action="store_true",
                    help="run all modes under secure aggregation "
                         "(dropouts then exercise exact mask recovery)")
    ap.add_argument("--max-staleness", type=int, default=2)
    args = ap.parse_args()

    data = synthetic.classification_dataset(n_train=4000, n_test=1000,
                                            seed=0)
    part = partition.iid(len(data.x_train), num_clients=args.clients,
                         seed=0)
    agg = aggregation.secure() if args.secure else None
    common = dict(batch_size=10, rounds=args.rounds,
                  eval_every=max(1, args.rounds // 6), eval_samples=1000,
                  hidden=32, seed=0, aggregation=agg)

    # the diurnal trace: straggler fraction peaks mid-period, delays
    # spread geometrically over 1..4 — delays past K become dropouts
    probs = staleness.diurnal_delay_probs(args.rounds, max_delay=4,
                                          straggler_frac=0.5,
                                          period=max(4, args.rounds // 3))
    trace = sample_staleness(args.clients,
                             np.arange(1, args.rounds + 1, dtype=np.int64),
                             0, probs)
    k = args.max_staleness
    print(f"trace: {args.rounds} rounds x {args.clients} slots, "
          f"{(trace > 0).mean():.0%} stale, "
          f"{int((trace > k).sum())} dropouts at K={k}")

    modes = [
        ("sync", None),
        ("async", staleness.StalenessConfig(
            max_staleness=k, delay_probs=tuple(map(tuple, probs)))),
        ("drop-stragglers", staleness.StalenessConfig(
            max_staleness=0, delay_probs=tuple(map(tuple, probs)))),
    ]
    results = []
    for name, cfg in modes:
        _, h = runtime.run_alg1(data, part, staleness=cfg, **common)
        clock = np.cumsum(staleness.round_times(
            trace, "sync" if cfg is None else "async", k))
        results.append((name, cfg, h, clock))
        print(f"=== {name} ===")
        for r, c, a in zip(h.rounds, h.train_cost, h.test_accuracy):
            print(f"  round {r:3d}  t={clock[r - 1]:6.1f}  "
                  f"cost {c:.4f}  acc {a:.4f}")
        if cfg is not None:
            a = h.comm["async"]
            print(f"  ledger: {a['dropped_total']} drops "
                  f"({a['dropout_rate']:.1%} of slots), recovery "
                  f"{a['recovery_bytes_per_drop']} B/drop -> "
                  f"{a['recovery_bytes_total']} B total"
                  + (" (secure seed-share recovery)" if args.secure
                     else " (linear: nothing to recover)"))

    print("\n=== summary (simulated wall-clock, unit = one "
          "no-straggler round) ===")
    print(f"{'mode':18s} {'final acc':>10s} {'total time':>11s} "
          f"{'acc/time vs sync':>17s}")
    sync_h, sync_clock = results[0][2], results[0][3]
    for name, cfg, h, clock in results:
        speed = float(sync_clock[-1]) / float(clock[-1])
        print(f"{name:18s} {h.test_accuracy[-1]:10.4f} "
              f"{float(clock[-1]):11.1f} {speed:16.2f}x")
    print("\nthe sync barrier pays the straggler tail every round; "
          "async keeps unit rounds by accepting discounted stale "
          "uploads (and recovering dropped masks exactly); dropping "
          "stragglers is free but discards their data.")
    print("ASYNC_EXAMPLE_OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
