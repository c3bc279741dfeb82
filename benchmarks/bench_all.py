"""Unified engine benchmark — the per-PR performance trajectory.

Measures round time for every engine configuration the repo ships:
{plain, secure (streaming), secure-reference, sampled} × {single-device,
client-sharded} × model size, and writes ``BENCH_engine.json`` at the
repo root so each PR lands against a recorded perf baseline (CI runs
``--smoke`` and uploads the file as an artifact).

The secure speedup headline — streaming one-pass masking
(:mod:`repro.kernels.secure_agg`) vs the PR-1 mask-materializing
reference — is recorded under ``derived.secure_streaming_speedup``;
both paths produce bit-identical aggregates, so the ratio is pure
implementation speed.

Schema v2 added the **communication ledger**: every config row carries
``uplink_bytes_per_round`` (exact wire bytes, dtype/sparsity/mask-
overhead aware), and the ``comm_curves`` section records
accuracy-vs-cumulative-uplink-bytes for {dense, 8-bit quantized,
top-k 10% + 8-bit} × {plain, secure} uploads — the paper's
communication-cost comparison, with
``derived.uplink_reduction_vs_dense`` as the headline ratios.

Schema v3 adds the **task dimension** (the FedTask refactor): every
``configs`` row carries ``"task"`` (the MLP grid), and the ``tasks``
section runs each non-MLP built-in task — a reduced transformer and
RWKV-6 — through real federated rounds on the client mesh composed
with secure aggregation + qsgd-compressed uploads, recording the
task-declared metric schema and its ledger row.

Schema v4 adds the **population-scaling section** (the cohort-native
engine): with the cohort fixed at S=8, the client population I is swept
over {100, 1k, 10k} ({100, 1k} in smoke) for the MLP and transformer
tasks, recording round wall-clock and the resident index-schedule bytes
((T, S) cohorts + (T, S, B) batches).  The acceptance target —
``derived.population_round_ratio`` ≈ 1, i.e. round time at I=10_000
within 2× of I=100 — is what "per-round cost is O(S), not O(I)" means
operationally.

Schema v5 adds the **sketched secure wire** (:mod:`repro.fed.sketch`):
the ``sketch`` section runs dense-secure vs sketch-secure uploads on
the MLP task long enough for the error-feedback loop to close, and
``derived.secure_wire_reduction`` / ``derived.sketch_acc_loss_pct``
record the acceptance headline — a ≥10× *secure*-uplink reduction at
≤1% final-accuracy loss.  v5 also surfaces the CPU mesh overhead
(host-device shard_map on one physical core is slower than shard1, not
faster) as ``derived.mesh_overhead_ratio``, so the number is a tracked
artifact rather than a surprise in the configs table.

Schema v6 adds the **hierarchy section** (the two-level secure tree,
:class:`repro.fed.aggregation.HierarchicalAggregation`): flat secure vs
``hierarchical(secure(num_sampled=S), groups=16)`` at S ∈ {64, 512,
4096} drawn from synthetic populations up to I = 1M, recording round
time, root-ingest bytes, and live mask-pair count per topology.  The
acceptance ratios — ``derived.hier_ingest_reduction`` and
``derived.hier_mask_pairs_ratio`` ≥ 4× with
``derived.hier_round_time_ratio`` ≤ 1.2 — are CI-gated; both
topologies produce bit-identical aggregates, so the reduction is free.

Schema v7 adds the **async section** (bounded staleness +
dropout-tolerant secure aggregation, :mod:`repro.fed.staleness`): one
straggler trace, three round modes — sync (the barrier pays
1 + max τ per round), async (unit rounds, stale uploads discounted from
the ring buffer, delays past K dropped with exact mask recovery) and
drop-stragglers (K = 0: every delayed upload discarded) — with
accuracy-vs-*simulated wall-clock* as the comparison axis
(:func:`repro.fed.staleness.round_times`).  CI-gated headlines:
``derived.async_wallclock_ratio`` ≤ 0.6 (async reaches the sync
trajectory's final accuracy in ≤ 0.6× the straggler-synced clock) and
``derived.dropout_recovery_overhead`` ≤ 1.2 (the alive-mask
cancellation arithmetic over a clean secure async round).  v7 also adds
the count-sketch row to ``comm_curves`` — the secure column of
``derived.uplink_reduction_vs_dense`` was pinned at 1.0× before (masked
dense words are incompressible by element coding); the sketch row is
the one that actually shrinks the *secure* wire.

Schema v8 adds the **memory section** (the home-sharded arena,
:mod:`repro.fed.arena`): every ``configs`` row now carries
``resident_bytes`` — peak live per-device bytes, sampled from
``jax.live_arrays()`` shard sizes while the run executes — and the
``memory`` section A/Bs ``arena="replicated"`` vs ``arena="sharded"``
over populations up to I = 1M (I ∈ {10k, 100k} in smoke) at S ∈ {8,
512} with top-k error feedback and async K = 4 rings, where the
(I, model) EF arena dominates residency.  CI-gated headlines:
``derived.resident_bytes_ratio`` ≤ 1/D + ε (the sharded arena actually
shrinks per-device residency by the device count) and
``derived.arena_round_time_ratio`` ≤ 1.1 (the collective cohort routing
does not tax the round) — both modes are bit-identical in trajectory
(``tests/sharded_arena_check.py``), so the residency drop is free.

Schema v9 adds the **pipeline section** (the software-pipelined round
engine, ``pipeline=True``): flat async τ≡1 (``max_staleness=1``,
constant discount, all-ones trace) vs the pipelined engine — the two
are bit-identical in trajectory (``tests/pipeline_engine_check.py``),
so the A/B isolates pure wall-clock — over secure cohorts S ∈ {64,
512}, the MLP and transformer tasks, and the available device counts.
The CI-gated headline, ``derived.pipeline_round_time_ratio`` ≤ 0.8, is
taken at the 2-device secure S=512 row with the upload eval balanced
against the masked encode, and applies on hosts with ≥ 2 CPUs (the
section records ``host_cpus``): the win is overlap — consume(t) and
produce(t+1) are independent dataflow, and the pipeline also drops the
generic async machine's evaluate-both-ring-slots-and-select upload —
and overlap needs parallel executors.  A single-CPU host serializes
the stages and timeslices the virtual devices (collective-rendezvous
jitter dominates the mesh A/B), so the gate there degrades to
pipeline-not-materially-slower, ≤ 1.25.  v9 also times with median-of-repeats (the
engine's ``wall_seconds`` is measured around a ``block_until_ready``'d
loop), counts the pipelined double buffer in the memory section
(``topk+pipe`` rows), and adds ``--profile`` to drop a
``jax.profiler`` trace of the gated pipelined run.

    PYTHONPATH=src python benchmarks/bench_all.py [--smoke]

Sharded configs run on virtual host devices
(``--xla_force_host_platform_device_count``), set up before jax
initializes — run this script standalone, not from an already-running
jax process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid for CI (seconds, not minutes)")
    ap.add_argument("--clients", type=int, default=8,
                    help="federated clients I (acceptance target: I>=8)")
    ap.add_argument("--shards", type=int, default=0,
                    help="devices of the sharded configs; 0 = one shard "
                         "per client (smoke default: 2)")
    ap.add_argument("--rounds", type=int, default=0,
                    help="rounds per timed run (0 = 60, smoke 6)")
    ap.add_argument("--batch-size", type=int, default=10)
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="write a jax.profiler trace of the gated "
                         "pipelined run under DIR")
    ap.add_argument("--out", default=str(ROOT / "BENCH_engine.json"))
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    shards = args.shards or (2 if args.smoke else args.clients)
    rounds = args.rounds or (6 if args.smoke else 60)
    n_train = 4000 if args.smoke else 20000
    models = [("h32", 32)] if args.smoke else [("h32", 32), ("h128", 128),
                                               ("h512", 512)]

    # the device count must be fixed before jax initializes
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count"
                                 f"={shards}")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.data import partition, synthetic
    from repro.fed import aggregation, compression, runtime
    from repro.launch.mesh import make_client_mesh

    data = synthetic.classification_dataset(n_train=n_train,
                                            n_test=1000, seed=0)
    part = partition.iid(n_train, args.clients, seed=0)
    mesh = make_client_mesh(shards)

    import gc
    import threading
    import time as time_mod

    def sample_resident(fn, interval=0.02):
        """Run ``fn()`` while a sampler thread sums live-array bytes per
        device (``jax.live_arrays()`` → per-shard ``data.nbytes``);
        return ``(fn(), peak_bytes_on_busiest_device)``.  The resident
        state under measurement — weights, EF arena, snapshot ring — is
        held as Python-level arrays across the engine's chunk loop, so
        a 20 ms sampler sees it; transient XLA scratch inside a single
        dispatch is invisible either way and identical across arenas."""
        peak = [0]
        stop = threading.Event()

        def sampler():
            while not stop.is_set():
                per_dev = {}
                for a in jax.live_arrays():
                    try:
                        for sh in a.addressable_shards:
                            d = sh.device.id
                            per_dev[d] = per_dev.get(d, 0) + sh.data.nbytes
                    except Exception:       # deleted under our feet
                        continue
                if per_dev:
                    peak[0] = max(peak[0], max(per_dev.values()))
                time_mod.sleep(interval)

        gc.collect()                        # drop prior configs' state
        t = threading.Thread(target=sampler, daemon=True)
        t.start()
        try:
            out = fn()
        finally:
            stop.set()
            t.join()
        return out, peak[0]

    def median_wall(fn, repeats=3):
        """Median wall-clock over ``repeats`` staged reruns of ``fn``
        (a closure returning ``(params, History)``); the engine measures
        ``wall_seconds`` around a ``jax.block_until_ready``'d chunk
        loop, so each sample is sync-clean and the median rejects the
        odd scheduler hiccup a min/best would hide less honestly."""
        walls, h = [], None
        for _ in range(repeats):
            _, h = fn()
            walls.append(h.wall_seconds)
        return float(np.median(walls)), h
    aggs = [
        ("plain", None, True),
        ("secure", aggregation.secure(), True),
        # the PR-1 baseline: sharding always streams, so reference is a
        # single-device-only configuration
        ("secure_ref", aggregation.secure(streaming=False), False),
        ("sampled", aggregation.sampled(max(1, args.clients // 2)), True),
    ]

    def timed_run(hidden, agg, use_mesh, compressor=None):
        kw = dict(batch_size=args.batch_size, rounds=rounds,
                  eval_every=rounds, eval_samples=500, hidden=hidden,
                  seed=0, aggregation=agg, compressor=compressor,
                  mesh=mesh if use_mesh else None)
        # compile + stage; the sampled rerun of the staged program is
        # what the resident-bytes column measures (timing stays clean —
        # the sampler thread never overlaps the timed runs)
        params = runtime.run_alg1(data, part, **kw)[0]
        _, resident = sample_resident(
            lambda: runtime.run_alg1(data, part, **kw))
        wall, hist = median_wall(
            lambda: runtime.run_alg1(data, part, **kw))
        count = sum(int(np.prod(w.shape)) for w in jax.tree.leaves(params))
        return wall, hist, count, resident

    configs = []
    print("name,us_per_call,derived")
    for mname, hidden in models:
        for aname, agg, shardable in aggs:
            for use_mesh in ([False, True] if shardable else [False]):
                d = shards if use_mesh else 1
                wall, h, count, resident = timed_run(hidden, agg, use_mesh)
                final = float(h.train_cost[-1])
                row = {"name": f"alg1/{aname}/shard{d}/{mname}",
                       "task": "mlp",
                       "aggregation": aname, "shards": d, "model": mname,
                       "hidden": hidden, "param_count": count,
                       "rounds": rounds, "wall_s": round(wall, 4),
                       "round_ms": round(wall / rounds * 1e3, 4),
                       "resident_bytes": resident,
                       "final_cost": round(final, 6),
                       "uplink_bytes_per_round": h.uplink_bytes_per_round,
                       "downlink_bytes_per_round":
                           h.downlink_bytes_per_round}
                configs.append(row)
                print(f"bench_all/{row['name']},"
                      f"{wall / rounds * 1e6:.1f},"
                      f"final_cost={final:.4f}")

    # -- the communication-cost comparison: accuracy vs cumulative bytes.
    # The count-sketch only composes with the secure wire (its whole
    # point is shrinking the *masked* upload; it emits on-grid values),
    # so its row runs under secure aggregation only.
    from repro.fed import sketch as sketch_mod
    comm_rounds = rounds if args.smoke else max(rounds, 60)
    comm_hidden = models[0][1]
    comm_sketch = sketch_mod.sketch(rows=4, cols=512, fraction=0.015,
                                    keep=64)
    compressors = [("dense", None, ("plain", "secure")),
                   ("qsgd8", compression.qsgd(8), ("plain", "secure")),
                   ("topk10_8b", compression.topk(0.1, bits=8),
                    ("plain", "secure")),
                   ("sketch", comm_sketch, ("secure",))]
    comm_curves = []
    for cname, comp, agg_names in compressors:
        for aname, agg in (("plain", None), ("secure",
                                             aggregation.secure())):
            if aname not in agg_names:
                continue
            kw = dict(batch_size=args.batch_size, rounds=comm_rounds,
                      eval_every=max(1, comm_rounds // 4),
                      eval_samples=500, hidden=comm_hidden, seed=0,
                      aggregation=agg, compressor=comp)
            _, h = runtime.run_alg1(data, part, **kw)
            comm_curves.append({
                "name": f"alg1/{cname}/{aname}",
                "compressor": cname, "aggregation": aname,
                "uplink_bytes_per_round": h.uplink_bytes_per_round,
                "rounds": h.rounds,
                "test_accuracy": [round(a, 4) for a in h.test_accuracy],
                "cum_uplink_bytes": h.cum_uplink_bytes,
                "comm": h.comm})
            print(f"bench_all/comm/{cname}/{aname},"
                  f"{h.uplink_bytes_per_round},"
                  f"acc={h.test_accuracy[-1]:.4f}")

    # -- the task dimension: non-MLP FedTasks through real federated
    # rounds on the client mesh, secure + compressed (the FedTask
    # refactor's acceptance scenario)
    from repro.fed.tasks import rwkv6_task, transformer_task
    task_rounds = 4 if args.smoke else 12
    task_rows = []
    for task in (transformer_task(seq_len=16, d_model=32, vocab=64),
                 rwkv6_task(seq_len=16, d_model=32, vocab=64)):
        tdata = task.default_data(n_train=32 * args.clients, n_test=64,
                                  seed=0)
        tpart = partition.iid(len(tdata.x_train), args.clients, seed=0)
        kw = dict(batch_size=4, rounds=task_rounds, eval_every=task_rounds,
                  eval_samples=128, seed=0, tau=2.0, lam=0.0,
                  aggregation=aggregation.secure(),
                  compressor=compression.qsgd(8), mesh=mesh)
        runtime.run_alg1(tdata, tpart, task=task, **kw)   # compile + stage
        _, h = runtime.run_alg1(tdata, tpart, task=task, **kw)
        row = {"name": f"alg1/{task.name}/secure+qsgd8/shard{shards}",
               "task": task.name, "aggregation": "secure",
               "compressor": "qsgd8", "shards": shards,
               "rounds": task_rounds,
               "wall_s": round(h.wall_seconds, 4),
               "round_ms": round(h.wall_seconds / task_rounds * 1e3, 4),
               "metrics": {k: [round(v, 6) for v in series]
                           for k, series in h.metrics.items()},
               "uplink_bytes_per_round": h.uplink_bytes_per_round,
               "downlink_bytes_per_round": h.downlink_bytes_per_round}
        task_rows.append(row)
        print(f"bench_all/{row['name']},"
              f"{h.wall_seconds / task_rounds * 1e6:.1f},"
              f"final_cost={h.metrics['train_cost'][-1]:.4f}")

    # -- population scaling: S fixed, I swept (the cohort-native engine's
    # acceptance scenario: round cost tracks the cohort, not the
    # population; index memory is O(T·S·B))
    from repro.fed import engine as engine_mod
    pop_cohort = 8
    pop_is = [100, 1000] if args.smoke else [100, 1000, 10000]
    population = []

    def pop_row(task_name, tdata, tpart, i_pop, rounds_p, bsz, run_kw):
        runtime.run_alg1(tdata, tpart, **run_kw)     # compile + stage
        best, h = None, None
        for _ in range(2):
            _, h = runtime.run_alg1(tdata, tpart, **run_kw)
            best = h.wall_seconds if best is None \
                else min(best, h.wall_seconds)
        cohorts_a, idx_a = engine_mod.build_schedule(
            tpart, bsz, rounds_p, 1, 0, cohort_size=pop_cohort)
        row = {"name": f"alg1/{task_name}/sampled{pop_cohort}/I{i_pop}",
               "task": task_name, "population": i_pop,
               "cohort": pop_cohort, "rounds": rounds_p,
               "batch_size": bsz,
               "wall_s": round(best, 4),
               "round_ms": round(best / rounds_p * 1e3, 4),
               "index_bytes": int(cohorts_a.nbytes + idx_a.nbytes),
               "uplink_bytes_per_round": h.uplink_bytes_per_round}
        population.append(row)
        print(f"bench_all/{row['name']},"
              f"{best / rounds_p * 1e6:.1f},"
              f"index_bytes={row['index_bytes']}")

    pop_rounds = rounds
    for i_pop in pop_is:
        ppart = partition.iid(n_train, i_pop, seed=0)
        pop_row("mlp", data, ppart, i_pop, pop_rounds, args.batch_size,
                dict(batch_size=args.batch_size, rounds=pop_rounds,
                     eval_every=pop_rounds, eval_samples=500,
                     hidden=models[0][1], seed=0,
                     aggregation=aggregation.sampled(pop_cohort)))
    from repro.fed.tasks import transformer_task
    ttask = transformer_task(seq_len=16, d_model=32, vocab=64)
    tn = max(pop_is)
    tdata = ttask.default_data(n_train=tn, n_test=64, seed=0)
    t_rounds = 3 if args.smoke else 8
    for i_pop in pop_is:
        tpart = partition.iid(tn, i_pop, seed=0)
        pop_row(ttask.name, tdata, tpart, i_pop, t_rounds, 2,
                dict(batch_size=2, rounds=t_rounds, eval_every=t_rounds,
                     eval_samples=64, seed=0, tau=2.0, lam=0.0,
                     task=ttask,
                     aggregation=aggregation.sampled(pop_cohort)))

    # -- the sketched secure wire: dense-secure vs sketch-secure on the
    # MLP — enough rounds for the two-phase error-feedback loop to
    # close, so the accuracy-loss claim is real, not a warmup artifact
    sk_rounds = 300
    if args.smoke:
        sk_hidden = 32
        sk_comp = sketch_mod.sketch(rows=4, cols=512, fraction=0.015,
                                    keep=64)
    else:
        sk_hidden = 128
        sk_comp = sketch_mod.sketch(rows=4, cols=1024, fraction=0.02,
                                    keep=256)
    sketch_rows = []
    for sname, comp in (("dense", None), ("sketch", sk_comp)):
        kw = dict(batch_size=args.batch_size, rounds=sk_rounds,
                  eval_every=max(1, sk_rounds // 4), eval_samples=1000,
                  hidden=sk_hidden, seed=0,
                  aggregation=aggregation.secure(), compressor=comp)
        _, h = runtime.run_alg1(data, part, **kw)
        row = {"name": f"alg1/{sname}/secure",
               "compressor": sname, "hidden": sk_hidden,
               "rounds": sk_rounds,
               "uplink_bytes_per_round": h.uplink_bytes_per_round,
               "downlink_bytes_per_round": h.downlink_bytes_per_round,
               "final_accuracy": round(h.test_accuracy[-1], 4),
               "test_accuracy": [round(a, 4) for a in h.test_accuracy],
               "cum_uplink_bytes": h.cum_uplink_bytes,
               "comm": h.comm}
        if comp is not None:
            row["sketch_config"] = {"rows": comp.rows, "cols": comp.cols,
                                    "fraction": comp.fraction,
                                    "keep": comp._keep}
        sketch_rows.append(row)
        print(f"bench_all/sketch/{sname},"
              f"{h.uplink_bytes_per_round},"
              f"acc={h.test_accuracy[-1]:.4f}")

    # -- the hierarchical tree: flat secure vs the two-level secure tree
    # (G=16 edge aggregators) at cohort sizes up to S=4096 drawn from
    # synthetic populations up to I=1M.  Round cost is O(S) either way
    # (cohort-native engine), so the tiny model isolates the combine; the
    # ledger columns are what the tree actually buys — root ingest and
    # live mask-pair state drop from O(S) to O(G)+O(S/G).
    hier_groups = 16
    hier_grid = [(64, 10_000), (512, 100_000), (4096, 1_000_000)]
    hier_rounds = 2
    hier_rows = []
    for s_coh, i_pop in hier_grid:
        hdata = synthetic.classification_dataset(n_train=i_pop, n_test=256,
                                                 seed=0, k=16)
        hpart = partition.iid(i_pop, i_pop, seed=0)
        tree_agg = aggregation.hierarchical(
            aggregation.secure(num_sampled=s_coh), groups=hier_groups)
        row = {"name": f"alg1/hier/S{s_coh}", "cohort": s_coh,
               "population": i_pop, "groups": hier_groups,
               "members": tree_agg.members(i_pop),
               "rounds": hier_rounds}
        for tname, agg in (("flat",
                            aggregation.secure(num_sampled=s_coh)),
                           ("tree", tree_agg)):
            kw = dict(batch_size=4, rounds=hier_rounds,
                      eval_every=hier_rounds, eval_samples=256, hidden=8,
                      seed=0, aggregation=agg)
            runtime.run_alg1(hdata, hpart, **kw)     # compile + stage
            params, h = runtime.run_alg1(hdata, hpart, **kw)
            dense = sum(int(np.prod(w.shape))
                        for w in jax.tree.leaves(params))
            if tname == "tree":
                ingest = agg.root_ingest_bytes(dense, i_pop)
                pairs = agg.mask_pair_count(i_pop)
            else:
                ingest = s_coh * 4 * dense
                pairs = s_coh * (s_coh - 1) // 2
            row["param_count"] = dense
            row[tname] = {
                "round_ms": round(h.wall_seconds / hier_rounds * 1e3, 4),
                "uplink_bytes_per_round": h.uplink_bytes_per_round,
                "root_ingest_bytes": ingest,
                "mask_pairs": pairs}
            print(f"bench_all/hier/S{s_coh}/{tname},"
                  f"{h.wall_seconds / hier_rounds * 1e6:.1f},"
                  f"ingest={ingest} pairs={pairs}")
        hier_rows.append(row)

    # -- the async round mode: one straggler trace, three round modes,
    # accuracy vs *simulated wall-clock* (unit = one no-straggler round).
    # The sync barrier pays 1 + max τ per round; async rounds are unit
    # time with stale uploads discounted from the staleness ring (and
    # delays past K dropped with exact secure-mask recovery);
    # drop-stragglers is the K = 0 degenerate (every delayed upload
    # discarded).  The sync *trajectory* is straggler-free — the barrier
    # waits, every upload arrives fresh — so its accuracy column doubles
    # as the no-straggler target the async mode must reach.
    from repro.data.partition import sample_staleness
    from repro.fed import staleness as stale_mod
    async_sync_rounds = 30 if args.smoke else 60
    async_k = 2
    async_probs = (0.5, 0.2, 0.15, 0.1, 0.05)     # delays 3, 4 drop at K=2
    async_seed = 0
    # the unit-time modes get a 2x round budget: their clock at 2R is
    # still well under the straggler-synced barrier's clock at R (~3.7R
    # under this trace), so "reach the sync target within the 0.6x clock
    # window" is a real race, not a round-count tie
    async_modes = [
        ("sync", None, async_sync_rounds),
        ("async", stale_mod.StalenessConfig(max_staleness=async_k,
                                            delay_probs=async_probs),
         2 * async_sync_rounds),
        ("drop", stale_mod.StalenessConfig(max_staleness=0,
                                           delay_probs=async_probs),
         2 * async_sync_rounds),
    ]
    async_trace = sample_staleness(
        args.clients,
        np.arange(1, 2 * async_sync_rounds + 1, dtype=np.int64),
        async_seed, async_probs)
    async_rows = []
    for mode, cfg, rounds_m in async_modes:
        kw = dict(batch_size=args.batch_size, rounds=rounds_m,
                  eval_every=max(1, rounds_m // 12), eval_samples=500,
                  hidden=models[0][1], seed=async_seed, staleness=cfg)
        _, h = runtime.run_alg1(data, part, **kw)
        k_eff = async_k if cfg is None else cfg.max_staleness
        times = stale_mod.round_times(async_trace[:rounds_m], mode, k_eff)
        sim_clock = np.cumsum(times)
        row = {"name": f"alg1/async/{mode}", "mode": mode,
               "rounds": rounds_m,
               "max_staleness": None if cfg is None else cfg.max_staleness,
               "final_accuracy": round(h.test_accuracy[-1], 4),
               "test_accuracy": [round(a, 4) for a in h.test_accuracy],
               "sim_clock": [round(float(sim_clock[r - 1]), 2)
                             for r in h.rounds],
               "sim_clock_total": round(float(sim_clock[-1]), 2),
               "wall_s": round(h.wall_seconds, 4)}
        if cfg is not None:
            row["async"] = h.comm["async"]
        async_rows.append(row)
        print(f"bench_all/async/{mode},"
              f"{h.wall_seconds / rounds_m * 1e6:.1f},"
              f"acc={h.test_accuracy[-1]:.4f}"
              f" sim_clock={sim_clock[-1]:.1f}")

    # the recovery-arithmetic overhead, isolated: secure async rounds
    # with the dropout trace vs secure async rounds with the all-zero
    # trace (same ring depth, same compiled structure — the delta is the
    # alive-mask cancellation itself)
    async_recovery = {}
    rec_trace = async_trace[:async_sync_rounds]
    for rname, trace in (("clean", np.zeros_like(rec_trace)),
                         ("dropout", rec_trace)):
        kw = dict(batch_size=args.batch_size, rounds=async_sync_rounds,
                  eval_every=async_sync_rounds, eval_samples=500,
                  hidden=models[0][1], seed=async_seed,
                  aggregation=aggregation.secure(),
                  staleness=stale_mod.StalenessConfig(
                      max_staleness=async_k, delay_probs=async_probs),
                  staleness_trace=trace)
        runtime.run_alg1(data, part, **kw)           # compile + stage
        best = None
        for _ in range(2):
            _, h = runtime.run_alg1(data, part, **kw)
            best = h.wall_seconds if best is None \
                else min(best, h.wall_seconds)
        async_recovery[rname] = {
            "round_ms": round(best / async_sync_rounds * 1e3, 4),
            "async": h.comm["async"]}
        print(f"bench_all/async/secure_{rname},"
              f"{best / async_sync_rounds * 1e6:.1f},"
              f"drops={h.comm['async']['dropped_total']}")

    # -- the memory section: replicated vs home-sharded arena residency.
    # A tiny model over a large population makes the (I_pad, model) EF
    # residual arena (and the async snapshot ring) the dominant resident
    # allocation, so the per-device peak isolates what the home-device
    # arena shards: sharded residency must land near 1/D of replicated
    # while round time stays flat — the trajectories themselves are
    # bit-identical (tests/sharded_arena_check.py), so the drop is free.
    from repro.fed.staleness import StalenessConfig
    mem_hidden = 8
    mem_rounds = 4
    mem_is = [10_000, 100_000] if args.smoke \
        else [10_000, 100_000, 1_000_000]
    mem_cohorts = [8] if args.smoke else [8, 512]
    # the pipelined variant rides along so the +1 snapshot slot (the
    # depth-2 param ring) and the in-flight pending buffer are *counted*
    # in the residency table, not just documented
    mem_variants = [("topk", compression.topk(0.1, bits=8), None, False),
                    ("topk+async4", compression.topk(0.1, bits=8),
                     StalenessConfig(max_staleness=4), False),
                    ("topk+pipe", compression.topk(0.1, bits=8), None,
                     True)]
    if not args.smoke:
        mem_variants.insert(0, ("plain", None, None, False))
    mem_rows = []
    for i_pop in mem_is:
        mdata = synthetic.classification_dataset(n_train=i_pop, n_test=256,
                                                 seed=0, k=16)
        mpart = partition.iid(i_pop, i_pop, seed=0)
        for s_coh in mem_cohorts:
            for vname, comp, scfg, pipe in mem_variants:
                for arena_mode in ("replicated", "sharded"):
                    kw = dict(batch_size=4, rounds=mem_rounds,
                              eval_every=mem_rounds // 2, eval_samples=256,
                              hidden=mem_hidden, seed=0,
                              aggregation=aggregation.sampled(s_coh),
                              compressor=comp, staleness=scfg,
                              pipeline=pipe, mesh=mesh, arena=arena_mode)
                    (_, h), resident = sample_resident(
                        lambda: runtime.run_alg1(mdata, mpart, **kw))
                    best, h = median_wall(
                        lambda: runtime.run_alg1(mdata, mpart, **kw))
                    mem_rows.append({
                        "name": f"alg1/mem/{vname}/I{i_pop}/S{s_coh}"
                                f"/{arena_mode}",
                        "variant": vname, "population": i_pop,
                        "cohort": s_coh, "arena": arena_mode,
                        "shards": shards, "hidden": mem_hidden,
                        "max_staleness":
                            None if scfg is None else scfg.max_staleness,
                        "pipeline": pipe,
                        "rounds": mem_rounds,
                        "round_ms": round(best / mem_rounds * 1e3, 4),
                        "resident_bytes": resident})
                    print(f"bench_all/{mem_rows[-1]['name']},"
                          f"{best / mem_rounds * 1e6:.1f},"
                          f"resident_bytes={resident}")
        del mdata, mpart

    # -- the pipelined round engine: flat async τ≡1 (max_staleness=1,
    # constant discount, all-ones trace) vs pipeline=True.  The two are
    # bit-identical in trajectory (tests/pipeline_engine_check.py), so
    # the A/B isolates pure wall-clock.  What the pipeline buys is
    # *overlap*: consume(t) (masked encode + combine + SSCA step) and
    # produce(t+1) (the next cohort's upload evals against the stale
    # buffer) are independent dataflow, so on a host with >= 2
    # executors (XLA:CPU runs independent thunks concurrently, and each
    # mesh device's program gets its own thread) the round costs
    # ~max(U, E) instead of U + E.  The gated row balances the two: a
    # 2-device secure S=512 combine (E: the O(S²·model) pairwise-PRG
    # encode) against a batch large enough that the cohort upload eval
    # U is the same order.  On a single-CPU host there is nothing to
    # overlap *with* — the A/B degenerates to the serial sum and the
    # honest ratio is ~0.95-1.0 (the pipeline still avoids the async
    # ring push/select machinery) — so `host_cpus` is recorded and the
    # CI gate keys off it.  rounds stay small: the gated secure round
    # is seconds on CPU, and the pipeline's per-round cost is exact at
    # any T (prologue+drain replace one scan step — no fill/drain
    # rounds to amortize)
    pipe_rounds = 2 if args.smoke else 4
    pipe_i, pipe_per = 1024, 128
    pipe_data = synthetic.classification_dataset(
        n_train=pipe_i * pipe_per, n_test=512, seed=0)
    pipe_part = partition.iid(pipe_i * pipe_per, pipe_i, seed=0)
    # the gate row's own dataset: fewer, fatter clients (every sample a
    # client holds is consumed each round) with k=392 features keeps U
    # ~ E at S=512 while the arrays stay under 1 GB
    gate_pop, gate_per, gate_k = 600, 768, 392
    gdata = synthetic.classification_dataset(
        n_train=gate_pop * gate_per, n_test=512, seed=0, k=gate_k)
    gpart = partition.iid(gate_pop * gate_per, gate_pop, seed=0)
    pipe_devs = [1] + [d for d in (2, 4) if d <= shards]
    gate_dev = 2 if 2 in pipe_devs else None
    # rows: (task, cohort, hidden, batch, devices, gate)
    pipe_grid = [("mlp", 64, 32, args.batch_size, d, False)
                 for d in pipe_devs]
    if not args.smoke:
        pipe_grid += [("mlp", 512, 128, 128, d, False)
                      for d in pipe_devs if d != gate_dev]
        pipe_grid += [("transformer", 64, None, 2, d, False)
                      for d in pipe_devs]
        if gate_dev:
            pipe_grid.append(("transformer", 512, None, 2, gate_dev,
                              False))
    elif gate_dev:
        pipe_grid.append(("transformer", 64, None, 2, gate_dev, False))
    if gate_dev:
        pipe_grid.append(("mlp", 512, 32, gate_per, gate_dev, True))
    tdata_p = ttask.default_data(n_train=pipe_i * 4, n_test=64, seed=0)
    tpart_p = partition.iid(pipe_i * 4, pipe_i, seed=0)
    pipe_host_cpus = len(os.sched_getaffinity(0)) \
        if hasattr(os, "sched_getaffinity") else os.cpu_count()
    pipe_rows = []
    for ptask, s_coh, hid, bsz, dev, is_gate in pipe_grid:
        pmesh = make_client_mesh(dev) if dev > 1 else None
        kw = dict(batch_size=bsz, rounds=pipe_rounds,
                  eval_every=pipe_rounds, seed=0, mesh=pmesh,
                  aggregation=aggregation.secure(num_sampled=s_coh))
        if ptask == "mlp":
            mdat, mprt = (gdata, gpart) if is_gate else (pipe_data,
                                                         pipe_part)
            run = lambda **m: runtime.run_alg1(mdat, mprt,
                                               eval_samples=256,
                                               hidden=hid, **kw, **m)
        else:
            run = lambda **m: runtime.run_alg1(tdata_p, tpart_p,
                                               task=ttask, tau=2.0,
                                               lam=0.0, eval_samples=64,
                                               **kw, **m)
        tau1 = stale_mod.StalenessConfig(
            max_staleness=1, schedule=stale_mod.ConstantDiscount())
        trace1 = np.ones((pipe_rounds, s_coh), np.int64)
        ms = {}
        for mode, extra in (
                ("flat", dict(staleness=tau1, staleness_trace=trace1)),
                ("pipe", dict(pipeline=True))):
            run(**extra)                             # compile + stage
            wall, _ = median_wall(lambda: run(**extra))
            ms[mode] = round(wall / pipe_rounds * 1e3, 4)
        if is_gate and args.profile:
            run(pipeline=True, profile_dir=args.profile)
        pipe_rows.append({
            "name": f"alg1/pipe/{ptask}/S{s_coh}/shard{dev}",
            "task": ptask, "cohort": s_coh, "shards": dev,
            "hidden": hid, "batch_size": bsz,
            "features": gate_k if is_gate else None,
            "aggregation": "secure",
            "gate": is_gate, "rounds": pipe_rounds,
            "flat_round_ms": ms["flat"], "pipe_round_ms": ms["pipe"],
            "ratio": round(ms["pipe"] / ms["flat"], 3)})
        print(f"bench_all/{pipe_rows[-1]['name']},"
              f"{ms['pipe'] / 1e-3:.1f},"
              f"ratio={pipe_rows[-1]['ratio']}"
              f"{' [gate]' if is_gate else ''}")
    del pipe_data, pipe_part, gdata, gpart, tdata_p, tpart_p

    def round_ms(name):
        return {c["name"]: c["round_ms"] for c in configs}[name]

    derived = {"secure_streaming_speedup_vs_reference": {
        m: round(round_ms(f"alg1/secure_ref/shard1/{m}")
                 / round_ms(f"alg1/secure/shard1/{m}"), 2)
        for m, _ in models}}
    derived["target"] = "secure streaming >= 2x reference at I>=8"
    derived["sharded_round_ratio"] = {
        m: round(round_ms(f"alg1/plain/shard{shards}/{m}")
                 / round_ms(f"alg1/plain/shard1/{m}"), 2)
        for m, _ in models}

    def curve(name):
        return {c["name"]: c for c in comm_curves}[name]

    dense_bytes = curve("alg1/dense/plain")["cum_uplink_bytes"][-1]
    derived["uplink_reduction_vs_dense"] = {
        c["name"]: round(dense_bytes / c["cum_uplink_bytes"][-1], 2)
        for c in comm_curves if c["name"] != "alg1/dense/plain"}
    derived["comm_target"] = ">= 4x fewer uplink bytes than dense for " \
        "8-bit / top-k plain uploads at <= 2% accuracy loss"

    derived["population_round_ratio"] = {}
    for tname in {r["task"] for r in population}:
        ms = {r["population"]: r["round_ms"] for r in population
              if r["task"] == tname}
        derived["population_round_ratio"][tname] = round(
            ms[max(ms)] / ms[min(ms)], 2)
    derived["population_target"] = \
        f"round wall-clock at I={max(pop_is)} within 2x of " \
        f"I={min(pop_is)} at S={pop_cohort} (O(S) rounds)"

    # the sketched secure wire headline: secure uplink bytes ratio and
    # final-accuracy gap, dense-secure vs sketch-secure
    sk_by = {r["compressor"]: r for r in sketch_rows}
    derived["secure_wire_reduction"] = round(
        sk_by["dense"]["uplink_bytes_per_round"]
        / sk_by["sketch"]["uplink_bytes_per_round"], 2)
    derived["sketch_acc_loss_pct"] = round(
        100.0 * (sk_by["dense"]["final_accuracy"]
                 - sk_by["sketch"]["final_accuracy"]), 3)
    derived["sketch_target"] = ">= 10x secure uplink reduction at " \
        "<= 1% final-accuracy loss"

    # the hierarchical headline: root-ingest and mask-pair reduction of
    # the two-level tree vs flat secure, plus the round-time tax (the
    # tree must not slow the round down while shrinking the root's state)
    derived["hier_ingest_reduction"] = {
        f"S{r['cohort']}": round(r["flat"]["root_ingest_bytes"]
                                 / r["tree"]["root_ingest_bytes"], 2)
        for r in hier_rows}
    derived["hier_mask_pairs_ratio"] = {
        f"S{r['cohort']}": round(r["flat"]["mask_pairs"]
                                 / r["tree"]["mask_pairs"], 2)
        for r in hier_rows}
    derived["hier_round_time_ratio"] = {
        f"S{r['cohort']}": round(r["tree"]["round_ms"]
                                 / r["flat"]["round_ms"], 2)
        for r in hier_rows}
    derived["hier_target"] = \
        f">= 4x root-ingest and mask-pair reduction at G={hier_groups} " \
        f"with tree round time <= 1.2x flat (bit-identical aggregates)"

    # the async headline: simulated wall-clock for the async mode to
    # reach the sync trajectory's final accuracy (small tolerance for
    # the stale-discount jitter), over the straggler-synced total clock
    by_mode = {r["mode"]: r for r in async_rows}
    sync_total = by_mode["sync"]["sim_clock_total"]
    target_acc = by_mode["sync"]["final_accuracy"] - 0.005
    a_row = by_mode["async"]
    reached = [t for t, acc in zip(a_row["sim_clock"],
                                   a_row["test_accuracy"])
               if acc >= target_acc]
    time_to_target = reached[0] if reached else float("inf")
    derived["async_wallclock_ratio"] = round(time_to_target / sync_total, 3)
    derived["async_target"] = \
        "async reaches sync-no-straggler final accuracy at <= 0.6x the " \
        "straggler-synced simulated wall-clock"
    derived["drop_stragglers_final_accuracy"] = \
        by_mode["drop"]["final_accuracy"]
    derived["dropout_recovery_overhead"] = round(
        async_recovery["dropout"]["round_ms"]
        / async_recovery["clean"]["round_ms"], 2)
    derived["dropout_recovery_target"] = \
        "secure async round with dropout recovery <= 1.2x the clean " \
        "(zero-trace) secure async round"

    # the home-sharded arena headlines: per-device peak residency and
    # round-time tax of arena="sharded" over arena="replicated", gated
    # at the largest-I top-k-EF sync row (where the (I, model) arena
    # dominates residency and the contract is sharpest)
    mem_by = {r["name"]: r for r in mem_rows}

    def mem_pair(variant, i_pop, s_coh):
        rep = mem_by[f"alg1/mem/{variant}/I{i_pop}/S{s_coh}/replicated"]
        sh = mem_by[f"alg1/mem/{variant}/I{i_pop}/S{s_coh}/sharded"]
        return rep, sh

    gate_i = max(i for i in mem_is if i <= 100_000)
    rep, sh = mem_pair("topk", gate_i, mem_cohorts[0])
    derived["resident_bytes_ratio"] = round(
        sh["resident_bytes"] / rep["resident_bytes"], 3)
    derived["arena_round_time_ratio"] = round(
        sh["round_ms"] / rep["round_ms"], 2)
    derived["arena_resident_ratio_by_config"] = {
        f"{v}/I{i}/S{s}": round(
            mem_pair(v, i, s)[1]["resident_bytes"]
            / mem_pair(v, i, s)[0]["resident_bytes"], 3)
        for v, *_ in mem_variants for i in mem_is for s in mem_cohorts}
    derived["arena_target"] = \
        f"sharded-arena peak per-device resident <= 1/{shards} + eps of " \
        f"replicated at I={gate_i} with top-k EF, round time <= 1.1x " \
        f"(trajectories bit-identical either way)"

    # the pipelined-engine headline: pipe/flat round time at the gated
    # 2-device secure S=512 compute-dominated row (trajectories are
    # bit-identical, so the ratio is pure wall-clock)
    gate_rows = [r for r in pipe_rows if r["gate"]]
    if gate_rows:
        derived["pipeline_round_time_ratio"] = gate_rows[0]["ratio"]
    derived["pipeline_ratio_by_config"] = {
        f"{r['task']}/S{r['cohort']}/shard{r['shards']}": r["ratio"]
        for r in pipe_rows}
    derived["pipeline_target"] = \
        "pipelined round <= 0.8x the flat async tau==1 round at the " \
        "2-device secure S=512 balanced row on hosts with >= 2 CPUs " \
        "(the overlap is a parallelism win; a single-executor host " \
        "serializes produce and consume, so there the gate degrades " \
        "to pipeline-never-slower, <= 1.1x)"

    # the CPU mesh tax, per aggregation x model: round time on the
    # host-device mesh over single-device (shard_map on one physical
    # core adds dispatch overhead; on real multi-chip backends this
    # ratio is what should drop below 1)
    derived["mesh_overhead_ratio"] = {
        f"{a}/{m}": round(round_ms(f"alg1/{a}/shard{shards}/{m}")
                          / round_ms(f"alg1/{a}/shard1/{m}"), 2)
        for a in ("plain", "secure") for m, _ in models}
    derived["mesh_overhead_note"] = \
        f"shard{shards}/shard1 round_ms on backend=" \
        f"{jax.default_backend()}; expected > 1 on CPU host devices"

    out = {"schema": "bench_engine/v9",
           "jax": jax.__version__,
           "backend": jax.default_backend(),
           "host_devices": jax.device_count(),
           "smoke": bool(args.smoke),
           "clients": args.clients, "batch_size": args.batch_size,
           "configs": configs, "tasks": task_rows,
           "population": population,
           "comm_curves": comm_curves,
           "sketch": sketch_rows,
           "hierarchy": hier_rows,
           "async": {"trace": {"delay_probs": list(async_probs),
                               "max_staleness": async_k,
                               "seed": async_seed,
                               "rounds": 2 * async_sync_rounds,
                               "stale_fraction":
                                   round(float((async_trace > 0).mean()), 4),
                               "dropped_total":
                                   int((async_trace > async_k).sum())},
                     "modes": async_rows,
                     "recovery": async_recovery},
           "memory": {"shards": shards, "hidden": mem_hidden,
                      "rows": mem_rows},
           "pipeline": {"rounds": pipe_rounds, "population": pipe_i,
                        "gate_population": gate_pop,
                        "host_cpus": pipe_host_cpus, "rows": pipe_rows},
           "derived": derived}
    Path(args.out).write_text(json.dumps(out, indent=1) + "\n")
    print(f"bench_all/summary,0.0,"
          f"secure_speedup={derived['secure_streaming_speedup_vs_reference']}"
          f" -> {args.out}")


if __name__ == "__main__":
    main()
