"""Beyond the paper: the SSCA optimizer on an assigned architecture.

Two modes:

* default — ~200 single-process training steps of a reduced llama3-8b
  (same family/wiring, 2 layers) on a synthetic token stream with
  Algorithm 1 as the optimizer (the exact train_step the 256-chip
  dry-run lowers), plus the FedSGD baseline.
  ``python -m repro.launch.train --arch <id> --full`` is the cluster
  entry.

* ``--federated`` — the same reduced architecture as a **federated
  task** (:func:`repro.fed.tasks.transformer.transformer_task`): I
  clients hold disjoint token shards and train through the real
  engine — mini-batch SSCA rounds composed with Bonawitz-style secure
  aggregation and qsgd-compressed uploads, optionally sharded over a
  client mesh (``--shards N`` forces N virtual devices; N must
  divide I).  This is the paper's "arbitrary model specification"
  claim running through the full stack, not just the launch path.

    PYTHONPATH=src python examples/transformer_ssca.py [--arch yi-9b]
    PYTHONPATH=src python examples/transformer_ssca.py --federated \
        [--clients 8] [--shards 2] [--rounds 30]

jax is imported inside the run functions (after argparse): the client
mesh's virtual-device count must land in XLA_FLAGS before jax
initializes.
"""
import argparse
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

ARCH_IDS = (
    "granite-34b", "yi-9b", "whisper-large-v3", "granite-8b",
    "recurrentgemma-9b", "phi-3-vision-4.2b", "rwkv6-7b", "llama3-8b",
    "llama4-maverick-400b-a17b", "qwen3-moe-235b-a22b",
)   # mirrors repro.configs.ARCH_IDS without importing (jax-free top level)


def run(cfg, optimizer: str, n_steps: int, batch: int, seq: int):
    import jax
    import numpy as np

    from repro.core import ssca
    from repro.core.schedules import PowerLaw
    from repro.launch import steps
    from repro.launch.train import batch_stream
    from repro.models import build_model

    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    if optimizer == "ssca":
        # LM-scale tuning: τ=2.0 gives an effective early step ργ/2τ ≈ 0.2
        # (the paper's τ=0.1 is tuned for its 784-dim MLP; τ is "any
        # positive constant" per the paper)
        hp = ssca.SSCAHyperParams(tau=2.0, rho=PowerLaw(0.9, 0.3),
                                  gamma=PowerLaw(0.9, 0.35))
        step_fn = jax.jit(steps.make_train_step(model, hp))
        state = ssca.init(params, with_beta=False)
    else:
        step_fn = jax.jit(steps.make_sgd_train_step(model,
                                                    PowerLaw(0.1, 0.5)))
        state = jax.numpy.asarray(1, jax.numpy.int32)
    stream = batch_stream(cfg, batch, seq, seed=1)
    losses = []
    for t in range(1, n_steps + 1):
        params, state, m = step_fn(params, state, next(stream))
        losses.append(float(m["loss"]))
        if t % 25 == 0:
            print(f"  [{optimizer}] step {t:4d}: "
                  f"loss {np.mean(losses[-25:]):.4f}")
    return losses


def run_federated(args):
    from repro.data import partition
    from repro.fed import compression, runtime
    from repro.fed.tasks import transformer_task
    from repro.launch.mesh import make_client_mesh

    task = transformer_task(args.arch, seq_len=args.seq)
    data = task.default_data(n_train=64 * args.clients, n_test=128, seed=0)
    part = partition.iid(len(data.x_train), args.clients, seed=0)
    mesh = make_client_mesh(args.shards) if args.shards > 1 else None
    print(f"federated SSCA on {task.name} "
          f"(I={args.clients} clients, {args.shards} shard(s), "
          f"secure + qsgd8 uploads)")
    _, h = runtime.run_alg1(
        data, part, task=task, batch_size=args.batch, rounds=args.rounds,
        eval_every=max(1, args.rounds // 5), eval_samples=256,
        seed=0, tau=2.0, lam=0.0, secure=True,
        compressor=compression.qsgd(8), mesh=mesh)
    for i, r in enumerate(h.rounds):
        line = "  ".join(f"{k} {h.metrics[k][i]:.4f}"
                         for k in task.metric_names)
        print(f"  round {r:3d}: {line}")
    print(f"secure uplink: {h.uplink_bytes_per_round} B/round "
          f"({h.comm['breakdown']['wire_overhead_bytes']} B/client mask "
          f"overhead); wall {h.wall_seconds:.1f}s")
    return h


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--federated", action="store_true",
                    help="train as a federated task (secure + compressed "
                         "uploads on the unified engine)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--shards", type=int, default=1,
                    help="client-mesh devices (federated mode; must "
                         "divide --clients)")
    ap.add_argument("--rounds", type=int, default=30)
    args = ap.parse_args()

    if args.federated and args.shards > 1:
        # must precede the first jax import (inside the run functions)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.shards}")

    if args.federated:
        run_federated(args)
        return

    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import reduced

    cfg = reduced(get_config(args.arch))
    print(f"training reduced {args.arch} "
          f"({cfg.num_layers}L d={cfg.d_model}) with SSCA vs FedSGD")
    l_ssca = run(cfg, "ssca", args.steps, args.batch, args.seq)
    l_sgd = run(cfg, "fedsgd", args.steps, args.batch, args.seq)
    print(f"\nfinal 25-step mean loss: "
          f"SSCA {np.mean(l_ssca[-25:]):.4f}  "
          f"FedSGD {np.mean(l_sgd[-25:]):.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
