"""Async round-mode bit-identity harness.

Two contracts, each checked against live runs in this process:

* **zero trace == sync, bitwise** — every configuration run with
  ``StalenessConfig(max_staleness=2)`` and no delay distribution (the
  all-zero trace) must reproduce the synchronous run of the same
  configuration ``float.hex()``-exactly.  The async engine carries the
  staleness ring buffer, the per-slot discount chain and the alive
  mask through the scan; an all-fresh round must leave every bit
  untouched.
* **nonzero trace: mesh == single, bitwise** (``--mesh`` only) — with a
  real delay trace (stale uploads, discounts, dropouts) the 2-device
  client-mesh run must match the single-device run exactly, for the
  configurations whose *synchronous* trajectories are themselves
  mesh-invariant (the plain-aggregation cases; the secure/compressed
  cases differ between single device and mesh already in sync mode —
  per-slot vmap width — so engine-level shard-invariance is only a
  meaningful contract where the sync baseline has it).

Usage (mirrors ``task_bitexact_check.py``)::

    python tests/async_engine_check.py [--mesh]
"""
import sys

from _subprocess import setup_virtual_devices

MESH = "--mesh" in sys.argv

setup_virtual_devices(2 if MESH else 1)

KW = dict(batch_size=10, rounds=6, eval_every=2, eval_samples=300, seed=3)

# the sync cases whose single-device and mesh trajectories are identical —
# engine-level shard-invariance under a nonzero trace is asserted here
MESH_INVARIANT = ("alg1/plain", "fedavg2/plain")


def cases():
    from repro.fed import aggregation, compression, runtime
    return [
        ("alg1/plain", runtime.run_alg1, {}),
        ("alg1/secure", runtime.run_alg1, {"secure": True}),
        ("alg1/sampled4", runtime.run_alg1,
         {"aggregation": aggregation.sampled(4)}),
        ("alg1/qsgd8", runtime.run_alg1,
         {"compressor": compression.qsgd(8)}),
        ("alg1/topk2_8b_secure", runtime.run_alg1,
         {"compressor": compression.topk(0.2, bits=8), "secure": True}),
        ("fedavg2/plain", runtime.run_fedavg,
         {"local_steps": 2, "lr_a": 2.0}),
        ("fedavg2/topk3", runtime.run_fedavg,
         {"local_steps": 2, "lr_a": 2.0,
          "compressor": compression.topk(0.3)}),
    ]


def trajectories(mesh, staleness=None):
    from repro.data import partition, synthetic
    data = synthetic.classification_dataset(n_train=2000, n_test=500, seed=0)
    part = partition.iid(2000, 10, seed=0)
    out = {}
    for name, fn, extra in cases():
        _, h = fn(data, part, mesh=mesh, staleness=staleness, **KW, **extra)
        out[name] = {
            "rounds": list(h.rounds),
            "train_cost": [float.hex(float(c)) for c in h.train_cost],
            "test_accuracy": [float.hex(float(a)) for a in h.test_accuracy],
        }
    return out


def check_zero_trace(mesh, sync, section):
    from repro.fed.staleness import StalenessConfig
    got = trajectories(mesh, StalenessConfig(max_staleness=2))
    for name, r in sync.items():
        g = got[name]
        assert g["rounds"] == r["rounds"], (section, name, "rounds")
        for key in ("train_cost", "test_accuracy"):
            assert g[key] == r[key], (
                f"{section}/{name}: async zero-trace {key} differs from "
                f"the synchronous run\n  got  {g[key]}\n"
                f"  want {r[key]}")
    print(f"zero-trace == sync [{section}]: {len(sync)} cases bitwise")


def check_nonzero_trace_mesh_invariant(mesh, sync):
    from repro.fed.staleness import StalenessConfig
    cfg = StalenessConfig(
        max_staleness=2,
        delay_probs=(0.5, 0.2, 0.15, 0.1, 0.05))   # delays 3, 4 drop
    single = trajectories(None, cfg)
    meshed = trajectories(mesh, cfg)
    for name in MESH_INVARIANT:
        for key in ("train_cost", "test_accuracy"):
            assert single[name][key] == meshed[name][key], (
                f"{name}: async nonzero-trace {key} differs between "
                f"single-device and 2-device mesh\n"
                f"  single {single[name][key]}\n"
                f"  mesh2  {meshed[name][key]}")
    # the trace actually bit (stale slots + dropouts), or the check above
    # is vacuous (alg1/plain's sync trajectory is mesh-invariant)
    assert single["alg1/plain"]["train_cost"] \
        != sync["alg1/plain"]["train_cost"], \
        "nonzero trace left the trajectory on the sync one — dead check"
    print(f"nonzero-trace mesh == single: {len(MESH_INVARIANT)} cases "
          "bitwise")


def main():
    section = "mesh2" if MESH else "single"
    mesh = None
    if MESH:
        from repro.launch.mesh import make_client_mesh
        mesh = make_client_mesh(2)
    sync = trajectories(mesh)
    check_zero_trace(mesh, sync, section)
    if MESH:
        check_nonzero_trace_mesh_invariant(mesh, sync)
    print("ASYNC_CHECK_OK")


if __name__ == "__main__":
    main()
