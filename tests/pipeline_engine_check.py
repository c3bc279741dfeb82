"""Pipelined round-mode bit-identity harness.

One contract: ``pipeline=True`` IS the async bounded-staleness mode at
the constant τ≡1 trace, executed overlapped — for every pinned
configuration, the pipelined run must reproduce the async run with
``StalenessConfig(max_staleness=1, schedule=ConstantDiscount())`` and an
all-ones ``staleness_trace`` **bit-for-bit**: final params
``np.array_equal`` per leaf and metric trajectories ``float.hex()``-
exact.  The A/B is self-contained (both sides run here), so no
reference file is needed — the async side is itself pinned against the
synchronous reference by ``tests/async_engine_check.py``.

Covered paths: the linear super-batch fast path (plain), the masked
int32 secure combine, compressed+secure (top-k), the two-phase sketched
wire, mean-combine (FedAvg E=2), and the hierarchical two-level tree.
``--mesh`` reruns the flat cases on a 2-device client mesh (where the
consume's chunked ppermute ring replaces the flat psum), the
hierarchical case on a (2, 1) group mesh, and adds a replicated-arena
variant (the sharded arena is the mesh default).

Usage (mirrors ``async_engine_check.py``)::

    python tests/pipeline_engine_check.py [--mesh]
"""
import sys

import numpy as np

from _subprocess import setup_virtual_devices

MESH = "--mesh" in sys.argv

setup_virtual_devices(2 if MESH else 1)

KW = dict(batch_size=10, rounds=6, eval_every=2, eval_samples=300, seed=3)


def cases():
    from repro.fed import aggregation, compression, runtime
    from repro.fed import sketch as sketch_mod
    base = [
        ("alg1/plain", runtime.run_alg1, {}),
        ("alg1/secure", runtime.run_alg1, {"secure": True}),
        ("alg1/topk2_8b_secure", runtime.run_alg1,
         {"compressor": compression.topk(0.2, bits=8), "secure": True}),
        ("alg1/sketch_secure", runtime.run_alg1,
         {"compressor": sketch_mod.sketch(), "secure": True}),
        ("fedavg2/plain", runtime.run_fedavg,
         {"local_steps": 2, "lr_a": 2.0}),
        ("alg1/hier2", runtime.run_alg1,
         {"aggregation": aggregation.hierarchical(groups=2)}),
    ]
    if MESH:
        base.append(
            ("alg1/topk_secure_repl", runtime.run_alg1,
             {"compressor": compression.topk(0.2, bits=8),
              "secure": True, "arena": "replicated"}))
        # S=5 on 2 shards: the cohort is sentinel-padded to 6 — the ring
        # must sum the padded shards' masked partials bit-exactly too
        base.append(
            ("alg1/secure_s5", runtime.run_alg1,
             {"aggregation": aggregation.secure(num_sampled=5)}))
    return base


def run_pair(name, fn, extra):
    import jax
    from repro.fed.staleness import ConstantDiscount, StalenessConfig
    mesh = None
    if MESH:
        from repro.launch.mesh import make_client_mesh, make_group_mesh
        mesh = make_group_mesh(2) if "hier" in name else make_client_mesh(2)
    tau1 = StalenessConfig(max_staleness=1, schedule=ConstantDiscount())
    s = getattr(extra.get("aggregation"), "num_sampled", None) or 10
    trace = np.ones((KW["rounds"], s), np.int64)
    p_a, h_a = fn(*DATA, mesh=mesh, staleness=tau1, staleness_trace=trace,
                  **KW, **extra)
    p_p, h_p = fn(*DATA, mesh=mesh, pipeline=True, **KW, **extra)
    la, lp = jax.tree.leaves(p_a), jax.tree.leaves(p_p)
    for i, (a, b) in enumerate(zip(la, lp)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.array_equal(a, b), (
            f"{name}: pipelined params leaf {i} differ from the async "
            f"τ≡1 run ({int((a != b).sum())}/{a.size} elements)")
    assert list(h_a.rounds) == list(h_p.rounds), (name, "rounds")
    for key in sorted(h_a.metrics):
        ta = [float.hex(float(v)) for v in h_a.metric(key)]
        tp = [float.hex(float(v)) for v in h_p.metric(key)]
        assert ta == tp, (
            f"{name}: pipelined {key} trajectory drifted from the async "
            f"τ≡1 run\n  async {ta}\n  pipe  {tp}")
    assert h_p.comm["pipeline"]["extra_snapshot_slots"] == 1, name
    print(f"pipeline == async τ≡1 [{name}]: params + trajectories bitwise")


def check_ring_psum():
    """``ring_psum_chunked`` == flat ``lax.psum`` **bitwise** on a mixed
    int32/float32/uint32 tree whose flattened int length (37·13 + 3) is
    not divisible by the chunk count — exercising the uneven chunk
    bounds alongside the dtype dispatch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.kernels import ops as kops
    from repro.launch import mesh as mesh_mod
    mesh = mesh_mod.make_client_mesh(2)
    rng = np.random.default_rng(0)
    tree = {
        "a": jnp.asarray(rng.integers(-2**31, 2**31 - 1, size=(2, 37, 13),
                                      dtype=np.int64), jnp.int32),
        "b": jnp.asarray(rng.standard_normal((2, 5)), jnp.float32),
        "c": jnp.asarray(rng.integers(0, 2**32, size=(2, 3),
                                      dtype=np.uint64), jnp.uint32),
        "d": jnp.asarray(rng.integers(-100, 100, size=(2, 3),
                                      dtype=np.int64), jnp.int32),
    }
    outs = {}
    for name, fn in (
            ("ring", lambda t: kops.ring_psum_chunked(
                t, "clients", num_shards=2, chunks=4)),
            ("flat", lambda t: jax.tree.map(
                lambda v: jax.lax.psum(v, "clients"), t))):
        outs[name] = jax.device_get(jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P("clients"),),
            out_specs=P("clients"), check_vma=False))(tree))
    for k in tree:
        assert np.array_equal(outs["ring"][k], outs["flat"][k]), (
            f"ring psum leaf {k} ({tree[k].dtype}) != flat psum")
    print("ring_psum_chunked == lax.psum: bitwise on all dtypes")


def check_staleness_conflict():
    from repro.fed import runtime
    from repro.fed.staleness import ConstantDiscount, StalenessConfig
    tau1 = StalenessConfig(max_staleness=1, schedule=ConstantDiscount())
    try:
        runtime.run_alg1(*DATA, pipeline=True, staleness=tau1, **KW)
    except ValueError as e:
        assert "pipeline=True IS the constant tau=1" in str(e), e
        print("pipeline + staleness= rejected with the expected error")
        return
    raise AssertionError("pipeline=True composed with staleness= — "
                         "expected a ValueError")


def main():
    global DATA
    from repro.data import partition, synthetic
    DATA = (synthetic.classification_dataset(n_train=2000, n_test=500,
                                             seed=0),
            partition.iid(2000, 10, seed=0))
    for name, fn, extra in cases():
        run_pair(name, fn, extra)
    if MESH:
        check_ring_psum()
    else:
        check_staleness_conflict()
    print("PIPELINE_CHECK_OK")


if __name__ == "__main__":
    main()
