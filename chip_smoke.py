"""Smoke run of the federated SSCA engine on TPU.

    python chip_smoke.py             # one chip: five training phases and
                                     # the kernel-vs-XLA bit-equality checks
    python chip_smoke.py --chips 4   # four chips: client mesh and group
                                     # mesh, each against one device

Drives the paper's Section-V model at its own width (784 -> 128 swish ->
10, N = 60 000 synthetic MNIST-like samples over I = 10 clients, see
``repro/configs/mlp_mnist.py``) through ``repro.fed.runtime.run_alg1`` /
``run_alg2`` for 20 rounds per phase:

  (a) Algorithm 1, plain aggregation, unfused server step;
  (b) Algorithm 1, secure aggregation, fused server step (the secure_agg
      and ssca_update kernels) -- within the plain trajectory's tolerance;
  (c) secure + qsgd(8) uploads (the compress kernel);
  (d) sketched secure uploads (the sketch kernel);
  (e) Algorithm 2.

Every phase checks that the train cost is finite and falls.  Then the
three upload kernels are run with ``use_kernel=True`` and ``False`` on the
same inputs, and their outputs must be bit-equal.

Everything runs in this one process.  With no TPU the script exits
non-zero before any work and prints no result.  The last line of a
successful run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

N_TRAIN, N_TEST, CLIENTS = 60000, 10000, 10
HIDDEN = 128
ROUNDS, EVAL_EVERY, BATCH = 20, 10, 100
SCALE_BITS = 20                     # aggregation.secure()'s default grid
SKETCH = dict(rows=4, cols=512, fraction=0.015, keep=64)
# secure vs plain Algorithm 1: the tolerance tests/test_secure_agg.py
# holds run_alg1(secure=True) to against run_alg1()
PARAM_ATOL, COST_ATOL = 5e-4, 1e-3


def _phase(name, fn):
    t0 = time.perf_counter()
    out = fn()
    print(f"phase {name}: {time.perf_counter() - t0:.2f} s "
          "(compile included)", flush=True)
    return out


def _check_cost(name, h):
    cost = np.asarray(h.train_cost, np.float64)
    if not (np.isfinite(cost).all() and cost[-1] < cost[0]):
        raise RuntimeError(f"{name}: train cost not finite and falling: "
                           f"{cost.tolist()} at rounds {h.rounds}")
    print(f"  {name}: train cost {cost.tolist()} at rounds {h.rounds}, "
          f"test accuracy {h.test_accuracy[-1]:.4f}", flush=True)


def _max_diff(p, q):
    return max(float(np.max(np.abs(np.asarray(a, np.float64)
                                   - np.asarray(b, np.float64))))
               for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)))


def _data():
    from repro.data import partition, synthetic
    data = synthetic.classification_dataset(n_train=N_TRAIN, n_test=N_TEST,
                                            seed=0)
    return data, partition.iid(N_TRAIN, CLIENTS, seed=0)


def _common():
    return dict(batch_size=BATCH, rounds=ROUNDS, eval_every=EVAL_EVERY,
                eval_samples=N_TEST, hidden=HIDDEN, seed=0)


def training_phases(data, part):
    from repro.fed import aggregation, compression, runtime, sketch

    kw = _common()
    p_a, h_a = _phase("a alg1 plain", lambda: runtime.run_alg1(
        data, part, **kw))
    _check_cost("a", h_a)

    p_b, h_b = _phase("b alg1 secure fused", lambda: runtime.run_alg1(
        data, part, secure=True, fused=True, **kw))
    _check_cost("b", h_b)
    dp = _max_diff(p_a, p_b)
    dc = abs(h_a.train_cost[-1] - h_b.train_cost[-1])
    print(f"  b vs a: max |param diff| {dp:.3e} (limit {PARAM_ATOL}), "
          f"|final cost diff| {dc:.3e} (limit {COST_ATOL})", flush=True)
    if not (dp <= PARAM_ATOL and dc <= COST_ATOL):
        raise RuntimeError("secure run left the plain trajectory")

    _, h_c = _phase("c alg1 secure qsgd8", lambda: runtime.run_alg1(
        data, part, secure=True, compressor=compression.qsgd(8), **kw))
    _check_cost("c", h_c)

    _, h_d = _phase("d alg1 secure sketch", lambda: runtime.run_alg1(
        data, part, aggregation=aggregation.secure(),
        compressor=sketch.sketch(**SKETCH), **kw))
    _check_cost("d", h_d)

    _, h_e = _phase("e alg2", lambda: runtime.run_alg2(data, part, **kw))
    _check_cost("e", h_e)


def _bit_equal(name, a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or not np.array_equal(
                x.view(np.uint32), y.view(np.uint32)):
            raise RuntimeError(f"{name}: outputs differ")
    print(f"  {name}: bitwise equal", flush=True)


def kernel_vs_xla():
    """The three upload kernels against their XLA paths on one input."""
    from repro.kernels import compress, ops
    from repro.kernels import sketch as ksk
    from repro.mlpapp.model import init_params

    key = jax.random.key(7)
    params = init_params(key, 784, HIDDEN, 10)
    msgs = jax.tree.map(
        lambda p: 0.05 * jax.random.normal(key, (CLIENTS,) + p.shape),
        params)
    kd = jax.random.key_data(jax.random.key(11))
    alive = (np.arange(CLIENTS) % 4 != 1).astype(np.int32)
    for with_alive in (False, True):
        kw = dict(scale_bits=SCALE_BITS,
                  alive=alive if with_alive else None)
        _bit_equal(f"secure_quant_sum kernel vs XLA, alive={with_alive}",
                   ops.secure_quant_sum(msgs, kd, use_kernel=True, **kw),
                   ops.secure_quant_sum(msgs, kd, use_kernel=False, **kw))

    n = sum(p.size for p in jax.tree.leaves(params))
    x = 0.05 * jax.random.normal(key, ((n + 127) // 128, 128))
    su = np.asarray([0x1234567, 0], np.uint32)
    sf = np.asarray([0.02, 2.0 ** -10], np.float32)
    for quantize, masked in ((True, False), (False, True), (True, True)):
        kw = dict(lbound=127, quantize=quantize, masked=masked)
        _bit_equal(f"compress_2d kernel vs XLA, quantize={quantize} "
                   f"masked={masked}",
                   compress.compress_2d(x, su, sf, use_kernel=True, **kw),
                   compress.compress_2d(x, su, sf, use_kernel=False, **kw))

    su3 = np.asarray([0x1234567, 0, 0xBEEF], np.uint32)
    kw = dict(rows=SKETCH["rows"], cols=SKETCH["cols"],
              scale_bits=SCALE_BITS)
    _bit_equal("sketch_encode kernel vs XLA",
               ksk.sketch_encode(x, su3, use_kernel=True, **kw),
               ksk.sketch_encode(x, su3, use_kernel=False, **kw))


def sharded_secure_sum():
    """The int32 masked sum of 12 clients: the psum of four chips' partial
    sums (3 clients each, directed mask streams) against one device's."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops
    from repro.launch.mesh import make_client_mesh

    msgs = 0.05 * jax.random.normal(jax.random.key(3), (12, 1000, 128))
    kd = jax.random.key_data(jax.random.key(5))

    def shard(m):
        off = jax.lax.axis_index("clients") * m.shape[0]
        part = ops.secure_quant_sum(m, kd, scale_bits=SCALE_BITS,
                                    client_offset=off, num_clients=12)
        return jax.lax.psum(part, "clients")

    mesh = make_client_mesh(4)
    got = jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=P("clients"),
                                out_specs=P(), check_vma=False))(msgs)
    want = ops.secure_quant_sum(msgs, kd, scale_bits=SCALE_BITS)
    _bit_equal("secure int32 sum, 4-chip psum vs 1 device", got, want)


def mesh_phases(data, part):
    """Four chips: the client mesh and the group mesh, each against the
    same configuration on one device."""
    from repro.fed import aggregation, runtime
    from repro.launch.mesh import make_client_mesh, make_group_mesh

    _phase("mesh secure sum client mesh 4", sharded_secure_sum)
    kw = _common()
    p_one, _ = _phase("mesh ref secure 1 device", lambda: runtime.run_alg1(
        data, part, secure=True, **kw))
    p_mesh, _ = _phase("mesh secure client mesh 4", lambda: runtime.run_alg1(
        data, part, secure=True, mesh=make_client_mesh(4), **kw))
    d_client = _max_diff(p_one, p_mesh)
    print(f"  client mesh (4) vs 1 device: max |param diff| {d_client:.3e}",
          flush=True)

    hier = aggregation.hierarchical(aggregation.secure(num_sampled=8),
                                    groups=4)
    p_one, _ = _phase("mesh ref hierarchical 1 device",
                      lambda: runtime.run_alg1(data, part, aggregation=hier,
                                               **kw))
    p_mesh, _ = _phase("mesh hierarchical group mesh 2x2",
                       lambda: runtime.run_alg1(
                           data, part, aggregation=hier,
                           mesh=make_group_mesh(2, 2), **kw))
    d_group = _max_diff(p_one, p_mesh)
    print(f"  group mesh (2x2) vs 1 device: max |param diff| {d_group:.3e}",
          flush=True)
    for name, d in (("client mesh", d_client), ("group mesh", d_group)):
        if not d <= PARAM_ATOL:
            raise RuntimeError(f"{name} left the one-device trajectory")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh comparisons, on four chips")
    args = ap.parse_args(argv)

    dev = jax.devices()
    if dev[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev[0].platform}")
    if len(dev) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"chips, JAX found {len(dev)}")

    from repro.launch.compile_cache import enable_compile_cache
    print(f"jax {jax.__version__}, device {dev[0].device_kind} x{len(dev)}, "
          f"compile cache {enable_compile_cache()}", flush=True)

    data, part = _phase("data", _data)
    if args.chips == 4:
        mesh_phases(data, part)
    else:
        training_phases(data, part)
        _phase("kernel vs xla", kernel_vs_xla)
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))


if __name__ == "__main__":
    main()
