"""Subprocess body for test_distributed: verifies the pjit-sharded SSCA
round on a (2, 4) mesh is numerically identical to the single-device
round (same params/state after 3 steps), proving the sharding rules and
activation constraints change the schedule, not the math.

Run directly:  python tests/distributed_check.py
"""
from _subprocess import setup_virtual_devices

setup_virtual_devices(8)

import dataclasses

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import reduced
from repro.core import ssca
from repro.launch import sharding, steps
from repro.launch.mesh import make_mesh
from repro.models import build_model


def main():
    cfg = dataclasses.replace(reduced(get_config("llama3-8b")),
                              vocab_size=512)
    mesh = make_mesh((2, 4), ("data", "model"))

    batch = {"tokens": jax.random.randint(jax.random.key(7), (4, 32), 0,
                                          cfg.vocab_size)}
    hp = ssca.SSCAHyperParams(tau=1.0)

    # single-device reference
    model_ref = build_model(cfg)
    params = model_ref.init(jax.random.key(0))
    step_ref = jax.jit(steps.make_train_step(model_ref, hp))
    p_ref, st_ref = params, ssca.init(params, with_beta=False)
    for _ in range(3):
        p_ref, st_ref, m_ref = step_ref(p_ref, st_ref, batch)

    # sharded
    model_sh = build_model(cfg, dp_axes=("data",),
                           layer_pspec_fn=sharding.layer_pspec_fn(mesh))
    with jax.set_mesh(mesh):
        p_shd = sharding.param_shardings(
            jax.eval_shape(model_sh.init, jax.random.key(0)), mesh)
        p = jax.device_put(params, p_shd)
        st = ssca.init(p, with_beta=False)
        b_sh = {"tokens": jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(("data",), None))}
        b = jax.device_put(batch, b_sh)
        step_sh = jax.jit(steps.make_train_step(model_sh, hp))
        for _ in range(3):
            p, st, m = step_sh(p, st, b)

    ref_leaves = jax.tree.leaves(p_ref)
    sh_leaves = jax.tree.leaves(jax.device_get(p))
    worst = 0.0
    for a, b_ in zip(ref_leaves, sh_leaves):
        scale = float(np.abs(np.asarray(a)).max()) + 1e-9
        worst = max(worst, float(np.abs(np.asarray(a) -
                                        np.asarray(b_)).max()) / scale)
    loss_diff = abs(float(m_ref["loss"]) - float(m["loss"]))
    print(f"worst rel param diff: {worst:.2e}  loss diff: {loss_diff:.2e}")
    assert worst < 5e-3, worst
    assert loss_diff < 5e-3, loss_diff

    # --- MoE: shard_map expert-parallel forward == pjit dense-dispatch ---
    cfg_m = dataclasses.replace(reduced(get_config("qwen3-moe-235b-a22b")),
                                vocab_size=512)
    batch_m = {"tokens": jax.random.randint(jax.random.key(9), (4, 16), 0,
                                            cfg_m.vocab_size)}
    model_m1 = build_model(cfg_m)                       # moe_ffn path
    params_m = model_m1.init(jax.random.key(1))
    logits_ref = model_m1.forward(params_m, batch_m)
    model_m2 = build_model(cfg_m, dp_axes=("data",),
                           layer_pspec_fn=sharding.layer_pspec_fn(mesh),
                           expert_parallel=True)
    with jax.set_mesh(mesh):
        p_shd = sharding.param_shardings(
            jax.eval_shape(model_m2.init, jax.random.key(1)), mesh)
        pm = jax.device_put(params_m, p_shd)
        bm = jax.device_put(batch_m, {"tokens": jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(("data",), None))})
        logits_sh = jax.jit(model_m2.forward)(pm, bm)
    err = float(np.max(np.abs(np.asarray(logits_sh) -
                              np.asarray(logits_ref))))
    scale = float(np.abs(np.asarray(logits_ref)).max()) + 1e-9
    print(f"moe expert-parallel vs dense-dispatch rel err: {err/scale:.2e}")
    assert err / scale < 2e-2, err / scale
    print("DISTRIBUTED_CHECK_OK")


if __name__ == "__main__":
    main()
