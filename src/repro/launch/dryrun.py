import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

DOC = """Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
combination against the production meshes, record memory / cost analysis
and the collective schedule for the roofline report.

MUST be run as a module entry point (``python -m repro.launch.dryrun``) —
the XLA_FLAGS line above executes before any jax import, including the
``from repro...`` ones below, because this module is imported first.

Usage:
    python -m repro.launch.dryrun [--arch ID ...] [--shape NAME ...]
        [--mesh single|multi|both] [--out EXPERIMENTS/dryrun]
        [--fsdp-params {1,0}] [--remat {1,0}]

Each combination writes ``<out>/<arch>__<shape>__<mesh>.json``
incrementally, so interrupted sweeps resume for free (--force recomputes).
"""
__doc__ = DOC

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.core import ssca
from repro.launch import hlo_cost, roofline, sharding, specs, steps
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import build_model


def _decode_window_for(cfg, shape):
    if shape.name == "long_500k" and cfg.family in ("dense", "vlm", "moe",
                                                    "audio"):
        return cfg.sliding_window   # sub-quadratic ring-buffer variant
    return 0


def lower_one(arch: str, shape_name: str, *, multi_pod: bool,
              fsdp_params: bool = True, donate: bool = True,
              variant: str = "baseline"):
    """``variant`` selects a §Perf hillclimb configuration:

    * baseline  — 2-D FSDP×TP (the paper-faithful mapping)
    * fsdp      — pure FSDP/ZeRO-3: batch over every mesh axis, no TP
                  (hypothesis: TP activation collectives dominate trains)
    * moe-wtp   — weight-stationary expert TP for decode: expert weights
                  F-sharded over `data`, MoE block computes replicated
                  batch + psum (hypothesis: per-step expert-weight FSDP
                  gathers dominate MoE decode collectives)
    """
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if variant in ("fsdp", "fsdp-bf16s"):
        dp = dp + ("model",)
    ndev = 1
    for a in dp:
        ndev *= mesh.shape[a]
    dp_axes = dp if shape.global_batch % ndev == 0 else None
    if variant == "fsdp-bf16s":
        from repro.models import attention as _attn
        _attn.SCORE_DTYPE = jnp.bfloat16
    if variant == "ctx":
        from repro.models import attention as _attn
        _attn.KV_SEQ_AXIS = "model"
    mfd = "f" if variant == "moe-wtp" else "d"
    if variant == "moe-wtp":
        # decode: non-expert weights are TP-only resident (~1.4 GB/dev for
        # maverick) — no per-token FSDP gathers; experts stay (E@model,
        # F@data) stationary.
        fsdp_params = False
    model = build_model(cfg, decode_window=_decode_window_for(cfg, shape),
                        dp_axes=dp_axes,
                        layer_pspec_fn=sharding.layer_pspec_fn(
                            mesh, fsdp_params=fsdp_params,
                            moe_fsdp_dim=mfd),
                        expert_parallel=(cfg.family == "moe"),
                        act_tp=None if variant in ("fsdp", "fsdp-bf16s")
                        else "model")
    if variant == "moe-wtp":
        model = dataclasses.replace(model, moe_weight_mode="stationary")

    with jax.set_mesh(mesh):
        p_sh = sharding.param_shardings(
            jax.eval_shape(model.init, jax.random.key(0)), mesh,
            fsdp_params=fsdp_params, moe_fsdp_dim=mfd)
        b_sh = sharding.batch_shardings(cfg, shape, mesh, dp_override=dp)
        p_specs = specs.param_specs(model, p_sh)
        batch = specs.input_specs(cfg, shape, b_sh)

        if shape.kind == "train":
            st_abs = jax.eval_shape(lambda p: ssca.init(p, with_beta=False),
                                    p_specs)
            st_sh = sharding.state_shardings(st_abs, p_sh, mesh)
            st_specs = jax.tree.map(
                lambda l, s: None if l is None else
                jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                st_abs, st_sh, is_leaf=lambda x: x is None)
            fn = steps.make_train_step(
                model, microbatches=2 if variant == "mb2" else 1)
            rep = sharding.replicated(mesh)
            metrics_sh = {"loss": rep, "kkt_residual": rep}
            jitted = jax.jit(fn, donate_argnums=(0, 1) if donate else (),
                             out_shardings=(p_sh, st_sh, metrics_sh))
            lowered = jitted.lower(p_specs, st_specs, batch)
        elif shape.kind == "prefill":
            fn = steps.make_prefill_step(model)
            lowered = jax.jit(fn).lower(p_specs, batch)
        else:  # decode
            d_abs = jax.eval_shape(
                lambda: model.init_decode(shape.global_batch, shape.seq_len))
            d_sh = sharding.decode_state_shardings(cfg, shape, mesh, d_abs)
            d_specs = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                  sharding=s),
                d_abs, d_sh)
            fn = steps.make_decode_step(model)
            jitted = jax.jit(fn, donate_argnums=(1,) if donate else ())
            lowered = jitted.lower(p_specs, d_specs, batch)
    return cfg, shape, mesh, lowered


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            fsdp_params: bool = True, variant: str = "baseline") -> dict:
    t0 = time.time()
    cfg, shape, mesh, lowered = lower_one(
        arch, shape_name, multi_pod=multi_pod, fsdp_params=fsdp_params,
        variant=variant)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    n_chips = int(np.prod(list(mesh.shape.values())))
    costs = hlo_cost.analyze(hlo)          # trip-count-aware per-device
    terms = roofline.roofline_terms(costs.flops, costs.bytes,
                                    costs.collective_bytes, n_chips)
    mf = roofline.model_flops(cfg, shape)
    useful = roofline.useful_fraction(cfg, shape,
                                      terms["hlo_flops_per_chip"], n_chips)

    per_dev_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    record = {
        "arch": arch,
        "shape": shape_name,
        "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
        "params_b": cfg.param_count() / 1e9,
        "active_params_b": cfg.active_param_count() / 1e9,
        "seconds_lower": round(t_lower, 1),
        "seconds_compile": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "per_device_total_bytes": per_dev_bytes,
            "per_device_total_gib": round(per_dev_bytes / 2**30, 3),
        },
        "roofline": terms,
        "xla_cost_analysis": {"flops": float(xla_cost.get("flops", 0.0)),
                              "bytes accessed":
                              float(xla_cost.get("bytes accessed", 0.0))},
        "model_flops_global": mf,
        "useful_flop_fraction": useful,
    }
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=list(ARCH_IDS))
    ap.add_argument("--shape", nargs="*", default=list(INPUT_SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--out", default="EXPERIMENTS/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--fsdp-params", type=int, default=1)
    ap.add_argument("--variant", default="baseline",
                    choices=("baseline", "fsdp", "moe-wtp", "fsdp-bf16s",
                             "ctx", "mb2"))
    args = ap.parse_args()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch in args.arch:
        for shape in args.shape:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                suffix = "" if args.variant == "baseline" \
                    else f"__{args.variant}"
                path = out / f"{arch}__{shape}__{mesh_name}{suffix}.json"
                if path.exists() and not args.force:
                    print(f"skip {path.name} (exists)")
                    continue
                print(f"=== {arch} × {shape} × {mesh_name} ...", flush=True)
                try:
                    rec = run_one(arch, shape, multi_pod=mp,
                                  fsdp_params=bool(args.fsdp_params),
                                  variant=args.variant)
                    path.write_text(json.dumps(rec, indent=1))
                    r = rec["roofline"]
                    print(f"    ok: {rec['memory']['per_device_total_gib']}"
                          f" GiB/dev, dominant={r['dominant']}, "
                          f"t=({roofline.fmt_seconds(r['t_compute_s'])},"
                          f"{roofline.fmt_seconds(r['t_memory_s'])},"
                          f"{roofline.fmt_seconds(r['t_collective_s'])}), "
                          f"compile={rec['seconds_compile']}s", flush=True)
                except Exception as e:  # noqa: BLE001 — record and continue
                    failures.append((arch, shape, mesh_name, repr(e)))
                    print(f"    FAIL: {e}")
                    traceback.print_exc(limit=4)
    if failures:
        print("\nFAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall requested combinations lowered + compiled OK")


if __name__ == "__main__":
    main()
