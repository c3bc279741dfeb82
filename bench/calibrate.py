"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--program] [--control] [--faults half_batch,no_exchange]

For every seed, in one process: ``--program`` reads the compared numbers
of the program's first call against the reference (the lower reading);
``--control`` reads them for the reference computed with float8 operands
in the program's place; each fault reads them for the reference with
that fault planted (see ``reference.rounds``).  One JSON line per
seed.  The benchmark's own runs never run this; it needs the cell's
chips, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))

import run  # noqa: E402


def readings(ctx, seed, devices, *, program, control, faults):
    import time

    import jax

    import judge

    t0 = time.perf_counter()
    data, params0, part_arrays, call = run.program(ctx, seed, devices)
    ref_params, g1, ref_costs = run.replay(ctx, seed, data, params0,
                                           part_arrays)
    out = {"seed": seed}

    def score(params, costs):
        return judge.numbers(params0, params, costs, ref_params, ref_costs, g1)

    if program:
        params, hist = call(0)
        jax.block_until_ready(params)
        out["program"] = score(params, dict(zip(hist.rounds,
                                                hist.train_cost)))
        del params
    if control:
        params, _, costs = run.replay(ctx, seed, data, params0, part_arrays,
                                      mode="fp8")
        out["control"] = score(params, costs)
    for f in faults:
        params, _, costs = run.replay(ctx, seed, data, params0, part_arrays,
                                      fault=f)
        out[f] = score(params, costs)
    out["seconds"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    ctx = run.load_cell(args.workload)
    run.setup_jax()
    try:
        devices = run.require_chips(ctx["cell"]["chips"])
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    faults = [f for f in args.faults.split(",") if f]
    for s in args.seeds.split(","):
        print(json.dumps(readings(ctx, int(s), devices, program=args.program,
                                  control=args.control, faults=faults)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
