"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one model configuration (``bench/configs/<config>.json`` with
its builder and plain reference ``bench/configs/<config>.py``) under one
traffic mix (``bench/traffic/<traffic>.json``), held to the limits in
``bench/limits/<cell>.json``.  The run

1. makes the data and the initial weights on the device from ``--seed``;
2. warms up with one call of ``repro.fed.runtime.run_alg1`` at the
   cell's shapes (compiled here, or loaded from the persistent cache in
   ``<checkout>/.jax_cache``); set-up ends when it returns;
3. times back-to-back calls of ``rounds_per_call`` rounds each, every
   call on its own sampling seed, until ``--seconds`` have passed:
   ``round_ms`` is the window's wall time over the rounds it ran;
4. with ``--trace 1``, also traces a segment of further calls and reads
   the per-layer metrics (``bench/metrics/<metric>.py``) instead;
5. replays the first timed call with the plain float32 reference and
   compares (``judge.py``).

The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with status 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 5.0


class NoChip(RuntimeError):
    """No accelerator of the kind the cell needs."""


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """Everything the harness reads for cell ``name``, found by name: the
    configuration's file, its module ``bench/configs/<config>.py``, the
    traffic ``bench/traffic/<traffic>.json``, the replay
    ``bench/algorithms/<name>.py`` of the traffic's algorithm, the limits
    ``bench/limits/<cell>.json`` (None where there are none yet: such a
    cell cannot be judged) and the cell's metrics."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}: {sorted(cells)}")
    cell = cells[name]
    bench = root / "bench"
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    traffic = json.loads(
        (bench / "traffic" / f"{cell['traffic']}.json").read_text())
    replay_mod = load_module(bench / "algorithms"
                             / f"{traffic['algorithm']['name']}.py")
    replay_mod.replayable(traffic)
    limits = bench / "limits" / f"{name}.json"
    return {
        "cell": cell,
        "config": json.loads((root / config["file"]).read_text()),
        "module": load_module(bench / "configs" / f"{cell['config']}.py"),
        "traffic": traffic,
        "replay": replay_mod,
        "limits": json.loads(limits.read_text()) if limits.exists() else None,
        "end_to_end": spec["end_to_end"],
        "per_layer": [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def setup_jax():
    """Point JAX's persistent cache at the checkout (unless the
    environment names one) and let it keep every program."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def require_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def load_peaks(kind: str, path: pathlib.Path = BENCH / "peaks.json") -> dict:
    table = json.loads(path.read_text())["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in {path.name}")
    return table[kind]


class CompileCounter:
    """Counts compilations and persistent-cache loads while ``on``."""

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, _secs, **_kw):
        if self.on and "backend_compile" in name:
            self.count += 1

    def _event(self, name, **_kw):
        if self.on and name.endswith("cache_hits"):
            self.count += 1


def call_seed(seed: int, k: int) -> int:
    """The program's sampling seed of call ``k`` (the warm-up is -1):
    distinct per call and below 2**31, whatever ``--seed`` is."""
    return (seed + 7919 * (k + 1)) % (2 ** 31)


def partition(traffic: dict, seed: int):
    """IID split of I·n samples, n per client, from the seed."""
    import numpy as np

    i, n = traffic["clients"], traffic["samples_per_client"]
    flat = np.random.default_rng(seed).permutation(i * n).astype(np.int64)
    sizes = np.full(i, n, np.int64)
    offsets = np.arange(i, dtype=np.int64) * n
    return flat, offsets, sizes


# Program objects a traffic mix names as {"kind": <factory>, **args}: built
# as <module>.<factory>(**args), a nested {"kind": ...} argument first.
FACTORIES = {"aggregation": "repro.fed.aggregation",
             "compressor": "repro.fed.compression",
             "staleness": "repro.fed.staleness",
             "mesh": "repro.launch.mesh"}


def build(key: str, spec):
    import importlib

    if not (isinstance(spec, dict) and "kind" in spec):
        return spec
    args = {k: build(key, v) for k, v in spec.items() if k != "kind"}
    return getattr(importlib.import_module(FACTORIES[key]),
                   spec["kind"])(**args)


def program(ctx: dict, seed: int, devices):
    """The system under test: data, weights and one call of the traffic's
    algorithm entry ``repro.fed.runtime.run_<name>``, with the mix's
    algorithm arguments, program objects and plain engine options
    (``engine``).  A cell on several chips runs on
    ``make_client_mesh(chips)`` unless the mix names a mesh."""
    import jax
    from repro.data.partition import Partition
    from repro.fed import runtime

    cfg, traffic, mod = ctx["config"], ctx["traffic"], ctx["module"]
    with jax.default_device(devices[0]):
        data, params0 = mod.make(cfg, traffic, seed)
    part_arrays = partition(traffic, seed)
    part = Partition(*part_arrays)
    alg = dict(traffic["algorithm"])
    entry = getattr(runtime, f"run_{alg.pop('name')}")
    objects = {k: build(k, traffic[k]) for k in FACTORIES if k in traffic}
    if "mesh" not in objects and len(devices) > 1:
        objects["mesh"] = build("mesh", {"kind": "make_client_mesh",
                                         "num_shards": len(devices)})
    kwargs = dict(alg, **objects, **traffic.get("engine", {}),
                  task=mod.task(cfg, traffic), params=params0,
                  batch_size=traffic["batch_size"],
                  rounds=traffic["rounds_per_call"],
                  eval_every=traffic["eval_every"],
                  eval_samples=traffic["eval_samples"])

    def call(k: int):
        return entry(data, part, seed=call_seed(seed, k), **kwargs)

    return data, params0, part_arrays, call


def timed(call, seconds: float, first: int, annotate: bool = False):
    """Back-to-back calls until ``seconds`` have passed.  Returns the
    elapsed wall time, [(caller seconds, engine loop seconds)] per call,
    and the first call's output."""
    import jax

    out, per_call, k = None, [], first
    t0 = time.perf_counter()
    while True:
        c0 = time.perf_counter()
        with (jax.profiler.TraceAnnotation("bench.call") if annotate
              else contextlib.nullcontext()):
            params, hist = call(k)
            jax.block_until_ready(params)
        c1 = time.perf_counter()
        per_call.append((c1 - c0, hist.wall_seconds))
        if out is None:
            out = (params, hist)
        del params
        k += 1
        if c1 - t0 >= seconds:
            return c1 - t0, per_call, out


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def traced_segment(call, seconds: float, first: int, devices, log_dir):
    """Trace further calls; return the flattened trace and its window."""
    import jax

    import devtrace

    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            window_s, per_call, _ = timed(call, seconds, first,
                                          annotate=True)
    finally:
        jax.profiler.stop_trace()
    tr = devtrace.load(log_dir)
    shutil.rmtree(log_dir, ignore_errors=True)
    return tr, window_s, per_call


def replay(ctx: dict, seed: int, data, params0, part_arrays, *,
           mode: str = "f32", fault: str | None = None):
    """The reference's replay of the first timed call: its final params,
    its first-round gradient and its eval costs by round."""
    import jax

    import reference

    traffic = ctx["traffic"]
    eval_ids = reference.eval_subset(
        traffic["clients"] * traffic["samples_per_client"],
        traffic["eval_samples"])
    with jax.default_matmul_precision("highest"):
        ref = ctx["module"].Reference(ctx["config"], traffic, data, eval_ids,
                                      reference.Dot(mode))
        return ctx["replay"].replay(traffic, params0, ref.grad_sum, ref.cost,
                                    part_arrays, call_seed(seed, 0),
                                    fault=fault)


def check(ctx: dict, seed: int, data, params0, part_arrays, first_out):
    """Replay the first timed call with the reference and judge it."""
    import judge

    prog_params, hist = first_out
    ref_params, g1, ref_costs = replay(ctx, seed, data, params0,
                                       part_arrays)
    values = judge.numbers(params0, prog_params,
                           dict(zip(hist.rounds, hist.train_cost)),
                           ref_params, ref_costs, g1)
    return judge.verdict(values, ctx["limits"])


def per_layer(ctx: dict, mctx: dict) -> dict:
    out = {}
    for m in ctx["per_layer"]:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(mctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def breakdown(tr: dict, devices) -> dict:
    import devtrace

    ids = [d.id for d in devices]
    ops = [e for i in ids for e in tr["devices"].get(i, [])]
    top = [[n, s / len(ids)] for n, s in devtrace.top_ops(ops)]
    win = [e for e in tr["host"] if e[2] == "bench.window"]
    if not win or not tr["devices"].get(ids[0]):
        return {"device_ops": top, "idle_gaps": []}
    a, b = win[0][0], win[0][0] + win[0][1]
    gaps = devtrace.idle_gaps(tr["devices"][ids[0]], a, b)[:10]
    return {"device_ops": top,
            "idle_gaps": [[devtrace.label(g, tr["host"]), (g[1] - g[0]) * 1e-9]
                          for g in gaps]}


def run_cell(ctx: dict, seed: int, seconds: float, trace: bool, devices,
             peaks: dict, t_start: float, log=sys.stdout):
    """One run of a cell on ``devices``; returns the result object."""
    import jax

    counter = CompileCounter()
    data, params0, part_arrays, call = program(ctx, seed, devices)
    params, _ = call(-1)                                  # warm-up
    jax.block_until_ready(params)
    del params
    setup_s = time.time() - t_start
    counter.on = True
    elapsed, per_call, first_out = timed(call, seconds, 0)
    counter.on = False
    rounds = len(per_call) * ctx["traffic"]["rounds_per_call"]
    print(f"window: {len(per_call)} calls, {rounds} rounds in "
          f"{elapsed:.6f} s; compilations in window: {counter.count}",
          file=log, flush=True)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    result = {"correct": False, "attempted": rounds, "failed": 0}
    if trace:
        import devtrace

        tr, window_s, traced_calls = traced_segment(
            call, min(seconds, TRACE_SECONDS), len(per_call), devices,
            TRACE_DIR / ctx["cell"]["name"])
        ids = [d.id for d in devices]
        busy = sum(devtrace.busy_ns(tr["devices"].get(i, []))
                   for i in ids) / len(ids) * 1e-9
        mctx = {"trace": tr, "device_ids": ids, "window_s": window_s,
                "trace_rounds": len(traced_calls)
                * ctx["traffic"]["rounds_per_call"],
                "calls": per_call, "elapsed_s": elapsed, "rounds": rounds,
                "flops_per_round": ctx["module"].model_flops_per_round(
                    ctx["config"], ctx["traffic"]),
                "peaks": peaks, "chips": len(devices),
                "traffic": ctx["traffic"], "config": ctx["config"],
                "n_params": sum(int(x.size)
                                for x in jax.tree.leaves(params0))}
        result["metrics"] = per_layer(ctx, mctx)
        device.update(busy_s=busy, window_s=window_s)
        result["breakdown"] = breakdown(tr, devices)
    else:
        result["metrics"] = {
            "round_ms": {"value": elapsed / rounds * 1e3, "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    device["memory_peak_bytes"] = memory_peak(devices)
    result["device"] = device
    correct, checks = check(ctx, seed, data, params0, part_arrays, first_out)
    result["correct"] = correct
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    ctx = load_cell(args.workload)
    jax = setup_jax()
    try:
        devices = require_chips(ctx["cell"]["chips"])
        peaks = load_peaks(devices[0].device_kind)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"device: {devices[0].platform} {devices[0].device_kind!r} x "
          f"{len(devices)}; jax {jax.__version__}", flush=True)
    result = run_cell(ctx, args.seed, args.seconds, bool(args.trace),
                      devices, peaks, T_START)
    print(f"correct: {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
