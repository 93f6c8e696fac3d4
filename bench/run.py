"""Run one benchmark cell once on the chip it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (`bench/configs/<config>.json`) and its traffic
(`bench/traffic/<traffic>.json`, driven by `bench/traffic/<kind>.py`) are
found by name from `BENCHMARK.json`. With `--trace 0` the result line carries
the cell's end-to-end metrics; with `--trace 1` its per-layer metrics, read
from a profiler trace of the window, and a breakdown of device time and idle
gaps. Each metric is computed by `bench/metrics/<name>.py`. Diagnostics come
first; the compared numbers and their limits are the last lines on standard
error; the last line on standard output is the result as one JSON object.
Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.

`--control 1` puts the plain reference, computed in bfloat16, in the
program's place for the check (the program's own readings are printed
first); such a run has to come out not correct. Several seeds after `--seed`
run one after another in one process, each with its own result line and a
set-up timed from its own start.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# The persistent compile cache lives in the checkout, at a fixed path, so
# that only a checkout's first run of a cell compiles.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(*a):
    print(*a, flush=True)


def metric_module(name):
    from bench import harness
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                               f"bench_metric_{name}")


def read_metric(name, run):
    return metric_module(name).read(run)


def applies(metric, cell) -> bool:
    return "workloads" not in metric or cell["name"] in metric["workloads"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness, peaks

    cell, cfg, mix = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    try:
        chip = peaks.peaks_for(kind)
    except KeyError as e:
        print(e, file=sys.stderr)
        return 2
    t = T_PROCESS
    for seed in args.seed:
        execute(harness.load_benchmark(), cell, cfg, mix, seed, args.seconds,
                bool(args.trace), {"platform": devices[0].platform,
                                   "kind": kind}, chip, t_process=t,
                control=bool(args.control))
        t = time.perf_counter()
    return 0


def execute(bench, cell, cfg, mix, seed, seconds, trace, device, chip,
            t_process, control=False) -> int:
    """Everything after the look for a chip: run the cell, read its metrics,
    print the diagnostics, the compared numbers and the result line."""
    from bench import harness, trace_reduce

    metrics = [m for m in bench["per_layer" if trace else "end_to_end"]
               if applies(m, cell)]
    mods = {m["name"]: metric_module(m["name"]) for m in metrics}
    kernels = {mod.KERNEL: mod for mod in mods.values()
               if hasattr(mod, "KERNEL")}
    out = harness.run_cell(
        cell, cfg, mix, seed, seconds, trace, t_process, log=log,
        control=control,
        instruments=[(k, mod.instrument) for k, mod in kernels.items()])
    run = out["_window"]
    run.peaks = chip
    run.setup_s = out["_setup_s"]
    device = dict(device, count=cell["chips"], memory_peak_bytes=out["_peak"])
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        run.trace = trace_reduce.reduce(
            *trace_reduce.load(out["_trace_dir"]), kernels=tuple(kernels))
        shutil.rmtree(out["_trace_dir"], ignore_errors=True)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        from bench.metrics import _roofline
        for k, mod in kernels.items():
            least, bounds = _roofline.least_seconds(run, k, mod.count)
            log(f"kernel {k}: {run.trace['kernel_s'].get(k, 0.0):.6f} s on "
                f"device, least {least:.6f} s, calls by bound {bounds}")
    values = {}
    for m in metrics:
        v = mods[m["name"]].read(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = values
    result["device"] = device
    if trace:
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = out["_checks"]
    for name, c in out["_checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
