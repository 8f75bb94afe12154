#!/usr/bin/env python3
"""Runs one cell of the benchmark once, as a new process:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It loads, warms up, measures for --seconds, checks what the timed path
produced against the plain reference, and prints one JSON object as the last
line of its standard output. Without a TPU (or with fewer chips than the cell
asks for) it exits with code 2 and prints no result.

This file holds no table of cells, drivers, configurations or metrics. It
opens `workloads/<cell>.json` and finds the rest by the names in it:
`configs/<config>.json`, `drivers/<driver>.py`, `models/<family>.py`,
`reference/<family>.py`, and, in a traced run, `layer_metrics/<metric>.py`
for every per-layer metric of BENCHMARK.json that lists the cell.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib.util
import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


def load(bench: Path, kind: str, name: str):
    """The module `<bench>/<kind>/<name>.py`, by path: a file dropped there
    is found with no other file edited."""
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> str:
    """The contract's line. `checks`, each number compared beside its limit,
    comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    finite = lambda x: x if x is not None and x == x and abs(x) != float("inf") else None
    out["checks"] = {name: {"value": finite(value), "limit": limit}
                     for name, value, limit in checks}
    return json.dumps(out)


def layer_metrics(bench: Path, benchmark: dict, cell_name: str, env) -> dict:
    """Every per-layer metric of BENCHMARK.json that lists this cell (or
    lists none), by its reader `layer_metrics/<name>.py`. A quantity split by
    the end-to-end metric it moves (`x.train`, `x.serve`) may share the reader
    `layer_metrics/x.py`. A reader that finds nothing to read returns None and
    the metric is left out."""
    out = {}
    for m in benchmark["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        reader = m["name"]
        if not (bench / "layer_metrics" / f"{reader}.py").is_file():
            reader = reader.rpartition(".")[0]
        value = load(bench, "layer_metrics", reader).compute(env)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: Path = BENCH, repo: Path = REPO,
             check_device: bool = True, t_start: float = None):
    """One run; returns (exit code, result dict or None). `check_device` is
    for the tests, which drive everything but the look for a chip on the CPU;
    no option of the command turns it off."""
    t_start = T_START if t_start is None else t_start
    bench, repo = Path(bench), Path(repo)
    for p in (str(repo), str(bench)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from harness import compare, device, flops, xplane

    cell = load_json(bench / "workloads" / f"{workload}.json")
    if check_device:
        try:
            device.require_tpu(int(cell["chips"]))
        except device.NoChip as e:
            print(f"benchmarks/run.py: {e}", file=sys.stderr)
            return 2, None
    info = device.device_info()
    cache_dir = device.enable_compile_cache(repo)
    config = load_json(bench / "configs" / f"{cell['config']}.json")
    benchmark = load_json(repo / "BENCHMARK.json")
    trace_root = repo / ".bench_out" / workload

    def trace_dir() -> str:
        shutil.rmtree(trace_root, ignore_errors=True)
        trace_root.mkdir(parents=True)
        return str(trace_root)

    ctx = SimpleNamespace(
        cell=cell, config=config, seed=int(seed), seconds=float(seconds),
        trace=bool(trace), trace_dir=trace_dir,
        model=load(bench, "models", config["family"]),
        reference=load(bench, "reference", config["family"]),
        device=device, compare=compare, compiles=device.CompileCounter())
    print(f"[run] {workload} seed {seed} on {info} cache {cache_dir}",
          flush=True)
    res = load(bench, "drivers", cell["driver"]).run(ctx)

    setup_s = res["t_open"] - t_start
    dev = dict(info, memory_peak_bytes=int(res["memory_peak_bytes"]))
    print(f"[run] memory_stats {res['memory_stats']}", flush=True)
    checks = [(n, float(v), None if lim is None else float(lim))
              for n, v, lim in res["checks"]]
    breakdown = None
    if trace:
        tr = xplane.read(xplane.find_xplane(res["trace_dir"]))
        shutil.rmtree(trace_root, ignore_errors=True)
        dev["busy_s"], dev["window_s"] = xplane.busy_seconds(tr)
        breakdown = {"device_ops": xplane.top_ops(tr),
                     "idle_gaps": xplane.idle_gaps(tr)}
        env = SimpleNamespace(
            trace=tr, facts=res["facts"], cell=cell, config=config,
            peak=device.peaks(info["kind"]) if check_device else None,
            flops=flops, xplane=xplane)
        metrics = layer_metrics(bench, benchmark, workload, env)
    else:
        units = {m["name"]: m["unit"] for m in benchmark["end_to_end"]}
        values = dict(res["end_to_end"], setup_s=setup_s)
        metrics = {n: {"value": float(v), "unit": units[n]}
                   for n, v in values.items()}
    correct = compare.verdict(checks)
    for name, value, limit in checks:
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    line = result_line(correct, res["attempted"], res["failed"], metrics, dev,
                       checks, breakdown)
    return 0, line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    rc, line = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    if line is not None:
        sys.stdout.flush()
        print(line, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
