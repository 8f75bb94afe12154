#!/usr/bin/env python3
"""Readings for the limits of a cell's `correct` (PERF.md section 2), on the
chip at the cell's own size, several seeds in one process:

    python3 benchmarks/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 8
        sound runs of the program: the numbers compared, for the lower readings
    ... --set precision.registry=bf16 [--set precision.kv_dtype=int8]
        the control where the program has a lower-precision path of its own:
        the cell as it stands, run from a copy of `benchmarks/` (under
        `.bench_out/`) whose configuration file has these keys changed
    ... --reference-controls 1
        the controls that the reference plants in itself (the driver's
        `reference_controls`): put in the program's place, no program run

Every control goes through the harness's own comparison, `compare.verdict`,
against the cell's limits, and its line says what `correct` came out as. The
benchmark's own runs never do this; its limits are set from these lines."""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from types import SimpleNamespace

import run as bench_run


def changed_copy(cell_name: str, changes: list):
    """A copy of benchmarks/ with `key.path=value` set in the cell's
    configuration file; no file of the benchmark itself is touched."""
    bench = bench_run.REPO / ".bench_out" / "calibrate" / "benchmarks"
    shutil.rmtree(bench.parent, ignore_errors=True)
    shutil.copytree(bench_run.BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    cell = bench_run.load_json(bench / "workloads" / f"{cell_name}.json")
    path = bench / "configs" / f"{cell['config']}.json"
    config = bench_run.load_json(path)
    for change in changes:
        keys, _, value = change.partition("=")
        *groups, last = keys.split(".")
        at = config
        for g in groups:
            at = at[g]
        at[last] = value
    path.write_text(json.dumps(config, indent=2))
    return bench


def reference_controls(cell_name: str, seed: int) -> dict:
    """{control: checks} from the cell's driver, with nothing of the program."""
    bench = bench_run.BENCH
    for p in (str(bench_run.REPO), str(bench)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from harness import compare, device
    device.enable_compile_cache(bench_run.REPO)
    cell = bench_run.load_json(bench / "workloads" / f"{cell_name}.json")
    config = bench_run.load_json(bench / "configs" / f"{cell['config']}.json")
    ctx = SimpleNamespace(
        cell=cell, config=config, seed=int(seed), compare=compare,
        reference=bench_run.load(bench, "reference", config["family"]))
    return bench_run.load(bench, "drivers", cell["driver"]).reference_controls(ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE")
    ap.add_argument("--reference-controls", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from harness import compare
    bench = changed_copy(a.workload, a.set) if a.set else bench_run.BENCH
    what = f"control {' '.join(a.set)}" if a.set else "program"
    for seed in (int(s) for s in a.seeds.split(",")):
        if a.reference_controls:
            for name, checks in reference_controls(a.workload, seed).items():
                print(f"calibrate {a.workload} seed {seed} control {name} in "
                      f"the program's place: correct "
                      f"{compare.verdict(checks)} checks "
                      f"{json.dumps({n: {'value': v, 'limit': lim} for n, v, lim in checks})}",
                      flush=True)
            continue
        rc, line = bench_run.run_cell(a.workload, seed, a.seconds, False,
                                      bench=bench, t_start=time.perf_counter())
        if rc:
            return rc
        out = json.loads(line)
        print(f"calibrate {a.workload} seed {seed} {what}: correct "
              f"{out['correct']} checks {json.dumps(out['checks'])} metrics "
              f"{json.dumps(out['metrics'])}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
