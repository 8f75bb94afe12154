"""graftlint CLI.

    python -m tools.graftlint deeplearning4j_tpu/            # AST lint vs baseline
    python -m tools.graftlint deeplearning4j_tpu/ --ir       # IR tier (jaxpr/HLO)
    python -m tools.graftlint pkg/ --write-baseline          # accept current
    python -m tools.graftlint pkg/ --metrics                 # Prometheus text
    python -m tools.graftlint --list-rules

Exit codes: 0 = clean against the baseline, 1 = new findings (or stale
baseline entries with --strict-stale), 2 = usage/parse error. The AST
pass is pure stdlib; `--ir` imports jax and abstract-evals the
package's jit entry points on the virtual 8-device mesh (baseline
section `ir_findings` in the same baseline file).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, Optional, Sequence

from .engine import RULES, LintResult, run_lint, write_baseline

DEFAULT_BASELINE = "graftlint_baseline.json"


def _find_baseline(paths: Sequence[str], explicit: Optional[str]
                   ) -> Optional[str]:
    """Explicit path wins; else look for graftlint_baseline.json next to
    the first target, then upward to the filesystem root, then cwd."""
    if explicit:
        return explicit
    start = os.path.abspath(paths[0]) if paths else os.getcwd()
    if os.path.isfile(start):
        start = os.path.dirname(start)
    cur = start
    while True:
        cand = os.path.join(cur, DEFAULT_BASELINE)
        if os.path.exists(cand):
            return cand
        parent = os.path.dirname(cur)
        if parent == cur:
            break
        cur = parent
    cand = os.path.join(os.getcwd(), DEFAULT_BASELINE)
    return cand if os.path.exists(cand) else None


def lint_metrics(paths: Sequence[str],
                 baseline: Optional[str] = None) -> Dict:
    """Programmatic entry (`tools/graftlint --metrics`): {'total', 'new',
    'by_rule', 'new_by_rule', 'files', 'wall_s'} for the given targets."""
    t0 = time.perf_counter()
    res = run_lint(paths, baseline_path=_find_baseline(paths, baseline))
    return {
        "total": len(res.findings),
        "new": len(res.new),
        "by_rule": res.by_rule(),
        "new_by_rule": res.new_by_rule(),
        "files": res.files,
        "wall_s": round(time.perf_counter() - t0, 3),
    }


def ir_lint_metrics(paths: Sequence[str] = (),
                    baseline: Optional[str] = None) -> Dict:
    """IR-tier counterpart of `lint_metrics`: runs the
    jaxpr/HLO pass over the probe roster (requires jax + the virtual
    mesh) and reports totals plus the measured whole-package IR wall
    time and the watch_compiles roster size."""
    from ..telemetry.compile_watch import roster_names
    from .ir import run_ir_lint
    from .ir_probes import build_entries

    t0 = time.perf_counter()
    entries = build_entries()
    res = run_ir_lint(entries,
                      baseline_path=_find_baseline(list(paths), baseline))
    # count the roster while `entries` still pins the jitted fns alive
    # (the ledger holds weakrefs)
    n_roster = len(roster_names())
    del entries
    return {
        "total": len(res.findings),
        "new": len(res.new),
        "by_rule": res.by_rule(),
        "new_by_rule": res.new_by_rule(),
        "entries": res.files,
        "roster": n_roster,
        "wall_s": round(time.perf_counter() - t0, 3),
    }


def _prometheus(res: LintResult, ir: bool = False) -> str:
    lines = [
        "# HELP dl4j_lint_findings_total graftlint findings by rule "
        "(baselined + new)",
        "# TYPE dl4j_lint_findings_total counter",
    ]
    for rule_id, n in sorted(res.by_rule().items()):
        lines.append(f'dl4j_lint_findings_total{{rule="{rule_id}"}} {n}')
    lines += [
        "# HELP dl4j_lint_new_findings_total graftlint findings not "
        "covered by the baseline",
        "# TYPE dl4j_lint_new_findings_total counter",
    ]
    for rule_id, n in sorted(res.new_by_rule().items()):
        lines.append(
            f'dl4j_lint_new_findings_total{{rule="{rule_id}"}} {n}')
    if ir:
        lines.append("# HELP dl4j_lint_ir_entries_total jit entry points "
                     "abstract-evaled by the IR tier")
        lines.append("# TYPE dl4j_lint_ir_entries_total gauge")
        lines.append(f"dl4j_lint_ir_entries_total {res.files}")
    else:
        lines.append("# HELP dl4j_lint_files_total files linted")
        lines.append("# TYPE dl4j_lint_files_total gauge")
        lines.append(f"dl4j_lint_files_total {res.files}")
    return "\n".join(lines) + "\n"


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftlint",
        description="JAX-aware static analysis for deeplearning4j_tpu "
                    "(jit/tracer hygiene, recompilation hazards, donation "
                    "safety, concurrency lint)")
    ap.add_argument("paths", nargs="*", help="files or directories to lint")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default: nearest "
                         f"{DEFAULT_BASELINE})")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding as new")
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept all current findings into the baseline")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--ir", action="store_true",
                    help="run the IR tier instead of the AST pass: "
                         "trace/lower/compile the package's jit entry "
                         "points on the virtual 8-device mesh and verify "
                         "shardings, collectives and donation aliasing "
                         "(requires jax; baseline section 'ir_findings')")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--metrics", action="store_true",
                    help="emit Prometheus text "
                         "(dl4j_lint_findings_total{rule=...}) and exit 0")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--strict-stale", action="store_true",
                    help="also fail when baseline entries no longer match "
                         "any finding (keeps the ratchet tight)")
    ap.add_argument("--show-baselined", action="store_true",
                    help="print baselined findings too, not just new ones")
    args = ap.parse_args(argv)

    if args.list_rules:
        # force registration (ir registers rule ids only — no jax import)
        from . import ir  # noqa: F401
        from . import rules_concurrency  # noqa: F401
        from . import rules_jit  # noqa: F401
        for rid, info in sorted(RULES.items()):
            print(f"{rid:26s} [{info.family}] {info.description}")
        return 0
    if not args.paths:
        ap.error("no paths given (try: deeplearning4j_tpu/)")

    if args.write_baseline and args.rules:
        # a filtered run sees only a subset of findings — writing it out
        # would silently erase every other rule's accepted entries
        ap.error("--write-baseline cannot be combined with --rules "
                 "(the baseline must cover ALL rules)")
    baseline_path = None if args.no_baseline else \
        _find_baseline(args.paths, args.baseline)
    rules = [r.strip() for r in args.rules.split(",")] if args.rules \
        else None
    missing = [p for p in args.paths if not os.path.exists(p)]
    if missing:
        print(f"graftlint: no such path: {', '.join(missing)}",
              file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    if args.ir:
        # the IR tier: probe-built jit entry points on the virtual mesh;
        # `paths` only locate the baseline file. Imported lazily so the
        # plain AST CLI keeps working in jax-free environments.
        from .ir import IR_BASELINE_SECTION, run_ir_lint
        try:
            res = run_ir_lint(baseline_path=baseline_path, rules=rules)
        except RuntimeError as e:      # 1-device backend: environment
            print(f"graftlint: {e}", file=sys.stderr)
            return 2
        section = IR_BASELINE_SECTION
        unit = "entries"
    else:
        try:
            res = run_lint(args.paths, baseline_path=baseline_path,
                           rules=rules)
        except SyntaxError as e:
            print(f"graftlint: {e}", file=sys.stderr)
            return 2
        section = "findings"
        unit = "files"
        if res.files == 0:
            print("graftlint: no .py files found under "
                  f"{', '.join(args.paths)}", file=sys.stderr)
            return 2
    wall = time.perf_counter() - t0

    if args.write_baseline:
        path = args.baseline or os.path.join(
            os.getcwd(), DEFAULT_BASELINE) if baseline_path is None \
            else baseline_path
        write_baseline(path, res.findings, section=section)
        print(f"graftlint: wrote {len(res.findings)} finding(s) to {path} "
              f"[{section}]")
        return 0

    if args.metrics:
        sys.stdout.write(_prometheus(res, ir=args.ir))
        return 0

    if args.format == "json":
        print(json.dumps({
            "files": res.files,
            "findings": [vars(f) for f in res.findings],
            "new": [vars(f) for f in res.new],
            "stale_baseline": res.stale_baseline,
            "wall_s": round(wall, 3),
        }, indent=1))
    else:
        shown = res.findings if args.show_baselined else res.new
        for f in shown:
            marker = "" if f in res.new else " (baselined)"
            print(f.render() + marker)
        for k in res.stale_baseline:
            print(f"stale baseline entry (no longer found): {k}")
        summary = (f"graftlint: {res.files} {unit}, "
                   f"{len(res.findings)} finding(s) "
                   f"({len(res.findings) - len(res.new)} baselined, "
                   f"{len(res.new)} new), "
                   f"{len(res.stale_baseline)} stale baseline entr"
                   f"{'y' if len(res.stale_baseline) == 1 else 'ies'} "
                   f"in {wall:.2f}s")
        print(summary)
    if res.new:
        return 1
    if args.strict_stale and res.stale_baseline:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
