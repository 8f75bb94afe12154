"""graftlint IR tier: jaxpr/HLO verification of jit entry points.

The AST tier (rules_jit/rules_concurrency) sees what the Python source
shows; SPMD correctness lives below it — in shard specs, collective
schedules and buffer aliasing that only exist once a program is traced,
partitioned and compiled. This tier abstract-evals registered jit entry
points under a virtual 8-device mesh (the same
`--xla_force_host_platform_device_count=8` mesh the test suite trains
on), then inspects three artifacts per entry:

  * the **closed jaxpr** (`fn.trace(...)`) — axis names, the
    `sharding_constraint` schedule, `optimization_barrier` ordering
    chains, redundant reshard pairs at the primitive level;
  * the **lowered StableHLO** (`.lower().as_text()`) — donation intent
    (`tf.aliasing_output` on donated parameters) plus the lowering-time
    "donated buffers were not usable" warning;
  * the **compiled, scheduled HLO** (`.compile().as_text()`) — the
    collectives GSPMD actually inserted (op, shape, replica groups, in
    schedule order), the executable's input→output alias map, and
    text-level reshard pairs.

Rule families (ids registered with the shared engine; findings flow
through the same pragma/baseline/ratchet machinery, under the
`ir_findings` baseline section):

  ir-collective-order        two lowerings of one entry disagree on the
                             collective issue sequence — the invariant
                             elastic resize (ROADMAP item 4) must
                             preserve across processes
  ir-invalid-axis            a collective names an axis the entry's mesh
                             does not carry
  ir-redundant-reshard       reduce-scatter immediately all-gathered
                             back (or psum_scatter -> all_gather in the
                             jaxpr): a full collective round-trip that a
                             plain psum/allreduce does in one
  ir-implicit-reshard        GSPMD-inserted collective bytes exceed the
                             step's declared static accounting
                             (parallel/zero.py `info["bytes"]`), or the
                             traced `sharding_constraint` count fell
                             below the plan's declared schedule — either
                             way a "sharded" tensor is being silently
                             materialized replicated
  ir-ineffective-donation    a donate_argnums buffer the lowering or XLA
                             quietly refused to alias — the donation is
                             a no-op and peak memory is 2x the tensor
  ir-nondeterministic-reduction
                             an entry asserting bit-exact resume issues
                             multiple float gradient reductions with no
                             optimization_barrier ordering chain — XLA's
                             collective combiner may merge/reorder them,
                             so the summed gradients are not stable
                             across schedules or elastic resizes
  ir-missing-custom-call     an entry declaring the shard_map'd Pallas
                             kernel path (expects_custom_call) whose
                             traced program carries no pallas_call
                             primitive — the kernel silently fell back
                             to the XLA path

The order check has a runtime counterpart
(`analysis.sanitizer.CollectiveSequenceHasher`): the static pass digests
a compiled program's collective sequence (op/shape/replica-groups from
the HLO text), the runtime hook digests the schedule each process
actually issues per step (op/bytes/multiplicity from the trainer's
accounting). The two hash different views and are each compared ACROSS
PROCESSES within their own domain — program digest vs program digest,
runtime stream vs runtime stream — which is how item 4's kill/rejoin
drills use them.
"""
from __future__ import annotations

import hashlib
import os
import re
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .engine import (Finding, LintResult, baseline_diff, load_baseline,
                     register_rule_id)

__all__ = ["IrEntry", "analyze_entry", "run_ir_lint", "collective_sequence",
           "sequence_digest", "check_cross_program_order",
           "measured_collective_bytes", "measured_collective_bytes_by_axis",
           "IR_RULES", "IR_BASELINE_SECTION"]

IR_BASELINE_SECTION = "ir_findings"

_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute", "all-to-all")
# HLO shape element bytes (shapes the package's programs produce)
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8, "c64": 8, "c128": 16}

IR_RULES = {
    "ir-collective-order": ("ir-collective", "collective issue order "
                            "diverges between lowerings of one entry"),
    "ir-invalid-axis": ("ir-collective", "collective references an axis "
                        "name the entry's mesh does not define"),
    "ir-redundant-reshard": ("ir-collective", "reduce-scatter immediately "
                             "all-gathered back (redundant reshard pair)"),
    "ir-implicit-reshard": ("ir-reshard", "GSPMD-inserted collective "
                            "traffic exceeds the declared static "
                            "accounting, or a declared shard constraint "
                            "is missing from the traced program"),
    "ir-ineffective-donation": ("ir-donation", "donated buffer the "
                                "lowering or XLA did not alias"),
    "ir-nondeterministic-reduction": ("ir-determinism", "bit-exact entry "
                                      "issues unordered float reductions "
                                      "XLA may reassociate"),
    "ir-missing-custom-call": ("ir-kernel", "entry declares a Pallas "
                               "kernel path but the traced program "
                               "carries no pallas_call — the kernel was "
                               "silently replaced by the XLA fallback"),
}
for _rid, (_fam, _desc) in IR_RULES.items():
    register_rule_id(_rid, _fam, _desc)

# Absolute floors added to a byte budget after `byte_slack`. A step's
# whole-program accounting is exact up to the score's scalar all-reduce
# (the ZeRO probes measure 4 bytes over their declared 1,680), so its
# floor must stay far below a probe's payload: at 1 KiB a ZeRO shard
# gathered replicated (+1,664 bytes) passed unseen. The per-axis budgets
# also take GSPMD's staging gathers on the 3-D steps (a few hundred bytes
# on the probes' sizes, 544 under a declared 0).
_SCALAR_FLOOR_BYTES = 64
_AXIS_FLOOR_BYTES = 1024


@dataclass
class IrEntry:
    """One jit entry point to abstract-eval. Probes (analysis/ir_probes)
    build these from real models/trainers on the virtual mesh; tests
    build them directly around seeded mutations.

    `fn` is the JITTED callable (donation/shardings baked in) and `args`
    a concrete or abstract argument tuple it can be `.trace()`d with.
    Alternatively `compiled` carries a pre-built executable (serving's
    AOT runners) — then only the text-level checks run.
    """
    name: str                       # roster/scope name, e.g. "parallel/zero2_step"
    path: str                       # package-relative source attribution
    fn: Any = None
    args: Tuple = ()
    kwargs: Dict[str, Any] = field(default_factory=dict)
    compiled: Any = None
    mesh_axes: Tuple[str, ...] = ()
    declared_bytes: Optional[int] = None   # static per-program collective payload
    check_bytes: bool = False              # byte-diff only for scan-free steps
    # 2-D mesh entries (ISSUE 14): per-axis byte budgets, diffed against
    # the measured collectives CLASSIFIED BY AXIS via replica-group size
    # (axis_sizes = {"data": d, "model": m}; sizes must be distinct or
    # the classification falls back to "other" and the check skips)
    declared_bytes_by_axis: Optional[Dict[str, int]] = None
    axis_sizes: Optional[Dict[str, int]] = None
    expected_constraints: Optional[int] = None
    requires_ordered_reductions: bool = False
    asserts_bitexact: bool = False
    # flash-under-SPMD entries (ISSUE 18): the step is built around the
    # shard_map'd Pallas kernel, so the traced jaxpr must carry a
    # pallas_call primitive (inside the shard_map body — _walk_eqns
    # descends it). Checked at the jaxpr level: it is backend-portable
    # (interpret-mode tracing emits the same primitive the TPU lowering
    # turns into the custom call), where compiled-HLO custom-call text
    # only exists on a real TPU.
    expects_custom_call: bool = False
    byte_slack: float = 1.5                # CPU emulates reduce-scatter as
                                           # full all-reduce; 1.5x absorbs
                                           # that, the floors below the rest

    def finding(self, rule: str, message: str, detail_key: str) -> Finding:
        """IR findings have no source line; the baseline key is
        (rule, path, entry name, stable detail token) so it survives
        unrelated edits exactly like the AST tier's line-free keys."""
        return Finding(rule, self.path, 0, 0, message, scope=self.name,
                       snippet=f"ir:{self.name}:{detail_key}")


# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------
_INSTR = re.compile(
    r"%?([\w.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\(([^)]*)\)(.*)")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS = re.compile(r"replica_groups=(\[[^\]]*\](?:<=\[\d+\])?|\{[^}]*\})")


def _shape_bytes(shape_text: str) -> int:
    """Total bytes of every 'dtype[dims]' shape in `shape_text`."""
    total = 0
    for dt, dims in _SHAPE.findall(shape_text):
        if dt not in _ITEMSIZE:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _ITEMSIZE[dt]
    return total


def collective_sequence(hlo_text: str) -> List[Tuple[str, str, str]]:
    """(op, result shape, replica groups) per collective instruction, in
    program-text order. Compiled modules are scheduled
    (`is_scheduled=true`) so text order IS the issue order each device
    executes — the sequence elastic resize must keep identical across
    per-process programs."""
    seq = []
    for ln in hlo_text.splitlines():
        m = _INSTR.search(ln)
        if not m:
            continue
        _, shape, op, suffix, _, tail = m.groups()
        if suffix == "-done":
            continue    # the async completion half: same collective,
            # already sequenced (and sized) at its -start
        g = _GROUPS.search(ln)
        seq.append((op, shape, g.group(1) if g else ""))
    return seq


def sequence_digest(seq: Sequence[Tuple]) -> str:
    """Stable digest of a STATIC collective sequence (as parsed from
    compiled HLO text). Compare program digests against program digests
    across processes; the runtime CollectiveSequenceHasher digests a
    different view (issued ops/bytes) and is compared within its own
    domain."""
    h = hashlib.sha256()
    for item in seq:
        h.update(repr(tuple(item)).encode())
        h.update(b"\0")
    return h.hexdigest()[:16]


def check_cross_program_order(sequences: Sequence[Sequence[Tuple]]
                              ) -> Optional[str]:
    """None when every program issues the identical collective sequence;
    else a message naming the first divergence. Used three ways: the
    static pass compares independent lowerings of one entry, and the
    multi-host drills compare per-process program texts and per-process
    runtime hashes."""
    if len(sequences) < 2:
        return None
    ref = list(sequences[0])
    for pi, seq in enumerate(sequences[1:], 1):
        seq = list(seq)
        if seq == ref:
            continue
        n = min(len(ref), len(seq))
        for i in range(n):
            if ref[i] != seq[i]:
                return (f"program {pi} diverges at collective {i}: "
                        f"{ref[i]} vs {seq[i]}")
        return (f"program {pi} issues {len(seq)} collectives, "
                f"program 0 issues {len(ref)}")
    return None


def measured_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Logical payload bytes by op from the compiled text, matching the
    convention of parallel/zero.py's static accounting (full tensor
    bytes once, not x(N-1)/N wire segments): all-reduce/all-gather count
    the (full) RESULT shape, reduce-scatter counts the full OPERAND.
    Collectives inside a scan/while body appear once in the text, so for
    looped programs this is a per-iteration lower bound."""
    out: Dict[str, int] = {}
    for ln in hlo_text.splitlines():
        m = _INSTR.search(ln)
        if not m:
            continue
        _, shape, op, suffix, operands, _ = m.groups()
        if suffix == "-done":
            continue    # async pair: payload counted once at -start
        b = _shape_bytes(operands if op == "reduce-scatter" else shape)
        out[op] = out.get(op, 0) + b
    return out


_FIRST_GROUP = re.compile(r"\{(\d+(?:\s*,\s*\d+)*)\}")
_IOTA_GROUPS = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_PERMUTE_PAIRS = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")


def _permute_axis(line: str, axis_items) -> Optional[str]:
    """Mesh-axis attribution for a collective-permute: its
    `source_target_pairs` connect LINEAR device ids, so unraveling each
    (src, dst) against the mesh shape (axis_items = ordered
    (name, size) pairs, mesh-major order — the order `make_mesh` builds)
    names the axis every pair moves along. Pipeline stage handoffs shift
    exactly one coordinate (the `pipe` axis); a permute whose pairs move
    along a different single axis is a LEAK the per-axis budgets catch,
    and multi-axis pairs (GSPMD reshard shuffles) land under "other".
    Returns None when the line carries no pairs."""
    m = _PERMUTE_PAIRS.search(line)
    if not m:
        return None
    shape = [int(s) for _, s in axis_items]
    names = [n for n, _ in axis_items]
    total = 1
    for s in shape:
        total *= s

    def unravel(idx):
        coords = []
        for s in reversed(shape):
            idx, c = divmod(idx, s)
            coords.append(c)
        return coords[::-1]

    axes = set()
    for pm in re.finditer(r"\{(\d+),(\d+)\}", m.group(1)):
        a, b = int(pm.group(1)), int(pm.group(2))
        if a == b:
            continue    # identity legs of a reshard shuffle
        if not shape or a >= total or b >= total:
            return "other"
        ca, cb = unravel(a), unravel(b)
        diff = [i for i in range(len(shape)) if ca[i] != cb[i]]
        if len(diff) != 1:
            return "other"
        axes.add(diff[0])
    if len(axes) == 1:
        return names[axes.pop()]
    return "other"


def _replica_group_size(line: str) -> Optional[int]:
    """Participant count per replica group of a collective instruction
    line — the key that maps it onto a mesh axis. Handles both HLO
    forms: explicit `replica_groups={{0,4},{1,5},...}` (count the first
    group's members) and iota `replica_groups=[G,S]<=[...]` (S). None
    when the line carries no groups (the collective spans everything)."""
    m = _IOTA_GROUPS.search(line)
    if m:
        return int(m.group(2))
    if "replica_groups=" not in line:
        return None
    m = _FIRST_GROUP.search(line.split("replica_groups=", 1)[1])
    if m:
        return len(m.group(1).split(","))
    return None


def measured_collective_bytes_by_axis(hlo_text: str,
                                      axis_sizes: Dict[str, int]
                                      ) -> Dict[str, Dict[str, int]]:
    """`measured_collective_bytes` split by MESH AXIS: each collective is
    attributed to the axis whose size equals its replica-group size
    (on a (2, 4) mesh, groups of 2 ride "data", groups of 4 ride
    "model"). Collectives whose group size matches no axis — or matches
    more than one (d == m; use distinct sizes for checkable meshes) —
    land under "other". Collective-PERMUTEs carry no replica groups;
    their `source_target_pairs` are unraveled against the mesh shape
    instead (`_permute_axis` — `axis_sizes` must list the axes in MESH
    order, as `make_mesh` builds them), so a pipeline stage handoff
    attributes to `pipe` and a permute leaking onto `data`/`model`
    attributes there even when axis sizes collide. This is how the IR
    tier verifies the 2-D/3-D contract: ZeRO's optimizer collectives
    must ride the data axis at the plan's declared payload, the model
    axis must carry only the Megatron activation psums, and only the
    pipe axis may carry stage handoffs."""
    inverse: Dict[int, List[str]] = {}
    for ax, n in axis_sizes.items():
        inverse.setdefault(int(n), []).append(ax)
    items = list(axis_sizes.items())
    out: Dict[str, Dict[str, int]] = {}
    for ln in hlo_text.splitlines():
        m = _INSTR.search(ln)
        if not m:
            continue
        _, shape, op, suffix, operands, _ = m.groups()
        if suffix == "-done":
            continue
        b = _shape_bytes(operands if op == "reduce-scatter" else shape)
        if op == "collective-permute":
            ax = _permute_axis(ln, items) or "other"
        else:
            gsize = _replica_group_size(ln)
            axes = inverse.get(gsize, []) if gsize is not None else []
            ax = axes[0] if len(axes) == 1 else "other"
        bucket = out.setdefault(ax, {})
        bucket[op] = bucket.get(op, 0) + b
    return out


def compiled_aliased_params(hlo_text: str) -> set:
    """Parameter indices the compiled executable aliases to an output
    (the `input_output_alias={ {0}: (3, {}, may-alias), ... }` header)."""
    head = hlo_text.split("\n", 1)[0]
    i = head.find("input_output_alias=")
    if i < 0:
        return set()
    # the map ends at the matching close of its outer brace pair
    body = head[i + len("input_output_alias="):]
    depth = 0
    for j, ch in enumerate(body):
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                body = body[: j + 1]
                break
    return {int(m.group(1)) for m in re.finditer(r"\(\s*(\d+)\s*,", body)}


def donated_params(stablehlo_text: str) -> set:
    """Parameter indices the lowering marked as consumed donations
    (`tf.aliasing_output` / `jax.buffer_donor` attributes). Parsed
    per-argument within the @main signature only — a span-based match
    would attribute a later arg's donation attribute to an earlier
    non-donated arg (and the body's bare `%argN` uses must not count)."""
    i = stablehlo_text.find("@main(")
    if i < 0:
        return set()
    # the signature ends at the paren matching "@main(" (types may nest
    # their own parens/brackets)
    j = i + len("@main(")
    depth, k = 1, j
    while k < len(stablehlo_text) and depth:
        c = stablehlo_text[k]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        k += 1
    sig = stablehlo_text[j:k - 1]
    out = set()
    decls = list(re.finditer(r"%arg(\d+):", sig))
    for idx, m in enumerate(decls):
        end = decls[idx + 1].start() if idx + 1 < len(decls) else len(sig)
        seg = sig[m.end():end]
        if "tf.aliasing_output" in seg or "jax.buffer_donor" in seg:
            out.add(int(m.group(1)))
    return out


def _redundant_reshard_pairs(hlo_text: str) -> List[str]:
    """all-gather instructions whose operand is (directly) a
    reduce-scatter result: the pair moves the full tensor twice where
    one all-reduce would."""
    producers = {}
    for ln in hlo_text.splitlines():
        m = _INSTR.search(ln)
        if m:
            producers[m.group(1)] = m.group(3)
    pairs = []
    for ln in hlo_text.splitlines():
        m = _INSTR.search(ln)
        if not m or m.group(3) != "all-gather" or m.group(4) == "-done":
            continue    # a -done consumes its own -start handle, not data
        for op_name in re.findall(r"%([\w.\-]+)", m.group(5)):
            if producers.get(op_name) == "reduce-scatter":
                pairs.append(f"{op_name} -> {m.group(1)}")
    return pairs


# ---------------------------------------------------------------------------
# jaxpr inspection
# ---------------------------------------------------------------------------
def _walk_eqns(jaxpr):
    """Every eqn in `jaxpr` and its nested sub-jaxprs (scan/while/cond
    bodies, shard_map bodies, custom-derivative branches). Params carry
    sub-programs as either ClosedJaxpr (`.jaxpr`) or raw Jaxpr
    (`.eqns`) — shard_map uses the raw form."""
    stack = [jaxpr]
    seen = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eq in j.eqns:
            yield eq
            for v in eq.params.values():
                vs = v if isinstance(v, (list, tuple)) else (v,)
                for vv in vs:
                    inner = getattr(vv, "jaxpr", None)
                    if inner is None and hasattr(vv, "eqns"):
                        inner = vv
                    if inner is not None:
                        stack.append(inner)


def count_primitives(jaxpr, name: str) -> int:
    return sum(1 for eq in _walk_eqns(jaxpr) if str(eq.primitive) == name)


def collect_axis_names(jaxpr) -> set:
    """Axis names referenced by collective primitives (psum, all_gather,
    psum_scatter, ppermute, axis_index, ...)."""
    out = set()
    for eq in _walk_eqns(jaxpr):
        for key in ("axis_name", "axes", "axis_index_groups_axis"):
            v = eq.params.get(key)
            if v is None:
                continue
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for a in vs:
                if isinstance(a, str):
                    out.add(a)
    return out


def _jaxpr_reshard_pairs(jaxpr) -> List[str]:
    """psum_scatter results consumed directly by all_gather over the same
    axis — the primitive-level form of the redundant pair."""
    scatter_vars = {}
    pairs = []
    for eq in _walk_eqns(jaxpr):
        prim = str(eq.primitive)
        if prim == "psum_scatter":
            ax = eq.params.get("axis_name")
            for ov in eq.outvars:
                scatter_vars[id(ov)] = ax
        elif prim == "all_gather":
            ax = eq.params.get("axis_name")
            for iv in eq.invars:
                if id(iv) in scatter_vars and scatter_vars[id(iv)] == ax:
                    pairs.append(f"psum_scatter->all_gather over {ax}")
    return pairs


# ---------------------------------------------------------------------------
# Per-entry analysis
# ---------------------------------------------------------------------------
def analyze_entry(entry: IrEntry) -> List[Finding]:
    """Trace, lower and compile `entry` twice; run every IR rule. Raises
    nothing on rule hits (findings are data); raises if the entry itself
    cannot be traced (a broken probe is a bug, not a finding)."""
    findings: List[Finding] = []
    if entry.compiled is not None and entry.fn is None:
        texts = [entry.compiled.as_text()]
        jaxpr = None
        stablehlo = ""
    else:
        with warnings.catch_warnings(record=True) as wlist:
            warnings.simplefilter("always")
            traced = entry.fn.trace(*entry.args, **entry.kwargs)
            lowered = traced.lower()
            stablehlo = lowered.as_text()
            compiled = lowered.compile()
        # an independent second trace+lower+compile: the issue-order
        # determinism check (set/dict iteration anywhere in the step
        # builder shows up as a reordered schedule)
        compiled2 = entry.fn.trace(*entry.args,
                                   **entry.kwargs).lower().compile()
        texts = [compiled.as_text(), compiled2.as_text()]
        jaxpr = traced.jaxpr.jaxpr
        for w in wlist:
            msg = str(w.message)
            if "donated" in msg and "not usable" in msg:
                findings.append(entry.finding(
                    "ir-ineffective-donation",
                    "lowering dropped donation(s): " + msg.split("\n")[0],
                    "lowering-dropped"))

    text = texts[0]
    seqs = [collective_sequence(t) for t in texts]

    # -- collective-audit --------------------------------------------------
    div = check_cross_program_order(seqs)
    if div is not None:
        findings.append(entry.finding(
            "ir-collective-order",
            f"collective issue order is not stable across lowerings: {div}",
            "order"))
    if jaxpr is not None and entry.mesh_axes:
        unknown = collect_axis_names(jaxpr) - set(entry.mesh_axes)
        if unknown:
            findings.append(entry.finding(
                "ir-invalid-axis",
                f"collectives reference axis name(s) {sorted(unknown)} "
                f"not defined by the entry's mesh {entry.mesh_axes}",
                "axis:" + ",".join(sorted(unknown))))
    pairs = _redundant_reshard_pairs(text)
    if jaxpr is not None:
        pairs += _jaxpr_reshard_pairs(jaxpr)
    for p in pairs:
        findings.append(entry.finding(
            "ir-redundant-reshard",
            f"reduce-scatter result is immediately all-gathered back "
            f"({p}) — the pair moves the full tensor twice where one "
            "all-reduce would; keep the scattered shard or reduce "
            "replicated", "pair"))

    # -- implicit-reshard --------------------------------------------------
    if entry.check_bytes and entry.declared_bytes is not None:
        measured = measured_collective_bytes(text)
        total = sum(measured.values())
        budget = (int(entry.declared_bytes * entry.byte_slack)
                  + _SCALAR_FLOOR_BYTES)
        if total > budget:
            findings.append(entry.finding(
                "ir-implicit-reshard",
                f"GSPMD inserted {total} collective bytes "
                f"({measured}) against {entry.declared_bytes} declared "
                f"by the step's static accounting (slack-adjusted budget "
                f"{budget}) — a sharded tensor is being materialized "
                "replicated", "bytes"))
    if entry.declared_bytes_by_axis and entry.axis_sizes:
        by_axis = measured_collective_bytes_by_axis(text, entry.axis_sizes)
        for ax in sorted(entry.declared_bytes_by_axis):
            declared = entry.declared_bytes_by_axis[ax]
            got = sum(by_axis.get(ax, {}).values())
            budget = int(declared * entry.byte_slack) + _AXIS_FLOOR_BYTES
            if got > budget:
                findings.append(entry.finding(
                    "ir-implicit-reshard",
                    f"GSPMD inserted {got} collective bytes on the "
                    f"'{ax}' mesh axis ({by_axis.get(ax, {})}) against "
                    f"{declared} declared for that axis (slack-adjusted "
                    f"budget {budget}) — a tensor sharded over the other "
                    "axis is being materialized/resharded here",
                    f"bytes:{ax}"))
    if entry.expected_constraints is not None and jaxpr is not None:
        got = count_primitives(jaxpr, "sharding_constraint")
        if got < entry.expected_constraints:
            findings.append(entry.finding(
                "ir-implicit-reshard",
                f"traced program carries {got} sharding_constraint(s) "
                f"but the plan's declared layout schedule has "
                f"{entry.expected_constraints} — a with_sharding_"
                "constraint was dropped; XLA propagation is now free to "
                "replicate the shard", "constraints"))

    # -- missing-custom-call ----------------------------------------------
    if entry.expects_custom_call and jaxpr is not None:
        calls = count_primitives(jaxpr, "pallas_call")
        if calls == 0:
            findings.append(entry.finding(
                "ir-missing-custom-call",
                "entry declares the shard_map'd Pallas kernel path but "
                "the traced program carries no pallas_call primitive — "
                "the kernel was dropped and the step silently runs the "
                "XLA fallback (the einsum path should be selected "
                "EXPLICITLY via configure_flash_attention, not by "
                "losing the kernel)", "custom-call"))

    # -- ineffective-donation ---------------------------------------------
    if stablehlo:
        intended = donated_params(stablehlo)
        aliased = compiled_aliased_params(text)
        dropped = intended - aliased
        if dropped:
            findings.append(entry.finding(
                "ir-ineffective-donation",
                f"XLA did not alias donated input(s) {sorted(dropped)} "
                f"in the executable (aliased: {sorted(aliased)}) — the "
                "donation is a no-op and the buffer is live twice",
                "xla-dropped"))

    # -- nondeterministic-reduction ---------------------------------------
    # requires_ordered_reductions = the program SHAPE half (stage-2,
    # multi-bucket float reductions); asserts_bitexact = the CONTRACT
    # half (the equivalence suite promises bit-exact resume). Only the
    # conjunction is a bug: unordered reductions on an entry nobody
    # asserts bit-exactness for are a performance choice, not a lint.
    if entry.requires_ordered_reductions and entry.asserts_bitexact \
            and jaxpr is not None:
        barriers = count_primitives(jaxpr, "optimization_barrier")
        if barriers == 0:
            findings.append(entry.finding(
                "ir-nondeterministic-reduction",
                "entry asserts bit-exact resume and issues bucketed "
                "float gradient reductions, but the traced program has "
                "NO optimization_barrier ordering chain — XLA's "
                "collective combiner may merge/reorder the reductions, "
                "so the summed gradients are not stable across "
                "schedules or elastic resizes (set "
                "ZeroConfig.ordered_flush=True)", "unordered"))
    return findings


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------
def run_ir_lint(entries: Optional[Sequence[IrEntry]] = None,
                baseline_path: Optional[str] = None,
                rules: Optional[Sequence[str]] = None) -> LintResult:
    """Analyze `entries` (default: the probe-built roster covering the
    package's jit entry points) and diff against the `ir_findings`
    baseline section. Mirrors engine.run_lint's contract so the CLI,
    metrics and tests treat both tiers uniformly.

    Raises RuntimeError on a single-device backend: with one device the
    virtual mesh degenerates, GSPMD inserts no collectives, and a
    "clean" run would have verified nothing — a silently green gate is
    worse than a loud environment error (set
    XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax
    initializes, as tests/conftest.py and tools/graftlint --ir do)."""
    import jax

    if jax.device_count() < 2:
        raise RuntimeError(
            f"graftlint IR pass needs a multi-device mesh, got "
            f"{jax.device_count()} device(s) — the sharding/collective "
            "rules cannot fire on one device and a clean run would "
            "verify nothing. Set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=8 (CPU) before jax initializes.")
    if entries is None:
        from .ir_probes import build_entries
        entries = build_entries()
    findings: List[Finding] = []
    for entry in entries:
        findings.extend(analyze_entry(entry))
    wanted = set(rules) if rules else None
    if wanted is not None:
        findings = [f for f in findings if f.rule in wanted]
    findings.sort(key=lambda f: (f.path, f.scope, f.rule, f.snippet))
    result = LintResult(findings=findings, files=len(list(entries)))
    baseline = load_baseline(baseline_path, section=IR_BASELINE_SECTION) \
        if baseline_path else {}
    if wanted is not None:
        baseline = {k: v for k, v in baseline.items()
                    if k.split("|", 1)[0] in wanted}
    result.new, result.stale_baseline = baseline_diff(findings, baseline)
    return result
