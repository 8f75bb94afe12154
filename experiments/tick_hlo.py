"""A served cell's tick (`build_tick_fn` at the cell's largest decode bucket)
compiled by the chip's own compiler, and what it holds (PERF.md section 5).
The weights are shapes: nothing is made and nothing runs.

    python3 experiments/tick_hlo.py <config> [attention] [experts] [window]
                                    [described]

`config` names `benchmarks/configs/<config>.json`: `longcat-flash-chat`
(cell 3, bucket 32), `granite-4.0-h-small` (cell 4, bucket 64),
`phi-4-mini-flash-reasoning` (cell 5, bucket 64) or
`nemotron-3-super-120b-a12b` (cell 6, bucket 128).
`attention` (`mla_absorbed`, `mla_paged`, `gather`, `paged_kernel`,
`diff_gather`, `diff_paged`), `experts` (`cond`, `grouped_kernel`) and
`window` (`ring_gather`, `ring_kernel`) are the tick's paths (default: what
the stack's layers answer on this backend; with `described`, the window
layers' `ring_kernel`, as they answer on the chip); `described` compiles for
a v5e that is described, not attached (on the CPU). It writes the optimised
HLO to .bench_out/hlo/<config>.tick.<attention>.<experts>[.<window>].hlo.txt
(git-ignored) and prints the number of conditionals, the ten heaviest
fusions by the bytes of their operands, every `copy` (with the bytes of its
operands and the source line its metadata names), and the kernels' custom
calls. Nothing is timed."""
import functools
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]

import jax
import jax.numpy as jnp

FAMILIES = {"longcat-flash-chat": ("longcat_flash", 32),
            "granite-4.0-h-small": ("granite_moe_hybrid", 64),
            "phi-4-mini-flash-reasoning": ("phi4flash", 64),
            "nemotron-3-super-120b-a12b": ("nemotron_h", 128)}
ATTENTIONS = ("mla_absorbed", "mla_paged", "mla_expanded", "gather",
              "paged_kernel", "diff_gather", "diff_paged")
EXPERTS = ("cond", "grouped_kernel")
WINDOWS = ("ring_gather", "ring_kernel")


def load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"b_{kind}_{name}", ROOT / "benchmarks" / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def nbytes(shape: str) -> int:
    m = re.match(r"(\w+)\[([\d,]*)\]", shape)
    if not m:
        return 0
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    bits = re.search(r"\d+$", m.group(1))
    return n * (int(bits.group()) if bits else 8) // 8


def main(argv):
    from deeplearning4j_tpu.serving.decode.cache import KvCacheSpec
    from deeplearning4j_tpu.serving.decode.engine import (_cache_arg_specs,
                                                          build_tick_fn,
                                                          cache_geometry)

    name = argv[0] if argv and argv[0] in FAMILIES else "longcat-flash-chat"
    family, rows = FAMILIES[name]
    described = "described" in argv
    attention = next((a for a in argv if a in ATTENTIONS), None)
    experts = next((a for a in argv if a in EXPERTS), None)
    ringed = FAMILIES[name][0] == "phi4flash"
    window = next((a for a in argv if a in WINDOWS),
                  "ring_kernel" if described and ringed else None)
    config = json.loads(
        (ROOT / "benchmarks" / "configs" / f"{name}.json").read_text())
    ref, models = load("reference", family), load("models", family)
    shapes = SimpleNamespace(
        dims=ref.dims, kinds=getattr(ref, "kinds", None),
        init_params=lambda c, s: jax.eval_shape(
            functools.partial(ref.init_params, c, s)))
    model = models.build(config, 0, shapes, train=False)
    leaves, treedef = jax.tree_util.tree_flatten(model.params)
    snapshot = SimpleNamespace(
        data=tuple(leaves),
        rebuild=lambda data: jax.tree_util.tree_unflatten(treedef, list(data)))
    channels, width, context, state = cache_geometry(model)
    table = context // 16
    spec = KvCacheSpec(channels=channels, width=width, block_len=16,
                       num_blocks=1 + table * rows, max_context=context,
                       kv_dtype="bf16", state=state,
                       state_slots=1 + rows if state else 0)
    if described:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    else:
        chip = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    put = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)
    paths = {"window_attention": window} if window else {}
    fn = build_tick_fn(model, snapshot, spec, rows_max=rows,
                       attention=attention, experts=experts, **paths)
    slots = (i32(rows),) if state else ()
    with jax.enable_x64(False):
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            put(snapshot.data), put(_cache_arg_specs(spec)), i32(rows),
            i32(rows), i32(rows), i32(rows, spec.table_width),
            *slots).compile()
    text = compiled.as_text()
    out = ROOT / ".bench_out" / "hlo"
    out.mkdir(parents=True, exist_ok=True)
    tag = f"{attention or 'default'}.{experts or 'default'}" \
        + (f".{window}" if window else "")
    (out / f"{name}.tick.{tag}.hlo.txt").write_text(text)
    mem = compiled.memory_analysis()
    print(f"{name} tick bucket {rows}, {tag}, "
          f"{'described v5e' if described else jax.devices()[0].device_kind}:"
          f" temp {mem.temp_size_in_bytes} alias {mem.alias_size_in_bytes} "
          f"arena {spec.arena_nbytes()}")
    print("conditionals:", len(re.findall(r" conditional\(", text)))

    shapes_of = {}              # instruction -> its result's shape
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ", text, re.M):
        shapes_of.setdefault(m.group(1), m.group(2))
    fusions, copies = [], []
    for line in text.split("\n"):
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\S+) (fusion|copy)"
                     r"\(([^)]*)\)", line)
        if not m:
            continue
        ops = [o.strip().lstrip("%") for o in m.group(4).split(",")]
        ops = [shapes_of.get(o, "?") for o in ops if o]
        where = re.findall(r'source_file="[^"]*/([^"/]+)" source_line=(\d+)',
                           line)
        row = (sum(map(nbytes, ops)), m.group(1), m.group(2)[:48], ops,
               where[:1])
        (copies if m.group(3) == "copy" else fusions).append(row)
    for title, rows_ in (("heaviest fusions", sorted(fusions)[::-1][:10]),
                         ("copies", copies)):
        print(f"{title}:")
        for size, op, shape, ops, where in rows_:
            print(f"  {op}: {shape} <- {', '.join(ops)[:120]} ({size} bytes)"
                  f" {where}")
    kernels = re.findall(r"%(paged_\w+?|ring_\w+?|grouped_experts)[.\d]* = "
                         r"\S+ custom-call\(", text)
    print("custom calls:", {k: kernels.count(k) for k in set(kernels)})


if __name__ == "__main__":
    main(sys.argv[1:])
