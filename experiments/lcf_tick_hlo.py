"""Cell 3's served tick (`build_tick_fn` of `longcat-flash-chat.generate-write`
at bucket 32) compiled by the chip's own compiler, and what its heaviest
fusions hold (PERF.md section 5). The weights are shapes: nothing is made and
nothing runs.

    python3 experiments/lcf_tick_hlo.py [attention] [described]

`attention` is the tick's path (`mla_absorbed`, `mla_paged`; default: what
the stack's layers answer on this backend); `described` compiles for a v5e
that is described, not attached (on the CPU). It writes the optimised HLO to
chiprun_out/lcf_tick.<attention>.hlo.txt and prints, for each fusion whose
name holds `multiply_reduce` and for each `copy`, its shape, the bytes of its
operands and the source lines its metadata names; then the paged kernels'
custom calls. Nothing is timed."""
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

    described = "described" in argv
    names = [a for a in argv if a.startswith("mla_")]
    config = json.loads(
        (ROOT / "benchmarks/configs/longcat-flash-chat.json").read_text())
    ref, models = load("reference", "longcat_flash"), load("models",
                                                           "longcat_flash")
    shapes = SimpleNamespace(dims=ref.dims, init_params=lambda c, s: jax.eval_shape(
        functools.partial(ref.init_params, c, s)))
    model = models.build(config, 0, shapes, train=False)
    leaves, treedef = jax.tree_util.tree_flatten(model.params)
    snapshot = SimpleNamespace(
        data=tuple(leaves),
        rebuild=lambda data: jax.tree_util.tree_unflatten(treedef, list(data)))
    channels, width, context, _ = cache_geometry(model)
    spec = KvCacheSpec(channels=channels, width=width, block_len=16,
                       num_blocks=1 + 128 * 32, max_context=context,
                       kv_dtype="bf16")
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
    rows, w = 32, spec.table_width
    attention = names[0] if names else None
    fn = build_tick_fn(model, snapshot, spec, rows_max=rows,
                       attention=attention)
    with jax.enable_x64(False):
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            put(snapshot.data), put(_cache_arg_specs(spec)), i32(rows),
            i32(rows), i32(rows), i32(rows, w)).compile()
    text = compiled.as_text()
    out = ROOT / "chiprun_out"
    out.mkdir(parents=True, exist_ok=True)
    tag = attention or "default"
    (out / f"lcf_tick.{tag}.hlo.txt").write_text(text)
    mem = compiled.memory_analysis()
    print(f"tick bucket {rows}, attention {tag}, "
          f"{'described v5e' if described else jax.devices()[0].device_kind}:"
          f" temp {mem.temp_size_in_bytes} alias {mem.alias_size_in_bytes} "
          f"arena {spec.arena_nbytes()}")

    shapes_of = {}              # instruction -> its result's shape
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ", text, re.M):
        shapes_of.setdefault(m.group(1), m.group(2))
    lines = text.split("\n")
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]*multiply_reduce[\w.\-]*|copy[.\d]*)"
                     r" = (\S+) (fusion|copy)\(([^)]*)\)", line)
        if not m or m.group(1).startswith("copy") and m.group(3) != "copy":
            continue
        ops = [o.strip().lstrip("%") for o in m.group(4).split(",")]
        ops = [(o, shapes_of.get(o, "?")) for o in ops if o]
        src = re.findall(r'op_name="([^"]+)"', line)
        where = re.findall(r'source_file="[^"]*/([^"/]+)" source_line=(\d+)',
                           line)
        print(f"{m.group(1)}: {m.group(2)[:48]} <- "
              + ", ".join(f"{s}({nbytes(s)})" for _, s in ops)
              + f" | {src[:1]} {where[:1]}")
    kernels = re.findall(r"%(paged_\w+?)[.\d]* = \S+ custom-call\(", text)
    print("custom calls:", {k: kernels.count(k) for k in set(kernels)})


if __name__ == "__main__":
    main(sys.argv[1:])
