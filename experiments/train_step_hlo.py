"""Cell 1's train step (`nn/train_step` of `gpt2-124m.fit`) compiled by the
chip's own compiler, and what the fusion family `divide_subtract_fusion`
holds (PR 37: not Adam's update alone: the weight-gradient products with the
update as their epilogue; PERF.md section 5). Run it on the chip:

    python3 experiments/train_step_hlo.py             # the attached chip
    python3 experiments/train_step_hlo.py described   # a v5e that is described
    python3 experiments/train_step_hlo.py tiny        # a rehearsal on the CPU

It writes the optimised HLO to chiprun_out/train_step[.described].hlo.txt
and prints the family's count, kinds and one body's product. Nothing is
timed."""
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "benchmarks")]
import importlib.util

import jax
import jax.numpy as jnp


def load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"b_{kind}_{name}", ROOT / "benchmarks" / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


if "reparse" not in sys.argv:
    tiny = len(sys.argv) > 1 and sys.argv[1] == "tiny"
    config = json.loads((ROOT / "benchmarks/configs/gpt2-124m.json").read_text())
    if tiny:
        config = dict(config, n_layer=2, n_embd=64, n_head=2, vocab_size=256,
                      n_positions=64, assumed=dict(config["assumed"],
                                                   padded_vocab_size=256))
    ref, models = load("reference", config["family"]), load("models", config["family"])
    model = models.build(config, 1, ref, train=True)
    B, T = (2, 64) if tiny else (8, 1024)
    V = int(config["assumed"]["padded_vocab_size"])
    x = jax.ShapeDtypeStruct((B, T, 1), jnp.int32)
    y = jax.ShapeDtypeStruct((B, T, V), jnp.float32)
    step = jnp.asarray(0, jnp.int32)
    rng = jax.random.PRNGKey(0)
    args = (model.params, model.state, model.updater_state, step, x, y, rng)
    if "described" in sys.argv:
        # a v5e that is described, not attached (nothing runs)
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), args)
out = ROOT / "chiprun_out"
out.mkdir(parents=True, exist_ok=True)
name = out / ("train_step.described.hlo.txt" if "described" in sys.argv
              else "train_step.hlo.txt")
if "reparse" in sys.argv:
    text = name.read_text()
else:
    text = model._train_step.__wrapped__.lower(
        *args, None, None).compile().as_text()
    name.write_text(text)

# computations by name
comps = {}
for m in re.finditer(r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text,
                     re.S | re.M):
    comps[m.group(1)] = m.group(2)
family = []     # (name, shape, operands, kind, calls)
for line in text.split("\n"):
    m = re.match(r"\s*(?:ROOT )?%?(divide_subtract_fusion[.\d]*) = (.*?) fusion\(", line)
    if m:
        k = re.search(r"kind=(\w+), calls=%?([\w.\-]+)", line)
        family.append((m.group(1), m.group(2), "", k.group(1), k.group(2)))
print(f"device {jax.devices()[0].device_kind}; computations {len(comps)}; "
      f"divide_subtract_fusion instances {len(family)}")
with_conv = []
for name, shape, operands, kind, calls in family:
    body = comps.get(calls, "")
    conv = re.findall(r"= (\S+) convolution\(([^)]*)\)[^\n]*", body)
    if conv:
        with_conv.append((name, kind, shape[:60], conv[0][0]))
kinds = {}
for _, _, _, kind, _ in family:
    kinds[kind] = kinds.get(kind, 0) + 1
print("kinds", kinds, "; with a convolution in the body", len(with_conv),
      "of which kOutput", sum(k == "kOutput" for _, k, _, _ in with_conv))
for row in with_conv[:4] + with_conv[-2:]:
    print("  ", row)
if with_conv:
    body = comps[[c for n, _, _, _, c in family if n == with_conv[0][0]][0]]
    for l in body.split("\n"):
        if " convolution(" in l:
            print("  body line:", l.strip()[:700])
    print("  body instructions:", len(body.strip().split("\n")))
