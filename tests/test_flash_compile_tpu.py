"""The flash kernels compiled for a TPU v5e that is described, not attached:
the chip's own compiler (Mosaic, libtpu) takes each tiling the chooser
derives at real widths, so a slice off the (8, 128) tiling or a step over
the VMEM limit fails here and not on the chip. So does the tick's paged
attention kernel at the served cell's shape, in every tick bucket. The
decode plane's two steps are compiled the same way over an abstract,
donated paged cache, and held to updating it in place: the tick on both of
its attention paths. Nothing runs: no result and no time comes from this
file.

All of these tests live in this one file, and the topology is described
inside a fixture, because only one process at a time may load the TPU's
library."""
import re

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.kernels.attention import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("B,T,S,Dh,dtype,causal,blocks", [
    (96, 1024, 1024, 64, "bfloat16", True, None),    # GPT-2 124M, B 8 x 12 heads
    (8, 1000, 1000, 64, "bfloat16", True, None),     # ragged causal tail
    (2, 2048, 2048, 128, "bfloat16", False, None),   # Dh 128, four chunks
    (4, 1024, 1024, 64, "float32", True, None),
    (2, 100, 100, 64, "bfloat16", False, None),      # one padded tile
    (2, 8192, 8192, 128, "bfloat16", True, None),    # 16 resident chunks
    (1, 32768, 32768, 128, "bfloat16", True, None),  # K/V streamed in blocks
    (2, 512, 1536, 64, "bfloat16", False, 128),      # the ring's off-diagonal block
])
def test_flash_kernels_compile_for_v5e(one_chip, B, T, S, Dh, dtype, causal,
                                       blocks):
    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal, block_q=blocks, block_k=blocks,
            interpret=False).astype(jnp.float32))

    arg = lambda n: jax.ShapeDtypeStruct((B, n, Dh), dtype, sharding=one_chip)
    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    # the suite's conftest turns x64 on for its gradient checks; nothing on
    # the chip runs with it, and Mosaic's lowering does not take 64-bit indices
    with jax.enable_x64(False):
        lowered = step.lower(arg(T), arg(S), arg(S))
        text = lowered.as_text()
        for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            assert name in text
        assert lowered.compile() is not None


@pytest.mark.parametrize("rows", [1, 2, 4, 8, 16])
@pytest.mark.parametrize("heads", [16, 12], ids=["HDh1024", "HDh768"])
def test_paged_attention_kernel_compiles_for_v5e(one_chip, rows, heads):
    """The tick's kernel at the served cell's cache geometry (1,025 blocks
    of 16 slots, tables 64 wide, 24 layers) for each tick bucket's row
    count; the arena is abstract and is not copied: the program holds no
    temporary."""
    from deeplearning4j_tpu.kernels.paged_attention import (
        paged_decode_attention)

    arg = lambda dtype, *s: jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    width = heads * 64
    step = jax.jit(lambda q, kv, c, tables, lengths: paged_decode_attention(
        q, kv, c, tables, lengths, n_heads=heads, interpret=False))
    with jax.enable_x64(False):
        lowered = step.lower(
            arg(jnp.float32, rows, width),
            arg(jnp.float32, 48, 1025, 16, width), arg(jnp.int32),
            arg(jnp.int32, rows, 64), arg(jnp.int32, rows))
        assert "paged_decode_attention" in lowered.as_text()
        compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("rows", [8, 64])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_grouped_paged_attention_kernel_compiles_for_v5e(one_chip, rows,
                                                         dtype):
    """The kernel with grouped queries at the Granite cell's geometry: 32
    query heads on 8 key/value heads of 128, an arena of 2 channels x 8,193
    pages of 16 slots x 1,024 lanes (bfloat16 as served), tables 128 wide.
    The arena is abstract and is not copied: what the program holds beside
    it is the block-diagonal query and the output, 128 kB a row each."""
    from deeplearning4j_tpu.kernels.paged_attention import (
        paged_decode_attention)

    arg = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    step = jax.jit(lambda q, kv, c, tables, lengths: paged_decode_attention(
        q, kv, c, tables, lengths, n_heads=32, n_kv_heads=8,
        sm_scale=0.0078125, interpret=False))
    with jax.enable_x64(False):
        lowered = step.lower(
            arg(jnp.float32, rows, 4096), arg(dtype, 2, 8193, 16, 1024),
            arg(jnp.int32), arg(jnp.int32, rows, 128), arg(jnp.int32, rows))
        assert "paged_decode_attention" in lowered.as_text()
        compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * rows * 32 * 1024 * 4 + (1 << 20)


@pytest.fixture(scope="module")
def decode_stack():
    """(model, snapshot, spec) at the served cell's cache geometry: width
    1024 in 16 heads, blocks of 16 slots, 1,025 of them, table width 64.
    Twelve blocks deep with the narrowest FFN and a 256-token vocabulary,
    so that no array over 4 MiB is made: the 1.5 GiB arena is abstract."""
    from deeplearning4j_tpu import (EmbeddingSequenceLayer, InputType,
                                    MultiLayerNetwork,
                                    NeuralNetConfiguration, RnnOutputLayer,
                                    Sgd, TransformerBlock)
    from deeplearning4j_tpu.serving.decode.cache import KvCacheSpec
    from deeplearning4j_tpu.serving.registry import _snapshot_params

    depth = 12
    b = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(0.0)).list()
         .layer(EmbeddingSequenceLayer(n_in=256, n_out=1024)))
    for _ in range(depth):
        b = b.layer(TransformerBlock(n_heads=16, ffn_mult=1))
    conf = (b.layer(RnnOutputLayer(n_out=256, activation="softmax",
                                   loss="mcxent"))
            .set_input_type(InputType.recurrent(1, 1024)).build())
    model = MultiLayerNetwork(conf).init()
    spec = KvCacheSpec(channels=2 * depth, width=1024, block_len=16,
                       num_blocks=1025, max_context=1024)
    return model, _snapshot_params(model, "fp32"), spec


@pytest.mark.parametrize("phase,bucket,attention", [
    ("tick", 16, "gather"), ("tick", 16, "paged_kernel"),
    ("prefill", 512, None)] + [
    ("served_tick", b, "paged_kernel") for b in (1, 2, 4, 8, 16)])
def test_decode_steps_update_the_arena_in_place_on_v5e(one_chip, decode_stack,
                                                       phase, bucket,
                                                       attention):
    """The paged cache is donated and written by scatters, so a step may
    hold no temporary of the arena's size: the device keeps the arena
    row-major as it arrives (a last dimension of H*Dh is whole lane tiles),
    the program neither converts nor copies it, and the output aliases the
    input. With the arena `[num_blocks, block_len, 2L, H, Dh]` that PR 31
    replaced, the device kept `num_blocks` minor and both steps converted
    the arena on entry and back on exit: the tick held 1.94 times the arena
    in temporaries at the served model's 24 blocks (6.55 GiB beside 3.375).
    What stays on the tick's gather path, whatever the depth, is the
    gathered view of one layer (16 rows x 1,024 slots x 1,024 floats,
    64 MiB, four of them live): a twelfth of a 24-deep arena, a sixth of
    this one. The paged kernel reads the arena where it lies: no view, no
    head-split relayout, under 32 MiB of temporaries, and the kernel's 16
    page operands do not make XLA copy the arena it is about to scatter
    into again. The tick as it is served (`build_tick_fn`: the tokens
    selected on the device from the last tick's ids, the argmax beside the
    logits) is held to the same in every bucket of the served cell."""
    import functools

    from deeplearning4j_tpu.serving.decode.engine import (_cache_arg_specs,
                                                          build_decode_fn,
                                                          build_prefill_fn,
                                                          build_tick_fn)

    model, snapshot, spec = decode_stack
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    w = spec.table_width
    if phase == "tick":
        fn = functools.partial(build_decode_fn, attention=attention)
        args = (i32(bucket), i32(bucket), i32(bucket, w))
    elif phase == "served_tick":
        fn = functools.partial(build_tick_fn, rows_max=16, attention=attention)
        args = (i32(16), i32(bucket), i32(bucket), i32(bucket, w))
    else:
        fn, args = build_prefill_fn, (i32(1, bucket), i32(1), i32(1, w))
    with jax.enable_x64(False):
        compiled = jax.jit(fn(model, snapshot, spec), donate_argnums=(1,)).lower(
            on_chip(snapshot.data), on_chip(_cache_arg_specs(spec)),
            *args).compile()
    arena = spec.arena_nbytes()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < arena / 4
    assert mem.alias_size_in_bytes >= arena
    text = compiled.as_text()
    dims = (spec.channels, spec.num_blocks, spec.block_len, spec.width)
    shape = "f32[%d,%d,%d,%d]" % dims
    # row-major on entry (and so, aliased, on exit): {3,2,1,0}
    layout = text[text.index("entry_computation_layout="):].split("\n")[0]
    assert shape + "{3,2,1,0:" in layout
    assert shape + "{" not in layout.replace(shape + "{3,2,1,0:", "")
    big = [m for m in re.finditer(
        r"= (\w+)\[([\d,]*)\]\S* copy\(", text)
        if _nbytes(m.group(1), m.group(2)) >= arena / spec.channels]
    assert not big, [m.group(0) for m in big]
    kernels = re.findall(r"%paged_decode_attention[.\d]* = \S+ custom-call\(",
                         text)
    assert len(kernels) == (spec.channels // 2 if attention == "paged_kernel"
                            else 0)
    if attention == "paged_kernel":
        assert mem.temp_size_in_bytes < 32 << 20
        # neither the gathered view nor its heads split out
        assert "f32[1024,16,1024]" not in text
        assert "[16,1024,16,64]" not in text
    if phase == "served_tick":      # (cache, ids [16], logits, ...)
        assert [o.shape for o in compiled.out_info[1:3]] == [(16,),
                                                             (bucket, 256)]


def _nbytes(dtype: str, dims: str) -> int:
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    bits = re.search(r"\d+$", dtype)          # f32, bf16, s8; pred has none
    return n * (int(bits.group()) if bits else 8) // 8


@pytest.fixture(scope="module")
def hybrid_stack():
    """(model, snapshot, spec) of three Mamba-2 layers and the grouped-query
    attention layer at Granite 4.0-H's widths (4096 wide, 128 heads of 64
    with a state of 128, 32 queries on 8 key/value heads of 128), 4 of the
    72 experts held and a 256-token vocabulary, over the served cell's 65
    state slots (0.84 GB of state) and a page table of 256 positions. The
    weights are shapes: nothing is made."""
    import functools
    import importlib.util
    import json
    from pathlib import Path
    from types import SimpleNamespace

    from deeplearning4j_tpu.serving.decode.cache import KvCacheSpec
    from deeplearning4j_tpu.serving.decode.engine import cache_geometry

    bench = Path(__file__).resolve().parents[1] / "benchmarks"

    def load(kind):
        spec = importlib.util.spec_from_file_location(
            f"compile_test_{kind}", bench / kind / "granite_moe_hybrid.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    ref, models = load("reference"), load("models")
    real = json.loads(
        (bench / "configs" / "granite-4.0-h-small.json").read_text())
    config = dict(
        real, num_hidden_layers=4,
        layer_types=["mamba", "attention", "mamba", "mamba"],
        num_local_experts=4, vocab_size=256, max_position_embeddings=256,
        deployment=dict(real["deployment"], held_experts=[0, 4]))
    shapes = SimpleNamespace(
        dims=ref.dims, init_params=lambda c, s: jax.eval_shape(
            functools.partial(ref.init_params, c, s)))
    model = models.build(config, 0, shapes, train=False)
    leaves, treedef = jax.tree_util.tree_flatten(model.params)
    snapshot = SimpleNamespace(
        data=tuple(leaves),
        rebuild=lambda data: jax.tree_util.tree_unflatten(treedef, list(data)))
    channels, width, context, state = cache_geometry(model)
    spec = KvCacheSpec(channels=channels, width=width, block_len=16,
                       num_blocks=1 + 16 * 64, max_context=context,
                       kv_dtype="bf16", state=state, state_slots=65)
    return model, snapshot, spec


@pytest.mark.parametrize("phase,bucket,attention", [
    ("tick", 64, "gather"), ("tick", 64, "paged_kernel"),
    ("tick", 8, "paged_kernel"), ("prefill", 512, None),
    ("served_tick", 64, "paged_kernel"), ("served_tick", 8, "paged_kernel")])
def test_decode_steps_update_the_state_in_place_on_v5e(one_chip, hybrid_stack,
                                                       phase, bucket,
                                                       attention):
    """The per-sequence state is donated with the arena and every step
    writes it where it lies. A 64-row tick (a quarter of the slots or more) runs the
    recurrence over every slot in one elementwise pass that reads a layer's
    leaf and writes it, the rows' output in the same fusion: no row gathered
    out, none scattered back. An 8-row tick gathers its 8 rows' states (34 MB
    a layer) and scatters them back. A prefill writes one slot. None holds a
    temporary of a quarter of the state, none copies a layer's leaf, and
    the outputs alias the inputs. The attention layer's tick through the
    paged kernel (grouped queries, bfloat16 pages) makes no view of its
    pages. The tick as it is served (`build_tick_fn`) is held to the same."""
    import functools

    from deeplearning4j_tpu.serving.decode.engine import (_cache_arg_specs,
                                                          build_decode_fn,
                                                          build_prefill_fn,
                                                          build_tick_fn)

    model, snapshot, spec = hybrid_stack
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    w = spec.table_width
    if phase == "tick":
        fn = functools.partial(build_decode_fn, attention=attention)
        args = (i32(bucket), i32(bucket), i32(bucket, w), i32(bucket))
    elif phase == "served_tick":
        fn = functools.partial(build_tick_fn, rows_max=64, attention=attention)
        args = (i32(64), i32(bucket), i32(bucket), i32(bucket, w), i32(bucket))
    else:
        fn, args = build_prefill_fn, (i32(1, bucket), i32(1), i32(1, w), i32(1))
    with jax.enable_x64(False):
        compiled = jax.jit(fn(model, snapshot, spec), donate_argnums=(1,)).lower(
            on_chip(snapshot.data), on_chip(_cache_arg_specs(spec)),
            *args).compile()
    state, leaf = spec.state_nbytes(), 65 * 128 * 64 * 128 * 4
    assert state == 3 * (leaf + 3 * 65 * 8448 * 4) > 0.8e9
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < state / 4
    assert mem.alias_size_in_bytes >= state + spec.arena_nbytes()
    text = compiled.as_text()
    copies = [m for m in re.finditer(r"= (\w+)\[([\d,]*)\]\S* copy\(", text)
              if _nbytes(m.group(1), m.group(2)) >= leaf]
    assert not copies, [m.group(0) for m in copies]
    kernels = re.findall(r"%paged_decode_attention[.\d]* = \S+ custom-call\(",
                         text)
    assert len(kernels) == (attention == "paged_kernel")
    if attention == "paged_kernel":     # no gathered view of the 16 pages
        assert f"bf16[{bucket},16,16,1024]" not in text
    if bucket == 64:
        # one fusion a layer gives the rows' output and the new leaf
        assert len(re.findall(
            r"= \(f32\[65,128,64\]\S*, f32\[65,128,64,128\]\S*\) fusion\(",
            text)) == 3
        assert "f32[64,128,64,128]" not in text


@pytest.mark.parametrize("rows", [1, 8, 32])
def test_latent_paged_attention_kernel_compiles_for_v5e(one_chip, rows):
    """The latent kernel at the LongCat-Flash cell's geometry: 64 heads over
    640-lane bfloat16 pages of 16 slots, an arena of 8 channels x 4,097
    blocks, tables 128 wide, the weighted sum over the first 512 lanes. The
    arena is abstract and is not copied."""
    from deeplearning4j_tpu.kernels.paged_attention import (
        paged_latent_attention)

    arg = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    step = jax.jit(lambda q, kv, c, tables, lengths: paged_latent_attention(
        q, kv, c, tables, lengths, v_width=512, sm_scale=192 ** -0.5,
        interpret=False))
    with jax.enable_x64(False):
        lowered = step.lower(
            arg(jnp.bfloat16, rows, 64, 640), arg(jnp.bfloat16, 8, 4097, 16, 640),
            arg(jnp.int32), arg(jnp.int32, rows, 128), arg(jnp.int32, rows))
        assert "paged_latent_attention" in lowered.as_text()
        compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.fixture(scope="module")
def latent_stack():
    """(model, snapshot, spec) of one LongCat-Flash block at the cell's
    widths (6144 wide, 64 heads, ranks 1536 / 512, heads 128 + 64 / 128; 2
    of the 16 held experts and a 256-token vocabulary) over the cell's
    latent arena, 4,097 blocks of 16 slots x 640 bfloat16 lanes, two
    channels. The weights are shapes: nothing is made."""
    import functools
    import importlib.util
    import json
    from pathlib import Path
    from types import SimpleNamespace

    from deeplearning4j_tpu.serving.decode.cache import KvCacheSpec
    from deeplearning4j_tpu.serving.decode.engine import cache_geometry

    bench = Path(__file__).resolve().parents[1] / "benchmarks"

    def load(kind):
        spec = importlib.util.spec_from_file_location(
            f"compile_test_lcf_{kind}", bench / kind / "longcat_flash.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    ref, models = load("reference"), load("models")
    real = json.loads(
        (bench / "configs" / "longcat-flash-chat.json").read_text())
    config = dict(real, num_layers=1, n_routed_experts=2, vocab_size=256,
                  deployment=dict(real["deployment"], held_experts=[0, 2]))
    shapes = SimpleNamespace(
        dims=ref.dims, init_params=lambda c, s: jax.eval_shape(
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
    return model, snapshot, spec


@pytest.mark.parametrize("phase,attention", [
    ("tick", "mla_paged"), ("served_tick", "mla_paged"),
    ("tick", "mla_absorbed")])
def test_latent_ticks_update_the_arena_in_place_on_v5e(one_chip, latent_stack,
                                                       phase, attention):
    """A 32-row tick of the latent block, as the TPU serves it (`mla_paged`):
    one `paged_latent_attention` custom call an attention, reading the
    pages where they lie, and no gathered view of them (the view path,
    `mla_absorbed`, holds one `[32, 128, 16, 640]` gather a channel, 84 MB
    of temporaries); the arena is donated, aliased, neither copied nor
    converted."""
    import functools

    from deeplearning4j_tpu.serving.decode.engine import (_cache_arg_specs,
                                                          build_decode_fn,
                                                          build_tick_fn)

    model, snapshot, spec = latent_stack
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    rows, w = 32, spec.table_width
    if phase == "tick":
        fn = functools.partial(build_decode_fn, attention=attention)
        args = (i32(rows), i32(rows), i32(rows, w))
    else:
        fn = functools.partial(build_tick_fn, rows_max=rows,
                               attention=attention)
        args = (i32(rows), i32(rows), i32(rows), i32(rows, w))
    with jax.enable_x64(False):
        compiled = jax.jit(fn(model, snapshot, spec), donate_argnums=(1,)).lower(
            on_chip(snapshot.data), on_chip(_cache_arg_specs(spec)),
            *args).compile()
    arena, view = spec.arena_nbytes(), rows * w * 16 * 640 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= arena
    text = compiled.as_text()
    layout = text[text.index("entry_computation_layout="):].split("\n")[0]
    assert "bf16[2,4097,16,640]{3,2,1,0:" in layout
    big = [m for m in re.finditer(r"= (\w+)\[([\d,]*)\]\S* copy\(", text)
           if _nbytes(m.group(1), m.group(2)) >= arena / spec.channels]
    assert not big, [m.group(0) for m in big]
    kernels = re.findall(r"%paged_latent_attention[.\d]* = \S+ custom-call\(",
                         text)
    views = re.findall(r"= bf16\[32,128,16,640\]\S* gather\(", text)
    if attention == "mla_paged":
        assert (len(kernels), len(views)) == (spec.channels, 0)
        assert mem.temp_size_in_bytes < view / 2
    else:                       # the view, 84 MB, is the largest temporary
        assert (len(kernels), len(views)) == (0, spec.channels)
        assert mem.temp_size_in_bytes > view


@pytest.mark.parametrize("rows", [8, 64])
def test_diff_paged_attention_kernel_compiles_for_v5e(one_chip, rows):
    """The differential kernel at the Phi-4-mini-flash cell's geometry: 20
    heads of query pairs (40 rows, padded to 48) on 10 key/value heads whose
    keys are pairs of 64 and values 128, over bfloat16 pages of 16 slots x
    1,280 lanes, an arena of 2 channels x 16,385 blocks, tables 256 wide. The
    arena is abstract and is not copied."""
    from deeplearning4j_tpu.kernels.paged_attention import paged_diff_attention

    arg = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    step = jax.jit(lambda q, kv, c, tables, lengths: paged_diff_attention(
        q, kv, c, tables, lengths, n_kv_heads=10, sm_scale=0.125,
        interpret=False))
    with jax.enable_x64(False):
        lowered = step.lower(
            arg(jnp.float32, rows, 20, 2, 64),
            arg(jnp.bfloat16, 2, 16385, 16, 1280), arg(jnp.int32),
            arg(jnp.int32, rows, 256), arg(jnp.int32, rows))
        assert "paged_diff_attention" in lowered.as_text()
        compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2 * rows * 48 * 1280 * 4 + (1 << 20)


@pytest.fixture(scope="module")
def sambay_stack():
    """(model, snapshot, spec) of Phi-4-mini-flash-reasoning at its widths
    (2,560 wide, Mamba 5,120 x 16, 20 differential heads on 10 key/value
    heads, a window of 512, MLP 10,240) in 8 layers: Mamba, window, Mamba,
    window | Mamba, full, GMU, cross; a 256-token vocabulary; over the cell's
    65 state slots and its arena of 16,385 pages of 16 x 1,280 bfloat16
    lanes. The weights are shapes: nothing is made."""
    import functools
    import importlib.util
    import json
    from pathlib import Path
    from types import SimpleNamespace

    from deeplearning4j_tpu.serving.decode.cache import KvCacheSpec
    from deeplearning4j_tpu.serving.decode.engine import cache_geometry

    bench = Path(__file__).resolve().parents[1] / "benchmarks"

    def load(kind):
        spec = importlib.util.spec_from_file_location(
            f"compile_test_p4f_{kind}", bench / kind / "phi4flash.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    ref, models = load("reference"), load("models")
    config = dict(json.loads(
        (bench / "configs" / "phi-4-mini-flash-reasoning.json").read_text()),
        num_hidden_layers=8, vocab_size=256)
    shapes = SimpleNamespace(
        dims=ref.dims, kinds=ref.kinds, init_params=lambda c, s: jax.eval_shape(
            functools.partial(ref.init_params, c, s)))
    model = models.build(config, 0, shapes, train=False)
    leaves, treedef = jax.tree_util.tree_flatten(model.params)
    snapshot = SimpleNamespace(
        data=tuple(leaves),
        rebuild=lambda data: jax.tree_util.tree_unflatten(treedef, list(data)))
    channels, width, context, state = cache_geometry(model)
    spec = KvCacheSpec(channels=channels, width=width, block_len=16,
                       num_blocks=1 + 256 * 64, max_context=context,
                       kv_dtype="bf16", state=state, state_slots=65)
    return model, snapshot, spec


@pytest.mark.parametrize("phase,bucket", [("served_tick", 64), ("tick", 8),
                                          ("prefill", 1024)])
def test_sambay_steps_update_arena_rings_and_state_in_place_on_v5e(
        one_chip, sambay_stack, phase, bucket):
    """The cell's prefill and tick, compiled for a described v5e: the arena
    (1.34 GB), the window rings (85 MB a layer) and the Mamba state are
    donated and written where they lie; no program holds a temporary of a
    quarter of them or copies a ring or a layer's state. The tick reads the
    shared pages through the differential kernel, once for each of the two
    layers that read them here, and makes no view of them."""
    import functools

    from deeplearning4j_tpu.serving.decode.engine import (_cache_arg_specs,
                                                          build_decode_fn,
                                                          build_prefill_fn,
                                                          build_tick_fn)

    model, snapshot, spec = sambay_stack
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    w = spec.table_width
    if phase == "served_tick":
        fn = functools.partial(build_tick_fn, rows_max=64,
                               attention="diff_paged")
        args = (i32(64), i32(bucket), i32(bucket), i32(bucket, w), i32(bucket))
    elif phase == "tick":
        fn = functools.partial(build_decode_fn, attention="diff_paged")
        args = (i32(bucket), i32(bucket), i32(bucket, w), i32(bucket))
    else:
        fn, args = build_prefill_fn, (i32(1, bucket), i32(1), i32(1, w), i32(1))
    with jax.enable_x64(False):
        compiled = jax.jit(fn(model, snapshot, spec), donate_argnums=(1,)).lower(
            on_chip(snapshot.data), on_chip(_cache_arg_specs(spec)),
            *args).compile()
    ring, ssm = 65 * 512 * 1280 * 2, 65 * 5120 * 16 * 4
    held = spec.state_nbytes() + spec.arena_nbytes()
    assert spec.state_nbytes() == 2 * 2 * ring + 3 * (ssm + 3 * 65 * 5120 * 4)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < held / 4
    assert mem.alias_size_in_bytes >= held
    text = compiled.as_text()
    copies = [m for m in re.finditer(r"= (\w+)\[([\d,]*)\]\S* copy\(", text)
              if _nbytes(m.group(1), m.group(2)) >= ssm]
    assert not copies, [m.group(0) for m in copies]
    kernels = re.findall(r"%paged_diff_attention[.\d]* = \S+ custom-call\(",
                         text)
    assert len(kernels) == (0 if phase == "prefill" else 2)
    assert f"bf16[{bucket},256,16,1280]" not in text


@pytest.mark.parametrize("rows", [8, 64])
def test_ring_diff_attention_kernel_compiles_for_v5e(one_chip, rows):
    """The window layers' kernel at the Phi-4-mini-flash cell's geometry: 20
    heads of query pairs on 10 key/value heads, the projection's 2,560 lanes
    as they are, over bfloat16 rings of 65 slots x 512 x 1,280 lanes. The
    rings are abstract and are not copied: the program holds the rows'
    queries and outputs beside them."""
    from deeplearning4j_tpu.kernels.ring_attention import (ring_diff_attention,
                                                           ring_plan)

    arg = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    step = jax.jit(lambda q, k, v, slot, live: ring_diff_attention(
        q, k, v, slot, live, n_heads=20, n_kv_heads=10, sm_scale=0.125,
        interpret=False))
    ring = arg(jnp.bfloat16, 65, 512, 1280)
    with jax.enable_x64(False):
        lowered = step.lower(arg(jnp.float32, rows, 2560), ring, ring,
                             arg(jnp.int32, rows), arg(jnp.int32, rows))
        assert "ring_diff_attention" in lowered.as_text()
        compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert ring_plan(rows, 512, 20, 10, 1280).vmem_bytes < 16 << 20


def test_sambay_tick_reads_the_rings_in_place_through_the_ring_kernel_on_v5e(
        one_chip, sambay_stack):
    """The cell's served tick with the window layers' `ring_kernel`: one
    `ring_diff_attention` call a window layer beside the shared pages' two
    `paged_diff_attention` calls; no block-diagonal query of the rings' 65
    slots (`[65, 40, 1280]`) or its scores (`[65, 40, 512]`) left, no copy
    of a ring, the window layers' `W_q` not relaid out (the cross layers'
    prologue still copies its own), the rings and the arena aliased."""
    import functools

    from deeplearning4j_tpu.serving.decode.engine import (_cache_arg_specs,
                                                          build_tick_fn)

    model, snapshot, spec = sambay_stack
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    w = spec.table_width
    texts = {}
    for window in ("ring_kernel", "ring_gather"):
        fn = functools.partial(build_tick_fn, rows_max=64,
                               attention="diff_paged",
                               window_attention=window)
        with jax.enable_x64(False):
            compiled = jax.jit(fn(model, snapshot, spec),
                               donate_argnums=(1,)).lower(
                on_chip(snapshot.data), on_chip(_cache_arg_specs(spec)),
                i32(64), i32(64), i32(64), i32(64, w), i32(64)).compile()
        texts[window] = compiled.as_text()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= spec.state_nbytes() \
            + spec.arena_nbytes()
    text = texts["ring_kernel"]
    calls = lambda name, t: len(re.findall(
        rf"%{name}[.\d]* = \S+ custom-call\(", t))
    assert (calls("ring_diff_attention", text),
            calls("paged_diff_attention", text)) == (2, 2)
    assert calls("ring_diff_attention", texts["ring_gather"]) == 0
    for shape in ("[65,40,1280]", "[65,40,512]", "[64,40,512]"):
        assert shape not in text, shape
    assert "[64,40,512]" in texts["ring_gather"]
    copies = lambda t: [m.group(0) for m in re.finditer(
        r"= (\w+)\[([\d,]*)\]\S* copy\(", t)
        if _nbytes(m.group(1), m.group(2)) >= 2560 * 2560 * 2]
    # a W_q relaid out a layer: the shared pages' two readers' alone
    assert len(copies(text)) == 2 < len(copies(texts["ring_gather"])), \
        copies(text)
    assert not any("[65,512,1280]" in c for c in copies(text))


@pytest.mark.parametrize("rows,d,h,experts", [
    (64, 4096, 768, 36), (1, 4096, 768, 36), (32, 6144, 2048, 16),
    (8, 6144, 2048, 16)], ids=["g4h-64", "g4h-1", "lcf-32", "lcf-8"])
def test_grouped_experts_kernel_compiles_for_v5e(one_chip, rows, d, h,
                                                 experts):
    """The held experts' kernel at the two expert cells' shapes, bfloat16
    as served: Granite 4.0-H's 36 experts of 4096 x 768 whole a grid step,
    LongCat-Flash's 16 of 6144 x 2048 in tiles of 512, inside the VMEM limit
    its plan states. The experts are abstract and are not copied: the
    program holds the rows and their output beside them."""
    from deeplearning4j_tpu.kernels.grouped_experts import (experts_plan,
                                                            grouped_experts)

    plan = experts_plan(rows, d, h, experts, 2)
    assert plan.vmem_bytes < plan.vmem_limit_bytes
    arg = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    step = jax.jit(lambda u, w, loads, g, up, dn: grouped_experts(
        u, w, loads, g, up, dn, interpret=False))
    with jax.enable_x64(False):
        lowered = step.lower(
            arg(jnp.float32, rows, d), arg(jnp.float32, rows, experts),
            arg(jnp.int32, experts), arg(jnp.bfloat16, experts, d, h),
            arg(jnp.bfloat16, experts, d, h), arg(jnp.bfloat16, experts, h, d))
        assert "grouped_experts" in lowered.as_text()
        compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("stack", ["hybrid_stack", "latent_stack"])
def test_expert_kernel_ticks_hold_no_conditional_and_no_copy_on_v5e(
        one_chip, stack, request):
    """The tick as the TPU serves it (`experts` "grouped_kernel"): one
    `grouped_experts` custom call an expert layer, no conditional left (the
    "cond" path holds one an expert and layer: `experiments/tick_hlo.py`),
    no copy of an expert leaf, the cache still aliased."""
    import functools

    from deeplearning4j_tpu.serving.decode.engine import (_cache_arg_specs,
                                                          build_tick_fn)

    model, snapshot, spec = request.getfixturevalue(stack)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    rows, w = (64, spec.table_width) if spec.state else (32, spec.table_width)
    attention = "paged_kernel" if spec.state else "mla_paged"
    layers = [b for b in model.layers if hasattr(b, "decode_experts")]
    held = layers[0].experts().held()
    leaves = {",".join(map(str, a.shape)) for a in snapshot.data
              if a.ndim == 3 and a.shape[0] == len(held)}
    fn = functools.partial(build_tick_fn, rows_max=rows, attention=attention,
                           experts="grouped_kernel")
    args = (i32(rows), i32(rows), i32(rows), i32(rows, w)) \
        + ((i32(rows),) if spec.state else ())
    with jax.enable_x64(False):
        compiled = jax.jit(fn(model, snapshot, spec), donate_argnums=(1,)).lower(
            on_chip(snapshot.data), on_chip(_cache_arg_specs(spec)),
            *args).compile()
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= spec.arena_nbytes() + spec.state_nbytes()
    text = compiled.as_text()
    kernels = re.findall(r"%grouped_experts[.\d]* = \S+ custom-call\(", text)
    assert len(kernels) == len(layers)
    assert " conditional(" not in text
    copies = [m.group(0) for m in re.finditer(r"= \w+\[([\d,]*)\]\S* copy\(",
                                              text) if m.group(1) in leaves]
    assert len(leaves) == 2 and not copies, copies


@pytest.mark.parametrize("rows", [128, 1], ids=["n3s-128", "n3s-1"])
def test_relu2_experts_kernel_compiles_for_v5e(one_chip, rows):
    """The held experts' kernel in its relu^2 form at Nemotron 3 Super's
    shape, bfloat16 as served: 128 experts of two matrices, 1,024 x 2,688
    in the latent, whole a grid step, 128 rows (the cell's tick) or one."""
    from deeplearning4j_tpu.kernels.grouped_experts import (experts_plan,
                                                            grouped_experts)

    d, h, experts = 1024, 2688, 128
    plan = experts_plan(rows, d, h, experts, 2, matrices=2)
    assert plan.tile == h and plan.vmem_bytes < plan.vmem_limit_bytes
    arg = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    step = jax.jit(lambda u, w, loads, up, dn: grouped_experts(
        u, w, loads, None, up, dn, interpret=False))
    with jax.enable_x64(False):
        lowered = step.lower(
            arg(jnp.float32, rows, d), arg(jnp.float32, rows, experts),
            arg(jnp.int32, experts), arg(jnp.bfloat16, experts, d, h),
            arg(jnp.bfloat16, experts, h, d))
        assert "grouped_experts" in lowered.as_text()
        compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 20


@pytest.fixture(scope="module")
def nemotron_stack():
    """(model, snapshot, spec) of one Mamba-2 layer (8 groups), the
    attention layer and one LatentMoE layer at Nemotron 3 Super's widths
    (4096 wide, 128 heads of 64 with a state of 128, 32 queries on 2
    key/value heads of 128, experts of 1,024 x 2,688 in the latent), 8 of
    the 512 experts held and a 256-token vocabulary, over the served cell's
    129 state slots and a page table of 256 positions. The weights are
    shapes: nothing is made."""
    import functools
    import importlib.util
    import json
    from pathlib import Path
    from types import SimpleNamespace

    from deeplearning4j_tpu.serving.decode.cache import KvCacheSpec
    from deeplearning4j_tpu.serving.decode.engine import cache_geometry

    bench = Path(__file__).resolve().parents[1] / "benchmarks"

    def load(kind):
        spec = importlib.util.spec_from_file_location(
            f"compile_test_n3s_{kind}", bench / kind / "nemotron_h.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    ref, models = load("reference"), load("models")
    real = json.loads(
        (bench / "configs" / "nemotron-3-super-120b-a12b.json").read_text())
    config = dict(
        real, num_hidden_layers=3, hybrid_override_pattern="M*E",
        n_routed_experts=8, vocab_size=256, max_position_embeddings=256,
        deployment=dict(real["deployment"], held_experts=[0, 8]))
    shapes = SimpleNamespace(
        dims=ref.dims, init_params=lambda c, s: jax.eval_shape(
            functools.partial(ref.init_params, c, s)))
    model = models.build(config, 0, shapes, train=False)
    leaves, treedef = jax.tree_util.tree_flatten(model.params)
    snapshot = SimpleNamespace(
        data=tuple(leaves),
        rebuild=lambda data: jax.tree_util.tree_unflatten(treedef, list(data)))
    channels, width, context, state = cache_geometry(model)
    spec = KvCacheSpec(channels=channels, width=width, block_len=16,
                       num_blocks=1 + 16 * 128, max_context=context,
                       kv_dtype="bf16", state=state, state_slots=129)
    return model, snapshot, spec


@pytest.mark.parametrize("phase", ["tick", "prefill"])
def test_nemotron_steps_update_arena_and_state_in_place_on_v5e(
        one_chip, nemotron_stack, phase):
    """The served tick of 128 rows as the TPU serves it: the attention
    layer's pages through the grouped paged kernel, the held experts
    through ONE relu^2 `grouped_experts` call, no conditional left, no copy
    of an expert leaf; a 256-token prefill (its experts under conditionals,
    the oracle). Both update the arena and the grouped Mamba-2 state where
    they lie, with no temporary of a quarter of the state."""
    import functools

    from deeplearning4j_tpu.serving.decode.engine import (_cache_arg_specs,
                                                          build_prefill_fn,
                                                          build_tick_fn)

    model, snapshot, spec = nemotron_stack
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    w = spec.table_width
    if phase == "tick":
        fn = functools.partial(build_tick_fn, rows_max=128,
                               attention="paged_kernel",
                               experts="grouped_kernel")
        args = (i32(128), i32(128), i32(128), i32(128, w), i32(128))
    else:
        fn, args = build_prefill_fn, (i32(1, 256), i32(1), i32(1, w), i32(1))
    with jax.enable_x64(False):
        compiled = jax.jit(fn(model, snapshot, spec),
                           donate_argnums=(1,)).lower(
            on_chip(snapshot.data), on_chip(_cache_arg_specs(spec)),
            *args).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= spec.arena_nbytes() + spec.state_nbytes()
    assert mem.temp_size_in_bytes < spec.state_nbytes() // 4
    text = compiled.as_text()
    kernels = re.findall(r"%grouped_experts[.\d]* = \S+ custom-call\(", text)
    if phase == "tick":
        assert len(kernels) == 1 and " conditional(" not in text
        leaves = {"8,1024,2688", "8,2688,1024"}
        copies = [m.group(0) for m in re.finditer(
            r"= \w+\[([\d,]*)\]\S* copy\(", text) if m.group(1) in leaves]
        assert not copies, copies
    else:
        assert not kernels
