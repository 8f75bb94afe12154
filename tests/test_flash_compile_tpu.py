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
    ("prefill", 512, None)])
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
    into again."""
    import functools

    from deeplearning4j_tpu.serving.decode.engine import (_cache_arg_specs,
                                                          build_decode_fn,
                                                          build_prefill_fn)

    model, snapshot, spec = decode_stack
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    w = spec.table_width
    if phase == "tick":
        fn = functools.partial(build_decode_fn, attention=attention)
        args = (i32(bucket), i32(bucket), i32(bucket, w))
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


def _nbytes(dtype: str, dims: str) -> int:
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    bits = re.search(r"\d+$", dtype)          # f32, bf16, s8; pred has none
    return n * (int(bits.group()) if bits else 8) // 8
