"""The flash kernels compiled for a TPU v5e that is described, not attached:
the chip's own compiler (Mosaic, libtpu) takes each tiling the chooser
derives at real widths, so a slice off the (8, 128) tiling or a step over
the VMEM limit fails here and not on the chip. Nothing runs: no result and
no time comes from this file.

All of these tests live in this one file, and the topology is described
inside a fixture, because only one process at a time may load the TPU's
library."""
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
