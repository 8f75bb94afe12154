"""Pallas kernel tier validation — the `CuDNNGradientChecks` pattern
(`deeplearning4j-cuda/src/test/.../gradientcheck/CuDNNGradientChecks.java`):
every accelerated kernel is checked against the plain-jnp reference
implementation and numerically gradient-checked. Run in Pallas interpreter
mode on the CPU mesh (same kernel code path the TPU compiles).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels import flash_attention, fused_bn_relu
from deeplearning4j_tpu.kernels.attention import attention_reference
from deeplearning4j_tpu.kernels.bn_relu import bn_relu_reference


def _qkv(B=2, T=96, S=80, D=64, dtype=np.float32, seed=0):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(B, T, D)).astype(dtype))
    k = jnp.asarray(r.normal(size=(B, S, D)).astype(dtype))
    v = jnp.asarray(r.normal(size=(B, S, D)).astype(dtype))
    return q, k, v


# ------------------------- flash attention --------------------------------

@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    q, k, v = _qkv(T=64, S=64)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("T,S", [(96, 80), (33, 17), (128, 5)])
def test_flash_attention_ragged_lengths(T, S):
    """Sequence lengths that don't divide the block size are masked, not
    silently padded into the softmax."""
    q, k, v = _qkv(T=T, S=S)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_causal_ragged():
    q, k, v = _qkv(T=50, S=50)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grad_matches_reference_grad():
    q, k, v = _qkv(T=48, S=48, D=32)

    def loss_k(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=True, block_q=16,
                                       block_k=16, interpret=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(attention_reference(q_, k_, v_, causal=True) ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_attention_numeric_gradcheck():
    """Central-difference check against the actual kernel forward,
    GradientCheckUtil.checkGradients:75 style. The kernel accumulates in
    f32, so step/tolerance are f32-scaled."""
    q, k, v = _qkv(B=1, T=8, S=8, D=4, dtype=np.float32)

    def loss(q_):
        return float(jnp.sum(
            flash_attention(q_, k, v, block_q=8, block_k=8,
                            interpret=True) ** 2))

    g = jax.grad(lambda q_: jnp.sum(
        flash_attention(q_, k, v, block_q=8, block_k=8,
                        interpret=True) ** 2))(q)
    g = np.asarray(g)
    qn = np.asarray(q)
    eps = 1e-2
    r = np.random.default_rng(3)
    for _ in range(8):
        i = tuple(r.integers(0, s) for s in qn.shape)
        qp, qm = qn.copy(), qn.copy()
        qp[i] += eps
        qm[i] -= eps
        num = (loss(jnp.asarray(qp)) - loss(jnp.asarray(qm))) / (2 * eps)
        rel = abs(num - g[i]) / max(abs(num) + abs(g[i]), 1e-9)
        assert rel < 2e-2, (i, num, g[i])


def test_flash_attention_bf16():
    q, k, v = _qkv(T=64, S=64)
    out = flash_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                          v.astype(jnp.bfloat16), block_q=32, block_k=32,
                          interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


# ----------------- flash attention: tiles from the shape -------------------

from deeplearning4j_tpu.kernels import attention as flash_mod
from deeplearning4j_tpu.kernels.attention import flash_schedule, flash_tiling


@pytest.mark.parametrize("T,S,Dh,dtype,causal", [
    (1024, 1024, 64, "bfloat16", True),     # the GPT-2 training shape
    (50, 50, 64, "float32", True),
    (96, 80, 64, "float32", False),
    (128, 5, 64, "float32", False),
    (2048, 2048, 128, "bfloat16", False),
    (8192, 8192, 128, "bfloat16", True),
    (1024, 1024, 64, "float32", True),
    (512, 1536, 32, "bfloat16", False),
])
def test_flash_tiling_respects_budget_and_tiling_rules(T, S, Dh, dtype, causal):
    til = flash_tiling(T, S, Dh, dtype, causal)
    assert til.vmem_bytes <= flash_mod._VMEM_BUDGET
    # derived tiles are whole (8, 128) tiles both as rows and as lanes
    assert til.block_q % 128 == 0 and til.block_k % 128 == 0
    # padding: whole resident blocks, and less than one block of it
    assert til.t_pad % (til.block_q * til.q_chunks) == 0
    assert til.s_pad % (til.block_k * til.k_chunks) == 0
    assert 0 <= til.t_pad - T < til.block_q * til.q_chunks
    assert 0 <= til.s_pad - S < til.block_k * til.k_chunks
    # a few hundred grid steps a call where there were thousands: at most
    # one q-major step for every 256 query rows of a head
    sched = flash_schedule(til, T, S, causal)
    assert sched["steps_q_major"] <= max(1, T // 256)
    assert sched["chunks_visited"] + sched["chunks_dead"] == (
        (til.t_pad // til.block_q) * (til.s_pad // til.block_k))
    # explicit blocks cap the derived ones, in the dtype's sublane multiple
    sub = 8 if dtype == "float32" else 16
    capped = flash_tiling(T, S, Dh, dtype, causal, block_q=32, block_k=16)
    assert capped.block_q <= 32 and capped.block_k <= 16
    assert capped.block_q % sub == 0 and capped.block_k % sub == 0
    assert capped.vmem_bytes <= til.vmem_bytes
    wide = flash_tiling(T, S, Dh, dtype, causal, block_q=256, block_k=128)
    assert wide.block_q <= 256 and wide.block_k <= 128


def test_flash_tiling_causal_steps_hold_no_dead_tile():
    til = flash_tiling(1024, 1024, 64, "bfloat16", True)
    sched = flash_schedule(til, 1024, 1024, True)
    nq, nk = 1024 // til.block_q, 1024 // til.block_k
    assert nq > 1 and til.k_chunks > 1      # several q tiles, an inner loop
    assert sched["dead_steps"] == 0
    assert sched["chunks_dead"] > 0         # the dead ones are never visited
    live = sum(1 for i in range(nq) for j in range(nk)
               if j * til.block_k <= (i + 1) * til.block_q - 1)
    assert sched["chunks_visited"] == live
    full = flash_schedule(til, 1024, 1024, False)
    assert full["chunks_visited"] == nq * nk and full["chunks_dead"] == 0


def test_flash_tiling_shrinks_tiles_to_the_budget(monkeypatch):
    monkeypatch.setattr(flash_mod, "_VMEM_BUDGET", 2 << 20)
    til = flash_tiling(4096, 4096, 128, "bfloat16", True)
    assert til.vmem_bytes <= 2 << 20
    assert til.block_q * til.block_k < 512 * 512
    assert til.k_chunks < 4096 // til.block_k   # K/V no longer all resident


def _value_and_grads(fn, q, k, v):
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)
    loss = lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_).astype(jnp.float32) * w)
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_flash_attention_derived_tiling_value_and_grad(causal, dtype, tol):
    """T = S = 1024 at Dh 64: the derived tiling has two q tiles and an inner
    loop of two chunks, one of them on the diagonal when causal."""
    til = flash_tiling(1024, 1024, 64, dtype, causal)
    assert 1024 // til.block_q > 1 and til.k_chunks > 1
    q, k, v = (a.astype(dtype) for a in _qkv(B=1, T=1024, S=1024, D=64, seed=7))
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = attention_reference(q, k, v, causal=causal)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)
    _, gk = _value_and_grads(lambda *a: flash_attention(
        *a, causal=causal, interpret=True), q, k, v)
    _, gr = _value_and_grads(lambda *a: attention_reference(
        *a, causal=causal), q, k, v)
    for a, b in zip(gk, gr):
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                   rtol=10 * tol, atol=tol * np.abs(b).max())


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T,S", [(96, 96), (80, 112)])
def test_flash_attention_streams_resident_blocks(monkeypatch, causal, T, S):
    """A budget too small for all of K/V (or Q/dO) at once: the kernels
    stream resident blocks over a third grid axis, and under the causal mask
    a dead block re-names a live one."""
    monkeypatch.setattr(flash_mod, "_VMEM_BUDGET", 48 << 10)
    flash_mod._planned.cache_clear()
    til = flash_tiling(T, S, 32, "float32", causal, 16, 16)
    assert 1 < til.k_chunks < -(-S // 16) and 1 < til.q_chunks < -(-T // 16)
    q, k, v = _qkv(B=2, T=T, S=S, D=32, seed=11)
    try:
        val, gk = _value_and_grads(lambda *a: flash_attention(
            *a, causal=causal, block_q=16, block_k=16, interpret=True), q, k, v)
    finally:
        flash_mod._planned.cache_clear()
    ref, gr = _value_and_grads(lambda *a: attention_reference(
        *a, causal=causal), q, k, v)
    np.testing.assert_allclose(float(val), float(ref), rtol=2e-5)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_tiling_record_once_per_compiled_shape():
    from deeplearning4j_tpu import telemetry
    previous = telemetry.tracer()
    telemetry.install_tracer(telemetry.Tracer())
    flash_mod._planned.cache_clear()
    flash_mod._fwd_call.clear_cache()    # a shape compiled before is not
    flash_mod._bwd_call.clear_cache()    # built, and so not recorded, again
    try:
        grad = jax.jit(jax.grad(lambda q_, k_, v_: jnp.sum(flash_attention(
            q_, k_, v_, causal=True, interpret=True))))
        for T in (40, 40, 72):          # forward and backward, twice, then new
            q, k, v = _qkv(B=1, T=T, S=T, D=16)
            grad(q, k, v)
        recs = [e for e in telemetry.tracer().snapshot()
                if e["name"] == "dl4j/kernels/flash_tiling"]
    finally:
        telemetry.install_tracer(previous)
        flash_mod._planned.cache_clear()
    assert [r["attrs"]["T"] for r in recs] == [40, 72]
    a = recs[0]["attrs"]
    assert recs[0]["ph"] == "i"
    assert (a["S"], a["Dh"], a["dtype"], a["causal"]) == (40, 16, "float32", True)
    assert a["block_q"] == 128 and a["steps_q_major"] == a["steps_a_call"] == 1
    assert a["chunks_visited"] == 1 and a["dead_steps"] == 0


# ------------------------- fused BN + ReLU --------------------------------

def test_fused_bn_relu_matches_reference():
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(64, 48)).astype(np.float32))
    g = jnp.asarray(r.normal(size=(48,)).astype(np.float32))
    b = jnp.asarray(r.normal(size=(48,)).astype(np.float32))
    y, mean, var = fused_bn_relu(x, g, b, interpret=True)
    yr, mr, vr = bn_relu_reference(x, g, b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(mr), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(var), np.asarray(vr), rtol=1e-5)


def test_fused_bn_relu_nhwc():
    r = np.random.default_rng(1)
    x = jnp.asarray(r.normal(size=(4, 6, 6, 24)).astype(np.float32))
    g = jnp.ones((24,), jnp.float32)
    b = jnp.zeros((24,), jnp.float32)
    y, mean, var = fused_bn_relu(x, g, b, interpret=True)
    yr, mr, vr = bn_relu_reference(x.reshape(-1, 24), g, b)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 24),
                               np.asarray(yr), rtol=2e-5, atol=2e-5)


def test_fused_bn_relu_grad_matches_reference():
    r = np.random.default_rng(2)
    x = jnp.asarray(r.normal(size=(32, 20)).astype(np.float32))
    g = jnp.asarray(1.0 + 0.1 * r.normal(size=(20,)).astype(np.float32))
    b = jnp.asarray(0.1 * r.normal(size=(20,)).astype(np.float32))
    w = jnp.asarray(r.normal(size=(32, 20)).astype(np.float32))

    def loss_k(x_, g_, b_):
        y, _, _ = fused_bn_relu(x_, g_, b_, interpret=True)
        return jnp.sum(y * w)

    def loss_ref(x_, g_, b_):
        y, _, _ = bn_relu_reference(x_, g_, b_)
        return jnp.sum(y * w)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, g, b)
    for a, c in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-4, atol=2e-5)


def test_fused_bn_relu_numeric_gradcheck():
    r = np.random.default_rng(4)
    x = jnp.asarray(r.normal(size=(12, 8)).astype(np.float32))
    g = jnp.asarray(1.0 + 0.1 * r.normal(size=(8,)).astype(np.float32))
    b = jnp.asarray(0.1 * r.normal(size=(8,)).astype(np.float32))

    def loss(x_):
        y, _, _ = fused_bn_relu(x_, g, b, interpret=True)
        return jnp.sum(y ** 2)

    grad = np.asarray(jax.grad(loss)(x))
    xn = np.asarray(x)
    eps = 1e-2   # kernel computes in f32; f32-scaled step/tolerance
    for _ in range(8):
        i = tuple(r.integers(0, s) for s in xn.shape)
        xp, xm = xn.copy(), xn.copy()
        xp[i] += eps
        xm[i] -= eps
        num = (float(loss(jnp.asarray(xp))) - float(loss(jnp.asarray(xm)))) \
            / (2 * eps)
        rel = abs(num - grad[i]) / max(abs(num) + abs(grad[i]), 1e-9)
        assert rel < 2e-2, (i, num, grad[i])


def test_flash_attention_bwd_ragged_noncausal():
    """Backward kernels on lengths that don't divide the blocks: the padded
    rows/cols must contribute zero gradient (round-3 Pallas backward)."""
    q, k, v = _qkv(T=33, S=17, D=32, seed=5)

    def loss_k(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, block_q=16, block_k=16,
                                       interpret=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(attention_reference(q_, k_, v_) ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_attention_bwd_causal_ragged():
    q, k, v = _qkv(T=50, S=50, D=16, seed=6)

    def loss_k(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=True, block_q=16,
                                       block_k=16, interpret=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(attention_reference(q_, k_, v_, causal=True) ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


# ----------------------- fused LSTM sequence kernel -----------------------

def _lstm_scan_oracle(x, W, b, pp, h0, c0, offs=1.0):
    """The layer's lax.scan cell math (nn/layers/recurrent._lstm_cell
    semantics) as the kernel oracle."""
    from jax import lax
    p_i, p_f, p_o = jnp.split(pp, 3)

    def step(carry, x_t):
        h_prev, c_prev = carry
        gates = jnp.concatenate([x_t, h_prev], -1) @ W + b
        i_g, f_g, o_g, g_g = jnp.split(gates, 4, -1)
        i = jax.nn.sigmoid(i_g + c_prev * p_i)
        f = jax.nn.sigmoid(f_g + c_prev * p_f + offs)
        g = jnp.tanh(g_g)
        c = f * c_prev + i * g
        o = jax.nn.sigmoid(o_g + c * p_o)
        h = o * jnp.tanh(c)
        return (h, c), h

    (hT, cT), hs = lax.scan(step, (h0, c0), x)
    return hs, hT, cT


def _lstm_args(T=6, B=3, F=5, H=6, seed=0):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=(T, B, F)).astype(np.float32)),
            jnp.asarray(r.normal(size=(F + H, 4 * H)).astype(np.float32)) * 0.3,
            jnp.asarray(r.normal(size=(4 * H,)).astype(np.float32)) * 0.1,
            jnp.asarray(r.normal(size=(3 * H,)).astype(np.float32)) * 0.1,
            jnp.asarray(r.normal(size=(B, H)).astype(np.float32)) * 0.5,
            jnp.asarray(r.normal(size=(B, H)).astype(np.float32)) * 0.5)


def test_fused_lstm_forward_matches_scan():
    from deeplearning4j_tpu.kernels.lstm import fused_lstm_sequence
    args = _lstm_args()
    hs0, hT0, cT0 = _lstm_scan_oracle(*args)
    hs1, hT1, cT1 = fused_lstm_sequence(*args, 1.0, True)
    np.testing.assert_allclose(np.asarray(hs1), np.asarray(hs0),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cT1), np.asarray(cT0),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("T", [1, 2, 7])
def test_fused_lstm_grads_match_scan(T):
    """All six gradients (x, W, b, peep, h0, c0) through every cotangent
    path (hs, h_T, c_T) against jax.grad of the scan oracle."""
    from deeplearning4j_tpu.kernels.lstm import fused_lstm_sequence
    args = _lstm_args(T=T)
    r = np.random.default_rng(1)
    B, H = args[4].shape
    ws = jnp.asarray(r.normal(size=(T, B, H)).astype(np.float32))
    wt = jnp.asarray(r.normal(size=(B, H)).astype(np.float32))
    wc = jnp.asarray(r.normal(size=(B, H)).astype(np.float32))

    def mix(outs):
        hs, hT, cT = outs
        return jnp.sum(hs * ws) + jnp.sum(hT * wt) + jnp.sum(cT * wc)

    g0 = jax.grad(lambda a: mix(_lstm_scan_oracle(*a)))(args)
    g1 = jax.grad(lambda a: mix(fused_lstm_sequence(*a, 1.0, True)))(args)
    for name, a, b in zip(("dx", "dW", "db", "dpeep", "dh0", "dc0"), g0, g1):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


def test_fused_lstm_probe_conditions():
    """Helper selection (cuDNN-RNN probing pattern): only on TPU, only
    mask-free sigmoid/tanh, only VMEM-feasible sizes."""
    from deeplearning4j_tpu.nn.layers.recurrent import GravesLSTM
    from deeplearning4j_tpu.kernels.lstm import lstm_fits_vmem
    layer = GravesLSTM(n_out=8)
    x = jnp.zeros((2, 4, 5), jnp.float32)
    # CPU backend (tests force cpu): probe must decline — the scan path
    # is the CI path; the kernel is exercised via interpret above
    assert layer._helper(x, None) is False
    assert layer._helper(x, jnp.ones((2, 4))) is False
    assert GravesLSTM(n_out=8, gate_activation="hardsigmoid") \
        ._helper(x, None) is False
    assert lstm_fits_vmem(77, 200, 64)          # char-RNN size fits
    assert not lstm_fits_vmem(4096, 4096, 256)  # too big for VMEM
