"""Fused BatchNorm + ReLU Pallas kernels.

Reference analog: `CudnnBatchNormalizationHelper.java` (289 LoC of cuDNN
descriptor plumbing) — here the fusion is one VMEM pass: batch statistics,
normalization, scale/shift, and the ReLU are computed without writing the
intermediate normalized tensor to HBM. The backward kernel fuses the ReLU
mask with the three BN reductions.

Layout: channels-last [N, C] (the wrapper flattens NHWC conv activations to
[N*H*W, C]); the grid tiles C so each program owns a channel block with the
full batch resident in VMEM. Stats are stop-gradient (running-average
semantics, as in the reference's BatchNormalization layer).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_bn_relu", "bn_relu_inference", "bn_relu_reference"]


def bn_relu_reference(x, gamma, beta, eps: float = 1e-5):
    """jnp oracle: batch-stat BN + ReLU over [N, C]. Returns (y, mean, var)
    (biased variance, the reference's batch-stats convention)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=0)
    var = jnp.mean(jnp.square(xf - mean), axis=0)
    inv = jax.lax.rsqrt(var + eps)
    y = jnp.maximum((xf - mean) * inv * gamma + beta, 0.0)
    return y.astype(x.dtype), mean, var


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, var_ref, *, n, eps):
    x = x_ref[:].astype(jnp.float32)                 # [N, bc]
    mean = jnp.sum(x, axis=0, keepdims=True) / n     # [1, bc]
    xc = x - mean
    var = jnp.sum(xc * xc, axis=0, keepdims=True) / n
    inv = jax.lax.rsqrt(var + eps)
    y = jnp.maximum(xc * inv * g_ref[:] + b_ref[:], 0.0)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    var_ref[:] = var


def _bwd_kernel(x_ref, g_ref, b_ref, mean_ref, var_ref, dy_ref,
                dx_ref, dg_ref, db_ref, *, n, eps):
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    mean = mean_ref[:]
    inv = jax.lax.rsqrt(var_ref[:] + eps)
    xhat = (x - mean) * inv
    pre = xhat * g_ref[:] + b_ref[:]
    dyr = jnp.where(pre > 0.0, dy, 0.0)              # fused ReLU mask
    dg = jnp.sum(dyr * xhat, axis=0, keepdims=True)
    db = jnp.sum(dyr, axis=0, keepdims=True)
    dx = (g_ref[:] * inv / n) * (n * dyr - db - xhat * dg)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dg_ref[:] = dg
    db_ref[:] = db


def _block_c(C: int, N: int) -> Optional[int]:
    """Channel tile: TPU lowering needs the lane dim to be a multiple of
    128 or the full array width, and the full batch stays in VMEM with
    in/out blocks double-buffered — cap one block at ~2MB. Returns None
    when the batch dim alone exceeds the budget (caller falls back to the
    XLA path)."""
    bc = 128 if C >= 128 else C
    if N * bc * 4 > 2 * 1024 * 1024:
        return None
    return bc


def _fwd_call(x, gamma, beta, eps, interpret):
    N, C = x.shape
    bc = _block_c(C, N)
    Cp = -(-C // bc) * bc
    xp = jnp.pad(x, ((0, 0), (0, Cp - C)))
    gp = jnp.pad(gamma.reshape(1, -1).astype(jnp.float32),
                 ((0, 0), (0, Cp - C)))
    bp = jnp.pad(beta.reshape(1, -1).astype(jnp.float32),
                 ((0, 0), (0, Cp - C)))
    y, mean, var = pl.pallas_call(
        functools.partial(_fwd_kernel, n=float(N), eps=float(eps)),
        out_shape=(jax.ShapeDtypeStruct((N, Cp), x.dtype),
                   jax.ShapeDtypeStruct((1, Cp), jnp.float32),
                   jax.ShapeDtypeStruct((1, Cp), jnp.float32)),
        grid=(Cp // bc,),
        in_specs=[pl.BlockSpec((N, bc), lambda c: (0, c),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, bc), lambda c: (0, c),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, bc), lambda c: (0, c),
                               memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((N, bc), lambda c: (0, c),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, bc), lambda c: (0, c),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, bc), lambda c: (0, c),
                                memory_space=pltpu.VMEM)),
        interpret=interpret,
        name="bn_relu_fwd",
    )(xp, gp, bp)
    return y[:, :C], mean[0, :C], var[0, :C]


def _bwd_call(x, gamma, beta, mean, var, dy, eps, interpret):
    N, C = x.shape
    bc = _block_c(C, N)
    Cp = -(-C // bc) * bc
    pc = lambda a: jnp.pad(a, ((0, 0), (0, Cp - C)))
    xp, dyp = pc(x), pc(dy)
    gp = pc(gamma.reshape(1, -1).astype(jnp.float32))
    bp = pc(beta.reshape(1, -1).astype(jnp.float32))
    mp = pc(mean.reshape(1, -1).astype(jnp.float32))
    # pad var with 1s so rsqrt(0+eps) on dead channels stays finite
    vp = jnp.pad(var.reshape(1, -1).astype(jnp.float32),
                 ((0, 0), (0, Cp - C)), constant_values=1.0)
    dx, dg, db = pl.pallas_call(
        functools.partial(_bwd_kernel, n=float(N), eps=float(eps)),
        out_shape=(jax.ShapeDtypeStruct((N, Cp), x.dtype),
                   jax.ShapeDtypeStruct((1, Cp), jnp.float32),
                   jax.ShapeDtypeStruct((1, Cp), jnp.float32)),
        grid=(Cp // bc,),
        in_specs=[pl.BlockSpec((N, bc), lambda c: (0, c),
                               memory_space=pltpu.VMEM)] +
                 [pl.BlockSpec((1, bc), lambda c: (0, c),
                               memory_space=pltpu.VMEM)] * 4 +
                 [pl.BlockSpec((N, bc), lambda c: (0, c),
                               memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((N, bc), lambda c: (0, c),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, bc), lambda c: (0, c),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, bc), lambda c: (0, c),
                                memory_space=pltpu.VMEM)),
        interpret=interpret,
        name="bn_relu_bwd",
    )(xp, gp, bp, mp, vp, dyp)
    return dx[:, :C], dg[0, :C], db[0, :C]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_relu(x, gamma, beta, eps, interpret):
    return _fwd_call(x, gamma, beta, eps, interpret)


def _bn_relu_fwd(x, gamma, beta, eps, interpret):
    y, mean, var = _fwd_call(x, gamma, beta, eps, interpret)
    return (y, mean, var), (x, gamma, beta, mean, var)


def _bn_relu_bwd(eps, interpret, res, cotangents):
    x, gamma, beta, mean, var = res
    dy, _dmean, _dvar = cotangents   # stats are stop-gradient (running avg)
    dx, dg, db = _bwd_call(x, gamma, beta, mean, var, dy, eps, interpret)
    return dx, dg.astype(gamma.dtype), db.astype(beta.dtype)


_bn_relu.defvjp(_bn_relu_fwd, _bn_relu_bwd)


def fused_bn_relu(x, gamma, beta, eps: float = 1e-5,
                  interpret: Optional[bool] = None):
    """Fused training-mode BatchNorm + ReLU. x: [N, C] or [N, H, W, C]
    (channels last). Returns (y, batch_mean, batch_var); the caller updates
    its running statistics from the returned batch stats, exactly like the
    reference's BatchNormalization layer does around its cuDNN helper."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shape = x.shape
    if x.ndim > 2:
        x = x.reshape(-1, shape[-1])
    if _block_c(x.shape[1], x.shape[0]) is None:
        # batch dim alone would blow VMEM — XLA's two-pass BN handles it
        y, mean, var = bn_relu_reference(x, gamma, beta, eps)
        return y.reshape(shape), mean, var
    y, mean, var = _bn_relu(x, gamma, beta, float(eps), bool(interpret))
    return y.reshape(shape), mean, var


def bn_relu_inference(x, gamma, beta, mean, var, eps: float = 1e-5):
    """Inference-mode fused path with running stats: a single elementwise
    expression, left to XLA (it fuses this perfectly — the kernel tier is
    only for the batch-stat reductions)."""
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    y = (x.astype(jnp.float32) - mean) * inv * gamma + beta
    return jnp.maximum(y, 0.0).astype(x.dtype)
