"""Fused Graves-LSTM sequence kernel — the whole scan in ONE Pallas call.

The SURVEY §7 phase-7 kernel target ("fused LSTM cell"), and the analog of
the cuDNN RNN API the reference era lacked (SURVEY notes no cuDNN LSTM
helper existed at v0.8; `LSTMHelpers.java` ran generic per-timestep ops).

Why a kernel can win here where, in an earlier installation's capture
(BASELINE.md), conv/BN kernels did not: the
XLA path is a `lax.scan` whose per-timestep work is a tiny [B, F+H] x
[F+H, 4H] matmul — too small to hide per-op overhead, and the weights are
re-read from HBM every timestep. At char-RNN size the FULL working set
(weights + biases + peepholes + [B, H] carries) fits VMEM, so one Pallas
kernel holds the carry on-chip across the whole sequence and reads the
weights once per *sequence* instead of once per *timestep* (the TPU grid
is sequential — exactly a time loop). Each step is ONE [B, F+H] x
[F+H, 4H] MXU matmul; gate splits are in-register slices.

Backward is a second Pallas kernel running the standard Graves-LSTM
adjoint in reverse time (peepholes included): per step one [B,4H] x
[4H, F+H] matmul for dz and one [F+H, B] x [B, 4H] matmul accumulating
dW in VMEM scratch; saved residuals are the forward's per-step gate
activations and cell states (the same tensors XLA's autodiff would save).

Selection follows the helper probing pattern
(`CudnnBatchNormalizationHelper` style): the layer uses this kernel only
on TPU for mask-free sigmoid/tanh LSTMs whose working set fits VMEM;
everything else takes the lax.scan path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_lstm_sequence", "lstm_fits_vmem"]


def _sig(x):
    return jax.nn.sigmoid(x)


def lstm_fits_vmem(n_in: int, n_out: int, batch: int,
                   dtype_bytes: int = 4, budget: int = 10 << 20) -> bool:
    """Rough VMEM feasibility: weights (x2 for the backward's dW
    accumulator) + a few [B, 4H] temporaries must fit."""
    f, h = n_in + n_out, n_out
    weights = f * 4 * h * dtype_bytes
    temps = 10 * batch * 4 * h * dtype_bytes
    return 2 * weights + temps < budget


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(x_ref, w_ref, b_ref, peep_ref, h0_ref, c0_ref,
                *out_refs, offs: float, H: int, save_residuals: bool):
    if save_residuals:
        hs_ref, cs_ref, ii_ref, ff_ref, oo_ref, gg_ref, h_scr, c_scr = \
            out_refs
    else:
        hs_ref, cT_ref, h_scr, c_scr = out_refs
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    h_prev = h_scr[:]
    c_prev = c_scr[:]
    zcat = jnp.concatenate([x_ref[0], h_prev], axis=-1)   # [B, F+H]
    gates = jax.lax.dot_general(
        zcat, w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b_ref[:]     # [B, 4H]
    i = _sig(gates[:, :H] + c_prev * peep_ref[:, :H])
    f = _sig(gates[:, H:2 * H] + c_prev * peep_ref[:, H:2 * H] + offs)
    g = jnp.tanh(gates[:, 3 * H:])
    c = f * c_prev + i * g
    o = _sig(gates[:, 2 * H:3 * H] + c * peep_ref[:, 2 * H:])
    h = o * jnp.tanh(c)
    hs_ref[0] = h
    if save_residuals:
        cs_ref[0] = c
        ii_ref[0] = i
        ff_ref[0] = f
        oo_ref[0] = o
        gg_ref[0] = g
    else:
        @pl.when(t == pl.num_programs(0) - 1)
        def _():
            cT_ref[:] = c
    h_scr[:] = h
    c_scr[:] = c


def _fwd_impl(x, W, b, peep, h0, c0, offs, interpret,
              save_residuals: bool = True):
    """save_residuals=True (the fwd-for-vjp path) emits the per-step gate
    activations and cell states the adjoint needs; False (the primal /
    inference path) emits only hs + the final cell state — 4 fewer
    [T, B, H] HBM writes per call."""
    T, B, F = x.shape
    H = h0.shape[-1]
    f32 = jnp.float32
    step = lambda shp: pl.BlockSpec((1,) + shp, lambda t: (t, 0, 0),
                                    memory_space=pltpu.VMEM)
    full = lambda a: pl.BlockSpec(a.shape, lambda t: (0,) * a.ndim,
                                  memory_space=pltpu.VMEM)
    if save_residuals:
        out_shape = tuple(jax.ShapeDtypeStruct((T, B, H), f32)
                          for _ in range(6))
        out_specs = tuple(step((B, H)) for _ in range(6))
    else:
        out_shape = (jax.ShapeDtypeStruct((T, B, H), f32),
                     jax.ShapeDtypeStruct((B, H), f32))
        out_specs = (step((B, H)),
                     pl.BlockSpec((B, H), lambda t: (0, 0),
                                  memory_space=pltpu.VMEM))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, offs=float(offs), H=H,
                          save_residuals=save_residuals),
        grid=(T,),
        in_specs=[step((B, F)), full(W), full(b), full(peep),
                  full(h0), full(c0)],
        out_shape=out_shape,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((B, H), f32), pltpu.VMEM((B, H), f32)],
        interpret=interpret,
        name="lstm_fwd",
    )(x, W, b, peep, h0, c0)


# ---------------------------------------------------------------------------
# backward (reverse-time adjoint)
# ---------------------------------------------------------------------------
def _bwd_kernel(x_ref, w_ref, peep_ref,
                hs_prev_ref, cs_ref, cs_prev_ref,
                ii_ref, ff_ref, oo_ref, gg_ref,
                h0_ref, c0_ref, dhs_ref, dhT_ref, dcT_ref,
                dx_ref, dw_ref, db_ref, dpeep_ref, dh0_ref, dc0_ref,
                dh_scr, dc_scr, aw, ab, apeep,
                *, T: int, H: int):
    r = pl.program_id(0)          # runs t = T-1 .. 0 (reverse index maps)

    @pl.when(r == 0)
    def _():
        dh_scr[:] = dhT_ref[:]
        dc_scr[:] = dcT_ref[:]
        aw[:] = jnp.zeros_like(aw)
        ab[:] = jnp.zeros_like(ab)
        apeep[:] = jnp.zeros_like(apeep)

    i = ii_ref[0]
    f = ff_ref[0]
    o = oo_ref[0]
    g = gg_ref[0]
    c = cs_ref[0]
    # at the earliest step (t == 0) the "previous" state is the initial
    # carry; the t-1 block specs clamp to index 0 there, so override
    first = r == T - 1
    c_prev = jnp.where(first, c0_ref[:], cs_prev_ref[0])
    h_prev = jnp.where(first, h0_ref[:], hs_prev_ref[0])

    dh = dhs_ref[0] + dh_scr[:]
    tc = jnp.tanh(c)
    do_pre = dh * tc * o * (1.0 - o)
    dc = (dh * o * (1.0 - tc * tc) + dc_scr[:]
          + do_pre * peep_ref[:, 2 * H:])
    di_pre = dc * g * i * (1.0 - i)
    df_pre = dc * c_prev * f * (1.0 - f)
    dg_pre = dc * i * (1.0 - g * g)
    dc_prev = (dc * f + di_pre * peep_ref[:, :H]
               + df_pre * peep_ref[:, H:2 * H])

    zcat = jnp.concatenate([x_ref[0], h_prev], axis=-1)     # [B, F+H]
    dgates = jnp.concatenate([di_pre, df_pre, do_pre, dg_pre],
                             axis=-1)                        # [B, 4H]
    aw[:] = aw[:] + jax.lax.dot_general(
        zcat, dgates, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [F+H, 4H]
    ab[:] = ab[:] + jnp.sum(dgates, axis=0, keepdims=True)
    apeep[:] = apeep[:] + jnp.concatenate(
        [jnp.sum(di_pre * c_prev, axis=0, keepdims=True),
         jnp.sum(df_pre * c_prev, axis=0, keepdims=True),
         jnp.sum(do_pre * c, axis=0, keepdims=True)], axis=-1)

    dz = jax.lax.dot_general(
        dgates, w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [B, F+H]
    F = x_ref.shape[-1]
    dx_ref[0] = dz[:, :F]
    dh_scr[:] = dz[:, F:]
    dc_scr[:] = dc_prev

    @pl.when(r == T - 1)
    def _():
        dw_ref[:] = aw[:]
        db_ref[:] = ab[:]
        dpeep_ref[:] = apeep[:]
        dh0_ref[:] = dh_scr[:]
        dc0_ref[:] = dc_scr[:]


def _bwd_impl(x, W, peep, h0, c0, hs, cs, ii, ff, oo, gg,
              dhs, dhT, dcT, interpret):
    T, B, F = x.shape
    H = h0.shape[-1]
    f32 = jnp.float32
    rev = lambda shp: pl.BlockSpec(
        (1,) + shp, lambda t: (T - 1 - t, 0, 0), memory_space=pltpu.VMEM)
    rev_prev = lambda shp: pl.BlockSpec(
        (1,) + shp, lambda t: (jnp.maximum(T - 2 - t, 0), 0, 0),
        memory_space=pltpu.VMEM)
    full = lambda a: pl.BlockSpec(a.shape, lambda t: (0,) * a.ndim,
                                  memory_space=pltpu.VMEM)
    small = lambda shp: pl.BlockSpec(shp, lambda t: (0, 0),
                                     memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, T=T, H=H),
        grid=(T,),
        in_specs=[rev((B, F)), full(W), full(peep),
                  rev_prev((B, H)),               # hs at t-1
                  rev((B, H)), rev_prev((B, H)),  # cs at t, t-1
                  rev((B, H)), rev((B, H)), rev((B, H)), rev((B, H)),
                  full(h0), full(c0), rev((B, H)), full(dhT), full(dcT)],
        out_shape=(jax.ShapeDtypeStruct((T, B, F), f32),
                   jax.ShapeDtypeStruct(W.shape, f32),
                   jax.ShapeDtypeStruct((1, 4 * H), f32),
                   jax.ShapeDtypeStruct((1, 3 * H), f32),
                   jax.ShapeDtypeStruct((B, H), f32),
                   jax.ShapeDtypeStruct((B, H), f32)),
        out_specs=(rev((B, F)), full(W), small((1, 4 * H)),
                   small((1, 3 * H)), full(h0), full(c0)),
        scratch_shapes=[pltpu.VMEM((B, H), f32), pltpu.VMEM((B, H), f32),
                        pltpu.VMEM(W.shape, f32),
                        pltpu.VMEM((1, 4 * H), f32),
                        pltpu.VMEM((1, 3 * H), f32)],
        interpret=interpret,
        name="lstm_bwd",
    )(x, W, peep, hs, cs, cs, ii, ff, oo, gg, h0, c0, dhs, dhT, dcT)


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------
def _canon(x, W, b, peep, h0, c0):
    f32 = lambda a: a.astype(jnp.float32)
    return (f32(x), f32(W), b.reshape(1, -1).astype(jnp.float32),
            peep.reshape(1, -1).astype(jnp.float32), f32(h0), f32(c0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def fused_lstm_sequence(x, W, b, peep, h0, c0, offs: float,
                        interpret: bool):
    """x: [T, B, F] (time-major), W: [F+H, 4H] (i|f|o|g column blocks),
    b: [4H], peep: [3H] (i|f|o), carries [B, H]. Returns
    (hs [T, B, H], h_T, c_T) — semantics identical to the layer's
    lax.scan `_lstm_cell` path with sigmoid gates / tanh cell. The
    primal (inference) path skips the gate/cell residual outputs."""
    hs, cT = _fwd_impl(*_canon(x, W, b, peep, h0, c0), offs, interpret,
                       save_residuals=False)
    return hs.astype(x.dtype), hs[-1].astype(x.dtype), cT.astype(x.dtype)


def _vjp_fwd(x, W, b, peep, h0, c0, offs, interpret):
    hs, cs, ii, ff, oo, gg = _fwd_impl(*_canon(x, W, b, peep, h0, c0),
                                       offs, interpret)
    out = (hs.astype(x.dtype), hs[-1].astype(x.dtype),
           cs[-1].astype(x.dtype))
    return out, (x, W, b, peep, h0, c0, hs, cs, ii, ff, oo, gg)


def _vjp_bwd(offs, interpret, res, cots):
    x, W, b, peep, h0, c0, hs, cs, ii, ff, oo, gg = res
    dhs, dhT, dcT = cots
    f32 = lambda a: a.astype(jnp.float32)
    # the hT/cT cotangents flow into the last step's dh/dc carries
    (dx, dW, db, dp, dh0, dc0) = _bwd_impl(
        f32(x), f32(W), peep.reshape(1, -1).astype(jnp.float32),
        f32(h0), f32(c0), hs, cs, ii, ff, oo, gg,
        f32(dhs), f32(dhT), f32(dcT), interpret)
    return (dx.astype(x.dtype), dW.astype(W.dtype),
            db.reshape(-1).astype(b.dtype),
            dp.reshape(-1).astype(peep.dtype), dh0.astype(h0.dtype),
            dc0.astype(c0.dtype))


fused_lstm_sequence.defvjp(_vjp_fwd, _vjp_bwd)
