"""Grouped experts — the held experts of a sparse expert layer over a decode
tick's rows, streamed through one Pallas TPU kernel a layer.

A tick of an expert layer (`nn/layers/shortcut_moe.py` `_held_sum`) holds a
few dozen rows, and every held expert runs over all of them under its rows'
weights: `sum_e w[:, e] * (silu(u W_g[e]) * (u W_u[e])) W_d[e]`, or, for the
non-gated experts of Nemotron-H (no `W_g`), `sum_e w[:, e] * relu(u W_u[e])^2
W_d[e]`: two matrices an expert in place of three. The rows cost
nothing beside the experts' weights (at 64 rows an expert's three matrices
take 18.9 MB of HBM time and 1.2 GFLOP of the MXU's), so the layer is a
stream of weights, and what decides its time is whether the stream stops.
Run an expert at a time under a conditional, it stops at every expert: XLA
cannot fetch the next expert's weights across the branch. This kernel makes
the held experts one grid, (expert e, tile j of the expert's hidden width),
so that Pallas's pipeline fetches step s + 1's blocks while step s computes.

- Only the experts some row picked are read. `loads` [E] is scalar-prefetched
  with a source block a grid step (`experts_sources`): a hit expert's own tile,
  and for an expert no row picked the block fetched last (the previous hit
  expert's last tile, or the first hit expert's tile 0 before any hit). The
  pipeline issues no DMA for a step whose block is the one it holds, and
  `pl.when(loads[e] > 0)` skips that step's products. Where no expert is hit
  one tile is read and nothing is computed.
- The tile is the widest multiple of 128 lanes that divides the hidden width
  and whose double-buffered weight blocks (`W_g`, `W_u`, `W_d`, or `W_u`,
  `W_d`) fit `_WEIGHT_VMEM` (`experts_plan`): whole experts at Granite
  4.0-H's 4096 x 768 and at Nemotron-H's 1024 x 2688, 512 of 2048 at
  LongCat-Flash's 6144 wide. The call's VMEM limit is the plan's estimate
  with room to spare.
- The arithmetic is `_swiglu`'s (or `_relu2`'s): the rows in the weights'
  dtype, the first products summed in float32, `silu(g) * up` (or
  `relu(up)^2`) cast to the weights' dtype and through `W_d` summed in
  float32, scaled by the row's weight (a dead row weighs 0), added into one
  float32 `[rows, d]` output that stays in VMEM over the whole grid. Experts
  are added in order; a tile's part of `W_d` is added on its own, the one
  difference in order from the conditionals'.
- The weights are read as the layer holds them, `[E, d, h]` and `[E, h, d]`:
  no copy, no relayout.

`dl4j/kernels/grouped_experts` in the span log says, once per call shape,
what a call is made of (experts, rows, widths, activation and matrices an
expert, tile, grid steps, VMEM).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _round_up

__all__ = ["grouped_experts", "ExpertsPlan", "experts_plan",
           "experts_sources", "grouped_experts_supported"]

_WEIGHT_VMEM = 40 << 20     # the double-buffered weight blocks at most
_VMEM_SPARE = 8 << 20       # the call's limit over the plan's estimate
_DTYPES = ("float32", "bfloat16")


class ExpertsPlan(NamedTuple):
    """What one call is made of."""
    tile: int               # lanes of an expert's hidden width a grid step
    tiles_an_expert: int
    steps_a_call: int       # grid steps: held experts x tiles
    rows_padded: int        # rows in whole sublane tiles of the weights' dtype
    vmem_bytes: int         # estimated: weight blocks, rows, output, values
    vmem_limit_bytes: int


def experts_plan(rows: int, d: int, h: int, experts: int,
                 itemsize: int = 2, matrices: int = 3) -> Optional[ExpertsPlan]:
    """The tile from the shape: the widest multiple of 128 that divides `h`
    whose double-buffered blocks of an expert's `matrices` (3 gated, 2
    relu^2) fit `_WEIGHT_VMEM`; None where there is none (`h` not in whole
    lane tiles, or `d` so wide that 128 lanes do not fit). `itemsize` is the
    weights' dtype's."""
    fits = [t for t in range(h - h % 128, 0, -128)
            if h % t == 0 and 2 * matrices * d * t * itemsize <= _WEIGHT_VMEM]
    if h % 128 or d % 128 or not fits:
        return None
    tile, rp = fits[0], _round_up(rows, 8 * max(1, 4 // itemsize))
    vmem = (2 * matrices * d * tile * itemsize  # the weight blocks, two each
            + 2 * rp * d * itemsize         # the rows
            + 2 * rp * 128 * 4              # the rows' weights, a lane tile
            + 2 * rp * d * 4                # the output
            + matrices * rp * tile * 4      # g, up, their product
            + rp * d * 4)                   # a tile's part of the output
    return ExpertsPlan(tile, h // tile, experts * (h // tile), rp, vmem,
                       vmem + _VMEM_SPARE)


def grouped_experts_supported(d: int, h: int, dtype="float32",
                              matrices: int = 3) -> bool:
    """Whether the compiled kernel takes experts of `matrices` matrices `d`
    wide with a hidden width `h` in `dtype`: float32 or bfloat16, whole lane
    tiles, and a tile of 128 lanes that fits VMEM."""
    dtype = jnp.dtype(dtype)
    return dtype.name in _DTYPES and experts_plan(
        8, d, h, 1, dtype.itemsize, matrices) is not None


def experts_sources(loads, tiles: int):
    """The weight block each grid step (e, j) names, flattened [2 E tiles]
    as (expert, tile) pairs: a hit expert's (e, j); for an expert no row
    picked (loads[e] == 0) the block the step before it names, so that the
    pipeline fetches nothing for it: the last hit expert's last tile, or,
    before any hit, the first hit expert's tile 0 ((0, 0) where none is
    hit)."""
    e_held = loads.shape[0]
    hit = loads > 0
    at = jnp.arange(e_held, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(hit, at, -1))    # last hit at or before e
    first = jnp.argmax(hit).astype(jnp.int32)         # 0 where none is hit
    tile = jnp.arange(tiles, dtype=jnp.int32)[None, :]
    src_e = jnp.where(hit, at, jnp.where(last >= 0, last, first))[:, None]
    src_j = jnp.where(hit[:, None], tile,
                      jnp.where(last >= 0, tiles - 1, 0)[:, None])
    src_e = jnp.broadcast_to(src_e, (e_held, tiles))
    return jnp.stack([src_e, src_j], axis=-1).reshape(-1)


@functools.lru_cache(maxsize=64)
def _planned(rows, d, h, experts, dtype, matrices=3) -> ExpertsPlan:
    """`experts_plan` for one call shape, worked out once a process; working
    it out leaves the record `dl4j/kernels/grouped_experts` in the span log:
    written while a kernel is built, never while one runs."""
    from ..telemetry import tracer

    plan = experts_plan(rows, d, h, experts, jnp.dtype(dtype).itemsize,
                        matrices)
    if plan is None:
        raise ValueError(f"no tile of 128 lanes fits experts {d} wide with a "
                         f"hidden width of {h} in {dtype}")
    tracer().instant("dl4j/kernels/grouped_experts", experts=experts,
                     rows=rows, d=d, h=h, dtype=dtype,
                     activation="swiglu" if matrices == 3 else "relu2",
                     matrices=matrices, **plan._asdict())
    return plan


def _make_kernel(tiles: int, gated: bool = True):
    """Grid (held experts, tiles). The rows `[rows, d]` and their weights
    `[rows, E]` are one block for the whole grid, as is the float32 output,
    which the first step zeroes and every hit step adds to. `gated`: the
    SwiGLU unit over `W_g`, `W_u`, `W_d`; else relu^2 over `W_u`, `W_d`."""

    def kernel(loads_ref, src_ref, u_ref, w_ref, *refs):
        *weights, o_ref = refs
        wd_ref = weights[-1]
        e, j = pl.program_id(0), pl.program_id(1)

        @pl.when((e == 0) & (j == 0))
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(loads_ref[e] > 0)
        def _():
            x = u_ref[...]
            if gated:
                g = jnp.dot(x, weights[0][0],
                            preferred_element_type=jnp.float32)
                up = jnp.dot(x, weights[1][0],
                             preferred_element_type=jnp.float32)
                hidden = jax.nn.silu(g) * up
            else:
                up = jnp.dot(x, weights[0][0],
                             preferred_element_type=jnp.float32)
                hidden = jnp.square(jax.nn.relu(up))
            hidden = hidden.astype(wd_ref.dtype)
            y = jnp.dot(hidden, wd_ref[0], preferred_element_type=jnp.float32)
            w = w_ref[...]
            mine = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1) == e
            o_ref[...] += y * jnp.sum(jnp.where(mine, w, 0.0), axis=1,
                                      keepdims=True)

    return kernel


def grouped_experts(u, w_rows, loads, w_g, w_u, w_d, *,
                    interpret: Optional[bool] = None):
    """sum over held e of w_rows[:, e] * Expert_e(u), the experts that no
    row picked neither read nor computed.

    u [n, d] (taken in the weights' dtype); w_rows [n, E] float32, each
    row's weight on each held expert (0 where it did not pick it, and for a
    dead row); loads [E] int, the rows that picked each expert (an expert
    is run where its load is over 0); w_g, w_u [E, d, h] and w_d [E, h, d],
    float32 or bfloat16. Returns [n, d] float32: `_swiglu`'s arithmetic, or
    `_relu2`'s where `w_g` is None (module docstring). Compiled Pallas on
    the TPU; `interpret=True` (automatic off it) runs the same kernel
    through the interpreter."""
    n, d = u.shape
    e_held, d_w, h = w_u.shape
    if (d_w, w_g is None or w_g.shape == w_u.shape, w_d.shape, w_rows.shape,
            loads.shape) != (d, True, (e_held, h, d), (n, e_held), (e_held,)):
        raise ValueError(f"rows {u.shape}, weights {w_rows.shape}, loads "
                         f"{loads.shape} and experts "
                         f"{None if w_g is None else w_g.shape} / "
                         f"{w_u.shape} / {w_d.shape} disagree")
    if jnp.dtype(w_u.dtype).name not in _DTYPES:
        raise ValueError(f"grouped_experts takes float32 or bfloat16 "
                         f"experts, got {w_u.dtype}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    matrices = (w_u, w_d) if w_g is None else (w_g, w_u, w_d)
    return _experts_call(u, w_rows, loads, matrices, bool(interpret))


@functools.partial(jax.jit, static_argnums=(4,))  # graftlint: disable=unwatched-jit-entry
def _experts_call(u, w_rows, loads, matrices, interpret):
    """`grouped_experts`' kernel, jitted on its own: a stack's identical
    expert layers then trace once a process and lower once an executable.
    `matrices`: (W_g, W_u, W_d), or (W_u, W_d) for relu^2 experts."""
    n, d = u.shape
    e_held, _, h = matrices[-2].shape
    dtype = matrices[-1].dtype
    plan = _planned(n, d, h, e_held, jnp.dtype(dtype).name, len(matrices))
    tile, tiles, rp = plan.tile, plan.tiles_an_expert, plan.rows_padded
    u = jnp.pad(u.astype(dtype), ((0, rp - n), (0, 0)))
    w_rows = jnp.pad(w_rows.astype(jnp.float32), ((0, rp - n), (0, 0)))
    src = experts_sources(loads, tiles)

    def weights(shape, at):
        return pl.BlockSpec(shape, lambda e, j, loads, src: at(
            src[2 * (e * tiles + j)], src[2 * (e * tiles + j) + 1]))

    whole = lambda shape: pl.BlockSpec(shape, lambda e, j, *_: (0, 0))
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=plan.vmem_limit_bytes)}
    into = weights((1, d, tile), lambda s, t: (s, 0, t))
    out = pl.pallas_call(
        _make_kernel(tiles, gated=len(matrices) == 3),
        out_shape=jax.ShapeDtypeStruct((rp, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(e_held, tiles),
            in_specs=[whole((rp, d)), whole((rp, e_held))]
            + [into] * (len(matrices) - 1)
            + [weights((1, tile, d), lambda s, t: (s, t, 0))],
            out_specs=whole((rp, d))),
        interpret=interpret,
        name="grouped_experts",
        cost_estimate=pl.CostEstimate(
            flops=2 * len(matrices) * rp * d * h * e_held,
            transcendentals=rp * h * e_held if len(matrices) == 3 else 0,
            bytes_accessed=len(matrices) * d * h * e_held
            * jnp.dtype(dtype).itemsize),
        **params,
    )(loads.astype(jnp.int32), src, u, w_rows, *matrices)
    return out[:n]
