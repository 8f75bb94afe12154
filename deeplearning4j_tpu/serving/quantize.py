"""Inference-time parameter transforms: bf16 cast and int8 weight-only
quantization.

Weight-only int8 (the LLM.int8()/AWQ-family baseline shape, minus the
outlier handling those papers add): every float weight tensor with >=
`min_elems` elements is stored as int8 plus ONE per-tensor symmetric
scale (`scale = absmax / 127`); activations stay float. Dequantization
(`int8 * scale`) happens INSIDE the compiled forward, so the serving
plane holds a ~4x smaller parameter snapshot and the XLA program sees a
constant-folded-friendly `convert+mul` on the weight path. Small leaves
(biases, BN stats) stay in their original dtype — quantizing a
10-element bias saves nothing and costs accuracy.

This is post-training quantization with no calibration pass: expect
~1e-2-level output drift on softmax heads (tested), NOT bit-exactness.
Accuracy-critical serving should stay on fp32/bf16; int8 is the
memory-bound-throughput knob.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["QuantizedTree", "quantize_tree", "cast_tree"]

_FLOAT_KINDS = ("f",)  # np dtype.kind for floating leaves


def _is_quantizable(leaf: np.ndarray, min_elems: int) -> bool:
    a = np.asarray(leaf)
    return (a.dtype.kind in _FLOAT_KINDS and a.ndim >= 2
            and a.size >= min_elems)


class QuantizedTree:
    """A flattened parameter pytree with int8-quantized weight leaves.

    `data` is the flat tuple handed to the compiled forward: a plain
    array for pass-through leaves, an `(int8_weights, scale_scalar)` pair
    for quantized ones. Keeping the scale a RUNTIME argument (not a
    trace-time constant) means two snapshots of the same architecture
    lower to identical XLA programs — so a hot-swap to a re-quantized
    checkpoint reuses the cached executables instead of recompiling
    every bucket. `scales[i]` records the python-float scale (or None)
    for introspection only. `rebuild(data)` runs under jit and returns
    the original tree structure with every leaf back in `compute_dtype`.
    """

    def __init__(self, data: Tuple, scales: Tuple[Optional[float], ...],
                 treedef, compute_dtype=jnp.float32):
        self.data = tuple(data)
        self.scales = tuple(scales)
        self.treedef = treedef
        self.compute_dtype = compute_dtype

    @property
    def n_quantized(self) -> int:
        return sum(1 for s in self.scales if s is not None)

    def nbytes(self) -> int:
        """From the leaves' shapes: nothing is copied off the device."""
        return int(sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree_util.tree_leaves(self.data)))

    def rebuild(self, data):
        """Dequantize a flat `data` tuple back into the original pytree —
        traceable (called inside the compiled forward)."""
        leaves = []
        for d, s in zip(data, self.scales):
            if s is not None:
                q, scale = d
                d = q.astype(self.compute_dtype) \
                    * scale.astype(self.compute_dtype)
            leaves.append(d)
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


def quantize_tree(tree, min_elems: int = 64,
                  compute_dtype=jnp.float32) -> QuantizedTree:
    """Per-tensor symmetric int8 weight-only quantization of a parameter
    pytree. Leaves below `min_elems` elements or with ndim < 2 pass
    through untouched (biases, scalars, BN running stats)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    data, scales = [], []
    for leaf in leaves:
        a = np.asarray(leaf)
        if _is_quantizable(a, min_elems):
            absmax = float(np.max(np.abs(a)))
            scale = (absmax / 127.0) if absmax > 0 else 1.0
            q = np.clip(np.round(a / scale), -127, 127).astype(np.int8)
            data.append((jnp.asarray(q), jnp.asarray(scale, np.float32)))
            scales.append(scale)
        else:
            data.append(jnp.asarray(a))
            scales.append(None)
    return QuantizedTree(tuple(data), tuple(scales), treedef,
                         compute_dtype=compute_dtype)


def cast_tree(tree, dtype):
    """Cast every floating leaf of a pytree to `dtype` (bf16 snapshot for
    the half-precision serving path); non-float leaves pass through."""
    dtype = jnp.dtype(dtype)

    def cast(leaf):
        a = jnp.asarray(leaf)
        if jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(dtype)
        return a

    return jax.tree_util.tree_map(cast, tree)
