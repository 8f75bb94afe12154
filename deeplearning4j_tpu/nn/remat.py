"""Selective rematerialization policies (ISSUE 18).

`remat` (NeuralNetConfiguration / LayerConf) decides WHERE checkpoint
boundaries go (None / "layer" / "blocks" / "full"; the 1F1B stage body
always checkpoints its vmapped stage). `remat_policy` decides WHAT each
boundary saves — a named `jax.checkpoint_policies` entry threaded
through every `jax.checkpoint(...)` site:

  name          policy                                 saves
  ----          ------                                 -----
  None          (jax default)                          nothing: recompute
                                                       everything from the
                                                       boundary inputs
  "nothing"     nothing_saveable                       same, stated
                                                       explicitly
  "dots"        checkpoint_dots                        matmul/einsum
                                                       outputs (recompute
                                                       only the cheap
                                                       elementwise tail)
  "dots_no_batch"  checkpoint_dots_with_no_batch_dims  matmuls WITHOUT a
                                                       batch dim (weight-
                                                       shaped residuals
                                                       only — activations
                                                       still recomputed)
  "everything"  everything_saveable                    all residuals (the
                                                       no-remat memory
                                                       profile inside a
                                                       checkpoint wrapper)

All policies are numerics no-ops: they trade activation memory for
recompute FLOPs without touching the math (asserted to f32-ulp
equivalence in tests/test_precision_remat.py).

`saved_bytes` is the static activation-byte accounting — what one
checkpoint boundary actually saves for a concrete call — published
through `_pp_info` the way `_ZeroPlan` publishes its byte accounting.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["REMAT_POLICIES", "resolve_policy", "saved_bytes"]

#: name -> jax.checkpoint policy callable (None = jax's save-nothing
#: default). Names are config-file citizens: serialized in the model
#: JSON and recorded in FitCheckpointer context.
REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch":
        jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "everything": jax.checkpoint_policies.everything_saveable,
}


def resolve_policy(name: Optional[str]):
    """Policy name -> `jax.checkpoint(policy=...)` callable (None stays
    None: jax's default save-nothing behaviour). Raises with the valid
    names on a typo — a silently-ignored policy would quietly change the
    memory profile the user asked for."""
    if name is None:
        return None
    try:
        return REMAT_POLICIES[name]
    except KeyError:
        raise ValueError(
            f"unknown remat_policy '{name}'. Valid policies: "
            f"{', '.join(sorted(REMAT_POLICIES))} (or None for jax's "
            "save-nothing default)") from None


def saved_bytes(fn: Callable, *args, policy: Optional[str] = None) -> int:
    """Static activation-byte accounting: total bytes of INTERMEDIATE
    residuals the checkpointed `fn(*args)` saves for the backward pass
    under the named policy (0 = recompute everything from the boundary
    inputs). Residuals that are just the boundary's own arguments are
    excluded — they are alive either way; the accounting counts only
    what the policy ADDS. Uses jax's (private) `saved_residuals` on
    concrete zero-filled arguments — a trace-time measurement, no
    training step involved."""
    # private: the installed jax does not re-export saved_residuals
    from jax._src.ad_checkpoint import saved_residuals

    def concrete(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            return jnp.zeros(a.shape, a.dtype)
        return a

    args = jax.tree_util.tree_map(concrete, args)
    ck = jax.checkpoint(fn, policy=resolve_policy(policy))
    total = 0
    for val, source in saved_residuals(ck, *args):
        if source.startswith("from the argument"):
            continue
        aval = getattr(val, "aval", val)
        total += int(np.prod(aval.shape) if aval.shape else 1) \
            * jnp.dtype(aval.dtype).itemsize
    return total
