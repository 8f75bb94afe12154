"""The layers of Nemotron-H (NVIDIA; `nemotron_h` in the public modelling
code; Nemotron 3 Super): a stack in which every layer is ONE part behind an
RMSNorm,

    x = x + Part_l(N_l x)

and `hybrid_override_pattern[l]` says which part:

    "M"  Mamba-2 with G groups of B and C, the gated norm taken within each
         group (`hybrid_ssm.py` module docstring: the same mixer as Granite
         4.0-H's, `ssm_groups` G)
    "*"  grouped-query attention without positions (the same as Granite's)
    "E"  LatentMoE (`shortcut_moe.py` `SparseExpertsLayer`):
           s = sigmoid(u W_r) in float32;  picks = top-k of s + b_corr
           w_e = scale * s_e / sum over the k picks of s
           m = (sum over picked held e of w_e * relu(l U_e)^2 V_e) W_up
               + relu(u S_u)^2 S_d                   l = u W_down
         the routed experts non-gated and in a latent narrower than the
         model, the router and the shared expert at full width.

No residual multiplier and no biases but the convolution's; the products
and the residual stream as in `hybrid_ssm.py`.

`NemotronHBlock` is a `HybridSSMBlock` whose topology is one part: the
mixers' code, their parameters and their served steps are Granite's, and
an "E" layer is the expert layer alone. Serving (`serving/decode/engine.py`
states the contract): an "M" layer keeps a sequence's state and no page,
a "*" layer pages keys and values, an "E" layer keeps neither and returns
its pick counts; its tick runs the held experts through the relu^2 form of
`kernels.grouped_experts` on the TPU (`decode_experts`).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..conf.base import register_layer
from ..conf.input_type import InputType
from .hybrid_ssm import HybridSSMBlock
from .shortcut_moe import _F32, SparseExpertsLayer, _rms_norm

__all__ = ["NemotronHBlock", "PATTERN"]

# hybrid_override_pattern's letters -> a block's part
PATTERN = {"M": "mamba", "*": "attention", "E": "moe"}


@register_layer
@dataclass
class NemotronHBlock(HybridSSMBlock):
    """One Nemotron-H layer (module docstring): x [B, T, d] -> [B, T, d]
    float32. `mixer` is its one part: "mamba", "attention" or "moe"; the
    expert fields describe the "moe" part, with `latent` the routed
    experts' width and `routed_scaling` the picks' scale."""

    latent: int = 0
    routed_scaling: float = 1.0

    def __post_init__(self):
        if self.mixer not in PATTERN.values():
            raise ValueError(f"mixer must be one of {tuple(PATTERN.values())}"
                             f", got {self.mixer!r}")

    def experts(self) -> SparseExpertsLayer:
        return SparseExpertsLayer(
            n_experts=self.n_experts, top_k=self.top_k,
            expert_hidden=self.expert_hidden, shared_hidden=self.shared_hidden,
            held_experts=self.held_experts, scoring="sigmoid",
            expert_activation="relu2", latent=self.latent,
            routed_scaling=self.routed_scaling, weight_init=self.weight_init,
            dist=self.dist, bias_init=self.bias_init, dtype=self.dtype)

    def init_params(self, rng, it: InputType):
        d = self._width(it)
        keys = iter(jax.random.split(rng, 12))
        norm = jnp.ones((d,), jnp.dtype(self.dtype or "float32"))
        if self.mixer == "moe":
            return {"n": norm, "moe": self.experts().init_params(
                next(keys), it, width=d)}
        return {"n": norm, "mixer": self.init_mixer(keys, d)}

    def _block(self, p, x, mix, live=None, experts="cond"):
        """x + Part(N x): `mix(p_mixer, x_normed)` is a mixer, the "moe"
        part is the expert layer (`experts` its held experts' path)."""
        x = x.astype(_F32)
        u = _rms_norm(x, p["n"], self.eps)
        if self.mixer == "moe":
            m, counts = self.experts().mix(p["moe"], u, live, experts)
            return x + m, counts
        return x + mix(p["mixer"], u), None

    # -- the decode plane's contract --------------------------------------
    def decode_cache(self, width: int):
        """An attention layer pages its Hkv key/value heads; the others keep
        no pages."""
        return (0, 0) if self.mixer == "moe" else super().decode_cache(width)

    def decode_experts(self, phase: str, width: int):
        """The held experts' path of a "moe" layer (`SparseExpertsLayer.
        decode_experts`); None for a mixer."""
        if self.mixer != "moe":
            return None
        return super().decode_experts(phase, width)

    def decode_prefill_step(self, io, attention=None):
        if self.mixer != "moe":
            return super().decode_prefill_step(io, attention)

        def step(p, x, kv, sc, channel, blk, off, pos, lengths):
            y, counts = self._block(p, x, None, pos < lengths[:, None])
            return y, kv, sc, counts
        return step

    def decode_tick_step(self, io, attention=None, experts="cond"):
        if self.mixer != "moe":
            return super().decode_tick_step(io, attention, experts)

        def step(p, x, kv, sc, channel, blk, off, tables, positions, lengths):
            # block 0 is the trash block: a row that writes there is a pad
            y, counts = self._block(p, x, None, (blk > 0)[:, None], experts)
            return y, kv, sc, counts
        return step
