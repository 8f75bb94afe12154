"""Latent attention and shortcut-connected sparse experts: the block of
LongCat-Flash (Meituan, 2025), and the two layers it is made of.

One block holds four RMSNorms, two latent-attention (MLA, DeepSeek-V2)
sub-layers, two dense SwiGLU FFNs and one sparse expert layer whose
output skips the second attention and FFN (the shortcut):

    a  = h + MLA_0(N_1 h)
    u  = N_2 a
    m  = MoE(u)
    b  = a + FFN_0(u)
    c  = b + MLA_1(N_3 b)
    h' = c + FFN_1(N_4 c) + m

    MLA(x):  q = (N_q(x W_qa) W_qb) * s_q -> heads of [q_nope | q_rope]
             [c | k_rope] = x W_kva;  c = N_kv(c) * s_kv
             [k_nope | v] a head = c W_kvb;  q_rope, k_rope rotated in
             interleaved pairs, k_rope shared by the heads
             p = softmax_causal((q_nope.k_nope + q_rope.k_rope) / sqrt(qk))
             out = concat_heads(p v) W_o
    MoE(u):  s = softmax(u W_r) in float32 over routed + identity experts
             picks = top-k of (s + b_corr);  w_e = scale * s_e
             m = sum over picked routed e of w_e * Expert_e(u)
               + sum over picked identity e of w_e * u

`SparseExpertsLayer` knows two more scoring rules, `scoring=
"softmax_picked"` (Granite 4.0): the picks are the top-k router LOGITS,
no correction bias, and `w_e = scale * softmax over the picked logits`;
and `scoring="sigmoid"` (Nemotron-H, DeepSeek-V3): `s = sigmoid(u W_r)`
in float32, picks by `s + b_corr`, `w_e = scale * s_e / sum over ALL the
picks of s` (held or not). It knows a SHARED expert (`shared_hidden`), the
same unit at its own width, that every token takes with weight 1; a
second expert unit, `expert_activation="relu2"` (Nemotron-H):
`relu(x W_u)^2 W_d`, two matrices and no gate; and a LATENT width
(`latent`, Nemotron-H's LatentMoE): the routed experts live between
`W_down` [d, latent] and `W_up` [latent, d],

    m = (sum over picked held e of w_e * Expert_e(u W_down)) W_up

while the router and the shared expert read `u` at full width. `W_up` is
linear, so the shares still add up.

No biases. `s_q = sqrt(d / q_rank)` and `s_kv = sqrt(d / kv_rank)` where
`mla_scale` is set. Matrix products take their operands in the weights'
dtype and sum in float32; norms, softmaxes, rotations and the residual
stream are float32 (a block of bfloat16 weights takes bfloat16 tokens
and hands on float32).

Expert parallelism is a share, not a runtime: `SparseExpertsLayer` is
TOLD which routed experts it holds (`held_experts`, a range), routes
over all of them, and computes its own experts' part of `m` plus the
identity experts' and the shared expert's part for its own tokens. The
parts the shares give, with the identity and the shared part counted
once, add up to the whole layer
(`tests/test_shortcut_moe.py`); what absent experts would have added is
another chip's to add.

The held experts' products are grouped: each expert runs over the rows
that picked it and no other, gathered into `rows_per_expert(n)` slots:
among hundreds of tokens (a prefill) one batched product over all the
held experts' slots. Where an expert's load passes its slots, the rows
left over are worked off in further passes of a quarter of the slots, by
the experts that have rows left and no other, each under a conditional (one
in a loop over the experts where a layer holds over `_UNROLLED_EXPERTS`,
so that the program's size and its compile do not grow with them): no
pick is ever dropped, and an uneven routing costs the experts it loads,
not every expert over every token. A batch of at most twice the slots (a
decode tick) is not gathered: every held expert runs over all the rows
under their weights, and not at all where no row picked it (its weights
are then not read). A tick takes one of two paths (`EXPERT_PATHS`): on
the TPU it streams the held experts through ONE Pallas kernel a layer
("grouped_kernel", `kernels.grouped_experts`: a grid step an expert or a
tile of it, the next one's weights fetched while this one computes, the
experts no row picked neither fetched nor computed), where the layer's
`decode_experts("tick", width)` finds the TPU and weights of whole lane
tiles, whatever the tick's rows (Nemotron-H's 128 are four times a held
expert's slots); everywhere else (`apply`, a prefill, the CPU) each
expert runs under its own conditional ("cond": the kernel's oracle).

Serving (`serving/decode/engine.py` states the layers' contract): the
block caches, for a token and an attention, the latent `c` (after norm
and scale) beside the rotated `k_rope`, zero-padded to whole 128-lane
tiles. A prefill expands `c W_kvb` into heads ("mla_expanded": cheaper
over a whole prompt); a tick absorbs `W_kvb` into the query and the
output and attends over the cached latent as it lies, never building
heads of K/V for every cached token: on the TPU inside one Pallas kernel
that reads the rows' live pages through their tables and multiplies on the
MXU ("mla_paged", `kernels.paged_attention.paged_latent_attention`),
elsewhere over the view the tables gather ("mla_absorbed": the CPU, an
int8 arena, the kernel's oracle).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..conf.base import LayerConf, register_layer
from ..conf.input_type import InputType

__all__ = ["RMSNormLayer", "SparseExpertsLayer", "ShortcutMoEBlock"]

_F32 = jnp.float32
_NEG = -1e30
PICK_COUNTS = ("picks", "identity", "held", "held_hit", "held_load_max")
# slots a held expert gets in a pass = this x its mean load under even
# routing, and never more than a quarter of the tokens; an expert whose load
# passes them takes further passes. Where a token picks under 1 route in 32
# (LongCat-Flash: 12 of 768, slots for an eighth of the tokens) the factor
# decides; where it picks more (Granite: 10 of 72, a mean load of 0.139 n)
# the quarter does, 1.8 times the mean load there: the products of the first
# pass, every held expert's slots, stay under twice the (token, held expert)
# pairs picked, and the few experts loaded beyond that take a second.
# Grouping engages for every density of picks at batches over twice the
# slots (`_held_sum`).
_SLOT_FACTOR = 8
# held experts up to which each has a conditional of its own in the program;
# a layer that holds more runs them in a loop under one conditional (in a
# program each costs compile time: Nemotron-H's 128 held in 5 layers made a
# prefill of 645, about 70 s to compile for a v5e)
_UNROLLED_EXPERTS = 64
SCORINGS = ("softmax_all", "softmax_picked", "sigmoid")
EXPERT_ACTIVATIONS = ("swiglu", "relu2")
EXPERT_PATHS = ("cond", "grouped_kernel")


def _rms_norm(x, g, eps):
    x = x.astype(_F32)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * g.astype(_F32))


def _mm(x, w):
    """x @ w: operands in the weights' dtype, sum and result float32."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=_F32)


def _swiglu(x, w_g, w_u, w_d):
    return _mm(jax.nn.silu(_mm(x, w_g)) * _mm(x, w_u), w_d)


def _relu2(x, w_u, w_d):
    return _mm(jnp.square(jax.nn.relu(_mm(x, w_u))), w_d)


def _unit(x, w_g, w_u, w_d):
    """One expert: SwiGLU where it has a gate, else relu^2 (`w_g` None)."""
    return _relu2(x, w_u, w_d) if w_g is None else _swiglu(x, w_g, w_u, w_d)


def _ffn(p, x):
    return _swiglu(x, p["W_g"], p["W_u"], p["W_d"])


def _rotate(x, pos, theta):
    """Rotary positions over interleaved pairs (x[2i], x[2i+1]) of the
    last axis; `pos` broadcasts against x's leading axes but the last."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = pos.astype(_F32)[..., None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(_F32).reshape(*x.shape[:-1], half, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _pad128(n: int) -> int:
    return 128 * -(-n // 128)


@register_layer
@dataclass
class RMSNormLayer(LayerConf):
    """x * rsqrt(mean(x^2) + eps) * g * scale over the last axis, in
    float32 (the final norm before a head; `scale` is what a model divides
    its logits by, inverted: Granite's 1 / `logits_scaling`). Keeps no
    cache: in a served stack it is applied to the tokens of the step, as
    they come."""

    input_kind = "any"

    eps: float = 1e-5
    scale: float = 1.0

    @property
    def has_params(self) -> bool:
        return True

    def init_params(self, rng, it: InputType):
        width = it.size if it.kind == "rnn" else it.flat_size()
        return {"g": jnp.ones((width,), jnp.dtype(self.dtype or "float32"))}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        return self._norm(params, x), state

    def _norm(self, p, x):
        y = _rms_norm(x, p["g"], self.eps)
        return y if self.scale == 1.0 else y * self.scale

    # -- the decode plane's contract --------------------------------------
    def decode_cache(self, width: int):
        return 0, 0

    def decode_state(self, width: int):
        return None

    def decode_attention(self, phase: str, spec):
        return None

    def decode_prefill_step(self, io, attention=None):
        def step(p, x, kv, sc, *_):
            return self._norm(p, x), kv, sc, None
        return step

    decode_tick_step = decode_prefill_step


@register_layer
@dataclass
class SparseExpertsLayer(LayerConf):
    """Top-k routed SwiGLU (or relu^2) experts with zero-compute (identity)
    experts, in sequence layout: x [B, T, d] -> [B, T, d] float32 (module
    docstring). `held_experts` = [lo, hi) of the `n_experts` routed ones
    live here (None: all); the weights hold those alone."""

    input_kind = "rnn"

    n_experts: int = 8              # routed experts of the whole layer
    n_identity: int = 0             # identity experts: no weights
    top_k: int = 2
    expert_hidden: int = 0          # default: 4 * width
    routed_scaling: float = 1.0
    held_experts: Optional[List[int]] = None
    scoring: str = "softmax_all"    # or "softmax_picked", "sigmoid"
    shared_hidden: int = 0          # the shared expert's width; 0: none
    expert_activation: str = "swiglu"   # or "relu2" (module docstring)
    latent: int = 0                 # the routed experts' width; 0: the model's

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(it.size, it.timesteps)

    @property
    def has_params(self) -> bool:
        return True

    def __post_init__(self):
        if self.scoring not in SCORINGS:
            raise ValueError(f"scoring must be one of {SCORINGS}, got "
                             f"{self.scoring!r}")
        if self.expert_activation not in EXPERT_ACTIVATIONS:
            raise ValueError(f"expert_activation must be one of "
                             f"{EXPERT_ACTIVATIONS}, got "
                             f"{self.expert_activation!r}")

    def held(self) -> range:
        lo, hi = self.held_experts or (0, self.n_experts)
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(f"held_experts {self.held_experts} is no "
                             f"range of the {self.n_experts} routed experts")
        return range(int(lo), int(hi))

    def rows_per_expert(self, n: int) -> int:
        """Slots a held expert has in a pass over `n` tokens:
        `_SLOT_FACTOR` times its mean load under even routing or a quarter
        of the tokens, whichever is less; at least 32, in whole sublane
        tiles; never more than the tokens."""
        mean = n * self.top_k / (self.n_experts + self.n_identity)
        share = min(_SLOT_FACTOR * mean, n / 4)
        return min(n, max(32, 8 * math.ceil(share / 8)))

    def init_params(self, rng, it: InputType, width: Optional[int] = None):
        d = width or it.size
        h = self.expert_hidden or 4 * d
        e, routes = len(self.held()), self.n_experts + self.n_identity
        k = jax.random.split(rng, 7)
        gated = self.expert_activation == "swiglu"
        de = self.latent or d
        p = {
            "router_W": self._winit(k[0], (d, routes), d, routes),
            # expert_-prefixed tensors shard on axis 0 (expert parallelism)
            "expert_W_u": self._winit(k[2], (e, de, h), de, h),
            "expert_W_d": self._winit(k[3], (e, h, de), h, de),
        }
        if gated:
            p["expert_W_g"] = self._winit(k[1], (e, de, h), de, h)
        if self.scoring != "softmax_picked":     # the correction bias
            p["router_bias"] = self._binit((routes,))
        if self.shared_hidden:
            s = self.shared_hidden
            p.update(shared_W_u=self._winit(k[5], (d, s), d, s),
                     shared_W_d=self._winit(k[6], (s, d), s, d))
            if gated:
                p["shared_W_g"] = self._winit(k[4], (d, s), d, s)
        if self.latent:
            down, up = jax.random.split(jax.random.fold_in(rng, 7))
            p.update(W_down=self._winit(down, (d, de), d, de),
                     W_up=self._winit(up, (de, d), de, d))
        return p

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        live = None if mask is None else mask.astype(bool)
        return self.mix(params, x, live)[0], state

    # -- routing -----------------------------------------------------------
    def route(self, p, u):
        """(ids [N, k] of the picked experts, w [N, k] their weights), in
        float32. "softmax_all": softmax over all routes, picks by score +
        correction bias, weights the scaled scores themselves (not
        renormalised). "softmax_picked": picks by logit, weights the
        scaled softmax over the picked logits. "sigmoid": picks by sigmoid
        score + correction bias, weights the scaled scores over their sum
        over the k picks."""
        with jax.default_matmul_precision("highest"):
            s = jnp.dot(u.astype(_F32), p["router_W"].astype(_F32))
        k = min(self.top_k, s.shape[-1])
        if self.scoring == "softmax_picked":
            top, ids = jax.lax.top_k(s, k)
            return ids, self.routed_scaling * jax.nn.softmax(top, axis=-1)
        if self.scoring == "sigmoid":
            s = jax.nn.sigmoid(s)
            _, ids = jax.lax.top_k(s + p["router_bias"].astype(_F32), k)
            w = jnp.take_along_axis(s, ids, axis=-1)
            return ids, self.routed_scaling * w / (
                jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        s = jax.nn.softmax(s, axis=-1)
        _, ids = jax.lax.top_k(s + p["router_bias"].astype(_F32), k)
        return ids, self.routed_scaling * jnp.take_along_axis(s, ids, axis=-1)

    def decode_experts(self, phase: str, width: int) -> str:
        """The path of the held experts' products in `phase` of a served
        stack (module docstring): "grouped_kernel" for a tick where the
        backend is the TPU (`pallas_supported`: and the kernels are not
        switched off) and the experts, `width` wide (or `latent`), are
        float32 or bfloat16 in whole lane tiles
        (`grouped_experts_supported`), else "cond"."""
        from ...kernels import pallas_supported
        from ...kernels.grouped_experts import grouped_experts_supported

        h = self.expert_hidden or 4 * width
        matrices = 3 if self.expert_activation == "swiglu" else 2
        if phase == "tick" and pallas_supported() and \
                grouped_experts_supported(self.latent or width, h,
                                          self.dtype or "float32", matrices):
            return "grouped_kernel"
        return "cond"

    def mix(self, p, x, live=None, experts="cond"):
        """(m [B, T, d] float32, counts [5] int32 as `PICK_COUNTS`): the
        held experts' part, the identity experts' part and the shared
        expert's of the layer's output for `x`; `live` [B, T] leaves pad
        tokens out of the first two and of the counts. `experts` is the
        path of a batch within twice the slots, and of any tick's batch
        for "grouped_kernel" (`EXPERT_PATHS`)."""
        if experts not in EXPERT_PATHS:
            raise ValueError(f"experts must be one of {EXPERT_PATHS}, got "
                             f"{experts!r}")
        shape = x.shape
        u = x.reshape(-1, shape[-1])
        ids, w = self.route(p, u)
        held = self.held()
        picked = jnp.broadcast_to(
            True if live is None else live.reshape(-1, 1), ids.shape)
        w = jnp.where(picked, w, 0.0)
        on_identity = picked & (ids >= self.n_experts)
        # hit[n, k, e]: token n's k-th pick is held expert e
        hit = picked[..., None] & (
            (ids - held.start)[..., None] == jnp.arange(len(held)))
        took = jnp.any(hit, axis=1)                             # [N, E]
        w_held = jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1)
        loads = jnp.sum(took, axis=0)
        if self.latent:
            m = _mm(self._held_sum(p, _mm(u, p["W_down"]), w_held, took,
                                   loads, experts), p["W_up"])
        else:
            m = self._held_sum(p, u, w_held, took, loads, experts)
        if self.n_identity:
            m = m + jnp.sum(jnp.where(on_identity, w, 0.0), axis=-1,
                            keepdims=True) * u.astype(_F32)
        if self.shared_hidden:
            m = m + _unit(u, p.get("shared_W_g"), p["shared_W_u"],
                          p["shared_W_d"])
        counts = jnp.stack([jnp.sum(picked), jnp.sum(on_identity),
                            jnp.sum(loads), jnp.sum(loads > 0),
                            jnp.max(loads)]).astype(jnp.int32)
        return m.reshape(shape).astype(_F32), counts

    def _held_sum(self, p, u, w_held, took, loads, experts="cond"):
        """sum over held e of w_held[:, e] * Expert_e(u): grouped (module
        docstring). u [N, d] (d the latent width where there is one);
        w_held, took [N, E]; loads [E]."""
        n, d = u.shape
        e_held = w_held.shape[1]
        slots = self.rows_per_expert(n)
        w_g = p.get("expert_W_g")                 # None: relu^2 experts
        u = u.astype(p["expert_W_u"].dtype)     # what the products take
        if experts == "grouped_kernel":     # a tick, whatever its rows
            from ...kernels.grouped_experts import grouped_experts
            # always the COMPILED kernel, whatever the process's backend
            return grouped_experts(u, w_held, loads, w_g, p["expert_W_u"],
                                   p["expert_W_d"], interpret=False)

        def expert(e, rows, w_rows, left):
            """w_rows * Expert_e(rows), or nothing where no row is `left`
            for e (its weights are then not read)."""
            def run(_):
                y = _unit(rows, None if w_g is None else w_g[e],
                          p["expert_W_u"][e], p["expert_W_d"][e])
                return y * w_rows[:, None]
            return jax.lax.cond(
                left > 0, run,
                lambda _: jnp.zeros((rows.shape[0], d), _F32), None)

        unrolled = e_held <= _UNROLLED_EXPERTS
        if n <= 2 * slots:      # not worth a gather and a scatter
            if unrolled:
                return sum(expert(e, u, w_held[:, e], loads[e])
                           for e in range(e_held))
            return jax.lax.fori_loop(
                0, e_held, lambda e, out: out + expert(e, u, w_held[:, e],
                                                       loads[e]),
                jnp.zeros((n, d), _F32))

        # rank of token n in expert e's group: how many before it took e
        rank = jnp.cumsum(took, axis=0) - 1                     # [N, E]
        tokens = jnp.arange(n, dtype=jnp.int32)[:, None]

        def one_pass(out, first, size, products):
            """The rows ranked first .. first + size - 1 of every expert's
            group, gathered into `size` slots, through `products`, added
            to their tokens."""
            slot = jnp.where(took & (rank >= first), rank - first, size)
            idx = jnp.zeros((e_held, size), jnp.int32).at[
                jnp.arange(e_held)[None, :], slot].set(tokens, mode="drop")
            left = loads - first
            filled = jnp.arange(size)[None, :] < left[:, None]
            w_rows = jnp.where(
                filled, jnp.take_along_axis(w_held.T, idx, axis=1), 0.0)
            parts = products(u[idx], w_rows, left)              # [E, S, d]
            return out.at[idx.reshape(-1)].add(parts.reshape(-1, d))

        def all_at_once(rows, w_rows, left):
            """Every expert's slots in one batched product: among hundreds
            of tokens every held expert has rows, and one product streams
            the experts' weights where a conditional an expert would stop
            and start 36 times a layer."""
            dot = functools.partial(jnp.einsum, preferred_element_type=_F32)
            if w_g is None:
                hidden = jnp.square(jax.nn.relu(
                    dot("esd,edh->esh", rows, p["expert_W_u"])))
            else:
                hidden = jax.nn.silu(dot("esd,edh->esh", rows, w_g)) \
                    * dot("esd,edh->esh", rows, p["expert_W_u"])
            return dot("esh,ehd->esd", hidden.astype(rows.dtype),
                       p["expert_W_d"]) * w_rows[..., None]

        def one_by_one(rows, w_rows, left):
            if unrolled:
                return jnp.stack([expert(e, rows[e], w_rows[e], left[e])
                                  for e in range(e_held)])
            return jax.lax.map(lambda a: expert(*a), (
                jnp.arange(e_held), rows, w_rows, left))

        # what passes the slots is a few rows of a few experts: a quarter
        # of the slots a pass, so that a pass gathers and scatters little
        tail = max(32, slots // 4)

        def overflow(out, first):
            """A further pass, where some load passes `first`: the experts
            with rows left run, each under its conditional, the others do
            not."""
            return jax.lax.cond(
                jnp.max(loads) > first,
                lambda out: one_pass(out, first, tail, one_by_one),
                lambda out: out, out), None

        # one pass where no load passes the slots; a load that does is
        # worked off `tail` rows a pass, by the experts it concerns alone
        out = one_pass(jnp.zeros((n, d), _F32), 0, slots, all_at_once)
        firsts = slots + tail * jnp.arange(-(-(n - slots) // tail),
                                           dtype=loads.dtype)
        return jax.lax.scan(overflow, out, firsts)[0]


@register_layer
@dataclass
class ShortcutMoEBlock(LayerConf):
    """The LongCat-Flash block (module docstring): x [B, T, d] ->
    [B, T, d] float32. Width from the input type unless `n_model`."""

    input_kind = "rnn"

    n_model: int = 0
    n_heads: int = 4
    q_rank: int = 0                 # query latent (q_lora_rank)
    kv_rank: int = 0                # cached latent (kv_lora_rank)
    qk_nope: int = 0                # a head's key part without position
    qk_rope: int = 0                # a head's rotated part, shared key
    v_head: int = 0
    ffn_hidden: int = 0             # the two dense FFNs
    n_experts: int = 8
    n_identity: int = 0
    top_k: int = 2
    expert_hidden: int = 0
    routed_scaling: float = 1.0
    held_experts: Optional[List[int]] = None
    mla_scale: bool = True
    rope_theta: float = 10000.0
    eps: float = 1e-5

    def _width(self, it: Optional[InputType] = None) -> int:
        if self.n_model:
            return self.n_model
        if it is None:
            raise ValueError("ShortcutMoEBlock needs n_model or an input type")
        return it.size

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self._width(it), it.timesteps)

    @property
    def has_params(self) -> bool:
        return True

    def experts(self) -> SparseExpertsLayer:
        return SparseExpertsLayer(
            n_experts=self.n_experts, n_identity=self.n_identity,
            top_k=self.top_k, expert_hidden=self.expert_hidden,
            routed_scaling=self.routed_scaling,
            held_experts=self.held_experts, weight_init=self.weight_init,
            dist=self.dist, bias_init=self.bias_init, dtype=self.dtype)

    def latent_width(self) -> int:
        """What an attention caches for a token: [c | k_rope], padded to
        whole lane tiles."""
        return _pad128(self.kv_rank + self.qk_rope)

    def init_params(self, rng, it: InputType):
        d, h, f = self._width(it), self.n_heads, self.ffn_hidden
        qk = self.qk_nope + self.qk_rope
        keys = iter(jax.random.split(rng, 17))
        w = lambda *s: self._winit(next(keys), s, s[0], s[1])
        one = lambda n: jnp.ones((n,), jnp.dtype(self.dtype or "float32"))

        def attention():
            return {"W_qa": w(d, self.q_rank), "q_norm": one(self.q_rank),
                    "W_qb": w(self.q_rank, h * qk),
                    "W_kva": w(d, self.kv_rank + self.qk_rope),
                    "kv_norm": one(self.kv_rank),
                    "W_kvb": w(self.kv_rank,
                               h * (self.qk_nope + self.v_head)),
                    "W_o": w(h * self.v_head, d)}

        ffn = lambda: {"W_g": w(d, f), "W_u": w(d, f), "W_d": w(f, d)}
        return {"attn0": attention(), "attn1": attention(),
                "ffn0": ffn(), "ffn1": ffn(),
                "moe": self.experts().init_params(next(keys), it, width=d),
                "n1": one(d), "n2": one(d), "n3": one(d), "n4": one(d)}

    # -- latent attention --------------------------------------------------
    def _queries(self, p, x, pos):
        """(q_nope [B, T, H, nope], q_rope [B, T, H, rope]), rotated."""
        b, t, d = x.shape
        s_q = math.sqrt(d / self.q_rank) if self.mla_scale else 1.0
        q = _mm(_rms_norm(_mm(x, p["W_qa"]), p["q_norm"], self.eps),
                p["W_qb"]) * s_q
        q = q.reshape(b, t, self.n_heads, self.qk_nope + self.qk_rope)
        return (q[..., :self.qk_nope],
                _rotate(q[..., self.qk_nope:], pos[:, :, None],
                        self.rope_theta))

    def _latent(self, p, x, pos):
        """What is cached for x's tokens, [B, T, kv_rank + qk_rope]: the
        latent c after norm and scale beside the rotated shared key."""
        s_kv = (math.sqrt(x.shape[-1] / self.kv_rank) if self.mla_scale
                else 1.0)
        ckr = _mm(x, p["W_kva"])
        c = _rms_norm(ckr[..., :self.kv_rank], p["kv_norm"], self.eps) * s_kv
        return jnp.concatenate(
            [c, _rotate(ckr[..., self.kv_rank:], pos, self.rope_theta)], -1)

    def _up(self, p):
        """W_kvb as (W_uk [kv_rank, H, nope], W_uv [kv_rank, H, v])."""
        w = p["W_kvb"].reshape(self.kv_rank, self.n_heads,
                               self.qk_nope + self.v_head)
        return w[..., :self.qk_nope], w[..., self.qk_nope:]

    def _softmax(self, s, pos, lengths):
        """Rows of scores s [B, H, T, S] over keys 0..S-1: causal at the
        queries' absolute `pos` [B, T], and below `lengths` [B]."""
        key = jnp.arange(s.shape[-1])
        ok = key[None, None, :] <= pos[:, :, None]
        if lengths is not None:
            ok = ok & (key[None, None, :] < lengths[:, None, None])
        return jax.nn.softmax(jnp.where(ok[:, None], s, _NEG), axis=-1)

    def _attend_expanded(self, p, q_nope, q_rope, latent, pos, lengths):
        """Heads built from the latent: -> [B, T, H * v]."""
        dt = p["W_kvb"].dtype
        w_uk, w_uv = self._up(p)
        c = latent[..., :self.kv_rank].astype(dt)
        k_rope = latent[..., self.kv_rank:self.kv_rank + self.qk_rope]
        k_nope = jnp.einsum("bsc,chn->bshn", c, w_uk,
                            preferred_element_type=_F32)
        v = jnp.einsum("bsc,chv->bshv", c, w_uv, preferred_element_type=_F32)
        s = (jnp.einsum("bthn,bshn->bhts", q_nope.astype(dt),
                        k_nope.astype(dt), preferred_element_type=_F32)
             + jnp.einsum("bthr,bsr->bhts", q_rope.astype(dt),
                          k_rope.astype(dt), preferred_element_type=_F32))
        w = self._softmax(s / math.sqrt(self.qk_nope + self.qk_rope), pos,
                          lengths)
        out = jnp.einsum("bhts,bshv->bthv", w.astype(dt), v.astype(dt),
                         preferred_element_type=_F32)
        return out.reshape(*out.shape[:2], -1)

    def _absorbed_query(self, p, q_nope, q_rope, width):
        """[q_nope W_uk | q_rope | 0] [B, T, H, width] float32: a head's
        query over the cache as it lies (W_kvb's key part absorbed)."""
        w_uk = self._up(p)[0]
        q_abs = jnp.einsum("bthn,chn->bthc", q_nope.astype(w_uk.dtype), w_uk,
                           preferred_element_type=_F32)
        pad = width - self.kv_rank - self.qk_rope
        return jnp.concatenate(
            [q_abs, q_rope, jnp.zeros((*q_rope.shape[:-1], pad), _F32)], -1)

    def _absorbed_out(self, p, o):
        """The weighted latents o [B, T, H, kv_rank] float32 through W_kvb's
        value part: -> [B, T, H * v]."""
        w_uv = self._up(p)[1]
        out = jnp.einsum("bthc,chv->bthv", o.astype(w_uv.dtype), w_uv,
                         preferred_element_type=_F32)
        return out.reshape(*out.shape[:2], -1)

    def _attend_absorbed(self, p, q_nope, q_rope, view, pos, lengths):
        """W_kvb absorbed into the query and the output; `view`
        [B, S, latent_width] is the cache as it lies: -> [B, T, H * v]."""
        dt = jnp.promote_types(view.dtype, p["W_kvb"].dtype)
        view = view.astype(dt)
        q = self._absorbed_query(p, q_nope, q_rope, view.shape[-1])
        s = jnp.einsum("bthl,bsl->bhts", q.astype(dt), view,
                       preferred_element_type=_F32)
        w = self._softmax(s / math.sqrt(self.qk_nope + self.qk_rope), pos,
                          lengths)
        o = jnp.einsum("bhts,bsc->bthc", w.astype(dt),
                       view[..., :self.kv_rank], preferred_element_type=_F32)
        return self._absorbed_out(p, o)

    def _attend_paged(self, p, q_nope, q_rope, kv, channel, tables, lengths):
        """The absorbed attention of a tick (one query a row) over the
        arena's latent pages where they lie, through the rows' tables
        (`kernels.paged_attention.paged_latent_attention`, always the
        COMPILED kernel): -> [B, 1, H * v]. The arithmetic is
        `_attend_absorbed`'s but for the softmax's sums, taken a chunk of
        pages at a time."""
        from ...kernels import paged_attention as paged

        dt = jnp.promote_types(kv.dtype, p["W_kvb"].dtype)
        q = self._absorbed_query(p, q_nope, q_rope, kv.shape[-1])[:, 0]
        o = paged.paged_latent_attention(
            q.astype(dt), kv, channel, tables, lengths, v_width=self.kv_rank,
            sm_scale=1.0 / math.sqrt(self.qk_nope + self.qk_rope),
            interpret=False)
        return self._absorbed_out(p, o[:, None])

    # -- the block ---------------------------------------------------------
    def _block(self, p, x, attend, live=None, experts="cond"):
        """The topology; `attend(i, p_attn, x_normed)` is attention i,
        `experts` the held experts' path (`SparseExpertsLayer.mix`)."""
        h = x.astype(_F32)
        a = h + _mm(attend(0, p["attn0"], _rms_norm(h, p["n1"], self.eps)),
                    p["attn0"]["W_o"])
        u = _rms_norm(a, p["n2"], self.eps)
        m, counts = self.experts().mix(p["moe"], u, live, experts)
        b = a + _ffn(p["ffn0"], u)
        c = b + _mm(attend(1, p["attn1"], _rms_norm(b, p["n3"], self.eps)),
                    p["attn1"]["W_o"])
        return c + _ffn(p["ffn1"], _rms_norm(c, p["n4"], self.eps)) + m, counts

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        b, t, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        lengths = None if mask is None else jnp.sum(
            mask.astype(jnp.int32), axis=1)

        def attend(i, pa, xn):
            qn, qr = self._queries(pa, xn, pos)
            return self._attend_expanded(pa, qn, qr, self._latent(pa, xn, pos),
                                         pos, lengths)

        live = None if mask is None else mask.astype(bool)
        return self._block(params, x, attend, live)[0], state

    # -- the decode plane's contract --------------------------------------
    def decode_cache(self, width: int):
        """One latent channel an attention."""
        return 2, self.latent_width()

    def decode_state(self, width: int):
        return None

    def decode_attention(self, phase: str, spec):
        """How `phase` attends over a cache of `spec`. A prefill builds heads
        from its own latents: "mla_expanded". A tick: "mla_paged" where the
        backend is the TPU (`pallas_supported`: and the kernels are not
        switched off) and the arena's pages are whole tiles of float32 or
        bfloat16 (`kernels.paged_attention.paged_latent_attention`), else
        "mla_absorbed" (the view through the tables: the CPU, int8)."""
        from ...kernels import pallas_supported
        from ...kernels.paged_attention import paged_attention_supported
        from ...serving.decode.cache import KV_DTYPES

        if phase != "tick":
            return "mla_expanded"
        if (pallas_supported() and spec.kv_dtype in ("fp32", "bf16")
                and paged_attention_supported(
                    spec.width, spec.block_len, KV_DTYPES[spec.kv_dtype])):
            return "mla_paged"
        return "mla_absorbed"

    def decode_experts(self, phase: str, width: int) -> str:
        """The held experts' path in `phase` (`SparseExpertsLayer.
        decode_experts`): "grouped_kernel" for a tick on the TPU, else
        "cond"."""
        return self.experts().decode_experts(phase, width)

    def _cached(self, io, latent):
        """The latent zero-padded to the arena's width (the scatter
        rounds it to the arena's dtype)."""
        pad = io.spec.width - latent.shape[-1]
        return jnp.pad(latent, [(0, 0)] * (latent.ndim - 1) + [(0, pad)])

    def decode_prefill_step(self, io, attention="mla_expanded"):
        if attention != "mla_expanded":
            raise ValueError(f"a prefill attends mla_expanded, got "
                             f"{attention!r}")

        def step(p, x, kv, sc, channel, blk, off, pos, lengths):
            cache = [kv, sc]

            def attend(i, pa, xn):
                qn, qr = self._queries(pa, xn, pos)
                latent = self._latent(pa, xn, pos)
                cache[:] = io.scatter(*cache, self._cached(io, latent), blk,
                                      off, channel + i)
                return self._attend_expanded(pa, qn, qr, latent, pos, lengths)

            y, counts = self._block(p, x, attend, pos < lengths[:, None])
            return y, *cache, counts
        return step

    def decode_tick_step(self, io, attention="mla_absorbed", experts="cond"):
        if attention not in ("mla_paged", "mla_absorbed", "mla_expanded"):
            raise ValueError(f"attention must be mla_paged|mla_absorbed|"
                             f"mla_expanded, got {attention!r}")
        if attention == "mla_paged" and io.spec.kv_dtype == "int8":
            raise ValueError("mla_paged reads the pages as they lie: an int8 "
                             "arena is read through its view (mla_absorbed)")
        attend_view = (self._attend_absorbed if attention == "mla_absorbed"
                       else self._attend_expanded)

        def step(p, x, kv, sc, channel, blk, off, tables, positions, lengths):
            cache = [kv, sc]
            pos = positions[:, None]

            def attend(i, pa, xn):
                qn, qr = self._queries(pa, xn, pos)
                latent = self._latent(pa, xn, pos)
                cache[:] = io.scatter(*cache, self._cached(io, latent)[:, 0],
                                      blk, off, channel + i)
                if attention == "mla_paged":
                    return self._attend_paged(pa, qn, qr, cache[0],
                                              channel + i, tables, lengths)
                view = io.gather(*cache, tables, channel + i)
                view = view.reshape(view.shape[0], -1, view.shape[-1])
                return attend_view(pa, qn, qr, view, pos, lengths)

            # block 0 is the trash block: a row that writes there is a pad
            y, counts = self._block(p, x, attend, (blk > 0)[:, None],
                                    experts)
            return y, *cache, counts
        return step
