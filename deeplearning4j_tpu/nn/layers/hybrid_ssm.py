"""State-space and attention layers side by side: the block of Granite
4.0-H (IBM, 2025; `GraniteMoeHybrid*` in the public modelling code).

Every block is a MIXER and an expert layer, each behind an RMSNorm and
scaled into the residual stream by `residual_multiplier` r:

    x = x + r * Mixer(N_1 x)
    h = N_2 x;  x = x + r * (Experts(h) + Shared(h))

The mixer is a Mamba-2 state-space layer (`mixer="mamba"`; Dao & Gu 2024)
or grouped-query attention without positions (`mixer="attention"`):

    Mamba-2 (H heads of P, state N, G groups, convolution of K taps):
      [z (H*P) | xBC (H*P + 2GN) | dt (H)] = u W_in
      xBC = silu(causal depthwise conv_K(xBC) + b_conv)
      [xs (H*P) | B (G x N) | C (G x N)] = xBC
      dt = softplus(dt + dt_bias);  A = -exp(A_log)            a head
      H_t = exp(dt_t A) H_{t-1} + dt_t * xs_t (x) B_t,g        [P, N] a head
      y_t = H_t C_t,g + D * xs_t          head h reads group g = h // (H/G)
      out = RMSNorm_g(y * silu(z)) W_out  the norm within each group of
                                          H*P/G channels, one gain of H*P
    Granite 4.0-H has one group (`ssm_groups` 1: the norm over all H*P),
    Nemotron-H eight (`nn/layers/nemotron_h.py`).
    Attention (Hq query heads, Hkv key/value heads of Dh, Hq/Hkv queries
    a key/value head, no rotation and no positional table):
      p = softmax_causal(q k^T * attention_multiplier);  out = (p v) W_o

No biases but the convolution's. Matrix products take their operands in
the weights' dtype and sum in float32; `dt`, `A`, the decays, the state H,
the norms, the softmaxes and the residual stream are float32.

The scan over a prompt is CHUNKED (`ssm_scan`): inside a chunk of Q tokens
the recurrence is the quadratic form `((C B^T) * decay) (dt xs)`, three
matrix products; between chunks the state H is carried, one small step a
chunk. A position whose `dt` is 0 carries H unchanged, so a right-padded
prompt leaves the state after its last real token. A decode tick is one
step of the recurrence on the stored state, in float32 on the VPU.

Serving (`serving/decode/engine.py` states the layers' contract). The
attention variant pages keys and values of `Hkv * Dh` (2 channels); a
prefill attends over its local projections, a tick over the gathered view.
The Mamba variant keeps NO pages and, for each sequence, a constant-size
STATE (`decode_state`): the recurrent state `ssm [slots, H, P, N]` float32
and the convolution's last K-1 inputs `conv [K-1, slots, H*P + 2GN]`
(the slot axis second: the device tiles the two minor dimensions, and 3
rows would be padded to 8). A prefill writes the state its prompt leaves
into its slot, a tick reads its rows' slots and writes them back; the
leaves are donated with the rest of the cache and updated in place.
Either mixer's tick runs its held experts as `SparseExpertsLayer` says
(`shortcut_moe.py` module docstring): through one Pallas kernel a layer
on the TPU (`decode_experts`), under a conditional an expert elsewhere.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..conf.base import LayerConf, register_layer
from ..conf.input_type import InputType
from .shortcut_moe import _F32, _NEG, SparseExpertsLayer, _mm, _rms_norm

__all__ = ["HybridSSMBlock", "ssm_scan", "ssm_step", "causal_conv", "conv_prompt",
           "conv_tail", "conv_tick", "state_tick", "to_slots"]

MIXERS = ("mamba", "attention")


@functools.lru_cache(maxsize=256)
def _scan_record(batch, tokens, chunk, heads, head_dim, state,
                 groups=1) -> None:
    """The span-log instant `dl4j/layers/ssm_scan`, once a call shape a
    process, written while a program is traced, never while one runs."""
    from ...telemetry import tracer

    tracer().instant(
        "dl4j/layers/ssm_scan", batch=batch, tokens=tokens, chunk=chunk,
        chunks=-(-tokens // chunk), heads=heads, head_dim=head_dim,
        state=state, groups=groups,
        state_bytes=4 * batch * heads * head_dim * state)


def ssm_scan(xs, dt, a, bm, cm, chunk: int, dot_dtype=_F32):
    """The recurrence over whole sequences, chunked (module docstring).

    xs [B, T, H, P], dt [B, T, H] (0 where a position is padding), a [H]
    (negative), bm, cm [B, T, N] (one group) or [B, T, G, N] (G groups:
    head h reads group h // (H/G)), all float32; from the zero state.
    Returns (y [B, T, H, P] without the `D xs` term, the state after the
    last position [B, H, P, N]), float32. The matrix products take their
    operands in `dot_dtype`."""
    b, t, h, p = xs.shape
    g = bm.shape[2] if bm.ndim == 4 else 1
    _scan_record(b, t, min(int(chunk), t), h, p, bm.shape[-1], g)
    if bm.ndim == 3:
        return _scan(xs, dt, a, bm, cm, chunk, dot_dtype)
    # each group is a sequence of its own over its H/G heads: the groups
    # are folded into the batch, and the one-group scan runs over them
    fold = lambda z: jnp.moveaxis(z.reshape(b, t, g, -1, *z.shape[3:]), 2,
                                  1).reshape(b * g, t, -1, *z.shape[3:])
    groups = lambda z: jnp.moveaxis(z, 2, 1).reshape(b * g, t, -1)
    y, last = _scan(fold(xs), fold(dt),
                    jnp.tile(a.reshape(g, 1, -1), (b, 1, 1)), groups(bm),
                    groups(cm), chunk, dot_dtype)
    y = jnp.moveaxis(y.reshape(b, g, t, -1, p), 1, 2).reshape(b, t, h, p)
    return y, last.reshape(b, h, p, -1)


def _scan(xs, dt, a, bm, cm, chunk: int, dot_dtype):
    """`ssm_scan` of one group; `a` [H], or any shape that broadcasts
    against dt [B, T, H]."""
    b, t, h, p = xs.shape
    n = bm.shape[-1]
    q = min(int(chunk), t)
    c = -(-t // q)
    pad = c * q - t
    if pad:     # dt = 0 there: the state passes through
        grow = lambda z: jnp.pad(z, [(0, 0), (0, pad)] + [(0, 0)] * (z.ndim - 2))
        xs, dt, bm, cm = grow(xs), grow(dt), grow(bm), grow(cm)
    dot = functools.partial(jnp.einsum, preferred_element_type=_F32)
    op = lambda z: z.astype(dot_dtype)
    xdt = (xs * dt[..., None]).reshape(b, c, q, h, p)
    bm, cm = bm.reshape(b, c, q, n), cm.reshape(b, c, q, n)
    # cum[.., h, i]: the log of the decay from the chunk's start through i
    cum = jnp.cumsum((dt * a).reshape(b, c, q, h).transpose(0, 1, 3, 2), -1)
    within = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(within, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                    # [B, c, H, Q, Q]
    scores = dot("bcqn,bcsn->bcqs", op(cm), op(bm))
    y = dot("bchqs,bcshp->bcqhp", op(scores[:, :, None] * decay), op(xdt))
    # what each chunk adds to the state at its end, and the states between
    to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 1, 3, 2)  # [B,c,Q,H]
    adds = dot("bcshp,bcsn->bchpn", op(xdt * to_end[..., None]), op(bm))
    whole = jnp.exp(cum[..., -1])                                # [B, c, H]

    def carry(state, chunk_):
        add, keep = chunk_
        return keep[..., None, None] * state + add, state

    last, before = jax.lax.scan(
        carry, jnp.zeros((b, h, p, n), _F32),
        (adds.transpose(1, 0, 2, 3, 4), whole.transpose(1, 0, 2)))
    before = before.transpose(1, 0, 2, 3, 4)                # [B, c, H, P, N]
    y = y + dot("bcqn,bchpn->bcqhp", op(cm), op(before)) \
        * jnp.exp(cum).transpose(0, 1, 3, 2)[..., None]
    return y.reshape(b, c * q, h, p)[:, :t], last


def ssm_step(state, xs, dt, a, bm, cm):
    """One step of the recurrence: state [B, H, P, N], xs [B, H, P], dt
    [B, H], a [H], bm, cm [B, N] (or [B, G, N]: G groups) -> (y [B, H, P]
    without `D xs`, the new state), in float32 with no matrix unit in
    it."""
    if bm.ndim == 3:        # G groups: the heads as [G, H/G]
        b, h, p, n = state.shape
        g = bm.shape[1]
        heads = lambda z: z.reshape(b, g, h // g, *z.shape[2:])
        keep = jnp.exp(heads(dt) * a.reshape(g, -1))[..., None, None]
        state = keep * heads(state) + heads(xs * dt[..., None])[..., None] \
            * bm[:, :, None, None]
        y = jnp.sum(state * cm[:, :, None, None], axis=-1)
        return y.reshape(b, h, p), state.reshape(b, h, p, n)
    keep = jnp.exp(dt * a)[..., None, None]
    state = keep * state + (xs * dt[..., None])[..., None] * bm[:, None, None]
    return jnp.sum(state * cm[:, None, None], axis=-1), state


# ---------------------------------------------------------------------------
# what every state-space mixer of a served stack does alike: the causal
# depthwise convolution over a prompt and over a tick's stored inputs, and
# the rows' slots of a per-sequence leaf (`nn/layers/sambay.py` shares them)
# ---------------------------------------------------------------------------
def causal_conv(p, window, taps: int):
    """window [K, ..., C]: the K newest inputs, oldest first -> silu of the
    convolution (`p["conv_W"]` [K, C], `p["conv_b"]` [C]) for the newest."""
    w = p["conv_W"].astype(_F32)
    total = sum(w[k] * window[k] for k in range(taps))
    return jax.nn.silu(total + p["conv_b"].astype(_F32))


def conv_prompt(p, xbc, taps: int):
    """The convolution over whole sequences xbc [B, T, C]: (out [B, T, C],
    the inputs with the K-1 zeros before the start, [B, K-1+T, C], which
    `conv_tail` reads)."""
    t = xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    return causal_conv(p, [padded[:, j:j + t] for j in range(taps)],
                       taps), padded


def conv_tail(padded, lengths, taps: int):
    """The K-1 inputs a tick needs after position `lengths - 1` of a
    right-padded prompt, [B, K-1, C] (the leaf holds them [K-1, slots, C]),
    from `conv_prompt`'s `padded`."""
    # inputs length-K+1 .. length-1: rows length .. length+K-2 of padded
    at = lengths[:, None] + jnp.arange(taps - 1)[None, :]
    return jnp.take_along_axis(padded, at[..., None], axis=1)


def to_slots(rows, slot, slots: int):
    """rows [B, ...] laid on a leaf's `slots` at `slot` [B], zeros where no
    row is."""
    return jnp.zeros((slots,) + rows.shape[1:], rows.dtype).at[slot].set(rows)


def everywhere(rows: int, slots: int) -> bool:
    """Whether a tick of `rows` updates every slot where it lies: from a
    quarter of the slots up a pass over the whole leaf in place costs less
    than gathering the rows' slots out and scattering them back."""
    return 4 * rows >= slots


def conv_tick(p, conv, new, slot, taps: int):
    """One input a row, new [B, C], through the stored inputs `conv`
    [K-1, slots, C] of the rows' slots: (the convolution's output [B, C],
    the new leaf). From a quarter of the slots up every slot where it lies,
    a slot no row names keeping its inputs (`everywhere`)."""
    slots = conv.shape[1]
    if everywhere(slot.shape[0], slots):
        named = to_slots(jnp.ones_like(slot, dtype=bool), slot, slots)
        window = jnp.concatenate([conv, to_slots(new, slot, slots)[None]],
                                 axis=0)
        out = causal_conv(p, window, taps)[slot]
        return out, jnp.where(named[None, :, None], window[1:], conv)
    window = jnp.concatenate([conv[:, slot], new[None]], axis=0)
    return causal_conv(p, window, taps), conv.at[:, slot].set(window[1:])


def state_tick(step, state, slot, *rows):
    """`step(state, *rows) -> (y, new state)` on the rows' slots of a leaf
    `state` [slots, ...]. From a quarter of the slots up it runs over every
    slot where it lies, the rows laid on their slots (a slot no row names
    must come out of `step` unchanged: a step of 0); fewer rows read and
    write their own slots alone."""
    slots = state.shape[0]
    if everywhere(slot.shape[0], slots):
        y, state = step(state, *(to_slots(r, slot, slots) for r in rows))
        return y[slot], state
    y, mine = step(state[slot], *rows)
    return y, state.at[slot].set(mine)


@register_layer
@dataclass
class HybridSSMBlock(LayerConf):
    """One Granite 4.0-H block (module docstring): x [B, T, d] -> [B, T, d]
    float32. Width from the input type unless `n_model`."""

    input_kind = "rnn"

    n_model: int = 0
    mixer: str = "mamba"
    # the Mamba-2 mixer
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    conv_kernel: int = 4
    chunk: int = 256
    # the attention mixer
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    attention_multiplier: float = 1.0
    # the experts
    n_experts: int = 8
    top_k: int = 2
    expert_hidden: int = 0
    shared_hidden: int = 0
    held_experts: Optional[List[int]] = None
    residual_multiplier: float = 1.0
    eps: float = 1e-5

    def __post_init__(self):
        if self.mixer not in MIXERS:
            raise ValueError(f"mixer must be one of {MIXERS}, got "
                             f"{self.mixer!r}")

    def _width(self, it: Optional[InputType] = None) -> int:
        if self.n_model:
            return self.n_model
        if it is None:
            raise ValueError("HybridSSMBlock needs n_model or an input type")
        return it.size

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self._width(it), it.timesteps)

    @property
    def has_params(self) -> bool:
        return True

    def experts(self) -> SparseExpertsLayer:
        return SparseExpertsLayer(
            n_experts=self.n_experts, top_k=self.top_k,
            expert_hidden=self.expert_hidden, shared_hidden=self.shared_hidden,
            held_experts=self.held_experts, scoring="softmax_picked",
            weight_init=self.weight_init, dist=self.dist,
            bias_init=self.bias_init, dtype=self.dtype)

    @property
    def _inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def _conv_width(self) -> int:
        return self._inner + 2 * self.ssm_groups * self.ssm_state

    def init_params(self, rng, it: InputType):
        d = self._width(it)
        keys = iter(jax.random.split(rng, 12))
        one = lambda n: jnp.ones((n,), jnp.dtype(self.dtype or "float32"))
        return {"n1": one(d), "mixer": self.init_mixer(keys, d), "n2": one(d),
                "moe": self.experts().init_params(next(keys), it, width=d)}

    def init_mixer(self, keys, d: int):
        """The mixer's parameters for a width `d`, drawn from the iterator of
        keys `keys`."""
        w = lambda *s: self._winit(next(keys), s, s[0], s[1])
        dtype = jnp.dtype(self.dtype or "float32")
        one = lambda n: jnp.ones((n,), dtype)
        if self.mixer == "attention":
            kv = self.n_kv_heads * self.head_dim
            return {"W_q": w(d, self.n_heads * self.head_dim),
                    "W_k": w(d, kv), "W_v": w(d, kv),
                    "W_o": w(self.n_heads * self.head_dim, d)}
        h, k = self.ssm_heads, self.conv_kernel
        uni = lambda lo, hi: jax.random.uniform(
            next(keys), (h,), _F32, lo, hi)
        step = jnp.exp(uni(jnp.log(1e-3), jnp.log(1e-1)))
        return {
            "W_in": w(d, self._inner + self._conv_width + h),
            "conv_W": jax.random.uniform(
                next(keys), (k, self._conv_width), _F32,
                -k ** -0.5, k ** -0.5).astype(dtype),
            "conv_b": jnp.zeros((self._conv_width,), dtype),
            # softplus(dt_bias) in 0.001-0.1, A in 1-16: the public
            # initialiser's ranges
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
            "A_log": jnp.log(uni(1.0, 16.0)).astype(dtype),
            "D": one(h), "norm": one(self._inner),
            "W_out": w(self._inner, d)}

    # -- the Mamba-2 mixer -------------------------------------------------
    def _project(self, p, u):
        """u [..., d] -> (z [..., H*P], xBC [..., H*P + 2GN] before the
        convolution, dt [..., H] before its bias), float32."""
        zxd = _mm(u, p["W_in"])
        i, c = self._inner, self._conv_width
        return zxd[..., :i], zxd[..., i:i + c], zxd[..., i + c:]

    def _split(self, p, xbc, dt):
        """(xs [..., H, P], B [..., N], C [..., N], dt [..., H] after its
        bias and softplus, a [H]); B and C [..., G, N] for G groups."""
        i, n = self._inner, self.ssm_groups * self.ssm_state
        xs = xbc[..., :i].reshape(*xbc.shape[:-1], self.ssm_heads,
                                  self.ssm_head_dim)
        dt = jax.nn.softplus(dt + p["dt_bias"].astype(_F32))
        bm, cm = xbc[..., i:i + n], xbc[..., i + n:]
        if self.ssm_groups > 1:
            bm, cm = (z.reshape(*z.shape[:-1], self.ssm_groups, -1)
                      for z in (bm, cm))
        return xs, bm, cm, dt, -jnp.exp(p["A_log"].astype(_F32))

    def _gate_out(self, p, y, xs, z):
        """(y + D xs) gated by z, normed over all H*P (within each group of
        H*P/G channels for G groups, one gain), projected."""
        y = y + p["D"].astype(_F32)[:, None] * xs
        y = y.reshape(*y.shape[:-2], self._inner) * jax.nn.silu(z)
        if self.ssm_groups > 1:
            y = y.reshape(*y.shape[:-1], self.ssm_groups, -1)
            y = _rms_norm(y, jnp.ones((), _F32), self.eps).reshape(z.shape)
            return _mm(y * p["norm"].astype(_F32), p["W_out"])
        return _mm(_rms_norm(y, p["norm"], self.eps), p["W_out"])

    def _mamba(self, p, u, lengths=None):
        """The mixer over whole sequences u [B, T, d], from the zero state:
        (out [B, T, d], the state after position `lengths - 1` (None: the
        last): ssm [B, H, P, N], conv [K-1, B, C])."""
        b, t, _ = u.shape
        z, xbc, dt = self._project(p, u)
        conv, padded = conv_prompt(p, xbc, self.conv_kernel)
        xs, bm, cm, dt, a = self._split(p, conv, dt)
        if lengths is None:
            lengths = jnp.full((b,), t, jnp.int32)
        live = jnp.arange(t)[None, :] < lengths[:, None]
        dt = jnp.where(live[..., None], dt, 0.0)
        y, last = ssm_scan(xs, dt, a, bm, cm, self.chunk, p["W_in"].dtype)
        tail = conv_tail(padded, lengths, self.conv_kernel)
        return self._gate_out(p, y, xs, z), last, tail.transpose(1, 0, 2)

    def _mamba_tick(self, p, u, state, slot):
        """One token a row, u [B, 1, d], on the rows' slots of `state`
        (`decode_state`'s leaves): (out [B, 1, d], the new leaves).

        Where the rows are a quarter of the slots or more the step runs
        over EVERY slot where it lies, the rows' inputs scattered to their
        slots: a slot no row names gets `dt` = 0, which carries it
        unchanged, so the whole leaf is one elementwise pass in place (a
        read and a write of the leaf), no row gathered out and none
        scattered back. Fewer rows read and write their own slots alone:
        a gathered copy of theirs, some five passes over it, far under the
        leaf (`conv_tick`, `state_tick`)."""
        z, xbc, dt = self._project(p, u[:, 0])
        out, conv = conv_tick(p, state["conv"], xbc, slot, self.conv_kernel)
        xs, bm, cm, dt, a = self._split(p, out, dt)
        y, ssm = state_tick(
            lambda s, xs_, dt_, bm_, cm_: ssm_step(s, xs_, dt_, a, bm_, cm_),
            state["ssm"], slot, xs, dt, bm, cm)
        return (self._gate_out(p, y, xs, z)[:, None],
                {"ssm": ssm, "conv": conv})

    # -- the attention mixer -----------------------------------------------
    def _qkv(self, p, u):
        """u [B, T, d] -> q [B, T, Hq, Dh], k, v [B, T, Hkv, Dh], float32."""
        heads = lambda z, h: z.reshape(*z.shape[:-1], h, self.head_dim)
        return (heads(_mm(u, p["W_q"]), self.n_heads),
                heads(_mm(u, p["W_k"]), self.n_kv_heads),
                heads(_mm(u, p["W_v"]), self.n_kv_heads))

    def _attend(self, p, q, k, v, pos, lengths, dt=None):
        """Grouped-query attention of q [B, T, Hq, Dh] at absolute `pos`
        [B, T] over k, v [B, S, Hkv, Dh] (keys 0..S-1, those below
        `lengths` [B] where given), projected by W_o: -> [B, T, d]. The
        two products take their operands in `dt` (the weights' dtype)."""
        b, t = q.shape[:2]
        dt = dt or p["W_o"].dtype
        q = q.reshape(b, t, self.n_kv_heads, -1, self.head_dim)
        s = jnp.einsum("btkgd,bskd->bkgts", q.astype(dt), k.astype(dt),
                       preferred_element_type=_F32) * self.attention_multiplier
        key = jnp.arange(k.shape[1])
        ok = key[None, None, :] <= pos[:, :, None]
        if lengths is not None:
            ok = ok & (key[None, None, :] < lengths[:, None, None])
        w = jax.nn.softmax(jnp.where(ok[:, None, None], s, _NEG), axis=-1)
        out = jnp.einsum("bkgts,bskd->btkgd", w.astype(dt), v.astype(dt),
                         preferred_element_type=_F32)
        return _mm(out.reshape(b, t, -1), p["W_o"])

    # -- the block ---------------------------------------------------------
    def _block(self, p, x, mix, live=None, experts="cond"):
        """The topology; `mix(p_mixer, x_normed)` is the mixer, `experts`
        the held experts' path (`SparseExpertsLayer.mix`)."""
        r = self.residual_multiplier
        x = x.astype(_F32)
        x = x + r * mix(p["mixer"], _rms_norm(x, p["n1"], self.eps))
        m, counts = self.experts().mix(
            p["moe"], _rms_norm(x, p["n2"], self.eps), live, experts)
        return x + r * m, counts

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        b, t, _ = x.shape
        lengths = None if mask is None else jnp.sum(
            mask.astype(jnp.int32), axis=1)
        if self.mixer == "mamba":
            mix = lambda pm, u: self._mamba(pm, u, lengths)[0]
        else:
            pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
            mix = lambda pm, u: self._attend(pm, *self._qkv(pm, u), pos,
                                             lengths)
        live = None if mask is None else mask.astype(bool)
        return self._block(params, x, mix, live)[0], state

    # -- the decode plane's contract --------------------------------------
    def decode_cache(self, width: int):
        """Keys and values of the Hkv heads, merged; a Mamba layer keeps no
        pages."""
        if self.mixer == "mamba":
            return 0, 0
        return 2, self.n_kv_heads * self.head_dim

    def decode_state(self, width: int):
        """What a Mamba layer keeps for a sequence, whatever its length:
        {name: (shape with `slots` for the sequences' axis, dtype)}."""
        if self.mixer != "mamba":
            return None
        return {"ssm": (("slots", self.ssm_heads, self.ssm_head_dim,
                         self.ssm_state), "float32"),
                "conv": ((self.conv_kernel - 1, "slots", self._conv_width),
                         "float32")}

    def decode_attention(self, phase: str, spec):
        """How `phase` attends over a cache of `spec`. A Mamba layer has no
        attention and a prefill attends over its local K/V (None: nothing
        to choose). The attention layer's tick: "paged_kernel" where the
        backend is the TPU and the arena's pages are whole tiles of float32
        or bfloat16 (`kernels.paged_attention`, grouped queries on the
        `Hkv*Dh` lanes), else "gather" (the view through the tables)."""
        from ...kernels import pallas_supported
        from ...kernels.paged_attention import paged_attention_supported
        from ...serving.decode.cache import KV_DTYPES

        if phase != "tick" or self.mixer != "attention":
            return None
        if (pallas_supported() and spec.kv_dtype in ("fp32", "bf16")
                and paged_attention_supported(
                    spec.width, spec.block_len, KV_DTYPES[spec.kv_dtype])):
            return "paged_kernel"
        return "gather"

    def decode_experts(self, phase: str, width: int) -> str:
        """The held experts' path in `phase` (`SparseExpertsLayer.
        decode_experts`): "grouped_kernel" for a tick on the TPU, else
        "cond"."""
        return self.experts().decode_experts(phase, width)

    def decode_prefill_step(self, io, attention=None):
        if self.mixer == "mamba":
            def step(p, x, kv, sc, channel, blk, off, pos, lengths, state,
                     slot):
                kept = {}

                def mix(pm, u):
                    out, kept["ssm"], kept["conv"] = self._mamba(pm, u, lengths)
                    return out

                y, counts = self._block(p, x, mix, pos < lengths[:, None])
                state = {"ssm": state["ssm"].at[slot].set(kept["ssm"]),
                         "conv": state["conv"].at[:, slot].set(kept["conv"])}
                return y, kv, sc, counts, state
            return step

        def step(p, x, kv, sc, channel, blk, off, pos, lengths):
            cache = [kv, sc]

            def mix(pm, u):
                q, k, v = self._qkv(pm, u)
                cache[:] = io.scatter(*cache, k, blk, off, channel)
                cache[:] = io.scatter(*cache, v, blk, off, channel + 1)
                return self._attend(pm, q, k, v, pos, lengths)

            y, counts = self._block(p, x, mix, pos < lengths[:, None])
            return y, *cache, counts
        return step

    def decode_tick_step(self, io, attention=None, experts="cond"):
        if self.mixer == "mamba":
            def step(p, x, kv, sc, channel, blk, off, tables, positions,
                     lengths, state, slot):
                kept = {}

                def mix(pm, u):
                    out, kept["state"] = self._mamba_tick(pm, u, state, slot)
                    return out

                # block 0 is the trash block: a row that writes there is a
                # pad (its slot is the trash slot, 0)
                y, counts = self._block(p, x, mix, (blk > 0)[:, None],
                                        experts)
                return y, kv, sc, counts, kept["state"]
            return step

        from ...kernels import paged_attention as paged

        attention = attention or "gather"
        if attention not in ("paged_kernel", "gather"):
            raise ValueError(f"attention must be paged_kernel|gather, got "
                             f"{attention!r}")

        def view(cache, tables, channel):
            v = io.gather(*cache, tables, channel)
            return v.reshape(v.shape[0], -1, self.n_kv_heads, self.head_dim)

        def step(p, x, kv, sc, channel, blk, off, tables, positions, lengths):
            cache = [kv, sc]

            def mix(pm, u):
                q, k, v = self._qkv(pm, u)
                cache[:] = io.scatter(*cache, k[:, 0], blk, off, channel)
                cache[:] = io.scatter(*cache, v[:, 0], blk, off, channel + 1)
                if attention == "paged_kernel":     # always the COMPILED one
                    out = paged.paged_decode_attention(
                        q.reshape(q.shape[0], -1), cache[0], channel, tables,
                        lengths, n_heads=self.n_heads,
                        n_kv_heads=self.n_kv_heads,
                        sm_scale=self.attention_multiplier, interpret=False)
                    return _mm(out[:, None], pm["W_o"])
                k_all = view(cache, tables, channel)
                return self._attend(
                    pm, q, k_all, view(cache, tables, channel + 1),
                    positions[:, None], lengths,
                    jnp.promote_types(k_all.dtype, pm["W_o"].dtype))

            y, counts = self._block(p, x, mix, (blk > 0)[:, None], experts)
            return y, *cache, counts
        return step
