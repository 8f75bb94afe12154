"""The decoder-hybrid-decoder of SambaY (Ren et al. 2025, arXiv:2507.06607)
as Phi-4-mini-flash-reasoning lays it out: a SELF-DECODER of Mamba layers
and sliding-window attention, then a CROSS-DECODER whose layers read one
full-attention cache and one memory, written once.

Notation: d the width, e = expand * d, N the state, R the rank of `dt`, K the
convolution's taps, Dh a query's or key's head size (a value's is 2 Dh). LN is
LayerNorm with gain and bias. Every layer l:

    h = x + Mix_l(LN1(x));   x' = h + W_2(silu(W_g u) * W_u u),  u = LN2(h)

(`W_1` = [W_g | W_u]; no projection has a bias). The mixers:

    Mamba (selective, per channel; Gu & Dao 2023):
      [c0 | z] = W_in u;   c = silu(causal depthwise conv_K(c0) + b)
      [delta | B | C] = W_x c;   dt = softplus(W_dt delta + b_dt)     [e]
      H_t = exp(dt_t (x) A) * H_{t-1} + (dt_t * c_t) (x) B_t         [e, N]
      g_t = (H_t C_t + D * c_t) * silu(z_t);   out = W_out g_t
    Differential attention (Ye et al. 2024, arXiv:2410.05258), H heads each
    a pair of queries (q1, q2) of Dh, Hkv key/value heads each a pair of keys
    (k1, k2) and a value of 2 Dh, head i on key/value head floor(i / (H/Hkv)):
      A^s = softmax(q^s k^s^T / sqrt(Dh) + mask),  s = 1, 2
      o_i = RMSNorm_2Dh((A^1 - lambda A^2) v) * (1 - lambda_init);  out = W_o o
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    No positions: the Mamba layers carry order. The mask is causal; a WINDOW
    layer also requires t - s < W (W keys, the query's own among them).
    GMU (gated memory unit):  out = W_out(silu(W_in u) * m)

`SambaYBlock` is one self-decoder layer (a Mamba or a window layer).
`CrossDecoderBlock` holds the rest: a Mamba layer whose gated value g is the
memory m, a full-attention layer whose keys and values are THE shared cache,
then GMU layers (reading m) and cross-attention layers (W_q and W_o of their
own, the full layer's keys and values) in turn.

Arithmetic: matrix products take their operands in the weights' dtype and sum
in float32; the state, dt, the decays, the norms, the softmaxes, lambda and
the residual stream are float32.

The scan over a prompt (`selective_scan`) is CHUNKED: chunks of Q tokens run
side by side, a step of the recurrence at a time, from the zero state (Q
steps); the chunks' end states are carried from chunk to chunk with the
chunk's whole decay exp(A sum dt) (one step a chunk); then every chunk runs
its Q steps again from its true start state, and gives y. 2Q + T/Q steps in
all, each over the whole sequence's channels, in place of T. A position whose
dt is 0 carries the state unchanged, so a right-padded prompt leaves the
state after its last real token. A tick is one step on the stored state.

Serving (`serving/decode/engine.py` states the layers' contract). A Mamba
layer keeps per-sequence STATE: `ssm [slots, e, N]` float32 and the
convolution's last K-1 inputs `conv [K-1, slots, e]` (shared with the Mamba-2
path: `hybrid_ssm.conv_*`, `state_tick`). A window layer keeps its keys and
values as per-sequence state too, a RING of W slots a sequence (`k`, `v`
`[slots, W, Hkv*2Dh]` in the weights' dtype): token t lies in ring slot t mod
W, so a sequence's bytes are bounded by the window whatever its length; a
prefill writes the last min(length, W) real tokens, a tick writes its token
and reads its ring's live slots in place (`ring_diff_attention`, a Pallas
kernel) where the TPU takes it, else the rows' rings gathered (the oracle).
The cross-decoder pages ONE pair of channels of Hkv*2Dh,
written by the full-attention layer and read by it and every cross layer: a
tick attends over the live pages in place (`paged_diff_attention`, a Pallas
kernel) where the TPU takes it, else over the gathered view (the oracle). A
prefill runs the cross-decoder's first layer and the full layer's keys and
values over every token, and everything after them for each prompt's LAST
real token alone (the YOCO skip: the head reads that token only), so the
block hands on x of one position [B, 1, d].
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..conf.base import LayerConf, register_layer
from ..conf.input_type import InputType
from .hybrid_ssm import conv_prompt, conv_tail, conv_tick, state_tick
from .shortcut_moe import _F32, _NEG, RMSNormLayer, _mm, _rms_norm

__all__ = ["SambaYBlock", "CrossDecoderBlock", "LayerNormLayer",
           "selective_scan", "selective_step", "lambda_init"]

MIXERS = ("mamba", "window")


def lambda_init(layer: int) -> float:
    """Differential attention's lambda_init of layer `layer` (0-based)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _layer_norm(x, g, b, eps):
    x = x.astype(_F32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(_F32) \
        + b.astype(_F32)


@register_layer
@dataclass
class LayerNormLayer(RMSNormLayer):
    """(x - mean) * rsqrt(var + eps) * g + b over the last axis, in float32:
    the final norm before a head. Keeps no cache (the decode contract's
    answers are RMSNormLayer's)."""

    def init_params(self, rng, it: InputType):
        width = it.size if it.kind == "rnn" else it.flat_size()
        dtype = jnp.dtype(self.dtype or "float32")
        return {"g": jnp.ones((width,), dtype), "b": jnp.zeros((width,), dtype)}

    def _norm(self, p, x):
        y = _layer_norm(x, p["g"], p["b"], self.eps)
        return y if self.scale == 1.0 else y * self.scale


# ---------------------------------------------------------------------------
# the selective scan
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=256)
def _scan_record(batch, tokens, chunk, channels, state) -> int:
    """The span-log instant `dl4j/layers/ssm_scan` (`kind` selective), once
    a call shape a process, written while a program is traced. Returns the
    number of chunks."""
    from ...telemetry import tracer

    chunks = -(-tokens // chunk)
    tracer().instant(
        "dl4j/layers/ssm_scan", kind="selective", batch=batch, tokens=tokens,
        chunk=chunk, chunks=chunks, channels=channels, state=state,
        state_bytes=4 * batch * channels * state,
        chunk_state_bytes=4 * batch * chunks * channels * state)
    return chunks


def selective_step(state, x, dt, a, bm, cm):
    """One step: state [B, e, N], x, dt [B, e], a [e, N], bm, cm [B, N] ->
    (y [B, e] without `D x`, the new state), float32."""
    state = jnp.exp(dt[..., None] * a) * state \
        + (dt * x)[..., None] * bm[..., None, :]
    return jnp.sum(state * cm[..., None, :], axis=-1), state


def selective_scan(x, dt, a, bm, cm, chunk: int):
    """The recurrence over whole sequences (module docstring), from the zero
    state. x, dt [B, T, e] (dt 0 where a position is padding), a [e, N], bm,
    cm [B, T, N], float32. Returns (y [B, T, e] without `D x`, the state
    after the last position [B, e, N])."""
    b, t, e = x.shape
    n = a.shape[-1]
    q = min(int(chunk), t)
    c = _scan_record(b, t, q, e, n)
    pad = c * q - t
    if pad:     # dt = 0 there: the state passes through
        grow = lambda z: jnp.pad(z, ((0, 0), (0, pad), (0, 0)))
        x, dt, bm, cm = grow(x), grow(dt), grow(bm), grow(cm)
    # [Q, B, c, ...]: a chunk's positions in turn, every chunk at once
    lay = lambda z: z.reshape(b, c, q, z.shape[-1]).transpose(2, 0, 1, 3)
    dts, xs, bms = lay(dt), lay(x), lay(bm)

    def step(h, now):
        dt_t, x_t, b_t = now[:3]
        return jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * x_t)[..., None] * b_t[..., None, :]

    ends, _ = jax.lax.scan(lambda h, now: (step(h, now), None),
                           jnp.zeros((b, c, e, n), _F32), (dts, xs, bms))
    whole = jnp.exp(jnp.sum(dts, axis=0)[..., None] * a)       # [B, c, e, N]

    def carry(h, chunk_):
        end, keep = chunk_
        return keep * h + end, h

    last, starts = jax.lax.scan(carry, jnp.zeros((b, e, n), _F32),
                                (ends.swapaxes(0, 1), whole.swapaxes(0, 1)))

    def again(h, now):
        *inputs, c_t = now
        h = step(h, inputs)
        return h, jnp.sum(h * c_t[..., None, :], axis=-1)

    _, y = jax.lax.scan(again, starts.swapaxes(0, 1), (dts, xs, bms, lay(cm)))
    return y.transpose(1, 2, 0, 3).reshape(b, c * q, e)[:, :t], last


# ---------------------------------------------------------------------------
# the mixers, as functions of a layer's widths
# ---------------------------------------------------------------------------
class Widths(NamedTuple):
    d: int
    e: int              # the Mamba layers' inner width
    n: int              # state
    r: int              # dt rank
    k: int              # convolution taps
    chunk: int
    heads: int          # differential heads
    kv_heads: int
    head: int           # a query's or key's size; a value is 2 x
    window: int
    mlp: int
    eps: float

    @property
    def kv_width(self) -> int:
        """A token's keys (or values) of all key/value heads."""
        return self.kv_heads * 2 * self.head


def _mlp(p, u, w: Widths):
    h = _mm(u, p["W_1"])
    return _mm(jax.nn.silu(h[..., :w.mlp]) * h[..., w.mlp:], p["W_2"])


def _sublayer(p, x, mix, w: Widths):
    """The topology around a mixer `mix(p_mix, LN1(x))`."""
    x = x.astype(_F32)
    x = x + mix(p["mix"], _layer_norm(x, p["ln1_g"], p["ln1_b"], w.eps))
    return x + _mlp(p, _layer_norm(x, p["ln2_g"], p["ln2_b"], w.eps), w)


def _select(p, c, w: Widths):
    """c [..., e] after the convolution -> (dt [..., e], B, C [..., N], A)."""
    dbc = _mm(c, p["W_x"])
    dt = jax.nn.softplus(_mm(dbc[..., :w.r], p["W_dt"])
                         + p["dt_b"].astype(_F32))
    return (dt, dbc[..., w.r:w.r + w.n], dbc[..., w.r + w.n:],
            -jnp.exp(p["A_log"].astype(_F32)))


def _gated(p, y, c, z):
    """(out, g): g = (y + D c) silu(z), the memory a cross-decoder keeps."""
    g = (y + p["D"].astype(_F32) * c) * jax.nn.silu(z)
    return _mm(g, p["W_out"]), g


def mamba_prompt(p, u, lengths, w: Widths):
    """The Mamba mixer over whole right-padded sequences u [B, T, d]:
    (out [B, T, d], g [B, T, e], the state after position `lengths - 1`:
    ssm [B, e, N], conv [K-1, B, e])."""
    b, t, _ = u.shape
    cz = _mm(u, p["W_in"])
    c, padded = conv_prompt(p, cz[..., :w.e], w.k)
    dt, bm, cm, a = _select(p, c, w)
    if lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)
    dt = jnp.where((jnp.arange(t)[None, :] < lengths[:, None])[..., None],
                   dt, 0.0)
    y, last = selective_scan(c, dt, a, bm, cm, w.chunk)
    tail = conv_tail(padded, lengths, w.k)
    out, g = _gated(p, y, c, cz[..., w.e:])
    return out, g, last, tail.transpose(1, 0, 2)


def mamba_tick(p, u, state, slot, w: Widths):
    """One token a row, u [B, d], on the rows' slots of `state`: (out
    [B, d], g [B, e], the new leaves)."""
    cz = _mm(u, p["W_in"])
    c, conv = conv_tick(p, state["conv"], cz[..., :w.e], slot, w.k)
    dt, bm, cm, a = _select(p, c, w)
    y, ssm = state_tick(
        lambda s, c_, dt_, b_, cm_: selective_step(s, c_, dt_, a, b_, cm_),
        state["ssm"], slot, c, dt, bm, cm)
    out, g = _gated(p, y, c, cz[..., w.e:])
    return out, g, {"ssm": ssm, "conv": conv}


def _lambda(p, lam0):
    dot = lambda a, b: jnp.exp(jnp.sum(p[a].astype(_F32) * p[b].astype(_F32)))
    return dot("lq1", "lk1") - dot("lq2", "lk2") + lam0


def _diff_out(p, o, lam0, w: Widths):
    """o [..., H, 2Dh] = (A1 - lambda A2) v -> W_o of the normed heads."""
    o = _rms_norm(o, p["subln"], w.eps) * (1.0 - lam0)
    return _mm(o.reshape(*o.shape[:-2], -1), p["W_o"])


def _queries(p, u, w: Widths):
    return _mm(u, p["W_q"]).reshape(*u.shape[:-1], w.heads, 2, w.head)


def diff_attend(q, k, v, ok, lam, w: Widths, dt):
    """Differential attention over local keys: q [B, T, H, 2, Dh], k [B, S,
    Hkv*2Dh], v [B, S, Hkv*2Dh], ok [B, T, S] -> (A1 - lambda A2) v
    [B, T, H, 2Dh]. The products take their operands in `dt`."""
    b, t = q.shape[:2]
    s = k.shape[1]
    q = q.reshape(b, t, w.kv_heads, w.heads // w.kv_heads, 2, w.head)
    k = k.reshape(b, s, w.kv_heads, 2, w.head)
    v = v.reshape(b, s, w.kv_heads, 2 * w.head)
    sc = jnp.einsum("btgjmd,bsgmd->bgjmts", q.astype(dt), k.astype(dt),
                    preferred_element_type=_F32) / math.sqrt(w.head)
    a = jax.nn.softmax(jnp.where(ok[:, None, None, None], sc, _NEG), axis=-1)
    diff = a[:, :, :, 0] - lam * a[:, :, :, 1]            # [B, g, j, T, S]
    out = jnp.einsum("bgjts,bsgd->btgjd", diff.astype(dt), v.astype(dt),
                     preferred_element_type=_F32)
    return out.reshape(b, t, w.heads, 2 * w.head)


def diff_attend_rows(q, keys, values, valid, lam, w: Widths, dt):
    """One query a row over a set of merged keys: q [B, H, 2, Dh], keys,
    values [B, S, Hkv*2Dh], valid [B, S] -> (A1 - lambda A2) v [B, H, 2Dh],
    through the block-diagonal query (no head split of the keys)."""
    from ...kernels.paged_attention import diff_block_diagonal, diff_own_lanes

    b, s = keys.shape[:2]
    qbd = diff_block_diagonal(q, w.kv_heads)
    sc = jnp.einsum("brl,bsl->brs", qbd.astype(dt), keys.astype(dt),
                    preferred_element_type=_F32) / math.sqrt(w.head)
    a = jax.nn.softmax(jnp.where(valid[:, None, :], sc, _NEG), axis=-1)
    a = a.reshape(b, 2, w.heads, s)
    out = jnp.einsum("bhs,bsl->bhl", (a[:, 0] - lam * a[:, 1]).astype(dt),
                     values.astype(dt), preferred_element_type=_F32)
    return diff_own_lanes(out, w.heads, w.kv_heads)


def _ok(t, s, lengths, window=None, q_pos=None):
    """[B, T, S]: causal (queries at `q_pos` [B, T], else 0..T-1), keys
    below `lengths`, and within `window` where given."""
    key = jnp.arange(s)[None, None, :]
    qp = jnp.arange(t)[None, :, None] if q_pos is None else q_pos[..., None]
    ok = (key <= qp) & (key < lengths[:, None, None])
    if window:
        ok = ok & (qp - key < window)
    return ok


def _gmu(p, u, m):
    return _mm(jax.nn.silu(_mm(u, p["W_in"])) * m, p["W_out"])


def _attention_dtype(p, cache=None):
    dt = p["W_q"].dtype
    return dt if cache is None else jnp.promote_types(cache.dtype, dt)


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------
@dataclass
class _SambaYConf(LayerConf):
    """The widths the self- and cross-decoder layers share."""

    input_kind = "rnn"

    n_model: int = 0
    ssm_state: int = 16
    conv_kernel: int = 4
    expand: int = 2
    dt_rank: int = 0            # 0: ceil(d / 16)
    chunk: int = 32
    n_heads: int = 0            # differential heads (pairs of queries)
    n_kv_heads: int = 0
    head_dim: int = 0           # a query's or a key's; a value is 2 x
    window: int = 512
    mlp_hidden: int = 0
    eps: float = 1e-5

    def _d(self, it=None) -> int:
        if self.n_model:
            return self.n_model
        if it is None:
            raise ValueError(f"{type(self).__name__} needs n_model or an "
                             "input type")
        return it.size

    def widths(self, d: int) -> Widths:
        return Widths(d=d, e=self.expand * d, n=self.ssm_state,
                      r=self.dt_rank or -(-d // 16), k=self.conv_kernel,
                      chunk=self.chunk, heads=self.n_heads,
                      kv_heads=self.n_kv_heads, head=self.head_dim,
                      window=self.window, mlp=self.mlp_hidden, eps=self.eps)

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self._d(it), it.timesteps)

    @property
    def has_params(self) -> bool:
        return True

    def _sublayer_params(self, key, kind: str, w: Widths):
        """One layer's parameters: LN gains and biases, the MLP, the mixer
        `kind` ("mamba", "attention" with its own keys and values, "cross",
        "gmu")."""
        keys = iter(jax.random.split(key, 16))
        dtype = jnp.dtype(self.dtype or "float32")
        mat = lambda *s: self._winit(next(keys), s, s[0], s[1])
        vec = lambda n, v: jnp.full((n,), v, dtype)
        if kind == "mamba":
            step = jnp.exp(jax.random.uniform(next(keys), (w.e,), _F32,
                                              math.log(1e-3), math.log(1e-1)))
            mix = {"W_in": mat(w.d, 2 * w.e),
                   "conv_W": jax.random.uniform(
                       next(keys), (w.k, w.e), _F32, -w.k ** -0.5,
                       w.k ** -0.5).astype(dtype),
                   "conv_b": vec(w.e, 0.0), "W_x": mat(w.e, w.r + 2 * w.n),
                   "W_dt": mat(w.r, w.e),
                   "dt_b": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
                   "A_log": jnp.log(jnp.broadcast_to(
                       jnp.arange(1, w.n + 1, dtype=_F32), (w.e, w.n))
                   ).astype(dtype),
                   "D": vec(w.e, 1.0), "W_out": mat(w.e, w.d)}
        elif kind == "gmu":
            mix = {"W_in": mat(w.d, w.e), "W_out": mat(w.e, w.d)}
        else:
            lam = lambda: (0.1 * jax.random.normal(next(keys), (w.head,), _F32)
                           ).astype(dtype)
            mix = {"W_q": mat(w.d, w.heads * 2 * w.head),
                   "W_o": mat(w.heads * 2 * w.head, w.d),
                   "lq1": lam(), "lk1": lam(), "lq2": lam(), "lk2": lam(),
                   "subln": vec(2 * w.head, 1.0)}
            if kind == "attention":
                mix.update(W_k=mat(w.d, w.kv_width), W_v=mat(w.d, w.kv_width))
        return {"ln1_g": vec(w.d, 1.0), "ln1_b": vec(w.d, 0.0), "mix": mix,
                "ln2_g": vec(w.d, 1.0), "ln2_b": vec(w.d, 0.0),
                "W_1": mat(w.d, 2 * w.mlp), "W_2": mat(w.mlp, w.d)}

    def _mamba_state(self, w: Widths):
        return {"ssm": (("slots", w.e, w.n), "float32"),
                "conv": ((w.k - 1, "slots", w.e), "float32")}


@register_layer
@dataclass
class SambaYBlock(_SambaYConf):
    """One self-decoder layer (module docstring): a Mamba mixer
    (`mixer="mamba"`) or differential attention over a window of
    `window` keys (`mixer="window"`; `lambda_init` is the layer's), then
    the gated MLP. x [B, T, d] -> [B, T, d] float32."""

    mixer: str = "mamba"
    lambda_init: float = 0.8

    def __post_init__(self):
        if self.mixer not in MIXERS:
            raise ValueError(f"mixer must be one of {MIXERS}, got "
                             f"{self.mixer!r}")

    @property
    def decode_window(self) -> int:
        """The keys a tick reads at most, for the engine's records (0: a
        layer that keeps no window)."""
        return self.window if self.mixer == "window" else 0

    def init_params(self, rng, it: InputType):
        w = self.widths(self._d(it))
        return self._sublayer_params(
            rng, "mamba" if self.mixer == "mamba" else "attention", w)

    def _qkv(self, pm, u, w):
        return (_queries(pm, u, w), _mm(u, pm["W_k"]), _mm(u, pm["W_v"]))

    def _window_prompt(self, pm, u, lengths, w):
        """(out [B, T, d], k, v [B, T, Hkv*2Dh]) over whole sequences."""
        b, t, _ = u.shape
        q, k, v = self._qkv(pm, u, w)
        lam = _lambda(pm, self.lambda_init)
        o = diff_attend(q, k, v, _ok(t, t, lengths, w.window), lam, w,
                        _attention_dtype(pm))
        return _diff_out(pm, o, self.lambda_init, w), k, v

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        b, t, _ = x.shape
        w = self.widths(x.shape[-1])
        lengths = (jnp.full((b,), t, jnp.int32) if mask is None
                   else jnp.sum(mask.astype(jnp.int32), axis=1))
        if self.mixer == "mamba":
            mix = lambda pm, u: mamba_prompt(pm, u, lengths, w)[0]
        else:
            mix = lambda pm, u: self._window_prompt(pm, u, lengths, w)[0]
        return _sublayer(params, x, mix, w), state

    # -- the decode plane's contract --------------------------------------
    def decode_cache(self, width: int):
        return 0, 0

    def decode_state(self, width: int):
        """A Mamba layer's state, or a window layer's ring of `window` keys
        and values a sequence (module docstring)."""
        w = self.widths(width)
        if self.mixer == "mamba":
            return self._mamba_state(w)
        ring = (("slots", w.window, w.kv_width),
                jnp.dtype(self.dtype or "float32").name)
        return {"k": ring, "v": ring}

    def decode_attention(self, phase: str, spec):
        return None

    def decode_prefill_step(self, io, attention=None):
        def step(p, x, kv, sc, channel, blk, off, pos, lengths, state, slot):
            w = self.widths(x.shape[-1])
            kept = {}

            def mix(pm, u):
                if self.mixer == "mamba":
                    out, _, ssm, conv = mamba_prompt(pm, u, lengths, w)
                    kept.update(ssm=state["ssm"].at[slot].set(ssm),
                                conv=state["conv"].at[:, slot].set(conv))
                    return out
                out, k, v = self._window_prompt(pm, u, lengths, w)
                # ring slot j holds the last real position t = j (mod W);
                # slots no position reaches are never read (j > position)
                j = jnp.arange(w.window)[None, :]
                at = jnp.clip(j + w.window * ((lengths[:, None] - 1 - j)
                                              // w.window), 0, u.shape[1] - 1)
                for name, z in (("k", k), ("v", v)):
                    ring = jnp.take_along_axis(z, at[..., None], axis=1)
                    kept[name] = state[name].at[slot].set(
                        ring.astype(state[name].dtype))
                return out

            return _sublayer(p, x, mix, w), kv, sc, None, kept
        return step

    def decode_window_attention(self, phase: str, width: int):
        """A window layer's tick over its ring: "ring_kernel" (the Pallas
        kernel, each row's live ring slots read in place) where the backend
        is the TPU and the ring's heads and window are whole tiles, else
        "ring_gather" (the rows' rings gathered, the kernel's oracle). A
        Mamba layer, or a prefill, has nothing to choose."""
        from ...kernels import pallas_supported
        from ...kernels.ring_attention import ring_attention_supported

        if self.mixer != "window" or phase != "tick":
            return None
        w = self.widths(width)
        if pallas_supported() and ring_attention_supported(
                2 * w.head, w.window, self.dtype or "float32"):
            return "ring_kernel"
        return "ring_gather"

    def decode_tick_step(self, io, attention=None, window_attention=None):
        from ...kernels import ring_attention as ring

        window_attention = window_attention or "ring_gather"
        if window_attention not in ("ring_kernel", "ring_gather"):
            raise ValueError(f"window_attention must be ring_kernel|"
                             f"ring_gather, got {window_attention!r}")

        def step(p, x, kv, sc, channel, blk, off, tables, positions, lengths,
                 state, slot):
            w = self.widths(x.shape[-1])
            kept = {}

            def mix(pm, u):
                u = u[:, 0]
                if self.mixer == "mamba":
                    out, _, new = mamba_tick(pm, u, state, slot, w)
                    kept.update(new)
                    return out[:, None]
                q = _mm(u, pm["W_q"])                           # [B, H*2Dh]
                k, v = _mm(u, pm["W_k"]), _mm(u, pm["W_v"])
                at = positions % w.window
                ring_k = state["k"].at[slot, at].set(k.astype(state["k"].dtype))
                ring_v = state["v"].at[slot, at].set(v.astype(state["v"].dtype))
                kept.update(k=ring_k, v=ring_v)
                lam = _lambda(pm, self.lambda_init)
                # ring slot j is live once the row has reached position j
                if window_attention == "ring_kernel":  # the COMPILED kernel
                    o = ring.ring_diff_attention(
                        q, ring_k, ring_v, slot,
                        jnp.minimum(positions + 1, w.window),
                        n_heads=w.heads, n_kv_heads=w.kv_heads,
                        sm_scale=1.0 / math.sqrt(w.head), interpret=False)
                    o = o[:, 0] - lam * o[:, 1]
                else:
                    o = diff_attend_rows(
                        q.reshape(-1, w.heads, 2, w.head), ring_k[slot],
                        ring_v[slot],
                        jnp.arange(w.window)[None, :] <= positions[:, None],
                        lam, w, _attention_dtype(pm, ring_k))
                return _diff_out(pm, o, self.lambda_init, w)[:, None]

            return _sublayer(p, x, mix, w), kv, sc, None, kept
        return step


@register_layer
@dataclass
class CrossDecoderBlock(_SambaYConf):
    """The cross-decoder (module docstring): `layers` layers from layer
    `first_layer` on: a Mamba layer whose gated value is the memory, a
    full-attention layer that writes the shared keys and values, then GMU
    and cross-attention layers in turn. x [B, T, d] -> [B, T, d] float32;
    in a served prefill -> [B, 1, d], each row's last real token."""

    layers: int = 16
    first_layer: int = 16

    def __post_init__(self):
        if self.layers < 2 or self.layers % 2:
            raise ValueError("a cross-decoder is a Mamba layer, a full "
                             "attention layer and (GMU, cross) pairs: an even "
                             f"number of layers >= 2, got {self.layers}")

    def kinds(self):
        return ("mamba", "attention") + ("gmu", "cross") * (self.layers // 2 - 1)

    @property
    def decode_shared_readers(self) -> int:
        """The layers that read the shared keys and values: the full layer
        and every cross layer."""
        return self.layers // 2

    decode_prefill_last = True      # a prefill hands on the last token alone

    def init_params(self, rng, it: InputType):
        w = self.widths(self._d(it))
        keys = jax.random.split(rng, self.layers)
        return {"layers": tuple(self._sublayer_params(k, kind, w)
                                for k, kind in zip(keys, self.kinds()))}

    def _lam0(self, j: int) -> float:
        return lambda_init(self.first_layer + j)

    def _after_kv(self, ps, x, m, attend, w):
        """Layers 1.. on x [B, T', d] with the memory m [B, T', e]:
        `attend(pm, q, lam)` is (A1 - lambda A2) v [B, T', H, 2Dh] over the
        shared keys and values."""
        for j, (p, kind) in enumerate(zip(ps, self.kinds())):
            if j == 0:
                continue
            lam0 = self._lam0(j)
            if kind == "gmu":
                mix = lambda pm, u: _gmu(pm, u, m)
            else:
                def mix(pm, u, lam0=lam0):
                    o = attend(pm, _queries(pm, u, w), _lambda(pm, lam0))
                    return _diff_out(pm, o, lam0, w)
            x = _sublayer(p, x, mix, w)
        return x

    def _first(self, p, x, mamba, w):
        """Layer 0 (Mamba: `mamba(pm, u) -> (out, g)`) and the full layer's
        keys and values: (x after layer 0, g, k, v [.., Hkv*2Dh])."""
        kept = {}

        def mix(pm, u):
            out, kept["g"] = mamba(pm, u)
            return out

        x = _sublayer(p[0], x, mix, w)
        pa = p[1]
        u = _layer_norm(x, pa["ln1_g"], pa["ln1_b"], w.eps)
        return x, kept["g"], _mm(u, pa["mix"]["W_k"]), _mm(u, pa["mix"]["W_v"])

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        b, t, _ = x.shape
        w = self.widths(x.shape[-1])
        lengths = (jnp.full((b,), t, jnp.int32) if mask is None
                   else jnp.sum(mask.astype(jnp.int32), axis=1))
        ps = params["layers"]
        x, g, k, v = self._first(
            ps, x, lambda pm, u: mamba_prompt(pm, u, lengths, w)[:2], w)
        ok = _ok(t, t, lengths)
        attend = lambda pm, q, lam: diff_attend(q, k, v, ok, lam, w,
                                                _attention_dtype(pm))
        return self._after_kv(ps, x, g, attend, w), state

    # -- the decode plane's contract --------------------------------------
    def decode_cache(self, width: int):
        """The shared pair: keys and values of the Hkv heads, merged."""
        return 2, self.widths(width).kv_width

    def decode_state(self, width: int):
        return self._mamba_state(self.widths(width))

    def decode_attention(self, phase: str, spec):
        """A tick's attention over the shared pages: "diff_paged" (the
        Pallas kernel, pages read in place) where the backend is the TPU
        and the pages are whole tiles of float32 or bfloat16, else
        "diff_gather" (the view through the tables). A prefill attends over
        its local keys and values: nothing to choose."""
        from ...kernels import pallas_supported
        from ...kernels.paged_attention import paged_attention_supported
        from ...serving.decode.cache import KV_DTYPES

        if phase != "tick":
            return None
        if (pallas_supported() and spec.kv_dtype in ("fp32", "bf16")
                and paged_attention_supported(
                    spec.width, spec.block_len, KV_DTYPES[spec.kv_dtype])):
            return "diff_paged"
        return "diff_gather"

    def decode_prefill_step(self, io, attention=None):
        def step(p, x, kv, sc, channel, blk, off, pos, lengths, state, slot):
            w = self.widths(x.shape[-1])
            ps, kept = p["layers"], {}

            def mamba(pm, u):
                out, g, kept["ssm"], kept["conv"] = mamba_prompt(
                    pm, u, lengths, w)
                return out, g

            x, g, k, v = self._first(ps, x, mamba, w)
            kv, sc = io.scatter(kv, sc, k, blk, off, channel)
            kv, sc = io.scatter(kv, sc, v, blk, off, channel + 1)
            # from here on each row's last real token alone
            last = (lengths - 1)[:, None, None]
            pick = lambda z: jnp.take_along_axis(z, last, axis=1)
            ok = _ok(1, x.shape[1], lengths, q_pos=(lengths - 1)[:, None])
            attend = lambda pm, q, lam: diff_attend(q, k, v, ok, lam, w,
                                                    _attention_dtype(pm))
            y = self._after_kv(ps, pick(x), pick(g), attend, w)
            new = {"ssm": state["ssm"].at[slot].set(kept["ssm"]),
                   "conv": state["conv"].at[:, slot].set(kept["conv"])}
            return y, kv, sc, None, new
        return step

    def decode_tick_step(self, io, attention=None):
        from ...kernels import paged_attention as paged

        attention = attention or "diff_gather"
        if attention not in ("diff_paged", "diff_gather"):
            raise ValueError(f"attention must be diff_paged|diff_gather, got "
                             f"{attention!r}")

        def step(p, x, kv, sc, channel, blk, off, tables, positions, lengths,
                 state, slot):
            w = self.widths(x.shape[-1])
            ps, kept = p["layers"], {}

            def mamba(pm, u):
                out, g, kept["state"] = mamba_tick(pm, u[:, 0], state, slot, w)
                return out[:, None], g[:, None]

            x, g, k, v = self._first(ps, x, mamba, w)
            kv, sc = io.scatter(kv, sc, k[:, 0], blk, off, channel)
            kv, sc = io.scatter(kv, sc, v[:, 0], blk, off, channel + 1)
            if attention == "diff_paged":       # always the COMPILED kernel
                def attend(pm, q, lam):
                    o = paged.paged_diff_attention(
                        q[:, 0], kv, channel, tables,
                        lengths, n_kv_heads=w.kv_heads,
                        sm_scale=1.0 / math.sqrt(w.head), interpret=False)
                    return (o[:, 0] - lam * o[:, 1])[:, None]
            else:
                keys = lambda c: io.gather(kv, sc, tables, c).reshape(
                    tables.shape[0], -1, w.kv_width)
                valid = jnp.arange(tables.shape[1] * io.spec.block_len)[None] \
                    < lengths[:, None]

                def attend(pm, q, lam):
                    dt = _attention_dtype(pm, kv)
                    return diff_attend_rows(q[:, 0], keys(channel),
                                            keys(channel + 1), valid, lam, w,
                                            dt)[:, None]

            y = self._after_kv(ps, x, g, attend, w)
            return y, kv, sc, None, kept["state"]
        return step
