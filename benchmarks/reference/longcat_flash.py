"""Plain reference for LongCat-Flash as the program serves one chip's share.

    tokens -> W[tokens] -> (block) x num_layers -> N_f(h) W_head

A block (N is RMSNorm with a gain, epsilon rms_norm_eps; no biases):

    a  = h + MLA_0(N_1 h)
    u  = N_2 a
    m  = MoE(u)                      # the shortcut: skips FFN_0 and MLA_1
    b  = a + FFN_0(u)                # FFN(x) = (silu(x W_g) * (x W_u)) W_d
    c  = b + MLA_1(N_3 b)
    h' = c + FFN_1(N_4 c) + m

    MLA(x):  q = (N_q(x W_qa) W_qb) * s_q -> heads of [q_nope | q_rope],
                 s_q = sqrt(hidden / q_lora_rank)
             [c | k_rope] = x W_kva;  c = N_kv(c) * s_kv,
                 s_kv = sqrt(hidden / kv_lora_rank)
             [k_nope | v] a head = c W_kvb; q_rope and k_rope rotated in
             interleaved pairs (theta rope_theta), k_rope shared by the heads
             p = softmax_causal((q_nope.k_nope + q_rope.k_rope)
                                / sqrt(qk_nope + qk_rope))
             out = concat_heads(p v) W_o
    MoE(u):  s = softmax(u W_r) over routed + identity experts, in float32
             picks = top-k of (s + b_corr);  w_e = scaling * s_e
             m = sum over picked HELD e of w_e * Expert_e(u)
               + sum over picked identity e of w_e * u

The share: the router keeps every output; of the routed experts the
weights hold `deployment.held_experts` alone, and what the absent ones
would have added is left out (another chip's part); the token table and
the head hold `vocab_size` rows, the slice.

Everything here is `jax.numpy`: full causal attention with the heads
expanded from the latent, every held expert over every token under a
mask, no cache, no kernels. It imports nothing of the program. The
weights are made here, from the seed, in bfloat16 as the configuration
states, leaf by leaf (the whole set fills most of a chip); the benchmark
hands the same arrays to the program. The forward is float32 and runs one
sub-layer a compiled call, so that only that sub-layer's weights are ever
upcast beside the bfloat16 set.

`precision` says in what arithmetic the matrix products are made (the
router's product is float32 in all, as the equations state):
  "float32"   operands as they are, under `default_matmul_precision(
              "highest")`: the true value
  "bfloat16"  what the configuration states: both operands of every matrix
              product rounded to bfloat16, products and sums float32
  "float8"    the control: both operands rounded to e4m3 with one scale a
              tensor, the rest as "float32"
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "float8")
HEAD_CHUNK = 8          # heads of attention computed at once


class Dims(NamedTuple):
    d: int
    layers: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_head: int
    ffn: int
    expert_ffn: int
    routed: int             # routed experts of the whole layer (published)
    held_lo: int
    held_hi: int
    identity: int
    top_k: int
    scaling: float
    theta: float
    eps: float
    vocab: int
    positions: int


def dims(config: dict) -> Dims:
    lo, hi = config["deployment"]["held_experts"]
    if hi - lo != int(config["n_routed_experts"]):
        raise ValueError("deployment.held_experts and n_routed_experts "
                         "(the experts held here) disagree")
    return Dims(
        d=int(config["hidden_size"]), layers=int(config["num_layers"]),
        heads=int(config["num_attention_heads"]),
        q_rank=int(config["q_lora_rank"]), kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]), v_head=int(config["v_head_dim"]),
        ffn=int(config["ffn_hidden_size"]),
        expert_ffn=int(config["expert_ffn_hidden_size"]),
        routed=int(config["published"]["n_routed_experts"]),
        held_lo=int(lo), held_hi=int(hi),
        identity=int(config["zero_expert_num"]), top_k=int(config["moe_topk"]),
        scaling=float(config["routed_scaling_factor"]),
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        vocab=int(config["vocab_size"]),
        positions=int(config["max_position_embeddings"]))


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


# ---------------------------------------------------------------------------
# weights, from the seed, on the device, one leaf a call
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("shape", "std", "mean", "dtype"))
def _normal(key, shape, std, mean, dtype):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16).astype(dtype)


def init_params(config: dict, seed: int):
    """Tuple of per-layer dicts: token table {W}, blocks {attn0, attn1,
    ffn0, ffn1, moe, n1..n4}, final norm {g}, head {W}. Every value is a
    bfloat16, held as `precision.weights` says (bfloat16; a test on a
    backend without bfloat16 products says float32).
    Matrices Xavier-normal, the token table of unit variance, norm gains
    1 + 0.02 and the router's correction bias 0.001 (of the order of a
    score, 1/768): nothing is exactly 0 or 1, so that a gain or the bias
    left out shows."""
    m = dims(config)
    routes, held = m.routed + m.identity, m.held_hi - m.held_lo
    qk = m.nope + m.rope
    dtype = jnp.dtype(config["precision"]["weights"])

    def maker(key):
        count = iter(range(1 << 20))

        def w(*shape):
            std = math.sqrt(2.0 / (shape[-2] + shape[-1]))
            return _normal(jax.random.fold_in(key, next(count)), shape, std,
                           0.0, dtype)

        def gain(n):
            return _normal(jax.random.fold_in(key, next(count)), (n,), 0.02,
                           1.0, dtype)

        def bias(n):
            return _normal(jax.random.fold_in(key, next(count)), (n,), 1e-3,
                           0.0, dtype)
        return w, gain, bias

    def block(key):
        w, gain, bias = maker(key)
        attention = lambda: {
            "W_qa": w(m.d, m.q_rank), "q_norm": gain(m.q_rank),
            "W_qb": w(m.q_rank, m.heads * qk),
            "W_kva": w(m.d, m.kv_rank + m.rope), "kv_norm": gain(m.kv_rank),
            "W_kvb": w(m.kv_rank, m.heads * (m.nope + m.v_head)),
            "W_o": w(m.heads * m.v_head, m.d)}
        ffn = lambda: {"W_g": w(m.d, m.ffn), "W_u": w(m.d, m.ffn),
                       "W_d": w(m.ffn, m.d)}
        return {"attn0": attention(), "attn1": attention(),
                "ffn0": ffn(), "ffn1": ffn(),
                "moe": {"router_W": w(m.d, routes), "router_bias": bias(routes),
                        "expert_W_g": w(held, m.d, m.expert_ffn),
                        "expert_W_u": w(held, m.d, m.expert_ffn),
                        "expert_W_d": w(held, m.expert_ffn, m.d)},
                "n1": gain(m.d), "n2": gain(m.d), "n3": gain(m.d),
                "n4": gain(m.d)}

    key = seed_key(seed)
    w, gain, _ = maker(jax.random.fold_in(key, 0))
    # token vectors of unit variance: the residual stream is then the
    # token's own before it is the attention's mean over the prompt, and
    # the router's picks differ from token to token as a trained router's
    # do (with Xavier's 0.009 every token of a prompt picked the same
    # experts: the largest load of a held expert was 7.4 times the mean)
    table = _normal(jax.random.fold_in(key, 1 << 20), (m.vocab, m.d), 1.0,
                    0.0, dtype)
    emb, norm, head = {"W": table}, {"g": gain(m.d)}, {"W": w(m.d, m.vocab)}
    blocks = tuple(block(jax.random.fold_in(key, 1 + i))
                   for i in range(m.layers))
    return (emb,) + blocks + (norm, head)


# ---------------------------------------------------------------------------
# forward: one sub-layer a compiled call
# ---------------------------------------------------------------------------
def _round(x, precision):
    """An operand of a matrix product, as `precision` takes it."""
    x = x.astype(jnp.float32)
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x


def _mm(x, w, precision):
    return _round(x, precision) @ _round(w, precision)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g.astype(jnp.float32)


def _rope(x, theta):
    """x [T, ..., rope]: interleaved pairs rotated by position * theta^(-2i/rope)."""
    t, half = x.shape[0], x.shape[-1] // 2
    inv = 1.0 / theta ** (2.0 * jnp.arange(half, dtype=jnp.float32)
                          / x.shape[-1])
    ang = jnp.arange(t, dtype=jnp.float32).reshape(
        (t,) + (1,) * (x.ndim - 1)) * inv
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


_jit = functools.partial(jax.jit, static_argnames=("m", "precision"))


@_jit
def _mla(p, g, h, *, m, precision):
    """MLA(N h) over the whole causal sequence h [T, d]."""
    with jax.default_matmul_precision("highest"):
        t = h.shape[0]
        x = _norm(h, g, m.eps)
        q = _mm(_norm(_mm(x, p["W_qa"], precision), p["q_norm"], m.eps),
                p["W_qb"], precision) * math.sqrt(m.d / m.q_rank)
        q = q.reshape(t, m.heads, m.nope + m.rope)
        q = jnp.concatenate([q[..., :m.nope], _rope(q[..., m.nope:], m.theta)],
                            axis=-1)
        ckr = _mm(x, p["W_kva"], precision)
        c = _norm(ckr[:, :m.kv_rank], p["kv_norm"], m.eps) \
            * math.sqrt(m.d / m.kv_rank)
        k_rope = _rope(ckr[:, m.kv_rank:], m.theta)
        kv = _mm(c, p["W_kvb"], precision).reshape(t, m.heads,
                                                   m.nope + m.v_head)
        k = jnp.concatenate(
            [kv[..., :m.nope],
             jnp.broadcast_to(k_rope[:, None], (t, m.heads, m.rope))], -1)
        v = kv[..., m.nope:]
        causal = jnp.tril(jnp.ones((t, t), bool))

        def heads(qkv):
            qh, kh, vh = qkv                        # [T, chunk, .]
            s = jnp.einsum("thd,shd->hts", _round(qh, precision),
                           _round(kh, precision)) / math.sqrt(m.nope + m.rope)
            w = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
            return jnp.einsum("hts,shv->thv", _round(w, precision),
                              _round(vh, precision))

        chunk = math.gcd(m.heads, HEAD_CHUNK)
        chunks = lambda z: z.reshape(t, -1, chunk, z.shape[-1]
                                     ).transpose(1, 0, 2, 3)
        out = jax.lax.map(heads, (chunks(q), chunks(k), chunks(v)))
        out = out.transpose(1, 0, 2, 3).reshape(t, m.heads * m.v_head)
        return _mm(out, p["W_o"], precision)


@_jit
def _ffn(p, g, h, *, m, precision):
    with jax.default_matmul_precision("highest"):
        x = _norm(h, g, m.eps)
        return _mm(jax.nn.silu(_mm(x, p["W_g"], precision))
                   * _mm(x, p["W_u"], precision), p["W_d"], precision)


@_jit
def _moe(p, g, h, *, m, precision):
    """The held experts' part, every one of them over every token under a
    mask, and the identity experts' part."""
    with jax.default_matmul_precision("highest"):
        u = _norm(h, g, m.eps)
        s = jax.nn.softmax(u @ p["router_W"].astype(jnp.float32), axis=-1)
        _, ids = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32),
                               m.top_k)
        rows = jnp.arange(u.shape[0])[:, None]
        gate = jnp.zeros_like(s).at[rows, ids].set(
            m.scaling * jnp.take_along_axis(s, ids, axis=-1))

        def expert(total, e):
            w_g, w_u, w_d, w_e = e
            y = _mm(jax.nn.silu(_mm(u, w_g, precision))
                    * _mm(u, w_u, precision), w_d, precision)
            return total + w_e[:, None] * y, None

        held = gate[:, m.held_lo:m.held_hi].T                  # [E, T]
        total, _ = jax.lax.scan(
            expert, jnp.zeros_like(u),
            (p["expert_W_g"], p["expert_W_u"], p["expert_W_d"], held))
        return total + jnp.sum(gate[:, m.routed:], -1, keepdims=True) * u


def block(p, h, m: Dims, precision: str = "float32"):
    """One block over h [T, d] float32."""
    kw = {"m": m, "precision": precision}
    a = h + _mla(p["attn0"], p["n1"], h, **kw)
    moe = _moe(p["moe"], p["n2"], a, **kw)
    b = a + _ffn(p["ffn0"], p["n2"], a, **kw)
    c = b + _mla(p["attn1"], p["n3"], b, **kw)
    return c + _ffn(p["ffn1"], p["n4"], c, **kw) + moe


@_jit
def _head(g, w, x, *, m, precision):
    with jax.default_matmul_precision("highest"):
        return _mm(_norm(x, g, m.eps), w, precision)


def hidden(params, tokens, m: Dims, precision: str = "float32"):
    """tokens int32 [T] -> the last block's output [T, d]."""
    h = params[0]["W"][tokens].astype(jnp.float32)
    for p in params[1:-2]:
        h = block(p, h, m, precision)
    return h


def served_logits(config: dict, params, sequence, first: int, count: int,
                  precision: str = None):
    """One full causal forward over `sequence` (prompt then served tokens),
    padded to the served context; returns the logits [count, V] at positions
    first-1 .. first+count-2: those that chose sequence[first:first+count]."""
    precision = precision or "float32"
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, got "
                         f"{precision!r}")
    m = dims(config)
    if len(sequence) > m.positions:
        raise ValueError("sequence longer than the served context")
    tok = jnp.zeros((m.positions,), jnp.int32).at[:len(sequence)].set(
        jnp.asarray(sequence, jnp.int32))
    h = hidden(params, tok, m, precision)
    width = 128 * -(-count // 128)              # few compiled shapes
    idx = jnp.minimum(first - 1 + jnp.arange(width), m.positions - 1)
    return _head(params[-2]["g"], params[-1]["W"], h[idx], m=m,
                 precision=precision)[:count]
