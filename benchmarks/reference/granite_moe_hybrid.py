"""Plain reference for Granite 4.0-H (`granitemoehybrid`) as the program
serves one chip's share.

    x0 = embedding_multiplier * E[tokens]
    every layer:  x = x + r * Mixer(N(x));  h = N(x)
                  x = x + r * (Experts(h) + Shared(h))      r = residual_multiplier
    logits = N(x) E^T / logits_scaling                      the head is the table

N is RMSNorm with a gain, epsilon rms_norm_eps; no biases but the
convolution's. `layer_types` says which mixer a layer has:

    "mamba" (Mamba-2: H heads of P, state N, one group, K taps):
      [z (H*P) | xBC (H*P + 2N) | dt (H)] = u W_in
      xBC = silu(causal depthwise conv_K(xBC) + b_conv)
      [xs (H*P) | B (N) | C (N)] = xBC
      dt = softplus(dt + dt_bias);  A = -exp(A_log)                 a head
      H_t = exp(dt_t A) H_{t-1} + dt_t * xs_t (x) B_t    [P, N] a head
      y_t = H_t C_t + D * xs_t                  B, C shared by the heads
      out = N_g(y * silu(z)) W_out       the norm over all H*P, after the gate
    "attention" (Hq query heads, Hkv key/value heads, Hq/Hkv queries a
    key/value head, no positional encoding of any kind):
      p = softmax_causal(q k^T * attention_multiplier);  out = (p v) W_o

    Experts(h):  l = h W_r (float32);  the num_experts_per_tok largest
                 logits;  g = softmax over those
                 Expert_e(h) = (silu(h W_g_e) * (h W_u_e)) W_d_e
                 sum over the picked HELD e of g_e * Expert_e(h)
    Shared(h):   the same gated unit at shared_intermediate_size, every token

The share: the router keeps every output; of the routed experts the
weights hold `deployment.held_experts` alone, and what the absent ones
would have added is left out (another chip's part); the token table holds
`vocab_size` rows, the slice.

Everything here is `jax.numpy`: the recurrence as a sequential `lax.scan`
over the tokens (no chunks), full causal attention, every held expert over
every token under a mask, no cache, no batching, no kernels. It imports
nothing of the program. The weights are made here, from the seed, in
bfloat16 as the configuration states, leaf by leaf; the benchmark hands the
same arrays to the program. The program cannot tie its head to its table:
`init_params` hands it a second copy of the table's values, transposed, and
this file multiplies by that copy too (the same numbers). The forward is
float32 and runs one sub-layer a compiled call, so that only that
sub-layer's weights are ever upcast beside the bfloat16 set.

`precision` says in what arithmetic the matrix products are made (the
router's product, the convolution and the recurrence are float32 in all):
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
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "float8")


class Dims(NamedTuple):
    d: int
    mixers: Tuple[str, ...]     # a layer's mixer, "mamba" | "attention"
    ssm_heads: int
    ssm_head: int
    ssm_state: int
    conv: int
    chunk: int                  # the program's; the recurrence here has none
    heads: int
    kv_heads: int
    head: int
    expert_ffn: int
    shared_ffn: int
    routed: int                 # routed experts of the whole layer (published)
    held_lo: int
    held_hi: int
    top_k: int
    attention_mult: float
    embedding_mult: float
    residual_mult: float
    logits_scaling: float
    eps: float
    vocab: int
    positions: int


def dims(config: dict) -> Dims:
    lo, hi = config["deployment"]["held_experts"]
    if hi - lo != int(config["num_local_experts"]):
        raise ValueError("deployment.held_experts and num_local_experts "
                         "(the experts held here) disagree")
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    ssm_heads, ssm_head = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if ssm_heads * ssm_head != int(config["mamba_expand"]) * d:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x "
                         "hidden_size")
    if int(config["mamba_n_groups"]) != 1:
        raise ValueError("one group of B and C is what is written here")
    if config["position_embedding_type"] != "nope":
        raise ValueError("attention without positions is what is written here")
    layers = int(config["num_hidden_layers"])
    return Dims(
        d=d, mixers=tuple(config["layer_types"][:layers]),
        ssm_heads=ssm_heads, ssm_head=ssm_head,
        ssm_state=int(config["mamba_d_state"]), conv=int(config["mamba_d_conv"]),
        chunk=int(config["mamba_chunk_size"]), heads=heads,
        kv_heads=int(config["num_key_value_heads"]), head=d // heads,
        expert_ffn=int(config["intermediate_size"]),
        shared_ffn=int(config["shared_intermediate_size"]),
        routed=int(config["published"]["num_local_experts"]),
        held_lo=int(lo), held_hi=int(hi),
        top_k=int(config["num_experts_per_tok"]),
        attention_mult=float(config["attention_multiplier"]),
        embedding_mult=float(config["embedding_multiplier"]),
        residual_mult=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        eps=float(config["rms_norm_eps"]), vocab=int(config["vocab_size"]),
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


@functools.partial(jax.jit, static_argnames=("shape", "lo", "hi", "how",
                                             "dtype"))
def _uniform(key, shape, lo, hi, how, dtype):
    """Uniform in [lo, hi) ("plain"); the logarithm of it ("log": A_log,
    A uniform); or the inverse softplus of a step drawn log-uniform in
    [lo, hi) ("step": dt_bias)."""
    if how == "step":
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                          math.log(lo), math.log(hi)))
        out = step + jnp.log(-jnp.expm1(-step))
    else:
        out = jax.random.uniform(key, shape, jnp.float32, lo, hi)
        out = jnp.log(out) if how == "log" else out
    return out.astype(jnp.bfloat16).astype(dtype)


@jax.jit
def _transposed(w):
    return w.T


def init_params(config: dict, seed: int):
    """Tuple of per-layer dicts: token table {W}, blocks {n1, mixer, n2,
    moe}, final norm {g}, head {W} (the table's values, transposed). Every
    value is a bfloat16, held as `precision.weights` says (bfloat16; a test
    on a backend without bfloat16 products says float32).

    Matrices Xavier-normal; norm gains and D 1 + 0.02 (nothing is exactly
    0 or 1, so that a gain left out shows). The state-space parameters as
    the public initialiser draws them, so that a seeded model's decays are
    a trained model's: A uniform in 1-16, softplus(dt_bias) log-uniform in
    0.001-0.1, the convolution's taps and bias uniform within 1/sqrt(K).
    The token table has a standard deviation of 0.15 / sqrt(hidden): with
    the head tied to it, a larger table makes every position predict its
    own input token (the logit of token t at a position that holds t grows
    with |E_t|^2), and greedy decoding then repeats one token for ever; at
    this size the input token's logit stands some 2 deviations over the
    others', under their largest. The mixers and the router see the
    token's direction at full size whatever its scale (N divides it out),
    so the router's picks still differ from token to token."""
    m = dims(config)
    held = m.held_hi - m.held_lo
    inner = m.ssm_heads * m.ssm_head
    conv_width = inner + 2 * m.ssm_state
    dtype = jnp.dtype(config["precision"]["weights"])

    def maker(key):
        count = iter(range(1 << 20))
        nxt = lambda: jax.random.fold_in(key, next(count))

        def w(*shape):
            std = math.sqrt(2.0 / (shape[-2] + shape[-1]))
            return _normal(nxt(), shape, std, 0.0, dtype)

        def gain(n):
            return _normal(nxt(), (n,), 0.02, 1.0, dtype)

        def uni(shape, lo, hi, how="plain"):
            return _uniform(nxt(), shape, lo, hi, how, dtype)
        return w, gain, uni

    def block(key, mixer):
        w, gain, uni = maker(key)
        if mixer == "attention":
            mix = {"W_q": w(m.d, m.heads * m.head),
                   "W_k": w(m.d, m.kv_heads * m.head),
                   "W_v": w(m.d, m.kv_heads * m.head),
                   "W_o": w(m.heads * m.head, m.d)}
        else:
            bound = m.conv ** -0.5
            mix = {"W_in": w(m.d, 2 * inner + 2 * m.ssm_state + m.ssm_heads),
                   "conv_W": uni((m.conv, conv_width), -bound, bound),
                   "conv_b": uni((conv_width,), -bound, bound),
                   "dt_bias": uni((m.ssm_heads,), 1e-3, 1e-1, "step"),
                   "A_log": uni((m.ssm_heads,), 1.0, 16.0, "log"),
                   "D": gain(m.ssm_heads), "norm": gain(inner),
                   "W_out": w(inner, m.d)}
        return {"n1": gain(m.d), "mixer": mix, "n2": gain(m.d),
                "moe": {"router_W": w(m.d, m.routed),
                        "expert_W_g": w(held, m.d, m.expert_ffn),
                        "expert_W_u": w(held, m.d, m.expert_ffn),
                        "expert_W_d": w(held, m.expert_ffn, m.d),
                        "shared_W_g": w(m.d, m.shared_ffn),
                        "shared_W_u": w(m.d, m.shared_ffn),
                        "shared_W_d": w(m.shared_ffn, m.d)}}

    key = seed_key(seed)
    _, gain, _ = maker(jax.random.fold_in(key, 0))
    table = _normal(jax.random.fold_in(key, 1 << 20), (m.vocab, m.d),
                    0.15 / math.sqrt(m.d), 0.0, dtype)
    blocks = tuple(block(jax.random.fold_in(key, 1 + i), mixer)
                   for i, mixer in enumerate(m.mixers))
    return (({"W": table},) + blocks
            + ({"g": gain(m.d)}, {"W": _transposed(table)}))


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


_jit = functools.partial(jax.jit, static_argnames=("m", "precision"))
f32 = lambda a: a.astype(jnp.float32)


@_jit
def _mamba(p, g, x, *, m, precision):
    """Mamba-2(N x) over the whole sequence x [T, d], token by token."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        inner = m.ssm_heads * m.ssm_head
        zxd = _mm(_norm(x, g, m.eps), p["W_in"], precision)
        z, xbc = zxd[:, :inner], zxd[:, inner:inner + inner + 2 * m.ssm_state]
        dt = jax.nn.softplus(zxd[:, 2 * inner + 2 * m.ssm_state:]
                             + f32(p["dt_bias"]))                   # [T, H]
        # the convolution reaches K-1 tokens back; zeros before the start
        back = jnp.pad(xbc, ((m.conv - 1, 0), (0, 0)))
        xbc = jax.nn.silu(f32(p["conv_b"]) + sum(
            f32(p["conv_W"])[k] * back[k:k + t] for k in range(m.conv)))
        xs = xbc[:, :inner].reshape(t, m.ssm_heads, m.ssm_head)
        b_t = xbc[:, inner:inner + m.ssm_state]
        c_t = xbc[:, inner + m.ssm_state:]
        a = -jnp.exp(f32(p["A_log"]))

        def token(h, now):
            x_t, dt_t, b, c = now
            h = jnp.exp(dt_t * a)[:, None, None] * h \
                + (dt_t[:, None] * x_t)[:, :, None] * b[None, None, :]
            return h, jnp.sum(h * c[None, None, :], axis=-1)

        _, y = jax.lax.scan(
            token, jnp.zeros((m.ssm_heads, m.ssm_head, m.ssm_state), jnp.float32),
            (xs, dt, b_t, c_t))
        y = (y + f32(p["D"])[:, None] * xs).reshape(t, inner)
        return _mm(_norm(y * jax.nn.silu(z), p["norm"], m.eps), p["W_out"],
                   precision)


@_jit
def _attention(p, g, x, *, m, precision):
    """Grouped-query attention of N x over the whole causal sequence."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        u = _norm(x, g, m.eps)
        group = m.heads // m.kv_heads
        q = _mm(u, p["W_q"], precision).reshape(t, m.kv_heads, group, m.head)
        k = _mm(u, p["W_k"], precision).reshape(t, m.kv_heads, m.head)
        v = _mm(u, p["W_v"], precision).reshape(t, m.kv_heads, m.head)
        causal = jnp.tril(jnp.ones((t, t), bool))

        def kv_head(qkv):           # a key/value head and its queries
            qh, kh, vh = qkv                    # [T, group, Dh], [T, Dh] x 2
            s = jnp.einsum("tgd,sd->gts", _round(qh, precision),
                           _round(kh, precision)) * m.attention_mult
            w = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
            return jnp.einsum("gts,sd->tgd", _round(w, precision),
                              _round(vh, precision))

        out = jax.lax.map(kv_head, (q.transpose(1, 0, 2, 3),
                                    k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
        out = out.transpose(1, 0, 2, 3).reshape(t, m.heads * m.head)
        return _mm(out, p["W_o"], precision)


def _gated(u, w_g, w_u, w_d, precision):
    return _mm(jax.nn.silu(_mm(u, w_g, precision)) * _mm(u, w_u, precision),
               w_d, precision)


@_jit
def _moe(p, g, x, *, m, precision):
    """The held experts' part, every one of them over every token under a
    mask, and the shared expert."""
    with jax.default_matmul_precision("highest"):
        u = _norm(x, g, m.eps)
        top, ids = jax.lax.top_k(u @ f32(p["router_W"]), m.top_k)
        rows = jnp.arange(u.shape[0])[:, None]
        gate = jnp.zeros((u.shape[0], m.routed), jnp.float32).at[rows, ids].set(
            jax.nn.softmax(top, axis=-1))

        def expert(total, e):
            w_g, w_u, w_d, w_e = e
            return total + w_e[:, None] * _gated(u, w_g, w_u, w_d,
                                                 precision), None

        total, _ = jax.lax.scan(
            expert, jnp.zeros_like(u),
            (p["expert_W_g"], p["expert_W_u"], p["expert_W_d"],
             gate[:, m.held_lo:m.held_hi].T))
        return total + _gated(u, p["shared_W_g"], p["shared_W_u"],
                              p["shared_W_d"], precision)


def block(p, x, mixer: str, m: Dims, precision: str = "float32"):
    """One layer over x [T, d] float32."""
    kw = {"m": m, "precision": precision}
    mix = _attention if mixer == "attention" else _mamba
    x = x + m.residual_mult * mix(p["mixer"], p["n1"], x, **kw)
    return x + m.residual_mult * _moe(p["moe"], p["n2"], x, **kw)


@_jit
def _head(g, w, x, *, m, precision):
    with jax.default_matmul_precision("highest"):
        return _mm(_norm(x, g, m.eps), w, precision) / m.logits_scaling


def hidden(params, tokens, m: Dims, precision: str = "float32"):
    """tokens int32 [T] -> the last layer's output [T, d]."""
    x = m.embedding_mult * f32(params[0]["W"][tokens])
    for p, mixer in zip(params[1:-2], m.mixers):
        x = block(p, x, mixer, m, precision)
    return x


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
