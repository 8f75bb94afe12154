"""Plain reference for Nemotron 3 Super (`nemotron_h`) as the program serves
one chip's share.

d = hidden_size, H = mamba_num_heads of P = mamba_head_dim, N =
ssm_state_size, G = n_groups, K = conv_kernel; Hq = num_attention_heads and
Hkv = num_key_value_heads of Dh = head_dim. N_l is RMSNorm with a gain
(x / rms * g, epsilon layer_norm_epsilon). No projection has a bias; the
convolution does.

    x0 = E[tokens]                                   no positional encoding
    every layer l:  x = x + Part_l(N_l x),  u = N_l x
    logits = N_f(x) W_head                           the head is untied

Part_l by hybrid_override_pattern[l]:

    "M" Mamba-2:
      [z (H*P) | xBC (H*P + 2GN) | dt (H)] = u W_in
      xBC = silu(causal depthwise conv_K(xBC) + b)
      [xs (H*P) | B (G x N) | C (G x N)] = xBC;  head h reads group
      g(h) = floor(h / (H/G))
      dt = softplus(dt + dt_bias), no clamp;  A = -exp(A_log)     a head
      S_t = exp(dt_t A_h) S_{t-1} + dt_t * xs_t (x) B_{t,g(h)}    [P, N] a head
      y_t = S_t C_{t,g(h)} + D_h xs_t
      out = GroupRMSNorm(y * silu(z)) W_out      the norm within each of the
                                                 G groups of H*P/G channels,
                                                 after the gate, one gain
    "*" attention (no rotary, no positional table):
      q = u W_q (Hq x Dh);  k = u W_k, v = u W_v (Hkv x Dh); query head i
      reads key/value head floor(i / (Hq/Hkv))
      p = softmax_causal(q k^T / sqrt(Dh));  out = [p v]_heads W_o
    "E" LatentMoE:
      s = sigmoid(u W_r) in float32, over all n_routed_experts
      picks = the num_experts_per_tok largest of s + b_corr
      w_e = routed_scaling_factor * s_e / sum over the picks of s
      l = u W_down                                    d -> moe_latent_size
      out = (sum over picked HELD e of w_e * relu(l U_e)^2 V_e) W_up
            + relu(u S_u)^2 S_d                        the shared expert

The share: the router keeps every output and its picks' sum; of the routed
experts the weights hold `deployment.held_experts` alone, and what the
absent ones would have added is left out (other chips' parts; W_up is
linear, so the parts add up); the token table and the head hold
`vocab_size` rows, the slice.

Everything here is `jax.numpy`: the recurrence a sequential `lax.scan` over
the tokens (no chunks), full causal attention one key/value head at a time
(no cache), every held expert over every token under its weights (0 where
it was not picked), no batching, no kernels. It imports nothing of the
program. The weights are made here, from the seed, in bfloat16 as the
configuration states, leaf by leaf; the benchmark hands the same arrays to
the program. The forward is float32 and runs one layer a compiled call, so
that only that layer's weights are ever upcast beside the bfloat16 set (the
experts one at a time), and the head a block of positions a call.

`precision` says in what arithmetic the matrix products are made (the
router's product, the convolution, the recurrence, the norms and the
softmaxes are float32 in all):
  "float32"   the true value: a product with a weight splits the other
              operand into three bfloat16 parts that add up to it (the
              weights' values are bfloat16 ones, so each part's products
              are exact in one pass), the attention's two products under
              `default_matmul_precision("highest")`
  "bfloat16"  what the configuration states: both operands of every matrix
              product rounded to bfloat16, products and sums float32 (one
              pass of the MXU at its default precision)
  "float8"    the control: both operands rounded to e4m3 with one scale a
              tensor, the rest as "float32"
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "float8")
PARTS = {"M": "mamba", "*": "attention", "E": "moe"}


class Dims(NamedTuple):
    d: int
    parts: Tuple[str, ...]      # a layer's part: "mamba" | "attention" | "moe"
    ssm_heads: int
    ssm_head: int
    ssm_state: int
    groups: int
    conv: int
    chunk: int                  # the program's; the recurrence here has none
    heads: int
    kv_heads: int
    head: int
    expert_ffn: int
    shared_ffn: int
    latent: int
    routed: int                 # routed experts of the whole layer (published)
    held_lo: int
    held_hi: int
    top_k: int
    scaling: float
    eps: float
    vocab: int
    positions: int


def dims(config: dict) -> Dims:
    lo, hi = config["deployment"]["held_experts"]
    if hi - lo != int(config["n_routed_experts"]):
        raise ValueError("deployment.held_experts and n_routed_experts (the "
                         "experts held here) disagree")
    d = int(config["hidden_size"])
    ssm_heads, ssm_head = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    if ssm_heads * ssm_head != int(config["expand"]) * d:
        raise ValueError("mamba_num_heads x mamba_head_dim is not expand x "
                         "hidden_size")
    groups = int(config["n_groups"])
    if ssm_heads % groups:
        raise ValueError("the groups must divide the Mamba heads")
    if (config["mlp_hidden_act"] != "relu2" or not config["norm_topk_prob"]
            or int(config["n_group"]) != 1 or int(config["n_shared_experts"]) != 1
            or config["mlp_bias"] or config["use_bias"]
            or config["attention_bias"]):
        raise ValueError("relu^2 experts, picks normalised over one group of "
                         "routes, one shared expert and no biases are what "
                         "is written here")
    layers = int(config["num_hidden_layers"])
    return Dims(
        d=d, parts=tuple(PARTS[c] for c in
                         config["hybrid_override_pattern"][:layers]),
        ssm_heads=ssm_heads, ssm_head=ssm_head,
        ssm_state=int(config["ssm_state_size"]), groups=groups,
        conv=int(config["conv_kernel"]), chunk=int(config["chunk_size"]),
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        head=int(config["head_dim"]),
        expert_ffn=int(config["moe_intermediate_size"]),
        shared_ffn=int(config["moe_shared_expert_intermediate_size"]),
        latent=int(config["moe_latent_size"]),
        routed=int(config["published"]["n_routed_experts"]),
        held_lo=int(lo), held_hi=int(hi),
        top_k=int(config["num_experts_per_tok"]),
        scaling=float(config["routed_scaling_factor"]),
        eps=float(config["layer_norm_epsilon"]),
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


@functools.partial(jax.jit, static_argnames=("shape", "lo", "hi", "floor",
                                             "how", "dtype"))
def _uniform(key, shape, lo, hi, floor, how, dtype):
    """Uniform in [lo, hi) ("plain"), or the inverse softplus of a step
    drawn log-uniform in [lo, hi) and held at `floor` at least ("step":
    dt_bias)."""
    if how == "step":
        step = jnp.maximum(floor, jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(lo), math.log(hi))))
        out = step + jnp.log(-jnp.expm1(-step))
    else:
        out = jax.random.uniform(key, shape, jnp.float32, lo, hi)
    return out.astype(jnp.bfloat16).astype(dtype)


@functools.partial(jax.jit, static_argnames=("heads", "dtype"))
def _a_log(heads, dtype):
    """A = 1 .. H, a head each (the public modelling code's initialiser)."""
    return jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)
                   ).astype(jnp.bfloat16).astype(dtype)


def init_params(config: dict, seed: int):
    """Tuple of per-layer dicts: token table {W}, layers {n, mixer} or {n,
    moe}, final norm {g}, head {W} [d, V]. Every value is a bfloat16, held
    as `precision.weights` says (bfloat16; a test on a backend without
    bfloat16 products says float32).

    Matrices Xavier-normal; norm gains and D 1 + 0.02 (nothing is exactly
    0 or 1, so that a gain left out shows); the correction bias normal with
    a deviation of 0.05 (the public initialiser's zeros would leave its term
    untested); A = 1..H, softplus(dt_bias) log-uniform in time_step_min -
    time_step_max held at time_step_floor, the convolution's taps and bias
    uniform within 1/sqrt(K) (the public modelling code's initialiser, so
    that a seeded model's decays are a trained model's); the token table
    normal with a deviation of 0.02 (its `initializer_range`)."""
    m = dims(config)
    held = m.held_hi - m.held_lo
    inner = m.ssm_heads * m.ssm_head
    conv_width = inner + 2 * m.groups * m.ssm_state
    dtype = jnp.dtype(config["precision"]["weights"])
    steps = tuple(float(config[k]) for k in
                  ("time_step_min", "time_step_max", "time_step_floor"))

    def layer(key, part):
        count = iter(range(1 << 20))
        nxt = lambda: jax.random.fold_in(key, next(count))

        def w(*shape):
            std = math.sqrt(2.0 / (shape[-2] + shape[-1]))
            return _normal(nxt(), shape, std, 0.0, dtype)

        gain = lambda n: _normal(nxt(), (n,), 0.02, 1.0, dtype)
        uni = lambda shape, lo, hi, floor=0.0, how="plain": _uniform(
            nxt(), shape, lo, hi, floor, how, dtype)
        if part == "moe":
            return {"n": gain(m.d), "moe": {
                "router_W": w(m.d, m.routed),
                "router_bias": _normal(nxt(), (m.routed,), 0.05, 0.0, dtype),
                "W_down": w(m.d, m.latent), "W_up": w(m.latent, m.d),
                "expert_W_u": w(held, m.latent, m.expert_ffn),
                "expert_W_d": w(held, m.expert_ffn, m.latent),
                "shared_W_u": w(m.d, m.shared_ffn),
                "shared_W_d": w(m.shared_ffn, m.d)}}
        if part == "attention":
            return {"n": gain(m.d), "mixer": {
                "W_q": w(m.d, m.heads * m.head),
                "W_k": w(m.d, m.kv_heads * m.head),
                "W_v": w(m.d, m.kv_heads * m.head),
                "W_o": w(m.heads * m.head, m.d)}}
        bound = m.conv ** -0.5
        return {"n": gain(m.d), "mixer": {
            "W_in": w(m.d, inner + conv_width + m.ssm_heads),
            "conv_W": uni((m.conv, conv_width), -bound, bound),
            "conv_b": uni((conv_width,), -bound, bound),
            "dt_bias": uni((m.ssm_heads,), *steps, how="step"),
            "A_log": _a_log(m.ssm_heads, dtype), "D": gain(m.ssm_heads),
            "norm": gain(inner), "W_out": w(inner, m.d)}}

    key = seed_key(seed)
    table = _normal(jax.random.fold_in(key, 1 << 20), (m.vocab, m.d), 0.02,
                    0.0, dtype)
    head = _normal(jax.random.fold_in(key, (1 << 20) + 1), (m.d, m.vocab),
                   math.sqrt(2.0 / (m.d + m.vocab)), 0.0, dtype)
    layers = tuple(layer(jax.random.fold_in(key, 1 + i), part)
                   for i, part in enumerate(m.parts))
    final = _normal(jax.random.fold_in(key, 0), (m.d,), 0.02, 1.0, dtype)
    return ({"W": table},) + layers + ({"g": final}, {"W": head})


# ---------------------------------------------------------------------------
# forward: one layer a compiled call
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


def _passes(precision):
    """Operands rounded to bfloat16 are exact in one pass of the MXU (the
    default precision), which sums in float32: the same value as the
    highest precision's passes, for a sixth of the work."""
    return jax.lax.Precision.DEFAULT if precision == "bfloat16" else None


def _mm(x, w, precision):
    """x @ w, w a weight (its values are bfloat16 ones). In float32, x is
    split into three bfloat16 parts that add up to it: the products of each
    with w are exact in one pass, and their float32 sums are the true value,
    in half the passes of the highest precision."""
    if precision != "float32":
        return jnp.matmul(_round(x, precision), _round(w, precision),
                          precision=_passes(precision))
    w, out = _round(w, "bfloat16"), 0.0
    for _ in range(3):
        part = _round(x, "bfloat16")
        out = out + jnp.matmul(part, w, precision=_passes("bfloat16"))
        x = x - part
    return out


def _einsum(spec, x, y, precision):
    return jnp.einsum(spec, _round(x, precision), _round(y, precision),
                      precision=_passes(precision))


f32 = lambda a: a.astype(jnp.float32)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * f32(g)


_jit = functools.partial(jax.jit, static_argnames=("m", "precision"))


@_jit
def _mamba(p, x, *, m, precision):
    """x + Mamba-2(N x) over the whole sequence x [T, d], token by token."""
    with jax.default_matmul_precision("highest"):
        t, q = x.shape[0], p["mixer"]
        inner, gn = m.ssm_heads * m.ssm_head, m.groups * m.ssm_state
        zxd = _mm(_norm(x, p["n"], m.eps), q["W_in"], precision)
        z, xbc = zxd[:, :inner], zxd[:, inner:2 * inner + 2 * gn]
        dt = jax.nn.softplus(zxd[:, 2 * inner + 2 * gn:]
                             + f32(q["dt_bias"]))                   # [T, H]
        # the convolution reaches K-1 tokens back; zeros before the start
        back = jnp.pad(xbc, ((m.conv - 1, 0), (0, 0)))
        xbc = jax.nn.silu(f32(q["conv_b"]) + sum(
            f32(q["conv_W"])[k] * back[k:k + t] for k in range(m.conv)))
        xs = xbc[:, :inner].reshape(t, m.ssm_heads, m.ssm_head)
        # each head's B and C: its group's
        per_head = lambda z: jnp.repeat(
            z.reshape(t, m.groups, m.ssm_state), m.ssm_heads // m.groups,
            axis=1)                                             # [T, H, N]
        b_t = per_head(xbc[:, inner:inner + gn])
        c_t = per_head(xbc[:, inner + gn:])
        a = -jnp.exp(f32(q["A_log"]))

        def token(s, now):
            x_t, dt_t, b, c = now
            s = jnp.exp(dt_t * a)[:, None, None] * s \
                + (dt_t[:, None] * x_t)[:, :, None] * b[:, None, :]
            return s, jnp.sum(s * c[:, None, :], axis=-1)

        _, y = jax.lax.scan(
            token, jnp.zeros((m.ssm_heads, m.ssm_head, m.ssm_state),
                             jnp.float32),
            (xs, dt, b_t, c_t), unroll=8)
        y = (y + f32(q["D"])[:, None] * xs).reshape(t, inner) * jax.nn.silu(z)
        y = _norm(y.reshape(t, m.groups, -1), jnp.ones(()), m.eps)
        return x + _mm(y.reshape(t, inner) * f32(q["norm"]), q["W_out"],
                       precision)


@_jit
def _attention(p, x, *, m, precision):
    """x + grouped-query attention of N x over the whole causal sequence,
    one key/value head at a time."""
    with jax.default_matmul_precision("highest"):
        t, q = x.shape[0], p["mixer"]
        u = _norm(x, p["n"], m.eps)
        group = m.heads // m.kv_heads
        qs = _mm(u, q["W_q"], precision).reshape(t, m.kv_heads, group, m.head)
        ks = _mm(u, q["W_k"], precision).reshape(t, m.kv_heads, m.head)
        vs = _mm(u, q["W_v"], precision).reshape(t, m.kv_heads, m.head)
        causal = jnp.tril(jnp.ones((t, t), bool))

        def kv_head(qkv):           # a key/value head and its queries
            qh, kh, vh = qkv                    # [T, group, Dh], [T, Dh] x 2
            s = _einsum("tgd,sd->gts", qh, kh, precision) / math.sqrt(m.head)
            w = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
            return _einsum("gts,sd->tgd", w, vh, precision)

        out = jax.lax.map(kv_head, (qs.transpose(1, 0, 2, 3),
                                    ks.transpose(1, 0, 2), vs.transpose(1, 0, 2)))
        out = out.transpose(1, 0, 2, 3).reshape(t, m.heads * m.head)
        return x + _mm(out, q["W_o"], precision)


def _relu2(x, w_u, w_d, precision):
    return _mm(jnp.square(jax.nn.relu(_mm(x, w_u, precision))), w_d,
               precision)


@_jit
def _moe(p, x, *, m, precision):
    """x + the held experts' part in the latent, every one of them over
    every token under its weights, and the shared expert."""
    with jax.default_matmul_precision("highest"):
        q = p["moe"]
        u = _norm(x, p["n"], m.eps)
        s = jax.nn.sigmoid(u @ f32(q["router_W"]))
        _, ids = jax.lax.top_k(s + f32(q["router_bias"]), m.top_k)
        picked = jnp.take_along_axis(s, ids, axis=-1)
        rows = jnp.arange(u.shape[0])[:, None]
        gate = jnp.zeros((u.shape[0], m.routed), jnp.float32).at[rows, ids].set(
            m.scaling * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20))
        lat = _mm(u, q["W_down"], precision)

        def expert(total, e):
            w_u, w_d, w_e = e
            return total + w_e[:, None] * _relu2(lat, w_u, w_d, precision), None

        total, _ = jax.lax.scan(
            expert, jnp.zeros_like(lat),
            (q["expert_W_u"], q["expert_W_d"],
             gate[:, m.held_lo:m.held_hi].T))
        return x + _mm(total, q["W_up"], precision) \
            + _relu2(u, q["shared_W_u"], q["shared_W_d"], precision)


PART_FN = {"mamba": _mamba, "attention": _attention, "moe": _moe}


def layer(p, x, part: str, m: Dims, precision: str = "float32"):
    """One layer over x [T, d] float32."""
    return PART_FN[part](p, x, m=m, precision=precision)


HEAD_ROWS = 512         # positions a call of the head: one compiled shape


@_jit
def _head(final, w, h, start, *, m, precision):
    """The logits at positions start .. start + HEAD_ROWS - 1 of h [T, d]
    (the last position repeated past the end)."""
    idx = jnp.minimum(start + jnp.arange(HEAD_ROWS), m.positions - 1)
    with jax.default_matmul_precision("highest"):
        return _mm(_norm(h[idx], final["g"], m.eps), w, precision)


def hidden(params, tokens, m: Dims, precision: str = "float32"):
    """tokens int32 [T] -> the last layer's output [T, d]."""
    x = f32(params[0]["W"][tokens])
    for p, part in zip(params[1:-2], m.parts):
        x = layer(p, x, part, m, precision)
    return x


def served_logits(config: dict, params, sequence, first: int, count: int,
                  precision: str = None):
    """One full causal forward over `sequence` (prompt then served tokens),
    padded to the served context; returns the logits [count, V], a host
    array, at positions first-1 .. first+count-2: those that chose
    sequence[first:first+count]."""
    precision = precision or "float32"
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, got "
                         f"{precision!r}")
    m = dims(config)
    if len(sequence) > m.positions:
        raise ValueError("sequence longer than the served context")
    tok = np.zeros(m.positions, np.int32)
    tok[:len(sequence)] = sequence
    h = hidden(params, jnp.asarray(tok), m, precision)
    return np.concatenate([
        np.asarray(_head(params[-2], params[-1]["W"], h, first - 1 + s, m=m,
                         precision=precision))
        for s in range(0, count, HEAD_ROWS)])[:count]
