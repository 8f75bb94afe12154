"""Plain reference for Phi-4-mini-flash-reasoning (`phi4flash`, the SambaY
decoder-hybrid-decoder: Ren et al. 2025, arXiv:2507.06607).

Notation: d hidden_size, e = mamba_expand * d, N mamba_d_state, R the dt rank,
K mamba_d_conv, Dh = d / num_attention_heads; H = num_attention_heads / 2
differential heads, Hkv = num_key_value_heads / 2 key/value heads. LN is
LayerNorm with gain and bias, eps layer_norm_eps.

    x0 = E[tokens]                                   no positional encoding
    every layer l:  h = x + Mix_l(LN1(x));  x' = h + W_2(silu(W_g u) * W_u u),
                    u = LN2(h),  W_1 = [W_g | W_u] (2 x intermediate_size)
    logits = LN_f(x) E^T                             the head is the table

No projection has a bias. The mixers, by layer (mb_per_layer 2: a Mamba layer
every second one; the first half of the layers is the self-decoder, the
second the cross-decoder):

    Mamba, layers 0, 2, ..., L/2 (L/2 = 16 hands its g on as the memory m):
      [c0 | z] = W_in u                                  d -> 2e
      c = silu(causal depthwise conv_K(c0) + b)
      [delta | B | C] = W_x c                            e -> R + N + N
      dt = softplus(W_dt delta + b_dt)  [e];   A = -exp(A_log)  [e, N]
      H_t = exp(dt_t (x) A) * H_{t-1} + (dt_t * c_t) (x) B_t      [e, N]
      g_t = (H_t C_t + D * c_t) * silu(z_t);   out = W_out g_t
    Differential attention (Ye et al. 2024, arXiv:2410.05258), layers 1, 3,
    ..., L/2 - 1 over a window of W = sliding_window keys (t - s < W, the
    query's own among them), layer L/2 + 1 full, and the cross layers
    L/2 + 3, L/2 + 5, ..., L - 1, which have only W_q and W_o and read layer
    L/2 + 1's keys and values:
      W_q u -> H heads, each (q1, q2) of Dh;  W_k u -> Hkv heads, each
      (k1, k2) of Dh;  W_v u -> Hkv values of 2 Dh;  head i reads key/value
      head floor(i / (H / Hkv))
      A^s = softmax(q^s k^s^T / sqrt(Dh) + causal mask),  s = 1, 2
      o_i = RMSNorm_2Dh((A^1 - lambda A^2) v) * (1 - lambda_init)
      out = W_o [o_0 ... o_H-1]
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
      lambda_init = 0.8 - 0.6 exp(-0.3 l)
    GMU (gated memory unit), layers L/2 + 2, L/2 + 4, ..., L - 2:
      out = W_out(silu(W_in u) * m),  W_in d -> e

Everything here is `jax.numpy`: the recurrence a sequential `lax.scan` over
the tokens (no chunks), every attention over the whole masked sequence with
no cache, one key/value head at a time (`lax.map`, so that the scores of a
4,096-token sequence fit), every layer over every token (no skip), no
batching, no kernels. It imports nothing of the program. The weights are
made here, from the seed, in bfloat16 as the configuration states, leaf by
leaf; the benchmark hands the same arrays to the program. The program cannot
tie its head to its table: `init_params` hands it a second copy of the
table's values, transposed, and this file multiplies by that copy too (the
same numbers). The forward is float32 and runs one layer a compiled call, so
that only that layer's weights are ever upcast beside the bfloat16 set.

`precision` says in what arithmetic the matrix products are made (the
recurrence, the convolution, the norms and the softmaxes are float32 in all):
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
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "float8")


class Dims(NamedTuple):
    d: int
    e: int
    n: int
    r: int
    k: int
    chunk: int          # the program's; the recurrence here has none
    heads: int          # differential heads
    kv_heads: int
    head: int           # Dh
    window: int
    mlp: int
    eps: float
    layers: int
    vocab: int
    positions: int


def dims(config: dict) -> Dims:
    d, layers = int(config["hidden_size"]), int(config["num_hidden_layers"])
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    if heads % 2 or kv % 2 or heads % kv:
        raise ValueError("differential attention pairs the heads: "
                         "num_attention_heads and num_key_value_heads even, "
                         "one a multiple of the other")
    if int(config["mb_per_layer"]) != 2 or layers % 4:
        raise ValueError("a Mamba layer every second one (mb_per_layer 2) "
                         "and halves of an even number of layers are what "
                         "is written here")
    if config.get("mlp_bias") or config.get("lm_head_bias"):
        raise ValueError("no biases is what is written here")
    return Dims(
        d=d, e=int(config["mamba_expand"]) * d, n=int(config["mamba_d_state"]),
        r=int(config["mamba_dt_rank"]), k=int(config["mamba_d_conv"]),
        chunk=int(config["mamba_chunk_size"]), heads=heads // 2,
        kv_heads=kv // 2, head=d // heads,
        window=int(config["sliding_window"]),
        mlp=int(config["intermediate_size"]),
        eps=float(config["layer_norm_eps"]), layers=layers,
        vocab=int(config["vocab_size"]),
        positions=int(config["max_position_embeddings"]))


def kinds(m: Dims):
    """Each layer's mixer: the self-decoder's, then the cross-decoder's."""
    half = m.layers // 2
    return (("mamba", "window") * (half // 2) + ("mamba", "full")
            + ("gmu", "cross") * (half // 2 - 1))


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
    """Uniform in [lo, hi) ("plain"), or the inverse softplus of a step
    drawn log-uniform in [lo, hi) ("step": b_dt)."""
    if how == "step":
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                          math.log(lo), math.log(hi)))
        out = step + jnp.log(-jnp.expm1(-step))
    else:
        out = jax.random.uniform(key, shape, jnp.float32, lo, hi)
    return out.astype(jnp.bfloat16).astype(dtype)


@functools.partial(jax.jit, static_argnames=("e", "n", "dtype"))
def _a_log(e, n, dtype):
    """A = 1 .. N for every channel (Mamba's initialiser)."""
    return jnp.log(jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32),
                                    (e, n))).astype(jnp.bfloat16).astype(dtype)


@jax.jit
def _transposed(w):
    return w.T


def init_params(config: dict, seed: int):
    """Tuple of per-layer dicts: token table {W}, the self-decoder's layers,
    the cross-decoder {layers: (...)}, final norm {g, b}, head {W} (the
    table's values, transposed). A layer is {ln1_g, ln1_b, mix, ln2_g, ln2_b,
    W_1, W_2}. Every value is a bfloat16, held as `precision.weights` says
    (bfloat16; a test on a backend without bfloat16 products says float32).

    Matrices Xavier-normal; norm gains, D and the heads' norm 1 + 0.02,
    norm biases 0.02 normal (nothing is exactly 0 or 1, so that a term left
    out shows); A = 1..N, softplus(b_dt) log-uniform in 0.001-0.1, the
    convolution's taps and bias uniform within 1/sqrt(K) (Mamba's
    initialiser, so that a seeded model's decays are a trained model's);
    the four lambda vectors normal(0, 0.1). The token table has a standard
    deviation of 0.15 / sqrt(d): with the head tied to it, a larger table
    makes every position predict its own input token."""
    m = dims(config)
    dtype = jnp.dtype(config["precision"]["weights"])

    def layer(key, kind):
        count = iter(range(1 << 20))
        nxt = lambda: jax.random.fold_in(key, next(count))

        def w(*shape):
            std = math.sqrt(2.0 / (shape[-2] + shape[-1]))
            return _normal(nxt(), shape, std, 0.0, dtype)

        gain = lambda n: _normal(nxt(), (n,), 0.02, 1.0, dtype)
        bias = lambda n: _normal(nxt(), (n,), 0.02, 0.0, dtype)
        uni = lambda shape, lo, hi, how="plain": _uniform(nxt(), shape, lo, hi,
                                                          how, dtype)
        if kind == "mamba":
            bound = m.k ** -0.5
            mix = {"W_in": w(m.d, 2 * m.e),
                   "conv_W": uni((m.k, m.e), -bound, bound),
                   "conv_b": uni((m.e,), -bound, bound),
                   "W_x": w(m.e, m.r + 2 * m.n), "W_dt": w(m.r, m.e),
                   "dt_b": uni((m.e,), 1e-3, 1e-1, "step"),
                   "A_log": _a_log(m.e, m.n, dtype), "D": gain(m.e),
                   "W_out": w(m.e, m.d)}
        elif kind == "gmu":
            mix = {"W_in": w(m.d, m.e), "W_out": w(m.e, m.d)}
        else:
            lam = lambda: _normal(nxt(), (m.head,), 0.1, 0.0, dtype)
            mix = {"W_q": w(m.d, m.heads * 2 * m.head),
                   "W_o": w(m.heads * 2 * m.head, m.d),
                   "lq1": lam(), "lk1": lam(), "lq2": lam(), "lk2": lam(),
                   "subln": gain(2 * m.head)}
            if kind != "cross":
                mix.update(W_k=w(m.d, m.kv_heads * 2 * m.head),
                           W_v=w(m.d, m.kv_heads * 2 * m.head))
        return {"ln1_g": gain(m.d), "ln1_b": bias(m.d), "mix": mix,
                "ln2_g": gain(m.d), "ln2_b": bias(m.d),
                "W_1": w(m.d, 2 * m.mlp), "W_2": w(m.mlp, m.d)}

    key = seed_key(seed)
    made = [layer(jax.random.fold_in(key, 1 + i), kind)
            for i, kind in enumerate(kinds(m))]
    final = jax.random.fold_in(key, 0)
    table = _normal(jax.random.fold_in(key, 1 << 20), (m.vocab, m.d),
                    0.15 / math.sqrt(m.d), 0.0, dtype)
    half = m.layers // 2
    return (({"W": table},) + tuple(made[:half])
            + ({"layers": tuple(made[half:])},)
            + ({"g": _normal(jax.random.fold_in(final, 0), (m.d,), 0.02, 1.0,
                             dtype),
                "b": _normal(jax.random.fold_in(final, 1), (m.d,), 0.02, 0.0,
                             dtype)},
               {"W": _transposed(table)}))


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


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * f32(g) + f32(b)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * f32(g)


def _mlp(p, h, m, precision):
    """h + the MLP of LN2(h)."""
    hid = _mm(_ln(h, p["ln2_g"], p["ln2_b"], m.eps), p["W_1"], precision)
    return h + _mm(jax.nn.silu(hid[:, :m.mlp]) * hid[:, m.mlp:], p["W_2"],
                   precision)


_jit = functools.partial(jax.jit, static_argnames=("m", "precision"))


@_jit
def _mamba(p, x, *, m, precision):
    """A Mamba layer over the whole sequence x [T, d], token by token:
    (x', g [T, e])."""
    with jax.default_matmul_precision("highest"):
        t, q = x.shape[0], p["mix"]
        cz = _mm(_ln(x, p["ln1_g"], p["ln1_b"], m.eps), q["W_in"], precision)
        c0, z = cz[:, :m.e], cz[:, m.e:]
        back = jnp.pad(c0, ((m.k - 1, 0), (0, 0)))   # zeros before the start
        c = jax.nn.silu(f32(q["conv_b"]) + sum(
            f32(q["conv_W"])[j] * back[j:j + t] for j in range(m.k)))
        dbc = _mm(c, q["W_x"], precision)
        dt = jax.nn.softplus(_mm(dbc[:, :m.r], q["W_dt"], precision)
                             + f32(q["dt_b"]))
        bm, cm = dbc[:, m.r:m.r + m.n], dbc[:, m.r + m.n:]
        a = -jnp.exp(f32(q["A_log"])).T                            # [N, e]

        def token(h, now):
            dtc_t, dt_t, b_t, cm_t = now
            h = jnp.exp(dt_t * a) * h + b_t[:, None] * dtc_t
            return h, jnp.sum(h * cm_t[:, None], axis=0)

        # the state is held [N, e], e along the lanes (an [e, N] state pads
        # N = 16 to 128 lanes); a step is a few elementwise passes over it:
        # unrolled, so that 4,096 steps do not each pay a loop iteration's
        # fixed cost
        _, y = jax.lax.scan(token, jnp.zeros((m.n, m.e), jnp.float32),
                            (dt * c, dt, bm, cm), unroll=16)
        g = (y + f32(q["D"]) * c) * jax.nn.silu(z)
        return _mlp(p, x + _mm(g, q["W_out"], precision), m, precision), g


def _lambdas(q, layer):
    """(lambda, lambda_init) of layer `layer` (a traced scalar: one compile
    serves every layer of a kind)."""
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * layer)
    dot = lambda a, b: jnp.exp(jnp.sum(f32(q[a]) * f32(q[b])))
    return dot("lq1", "lk1") - dot("lq2", "lk2") + lam0, lam0


def _differential(q, x, k, v, lam, lam0, m, precision, window):
    """The attention of LN1'd x [T, d] over k, v [T, Hkv*2Dh] (causal, and
    within `window` where given), through W_o: one key/value head at a
    time."""
    t, group = x.shape[0], m.heads // m.kv_heads
    qs = _mm(x, q["W_q"], precision).reshape(t, m.kv_heads, group, 2, m.head)
    ks = k.reshape(t, m.kv_heads, 2, m.head)
    vs = v.reshape(t, m.kv_heads, 2 * m.head)
    at, key = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    ok = (key <= at) & ((window <= 0) | (at - key < window))

    def kv_head(qkv):
        qh, kh, vh = qkv               # [T, group, 2, Dh], [T, 2, Dh], [T, 2Dh]
        s = _einsum("tgmd,smd->gmts", qh, kh, precision) / math.sqrt(m.head)
        w = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        diff = w[:, 0] - lam * w[:, 1]                      # [group, T, T]
        return _einsum("gts,sd->tgd", diff, vh, precision)

    o = jax.lax.map(kv_head, (qs.transpose(1, 0, 2, 3, 4),
                              ks.transpose(1, 0, 2, 3), vs.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(t, m.heads, 2 * m.head)
    o = _rms(o, q["subln"], m.eps) * (1.0 - lam0)
    return _mm(o.reshape(t, -1), q["W_o"], precision)


@_jit
def _attention(p, x, layer, *, m, precision, window):
    """A window (`window` keys) or full (`window` 0) attention layer over x
    [T, d]: (x', its keys and values [T, Hkv*2Dh]). `window` is traced: one
    compile serves both."""
    with jax.default_matmul_precision("highest"):
        q = p["mix"]
        u = _ln(x, p["ln1_g"], p["ln1_b"], m.eps)
        k, v = _mm(u, q["W_k"], precision), _mm(u, q["W_v"], precision)
        h = x + _differential(q, u, k, v, *_lambdas(q, layer), m, precision,
                              window)
        return _mlp(p, h, m, precision), k, v


@_jit
def _cross(p, x, k, v, layer, *, m, precision):
    """A cross-attention layer: its queries over the full layer's k, v."""
    with jax.default_matmul_precision("highest"):
        q = p["mix"]
        u = _ln(x, p["ln1_g"], p["ln1_b"], m.eps)
        h = x + _differential(q, u, k, v, *_lambdas(q, layer), m, precision,
                              0)
        return _mlp(p, h, m, precision)


@_jit
def _gmu(p, x, g, *, m, precision):
    with jax.default_matmul_precision("highest"):
        q = p["mix"]
        u = _ln(x, p["ln1_g"], p["ln1_b"], m.eps)
        h = x + _mm(jax.nn.silu(_mm(u, q["W_in"], precision)) * g,
                    q["W_out"], precision)
        return _mlp(p, h, m, precision)


HEAD_ROWS = 512         # positions a call of the head: one compiled shape


@_jit
def _head(final, w, h, start, *, m, precision):
    """The logits at positions start .. start + HEAD_ROWS - 1 of h [T, d]
    (the last position repeated past the end)."""
    idx = jnp.minimum(start + jnp.arange(HEAD_ROWS), m.positions - 1)
    with jax.default_matmul_precision("highest"):
        return _mm(_ln(h[idx], final["g"], final["b"], m.eps), w, precision)


def hidden(params, tokens, m: Dims, precision: str = "float32"):
    """tokens int32 [T] -> the last layer's output [T, d]."""
    x = f32(params[0]["W"][tokens])
    layers = list(params[1:-3]) + list(params[-3]["layers"])
    kw = {"m": m, "precision": precision}
    g = k = v = None
    for l, (p, kind) in enumerate(zip(layers, kinds(m))):
        if kind == "mamba":
            x, g = _mamba(p, x, **kw)
        elif kind in ("window", "full"):
            x, k, v = _attention(p, x, jnp.float32(l), window=(
                m.window if kind == "window" else 0), **kw)
        elif kind == "gmu":
            x = _gmu(p, x, g, **kw)
        else:
            x = _cross(p, x, k, v, jnp.float32(l), **kw)
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
