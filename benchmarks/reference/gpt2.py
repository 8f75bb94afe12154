"""Plain reference for the GPT-2 family as the program builds it.

    tokens -> W[tokens] + P[:T] -> (pre-LN block) x n_layer -> x @ W_head + b

A block is x + proj(attn(LN1 x)), then x + FFN(LN2 x), with the tanh GELU
(`gelu_new`), LayerNorm epsilon 1e-5, causal softmax attention over heads of
n_embd / n_head. Departures from Radford et al. 2019, shared with the program
because its builder has neither: no final LayerNorm, and an output head that is
not tied to the token table. The loss is the mean over all B*T positions of the
next-token cross-entropy; the update is Adam with bias correction folded into
the step size, as ND4J's AdamUpdater has it.

Everything here is `jax.numpy` in float32. It imports nothing of the program.
It makes the weights itself, from the seed, in one jitted call; the benchmark
hands the same weights to the program as an input.

`precision` says in what arithmetic the matrix products are made:
  "float32"   under `default_matmul_precision("highest")`: the true value
  "default"   at the backend's default precision, which is what a
              configuration states that says float32 and no more (on the TPU
              both operands are rounded to bfloat16 and the sum is float32; on
              the CPU it is "float32" again). Serving only.
  "float8"    the control of a bfloat16 configuration: both operands of every
              matrix product rounded to e4m3 with one scale a tensor
              (straight-through gradient), the rest float32 "highest"
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "default", "float8")


def dims(config: dict) -> tuple:
    """(d, layers, heads, vocab rows, positions, ffn) — hashable."""
    a = config["assumed"]
    return (int(config["n_embd"]), int(config["n_layer"]),
            int(config["n_head"]), int(a["padded_vocab_size"]),
            int(config["n_positions"]), int(a["n_inner"]))


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


# ---------------------------------------------------------------------------
# weights, from the seed, on the device, in one call
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("dm",))
def _init(key, dm):
    d, layers, _, vocab, positions, ffn = dm
    keys = iter(jax.random.split(key, 24))

    def normal(shape, std, mean=0.0):
        return mean + std * jax.random.normal(next(keys), shape, jnp.float32)

    def xavier(fan_in, fan_out):
        return math.sqrt(2.0 / (fan_in + fan_out))

    emb = {"W": normal((vocab, d), xavier(vocab, d)),
           "P": normal((positions, d), 0.02)}
    stack = {
        "W_q": normal((layers, d, d), xavier(d, d)),
        "W_k": normal((layers, d, d), xavier(d, d)),
        "W_v": normal((layers, d, d), xavier(d, d)),
        "W_o": normal((layers, d, d), xavier(d, d)),
        "W_ffn_in": normal((layers, d, ffn), xavier(d, ffn)),
        "W_ffn_out": normal((layers, ffn, d), xavier(ffn, d)),
        "b_q": normal((layers, d), 0.02), "b_k": normal((layers, d), 0.02),
        "b_v": normal((layers, d), 0.02), "b_o": normal((layers, d), 0.02),
        "b_ffn_in": normal((layers, ffn), 0.02),
        "b_ffn_out": normal((layers, d), 0.02),
        "ln1_g": normal((layers, d), 0.02, 1.0),
        "ln1_b": normal((layers, d), 0.02),
        "ln2_g": normal((layers, d), 0.02, 1.0),
        "ln2_b": normal((layers, d), 0.02),
    }
    blocks = tuple({k: v[i] for k, v in stack.items()} for i in range(layers))
    head = {"W": normal((d, vocab), xavier(d, vocab)),
            "b": normal((vocab,), 0.02)}
    return (emb,) + blocks + (head,)


def init_params(config: dict, seed: int):
    """Tuple of per-layer dicts: embedding {W, P}, blocks, head {W, b}.
    Matrices Xavier-normal, positions 0.02, biases and LayerNorm offsets 0.02,
    LayerNorm gains 1 + 0.02 (nothing is exactly 0 or 1, so that a bias or a
    gain left out shows)."""
    return _init(seed_key(seed), dims(config))


def leaf_names(params) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return [".".join(str(getattr(k, "idx", getattr(k, "key", k)))
                     for k in path) for path, _ in flat]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _round_e4m3(x):
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, precision):
    if precision == "float8":
        x, w = _round_e4m3(x), _round_e4m3(w)
    return x @ w


def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b


def _gelu_new(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def _block(p, x, n_head, precision):
    b, t, d = x.shape
    h = _layer_norm(x, p["ln1_g"], p["ln1_b"])
    split = lambda z: z.reshape(b, t, n_head, d // n_head).transpose(0, 2, 1, 3)
    q = split(_mm(h, p["W_q"], precision) + p["b_q"])
    k = split(_mm(h, p["W_k"], precision) + p["b_k"])
    v = split(_mm(h, p["W_v"], precision) + p["b_v"])
    if precision == "float8":
        q, k = _round_e4m3(q), _round_e4m3(k)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) / math.sqrt(d // n_head)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    if precision == "float8":
        w, v = _round_e4m3(w), _round_e4m3(v)
    a = jnp.einsum("bhts,bhsd->bhtd", w, v).transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + _mm(a, p["W_o"], precision) + p["b_o"]
    h = _layer_norm(x, p["ln2_g"], p["ln2_b"])
    f = _gelu_new(_mm(h, p["W_ffn_in"], precision) + p["b_ffn_in"])
    return x + _mm(f, p["W_ffn_out"], precision) + p["b_ffn_out"]


def hidden(params, tokens, n_head, precision="float32"):
    """tokens int32 [B, T] -> the last block's output [B, T, d]."""
    emb = params[0]
    x = emb["W"][tokens] + emb["P"][:tokens.shape[1]][None]
    for p in params[1:-1]:
        x = _block(p, x, n_head, precision)
    return x


def logits_of(params, x, precision="float32"):
    head = params[-1]
    return _mm(x, head["W"], precision) + head["b"]


def loss_sum(params, tokens, targets, n_head, precision="float32"):
    """Sum over the block's B*T positions of -log softmax(logits)[target]."""
    z = logits_of(params, hidden(params, tokens, n_head, precision), precision)
    logp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


# ---------------------------------------------------------------------------
# training: loss, gradients, Adam — in blocks of rows so that it fits
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_head", "precision"))
def _block_grads(params, tokens, targets, n_head, precision):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_sum)(params, tokens, targets, n_head,
                                            precision)


@jax.jit
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"),
                   donate_argnums=(0, 2, 3))
def _adam(params, grads, m, v, step, lr, b1, b2, eps):
    t = step.astype(jnp.float32) + 1.0
    tm = jax.tree_util.tree_map
    m = tm(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = tm(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    alpha = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    params = tm(lambda p, m_, v_: p - alpha * m_ / (jnp.sqrt(v_) + eps),
                params, m, v)
    return params, m, v


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in jax.tree_util.tree_leaves(tree)]


@jax.jit
def _diff_norms(a, b):
    return _norms(jax.tree_util.tree_map(jnp.subtract, a, b))


def leaf_norms(tree) -> list:
    """Euclidean norm of every leaf, as host floats, in leaf order."""
    return [float(x) for x in _norms(tree)]


def diff_norms(a, b) -> list:
    return [float(x) for x in _diff_norms(a, b)]


def train_readings(config: dict, seed: int, tokens, targets, *, steps: int,
                   adam: dict, block_rows: int, precision: str = "float32",
                   rows_used=None) -> dict:
    """Follow the first `steps` steps from the seed's weights over the ring
    `tokens`/`targets` (int [ring, batch, seq]; step s takes batch s % ring).
    Returns each step's loss, every leaf's gradient norm at step 1 and every
    leaf's change after the last step. `rows_used` < batch plants the fault
    "part of the batch left out, the mean taken over the rest"."""
    if precision not in ("float32", "float8"):
        raise ValueError(precision)
    n_head = int(config["n_head"])
    params = init_params(config, seed)
    tok = jnp.asarray(tokens, jnp.int32)
    tgt = jnp.asarray(targets, jnp.int32)
    ring, batch, seq = tok.shape
    rows = batch if rows_used is None else int(rows_used)
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    m, v = zeros(), zeros()
    losses, grad_norms = [], None
    for s in range(steps):
        total, grads = 0.0, None
        for r0 in range(0, rows, block_rows):
            sl = slice(r0, min(r0 + block_rows, rows))
            l, g = _block_grads(params, tok[s % ring, sl], tgt[s % ring, sl],
                                n_head, precision)
            total = total + l
            grads = g if grads is None else _add(grads, g)
        scale = 1.0 / (rows * seq)
        grads = jax.tree_util.tree_map(lambda a: a * scale, grads)
        losses.append(float(total) * scale)
        if s == 0:
            grad_norms = leaf_norms(grads)
        params, m, v = _adam(params, grads, m, v, jnp.asarray(s, jnp.int32),
                             float(adam["learning_rate"]), float(adam["beta1"]),
                             float(adam["beta2"]), float(adam["epsilon"]))
        del grads
    change = diff_norms(params, init_params(config, seed))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, "names": leaf_names(params)}


# ---------------------------------------------------------------------------
# serving: logits at the served positions of whole sequences
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_head", "precision"))
def _logits_at(params, tokens, positions, n_head, precision):
    with (contextlib.nullcontext() if precision == "default"
          else jax.default_matmul_precision("highest")):
        x = hidden(params, tokens[None], n_head, precision)[0]
        return logits_of(params, x[positions], precision)


def served_logits(config: dict, params, sequence, first: int, count: int,
                  precision: str = "float32"):
    """One full causal forward over `sequence` (prompt then served tokens),
    padded to the positional table; returns the logits [count, V] at positions
    first-1 .. first+count-2: those that chose sequence[first:first+count]."""
    positions = int(config["n_positions"])
    if len(sequence) > positions:
        raise ValueError("sequence longer than the positional table")
    tok = jnp.zeros((positions,), jnp.int32).at[:len(sequence)].set(
        jnp.asarray(sequence, jnp.int32))
    width = 128 * -(-count // 128)              # few compiled shapes
    idx = jnp.minimum(first - 1 + jnp.arange(width), positions - 1)
    return _logits_at(params, tok, idx, int(config["n_head"]),
                      precision)[:count]
