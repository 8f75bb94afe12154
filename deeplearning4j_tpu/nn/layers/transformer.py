"""GPT-style transformer block + sequence embedding (ISSUE 14).

NEW capability relative to the reference (SURVEY.md has no attention at
all) and the scenario driver for the 2-D mesh parallelism path
(`parallel/sharding.py` 2-D specs, `parallel/zero.py` ZERO1×TP): the
block's parameters are NAMED for their Megatron-LM (Shoeybi et al.,
2019) tensor-parallel role, and `tp_shard_axis` (LayerConf hook) tells
the sharding rules which axis rides the ``model`` mesh axis:

  * column-parallel (shard the OUTPUT feature axis; activations come out
    head/feature-sharded, no collective): ``W_q/W_k/W_v`` + biases,
    ``W_ffn_in`` + bias;
  * row-parallel (shard the INPUT/contraction axis; XLA inserts ONE
    psum over ``model`` to combine the partial products): ``W_o``,
    ``W_ffn_out``; their biases replicated (added after the psum);
  * replicated: the LayerNorm scales/offsets.

With that layout the attention heads are sharded over ``model``
(`n_heads % model_size == 0` keeps the QKV reshape a local view), the
whole block runs on local shards, and exactly two model-axis psums per
block (attention out-proj, FFN out-proj) carry activations — the
Megatron communication recipe, expressed through GSPMD constraints
instead of hand-written collectives.

Attention itself reuses `kernels/attention.py`: the Pallas flash kernel
(full custom-VJP backward) with heads folded into its batch axis on TPU,
the einsum `attention_reference` elsewhere. Pallas custom calls cannot be
auto-partitioned by GSPMD, so inside a trainer-managed sharded step the
kernel rides `flash_attention_spmd` — the same kernel under `shard_map`
over (data, model); the Megatron head sharding makes each shard's local
[B/d, T, H/m, Dh] block a standalone attention problem (`flash="spmd"`,
set by `parallel/trainer.py:configure_flash_attention`). `flash="auto"`
picks kernel-vs-einsum for replicated/single-device runs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ..conf.base import LayerConf, register_layer
from ..conf.input_type import InputType

__all__ = ["TransformerBlock", "EmbeddingSequenceLayer"]


def _layer_norm(x, g, b, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


@register_layer
@dataclass
class TransformerBlock(LayerConf):
    """Pre-LN transformer block: x + MHA(LN(x)), then x + FFN(LN(x)).

    Input/output [B, T, n_model] (the package's RNN layout). Causal by
    default (GPT-style LM). `flash` selects the attention implementation:
    True = `kernels.attention.flash_attention_heads` (Pallas, heads
    folded into the batch axis), False =
    `kernels.attention.attention_reference` (einsum), "auto" = flash on the TPU backend, reference elsewhere, and
    "spmd" = `kernels.attention.flash_attention_spmd` — the kernel under
    `shard_map` over the (data, model) mesh recorded in `flash_spmd`
    (an instance attr `(mesh, data_axis, model_axis)` the trainer's
    capability probe sets alongside the mode). GSPMD has no partitioning
    rule for a Pallas custom call, so inside a trainer-managed sharded
    jit the kernel must either run per-shard via shard_map ("spmd" —
    the Megatron head sharding makes each local block a standalone
    attention problem, zero collectives) or give way to the einsum path
    (False); `parallel/trainer.py:configure_flash_attention` picks per
    backend/mesh. "auto" is for standalone/single-device models.
    """

    input_kind = "rnn"

    n_model: int = 0            # embedding width (0 = take from input type)
    n_heads: int = 4
    ffn_mult: int = 4           # FFN hidden = ffn_mult * n_model
    causal: bool = True
    flash = "auto"              # class attr: not part of the config JSON
    flash_spmd = None           # (mesh, data_axis, model_axis) for "spmd"

    # Megatron tensor-parallel roles (see parallel/sharding.py):
    # axis index to shard over ``model``, or "replicated"
    _TP_ROLES = {
        "W_q": -1, "W_k": -1, "W_v": -1,        # column parallel
        "b_q": 0, "b_k": 0, "b_v": 0,
        "W_ffn_in": -1, "b_ffn_in": 0,
        "W_o": 0, "W_ffn_out": 0,               # row parallel
        "b_o": "replicated", "b_ffn_out": "replicated",
        "ln1_g": "replicated", "ln1_b": "replicated",
        "ln2_g": "replicated", "ln2_b": "replicated",
    }

    def _width(self, it: Optional[InputType] = None) -> int:
        if self.n_model:
            return self.n_model
        if it is None:
            raise ValueError("TransformerBlock needs n_model or an input type")
        return it.size

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self._width(it), it.timesteps)

    @property
    def has_params(self) -> bool:
        return True

    def tp_shard_axis(self, key: str, shape):
        return self._TP_ROLES.get(key)

    def tp_validate(self, model_size: int):
        """Up-front 2-D-mesh check (called by `sharding.param_specs`):
        the QKV reshape [.., F] -> [.., H, Dh] stays a LOCAL view only
        when the model axis divides the head count — otherwise shard
        boundaries cut across heads and GSPMD inserts resharding
        collectives inside attention, silently breaking the
        two-psums-per-block contract the IR budgets verify."""
        if model_size > 1 and self.n_heads % model_size:
            raise ValueError(
                f"TransformerBlock(n_heads={self.n_heads}) cannot shard "
                f"over a model axis of size {model_size}: heads must "
                "split evenly across the axis (n_heads % model_size == "
                "0). Use a head count divisible by the model-axis size, "
                "or a smaller model axis")

    def init_params(self, rng, it: InputType):
        d = self._width(it)
        if d % self.n_heads:
            raise ValueError(
                f"n_model={d} not divisible by n_heads={self.n_heads}")
        h = self.ffn_mult * d
        ks = jax.random.split(rng, 6)
        # four DISTINCT arrays: donated buffers must not alias across leaves
        one = lambda: jnp.ones((d,), jnp.float32)
        zero = lambda: jnp.zeros((d,), jnp.float32)
        return {
            "W_q": self._winit(ks[0], (d, d), d, d),
            "W_k": self._winit(ks[1], (d, d), d, d),
            "W_v": self._winit(ks[2], (d, d), d, d),
            "b_q": self._binit((d,)), "b_k": self._binit((d,)),
            "b_v": self._binit((d,)),
            "W_o": self._winit(ks[3], (d, d), d, d),
            "b_o": self._binit((d,)),
            "W_ffn_in": self._winit(ks[4], (d, h), d, h),
            "b_ffn_in": self._binit((h,)),
            "W_ffn_out": self._winit(ks[5], (h, d), h, d),
            "b_ffn_out": self._binit((d,)),
            "ln1_g": one(), "ln1_b": zero(), "ln2_g": one(), "ln2_b": zero(),
        }

    # -- attention core ----------------------------------------------------
    def _use_flash(self) -> bool:
        flash = self.flash
        if flash == "auto":
            return jax.default_backend() == "tpu"
        return bool(flash)

    def _attend(self, q, k, v, mask):
        """q/k/v [B, T, H, Dh] -> [B, T, H, Dh]. On the einsum paths the
        head axis stays an explicit axis (no batch-merge reshape) so a
        ``model``-axis sharding on H partitions the whole attention
        locally; the kernel paths run on unsharded or shard-local blocks
        and fold heads into the batch."""
        from ...kernels.attention import (attention_reference,
                                          flash_attention_heads)

        if mask is not None:
            # padded timesteps (time_buckets): keys at masked positions
            # must not receive attention weight — inline masked einsum
            # (the kernels take no mask; masked QUERY rows produce
            # garbage that the masked loss already ignores)
            scale = 1.0 / (q.shape[-1] ** 0.5)
            logits = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                                k.astype(jnp.float32)) * scale
            neg = jnp.float32(-1e30)
            if self.causal:
                t = jnp.arange(q.shape[1])
                logits = jnp.where(t[None, None, :, None]
                                   >= t[None, None, None, :], logits, neg)
            logits = jnp.where(
                mask.astype(bool)[:, None, None, :], logits, neg)
            w = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bhts,bshd->bthd", w, v.astype(jnp.float32))
            return out.astype(q.dtype)
        if self.flash == "spmd":
            # trainer-managed sharded jit: run the kernel per-shard via
            # shard_map (configure_flash_attention set flash_spmd)
            from ...kernels.attention import flash_attention_spmd

            mesh, data_axis, model_axis = self.flash_spmd
            return flash_attention_spmd(
                q, k, v, self.causal, mesh=mesh,
                data_axis=data_axis, model_axis=model_axis)
        if self._use_flash():
            return flash_attention_heads(q, k, v, self.causal)
        # [B, T, H, Dh]: map the oracle ([B, T, D] contract) over heads
        return jax.vmap(attention_reference, in_axes=(2, 2, 2, None),
                        out_axes=2)(q, k, v, self.causal)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        b, t, d = x.shape
        hd = d // self.n_heads

        h1 = _layer_norm(x, params["ln1_g"], params["ln1_b"])
        split = lambda z: z.reshape(b, t, self.n_heads, hd)
        q = split(h1 @ params["W_q"] + params["b_q"])
        k = split(h1 @ params["W_k"] + params["b_k"])
        v = split(h1 @ params["W_v"] + params["b_v"])
        a = self._attend(q, k, v, mask).reshape(b, t, d)
        x = x + a @ params["W_o"] + params["b_o"]

        h2 = _layer_norm(x, params["ln2_g"], params["ln2_b"])
        f = self._act(h2 @ params["W_ffn_in"] + params["b_ffn_in"])
        x = x + f @ params["W_ffn_out"] + params["b_ffn_out"]
        return x, state

    # -- decode mode (KV-cache generation, serving/decode) -----------------
    # The decode plane takes its layers by contract (`serving/decode/
    # engine.py` states it): `decode_cache` says what the block writes for
    # a token, `decode_attention` which attention path a phase takes over
    # a given cache, and `decode_prefill_step` / `decode_tick_step` build
    # the traced steps. The steps are made of three pieces, so that the
    # PAGED cache scatter/gather can happen between them (the layer owns
    # the math, the engine's `CacheIO` the arena and the block tables):
    #   q, k, v = blk.decode_qkv(p, x)        # LN1 + projections
    #   <scatter k/v into the arena, gather the cache view>
    #   a = blk.decode_attend(q, k_all, v_all, positions, lengths)
    #     (a tick on the TPU: kernels.paged_attention reads the arena's
    #      pages through the block table instead of the two lines above)
    #   y = blk.decode_finish(p, x, a)        # out-proj + FFN residuals
    # Chaining the three over a full causal prompt (k_all = k, v_all = v,
    # positions = arange) is mathematically `apply` — the prefill+decode
    # equivalence suite asserts it against the full-sequence forward.
    def decode_cache(self, width: int):
        """(channels, width) written for a token: its keys and its values,
        heads merged (`serving/decode/cache.py` says why merged)."""
        return 2, self.n_model or int(width)

    def decode_state(self, width: int):
        """No per-sequence state beside the pages."""
        return None

    def decode_attention(self, phase: str, spec):
        """How `phase` attends over a cache of `spec`, from what the code
        can see. A prefill attends over its local K/V (None: nothing to
        choose). A tick: "paged_kernel" where the backend is the TPU
        (`pallas_supported`: and the kernels are not switched off) and the
        arena is float32 in pages of whole (8, 128) tiles, else "gather"
        (the view through the tables and `decode_attend`)."""
        from ...kernels import pallas_supported
        from ...kernels.paged_attention import paged_attention_supported

        if phase != "tick":
            return None
        if (pallas_supported() and spec.kv_dtype == "fp32"
                and paged_attention_supported(spec.width, spec.block_len)):
            return "paged_kernel"
        return "gather"

    def decode_prefill_step(self, io, attention=None):
        """The prefill step over the whole right-padded prompt. The prompt
        attends over the LOCAL (exact) projections, so an int8 cache only
        affects later ticks."""
        def step(p, x, kv, sc, channel, blk, off, pos, lengths):
            q, k, v = self.decode_qkv(p, x)
            kv, sc = io.scatter(kv, sc, k, blk, off, channel)
            kv, sc = io.scatter(kv, sc, v, blk, off, channel + 1)
            a = self.decode_attend(q, k, v, pos, lengths)
            return self.decode_finish(p, x, a), kv, sc, None
        return step

    def decode_tick_step(self, io, attention: str):
        """The one-token tick; `attention` as `decode_attention` names it
        ("paged_kernel" is always the COMPILED kernel)."""
        from ...kernels import paged_attention as paged

        if attention not in ("paged_kernel", "gather"):
            raise ValueError(f"attention must be paged_kernel|gather, got "
                             f"{attention!r}")

        def view(kv, sc, tables, channel):
            return io.gather(kv, sc, tables, channel).reshape(
                tables.shape[0], -1, self.n_heads,
                io.spec.width // self.n_heads)

        def step(p, x, kv, sc, channel, blk, off, tables, positions, lengths):
            q, k, v = self.decode_qkv(p, x)
            kv, sc = io.scatter(kv, sc, k[:, 0], blk, off, channel)
            kv, sc = io.scatter(kv, sc, v[:, 0], blk, off, channel + 1)
            if attention == "paged_kernel":
                a = paged.paged_decode_attention(
                    q.reshape(q.shape[0], -1), kv, channel, tables, lengths,
                    n_heads=self.n_heads, interpret=False)
            else:
                k_all = view(kv, sc, tables, channel)
                v_all = view(kv, sc, tables, channel + 1)
                a = self.decode_attend(q, k_all, v_all, positions[:, None],
                                       lengths)
            return self.decode_finish(p, x, a), kv, sc, None
        return step

    def decode_qkv(self, params, x):
        """LN1 + QKV projection: x [B, T, D] -> q/k/v each [B, T, H, Dh]."""
        b, t, d = x.shape
        hd = d // self.n_heads
        h1 = _layer_norm(x, params["ln1_g"], params["ln1_b"])
        split = lambda z: z.reshape(b, t, self.n_heads, hd)
        return (split(h1 @ params["W_q"] + params["b_q"]),
                split(h1 @ params["W_k"] + params["b_k"]),
                split(h1 @ params["W_v"] + params["b_v"]))

    def decode_attend(self, q, k_all, v_all, positions, lengths):
        """Attention over a cached-key view: q [B, Tn, H, Dh] (the Tn
        newest tokens, absolute key indices `positions` [B, Tn]),
        k_all/v_all [B, S, H, Dh] the full cache view (new keys already
        merged in), `lengths` [B] valid cache slots per row. Causal
        offsets + per-row valid length ride the extended
        `attention_reference` mask."""
        from ...kernels.attention import attention_reference

        fn = lambda qh, kh, vh: attention_reference(
            qh, kh, vh, self.causal, q_positions=positions,
            kv_length=lengths)
        return jax.vmap(fn, in_axes=(2, 2, 2), out_axes=2)(q, k_all, v_all)

    def decode_finish(self, params, x, attn):
        """Post-attention half: out-projection residual, then the FFN
        residual. attn [B, Tn, H, Dh] -> [B, Tn, D]."""
        b, t, d = x.shape
        x = x + attn.reshape(b, t, d) @ params["W_o"] + params["b_o"]
        h2 = _layer_norm(x, params["ln2_g"], params["ln2_b"])
        f = self._act(h2 @ params["W_ffn_in"] + params["b_ffn_in"])
        return x + f @ params["W_ffn_out"] + params["b_ffn_out"]

    def __post_init__(self):
        # FFN nonlinearity defaults to gelu (GPT convention), not the
        # base "identity"
        if self.activation is None:
            self.activation = "gelu"


@register_layer
@dataclass
class EmbeddingSequenceLayer(LayerConf):
    """Token + learned-position embedding for sequences: int indices
    [B, T] (or [B, T, 1]) -> [B, T, n_out]. The DL4J analog is
    `EmbeddingSequenceLayer.java`; here the table is additionally a 2-D
    mesh citizen — `tp_shard_axis` declares the VOCAB axis sharded over
    ``model`` (Megatron's embedding split: the gather touches only the
    local vocab shard, XLA combines with one psum over ``model``)."""

    input_kind = "rnn"

    n_in: int = 0               # vocab size
    n_out: int = 0
    max_timesteps: Optional[int] = None   # positional table length
                                          # (default: input type timesteps)
    positional: bool = True     # False: the token table alone (a stack
                                # whose blocks rotate their own positions,
                                # or know none); `max_timesteps` then
                                # STATES the context
    multiplier: float = 1.0     # the token vectors' scale (Granite's
                                # `embedding_multiplier`), applied in float32

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timesteps)

    @property
    def has_params(self) -> bool:
        return True

    def tp_shard_axis(self, key: str, shape):
        # vocab-sharded token table (Megatron's embedding split);
        # positional table column-parallel on the WIDTH axis — its rows
        # are statically sliced [:t], so sharding the feature axis keeps
        # the lookup local and its moments 1/(d·m) like the rest
        return 0 if key == "W" else -1

    def init_params(self, rng, it: InputType):
        if not self.n_in or not self.n_out:
            raise ValueError("EmbeddingSequenceLayer needs n_in (vocab) "
                             "and n_out (width)")
        tmax = self.max_timesteps or it.timesteps
        if tmax is None and self.positional:
            raise ValueError(
                "EmbeddingSequenceLayer needs max_timesteps (or an input "
                "type with a fixed timestep count) for the positional "
                "table")
        k1, k2 = jax.random.split(rng)
        if not self.positional:
            return {"W": self._winit(k1, (self.n_in, self.n_out),
                                     self.n_in, self.n_out)}
        return {"W": self._winit(k1, (self.n_in, self.n_out),
                                 self.n_in, self.n_out),
                "P": 0.02 * jax.random.normal(
                    k2, (int(tmax), self.n_out), jnp.float32)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        idx = x
        if idx.ndim == 3 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        idx = idx.astype(jnp.int32)
        z = self._scaled(jnp.take(params["W"], idx, axis=0))
        if not self.positional:
            return z, state
        t = z.shape[1]
        return z + params["P"][:t][None], state

    def _scaled(self, z):
        if self.multiplier == 1.0:
            return z
        return z.astype(jnp.float32) * self.multiplier

    def decode_context(self, params):
        """The positions a served sequence may hold: the positional
        table's rows, or what `max_timesteps` states where there is no
        table (None: the stack cannot be served)."""
        if self.positional:
            return int(params["P"].shape[0])
        return self.max_timesteps

    def decode_embed(self, params, idx, positions):
        """Decode-mode lookup: token + position embedding at ARBITRARY
        absolute positions (a decode step embeds one token at position
        `t`, not a [0..T) prefix slice). idx/positions [B, T] ->
        [B, T, n_out]. `positions` must stay below the positional table
        length — the table bounds the decode plane's context window."""
        z = self._scaled(jnp.take(params["W"], idx.astype(jnp.int32), axis=0))
        if not self.positional:
            return z
        return z + jnp.take(params["P"], positions.astype(jnp.int32),
                            axis=0)
