"""Transformer model family: BERT-style encoder and GPT-style decoder LM.

Reference analogue: BERT-Large is the reference's second headline benchmark
(SURVEY.md §6, BASELINE.md) — the reference treats it as an opaque torch
model whose gradients it synchronises; here the models are first-class flax
modules so the framework's benchmarks and examples are self-contained.

TPU-first choices: bfloat16 matmuls (MXU-native) with float32 layernorm /
softmax / logits, static shapes, and a pluggable attention implementation —
``attn_impl='full' | 'flash' | 'ring' | 'ulysses'`` — so the same module
runs single-chip or sequence-parallel under ``shard_map`` (ring attention /
all-to-all resharding from byteps_tpu.parallel, the long-context path).
``'full'`` means "the sequence is not sharded": exact attention by
``byteps_tpu.parallel.full_attention``, which on a TPU runs the Pallas
flash kernel for the shapes where that is the faster form (causal bf16
from s 512 up) and the XLA einsums elsewhere; ``'flash'`` forces the
kernel whatever the shape or backend (interpreted off the chip).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from byteps_tpu.jax._compat import axis_size as _axis_size

from byteps_tpu.parallel.ring_attention import full_attention, ring_attention
from byteps_tpu.parallel.ulysses import ulysses_attention


def _attention_fn(impl: str, sp_axis: Optional[str]) -> Callable:
    if impl not in ("full", "flash", "ring", "ulysses"):
        raise ValueError(
            f"attn_impl must be full|flash|ring|ulysses, got {impl!r}")
    if impl == "flash":
        from byteps_tpu.ops.flash_attention import flash_attention
        if sp_axis is None:
            return flash_attention
        # sequence-parallel + Pallas: Ulysses reshards to full sequences
        # per device, the flash kernel runs the inner attention
        return partial(ulysses_attention, axis=sp_axis,
                       attn_fn=flash_attention)
    if impl == "full":
        if sp_axis is not None:
            raise ValueError(
                "attn_impl='full' attends within each device's sequence "
                "block only — silently wrong under sequence parallelism; "
                "use 'ring', 'ulysses', or 'flash' with sp_axis")
        return full_attention
    if sp_axis is None:
        return full_attention
    if impl == "ring":
        return partial(ring_attention, axis=sp_axis)
    return partial(ulysses_attention, axis=sp_axis)


def _default_positions(s: int, sp_axis: Optional[str]):
    """Global position ids for the local block: under sequence parallelism
    each device holds sequence slice [idx*s, (idx+1)*s)."""
    pos = jnp.arange(s)[None, :]
    if sp_axis is not None:
        pos = pos + jax.lax.axis_index(sp_axis) * s
    return pos


class MultiHeadAttention(nn.Module):
    """Self-attention with a pluggable (possibly sequence-parallel) core."""

    num_heads: int
    dtype: jnp.dtype = jnp.bfloat16
    causal: bool = False
    attn_impl: str = "full"
    sp_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        head_dim = d_model // self.num_heads
        dense = partial(nn.DenseGeneral, dtype=self.dtype,
                        features=(self.num_heads, head_dim))
        q = dense(name="query")(x)
        k = dense(name="key")(x)
        v = dense(name="value")(x)
        attn = _attention_fn(self.attn_impl, self.sp_axis)
        out = attn(q, k, v, causal=self.causal)
        return nn.DenseGeneral(d_model, axis=(-2, -1), dtype=self.dtype,
                               name="out")(out)


class TransformerLayer(nn.Module):
    """Pre-LN transformer block (more stable than BERT's original post-LN
    at bf16; layernorms in f32)."""

    num_heads: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    causal: bool = False
    attn_impl: str = "full"
    sp_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        y = nn.LayerNorm(dtype=jnp.float32)(x)
        y = MultiHeadAttention(self.num_heads, self.dtype, self.causal,
                               self.attn_impl, self.sp_axis,
                               name="attention")(y)
        x = x + y.astype(x.dtype)
        y = nn.LayerNorm(dtype=jnp.float32)(x)
        y = nn.Dense(self.mlp_dim, dtype=self.dtype, name="mlp_in")(y)
        y = nn.gelu(y)
        y = nn.Dense(x.shape[-1], dtype=self.dtype, name="mlp_out")(y)
        return x + y.astype(x.dtype)


class TransformerEncoder(nn.Module):
    """BERT-style bidirectional encoder with an MLM head.

    ``__call__`` returns MLM logits [batch, seq, vocab] in float32.
    """

    vocab_size: int = 30522
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 512
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "full"
    sp_axis: Optional[str] = None

    @nn.compact
    def __call__(self, tokens, *, positions=None):
        b, s = tokens.shape
        if positions is None:
            positions = _default_positions(s, self.sp_axis)
        tok = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                       name="tok_embed")(tokens)
        pos = nn.Embed(self.max_len, self.d_model, dtype=self.dtype,
                       name="pos_embed")(positions)
        x = tok + pos
        for i in range(self.num_layers):
            x = TransformerLayer(self.num_heads, self.mlp_dim, self.dtype,
                                 causal=False, attn_impl=self.attn_impl,
                                 sp_axis=self.sp_axis, name=f"layer_{i}")(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="final_ln")(x)
        # MLM head: transform + tied-free decoder (f32 logits)
        x = nn.Dense(self.d_model, dtype=self.dtype, name="mlm_dense")(x)
        x = nn.gelu(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="mlm_ln")(x)
        return nn.Dense(self.vocab_size, dtype=jnp.float32,
                        name="mlm_out")(x)


class TransformerLM(nn.Module):
    """GPT-style causal decoder LM; returns next-token logits in f32."""

    vocab_size: int = 50257
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 1024
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "full"
    sp_axis: Optional[str] = None

    @nn.compact
    def __call__(self, tokens, *, positions=None):
        b, s = tokens.shape
        if positions is None:
            positions = _default_positions(s, self.sp_axis)
        embed = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         name="tok_embed")
        pos = nn.Embed(self.max_len, self.d_model, dtype=self.dtype,
                       name="pos_embed")(positions)
        x = embed(tokens) + pos
        for i in range(self.num_layers):
            x = TransformerLayer(self.num_heads, self.mlp_dim, self.dtype,
                                 causal=True, attn_impl=self.attn_impl,
                                 sp_axis=self.sp_axis, name=f"layer_{i}")(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="final_ln")(x)
        # weight-tied output projection
        logits = embed.attend(x.astype(self.dtype))
        return logits.astype(jnp.float32)


# Named configurations (BERT sizes per the original paper; the reference
# benchmarks BERT-Large, BASELINE.md config 2).
BertBase = partial(TransformerEncoder, num_layers=12, d_model=768,
                   num_heads=12, mlp_dim=3072)
BertLarge = partial(TransformerEncoder, num_layers=24, d_model=1024,
                    num_heads=16, mlp_dim=4096)
GPT2Small = partial(TransformerLM, num_layers=12, d_model=768,
                    num_heads=12, mlp_dim=3072)
# GPT-2 Medium (~345M): the reference's gradient-compression benchmark
# model (BASELINE.md config 3 pairs it with onebit/topk codecs).
GPT2Medium = partial(TransformerLM, num_layers=24, d_model=1024,
                     num_heads=16, mlp_dim=4096)


def masked_lm_loss(logits: jax.Array, labels: jax.Array,
                   mask: jax.Array) -> jax.Array:
    """Mean cross-entropy over positions where ``mask`` is 1 (MLM)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = mask.astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def lm_log_likelihood(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """[batch, seq - 1]: the log-probability ``logits`` at position t give
    token t + 1 (shifted; the last position predicts nothing)."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    tgt = tokens[:, 1:]
    return jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]


def lm_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Next-token cross-entropy (shifted), mean over all positions."""
    return -lm_log_likelihood(logits, tokens).mean()


def sp_lm_loss(logits: jax.Array, tokens: jax.Array, axis: str) -> jax.Array:
    """``lm_loss`` for sequence-sharded chunks (per-device code under
    shard_map, sequence split over ``axis``).

    Plain ``lm_loss`` per chunk silently drops every chunk-boundary
    prediction (each chunk loses its last position), so chunked and
    full-sequence losses diverge. Here each device's last position is
    scored against the NEXT chunk's first token (one ppermute around the
    sp ring); only the globally-last position goes unscored, and the
    value is scaled so ``pmean`` over ``axis`` (and over any
    disjoint-batch DP axes) equals the full-sequence ``lm_loss`` exactly.
    """
    k = _axis_size(axis)
    if k == 1:
        return lm_loss(logits, tokens)
    idx = jax.lax.axis_index(axis)
    nxt_first = jax.lax.ppermute(
        tokens[:, 0], axis, [(i, (i - 1) % k) for i in range(k)])
    tgt = jnp.concatenate([tokens[:, 1:], nxt_first[:, None]], axis=1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    # The last device's final position has no successor token.
    scored = jnp.ones_like(ll).at[:, -1].set(
        jnp.where(idx == k - 1, 0.0, 1.0))
    b, s_local = ll.shape
    total = b * (k * s_local - 1)  # positions scored across the ring
    return -jnp.sum(ll * scored) * k / total
