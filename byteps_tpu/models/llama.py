"""LLaMA-family decoder (RMSNorm / RoPE / SwiGLU / grouped-query attention).

TPU-first flax implementation of the modern decoder recipe, rounding out
the model zoo beyond the reference's ResNet/VGG/BERT era (SURVEY.md §2.6
ships models inside example scripts; here they are library modules). Works
with every attention backend in byteps_tpu — ``attn_impl='full' | 'flash'
(Pallas) | 'ring' | 'ulysses'`` — so the same module covers single-chip,
long-context sequence-parallel, and MXU-optimised paths.

Design notes for TPU:
- bf16 activations/weights, f32 for RMSNorm statistics and rotary tables;
- GQA repeats K/V heads host-side of the kernel (a gather XLA fuses),
  keeping the attention kernels oblivious to the group structure;
- weight-tied LM head via ``embed.attend`` like TransformerLM.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from byteps_tpu.jax._compat import axis_size as _axis_size

from byteps_tpu.models.transformer import _attention_fn, _default_positions


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        orig_dtype = x.dtype
        xf = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1,
                                        keepdims=True) + self.eps)
        return (y * scale).astype(orig_dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
          interleaved: bool = False) -> jax.Array:
    """Rotary position embedding over [batch, seq, heads, head_dim], in
    float32. Pair j of a head is rotated by ``position theta^(-2j / d)``:
    entries ``(j, j + d/2)`` (half against half), or with ``interleaved``
    entries ``(2j, 2j + 1)``, each staying where it was."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [b, s, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if interleaved:
        pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    x1, x2 = x[..., :half].astype(jnp.float32), \
        x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


class LlamaAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "full"
    sp_axis: Optional[str] = None
    rope_theta: float = 10000.0

    @nn.compact
    def __call__(self, x, positions):
        d_model = x.shape[-1]
        head_dim = d_model // self.num_heads
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads ({self.num_heads}) must be a multiple of "
                f"num_kv_heads ({self.num_kv_heads})")
        dense = partial(nn.DenseGeneral, dtype=self.dtype, use_bias=False)
        q = dense(features=(self.num_heads, head_dim), name="q")(x)
        k = dense(features=(self.num_kv_heads, head_dim), name="k")(x)
        v = dense(features=(self.num_kv_heads, head_dim), name="v")(x)
        q = _rope(q, positions, self.rope_theta)
        k = _rope(k, positions, self.rope_theta)
        groups = self.num_heads // self.num_kv_heads
        out = None
        if (groups > 1 and self.sp_axis is not None
                and self.attn_impl in ("ulysses", "flash")):
            # GQA + Ulysses: reshard the UNrepeated K/V heads (1/groups of
            # the all-to-all bytes), expand per query group only after the
            # exchange, inside the inner kernel.
            from byteps_tpu.parallel.ulysses import ulysses_attention
            if self.num_kv_heads % _axis_size(self.sp_axis) == 0:
                if self.attn_impl == "flash":
                    from byteps_tpu.ops.flash_attention import \
                        flash_attention as _inner
                else:
                    from byteps_tpu.parallel.ring_attention import \
                        full_attention as _inner

                def _grouped(q_, k_, v_, *, causal, scale=None):
                    k_ = jnp.repeat(k_, groups, axis=2)
                    v_ = jnp.repeat(v_, groups, axis=2)
                    return _inner(q_, k_, v_, causal=causal, scale=scale)

                out = ulysses_attention(q, k, v, axis=self.sp_axis,
                                        causal=True, attn_fn=_grouped)
        if out is None:
            if groups > 1:
                # local repeat: a gather XLA fuses into the attention
                k = jnp.repeat(k, groups, axis=2)
                v = jnp.repeat(v, groups, axis=2)
            attn = _attention_fn(self.attn_impl, self.sp_axis)
            out = attn(q, k, v, causal=True)
        return nn.DenseGeneral(d_model, axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, name="o")(out)


class LlamaMLP(nn.Module):
    """SwiGLU feed-forward: silu(W_gate x) * (W_up x) -> W_down."""

    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        gate = nn.Dense(self.mlp_dim, use_bias=False, dtype=self.dtype,
                        name="gate")(x)
        up = nn.Dense(self.mlp_dim, use_bias=False, dtype=self.dtype,
                      name="up")(x)
        return nn.Dense(d_model, use_bias=False, dtype=self.dtype,
                        name="down")(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "full"
    sp_axis: Optional[str] = None
    rope_theta: float = 10000.0
    remat: bool = False

    @nn.compact
    def __call__(self, x, positions):
        x = x + LlamaAttention(
            self.num_heads, self.num_kv_heads, self.dtype, self.attn_impl,
            self.sp_axis, self.rope_theta, name="attn")(
                RMSNorm(name="attn_norm")(x), positions)
        x = x + LlamaMLP(self.mlp_dim, self.dtype, name="mlp")(
            RMSNorm(name="mlp_norm")(x))
        return x


class LlamaModel(nn.Module):
    """Causal LM. ``tokens`` [batch, seq_local] -> f32 logits.

    Under sequence parallelism, seq_local is the per-device slice and
    positions default to the device's global offsets. ``remat=True`` wraps
    each block in jax.checkpoint (HBM for FLOPs — the TPU long-context
    recipe)."""

    vocab_size: int
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "full"
    sp_axis: Optional[str] = None
    rope_theta: float = 10000.0
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, *, positions=None):
        embed = nn.Embed(self.vocab_size, self.d_model,
                         dtype=self.dtype, name="embed")
        x = embed(tokens)
        if positions is None:
            positions = _default_positions(tokens.shape[1], self.sp_axis)
        block = LlamaBlock
        if self.remat:
            block = nn.remat(LlamaBlock, static_argnums=())
        for i in range(self.num_layers):
            x = block(self.num_heads, self.num_kv_heads, self.mlp_dim,
                      self.dtype, self.attn_impl, self.sp_axis,
                      self.rope_theta, name=f"layer_{i}")(x, positions)
        x = RMSNorm(name="final_norm")(x)
        logits = embed.attend(x.astype(self.dtype))
        return logits.astype(jnp.float32)


# Named configurations. Tiny is for tests. Llama1B follows TinyLlama-1.1B
# (22 layers, d 2048, 32 heads, 4 KV heads, mlp 5632, vocab 32000);
# Llama7B follows LLaMA-1/2-7B (32 layers, d 4096, 32 heads, no GQA,
# mlp 11008, vocab 32000).
LlamaTiny = partial(LlamaModel, vocab_size=1024, num_layers=2, d_model=64,
                    num_heads=4, num_kv_heads=2, mlp_dim=128)
Llama1B = partial(LlamaModel, vocab_size=32000, num_layers=22,
                  d_model=2048, num_heads=32, num_kv_heads=4, mlp_dim=5632)
Llama7B = partial(LlamaModel, vocab_size=32000, num_layers=32,
                  d_model=4096, num_heads=32, num_kv_heads=32,
                  mlp_dim=11008)
