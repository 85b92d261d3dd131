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

import math
from functools import partial
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from byteps_tpu.jax._compat import axis_size as _axis_size

from byteps_tpu.models.transformer import _attention_fn, _default_positions


class RMSNorm(nn.Module):
    """``x rsqrt(mean x^2 + eps) scale``, float32. ``zero_centred``: the
    learned vector starts at 0 and the row is multiplied by ``1 + scale``
    (Qwen3-Next's; weight decay then pulls the scale to 1, not 0).
    ``initial``: another starting value (``models/zaya.py``'s final norm,
    whose rows meet a tied unit-variance embedding and start at ln(vocab) / d)."""

    eps: float = 1e-6
    zero_centred: bool = False
    # where the learned vector starts, if not at 0 (zero_centred) or 1
    initial: Optional[float] = None

    @nn.compact
    def __call__(self, x):
        orig_dtype = x.dtype
        xf = x.astype(jnp.float32)
        scale = self.param(
            "scale", nn.initializers.constant(self.initial)
            if self.initial is not None else nn.initializers.zeros
            if self.zero_centred else nn.initializers.ones,
            (x.shape[-1],), jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1,
                                        keepdims=True) + self.eps)
        return (y * (1.0 + scale if self.zero_centred else scale)).astype(
            orig_dtype)


def yarn_inv_freq(rotary_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float,
                  beta_slow: float) -> jax.Array:
    """YaRN's blended rotary frequencies (arXiv 2309.00071, "NTK-by-parts"),
    float32 [rotary_dim / 2]. Pair j's own frequency ``f_j = theta^(-2j /
    rotary_dim)`` where it turns more than ``beta_fast`` times over
    ``original_max`` positions, ``f_j / factor`` where fewer than
    ``beta_slow``, a linear ramp between the two pairs where that happens:
    ``r(beta) = rotary_dim ln(original_max / (2 pi beta)) / (2 ln theta)``,
    ``lo = floor(r(beta_fast))``, ``hi = ceil(r(beta_slow))``, both clamped
    to ``0 .. rotary_dim - 1``, ``gamma_j = clip((j - lo) / (hi - lo), 0,
    1)``, ``omega_j = (1 - gamma_j) f_j + gamma_j f_j / factor``."""
    lo, hi = yarn_ramp(rotary_dim, theta, original_max, beta_fast, beta_slow)
    j = jnp.arange(rotary_dim // 2, dtype=jnp.float32)
    freqs = theta ** (-j / (rotary_dim // 2))
    gamma = jnp.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (1.0 - gamma) * freqs + gamma * freqs / factor


def yarn_ramp(rotary_dim: int, theta: float, original_max: int,
              beta_fast: float, beta_slow: float) -> tuple:
    """``(lo, hi)`` of ``yarn_inv_freq``: the pairs the ramp runs between."""
    def pair(beta):
        return (rotary_dim * math.log(original_max / (2 * math.pi * beta))
                / (2 * math.log(theta)))

    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), rotary_dim - 1))


def _rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
          interleaved: bool = False, *, rotary_dim: Optional[int] = None,
          inv_freq: Optional[jax.Array] = None,
          factor: float = 1.0) -> jax.Array:
    """Rotary position embedding over [batch, seq, heads, head_dim], in
    float32. Pair j of a head is rotated by ``position theta^(-2j / d)``:
    entries ``(j, j + d/2)`` (half against half), or with ``interleaved``
    entries ``(2j, 2j + 1)``, each staying where it was. ``rotary_dim``:
    the first ``rotary_dim`` entries are the ``d`` that is rotated and the
    rest pass as they are (a partial rotary factor). ``inv_freq`` [d / 2]:
    the pairs' frequencies where they are not ``theta``'s
    (``yarn_inv_freq``). ``factor``: cos and sin are multiplied by it (YaRN's
    attention factor: the rotated part of a logit carries its square)."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        return jnp.concatenate([
            _rope(x[..., :rotary_dim], positions, theta, interleaved,
                  inv_freq=inv_freq, factor=factor),
            x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    half = d // 2
    if inv_freq is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = inv_freq
    angles = positions[..., None].astype(jnp.float32) * freqs  # [b, s, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
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
