"""Laguna-family decoder (``poolside/Laguna-XS.2``, ``model_type`` laguna):
pre-norm blocks

    a = x + Attn_l(N1(x)),    y = a + FFN_l(N2(a)),

whose mixers are grouped-query softmax attention of two kinds, read off
per-layer lists: **windowed** layers (a query sees the last ``window`` keys,
itself among them) and **global** ones (every earlier key), each kind with
its own number of query heads over the same ``kv_heads`` key heads and its
own rotary embedding, and every head's output under a gate of its own.

**Attention of layer l** (``LagunaAttention``; ``heads`` = the layer's,
``head_dim`` wide). ``q = h Wq`` [s, heads, head_dim], ``k, v = h Wk, h Wv``
[s, kv_heads, head_dim]; query head i reads key head ``i // (heads /
kv_heads)``. Rotary on q and k, float32, half against half
(``models/llama.py::_rope``): a windowed layer rotates the whole head by
``theta``; a global layer the first ``rotary_factor head_dim`` entries, the
rest passing as they are, at YaRN's blended frequencies
(``yarn_inv_freq``) with cos and sin times the attention factor. Scores ``q
k / sqrt(head_dim)``, exact softmax over the band or the causal triangle
(``parallel.full_attention(window=...)``: on the chip the flash kernels,
whose grid walks the band alone and whose K/V index maps read the group's
key head). Gate ``g = sigmoid(h Wg)`` [s, heads], float32: head i's output
times ``g_i``, then ``Wo``.

**Feed-forward.** ``"dense"``: a SwiGLU ``dense_mlp_dim`` wide. ``"sparse"``:
``models/kimi_linear.py::KimiSparseMoe`` without a selection bias — sigmoid
scores over all ``num_experts`` in float32, the top ``top_k``, their scores
renormalised and scaled by ``routed_scale``, dropless over the held share
(``num_local_experts`` from ``first_expert``), plus a shared expert every
token passes.

Precisions and recomputation are Kimi-Linear's: float32 parameters,
residual stream, norms, rotation, gate's sigmoid and router; ``dtype``
(bf16) matmul operands with float32 accumulation; each half of a block
under ``nn.remat``; head and cross-entropy in blocks of ``loss_rows`` rows
(``next_token_nll``). The model returns the per-position cross-entropy
[batch, seq - 1]; ``laguna_loss`` is its mean. Apply with
``mutable=["moe_stats"]`` for the per-expert counts.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from byteps_tpu.models.kimi_linear import (KimiBlock, KimiSparseMoe,
                                           next_token_nll)
from byteps_tpu.models.llama import LlamaMLP, RMSNorm, _rope, yarn_inv_freq
from byteps_tpu.parallel.ring_attention import full_attention

WINDOW_SCOPE = "bps.swa.window"   # a windowed layer's attention call
FULL_SCOPE = "bps.swa.full"       # a global layer's
PROJ_SCOPE = "bps.swa.proj"       # q, k, v, gate, rotation, gating, Wo

WINDOW, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class Rotary:
    """One kind of layer's rotary embedding. ``yarn``: ``(factor,
    original_max_position_embeddings, beta_fast, beta_slow,
    attention_factor)``."""

    theta: float
    rotary_factor: float = 1.0
    yarn: Optional[tuple] = None

    def __call__(self, x):
        b, s, _, head_dim = x.shape
        rotary_dim = int(head_dim * self.rotary_factor)
        inv_freq, factor = None, 1.0
        if self.yarn is not None:
            scale, original_max, fast, slow, factor = self.yarn
            inv_freq = yarn_inv_freq(rotary_dim, self.theta, scale,
                                     original_max, fast, slow)
        return _rope(x, jnp.broadcast_to(jnp.arange(s), (b, s)), self.theta,
                     rotary_dim=rotary_dim, inv_freq=inv_freq, factor=factor)


class LagunaAttention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    rotary: Rotary
    window: Optional[int] = None        # None: a global layer
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, d_model = x.shape
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        with jax.named_scope(PROJ_SCOPE):
            q = dense(self.heads * self.head_dim, name="q")(x).reshape(
                b, s, self.heads, self.head_dim)
            k, v = (dense(self.kv_heads * self.head_dim, name=name)(x)
                    .reshape(b, s, self.kv_heads, self.head_dim)
                    for name in "kv")
            gate = jax.nn.sigmoid(
                dense(self.heads, name="gate")(x).astype(jnp.float32))
            q, k = self.rotary(q), self.rotary(k)
        with jax.named_scope(FULL_SCOPE if self.window is None
                             else WINDOW_SCOPE):
            out = full_attention(q, k, v, causal=True,
                                 scale=self.head_dim ** -0.5,
                                 window=self.window)
        with jax.named_scope(PROJ_SCOPE):
            return dense(d_model, name="o")(
                (out * gate[..., None]).reshape(
                    b, s, self.heads * self.head_dim))


class LagunaModel(nn.Module):
    """Causal LM. ``tokens`` [batch, seq] -> the next-token cross-entropy
    [batch, seq - 1], float32. One entry a layer in ``layer_kinds``
    (``"sliding_attention"`` | ``"full_attention"``), ``layer_heads`` (its
    query heads) and ``layer_ffn`` (``"dense"`` | ``"sparse"``)."""

    vocab_size: int
    layer_kinds: Sequence[str]
    layer_heads: Sequence[int]
    layer_ffn: Sequence[str]
    d_model: int
    kv_heads: int
    head_dim: int
    window: int
    full_rotary: Rotary
    window_rotary: Rotary
    dense_mlp_dim: int
    num_experts: int
    num_local_experts: int
    top_k: int
    mlp_dim: int
    routed_scale: float
    shared_mlp_dim: int
    first_expert: int = 0
    loss_rows: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-6

    def setup(self):
        if not (len(self.layer_kinds) == len(self.layer_heads)
                == len(self.layer_ffn)):
            raise ValueError("layer_kinds, layer_heads and layer_ffn name "
                             "one entry a layer")
        if (not set(self.layer_kinds) <= {WINDOW, FULL}
                or not set(self.layer_ffn) <= {DENSE, SPARSE}):
            raise ValueError(
                f"layer_kinds are {WINDOW!r} | {FULL!r} and layer_ffn "
                f"{DENSE!r} | {SPARSE!r}, got {tuple(self.layer_kinds)} and "
                f"{tuple(self.layer_ffn)}")
        # unit-variance embeddings: models/keye.py has the reason
        self.embed = nn.Embed(self.vocab_size, self.d_model,
                              embedding_init=nn.initializers.normal(1.0))
        ffn = {
            DENSE: partial(LlamaMLP, self.dense_mlp_dim, self.dtype,
                           name="mlp"),
            # ``shared`` counts experts' widths: one of shared_mlp_dim
            SPARSE: partial(KimiSparseMoe, self.num_experts,
                            self.num_local_experts, self.first_expert,
                            self.top_k, self.mlp_dim, self.routed_scale,
                            self.shared_mlp_dim // self.mlp_dim, self.dtype,
                            select_bias=False, name="moe")}
        for i, (kind, heads, feed) in enumerate(zip(
                self.layer_kinds, self.layer_heads, self.layer_ffn)):
            windowed = kind == WINDOW
            setattr(self, f"layer_{i}", KimiBlock(
                partial(LagunaAttention, heads, self.kv_heads, self.head_dim,
                        self.window_rotary if windowed else self.full_rotary,
                        self.window if windowed else None, self.dtype,
                        name="attn"),
                ffn[feed], self.eps))
        self.final_norm = RMSNorm(self.eps)
        self.lm_head = nn.Dense(self.vocab_size, use_bias=False,
                                dtype=self.dtype)

    def __call__(self, tokens):
        x = self.embed(tokens)       # float32 from here on (module docstring)
        for i in range(len(self.layer_kinds)):
            x = getattr(self, f"layer_{i}")(x)
        return next_token_nll(self, self.final_norm(x), tokens, 1)


def laguna_loss(nll: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy over the model's output."""
    return nll.mean()


# Tiny is for tests (a share: experts 0..1 of 8; 6 and 4 query heads over 2
# key heads; a window of 8). LagunaXS2 follows poolside/Laguna-XS.2 (40
# layers, global : windowed 1 : 3 with 48 / 64 heads over 8 key heads of
# 128, window 512, d 2048, dense 8192 then 256 experts of width 512, 8 per
# token, one shared, vocab 100352).
_XS2_YARN = (64.0, 4096, 64.0, 1.0, 1.4158883083359672)
LagunaTiny = partial(
    LagunaModel, vocab_size=512,
    layer_kinds=(FULL, WINDOW, WINDOW, FULL), layer_heads=(4, 6, 6, 4),
    layer_ffn=(DENSE, SPARSE, SPARSE, SPARSE), d_model=64, kv_heads=2,
    head_dim=16, window=8,
    full_rotary=Rotary(500000.0, 0.5, (64.0, 16, 64.0, 1.0, 1.4158883083359672)),
    window_rotary=Rotary(10000.0), dense_mlp_dim=128, num_experts=8,
    num_local_experts=2, top_k=2, mlp_dim=32, routed_scale=2.5,
    shared_mlp_dim=32, loss_rows=32)
LagunaXS2 = partial(
    LagunaModel, vocab_size=100352,
    layer_kinds=(FULL, WINDOW, WINDOW, WINDOW) * 10,
    layer_heads=(48, 64, 64, 64) * 10,
    layer_ffn=(DENSE,) + (SPARSE,) * 39, d_model=2048, kv_heads=8,
    head_dim=128, window=512, full_rotary=Rotary(500000.0, 0.5, _XS2_YARN),
    window_rotary=Rotary(10000.0), dense_mlp_dim=8192, num_experts=256,
    num_local_experts=256, top_k=8, mlp_dim=512, routed_scale=2.5,
    shared_mlp_dim=512)
