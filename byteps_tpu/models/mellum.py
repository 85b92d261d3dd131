"""Mellum-family decoder (``JetBrains/Mellum2-12B-A2.5B-Instruct``,
``model_type`` mellum): pre-norm blocks

    a = x + Attn_l(N1(x)),    y = a + MoE(N2(a)),

whose mixers are grouped-query softmax attention of two kinds read off a
per-layer list — **windowed** layers (a query sees the last ``window`` keys,
itself among them) and **global** ones (every earlier key) — with one head
count and one ``rope_theta`` in both kinds, and whose feed-forward is an
expert layer in every block, with no shared expert and no leading dense
layer.

**Attention of layer l** (``MellumAttention``). ``q = h Wq`` [s, heads,
head_dim], ``k, v = h Wk, h Wv`` [s, kv_heads, head_dim]; query head i reads
key head ``i // (heads / kv_heads)``. Rotary on q and k over the whole
head, float32, half against half (``models/laguna.py::Rotary`` ->
``models/llama.py::_rope``): a windowed layer at ``theta``'s own
frequencies, a global layer at YaRN's blended ones (``yarn_inv_freq``)
with cos and sin times the attention factor. Scores ``q k /
sqrt(head_dim)``, exact softmax over the band or the causal triangle
(``parallel.full_attention(window=...)``: on the chip the flash kernels,
whose grid walks the band alone and whose K/V index maps read the group's
key head), then ``Wo``. No gate, no q/k norm, no bias.

**Expert layer.** ``models/kimi_linear.py::KimiSparseMoe`` with
``scoring="softmax"``, no selection bias and ``shared=0``: softmax over all
``num_experts`` in float32, the top ``top_k``, their probabilities divided
by their sum, no scaling factor, dropless over the held share
(``num_local_experts`` from ``first_expert``), no auxiliary loss.

Precisions and recomputation are Kimi-Linear's and Laguna's: float32
parameters, residual stream, norms, rotation and router; ``dtype`` (bf16)
matmul operands with float32 accumulation; each half of a block under
``nn.remat``; head and cross-entropy in blocks of ``loss_rows`` rows
(``next_token_nll``). The model returns the per-position cross-entropy
[batch, seq - 1]; ``mellum_loss`` is its mean. Apply with
``mutable=["moe_stats"]`` for the per-expert counts.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from byteps_tpu.models.kimi_linear import (KimiBlock, KimiSparseMoe,
                                           next_token_nll)
from byteps_tpu.models.laguna import (FULL, FULL_SCOPE, PROJ_SCOPE, WINDOW,
                                      WINDOW_SCOPE, Rotary)
from byteps_tpu.models.llama import RMSNorm
from byteps_tpu.monitor import metrics
from byteps_tpu.parallel.ring_attention import full_attention

MELLUM_SITES = "bps_mellum_sites_total"   # counted at trace time, a mixer


class MellumAttention(nn.Module):
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
        metrics.inc_counter(MELLUM_SITES)
        with jax.named_scope(PROJ_SCOPE):
            q = dense(self.heads * self.head_dim, name="q")(x).reshape(
                b, s, self.heads, self.head_dim)
            k, v = (dense(self.kv_heads * self.head_dim, name=name)(x)
                    .reshape(b, s, self.kv_heads, self.head_dim)
                    for name in "kv")
            q, k = self.rotary(q), self.rotary(k)
        with jax.named_scope(FULL_SCOPE if self.window is None
                             else WINDOW_SCOPE):
            out = full_attention(q, k, v, causal=True,
                                 scale=self.head_dim ** -0.5,
                                 window=self.window)
        with jax.named_scope(PROJ_SCOPE):
            return dense(d_model, name="o")(
                out.reshape(b, s, self.heads * self.head_dim))


class MellumModel(nn.Module):
    """Causal LM. ``tokens`` [batch, seq] -> the next-token cross-entropy
    [batch, seq - 1], float32. One entry a layer in ``layer_kinds``
    (``"sliding_attention"`` | ``"full_attention"``)."""

    vocab_size: int
    layer_kinds: Sequence[str]
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    full_rotary: Rotary
    window_rotary: Rotary
    num_experts: int
    num_local_experts: int
    top_k: int
    mlp_dim: int
    first_expert: int = 0
    loss_rows: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-6

    def setup(self):
        if not set(self.layer_kinds) <= {WINDOW, FULL}:
            raise ValueError(f"layer_kinds are {WINDOW!r} | {FULL!r}, got "
                             f"{tuple(self.layer_kinds)}")
        # unit-variance embeddings: models/keye.py has the reason
        self.embed = nn.Embed(self.vocab_size, self.d_model,
                              embedding_init=nn.initializers.normal(1.0))
        # routed_scale 1.0 and shared 0: the renormalised probabilities as
        # they are, and no expert that every token passes
        moe = partial(KimiSparseMoe, self.num_experts,
                      self.num_local_experts, self.first_expert, self.top_k,
                      self.mlp_dim, 1.0, 0, self.dtype, select_bias=False,
                      scoring="softmax", name="moe")
        for i, kind in enumerate(self.layer_kinds):
            windowed = kind == WINDOW
            setattr(self, f"layer_{i}", KimiBlock(
                partial(MellumAttention, self.heads, self.kv_heads,
                        self.head_dim,
                        self.window_rotary if windowed else self.full_rotary,
                        self.window if windowed else None, self.dtype,
                        name="attn"),
                moe, self.eps))
        self.final_norm = RMSNorm(self.eps)
        self.lm_head = nn.Dense(self.vocab_size, use_bias=False,
                                dtype=self.dtype)

    def __call__(self, tokens):
        x = self.embed(tokens)       # float32 from here on (module docstring)
        for i in range(len(self.layer_kinds)):
            x = getattr(self, f"layer_{i}")(x)
        return next_token_nll(self, self.final_norm(x), tokens, 1)


def mellum_loss(nll: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy over the model's output. No auxiliary
    loss: no key of the source's configuration weighs one."""
    return nll.mean()


# Tiny is for tests (a share: experts 0..1 of 8; 4 query heads over 2 key
# heads; a window of 8; YaRN's ramp over pairs 3..6 of a head's 8).
# Mellum2_12B follows JetBrains/Mellum2-12B-A2.5B-Instruct (28 layers,
# windowed : global 3 : 1, 32 heads over 4 key heads of 128, window 1024, d
# 2304, 64 experts of width 896, 8 per token, none shared, vocab 98304).
_MELLUM2_YARN = (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
MellumTiny = partial(
    MellumModel, vocab_size=512, layer_kinds=(WINDOW, WINDOW, WINDOW, FULL),
    d_model=64, heads=4, kv_heads=2, head_dim=16, window=8,
    full_rotary=Rotary(500000.0, 1.0, (16.0, 65536, 32.0, 1.0,
                                       1.2772588722239782)),
    window_rotary=Rotary(500000.0), num_experts=8, num_local_experts=2,
    top_k=2, mlp_dim=32, loss_rows=32)
Mellum2_12B = partial(
    MellumModel, vocab_size=98304,
    layer_kinds=(WINDOW, WINDOW, WINDOW, FULL) * 7, d_model=2304, heads=32,
    kv_heads=4, head_dim=128, window=1024,
    full_rotary=Rotary(500000.0, 1.0, _MELLUM2_YARN),
    window_rotary=Rotary(500000.0), num_experts=64, num_local_experts=64,
    top_k=8, mlp_dim=896)
