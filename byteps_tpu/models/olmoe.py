"""OLMoE-family sparse decoder (Muennighoff et al. 2024, OLMoE: Open
Mixture-of-Experts Language Models).

``llama.py``'s block — RMSNorm, RoPE, SwiGLU — with two changes, as the
published model has them: Q and K are RMS-normalised over the whole
projected width before the split into heads, and the feed-forward is a
dropless top-k expert layer (``parallel/moe.py::dropless_moe_ffn``: 64
experts of width 1024, 8 per token, no shared expert, the router's raw
probabilities as weights) in place of ``LlamaMLP``. Multi-head attention (no
GQA), no biases, untied output head. bf16 matmul operands over float32
parameters; the residual stream (embedding, norms, residual sums) and the
router in float32, which is what torch autocast leaves of the published
code, and what keeps the first adamw steps of two implementations within
1.5e-3 of each other where a bf16 stream gave 2.2e-3 (PERF.md, PR 28).

The model returns its logits and the two auxiliary losses of the expert
layers (mean over layers); ``olmoe_loss`` adds them to the next-token
cross-entropy with the paper's weights. Per-expert assignment counts are
sown into the ``"moe_stats"`` collection: apply with
``mutable=["moe_stats"]`` to get them (``parallel/moe.py::
publish_moe_stats``), and pay nothing otherwise.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp

from byteps_tpu.models.llama import RMSNorm, _rope
from byteps_tpu.models.transformer import (_attention_fn,
                                           _default_positions, lm_loss)
from byteps_tpu.parallel.moe import dropless_moe_ffn


class OlmoeAttention(nn.Module):
    num_heads: int
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "full"
    rope_theta: float = 10000.0
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x, positions):
        b, s, d_model = x.shape
        dense = partial(nn.Dense, d_model, use_bias=False, dtype=self.dtype)
        heads = (b, s, self.num_heads, d_model // self.num_heads)
        q = RMSNorm(self.eps, name="q_norm")(dense(name="q")(x))
        k = RMSNorm(self.eps, name="k_norm")(dense(name="k")(x))
        v = dense(name="v")(x)
        q = _rope(q.reshape(heads), positions, self.rope_theta)
        k = _rope(k.reshape(heads), positions, self.rope_theta)
        out = _attention_fn(self.attn_impl, None)(q, k, v.reshape(heads),
                                                  causal=True)
        return dense(name="o")(out.reshape(b, s, d_model))


class OlmoeSparseMoe(nn.Module):
    """Router + ``num_experts`` SwiGLU experts of width ``mlp_dim``."""

    num_experts: int
    top_k: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        e, m = self.num_experts, self.mlp_dim
        # fan-in scaling per expert: axis 0 counts experts, not inputs
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=0)
        y, load_balance, z_loss, counts = dropless_moe_ffn(
            x.reshape(b * s, d),
            self.param("router", nn.initializers.lecun_normal(), (d, e),
                       jnp.float32),
            self.param("gate", init, (e, d, m), jnp.float32),
            self.param("up", init, (e, d, m), jnp.float32),
            self.param("down", init, (e, m, d), jnp.float32),
            top_k=self.top_k, dtype=self.dtype)
        if not self.is_initializing():    # init() returns parameters only
            self.sow("moe_stats", "counts", counts)
        return y.reshape(b, s, d), load_balance, z_loss


class OlmoeBlock(nn.Module):
    num_heads: int
    num_experts: int
    top_k: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "full"
    rope_theta: float = 10000.0
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x, positions):
        x = x + OlmoeAttention(
            self.num_heads, self.dtype, self.attn_impl, self.rope_theta,
            self.eps, name="attn")(RMSNorm(self.eps, name="attn_norm")(x),
                                   positions)
        y, load_balance, z_loss = OlmoeSparseMoe(
            self.num_experts, self.top_k, self.mlp_dim, self.dtype,
            name="moe")(RMSNorm(self.eps, name="moe_norm")(x))
        return x + y, load_balance, z_loss


class OlmoeModel(nn.Module):
    """Causal LM. ``tokens`` [batch, seq] -> ``(float32 logits, {
    "load_balance", "z_loss"})``, the auxiliary losses averaged over the
    layers."""

    vocab_size: int
    num_layers: int
    d_model: int
    num_heads: int
    num_experts: int
    top_k: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "full"
    rope_theta: float = 10000.0
    eps: float = 1e-5

    @nn.compact
    def __call__(self, tokens):
        # float32 from here on: the residual stream (module docstring)
        x = nn.Embed(self.vocab_size, self.d_model, name="embed")(tokens)
        positions = _default_positions(tokens.shape[1], None)
        aux = []
        for i in range(self.num_layers):
            x, *layer_aux = OlmoeBlock(
                self.num_heads, self.num_experts, self.top_k, self.mlp_dim,
                self.dtype, self.attn_impl, self.rope_theta, self.eps,
                name=f"layer_{i}")(x, positions)
            aux.append(layer_aux)
        x = RMSNorm(self.eps, name="final_norm")(x)
        logits = nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                          name="lm_head")(x)
        load_balance, z_loss = (sum(a) / self.num_layers for a in zip(*aux))
        return logits.astype(jnp.float32), {"load_balance": load_balance,
                                            "z_loss": z_loss}


def olmoe_loss(outputs, tokens, *, load_balance_weight: float = 0.01,
               z_loss_weight: float = 0.001) -> jax.Array:
    """Next-token cross-entropy + 0.01 x load-balancing loss + 0.001 x
    router z-loss (the OLMoE paper's training weights) over the model's
    ``(logits, aux)``."""
    logits, aux = outputs
    return (lm_loss(logits, tokens)
            + load_balance_weight * aux["load_balance"]
            + z_loss_weight * aux["z_loss"])


# Tiny is for tests. Olmoe1B7B follows allenai/OLMoE-1B-7B-0125 (16 layers,
# d 2048, 16 heads, 64 experts of width 1024, 8 per token, vocab 50304).
OlmoeTiny = partial(OlmoeModel, vocab_size=512, num_layers=2, d_model=64,
                    num_heads=4, num_experts=8, top_k=2, mlp_dim=32)
Olmoe1B7B = partial(OlmoeModel, vocab_size=50304, num_layers=16,
                    d_model=2048, num_heads=16, num_experts=64, top_k=8,
                    mlp_dim=1024)
