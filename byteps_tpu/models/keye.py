"""Keye-VL-2.0-family sparse decoder, the language model (Kwai-Keye,
Keye-VL-2.0-30B-A3B ``config.json``): the Qwen3-MoE block — RMSNorm,
grouped-query attention with per-head Q/K RMSNorm, RoPE, a dropless top-k
expert layer whose chosen weights are renormalised, no shared expert — with
DeepSeek-V3.2's learned sparse attention in place of dense attention
(``parallel/sparse_attention.py``): a lightning indexer of ``index_heads``
heads of ``index_dim`` over one key head scores every earlier key, each
query attends to its ``index_topk`` best, and the indexer learns from a KL
term towards the attention probabilities.

Two detachments make one scalar train both: the indexer reads
``stop_gradient`` of the normalised hidden state and is trained towards
detached attention probabilities, so the language-model loss leaves no
gradient on the indexer's leaves and the indexer's loss none on any other.
Text only: the vision tower is left out, and with text the three M-RoPE
position streams are equal, which is RoPE. The expert layer may hold a
share of the experts (``num_local_experts`` from ``first_expert``):
``parallel/moe.py::dropless_moe_ffn`` routes over all and computes its own.

bf16 matmul operands over float32 parameters; the residual stream, the
router and the whole indexer in float32 (``models/olmoe.py`` has the
reasons). The model returns its logits and the mean over layers of the
load-balancing loss and of the indexer's loss; ``keye_loss`` adds them to
the next-token cross-entropy. Apply with ``mutable=["moe_stats",
"dsa_stats"]`` for the per-expert counts and the selected-key counts
(``publish_moe_stats``, ``publish_dsa_stats``), and pay nothing otherwise.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp

from byteps_tpu.models.llama import RMSNorm, _rope
from byteps_tpu.models.transformer import _default_positions, lm_loss
from byteps_tpu.parallel.moe import dropless_moe_ffn
from byteps_tpu.parallel.sparse_attention import (INDEXER_SCOPE,
                                                  sparse_attention)


class KeyeIndexer(nn.Module):
    """Index queries [b, s, hi, di], the one key head [b, s, di] and the
    per-head weights [b, s, hi] from the detached hidden state, float32."""

    heads: int
    dim: int
    rope_theta: float = 1e7
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x, positions):
        b, s, _ = x.shape
        dense = partial(nn.Dense, use_bias=False, dtype=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
        x = jax.lax.stop_gradient(x).astype(jnp.float32)
        with jax.named_scope(INDEXER_SCOPE):
            q = dense(self.heads * self.dim, name="q")(x)
            k = nn.LayerNorm(epsilon=self.eps, dtype=jnp.float32,
                             name="k_norm")(dense(self.dim, name="k")(x))
            w = dense(self.heads, name="w")(x) * (self.heads * self.dim) ** -0.5
            q = _rope(q.reshape(b, s, self.heads, self.dim), positions,
                      self.rope_theta)
            k = _rope(k[:, :, None, :], positions, self.rope_theta)[:, :, 0]
        return q, k, w


class KeyeAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    index_heads: int
    index_dim: int
    index_topk: int
    block: int = 512
    dtype: jnp.dtype = jnp.bfloat16
    rope_theta: float = 1e7
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x, positions):
        b, s, d_model = x.shape
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)

        def heads(name, n, normed):
            y = dense(n * self.head_dim, name=name)(x).reshape(
                b, s, n, self.head_dim)
            if not normed:
                return y
            # per head over its head_dim, one learned scale for all heads
            return _rope(RMSNorm(self.eps, name=name + "_norm")(y),
                         positions, self.rope_theta)

        out, index_loss, selected = sparse_attention(
            heads("q", self.num_heads, True),
            heads("k", self.num_kv_heads, True),
            heads("v", self.num_kv_heads, False),
            *KeyeIndexer(self.index_heads, self.index_dim, self.rope_theta,
                         self.eps, name="indexer")(x, positions),
            topk=self.index_topk, block=self.block)
        if not self.is_initializing():    # init() returns parameters only
            self.sow("dsa_stats", "selected", selected)
            self.sow("dsa_stats", "causal",
                     jnp.full((b,), s * (s + 1) // 2, jnp.int32))
        return dense(d_model, name="o")(
            out.reshape(b, s, self.num_heads * self.head_dim)), index_loss


class KeyeSparseMoe(nn.Module):
    """Router over ``num_experts``; the SwiGLU experts ``first_expert ..
    first_expert + num_local_experts - 1`` of width ``mlp_dim`` held here."""

    num_experts: int
    num_local_experts: int
    first_expert: int
    top_k: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        held, m = self.num_local_experts, self.mlp_dim
        # fan-in scaling per expert: axis 0 counts experts, not inputs
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=0)
        ffn = partial(dropless_moe_ffn, top_k=self.top_k, dtype=self.dtype,
                      first_expert=self.first_expert, norm_topk=True)
        # the routed part keeps its arguments alone either way; recomputed,
        # the router's half keeps neither its [T, E] scores nor the sort
        y, load_balance, _, counts = (jax.checkpoint(ffn) if self.remat
                                      else ffn)(
            x.reshape(b * s, d),
            self.param("router", nn.initializers.lecun_normal(),
                       (d, self.num_experts), jnp.float32),
            self.param("gate", init, (held, d, m), jnp.float32),
            self.param("up", init, (held, d, m), jnp.float32),
            self.param("down", init, (held, m, d), jnp.float32))
        if not self.is_initializing():
            self.sow("moe_stats", "counts", counts)
        return y.reshape(b, s, d), load_balance


class KeyeBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    index_heads: int
    index_dim: int
    index_topk: int
    num_experts: int
    num_local_experts: int
    first_expert: int
    top_k: int
    mlp_dim: int
    block: int = 512
    dtype: jnp.dtype = jnp.bfloat16
    rope_theta: float = 1e7
    eps: float = 1e-6
    remat_experts: bool = False

    @nn.compact
    def __call__(self, x, positions):
        y, index_loss = KeyeAttention(
            self.num_heads, self.num_kv_heads, self.head_dim,
            self.index_heads, self.index_dim, self.index_topk, self.block,
            self.dtype, self.rope_theta, self.eps, name="attn")(
                RMSNorm(self.eps, name="attn_norm")(x), positions)
        x = x + y
        y, load_balance = KeyeSparseMoe(
            self.num_experts, self.num_local_experts, self.first_expert,
            self.top_k, self.mlp_dim, self.dtype, self.remat_experts,
            name="moe")(
                RMSNorm(self.eps, name="moe_norm")(x))
        return x + y, load_balance, index_loss


class KeyeModel(nn.Module):
    """Causal LM. ``tokens`` [batch, seq] -> ``(float32 logits, {
    "load_balance", "index_loss"})``, both averaged over the layers.
    ``remat_experts`` recomputes each expert layer in the backward pass."""

    vocab_size: int
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    num_local_experts: int
    top_k: int
    mlp_dim: int
    index_heads: int
    index_dim: int
    index_topk: int
    first_expert: int = 0
    block: int = 512
    dtype: jnp.dtype = jnp.bfloat16
    rope_theta: float = 1e7
    eps: float = 1e-6
    remat_experts: bool = False

    @nn.compact
    def __call__(self, tokens):
        # float32 from here on: the residual stream (module docstring).
        # Unit-variance embeddings keep that stream the tokens' own at
        # initialisation: under flax's default (std d^-1/2, the source's
        # 0.02) the attention's output, much the same for every query, is
        # as large as the embedding, and from the second layer on every
        # token chooses the same experts (PERF.md, PR 33).
        x = nn.Embed(self.vocab_size, self.d_model, name="embed",
                     embedding_init=nn.initializers.normal(1.0))(tokens)
        positions = _default_positions(tokens.shape[1], None)
        aux = []
        for i in range(self.num_layers):
            x, *layer_aux = KeyeBlock(
                self.num_heads, self.num_kv_heads, self.head_dim,
                self.index_heads, self.index_dim, self.index_topk,
                self.num_experts, self.num_local_experts, self.first_expert,
                self.top_k, self.mlp_dim, self.block, self.dtype,
                self.rope_theta, self.eps, self.remat_experts,
                name=f"layer_{i}")(x, positions)
            aux.append(layer_aux)
        x = RMSNorm(self.eps, name="final_norm")(x)
        logits = nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                          name="lm_head")(x)
        load_balance, index_loss = (sum(a) / self.num_layers
                                    for a in zip(*aux))
        return logits.astype(jnp.float32), {"load_balance": load_balance,
                                            "index_loss": index_loss}


def keye_loss(outputs, tokens, *, load_balance_weight: float = 0.001,
              index_loss_weight: float = 1.0) -> jax.Array:
    """Next-token cross-entropy + 0.001 x load-balancing loss (Qwen3-MoE's
    ``router_aux_loss_coef``) + the indexer's KL loss over the model's
    ``(logits, aux)``: the first two train the model, the third the
    indexer alone."""
    logits, aux = outputs
    return (lm_loss(logits, tokens)
            + load_balance_weight * aux["load_balance"]
            + index_loss_weight * aux["index_loss"])


# Tiny is for tests (a share: experts 0..1 of 8). Keye30BA3B follows
# Kwai-Keye/Keye-VL-2.0-30B-A3B's language model (48 layers, d 2048, 32
# query / 4 key-value heads of 128, 128 experts of width 768, 8 per token,
# indexer 16 x 64 selecting 2048 keys, vocab 151936).
KeyeTiny = partial(KeyeModel, vocab_size=512, num_layers=2, d_model=64,
                   num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
                   num_local_experts=2, top_k=2, mlp_dim=32, index_heads=2,
                   index_dim=8, index_topk=16, block=16)
Keye30BA3B = partial(KeyeModel, vocab_size=151936, num_layers=48,
                     d_model=2048, num_heads=32, num_kv_heads=4,
                     head_dim=128, num_experts=128, num_local_experts=128,
                     top_k=8, mlp_dim=768, index_heads=16, index_dim=64,
                     index_topk=2048)
