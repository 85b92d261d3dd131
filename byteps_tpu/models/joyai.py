"""JoyAI-LLM-Flash-family decoder (``jdopensource/JoyAI-LLM-Flash``, whose
keys and layer equations are DeepSeek-V3's, arXiv 2412.19437 sections 2.1
and 2.2): pre-norm blocks

    a = x + MLA(N1(x)),    y = a + FFN(N2(a)),

every mixer latent attention with a low-rank query and a rotary key part,
``first_dense`` leading layers with a dense SwiGLU and DeepSeek-V3's expert
layer after them, and one multi-token-prediction module behind the main
stack.

The pieces are ``models/kimi_linear.py``'s, which exist once:
``KimiLatentAttention`` (here with ``q_rank`` and ``rope_theta``: ``q =
RMSNorm(h Wqa) Wqb``, the last ``rope_dim`` of each query and the one key
part all heads share rotated in interleaved pairs by the row's position),
``KimiSparseMoe`` (sigmoid scores, a selection bias that chooses and never
weighs, the held share of the experts plus a shared expert), ``KimiBlock``
(each half recomputed in the backward pass) and ``next_token_nll`` (head and
cross-entropy in row blocks, each recomputed).

**MTP, depth 1.** With ``h`` the main stack's output before its final norm,
row i of the module is ``z_i = [Ne(Embed(t_{i+1})) ; Nh(h_i)] Weh`` — the
embedding's part first — through one more whole block, causal, a row's
position its own, and predicts ``t_{i+2}`` through ``Head(Ns(.))``.
``Embed`` and ``Head`` are the main model's own leaves: each is used twice
in a step and its gradient is the sum of both paths. The module runs at all
s rows, so that kernels and row blocks see the main stack's shapes: its last
row gets a zero for the embedding it lacks, and the two rows without a
target are dropped from the loss.

The model returns both per-position cross-entropies, ``(main [batch, seq -
1], mtp [batch, seq - 2])``, float32; ``joyai_loss`` is ``mean(main) +
mtp_weight mean(mtp)``. Precisions are Kimi-Linear's: float32 parameters,
residual stream, norms, rotation and router, ``dtype`` (bf16) matmul
operands with float32 accumulation. Apply with ``mutable=["moe_stats",
"mtp_stats"]`` for the per-expert counts and the batch's two mean losses
(``publish_moe_stats``, ``publish_mtp_stats``), and pay nothing otherwise.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from byteps_tpu.models.kimi_linear import (KimiBlock, KimiLatentAttention,
                                           KimiSparseMoe, next_token_nll)
from byteps_tpu.models.llama import LlamaMLP, RMSNorm

MTP_SCOPE = "bps.mtp"                   # the whole module, its head included
MTP_COMBINE_SCOPE = "bps.mtp.combine"   # the two norms and Weh


class MTPModule(nn.Module):
    """``Ns(Block([Ne(emb) ; Nh(h)] Weh))``: emb [b, s, d] the next tokens'
    embeddings, h [b, s, d] the main stack's output."""

    block: Callable[[], nn.Module]
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-6

    @nn.compact
    def __call__(self, emb, h):
        with jax.named_scope(MTP_COMBINE_SCOPE):
            z = nn.Dense(h.shape[-1], use_bias=False, dtype=self.dtype,
                         name="eh_proj")(jnp.concatenate(
                             [RMSNorm(self.eps, name="embed_norm")(emb),
                              RMSNorm(self.eps, name="hidden_norm")(h)],
                             axis=-1))
        # the block's input is the float32 residual stream, as a main one's
        return RMSNorm(self.eps, name="norm")(
            self.block()(z.astype(jnp.float32)))


class JoyAIFlashModel(nn.Module):
    """Causal LM with one MTP module. ``tokens`` [batch, seq] -> ``(main,
    mtp)``: the cross-entropy of the next token [batch, seq - 1] and of the
    one after it [batch, seq - 2], float32. ``num_layers`` counts the main
    stack."""

    vocab_size: int
    num_layers: int
    d_model: int
    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    dense_mlp_dim: int
    num_experts: int
    num_local_experts: int
    top_k: int
    mlp_dim: int
    routed_scale: float
    first_dense: int = 1
    shared: int = 1
    first_expert: int = 0
    rope_theta: float = 32e6
    loss_rows: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-6

    def setup(self):
        # unit-variance embeddings: models/keye.py has the reason
        self.embed = nn.Embed(self.vocab_size, self.d_model,
                              embedding_init=nn.initializers.normal(1.0))
        mla = partial(KimiLatentAttention, self.heads, self.nope_dim,
                      self.rope_dim, self.v_dim, self.kv_rank, self.dtype,
                      self.eps, q_rank=self.q_rank,
                      rope_theta=self.rope_theta, name="mla")
        dense = partial(LlamaMLP, self.dense_mlp_dim, self.dtype, name="mlp")
        moe = partial(KimiSparseMoe, self.num_experts,
                      self.num_local_experts, self.first_expert, self.top_k,
                      self.mlp_dim, self.routed_scale, self.shared,
                      self.dtype, name="moe")
        for i in range(self.num_layers):
            setattr(self, f"layer_{i}", KimiBlock(
                mla, dense if i < self.first_dense else moe, self.eps))
        self.final_norm = RMSNorm(self.eps)
        self.lm_head = nn.Dense(self.vocab_size, use_bias=False,
                                dtype=self.dtype)
        self.mtp = MTPModule(partial(KimiBlock, mla, moe, self.eps,
                                     name="block"), self.dtype, self.eps)

    def __call__(self, tokens):
        x = embedded = self.embed(tokens)        # float32 from here on
        for i in range(self.num_layers):
            x = getattr(self, f"layer_{i}")(x)
        main = next_token_nll(self, self.final_norm(x), tokens, 1)
        with jax.named_scope(MTP_SCOPE):
            # row i gets the embedding of token i + 1, the last row a zero
            ahead = jnp.pad(embedded[:, 1:], ((0, 0), (0, 1), (0, 0)))
            mtp = next_token_nll(self, self.mtp(ahead, x), tokens, 2)
        if (self.is_mutable_collection("mtp_stats")
                and not self.is_initializing()):   # init(): parameters only
            self.sow("mtp_stats", "main_loss", main.mean())
            self.sow("mtp_stats", "next2_loss", mtp.mean())
        return main, mtp


def joyai_loss(nll, mtp_weight: float = 0.3) -> jax.Array:
    """``mean(main) + mtp_weight mean(mtp)`` over the model's output
    (DeepSeek-V3's lambda for most of its run). No auxiliary loss: the
    family balances its experts through the selection bias."""
    main, mtp = nll
    return main.mean() + mtp_weight * mtp.mean()


def publish_mtp_stats(mtp_stats) -> dict:
    """The ``"mtp_stats"`` collection of a model applied with it mutable
    (the batch's mean cross-entropy of each stream) to
    ``monitor/metrics.py``: gauges ``bps_mtp_main_loss`` and
    ``bps_mtp_next2_loss``. Returns what it published."""
    from byteps_tpu.monitor import metrics

    out = {}
    for leaf, gauge in (("main_loss", "bps_mtp_main_loss"),
                        ("next2_loss", "bps_mtp_next2_loss")):
        if leaf in mtp_stats:
            out[gauge] = float(mtp_stats[leaf][-1])  # sown once an apply
            metrics.set_gauge(gauge, out[gauge])
    return out


# Tiny is for tests (a share: experts 0..1 of 8; keys 24 wide against values
# 16; three main layers and the module). JoyAIFlash48BA3B follows
# jdopensource/JoyAI-LLM-Flash (40 layers, d 2048, 32 heads of 128 + 64 /
# 128 behind latents of 1536 and 512, dense 7168 then 256 experts of width
# 768, 8 per token, one shared, rotary base 32e6, vocab 129280, one MTP
# module).
JoyAIFlashTiny = partial(
    JoyAIFlashModel, vocab_size=512, num_layers=3, d_model=64, heads=4,
    q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16,
    dense_mlp_dim=128, num_experts=8, num_local_experts=2, top_k=2,
    mlp_dim=32, routed_scale=2.5, loss_rows=32)
JoyAIFlash48BA3B = partial(
    JoyAIFlashModel, vocab_size=129280, num_layers=40, d_model=2048,
    heads=32, q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
    dense_mlp_dim=7168, num_experts=256, num_local_experts=256, top_k=8,
    mlp_dim=768, routed_scale=2.5)
