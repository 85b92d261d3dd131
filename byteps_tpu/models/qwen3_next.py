"""Qwen3-Next-family hybrid decoder (``Qwen/Qwen3-Next-80B-A3B-Instruct``,
``model_type`` qwen3_next): pre-norm blocks

    a = x + Mix(N1(x)),    y = a + MoE(N2(a)),

three of Gated DeltaNet (the gated delta rule with one decay a head, arXiv
2412.06464) to one of gated softmax attention, an expert layer in every
block, one final norm before the untied head. Every ``N`` is the
zero-centred RMSNorm ``x rsqrt(mean x^2 + eps) (1 + w)``, ``w`` from 0
(``models/llama.py::RMSNorm(zero_centred=True)``).

**Gated DeltaNet** (``key_heads`` heads of ``key_dim`` keys under
``value_heads`` heads of ``value_dim`` values; key head j serves value
heads ``j groups .. (j + 1) groups - 1``). ``[q; k; v; z] = h W_qkvz``,
``[b; a] = h W_ba``, no biases. ``(q, k, v) <- SiLU(conv(.))``: one causal
depthwise convolution of ``conv_kernel`` taps over the concatenated q, k, v
channels, no bias. ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
dt_bias)`` per value head, float32. ``q``, ``k`` divided by their norm over a
head (``x / sqrt(sum x^2 + 1e-6)``), ``q`` times ``key_dim^-1/2``. Per value
head a float32 state ``S`` [key_dim, value_dim] from zero:

    S' = e^{g_t} S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;
    o_t = S_t^T q_t

(``parallel/linear_attention.py::kda_attention`` with a log-decay of rank
3: the chunked scan's per-head form). Output ``(RMSNorm(o) w_n SiLU(z))
W_o``: the norm over a head's ``value_dim``, ``w_n`` one learned vector
from 1 (the source's gated norm is not zero-centred). The source stores
``W_qkvz`` interleaved by key-head group; here its columns are q, k, v, z
one after another — with weights from a seed the order is immaterial.

**Gated attention** (``heads`` query heads over ``kv_heads`` key heads,
``head_dim`` wide). ``[q; gamma] = h W_q`` per head (the projection is twice
as wide as the queries), ``k = h W_k``, ``v = h W_v``, no biases. ``q`` and
``k`` under a zero-centred RMSNorm over a head (one vector each, shared by
the heads), then a rotary embedding on the first ``rotary_factor head_dim``
entries, half against half (``models/llama.py::_rope``), the rest passing.
Causal softmax at ``head_dim^-1/2``, query head i reading key head ``i //
(heads / kv_heads)`` (``parallel.full_attention``: on the chip the flash
kernels 256 wide). Output ``(attn sigmoid(gamma)) W_o``, the gate a number
a channel.

**Expert layer** (``models/kimi_linear.py::KimiSparseMoe``): a softmax over
all ``num_experts`` in float32, the top ``top_k``, their probabilities
divided by their sum, dropless over the held share (``num_local_experts``
from ``first_expert``), plus ``sigmoid(h w_sg) SwiGLU_s(h)``, the shared
expert under a gate of its own, whole on every chip. The load-balance loss
of ``dropless_moe_ffn`` comes back as the mean over the layers.

Precisions and recomputation are Kimi-Linear's: float32 parameters,
residual stream, norms, gates' nonlinearities, decay and state; ``dtype``
(bf16) matmul operands with float32 accumulation; the router and ``W_ba``
(its ``a`` half is cumulated over thousands of tokens) in float32 at the
highest matmul precision; each half of a block under ``nn.remat`` with the
scan's output kept; the mixer's elementwise preparation recomputed; head
and cross-entropy in blocks of ``loss_rows`` rows (``next_token_nll``). The
model returns ``(nll [batch, seq - 1], load_balance)``; ``qwen3_next_loss``
is the mean plus 0.001 of the second. Apply with ``mutable=["moe_stats",
"kda_stats"]`` for the per-expert counts and each Gated DeltaNet layer's
most negative cumulated log-decay of a chunk. The source's MTP module is
left out: no key of its config sizes it.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from byteps_tpu.models.kimi_linear import (KDA_SAVED, KimiSparseMoe,
                                           KimiSublayer, _a_log_init,
                                           _dt_bias_init, causal_conv,
                                           next_token_nll)
from byteps_tpu.models.llama import RMSNorm, _rope
from byteps_tpu.parallel.linear_attention import (GDN_PREP_SCOPE,
                                                  chunk_log_decay,
                                                  kda_attention)
from byteps_tpu.parallel.ring_attention import full_attention

GDN_OUT_SCOPE = "bps.gdn.out"            # head norm and SiLU(z) gate
GATTN_ATTEND_SCOPE = "bps.gattn.attend"  # around full_attention's own scope
GATTN_PROJ_SCOPE = "bps.gattn.proj"      # projections, norms, rotation, gate

LINEAR, FULL = "linear_attention", "full_attention"

ZeroCentredNorm = partial(RMSNorm, zero_centred=True)


class GatedDeltaNet(nn.Module):
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int = 4
    chunk: int = 64
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        b, s, d_model = x.shape
        f32 = jnp.float32
        keys = self.key_heads * self.key_dim
        values = self.value_heads * self.value_dim
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        qkvz = dense(2 * keys + 2 * values, name="qkvz")(x)
        ba = nn.Dense(2 * self.value_heads, use_bias=False, dtype=f32,
                      precision=jax.lax.Precision.HIGHEST, name="ba")(
                          x.astype(f32))
        conv = self.param("conv", nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=0, out_axis=1),
            (self.conv_kernel, 2 * keys + values), f32)
        a_log = self.param("A_log", _a_log_init, (self.value_heads,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (self.value_heads,),
                             f32)

        # elementwise, and recomputed in the backward pass: what is kept is
        # the projections' output
        @jax.checkpoint
        def prepared(qkv, conv, ba, a_log, dt_bias):
            with jax.named_scope(GDN_PREP_SCOPE):
                mixed = jax.nn.silu(causal_conv(qkv.astype(f32), conv))
                q, k = (mixed[..., i * keys:(i + 1) * keys].reshape(
                    b, s, self.key_heads, self.key_dim) for i in (0, 1))

                def unit(y):
                    return y * jax.lax.rsqrt(
                        (y * y).sum(-1, keepdims=True) + 1e-6)

                g = -jnp.exp(a_log) * jax.nn.softplus(
                    ba[..., self.value_heads:] + dt_bias)
                return (unit(q) * self.key_dim ** -0.5, unit(k),
                        mixed[..., 2 * keys:].reshape(
                            b, s, self.value_heads, self.value_dim), g,
                        jax.nn.sigmoid(ba[..., :self.value_heads]))

        q, k, v, g, beta = prepared(qkvz[..., :2 * keys + values], conv, ba,
                                    a_log, dt_bias)
        if (self.is_mutable_collection("kda_stats")
                and not self.is_initializing()):   # init(): parameters only
            self.sow("kda_stats", "min_chunk_log_decay",
                     chunk_log_decay(g, self.chunk).min())
        # kept when the mixer is recomputed (Qwen3NextBlock), as Kimi-Linear's
        o = checkpoint_name(
            kda_attention(q, k, v, g, beta, chunk=self.chunk, sub=self.chunk,
                          dtype=self.dtype), KDA_SAVED)
        with jax.named_scope(GDN_OUT_SCOPE):
            z = qkvz[..., 2 * keys + values:].astype(f32)
            gated = (RMSNorm(self.eps, name="o_norm")(o).reshape(b, s, values)
                     * jax.nn.silu(z))
        return dense(d_model, name="o")(gated)


class GatedAttention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    rotary_factor: float
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        b, s, d_model = x.shape
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        rotate = partial(_rope, positions=positions, theta=self.rope_theta,
                         rotary_dim=int(self.head_dim * self.rotary_factor))
        with jax.named_scope(GATTN_PROJ_SCOPE):
            q, gate = jnp.split(
                dense(self.heads * self.head_dim * 2, name="q")(x).reshape(
                    b, s, self.heads, 2 * self.head_dim), 2, axis=-1)
            k, v = (dense(self.kv_heads * self.head_dim, name=name)(x)
                    .reshape(b, s, self.kv_heads, self.head_dim)
                    for name in "kv")
            q = rotate(ZeroCentredNorm(self.eps, name="q_norm")(q))
            k = rotate(ZeroCentredNorm(self.eps, name="k_norm")(k))
        with jax.named_scope(GATTN_ATTEND_SCOPE):
            out = full_attention(q, k, v, causal=True,
                                 scale=self.head_dim ** -0.5)
        with jax.named_scope(GATTN_PROJ_SCOPE):
            return dense(d_model, name="o")(
                (out * jax.nn.sigmoid(gate.astype(jnp.float32))).reshape(
                    b, s, self.heads * self.head_dim))


class ExpertSublayer(nn.Module):
    """``(x + y, load_balance)`` of ``(y, load_balance) = f(N(x))``: the
    expert half of a block."""

    make: Callable[[], nn.Module]
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        y, load_balance = self.make()(
            ZeroCentredNorm(self.eps, name="norm")(x))
        return x + y, load_balance


class Qwen3NextBlock(nn.Module):
    """Mixer half, then expert half, each recomputed in the backward pass on
    its own with the chunked scan's output kept (``models/kimi_linear.py::
    KimiBlock`` has the reasons)."""

    mixer: Callable[[], nn.Module]
    ffn: Callable[[], nn.Module]
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        kept = jax.checkpoint_policies.save_only_these_names(KDA_SAVED)
        a = nn.remat(KimiSublayer, policy=kept)(
            self.mixer, self.eps, ZeroCentredNorm, name="mixer")(x)
        return nn.remat(ExpertSublayer, policy=kept)(
            self.ffn, self.eps, name="ffn")(a)


class Qwen3NextModel(nn.Module):
    """Causal LM. ``tokens`` [batch, seq] -> ``(the next-token cross-entropy
    [batch, seq - 1] float32, the layers' mean load-balance loss)``.
    ``layer_kinds``: one of ``"linear_attention"`` / ``"full_attention"`` a
    layer."""

    vocab_size: int
    layer_kinds: Sequence[str]
    d_model: int
    heads: int                    # gated attention's query heads
    kv_heads: int
    head_dim: int
    rope_theta: float
    rotary_factor: float
    key_heads: int                # Gated DeltaNet's
    value_heads: int
    key_dim: int
    value_dim: int
    num_experts: int
    num_local_experts: int
    top_k: int
    mlp_dim: int
    shared_mlp_dim: int
    first_expert: int = 0
    conv_kernel: int = 4
    chunk: int = 64
    loss_rows: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-6

    def setup(self):
        if not set(self.layer_kinds) <= {LINEAR, FULL}:
            raise ValueError(f"layer_kinds are {LINEAR!r} | {FULL!r}, got "
                             f"{tuple(self.layer_kinds)}")
        # unit-variance embeddings: models/keye.py has the reason
        self.embed = nn.Embed(self.vocab_size, self.d_model,
                              embedding_init=nn.initializers.normal(1.0))
        mixers = {
            LINEAR: partial(GatedDeltaNet, self.key_heads, self.value_heads,
                            self.key_dim, self.value_dim, self.conv_kernel,
                            self.chunk, self.dtype, self.eps, name="gdn"),
            FULL: partial(GatedAttention, self.heads, self.kv_heads,
                          self.head_dim, self.rope_theta, self.rotary_factor,
                          self.dtype, self.eps, name="attn")}
        # ``shared`` counts experts' widths: one of shared_mlp_dim
        moe = partial(KimiSparseMoe, self.num_experts, self.num_local_experts,
                      self.first_expert, self.top_k, self.mlp_dim, 1.0,
                      self.shared_mlp_dim // self.mlp_dim, self.dtype,
                      select_bias=False, scoring="softmax", shared_gate=True,
                      aux=True, name="moe")
        for i, kind in enumerate(self.layer_kinds):
            setattr(self, f"layer_{i}",
                    Qwen3NextBlock(mixers[kind], moe, self.eps))
        self.final_norm = ZeroCentredNorm(self.eps)
        self.lm_head = nn.Dense(self.vocab_size, use_bias=False,
                                dtype=self.dtype)

    def __call__(self, tokens):
        x = self.embed(tokens)       # float32 from here on (module docstring)
        load_balance = 0.0
        for i in range(len(self.layer_kinds)):
            x, aux = getattr(self, f"layer_{i}")(x)
            load_balance += aux / len(self.layer_kinds)
        return (next_token_nll(self, self.final_norm(x), tokens, 1),
                load_balance)


def qwen3_next_loss(outputs, *, load_balance_weight: float = 0.001):
    """Mean next-token cross-entropy + 0.001 x the load-balance loss (the
    family's ``router_aux_loss_coef``) over the model's output."""
    nll, load_balance = outputs
    return nll.mean() + load_balance_weight * load_balance


def layer_kinds(full_attention_interval: int, num_layers: int) -> tuple:
    """Every ``full_attention_interval``-th layer is gated attention, the
    rest Gated DeltaNet (the source's ``layer_types`` default)."""
    return tuple(FULL if (i + 1) % full_attention_interval == 0 else LINEAR
                 for i in range(num_layers))


# Tiny is for tests (a share: experts 0..1 of 8; 2 key heads under 4 value
# heads; 4 query heads over 2 key heads, a quarter of each rotated).
# Qwen3Next80BA3B follows Qwen/Qwen3-Next-80B-A3B-Instruct (48 layers, 3 : 1,
# d 2048, Gated DeltaNet 16 x 128 keys under 32 x 128 values, attention 16 /
# 2 x 256 with rotary on 64, 512 experts of width 512, 10 per token, one
# gated shared expert, vocab 151936).
Qwen3NextTiny = partial(
    Qwen3NextModel, vocab_size=512, layer_kinds=layer_kinds(4, 4), d_model=64,
    heads=4, kv_heads=2, head_dim=16, rope_theta=1e7, rotary_factor=0.25,
    key_heads=2, value_heads=4, key_dim=16, value_dim=16, num_experts=8,
    num_local_experts=2, top_k=2, mlp_dim=32, shared_mlp_dim=32, chunk=8,
    loss_rows=32)
Qwen3Next80BA3B = partial(
    Qwen3NextModel, vocab_size=151936, layer_kinds=layer_kinds(4, 48),
    d_model=2048, heads=16, kv_heads=2, head_dim=256, rope_theta=1e7,
    rotary_factor=0.25, key_heads=16, value_heads=32, key_dim=128,
    value_dim=128, num_experts=512, num_local_experts=512, top_k=10,
    mlp_dim=512, shared_mlp_dim=512)
