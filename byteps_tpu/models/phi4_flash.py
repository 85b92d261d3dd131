"""SambaY decoder-hybrid-decoder (Microsoft, "Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation", arXiv
2507.06607; ``microsoft/Phi-4-mini-flash-reasoning``, ``model_type``
phi4flash). Every layer is a mixer and a SwiGLU MLP, each under a LayerNorm
with weight and bias, on a float32 residual stream:

    h <- h + Mixer_l(LN(h));   h <- h + W_2 (up . SiLU(gate)),
    [gate; up] = LN'(h) W_1

with a final LayerNorm and the embedding transposed as the head; **no
positional embedding anywhere**. The mixer turns on the layer's *published*
index ``l`` (``layers`` names them: the whole model is ``range(32)``, a cut
keeps the indices it holds), with ``self_decoder`` = 16 = half the depth the
last layer of the self-decoder's Mamba stack:

**Mamba-1** (Gu & Dao, arXiv 2312.00752; ``l`` even, ``l <= self_decoder``).
``[x; z] = u W_in``; ``x <- SiLU(conv(x) + b)``, causal, depthwise
(``kimi_linear.py::causal_conv``); ``[delta; B_t; C_t] = x W_x``; ``Delta =
softplus(delta W_Delta + b_Delta)`` in float32, a number a channel and
token; ``A = -exp(A_log)`` [d_inner, d_state]; the recurrence is
``parallel/linear_attention.py::selective_scan`` (one decay a channel *and*
state entry). ``y = scan + D x``; output ``W_out (y . SiLU(z))``. Layer
``self_decoder`` also hands on ``m = y``, skip in and gate not yet applied
(what the source's memory is: its scan runs once with ``D`` and no gate for
the memory), rounded to ``dtype``.

**Differential attention** (Ye et al., arXiv 2410.05258; ``l`` odd).
``heads`` query heads over ``kv_heads`` key heads of ``head_dim`` are
``heads / 2`` pairs ``(q1, q2)`` over ``kv_heads / 2`` pairs ``(k1, k2)``;
a key pair's two value heads side by side are one value of ``2 head_dim``.
Pair i reads key pair ``i // (heads / kv_heads)``:

    o_i = (softmax(q1 k1^T / sqrt(head_dim)) - lambda softmax(q2 k2^T / ..))
          [v1; v2]
    lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init(l)
    o_i <- RMSNorm(o_i) w (1 - lambda_init(l))

``lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)`` with the published ``l``. The
columns of the projections are laid out by halves: query heads ``0 ..
heads/2 - 1`` are the pairs' ``q1`` and the rest their ``q2``; key and
value heads likewise — then ``full_attention``'s own grouping (query head
``j`` reads key head ``j // (heads / kv_heads)``) sends ``q1`` of pair i to
``k1`` of pair ``i // ..`` and ``q2`` to ``k2``, and the two maps are two
calls at the model's own widths: queries ``[q1; q2]`` over keys ``[k1;
k2]``, once with values ``[v1; v1]`` and once with ``[v2; v2]``. (Each score
map is computed twice so; one call with values ``2 head_dim`` wide is a
width ``ring_attention.KERNEL_HEAD_DIMS`` does not hold.) ``l <
self_decoder``: the last ``window`` keys. ``l = self_decoder + 1``: every
key at or before the query, **and its K and V are handed on**. ``l >
self_decoder + 1``: **cross** — the layer has ``W_q``, ``W_o``, its own
lambda vectors and sub-norm, and reads the handed K and V, causally.

**Gated Memory Unit** (``l`` even, ``l > self_decoder``): ``W_2 (m .
SiLU(LN(h) W_1))`` with ``m`` layer ``self_decoder``'s.

The layer loop carries ``(h, m, K, V)``. Every half (mixer, MLP) is
recomputed on its own under ``nn.remat`` and keeps its inputs, so ``m``,
``K``, ``V`` are kept once (the same arrays at every reader) and the
cotangents of all readers sum at the one writer; a Mamba mixer also keeps
its scan's output. Precisions are Nemotron-H's: float32 parameters, residual
stream, norms, SiLU, softplus, Delta, decay and state, softmax statistics;
``W_Delta`` in float32 at the highest matmul precision; every other product
``dtype`` (bf16) operands with float32 accumulation. Head and cross-entropy
in blocks of ``loss_rows`` rows over the tied embedding
(``kimi_linear.py::next_token_nll``). The model returns the per-position
cross-entropy [batch, seq - 1]; ``phi4_flash_loss`` is its mean. Apply with
``mutable=["sel_stats"]`` for each Mamba layer's smallest log-decay of a
scan chunk.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from byteps_tpu.models.kimi_linear import (KDA_SAVED, KimiSublayer,
                                           _a_log_init, _dt_bias_init,
                                           causal_conv, next_token_nll)
from byteps_tpu.monitor import metrics
from byteps_tpu.parallel.linear_attention import (SEL_PREP_SCOPE, SEL_STATES,
                                                  sel_chunk_log_decay,
                                                  selective_scan)
from byteps_tpu.parallel.ring_attention import full_attention

SEL_PROJ_SCOPE = "bps.sel.proj"        # in- and out-projection
SEL_OUT_SCOPE = "bps.sel.out"          # D x and the SiLU(z) gate
DATTN_PROJ_SCOPE = "bps.dattn.proj"    # q, k, v and W_o
DATTN_WINDOW_SCOPE = "bps.dattn.window"   # a layer kind's attention calls
DATTN_FULL_SCOPE = "bps.dattn.full"
DATTN_CROSS_SCOPE = "bps.dattn.cross"
DATTN_DIFF_SCOPE = "bps.dattn.diff"    # lambda, the subtraction, sub-norm
GMU_SCOPE = "bps.gmu"
# layers traced into a program, all and by kind
PHI4FLASH_SITES = "bps_phi4flash_sites_total"
MAMBA, WINDOW, FULL, CROSS, GMU = "mamba", "window", "full", "cross", "gmu"


def kind_sites(kind: str) -> str:
    """The counter of one kind's layers."""
    return f"bps_phi4flash_{kind}_sites_total"


def layer_kind(index: int, self_decoder: int) -> str:
    """The mixer of published layer ``index``."""
    if index % 2 == 0:
        return MAMBA if index <= self_decoder else GMU
    if index < self_decoder:
        return WINDOW
    return FULL if index == self_decoder + 1 else CROSS


def lambda_init(index: int) -> float:
    """The Differential Transformer's schedule, at the published index."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


class Mamba1Mixer(nn.Module):
    """``(x) -> (out, y)``: ``y`` [b, s, d_inner] float32 is the scan's
    output with the skip, before the gate."""

    d_inner: int
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        f32 = jnp.float32
        inner, n, rank = self.d_inner, self.d_state, self.dt_rank
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        with jax.named_scope(SEL_PROJ_SCOPE):
            xz = dense(2 * inner, name="in")(x)
        conv = self.param("conv", nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=0, out_axis=1),
            (self.d_conv, inner), f32)
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (inner,),
                               f32)
        w_x = self.param("x_proj", nn.initializers.lecun_normal(),
                         (inner, rank + 2 * n), f32)
        w_dt = self.param("dt_proj", nn.initializers.lecun_normal(),
                          (rank, inner), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,), f32)
        a_log = self.param("A_log", _a_log_init, (inner, n), f32)
        skip = self.param("D", nn.initializers.ones, (inner,), f32)

        # elementwise and two thin products, recomputed in the backward
        # pass: what is kept is the in-projection's output
        @jax.checkpoint
        def prepared(x_in, conv, conv_bias, w_x, w_dt, dt_bias):
            with jax.named_scope(SEL_PREP_SCOPE):
                x_c = causal_conv(x_in, conv, conv_bias, activation="silu")
                dbc = jnp.einsum(
                    "bsc,cr->bsr", x_c.astype(self.dtype),
                    w_x.astype(self.dtype), preferred_element_type=f32)
                step = jax.nn.softplus(jnp.einsum(
                    "bsr,rc->bsc", dbc[..., :rank], w_dt,
                    precision=jax.lax.Precision.HIGHEST) + dt_bias)
                return x_c, step, dbc[..., rank:rank + n], dbc[..., rank + n:]

        x_c, step, b_t, c_t = prepared(xz[..., :inner], conv, conv_bias, w_x,
                                       w_dt, dt_bias)
        a = -jnp.exp(a_log)
        if (self.is_mutable_collection("sel_stats")
                and not self.is_initializing()):   # init(): parameters only
            self.sow("sel_stats", "min_chunk_log_decay",
                     sel_chunk_log_decay(step, a).min())
        # kept when the layer is recomputed, as Kimi-Linear's scan output
        y = checkpoint_name(selective_scan(x_c, step, a, b_t, c_t), KDA_SAVED)
        with jax.named_scope(SEL_OUT_SCOPE):
            y = y + skip * x_c
            gated = (y * jax.nn.silu(xz[..., inner:].astype(f32))).astype(
                self.dtype)
        with jax.named_scope(SEL_PROJ_SCOPE):
            return dense(d_model, name="out")(gated), y


class DifferentialAttention(nn.Module):
    """``(x, k, v) -> (out, k, v)``. ``index``: the published layer index
    (``lambda_init``). ``window``: the keys a query sees, None for all at or
    before it. ``cross``: no ``W_k``, ``W_v``: the ``k``, ``v`` [b, s,
    kv_heads, head_dim] it is given are read; otherwise they are this
    layer's own and are handed back."""

    heads: int
    kv_heads: int
    head_dim: int
    index: int
    window: Optional[int] = None
    cross: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x, k=None, v=None):
        b, s, d_model = x.shape
        f32 = jnp.float32
        pairs, kv_pairs, wide = self.heads // 2, self.kv_heads // 2, \
            2 * self.head_dim
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        with jax.named_scope(DATTN_PROJ_SCOPE):
            q = dense(self.heads * self.head_dim, name="q")(x).reshape(
                b, s, self.heads, self.head_dim)
            if not self.cross:
                k, v = (dense(self.kv_heads * self.head_dim, name=name)(x)
                        .reshape(b, s, self.kv_heads, self.head_dim)
                        for name in "kv")
        lam = [self.param(f"lambda_{name}", nn.initializers.normal(0.1),
                          (self.head_dim,), f32)
               for name in ("q1", "k1", "q2", "k2")]
        subln = self.param("subln", nn.initializers.ones, (wide,), f32)
        scope = (DATTN_CROSS_SCOPE if self.cross else DATTN_FULL_SCOPE
                 if self.window is None else DATTN_WINDOW_SCOPE)
        with jax.named_scope(scope):
            # the two maps over [v1; v1], then over [v2; v2] (module
            # docstring): heads 0 .. pairs - 1 of each are map 1's
            halves = [full_attention(
                q, k, jnp.concatenate([part, part], axis=2), causal=True,
                scale=self.head_dim ** -0.5, window=self.window)
                for part in (v[:, :, :kv_pairs], v[:, :, kv_pairs:])]
        with jax.named_scope(DATTN_DIFF_SCOPE):
            init = lambda_init(self.index)
            lam = (jnp.exp(jnp.sum(lam[0] * lam[1]))
                   - jnp.exp(jnp.sum(lam[2] * lam[3])) + init)
            first, second = (jnp.concatenate(
                [half[:, :, rows].astype(f32) for half in halves], axis=-1)
                for rows in (slice(0, pairs), slice(pairs, None)))
            o = first - lam * second                # [b, s, pairs, wide]
            o = (o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                   + self.eps)) * (subln * (1.0 - init))
            o = o.astype(self.dtype).reshape(b, s, pairs * wide)
        with jax.named_scope(DATTN_PROJ_SCOPE):
            return dense(d_model, name="o")(o), k, v


class GatedMemoryUnit(nn.Module):
    """``W_2 (m . SiLU(x W_1))``: ``m`` [b, s, d_inner] the handed memory."""

    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, m):
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        with jax.named_scope(GMU_SCOPE):
            gate = jax.nn.silu(dense(m.shape[-1], name="in")(x).astype(
                jnp.float32))
            return dense(x.shape[-1], name="out")(
                (m.astype(jnp.float32) * gate).astype(self.dtype))


class SwiGLU(nn.Module):
    """``W_2 (up . SiLU(gate))``, ``[gate; up] = x W_1``: the source's one
    fused matrix; SiLU and the product on the projection's ``dtype`` output,
    as ``llama.py::LlamaMLP`` (widened first, the compiler keeps [s, 2
    mlp_dim] in float32: 1.3 GB a layer at s 16,384)."""

    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        gate_up = dense(2 * self.mlp_dim, name="gate_up")(x)
        return dense(x.shape[-1], name="down")(
            gate_up[..., self.mlp_dim:] * nn.silu(gate_up[..., :self.mlp_dim]))


class MixerHalf(nn.Module):
    """``h + Mixer(LN(h))`` with the handed tensors: ``(h, m, k, v) -> (h,
    m, k, v)``, and the unit of recomputation. The one writer of ``m``
    (``hands_on`` on a Mamba layer) and of ``k``, ``v`` (the full-attention
    layer) replace them, in the compute dtype; everyone else passes them
    through."""

    make: Callable[[], nn.Module]
    kind: str
    hands_on: bool = False
    eps: float = 1e-5

    @nn.compact
    def __call__(self, h, m, k, v):
        x = nn.LayerNorm(self.eps, name="norm")(h)
        mixer = self.make()
        if self.kind == MAMBA:
            out, y = mixer(x)
            if self.hands_on:
                m = y.astype(mixer.dtype)
        elif self.kind == GMU:
            out = mixer(x, m)
        elif self.kind == CROSS:
            out = mixer(x, k, v)[0]
        else:
            out, k_own, v_own = mixer(x)
            if self.hands_on:
                k, v = k_own, v_own
        return h + out, m, k, v


class Phi4FlashModel(nn.Module):
    """Causal LM. ``tokens`` [batch, seq] -> the next-token cross-entropy
    [batch, seq - 1], float32. ``layers``: the published indices of the
    layers held, in order (the whole model: ``range(num_layers)``);
    ``num_layers`` the published depth, whose half is the last Mamba
    layer."""

    vocab_size: int
    layers: Sequence[int]
    num_layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    mlp_dim: int
    window: int
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    loss_rows: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-5

    def setup(self):
        held = tuple(self.layers)
        half = self.num_layers // 2          # the self-decoder's last Mamba
        kinds = [layer_kind(i, half) for i in held]
        if (not held or list(held) != sorted(set(held))
                or not 0 <= held[0] <= held[-1] < self.num_layers):
            raise ValueError(f"layers are published indices below "
                             f"{self.num_layers}, ascending; got {held}")
        for reader, writer in ((GMU, half), (CROSS, half + 1)):
            if reader in kinds and writer not in held:
                raise ValueError(f"a {reader} layer reads what layer "
                                 f"{writer} hands on; layers {held}")
        if self.heads % 2 or self.kv_heads % 2 or \
                (self.heads // 2) % (self.kv_heads // 2):
            raise ValueError("differential attention pairs its heads: "
                             f"{self.heads} over {self.kv_heads}")
        inner = self.expand * self.d_model
        # unit-variance embeddings: models/keye.py has the reason; the rows
        # are the head's too
        self.embed = nn.Embed(self.vocab_size, self.d_model,
                              embedding_init=nn.initializers.normal(1.0))
        attention = partial(DifferentialAttention, self.heads, self.kv_heads,
                            self.head_dim, dtype=self.dtype, eps=self.eps,
                            name="attn")
        mixers = {
            MAMBA: lambda i: partial(
                Mamba1Mixer, inner, self.d_state, self.d_conv,
                -(-self.d_model // 16), self.dtype, name="ssm"),
            GMU: lambda i: partial(GatedMemoryUnit, self.dtype, name="gmu"),
            WINDOW: lambda i: partial(attention, index=i,
                                      window=self.window),
            FULL: lambda i: partial(attention, index=i),
            CROSS: lambda i: partial(attention, index=i, cross=True)}
        # a half is the unit of recomputation; a Mamba scan's output and the
        # states it keeps (one a chunk) are kept, so that recomputing the
        # half runs no scan again
        mixer_half = nn.remat(MixerHalf, policy=(
            jax.checkpoint_policies.save_only_these_names(KDA_SAVED,
                                                          SEL_STATES)))
        mlp_half = nn.remat(KimiSublayer)
        for i, kind in zip(held, kinds):
            setattr(self, f"layer_{i}_mixer", mixer_half(
                mixers[kind](i), kind, i in (half, half + 1), self.eps))
            setattr(self, f"layer_{i}_mlp", mlp_half(
                partial(SwiGLU, self.mlp_dim, self.dtype, name="mlp"),
                self.eps, nn.LayerNorm))
        self.kinds = tuple(kinds)
        # the scale from ln(vocab) / d and not from 1: models/zaya.py's
        # final norm has the reason (a tied unit-variance embedding)
        self.final_norm = nn.LayerNorm(
            self.eps, scale_init=nn.initializers.constant(
                math.log(self.vocab_size) / self.d_model))

    def lm_head(self, h):
        """The embedding transposed: ``dtype`` operands, float32 logits."""
        return jnp.einsum("rd,vd->rv", h.astype(self.dtype),
                          self.embed.embedding.astype(self.dtype),
                          preferred_element_type=jnp.float32)

    def __call__(self, tokens):
        h = self.embed(tokens)       # float32 from here on (module docstring)
        m = k = v = None
        for i, kind in zip(self.layers, self.kinds):
            metrics.inc_counter(PHI4FLASH_SITES)
            metrics.inc_counter(kind_sites(kind))
            h, m, k, v = getattr(self, f"layer_{i}_mixer")(h, m, k, v)
            h = getattr(self, f"layer_{i}_mlp")(h)
        return next_token_nll(self, self.final_norm(h), tokens, 1)


def phi4_flash_loss(nll: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy over the model's output."""
    return nll.mean()


# Tiny is for tests: published depth 8 (Mamba 0 2 4, windowed 1 3, full 5,
# GMU 6, cross 7), 4 query heads over 2 key heads of 8 — one key pair under
# two pairs — a window of 8. Phi4MiniFlash follows
# microsoft/Phi-4-mini-flash-reasoning (32 layers: Mamba-1 9, windowed 8,
# full 1, GMU 7, cross 7; d 2560, 40 / 20 heads of 64, window 512, SwiGLU
# 10240, Mamba d_inner 5120, d_state 16, d_conv 4, dt_rank 160, tied vocab
# 200064).
Phi4FlashTiny = partial(
    Phi4FlashModel, vocab_size=512, layers=tuple(range(8)), num_layers=8,
    d_model=64, heads=4, kv_heads=2, head_dim=8, mlp_dim=96, window=8,
    d_state=4, loss_rows=32)
Phi4MiniFlash = partial(
    Phi4FlashModel, vocab_size=200064, layers=tuple(range(32)),
    num_layers=32, d_model=2560, heads=40, kv_heads=20, head_dim=64,
    mlp_dim=10240, window=512)
