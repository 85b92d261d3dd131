"""ZAYA1-family decoder (``Zyphra/ZAYA1-8B``, ``model_type`` zaya): every
layer (``hybrid``) an attention sublayer then an expert sublayer, pre-norm,
under learned residual scaling (``scale_residual_merge``; the ZAYA1 report,
arXiv 2511.17127),

    a = (s1 * x + b1) + (u1 * CCA(N1(x)) + c1)
    y = (s2 * a + b2) + (u2 * MoE(N2(a), r_prev) + c2),   r to the next block

``s``, ``u`` [D] from 1, ``b``, ``c`` [D] from 0, float32. ``N`` is RMSNorm,
``x rsqrt(mean x^2 + eps) w``, ``w`` from 1 (``models/llama.py::RMSNorm``).
One final ``N`` before the head (its ``w`` from ln(vocab) / d here: ``setup``
has the reason); the head is the embedding transposed
(``tie_word_embeddings``). No biases in the linear projections.

**CCA, grouped** (compressed convolutional attention, arXiv 2510.04476:
CCGQA). ``heads`` query heads over ``kv_heads`` key heads of ``head_dim``,
group ``G = heads / kv_heads`` (query head i reads key head ``i // G``);
``h = N1(x)`` [S, D]:

    q~ = h W_q  [S, heads d]         k~ = h W_k  [S, kv_heads d]
    v  = [ h W_v1 ; shift(h W_v2) ]  the first half of the key heads carry
         the current token's value, the second half the PREVIOUS token's
         (``shift``: one token later, a zero at token 0; without a bias
         ``shift(h) W = shift(h W)``, and the narrow side is shifted)
    z  = conv1(conv0([q~ ; k~]))     over the heads d + kv_heads d channels,
         both causal (zeros before the sequence): conv0 depthwise,
         ``conv0_taps`` taps a channel (``kimi_linear.causal_conv``); conv1
         grouped by head, ``conv1_taps`` taps, each a [d, d] matrix a head
         (``grouped_causal_conv``); each with a bias a channel, from 0
    m_q[i] = (q~[i] + k~[i // G]) / 2
    m_k[j] = (mean_{i // G = j} q~[i] + k~[j]) / 2     the q-k mean, of the
         projections BEFORE the convolutions
    q = z_q + m_q                    k = z_k + m_k
    q^ = sqrt(d) q / |q|             k^ = sqrt(d) tau_j k / |k|
         per head over its d, float32 (``x rsqrt(sum x^2 + 1e-12)``);
         ``tau_j = exp(theta_j)``, theta [kv_heads] from 0, the learned
         temperature on the keys
    rotary at ``rope_theta`` on the first ``rotary_factor d`` entries, half
         against half (``models/llama.py::_rope``), on q^ and k^
    o = causal softmax(q^ k^T / sqrt(d)) v     IN THE LATENT: heads d wide,
         float32 logits and statistics (``parallel.full_attention``: on the
         chip the flash kernels 128 wide, grouped)
    CCA(h) = o W_o   [heads d -> D]

Nothing is expanded to the model's width before the product: queries at
half the model's width, keys and values at an eighth; that is the mechanism.

**Expert sublayer**, ``g = N2(a)``. The router is a network with a state
``r`` [R] carried from block to block (exponential depth averaging):

    r = g W_down (+ gamma * r_prev; nothing is added in the first block)
    l = W3 gelu(W2 gelu(W1 r + beta1) + beta2)        [R -> R -> R -> E]
    p = softmax(l) over all E;  e* = argmax(p + bal);  weight p[e*]

``gamma`` [R] from 0, gelu exact (erf), ``bal`` [E] the balancing bias: it
chooses and never weighs, no loss reaches it, and here it stays where it was
initialised (0): the family moves it outside the gradient. The chosen
probability is NOT renormalised (a renormalised top-1 weight is 1 and gives
the router no gradient). ``r`` after the mix and before the MLP is what the
next block receives. The experts are SwiGLU, ``mlp_dim`` wide, no shared
expert, dropless over the held share (``num_local_experts`` from
``first_expert``) through ``parallel/moe.py::dropless_moe_ffn``, which takes
the logits from here. No auxiliary loss. The family's training configs
carry a mixture-of-depths flag; no key of this model's config gives the
router one output more or a skipped expert, and none is built.

float32 parameters, residual stream, norms, router (at the highest matmul
precision), normalisation, temperature and rotation; ``dtype`` (bf16) matmul
operands with float32 accumulation elsewhere, the grouped convolution among
them. Each half of a block is recomputed in the backward pass (``nn.remat``;
``r`` is an input and an output of the second half), and so is every block
of ``loss_rows`` rows of the head and the cross-entropy
(``kimi_linear.next_token_nll``). The model returns the per-position
cross-entropy [batch, seq - 1]; ``zaya_loss`` is its mean. Apply with
``mutable=["moe_stats"]`` for the per-expert counts.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from byteps_tpu.models.kimi_linear import causal_conv, next_token_nll
from byteps_tpu.models.llama import RMSNorm, _rope
from byteps_tpu.monitor import metrics
from byteps_tpu.parallel.moe import dropless_moe_ffn
from byteps_tpu.parallel.ring_attention import full_attention

CCA_PROJ_SCOPE = "bps.cca.proj"      # W_q, W_k, W_v1, W_v2, W_o
# both convolutions, the mean, the value shift, normalisation, temperature,
# rotation
CCA_MIX_SCOPE = "bps.cca.mix"
CCA_ATTEND_SCOPE = "bps.cca.attend"  # around full_attention's own scope
# down-projection, depth averaging, MLP: ahead of the expert layer's
# ``bps.moe.route``, whose name this one begins with (benchmark/layers/
# zmoe.py takes it off the route scope's time)
ROUTER_SCOPE = "bps.moe.router"

CCA_SITES = "bps_cca_sites_total"    # counted at trace time, as the kernel's

HIGHEST = jax.lax.Precision.HIGHEST


def shift_tokens(x):
    """[b, s, ...] one token later: row t holds row t - 1, row 0 zeros."""
    return jnp.pad(x[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))


def grouped_causal_conv(x, w, dtype):
    """Causal convolution that mixes the channels within a head: x [b, s,
    heads, d] float32, w [taps, heads, d, d]; ``y_t = sum_i x_{t - taps + 1
    + i} w[i]`` per head, zeros before the sequence: ``taps`` batched
    products (``dtype`` operands, float32 accumulation) of shifted rows, as
    ``kimi_linear.causal_conv`` shifts them."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(dtype),
                     ((0, 0), (taps - 1, 0), (0, 0), (0, 0)))
    return sum(jnp.einsum("bshc,hcd->bshd", padded[:, i:i + s],
                          w[i].astype(dtype),
                          preferred_element_type=jnp.float32)
               for i in range(taps))


def _unit(x):
    """x / |x| over the last axis, float32."""
    return x * jax.lax.rsqrt((x * x).sum(axis=-1, keepdims=True) + 1e-12)


class CompressedConvAttention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    rotary_factor: float
    conv0_taps: int = 2
    conv1_taps: int = 2
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, d_model = x.shape
        f32 = jnp.float32
        heads, kv, d = self.heads, self.kv_heads, self.head_dim
        if heads % kv or kv % 2:
            raise ValueError(f"{heads} query heads over {kv} key heads: the "
                             "queries divide over the keys, and the keys "
                             "into current and shifted values")
        group, channels = heads // kv, (heads + kv) * d
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        conv0 = self.param("conv0", nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=0, out_axis=1),
            (self.conv0_taps, channels), f32)
        conv0_bias = self.param("conv0_bias", nn.initializers.zeros,
                                (channels,), f32)
        # variance 1 / (taps x fan-in): taps and inputs both count as fan-in
        conv1 = self.param("conv1", nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=(0, 2), out_axis=3,
            batch_axis=1), (self.conv1_taps, heads + kv, d, d), f32)
        conv1_bias = self.param("conv1_bias", nn.initializers.zeros,
                                (heads + kv, d), f32)
        theta = self.param("temperature", nn.initializers.zeros, (kv,), f32)
        metrics.inc_counter(CCA_SITES)

        with jax.named_scope(CCA_PROJ_SCOPE):
            q_in = dense(heads * d, name="q")(x)
            k_in = dense(kv * d, name="k")(x)
            v_now = dense(kv // 2 * d, name="v1")(x)
            v_before = dense(kv // 2 * d, name="v2")(x)
        with jax.named_scope(CCA_MIX_SCOPE):
            v = jnp.concatenate([v_now, shift_tokens(v_before)],
                                axis=-1).reshape(b, s, kv, d)
            q_in, k_in = q_in.astype(f32), k_in.astype(f32)
            z = (causal_conv(jnp.concatenate([q_in, k_in], axis=-1), conv0)
                 + conv0_bias).reshape(b, s, heads + kv, d)
            z = grouped_causal_conv(z, conv1, self.dtype) + conv1_bias
            q_in = q_in.reshape(b, s, kv, group, d)
            k_in = k_in.reshape(b, s, kv, d)
            q = (z[:, :, :heads].reshape(b, s, kv, group, d)
                 + 0.5 * (q_in + k_in[:, :, :, None])).reshape(
                     b, s, heads, d)
            k = z[:, :, heads:] + 0.5 * (q_in.mean(axis=3) + k_in)
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
            rotate = partial(_rope, positions=positions,
                             theta=self.rope_theta,
                             rotary_dim=int(d * self.rotary_factor))
            q = rotate(d ** 0.5 * _unit(q)).astype(self.dtype)
            k = rotate(d ** 0.5 * jnp.exp(theta)[:, None]
                       * _unit(k)).astype(self.dtype)
        with jax.named_scope(CCA_ATTEND_SCOPE):
            out = full_attention(q, k, v, causal=True, scale=d ** -0.5)
        with jax.named_scope(CCA_PROJ_SCOPE):
            return dense(d_model, name="o")(out.reshape(b, s, heads * d))


class ZayaRouter(nn.Module):
    """``(logits [b, s, E], r [b, s, R])`` of ``g`` [b, s, D] and the block
    before's ``r`` (None in the first block), float32 at the highest matmul
    precision: which expert a token reaches must not turn on bf16
    rounding."""

    hidden: int
    num_experts: int

    @nn.compact
    def __call__(self, g, r_prev=None):
        dense = partial(nn.Dense, dtype=jnp.float32, precision=HIGHEST)
        with jax.named_scope(ROUTER_SCOPE):
            r = dense(self.hidden, use_bias=False, name="down")(
                g.astype(jnp.float32))
            if r_prev is not None:
                r = r + r_prev * self.param(
                    "depth_decay", nn.initializers.zeros, (self.hidden,),
                    jnp.float32)
            hidden = r
            for name in ("mlp_1", "mlp_2"):
                hidden = jax.nn.gelu(dense(self.hidden, name=name)(hidden),
                                     approximate=False)
            return dense(self.num_experts, use_bias=False,
                         name="mlp_3")(hidden), r


class ZayaSparseMoe(nn.Module):
    """``(MoE(g, r_prev), r)``: the router above, top-1 by ``argmax(p +
    bal)`` with the chosen probability as the weight, the SwiGLU experts
    ``first_expert .. first_expert + num_local_experts - 1`` held here."""

    num_experts: int
    num_local_experts: int
    first_expert: int
    mlp_dim: int
    router_hidden: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, g, r_prev=None):
        b, s, d = g.shape
        held, m = self.num_local_experts, self.mlp_dim
        logits, r = ZayaRouter(self.router_hidden, self.num_experts,
                               name="router")(g, r_prev)
        # fan-in scaling per expert: axis 0 counts experts, not inputs
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=0)
        y, _, _, counts = dropless_moe_ffn(
            g.reshape(b * s, d), None,
            self.param("gate", init, (held, d, m), jnp.float32),
            self.param("up", init, (held, d, m), jnp.float32),
            self.param("down", init, (held, m, d), jnp.float32),
            top_k=1, dtype=self.dtype, first_expert=self.first_expert,
            select_bias=jax.lax.stop_gradient(self.param(
                "select_bias", nn.initializers.zeros, (self.num_experts,),
                jnp.float32)),
            logits=logits.reshape(b * s, self.num_experts))
        if not self.is_initializing():
            self.sow("moe_stats", "counts", counts)
        return y.reshape(b, s, d), r


class ScaledResidual(nn.Module):
    """``(s * x + b) + (u * y + c)``: the residual merge with its four
    learned vectors, float32."""

    @nn.compact
    def __call__(self, x, y):
        d = x.shape[-1]
        s, u = (self.param(name, nn.initializers.ones, (d,), jnp.float32)
                for name in ("stream_scale", "branch_scale"))
        b, c = (self.param(name, nn.initializers.zeros, (d,), jnp.float32)
                for name in ("stream_bias", "branch_bias"))
        return (s * x + b) + (u * y.astype(jnp.float32) + c)


class ZayaMixerHalf(nn.Module):
    mixer: Callable[[], nn.Module]
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        return ScaledResidual(name="merge")(
            x, self.mixer()(RMSNorm(self.eps, name="norm")(x)))


class ZayaExpertHalf(nn.Module):
    ffn: Callable[[], nn.Module]
    eps: float = 1e-5

    @nn.compact
    def __call__(self, a, r_prev=None):
        y, r = self.ffn()(RMSNorm(self.eps, name="norm")(a), r_prev)
        return ScaledResidual(name="merge")(a, y), r


class ZayaBlock(nn.Module):
    """``(y, r)`` of ``x`` and the block before's router state (None in the
    first): mixer half, then expert half, each recomputed in the backward
    pass on its own (``models/kimi_linear.py::KimiBlock`` has the reasons);
    what is kept is each half's input, ``r_prev`` among the second's."""

    mixer: Callable[[], nn.Module]
    ffn: Callable[[], nn.Module]
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x, r_prev=None):
        a = nn.remat(ZayaMixerHalf)(self.mixer, self.eps, name="mixer")(x)
        half = nn.remat(ZayaExpertHalf)(self.ffn, self.eps, name="ffn")
        return half(a) if r_prev is None else half(a, r_prev)


class ZayaModel(nn.Module):
    """Causal LM. ``tokens`` [batch, seq] -> the next-token cross-entropy
    [batch, seq - 1], float32."""

    vocab_size: int
    num_layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    rotary_factor: float
    router_hidden: int
    num_experts: int
    num_local_experts: int
    mlp_dim: int
    first_expert: int = 0
    conv0_taps: int = 2
    conv1_taps: int = 2
    loss_rows: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-5

    def setup(self):
        # unit-variance embeddings: models/keye.py has the reason; the rows
        # are the head's too
        self.embed = nn.Embed(self.vocab_size, self.d_model,
                              embedding_init=nn.initializers.normal(1.0))
        mixer = partial(CompressedConvAttention, self.heads, self.kv_heads,
                        self.head_dim, self.rope_theta, self.rotary_factor,
                        self.conv0_taps, self.conv1_taps, self.dtype,
                        name="cca")
        moe = partial(ZayaSparseMoe, self.num_experts,
                      self.num_local_experts, self.first_expert, self.mlp_dim,
                      self.router_hidden, self.dtype, name="moe")
        for i in range(self.num_layers):
            setattr(self, f"layer_{i}", ZayaBlock(mixer, moe, self.eps))
        # from ln(vocab) / d and not from 1: a normalised row meets the
        # unit-variance rows of the tied embedding, and a token's own row
        # (the stream is the token's own at initialisation) then reads about
        # ln(vocab), the others 45 times less; from 1 it would read d = 2048
        # and from d^-1/2, the picture the source's initialisation gives
        # (rows of std 0.02), 45: a loss that multiplies a relative error
        # in the stream's norm by 45, which two bf16 implementations differ
        # in by 4e-5 (PERF.md section 6, PR 55)
        self.final_norm = RMSNorm(
            self.eps, initial=math.log(self.vocab_size) / self.d_model)

    def lm_head(self, h):
        """The embedding transposed: ``dtype`` operands, float32 logits (a
        token's own row reads about ln(vocab) at initialisation and half
        the probability: rounded to bf16 it would move a row's loss by
        1e-2)."""
        return jnp.einsum("rd,vd->rv", h.astype(self.dtype),
                          self.embed.embedding.astype(self.dtype),
                          preferred_element_type=jnp.float32)

    def __call__(self, tokens):
        x = self.embed(tokens)       # float32 from here on (module docstring)
        r: Optional[jax.Array] = None
        for i in range(self.num_layers):
            x, r = getattr(self, f"layer_{i}")(x, r)
        return next_token_nll(self, self.final_norm(x), tokens, 1)


def zaya_loss(nll: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy over the model's output. No auxiliary
    loss: the family balances its experts through the balancing bias."""
    return nll.mean()


# Tiny is for tests (a share: experts 0..1 of 4; 4 query heads over 2 key
# heads of 16 in a latent 64 / 32 wide). Zaya1_8B follows Zyphra/ZAYA1-8B
# (40 layers, d 2048, 8 query heads over 2 key heads of 128, two taps in
# each convolution, rotary on 64 of the 128 at theta 5e6, a router 256 wide
# over 16 experts of width 2048, top-1, tied vocabulary of 262,272).
ZayaTiny = partial(
    ZayaModel, vocab_size=512, num_layers=5, d_model=64, heads=4, kv_heads=2,
    head_dim=16, rope_theta=5e6, rotary_factor=0.5, router_hidden=16,
    num_experts=4, num_local_experts=2, mlp_dim=32, loss_rows=32)
Zaya1_8B = partial(
    ZayaModel, vocab_size=262272, num_layers=40, d_model=2048, heads=8,
    kv_heads=2, head_dim=128, rope_theta=5e6, rotary_factor=0.5,
    router_hidden=256, num_experts=16, num_local_experts=16, mlp_dim=2048)
