"""Kimi-Linear-family hybrid decoder (Moonshot AI 2025, Kimi Linear, arXiv
2510.26692; ``moonshotai/Kimi-Linear-48B-A3B-Instruct``): layers of two
kinds of token mixer under one pre-norm block,

    a = x + Mix(N1(x)),    y = a + FFN(N2(a)),

three of gated delta-rule linear attention (KDA) to one of latent
attention (MLA) without position embedding, and DeepSeek-V3's expert layer
after ``first_dense`` leading layers with a dense SwiGLU.

**KDA** (``parallel/linear_attention.py``; ``heads`` heads of ``head_dim``
keys and values). ``q = l2norm(silu(conv4(h Wq))) head_dim^-1/2``, ``k`` the
same without the scale, ``v = silu(conv4(h Wv))``; ``conv4`` a causal
depthwise convolution of ``conv_kernel`` taps over the channels. The
log-decay, per channel, ``g = -exp(A_log[head]) softplus((h Wf1) Wf2 +
dt_bias)``; the write strength ``beta = sigmoid(h Wb)`` per head; the
output ``(RMSNorm_head(o) * sigmoid((h Wg1) Wg2 + bg)) Wo``. Position
reaches the model through the decay and the convolutions alone.

**MLA, NoPE**. ``q = h Wq`` [heads, nope + rope]; ``c = h Wkva``; its first
``kv_rank`` entries, RMS-normalised, expand to each head's ``k_nope`` and
``v``; the last ``rope`` are one key part shared by all heads — with no
rotary embedding on them (``mla_use_nope``). Causal softmax over keys as
wide as ``nope + rope`` and values as wide as ``v_dim``
(``parallel.full_attention``: on the chip the flash kernel at two widths).
``KimiLatentAttention`` is DeepSeek-V3's layer whole: with ``q_rank`` the
queries go through a normalised latent of their own, and with
``rope_theta`` the ``rope`` parts are rotated (``models/joyai.py`` sets
both; here both are off).

**Expert layer**. ``dropless_moe_ffn`` with sigmoid scores, a selection
bias that chooses and never weighs, the chosen weights renormalised (+
1e-20) and scaled by ``routed_scale``, over the held share of the experts
(``num_local_experts`` from ``first_expert``), plus a shared expert every
token passes through, computed whole on every chip. The bias is a parameter
no loss reaches: the family moves it outside the gradient, by a rule of
speed it does not publish; here it stays where it was initialised (zero),
its gradient leaf is zero and adamw's decay of a zero is zero.

float32 parameters, residual stream, norms, gates' nonlinearities, decay and
state; ``dtype`` (bf16) matmul operands with float32 accumulation; the
decay's two projections and the router in float32 at the highest matmul
precision (a decay is cumulated over thousands of tokens, a router's
rounding changes which experts a token reaches). Each half of a block is
recomputed in the backward pass (``nn.remat``), and so is every block of
``loss_rows`` rows of the head and the cross-entropy (``next_token_nll``,
which ``models/joyai.py`` calls for two streams): one block's [rows,
vocab] float32 logits are alive at a time. The model returns the per-position
cross-entropy [batch, seq - 1]; ``kimi_linear_loss`` is its mean. Apply
with ``mutable=["moe_stats", "kda_stats"]`` for the per-expert counts and
each KDA layer's most negative cumulated log-decay of a chunk
(``publish_moe_stats``, ``publish_kda_stats``), and pay nothing otherwise.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from byteps_tpu.models.llama import LlamaMLP, RMSNorm, _rope
from byteps_tpu.parallel.linear_attention import (PREP_SCOPE, chunk_log_decay,
                                                  kda_attention)
from byteps_tpu.parallel.moe import dropless_moe_ffn
from byteps_tpu.parallel.ring_attention import full_attention

KDA_OUT_SCOPE = "bps.kda.out"          # head norm and output gate
MLA_ATTEND_SCOPE = "bps.mla.attend"    # around full_attention's own scope
MLA_PROJ_SCOPE = "bps.mla.proj"        # projections, latent norms, rotation
SHARED_SCOPE = "bps.moe.shared"        # the shared expert
HEAD_SCOPE = "bps.lm.head"             # head and cross-entropy, row blocks

KDA_SAVED = "kda_scan_out"              # checkpoint_name of the scan's output

KDA, MLA = "kda", "mla"


def _a_log_init(key, shape, dtype=jnp.float32):
    """log U(1, 16): Mamba's rule for the decay's rate."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of dt, log-uniform over (1e-3, 1e-1): Mamba's
    rule for the decay's step."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, np.log(1e-3),
                                    np.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def causal_conv(x, w, bias=None):
    """Depthwise causal convolution: x [b, s, channels], w [taps,
    channels]; ``y_t = sum_i w[i] x_{t - taps + 1 + i}``, zeros before the
    sequence, plus ``bias`` [channels] where one is given."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, i:i + s] * w[i] for i in range(taps))
    return y if bias is None else y + bias


class KimiDeltaAttention(nn.Module):
    heads: int
    head_dim: int
    gate_rank: int
    conv_kernel: int = 4
    chunk: int = 64
    sub_chunk: int = 16
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        b, s, d_model = x.shape
        width = self.heads * self.head_dim
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        exact = partial(nn.Dense, use_bias=False, dtype=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
        f32 = jnp.float32
        conv_init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=0, out_axis=1)

        projected = [dense(width, name=name)(x) for name in "qkv"]
        convs = [self.param(name + "_conv", conv_init,
                            (self.conv_kernel, width), f32) for name in "qkv"]
        decay_in = exact(width, name="f_b")(
            exact(self.gate_rank, name="f_a")(x.astype(f32)))
        beta_in = dense(self.heads, name="beta")(x)
        gate_in = nn.Dense(width, dtype=self.dtype, name="g_b")(
            dense(self.gate_rank, name="g_a")(x))
        a_log = self.param("A_log", _a_log_init, (self.heads,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (width,), f32)

        # elementwise, and recomputed in the backward pass: what is kept is
        # the projections' output, not eight float32 [s, width] tensors
        @jax.checkpoint
        def prepared(projected, convs, decay_in, beta_in, a_log, dt_bias):
            with jax.named_scope(PREP_SCOPE):
                q, k, v = (
                    jax.nn.silu(causal_conv(y.astype(f32), w)).reshape(
                        b, s, self.heads, self.head_dim)
                    for y, w in zip(projected, convs))

                def unit(y):
                    return y * jax.lax.rsqrt(
                        (y * y).sum(-1, keepdims=True) + 1e-6)

                g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                    decay_in + dt_bias).reshape(
                        b, s, self.heads, self.head_dim)
                return (unit(q) * self.head_dim ** -0.5, unit(k), v, g,
                        jax.nn.sigmoid(beta_in.astype(f32)))

        q, k, v, g, beta = prepared(projected, convs, decay_in, beta_in,
                                    a_log, dt_bias)
        if (self.is_mutable_collection("kda_stats")
                and not self.is_initializing()):   # init(): parameters only
            self.sow("kda_stats", "min_chunk_log_decay",
                     chunk_log_decay(g, self.chunk).min())
        # kept when the mixer is recomputed (KimiBlock): the scan's backward
        # pass needs its inputs, not a second forward pass for this
        o = checkpoint_name(
            kda_attention(q, k, v, g, beta, chunk=self.chunk,
                          sub=self.sub_chunk, dtype=self.dtype), KDA_SAVED)
        # per head over its head_dim, one learned scale for all heads
        with jax.named_scope(KDA_OUT_SCOPE):
            gated = (RMSNorm(self.eps, name="o_norm")(o).reshape(b, s, width)
                     * jax.nn.sigmoid(gate_in.astype(f32)))
        return dense(d_model, name="o")(gated)


class KimiLatentAttention(nn.Module):
    """DeepSeek-V3's latent attention. ``q_rank``: the queries go through a
    ``q_rank``-wide RMS-normalised latent (``q_a``, ``q_norm``, ``q_b``)
    and not one projection ``q``. ``rope_theta``: the last ``rope_dim`` of
    every query and the shared key part carry a rotary embedding of the
    row's own position, in interleaved pairs (``models/llama.py::_rope``,
    float32). Both None is Kimi-Linear's layer."""

    heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-5
    q_rank: Optional[int] = None
    rope_theta: Optional[float] = None

    @nn.compact
    def __call__(self, x):
        b, s, d_model = x.shape
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        qk_dim = self.nope_dim + self.rope_dim
        with jax.named_scope(MLA_PROJ_SCOPE):
            if self.q_rank is None:
                q = dense(self.heads * qk_dim, name="q")(x)
            else:
                q = dense(self.heads * qk_dim, name="q_b")(
                    RMSNorm(self.eps, name="q_norm")(
                        dense(self.q_rank, name="q_a")(x)))
            q = q.reshape(b, s, self.heads, qk_dim)
            c = dense(self.kv_rank + self.rope_dim, name="kv_a")(x)
            shared = c[:, :, None, self.kv_rank:]
            if self.rope_theta is not None:
                positions = jnp.broadcast_to(jnp.arange(s), (b, s))
                rotate = partial(_rope, positions=positions,
                                 theta=self.rope_theta, interleaved=True)
                q = jnp.concatenate([q[..., :self.nope_dim],
                                     rotate(q[..., self.nope_dim:])], axis=-1)
                shared = rotate(shared)
            shared = jnp.broadcast_to(shared,
                                      (b, s, self.heads, self.rope_dim))
            kv = dense(self.heads * (self.nope_dim + self.v_dim),
                       name="kv_b")(
                RMSNorm(self.eps, name="kv_norm")(c[..., :self.kv_rank])
            ).reshape(b, s, self.heads, self.nope_dim + self.v_dim)
            k = jnp.concatenate([kv[..., :self.nope_dim], shared], axis=-1)
        with jax.named_scope(MLA_ATTEND_SCOPE):
            out = full_attention(q, k, kv[..., self.nope_dim:], causal=True,
                                 scale=qk_dim ** -0.5)
        with jax.named_scope(MLA_PROJ_SCOPE):
            return dense(d_model, name="o")(
                out.reshape(b, s, self.heads * self.v_dim))


class Relu2MLP(nn.Module):
    """The ungated feed-forward ``W_down relu(W_up x)^2``."""

    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        return dense(x.shape[-1], name="down")(
            jnp.square(nn.relu(dense(self.mlp_dim, name="up")(x))))


class KimiSparseMoe(nn.Module):
    """Router over ``num_experts`` and its selection bias; the experts
    ``first_expert .. first_expert + num_local_experts - 1`` of width
    ``mlp_dim`` held here; ``shared`` experts' worth of one expert of the
    same body that every token passes (0: no such module is built, nothing
    added). The body is SwiGLU, or with ``gated`` False the ungated
    ``down(relu(up(x))^2)``: no ``gate`` matrix is built, held or shared."""

    num_experts: int
    num_local_experts: int
    first_expert: int
    top_k: int
    mlp_dim: int
    routed_scale: float
    shared: int = 1
    dtype: jnp.dtype = jnp.bfloat16
    select_bias: bool = True      # False: the scores alone choose
    scoring: str = "sigmoid"      # or "softmax" over all num_experts
    # the shared expert's output times sigmoid(h w_sg), w_sg [d, 1]
    shared_gate: bool = False
    aux: bool = False             # True: returns (y, load-balance loss)
    gated: bool = True            # False: relu^2 experts of two matrices

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        held, m = self.num_local_experts, self.mlp_dim
        # fan-in scaling per expert: axis 0 counts experts, not inputs
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=0)
        y, load_balance, _, counts = dropless_moe_ffn(
            x.reshape(b * s, d),
            self.param("router", nn.initializers.lecun_normal(),
                       (d, self.num_experts), jnp.float32),
            self.param("gate", init, (held, d, m), jnp.float32)
            if self.gated else None,
            self.param("up", init, (held, d, m), jnp.float32),
            self.param("down", init, (held, m, d), jnp.float32),
            top_k=self.top_k, dtype=self.dtype,
            first_expert=self.first_expert, norm_topk=True,
            scoring=self.scoring, norm_eps=1e-20,
            routed_scale=self.routed_scale,
            select_bias=jax.lax.stop_gradient(self.param(
                "select_bias", nn.initializers.zeros, (self.num_experts,),
                jnp.float32)) if self.select_bias else None)
        if not self.is_initializing():
            self.sow("moe_stats", "counts", counts)
        with jax.named_scope(SHARED_SCOPE):
            y = y.reshape(b, s, d)
            if not self.shared:
                return (y, load_balance) if self.aux else y
            shared = (LlamaMLP if self.gated else Relu2MLP)(
                self.shared * m, self.dtype, name="shared")(x)
            if self.shared_gate:
                shared = shared * jax.nn.sigmoid(nn.Dense(
                    1, use_bias=False, dtype=self.dtype,
                    name="shared_gate")(x).astype(jnp.float32))
            y = y + shared
        return (y, load_balance) if self.aux else y


class KimiSublayer(nn.Module):
    """``x + f(RMSNorm(x))``: half a block, and the unit of recomputation.
    ``make`` builds ``f``, the mixer or the feed-forward, under its name."""

    make: Callable[[], nn.Module]
    eps: float = 1e-5
    norm: Callable[..., nn.Module] = RMSNorm

    @nn.compact
    def __call__(self, x):
        return x + self.make()(self.norm(self.eps, name="norm")(x))


class KimiBlock(nn.Module):
    """Mixer half, then feed-forward half, each recomputed in the backward
    pass on its own (``nn.remat``): what is kept is each half's input, and
    the backward pass holds one half's intermediates at a time — a KDA
    mixer's dozen float32 [s, 4096] tensors are not alive beside the expert
    layer's worst-case [s k, d] rows. One thing more is kept: the chunked
    scan's output (256 MB a KDA layer at s 16384), so that recomputing a
    mixer does not run the scan's forward pass a third time (its own
    backward pass recomputes it group by group already)."""

    mixer: Callable[[], nn.Module]
    ffn: Callable[[], nn.Module]
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        half = nn.remat(KimiSublayer, policy=(
            jax.checkpoint_policies.save_only_these_names(KDA_SAVED)))
        return half(self.ffn, self.eps, name="ffn")(
            half(self.mixer, self.eps, name="mixer")(x))


def _block_nll(model, h, targets):
    """[rows, d] and the rows' targets -> their cross-entropy through
    ``model.lm_head``."""
    with jax.named_scope(HEAD_SCOPE):
        logp = jax.nn.log_softmax(model.lm_head(h).astype(jnp.float32))
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


def next_token_nll(model, h, tokens, ahead):
    """The cross-entropy [b, s - ahead] of row i's prediction of token ``i +
    ahead`` from the normalised hidden rows ``h`` [b, s, d], through
    ``model.lm_head`` in blocks of ``model.loss_rows`` rows, each recomputed
    in the backward pass: one block's [rows, vocab] float32 logits are alive
    at a time. A sequence's last ``ahead`` rows have no target: they get
    token 0 and are dropped, so that b s rows divide evenly. For any setup()
    model with those two attributes, and for each of its streams."""
    b, s, d = h.shape
    h = h.reshape(b * s, d)
    targets = jnp.pad(tokens[:, ahead:], ((0, 0), (0, ahead))).reshape(b * s)
    rows = model.loss_rows if (b * s) % model.loss_rows == 0 else b * s
    nll = nn.remat(_block_nll, prevent_cse=False)
    if model.is_initializing() or rows == b * s:
        out = nll(model, h, targets)
    else:
        out = nn.scan(
            lambda model, _, block: (None, nll(model, *block)),
            variable_broadcast="params", split_rngs={"params": False})(
                model, None, (h.reshape(-1, rows, d),
                              targets.reshape(-1, rows)))[1]
    return out.reshape(b, s)[:, :s - ahead]


class KimiLinearModel(nn.Module):
    """Causal LM. ``tokens`` [batch, seq] -> the next-token cross-entropy
    [batch, seq - 1], float32. ``layer_kinds``: one of ``"kda"`` / ``"mla"``
    a layer."""

    vocab_size: int
    layer_kinds: Sequence[str]
    d_model: int
    heads: int
    head_dim: int                 # KDA's keys and values
    gate_rank: int                # KDA's low-rank gates; its head_dim
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    dense_mlp_dim: int
    num_experts: int
    num_local_experts: int
    top_k: int
    mlp_dim: int
    routed_scale: float
    first_dense: int = 1
    shared: int = 1
    first_expert: int = 0
    conv_kernel: int = 4
    chunk: int = 64
    sub_chunk: int = 16
    loss_rows: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-5

    def setup(self):
        if not set(self.layer_kinds) <= {KDA, MLA}:
            raise ValueError(f"layer_kinds are {KDA!r} | {MLA!r}, got "
                             f"{tuple(self.layer_kinds)}")
        # unit-variance embeddings: models/keye.py has the reason
        self.embed = nn.Embed(self.vocab_size, self.d_model,
                              embedding_init=nn.initializers.normal(1.0))
        mixers = {
            KDA: partial(KimiDeltaAttention, self.heads, self.head_dim,
                         self.gate_rank, self.conv_kernel, self.chunk,
                         self.sub_chunk, self.dtype, self.eps, name="kda"),
            MLA: partial(KimiLatentAttention, self.heads, self.nope_dim,
                         self.rope_dim, self.v_dim, self.kv_rank, self.dtype,
                         self.eps, name="mla")}
        dense = partial(LlamaMLP, self.dense_mlp_dim, self.dtype, name="mlp")
        moe = partial(KimiSparseMoe, self.num_experts,
                      self.num_local_experts, self.first_expert, self.top_k,
                      self.mlp_dim, self.routed_scale, self.shared,
                      self.dtype, name="moe")
        for i, kind in enumerate(self.layer_kinds):
            setattr(self, f"layer_{i}", KimiBlock(
                mixers[kind], dense if i < self.first_dense else moe,
                self.eps))
        self.final_norm = RMSNorm(self.eps)
        self.lm_head = nn.Dense(self.vocab_size, use_bias=False,
                                dtype=self.dtype)

    def __call__(self, tokens):
        x = self.embed(tokens)       # float32 from here on (module docstring)
        for i in range(len(self.layer_kinds)):
            x = getattr(self, f"layer_{i}")(x)
        return next_token_nll(self, self.final_norm(x), tokens, 1)


def kimi_linear_loss(nll: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy over the model's output. No auxiliary
    loss: the family balances its experts through the selection bias."""
    return nll.mean()


def layer_kinds(kda_layers, full_attn_layers, num_layers: int) -> tuple:
    """The kinds of the first ``num_layers`` layers from the source's two
    1-indexed lists (``linear_attn_config``)."""
    kinds = {**{i: KDA for i in kda_layers},
             **{i: MLA for i in full_attn_layers}}
    return tuple(kinds[i] for i in range(1, num_layers + 1))


# Tiny is for tests (a share: experts 0..1 of 8; key width 24 against value
# width 16 in the latent layer). KimiLinear48BA3B follows
# moonshotai/Kimi-Linear-48B-A3B-Instruct (27 layers, 20 KDA : 7 MLA, d 2304,
# 32 heads, KDA 128 x 128, MLA 128 + 64 / 128 over a 512-wide latent, dense
# 9216 then 256 experts of width 1024, 8 per token, one shared, vocab
# 163840).
KimiLinearTiny = partial(
    KimiLinearModel, vocab_size=512, layer_kinds=(KDA, KDA, MLA, KDA),
    d_model=64, heads=4, head_dim=16, gate_rank=16, nope_dim=16, rope_dim=8,
    v_dim=16, kv_rank=32, dense_mlp_dim=128, num_experts=8,
    num_local_experts=2, top_k=2, mlp_dim=32, routed_scale=2.446, chunk=8,
    sub_chunk=4, loss_rows=32)
KimiLinear48BA3B = partial(
    KimiLinearModel, vocab_size=163840,
    layer_kinds=layer_kinds(
        [i for i in range(1, 27) if i % 4], [4, 8, 12, 16, 20, 24, 27], 27),
    d_model=2304, heads=32, head_dim=128, gate_rank=128, nope_dim=128,
    rope_dim=64, v_dim=128, kv_rank=512, dense_mlp_dim=9216, num_experts=256,
    num_local_experts=256, top_k=8, mlp_dim=1024, routed_scale=2.446)
