"""Nemotron-H-family hybrid decoder (NVIDIA, arXiv 2504.03624;
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``, ``model_type`` nemotron_h):
a stack whose every layer is ONE mixer under one pre-norm,

    x <- x + Mixer_c(N(x)),

the mixer chosen a layer by a character of ``pattern`` (the source's
``hybrid_override_pattern``): ``M`` a Mamba-2 state-space layer, ``E`` an
expert layer, ``*`` softmax attention. No block pairs a token mixer with a
feed-forward; one final norm before the untied head. Every ``N`` is an
RMSNorm ``x rsqrt(mean x^2 + eps) w``; no bias anywhere but the
convolution's.

**M, Mamba-2** (Dao & Gu 2024, arXiv 2405.21060; ``heads`` heads of
``head_dim`` channels, ``groups`` groups of ``B`` and ``C`` of ``state``
entries, group j serving heads ``j heads / groups ..``). ``[z; xBC] = h
W_in`` and ``dt = h W_dt`` (the source's one fused in-projection, its
columns ``z, xBC, dt`` one after another; here the ``dt`` columns are a
matrix of their own so that they run in float32: with weights from a seed
the split is immaterial). ``xBC <- SiLU(conv(xBC) + b)``: one causal
depthwise convolution of ``conv_kernel`` taps over the concatenated ``x``,
``B``, ``C`` channels, with a bias. ``Delta = softplus(dt + dt_bias)``, no
clamp; ``g = -exp(A_log) Delta``, one number a head and token. Per head a
float32 state ``S`` [state, head_dim] from zero:

    S_t = e^{g_t} S_{t-1} + B_t (Delta_t x_t)^T;   y_t = S_t^T C_t + D x_t

(``parallel/linear_attention.py::ssd_scan_channels``: the chunked scan with
no delta rule, over ``x``, ``B`` and ``C`` as the convolution leaves them).
Output ``W_out GN(y SiLU(z))``: gate first, then ``GN``, an RMSNorm over
each of the ``groups`` groups of channels times one weight vector (the
family's ``MambaRMSNormGated`` with ``norm_before_gate`` false). Skip, gate
and norm are one function, ``gated_group_norm``: float32 arithmetic, rounded
once to ``dtype``, the operand ``W_out``'s product takes. Its XLA form is the
``jax.numpy`` lines; where ``gate_form`` says so — a ``tpu`` backend, a bf16
result, groups of whole 128-lane tiles up to 512 channels that hold whole
heads, the sequence in blocks of 512 tokens — it is the kernel pair of
``byteps_tpu.ops.gated_norm``, ``bps_gated_norm_fwd`` / ``bps_gated_norm_bwd``
(PR 69), which reads ``z`` and ``x`` where the in-projection and the
convolution leave them and writes the bf16 operand once, so that XLA has no
chain to rebuild inside the product; each call site is counted at trace time
(``bps_gated_norm_sites_total``, ``bps_gated_norm_kernel_sites_total``).

**E, experts** (``models/kimi_linear.py::KimiSparseMoe`` with ``gated``
False): sigmoid scores over all ``num_experts`` in float32, a selection
bias that chooses and never weighs, the chosen weights renormalised and
scaled by ``routed_scale``, dropless over the held share
(``num_local_experts`` from ``first_expert``); an expert is the ungated
``W_down relu(W_up h)^2``, and so is the shared one every token passes, of
``shared_mlp_dim``, whole on every chip. No auxiliary loss.

**\\*, attention**: ``heads`` query heads over ``kv_heads`` key heads of
``head_dim``, no rotation and no q/k norm (the Mamba layers carry
position); causal softmax at ``head_dim^-1/2`` through
``parallel.full_attention`` (on the chip the grouped flash kernels).

Precisions and recomputation are Kimi-Linear's: float32 parameters,
residual stream, norms, SiLU and softplus, decay and state; ``dtype`` (bf16)
matmul operands with float32 accumulation; the router and ``W_dt`` (its
output is cumulated over thousands of tokens) in float32 at the highest
matmul precision; every layer under ``nn.remat`` with the scan's output
kept; the mixer's elementwise preparation recomputed; head and
cross-entropy in blocks of ``loss_rows`` rows (``next_token_nll``). The
model returns the per-position cross-entropy [batch, seq - 1];
``nemotron_h_loss`` is its mean. Apply with ``mutable=["moe_stats",
"ssm_stats"]`` for the per-expert counts and each Mamba-2 layer's most
negative cumulated log-decay of a chunk.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from byteps_tpu.models.kimi_linear import (KDA_SAVED, KimiSparseMoe,
                                           KimiSublayer, _a_log_init,
                                           _dt_bias_init, causal_conv,
                                           next_token_nll)
from byteps_tpu.models.llama import RMSNorm
from byteps_tpu.monitor import metrics
from byteps_tpu.parallel.linear_attention import (SSM_PREP_SCOPE,
                                                  chunk_log_decay,
                                                  ssd_scan_channels)
from byteps_tpu.parallel.ring_attention import full_attention

SSM_PROJ_SCOPE = "bps.ssm.proj"          # in- and out-projection
SSM_OUT_SCOPE = "bps.ssm.out"            # D x, the SiLU(z) gate, group norm
NATTN_ATTEND_SCOPE = "bps.nattn.attend"  # around full_attention's own scope
NATTN_PROJ_SCOPE = "bps.nattn.proj"      # the four projections
NEMOTRON_SITES = "bps_nemotron_sites_total"   # layers, at trace time
# call sites of ``gated_group_norm`` traced into a program, and of those the
# ones that took the kernels of ``byteps_tpu.ops.gated_norm``
GATE_SITES = "bps_gated_norm_sites_total"
GATE_KERNEL_SITES = "bps_gated_norm_kernel_sites_total"
# the kernels' tiling, as ``byteps_tpu.ops.gated_norm`` has it (ROWS,
# MAX_GROUP_WIDTH; tests/test_gated_norm_kernel.py holds the two equal):
# kept here so that asking for the form imports no kernel library
GATE_ROWS, GATE_MAX_GROUP_WIDTH = 512, 512

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def gate_form(backend: str, s: int, inner: int, groups: int, head_dim: int,
              dtype) -> str:
    """``"kernel"`` or ``"xla"``: how ``gated_group_norm`` runs at these
    shapes. One algorithm: the XLA form for every backend and shape, and the
    kernel pair of ``byteps_tpu.ops.gated_norm`` on a ``tpu`` backend where
    its tiling holds — a group's channels whole 128-lane tiles, at most
    ``GATE_MAX_GROUP_WIDTH`` (a block's lanes), and whole heads of
    ``head_dim``; the sequence in whole blocks of ``GATE_ROWS`` tokens; a
    bf16 result."""
    if backend != "tpu" or jnp.dtype(dtype) != jnp.bfloat16:
        return "xla"
    if groups < 1 or head_dim < 1 or inner % groups or s % GATE_ROWS:
        return "xla"
    width = inner // groups
    if width % 128 or width > GATE_MAX_GROUP_WIDTH or width % head_dim:
        return "xla"
    return "kernel"


def gated_group_norm(y, x, z, skip, weight, *, groups: int, head_dim: int,
                     eps: float = 1e-5, dtype=jnp.bfloat16, x_lies_in=None):
    """A Mamba-2 mixer's output chain, the out-projection's operand:
    ``GN((y + repeat(skip) x) SiLU(z)) weight`` [b, s, inner] in ``dtype``
    — gate before norm, ``GN`` an RMSNorm over each of the ``groups`` groups
    of channels, float32 arithmetic, rounded once at the end (the rounding a
    ``Dense(dtype=dtype)`` gives its input). y, x [b, s, inner] as the scan
    hands them back; z [b, s, >= inner], its first ``inner`` columns the
    gate's (the in-projection's output as it lies); skip [inner /
    head_dim], a number a head; weight [inner].

    On a TPU at the shapes ``gate_form`` names it is one kernel each way,
    ``bps_gated_norm_fwd`` and, under a rule that keeps only the operands
    it is given, ``bps_gated_norm_bwd``; everywhere else XLA's
    (``gated_group_norm_xla``: what the kernels are held to,
    ``tools/scan_check.py --cases gate``). ``x_lies_in``: the array [b, s,
    >= inner] whose first ``inner`` columns ``x`` is (what the scan was
    handed): the kernels then read ``x`` there, as they read ``z``, and no
    slice of it is written, while ``x``'s cotangent still goes back through
    ``x`` and none to that array; the XLA form takes no notice of it."""
    metrics.inc_counter(GATE_SITES)
    if gate_form(jax.default_backend(), y.shape[1], y.shape[2], groups,
                 head_dim, dtype) == "kernel":
        metrics.inc_counter(GATE_KERNEL_SITES)
        return _kernel_gate(y, x, x if x_lies_in is None else x_lies_in, z,
                            skip, weight, groups, head_dim, eps,
                            jnp.dtype(dtype))
    return gated_group_norm_xla(y, x, z, skip, weight, groups=groups,
                                head_dim=head_dim, eps=eps, dtype=dtype)


def gated_group_norm_xla(y, x, z, skip, weight, *, groups: int,
                         head_dim: int, eps: float = 1e-5,
                         dtype=jnp.bfloat16):
    """``gated_group_norm``'s XLA form, whatever the backend."""
    b, s, inner = y.shape
    f32 = jnp.float32
    gated = ((y + jnp.repeat(skip, head_dim) * x)
             * jax.nn.silu(z[..., :inner].astype(f32)))
    # an RMSNorm a group of channels, one weight vector over all
    grouped = gated.reshape(b, s, groups, inner // groups)
    normed = (grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        + eps)).reshape(b, s, inner) * weight
    return normed.astype(dtype)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _kernel_gate(y, x, x_wide, z, skip, weight, groups, head_dim, eps, dtype):
    """``x_wide`` is what the kernels read ``x`` in; ``x`` itself is here
    for its cotangent's way back alone."""
    # imported here: a CPU run pays for no kernel library
    # (tests/test_import_footprint.py)
    from byteps_tpu.ops.gated_norm import gated_norm_forward

    return gated_norm_forward(y, x_wide, z, jnp.repeat(skip, head_dim),
                              weight, groups=groups, eps=eps, dtype=dtype)


def _kernel_gate_fwd(y, x, x_wide, z, skip, weight, groups, head_dim, eps,
                     dtype):
    return (_kernel_gate(y, x, x_wide, z, skip, weight, groups, head_dim,
                         eps, dtype), (y, x_wide, z, skip, weight))


def _kernel_gate_bwd(groups, head_dim, eps, dtype, res, ct):
    from byteps_tpu.ops.gated_norm import gated_norm_backward

    y, x_wide, z, skip, weight = res
    dy, dx, dz, dskip, dweight = gated_norm_backward(
        y, x_wide, z, jnp.repeat(skip, head_dim), weight, ct, groups=groups,
        eps=eps)
    # the gate's columns of ``z``'s cotangent; the others read nothing here
    dz = jnp.pad(dz, ((0, 0), (0, 0), (0, z.shape[2] - dz.shape[2])))
    return (dy, dx, None, dz,
            dskip.reshape(-1, head_dim).sum(-1).astype(skip.dtype),
            dweight.astype(weight.dtype))


_kernel_gate.defvjp(_kernel_gate_fwd, _kernel_gate_bwd)


class Mamba2Mixer(nn.Module):
    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int = 4
    chunk: int = 128
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        f32 = jnp.float32
        inner, bc = self.heads * self.head_dim, self.groups * self.state
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        with jax.named_scope(SSM_PROJ_SCOPE):
            zxbc = dense(2 * inner + 2 * bc, name="in")(x)
            dt = nn.Dense(self.heads, use_bias=False, dtype=f32,
                          precision=jax.lax.Precision.HIGHEST, name="dt")(
                              x.astype(f32))
        conv = self.param("conv", nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=0, out_axis=1),
            (self.conv_kernel, inner + 2 * bc), f32)
        conv_bias = self.param("conv_bias", nn.initializers.zeros,
                               (inner + 2 * bc,), f32)
        a_log = self.param("A_log", _a_log_init, (self.heads,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (self.heads,), f32)
        skip = self.param("D", nn.initializers.ones, (self.heads,), f32)

        # elementwise, and recomputed in the backward pass: what is kept is
        # the projections' output; ``causal_conv`` takes the bf16 projection
        # as it is and widens it itself (in VMEM, where its forward is the
        # kernel)
        @jax.checkpoint
        def prepared(xbc, conv, conv_bias, dt, a_log, dt_bias):
            with jax.named_scope(SSM_PREP_SCOPE):
                step = jax.nn.softplus(dt + dt_bias)
                return (causal_conv(xbc, conv, conv_bias, activation="silu"),
                        -jnp.exp(a_log) * step, step)

        # ``mixed`` = [x | B | C], a token's channels on lanes as the
        # convolution leaves them: the scan takes them so (its kernels read
        # and write that layout; its XLA form cuts heads and groups itself)
        # and hands ``x`` back for the skip, whose cotangent then reaches
        # ``mixed`` through the scan's own backward pass
        mixed, g, step = prepared(
            zxbc[..., inner:], conv, conv_bias, dt, a_log, dt_bias)
        if (self.is_mutable_collection("ssm_stats")
                and not self.is_initializing()):   # init(): parameters only
            self.sow("ssm_stats", "min_chunk_log_decay",
                     chunk_log_decay(g, self.chunk).min())
        y, x_in = ssd_scan_channels(
            mixed, g, step, heads=self.heads, groups=self.groups,
            state=self.state, chunk=self.chunk, dtype=self.dtype)
        # kept when the layer is recomputed, as Kimi-Linear's scan output
        y = checkpoint_name(y, KDA_SAVED)
        with jax.named_scope(SSM_OUT_SCOPE):
            # ``z`` and ``x_in`` where they lie: the first ``inner`` columns
            # of the projection and of ``mixed``
            normed = gated_group_norm(
                y, x_in, zxbc, skip, self.param(
                    "norm", nn.initializers.ones, (inner,), f32),
                groups=self.groups, head_dim=self.head_dim, eps=self.eps,
                dtype=self.dtype, x_lies_in=mixed)
        with jax.named_scope(SSM_PROJ_SCOPE):
            return dense(d_model, name="out")(normed)


class NemotronAttention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, d_model = x.shape
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        with jax.named_scope(NATTN_PROJ_SCOPE):
            q, k, v = (dense(n * self.head_dim, name=name)(x).reshape(
                b, s, n, self.head_dim) for name, n in (
                    ("q", self.heads), ("k", self.kv_heads),
                    ("v", self.kv_heads)))
        with jax.named_scope(NATTN_ATTEND_SCOPE):
            out = full_attention(q, k, v, causal=True,
                                 scale=self.head_dim ** -0.5)
        with jax.named_scope(NATTN_PROJ_SCOPE):
            return dense(d_model, name="o")(
                out.reshape(b, s, self.heads * self.head_dim))


class NemotronHModel(nn.Module):
    """Causal LM. ``tokens`` [batch, seq] -> the next-token cross-entropy
    [batch, seq - 1], float32. ``pattern``: one of ``M`` / ``E`` / ``*`` a
    layer."""

    vocab_size: int
    pattern: str
    d_model: int
    heads: int                    # attention's query heads
    kv_heads: int
    head_dim: int
    ssm_heads: int                # Mamba-2's
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    num_experts: int
    num_local_experts: int
    top_k: int
    mlp_dim: int
    shared_mlp_dim: int
    routed_scale: float
    first_expert: int = 0
    conv_kernel: int = 4
    chunk: int = 128
    loss_rows: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    eps: float = 1e-5

    def setup(self):
        if not self.pattern or set(self.pattern) - {MAMBA, EXPERTS,
                                                    ATTENTION}:
            raise ValueError(f"pattern is made of {MAMBA!r}, {EXPERTS!r} "
                             f"and {ATTENTION!r}, got {self.pattern!r}")
        # unit-variance embeddings: models/keye.py has the reason
        self.embed = nn.Embed(self.vocab_size, self.d_model,
                              embedding_init=nn.initializers.normal(1.0))
        mixers = {
            MAMBA: partial(Mamba2Mixer, self.ssm_heads, self.ssm_head_dim,
                           self.ssm_groups, self.ssm_state, self.conv_kernel,
                           self.chunk, self.dtype, self.eps, name="ssm"),
            # ``shared`` counts experts' widths: one of shared_mlp_dim
            EXPERTS: partial(KimiSparseMoe, self.num_experts,
                             self.num_local_experts, self.first_expert,
                             self.top_k, self.mlp_dim, self.routed_scale,
                             self.shared_mlp_dim // self.mlp_dim, self.dtype,
                             gated=False, name="moe"),
            ATTENTION: partial(NemotronAttention, self.heads, self.kv_heads,
                               self.head_dim, self.dtype, name="attn")}
        # a layer is the unit of recomputation, the scan's output kept
        layer = nn.remat(KimiSublayer, policy=(
            jax.checkpoint_policies.save_only_these_names(KDA_SAVED)))
        for i, kind in enumerate(self.pattern):
            setattr(self, f"layer_{i}", layer(mixers[kind], self.eps))
        self.final_norm = RMSNorm(self.eps)
        self.lm_head = nn.Dense(self.vocab_size, use_bias=False,
                                dtype=self.dtype)

    def __call__(self, tokens):
        x = self.embed(tokens)       # float32 from here on (module docstring)
        for i in range(len(self.pattern)):
            metrics.inc_counter(NEMOTRON_SITES)
            x = getattr(self, f"layer_{i}")(x)
        return next_token_nll(self, self.final_norm(x), tokens, 1)


def nemotron_h_loss(nll: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy over the model's output. No auxiliary
    loss: the family balances its experts through the selection bias."""
    return nll.mean()


# Tiny is for tests (a share: experts 0..1 of 8; 4 state-space heads of 8
# over 2 groups of state 16; 4 query heads over 2 key heads).
# Nemotron3Nano30BA3B follows nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (52
# layers, 23 Mamba-2 : 23 expert : 6 attention, d 2688, Mamba-2 64 heads of
# 64 over 8 groups of state 128, attention 32 / 2 x 128 without rotation,
# 128 relu^2 experts of width 1856, 6 per token, one shared of 3712, vocab
# 131072).
NemotronHTiny = partial(
    NemotronHModel, vocab_size=512, pattern="MEM*E", d_model=64, heads=4,
    kv_heads=2, head_dim=16, ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
    ssm_state=16, num_experts=8, num_local_experts=2, top_k=2, mlp_dim=32,
    shared_mlp_dim=64, routed_scale=2.5, chunk=8, loss_rows=32)
Nemotron3Nano30BA3B = partial(
    NemotronHModel, vocab_size=131072,
    pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    d_model=2688, heads=32, kv_heads=2, head_dim=128, ssm_heads=64,
    ssm_head_dim=64, ssm_groups=8, ssm_state=128, num_experts=128,
    num_local_experts=128, top_k=6, mlp_dim=1856, shared_mlp_dim=3712,
    routed_scale=2.5)
