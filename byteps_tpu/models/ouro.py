"""Ouro-family looped decoder (ByteDance Seed 2025, Scaling Latent Reasoning
via Looped Language Models; ``ByteDance/Ouro-2.6B``).

One stack of ``num_layers`` blocks is run ``num_passes`` times over the SAME
parameters; after every pass the final norm closes the pass, an exit gate
and the one output head read it, and the normed state is what the next pass
starts from. The block is ``llama.py``'s — RMSNorm, RoPE, SwiGLU, multi-head
attention, no bias — with two more norms: a sublayer's *output* is normed
before it joins the residual stream ("sandwich"),

    a = x + N2(Attn(N1(x))),    y = a + N4(MLP(N3(a))).

The parameter tree holds each block once (``layer_0 .. layer_{L-1}``,
``final_norm``, ``exit_gate``, ``embed``, ``lm_head``); a block's gradient
is the sum over the passes, taken where the loop's backward pass carries it:
the passes are ONE ``lax.scan`` with the parameters broadcast into its body
(``nn.scan``), so the program holds one copy of the stack whatever
``num_passes`` is (PERF.md, PR 35: against the passes unrolled, 2% less
time, 1.4 GB less memory and a third of the compile). Every block
application is recomputed in the backward pass (``nn.remat``): what is
kept is each application's input, not its seven matmuls' operands. (Where
``full_attention`` takes its XLA form — off the TPU, or under s 512 — one
application's float32 scores are 16 x s^2 x 4 B as well; on the chip at
the benchmark's s 4096 the flash kernel holds a block of them in VMEM and
saves q, k, v, the output and a logsumexp a row.) The exit of a pass — head, softmax, per-position
cross-entropy — is recomputed too, so that one pass's ``[s, vocab]`` float32
logits are live at a time in both directions.

bf16 matmul operands over float32 parameters; the residual stream and the
exit gate in float32 (``models/olmoe.py`` has the reasons). The model
returns, per pass, the per-position next-token cross-entropy, the gate's
logits and the normed hidden state; ``ouro_loss`` is the expected loss over
the exit distribution less ``beta`` times its entropy (the paper's
entropy-regularised objective). Nothing is detached: every pass's head,
every gate and every use of every block is reached by the gradient. Apply
with ``mutable=["loop_stats"]`` for the exit distribution's mean over
positions and the block applications made (``publish_loop_stats``), and pay
nothing otherwise.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp

from byteps_tpu.models.llama import LlamaMLP, RMSNorm, _rope
from byteps_tpu.models.transformer import (_attention_fn,
                                           _default_positions,
                                           lm_log_likelihood)

STACK_SCOPE, EXIT_SCOPE = "bps.loop.stack", "bps.loop.exit"


class OuroBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "full"
    rope_theta: float = 1e6
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x, positions):
        b, s, d_model = x.shape
        dense = partial(nn.Dense, d_model, use_bias=False, dtype=self.dtype)
        heads = (b, s, self.num_heads, d_model // self.num_heads)
        norm = partial(RMSNorm, self.eps)
        h = norm(name="attn_norm")(x)
        q = _rope(dense(name="q")(h).reshape(heads), positions,
                  self.rope_theta)
        k = _rope(dense(name="k")(h).reshape(heads), positions,
                  self.rope_theta)
        out = _attention_fn(self.attn_impl, None)(
            q, k, dense(name="v")(h).reshape(heads), causal=True)
        x = x + norm(name="attn_post_norm")(
            dense(name="o")(out.reshape(b, s, d_model)))
        return x + norm(name="mlp_post_norm")(
            LlamaMLP(self.mlp_dim, self.dtype, name="mlp")(
                norm(name="mlp_norm")(x)))


def exit_distribution(gate_logits: jax.Array) -> jax.Array:
    """``gate_logits`` [R, ...] -> log p [R, ...]: p(r) = lambda_r prod_{j<r}
    (1 - lambda_j) with lambda = sigmoid(logit), and the last pass takes
    what is left (its own gate is not read), so p sums to 1 over R. In logs:
    a gate far open or far shut costs no precision."""
    zero = jnp.zeros_like(gate_logits[:1])
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits[:-1]), axis=0)
    leaves = jax.nn.log_sigmoid(gate_logits[:-1])
    return (jnp.concatenate([zero, stayed])
            + jnp.concatenate([leaves, zero]))


class OuroModel(nn.Module):
    """Causal looped LM. ``tokens`` [batch, seq] -> ``(nll [R, batch, seq -
    1], gate_logits [R, batch, seq], hidden [R, batch, seq, d])``, float32:
    per pass the next-token cross-entropy of its logits at every position,
    the exit gate's logit and the normed state the head read."""

    vocab_size: int
    num_layers: int
    d_model: int
    num_heads: int
    mlp_dim: int
    num_passes: int = 4
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "full"
    rope_theta: float = 1e6
    eps: float = 1e-6

    def setup(self):
        self.embed = nn.Embed(self.vocab_size, self.d_model)
        # scan keeps its body's common subexpressions apart by itself
        block = nn.remat(OuroBlock, prevent_cse=False)
        for i in range(self.num_layers):
            setattr(self, f"layer_{i}", block(
                self.num_heads, self.mlp_dim, self.dtype, self.attn_impl,
                self.rope_theta, self.eps))
        self.final_norm = RMSNorm(self.eps)
        # zero: lambda = 1/2 at every pass until it has learned otherwise
        self.exit_gate = nn.Dense(
            1, dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
            kernel_init=nn.initializers.zeros)
        self.lm_head = nn.Dense(self.vocab_size, use_bias=False,
                                dtype=self.dtype)

    def _exit(self, h, tokens):
        with jax.named_scope(EXIT_SCOPE):
            logits = self.lm_head(h).astype(jnp.float32)
            return (-lm_log_likelihood(logits, tokens),
                    self.exit_gate(h)[..., 0])

    def _pass(self, x, tokens):
        with jax.named_scope(STACK_SCOPE):
            positions = _default_positions(tokens.shape[1], None)
            for i in range(self.num_layers):
                x = getattr(self, f"layer_{i}")(x, positions)
            h = self.final_norm(x)
        nll, gate = nn.remat(OuroModel._exit, prevent_cse=False)(
            self, h, tokens)
        return h, (nll, gate, h)

    def __call__(self, tokens):
        # float32 from here on: the residual stream (module docstring)
        x = self.embed(tokens)
        _, (nll, gate, hidden) = nn.scan(
            OuroModel._pass, variable_broadcast="params",
            split_rngs={"params": False}, in_axes=nn.broadcast,
            length=self.num_passes)(self, x, tokens)
        if (self.is_mutable_collection("loop_stats")
                and not self.is_initializing()):   # init(): parameters only
            p = jnp.exp(exit_distribution(gate))
            self.sow("loop_stats", "exit_mean", p.mean(axis=(1, 2)))
            self.sow("loop_stats", "block_applications",
                     jnp.int32(self.num_passes * self.num_layers))
        return nll, gate, hidden


def ouro_loss(outputs, *, beta: float = 0.05) -> jax.Array:
    """mean_t [ sum_r p_t(r) nll_t(r) - beta H(p_t) ] over the model's
    outputs: the expected next-token cross-entropy under the exit
    distribution less ``beta`` times that distribution's entropy. The
    gate's logit at a sequence's last position predicts nothing and is left
    out, as ``lm_loss`` leaves out that position's logits."""
    nll, gate_logits = outputs[0], outputs[1][..., :-1]
    with jax.named_scope(EXIT_SCOPE):
        log_p = exit_distribution(gate_logits)
        p = jnp.exp(log_p)
        return (p * (nll + beta * log_p)).sum(axis=0).mean()


def publish_loop_stats(loop_stats) -> dict:
    """The ``"loop_stats"`` collection of a model applied with it mutable
    (``exit_mean`` [R], the exit distribution's mean over positions, and
    ``block_applications``) to ``monitor/metrics.py``: gauge
    ``bps_loop_mean_exit_pass`` (sum_r r x mean_t p_t(r), passes counted
    from 1), counter ``bps_loop_block_applications_total``. Returns what it
    published."""
    import numpy as np

    from byteps_tpu.monitor import metrics

    if "exit_mean" not in loop_stats:
        return {}
    # sown once an apply: one-entry tuples
    exit_mean = np.asarray(loop_stats["exit_mean"][-1], np.float64)
    applications = float(np.sum(loop_stats["block_applications"]))
    out = {"bps_loop_mean_exit_pass": float(
        (exit_mean * np.arange(1, exit_mean.size + 1)).sum()),
        "bps_loop_block_applications_total": applications}
    metrics.set_gauge("bps_loop_mean_exit_pass",
                      out["bps_loop_mean_exit_pass"])
    metrics.inc_counter("bps_loop_block_applications_total", applications)
    return out


# Tiny is for tests. Ouro2_6B follows ByteDance/Ouro-2.6B (48 layers run 4
# times, d 2048, 16 heads of 128, mlp 5632, vocab 49152).
OuroTiny = partial(OuroModel, vocab_size=512, num_layers=3, d_model=64,
                   num_heads=4, mlp_dim=128)
Ouro2_6B = partial(OuroModel, vocab_size=49152, num_layers=48, d_model=2048,
                   num_heads=16, mlp_dim=5632)
